"""Operator kernel library — importing this package registers all ops.

The registry (registry.py) replaces the reference's OpInfoMap/kernel
registries (reference: framework/op_registry.h, op_info.h); kernels are pure
JAX functions compiled by XLA rather than per-device C++ functors."""
from .registry import OPS, register_op, register_grad_maker  # noqa: F401

from . import math_ops       # noqa: F401
from . import tensor_ops     # noqa: F401
from . import nn_ops         # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import framework_ops  # noqa: F401
from . import nn_extra_ops   # noqa: F401
from . import collective_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import decoder_ops  # noqa: F401
from . import sequence_ops   # noqa: F401
from . import rnn_ops        # noqa: F401
from . import distributed_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import loss_extra_ops  # noqa: F401
from . import quant_ops  # noqa: F401
from . import vision_ops  # noqa: F401
from . import misc_ops   # noqa: F401
from . import fused_ops  # noqa: F401
from . import metrics_misc_ops  # noqa: F401
from . import detection_train_ops  # noqa: F401
from . import lod_control_ops  # noqa: F401
from . import ps_quant_misc_ops  # noqa: F401
