"""fluid.layers — op-builder functions (reference: python/paddle/fluid/layers/).

Each function appends ops to the current program block and returns output
Variables; in dygraph mode append_op routes through the tracer and executes
immediately (reference framework.py:2758,2781)."""
from . import tensor as _tensor_mod
from .tensor import *          # noqa: F401,F403
from . import nn as _nn_mod
from .nn import *              # noqa: F401,F403
from . import ops as _ops_mod
from .ops import *             # noqa: F401,F403
from . import loss as _loss_mod
from .loss import *            # noqa: F401,F403
from . import control_flow as _cf_mod
from .control_flow import *    # noqa: F401,F403
from . import learning_rate_scheduler as _lrs_mod
from .learning_rate_scheduler import *  # noqa: F401,F403
from . import metric_op as _metric_mod
from .metric_op import *       # noqa: F401,F403
from . import io as _io_mod
from .io import *              # noqa: F401,F403
from . import sequence_lod as _seq_mod
from .sequence_lod import *    # noqa: F401,F403
from . import collective as _coll_mod
from . import collective  # noqa: F401
# the reference exports these underscore helpers at layers scope
# (layers/collective.py __all__ lists them, so * picks them up there)
from .collective import (_allreduce, _broadcast, _c_allreduce,  # noqa: F401
                         _c_broadcast, _c_allgather,  # noqa: F401
                         _c_reducescatter, _c_sync_calc_stream,  # noqa: F401
                         _c_sync_comm_stream)  # noqa: F401
from . import detection as _det_mod
from .detection import *       # noqa: F401,F403
from . import rnn as _rnn_mod
from .rnn import *             # noqa: F401,F403
from . import decoder as _decoder_mod
from .decoder import *          # noqa: F401,F403
from . import distributions  # noqa: F401
from .distributions import (Uniform, Normal, Categorical,  # noqa: F401
                            MultivariateNormalDiag)  # noqa: F401

from .tensor import math_op  # noqa: F401
