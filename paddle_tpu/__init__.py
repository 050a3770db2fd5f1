"""paddle_tpu — a TPU-native deep-learning framework with the capabilities
of PaddlePaddle v1.7 "Fluid" (see SURVEY.md): Program/Block/Op/Var graph IR,
fluid.layers API, Executor, append_backward autodiff, optimizers, DyGraph,
Fleet distributed training — built on JAX/XLA/Pallas/pjit.

Programs compile to single XLA computations per block; parallelism is
sharding over a jax.sharding.Mesh (ICI collectives), not graph rewrites."""

__version__ = "0.1.0"

from . import ops          # registers the operator set
from . import fluid        # the Fluid-compatible front end
from . import inference    # AnalysisPredictor engine
from . import nn           # 2.0-preview namespaces
from . import tensor
from . import framework
from . import optimizer
from . import metric
from . import device
from . import distribution
from . import incubate
from . import dataset      # offline dataset readers (synthetic fallback)
from . import reader       # reader decorators (map/shuffle/buffered/...)
from . import version
from .batch import batch
from .framework import manual_seed, get_default_dtype, set_default_dtype
# tensor functions at top level (reference paddle/__init__.py re-exports)
from .tensor import *  # noqa: F401,F403

# 2.0-style convenience aliases (reference: python/paddle/__init__.py
# re-exports under torch-like names)
from .fluid import (Program, Executor, CPUPlace, TPUPlace, CUDAPlace,
                    program_guard, default_main_program,
                    default_startup_program, global_scope, scope_guard,
                    ParamAttr)
from .fluid.dygraph import (enable_dygraph, disable_dygraph, grad, no_grad,
                            to_variable)
from .fluid.framework import in_dygraph_mode as in_dynamic_mode


def enable_static():
    """2.0 naming: leave imperative mode (reference paddle.enable_static)."""
    disable_dygraph()


def disable_static():
    """2.0 naming: enter imperative mode (reference
    paddle.disable_static)."""
    enable_dygraph()


def summary(net, input_size=None, dtypes=None):
    """Parameter summary of a dygraph Layer (reference paddle.summary's
    role; prints the per-parameter shapes and the total count)."""
    import builtins
    rows = []
    total = 0
    for name, p in net.named_parameters():
        n = 1
        for s in p.shape:
            n *= int(s)
        total += n
        rows.append((name, tuple(p.shape), n))
    # builtins.max: `from .tensor import *` above shadows max with the
    # tensor reduction at module scope
    width = builtins.max((len(r[0]) for r in rows), default=10) + 2
    print(f"{'Param':<{width}}{'Shape':<20}{'Count':>12}")
    for name, shape, n in rows:
        print(f"{name:<{width}}{str(shape):<20}{n:>12}")
    print(f"{'Total params:':<{width + 20}}{total:>12}")
    return {"total_params": total, "trainable_params": total}

__all__ = ["fluid", "ops", "inference", "__version__"]
