"""Op kernels: the flash kernels' share of the chip's roofline: the
least time the chip could take for the attention maps a step requires
(the larger of FLOP over the bf16 peak and bytes over the HBM peak;
`attn_required` in the configuration's .py counts the scores and
weighted sums over the keys each mask KEEPS and the bytes of Q, K, V,
O and their gradients, forward + backward, nothing recomputed, no
block visited and then masked) over the device time of `flash_fwd` +
`flash_bwd_dkv` + `flash_bwd_dq` by the names their `pallas_call`s
carry, the recomputed forward included."""
import importlib.util
import os
import sys


def helper():
    """`benchmark/scope_union.py`, by path; one instance a process, so
    that every reader finds the one set of intervals the trace gave."""
    name = "_benchmark_scope_union"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scope_union.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


helper().watch()

KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


def compute(run):
    scopes = helper().shared().last()
    if not scopes or not scopes["chips"]:
        return None
    found = [scopes["kernels"][k]["s"] for k in KERNELS
             if k in scopes["kernels"]]
    if not found:
        return None  # no flash kernel ran
    config, traffic, model = helper().cell_files()
    if not hasattr(model, "attn_required"):
        return None  # a configuration that counts no attention maps
    return helper().roofline_pct(run, model.attn_required(config, traffic),
                                 sum(found) / scopes["steps"] * 1e3)
