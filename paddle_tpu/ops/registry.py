"""Operator registry — TPU-native equivalent of the reference's OpInfoMap +
kernel registry (reference: paddle/fluid/framework/op_info.h:124,
op_registry.h:223, operator.h:442).

Design inversion for TPU: the reference registers per-device C++ kernel
functions chosen at run time by OpKernelType; here every op has ONE pure
JAX kernel ``kernel(ins, attrs) -> outs`` that the executor either applies
eagerly (interpreter oracle) or traces into a single XLA computation for the
whole block (compiled mode). Device placement, layout, fusion and memory are
XLA's job.

Gradient strategy (replaces reference GradOpDescMaker C++ classes,
grad_op_desc_maker.h): by default an op's grad is derived mechanically from
its forward kernel with ``jax.vjp``. The generated ``<op>_grad`` op follows
the reference slot convention: inputs = forward inputs + forward outputs +
``<out_slot>@GRAD``; outputs = ``<in_slot>@GRAD``. When forward+backward are
jitted together XLA CSE merges the re-traced forward, so this costs nothing
at run time. Ops whose reference grad semantics differ (dropout via Mask,
integer-indexed scatters, …) register custom grad ops / grad makers.

Kernel calling convention:
    ins:   dict slot_name -> list of jnp arrays (or None for absent
           dispensable slots). Duplicable slots hold len>1 lists.
    attrs: dict of python attr values. ``run_op`` injects ``_rng`` (jax
           PRNG key) if the op declared needs_rng; the executor injects
           ``_ctx`` (ExecContext) for stateful ops, which break pure
           tracing, and ``_lod`` for needs_lod ones.
    returns: dict slot_name -> list of jnp arrays.

``run_op`` is the one dispatch: the compiled trace, the interpreter and
the dygraph tracer all ask it which kernel runs and with which key.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


class OpInfo:
    __slots__ = (
        "type", "kernel", "infer_shape", "infer_dtype", "grad_maker",
        "no_grad", "needs_rng", "stateful", "diff_input_slots",
        "diff_output_slots", "attr_defaults", "input_slots", "output_slots",
        "needs_lod", "host_inputs",
    )

    def __init__(self, type_: str):
        self.type = type_
        self.kernel: Optional[Callable] = None
        self.infer_shape: Optional[Callable] = None
        self.infer_dtype: Optional[Callable] = None
        self.grad_maker: Optional[Callable] = None  # custom: (op) -> [opdesc dicts]
        self.no_grad = False
        self.needs_rng = False
        self.stateful = False
        self.diff_input_slots: Optional[Sequence[str]] = None
        self.diff_output_slots: Optional[Sequence[str]] = None
        self.attr_defaults: Dict[str, Any] = {}
        self.input_slots: Optional[Sequence[str]] = None
        self.output_slots: Optional[Sequence[str]] = None
        self.needs_lod = False
        # input slots whose VALUES the kernel reads host-side (trace-time):
        # the executor must run blocks containing such ops interpreted —
        # feeding them as traced jit arguments would TracerError
        self.host_inputs: Sequence[str] = ()


def _not_registered(op_type: str) -> NotImplementedError:
    return NotImplementedError(f"operator '{op_type}' is not registered")


class OpInfoMap:
    def __init__(self):
        self._map: Dict[str, OpInfo] = {}

    def get(self, type_: str) -> OpInfo:
        info = self._map.get(type_)
        if info is None:
            raise _not_registered(type_)
        return info

    def has(self, type_: str) -> bool:
        return type_ in self._map

    def get_or_create(self, type_: str) -> OpInfo:
        if type_ not in self._map:
            self._map[type_] = OpInfo(type_)
        return self._map[type_]

    def all_op_types(self):
        return sorted(self._map.keys())


OPS = OpInfoMap()


def resolve_base_info(op_type: str):
    """Registry info for an op type, resolving *_grad / *_grad_grad names
    to their base op. None for unknown types. Shared by the executor's
    compilability checks and the ir-level segmentation analysis — ONE
    resolver so the two can never classify the same op differently."""
    t = op_type
    if OPS.has(t):
        return OPS.get(t)
    while t.endswith("_grad"):
        t = t[:-5]
        if OPS.has(t):
            return OPS.get(t)
    return None


def own_seed(attrs: Dict) -> bool:
    """The op draws from a seed of its own (``fix_seed`` or a non-zero
    ``seed``), not from the program's random stream."""
    return bool(attrs.get("fix_seed", False) or attrs.get("seed", 0))


def with_rng(info: OpInfo, attrs: Dict, rng, idx: int) -> Dict:
    """``attrs`` with the key an op of ``info`` draws from in ``_rng``. A
    key already there is kept. Else, where the op declares needs_rng, its
    own seed gives ``key(seed)``, and the stream gives ``fold_in(rng, i)``
    at ``i`` = the forward op's ``_fwd_idx`` for a grad op (its vjp
    replays the forward's draws), the op's own ``idx`` otherwise. Copies
    ``attrs`` only where it adds the key."""
    if not info.needs_rng or "_rng" in attrs:
        return attrs
    attrs = dict(attrs)
    if own_seed(attrs):
        attrs["_rng"] = jax.random.key(int(attrs.get("seed", 0)))
    else:
        attrs["_rng"] = jax.random.fold_in(
            rng, int(attrs.get("_fwd_idx", idx)))
    return attrs


def run_op(op_type: str, ins: Dict[str, List], attrs: Dict,
           out_slots, in_slots, rng, idx: int) -> Dict[str, List]:
    """Run op ``op_type``: its registered kernel (a registered
    ``<op>_grad`` included); else ``<base>_grad`` through the vjp of the
    base kernel, producing the ``out_slots`` grads of the forward inputs
    ``_fwd_in`` (default ``in_slots``); else ``<base>_grad_grad``
    through the vjp of that. ``rng`` and ``idx`` give the key
    (``with_rng``)."""
    info = OPS._map.get(op_type)
    if info is not None:
        return info.kernel(ins, with_rng(info, attrs, rng, idx))
    if op_type.endswith("_grad"):
        info = OPS._map.get(op_type[:-5])
        if info is not None:
            return run_generic_grad(
                op_type[:-5], ins, with_rng(info, attrs, rng, idx),
                out_slots, attrs.get("_fwd_in", in_slots))
        info = (OPS._map.get(op_type[:-10])
                if op_type.endswith("_grad_grad") else None)
        if info is not None:
            return run_generic_grad_grad(
                op_type[:-10], ins, with_rng(info, attrs, rng, idx),
                out_slots, attrs.get("_fwd_in", in_slots))
    raise _not_registered(op_type)


def register_op(type_: str, *, no_grad: bool = False, needs_rng: bool = False,
                stateful: bool = False, needs_lod: bool = False,
                diff_inputs: Optional[Sequence[str]] = None,
                diff_outputs: Optional[Sequence[str]] = None,
                infer_shape: Optional[Callable] = None,
                attr_defaults: Optional[Dict[str, Any]] = None,
                inputs: Optional[Sequence[str]] = None,
                outputs: Optional[Sequence[str]] = None,
                host_inputs: Optional[Sequence[str]] = None):
    """Decorator registering a forward kernel under op name ``type_``.

    ``needs_lod``: the kernel consumes LoD (variable-length sequence)
    metadata. The executor injects ``attrs["_lod"] = {slot: [levels|None]}``
    where ``levels`` is a tuple of offset-tuples — HOST-STATIC under jit
    (the jit cache is keyed per feed-LoD bucket), so segment ids derived
    from it are XLA constants (TPU-friendly; replaces the reference's
    per-step dynamic LoD InferShape, lod_tensor.h:104). Kernels may return
    a special ``"_lod"`` entry ``{out_slot: [levels|None]}`` to set output
    LoD; absent that, the executor shares the first lod-bearing input's LoD
    with any output of matching leading length (the reference's ShareLoD
    default)."""
    def deco(fn: Callable):
        info = OPS.get_or_create(type_)
        info.kernel = fn
        info.no_grad = no_grad
        info.needs_rng = needs_rng
        info.stateful = stateful
        info.needs_lod = needs_lod
        info.diff_input_slots = diff_inputs
        info.diff_output_slots = diff_outputs
        info.infer_shape = infer_shape
        info.attr_defaults = dict(attr_defaults or {})
        info.input_slots = inputs
        info.output_slots = outputs
        info.host_inputs = tuple(host_inputs or ())
        return fn
    return deco


def register_grad_maker(type_: str):
    """Decorator registering a custom grad maker for op ``type_``. The maker
    receives the forward Operator and a dict mapping each forward-output var
    name to its grad var name, and returns a list of op-desc dicts:
    ``{"type":..., "inputs": {...}, "outputs": {...}, "attrs": {...}}``."""
    def deco(fn: Callable):
        OPS.get_or_create(type_).grad_maker = fn
        return fn
    return deco


def mark_no_grad(*types: str):
    for t in types:
        OPS.get_or_create(t).no_grad = True


# --------------------------------------------------------------------------
# kernel helpers
# --------------------------------------------------------------------------
def first(ins: Dict[str, List], slot: str):
    """Single (non-duplicable) input."""
    v = ins.get(slot)
    if not v:
        return None
    return v[0]


def seq(ins: Dict[str, List], slot: str) -> List:
    return ins.get(slot) or []


def out(**kwargs) -> Dict[str, List]:
    """outs(Out=x, Mask=[m]) — scalars are wrapped into 1-element lists."""
    res = {}
    for k, v in kwargs.items():
        if v is None:
            continue
        res[k] = v if isinstance(v, list) else [v]
    return res


# --------------------------------------------------------------------------
# generic vjp-based grad execution
# --------------------------------------------------------------------------
def _is_diff_leaf(x) -> bool:
    return x is not None and jnp.issubdtype(jnp.result_type(x), jnp.inexact)


def run_generic_grad(fwd_type: str, ins: Dict[str, List], attrs: Dict,
                     wanted_grad_slots: Sequence[str],
                     fwd_input_slots: Sequence[str]) -> Dict[str, List]:
    """Execute ``<fwd_type>_grad`` via jax.vjp over the forward kernel.

    ``ins`` holds forward inputs/outputs by their original slot names plus
    output grads under ``<slot>@GRAD``. ``fwd_input_slots`` names the slots
    that were genuine forward inputs (recorded by the default grad maker in
    the grad op's ``_fwd_in`` attr — slot names like "Y" are inputs for some
    ops and outputs for others, so this must be explicit). Returns
    ``<slot>@GRAD`` lists for the requested input slots."""
    info = OPS.get(fwd_type)
    return _vjp_through(info.kernel, info.diff_input_slots, ins, attrs,
                        wanted_grad_slots, fwd_input_slots)


def run_generic_grad_grad(base_type: str, ins: Dict[str, List], attrs: Dict,
                          wanted_grad_slots: Sequence[str],
                          gradop_slots: Sequence[str]) -> Dict[str, List]:
    """Execute ``<base>_grad_grad`` — the vjp of the generic grad
    computation itself (static double grad: gradient-penalty losses).

    ``gradop_slots`` names the ``<base>_grad`` op's own input slots
    (primals + outputs + output cotangents); ``ins`` additionally holds
    the first-order grads under their slots plus the incoming
    second-order cotangents under ``<slot>@GRAD@GRAD``-style names. The
    base op's true forward slots ride in ``attrs["_fwd_in_base"]``."""
    base_attrs = {k: v for k, v in attrs.items()
                  if k not in ("_fwd_in", "_fwd_in_base")}
    base_fwd = list(attrs.get("_fwd_in_base")
                    or [s for s in gradop_slots
                        if not s.endswith(GRAD_SUFFIX)])
    base_attrs["_fwd_in"] = base_fwd
    # the inner grad op's outputs = slots that carry a cotangent here
    inner_out_slots = [s for s in ins
                       if s.endswith(GRAD_SUFFIX)
                       and s + GRAD_SUFFIX in ins]

    def inner_kernel(merged, _attrs):
        gouts = run_generic_grad(base_type, merged, base_attrs,
                                 inner_out_slots, base_fwd)
        fixed = {}
        for s, vals in gouts.items():
            prim = merged.get(s[:-len(GRAD_SUFFIX)], [])
            fixed[s] = [
                v if v is not None else
                (jnp.zeros_like(prim[i]) if i < len(prim)
                 and _is_diff_leaf(prim[i]) else None)
                for i, v in enumerate(vals)]
        return fixed

    return _vjp_through(inner_kernel, None, ins, base_attrs,
                        wanted_grad_slots, gradop_slots)


def _vjp_through(kernel, diff_input_slots, ins: Dict[str, List],
                 attrs: Dict, wanted_grad_slots: Sequence[str],
                 fwd_input_slots: Sequence[str]) -> Dict[str, List]:
    """Shared vjp core: differentiate ``kernel(ins, attrs)`` w.r.t. the
    differentiable leaves of ``fwd_input_slots``, with cotangents taken
    from ``<slot>@GRAD`` entries of ``ins``."""
    fwd_in_slots = [s for s in fwd_input_slots if s in ins]
    # Partition forward-input leaves into differentiable / constant.
    diff_sel: Dict[str, List[bool]] = {}
    allowed = set(diff_input_slots) if diff_input_slots else None
    for s in fwd_in_slots:
        vals = ins[s] or []
        diff_sel[s] = [
            _is_diff_leaf(v) and (allowed is None or s in allowed)
            for v in vals
        ]
    diff_part = {s: [v for v, d in zip(ins[s], diff_sel[s]) if d]
                 for s in fwd_in_slots}
    diff_part = {s: v for s, v in diff_part.items() if v}

    def fwd(dp):
        merged = {}
        for s in fwd_in_slots:
            vals = list(ins[s] or [])
            it = iter(dp.get(s, []))
            merged[s] = [next(it) if d else v for v, d in zip(vals, diff_sel[s])]
        outs = kernel(merged, attrs)
        # Only outputs that have incoming grads (or are float) participate;
        # "_lod"-style metadata entries are not tensors.
        return {k: v for k, v in outs.items()
                if not k.startswith("_") and any(_is_diff_leaf(x) for x in v)}

    primals_out, vjp_fn = jax.vjp(fwd, diff_part)

    cotangents = {}
    for oslot, ovals in primals_out.items():
        gslot = oslot + GRAD_SUFFIX
        gvals = ins.get(gslot)
        cots = []
        for i, ov in enumerate(ovals):
            if ov is None:  # non-diff entry kept for slot alignment
                cots.append(None)
                continue
            g = gvals[i] if gvals is not None and i < len(gvals) and gvals[i] is not None else None
            if g is None:
                g = jnp.zeros_like(ov)
            else:
                g = jnp.asarray(g, ov.dtype) if g.dtype != ov.dtype else g
                if g.shape != ov.shape:
                    g = jnp.broadcast_to(g, ov.shape)
            cots.append(g)
        cotangents[oslot] = cots

    (grads_in,) = vjp_fn(cotangents)

    result: Dict[str, List] = {}
    for s in fwd_in_slots:
        gslot = s + GRAD_SUFFIX
        if gslot not in wanted_grad_slots:
            continue
        gl = []
        it = iter(grads_in.get(s, []))
        for v, d in zip(ins[s] or [], diff_sel[s]):
            gl.append(next(it) if d else None)
        result[gslot] = gl
    return result
