"""DyGraph core: VarBase + Tracer + tape autograd.

Reference mapping: VarBase (imperative/layer.h:56), Tracer::TraceOp
(imperative/tracer.cc:45) which creates the op, runs it, and records a grad
node; BasicEngine::Execute (imperative/basic_engine.cc:159) which sweeps the
grad DAG with GradientAccumulators.

TPU design: ops execute eagerly as jax calls (async dispatch gives the
pipelining the reference gets from CUDA streams) through the one dispatch
the static executor uses, ops/registry.py ``run_op``; the tape stores (op,
ins, outs, attrs) with the forward's key in ``_rng``, and backward runs
``<op>_grad`` through ``run_op`` too (a registered grad kernel or the vjp
of the forward, drawing what the forward drew) — one grad semantics for
both modes. ``dygraph.jit`` re-traces functions into jax.jit for the
compiled path (reference dygraph_to_static / TracedLayer)."""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import core, framework, unique_name
from ..core import VarDesc, convert_np_dtype_to_dtype_, dtype_to_jnp
from ...ops.registry import OPS, GRAD_SUFFIX, own_seed, run_op, with_rng

__all__ = ["guard", "to_variable", "enabled", "no_grad", "grad", "VarBase",
           "Tracer", "enable_dygraph", "disable_dygraph",
           "BackwardStrategy"]


class BackwardStrategy:
    """reference: pybind imperative.cc BackwardStrategy — sort_sum_gradient
    forces deterministic gradient accumulation order. The tape here sums
    fan-in in recorded order, which is already deterministic, so the knob
    is accepted and recorded only."""

    def __init__(self):
        self.sort_sum_gradient = False


class VarBase:
    """Imperative tensor (reference imperative/layer.h:56)."""

    def __init__(self, array=None, name: Optional[str] = None,
                 stop_gradient: bool = True, persistable: bool = False,
                 trainable: bool = False, dtype=None, shape=None):
        if array is not None and not isinstance(array, jax.Array):
            array = jnp.asarray(array)
        self._array = array
        self.name = name or unique_name.generate("generated_var")
        self.stop_gradient = stop_gradient
        self.persistable = persistable
        self.trainable = trainable
        self._grad: Optional[jnp.ndarray] = None
        self._declared_dtype = dtype
        self._declared_shape = shape
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_data = False
        self.lod_level = 0
        self.type = VarDesc.VarType.LOD_TENSOR

    # -- data -------------------------------------------------------------
    @property
    def shape(self):
        if self._array is not None:
            return tuple(self._array.shape)
        return tuple(self._declared_shape or ())

    @shape.setter
    def shape(self, value):
        # static layer helpers annotate declared shape before the op runs;
        # once an array exists, its real shape wins
        self._declared_shape = tuple(value)

    @property
    def dtype(self):
        if self._array is not None:
            return core.np_to_dtype(np.dtype(str(self._array.dtype))
                                    if self._array.dtype != jnp.bfloat16
                                    else "bfloat16")
        return self._declared_dtype or VarDesc.VarType.FP32

    @property
    def array(self):
        return self._array

    def numpy(self):
        return np.asarray(self._array)

    def set_value(self, value):
        if isinstance(value, VarBase):
            value = value._array
        self._array = jnp.asarray(np.asarray(value)) \
            if not isinstance(value, jax.Array) else value

    def detach(self):
        return VarBase(self._array, stop_gradient=True)

    def astype(self, dtype):
        return _trace_simple("cast", {"X": [self]},
                             {"in_dtype": self.dtype,
                              "out_dtype": convert_np_dtype_to_dtype_(dtype)
                              if not isinstance(dtype, int) else dtype})

    # -- autograd ---------------------------------------------------------
    def backward(self, backward_strategy=None):
        tracer = framework._dygraph_tracer()
        assert tracer is not None, "backward() outside dygraph guard"
        tracer.run_backward(self)

    def gradient(self):
        return np.asarray(self._grad) if self._grad is not None else None

    @property
    def _grad_ivar(self):
        if self._grad is None:
            return None
        return VarBase(self._grad, name=self.name + "@GRAD",
                       stop_gradient=True)

    def clear_gradient(self):
        self._grad = None

    # -- operator sugar ---------------------------------------------------
    def _binary(self, other, op_type, reverse=False):
        if not isinstance(other, VarBase):
            other = VarBase(jnp.asarray(other, dtype_to_jnp(self.dtype)),
                            stop_gradient=True)
        x, y = (other, self) if reverse else (self, other)
        return _trace_simple(op_type, {"X": [x], "Y": [y]}, {"axis": -1})

    __add__ = lambda s, o: s._binary(o, "elementwise_add")
    __radd__ = lambda s, o: s._binary(o, "elementwise_add", True)
    __sub__ = lambda s, o: s._binary(o, "elementwise_sub")
    __rsub__ = lambda s, o: s._binary(o, "elementwise_sub", True)
    __mul__ = lambda s, o: s._binary(o, "elementwise_mul")
    __rmul__ = lambda s, o: s._binary(o, "elementwise_mul", True)
    __truediv__ = lambda s, o: s._binary(o, "elementwise_div")
    __rtruediv__ = lambda s, o: s._binary(o, "elementwise_div", True)
    __pow__ = lambda s, o: s._binary(o, "elementwise_pow")
    __lt__ = lambda s, o: s._binary(o, "less_than")
    __le__ = lambda s, o: s._binary(o, "less_equal")
    __gt__ = lambda s, o: s._binary(o, "greater_than")
    __ge__ = lambda s, o: s._binary(o, "greater_equal")

    def __bool__(self):
        return bool(np.asarray(self._array).reshape(-1)[0]) \
            if np.asarray(self._array).size == 1 \
            else bool(np.asarray(self._array).any())

    def __float__(self):
        return float(np.asarray(self._array).reshape(-1)[0])

    def __int__(self):
        return int(np.asarray(self._array).reshape(-1)[0])

    def __len__(self):
        return int(self.shape[0]) if self.shape else 0

    def __repr__(self):
        return (f"VarBase(name={self.name}, shape={list(self.shape)}, "
                f"stop_gradient={self.stop_gradient})\n{self.numpy()}")

    # block attr for API compat with static Variable
    @property
    def block(self):
        return framework.default_main_program().global_block()


class _TapeEntry:
    __slots__ = ("op_type", "ins", "outs", "attrs")

    def __init__(self, op_type, ins, outs, attrs):
        self.op_type = op_type
        self.ins = ins
        self.outs = outs
        self.attrs = attrs


class Tracer:
    """reference imperative/tracer.cc:45 — eager exec + grad-node record."""

    def __init__(self):
        self._tape: List[_TapeEntry] = []
        self._no_grad = False
        self._train_mode = True
        self._rng_counter = 0
        self._params: Dict[str, VarBase] = {}

    # ---------------------------------------------------------------- ops
    def trace_op(self, op_type, inputs, outputs, attrs):
        attrs = dict(attrs or {})
        info = OPS.get(op_type)
        ins_vb: Dict[str, List[VarBase]] = {}
        for slot, vals in (inputs or {}).items():
            if vals is None:
                continue
            if not isinstance(vals, (list, tuple)):
                vals = [vals]
            ins_vb[slot] = [v for v in vals]
        ins_arrays = {slot: [v._array if isinstance(v, VarBase) else
                             (v.array if hasattr(v, "array") else jnp.asarray(v))
                             for v in vals]
                      for slot, vals in ins_vb.items()}
        if info.needs_rng:
            # the key goes on the tape: backward and replay draw the same
            attrs = with_rng(info, attrs,
                             jax.random.key(core.globals_["FLAGS_seed"]),
                             self._rng_counter)
            if not own_seed(attrs):
                self._rng_counter += 1
        if info.stateful:
            raise RuntimeError(
                f"op {op_type} is host-stateful and has no dygraph path")
        outs_arrays = run_op(op_type, ins_arrays, attrs, (), (), None, 0)
        outs_vb: Dict[str, List[VarBase]] = {}
        fresh: List[VarBase] = []
        for slot, vals in (outputs or {}).items():
            if vals is None:
                continue
            if not isinstance(vals, (list, tuple)):
                vals = [vals]
            produced = (outs_arrays or {}).get(slot, [])
            lst = []
            for k, ov in enumerate(vals):
                arr = produced[k] if k < len(produced) else None
                if isinstance(ov, VarBase):
                    was_fresh = ov._array is None
                    if arr is not None:
                        ov._array = arr
                    if was_fresh:
                        fresh.append(ov)
                    lst.append(ov)
                else:
                    nv = VarBase(arr)
                    fresh.append(nv)
                    lst.append(nv)
            outs_vb[slot] = lst
        # default-constructed outputs for slots the layer didn't pass
        for slot, produced in (outs_arrays or {}).items():
            if slot not in outs_vb:
                outs_vb[slot] = [VarBase(a) for a in produced]
                fresh.extend(outs_vb[slot])

        requires_grad = (not self._no_grad and not info.no_grad and any(
            isinstance(v, VarBase) and not v.stop_gradient
            for vals in ins_vb.values() for v in vals))
        # only fresh outputs inherit requires_grad; pre-existing vars
        # (in-place params of optimizer ops) keep their own flag
        for v in fresh:
            v.stop_gradient = not requires_grad
        if requires_grad:
            self._tape.append(_TapeEntry(op_type, ins_vb, outs_vb,
                                         {k: v for k, v in attrs.items()}))
        first_slot = next(iter(outs_vb.values()), [None])
        return first_slot[0] if len(outs_vb) == 1 and len(first_slot) == 1 \
            else outs_vb

    # ---------------------------------------------------------- backward
    def run_backward(self, loss: VarBase):
        grads: Dict[int, jnp.ndarray] = {
            id(loss): jnp.ones_like(loss._array)}
        for entry in reversed(self._tape):
            ograds_present = any(
                id(v) in grads for vals in entry.outs.values() for v in vals)
            if not ograds_present:
                continue
            if entry.op_type == "@functional@":
                # a dygraph.grad(create_graph=True) node: backward is the
                # vjp of the recorded grad computation (vjp-of-the-vjp)
                in_vbs = entry.ins["In"]
                out_vbs = entry.outs["Out"]
                in_arrays = [v._array for v in in_vbs]
                outs_vals, vjp_fn = jax.vjp(entry.attrs["_fn"], *in_arrays)
                cots = tuple(
                    grads.get(id(v), None) if grads.get(id(v), None)
                    is not None else jnp.zeros_like(o)
                    for v, o in zip(out_vbs, outs_vals))
                for v, g in zip(in_vbs, vjp_fn(cots)):
                    if isinstance(v, VarBase) and not v.stop_gradient:
                        prev = grads.get(id(v))
                        grads[id(v)] = g if prev is None else prev + g
                continue
            ins = {slot: [v._array for v in vals]
                   for slot, vals in entry.ins.items()}
            for slot, vals in entry.outs.items():
                ins.setdefault(slot, [v._array for v in vals])
                ins[slot + GRAD_SUFFIX] = [grads.get(id(v)) for v in vals]
            wanted = []
            for slot, vals in entry.ins.items():
                if any(isinstance(v, VarBase) and not v.stop_gradient
                       for v in vals):
                    wanted.append(slot + GRAD_SUFFIX)
            if not wanted:
                continue
            gouts = run_op(entry.op_type + "_grad", ins, entry.attrs, wanted,
                           entry.ins, None, 0)
            for slot, vals in entry.ins.items():
                gvals = (gouts or {}).get(slot + GRAD_SUFFIX)
                if gvals is None:
                    continue
                for v, g in zip(vals, gvals):
                    if g is None or not isinstance(v, VarBase) \
                            or v.stop_gradient:
                        continue
                    # GradientAccumulator: sum fan-in
                    prev = grads.get(id(v))
                    grads[id(v)] = g if prev is None else prev + g
        # write grads onto leaves (params + any var the user watches) —
        # ONCE per var: grads[] already holds the fan-in total, and a var
        # appearing in several tape entries (x*x, residual reuse) must
        # not have its total added per occurrence (round-4 fix: y=x*x
        # used to report dx=4x). The += below is only the accumulation
        # ACROSS separate backward() calls, per reference semantics.
        written_leaves = set()
        for entry in self._tape:
            for vals in entry.ins.values():
                for v in vals:
                    if isinstance(v, VarBase) and not v.stop_gradient \
                            and id(v) in grads \
                            and id(v) not in written_leaves:
                        written_leaves.add(id(v))
                        g = grads[id(v)]
                        v._grad = g if v._grad is None else v._grad + g
        self._tape.clear()

    # ------------------------------------------------------------ params
    def create_parameter(self, name, shape, dtype, initializer, trainable,
                         optimize_attr=None, regularizer=None):
        if name in self._params:
            return self._params[name]
        arr = _run_initializer(initializer, shape, dtype, self)
        p = VarBase(arr, name=name, stop_gradient=not trainable,
                    persistable=True, trainable=trainable)
        p.optimize_attr = optimize_attr or {"learning_rate": 1.0}
        p.regularizer = regularizer
        self._params[name] = p
        return p

    def init_variable(self, var, initializer):
        if isinstance(var, VarBase) and var._array is None:
            var._array = _run_initializer(initializer, var.shape, var.dtype,
                                          self)
        return var

    @contextlib.contextmanager
    def _no_grad_guard(self):
        old = self._no_grad
        self._no_grad = True
        try:
            yield
        finally:
            self._no_grad = old


def _run_initializer(initializer, shape, dtype, tracer: Tracer):
    """Run an initializer's op spec eagerly to produce the param array."""
    from ..initializer import (ConstantInitializer, UniformInitializer,
                               NormalInitializer, TruncatedNormalInitializer,
                               XavierInitializer, MSRAInitializer,
                               NumpyArrayInitializer)
    if not isinstance(dtype, int):
        dtype = convert_np_dtype_to_dtype_(dtype)
    jdt = dtype_to_jnp(dtype)
    key = jax.random.fold_in(jax.random.key(core.globals_["FLAGS_seed"]),
                             tracer._rng_counter)
    tracer._rng_counter += 1
    shape = [int(s) for s in shape]
    if initializer is None:
        initializer = XavierInitializer()
    if isinstance(initializer, ConstantInitializer):
        return jnp.full(shape, initializer._value, jdt)
    if isinstance(initializer, UniformInitializer):
        return jax.random.uniform(key, shape, jdt, initializer._low,
                                  initializer._high)
    if isinstance(initializer, NormalInitializer):
        return initializer._mean + initializer._std * jax.random.normal(
            key, shape, jdt)
    if isinstance(initializer, TruncatedNormalInitializer):
        return initializer._mean + initializer._std * \
            jax.random.truncated_normal(key, -2.0, 2.0, shape, jdt)
    if isinstance(initializer, NumpyArrayInitializer):
        return jnp.asarray(initializer._value.astype(np.dtype(jdt)))
    if isinstance(initializer, (XavierInitializer, MSRAInitializer)):
        class _V:
            pass
        v = _V()
        v.shape = shape
        fin, fout = initializer._compute_fans(v)
        import math
        if isinstance(initializer, XavierInitializer):
            fin = initializer._fan_in or fin
            fout = initializer._fan_out or fout
            if initializer._uniform:
                lim = math.sqrt(6.0 / (fin + fout))
                return jax.random.uniform(key, shape, jdt, -lim, lim)
            std = math.sqrt(2.0 / (fin + fout))
            return std * jax.random.normal(key, shape, jdt)
        fin = initializer._fan_in or fin
        if initializer._uniform:
            lim = math.sqrt(6.0 / fin)
            return jax.random.uniform(key, shape, jdt, -lim, lim)
        return math.sqrt(2.0 / fin) * jax.random.normal(key, shape, jdt)
    raise TypeError(f"unsupported dygraph initializer {initializer}")


def _trace_simple(op_type, ins, attrs):
    tracer = framework._dygraph_tracer()
    return tracer.trace_op(op_type, ins, {"Out": [VarBase(None)]}, attrs)


# --------------------------------------------------------------------------
# mode management (reference dygraph/base.py guard/enabled/no_grad)
# --------------------------------------------------------------------------
_global_tracer: Optional[Tracer] = None


def enabled():
    return framework.in_dygraph_mode()


@contextlib.contextmanager
def guard(place=None):
    tracer = Tracer()
    with framework.program_guard(framework.Program(), framework.Program()):
        with unique_name.guard():
            with framework._dygraph_guard(tracer):
                with framework._dygraph_place_guard(
                        place or framework._current_expected_place()):
                    yield


def enable_dygraph(place=None):
    global _global_tracer
    _global_tracer = Tracer()
    framework._dygraph_tracer_ = _global_tracer


def disable_dygraph():
    global _global_tracer
    framework._dygraph_tracer_ = None
    _global_tracer = None


def to_variable(value, name=None, zero_copy=None):
    if isinstance(value, VarBase):
        return value
    arr = np.asarray(value)
    return VarBase(jnp.asarray(arr), name=name, stop_gradient=True)


def no_grad(fn=None):
    tracer = framework._dygraph_tracer()
    if fn is None:
        if tracer is None:
            return contextlib.nullcontext()
        return tracer._no_grad_guard()

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        t = framework._dygraph_tracer()
        if t is None:
            return fn(*args, **kwargs)
        with t._no_grad_guard():
            return fn(*args, **kwargs)
    return wrapper


def _reachable(tape, inputs, no_grad_ids):
    """Structural reachability: ids of every var transitively computed
    from ``inputs`` along the tape (no kernels executed)."""
    live = {id(v) for v in inputs if id(v) not in no_grad_ids}
    for entry in tape:
        if any(id(v) in live
               for vals in entry.ins.values() for v in vals):
            live.update(id(v) for vals in entry.outs.values()
                        for v in vals)
    return live


def _replayable_fn(tape, inputs, outputs, no_grad_ids):
    """Build a PURE function f(*input_arrays) -> output_arrays by
    replaying the tape segment between ``inputs`` and ``outputs`` with
    the recorded attrs (rng keys included, so dropout replays the same
    mask). Vars outside the input-reachable set enter as recorded
    constants. An input that is ITSELF produced by a replayed entry
    (grad(z, [x, y]) with y on the x→z path) is rebound as
    recomputed + (arg − stop_gradient(arg)): the value stays the
    recomputed one (total derivative flows through to x) while the
    identity residual routes the partial ∂/∂y to the y argument —
    the reference/PyTorch multi-input grad contract."""
    input_ids = {id(v): k for k, v in enumerate(inputs)}

    def f(*in_arrays):
        env = {id(v): a for v, a in zip(inputs, in_arrays)
               if id(v) not in no_grad_ids}

        def bind(v, a):
            k = input_ids.get(id(v))
            if k is None:
                env[id(v)] = a
            else:
                arg = in_arrays[k]
                env[id(v)] = a + (arg - jax.lax.stop_gradient(arg))

        for entry in tape:
            if entry.op_type == "@functional@":
                if not any(id(v) in env for v in entry.ins["In"]):
                    continue
                vals = [env.get(id(v), v._array) for v in entry.ins["In"]]
                outs = entry.attrs["_fn"](*vals)
                for v, a in zip(entry.outs["Out"], outs):
                    bind(v, a)
                continue
            if not any(id(v) in env
                       for vals in entry.ins.values() for v in vals):
                continue
            ins = {slot: [env.get(id(v), v._array) for v in vals]
                   for slot, vals in entry.ins.items()}
            outs = run_op(entry.op_type, ins, entry.attrs, (), (), None, 0)
            for slot, vals in entry.outs.items():
                produced = (outs or {}).get(slot)
                if produced is None:
                    continue
                for v, a in zip(vals, produced):
                    if a is not None:
                        bind(v, a)
        return tuple(env.get(id(o), o._array) for o in outputs)
    return f


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None, backward_strategy=None):
    """Gradients of ``outputs`` w.r.t. ``inputs`` over the live tape
    (reference imperative/partial_grad_engine.cc). The tape segment is
    replayed as a pure function and differentiated with jax.vjp; with
    ``create_graph=True`` the grad computation is recorded back onto the
    tape as a functional node whose backward is the vjp-of-the-vjp, so
    losses built from these grads (gradient penalty) differentiate
    correctly. ``grad_outputs`` seeds the cotangents (None entries mean
    ones); ``allow_unused`` returns None for disconnected inputs instead
    of raising. The tape is NOT consumed (retain_graph semantics are
    automatic; pass retain_graph=False alongside create_graph=False to
    release it)."""
    if not only_inputs:
        raise NotImplementedError("only_inputs=False is deprecated in the "
                                  "reference and unsupported here")
    tracer = framework._dygraph_tracer()
    assert tracer is not None, "dygraph.grad() outside dygraph guard"
    outputs = list(outputs) if isinstance(outputs, (list, tuple)) \
        else [outputs]
    inputs = list(inputs) if isinstance(inputs, (list, tuple)) \
        else [inputs]
    if grad_outputs is not None:
        grad_outputs = list(grad_outputs) \
            if isinstance(grad_outputs, (list, tuple)) else [grad_outputs]
        if len(grad_outputs) != len(outputs):
            raise ValueError("grad_outputs must match outputs length")
    no_grad_ids = {id(v) for v in (no_grad_vars or [])}
    tape = list(tracer._tape)
    out_ids = {id(o) for o in outputs}
    # per-input structural connectivity (which outputs each input reaches)
    input_connected = [bool(out_ids & _reachable(tape, [v], no_grad_ids))
                       for v in inputs]
    if not any(input_connected):
        if not allow_unused:
            raise RuntimeError(
                "dygraph.grad: outputs are not connected to inputs "
                "(pass allow_unused=True to get None)")
        return [None for _ in inputs]

    # every OTHER differentiable leaf the segment reads (params, earlier
    # activations from outside the segment): they must be real arguments
    # of the replayed function, not captured constants — otherwise
    # create_graph second-order grads can't flow to them (the gradient-
    # penalty-to-weights path)
    seen = {id(v) for v in inputs}
    produced = {id(v) for e in tape
                for vals in e.outs.values() for v in vals}
    extras: List[VarBase] = []
    for e in tape:
        for vals in e.ins.values():
            for v in vals:
                if isinstance(v, VarBase) and not v.stop_gradient \
                        and id(v) not in seen and id(v) not in produced \
                        and id(v) not in no_grad_ids:
                    seen.add(id(v))
                    extras.append(v)

    f = _replayable_fn(tape, inputs + extras, outputs, no_grad_ids)

    cots = []
    cot_vbs = []  # VarBase cotangents participate in the graph
    for k, o in enumerate(outputs):
        g = grad_outputs[k] if grad_outputs is not None else None
        if g is None:
            cots.append(jnp.ones_like(o._array))
            cot_vbs.append(None)
        elif isinstance(g, VarBase):
            cots.append(g._array)
            cot_vbs.append(g)
        else:
            cots.append(jnp.asarray(g))
            cot_vbs.append(None)

    n_in, n_out, n_extra = len(inputs), len(outputs), len(extras)

    def gfn(*arrays):
        """arrays = input vals + cotangent vals + extra-leaf vals ->
        grads w.r.t. the inputs only."""
        ivals = arrays[:n_in]
        cvals = arrays[n_in:n_in + n_out]
        evals = arrays[n_in + n_out:]
        _, vjp_fn = jax.vjp(f, *(tuple(ivals) + tuple(evals)))
        return tuple(vjp_fn(tuple(cvals))[:n_in])

    call_args = [v._array for v in inputs] + cots \
        + [v._array for v in extras]
    gin = gfn(*call_args)

    # disconnected inputs -> None per the reference contract
    results: List[Optional[VarBase]] = []
    for k, (v, g) in enumerate(zip(inputs, gin)):
        if not input_connected[k]:
            if not allow_unused:
                raise RuntimeError(
                    f"dygraph.grad: input {v.name} is unreachable from "
                    f"outputs (pass allow_unused=True to get None)")
            results.append(None)
            continue
        results.append(VarBase(g, name=v.name + "@GRAD",
                               stop_gradient=not create_graph))

    if create_graph:
        # record the whole grad computation as ONE tape node; its
        # backward is jax.vjp(gfn, ...) — true second order, with
        # cotangents flowing to inputs, VarBase grad_outputs AND the
        # extra leaves (params)
        live_cots = [c for c in cot_vbs if c is not None]
        in_vbs = list(inputs) + live_cots + list(extras)

        def gfn_tape(*arrays):
            ins = list(arrays[:n_in])
            j = n_in
            cs = list(cots)
            for k, c in enumerate(cot_vbs):
                if c is not None:
                    cs[k] = arrays[j]
                    j += 1
            evals = list(arrays[j:])
            full = gfn(*(ins + cs + evals))
            return tuple(full[k] for k in range(len(inputs))
                         if results[k] is not None)

        tracer._tape.append(_TapeEntry(
            "@functional@", {"In": in_vbs},
            {"Out": [r for r in results if r is not None]},
            {"_fn": gfn_tape}))
    return results


# hooks used by Optimizer in dygraph mode
def _dygraph_backward(optimizer, loss, parameter_list):
    loss.backward()
    params = parameter_list or list(
        framework._dygraph_tracer()._params.values())
    return [(p, p._grad_ivar) for p in params
            if p.trainable and p._grad_ivar is not None]


def _dygraph_minimize(optimizer, loss, startup_program, parameter_list,
                      no_grad_set):
    params_grads = _dygraph_backward(optimizer, loss, parameter_list)
    optimize_ops = optimizer._create_optimization_pass(params_grads)
    return optimize_ops, params_grads


def _clear_gradients(parameter_list):
    tracer = framework._dygraph_tracer()
    params = parameter_list or (list(tracer._params.values())
                                if tracer else [])
    for p in params:
        if isinstance(p, VarBase):
            p.clear_gradient()
