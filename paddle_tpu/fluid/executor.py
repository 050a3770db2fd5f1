"""Executor — runs Programs on TPU.

The reference Executor (reference: paddle/fluid/framework/executor.cc:184,
python/paddle/fluid/executor.py:457) interprets a block op-by-op per step,
doing per-op kernel choice, data transform, InferShape and GC. That design
is inverted here for TPU: ``Executor.run`` traces the whole block ONCE into
a pure function ``(state, feeds, rng) -> (fetches, new_state)`` and compiles
it with ``jax.jit`` — op fusion, layout, memory planning and GC all become
XLA's job, and parameter updates alias in-place via buffer donation.

Three paths:
  * compiled (default): pure-traceable blocks. Program cache keyed like the
    reference's (executor.py:1171 cache) by (program id, version, feeds,
    fetches, scope).
  * segmented (default when the block is NOT fully traceable): the op list
    is partitioned into maximal pure runs — each jitted as its own donated
    computation — around stateful/host-op *islands* the interpreter
    dispatches eagerly (``_SegmentedBlock``; analysis in
    fluid/ir.py:analyze_block_segments). One auc/print/read op no longer
    de-compiles the whole block: the reference pays per-op dispatch
    everywhere (executor.cc:469-475), this build pays it only at islands.
  * interpreted: the correctness oracle, also used for startup programs and
    blocks with nothing worth jitting (FLAGS_executor_segmentation=False
    forces it for all partially-stateful blocks). Still executes on
    device, just eagerly.

Feed/fetch: direct dict-in/list-out like the reference API; programs that
already contain feed/fetch ops (e.g. deserialized reference models) work
too — their feed/fetch ops read/write the same feed/fetch list variables
(reference: executor.cc:195-306).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import os
import weakref

import numpy as np
import jax
import jax.numpy as jnp

from . import core
from . import telemetry as _telemetry
from . import analysis as _analysis
from .core import LoDTensor, Scope, global_scope
from .framework import Program, Variable, default_main_program
from ..ops.registry import (GRAD_SUFFIX, run_op,
                            resolve_base_info as _resolve_base_info)

__all__ = ["Executor", "global_scope", "scope_guard", "FetchHandler"]


class FetchHandler:
    """Periodic async fetch during dataset training (reference:
    executor.py FetchHandler + trainer FetchHandlerMonitor thread — user
    overrides handler(); it receives {var_name: numpy|None} snapshots every
    ``period_secs`` while train_from_dataset runs)."""

    def __init__(self, var_dict=None, period_secs=60):
        if var_dict is None or not isinstance(var_dict, dict):
            raise TypeError("var_dict must be a {name: Variable} dict")
        self.var_dict = var_dict
        self.period_secs = period_secs

    def handler(self, res_dict):
        for key in res_dict:
            if isinstance(res_dict[key], np.ndarray):
                print(f"{key}[0]: {res_dict[key][0]} ")

    @staticmethod
    def help():
        print("""
class FetchHandlerExample(FetchHandler):
    def handler(self, res_dict):
        print(res_dict["var1"])  # numpy snapshot (None if not yet set)
handler = FetchHandlerExample(var_dict={"var1": var1}, period_secs=60)
""")


class _FetchHandlerMonitor:
    """Daemon thread sampling scope vars for a FetchHandler (reference:
    trainer_factory.py FetchHandlerMonitor)."""

    def __init__(self, scope: Scope, handler: FetchHandler):
        import threading
        self._scope = scope
        self._handler = handler
        self._stop_evt = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _sample(self):
        res = {}
        for name, var in self._handler.var_dict.items():
            vname = getattr(var, "name", var)
            v = self._scope.find_var(vname)
            if v is None or not v.is_initialized():
                res[name] = None
                continue
            try:
                res[name] = np.asarray(v.get_tensor().array)
            except (RuntimeError, TypeError):
                # RuntimeError: donated state buffer invalidated between
                # the scope read and the host copy (the training step
                # aliases it in place). TypeError: non-LoDTensor holder
                # (e.g. SelectedRows) has no dense tensor view.
                # Monitoring is best-effort — report None.
                res[name] = None
        return res

    def _loop(self):
        while not self._stop_evt.wait(self._handler.period_secs):
            self._handler.handler(self._sample())

    def start(self):
        self._thread.start()

    def stop(self):
        # stop the periodic loop and join BEFORE the final synchronous
        # sample, so the user handler is never invoked concurrently with
        # (or after) it
        self._stop_evt.set()
        if self._thread.is_alive():
            # unbounded: the loop exits as soon as any in-flight handler
            # call returns (the event is already set), and joining fully is
            # what guarantees no concurrent handler invocation below
            self._thread.join()
        # final synchronous sample so short runs still see one callback
        self._handler.handler(self._sample())


import contextlib


@contextlib.contextmanager
def scope_guard(scope: Scope):
    old = core._switch_scope(scope)
    try:
        yield
    finally:
        core._switch_scope(old)


class ExecContext:
    """Handed to stateful kernels via attrs['_ctx']."""
    __slots__ = ("scope", "executor", "op", "place", "rng_base")

    def __init__(self, scope, executor, op, place, rng_base):
        self.scope = scope
        self.executor = executor
        self.op = op
        self.place = place
        self.rng_base = rng_base


def _as_lodtensor(data, place) -> LoDTensor:
    if isinstance(data, LoDTensor):
        if not isinstance(data.array, jax.Array):
            data.set(np.asarray(data.array), place)
        return data
    if isinstance(data, jax.Array):
        # already device-resident (e.g. the DataLoader window prefetch
        # stage device_put the batch while the previous window computed)
        # — wrap without a host round-trip, nothing to re-upload
        return LoDTensor(data)
    t = LoDTensor()
    t.set(data if isinstance(data, np.ndarray) else np.asarray(data), place)
    return t


def _committed(a):
    """An uncommitted single-device ``jax.Array`` as a committed one over
    the same buffer; anything else as it is."""
    if isinstance(a, jax.Array) and not a.committed:
        return jax.device_put(a, next(iter(a.devices())))
    return a


def _initialized_tensor(scope, name) -> Optional[LoDTensor]:
    """The scope var's holder when it exists and is an initialized dense
    LoDTensor; None otherwise. THE numeric-fault-plane state predicate:
    the compiled guard classification (_CompiledBlock._init_guard) and
    the interpreter oracle (_interp_guard_cfg/_run_interpreted_step)
    must agree on it or their health/select variable sets drift and the
    bit-parity contract breaks."""
    v = scope.find_var(name)
    if v is not None and v.is_initialized() and isinstance(v.value(),
                                                           LoDTensor):
        return v.value()
    return None


def _window_feed_names(program, feed, n_steps) -> Tuple[str, ...]:
    """Feeds carrying a leading window dimension: value rank is the
    program var's rank + 1 and the leading dim equals ``n_steps`` —
    `feed={x: [K, batch, ...]}` with `n_steps=K` means the K slices are
    K *distinct* batches, consumed one per step (lax.scan xs on the
    compiled path). A rank-matched feed whose leading dim disagrees
    with n_steps is a user error and raises; LoD cannot describe a
    stacked window, so a windowed feed with LoD raises too."""
    names = []
    block = program.global_block()
    for name, data in feed.items():
        arr = data.array if isinstance(data, LoDTensor) else data
        shp = getattr(arr, "shape", None)
        if not shp:
            continue
        v = block._find_var_recursive(name)
        vshape = getattr(v, "shape", None) if v is not None else None
        if vshape is None or len(shp) != len(vshape) + 1:
            continue
        # only batch-majored vars (first dim -1, the fluid.data shape)
        # are unambiguous: a normal feed has exactly the var's rank, so
        # rank+1 can only mean a leading window dim. Vars declared with
        # a concrete full shape (raw create_var) commonly take feeds of
        # any rank through rank-polymorphic kernels — never windowed.
        if vshape[0] != -1:
            continue
        if shp[0] != n_steps:
            if n_steps == 1:
                # a plain run may legitimately feed extra-rank data to
                # rank-polymorphic ops — only an explicit multi-step
                # request makes the mismatch a user error
                continue
            raise ValueError(
                f"feed '{name}' has shape {tuple(shp)} — rank says it "
                f"carries a leading window dimension (program var rank "
                f"{len(vshape)}), but the window length {shp[0]} does not "
                f"match n_steps={n_steps}")
        if isinstance(data, LoDTensor) and data.lod():
            raise NotImplementedError(
                f"windowed feed '{name}' carries LoD — one LoD cannot "
                f"describe K stacked batches; feed dense windows or run "
                f"per-step (n_steps=1)")
        names.append(name)
    return tuple(names)


def _op_reads_host_values(op) -> bool:
    """Ops whose kernels read input VALUES host-side (registry
    host_inputs) cannot take those values as traced jit arguments."""
    info = _resolve_base_info(op.type)
    return bool(info is not None and info.host_inputs)


def _op_is_stateful(op) -> bool:
    info = _resolve_base_info(op.type)
    if info is None:
        return True  # unknown op: be safe, run eagerly (raises w/ context)
    return info.stateful


# control-flow ops the compiled path lowers to lax primitives instead of
# scope interpretation (see _CompiledBlock._exec_ops)
_LOWERED_CONTROL = frozenset({"while", "conditional_block",
                              "conditional_block_infer", "select_input"})


def _op_needs_rng(op_type: str) -> bool:
    info = _resolve_base_info(op_type)
    return info is not None and info.needs_rng


def _ops_compilable(ops, in_cond=False) -> bool:
    """True if every op either has a pure kernel or is control flow whose
    sub-blocks are themselves compilable. ``in_cond``: inside a
    conditional_block sub-block, where the compiled lowering traces BOTH
    branches and mask-merges — an rng op there would draw in the untaken
    branch too, so such programs route to the interpreter's
    single-branch semantics instead (reference
    conditional_block_op.cc executes only the taken branch)."""
    for op in ops:
        if op.type in ("feed", "fetch"):
            continue
        if op.type in _LOWERED_CONTROL:
            sub = op.attrs.get("sub_block")
            cond = in_cond or op.type.startswith("conditional_block")
            if sub is not None and not _ops_compilable(sub.ops, cond):
                return False
        elif _op_is_stateful(op) or _op_reads_host_values(op):
            return False
        elif in_cond and _op_needs_rng(op.type):
            return False
    return True


# ------------------------------------------------------------------ LoD
# LoD (variable-length sequence) metadata rides NEXT TO arrays as
# host-static nested tuples; under jit it is trace-time constant (the jit
# cache is keyed per feed-LoD bucket), so segment ids computed from it
# lower to XLA constants. Replaces the reference's per-step LoD InferShape
# (framework/lod_tensor.h:104, operator.cc:967).
def _normalize_lod(lod):
    if not lod:
        return None
    return tuple(tuple(int(x) for x in lvl) for lvl in lod)


def _op_scope(op) -> str:
    """`<phase>/<op type>`, the `jax.named_scope` a Fluid op is traced
    under: `bwd` and `opt` from the op_role bits `append_backward` and
    the optimizers stamp (backward.py OP_ROLE_*), `fwd` for everything
    else, the loss op (role 256) included."""
    role = int(op.attrs.get("op_role", 0) or 0)
    phase = "bwd" if role & 1 else "opt" if role & 2 else "fwd"
    return f"{phase}/{op.type}"


def _op_needs_lod(op) -> bool:
    info = _resolve_base_info(op.type)
    return info is not None and info.needs_lod


def _collect_in_lods(op, lookup):
    return {slot: [lookup(n) for n in names]
            for slot, names in op.inputs.items()}


def _propagate_lods(op, outs, in_lods, set_lod, get_len):
    """Apply kernel-declared output LoDs; else share the first lod-bearing
    input's LoD with outputs of matching leading length (reference ShareLoD
    default)."""
    explicit = None
    if isinstance(outs, dict):
        explicit = outs.pop("_lod", None)
    if explicit:
        for slot, levels_list in explicit.items():
            names = op.outputs.get(slot) or []
            for n, lv in zip(names, levels_list):
                set_lod(n, _normalize_lod(lv))
        return
    src = None
    for slot, lods in in_lods.items():
        for lv in lods:
            if lv:
                src = lv
                break
        if src:
            break
    if not src:
        return
    total = src[-1][-1]
    for slot, names in op.outputs.items():
        for n in names:
            if get_len(n) == total:
                set_lod(n, src)


def _classify_block_state(ops, block, feed_names, scope):
    """Classify a block's variables for a traced step: names read before
    any write that are initialized LoDTensors in the scope become *state*
    (threaded through the step and donated when overwritten); everything
    written (including sub-block writes) lands in *written*. Raises for
    data vars missing from the feed and for uninitialized persistables —
    the same contract for the fused and segmented compiled paths."""
    written: set = set()
    state_names: List[str] = []
    block_vars = block.vars
    for op in ops:
        for name in op.input_arg_names:
            if name in written or name in feed_names or name in state_names:
                continue
            bv = block_vars.get(name)
            if bv is not None and (bv.is_data or bv.need_check_feed):
                # a data var must come from the feed dict — pulling a
                # stale value from scope would silently compute on the
                # previous batch (reference: executor feed checks)
                raise KeyError(
                    f"feed variable '{name}' is required by the program "
                    f"but was not provided in feed=")
            v = scope.find_var(name)
            if v is not None and v.is_initialized() and isinstance(
                    v.value(), LoDTensor):
                state_names.append(name)
            elif bv is not None and bv.persistable:
                raise RuntimeError(
                    f"persistable variable '{name}' (read by op "
                    f"'{op.type}') is not initialized in the scope — "
                    f"run the startup program first")
        written.update(op.output_arg_names)
        sub = op.attrs.get("sub_block")
        if sub is not None:
            stack = [sub]
            while stack:
                b = stack.pop()
                for sop in b.ops:
                    written.update(sop.output_arg_names)
                    sb = sop.attrs.get("sub_block")
                    if sb is not None:
                        stack.append(sb)
    return state_names, written


_GUARD_ACTIONS = frozenset({"raise", "skip", "rollback"})


def _block_reads_amp_scale(ops, amp) -> bool:
    """True when the (feed/fetch-free) op list actually consumes the AMP
    loss-scaling var — i.e. the scaled-loss/unscale machinery survived
    into this program. A clone/prune that sliced it away (forward-only
    eval programs) must not run the scale epilogue: eval steps would
    silently inflate the shared training scale and counters."""
    name = amp["scale"]
    return any(name in op.input_arg_names for op in ops)


def _amp_scale_update(healthy, scale, good, bad, cfg):
    """Dynamic loss-scaling state transition (reference:
    operators/amp/update_loss_scaling_op.h Update<T>), fused into the
    step from the SAME health scalar the numeric fault guard computes —
    the scaler never re-reduces the grads:

      healthy: good+=1; bad=0; good==incr_every_n_steps -> scale*=incr
      tripped: bad+=1;  good=0; bad==decr_every_n_nan_or_inf -> scale*=decr
               (floored at 1.0 — the reference clamps the decayed scale
               so persistent overflow can't drive it to fp32 zero,
               where 0*incr == 0 sticks forever and the zeroed scaled
               loss would read as "healthy")

    All arrays are shape [1] (scale float, counters int32); ``healthy``
    is the scalar bool. Pure jnp, so the compiled path fuses it and the
    interpreter oracle runs the IDENTICAL arithmetic (bit-parity)."""
    good_i = good + 1
    bad_i = bad + 1
    incr_hit = good_i >= jnp.asarray(int(cfg["incr_every_n_steps"]),
                                     good.dtype)
    decr_hit = bad_i >= jnp.asarray(int(cfg["decr_every_n_nan_or_inf"]),
                                    bad.dtype)
    scale_good = jnp.where(incr_hit,
                           scale * jnp.asarray(cfg["incr_ratio"],
                                               scale.dtype), scale)
    scale_bad = jnp.where(decr_hit,
                          jnp.maximum(
                              scale * jnp.asarray(cfg["decr_ratio"],
                                                  scale.dtype),
                              jnp.asarray(1.0, scale.dtype)), scale)
    zero = jnp.zeros_like(good)
    new_scale = jnp.where(healthy, scale_good, scale_bad)
    new_good = jnp.where(healthy, jnp.where(incr_hit, zero, good_i), zero)
    new_bad = jnp.where(healthy, zero, jnp.where(decr_hit, zero, bad_i))
    return new_scale, new_good, new_bad


class _CompiledBlock:
    """One traced+jitted step function for (program, feeds, fetches)."""

    kind = "compiled"

    def __init__(self, program: Program, feed_names: Tuple[str, ...],
                 fetch_names: Tuple[str, ...], scope: Scope, seed: int,
                 mesh=None, param_shardings=None, feed_lods=None,
                 guard: bool = True):
        import weakref
        self._scope_ref = weakref.ref(scope)
        # trace-time-static LoD of feeds + initialized state vars
        self._init_lods: Dict[str, tuple] = dict(feed_lods or {})
        self.fetch_lods: List = [None] * len(fetch_names)
        self.mesh = mesh
        # name → PartitionSpec for tensor-parallel params (anything absent
        # is replicated); the optimizer state for a sharded param follows
        # the param's spec automatically when shapes match
        self.param_shardings = dict(param_shardings or {})
        self.program = program
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        block = program.global_block()
        ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
        self.ops = ops

        # classify variables: read-before-write & initialized in scope -> state
        state_names, written = _classify_block_state(ops, block, feed_names,
                                                     scope)
        self.written = written
        # state vars that get overwritten -> donated & written back
        self.mut_state = tuple(n for n in state_names if n in written)
        self.ro_state = tuple(n for n in state_names if n not in written)
        for n in state_names:
            lv = _normalize_lod(scope.find_var(n).get_tensor().lod())
            if lv:
                self._init_lods.setdefault(n, lv)
        # persistable outputs not in state (e.g. newly created opt moments
        # already initialized by startup → they are in state; anything else
        # persistable written gets written back too)
        persistable = {v.name for v in block.vars.values() if v.persistable}
        self.extra_writeback = tuple(
            n for n in written
            if n in persistable and n not in self.mut_state
            and n not in feed_names)
        self.seed = seed
        self._init_guard(program, scope, enabled=guard)
        # PipelineOptimizer-sectioned program + a mesh with a "pp" axis:
        # lower the homogeneous interior onto the compiled gpipe schedule
        # (fused fallback with a warning otherwise)
        self._pipeline_plan = None
        popt = getattr(program, "_pipeline_opt", None)
        if popt and mesh is not None and "pp" in mesh.axis_names:
            from .pipeline_lowering import build_plan
            self._pipeline_plan = build_plan(self, popt)
        # RecomputeOptimizer checkpoints → jax.checkpoint segments
        self._remat_plan = None
        ropt = getattr(program, "_recompute_opt", None)
        if ropt and self._pipeline_plan is None:
            from .recompute_lowering import build_plan as build_remat
            self._remat_plan = build_remat(self, ropt["checkpoints"])
        elif ropt and self._pipeline_plan is not None:
            import warnings as _warnings
            _warnings.warn(
                "program carries BOTH pipeline sections and recompute "
                "checkpoints; the pipelined schedule runs and the "
                "checkpoints are NOT rematerialized", stacklevel=2)
        self._jitted = jax.jit(self._step, donate_argnums=(0,))
        # (n_steps, windowed-feed names) → scanned jit; shape changes
        # within a key retrace inside jax.jit as usual
        self._multi_jit: Dict[Tuple[int, Tuple[str, ...]], Any] = {}
        # step telemetry (docs/OBSERVABILITY.md): first dispatch of the
        # single-step jit bumps executor_compiles_total{kind="step"}
        self._dispatched = False
        # state name → NamedSharding on ``mesh`` (_planned_sharding), and
        # how many state arrays the last _place_inputs call had to place
        self._placement_plan: Dict[str, Any] = {}
        self._placed = 0

    # ---------------------------------------------- numeric fault guard
    def _init_guard(self, program: Program, scope: Scope,
                    enabled: bool = True):
        """Capture the numeric-fault-plane config at build time (the
        guard is BAKED into the trace; the Executor's program cache is
        keyed by the flags, so flipping them rebuilds rather than
        retraces per step — docs/FAULT_TOLERANCE.md "Numeric faults").

          _guard_check  FLAGS_check_nan_inf at build
          _guard_action raise | skip | rollback
          _amp          program._amp_dynamic (AMP dynamic loss scaling
                        state names + hyperparams) or None
          _guard_select True when the step must keep its pre-step state
                        reachable for the fused bad-step discard (skip/
                        rollback, and always under AMP — an overflowed
                        step is dropped, its scale update applied)

        Under select, initialized extra-writeback persistables are
        promoted into mut_state so the discard covers EVERY persistable
        the step writes, and the AMP state vars join mut_state so the
        epilogue's scale/counter updates thread through the step (and
        ride the lax.scan carry on the windowed path)."""
        if not enabled:
            # build-time opt-out (the dygraph tape op: no post-step
            # host hook exists there, so a baked-in guard would revert
            # NaN steps with nobody reading the verdict) — skipped
            # BEFORE any classification side effect (mut-state
            # promotion, AMP var splicing, scale-var init checks)
            self._guard_check = False
            self._guard_action = "raise"
            self._amp = None
            self._guard_select = False
            self._guard_active = False
            self._select_names = ()
            self._health_names: Tuple[str, ...] = ()
            return
        self._guard_check = bool(core.globals_["FLAGS_check_nan_inf"])
        self._guard_action = str(core.globals_["FLAGS_nan_inf_action"])
        if self._guard_check and self._guard_action not in _GUARD_ACTIONS:
            # a typo'd action must not silently disable every policy
            # while the check flag still claims protection is on
            raise ValueError(
                f"FLAGS_nan_inf_action={self._guard_action!r} is not one "
                f"of {sorted(_GUARD_ACTIONS)}")
        self._amp = getattr(program, "_amp_dynamic", None)
        if self._amp is not None and not _block_reads_amp_scale(
                self.ops, self._amp):
            # a clone/prune sliced the scaled-loss machinery away (e.g.
            # an eval program pruned to a forward fetch) — the epilogue
            # must NOT keep mutating the shared scale/counters there
            self._amp = None
        # raise keeps the select too: the localizer re-runs the tripped
        # step through the interpreter and needs exactly the pre-step
        # state to reproduce it
        self._guard_select = (self._amp is not None
                              or (self._guard_check and self._guard_action
                                  in ("raise", "skip", "rollback")))
        self._guard_active = self._guard_check or self._amp is not None
        if not self._guard_active:
            self._select_names: Tuple[str, ...] = ()
            return

        def _scope_tensor_ok(n):
            return _initialized_tensor(scope, n) is not None

        if self._guard_select:
            promoted = tuple(n for n in self.extra_writeback
                             if _scope_tensor_ok(n))
            if promoted:
                self.mut_state = self.mut_state + promoted
                self.extra_writeback = tuple(
                    n for n in self.extra_writeback if n not in promoted)
        if self._amp is not None:
            for n in (self._amp["scale"], self._amp["good"],
                      self._amp["bad"]):
                if n in self.ro_state:
                    self.ro_state = tuple(x for x in self.ro_state
                                          if x != n)
                if n not in self.mut_state:
                    if not _scope_tensor_ok(n):
                        raise RuntimeError(
                            f"AMP dynamic loss scaling var '{n}' is not "
                            f"initialized in the scope — run the startup "
                            f"program first")
                    self.mut_state = self.mut_state + (n,)
        # the bad-step discard covers exactly the state the step
        # overwrites; the AMP vars are epilogue-managed (never reverted
        # — a dropped step still updates the scale)
        amp_names = (set() if self._amp is None else
                     {self._amp["scale"], self._amp["good"],
                      self._amp["bad"]})
        self._select_names = tuple(
            n for n in self.mut_state
            if n in self.written and n not in amp_names)
        # health reduces over the PARAM GRADIENTS (+ float fetches), not
        # the updated params: finite grads into a finite optimizer step
        # keep params finite, and the health scalar is then available
        # BEFORE the update ops at the XLA level — no reduction barrier
        # on the new state (reducing the updated params measured 37%
        # lane overhead; the grad-sourced reduce itself measures ~0%,
        # every remaining cost is the discard select — CPU,
        # builder-run, not recorded). Param grads subsume activation
        # grads (chain rule drags any upstream NaN into them), and
        # skipping the batch-sized activation-grad reductions measured
        # ~9% of the lane back. Blocks with no param grads fall back to
        # all grads, then to the written state itself (inference/eval).
        grads = {n for n in self.written if n.endswith(GRAD_SUFFIX)}
        self._health_names = tuple(
            n + GRAD_SUFFIX for n in self._select_names
            if n + GRAD_SUFFIX in grads) or tuple(sorted(grads))

    def _warn_unselectable(self, name, old, new):
        """A state var whose SHAPE changed during the step cannot be
        selected back — on a tripped step it keeps its (possibly
        non-finite) post-step value while everything else reverts. That
        hole in the discard must be loud, once per var: a NaN parked
        there re-trips every following step and burns the rollback
        budget on what looked like a transient fault."""
        import warnings as _warnings
        warned = getattr(self, "_warned_unselectable", None)
        if warned is None:
            warned = self._warned_unselectable = set()
        if name in warned:
            return
        warned.add(name)
        _warnings.warn(
            f"numeric fault guard: state var '{name}' changes shape "
            f"during the step ({getattr(old, 'shape', None)} -> "
            f"{getattr(new, 'shape', None)}) and CANNOT be covered by "
            f"the bad-step discard — on a tripped step it keeps its "
            f"post-step value", stacklevel=3)

    def _guard_epilogue(self, orig_mut, new_mut, fetches, env):
        """Fused guard tail of one traced step: the single health
        scalar (over grads + float fetches — see _init_guard), the
        bad-step discard (select back to the pre-step state), and the
        AMP scale transition — all device-side, zero host round-trips.
        Returns (new_mut, health)."""
        from .ir import fused_health
        vals = [env[n] for n in self._health_names if n in env]
        if not vals:  # no grads in this block: reduce the state writes
            vals = [new_mut[n] for n in self._select_names
                    if n in new_mut]
        vals = vals + list(fetches)
        health = fused_health(vals)
        return self._apply_discard(new_mut, orig_mut, health), health

    def _apply_discard(self, store, orig, health):
        """The fused bad-step discard (select back to the pre-step
        state, shape-mismatch vars warned once) + the AMP scale
        transition, over one name→array mapping — ``new_mut`` for the
        fused epilogue, ``env`` for the segmented step. ONE
        implementation, so the paths whose bit-parity the design
        depends on cannot drift apart."""
        if self._guard_select:
            for n in self._select_names:
                new, old = store.get(n), orig.get(n)
                if new is None or old is None or new is old:
                    continue
                if getattr(new, "shape", None) == getattr(old, "shape",
                                                          None):
                    store[n] = jnp.where(health, new, old)
                else:
                    self._warn_unselectable(n, old, new)
        if self._amp is not None:
            a = self._amp
            olds = (store[a["scale"]], store[a["good"]], store[a["bad"]])
            news = _amp_scale_update(health, *olds, a)
            if self._guard_check and self._guard_action == "raise":
                # raise mode replays the tripped step through the
                # interpreter localizer from its exact pre-step state —
                # INCLUDING the loss scale: letting the decay land first
                # would shrink loss*scale on the replay, the overflow
                # would not reproduce, and the localizer would mis-report
                # "the fault did not replay". The scale vars are
                # epilogue-managed (step ops only read them), so the
                # pre-transition values ARE the pre-step values.
                news = tuple(jnp.where(health, nv, ov)
                             for nv, ov in zip(news, olds))
            store[a["scale"]], store[a["good"]], store[a["bad"]] = news
        return store

    def _step(self, mut_state: Dict[str, Any], ro_state: Dict[str, Any],
              feeds: Dict[str, Any], rng):
        # the pre-step state refs stay reachable for the guard's fused
        # bad-step discard (jax arrays are immutable; XLA resolves the
        # donation aliasing)
        orig_mut = dict(mut_state) if self._guard_select else None
        env: Dict[str, Any] = {}
        env.update(ro_state)
        env.update(mut_state)
        env.update(feeds)
        lod_env: Dict[str, tuple] = dict(self._init_lods)
        from ..ops.pallas.flash_attention import mesh_guard
        kept = 0
        with mesh_guard(self.mesh):  # Mosaic kernels partition themselves
            if self._pipeline_plan is not None:
                from .pipeline_lowering import exec_plan
                exec_plan(self, self._pipeline_plan, env, lod_env, rng)
            elif self._remat_plan is not None:
                from .recompute_lowering import exec_plan as exec_remat
                kept = exec_remat(self, self._remat_plan, env, lod_env, rng)
            else:
                self._exec_ops(self.ops, env, lod_env, rng)
        _telemetry.REGISTRY.gauge(
            "remat_kept_bytes",
            "bytes of the values the traced step's recomputed segments "
            "keep by name (recompute_lowering.KEPT) instead of recomputing "
            "them, summed over segments; 0 without checkpoints").set(kept)
        fetches = []
        for i, n in enumerate(self.fetch_names):
            if n not in env:
                raise KeyError(f"fetch var '{n}' not produced by program")
            fetches.append(env[n])
            self.fetch_lods[i] = lod_env.get(n)
        new_mut = {n: env[n] for n in self.mut_state}
        extra = {n: env[n] for n in self.extra_writeback if n in env}
        health = jnp.bool_(True)
        if self._guard_active:
            new_mut, health = self._guard_epilogue(orig_mut, new_mut,
                                                   fetches, env)
        return fetches, new_mut, extra, health

    # -------------------------------------------------- control-flow lowering
    # The reference interprets while/conditional_block by re-entering the
    # scope-based executor on the sub-block (while_op.cc,
    # conditional_block_op.cc). Compiled lowering instead: conditional
    # branches trace unconditionally and merge at select_input (on TPU a
    # vectorized select is the idiomatic lowering — lax.cond frequently
    # becomes a select anyway), and `while` becomes lax.while_loop with the
    # loop-carried names as the carry dict.
    def _exec_while(self, op, env, lod_env, rng):
        import jax.lax as lax
        sub = op.attrs["sub_block"]
        cond_name = op.inputs["Condition"][0]
        x_names = list(op.inputs.get("X", []))
        written = set()
        for sop in sub.ops:
            written.update(sop.output_arg_names)
        out_names = [n for n in op.outputs.get("Out", []) if n in env]
        carry_names = sorted({cond_name}
                             | set(out_names)
                             | {n for n in x_names
                                if n in written and n in env})
        missing = [n for n in carry_names if n not in env]
        if missing:
            raise KeyError(
                f"while op reads undefined vars {missing} — outer program "
                f"did not produce them")
        base_env = dict(env)
        sub_ops = sub.ops
        _IT = "@while_iter@"  # loop counter so per-iteration RNG differs

        def cond_fn(carry):
            return jnp.reshape(carry[cond_name], ()).astype(bool)

        def body_fn(carry):
            e = dict(base_env)
            it = carry[_IT]
            e.update({n: v for n, v in carry.items() if n != _IT})
            le = dict(lod_env)
            self._exec_ops(sub_ops, e, le, jax.random.fold_in(rng, it))
            out = {n: e[n] for n in carry_names}
            out[_IT] = it + 1
            return out

        init = {n: env[n] for n in carry_names}
        init[_IT] = jnp.zeros((), jnp.int32)
        final = lax.while_loop(cond_fn, body_fn, init)
        final.pop(_IT, None)
        env.update(final)

    def _exec_ops(self, ops, env, lod_env, rng, idx0=0):
        # ``idx0``: global index of ops[0] in the block's (feed/fetch-free)
        # op list — per-op rng keys fold from GLOBAL indices so a segmented
        # run draws the same streams as the fused compiled run would
        for local_idx, op in enumerate(ops):
            # trace-time metadata only: every HLO instruction the op
            # lowers to (and the fusion it becomes the root of) carries
            # `<phase>/<op type>` in its op_name, which is how a device
            # trace is read by Fluid op (docs/OBSERVABILITY.md)
            with jax.named_scope(_op_scope(op)):
                self._exec_op(op, idx0 + local_idx, env, lod_env, rng)

    def _exec_op(self, op, idx, env, lod_env, rng):
        otype = op.type
        if otype == "while":
            self._exec_while(op, env, lod_env, rng)
            return
        if otype in ("conditional_block", "conditional_block_infer"):
            # Trace the branch unconditionally on an env COPY (both-
            # branch compute = TPU select idiom), then mask-merge any
            # write to a pre-existing outer var so the untaken branch
            # cannot clobber state; fresh vars flow through for
            # select_input to pick.
            branch_env = dict(env)
            self._exec_ops(op.attrs["sub_block"].ops, branch_env,
                           lod_env, rng)
            cnames = op.inputs.get("Cond") or []
            mask = (jnp.reshape(env[cnames[0]], ()) != 0) \
                if cnames and cnames[0] in env else None
            for n, v in branch_env.items():
                old = env.get(n)
                if old is v:
                    continue
                if old is None or mask is None:
                    env[n] = v
                elif getattr(old, "shape", None) == getattr(v, "shape",
                                                            None):
                    env[n] = jnp.where(mask, v, old)
                else:
                    raise NotImplementedError(
                        f"conditional_block branch changes the shape of "
                        f"outer var '{n}' ({getattr(old, 'shape', None)}"
                        f" -> {getattr(v, 'shape', None)}); conditional "
                        f"shape-changing writes cannot be compiled — "
                        f"produce a new variable instead")
            return
        if otype == "select_input":
            mask = jnp.reshape(env[op.inputs["Mask"][0]], ()) != 0
            xf = env.get(op.inputs["X"][0])
            xt = env.get(op.inputs["X"][1])
            if xf is None or xt is None:
                picked = xt if xf is None else xf
            elif xt.shape == xf.shape:
                picked = jnp.where(mask, xt, xf)
            else:
                raise NotImplementedError(
                    f"cond branches produce different shapes "
                    f"({xt.shape} vs {xf.shape}) for the same output — "
                    f"XLA needs matching branch shapes; pad or "
                    f"restructure the branches")
            env[op.outputs["Out"][0]] = picked
            return
        ins = {}
        for slot, names in op.inputs.items():
            ins[slot] = [env.get(n) for n in names]
        attrs = op.attrs
        in_lods = _collect_in_lods(op, lod_env.get)
        if _op_needs_lod(op):
            attrs = dict(attrs)
            attrs["_lod"] = in_lods
        outs = run_op(otype, ins, attrs, op.outputs, op.inputs, rng, idx)
        for slot, names in op.outputs.items():
            vals = outs.get(slot)
            if vals is None:
                continue
            for n, v in zip(names, vals):
                if v is not None and n != "@EMPTY@":
                    env[n] = v
        _propagate_lods(
            op, outs, in_lods,
            lod_env.__setitem__,
            lambda n: (env[n].shape[0] if n in env and
                       getattr(env[n], "ndim", 0) else None))

    def _place_inputs(self, scope: Scope, feeds: Dict[str, Any], rng,
                      window_names=()):
        """State from the scope + feeds, as the step takes them. Shared by
        run() and by HLO-inspection helpers (lowered()).

        On a mesh each state name has ONE sharding for the life of the
        block (``_planned_sharding``: the placement plan, filled the first
        time the block places its inputs). A state array that already
        lies on the mesh the way the plan says — what the jitted step
        handed back the step before — is passed through as the object it
        is: nothing is moved and no ``device_put`` is called. Anything
        else (the start-up program's single-device arrays on step 1, a
        numpy value a checkpoint load or a ``set_value`` put in the
        scope) is placed to the plan's sharding, and counted: the
        `exe:place` span's ``placed``, the registry's
        ``executor_state_arrays_placed_total``. State the step overwrites
        comes back placed through its write-back; read-only state, which
        no step writes back, is left in the scope as placed. In a steady
        step only the feeds and the rng key are left to place.

        Feeds named in ``window_names`` are [K, batch, ...] window STACKS:
        their batch dim is dim 1, so the mesh placement shards THAT dim
        over "dp" and leaves the window dim whole for the scan (one
        device_put per window — docs/INPUT_PIPELINE.md)."""
        mut = {n: scope.find_var(n).get_tensor().array for n in self.mut_state}
        ro = {n: scope.find_var(n).get_tensor().array for n in self.ro_state}
        self._placed = 0
        if self.mesh is None and not self._dispatched:
            # one signature for the first step and those after it. A
            # start-up program takes no feed, so it leaves its state
            # uncommitted; a step hands the state it overwrites back
            # committed (its feeds are), and jit keys on that. Without
            # this the second step is a second trace, lowering and
            # compile of the same computation, and a second entry in
            # the compile cache. New handles on the same device
            # buffers: nothing is copied. Read-only state stays as the
            # scope holds it, which is what every later step is given.
            mut = {n: _committed(a) for n, a in mut.items()}
        if self.mesh is not None:
            # data-parallel placement: params/state replicated, feed batch
            # sharded on the dp axis. XLA's sharding propagation inserts the
            # grad all-reduces over ICI (replaces reference allreduce
            # op-handles — multi_devices_graph_pass.cc:604).
            from ..parallel.mesh import replicated, shard_feed
            multiproc = jax.process_count() > 1

            def place(n, a):
                sh = self._planned_sharding(n, a)
                # already right, or already global (no one process could
                # place it again): what the step wrote back. Equivalence
                # decides: XLA may spell an equal sharding otherwise;
                # `==` first because it is the cheaper yes
                if isinstance(a, jax.Array) and (
                        a.sharding == sh
                        or a.sharding.is_equivalent_to(sh, a.ndim)
                        or not a.is_fully_addressable):
                    return a
                self._placed += 1
                if multiproc:
                    # device_put can't target non-addressable devices; every
                    # process holds the full value (startup ran identically
                    # on all ranks), so assemble the global array from the
                    # process-local copy. global_shape MUST be passed: it is
                    # the documented "data is identical across hosts" mode —
                    # without it a cross-process sharded dim would be
                    # inferred as local_size × process_slices (2× too big)
                    host = np.asarray(a)
                    return jax.make_array_from_process_local_data(
                        sh, host, global_shape=host.shape)
                return jax.device_put(a, sh)
            mut = {n: place(n, a) for n, a in mut.items()}
            for n, a in ro.items():
                ro[n] = placed = place(n, a)
                if placed is not a:
                    # no step writes read-only state back: leave the
                    # placed array in the scope, or every step places
                    # it again (the learning rate; a forward program's
                    # every parameter)
                    var = scope.find_var(n)
                    var.set_value(LoDTensor(placed, var.get_tensor().lod()))
            if self._placed:
                _telemetry.count_state_placed(self._placed)
            feeds = {n: shard_feed(self.mesh, n, a,
                                   window=n in window_names)
                     for n, a in feeds.items()}
            if not multiproc:
                # multi-process: leave the key uncommitted — identical on
                # every rank, jit replicates it (key arrays can't go
                # through make_array_from_process_local_data)
                rng = jax.device_put(rng, replicated(self.mesh))
        return mut, ro, feeds, rng

    def _planned_sharding(self, name: str, a):
        """The placement plan's entry for a state name: its
        ``NamedSharding`` on the block's mesh (``_sharding_for``'s spec,
        else replicated), decided the first time the name is placed —
        the accumulator rule needs the array's ``ndim`` — and kept."""
        sh = self._placement_plan.get(name)
        if sh is None:
            from jax.sharding import NamedSharding
            from ..parallel.mesh import replicated
            spec = self._sharding_for(name, a)
            sh = self._placement_plan[name] = (
                replicated(self.mesh) if spec is None
                else NamedSharding(self.mesh, spec))
        return sh

    def lowered(self, scope: Scope, feeds: Dict[str, Any], rng):
        """jax lowering of the single-step function over the CURRENT scope
        state — ``.compile().as_text()`` is the optimized HLO the step
        actually runs (donated aliases, collectives, fusions). Used by
        tests/test_ir_passes.py to EVIDENCE the absorbed-pass claims."""
        mut, ro, feeds, rng = self._place_inputs(scope, feeds, rng)
        return self._jitted.lower(mut, ro, feeds, rng)

    @contextlib.contextmanager
    def place_span(self):
        """The `exe:place` stage span: around gathering the state arrays
        from the scope for a dispatch. ``arrays``: how many the step
        takes; ``placed``: how many of them `_place_inputs` had to put on
        the mesh in this call (0 off a mesh, and on one in a steady
        step)."""
        from . import profiler as _profiler
        with _profiler.RecordEvent("exe:place", cat="executor") as span:
            yield
            span.args = {"arrays": len(self.mut_state) + len(self.ro_state),
                         "placed": self._placed}

    def run(self, scope: Scope, feeds: Dict[str, Any], rng):
        """One training/inference step: ONE dispatch of the jitted step.
        Returns (fetches, health) — health is the step's fused finite
        scalar (constant True when the guard is off), LAZY on device so
        the happy path costs no host sync."""
        with self.place_span():
            placed = self._place_inputs(scope, feeds, rng)
        return self.run_placed(scope, placed)

    def run_placed(self, scope: Scope, placed):
        """`run` from `_place_inputs`' result on: dispatch, write back."""
        from . import profiler as _profiler
        mut, ro, feeds, rng = placed
        first = not self._dispatched
        if first:
            self._dispatched = True
            _telemetry.count_compile("step")
        # the whole program is ONE dispatch on TPU — a single span
        # (per-op timing lives in the device trace, by the named scopes
        # of _exec_ops). The first dispatch additionally carries a
        # cat="compile" span: that is where jax traces+compiles the step
        # (the jax.monitoring listener records the exact trace, lower
        # and compile durations inside it). ONE call site whether or not
        # anything records: a Pallas kernel carries the Python stack it
        # was traced under into the compile-cache key.
        with _profiler.RecordEvent("compiled_step", cat="executor"), \
                (_profiler.RecordEvent("compile:step", cat="compile")
                 if first else contextlib.nullcontext()):
            fetches, new_mut, extra, health = self._jitted(mut, ro, feeds,
                                                           rng)
            if _profiler.is_session():
                # a start_profiler() session reports a step until the
                # device is done (the reference-style table); nothing
                # else pays the sync — not shard streaming, not a
                # jax.profiler trace someone else started
                jax.block_until_ready(fetches)
        with _profiler.RecordEvent("exe:write_back", cat="executor"):
            self._write_back(scope, new_mut, extra)
            # the donated inputs are dead buffers now and the scope has
            # let go of them: drop the last ~1000 references here, so
            # that freeing them is timed as the hand-back it is and not
            # left to whenever the caller's frame dies
            mut.clear()
        return fetches, health

    def run_window(self, scope: Scope, feeds: Dict[str, Any], rng_base,
                   idx0: int, n_steps: int, window_names=()):
        """``n_steps`` as ONE dispatched lax.scan window. Feeds named in
        ``window_names`` carry a leading [n_steps, ...] dim of *distinct*
        batches consumed one slice per step (scan xs); every other feed
        broadcasts to all steps (the degenerate same-feeds mode — the
        pre-window benchmark shape). Per-dispatch host costs amortize to
        one dispatch per window. Fetches
        come back stacked [n_steps, ...], and so does the per-step
        health flag ([n_steps] bool; the guard rides the scan carry —
        a bad step's discard selects against THAT step's carry-in, so
        step i+1 of a faulted window continues from step i's pre-fault
        state)."""
        from . import profiler as _profiler
        with self.place_span():
            mut, ro, feeds, rng_base = self._place_inputs(
                scope, feeds, rng_base, window_names=window_names)
        tag = "realdata" if window_names else "broadcast"
        with _profiler.RecordEvent(f"window[{n_steps}]:{tag}",
                                   cat="window"):
            fetches, new_mut, extra, health = self._run_multi(
                mut, ro, feeds, rng_base, idx0, n_steps, window_names)
            if _profiler.is_session():
                jax.block_until_ready(fetches)
        with _profiler.RecordEvent("exe:write_back", cat="executor"):
            self._write_back(scope, new_mut, extra)
        return fetches, health

    def _write_back(self, scope, new_mut, extra):
        for n, v in {**new_mut, **extra}.items():
            scope.var(n).set_value(LoDTensor(v))

    def _run_multi(self, mut, ro, feeds, rng_base, idx0, n_steps,
                   window_names):
        """The scanned window body. ``rng_base`` is the UNfolded program
        key and ``idx0`` the global step index of the window's first
        step: per-step keys fold by global index (idx0 + i), which are
        EXACTLY the keys ``n_steps`` sequential single-step run() calls
        would draw — windowed and per-step training see identical rng
        streams. Programs with extra-writeback vars fall back to a
        per-step dispatch loop with the same stacked-fetch contract.
        LoD-carrying fetches are refused: a single-step LoD cannot
        describe a stacked [n_steps, ...] dim."""
        self._check_no_lod_fetch()
        xs = {n: feeds[n] for n in window_names}
        bcast = {n: v for n, v in feeds.items() if n not in window_names}
        if not self.extra_writeback:
            key = (n_steps, tuple(sorted(window_names)))
            jitted = self._multi_jit.get(key)
            fresh = jitted is None
            if fresh:
                # a miss AFTER warm-up is a retrace (a new window/bucket
                # signature appeared late) — the scrapeable form of the
                # serving plane's no-recompile claim
                _telemetry.count_compile(
                    "window", retrace=bool(self._multi_jit))
                from jax import lax

                def many(mut, ro, bcast, xs, rng_b, i0):
                    def body(mut_c, x):
                        i, sl = x
                        f = dict(bcast)
                        f.update(sl)
                        fetches, new_mut, _, health = self._step(
                            mut_c, ro, f, jax.random.fold_in(rng_b, i))
                        return new_mut, (fetches, health)
                    new_mut, (ys, healths) = lax.scan(
                        body, mut, (i0 + jnp.arange(n_steps), xs))
                    return ys, new_mut, healths
                jitted = jax.jit(many, donate_argnums=(0,))
                self._multi_jit[key] = jitted
            from . import profiler as _profiler
            with (_profiler.RecordEvent(
                    f"compile:window[{n_steps}]", cat="compile",
                    args={"n_steps": int(n_steps)})
                  if fresh else contextlib.nullcontext()):
                ys, new_mut, healths = jitted(mut, ro, bcast, xs,
                                              rng_base, jnp.int32(idx0))
            self._check_no_lod_fetch()  # lods appear during the trace
            return ys, new_mut, {}, healths
        per_step = []
        step_health = []
        extra = {}
        for i in range(n_steps):
            f = dict(bcast)
            for n, a in xs.items():
                f[n] = a[i]
            fetches, mut, extra, health = self._jitted(
                mut, ro, f, jax.random.fold_in(rng_base, idx0 + i))
            per_step.append(fetches)
            step_health.append(health)
        self._check_no_lod_fetch()
        stacked = [jnp.stack([s[k] for s in per_step])
                   for k in range(len(self.fetch_names))]
        return stacked, mut, extra, jnp.stack(step_health)

    def _check_no_lod_fetch(self):
        if any(l is not None for l in self.fetch_lods):
            raise NotImplementedError(
                "n_steps > 1 cannot stack LoD-carrying fetches — fetch "
                "dense vars or run per-step (n_steps=1)")

    def _sharding_for(self, name: str, a):
        """TP spec for a state var: exact param match, or an optimizer
        accumulator named '<param>_<acc>' with the param's shape."""
        spec = self.param_shardings.get(name)
        if spec is not None:
            return spec
        for pname, pspec in self.param_shardings.items():
            if name.startswith(pname + "_"):
                try:
                    ndim = len(pspec)
                except TypeError:
                    return None
                if hasattr(a, "ndim") and a.ndim == ndim:
                    return pspec
        return None


class _NotSegmentable(Exception):
    """Raised at build time when a block gains nothing from segmentation
    (no/too-few compilable ops) — the caller falls back to the pure
    interpreter quietly."""


def _effective_reads(op) -> List[str]:
    """Names an op may read, including through its sub-blocks (an island
    while/conditional re-enters the eager executor on the sub-block, whose
    ops read the scope directly)."""
    names = list(op.input_arg_names)
    stack = [op.attrs.get("sub_block")]
    while stack:
        b = stack.pop()
        if b is None:
            continue
        for sop in b.ops:
            names.extend(sop.input_arg_names)
            stack.append(sop.attrs.get("sub_block"))
    return names


def _effective_writes(op) -> List[str]:
    names = list(op.output_arg_names)
    stack = [op.attrs.get("sub_block")]
    while stack:
        b = stack.pop()
        if b is None:
            continue
        for sop in b.ops:
            names.extend(sop.output_arg_names)
            stack.append(sop.attrs.get("sub_block"))
    return names


class _SegmentedBlock(_CompiledBlock):
    """Segmented compilation: the block's op list partitioned into maximal
    pure runs — each traced+jitted as its own donated step — separated by
    stateful/host-op *islands* the interpreter dispatches eagerly.

    Kills the whole-block interpreter cliff: before this, ONE stateful op
    (auc, print, read, ...) among hundreds routed the ENTIRE block to
    op-by-op interpretation with per-op host sync (`_ops_compilable` at
    the top of Executor.run is all-or-nothing). The reference pays per-op
    dispatch everywhere by design (executor.cc:469-475); this build pays
    it only at the islands — fwd+bwd+optimizer stay fused XLA
    computations.

    Env handoff contract: one step threads a host-side ``env`` dict of
    DEVICE arrays through the segments in program order. Compiled segments
    consume/produce env entries through their jitted functions (state they
    overwrite is donated, exactly like the fused path); islands read env
    values pushed into the scope (a LoDTensor wrap of the device array —
    no host copy; only values the island actually reads are pushed) and
    their scope writes are pulled back into env. Values cross segment
    boundaries on device — the only host syncs are the ones island kernels
    themselves perform (e.g. auc's histogram update).

    Inherits the op tracing/lowering machinery from _CompiledBlock; the
    whole-step jit, pipeline/remat plans and multi-step scan are replaced
    by the per-segment plan (islands have per-step side effects, so
    multi-step windows run as a host loop in Executor.run)."""

    kind = "segmented"

    def __init__(self, program: Program, feed_names: Tuple[str, ...],
                 fetch_names: Tuple[str, ...], scope: Scope, seed: int,
                 feed_lods=None, seg_min_ops: Optional[int] = None):
        from .ir import analyze_block_segments
        self._scope_ref = weakref.ref(scope)
        self._init_lods: Dict[str, tuple] = dict(feed_lods or {})
        self.fetch_lods: List = [None] * len(fetch_names)
        self.mesh = None
        self.param_shardings = {}
        self.program = program
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        block = program.global_block()
        ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
        self.ops = ops
        self.seed = seed
        self._pipeline_plan = None
        self._remat_plan = None

        self.segments = analyze_block_segments(ops)
        n_compilable = sum(len(s.ops) for s in self.segments
                           if s.kind == "compiled")
        if seg_min_ops is None:
            seg_min_ops = core.globals_["FLAGS_executor_seg_min_ops"]
        if n_compilable < seg_min_ops:
            raise _NotSegmentable(
                f"only {n_compilable} compilable ops (< "
                f"FLAGS_executor_seg_min_ops)")

        state_names, written = _classify_block_state(ops, block, feed_names,
                                                     scope)
        self.written = written
        self.mut_state = tuple(n for n in state_names if n in written)
        self.ro_state = tuple(n for n in state_names if n not in written)
        for n in state_names:
            lv = _normalize_lod(scope.find_var(n).get_tensor().lod())
            if lv:
                self._init_lods.setdefault(n, lv)
        persistable = {v.name for v in block.vars.values() if v.persistable}
        self.extra_writeback = tuple(
            n for n in written
            if n in persistable and n not in self.mut_state
            and n not in feed_names)
        self._init_guard(program, scope)

        # ---- per-segment dataflow: external reads / writes -------------
        seg_reads: List[List[str]] = []
        seg_writes: List[set] = []
        for seg in self.segments:
            reads: List[str] = []
            written_in: set = set()
            op_io = []
            for op in seg.ops:
                r, w = _effective_reads(op), _effective_writes(op)
                op_io.append((op, r, w))
                for n in r:
                    if n not in written_in and n not in reads:
                        reads.append(n)
                written_in.update(w)
            seg_reads.append(reads)
            seg_writes.append(written_in)
            if seg.kind == "island":
                # static per-op read/write lists: the island dispatch
                # pushes/pulls these every step — don't re-walk sub-block
                # trees on the hot path
                seg.op_io = op_io

        # fetch names must be resolvable BEFORE anything runs: a compiled
        # segment may donate state buffers, so failing at fetch-collection
        # time (the interpreter's behavior) would leave the scope pointing
        # at deleted arrays
        producible = set()
        for w in seg_writes:
            producible |= w
        for n in fetch_names:
            if n not in producible and n not in state_names \
                    and n not in feed_names and scope.find_var(n) is None:
                raise KeyError(f"fetch var '{n}' not produced by program")

        # liveness: a compiled segment only returns what someone later
        # needs (later segments/islands, the fetch list, state/persistable
        # writeback); state it overwrites is donated — whole-state
        # donation, segment by segment
        need_at_end = (set(fetch_names) | set(self.mut_state)
                       | set(self.extra_writeback))
        donatable = set(self.mut_state)
        for i, seg in enumerate(self.segments):
            if seg.kind != "compiled":
                continue
            later_reads: set = set()
            for r in seg_reads[i + 1:]:
                later_reads.update(r)
            seg.out_names = tuple(sorted(
                n for n in seg_writes[i]
                if n in later_reads or n in need_at_end))
            seg.donated_names = tuple(sorted(
                n for n in seg_reads[i]
                if n in donatable and n in seg_writes[i]))
            seg.in_names = tuple(sorted(
                set(seg_reads[i]) - set(seg.donated_names)))
            if self._guard_select:
                # the fused bad-step discard needs the step's pre-state
                # refs alive until the select at the end of run_step —
                # per-segment donation would delete them mid-step
                seg.in_names = tuple(sorted(
                    set(seg.in_names) | set(seg.donated_names)))
                seg.donated_names = ()
            seg.guard_names = ()
            seg._cache = {}  # lod-key -> [jitted step, captured out lods]

    # -------------------------------------------------------------- step
    def _seg_dispatch(self, seg, env, lod_env, rng):
        """Run one compiled segment: jit-cache keyed by the LoD of its
        inputs (trace-time-static, same contract as the fused path's
        feed-LoD-keyed program cache). When the numeric fault guard is
        on, a per-segment finite check over the segment's float outputs
        is FUSED into the jitted step and returned as one extra bool —
        run_step ANDs the flags into the step health with no host sync.
        Returns (outs, health_flag_or_None)."""
        from .ir import fused_health, guarded_float_names
        in_all = seg.in_names + seg.donated_names
        lkey = tuple((n, lod_env[n]) for n in in_all if n in lod_env)
        entry = seg._cache.get(lkey)
        first = entry is None
        if first:
            # a new LoD key on a warm segment cache IS a retrace
            _telemetry.count_compile("segment",
                                     retrace=bool(seg._cache))
            static_lods = dict(lkey)
            captured: Dict[str, Any] = {}
            seg_ops, start, out_names = seg.ops, seg.start, seg.out_names
            guard = self._guard_active

            def step(donated, held, rng_):
                e = dict(held)
                e.update(donated)
                le = dict(static_lods)
                self._exec_ops(seg_ops, e, le, rng_, idx0=start)
                captured.clear()
                captured.update({n: le[n] for n in out_names if n in le})
                res = {n: e[n] for n in out_names if n in e}
                if not guard:
                    return res, jnp.bool_(True)
                seg.guard_names = tuple(guarded_float_names(out_names, e))
                return res, fused_health(
                    [e[n] for n in seg.guard_names])

            entry = seg._cache[lkey] = [
                jax.jit(step, donate_argnums=(0,)), captured]
        jitted, captured = entry
        donated = {n: env[n] for n in seg.donated_names if n in env}
        held = {n: env[n] for n in seg.in_names if n in env}
        from . import profiler as _profiler
        tag = "compile" if first else "exec"
        with _profiler.RecordEvent(
                f"segment[{seg.start}:{seg.stop}]:{tag}", cat="segment"):
            outs, seg_health = jitted(donated, held, rng)
            if _profiler.is_session():
                jax.block_until_ready(outs)
        env.update(outs)
        for n, lv in captured.items():
            if lv:
                lod_env[n] = lv
        return outs, (seg_health if self._guard_active else None)

    def _island_dispatch(self, seg, env, lod_env, rng, scope, executor,
                         profiling):
        """Run one island through the eager interpreter: push the env
        values the island reads into the scope (device-array wrap, no host
        copy), dispatch each op, pull its writes back into env."""
        ctx = None
        if profiling:
            from . import profiler as _profiler
            ctx = _profiler.RecordEvent(
                f"island[{seg.start}:{seg.stop}]:"
                + ",".join(sorted({o.type for o in seg.ops})),
                cat="segment")
            ctx.__enter__()
        try:
            for off, (op, op_reads, op_writes) in enumerate(seg.op_io):
                for n in op_reads:
                    if n in env:
                        scope.var(n).set_value(
                            LoDTensor(env[n], lod_env.get(n)))
                executor._run_op_eager(op, scope, rng, seg.start + off)
                for n in op_writes:
                    v = scope.find_var(n)
                    if v is None or not v.is_initialized():
                        continue
                    val = v.value()
                    if isinstance(val, LoDTensor) and val.array is not None:
                        env[n] = val.array
                        lv = _normalize_lod(val.lod())
                        if lv:
                            lod_env[n] = lv
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)

    def run_step(self, scope: Scope, feeds: Dict[str, Any], rng, executor):
        """One training/inference step through the segment plan. Returns
        (fetch arrays, fetch lods, health). Health is the AND of every
        compiled segment's fused finite flag, the islands' written float
        env values, and the float fetches — all device-side, so the
        happy path stays sync-free. Under a select action (skip/
        rollback/AMP) a tripped step's state writes select back to
        their pre-step values; island-INTERNAL side effects (an auc
        histogram, a print) cannot be unwound and are documented as
        out of the discard's reach."""
        from . import profiler as _profiler
        from .ir import fused_health
        profiling = _profiler.is_profiling()
        env: Dict[str, Any] = {}
        for n in self.ro_state + self.mut_state:
            env[n] = scope.find_var(n).get_tensor().array
        env.update(feeds)
        orig = ({n: env[n] for n in self._select_names if n in env}
                if self._guard_select else None)
        lod_env: Dict[str, tuple] = dict(self._init_lods)
        n_comp = sum(1 for s in self.segments if s.kind == "compiled")
        seg_flags: List[Tuple[str, Any]] = []  # (segment label, bool flag)
        try:
            with _profiler.RecordEvent(
                    f"segmented_step[{n_comp}c/"
                    f"{len(self.segments) - n_comp}i]", cat="segment") \
                    if profiling else contextlib.nullcontext():
                for seg in self.segments:
                    if seg.kind == "compiled":
                        _outs, flag = self._seg_dispatch(
                            seg, env, lod_env, rng)
                        if flag is not None:
                            seg_flags.append(
                                (f"segment[{seg.start}:{seg.stop}]", flag))
                    else:
                        self._island_dispatch(seg, env, lod_env, rng,
                                              scope, executor, profiling)
                        if self._guard_active:
                            written = {n for _op, _r, w in seg.op_io
                                       for n in w}
                            vals = [env[n] for n in sorted(written)
                                    if n in env]
                            seg_flags.append(
                                (f"island[{seg.start}:{seg.stop}]",
                                 fused_health(vals)))
        except Exception:
            if orig is not None:
                # guard-select runs promise the PRE-step state on any
                # trip — an island's raise-mode localizer fires mid-step
                # (before the end-of-step select), so earlier segments'
                # partial writes must not be committed (donation is
                # disabled under select, the refs are intact)
                env.update(orig)
            # a failure AFTER a donating segment ran would leave the scope
            # pointing at deleted buffers; restore the freshest state
            # (interpreter-like partial-step semantics for unguarded
            # runs) before surfacing
            self._write_back_state(scope, env, lod_env)
            raise
        fetched, fetch_lods = [], []
        for n in self.fetch_names:
            if n in env:
                fetched.append(env[n])
                fetch_lods.append(lod_env.get(n))
                continue
            v = scope.find_var(n)
            if v is None or not v.is_initialized():
                raise KeyError(f"fetch var '{n}' not produced by program")
            val = v.value()
            if isinstance(val, LoDTensor):
                fetched.append(val.array)
                fetch_lods.append(_normalize_lod(val.lod()))
            else:
                fetched.append(val)
                fetch_lods.append(None)
        self.fetch_lods = fetch_lods
        health = jnp.bool_(True)
        if self._guard_active:
            health = fused_health(list(fetched))
            for _label, flag in seg_flags:
                health = jnp.logical_and(health, flag)
            self._last_seg_flags = seg_flags  # trip localization (lazy)
            self._apply_discard(env, orig, health)
        self._write_back_state(scope, env, lod_env)
        return fetched, fetch_lods, health

    def _write_back_state(self, scope, env, lod_env):
        for n in self.mut_state + self.extra_writeback:
            v = env.get(n)
            if v is None:
                continue
            if isinstance(v, jax.Array) and v.is_deleted():
                continue  # donated by a segment that then failed mid-run
            scope.var(n).set_value(LoDTensor(v, lod_env.get(n)))


class HealthMonitor:
    """Rollback policy engine of the numeric fault plane
    (FLAGS_nan_inf_action=rollback — docs/FAULT_TOLERANCE.md "Numeric
    faults"). Consumes the per-step fused health flag the compiled/
    windowed/segmented paths already produce; after
    ``tolerance`` CONSECUTIVE tripped steps it restores the last intact
    PR-3 checkpoint under ``ckpt_dir`` (parameters, optimizer slots,
    rng fold counter, optional DataLoader position — bit-exact, so the
    re-run of the faulted window matches an oracle that never saw the
    fault). At most ``max_rollbacks`` restores; the next trip past that
    (or a trip with no intact checkpoint to restore) raises
    ``core.NumericFaultError``. Until tolerance is reached, tripped
    steps are discarded by the fused skip-select, so state never holds
    a NaN between observations."""

    def __init__(self, executor, ckpt_dir, program=None, scope=None,
                 tolerance: Optional[int] = None,
                 max_rollbacks: Optional[int] = None, dataloader=None,
                 on_rollback=None):
        self.executor = executor
        self.ckpt_dir = ckpt_dir
        self.program = program
        self.scope = scope
        self.dataloader = dataloader
        self.on_rollback = on_rollback
        self.tolerance = max(1, int(
            core.globals_["FLAGS_nan_inf_tolerance"]
            if tolerance is None else tolerance))
        self.max_rollbacks = int(
            core.globals_["FLAGS_nan_inf_max_rollbacks"]
            if max_rollbacks is None else max_rollbacks)
        self.trips = 0
        self.consecutive_bad = 0
        self.rollbacks = 0
        self.last_trip_step: Optional[int] = None
        self.last_rollback_step: Optional[int] = None
        self.last_manifest: Optional[Dict[str, Any]] = None

    def observe(self, healthy: bool, step: int) -> str:
        """Feed one step's health verdict. Returns "ok" | "tripped" |
        "rolled_back"; raises core.NumericFaultError when the retry
        budget is spent."""
        if healthy:
            self.consecutive_bad = 0
            return "ok"
        from . import profiler as _profiler
        self.trips += 1
        self.consecutive_bad += 1
        self.last_trip_step = int(step)
        _profiler.record_instant(
            f"health:trip[step {step}]", cat="health",
            args={"step": int(step), "action": "rollback",
                  "consecutive_bad": self.consecutive_bad})
        if self.consecutive_bad < self.tolerance:
            return "tripped"
        return self._rollback(step)

    def _rollback(self, step: int) -> str:
        from . import io as _io
        from . import profiler as _profiler
        if self.rollbacks >= self.max_rollbacks:
            raise core.NumericFaultError(
                f"numeric fault at step {step}: "
                f"{self.consecutive_bad} consecutive non-finite steps "
                f"and the rollback budget "
                f"(FLAGS_nan_inf_max_rollbacks={self.max_rollbacks}) is "
                f"spent — the fault is persistent, not transient")
        scope = self.scope if self.scope is not None else global_scope()
        manifest = _io.rollback_to_latest(self.executor, self.ckpt_dir,
                                          main_program=self.program,
                                          scope=scope)
        if manifest is None:
            raise core.NumericFaultError(
                f"numeric fault at step {step}: "
                f"FLAGS_nan_inf_action=rollback but no intact checkpoint "
                f"under {self.ckpt_dir!r} to roll back to")
        if self.dataloader is not None and manifest.get("dataloader"):
            self.dataloader.load_state_dict(manifest["dataloader"])
        self.rollbacks += 1
        self.consecutive_bad = 0
        self.last_rollback_step = int(step)
        self.last_manifest = manifest
        cfg = self.executor._auto_ckpt
        if cfg is not None:
            cfg["last_step"] = int(manifest["global_step"])
        _profiler.record_instant(
            f"health:rollback[step {step}->"
            f"{manifest['global_step']}]", cat="health",
            args={"step": int(step), "action": "rollback",
                  "restored_step": int(manifest["global_step"]),
                  "rollbacks": self.rollbacks})
        if self.on_rollback is not None:
            self.on_rollback(manifest)
        return "rolled_back"


class Executor:
    """Drop-in equivalent of fluid.Executor (reference executor.py:457)."""

    def __init__(self, place=None):
        self.place = place if place is not None else (
            core.TPUPlace(0) if core.is_compiled_with_tpu() else core.CPUPlace())
        self._compiled_cache: Dict[Tuple, _CompiledBlock] = {}
        self._closed = False
        self._maybe_enable_compile_cache()
        # step telemetry (docs/OBSERVABILITY.md): backend-compile
        # listener (cat="compile" spans + jax_backend_compiles_total)
        # and the opt-in FLAGS_metrics_port sidecar — both idempotent
        # process-wide, so per-Executor construction is free
        _telemetry.install_jax_compile_listener()
        _telemetry.maybe_start_metrics_server()
        # how the LAST run executed: "compiled" | "segmented" |
        # "interpreted" (observability for tests)
        self._last_run_mode: Optional[str] = None
        # periodic atomic checkpointing (set_auto_checkpoint /
        # resume_from — docs/FAULT_TOLERANCE.md)
        self._auto_ckpt: Optional[Dict[str, Any]] = None
        # numeric fault plane (FLAGS_check_nan_inf +
        # FLAGS_nan_inf_action): the last step's LAZY device health
        # flag(s), host-side trip counters (only advanced on paths that
        # sync — raise/rollback/profiling), and the rollback monitor
        self._last_health = None
        self._health_stats = {"steps_checked": 0, "trips": 0}
        self._health_monitor: Optional[HealthMonitor] = None
        # True while the just-finished step tripped the guard (only
        # meaningful on synced paths): gates the auto-checkpoint so a
        # snapshot is never taken from inside a fault window — its rng
        # counter would record the DISCARDED step and break the
        # rollback replay's bit-exactness
        self._last_step_tripped = False
        # per-instance override of FLAGS_executor_seg_min_ops (None =
        # use the global). The serving engine pins its private executor
        # to 1 so even tiny stateful programs run their dense chains as
        # compiled segments — an instance attribute, NOT a global flag
        # swap, so a co-resident training executor can never observe it
        self._seg_min_ops_override: Optional[int] = None

    def _build_segmented(self, program, feed, fetch_names, scope, seed,
                         feed_lods) -> Optional[_SegmentedBlock]:
        """Build the segment plan for a block that failed the all-or-
        nothing compiled check. None -> pure interpreter (too few
        compilable ops, or the plan could not be built — the interpreter
        stays the correctness oracle and fallback). Contract violations
        raise exactly like the fused compiled path: KeyError for a data
        var missing from feed= / an unproducible fetch, RuntimeError for
        an uninitialized persistable (startup program not run)."""
        try:
            return _SegmentedBlock(program, tuple(sorted(feed)),
                                   tuple(fetch_names), scope, seed,
                                   feed_lods=feed_lods,
                                   seg_min_ops=self._seg_min_ops_override)
        except _NotSegmentable:
            return None
        except (KeyError, RuntimeError):
            raise  # user errors, not fallback cases
        except Exception as e:  # noqa: BLE001 — any plan failure
            import warnings as _warnings
            _warnings.warn(
                f"segmented compilation unavailable for this program "
                f"({e!r}); falling back to the op-by-op interpreter",
                stacklevel=3)
            return None

    def _maybe_enable_compile_cache(self):
        """Opt-in persistent XLA executable cache: repeated processes
        running the same program skip the compile (the executable loads
        from disk, keyed by HLO hash). Checked at construction AND per
        run — like the dataloader timeout flags, setting
        FLAGS_compilation_cache_dir after the Executor exists must not
        be silently ignored (enable_compile_cache is idempotent per
        dir, so the per-run check is a dict lookup)."""
        cache_dir = core.globals_["FLAGS_compilation_cache_dir"]
        if cache_dir:
            from ..inference import enable_compile_cache
            enable_compile_cache(cache_dir)

    # ------------------------------------------------------------------ API
    def close(self):
        self._closed = True

    # ------------------------------------------- fault-tolerant training
    def set_auto_checkpoint(self, dirname, every_n_steps: int,
                            program=None, scope: Optional[Scope] = None,
                            max_to_keep: int = 3, dataloader=None):
        """Enable periodic atomic checkpoints: every run() whose global
        step counter crosses a multiple of ``every_n_steps`` snapshots
        all persistables (params + optimizer slots) plus the rng fold
        counter to ``dirname/ckpt-<step>`` (io.save_checkpoint — temp
        dir, fsync, rename; a kill mid-save can't corrupt an existing
        checkpoint). ``program``/``scope`` (when given) restrict which
        runs are counted — pass the TRAINING program so startup or eval
        runs don't trigger saves. ``dataloader``: its state_dict() rides
        the manifest so resume can fast-forward the input stream.
        ``every_n_steps <= 0`` disables."""
        if not dirname or every_n_steps <= 0:
            self._auto_ckpt = None
            return
        self._auto_ckpt = {
            "dir": dirname, "every": int(every_n_steps),
            "program": program, "scope": scope,
            "max_to_keep": int(max_to_keep), "dataloader": dataloader,
            "last_step": 0,
        }

    def resume_from(self, path, program=None, scope: Optional[Scope] = None,
                    dataloader=None) -> Optional[Dict[str, Any]]:
        """Restore the newest VALID checkpoint under ``path`` (or that
        exact ckpt dir): parameters, optimizer slot vars, the global rng
        fold counter, and (when ``dataloader`` is passed) the input
        stream position — a killed-and-resumed run then produces
        bit-identical per-step losses to an uninterrupted one (the
        kill-resume parity test in tests/test_fault_tolerance.py).
        Returns the manifest, or None when ``path`` has no checkpoint
        yet (a fresh start — callers can treat both cases uniformly)."""
        from . import io as _io
        if scope is None:
            scope = global_scope()
        if isinstance(path, str) and not os.path.isdir(path):
            return None  # checkpoint root never created: fresh start
        try:
            manifest = _io.load_checkpoint(self, path,
                                           main_program=program,
                                           scope=scope)
        except core.CheckpointError:
            if _io.latest_checkpoint(path) is None and \
                    not os.path.exists(os.path.join(path,
                                                    _io.CKPT_MANIFEST)):
                # nothing restorable: fresh start — loud when ckpt dirs
                # exist but ALL failed validation (vs. a truly empty root)
                if _io._checkpoint_steps(path):
                    import warnings as _warnings
                    _warnings.warn(
                        f"resume_from({path!r}): checkpoints exist but "
                        f"none validated — starting FRESH from step 0",
                        stacklevel=2)
                return None
            raise
        if dataloader is not None and manifest.get("dataloader"):
            dataloader.load_state_dict(manifest["dataloader"])
        if self._auto_ckpt is not None:
            self._auto_ckpt["last_step"] = int(manifest["global_step"])
        return manifest

    def _maybe_auto_checkpoint(self, program, scope: Scope):
        cfg = self._auto_ckpt
        if cfg is None:
            return
        if self._last_step_tripped:
            return  # never checkpoint out of a fault window
        if cfg["program"] is not None and program is not cfg["program"]:
            return
        if cfg["scope"] is not None and scope is not cfg["scope"]:
            return
        step = Executor._rng_counters.get(scope)
        if step is None:
            return
        every = cfg["every"]
        if step // every <= cfg["last_step"] // every:
            return  # no boundary crossed since the last save
        from . import io as _io
        dl = cfg["dataloader"]
        dl_state = (dl.state_dict()
                    if dl is not None and hasattr(dl, "state_dict")
                    else None)
        _io.save_checkpoint(self, cfg["dir"],
                            main_program=cfg["program"] or program,
                            scope=scope, global_step=step,
                            dataloader_state=dl_state,
                            max_to_keep=cfg["max_to_keep"])
        cfg["last_step"] = step

    # ------------------------------------------------ numeric fault plane
    def set_health_monitor(self, ckpt_dir, program=None, scope=None,
                           tolerance=None, max_rollbacks=None,
                           dataloader=None, on_rollback=None
                           ) -> HealthMonitor:
        """Explicitly configure the FLAGS_nan_inf_action=rollback
        monitor (docs/FAULT_TOLERANCE.md "Numeric faults"). Without
        this, the monitor is derived lazily from the auto-checkpoint
        config (set_auto_checkpoint / train_from_dataset
        checkpoint_dir=) on the first tripped step."""
        self._health_monitor = HealthMonitor(
            self, ckpt_dir, program=program, scope=scope,
            tolerance=tolerance, max_rollbacks=max_rollbacks,
            dataloader=dataloader, on_rollback=on_rollback)
        return self._health_monitor

    def _ensure_health_monitor(self, program, scope) -> HealthMonitor:
        if self._health_monitor is not None:
            return self._health_monitor
        cfg = self._auto_ckpt
        if cfg is None or not cfg.get("dir"):
            raise core.NumericFaultError(
                "FLAGS_nan_inf_action=rollback tripped but no checkpoint "
                "plane is configured — call set_auto_checkpoint() (or "
                "pass checkpoint_dir= to train_from_dataset), or wire "
                "set_health_monitor() explicitly")
        self._health_monitor = HealthMonitor(
            self, cfg["dir"], program=cfg["program"] or program,
            scope=cfg["scope"] or scope, dataloader=cfg.get("dataloader"))
        return self._health_monitor

    @staticmethod
    def _offending_segment(cb) -> Optional[str]:
        """Label of the first segment whose fused flag tripped (only
        meaningful for segmented blocks; one host sync per flag — called
        exclusively on the already-tripped slow path)."""
        for label, flag in getattr(cb, "_last_seg_flags", ()) or ():
            if not bool(np.asarray(flag)):
                return label
        return None

    def _localize_and_raise(self, cb, program, scope, rng, step: int):
        """raise-mode tail: the fused health scalar tripped, the select
        kept the pre-step state — re-run the SAME step (same feeds in
        scope, same rng key) through the interpreter, whose per-op
        localizer names the first bad op/var/indices. Segmented blocks:
        island side effects (auc/print) run a second time on this crash
        path — documented in docs/FAULT_TOLERANCE.md."""
        from . import profiler as _profiler
        seg = self._offending_segment(cb)
        _profiler.record_instant(
            f"health:trip[step {step}]", cat="health",
            args={"step": int(step), "action": "raise",
                  "segment": seg or "-"})
        try:
            self._run_block_eager(program.global_block(), scope, rng)
        except FloatingPointError as e:
            raise FloatingPointError(
                f"numeric fault at global step {step}"
                + (f" (first tripped {seg})" if seg else "")
                + f": {e}") from e
        raise core.NumericFaultError(
            f"health guard tripped at global step {step}"
            + (f" in {seg}" if seg else "")
            + " but the interpreter re-run reproduced no non-finite op "
            "output — the fault did not replay (e.g. a poisoned feed "
            "replaced since, or island-stateful nondeterminism)")

    def _process_health(self, cb, program, scope, health, step0: int,
                        n_steps: int, rng=None):
        """Post-step policy dispatch over the fused health flag(s).
        skip (and AMP-only) stays LAZY — no host sync unless the
        profiler wants trip markers; raise and rollback read the flags
        back (that sync is those actions' documented cost)."""
        if not cb._guard_active:
            return
        self._last_health = health
        from . import profiler as _profiler
        # trip markers need a host readback of the flags — only a real
        # profiler session pays it; FLAGS_trace_dir shard streaming
        # must not re-add the per-step sync skip-mode avoids
        profiling = _profiler.is_session()
        action = cb._guard_action if cb._guard_check else None
        if action not in ("raise", "rollback") and not profiling:
            return
        flags = np.asarray(health).reshape(-1).astype(bool)
        self._health_stats["steps_checked"] += len(flags)
        n_bad = int((~flags).sum())
        self._health_stats["trips"] += n_bad
        if action in ("raise", "rollback"):
            # sticky across the steps of ONE run (segmented/window
            # loops): any tripped step gates this run's auto-checkpoint
            # — a rollback target must never come from inside a fault
            # window. ONLY policy-bearing actions set it: skip always
            # syncs here only when profiling, and observability must
            # not change checkpoint cadence (a skip-discarded step
            # leaves clean state, so snapshotting it is valid).
            self._last_step_tripped = self._last_step_tripped \
                or bool(n_bad)
        if action == "raise":
            if n_bad:
                bad = int(np.flatnonzero(~flags)[0])
                if rng is None:
                    # no single-step rng context (mesh window path) —
                    # surface typed instead of mis-localizing
                    raise core.NumericFaultError(
                        f"numeric fault at global step {step0 + bad} "
                        f"(windowed mesh run — re-run per-step for the "
                        f"op-level localization)")
                self._localize_and_raise(cb, program, scope, rng,
                                         step0 + bad)
            return
        if action == "rollback":
            mon = self._health_monitor
            for i, ok_ in enumerate(flags):
                if ok_:
                    if mon is not None:
                        mon.observe(True, step0 + i)
                    continue
                if mon is None:
                    mon = self._ensure_health_monitor(program, scope)
                if mon.observe(False, step0 + i) == "rolled_back":
                    # flags past the restore describe discarded compute
                    break
            return
        if n_bad and profiling:  # skip / AMP-only: markers, no policy
            seg = self._offending_segment(cb)
            for i in np.flatnonzero(~flags):
                _profiler.record_instant(
                    f"health:trip[step {step0 + int(i)}]", cat="health",
                    args={"step": int(step0 + int(i)),
                          "action": action or "amp",
                          "segment": seg or "-"})

    def health_stats(self) -> Dict[str, int]:
        """Host-side guard counters. Only paths that sync (raise/
        rollback/profiling) advance them — skip mode is deliberately
        sync-free; read ``_last_health`` (device) for its verdicts."""
        return dict(self._health_stats)

    def _interp_guard_cfg(self, program, feed_names, scope):
        """The interpreter oracle's guard plan, mirroring
        _CompiledBlock._init_guard's state classification so compiled
        and interpreted runs reduce health over the SAME variable set
        (the AMP bit-parity contract). None when the fault plane is
        off."""
        check = bool(core.globals_["FLAGS_check_nan_inf"])
        amp = getattr(program, "_amp_dynamic", None)
        if not check and amp is None:
            return None
        action = str(core.globals_["FLAGS_nan_inf_action"])
        if check and action not in _GUARD_ACTIONS:
            raise ValueError(
                f"FLAGS_nan_inf_action={action!r} is not one of "
                f"{sorted(_GUARD_ACTIONS)}")
        # the classification is invariant per (program version, feeds,
        # scope, flags) — cache ON the program (dies with it, like
        # _prune_cache; the scope weakref guards id reuse), mirroring
        # the compiled path's classify-once-at-build semantics instead
        # of re-walking every op each interpreted step
        ckey = (program._version, tuple(sorted(feed_names)), check,
                action)
        cache = program.__dict__.setdefault("_interp_guard_cache", {})
        hit = cache.get(ckey)
        if hit is not None and hit[0]() is scope:
            return hit[1]
        block = program.global_block()
        ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
        if amp is not None and not _block_reads_amp_scale(ops, amp):
            amp = None  # pruned-away machinery: same rule as _init_guard
        if not check and amp is None:
            cache[ckey] = (weakref.ref(scope), None)
            return None

        def _ok(n):
            return _initialized_tensor(scope, n) is not None

        written: set = set()
        rbw: List[str] = []
        for op in ops:
            for name in op.input_arg_names:
                if name in written or name in feed_names or name in rbw:
                    continue
                if _ok(name):
                    rbw.append(name)
            written.update(_effective_writes(op))
        persistable = {v.name for v in block.vars.values()
                       if v.persistable}
        amp_names = (set() if amp is None else
                     {amp["scale"], amp["good"], amp["bad"]})
        sel = [n for n in rbw if n in written and n not in amp_names]
        for n in sorted(written):
            if (n in persistable and n not in sel
                    and n not in feed_names and n not in amp_names
                    and _ok(n)):
                sel.append(n)
        cfg = {"check": check, "action": action, "amp": amp,
               "select_names": tuple(sel),
               # same health source as the compiled epilogue: param
               # grads (+ fetches), falling back to all grads then to
               # the written state
               "health_names": tuple(
                   n + GRAD_SUFFIX for n in sel
                   if n + GRAD_SUFFIX in written) or tuple(
                   n for n in sorted(written)
                   if n.endswith(GRAD_SUFFIX)),
               "select": amp is not None or (
                   check and action in ("skip", "rollback"))}
        cache[ckey] = (weakref.ref(scope), cfg)
        return cfg

    def _run_interpreted_step(self, program, scope, rng, guard,
                              fetch_names) -> bool:
        """One eager step + the numeric-fault epilogue (same health
        set, same select/AMP arithmetic as the compiled epilogue — the
        interpreter is the oracle the compiled guard is tested
        against). raise-mode localization fires PER OP inside
        _run_op_eager, so a bad op raises mid-step with full detail;
        skip/rollback restore the pre-step state refs (jax arrays are
        immutable, so the snapshot is free). Returns the step's health
        verdict (True when unguarded)."""
        block = program.global_block()
        if guard is None:
            self._run_block_eager(block, scope, rng)
            return True
        from .ir import fused_health

        def _val(n):
            return _initialized_tensor(scope, n)
        snap = {}
        if guard["select"]:
            for n in guard["select_names"]:
                t = _val(n)
                if t is not None:
                    snap[n] = (t.array, t.lod())
        self._run_block_eager(block, scope, rng)
        vals = []
        for n in guard["health_names"]:
            t = _val(n)
            if t is not None:
                vals.append(t.array)
        if not vals:
            for n in guard["select_names"]:
                t = _val(n)
                if t is not None:
                    vals.append(t.array)
        for n in fetch_names or ():
            t = _val(n)
            if t is not None:
                vals.append(t.array)
        health = fused_health(vals)
        healthy = bool(np.asarray(health))
        if guard["amp"] is not None and not (
                guard["check"] and guard["action"] == "raise"
                and not healthy):
            # same rule as _apply_discard: under raise a tripped step
            # keeps its pre-step scale/counters (the localizer replay
            # must see the exact overflow-producing scale)
            a = guard["amp"]
            new_scale, new_good, new_bad = _amp_scale_update(
                health, _val(a["scale"]).array, _val(a["good"]).array,
                _val(a["bad"]).array, a)
            scope.var(a["scale"]).set_value(LoDTensor(new_scale))
            scope.var(a["good"]).set_value(LoDTensor(new_good))
            scope.var(a["bad"]).set_value(LoDTensor(new_bad))
        self._last_health = health
        self._health_stats["steps_checked"] += 1
        if guard["check"] and guard["action"] in ("raise", "rollback"):
            # same rule as _process_health: only policy-bearing actions
            # gate the auto-checkpoint
            self._last_step_tripped = self._last_step_tripped \
                or not healthy
        if not healthy:
            self._health_stats["trips"] += 1
            if guard["select"]:
                for n, (arr, lod) in snap.items():
                    scope.var(n).set_value(LoDTensor(arr, lod))
        if guard["check"] and guard["action"] == "rollback":
            step = Executor._rng_counters.get(scope, 1) - 1
            mon = self._health_monitor
            if not healthy and mon is None:
                mon = self._ensure_health_monitor(program, scope)
            if mon is not None:
                mon.observe(healthy, step)
        return healthy

    def _find_block(self, program, feed, fetch_names, scope, feed_lods,
                    mesh, param_shardings, compiled_ok):
        """(block, hit): the cached `_CompiledBlock` / `_SegmentedBlock`
        for this program, signature and scope — built on a miss — or
        None where the block is known to run interpreted. Building does
        not trace: that happens at the block's first dispatch."""
        key = (id(program), program._version, tuple(sorted(feed)),
               tuple(fetch_names), id(scope),
               tuple(sorted(feed_lods.items())),
               # the numeric fault guard is BAKED into the trace —
               # flipping its flags rebuilds the program instead of
               # silently running an unguarded (or stale-action)
               # executable
               (core.globals_["FLAGS_check_nan_inf"],
                core.globals_["FLAGS_nan_inf_action"]),
               None if mesh is None else
               (tuple(mesh.shape.items()), tuple(map(id, mesh.devices.flat))),
               None if not param_shardings else
               tuple(sorted((k, str(v))
                            for k, v in param_shardings.items())))
        cached = self._compiled_cache.get(key)
        # guard id() reuse: a dead scope's id can be recycled by a new
        # scope with different state — every cache entry (including
        # the "interpreted" unprofitable-key marker) validates a scope
        # weakref before being trusted
        cb, rebuild = None, True
        if isinstance(cached, tuple):  # ("interpreted", scope_ref)
            if cached[1]() is scope:
                rebuild = False  # known unprofitable for this scope
        elif cached is not None and cached._scope_ref() is scope:
            cb, rebuild = cached, False
        if rebuild:
            # static-analysis choke point (docs/ANALYSIS.md): verify
            # ONCE per program version at its first compile, BEFORE
            # tracing — a structural defect gets a diagnostic with a
            # fix hint instead of a deep TracerError. An error-level
            # failure caches nothing, so a retry re-verifies.
            _analysis.maybe_verify(
                program, "executor", feed_names=tuple(sorted(feed)),
                fetch_names=tuple(fetch_names),
                param_shardings=param_shardings, scope=scope)
            seed = (program.random_seed
                    or core.globals_["FLAGS_seed"])
            if compiled_ok:
                cb = _CompiledBlock(program, tuple(sorted(feed)),
                                    tuple(fetch_names), scope, seed,
                                    mesh=mesh,
                                    param_shardings=param_shardings,
                                    feed_lods=feed_lods)
            else:
                cb = self._build_segmented(
                    program, feed, fetch_names, scope, seed,
                    feed_lods)
            if cb is not None and cb.kind == "segmented":
                # donation-safety cross-check against the plan the
                # segmented build ACTUALLY produced (own dedup key:
                # the plan exists only post-build)
                _analysis.maybe_verify(
                    program, "executor-plan",
                    feed_names=tuple(sorted(feed)),
                    fetch_names=tuple(fetch_names),
                    segment_plan=cb.segments, scope=scope)
            self._compiled_cache[key] = (
                cb if cb is not None
                else ("interpreted", weakref.ref(scope)))
        return cb, not rebuild

    def run(self, program: Optional[Program] = None, feed=None,
            fetch_list=None, feed_var_name="feed", fetch_var_name="fetch",
            scope: Optional[Scope] = None, return_numpy: bool = True,
            use_program_cache: bool = False, use_prune: bool = False,
            mesh=None, param_shardings=None, n_steps: int = 1):
        """reference executor.py:457 Executor.run. ``n_steps > 1`` runs
        that many steps with the SAME feeds as one dispatched lax.scan
        on the compiled path (fetches come back stacked [n_steps, ...]);
        per-dispatch host overhead amortizes to a single dispatch
        — the benchmark/training-loop shape. Interpreted programs run
        the steps sequentially and return the final fetch values."""
        from . import profiler as _profiler
        with contextlib.ExitStack() as stack:
            if _profiler.is_profiling() \
                    and _telemetry.current_trace() is None:
                # trace correlation (docs/OBSERVABILITY.md): one root
                # trace per run() — every span this step records (stage
                # spans, segments, windows, the PS round's rpc calls and
                # their pserver handler spans) shares one trace id, which
                # is what makes a training round followable
                # trainer→pserver in the merged cluster timeline.
                # Serving/batch callers that already installed a context
                # keep theirs. Entered here and not through a second
                # run() frame: the step is traced under the same Python
                # stack whether or not anything records.
                stack.enter_context(_telemetry.trace_scope())
            if _telemetry.open_step() is None:
                # the parent of the stage spans, and the step record
                # (telemetry.STEPS) it leaves; a run() re-entered on this
                # thread (CompiledProgram._run, a window's per-step
                # fallback) joins the span and the record that are open
                stack.enter_context(
                    _profiler.RecordEvent(_telemetry.RUN_SPAN,
                                          cat="executor"))
            return self._run(program, feed, fetch_list, scope, return_numpy,
                             use_prune, mesh, param_shardings, n_steps)

    def _run(self, program, feed, fetch_list, scope, return_numpy,
             use_prune, mesh, param_shardings, n_steps):
        from .compiler import CompiledProgram
        from . import profiler as _profiler
        self._maybe_enable_compile_cache()
        if program is None:
            program = default_main_program()
        if isinstance(program, CompiledProgram):
            return program._run(self, feed, fetch_list, scope, return_numpy,
                                mesh=mesh, param_shardings=param_shardings,
                                n_steps=n_steps)
        if scope is None:
            scope = global_scope()
        feed = feed or {}
        _telemetry.step_block(program)  # until a compiled block is found
        fetch_names = _to_fetch_names(fetch_list)
        # stale trip verdicts must not gate THIS run's auto-checkpoint
        self._last_step_tripped = False

        if use_prune and fetch_names:
            # backward-slice to the fetch targets (reference executor.py
            # _prune_program + prune cache keyed like the run cache). Note
            # the reference caveat applies: pruning a training program by
            # its loss drops the optimizer ops.
            # cache lives ON the program object (not keyed by id()), so it
            # dies with the program and a recycled id can never serve a
            # stale pruned copy
            pkey = (program._version, tuple(fetch_names))
            cache = program.__dict__.setdefault("_prune_cache", {})
            pruned = cache.get(pkey)
            if pruned is None:
                pruned = cache[pkey] = program._prune(list(fetch_names))
            program = pruned

        # a WindowBatch (DataLoader.window) knows its own window length —
        # forgetting n_steps=k must not silently broadcast the [K, ...]
        # stack as one giant step
        window_names: Tuple[str, ...] = ()
        wk = getattr(feed, "k", None)
        if isinstance(wk, int) and wk > 0:
            if n_steps == 1:
                n_steps = wk
            elif n_steps != wk:
                raise ValueError(
                    f"feed is a WindowBatch of {wk} stacked batches but "
                    f"n_steps={n_steps} was requested")
            # every WindowBatch entry is K stacked real batches by
            # construction, so slicing is always correct — no rank
            # heuristic (which would silently BROADCAST the stack for a
            # var it cannot classify, e.g. a concrete-first-dim var)
            window_names = tuple(feed)
        elif feed and n_steps > 1:
            # raw dict feeds: a leading [n_steps, ...] dim means n_steps
            # DISTINCT batches consumed one slice per step; empty tuple
            # = the same-feeds broadcast degenerate case. Detection only
            # engages for an explicit multi-step request — a plain
            # n_steps=1 dict run keeps the pre-window semantics for
            # rank-polymorphic feeds and skips the per-feed var scan on
            # the hot path.
            window_names = _window_feed_names(program, feed, n_steps)

        mode = core.globals_["FLAGS_executor_mode"]

        def compilable():
            return (mode == "compiled"
                    and _ops_compilable(program.global_block().ops))

        # only a windowed run has to know BEFORE the feed upload whether
        # its block compiles (the two fallbacks below); a single step
        # walks its ops inside the exe:lookup span
        compiled_ok = compilable() if n_steps > 1 or window_names else None

        if window_names and not compiled_ok:
            # Documented per-step fallback for windowed feeds on paths
            # where the window cannot collapse to one dispatch:
            # segmented blocks (islands have per-step host side
            # effects) and interpreted blocks. Same contract as the
            # compiled window: step i consumes slice i of every
            # windowed feed, rng advances one global step per slice,
            # fetches come back stacked [n_steps, ...]. Decided BEFORE
            # the feed upload below — the whole [K, ...] stack must not
            # be device_put just to be re-uploaded slice by slice.
            # Compiled MESH programs scan the window like the 1-device
            # path since the 3D lane work: the stack is device_put ONCE
            # with its batch dim (dim 1) sharded over "dp" and the
            # window dim left whole for the scan — pipeline-sectioned
            # programs consume DataLoader window stacks directly, the
            # microbatch slices carved on-device inside the scanned
            # step.
            return self._run_window_fallback(
                program, feed, fetch_list, scope, return_numpy, mesh,
                param_shardings, n_steps, window_names)

        if (n_steps > 1 or window_names) and compiled_ok \
                and core.globals_["FLAGS_check_nan_inf"] \
                and core.globals_["FLAGS_nan_inf_action"] == "raise":
            # raise is the DEBUGGING action: the offending step must
            # re-run through the interpreter localizer from exactly its
            # pre-step state, so windows take the documented per-step
            # fallback instead of one fused scan. Decided BEFORE the
            # feed upload below, like the fallback above — the [K, ...]
            # stack must not be device_put just to be re-uploaded slice
            # by slice.
            return self._run_window_fallback(
                program, feed, fetch_list, scope, return_numpy, mesh,
                param_shardings, n_steps, window_names)

        # materialize program vars' metadata for persistables (create slots)
        # feeds → device
        feed_arrays = {}
        feed_lods = {}
        # host batch -> device array; the upload is enqueued, not awaited
        with _profiler.RecordEvent("exe:feed", cat="executor") as span:
            for name, data in feed.items():
                t = _as_lodtensor(data, self.place)
                scope.var(name).set_value(t)
                feed_arrays[name] = t.array
                lv = _normalize_lod(t.lod())
                if lv:
                    feed_lods[name] = lv
            span.args = {"arrays": len(feed_arrays),
                         "bytes": sum(int(getattr(a, "nbytes", 0))
                                      for a in feed_arrays.values())}
        # which way the block runs, and the block: compiled whole;
        # segmented (default when the all-or-nothing check fails: jitted
        # islands of pure ops around interpreted stateful ops, instead of
        # interpreting the WHOLE block; mesh runs keep their existing
        # paths, compiled or interpreted); or interpreted (no block)
        with _profiler.RecordEvent("exe:lookup", cat="executor") as span:
            if compiled_ok is None:
                compiled_ok = compilable()
            try_segmented = (
                not compiled_ok and mode == "compiled" and mesh is None
                and core.globals_["FLAGS_executor_segmentation"])
            cb, hit = None, False
            if compiled_ok or try_segmented:
                cb, hit = self._find_block(
                    program, feed, fetch_names, scope, feed_lods, mesh,
                    param_shardings, compiled_ok)
                if cb is not None:
                    _telemetry.step_block(cb)
            span.args = {"hit": hit}

        if cb is not None and cb.kind == "compiled":
            if n_steps > 1 or window_names:
                rng_base, idx0 = self._next_rng_window(scope, program,
                                                       n_steps)
                fetched, health = cb.run_window(scope, feed_arrays,
                                                rng_base, idx0, n_steps,
                                                window_names)
                self._process_health(cb, program, scope, health, idx0,
                                     n_steps)
            else:
                with cb.place_span():
                    # deriving the step's key is one small dispatch
                    rng = self._next_rng(scope, program)
                    placed = cb._place_inputs(scope, feed_arrays, rng)
                fetched, health = cb.run_placed(scope, placed)
                self._process_health(
                    cb, program, scope, health,
                    Executor._rng_counters.get(scope, 1) - 1, 1, rng=rng)
            fetch_lods = cb.fetch_lods
            self._last_run_mode = "compiled"
        elif cb is not None:  # segmented: host loop per step (islands
            # have per-step side effects); final step's fetches returned,
            # the interpreter contract
            fetched, fetch_lods = [], []
            for _ in range(n_steps):
                rng = self._next_rng(scope, program)
                fetched, fetch_lods, health = cb.run_step(
                    scope, feed_arrays, rng, self)
                self._process_health(
                    cb, program, scope, health,
                    Executor._rng_counters.get(scope, 1) - 1, 1, rng=rng)
            self._last_run_mode = "segmented"
        else:
            # interpreted programs have no compile event — the analysis
            # choke point anchors on the once-per-version guard-config
            # build instead (maybe_verify dedups by program version)
            _analysis.maybe_verify(
                program, "executor", feed_names=tuple(sorted(feed)),
                fetch_names=tuple(fetch_names), scope=scope)
            guard = self._interp_guard_cfg(program, set(feed), scope)
            for _ in range(n_steps - 1):  # same feeds, repeated steps
                rng = self._next_rng(scope, program)
                self._run_interpreted_step(program, scope, rng, guard,
                                           fetch_names)
            rng = self._next_rng(scope, program)
            self._run_interpreted_step(program, scope, rng, guard,
                                       fetch_names)
            self._last_run_mode = "interpreted"
            fetched = []
            fetch_lods = []
            for n in fetch_names:
                v = scope.find_var(n)
                if v is None:
                    raise KeyError(f"fetch var '{n}' not found in scope")
                val = v.value()
                if isinstance(val, LoDTensor):
                    fetched.append(val.array)
                    fetch_lods.append(_normalize_lod(val.lod()))
                else:
                    fetched.append(val)
                    fetch_lods.append(None)

        # periodic atomic checkpoint AFTER the step's state writeback —
        # the snapshot sees exactly the post-step scope
        self._maybe_auto_checkpoint(program, scope)

        if fetch_names and return_numpy:
            # where the plain loop waits for the device
            with _profiler.RecordEvent("exe:fetch", cat="executor"):
                return [_restore_fetch_dtype(program, n, _fetch_to_host(f))
                        for n, f in zip(fetch_names, fetched)]
        if fetch_names:
            # LoDTensor fetches stay LAZY device arrays (the async
            # training-loop contract — no per-step sync); only a
            # non-addressable multi-process global must gather here. The
            # int64-restore policy applies at np conversion, i.e. on the
            # return_numpy=True path.
            return [LoDTensor(f if not (isinstance(f, jax.Array)
                                        and not f.is_fully_addressable)
                              else _fetch_to_host(f), lod=lv)
                    for f, lv in zip(fetched, fetch_lods)]
        return []

    # ------------------------------------------------------ dataset path
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None, mesh=None, window_size=1,
                           checkpoint_dir=None,
                           checkpoint_every_n_steps=0, resume_from=None):
        """One pass over a Dataset (reference: executor.py:1438
        train_from_dataset → C++ MultiTrainer/HogwildWorker threads,
        trainer.h:64). The TPU inversion: batches stream from the native
        C++ feed engine into the ONE jitted step — XLA pipelining replaces
        the reference's per-thread op loops. ``mesh``: a device mesh for
        the step; with a "pp" axis, a PipelineOptimizer-sectioned program
        runs stage-parallel (the SectionWorker/PipelineTrainer role —
        section_worker.cc:142 — via fluid/pipeline_lowering.py).
        ``window_size=K``: stack K consecutive dense same-shape batches
        into one [K, ...]-windowed run (ONE dispatch on the compiled
        path — docs/INPUT_PIPELINE.md); batches that carry LoD or ragged
        shapes run per-step as before.

        ``checkpoint_dir`` + ``checkpoint_every_n_steps``: enable
        periodic atomic checkpoints for this training program (see
        set_auto_checkpoint); ``resume_from``: restore the newest valid
        checkpoint under that path first (see resume_from) — together
        they make a killed-and-relaunched dataset run continue with
        bit-identical rng streams (docs/FAULT_TOLERANCE.md)."""
        if program is None:
            program = default_main_program()
        if checkpoint_dir and checkpoint_every_n_steps > 0:
            self.set_auto_checkpoint(checkpoint_dir,
                                     checkpoint_every_n_steps,
                                     program=program, scope=scope)
        if resume_from:
            self.resume_from(resume_from, program=program, scope=scope)
        return self._run_from_dataset(program, dataset, scope, fetch_list,
                                      fetch_info, print_period,
                                      fetch_handler, mesh=mesh,
                                      window_size=window_size)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None, mesh=None, window_size=1):
        return self._run_from_dataset(program, dataset, scope, fetch_list,
                                      fetch_info, print_period,
                                      fetch_handler, mesh=mesh,
                                      window_size=window_size)

    @staticmethod
    def _stack_dataset_window(feeds: List[Dict[str, Any]]):
        """[{name: LoDTensor}] * K → WindowBatch of [K, ...] arrays when
        every value is LoD-free and shapes match across the window; None
        otherwise (the caller falls back to per-step runs). Same
        assembly contract as DataLoader.window (reader._stack_window),
        just non-raising."""
        from .reader import _stack_window
        try:
            return _stack_window(feeds, len(feeds), len(feeds))
        except (ValueError, KeyError):
            return None

    def _run_from_dataset(self, program, dataset, scope, fetch_list,
                          fetch_info, print_period, fetch_handler=None,
                          mesh=None, window_size=1):
        if dataset is None:
            raise ValueError("dataset must be provided")
        if program is None:
            program = default_main_program()
        if scope is None:
            scope = global_scope()
        dataset._ensure_handle()
        if dataset.get_memory_data_size() == 0:
            dataset._load()
        fetch_names = _to_fetch_names(fetch_list)
        monitor = None
        if fetch_handler is not None:
            monitor = _FetchHandlerMonitor(scope, fetch_handler)
            monitor.start()
        step = 0
        last = []

        def report(vals, count=1):
            # fire once per print_period: when a period boundary falls
            # in [step, step + count) — per-step runs (count=1) print
            # exactly at multiples of print_period like before, windows
            # print once per crossed boundary (labelled by the window's
            # first global step; the value is the window's final step)
            if not (fetch_names and print_period):
                return
            off = step % print_period
            if off != 0 and off + count <= print_period:
                return
            infos = fetch_info or fetch_names
            msg = ", ".join(
                f"{i}={np.asarray(v).reshape(-1)[-1]:.6f}"
                for i, v in zip(infos, vals))
            print(f"[train_from_dataset] step {step}: {msg}")

        pending: List[Dict[str, Any]] = []

        def flush():
            nonlocal step, last
            if not pending:
                return
            # _stack_dataset_window returns a WindowBatch, which run()
            # treats as windowed WHOLESALE (no rank heuristic that could
            # silently broadcast an unclassifiable var's stack)
            stacked = (self._stack_dataset_window(pending)
                       if len(pending) > 1 else None)
            if stacked is not None:
                last = self.run(program, feed=stacked,
                                fetch_list=fetch_list, scope=scope,
                                mesh=mesh, n_steps=len(pending))
                # report BEFORE advancing: the label is the window's
                # first global step (matching per-step mode's step 0
                # baseline row)
                report(last, count=len(pending))
                step += len(pending)
            else:
                for f in pending:
                    last = self.run(program, feed=f,
                                    fetch_list=fetch_list, scope=scope,
                                    mesh=mesh)
                    report(last)
                    step += 1
            pending.clear()

        try:
            for feed in dataset._iter_batches():
                pending.append(feed)
                if len(pending) >= max(1, window_size):
                    flush()
            flush()
        finally:
            if monitor is not None:
                monitor.stop()
        return last

    # --------------------------------------------------------------- eager
    _fold_rng = None  # class-level jitted fold: one dispatch per step
    _rng_counters = weakref.WeakKeyDictionary()  # scope -> host step count

    def _advance_rng_counter(self, scope: Scope, n: int) -> int:
        # the step counter is a host int per scope (a device round-trip per
        # step costs ~0.4ms of pure overhead); the scope var mirrors it for
        # inspection, stored as a lazy numpy buffer
        cnt = Executor._rng_counters.get(scope)
        if cnt is None:
            v = scope.var("@RNG_COUNTER@")
            cnt = (int(np.asarray(v.get_tensor().array).reshape(-1)[0])
                   if v.is_initialized() else 0)
        Executor._rng_counters[scope] = cnt + n
        scope.var("@RNG_COUNTER@").set_value(
            LoDTensor(np.asarray([cnt + n], np.int32)))
        return cnt

    def _program_seed(self, program: Program) -> int:
        return int(program.random_seed or core.globals_["FLAGS_seed"])

    def _next_rng(self, scope: Scope, program: Program):
        # the fold is jitted once so deriving the step key is one cached
        # dispatch
        cnt = self._advance_rng_counter(scope, 1)
        seed = self._program_seed(program)
        if Executor._fold_rng is None:
            Executor._fold_rng = jax.jit(
                lambda s, c: jax.random.fold_in(jax.random.key(s), c))
        if getattr(self, "_seed_cache", None) is None or \
                self._seed_cache[0] != seed:
            self._seed_cache = (seed, jnp.int32(seed))
        return Executor._fold_rng(self._seed_cache[1], np.int32(cnt))

    def _next_rng_window(self, scope: Scope, program: Program,
                         n_steps: int):
        """Base key + starting global step index for a windowed run. The
        counter advances by n_steps, so the per-step keys the scan body
        derives — fold_in(key(seed), idx0 + i) — are EXACTLY the keys
        n_steps sequential single-step run() calls would draw."""
        cnt = self._advance_rng_counter(scope, n_steps)
        seed = self._program_seed(program)
        if getattr(self, "_base_key_cache", None) is None or \
                self._base_key_cache[0] != seed:
            self._base_key_cache = (seed, jax.random.key(seed))
        return self._base_key_cache[1], cnt

    def _run_window_fallback(self, program, feed, fetch_list, scope,
                             return_numpy, mesh, param_shardings, n_steps,
                             window_names):
        """Per-step loop with the windowed-run CONTRACT (slice i per
        step, one global rng step per slice, stacked fetches) for paths
        where one-dispatch scanning is unavailable — see the call site
        in run(). Each step re-enters run() with n_steps=1, so the
        per-path semantics (segment islands, interpreter, mesh
        placement) are exactly the sequential-loop ones."""
        from . import profiler as _profiler
        from . import async_overlap as _ao
        # sparse prefetch (docs/PS_DATA_PLANE.md "Async overlap"): with
        # the overlap plane on, window i+1's embedding ids are staged to
        # the prefetch thread BEFORE step i dispatches — its deduped
        # row fan-out runs while step i computes, and step i+1's
        # distributed_lookup_table consumes the buffered rows without
        # an RPC (the row-cache consult hook).
        plane = _ao.maybe_plane()
        plan = _ao.prefetch_plan(program) if plane is not None else ()

        def _slice(name, i):
            v = feed[name]
            a = v.array if isinstance(v, LoDTensor) else v
            return a[i]

        def _stage(i):
            for table, ids_name, eps in plan:
                if ids_name in window_names and ids_name in feed:
                    plane.stage(table, np.asarray(_slice(ids_name, i)),
                                list(eps))

        ctx = (_profiler.RecordEvent(f"window[{n_steps}]:fallback",
                                     cat="window")
               if _profiler.is_profiling() else contextlib.nullcontext())
        per_step = []
        with ctx:
            for i in range(n_steps):
                if plan and i + 1 < n_steps:
                    _stage(i + 1)
                f = {}
                for n, v in feed.items():
                    if n in window_names:
                        a = v.array if isinstance(v, LoDTensor) else v
                        f[n] = a[i]
                    else:
                        f[n] = v
                per_step.append(self.run(
                    program, feed=f, fetch_list=fetch_list, scope=scope,
                    return_numpy=return_numpy, mesh=mesh,
                    param_shardings=param_shardings))
        if not per_step or not per_step[0]:
            return per_step[-1] if per_step else []
        n_fetch = len(per_step[0])
        if return_numpy:
            return [np.stack([s[k] for s in per_step])
                    for k in range(n_fetch)]
        stacked = []
        for k in range(n_fetch):
            if any(s[k].lod() for s in per_step):
                raise NotImplementedError(
                    "windowed run cannot stack LoD-carrying fetches — "
                    "fetch dense vars or run per-step (n_steps=1)")
            stacked.append(
                LoDTensor(jnp.stack([s[k].array for s in per_step])))
        return stacked

    def _run_block_eager(self, block, scope: Scope, rng_base,
                         check_nan: Optional[bool] = None):
        """``check_nan``: None infers the per-op localizer from the
        flags (raise mode only — skip/rollback get the end-of-step
        fused check instead); True forces it regardless of action.
        listen_and_serv forces it for pserver optimize blocks, which
        run OUTSIDE Executor.run and would otherwise lose all guarding
        under skip/rollback (the server has no step epilogue — raising
        back to the trainer is its containment)."""
        for idx, op in enumerate(block.ops):
            self._run_op_eager(op, scope, rng_base, idx,
                               check_nan=check_nan)

    def _run_op_eager(self, op, scope: Scope, rng_base, idx: int = 0,
                      check_nan: Optional[bool] = None):
        from . import profiler as _profiler
        if _profiler.is_profiling():
            # per-op host span (reference operator.cc:948-977 RecordEvent
            # hooks around prepare/infer_shape/compute)
            with _profiler.RecordEvent(op.type):
                return self._run_op_eager_impl(op, scope, rng_base, idx,
                                               check_nan)
        return self._run_op_eager_impl(op, scope, rng_base, idx,
                                       check_nan)

    def _run_op_eager_impl(self, op, scope: Scope, rng_base, idx: int = 0,
                           check_nan: Optional[bool] = None):
        attrs = op.attrs
        if _op_is_stateful(op):
            attrs = dict(attrs)
            attrs["_ctx"] = ExecContext(scope, self, op, self.place, rng_base)
        ins = {}
        for slot, names in op.inputs.items():
            vals = []
            for n in names:
                v = scope.find_var(n)
                if v is None or not v.is_initialized():
                    vals.append(None)
                elif isinstance(v.value(), LoDTensor):
                    vals.append(v.value().array)
                else:
                    vals.append(None)  # stateful kernels read scope directly
            ins[slot] = vals

        def _scope_lod(n):
            v = scope.find_var(n)
            if v is not None and v.is_initialized() and isinstance(
                    v.value(), LoDTensor):
                return _normalize_lod(v.value().lod())
            return None
        in_lods = _collect_in_lods(op, _scope_lod)
        if _op_needs_lod(op):
            attrs = dict(attrs)
            attrs["_lod"] = in_lods
        outs = run_op(op.type, ins, attrs, op.outputs, op.inputs, rng_base,
                      idx)
        if check_nan is None:
            check_nan = (core.globals_["FLAGS_check_nan_inf"]
                         and core.globals_["FLAGS_nan_inf_action"]
                         == "raise")
        if check_nan:
            # raise-mode per-op localizer; skip/rollback use the
            # end-of-step fused health instead (no per-op host syncs)
            _check_op_outputs_finite(op, idx, outs)
        for slot, names in op.outputs.items():
            vals = (outs or {}).get(slot)
            if vals is None:
                continue
            for n, v in zip(names, vals):
                if v is not None and n != "@EMPTY@":
                    scope.var(n).set_value(LoDTensor(v))

        def _set_scope_lod(n, lv):
            v = scope.find_var(n)
            if v is not None and v.is_initialized() and isinstance(
                    v.value(), LoDTensor):
                v.value().set_lod([list(l) for l in lv] if lv else [])

        def _scope_len(n):
            v = scope.find_var(n)
            if (v is not None and v.is_initialized()
                    and isinstance(v.value(), LoDTensor)
                    and getattr(v.value().array, "ndim", 0)):
                return v.value().array.shape[0]
            return None
        _propagate_lods(op, outs, in_lods, _set_scope_lod, _scope_len)


def _check_op_outputs_finite(op, idx: int, outs) -> None:
    """raise-mode localizer (interpreter path). ONE device fetch per op:
    each float output contributes a fused ``isfinite().all()`` flag and
    the stacked flags cross to host together — the reference pays one
    blocking device→host copy PER OUTPUT (nan_inf_utils_detail.cc
    CheckVarHasNanOrInf), and so did this port before. On a trip the
    slow path re-walks the outputs and names the op index/type, output
    slot, var name, dtype, NaN/Inf counts, and the first offending flat
    indices — the FloatingPointError message the raise action exists
    for."""
    flat = []  # (slot, var name, value)
    for slot, vals in (outs or {}).items():
        if slot.startswith("_"):  # "_lod"-style metadata, not tensors
            continue
        names = op.outputs.get(slot) or []
        for k, v in enumerate(vals or []):
            if v is not None and hasattr(v, "dtype") \
                    and jnp.issubdtype(v.dtype, jnp.inexact):
                flat.append((slot,
                             names[k] if k < len(names) else f"[{k}]", v))
    if not flat:
        return
    flags = jnp.stack([jnp.all(jnp.isfinite(v)) for _, _, v in flat])
    host_flags = np.asarray(flags)  # the ONE host sync for this op
    if host_flags.all():
        return
    problems = []
    for ok_, (slot, name, v) in zip(host_flags, flat):
        if ok_:
            continue
        arr = np.asarray(v)
        bad = np.flatnonzero(~np.isfinite(arr.reshape(-1)))[:8].tolist()
        problems.append(
            f"output {slot} (var '{name}', dtype {arr.dtype}, shape "
            f"{tuple(arr.shape)}): {int(np.isnan(arr).sum())} NaN / "
            f"{int(np.isinf(arr).sum())} Inf, first offending flat "
            f"indices {bad}")
    raise FloatingPointError(
        f"NaN/Inf in output of op #{idx} '{op.type}': "
        + "; ".join(problems))


def _fetch_to_host(f) -> np.ndarray:
    """Fetched value → host numpy. In multi-process runs a fetched global
    array spans non-addressable devices: replicated values read the local
    copy, sharded values gather across processes (the reference pulls
    fetches to trainer rank over gRPC — operators/distributed; here the
    collective rides jax's runtime)."""
    if isinstance(f, jax.Array) and not f.is_fully_addressable:
        if f.sharding.is_fully_replicated:
            return np.asarray(f.addressable_data(0))
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(f, tiled=True))
    return np.asarray(f)


def _restore_fetch_dtype(program, name: str, arr: np.ndarray) -> np.ndarray:
    """Device integers are 32-bit by policy (core._to_device_array); widen
    a fetched int32/uint32 back to the program-declared 64-bit dtype so
    user-visible numpy matches the reference op contracts."""
    if arr.dtype not in (np.int32, np.uint32):
        return arr
    try:
        v = program.global_block()._find_var_recursive(name)
    except Exception:
        return arr
    want = getattr(v, "dtype", None) if v is not None else None
    if want is None:
        return arr
    try:  # var dtype may be a string ("int64") or a VarType enum
        np_want = np.dtype(want) if isinstance(want, str) \
            else np.dtype(core.dtype_to_np(want))
    except Exception:
        return arr
    if np_want in (np.dtype(np.int64), np.dtype(np.uint64)):
        return arr.astype(np_want)
    return arr


def _to_fetch_names(fetch_list) -> List[str]:
    names = []
    if fetch_list is None:
        return names
    if not isinstance(fetch_list, (list, tuple)):
        fetch_list = [fetch_list]
    for f in fetch_list:
        if isinstance(f, Variable):
            names.append(f.name)
        elif isinstance(f, str):
            names.append(f)
        elif isinstance(f, (list, tuple)):
            names.extend(_to_fetch_names(f))
        else:
            raise TypeError(f"bad fetch entry {f!r}")
    return names
