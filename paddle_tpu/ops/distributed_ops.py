"""Parameter-server ops — send / recv / barriers / listen_and_serv /
distributed_lookup_table (reference: paddle/fluid/operators/distributed_ops/
send_op.cc, recv_op.cc, send_barrier_op.cc, fetch_barrier_op.cc,
listen_and_serv_op.cc:333,110,226, distributed_lookup_table_op.cc,
checkpoint_notify_op.cc; RPC plane in ../fluid/ps_rpc.py).

All stateful host ops: the PS plane lives on TPU-VM hosts over DCN; the
dense data path on TPU uses ICI collectives instead (parallel/). Sync-mode
server semantics follow RunSyncLoop (listen_and_serv_op.cc:110): collect
each trainer's grads + a send barrier, SUM per grad name, run the optimize
blocks, then serve gets until the next round. Async follows RunAsyncLoop
(:226): apply a grad's optimize block on arrival.
"""
from __future__ import annotations

import itertools
import os
import socket
import threading
import time

import numpy as np
import jax.numpy as jnp

from .registry import register_op, register_grad_maker, first, seq, out
from ..fluid import core


def _client(ep):
    from ..fluid.ps_rpc import VarClient
    return VarClient.of(ep)


# shared fan-out pool for per-pserver RPC overlap (reference:
# parameter_prefetch.cc issues every section's RPC before waiting on any
# of them). Threads are IO-bound socket waiters, so a small shared pool
# is plenty; VarClient's per-endpoint channel pool keeps the concurrent
# calls from serializing on one socket.
_FANOUT_POOL = None
_FANOUT_LOCK = threading.Lock()


def _legacy_dataplane() -> bool:
    """PADDLE_TPU_PS_PICKLE_WIRE=1 = the full legacy data plane (serial
    shard walks, no dedup, no batched RPCs) — one source of truth in
    ps_rpc."""
    from ..fluid.ps_rpc import _pickle_wire_forced
    return _pickle_wire_forced()


def _fanout(tasks):
    """Run callables concurrently; return their results in order. The
    FIRST error wins — the rest are drained (awaited) first so no RPC is
    left in flight against a half-torn-down scope. The submitting
    thread's RPC call budget (serving deadline propagation,
    ps_rpc.call_budget) is re-installed on the pool threads — without
    it every sharded section RPC of a deadline-stamped request would
    run unbudgeted."""
    if len(tasks) == 1 or _legacy_dataplane():
        return [t() for t in tasks]
    global _FANOUT_POOL
    with _FANOUT_LOCK:
        if _FANOUT_POOL is None:
            from concurrent.futures import ThreadPoolExecutor
            _FANOUT_POOL = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="ps-fanout")
    from ..fluid import ps_rpc as _ps_rpc
    from ..fluid import telemetry as _telemetry
    budget = _ps_rpc.current_call_budget()
    # the submitting thread's TRACE context rides along with its budget:
    # every sharded section RPC of one lookup must carry the same trace
    # id or the pserver-side handler spans fall out of the request's
    # timeline (docs/OBSERVABILITY.md)
    tctx = _telemetry.current_trace()
    if budget is not None or tctx is not None:
        tasks = [(lambda t=t: _run_budgeted(t, budget, tctx))
                 for t in tasks]
    futs = [_FANOUT_POOL.submit(t) for t in tasks]
    results, first_err = [], None
    for f in futs:
        try:
            results.append(f.result())
        except BaseException as e:  # noqa: BLE001 — re-raised below
            if first_err is None:
                first_err = e
            results.append(None)
    if first_err is not None:
        raise first_err
    return results


def _run_budgeted(task, budget, tctx=None):
    from ..fluid import ps_rpc as _ps_rpc
    from ..fluid import telemetry as _telemetry
    import contextlib
    tcm = (_telemetry.trace_scope(adopt=tctx) if tctx is not None
           else contextlib.nullcontext())
    with tcm, _ps_rpc.call_budget(budget):
        return task()


def _np_of(scope, name):
    v = scope.find_var(name)
    if v is None or not v.is_initialized():
        return None
    val = v.value()
    if isinstance(val, core.SelectedRows):
        return val
    return np.asarray(val.array)


# --------------------------------------------------------------------------
# trainer-side ops
# --------------------------------------------------------------------------
# send op: vars whose scope slot was never initialized (a conditional
# branch that never ran, an optimizer slot created late) are SKIPPED with
# a one-time warning instead of shipping None into send_var and crashing
# the pserver handler
_warned_uninit_sends = set()


def _push_dense_batch(ep, items, tid, legacy=False):
    """Ship one endpoint's dense grads: with FLAGS_dgc on, eligible
    grads go out as top-k (indices, values) ``dgc_send`` frames with
    the unsent mass staying in the trainer's error-feedback residual
    (docs/PS_DATA_PLANE.md "Compression"); everything else takes the
    PR 4 coalesced ``send_vars_batch`` path. An old server without
    ``dgc_send`` ("no method" — nothing applied) gets the FULL
    accumulated grad dense instead, residual cleared, so the fallback
    neither loses nor double-sends mass; the miss is memoized."""
    from ..fluid import communicator as _comm
    from ..fluid.ps_rpc import send_vars_batch
    cli = _client(ep)
    rest = []
    if _comm.dgc_enabled() and not legacy:
        comp = _comm.dgc_compressor()
        for name, val in items:
            val = np.asarray(val)
            enc = (comp.compress(name, val)
                   if "dgc_send" not in cli._missing_methods else None)
            if enc is None:
                rest.append((name, val))
                continue
            idx, vals = enc
            try:
                cli.call("dgc_send", name=name, values=vals,
                         indices=idx, shape=list(val.shape),
                         trainer_id=tid)
            except RuntimeError as e:
                if "no method dgc_send" not in str(e):
                    raise
                cli._missing_methods.add("dgc_send")
                full = comp.restore_dense(name, idx, vals)
                rest.append((name, full.reshape(val.shape)))
    else:
        rest = [(n, v) for n, v in items]
    if not rest:
        return
    if len(rest) > 1 and not legacy:
        send_vars_batch(cli, rest, trainer_id=tid)
    else:
        for name, val in rest:
            cli.send_var(name, val, trainer_id=tid)


@register_op("send", stateful=True, no_grad=True,
             attr_defaults={"epmap": [], "trainer_id": 0})
def _send(ins, attrs):
    import logging
    from ..fluid.communicator import Communicator
    ctx = attrs["_ctx"]
    names = ctx.op.input("X")
    epmap = attrs.get("epmap") or []
    tid = int(attrs.get("trainer_id", 0))
    comm = Communicator.global_instance()
    dense_by_ep: dict = {}
    for i, name in enumerate(names):
        ep = epmap[i if i < len(epmap) else -1]
        val = _np_of(ctx.scope, name)
        if val is None:
            if name not in _warned_uninit_sends:
                _warned_uninit_sends.add(name)
                logging.getLogger("paddle_tpu.ps").warning(
                    "send op: var '%s' is uninitialized in this scope — "
                    "skipping its RPC to %s (warned once)", name, ep)
            continue
        if isinstance(val, core.SelectedRows):
            _client(ep).send_var(name, np.asarray(val.get_tensor().array),
                                 trainer_id=tid, rows=val.rows(),
                                 height=val.height())
        elif comm is not None:
            # async mode with a running Communicator: enqueue for the
            # merge thread (reference AsyncCommunicator::Send)
            comm.push(name, val, ep, trainer_id=tid)
        else:
            dense_by_ep.setdefault(ep, []).append((name, val))
    # dense grads coalesce into ONE batched RPC per endpoint (the dedup
    # token covers the batch, old servers get the per-var fallback —
    # ps_rpc.send_vars_batch; the legacy lane keeps one RPC per var);
    # FLAGS_dgc routes eligible grads through top-k compression first
    for ep, items in dense_by_ep.items():
        _push_dense_batch(ep, items, tid, legacy=_legacy_dataplane())
    return {}


# --------------------------------------------------------------------------
# geo async WAN lane (docs/PS_DATA_PLANE.md "Compression"): when
# FLAGS_async_staleness > 0, geo_sgd_send submits each DENSE delta-merge
# round (push delta → pull merged param) to the communicator's geo
# RoundPipeline instead of blocking the local step on the WAN RTT. The
# pipeline worker computes each round's REMOTE increment ("shift") by
# telescoping against the previous round's pull — shift_j = F_j -
# (F_{j-1} + sent_j) — and queues it FIFO; the op installs every queued
# shift at the next step boundary onto BOTH the param and its @GEO_OLD
# baseline, so local progress and the un-pushed residual survive the
# merge. One state per process, like the round pipeline (one trainer
# per process); the step-1 anchor resets it for a fresh job.
_GEO_ASYNC = {"last_f": {}, "shifts": None, "push_step": 0}
_GEO_ASYNC_LOCK = threading.Lock()


def _geo_async_reset():
    from collections import deque
    with _GEO_ASYNC_LOCK:
        _GEO_ASYNC["last_f"] = {}
        _GEO_ASYNC["last_f_sparse"] = {}
        _GEO_ASYNC["shifts"] = deque()
        _GEO_ASYNC["push_step"] = 0


def _geo_install_shifts(scope):
    """Apply every completed round's queued remote increment, FIFO.
    Shifts translate the param AND its @GEO_OLD baseline by the same
    amount, so the pending local delta (cur - old) is untouched.
    Sparse-table entries are row-keyed — ("rows", row_ids, shift_rows)
    — and translate only the touched rows of both tensors."""
    q = _GEO_ASYNC["shifts"]
    if not q:
        return
    while True:
        try:
            shift_map = q.popleft()
        except IndexError:
            break
        for name, shift in shift_map.items():
            var = scope.find_var(name)
            if var is None or not var.is_initialized():
                continue
            if isinstance(shift, tuple):
                rows, sh = shift[1], shift[2]
                if not np.any(sh):
                    continue
                cur = np.asarray(var.value().array).copy()
                cur[rows] += sh
                var.set_value(core.LoDTensor(jnp.asarray(cur)))
                old_var = scope.var(name + "@GEO_OLD")
                if old_var.is_initialized():
                    old = np.asarray(
                        old_var.get_tensor().array).copy()
                    old[rows] += sh
                    old_var.set_value(core.LoDTensor(old))
                continue
            if not np.any(shift):
                continue
            cur = np.asarray(var.value().array)
            var.set_value(core.LoDTensor(jnp.asarray(cur + shift)))
            old_var = scope.var(name + "@GEO_OLD")
            if old_var.is_initialized():
                old = np.asarray(old_var.get_tensor().array)
                old_var.set_value(core.LoDTensor(old + shift))


def _geo_dense_round_async(ctx, scope, names, epmap, tid, staleness):
    """Submit one dense delta-merge round to the geo RoundPipeline.

    Error feedback happens HERE, synchronously: ``old`` advances by
    exactly what this round will push (under FLAGS_dgc, only the top-k
    selection — the residual stays in cur-old and ships next round).
    The background closure pushes the captured payloads, pulls each
    merged param, and queues shift = fresh - (last_f + sent): with no
    remote regions both terms are the same fp add, so the shift is
    exactly zero and a single-region async run tracks the inline one."""
    from ..fluid import communicator as _comm
    pushes = []
    dgc = _comm.dgc_enabled()
    min_el = int(core.globals_["FLAGS_dgc_min_elements"])
    push_step = _GEO_ASYNC["push_step"]
    _GEO_ASYNC["push_step"] = push_step + 1
    for i, name in enumerate(names):
        ep = epmap[i if i < len(epmap) else -1]
        var = scope.find_var(name)
        if var is None or not var.is_initialized():
            continue
        cur = np.asarray(var.value().array)
        old_var = scope.var(name + "@GEO_OLD")
        old = np.asarray(old_var.get_tensor().array)
        delta = np.ascontiguousarray(cur - old)
        if dgc and delta.dtype == np.float32 and delta.size >= min_el:
            sparsity = _comm.DGCCompressor._sparsity_at(push_step)
            idx, vals = _comm.topk_sparsify(delta.reshape(-1), sparsity)
            sent = np.zeros(delta.size, delta.dtype)
            sent[idx] = vals
            sent = sent.reshape(delta.shape)
            _comm.dgc_compressor().note_external(
                delta.size, idx.size, delta.nbytes,
                idx.nbytes + vals.nbytes)
            pushes.append((name, ep, idx, vals, sent))
        else:
            sent = delta
            pushes.append((name, ep, None, None, sent))
        # error feedback: the baseline advances by the SENT part only
        old_var.set_value(core.LoDTensor(old + sent))
    if not pushes:
        return

    def do_geo_round():
        cli_of = _client
        shift_map = {}
        for name, ep, idx, vals, sent in pushes:
            cli = cli_of(ep)
            if idx is not None \
                    and "geo_delta#flat" not in cli._missing_methods:
                try:
                    cli.call("geo_delta", name=name, value=vals,
                             rows=idx, flat=True, trainer_id=tid)
                except (RuntimeError, TypeError) as e:
                    if "unexpected keyword" not in str(e) \
                            and "no method" not in str(e):
                        raise
                    # pre-compression server: ship the dense sent mass
                    # (same applied values — idx/vals scattered)
                    cli._missing_methods.add("geo_delta#flat")
                    cli.call("geo_delta", name=name, value=sent,
                             trainer_id=tid)
            else:
                cli.call("geo_delta", name=name, value=sent,
                         trainer_id=tid)
            fresh = np.asarray(cli.get_var(name, trainer_id=tid))
            last_f = _GEO_ASYNC["last_f"].get(name)
            if last_f is None or last_f.shape != fresh.shape:
                shift = np.zeros_like(fresh)
            else:
                shift = fresh - (last_f + sent)
            _GEO_ASYNC["last_f"][name] = fresh
            shift_map[name] = shift
        _GEO_ASYNC["shifts"].append(shift_map)

    _comm.geo_round_pipeline().submit(do_geo_round, staleness,
                                      label="geo_round")


def _geo_sparse_round_async(ctx, scope, sparse_names, epmap, n_dense,
                            tid, staleness):
    """Submit one sparse row-delta round to the geo RoundPipeline (the
    PR 11 remainder: these used to sync inline at every push point,
    stalling the local step on the WAN RTT even at staleness > 0).

    Same contract as the dense lane, row-keyed: error feedback happens
    HERE synchronously (@GEO_OLD's touched rows advance by exactly the
    pushed delta), the background closure pushes the row deltas, pulls
    the merged rows, and queues a per-row telescoped shift —
    shift_j[r] = F_j[r] - (F_{j-1}[r] + sent_j[r]) — installed FIFO
    onto the param AND the baseline at the next step boundary. A row's
    first-ever pull uses its baseline value at push time as the
    F_{j-1} estimate (the baseline tracks anchor + sent + installed
    shifts = our best estimate of the server row), so a single-region
    run's shifts are exactly zero and it tracks the inline path."""
    pushes = []
    for j, name in enumerate(sparse_names):
        ep_idx = n_dense + j
        ep = epmap[ep_idx if ep_idx < len(epmap) else -1]
        var = scope.find_var(name)
        if var is None or not var.is_initialized():
            continue
        cur = np.asarray(var.value().array)
        old_var = scope.var(name + "@GEO_OLD")
        if not old_var.is_initialized():
            old_var.set_value(core.LoDTensor(cur.copy()))
            continue
        old = np.asarray(old_var.get_tensor().array)
        delta = cur - old
        touched = np.where(np.abs(delta).reshape(len(delta), -1)
                           .max(axis=1) > 0)[0]
        if not len(touched):
            continue
        payload = np.ascontiguousarray(delta[touched])
        prev_est = old[touched].copy()
        pushes.append((name, ep, touched, payload, prev_est))
        # error feedback: baseline rows advance by the SENT delta only
        old = old.copy()
        old[touched] = cur[touched]
        old_var.set_value(core.LoDTensor(old))
    if not pushes:
        return
    from ..fluid import communicator as _comm

    def do_geo_sparse_round():
        shift_map = {}
        for name, ep, touched, payload, prev_est in pushes:
            cli = _client(ep)
            cli.call("geo_delta", name=name, value=payload,
                     rows=touched, trainer_id=tid)
            fresh_rows = np.asarray(cli.prefetch_rows(name, touched))
            lf = _GEO_ASYNC["last_f_sparse"].setdefault(name, {})
            shift = np.zeros_like(fresh_rows)
            for i, r in enumerate(touched):
                r = int(r)
                prev = lf.get(r)
                if prev is None or prev.shape != fresh_rows[i].shape:
                    prev = prev_est[i]
                shift[i] = fresh_rows[i] - (prev + payload[i])
                lf[r] = fresh_rows[i].copy()
            shift_map[name] = ("rows", touched, shift)
        _GEO_ASYNC["shifts"].append(shift_map)

    _comm.geo_round_pipeline().submit(do_geo_sparse_round, staleness,
                                      label="geo_sparse_round")


@register_op("geo_sgd_send", stateful=True, no_grad=True,
             attr_defaults={"epmap": [], "push_nums": 100, "trainer_id": 0,
                            "trainers": 1})
def _geo_sgd_send(ins, attrs):
    """GEO-SGD delta sync (reference: GeoSgdCommunicator,
    communicator.h:383): every ``push_nums`` local steps push
    (param - snapshot) to the param's pserver, pull the merged global
    param back, and reset the snapshot. Between syncs training is fully
    local, so the step stays on-device.

    With FLAGS_async_staleness > 0 the dense sync rides the geo
    RoundPipeline (see _GEO_ASYNC above): the push/pull round drains in
    the background while local steps continue, bounded at k rounds in
    flight, and FLAGS_dgc additionally top-k-sparsifies each delta with
    the residual kept in the @GEO_OLD baseline (old advances only by
    what was SENT — exact error feedback). Sparse tables ride the same
    pipeline with row-keyed deltas and per-row telescoped shifts
    (_geo_sparse_round_async, r20). At staleness 0 the path below is
    byte-for-byte the pre-compression inline code — bit-identical."""
    ctx = attrs["_ctx"]
    scope = ctx.scope
    names = ctx.op.input("Params")
    epmap = attrs.get("epmap") or []
    tid = int(attrs.get("trainer_id", 0))
    push_nums = max(1, int(attrs.get("push_nums", 100)))
    staleness = int(core.globals_["FLAGS_async_staleness"])

    if staleness > 0:
        # step boundary: land every completed background round first
        _geo_install_shifts(scope)

    cvar = scope.var("@GEO_STEP@")
    step = 0
    if cvar.is_initialized():
        step = int(np.asarray(cvar.get_tensor().array).reshape(-1)[0])
    step += 1
    cvar.set_value(core.LoDTensor(np.asarray([step], np.int64)))

    if step == 1:
        # anchor: snapshot the server's params (dense AND sparse tables)
        # as the delta baseline (reference GeoSgdCommunicator pulls at
        # init_worker; trainers and server share the startup init, so
        # this is the common start)
        if staleness > 0:
            _geo_async_reset()
        all_names = list(names) + list(ctx.op.input("SparseParams") or [])
        for i, name in enumerate(all_names):
            ep = epmap[i if i < len(epmap) else -1]
            fresh = np.asarray(_client(ep).get_var(name, trainer_id=tid))
            scope.var(name + "@GEO_OLD").set_value(
                core.LoDTensor(fresh.copy()))
            if staleness > 0 and name in names:
                _GEO_ASYNC["last_f"][name] = fresh.copy()
        return {}
    if step % push_nums != 0:
        return {}

    if staleness > 0:
        _geo_dense_round_async(ctx, scope, names, epmap, tid, staleness)
        # sparse tables ride the SAME pipeline now (r20; formerly they
        # synced inline even at staleness > 0 — the PR 11 remainder):
        # row-keyed deltas push/pull in the background and install as
        # per-row shifts at the next step boundary
        _geo_sparse_round_async(
            ctx, scope, list(ctx.op.input("SparseParams") or []),
            epmap, len(names), tid, staleness)
        return {}
    for i, name in enumerate(names):
        ep = epmap[i if i < len(epmap) else -1]
        cur = np.asarray(scope.find_var(name).value().array)
        old_var = scope.var(name + "@GEO_OLD")
        old = np.asarray(old_var.get_tensor().array)
        _client(ep).call("geo_delta", name=name,
                         value=np.ascontiguousarray(cur - old),
                         trainer_id=tid)
        fresh = np.asarray(_client(ep).get_var(name, trainer_id=tid))
        scope.find_var(name).set_value(
            core.LoDTensor(jnp.asarray(fresh)))
        old_var.set_value(core.LoDTensor(fresh.copy()))

    # sparse tables: push only the TOUCHED row deltas, pull those rows'
    # merged values back (reference GeoSgdCommunicator
    # SendUpdateSparseVars / RecvUpdateSparseVars)
    n_dense = len(names)
    for j, name in enumerate(ctx.op.input("SparseParams") or []):
        ep_idx = n_dense + j
        ep = epmap[ep_idx if ep_idx < len(epmap) else -1]
        cur = np.asarray(scope.find_var(name).value().array)
        old_var = scope.var(name + "@GEO_OLD")
        if not old_var.is_initialized():
            old_var.set_value(core.LoDTensor(cur.copy()))
            continue
        old = np.asarray(old_var.get_tensor().array)
        delta = cur - old
        touched = np.where(np.abs(delta).reshape(len(delta), -1)
                           .max(axis=1) > 0)[0]
        if len(touched):
            _client(ep).call("geo_delta", name=name,
                             value=np.ascontiguousarray(delta[touched]),
                             rows=touched, trainer_id=tid)
            fresh_rows = np.asarray(
                _client(ep).prefetch_rows(name, touched))
            cur = cur.copy()
            cur[touched] = fresh_rows
            scope.find_var(name).set_value(
                core.LoDTensor(jnp.asarray(cur)))
        old_var.set_value(core.LoDTensor(cur.copy()))
    return {}


@register_op("recv", stateful=True, no_grad=True,
             attr_defaults={"epmap": [], "trainer_id": 0})
def _recv(ins, attrs):
    ctx = attrs["_ctx"]
    names = ctx.op.output("Out")
    epmap = attrs.get("epmap") or []
    tid = int(attrs.get("trainer_id", 0))
    from ..fluid.communicator import Communicator
    comm = Communicator.global_instance()
    if comm is not None:
        # fully-async mode (reference AsyncCommunicator::RecvThread):
        # never block the step on a pull — register the set once, let
        # the communicator's background thread refresh a double buffer
        # at its recv interval, and install only the newest completed
        # buffer here at the step boundary. The FIRST call primes the
        # buffer synchronously so params exist before step 1 computes.
        pairs = [(n, epmap[i if i < len(epmap) else -1])
                 for i, n in enumerate(names)]
        comm.register_recv(pairs, trainer_id=tid)
        buf = comm.take_fresh_recv()
        if buf is None and not getattr(comm, "_recv_primed", False):
            buf = comm.recv()
            comm._recv_primed = True
        if buf:
            for name, arr in buf.items():
                if name in names:
                    ctx.scope.var(name).set_value(
                        core.LoDTensor(jnp.asarray(arr)))
        # async mode has no fetch_barrier, so the save/shrink cron
        # (FLAGS_ps_shrink_every_steps) ticks here — the recv op is
        # the one per-step boundary the async trainer still crosses
        _shrink_cron_tick(list(dict.fromkeys(epmap)), tid)
        return {}
    by_ep: dict = {}
    for i, name in enumerate(names):
        ep = epmap[i if i < len(epmap) else -1]
        by_ep.setdefault(ep, []).append(name)
    for ep, ep_names in by_ep.items():
        cli = _client(ep)
        if len(ep_names) == 1 or _legacy_dataplane() \
                or "get_vars_batch" in cli._missing_methods:
            got = [cli.get_var(n, trainer_id=tid) for n in ep_names]
        else:
            # one batched fetch per endpoint (get_vars_batch; falls back
            # per-var when an old server doesn't know the method — any
            # other failure propagates; the miss is memoized so only
            # the first call pays the probe)
            try:
                got = cli.call("get_vars_batch", names=ep_names,
                               trainer_id=tid)
            except RuntimeError as e:
                if "no method get_vars_batch" not in str(e):
                    raise
                cli._missing_methods.add("get_vars_batch")
                got = [cli.get_var(n, trainer_id=tid) for n in ep_names]
        for name, arr in zip(ep_names, got):
            ctx.scope.var(name).set_value(
                core.LoDTensor(jnp.asarray(arr)))
    return {}


_SHRINK_CRON_STEPS: dict = {}   # (endpoints) -> trainer-0 round count
_shrink_cron_warned: set = set()


def reset_shrink_cron() -> None:
    """Forget cron round counts (tests / a new transpiled job)."""
    _SHRINK_CRON_STEPS.clear()


def _shrink_cron_tick(endpoints, tid) -> None:
    """Trainer-driven shrink schedule (FLAGS_ps_shrink_every_steps — the
    PSLib save/shrink cron analogue, docs/PS_DATA_PLANE.md): trainer 0
    counts its completed sync rounds per endpoint set and, every N-th,
    fires ONE `table_shrink` admin RPC at each pserver (decay/threshold
    from FLAGS_ps_shrink_decay/_threshold). The RPC lands between
    rounds — the server runs it under the grad lock — so training never
    observes a half-shrunk table. Best-effort like the reference cron:
    a failed shrink warns (once per endpoint) and training continues;
    evidence is the server-side slab "shrink_runs"/"shrunk_rows"
    counters."""
    every = int(core.globals_["FLAGS_ps_shrink_every_steps"] or 0)
    if every <= 0 or tid != 0 or not endpoints:
        return
    key = tuple(endpoints)
    n = _SHRINK_CRON_STEPS.get(key, 0) + 1
    _SHRINK_CRON_STEPS[key] = n
    if n % every:
        return
    import logging
    decay = float(core.globals_["FLAGS_ps_shrink_decay"])
    threshold = float(core.globals_["FLAGS_ps_shrink_threshold"])
    for ep in dict.fromkeys(endpoints):
        try:
            _client(ep).call("table_shrink", decay=decay,
                             threshold=threshold)
        except Exception as e:  # noqa: BLE001 — cron is best-effort
            if ep not in _shrink_cron_warned:
                _shrink_cron_warned.add(ep)
                logging.getLogger("paddle_tpu.ps").warning(
                    "shrink cron: table_shrink on %s failed (%r) — "
                    "continuing (warned once)", ep, e)


def _barrier_op(kind):
    def _kernel(ins, attrs):
        ctx = attrs["_ctx"]
        tid = int(attrs.get("trainer_id", 0))
        eps = list(dict.fromkeys(attrs.get("endpoints") or []))
        for ep in eps:
            _client(ep).barrier(kind, trainer_id=tid)
        if kind == "fetch":
            # the fetch barrier closes trainer 0's sync round — the
            # between-rounds window the shrink cron fires in
            _shrink_cron_tick(eps, tid)
        return {}
    return _kernel


register_op("send_barrier", stateful=True, no_grad=True,
            attr_defaults={"endpoints": [], "trainer_id": 0})(
    _barrier_op("send"))
register_op("fetch_barrier", stateful=True, no_grad=True,
            attr_defaults={"endpoints": [], "trainer_id": 0})(
    _barrier_op("fetch"))


@register_op("ps_round", stateful=True, no_grad=True,
             attr_defaults={"grad_epmap": [], "param_epmap": [],
                            "endpoints": [], "trainer_id": 0})
def _ps_round(ins, attrs):
    """The whole sync comm tail — push grads → send barrier → pull
    params → fetch barrier — as ONE op, emitted by the transpiler's
    async-mode rewrite (docs/PS_DATA_PLANE.md "Async overlap").

    ``FLAGS_async_staleness = 0``: the round runs INLINE, replaying the
    exact RPC sequence of the pre-overlap send/send_barrier/recv/
    fetch_barrier tail — the trajectory is bit-identical to sync mode
    (the golden-oracle contract; tested on the 3-trainer wide_deep
    agreement run).

    ``FLAGS_async_staleness = k > 0``: the round is SUBMITTED to the
    communicator's RoundPipeline and the op returns immediately, so the
    executor launches window i+1 while round i's wire work drains in
    the background; at most k submitted-but-unacked rounds may be in
    flight (a full pipe blocks here — backpressure, not divergence).
    Each round's pulled params land in the pipeline's latest-pull
    buffer; the newest completed buffer is installed into the scope at
    this (step-boundary) call — the double-buffered dense pull. A
    background round failure re-raises TYPED at the next submit."""
    import logging
    ctx = attrs["_ctx"]
    scope = ctx.scope
    grad_names = list(ctx.op.input("X") or [])
    param_names = list(ctx.op.output("Out") or [])
    gmap = [str(e) for e in (attrs.get("grad_epmap") or [])]
    pmap = [str(e) for e in (attrs.get("param_epmap") or [])]
    beps = list(dict.fromkeys(
        str(e) for e in (attrs.get("endpoints") or [])))
    tid = int(attrs.get("trainer_id", 0))
    legacy = _legacy_dataplane()

    # snapshot grads NOW (jax arrays are immutable, so holding the refs
    # is safe while the next step replaces the scope slots); host
    # conversion happens inside the round so the D2H wait overlaps too
    send_groups: dict = {}
    for i, name in enumerate(grad_names):
        ep = gmap[i if i < len(gmap) else -1]
        val = _np_of(scope, name)
        if val is None:
            if name not in _warned_uninit_sends:
                _warned_uninit_sends.add(name)
                logging.getLogger("paddle_tpu.ps").warning(
                    "ps_round: var '%s' is uninitialized in this scope "
                    "— skipping its push to %s (warned once)", name, ep)
            continue
        send_groups.setdefault(ep, []).append((name, val))
    recv_groups: dict = {}
    for i, name in enumerate(param_names):
        ep = pmap[i if i < len(pmap) else -1]
        recv_groups.setdefault(ep, []).append(name)

    def do_round():
        for ep, items in send_groups.items():
            dense = []
            for n, v in items:
                if isinstance(v, core.SelectedRows):
                    _client(ep).send_var(
                        n, np.asarray(v.get_tensor().array),
                        trainer_id=tid, rows=v.rows(),
                        height=v.height())
                else:
                    dense.append((n, np.asarray(v)))
            if dense:
                _push_dense_batch(ep, dense, tid, legacy=legacy)
        for ep in beps:
            _client(ep).barrier("send", trainer_id=tid)
        pulled = {}
        for ep, names in recv_groups.items():
            cli = _client(ep)
            if len(names) == 1 or legacy \
                    or "get_vars_batch" in cli._missing_methods:
                got = [cli.get_var(n, trainer_id=tid) for n in names]
            else:
                try:
                    got = cli.call("get_vars_batch", names=names,
                                   trainer_id=tid)
                except RuntimeError as e:
                    if "no method get_vars_batch" not in str(e):
                        raise
                    cli._missing_methods.add("get_vars_batch")
                    got = [cli.get_var(n, trainer_id=tid)
                           for n in names]
            pulled.update(zip(names, got))
        for ep in beps:
            _client(ep).barrier("fetch", trainer_id=tid)
        return pulled

    def install(pulled):
        for name, arr in pulled.items():
            scope.var(name).set_value(core.LoDTensor(jnp.asarray(arr)))

    staleness = int(core.globals_["FLAGS_async_staleness"])
    if staleness <= 0:
        install(do_round())
        # round complete — same cron point as the sync fetch_barrier
        _shrink_cron_tick(beps, tid)
        return {}
    from ..fluid import communicator as _comm
    pipe = _comm.round_pipeline()
    pipe.submit(do_round, staleness, label="ps_round")
    fresh = pipe.take_fresh_pulls()
    if fresh:
        install(fresh)
    # async rounds: count at submit — the shrink RPC itself serializes
    # on the server's grad lock, so landing mid-drain is still safe
    _shrink_cron_tick(beps, tid)
    return {}


@register_op("checkpoint_notify", stateful=True, no_grad=True,
             attr_defaults={"epmap": [], "dir": ""})
def _checkpoint_notify(ins, attrs):
    for ep in dict.fromkeys(attrs.get("epmap") or []):
        _client(ep).call("checkpoint", dir=attrs.get("dir", ""))
    return {}


def _table_dim(ctx, w_name):
    """Embedding dim of the (possibly remote-only) table, from the block
    var desc; last resort 1 when the program never declared the var."""
    try:
        v = ctx.op.block.var(w_name)
        shape = list(getattr(v, "shape", None) or [])
        if shape and int(shape[-1]) > 0:
            return int(shape[-1])
    except Exception:
        pass
    return 1


def _table_dtype(ctx, w_name):
    """The table's declared dtype from the block var desc — the empty-ids
    fast path must carry it (an fp16/bf16 table must not silently upcast
    its zero-row result to float32)."""
    try:
        v = ctx.op.block.var(w_name)
        return jnp.dtype(core.dtype_to_np(v.dtype))
    except Exception:
        return jnp.float32


def _pull_rows_sharded(eps, w_name, uniq, prefetch=False):
    """One deduped row pull, row-sharded across ``eps`` by
    ``id %% n_pservers`` with every per-pserver section RPC issued
    concurrently (reference parameter_prefetch overlap). ``uniq`` must
    hold distinct ids; returns [len(uniq), dim] in input order.
    ``prefetch=True`` tags the RPCs as async-overlap early fetches for
    the server-side stats counter."""
    uniq = np.asarray(uniq)
    if len(eps) == 1:
        return np.asarray(_client(eps[0]).prefetch_rows(
            w_name, uniq, prefetch=prefetch))
    shard = uniq % len(eps)
    sels = [np.where(shard == k)[0] for k in range(len(eps))]
    live = [(ep, sel) for ep, sel in zip(eps, sels) if len(sel)]

    def _pull(ep, sel):
        return np.asarray(_client(ep).prefetch_rows(
            w_name, uniq[sel], prefetch=prefetch))

    parts = _fanout([(lambda ep=ep, sel=sel: _pull(ep, sel))
                     for ep, sel in live])
    rows_u = np.empty((len(uniq), parts[0].shape[-1]), parts[0].dtype)
    for (_ep, sel), part in zip(live, parts):
        rows_u[sel] = part
    return rows_u


@register_op("distributed_lookup_table", stateful=True,
             attr_defaults={"epmap": [], "table_names": [], "padding_idx": -1,
                            "is_distributed": True, "trainer_id": 0})
def _distributed_lookup_table(ins, attrs):
    """Pulls embedding rows from the pserver-resident table, row-sharded
    across ALL endpoints in epmap by ``id %% n_pservers`` (reference:
    distributed_lookup_table_op.cc over parameter_prefetch.cc, which
    splits ids per-section the same way).

    Serving mode (docs/SERVING.md): when a row cache is installed
    (``ps_rpc.install_row_cache`` — the ServingEngine's EmbeddingCache),
    the deduped id set consults it first and only the misses fan out;
    a fully-hit lookup issues ZERO RPCs. Training paths never install a
    cache, so this is dead code there."""
    from ..fluid import ps_rpc as _ps_rpc
    ctx = attrs["_ctx"]
    id_names = ctx.op.input("Ids")
    w_name = (attrs.get("table_names") or ctx.op.input("W"))[0]
    eps = [e for e in (attrs.get("epmap") or []) if e] or [None]
    outs = []
    for nm in id_names:
        ids = np.asarray(ctx.scope.find_var(nm).value().array).reshape(-1)
        if len(ids) == 0:
            # legitimately empty id batch: no RPC; the result must still
            # carry the table's embedding dim AND dtype or downstream
            # ops reject the shape / silently upcast (ADVICE r2)
            outs.append(jnp.zeros((0, _table_dim(ctx, w_name)),
                                  _table_dtype(ctx, w_name)))
            continue
        # duplicate-id dedup: a CTR batch repeats hot ids heavily — pull
        # each distinct row ONCE and scatter back via the inverse map
        # (reference parameter_prefetch merges ids per section the same
        # way); cuts the payload by the batch's duplication factor
        if _legacy_dataplane():
            uniq, inv = ids, np.arange(len(ids))
        else:
            uniq, inv = np.unique(ids, return_inverse=True)
        cache = _ps_rpc.current_row_cache()
        if cache is not None:
            rows_u = cache.lookup(
                w_name, uniq,
                lambda miss: _pull_rows_sharded(eps, w_name, miss))
        else:
            rows_u = _pull_rows_sharded(eps, w_name, uniq)
        outs.append(jnp.asarray(rows_u[inv]))
    return {"Outputs": outs}


def _program_has_ps_round(program) -> bool:
    """Whether the trainer program was async-rewritten (ps_round tail);
    cached per program version."""
    cached = program.__dict__.get("_has_ps_round")
    if cached is None or cached[0] != program._version:
        has = any(op.type == "ps_round"
                  for op in program.global_block().ops)
        program.__dict__["_has_ps_round"] = cached = \
            (program._version, has)
    return cached[1]


@register_grad_maker("distributed_lookup_table")
def _dist_lookup_grad_maker(op, grad_map):
    return [{
        "type": "distributed_lookup_table_grad",
        "inputs": {"Ids": op.input("Ids"), "W": op.input("W"),
                   "Outputs@GRAD": [grad_map[n]
                                    for n in op.output("Outputs")]},
        "outputs": {},
        "attrs": {k: v for k, v in op.attrs.items()
                  if not k.startswith("_")},
    }]


@register_op("distributed_lookup_table_grad", stateful=True, no_grad=True,
             attr_defaults={"epmap": [], "table_names": [], "trainer_id": 0})
def _distributed_lookup_table_grad(ins, attrs):
    """Pushes SelectedRows gradients back, row-sharded across epmap the
    same way the forward pull routes ids."""
    from ..fluid import ps_rpc as _ps_rpc
    ctx = attrs["_ctx"]
    id_names = ctx.op.input("Ids")
    w_name = (attrs.get("table_names") or ctx.op.input("W"))[0]
    eps = [e for e in (attrs.get("epmap") or []) if e] or [None]
    tid = int(attrs.get("trainer_id", 0))
    g_names = ctx.op.input("Outputs@GRAD")
    # async pushes require the ps_round tail, not just the flag: in a
    # program still carrying the plain send_barrier tail (flag flipped
    # after transpile) a backgrounded push could land AFTER the
    # main-thread barrier released its round — a phantom next-round
    # arrival — and with no ps_round submit()/drain() on this program
    # a failed push's deferred error would never re-raise
    overlap = int(core.globals_["FLAGS_async_staleness"]) > 0 \
        and _program_has_ps_round(ctx.op.block.program)
    for nm, gn in zip(id_names, g_names):
        ids = np.asarray(ctx.scope.find_var(nm).value().array).reshape(-1)
        if len(ids) == 0:
            continue  # nothing to push, no RPC
        g = np.asarray(ctx.scope.find_var(gn).value().array)
        g = g.reshape(len(ids), -1)
        # pre-merge duplicate rows client-side: the server applies ONE
        # row per distinct id (sum of the duplicates), the payload
        # shrinks by the duplication factor. NOT gated by the legacy
        # lane: merging changes fp accumulation ORDER, and every
        # legacy-gated difference must be numerics-exact
        # (wire/fan-out/pool/coalescing/lookup-dedup all are)
        uniq, inv = np.unique(ids, return_inverse=True)
        if len(uniq) < len(ids):
            merged = np.zeros((len(uniq), g.shape[1]), g.dtype)
            np.add.at(merged, inv, g)
            ids, g = uniq, merged
        # async overlap: the prefetch buffer must drop its copies of
        # the rows this push dirties BEFORE the push even enqueues —
        # inline on the main thread, so no later lookup can race a
        # known-dirty row (docs/PS_DATA_PLANE.md "Async overlap")
        cache = _ps_rpc.current_row_cache()
        if cache is not None and hasattr(cache, "invalidate_rows"):
            try:
                # same-process train+serve: the push instant IS the
                # event time for the freshness histogram
                cache.invalidate_rows(w_name, ids, t_event=time.time())
            except TypeError:
                cache.invalidate_rows(w_name, ids)
        # cross-process half (docs/SERVING.md "Fleet"): fan the same
        # pushed-row invalidation to every REMOTE serving cache via the
        # fleet publisher — enqueue-only here (subscribers long-poll),
        # so the push path never blocks on a slow serving box
        pub = _ps_rpc.current_invalidation_publisher()
        if pub is not None:
            pub.publish(w_name, ids)

        def _push_all(ids=ids, g=g):
            if len(eps) == 1:
                _client(eps[0]).send_var(w_name + "@GRAD", g,
                                         trainer_id=tid, rows=ids,
                                         height=0)
                return
            # concurrent per-pserver sends, first error wins (fan-out
            # like the forward pull)
            shard = ids % len(eps)
            sels = [np.where(shard == k)[0] for k in range(len(eps))]
            live = [(ep, sel) for ep, sel in zip(eps, sels) if len(sel)]

            def _push(ep, sel):
                _client(ep).send_var(w_name + "@GRAD", g[sel],
                                     trainer_id=tid, rows=ids[sel],
                                     height=0)

            _fanout([(lambda ep=ep, sel=sel: _push(ep, sel))
                     for ep, sel in live])

        if overlap:
            # ride the round pipeline's FIFO: the push lands after the
            # previous round's release and before this round's sends —
            # exactly where the inline path would have put it — while
            # the main thread keeps computing. Errors surface typed at
            # the next ps_round submit.
            from ..fluid import communicator as _comm
            _comm.round_pipeline().submit_task(
                _push_all, label=f"sparse_push:{w_name}")
        else:
            _push_all()
    return {}


_SPILL_PATH_SEQ = itertools.count()


def _safe_name(name: str) -> str:
    """Filesystem-safe var/section name — ONE collision-sensitive rule
    shared by every spill/staging path builder (always paired with a
    uniquifying sequence, since the mapping is lossy)."""
    return "".join(c if c.isalnum() else "_" for c in name)


@register_op("lazy_table_init", stateful=True, no_grad=True,
             attr_defaults={"height": 0, "dim": 0, "seed": 0,
                            "scale": 0.0, "max_rows": 0})
def _lazy_table_init(ins, attrs):
    """Initializes a pserver var as a LazyEmbeddingTable: rows materialize
    on first touch, so the logical [height, dim] never allocates
    (reference: fleet_wrapper.h DownpourSparseTable pull-creates).

    Capacity tier (docs/PS_DATA_PLANE.md "Capacity tier"): the spill/
    gating FLAGS are read HERE, at pserver startup — env-settable, so
    subprocess pservers of one bench/test lane configure the tier
    without new program attrs (the async-overlap flag precedent). With
    the flags at their defaults the table is the exact pre-tier slab."""
    ctx = attrs["_ctx"]
    name = ctx.op.output("Out")[0]
    scale = float(attrs.get("scale") or 0.0)
    tier_kw = {}
    spill_dir = str(core.globals_["FLAGS_ps_slab_spill_dir"] or "")
    if spill_dir:
        hot = int(core.globals_["FLAGS_ps_slab_hot_rows"])
        if hot <= 0:
            raise ValueError(
                "FLAGS_ps_slab_spill_dir is set but "
                "FLAGS_ps_slab_hot_rows is 0 — the spill tier needs a "
                "hot-set bound (silently ignoring the spill dir would "
                "run the table unbounded in RAM)")
        # per-process sequence: two table names that sanitize to the
        # same string (or a handoff-rebuilt replacement) must never
        # open — and truncate — each other's live log
        tier_kw = dict(
            spill_path=os.path.join(
                spill_dir,
                f"{_safe_name(name)}-{os.getpid()}"
                f"-i{next(_SPILL_PATH_SEQ)}.slab"),
            hot_rows=hot,
            at_rest_quant=str(
                core.globals_["FLAGS_ps_at_rest_quant"] or ""),
            spill_seg_rows=int(core.globals_["FLAGS_ps_slab_seg_rows"]),
            track_scores=(True if core.globals_[
                "FLAGS_ps_slab_track_scores"] else None))
    thr = int(core.globals_["FLAGS_ps_entry_threshold"])
    if thr > 1:
        tier_kw["entry_threshold"] = thr
    # score tracking without the spill tier: FLAGS_ps_slab_track_scores
    # alone makes the table shrinkable (the cron's table_shrink needs
    # per-row touch scores; an online-learning pserver wants idle rows
    # decaying out whether or not it also spills). max_rows-bounded
    # tables keep their LRU semantics — the tier would reject the combo.
    if core.globals_["FLAGS_ps_slab_track_scores"] \
            and "track_scores" not in tier_kw \
            and not int(attrs.get("max_rows") or 0):
        tier_kw["track_scores"] = True
    tbl = core.LazyEmbeddingTable(
        height=int(attrs["height"]), dim=int(attrs["dim"]),
        seed=int(attrs.get("seed", 0)),
        scale=scale if scale > 0 else None,
        max_rows=int(attrs.get("max_rows") or 0) or None, **tier_kw)
    # a startup re-run over a scope already holding a tiered table
    # must release the old spill log (every replacement path does)
    from ..fluid import io as fio
    fio._drop_replaced_table(ctx.scope.find_var(name))
    ctx.scope.var(name).set_value(tbl)
    return {}


# --------------------------------------------------------------------------
# split/merge helpers for sharded sparse ids (reference: split_ids_op.cc,
# merge_ids_op.cc — used when a table spans several pservers)
# --------------------------------------------------------------------------
@register_op("split_ids", stateful=True, no_grad=True)
def _split_ids(ins, attrs):
    ctx = attrs["_ctx"]
    ids = np.asarray(
        ctx.scope.find_var(ctx.op.input("Ids")[0]).value().array).reshape(-1)
    n = len(ctx.op.output("Out"))
    return {"Out": [jnp.asarray(ids[ids % n == k]) for k in range(n)]}


@register_op("merge_ids", stateful=True, no_grad=True)
def _merge_ids(ins, attrs):
    ctx = attrs["_ctx"]
    ids = np.asarray(
        ctx.scope.find_var(ctx.op.input("Ids")[0]).value().array).reshape(-1)
    n = len(ctx.op.input("X"))
    parts = [np.asarray(ctx.scope.find_var(nm).value().array)
             for nm in ctx.op.input("X")]
    dim = parts[0].shape[-1]
    merged = np.zeros((len(ids), dim), parts[0].dtype)
    counters = [0] * n
    for i, idv in enumerate(ids):
        k = int(idv) % n
        merged[i] = parts[k][counters[k]]
        counters[k] += 1
    return {"Out": [jnp.asarray(merged)]}


# --------------------------------------------------------------------------
# listen_and_serv (reference: listen_and_serv_op.cc)
# --------------------------------------------------------------------------
@register_op("listen_and_serv", stateful=True, no_grad=True,
             attr_defaults={"endpoint": "", "sync_mode": True, "Fanin": 1,
                            "grad_to_block_id": [], "sparse_lr": 0.01,
                            "distributed_mode": 0,
                            # elastic membership (docs/FAULT_TOLERANCE.md
                            # "Elastic membership"): the full slot list,
                            # whether this process starts as a warm
                            # standby (drain destination / replica), the
                            # slot it replicates, and the PHYSICAL
                            # endpoint to bind when serving a slot
                            # program at another address
                            "pserver_endpoints": [], "standby": False,
                            "replica_of": "", "bind_endpoint": ""})
def _listen_and_serv(ins, attrs):
    """Server loop: blocks until a stop RPC (parity with RunImpl's
    server_thread join, listen_and_serv_op.cc:382)."""
    from ..fluid import io as fio
    from ..fluid import ps_membership
    from ..fluid.ps_rpc import (BarrierManager, HeartBeatMonitor,
                                VarClient, VarServer,
                                note_request_token_applied)
    ctx = attrs["_ctx"]
    scope, executor = ctx.scope, ctx.executor
    endpoint = attrs["endpoint"]
    sync = bool(attrs.get("sync_mode", True))
    fanin = int(attrs.get("Fanin", 1))
    optimize_blocks = attrs.get("optimize_blocks") or []
    grad_to_block = dict(
        kv.split(":") for kv in attrs.get("grad_to_block_id") or [])
    sparse_lr = float(attrs.get("sparse_lr", 0.01))

    # ---- elastic membership plane -------------------------------------
    # ``endpoint`` is the SLOT name (what the transpiler baked into every
    # program); ``bind`` is where THIS process actually listens — they
    # differ for standbys/replicas serving a slot program elsewhere.
    bind = str(attrs.get("bind_endpoint") or "") or endpoint
    slot_eps = [str(e) for e in (attrs.get("pserver_endpoints") or [])] \
        or [endpoint]
    replica_of = str(attrs.get("replica_of") or "")
    standby = bool(attrs.get("standby", False)) or bool(replica_of)
    membership = ps_membership.MembershipPlane(
        slot=endpoint, bind=bind,
        view=ps_membership.ClusterView.initial(slot_eps),
        state=(ps_membership.STANDBY if standby
               else ps_membership.ACTIVE),
        replica_of=replica_of)

    # ONE lock guards grad state for send/geo handlers AND backs the
    # BarrierManager's condition — the release action (aggregate +
    # optimize) runs holding it, so it can't race a straggler send.
    # pending: dense grads per name; pending_sparse: row grads as
    # (trainer_id, seq, name, value, rows) — in SYNC mode sparse applies
    # are DEFERRED to the barrier release (reference RunSyncLoop applies
    # everything after the send barrier), so every trainer's pulls
    # within a round see the same pre-round table, and the release
    # applies entries in a deterministic (trainer, seq) order.
    lock = threading.RLock()
    state = {"pending": {}, "pending_sparse": [], "sparse_seq": 0}

    # numeric fault plane, pserver side (FLAGS_ps_reject_nonfinite —
    # docs/FAULT_TOLERANCE.md "Numeric faults"): trip counters surface
    # through the built-in "stats" RPC under the "health" key. They get
    # their OWN innermost lock (like VarServer's _stats_lock) so a
    # monitoring stats RPC never blocks behind an in-flight sync
    # optimize round holding the grad lock.
    health = {"dropped_sparse_rows": 0, "dropped_dense_updates": 0,
              "rejected_calls": 0, "per_var": {}}
    health_lock = threading.Lock()
    # async-overlap observability: row pulls tagged prefetch=True (the
    # trainer-side prefetch thread's early fetches) — shares the
    # innermost counter lock with the health counters
    prefetch_stats = {"calls": 0, "rows": 0}

    def _bump_health(key, name, n):
        with health_lock:
            health[key] += n
            health["per_var"][name] = health["per_var"].get(name, 0) + n

    def _guard_nonfinite(name, value, rows, trainer_id):
        """Apply FLAGS_ps_reject_nonfinite to one incoming update.
        Returns (value, rows, apply?) — sparse updates drop only their
        non-finite rows, a non-finite dense update drops wholesale;
        "reject" raises NumericFaultError back to the SENDING trainer
        (typed across the wire), leaving server state untouched. The
        checks run on host numpy — the grads already live there."""
        mode = str(core.globals_["FLAGS_ps_reject_nonfinite"] or "")
        if not mode:
            return value, rows, True
        value = np.asarray(value)
        if not np.issubdtype(value.dtype, np.floating):
            return value, rows, True
        if rows is not None and len(rows) == 0:
            # benign no-op update (public send_var allows it): nothing
            # to check, and reshape(0, -1) cannot infer a dimension
            return value, rows, False
        if rows is not None:
            n = len(rows)
            if value.shape[0] != n:
                # flat payload: row-major it so per-row masking works
                value = value.reshape(n, -1)
            # check on a 2-D VIEW; the clean pass-through and the
            # filtered value keep the sender's original shape (a 1-D
            # payload must not come back (n, 1) just because the guard
            # flag is on)
            per_row = np.isfinite(value.reshape(n, -1)).all(axis=1)
            if per_row.all():
                return value, rows, True
            n_bad = int((~per_row).sum())
            if mode == "reject":
                _bump_health("rejected_calls", name, 1)
                raise core.NumericFaultError(
                    f"pserver rejected sparse grad '{name}' from trainer "
                    f"{trainer_id}: {n_bad}/{len(rows)} non-finite rows "
                    f"(FLAGS_ps_reject_nonfinite=reject)")
            _bump_health("dropped_sparse_rows", name, n_bad)
            return (value[per_row],
                    np.asarray(rows).reshape(-1)[per_row], True)
        if np.isfinite(value).all():
            return value, rows, True
        if mode == "reject":
            _bump_health("rejected_calls", name, 1)
            raise core.NumericFaultError(
                f"pserver rejected dense update '{name}' from trainer "
                f"{trainer_id}: non-finite values "
                f"(FLAGS_ps_reject_nonfinite=reject)")
        _bump_health("dropped_dense_updates", name, 1)
        return value, rows, False

    # failure-detection cadence is deploy-tunable (tests shrink it to
    # seconds; reference FLAGS_worker_update_interval_secs plays this role)
    hb_timeout = float(os.environ.get("PADDLE_PS_HEARTBEAT_TIMEOUT", 60.0))
    monitor = HeartBeatMonitor(
        fanin, timeout=hb_timeout,
        check_interval=min(3.0, max(0.2, hb_timeout / 4)))
    barriers = BarrierManager(fanin, monitor=monitor, lock=lock)

    def _apply_sparse(name, value, rows):
        # row-wise SGD on the host-resident table (reference async sparse
        # update path; communicator.h AsyncCommunicator). In sync mode
        # each trainer's grad is the mean over ITS shard of the global
        # batch, so 1/fanin makes the applied sum the full-batch mean —
        # the reference transpiler's scale(1/trainers) on the server.
        scale = 1.0 / fanin if sync else 1.0
        pname = name[:-5] if name.endswith("@GRAD") else name
        var = scope.find_var(pname)
        val = var.value()
        if isinstance(val, core.LazyEmbeddingTable):
            val.apply_grad(rows, np.asarray(value) * scale, sparse_lr)
            return
        tbl = np.array(val.array)  # jax-array views are read-only
        np.subtract.at(tbl, np.asarray(rows, np.int64),
                       sparse_lr * scale * np.asarray(value))
        var.set_value(core.LoDTensor(jnp.asarray(tbl)))

    def _run_block_for(grad_name):
        blk_id = grad_to_block.get(grad_name)
        # the pserver optimize block runs OUTSIDE any Executor.run step
        # epilogue, so the per-op localizer is its ONLY numeric guard —
        # force it whenever the check flag is on, regardless of the
        # (trainer-oriented) action: a NaN minted here raises back to
        # the trainer typed instead of landing in the served params
        check = bool(core.globals_["FLAGS_check_nan_inf"]) or None
        for i, blk in enumerate(optimize_blocks):
            if blk_id is None or str(i) == str(blk_id):
                executor._run_block_eager(blk, scope, ctx.rng_base,
                                          check_nan=check)
                if blk_id is not None:
                    break

    def _apply_checked_locked(name, value, rows, trainer_id=0):
        """Apply one already-guarded update (rows pre-filtered)."""
        if rows is not None:
            if sync:
                state["sparse_seq"] += 1
                state["pending_sparse"].append(
                    (int(trainer_id), state["sparse_seq"], name,
                     np.asarray(value), np.asarray(rows, np.int64)))
            else:
                _apply_sparse(name, value, rows)
            return
        if sync:
            # tagged (trainer, seq) like the sparse entries: the release
            # SORTS before summing, so the fp accumulation order is
            # deterministic regardless of arrival interleaving — what
            # makes a 3-trainer round bit-identical run-to-run (2-way
            # sums are commutative, 3-way sums are not) and across a
            # replica failover's re-ordered replays
            state["sparse_seq"] += 1
            state["pending"].setdefault(name, []).append(
                (int(trainer_id), state["sparse_seq"],
                 np.asarray(value)))
        else:
            scope.var(name).set_value(
                core.LoDTensor(jnp.asarray(value)))
            _run_block_for(name)

    def _apply_one_locked(name, value, rows, trainer_id=0):
        value, rows, apply_ = _guard_nonfinite(name, value, rows,
                                               trainer_id)
        if not apply_ or (rows is not None and len(rows) == 0):
            return
        _apply_checked_locked(name, value, rows, trainer_id)

    def _apply_batch_locked(vars, trainer_id=0):
        """The numeric guard runs over the WHOLE batch before anything
        applies (one scan per array, not two): under
        FLAGS_ps_reject_nonfinite=reject a half-applied batch would be
        unrecoverable — the dedup cache replays the error on retry and
        nothing re-sends the tail — so reject must leave server state
        untouched."""
        checked = [(v["name"],) + _guard_nonfinite(
            v["name"], v["value"], v.get("rows"), trainer_id)
            for v in vars]
        for name, value, rows, apply_ in checked:
            if apply_ and not (rows is not None and len(rows) == 0):
                _apply_checked_locked(name, value, rows, trainer_id)

    def h_send_var(name, value, trainer_id=0, rows=None, height=0):
        monitor.update(trainer_id)
        with lock:
            # race-free drain guard: the handoff commit flips the
            # membership state while holding this same lock, so a send
            # that slipped past the server-level pre_dispatch is
            # refused HERE — never applied to a shard that moved
            membership.check_serving()
            _apply_one_locked(name, value, rows, trainer_id)
            # forward BEFORE noting the token applied: the forward is
            # where a false promotion surfaces (typed stale refusal),
            # and a token noted first would let a lost-response retry
            # replay a cached success for an apply that only ever
            # mutated this server's fenced-out state
            _forward("send_var", {"name": name,
                                  "value": np.asarray(value),
                                  "trainer_id": int(trainer_id),
                                  "rows": rows, "height": int(height)})
            note_request_token_applied()
        return True

    def h_send_vars_batch(vars, trainer_id=0):
        """Coalesced multi-var send (Communicator flush): every entry
        applies under ONE grad-lock acquisition; the caller's dedup
        token covers the whole batch, so a replayed retry re-applies
        none of it."""
        monitor.update(trainer_id)
        with lock:
            membership.check_serving()
            _apply_batch_locked(vars, trainer_id)
            # forward-then-note, same fencing rationale as h_send_var
            _forward("send_vars_batch", {"vars": vars,
                                         "trainer_id": int(trainer_id)})
            note_request_token_applied()
        return True

    def _release_send_round():
        # aggregate: average each grad across trainers (the reference
        # transpiler's sum + scale(1/trainers) on the server optimize
        # path), then run optimize. Runs under the shared lock, invoked
        # by the LAST arrival inside BarrierManager.arrive. Sparse row
        # grads deferred by _apply_one_locked apply FIRST, in
        # (trainer, seq) order — deterministic regardless of arrival
        # interleaving, so lock-stepped trainers reproduce bit-for-bit.
        for tid, seq, name, value, rows in sorted(
                state["pending_sparse"], key=lambda e: (e[0], e[1])):
            _apply_sparse(name, value, rows)
        state["pending_sparse"].clear()
        state["sparse_seq"] = 0
        for name, parts in state["pending"].items():
            entries = sorted(parts, key=lambda e: (e[0], e[1]))
            total = entries[0][2]
            for _tid, _seq, p in entries[1:]:
                total = total + p
            scope.var(name).set_value(
                core.LoDTensor(jnp.asarray(total / len(entries))))
        for name in list(state["pending"]):
            _run_block_for(name)
        state["pending"].clear()
        # chain replication: the standby buffered this round's forwarded
        # sends in ITS pending state; releasing its round from here (in
        # primary order, under the primary's lock) keeps the replica's
        # optimize trajectory bit-identical to the primary's
        _forward("round_release", {})

    def h_barrier(kind, trainer_id=0):
        monitor.update(trainer_id)
        if not sync or kind != "send":
            return True
        # the whole rendezvous runs under the shared grad RLock (the
        # BarrierManager Condition wraps it and fully releases it in
        # wait()), so the drain guard, the arrival, and the
        # applied-token note are one atomic step against a concurrent
        # handoff commit
        with lock:
            membership.check_serving()
            try:
                barriers.arrive("send", trainer_id,
                                on_release=_release_send_round)
            except core.WorkerDeadError:
                # drop the dead trainer's (and the whole aborted
                # round's) pending grads so the next round starts clean
                # instead of double-counting a partial batch — and the
                # standby's forwarded copy of them too: the survivors'
                # retried round would otherwise average in the aborted
                # entries on the replica only, so a later promotion
                # would serve a silently diverged trajectory
                state["pending"].clear()
                state["pending_sparse"].clear()
                state["sparse_seq"] = 0
                _forward("round_abort", {})
                raise
            # a completed barrier must replay (not re-arrive) if its
            # lost response is retried against the post-drain owner — a
            # fresh arrival there would phantom-join the next round
            note_request_token_applied()
            # and the same for the FAILOVER owner: register this
            # completed barrier's token on the replica so a lost-ack
            # retry replays there too instead of phantom-arriving
            _forward("barrier_done", {})
        return True

    def h_dgc_send(name, values, indices, shape, trainer_id=0):
        """DGC top-k dense-grad push (docs/PS_DATA_PLANE.md
        "Compression"): scatter the (indices, values) selection into a
        dense zeros grad and apply it EXACTLY like send_var would —
        sync mode defers it into the round's pending set, async runs
        the optimize block. The values arrive already dequantized
        (wire v3 decodes at receive), so the FLAGS_ps_reject_nonfinite
        guard inside _apply_one_locked sees the real numbers. The
        replica chain forwards the DECODED dense apply, never the
        compressed frame — a warm standby must stay bit-identical to
        the primary through a quantized/DGC push."""
        monitor.update(trainer_id)
        vals = np.asarray(values).reshape(-1)
        dims = [int(d) for d in shape]
        n_elems = 1
        for d in dims:
            n_elems *= d
        dense = np.zeros(n_elems, vals.dtype)
        dense[np.asarray(indices, np.int64).reshape(-1)] = vals
        dense = dense.reshape(dims)
        with lock:
            membership.check_serving()
            _apply_one_locked(name, dense, None, trainer_id)
            # forward-then-note, same fencing rationale as h_send_var
            _forward("send_var", {"name": name, "value": dense,
                                  "trainer_id": int(trainer_id),
                                  "rows": None, "height": 0})
            note_request_token_applied()
        return True

    def h_get_var(name, trainer_id=0):
        arr = _np_of(scope, name)
        if arr is None:
            raise KeyError(f"pserver has no var '{name}'")
        return np.asarray(arr)

    def h_get_vars_batch(names, trainer_id=0):
        """Batched fetch: the recv op pulls all of an endpoint's params
        in ONE RPC (read-only, idempotent like get_var)."""
        return [h_get_var(n, trainer_id) for n in names]

    def h_prefetch_rows(name, rows, prefetch=False):
        # ``prefetch=True`` marks an async-overlap early fetch (the
        # trainer pulled window i+1's rows while window i computed) —
        # counted separately under stats()["prefetch"] so operators can
        # see how much of the row traffic moved off the step's critical
        # path (docs/PS_DATA_PLANE.md "Async overlap")
        if prefetch:
            with health_lock:
                prefetch_stats["calls"] += 1
                prefetch_stats["rows"] += len(rows)
        # under the grad lock: get_rows materializes rows (slab growth,
        # index/LRU mutation) and must not interleave with a concurrent
        # apply_grad — the channel pool + fan-out make overlapping RPCs
        # from one trainer routine now
        with lock:
            val = scope.find_var(name).value()
            if isinstance(val, core.LazyEmbeddingTable):
                return val.get_rows(rows)
            tbl = np.asarray(val.array)
            return tbl[np.asarray(rows, np.int64)]

    def h_table_stats(name):
        """Introspection for tests/monitoring: touched rows + evictions
        (+ capacity-tier gauges for tiered tables)."""
        val = scope.find_var(name).value()
        if isinstance(val, core.LazyEmbeddingTable):
            out = {"touched": val.touched_rows(),
                   "evictions": val.evictions,
                   "nbytes": val.nbytes(),
                   "logical_params": val.logical_params()}
            # bounded acquire like _slab_stats_snapshot: a drain or a
            # wedged optimize round holds the grad lock for seconds and
            # this poll must not stall behind it (it just omits the
            # tier section then)
            if val._tier is not None and lock.acquire(timeout=1.0):
                try:
                    tier = val.tier_stats()
                finally:
                    lock.release()
                if tier:
                    out["tier"] = tier
            return out
        arr = np.asarray(val.array)
        return {"touched": int(arr.shape[0]), "evictions": 0,
                "nbytes": int(arr.nbytes),
                "logical_params": int(arr.size)}

    def h_table_shrink(name="", decay=0.5, threshold=0.5):
        """Decay-based shrink of one named (or every) tiered/gated
        table — the reference PSLib shrink() admin RPC. Runs under the
        grad lock so it can't interleave with an apply."""
        out = {}
        with lock:
            names = [name] if name else list(scope.local_var_names())
            for n in names:
                var = scope.find_var(n)
                if var is None or not var.is_initialized():
                    continue
                val = var.value()
                if isinstance(val, core.LazyEmbeddingTable) \
                        and val._tier is not None \
                        and val._tier.track_scores:
                    out[n] = val.shrink(decay=float(decay),
                                        threshold=float(threshold))
        return out

    def h_checkpoint(dir=""):
        return True

    def _geo_apply_locked(name, value, rows, flat=False):
        var = scope.find_var(name)
        if var is None:
            raise KeyError(f"geo pserver has no param '{name}'")
        cur = np.asarray(var.value().array)
        if rows is not None and flat:
            # DGC'd delta: ``rows`` are FLAT element indices of the
            # top-k selection, not leading-axis row ids
            cur = np.array(cur)
            flat_view = cur.reshape(-1)
            np.add.at(flat_view, np.asarray(rows, np.int64).reshape(-1),
                      np.asarray(value).reshape(-1))
            var.set_value(core.LoDTensor(jnp.asarray(cur)))
        elif rows is not None:
            cur = np.array(cur)  # jax-array views are read-only
            np.add.at(cur, np.asarray(rows, np.int64),
                      np.asarray(value))
            var.set_value(core.LoDTensor(jnp.asarray(cur)))
        else:
            var.set_value(core.LoDTensor(
                jnp.asarray(cur + np.asarray(value))))

    def h_geo_delta(name, value, trainer_id=0, rows=None, flat=False):
        """GEO-SGD delta apply: param += delta on arrival; with ``rows``
        only those table rows are touched (reference GeoSgdCommunicator
        sparse-id sync, communicator.h:383 SendUpdateSparseVars);
        ``flat=True`` marks a DGC top-k delta whose ``rows`` are flat
        element indices (docs/PS_DATA_PLANE.md "Compression")."""
        monitor.update(trainer_id)
        with lock:
            membership.check_serving()
            _geo_apply_locked(name, value, rows, flat=bool(flat))
            # forward-then-note, same fencing rationale as h_send_var.
            # The forwarded values are the DECODED delta (post-dequant)
            # so the standby applies bit-identically to this primary.
            _forward("geo_delta", {"name": name,
                                   "value": np.asarray(value),
                                   "rows": rows, "flat": bool(flat)})
            note_request_token_applied()
        return True

    # ---- replication: chain-forward applied updates to a warm standby
    # (FLAGS_ps_replicas=2 — docs/FAULT_TOLERANCE.md "Elastic
    # membership"). Forwards run UNDER the grad lock in receipt order on
    # one private single-channel client, so the replica sees the exact
    # apply sequence the primary ran — bit-identical state. A forward
    # failure marks replication broken (warn once, stop forwarding):
    # promoting a replica that missed updates would diverge, which the
    # docs call out as the replica-consistency caveat.
    fwd = {"client": None, "broken": False, "warned": False}

    def _replica_target(for_beat=False):
        # DRAINING still accepts writes (the quiesce window), so the
        # chain must keep forwarding through it — a gap here would
        # silently diverge the warm standby without marking it BROKEN.
        # A BROKEN chain stops data forwards but NOT liveness beats:
        # beats keep flowing with chain_broken=True so the stale
        # standby disables its own promotion — without them the break
        # itself looks like primary death and the standby promotes
        # over a live primary with state missing every update since
        # the break (split views, silent rollback at the real death).
        if int(core.globals_["FLAGS_ps_replicas"]) < 2 \
                or (fwd["broken"] and not for_beat) \
                or membership.state not in (ps_membership.ACTIVE,
                                            ps_membership.DRAINING):
            return None
        reps = [r for r in membership.view.replicas(endpoint)
                if r != bind]
        return reps[0] if reps else None

    def _forward(method, kw):
        target = _replica_target()
        if target is None:
            return
        from ..fluid.ps_rpc import request_dedup_token
        token = request_dedup_token()
        try:
            cli = fwd.get("client")
            if cli is None or cli.endpoint != target:
                cli = fwd["client"] = VarClient(
                    target, connect_timeout=5.0, channels=1,
                    resolve=False)
            # the view rides every forward: the replica's minting floor
            # must track epochs OTHER slots' drains created, or its
            # promotion would mint an epoch trainers already hold
            # bounded schedule: this runs holding the grad lock, so the
            # full FLAGS_rpc_deadline×retries ladder against a hung
            # replica would stall every data handler on this pserver —
            # one dedup-tokened retry inside ~2×hb, then BROKEN
            cli.call("replica_apply", fwd_method=method, kw=kw,
                     token=token, from_ep=bind,
                     view=membership.view.to_dict(),
                     _rpc_timeout=max(1.0, hb_timeout), _rpc_retries=1)
            membership.replication["forwarded_calls"] += 1
        except core.StaleClusterViewError as e:
            # the replica refused the forward: it PROMOTED while this
            # server was presumed dead (GC pause / healed partition).
            # Absorb its newer view — note_gossip demotes this server
            # out of ACTIVE so it stops serving a shard that moved —
            # and stop forwarding (the chain inverted).
            membership.replication["forward_failures"] += 1
            fwd["broken"] = True
            membership.note_gossip(view=getattr(e, "view_dict", None))
            if not fwd["warned"]:
                fwd["warned"] = True
                import logging
                logging.getLogger("paddle_tpu.ps").warning(
                    "replica forward refused by %s (%r) — the replica "
                    "promoted; this server has been replaced as the "
                    "owner of slot %s", target, e, endpoint)
            if method in ("send_var", "send_vars_batch", "geo_delta"):
                # surface the refusal to the CLIENT of the data call:
                # its re-route replays the same token on the true owner
                # (this server's local apply is on fenced-out state).
                # Barrier-internal forwards (round_release/barrier_done)
                # swallow instead — clients learn at their next data RPC
                raise membership.stale_error()
        except Exception as e:  # noqa: BLE001 — degraded, not fatal
            membership.replication["forward_failures"] += 1
            fwd["broken"] = True
            if not fwd["warned"]:
                fwd["warned"] = True
                import logging
                logging.getLogger("paddle_tpu.ps").warning(
                    "replica forward to %s failed (%r) — replication "
                    "for slot %s is BROKEN from here on; a later "
                    "promotion of that replica would serve stale state",
                    target, e, endpoint)

    # ---- replica side: primary-liveness monitor + forwarded applies.
    # The primary is participant 0 of a dedicated monitor; its forwards
    # and replica_beat pings are the beats. On silence past the timeout
    # the dead-listener PROMOTES this standby: it mints view epoch+1
    # with itself as the slot's primary, and trainers pick it up through
    # the get_view probes their reconnect loops run.
    upstream = {"ep": None, "stale": False}
    pmon = None
    if replica_of:
        pmon = HeartBeatMonitor(
            1, timeout=hb_timeout,
            check_interval=min(1.0, max(0.1, hb_timeout / 4)))

        def _on_primary_dead(_wid):
            if upstream["stale"]:
                # the primary told us the replication chain is BROKEN
                # (we missed forwards): promoting would serve state
                # missing those updates. Failover is disabled for this
                # slot — the next primary death is a WorkerDeadError
                # abort, exactly the documented broken-chain caveat.
                import logging
                logging.getLogger("paddle_tpu.ps").warning(
                    "standby %s: primary %s silent but this standby is "
                    "STALE (replication chain broke earlier) — refusing "
                    "promotion; failover for this slot is disabled",
                    bind, upstream["ep"] or replica_of)
                return
            if upstream["ep"] is None:
                # never heard a single forward/beat: the primary may
                # still be BOOTING (process spawned, socket not serving
                # yet). Probe its liveness before a first-contact
                # promotion; a connectable primary just hasn't found us
                # — re-arm and keep waiting.
                target = membership.view.resolve(replica_of) \
                    if membership.view is not None else replica_of
                host, port = target.rsplit(":", 1)
                try:
                    socket.create_connection(
                        (host, int(port)), timeout=1.0).close()
                    pmon.update(0)
                    return
                except OSError:
                    pass
            membership.promote()

        pmon.add_dead_listener(_on_primary_dead)
        pmon.start_monitor()
        # seed the silence clock: without a first beat the monitor's
        # table is empty and a primary that dies BEFORE its first
        # forward/beat (or was already down when this replica started
        # to restore redundancy) would never be declared dead
        pmon.update(0)

    def _on_upstream(from_ep):
        if pmon is None:
            return
        if from_ep and upstream["ep"] != from_ep:
            # a NEW upstream (the post-drain owner) took over forwarding
            # — an intentional-drain mark left by the old one no longer
            # applies to it
            upstream["ep"] = from_ep
            pmon.clear_draining(0)
        pmon.update(0)

    def h_replica_apply(fwd_method, kw, token=None, from_ep="",
                        view=None):
        """Apply one forwarded primary update on the standby. The
        ORIGINAL caller's dedup token is registered as completed here,
        so a trainer replaying that very call after failing over to
        this (promoted) replica gets the cached response instead of a
        double apply — exactly-once across the failover. The primary's
        view piggybacks so a later promotion mints ABOVE every epoch
        the cluster has seen and maps the OTHER slots correctly."""
        membership.note_gossip(view=view)
        if membership.state != ps_membership.STANDBY:
            # ownership fence: this replica PROMOTED (its primary was
            # presumed dead) — the forwarder is a demoted-but-alive
            # primary whose updates must not double-apply on top of the
            # re-routed trainers' direct sends. The typed refusal
            # carries our newer view; the primary absorbs it and steps
            # down (note_gossip demotion).
            raise membership.stale_error()
        _on_upstream(from_ep)
        with lock:
            if fwd_method == "send_var":
                _apply_one_locked(kw["name"], kw["value"],
                                  kw.get("rows"),
                                  kw.get("trainer_id", 0))
            elif fwd_method == "send_vars_batch":
                _apply_batch_locked(kw["vars"], kw.get("trainer_id", 0))
            elif fwd_method == "round_release":
                _release_send_round()
            elif fwd_method == "round_abort":
                # the primary aborted the round (WorkerDeadError): wipe
                # the forwarded pending grads so the survivors' retried
                # round isn't double-counted on this standby
                state["pending"].clear()
                state["pending_sparse"].clear()
                state["sparse_seq"] = 0
            elif fwd_method == "barrier_done":
                pass  # only the token registration below matters
            elif fwd_method == "geo_delta":
                _geo_apply_locked(kw["name"], kw["value"],
                                  kw.get("rows"),
                                  flat=bool(kw.get("flat", False)))
            else:
                raise KeyError(
                    f"replica_apply: unknown forwarded method "
                    f"{fwd_method!r}")
            if token is not None:
                srv_box[0]._dedup_put(tuple(token),
                                      {"ok": True, "result": True})
                srv_box[0]._note_token_applied(tuple(token))
        return True

    def h_replica_beat(from_ep="", view=None, chain_broken=False):
        membership.note_gossip(view=view)
        if chain_broken and pmon is not None and not upstream["stale"]:
            # permanent for this process lifetime: the missed forwards
            # are unrecoverable short of a full handoff, which installs
            # state wholesale and flips this server out of STANDBY
            upstream["stale"] = True
            membership.replication["stale_standby"] = 1
            import logging
            logging.getLogger("paddle_tpu.ps").warning(
                "standby %s: primary %s reports the replication chain "
                "BROKEN — this standby missed updates and will refuse "
                "promotion", bind, from_ep)
        _on_upstream(from_ep)
        return True

    def h_peer_draining(from_ep=""):
        """The primary announces an INTENTIONAL drain before it goes
        silent: its silence afterwards must not trigger a promotion —
        the new owner's first forward re-arms monitoring."""
        if pmon is not None:
            pmon.mark_draining(0)
        return True

    def h_get_view():
        return membership.view.to_dict()

    # ---- drain / handoff (the elastic resharding protocol) ------------
    # destination-side staging: sections validate against the manifest's
    # crc32/size as they stream in; nothing touches the scope until
    # handoff_commit has the complete, validated set
    staging = {}
    staging_lock = threading.Lock()

    def _clear_staging_locked():
        sdir = staging.pop("dir", None)
        staging.clear()
        if sdir:
            import shutil
            shutil.rmtree(sdir, ignore_errors=True)

    hand_seq = itertools.count()

    def _dest_spill_path(var_name):
        """Where a handed-off table's spill log lands on THIS server:
        the configured spill dir, else a fresh tempdir (never the
        source's path — both processes may share the box; the sequence
        keeps a rebuilt table from truncating the log of the still-
        installed table it replaces)."""
        import tempfile
        sdir = str(core.globals_["FLAGS_ps_slab_spill_dir"] or "")
        if not sdir:
            sdir = tempfile.mkdtemp(prefix="pt-slab-handoff-")
        return os.path.join(
            sdir, f"{_safe_name(var_name)}-{os.getpid()}"
            f"-h{next(hand_seq)}.slab")

    def h_handoff_begin(manifest):
        # STANDBY is the normal destination; DRAINED covers the REJOIN
        # without a restart — drain A→B, later drain B→A re-uses the
        # still-running drained A as the destination
        if membership.state not in (ps_membership.STANDBY,
                                    ps_membership.DRAINED):
            raise RuntimeError(
                f"handoff destination must be a standby or drained "
                f"server (state={membership.state})")
        if str(manifest.get("slot", "")) != endpoint:
            # a drain aimed at the wrong standby (swapped endpoints in
            # an operator script) would otherwise CRC-validate and
            # commit another slot's shard onto this server
            raise RuntimeError(
                f"handoff manifest is for slot "
                f"{manifest.get('slot')!r} but this server hosts slot "
                f"{endpoint!r}")
        if int(manifest.get("format_version", 0)) != \
                fio.HANDOFF_FORMAT_VERSION:
            raise core.CheckpointError(
                f"handoff manifest format "
                f"{manifest.get('format_version')!r} not supported")
        with staging_lock:
            _clear_staging_locked()
            staging["manifest"] = manifest
            staging["payloads"] = {}
            staging["files"] = {}
        return True

    def h_handoff_section(name, payload):
        blob = np.asarray(payload, np.uint8).tobytes()
        with staging_lock:
            man = staging.get("manifest")
            if man is None:
                raise RuntimeError("handoff_section before handoff_begin")
            entry = fio.check_handoff_section(man, name, blob)
            if str(entry.get("kind", "")).startswith("tier"):
                # capacity-tier sections STAGE ON DISK: the sum of a
                # spilled table's sections is the whole table, and the
                # destination's RSS must stay bounded by one section
                # (docs/PS_DATA_PLANE.md "Capacity tier")
                sdir = staging.get("dir")
                if sdir is None:
                    import tempfile
                    sdir = staging["dir"] = tempfile.mkdtemp(
                        prefix="pt-handoff-stage-")
                # index prefix: two section names that sanitize to the
                # same string must not clobber each other's staged
                # bytes (the map below is keyed by the TRUE name)
                path = os.path.join(
                    sdir,
                    f"{len(staging['files'])}-{_safe_name(name)}")
                with open(path, "wb") as f:
                    f.write(blob)
                staging["files"][name] = path
            else:
                staging["payloads"][name] = blob
        return True

    def h_handoff_commit():
        with staging_lock:
            man = staging.get("manifest")
            if man is None:
                raise RuntimeError("handoff_commit before handoff_begin")
            missing = sorted(set(man["sections"])
                             - set(staging["payloads"])
                             - set(staging["files"]))
            if missing:
                raise core.CheckpointError(
                    f"handoff incomplete: {len(missing)} section(s) "
                    f"never arrived: {', '.join(missing)}")
            lazy_meta = (man.get("extra") or {}).get("lazy_meta") or {}

            def _staged_bytes(name):
                path = staging["files"].get(name)
                if path is not None:
                    with open(path, "rb") as f:
                        return f.read()
                return staging["payloads"][name]

            with lock:
                slabs = {}
                tier_vars = set()
                for name, entry in man["sections"].items():
                    if str(entry.get("kind", "")).startswith("tier"):
                        tier_vars.add(entry["meta"]["var"])
                        continue
                    blob = staging["payloads"][name]
                    if entry["kind"] == "dense":
                        scope.var(entry["meta"]["var"]).set_value(
                            fio._deserialize_lod_tensor(blob))
                    elif entry["kind"] in ("slab_ids", "slab_rows"):
                        slabs.setdefault(entry["meta"]["var"],
                                         {})[entry["kind"]] = blob
                for var_name, parts in slabs.items():
                    meta = lazy_meta[var_name]
                    ids = np.frombuffer(parts["slab_ids"], np.int64)
                    rows = np.frombuffer(
                        parts["slab_rows"],
                        np.dtype(meta["dtype"])).reshape(
                            len(ids), int(meta["dim"]))
                    new_tbl = core.LazyEmbeddingTable.from_state(
                        meta, ids, rows)
                    # drop the replaced table only AFTER the new one
                    # built — a failed rebuild must not brick the
                    # still-installed table's cold rows
                    fio._drop_replaced_table(scope.find_var(var_name))
                    scope.var(var_name).set_value(new_tbl)
                for var_name in sorted(tier_vars):
                    # tiered rebuild: sections feed in one at a time
                    # from the staged files — peak RSS is one section
                    # plus the hot slab, never the spilled payload
                    from ..fluid import slab_spill
                    import json as _json
                    prefix = f"tier:{var_name}:"

                    def _sec(rel, prefix=prefix):
                        return _staged_bytes(
                            prefix + rel[len("tier:"):])

                    t_meta = _json.loads(_sec("tier:meta"))
                    spilled = bool(
                        (t_meta.get("tier") or {}).get("spilled"))
                    new_tbl = slab_spill.build_table_from_sections(
                        t_meta, _sec,
                        spill_path=(_dest_spill_path(var_name)
                                    if spilled else None))
                    # drop-after-build, same rationale as from_state
                    fio._drop_replaced_table(scope.find_var(var_name))
                    scope.var(var_name).set_value(new_tbl)
                srv_box[0].install_dedup_hwms(man.get("dedup_hwms"))
                membership.state = ps_membership.ACTIVE
                membership.install(man["view_next"])
            _clear_staging_locked()
        return True

    def h_handoff_abort():
        with staging_lock:
            _clear_staging_locked()
        return True

    def _handoff_sections_locked():
        """Snapshot every scope-resident piece of shard state as
        CRC-manifested sections (called under the grad lock, round
        quiesced): dense vars AND optimizer slots as reference-format
        tensor blobs, LazyEmbeddingTable sparse shards as slab
        (ids, rows) pairs with their meta riding the manifest."""
        sections, lazy_meta = {}, {}
        for name in scope.local_var_names():
            var = scope.find_var(name)
            if var is None or not var.is_initialized():
                continue
            val = var.value()
            if isinstance(val, core.LazyEmbeddingTable):
                if val._tier is not None:
                    # capacity tier: STREAM the table section-by-section
                    # (hot chunks + verbatim spill-log records) instead
                    # of a RAM-materializing export — source RSS stays
                    # O(one section) no matter how much is spilled, and
                    # quantized segments move bit-identically
                    # (docs/PS_DATA_PLANE.md "Capacity tier")
                    from ..fluid import slab_spill
                    for rel, sec in slab_spill.table_sections(
                            val).items():
                        full = f"tier:{name}:{rel[len('tier:'):]}"
                        sections[full] = {
                            "kind": sec["kind"], "meta": {"var": name},
                            "size": sec["size"], "crc32": sec["crc32"],
                            "read": sec["read"]}
                    lazy_meta[name] = {"tiered": True}
                    continue
                meta, ids, rows = val.export_state()
                lazy_meta[name] = meta
                sections[f"slab:{name}:ids"] = {
                    "kind": "slab_ids", "bytes": ids.tobytes(),
                    "meta": {"var": name}}
                sections[f"slab:{name}:rows"] = {
                    "kind": "slab_rows",
                    "bytes": np.ascontiguousarray(rows).tobytes(),
                    "meta": {"var": name}}
            elif isinstance(val, core.LoDTensor):
                sections[f"var:{name}"] = {
                    "kind": "dense",
                    "bytes": fio._serialize_lod_tensor(val),
                    "meta": {"var": name}}
        return sections, lazy_meta

    def h_drain(dest):
        """Admin RPC on the current owner: quiesce, stream this slot's
        state to ``dest`` in CRC-manifested sections, and commit the
        epoch bump — the between-rounds view flip that keeps
        lock-stepped sync training bit-identical across the move. Any
        failure aborts with the source still serving. A REJOIN is the
        same call with ``dest`` = the restarted original endpoint
        (running as a standby) — the protocol in reverse."""
        import logging
        log = logging.getLogger("paddle_tpu.ps")
        dest = str(dest)
        # check-and-set under the grad lock: two concurrent drain RPCs
        # (e.g. an operator retry from a fresh client — different dedup
        # token) must not both pass the ACTIVE gate and hand the shard
        # to two destinations
        with lock:
            if membership.state != ps_membership.ACTIVE:
                raise RuntimeError(
                    f"drain: server for slot {endpoint!r} is "
                    f"{membership.state}, not active")
            membership.state = ps_membership.DRAINING
        membership.handoff.update(in_progress=True, bytes=0,
                                  sections_done=0, total_sections=0)
        committed = False
        dest_cli = None
        try:
            dest_cli = VarClient(dest, connect_timeout=10.0, channels=1,
                                 resolve=False)
            quiesce_end = time.time() + float(
                core.globals_["FLAGS_ps_drain_quiesce_deadline"])
            while True:
                with lock:
                    if not state["pending"] and \
                            not state["pending_sparse"] and \
                            barriers.idle("send"):
                        summary = _do_handoff_locked(dest_cli, dest)
                        committed = True
                        break
                if time.time() > quiesce_end:
                    raise TimeoutError(
                        f"drain: slot {endpoint!r} could not quiesce "
                        f"within FLAGS_ps_drain_quiesce_deadline — a "
                        f"sync round never reached a between-rounds "
                        f"window")
                time.sleep(0.02)
            log.warning("membership: slot %s DRAINED %d bytes in %d "
                        "sections to %s (view epoch %d)", endpoint,
                        summary["bytes"], summary["sections"], dest,
                        summary["epoch"])
            return summary
        except BaseException as e:
            membership.handoff["aborts"] += 1
            if not committed:
                # clean abort: the source keeps serving, the destination
                # discards whatever it staged
                if dest_cli is not None:
                    try:
                        dest_cli.call("handoff_abort", _rpc_retries=0)
                    except Exception:
                        pass
                membership.state = ps_membership.ACTIVE
                log.warning("membership: drain of slot %s to %s "
                            "ABORTED (%r) — source still serving",
                            endpoint, dest, e)
            raise
        finally:
            membership.handoff["in_progress"] = False
            if dest_cli is not None:
                dest_cli.close()

    def _do_handoff_locked(dest_cli, dest):
        """Runs holding the grad lock with the round quiesced: snapshot,
        stream, commit, flip. Everything before handoff_commit is
        staged destination-side only, so an error anywhere leaves the
        source authoritative."""
        sections, lazy_meta = _handoff_sections_locked()
        new_view = membership.mint_moved(endpoint, dest)
        manifest = fio.build_handoff_manifest(
            endpoint, new_view.epoch, new_view.to_dict(), sections,
            dedup_hwms=srv_box[0].dedup_hwms(),
            extra={"lazy_meta": lazy_meta, "source": bind})
        membership.handoff["total_sections"] = len(sections)
        dest_cli.call("handoff_begin", manifest=manifest)
        for name, sec in sections.items():
            # tier sections regenerate on demand (read()) so the whole
            # spilled table is never resident; plain sections carry
            # their bytes inline as before
            payload = sec["bytes"] if "bytes" in sec else sec["read"]()
            if ps_membership._corrupt_section_hook is not None:
                payload = ps_membership._corrupt_section_hook(
                    name, payload)
            dest_cli.call("handoff_section", name=name,
                          payload=np.frombuffer(payload, np.uint8))
            membership.handoff["bytes"] += len(payload)
            membership.handoff["sections_done"] += 1
        try:
            dest_cli.call("handoff_commit")
        except Exception:
            # lost-ack hazard: the destination may have committed and
            # become the epoch+1 owner before the ack died in transit.
            # Reverting this source to ACTIVE then would fork the shard
            # (both ends serving), so probe the destination's view on a
            # fresh connection before deciding the commit failed. An
            # unreachable destination can't serve either side of a
            # split, so aborting is safe there (in-memory staging dies
            # with it); see the residual-partition caveat in
            # docs/FAULT_TOLERANCE.md.
            committed_remote = False
            try:
                probe = VarClient(dest, connect_timeout=5.0, channels=1,
                                  resolve=False)
                try:
                    v = probe.call("get_view", _rpc_retries=1)
                    committed_remote = bool(v) and \
                        int(v.get("epoch", -1)) >= new_view.epoch
                finally:
                    probe.close()
            except Exception:
                pass
            if not committed_remote:
                raise
        # tell our replica (if any) the coming silence is intentional —
        # the new owner's first forward re-arms its monitoring
        for rep in membership.view.replicas(endpoint):
            if rep == dest:
                continue
            try:
                rc = VarClient(rep, connect_timeout=2.0, channels=1,
                               resolve=False)
                try:
                    rc.call("peer_draining", from_ep=bind,
                            _rpc_retries=0)
                finally:
                    rc.close()
            except Exception:
                pass
        membership.state = ps_membership.DRAINED
        membership.install(new_view)
        membership.handoff["completed"] += 1
        return {"bytes": membership.handoff["bytes"],
                "sections": len(sections), "dest": dest,
                "epoch": new_view.epoch}

    monitor.start_monitor()
    # cluster-timeline identity (docs/OBSERVABILITY.md): label this
    # process's trace shard with its pserver bind so the timeline
    # merger can match it against the clock offsets trainers measured
    # in the _hello handshake (PADDLE_TPU_TRACE_ROLE env still wins)
    from ..fluid import telemetry as _telemetry
    _telemetry.set_process_role(f"pserver-{bind}", endpoint=bind)
    srv_box = []
    srv = VarServer(bind, {
        "send_var": h_send_var, "send_vars_batch": h_send_vars_batch,
        "dgc_send": h_dgc_send,
        "barrier": h_barrier, "get_var": h_get_var,
        "get_vars_batch": h_get_vars_batch,
        "prefetch_rows": h_prefetch_rows, "checkpoint": h_checkpoint,
        "table_stats": h_table_stats, "table_shrink": h_table_shrink,
        "geo_delta": h_geo_delta,
        # elastic membership plane
        "drain": h_drain, "get_view": h_get_view,
        "handoff_begin": h_handoff_begin,
        "handoff_section": h_handoff_section,
        "handoff_commit": h_handoff_commit,
        "handoff_abort": h_handoff_abort,
        "replica_apply": h_replica_apply,
        "replica_beat": h_replica_beat,
        "peer_draining": h_peer_draining,
        **monitor.handlers(),
    }, membership=membership)
    srv_box.append(srv)
    def _health_stats_snapshot():
        # the dedicated counter lock, NOT the grad lock: an unlocked
        # dict() copy can die mid-iteration against a _bump_health
        # writer, and the grad lock would stall this observability RPC
        # behind a whole sync optimize round
        with health_lock:
            return {"health": {
                "dropped_sparse_rows": health["dropped_sparse_rows"],
                "dropped_dense_updates": health["dropped_dense_updates"],
                "rejected_calls": health["rejected_calls"],
                "per_var": dict(health["per_var"]),
            }, "prefetch": dict(prefetch_stats)}

    srv.add_stats_source(_health_stats_snapshot)
    # drain tooling / tests poll epoch, state, handoff progress, and
    # failover promotions through the same stats RPC the health and
    # per-op counters ride (docs/FAULT_TOLERANCE.md "Elastic membership")
    srv.add_stats_source(membership.stats_section)

    def _slab_stats_snapshot():
        """Capacity-tier gauges aggregated over every tiered table —
        resident/spilled rows+bytes, hit rate, spill/promote counters,
        at-rest density (docs/PS_DATA_PLANE.md "Capacity tier"). Rides
        the stats RPC whose numeric leaves the PR 10 registry view
        scrapes as ps_server_slab_* gauges. Takes the grad lock with a
        bounded wait: a wedged optimize round costs the scrape its
        slab section, never a stall."""
        if not lock.acquire(timeout=1.0):
            return {}
        try:
            from ..fluid import slab_spill
            per_table = []
            for n in scope.local_var_names():
                var = scope.find_var(n)
                if var is None or not var.is_initialized():
                    continue
                val = var.value()
                if isinstance(val, core.LazyEmbeddingTable) \
                        and val._tier is not None:
                    per_table.append(val.tier_stats())
            agg = slab_spill.merge_tier_stats(per_table)
            return {"slab": agg} if agg else {}
        finally:
            lock.release()

    srv.add_stats_source(_slab_stats_snapshot)

    # primary → replica liveness pings: forwards already beat, but an
    # IDLE primary (no traffic) must still prove liveness or the replica
    # would promote over a quiet cluster
    beat_stop = threading.Event()

    def _replica_beat_loop():
        beat_cli = {}
        interval = min(2.0, max(0.2, hb_timeout / 4))
        while not beat_stop.wait(interval):
            target = _replica_target(for_beat=True)
            if target is None:
                continue
            try:
                cli = beat_cli.get(target)
                if cli is None:
                    cli = beat_cli[target] = VarClient(
                        target, connect_timeout=max(1.0, interval),
                        channels=1, resolve=False)
                cli.call("replica_beat", from_ep=bind,
                         view=membership.view.to_dict(),
                         chain_broken=bool(fwd["broken"]),
                         _rpc_timeout=max(1.0, interval * 2),
                         _rpc_retries=0)
            except Exception:
                beat_cli.pop(target, None)

    beat_thread = threading.Thread(target=_replica_beat_loop,
                                   name=f"ps-replica-beat-{bind}",
                                   daemon=True)
    beat_thread.start()
    srv.start()
    try:
        srv.wait_stopped()
    finally:
        beat_stop.set()
        monitor.stop()
        if pmon is not None:
            pmon.stop()
        srv.shutdown()
    return {}


# ---------------------------------------------------------------- pslib ops
@register_op("pslib_pull_sparse", stateful=True, no_grad=True,
             attr_defaults={"TableId": 0, "EmbeddingDim": 8,
                            "padding_idx": -1})
def _pslib_pull_sparse(ins, attrs):
    """Pull rows from a downpour sparse table (TPU-native replacement for
    the reference PSLib pull path — fleet_wrapper.h:86 PullSparseVarsSync).
    Emitted by DownpourOptimizer's rewrite of is_distributed lookups."""
    from ..fluid.incubate.fleet.parameter_server.pslib import _runtime
    ctx = attrs["_ctx"]
    name = ctx.op.input("Ids")[0]
    ids = np.asarray(ctx.scope.find_var(name).value().array)
    flat = ids.reshape(-1)
    pad = int(attrs.get("padding_idx", -1))
    dim = int(attrs["EmbeddingDim"])
    # padding ids never touch the table (no lazy row creation, no
    # last-seen refresh) — reference lookup_table padding semantics
    live = flat != pad if pad >= 0 else np.ones(flat.shape, bool)
    rows = np.zeros((flat.size, dim), np.float32)
    if live.any():
        rows[live] = _runtime.pull(int(attrs["TableId"]), flat[live])
    lead = ids.shape[:-1] if ids.ndim > 1 and ids.shape[-1] == 1 \
        else ids.shape
    out = jnp.asarray(rows).reshape(tuple(lead) + (dim,))
    return {"Out": [out]}


@register_op("pslib_push_sparse", stateful=True, no_grad=True,
             attr_defaults={"TableId": 0, "EmbeddingDim": 8,
                            "padding_idx": -1})
def _pslib_push_sparse(ins, attrs):
    """Push row gradients to a downpour sparse table (reference
    fleet_wrapper.h:130 PushSparseVarsWithLabelAsync). padding_idx rows get
    no gradient, matching lookup_table."""
    from ..fluid.incubate.fleet.parameter_server.pslib import _runtime
    ctx = attrs["_ctx"]
    ids = np.asarray(
        ctx.scope.find_var(ctx.op.input("Ids")[0]).value().array)
    gname = ctx.op.input("Grads")[0]
    gvar = ctx.scope.find_var(gname)
    if gvar is None or not gvar.is_initialized():
        return {}
    dim = int(attrs["EmbeddingDim"])
    flat = ids.reshape(-1)
    grads = np.asarray(gvar.value().array).reshape(-1, dim)
    pad = int(attrs.get("padding_idx", -1))
    if pad >= 0:
        live = flat != pad
        flat, grads = flat[live], grads[live]
    if flat.size:
        _runtime.push(int(attrs["TableId"]), flat, grads)
    return {}
