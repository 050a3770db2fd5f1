"""Lower RecomputeOptimizer checkpoints onto jax.checkpoint segments.

The reference rewrites the backward program to re-run forward subgraphs
between user-chosen checkpoint variables so activations inside a segment
are never stored (reference: python/paddle/fluid/optimizer.py
RecomputeOptimizer:3850, backward.py _append_backward_ops_with_
checkpoints_). A plain program-level rewrite would be undone by XLA's
CSE (the recomputed subgraph is identical to the stored one), so the
TPU lowering happens at trace level instead: each forward segment
becomes ONE ``jax.checkpoint``-wrapped function (XLA keeps the
rematerialization barrier), and the segment's backward ops are replaced
by the ``jax.vjp`` of that wrapped function — only the segment-boundary
values stay live between forward and backward.

A value one segment produces and several later ones read (a memory, a
kept K/V), and a parameter two segments read (a tied embedding), are
carried like any boundary value: each reader's vjp gives its own part
of the gradient, and the parts meet where the program's backward sums
them. A grad op belongs to the segment of its forward op (whose outputs'
gradients it reads); the ``sum`` ops the backward inserts at a fan-in
are glue, dropped with the span they lie in or left to run between the
spans. A span's vjp then writes each input's gradient under the name
the span's ops would have left it under (``grad_sinks``), added to the
parts that reached the span from outside, and reads each output's
cotangent from the names that reach the span from outside
(``cot_sources``).

Lowering preconditions (else fused fallback with a warning — same
numerics, more memory):
  * checkpoints are produced in the main block, no control flow inside
    a segment
  * the spans are contiguous per segment, in reverse segment order, and
    no op outside them reads a segment's internals
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp



class Segment:
    def __init__(self):
        self.ops = []
        self.ins: List[str] = []     # external reads, in first-use order
        self.outs: List[str] = []    # written AND read outside


class RematPlan:
    def __init__(self):
        self.pre_ops = []            # ops before the first segment
        self.segments: List[Segment] = []
        self.rest_head = []          # loss head + its bwd (after last seg)
        self.spans: List[List] = []  # per segment: replaced bwd ops
        self.between: List[List] = []  # rest ops between spans (reverse)
        self.span_order: List[int] = []  # segment index per span, in order
        # per segment: {output: the gradient names that reach its span
        # from outside} and {input: (the name its gradient leaves the
        # span under, the names of its parts that reach the span)}
        self.cot_sources: List[Dict[str, List[str]]] = []
        self.grad_sinks: List[Dict[str, tuple]] = []
        self.tail_ops = []           # pre-segment bwd + optimizer ops


def _fallback(reason):
    warnings.warn(
        f"RecomputeOptimizer checkpoints not lowerable onto "
        f"jax.checkpoint segments ({reason}); executing without "
        f"rematerialization (same numerics, more memory)", stacklevel=3)
    return None


def build_plan(cb, ckpt_names) -> Optional[RematPlan]:
    ops = cb.ops
    producer = {}
    for i, op in enumerate(ops):
        for n in op.output_arg_names:
            producer.setdefault(n, i)
    missing = [c for c in ckpt_names if c not in producer]
    if missing:
        return _fallback(f"checkpoint vars {missing} not produced")
    cks = sorted(set(ckpt_names), key=lambda c: producer[c])
    # find where the forward ends: the loss-grad seed fill_constant is
    # the first op whose outputs are all @GRAD names
    fwd_end = len(ops)
    for i, op in enumerate(ops):
        outs = op.output_arg_names
        if outs and all("@GRAD" in n for n in outs):
            fwd_end = i
            break
    bounds = [producer[c] + 1 for c in cks]
    if bounds[-1] > fwd_end:
        return _fallback("a checkpoint is produced by a backward op")

    plan = RematPlan()
    # segments live BETWEEN checkpoints: the region up to the first
    # checkpoint stays un-remat'ed (its inputs are the feeds; storing
    # them is free), matching the reference's use of checkpoints as
    # segment boundaries
    plan.pre_ops = ops[:bounds[0]]
    seg_ranges = [(bounds[i], bounds[i + 1])
                  for i in range(len(bounds) - 1)]
    if bounds[-1] < fwd_end:
        seg_ranges.append((bounds[-1], fwd_end))
    if not seg_ranges:
        return _fallback("need at least one segment after a checkpoint")
    rest = ops[fwd_end:]

    from .executor import _op_needs_rng

    def _op_uses_rng(op):
        """rng-REGISTERED is not rng-USING: an attention/dropout op with
        rate 0 (or is_test) draws nothing, so remat replay is exact."""
        if not _op_needs_rng(op.type):
            return False
        if op.attrs.get("is_test"):
            return False
        rate_keys = [k for k in op.attrs
                     if k in ("dropout_rate", "dropout_prob")]
        if rate_keys:
            return max(float(op.attrs[k] or 0.0) for k in rate_keys) > 0.0
        return True  # unconditional generator (uniform_random, ...)

    # writeback names that must survive even if no forward op reads
    # them: mutable state + persistable outputs (batch_norm running
    # stats, counters) — a segment-local write would otherwise be
    # silently dropped and the old value written back every step
    writeback = set(cb.mut_state) | set(cb.extra_writeback)
    # the trained loss: the backward's seed (the fill_constant at fwd_end)
    # writes its @GRAD outside every span, so its segment must hand it
    # out even where another var (a part of it) is what is fetched
    seeded = {n[:-len("@GRAD")] for n in rest[0].output_arg_names} \
        if rest else set()
    fwd_reads: Dict[int, set] = {}
    for i, op in enumerate(ops[:fwd_end]):
        fwd_reads[i] = set(op.input_arg_names)
    for lo, hi in seg_ranges:
        seg = Segment()
        seg.ops = ops[lo:hi]
        if not seg.ops:
            return _fallback("empty checkpoint segment")
        for op in seg.ops:
            if op.attrs.get("sub_block") is not None:
                return _fallback("control flow inside a segment")
            if _op_uses_rng(op):
                # segment-local rng indices would collide across
                # segments and diverge from the fused run's keys
                return _fallback(
                    f"rng op '{op.type}' inside a segment")
        # a dict for its order: `outs` is the order of the segment's
        # results in the traced step, and a set's order changes with the
        # process's hash seed: another module, so another entry in the
        # compile cache, every run
        written = {}
        for op in seg.ops:
            for n in op.input_arg_names:
                if n not in written and n not in seg.ins:
                    seg.ins.append(n)
            written.update(dict.fromkeys(op.output_arg_names))
        # outputs = the segment BOUNDARY: vars consumed by other FORWARD
        # ops, fetched, or state/persistable writebacks. Backward reads
        # of internals don't count — the segment's grad ops are replaced
        # by the vjp, which recomputes those values (that IS the
        # rematerialization); a non-replaced rest op reading an internal
        # is checked at the end.
        outside = set(cb.fetch_names) | writeback | seeded
        for i in range(fwd_end):
            if lo <= i < hi:
                continue
            outside |= fwd_reads[i]
        seg.outs = [n for n in written if n in outside]
        if not seg.outs:
            return _fallback("segment writes nothing consumed outside")
        plan.segments.append(seg)

    # ---- classify the backward spans ------------------------------------
    def base_of(name):
        """The forward var a gradient name (renamed at a fan-in or not)
        is the gradient of; None for any other name."""
        i = name.find("@GRAD")
        return name[:i] if i >= 0 else None

    written_by: Dict[str, int] = {}
    for k, seg in enumerate(plan.segments):
        for op in seg.ops:
            for n in op.output_arg_names:
                written_by.setdefault(n, k)

    def is_glue(op):
        """A fan-in's ``sum`` of gradient parts: it belongs to no forward
        op, and lies right after the op that wrote the last part."""
        return op.type == "sum" and all(
            base_of(n) is not None
            for n in op.input_arg_names + op.output_arg_names)

    def owner_of(op):
        """The segments whose forward outputs' gradients ``op`` reads:
        the one segment of its forward op, for a grad op."""
        return {written_by[base_of(n)] for n in op.input_arg_names
                if base_of(n) in written_by}

    idxs: Dict[int, List[int]] = {k: [] for k in range(len(plan.segments))}
    glue = []
    for i, op in enumerate(rest):
        if is_glue(op):
            glue.append(i)
            continue
        hits = owner_of(op)
        if len(hits) > 1:
            return _fallback(
                f"grad op '{op.type}' reads gradients of segments "
                f"{sorted(hits)}")
        if hits:
            idxs[hits.pop()].append(i)
    for i in glue:  # inside a span: dropped with it; else it runs
        for k in idxs:
            if idxs[k] and min(idxs[k]) < i < max(idxs[k]):
                idxs[k].append(i)
    live = [k for k in idxs if idxs[k]]
    if not live:
        return _fallback("no segment gradient ops found")
    # spans must be contiguous and in reverse segment order
    ordered = sorted(live, key=lambda k: idxs[k][0])
    if ordered != sorted(live, reverse=True):
        return _fallback("backward spans not in reverse segment order")
    marks = []
    for k in ordered:
        lo, hi = min(idxs[k]), max(idxs[k])
        if any(i not in idxs[k] for i in range(lo, hi + 1)):
            return _fallback(f"segment {k} backward span not contiguous")
        marks.append((k, lo, hi))
    plan.rest_head = rest[:marks[0][1]]
    plan.spans = [None] * len(plan.segments)
    plan.between = []
    for j, (k, lo, hi) in enumerate(marks):
        plan.spans[k] = rest[lo:hi + 1]
        nxt_lo = marks[j + 1][1] if j + 1 < len(marks) else None
        seg_after = rest[hi + 1:nxt_lo] if nxt_lo is not None \
            else rest[hi + 1:]
        plan.between.append(seg_after)
    plan.span_order = [k for k, _, _ in marks]
    plan.tail_ops = plan.between.pop() if plan.between else []
    # what flows into and out of each span, by gradient name
    plan.cot_sources = [{} for _ in plan.segments]
    plan.grad_sinks = [{} for _ in plan.segments]
    for k, seg in enumerate(plan.segments):
        span = plan.spans[k] or []
        reads = [n for op in span for n in op.input_arg_names]
        writes = [n for op in span for n in op.output_arg_names]
        flows_in = [n for n in dict.fromkeys(reads) if n not in writes]
        leaves = [n for n in dict.fromkeys(writes) if n not in reads]
        for v in seg.outs:
            plan.cot_sources[k][v] = [n for n in flows_in
                                      if base_of(n) == v]
        for v in seg.ins:
            names = [n for n in leaves if base_of(n) == v]
            if names:
                plan.grad_sinks[k][v] = (
                    names, [n for n in flows_in if base_of(n) == v])
    # every rest op that SURVIVES (not in a replaced span) must not read
    # a segment internal — those values are never materialized in env
    internals = {n for n, k in written_by.items()
                 if n not in plan.segments[k].outs}
    replaced = {id(op) for span in plan.spans if span for op in span}
    for op in rest:
        if id(op) in replaced:
            continue
        bad = internals & set(op.input_arg_names)
        if bad:
            return _fallback(
                f"op '{op.type}' outside the replaced spans reads "
                f"segment internals {sorted(bad)[:3]}")
    return plan


def exec_plan(cb, plan: RematPlan, env: Dict[str, Any], lod_env, rng):
    """One rematerialized step into ``env`` (called inside jit)."""
    cb._exec_ops(plan.pre_ops, env, lod_env, rng)

    vjps = []
    for seg in plan.segments:
        ins = [env[n] for n in seg.ins]

        def seg_fn(vals, _seg=seg):
            e = {n: v for n, v in zip(_seg.ins, vals)}
            cb._exec_ops(_seg.ops, e, dict(lod_env), rng)
            return tuple(e[n] for n in _seg.outs)

        wrapped = jax.checkpoint(seg_fn)
        outs, vjp_fn = jax.vjp(wrapped, ins)
        for n, v in zip(seg.outs, outs):
            env[n] = v
        vjps.append(vjp_fn)

    cb._exec_ops(plan.rest_head, env, lod_env, rng)

    import numpy as _np
    for j, k in enumerate(plan.span_order):
        seg = plan.segments[k]
        cots = []
        for n in seg.outs:
            out_val = env[n]
            if not jnp.issubdtype(out_val.dtype, jnp.inexact):
                # integer/bool boundary: vjp wants a float0 tangent
                cots.append(_np.zeros(out_val.shape, jax.dtypes.float0))
                continue
            parts = [env[g].astype(out_val.dtype)
                     for g in plan.cot_sources[k][n] if env.get(g) is not None]
            cots.append(sum(parts[1:], parts[0]) if parts
                        else jnp.zeros_like(out_val))
        (d_ins,) = vjps[k](tuple(cots))
        for n, g in zip(seg.ins, d_ins):
            if g is None or n not in plan.grad_sinks[k]:
                continue  # a feed, or a var no one wants the gradient of
            names, parts = plan.grad_sinks[k][n]
            for part in parts:  # what other readers sent back before
                g = g + env[part].astype(g.dtype)
            env[names[0]] = g
            for other in names[1:]:  # parts a later sum adds to the first
                env[other] = jnp.zeros_like(g)
        after = plan.between[j] if j < len(plan.between) else []
        cb._exec_ops(after, env, lod_env, rng)

    cb._exec_ops(plan.tail_ops, env, lod_env, rng)
