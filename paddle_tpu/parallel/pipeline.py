"""Pipeline parallelism over a TPU mesh axis — GPipe schedule as a compiled
collective program.

The reference implements pipelining as a *runtime*: `PipelineOptimizer`
(reference: python/paddle/fluid/optimizer.py:3550) cuts the program into
sections, and `PipelineTrainer`/`SectionWorker` threads move scopes through
blocking queues between devices (reference:
paddle/fluid/framework/pipeline_trainer.cc:24, section_worker.cc:142,
trainer_desc.proto:77 SectionWorkerParameter).

On TPU the schedule is *compiled* instead: every stage lives on one slice of
a mesh axis (``"pp"``), stage parameters are sharded over that axis with a
leading stage dimension, and one `shard_map`-ped function runs the classic
GPipe tick loop — at tick t, stage s computes microbatch (t - s), then the
activation ring-shifts one stage forward via `lax.ppermute` over ICI. The
whole forward (and, through `jax.grad`, the reverse pipeline — ppermute
transposes to the opposite shift) is a single XLA computation: no queues, no
threads, no host in the loop.

Two layers:
  * `gpipe(...)` / `gpipe_het(...)` — the functional schedulers (this
    file): stacked stage params for homogeneous stages, a flat
    lax.switch ring for arbitrary per-stage bodies. Used directly by
    model code for peak MFU.
  * `PipelineOptimizer` (fluid/optimizer.py) — reference-API program
    splitter whose section metadata `fluid/pipeline_lowering.py` lowers
    onto `gpipe` (homogeneous sections) or `gpipe_het` (heterogeneous),
    falling back to fused execution when neither schedule applies.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import axis_mesh, shard_map

PIPELINE_AXIS = "pp"

__all__ = ["PIPELINE_AXIS", "stack_stage_params", "pipeline_mesh", "gpipe",
           "gpipe_het", "gpipe_loss_fn"]


def pipeline_mesh(n_stages: int, devices=None) -> Mesh:
    return axis_mesh(n_stages, PIPELINE_AXIS, devices)


def stack_stage_params(per_stage: Sequence[Any]):
    """Stack N same-structure stage param trees along a new leading stage
    axis (the axis `gpipe` shards over ``"pp"``)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage)


def _shard_stacked(mesh: Mesh, stacked, param_specs=None):
    """Place stacked stage params: leading (stage) dim over the pp axis
    (or the caller's explicit per-leaf specs, for params that ALSO shard
    over other mesh axes — e.g. MoE expert slices over "dp")."""
    if param_specs is None:
        param_specs = jax.tree_util.tree_map(
            lambda x: P(PIPELINE_AXIS, *([None] * (x.ndim - 1))), stacked)

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(put, stacked, param_specs), param_specs


_VMA_OFF_CHECKED_WITH_JAX = "0.9.0"  # see gpipe_het


def _vary_over(x, axes, mesh: Mesh):
    """``x`` marked device-varying over those of ``axes`` it is not
    varying over yet (lax.pcast refuses axes already in that state)."""
    missing = tuple(a for a in mesh.axis_names
                    if a in axes and a not in jax.typeof(x).vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def gpipe(stage_fn: Callable[..., Any], stacked_params, xs, *,
          mesh: Mesh, axis: str = PIPELINE_AXIS, param_specs=None,
          xs_spec: P = P(), with_aux: bool = False,
          pass_micro: bool = False):
    """Run microbatches ``xs`` through an ``n_stages``-deep pipeline.

    stage_fn(params_i, x) -> y          one stage; same signature per stage
                                        (heterogeneity via lax.switch inside)
    stacked_params                      pytree, leading dim n_stages
                                        (see `stack_stage_params`)
    xs : [n_micro, mb, ...]             microbatched input (replicated)
    returns ys : [n_micro, mb, ...]     last stage's outputs (replicated)

    Stage activations must keep the input's shape/dtype contract
    (y.shape == stage input shape) — the usual transformer/MLP residual-width
    case. The tick loop runs n_micro + n_stages - 1 steps; bubbles compute on
    garbage and are masked out, exactly the GPipe cost model.

    Composition hooks (the 3D lane, parallel/lm3d.py):
      param_specs   pytree of PartitionSpecs matching ``stacked_params``
                    for leaves that shard over MORE than the leading
                    stage dim (every spec must still lead with ``axis``;
                    e.g. MoE expert weights P("pp", "dp", ...)). Default:
                    P(axis, None, ...) per leaf.
      xs_spec       PartitionSpec of ``xs`` (and of the returned ys) on
                    the non-pipeline mesh axes — dim 0 is the microbatch
                    dim and must stay unsharded (it is the scan axis);
                    e.g. P(None, "dp", "sp", None) for [n_micro, mb, S, D]
                    batch/sequence sharding. Default replicated.
      with_aux      stage_fn returns ``(y, aux_scalar)``; the aux values
                    of VALID ticks (bubbles excluded) are summed over
                    ticks, stages, and every other mesh axis, and
                    returned replicated as ``(ys, aux_total)`` — e.g.
                    counted MoE token drops across the whole schedule.
      pass_micro    stage_fn is called ``stage_fn(params_i, x, micro)``
                    with the (clamped) global microbatch index this tick
                    computes — the rng-fold hook: a stage body folding
                    its dropout key by (stage, layer, micro) draws the
                    same masks the sequential oracle does.
    """
    n_stages = mesh.shape[axis]
    n_micro = xs.shape[0]
    total = n_micro + n_stages - 1
    stacked_params, pspec_params = _shard_stacked(mesh, stacked_params,
                                                  param_specs)

    def per_device(params, xs_local):
        # params leaves arrive with leading dim 1 (this stage's slice)
        params = jax.tree_util.tree_map(lambda x: x[0], params)
        sidx = lax.axis_index(axis)
        right = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            inbuf, ys, aux_acc = carry
            # the microbatch THIS stage computes at tick t (stage 0
            # ingests mb t; stage s is s ticks behind; clamped — bubble
            # ticks compute on garbage and are masked below)
            midx = jnp.clip(t - sidx, 0, n_micro - 1)
            mb = lax.dynamic_index_in_dim(xs_local, jnp.clip(
                t, 0, n_micro - 1), keepdims=False)
            x = jnp.where(sidx == 0, mb, inbuf)
            args = (params, x, midx) if pass_micro else (params, x)
            y = stage_fn(*args)
            if with_aux:
                y, aux = y
                # a bubble tick's aux is garbage-in-garbage-out: count
                # only ticks where this stage holds a real microbatch
                live = jnp.logical_and(t - sidx >= 0,
                                       t - sidx < n_micro)
                aux_acc = aux_acc + jnp.where(live, aux,
                                              jnp.zeros_like(aux))
            # last stage writes microbatch (t - n_stages + 1) when valid
            oidx = t - (n_stages - 1)
            valid = jnp.logical_and(sidx == n_stages - 1, oidx >= 0)
            upd = lax.dynamic_update_index_in_dim(
                ys, y, jnp.clip(oidx, 0, n_micro - 1), 0)
            ys = jnp.where(valid, upd, ys)
            nxt = lax.ppermute(y, axis, right)
            return (nxt, ys, aux_acc), None

        x0 = jnp.zeros_like(xs_local[0])
        aux0 = jnp.zeros((), jnp.int32)
        if with_aux:
            # discover the aux dtype/shape from an abstract stage eval
            aux_shape = jax.eval_shape(
                lambda p, x: stage_fn(*((p, x, jnp.int32(0))
                                        if pass_micro else (p, x)))[1],
                params, x0)
            aux0 = jnp.zeros(aux_shape.shape, aux_shape.dtype)
        init = (x0,
                jnp.zeros((n_micro,) + xs_local.shape[1:],
                          xs_local.dtype),
                aux0)
        # the carry becomes device-varying after the first tick: over
        # the pipeline axis (axis_index / ppermute) and over every other
        # mesh axis the stage body's operands vary on (dp/sp-sharded xs,
        # expert-sharded params). lax.scan wants the initial carry typed
        # as the body returns it, so ask the body (abstractly) and cast
        # each leaf over the axes it does not vary on yet.
        carry_out = jax.eval_shape(lambda c: tick(c, jnp.int32(0))[0], init)
        init = jax.tree_util.tree_map(
            lambda x, out: _vary_over(x, out.vma, mesh), init, carry_out)
        (_, ys, aux_acc), _ = lax.scan(tick, init, jnp.arange(total))
        # ys is only populated on the last stage; zero elsewhere + psum
        # replicates it to every stage (single all-reduce over ICI).
        ys = lax.psum(jnp.where(sidx == n_stages - 1, ys,
                                jnp.zeros_like(ys)), axis)
        if with_aux:
            # total over stages AND the data/sequence shards — the
            # schedule-global count, replicated everywhere
            return ys, lax.psum(_vary_over(aux_acc, mesh.axis_names, mesh),
                                tuple(mesh.axis_names))
        return ys

    out_specs = (xs_spec, P()) if with_aux else xs_spec
    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(pspec_params, xs_spec), out_specs=out_specs)
    return fn(stacked_params, xs)


def gpipe_het(stage_fns: Sequence[Callable[[Any, Any], Any]],
              per_stage_params: Sequence[Any], xs, *, mesh: Mesh,
              axis: str = PIPELINE_AXIS):
    """Heterogeneous GPipe: stage i runs ``stage_fns[i](params_i, x)``.

    Unlike `gpipe`, stages need NOT share an op body, parameter structure,
    or activation shape (the reference SectionWorker runs arbitrary
    per-device program sections — section_worker.cc:142; this is the
    compiled equivalent). The ppermute ring carries a flat buffer sized to
    the LARGEST stage boundary; each stage statically unflattens its input
    shape and re-pads its output, so uneven towers (embedding-heavy stage
    0, narrow head stage) still pipeline.

    xs : [n_micro, mb, ...]  microbatched stage-0 input (replicated)
    returns ys : [n_micro, mb_out, ...] last stage's outputs (replicated)

    Every stage body is compiled on every device but only the selected
    branch executes (lax.switch over the stage index), so per-device
    compute stays work-optimal; params are replicated. The homogeneous
    `gpipe` stacked-param path remains the memory-lean choice when stages
    do stack.

    shard_map runs with the varying-manual-axes checker OFF: jax 0.9.0's
    vma tracking mis-transposes lax.switch under scan+ppermute (observed:
    grads off by O(1) with the checker on and the carry pcast to
    varying, exact to 2e-7 against the sequential oracle with it off).
    Collective transposes with the checker off are version-sensitive, so
    the call refuses any other jax (_VMA_OFF_CHECKED_WITH_JAX).
    """
    import numpy as np
    n_stages = mesh.shape[axis]
    if len(stage_fns) != n_stages or len(per_stage_params) != n_stages:
        raise ValueError(
            f"{len(stage_fns)} stage fns / {len(per_stage_params)} param "
            f"sets vs pp axis size {n_stages}")
    n_micro = xs.shape[0]
    total = n_micro + n_stages - 1

    # boundary shape chain (per microbatch), discovered abstractly
    shapes = [tuple(xs.shape[1:])]
    dtype = xs.dtype
    for i, (f, p) in enumerate(zip(stage_fns, per_stage_params)):
        a = jax.eval_shape(f, p, jax.ShapeDtypeStruct(shapes[-1], dtype))
        if a.dtype != dtype:
            raise ValueError(
                f"stage {i} output dtype {a.dtype} != ring dtype {dtype}")
        shapes.append(tuple(a.shape))
    sizes = [int(np.prod(s)) for s in shapes]
    buf_size = max(sizes)
    out_shape, out_size = shapes[-1], sizes[-1]

    def per_device(params_all, xs_local):
        sidx = lax.axis_index(axis)
        right = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def mk_branch(i):
            in_size, in_shape = sizes[i], shapes[i]

            def run(pall, bufv):
                x = bufv[:in_size].reshape(in_shape)
                y = stage_fns[i](pall[i], x).reshape(-1)
                return jnp.pad(y, (0, buf_size - y.size))
            return run

        branches = [mk_branch(i) for i in range(n_stages)]

        def tick(carry, t):
            inbuf, ys = carry
            mb = lax.dynamic_index_in_dim(
                xs_local, jnp.clip(t, 0, n_micro - 1), keepdims=False)
            mb_buf = jnp.pad(mb.reshape(-1), (0, buf_size - sizes[0]))
            x_buf = jnp.where(sidx == 0, mb_buf, inbuf)
            y_buf = lax.switch(sidx, branches, params_all, x_buf)
            oidx = t - (n_stages - 1)
            valid = jnp.logical_and(sidx == n_stages - 1, oidx >= 0)
            upd = lax.dynamic_update_index_in_dim(
                ys, y_buf[:out_size], jnp.clip(oidx, 0, n_micro - 1), 0)
            ys = jnp.where(valid, upd, ys)
            nxt = lax.ppermute(y_buf, axis, right)
            return (nxt, ys), None

        init = (jnp.zeros((buf_size,), dtype),
                jnp.zeros((n_micro, out_size), dtype))
        (_, ys), _ = lax.scan(tick, init, jnp.arange(total))
        ys = lax.psum(jnp.where(sidx == n_stages - 1, ys,
                                jnp.zeros_like(ys)), axis)
        return ys

    pspec_params = jax.tree_util.tree_map(lambda x: P(),
                                          list(per_stage_params))
    if jax.__version__ != _VMA_OFF_CHECKED_WITH_JAX:
        raise RuntimeError(
            f"gpipe_het runs shard_map with check_vma=False, which was "
            f"checked against the sequential oracle under jax "
            f"{_VMA_OFF_CHECKED_WITH_JAX} only (this is {jax.__version__}):"
            f" re-run tests/test_pipeline.py::test_gpipe_het_matches_"
            f"sequential with the checker on and off, then move the pin")
    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(pspec_params, P()), out_specs=P(),
                   check_vma=False)
    ys = fn(list(per_stage_params), xs)
    return ys.reshape((n_micro,) + out_shape)


def gpipe_loss_fn(stage_fn, loss_fn):
    """Compose gpipe with a per-microbatch loss → mean scalar, for jax.grad.

    loss_fn(y, target_microbatch) -> scalar.  Targets shaped like xs'
    leading microbatch dim. Backward through the pipeline is automatic:
    jax.grad transposes the ppermute ring into the reverse schedule.
    """
    def fn(stacked_params, xs, targets, *, mesh, axis=PIPELINE_AXIS):
        ys = gpipe(stage_fn, stacked_params, xs, mesh=mesh, axis=axis)
        losses = jax.vmap(loss_fn)(ys, targets)
        return jnp.mean(losses)
    return fn
