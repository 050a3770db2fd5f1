"""What a recomputed segment keeps (fluid/recompute_lowering.KEPT): the
flash attention op names its output and its one-column log-sum-exp, the
segments' checkpoint policy saves values by those names, and the
segment's backward reads them instead of running the forward kernel
again. Everything runs on the CPU through the Pallas interpreter, so the
kernels traced are the chip's."""
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, layers, recompute_lowering, telemetry
from paddle_tpu.models._decoder_parts import (gated_ffn, linear, minimize,
                                              rms_norm)
from paddle_tpu.models.bert import fused_multihead_attention
from paddle_tpu.ops.pallas import flash_attention as fa

CFG = dict(vocab_size=64, hidden=64, heads=4, kv_heads=2, head_dim=16,
           eps=1e-6, init_std=0.02)
SEQ, LAYERS = 256, 2   # past DENSE_MAX_SEQ: the kernels serve the call


def _build(recompute):
    """A two-layer decoder LM (grouped causal attention, a gated FFN),
    under a checkpoint a layer where ``recompute``."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    d = CFG["head_dim"]
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", shape=[SEQ], dtype="int64")
        labels = fluid.data("labels", shape=[SEQ, 1], dtype="int64")
        x = layers.embedding(ids, [CFG["vocab_size"], CFG["hidden"]])
        checkpoints = [x]
        for i in range(LAYERS):
            p = f"layers.{i}."
            h = rms_norm(x, p + "input_norm", CFG)
            q = linear(h, CFG["heads"] * d, p + "w_q", CFG)
            k = linear(h, CFG["kv_heads"] * d, p + "w_k", CFG)
            v = linear(h, CFG["kv_heads"] * d, p + "w_v", CFG)
            o = fused_multihead_attention(q, k, v, CFG["heads"], causal=True,
                                          n_kv_head=CFG["kv_heads"])
            x = layers.elementwise_add(
                x, linear(o, CFG["hidden"], p + "w_o", CFG))
            x = layers.elementwise_add(x, gated_ffn(
                rms_norm(x, p + "post_norm", CFG), 128, p + "mlp.", CFG))
            checkpoints.append(x)
        logits = linear(rms_norm(x, "final_norm", CFG), CFG["vocab_size"],
                        "lm_head", CFG)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, labels))
        minimize(loss, 1e-3, recompute, checkpoints)
    return main, startup, loss


def _one_step(recompute):
    """(the loss and every parameter's gradient of one step, their
    names, the compiled block, its step's jaxpr as text, the
    ``remat_kept_bytes`` the trace set)."""
    main, startup, loss = _build(recompute)
    grads = [p.name + "@GRAD" for p in main.all_parameters()]
    doc = np.random.default_rng(0).integers(0, CFG["vocab_size"],
                                            (1, SEQ + 1))
    feed = {"ids": doc[:, :-1], "labels": doc[:, 1:, None]}
    exe, scope = fluid.Executor(), core.Scope()
    with fa.interpret_guard(), fluid.scope_guard(scope):
        exe.run(startup)
        fetched = exe.run(main, feed=feed, fetch_list=[loss.name] + grads)
        kept = telemetry.REGISTRY.get("remat_kept_bytes").value()
        cb = list(exe._compiled_cache.values())[-1]
        state = [{n: scope.find_var(n).get_tensor().array for n in names}
                 for names in (cb.mut_state, cb.ro_state)]
        feeds = {n: jnp.asarray(a, jnp.int32) for n, a in feed.items()}
        jaxpr = str(jax.make_jaxpr(cb._step)(*state, feeds,
                                             jax.random.key(0)))
    return [np.asarray(f) for f in fetched], grads, cb, jaxpr, kept


@pytest.fixture(scope="module")
def steps():
    return {recompute: _one_step(recompute) for recompute in (True, False)}


@pytest.fixture(params=[True, False], ids=["recompute", "no_checkpoints"])
def stepped(request, steps):
    return request.param, steps[request.param]


def _kernel_calls(jaxpr):
    return {name: len(re.findall(rf"name={name}\b", jaxpr))
            for name in fa.KERNELS}


def test_a_recomputed_layer_traces_the_forward_kernel_once(stepped):
    """Under a checkpoint a layer the step traces ONE forward kernel a
    layer (the segment's backward reads the kept output and log-sum-exp);
    without checkpoints two, as before: the forward op's and the one the
    grad op's vjp runs."""
    recompute, (_, _, cb, jaxpr, _) = stepped
    assert (cb._remat_plan is not None) == recompute
    assert _kernel_calls(jaxpr) == {
        "flash_fwd": LAYERS * (1 if recompute else 2),
        "flash_bwd_dkv": LAYERS, "flash_bwd_dq": LAYERS}


def test_remat_kept_bytes_reads_the_kept_output_and_lse(stepped):
    """The gauge holds, summed over the segments, the bytes of the values
    kept by name: a layer's output [B, H, S, D] and its one-column
    log-sum-exp [B·H, S] f32 (never the 128-lane wire form); 0 without
    checkpoints."""
    recompute, (_, _, _, _, kept) = stepped
    rows = 1 * CFG["heads"] * SEQ
    per_layer = rows * CFG["head_dim"] * 4 + rows * 4
    assert kept == (LAYERS * per_layer if recompute else 0)


def test_the_loss_and_every_gradient_equal_the_plain_programs_bit_for_bit(
        steps):
    """Keeping the forward kernel's results instead of recomputing them
    changes no number: the recomputed program's loss and every
    parameter's gradient equal the program's without checkpoints."""
    (kept, names), plain = steps[True][:2], steps[False][0]
    assert len(kept) == len(plain) == 1 + len(names)
    for name, a, b in zip(["loss"] + names, kept, plain):
        assert np.array_equal(a, b), name


def _single_custom_vjp(q, k, v, seed, bias, sm_scale, mask, dropout_rate):
    """The entry as it stood before the kernel's results could be kept:
    one custom_vjp whose forward rule runs the forward kernel."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
    def call(q, k, v, seed, bias, sm_scale, mask, dropout_rate):
        return fwd(q, k, v, seed, bias, sm_scale, mask, dropout_rate)[0]

    def fwd(q, k, v, seed, bias, sm_scale, mask, dropout_rate):
        biased = fa.Mask.of(mask)._replace(bias=bias)
        o, lse = fa._pallas_fwd(q, k, v, seed, sm_scale, biased,
                                *fa._blocks_of("flash_fwd", q, k, v, biased),
                                dropout_rate)
        return o, (q, k, v, o, lse[:, :, 0], seed, bias)

    def bwd(sm_scale, mask, dropout_rate, res, g):
        dq, dk, dv, _, _, dseed, dbias = fa._fp_bwd(sm_scale, mask,
                                                    dropout_rate, res, g)
        return dq, dk, dv, dseed, dbias

    call.defvjp(fwd, bwd)
    return call(q, k, v, seed, bias, sm_scale, mask, dropout_rate)


def test_keypad_dropout_gradients_equal_the_single_custom_vjps():
    """Outside recomputation the work is the old entry's, kernel for
    kernel: the padded BERT cell's form (a key-padding bias, in-kernel
    dropout) gives the same output and gradients, bit for bit."""
    rng = np.random.default_rng(3)
    B, H, S, D = 2, 2, 384, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
               for _ in range(3))
    lengths = np.array([384, 250])
    bias = jnp.asarray(np.where(np.arange(S)[None] < lengths[:, None],
                                0.0, -1e4), jnp.float32)
    seed = jnp.asarray([12345], jnp.int32)
    g = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)

    def vjp(entry):
        o, back = jax.vjp(lambda q, k, v: entry(
            q, k, v, seed, bias, 0.125, fa.Mask(), 0.1), q, k, v)
        return (o, *back(g))

    with fa.interpret_guard():
        new, old = vjp(fa._flash_pallas), vjp(_single_custom_vjp)
    for name, a, b in zip(("o", "dq", "dk", "dv"), new, old):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def _sines_in_step(form, policy):
    """Sine kernels in the lowered step of a checkpointed segment
    tanh -> custom_vjp(sin(q kᵀ)) -> tanh under ``policy``, the forward's
    value named "kept" where ``form`` says: inside the custom_vjp's
    forward rule, on the call's output, or computed outside the call
    under stop_gradient and named as its input (the flash entry's
    form)."""
    def kernel(q, k):
        return jnp.sin(q @ k.T)

    @jax.custom_vjp
    def call(q, k):
        return kernel(q, k)

    def call_fwd(q, k):
        o = kernel(q, k)
        if form == "inside_rule":
            o = checkpoint_name(o, "kept")
        return o, (q, k, o)

    def call_bwd(res, g):
        q, k, o = res
        return g @ k * o.sum(), g.T @ q

    call.defvjp(call_fwd, call_bwd)

    @jax.custom_vjp
    def attach(q, k, o):
        return o

    def attach_fwd(q, k, o):
        return o, (q, k, o)

    def attach_bwd(res, g):
        q, k, o = res
        return g @ k * o.sum(), g.T @ q, jnp.zeros_like(o)

    attach.defvjp(attach_fwd, attach_bwd)

    def op(q, k):
        if form == "on_input":
            o = kernel(*jax.lax.stop_gradient((q, k)))
            return attach(q, k, checkpoint_name(o, "kept"))
        o = call(q, k)
        return checkpoint_name(o, "kept") if form == "on_output" else o

    def seg(x, w):
        return jnp.tanh(op(jnp.tanh(x @ w), jnp.tanh(x)))

    def step(x, w):
        y, back = jax.vjp(jax.checkpoint(seg, policy=policy), x, w)
        return y, back(jnp.ones_like(y))

    x = jnp.ones((8, 8))
    return jax.jit(step).lower(x, x).as_text().count("stablehlo.sine")


@pytest.mark.parametrize("form,kept", [("inside_rule", True),
                                       ("on_output", False),
                                       ("on_input", True)])
def test_which_names_a_checkpoint_policy_sees(form, kept):
    """The jax behaviour the entry rests on (jax 0.9.0): under the name
    policy the forward runs once where the named value is what the
    backward reads (the flash entry's form, a name on the input of the
    custom_vjp that attaches the gradient; and, as this jax traces a
    custom_vjp's forward rule into the segment, a name inside that rule),
    twice where it is not (a name on the call's output: the rule runs
    again for its residuals); with no policy twice, whatever is named."""
    names = jax.checkpoint_policies.save_only_these_names("kept")
    assert _sines_in_step(form, names) == (1 if kept else 2)
    assert _sines_in_step(form, None) == 2


def test_keep_takes_only_the_kept_names():
    assert set(recompute_lowering.KEPT) == {fa.FLASH_OUT, fa.FLASH_LSE}
    with pytest.raises(AssertionError):
        recompute_lowering.keep(jnp.ones(3), "another")
