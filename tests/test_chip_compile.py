"""Compile for the chip, without the chip: the TPU's compiler is installed
here and compiles for a v5e that is described, not attached
(/opt/skills/guides/on-chip-measurement §2, rehearsal 3). It refuses what
interpret mode cannot see — a slice not aligned to the tiling, too much
VMEM, a program that does not fit 16 GB of HBM, a kernel XLA is asked to
partition. A compile that passes is not a chip run: nothing executes,
and no result or time comes out of this file.

Rules this file keeps (the guide gives the reasons): the topology is
described inside a module-scoped, non-autouse fixture that skips when it
cannot be — never at import, in a skipif, in parametrize or in
conftest.py; everything built from it is built in a fixture or a test;
the compile runs in the test's own process (only one process may hold
libtpu) with the persistent cache off around it (a described-device
executable cannot be read back); all such tests live in this ONE file so
they land on one xdist worker. Code that asks `jax.default_backend()`
sees the CPU here, so the tests patch the kernels' `_on_tpu` — the
program has no option for it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core
from paddle_tpu.fluid.executor import _CompiledBlock
from paddle_tpu.models import bert
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import selective_scan as ss

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever keeps libtpu from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def for_the_chip(monkeypatch):
    """Take the TPU branches, and keep the persistent compile cache out."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("shape,kw", [
    ((128, 12, 128, 64), {}),                        # chip_smoke / BERT s128
    ((16, 12, 512, 64), dict(bias=True, dropout=0.1)),  # padded s512 cell
    ((4, 16, 2048, 64), dict(causal=True)),          # long causal
    ((8, 12, 500, 64), {}),                          # ragged boundary block
    ((1, 16, 4096, 256), dict(causal=True)),         # Qwen3-Next's head
    ((1, 40, 4096, 64), dict(causal=True, dv=128)),  # Phi's full layer
    ((1, 40, 4096, 64), dict(window=512, dv=128)),   # Phi's window layer
    # the float32 parity programs (chip_smoke.py --qwen3-next / --phi4-flash)
    ((1, 16, 4096, 256), dict(causal=True, f32="highest")),
    ((1, 40, 4096, 64), dict(causal=True, dv=128, f32="highest")),
    # Laguna's two layer kinds: the first D 128 and S 8192 calls, both
    # precisions (chip_smoke.py --laguna runs the float32 program too)
    ((1, 48, 8192, 128), dict(causal=True)),
    ((1, 64, 8192, 128), dict(window=512)),
    ((1, 48, 8192, 128), dict(causal=True, f32="highest")),
    ((1, 64, 8192, 128), dict(window=512, f32="highest")),
    # SmallThinker's two layer kinds: the first S 16384 calls and the
    # first window wider than a block (4096), both precisions
    ((1, 28, 16384, 128), dict(causal=True)),
    ((1, 28, 16384, 128), dict(window=4096)),
    ((1, 28, 16384, 128), dict(causal=True, f32="highest")),
    ((1, 28, 16384, 128), dict(window=4096, f32="highest")),
    # JoyAI's latent attention: Q.K 192 wide (two 128-lane tiles, no
    # padding) over V 128, causal at S 8192, both precisions
    ((1, 32, 8192, 192), dict(causal=True, dv=128)),
    ((1, 32, 8192, 192), dict(causal=True, dv=128, f32="highest")),
], ids=["b128_s128", "s512_keypad_dropout", "s2048_causal", "s500_ragged",
        "s4096_causal_d256", "s4096_causal_dv128", "s4096_w512_dv128",
        "s4096_causal_d256_f32", "s4096_causal_dv128_f32",
        "s8192_causal_d128", "s8192_w512_d128", "s8192_causal_d128_f32",
        "s8192_w512_d128_f32", "s16384_causal_d128", "s16384_w4096_d128",
        "s16384_causal_d128_f32", "s16384_w4096_d128_f32",
        "s8192_causal_d192_dv128", "s8192_causal_d192_dv128_f32"])
def test_flash_fwd_bwd_compiles_for_v5e(one_chip, for_the_chip, shape, kw):
    """Forward + dK/dV + dQ kernels of `_flash_pallas`, bf16, Mosaic, each
    at the blocks `_block_sizes` CHOOSES for the shape: the gate that the
    chooser's VMEM budget (and the limit the kernels ask for above
    Mosaic's default) is one the chip's compiler accepts. The f32 cases
    compile at the "highest" matmul precision, as the float32 parity
    programs run: the chip refused those once (PR 33: 27.9 MiB asked of
    a 23.75 MiB limit) where every bf16 case had passed."""
    B, H, S, D = shape
    dtype = jnp.float32 if kw.get("f32") else jnp.bfloat16
    qk = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((B, H, S, kw.get("dv", D)), dtype,
                             sharding=one_chip)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    bias = (jax.ShapeDtypeStruct((B, S), jnp.float32, sharding=one_chip)
            if kw.get("bias") else None)
    mask = fa.Mask(kw.get("causal", False), kw.get("window", 0))

    def loss(q, k, v, seed, bias):
        o = fa._flash_pallas(q, k, v, seed, bias, 1.0 / np.sqrt(D), mask,
                             kw.get("dropout", 0.0))
        return jnp.sum(o.astype(jnp.float32) ** 2)

    with jax.default_matmul_precision(kw.get("f32", "default")):
        compiled = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2))).lower(qk, qk, v, seed, bias).compile()
    assert compiled.as_text().count(KERNEL) == 3
    assert _kernel_names(compiled.as_text()) == {
        (None, "flash_fwd"): 1, (None, "flash_bwd_dkv"): 1,
        (None, "flash_bwd_dq"): 1}


def test_scan_kernels_compile_for_v5e(one_chip, for_the_chip):
    """`selective_scan` forward + backward at the Phi cell's call (1 x
    4096 positions x 5120 channels x 16 states, chunks of 64, float32),
    through the op, at the blocks `_block_sizes` chooses: one Mosaic
    kernel each way, and none of the `lax.scan` lowering's loops."""
    from paddle_tpu.ops import decoder_ops
    names = ("X", "Dt", "B", "C", "ALog", "D", "DtBias")
    shapes = ((1, 4096, 5120),) * 2 + ((1, 4096, 16),) * 2 \
        + ((5120, 16), (5120,), (5120,))
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]

    def loss(*xs):
        y = decoder_ops._selective_scan(
            {k: [v] for k, v in zip(names, xs)},
            {"chunk_size": 64, "site": "compile"})["Out"][0]
        return jnp.sum(y ** 2)

    assert ss._block_sizes(5120, 16, 4096, 64) == (1280, 64)
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(7)))).lower(
        *args).compile()
    text = compiled.as_text()
    assert _kernel_names(text) == {(None, "ssm_scan_fwd"): 1,
                                   (None, "ssm_scan_bwd"): 1}
    assert " while(" not in text


@pytest.mark.parametrize("op_type", ["gated_delta_rule", "kda"])
@pytest.mark.parametrize("precision", ["bf16", "f32"])
def test_delta_rule_kernels_compile_for_v5e(one_chip, for_the_chip, op_type,
                                            precision):
    """`gated_delta_rule` (Qwen3-Next's call: 16 key heads repeated to 32
    value heads) and `kda` (Ling's: 32 heads, the decay a key channel)
    forward + backward at the cells' shapes (1 x 4096 positions, heads
    of 128, chunks of 64), through the op, at the blocks `block_sizes`
    chooses: bf16 operands as the cells run them, and float32 ones at
    the "highest" precision as the parity programs do. One Mosaic kernel
    each way, no triangular-solve custom call and none of the `lax.scan`
    lowering's loops."""
    from paddle_tpu.ops import decoder_ops
    from paddle_tpu.ops.pallas import delta_rule as dr
    from paddle_tpu.ops.registry import OPS
    channel = op_type == "kda"
    key_heads = 32 if channel else 16
    names = ("Q", "K", "V", "A", "B", "ALog", "DtBias")
    shapes = ((1, 4096, key_heads * 128),) * 2 + ((1, 4096, 4096),
                                                  (1, 4096, 4096 if channel
                                                   else 32),
                                                  (1, 4096, 32), (32,),
                                                  (4096 if channel else 32,))
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    attrs = {"lower_bound": -5.0} if channel else {
        "num_key_heads": 16, "num_value_heads": 32}

    def loss(*xs):
        y = OPS.get(op_type).kernel(
            {k: [v] for k, v in zip(names, xs)},
            dict(attrs, chunk_size=64, site="compile"))["Out"][0]
        return jnp.sum(y ** 2)

    bf16 = precision == "bf16"
    core.set_flag("FLAGS_use_bf16_matmul", bf16)
    try:
        assert decoder_ops._delta_blocks(1, 32, 4096, 64, 128, 128) == (
            (4, 8) if bf16 else (4, 4))
        with jax.default_matmul_precision("default" if bf16 else "highest"):
            text = jax.jit(jax.grad(loss, argnums=tuple(range(7)))).lower(
                *args).compile().as_text()
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", False)
    assert _kernel_names(text) == {(None, "delta_fwd"): 1,
                                   (None, "delta_bwd"): 1}
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert " while(" not in text
    assert dr.grid_steps(32, 64, (4, 8)) == 64


@pytest.mark.parametrize("tokens,k,held,width,d,f,activation,kernels", [
    (4096, 10, 32, 512, 2048, 512, "silu", True),
    (8192, 8, 32, 256, 2048, 512, "silu", True),
    (16384, 6, 16, 64, 2560, 768, "relu", False),
], ids=["qwen3_next", "laguna", "smallthinker"])
def test_expert_kernels_compile_for_v5e_in_float32(
        one_chip, for_the_chip, tokens, k, held, width, d, f, activation,
        kernels):
    """`moe_expert_ffn` forward + backward at the three expert cells'
    calls with float32 operands at the "highest" matmul precision, as
    the float32 parity programs run it (`chip_smoke.py --qwen3-next /
    --laguna / --smallthinker`; the bf16 calls compile inside the cells'
    steps below): the seven kernels of `grouped_matmul.py` at the blocks
    `_block_sizes` chooses, inside the VMEM the chip's compiler allows,
    and no `ragged-dot`; SmallThinker's 2560 x 768 blocks do not fit the
    budget in float32, the chooser declines and the op keeps
    `lax.ragged_dot`."""
    from paddle_tpu.ops import decoder_ops
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.registry import OPS
    rows = decoder_ops.row_bound(tokens, k, held, width)
    assert (gm._block_sizes(rows, d, f, held, 4) is not None) == kernels
    shapes = (((1, tokens, d), jnp.float32), ((1, tokens, k), jnp.int32),
              ((1, tokens, k), jnp.float32), ((held, d, 2 * f), jnp.float32),
              ((held, f, d), jnp.float32))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    names = ("X", "TopkIdx", "TopkWeight", "WGateUp", "WDown")

    def loss(x, idx, *rest):
        out = OPS.get("moe_expert_ffn").kernel(
            {n: [v] for n, v in zip(names, (x, idx) + rest)},
            {"num_experts": width, "site": "compile",
             "activation": activation})["Out"][0]
        return jnp.sum(out ** 2)

    with jax.default_matmul_precision("highest"):
        text = jax.jit(jax.grad(loss, (0, 2, 3, 4))).lower(
            *args).compile().as_text()
    if not kernels:
        assert "ragged-dot" in text and "pallas_call" not in text
        return
    assert "ragged-dot" not in text
    assert _kernel_names(text) == {
        (None, name): n for (_, name), n in _expert_kernels(1).items()}


def test_stem_max_pool_compiles_to_one_select_and_scatter_for_v5e(one_chip,
                                                                for_the_chip):
    """ResNet's stem pool (3x3 stride 2 pad 1 behind a ReLU) at the cell's
    size, forward + gradient: the chip's compiler keeps ONE reduce-window
    and ONE select-and-scatter, the padding inside their windows, and no
    `pad` of the 822 MB tensor (the nine-slice form it replaced compiled
    to nine: PR 35)."""
    import re
    from paddle_tpu.ops import nn_ops
    attrs = dict(pooling_type="max", ksize=[3, 3], strides=[2, 2],
                 paddings=[1, 1])
    x = jax.ShapeDtypeStruct((256, 64, 112, 112), jnp.float32,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((256, 64, 56, 56), jnp.float32,
                             sharding=one_chip)

    def both(x, g):
        o, vjp = jax.vjp(
            lambda x: nn_ops._pool2d_impl(jax.nn.relu(x), attrs), x)
        return o, vjp(g)[0]

    text = jax.jit(both).lower(x, g).compile().as_text()
    ops = re.findall(r"= \S+ ([a-z][\w-]*)\(", text)
    assert ops.count("reduce-window") == 1
    assert ops.count("select-and-scatter") == 1
    assert ops.count("pad") == 0


def _kernel_names(text):
    """{(Fluid-op scope, kernel name): custom calls} of a compiled
    module's text: what a device trace of the chip is read by
    (benchmark/trace_scopes.py). The name is what stands before
    `/pallas_call` in the op_name, out of the `jvp(...)` the grad op's
    vjp wraps it in."""
    import re
    found = {}
    for line in text.splitlines():
        if KERNEL in line:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            scope = re.search(r"(?:^|[/(])((?:fwd|bwd|opt)/\w+)", op_name)
            kernel = re.search(r"(\w+)\)*/pallas_call$", op_name).group(1)
            key = (scope and scope.group(1), kernel)
            found[key] = found.get(key, 0) + 1
    return found


def _expert_kernels(layers):
    """The kernels of ``layers`` expert layers' passes in a step
    (ops/pallas/grouped_matmul.py), each once a layer, inside the loops
    over the windows: the two products with their epilogues forward (the
    recomputed segment's are dead code: the grad op's vjp makes what it
    needs itself), the gate/up product again, the backward of both
    products and the two weights' gradients in the grad op; the combine
    twice, the rows forward and their gradient back."""
    kernels = {("fwd/moe_expert_ffn", name): layers for name in (
        "moe_gmm_gate_up", "moe_gmm_down", "moe_gmm_project",
        "moe_gmm_down_bwd", "moe_gmm_rows_bwd", "moe_tgmm_gate_up",
        "moe_tgmm_down")}
    kernels["fwd/moe_expert_ffn", "moe_combine"] = 2 * layers
    return kernels


def _bert_base_width_step(layers, batch, seq_len, one_chip, mesh=None,
                          shardings=None):
    """The executor's own compiled block for a BERT-base-WIDTH pretrain
    step, and its arguments as shapes, each with where it lives: on
    ``one_chip``, or placed over ``mesh`` as the executor places them."""
    cfg = dict(bert.bert_base_config(), layers=layers)
    main, startup, _, fetches = bert.build_bert_pretrain_program(
        cfg, seq_len=seq_len, dropout=0.0, lr=1e-4)
    feed = bert.synthetic_pretrain_batch(cfg, batch, seq_len)
    exe = fluid.Executor()
    scope = core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)  # on the CPU: only the shapes are used
        cb = _CompiledBlock(main, tuple(sorted(feed)), (fetches[0].name,),
                            scope, seed=0, mesh=mesh,
                            param_shardings=shardings
                            and shardings(main, cfg))

        def on(spec):
            return one_chip if mesh is None else NamedSharding(mesh, spec)

        def state(names):
            out = {}
            for n in names:
                a = scope.find_var(n).get_tensor().array
                out[n] = jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=on(cb._sharding_for(n, a) or P()))
            return out
        mut, ro = state(cb.mut_state), state(cb.ro_state)
    feeds = {n: jax.ShapeDtypeStruct(  # device integers are 32-bit
                 a.shape, jnp.int32,
                 sharding=on(P("dp", *([None] * (a.ndim - 1)))))
             for n, a in feed.items()}
    key = jax.eval_shape(lambda: jax.random.key(0))
    rng = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=on(P()))
    return cb, (mut, ro, feeds, rng)


@pytest.mark.parametrize("on_mesh", [False, True],
                         ids=["one_chip", "dp2_mp2"])
@pytest.mark.parametrize("batch,seq_len", [(128, 128), (64, 256)],
                         ids=["b128_s128", "b64_s256"])
def test_bert_base_width_train_step_compiles_for_v5e(topo, one_chip,
                                                     for_the_chip, on_mesh,
                                                     batch, seq_len):
    """One whole train step (fwd + bwd + Adam) lowered from the
    executor's `_CompiledBlock` at BERT-base width (768 x 12 heads x
    3072, vocab 30522; 2 layers — 12 is a ~40 s by-hand rehearsal), bf16
    matmuls, 16,384 tokens: on one chip, and on a dp2 x mp2 mesh with
    the dry-run's tensor-parallel shardings, where the gradients must
    meet in an all-reduce. On each side of the attention ops' rule
    (`attention_ops.DENSE_MAX_SEQ`): at s128 a head's score tile is one
    kernel block, the step is XLA's own ops and holds NO Mosaic kernel;
    at s256 it holds the flash kernels, which on the mesh must have
    partitioned themselves (XLA refuses to)."""
    import __graft_entry__ as legs
    core.set_flag("FLAGS_use_bf16_matmul", True)
    try:
        where = {}
        if on_mesh:
            where = dict(
                mesh=Mesh(np.asarray(topo.devices).reshape(2, 2),
                          ("dp", "mp")),
                shardings=legs.bert_tp_shardings)
        cb, args = _bert_base_width_step(2, batch, seq_len, one_chip,
                                         **where)
        compiled = cb._jitted.lower(*args).compile()
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", False)
    text = compiled.as_text()
    if seq_len <= attention_ops.DENSE_MAX_SEQ:
        assert KERNEL not in text
    else:
        # per layer: the forward kernel twice (the forward op, and again
        # inside the grad op's vjp) + dK/dV + dQ
        assert text.count(KERNEL) == 4 * 2
        assert _kernel_names(text) == {
            ("fwd/fused_attention_qkv", "flash_fwd"): 2,
            ("bwd/fused_attention_qkv_grad", "flash_fwd"): 2,
            ("bwd/fused_attention_qkv_grad", "flash_bwd_dkv"): 2,
            ("bwd/fused_attention_qkv_grad", "flash_bwd_dq"): 2}
    assert ("all-reduce" in text) == on_mesh
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _decoder_step(main, startup, feed, fetches, one_chip):
    """(the executor's compiled block of a decoder's train step, a
    function that lowers it for ``one_chip``): start-up runs on the CPU
    for the shapes alone, and its scope is let go before the compile."""
    scope = core.Scope()
    fluid.Executor().run(startup, scope=scope)
    cb = _CompiledBlock(main, tuple(sorted(feed)), (fetches[0].name,),
                        scope, seed=0)
    lower = _lower(cb, scope, feed, one_chip)
    del scope
    return cb, lower


def _lower(cb, scope, feed, one_chip):
    """A function that lowers ``cb``'s step for ``one_chip`` from the
    shapes of its state in ``scope`` and of ``feed``."""
    def state(names):
        arrays = {n: scope.find_var(n).get_tensor().array for n in names}
        return {n: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                for n, a in arrays.items()}
    mut, ro = state(cb.mut_state), state(cb.ro_state)
    feeds = {n: jax.ShapeDtypeStruct(a.shape, jnp.int32, sharding=one_chip)
             for n, a in feed.items()}
    key = jax.eval_shape(lambda: jax.random.key(0))
    rng = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    return lambda: cb._jitted.lower(mut, ro, feeds, rng)


# Each decoder step's bytes by this compile while its recomputed segments
# still ran the flash forward kernel again (PERF.md §6): keeping the
# kernel's output and log-sum-exp adds about `remat_kept_bytes` to them.
RECOMPUTING_STEP_BYTES = {"qwen3_next": 11928771584, "phi4_flash": 11173550080,
                          "laguna": 13067909120, "smallthinker": 10501575168,
                          "joyai_flash": 12429286912}


def _step_bytes(name, compiled, parameters):
    """The compiled step's bytes (arguments, outputs not aliased to them,
    temporaries and code), printed beside what the step took before it
    kept the flash kernels' results; its arguments are 12 bytes a
    parameter (the gradients are temporaries)."""
    mem = compiled.memory_analysis()
    assert abs(mem.argument_size_in_bytes / (12 * parameters) - 1) < 0.01
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes
             + mem.generated_code_size_in_bytes)
    print(name, "step bytes", total, "more than recomputing:",
          total - RECOMPUTING_STEP_BYTES[name]
          if name in RECOMPUTING_STEP_BYTES else "(never measured)",
          "temporaries", mem.temp_size_in_bytes, "code",
          mem.generated_code_size_in_bytes)
    return total


def test_qwen3_next_train_step_compiles_for_v5e(one_chip, for_the_chip):
    """The step of `qwen3_next_80b_a3b.b1_s4096` as the executor lowers
    it, at the published widths and the cell's cut (4 layers, 32 of 512
    experts held, 18,992 rows of vocabulary), 1 x 4096 tokens, bf16
    matmul operands, recomputation a layer: it fits the chip's 16 GB
    (12 bytes a parameter of arguments: the gradients are temporaries),
    the attention layer runs the flash kernels at D = 256 causal (the
    forward once: the recomputed segment keeps its output and
    log-sum-exp; then dK/dV and dQ), every expert product is XLA's own grouped
    kernel over `row_bound` = 5,120 rows (twice the 2,560 the 32 held
    experts of 512 expect; never the 40,960 a layer could be sent), each
    of the three Gated DeltaNet layers runs the delta rule's kernel pair
    (forward, again in the recomputed segment, backward) and no
    triangular solve, and the only loops are the expert layers' windows.
    Start-up runs on the CPU for the shapes alone (7.5 GB of host
    memory, ~5 s)."""
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.models import qwen3_next
    cfg = dict(qwen3_next.qwen3_next_config(), layers=4, experts_held=32,
               vocab_size=18992)
    core.set_flag("FLAGS_use_bf16_matmul", True)
    try:
        main, startup, _, fetches = \
            qwen3_next.build_qwen3_next_pretrain_program(cfg, seq_len=4096)
        feed = qwen3_next.synthetic_pretrain_batch(cfg, 1, 4096)
        cb, lower = _decoder_step(main, startup, feed, fetches, one_chip)
        assert cb._remat_plan is not None and len(
            cb._remat_plan.segments) == 5  # four layers and the head
        compiled = lower().compile()
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", False)
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert "[5120,1024]" in text and "[40960,1024]" not in text \
        and "[40960,2048]" not in text
    assert _kernel_names(text) == {
        ("fwd/fused_attention_qkv", "flash_fwd"): 1,
        ("fwd/fused_attention_qkv", "flash_bwd_dkv"): 1,
        ("fwd/fused_attention_qkv", "flash_bwd_dq"): 1,
        ("fwd/gated_delta_rule", "delta_fwd"): 3 * 2,
        ("fwd/gated_delta_rule", "delta_bwd"): 3,
        **_expert_kernels(4)}
    assert "InvertDiagBlocksLowerTriangular" not in text
    import re
    # 4 expert layers x (forward, backward): the loops over the windows
    # of `row_bound` rows (the recomputed segment's is dead code: nothing
    # reads its output)
    assert len(re.findall(r" while\(", text)) == 8
    gdns = [op.attr("site") for op in main.global_block().ops
            if op.type == "gated_delta_rule"]
    assert [telemetry.REGISTRY.get("delta_grid_steps_per_step").value(
        site=s) for s in gdns] == [8 * 8] * 3
    assert 4e9 < _step_bytes("qwen3_next", compiled, 625.7e6) < 14e9


def test_phi4_flash_train_step_compiles_for_v5e(one_chip, for_the_chip):
    """The step of `phi4_mini_flash.b1_s4096` as the executor lowers it,
    at the published widths and the cell's cut (six layers, one of each
    kind; 25,008 rows of vocabulary), 1 x 4096 tokens, bf16 matmul
    operands, recomputation a layer: the plan is not the fallback (seven
    segments, the memory and the kept K, V carried between them, the
    tied embedding read before the first and by the last), it fits the
    chip's 16 GB, each of the three attention layers runs the flash
    kernels (D 64, values 128 wide: forward once, the recomputed segment
    keeping its results, dK/dV and dQ) at the blocks `_block_sizes` chooses, the
    window layer's grids are cut to the blocks its window holds, and
    each of the two Mamba layers runs the scan's kernel pair (forward,
    again in the recomputed segment, backward). Start-up runs on the CPU
    for the shapes alone (8.4 GB of host memory, ~4 s)."""
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.models import phi4_flash
    cfg = dict(phi4_flash.phi4_flash_config(), vocab_size=25008,
               layer_kinds=["mamba", "sliding", "mamba_memory", "full",
                            "gmu", "cross"],
               published_index=[0, 1, 16, 17, 18, 19])
    core.set_flag("FLAGS_use_bf16_matmul", True)
    try:
        main, startup, _, fetches = \
            phi4_flash.build_phi4_flash_pretrain_program(cfg, seq_len=4096)
        feed = phi4_flash.synthetic_pretrain_batch(cfg, 1, 4096)
        cb, lower = _decoder_step(main, startup, feed, fetches, one_chip)
        plan = cb._remat_plan
        assert plan is not None and len(plan.segments) == 7
        assert [len(s.outs) for s in plan.segments] == [1, 1, 2, 3, 1, 1, 1]
        compiled = lower().compile()
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", False)
    text = compiled.as_text()
    assert _kernel_names(text) == {
        ("fwd/fused_attention_qkv", "flash_fwd"): 3,
        ("fwd/fused_attention_qkv", "flash_bwd_dkv"): 3,
        ("fwd/fused_attention_qkv", "flash_bwd_dq"): 3,
        ("fwd/selective_scan", "ssm_scan_fwd"): 2 * 2,
        ("fwd/selective_scan", "ssm_scan_bwd"): 2}
    scans = [op.attr("site") for op in main.global_block().ops
             if op.type == "selective_scan"]
    grid = telemetry.REGISTRY.get("ssm_grid_steps_per_step")
    assert [grid.value(site=s) for s in scans] == [4 * 64] * 2
    # block pairs a forward kernel computes and the steps of its grid, at
    # the blocks chosen for each call: 40 heads x 15 pairs of 512 x 512
    # under the window (8 row blocks x 2, the first of them one), x 10 of
    # 1024 x 1024 causal (full and cross); 16 steps a head either way
    # (6,000 / 21,120 / 21,120 pairs in 6,400 / 40,960 / 40,960 steps at
    # 128 x 128, before PR 33)
    sites = phi4_flash.attention_sites(main)
    assert list(sites.values()) == [(40, 512), (40, 0), (40, 0)]
    pairs, steps = [], []
    for _, window in sites.values():
        mask = fa.Mask(True, window)
        blocks = fa._block_sizes("flash_fwd", 4096, 4096, 64, 128, mask, 2)
        pairs.append(40 * fa.visited_blocks(4096, 4096, *blocks, mask))
        steps.append(40 * fa.grid_steps(4096, 4096, *blocks, mask))
    assert (pairs, steps) == ([600, 400, 400], [640, 640, 640])
    for name, want in (("attn_kv_blocks_per_step", pairs),
                       ("attn_grid_steps_per_step", steps)):
        gauge = telemetry.REGISTRY.get(name)
        assert [gauge.value(site=s) for s in sites] == want
    assert 4e9 < _step_bytes("phi4_flash", compiled, 697.1e6) < 14.5e9


def test_laguna_train_step_compiles_for_v5e(one_chip, for_the_chip):
    """The step of `laguna_xs2.b1_s8192` as the executor lowers it, at
    the published widths and the cell's cut (five layers: the dense one
    and a whole period; 32 of 256 experts held; 12,544 rows of
    vocabulary), 1 x 8192 tokens, bf16 matmul operands, recomputation a
    layer: six segments, it fits under the 15.0e9 bytes of a step that
    fits (12 bytes a parameter of arguments), every layer runs the flash
    kernels at D 128 with ITS head count and mask (48 heads causal on
    layers 0 and 4, 64 heads under window 512 on layers 1-3: forward
    once, the recomputed segment keeping its results, dK/dV and dQ),
    and every expert product is
    XLA's grouped kernel over `row_bound` = 16,384 rows (twice the 8,192
    the 32 held experts of 256 expect; never the 65,536 a layer could be
    sent). Start-up runs on the CPU for the shapes alone (8.3 GB of host
    memory)."""
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.models import laguna
    from paddle_tpu.ops import decoder_ops
    cfg = dict(laguna.laguna_config(), vocab_size=12544, experts_held=32,
               layer_types=["full", "sliding", "sliding", "sliding", "full"],
               heads_per_layer=[48, 64, 64, 64, 48],
               mlp_types=["dense"] + ["sparse"] * 4)
    core.set_flag("FLAGS_use_bf16_matmul", True)
    try:
        main, startup, _, fetches = laguna.build_laguna_pretrain_program(
            cfg, seq_len=8192)
        feed = laguna.synthetic_pretrain_batch(cfg, 1, 8192)
        cb, lower = _decoder_step(main, startup, feed, fetches, one_chip)
        assert cb._remat_plan is not None and len(
            cb._remat_plan.segments) == 6  # five layers and the head
        compiled = lower().compile()
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", False)
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert _kernel_names(text) == {
        ("fwd/fused_attention_qkv", "flash_fwd"): 5,
        ("fwd/fused_attention_qkv", "flash_bwd_dkv"): 5,
        ("fwd/fused_attention_qkv", "flash_bwd_dq"): 5,
        **_expert_kernels(4)}
    assert decoder_ops.row_bound(8192, 8, 32, 256) == 16384
    passes = laguna.expert_passes(main)
    for name, want in (("moe_row_tile", 256),
                       ("moe_grid_row_tiles_per_step", 4 * 64)):
        gauge = telemetry.REGISTRY.get(name)
        assert [gauge.value(site=s) for s in passes] == [want] * 4
    assert "[16384,1024]" in text and "[65536,1024]" not in text
    sites = laguna.attention_sites(main)
    assert list(sites.values()) == [(48, 0), (64, 512), (64, 512),
                                    (64, 512), (48, 0)]
    heads = telemetry.REGISTRY.get("attn_query_heads")
    assert [heads.value(site=s) for s in sites] == [48, 64, 64, 64, 48]
    width = telemetry.REGISTRY.get("moe_router_width")
    assert [child.value() for child in width.children()][-4:] == [256] * 4
    assert 4e9 < _step_bytes("laguna", compiled, 691623936) < 15.0e9


def test_smallthinker_train_step_compiles_for_v5e(one_chip, for_the_chip):
    """The step of `smallthinker_21b_a3b.b1_s16384` as the executor
    lowers it, at the published widths and the cell's cut (four layers,
    one whole period; 16 of 64 experts held; 18,992 rows of vocabulary),
    1 x 16384 tokens, bf16 matmul operands, recomputation a layer: five
    segments (the router's choice crosses the attention block INSIDE a
    layer's segment), it fits under the 15.0e9 bytes of a step that fits
    (12 bytes a parameter of arguments), every layer runs the flash
    kernels at D 128 with 28 query heads over 4 (causal in full on layer
    0, under window 4096 on layers 1-3: forward once, the recomputed
    segment keeping its results, dK/dV and dQ), only the three window layers rotate, and every expert
    product is XLA's grouped kernel over passes of 49,152 rows
    (`row_bound`: twice the 24,576 the 16 held experts of 64 expect, of
    the 98,304 a layer could be sent: two windows at most, the second
    inside the op's loop). Start-up runs on the CPU for the shapes alone
    (6.7 GB of host memory)."""
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.models import smallthinker
    from paddle_tpu.ops import decoder_ops
    cfg = dict(smallthinker.smallthinker_config(), vocab_size=18992,
               experts_held=16, rope_layout=[0, 1, 1, 1],
               window_layout=[0, 1, 1, 1])
    core.set_flag("FLAGS_use_bf16_matmul", True)
    try:
        main, startup, _, fetches = \
            smallthinker.build_smallthinker_pretrain_program(cfg,
                                                             seq_len=16384)
        feed = smallthinker.synthetic_pretrain_batch(cfg, 1, 16384)
        cb, lower = _decoder_step(main, startup, feed, fetches, one_chip)
        assert cb._remat_plan is not None and len(
            cb._remat_plan.segments) == 5  # four layers and the head
        assert [len(s.outs) for s in cb._remat_plan.segments] == [1] * 5
        compiled = lower().compile()
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", False)
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert _kernel_names(text) == {
        ("fwd/fused_attention_qkv", "flash_fwd"): 4,
        ("fwd/fused_attention_qkv", "flash_bwd_dkv"): 4,
        ("fwd/fused_attention_qkv", "flash_bwd_dq"): 4,
        **_expert_kernels(4)}
    assert decoder_ops.row_bound(16384, 6, 16, 64) == 49152
    assert "[49152,1536]" in text and "[98304,1536]" not in text
    sites = smallthinker.attention_sites(main)
    assert list(sites.values()) == [(28, 0), (28, 4096), (28, 4096),
                                    (28, 4096)]
    for name, want in (("attn_window", [0, 4096, 4096, 4096]),
                       ("attn_kv_repeat", [7] * 4)):
        gauge = telemetry.REGISTRY.get(name)
        assert [gauge.value(site=s) for s in sites] == want
    passes = smallthinker.expert_passes(main)
    for name, want in (("moe_activation_relu", 1), ("moe_row_tile", 256),
                       ("moe_grid_row_tiles_per_step", 2 * 192)):
        gauge = telemetry.REGISTRY.get(name)
        assert [gauge.value(site=s) for s in passes] == [want] * 4
    assert sum(op.type == "rotary_embedding"
               for op in main.global_block().ops) == 2 * 3
    assert 4e9 < _step_bytes("smallthinker", compiled, 559290880) < 15.0e9


def _shapes_scope(startup):
    """A scope whose start-up state is there by shape and dtype alone:
    each persistable a zero broadcast to its shape, which holds no
    memory (680M parameters and Adam's moments would be 11 GB of host
    memory)."""
    scope = core.Scope()
    for var in startup.global_block().vars.values():
        if var.persistable:
            zero = np.zeros((), core.dtype_to_jnp(var.dtype))
            scope.var(var.name).get_tensor()._array = np.broadcast_to(
                zero, tuple(var.shape))
    return scope


def test_joyai_flash_train_step_compiles_for_v5e(one_chip, for_the_chip):
    """The step of `joyai_llm_flash.b1_s8192` as the executor lowers it,
    at the published widths and the cell's cut (five layers: the dense
    one and four sparse; 16 of 256 experts held; 16,160 rows of
    vocabulary; the MTP module whole), 1 x 8192 tokens, bf16 matmul
    operands, recomputation a layer: seven segments (five layers, the
    MTP block, the two heads), it fits under the 15.0e9 bytes that
    decide the 16-expert cut (ISSUE 41; 12 bytes a parameter of
    arguments), six latent-attention ops make the heads, every one of
    the six attention layers runs the flash kernels at D 192 / Dv 128,
    causal (forward once, the recomputed segment keeping its results,
    dK/dV and dQ), and
    every expert product is the grouped kernels over `row_bound` = 8,192
    rows (twice the 4,096 the 16 held experts of 256 expect)."""
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.models import joyai_flash
    from paddle_tpu.ops import decoder_ops
    cfg = dict(joyai_flash.joyai_flash_config(), vocab_size=16160,
               experts_held=16, mlp_types=["dense"] + ["sparse"] * 4)
    core.set_flag("FLAGS_use_bf16_matmul", True)
    try:
        main, startup, _, fetches = \
            joyai_flash.build_joyai_flash_pretrain_program(cfg, seq_len=8192)
        feed = joyai_flash.synthetic_pretrain_batch(cfg, 1, 8192)
        scope = _shapes_scope(startup)
        cb = _CompiledBlock(main, tuple(sorted(feed)), (fetches[0].name,),
                            scope, seed=0)
        assert cb._remat_plan is not None and len(
            cb._remat_plan.segments) == 7  # 5 layers, MTP, the heads
        compiled = _lower(cb, scope, feed, one_chip)().compile()
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", False)
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert _kernel_names(text) == {
        ("fwd/fused_attention_qkv", "flash_fwd"): 6,
        ("fwd/fused_attention_qkv", "flash_bwd_dkv"): 6,
        ("fwd/fused_attention_qkv", "flash_bwd_dq"): 6,
        **_expert_kernels(5)}
    assert "fwd/latent_qkv" in text
    assert decoder_ops.row_bound(8192, 8, 16, 256) == 8192
    sites = joyai_flash.attention_sites(main)
    assert list(sites.values()) == [(32, 0)] * 6
    latent = [op.attr("site") for op in main.global_block().ops
              if op.type == "latent_qkv"]
    for name, want in (("mla_heads", 32), ("mla_q_latent_width", 1536),
                       ("mla_kv_latent_width", 512), ("mla_rope_dim", 64)):
        gauge = telemetry.REGISTRY.get(name)
        assert [gauge.value(site=s) for s in latent] == [want] * 6
    routers = joyai_flash.router_biases(main)
    assert len(routers) == 5
    for name, want in (("moe_router_biased", 1),
                       ("moe_router_bias_rate", 0.001)):
        gauge = telemetry.REGISTRY.get(name)
        assert [gauge.value(site=s) for s in routers] == [want] * 5
    assert 4e9 < _step_bytes("joyai_flash", compiled, 680439808) < 15.0e9


def test_ling3_flash_train_step_compiles_for_v5e(one_chip, for_the_chip):
    """The step of `ling3_flash_vl.b1_s4096` as the executor lowers it,
    at the published widths and the cell's cut (six layers: five KDA and
    one latent attention, two dense and four sparse; 8 of 512 experts
    held; 19,648 rows of vocabulary), 1 x 4096 tokens, bf16 matmul
    operands, recomputation a layer: seven segments (six layers and the
    head), it fits under the 15.0e9 bytes a step that fits the chip
    leaves room beside (12 bytes a parameter of arguments; the CPU
    compile reads 11.57e9, 13.09e9 with the `lax.scan` lowering), the KDA
    op is in all five KDA layers, each running the delta rule's kernel
    pair (forward, again in the recomputed segment, backward) and no
    triangular solve, the one latent-attention layer runs the flash
    kernels at D 192 / Dv 128,
    causal (forward once, dK/dV and dQ), and every expert product is
    the grouped kernels over `row_bound` = 1,024 rows (twice the 512 the
    8 held experts of 512 expect)."""
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.models import ling3_flash
    from paddle_tpu.ops import decoder_ops
    cfg = dict(ling3_flash.ling3_flash_config(), vocab_size=19648,
               experts_held=8, mlp_types=["dense"] * 2 + ["sparse"] * 4)
    core.set_flag("FLAGS_use_bf16_matmul", True)
    try:
        main, startup, _, fetches = \
            ling3_flash.build_ling3_flash_pretrain_program(cfg, seq_len=4096)
        feed = ling3_flash.synthetic_pretrain_batch(cfg, 1, 4096)
        scope = _shapes_scope(startup)
        cb = _CompiledBlock(main, tuple(sorted(feed)), (fetches[0].name,),
                            scope, seed=0)
        assert cb._remat_plan is not None and len(
            cb._remat_plan.segments) == 7  # 6 layers, the head
        compiled = _lower(cb, scope, feed, one_chip)().compile()
    finally:
        core.set_flag("FLAGS_use_bf16_matmul", False)
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert _kernel_names(text) == {
        ("fwd/fused_attention_qkv", "flash_fwd"): 1,
        ("fwd/fused_attention_qkv", "flash_bwd_dkv"): 1,
        ("fwd/fused_attention_qkv", "flash_bwd_dq"): 1,
        ("fwd/kda", "delta_fwd"): 5 * 2,
        ("fwd/kda", "delta_bwd"): 5,
        **_expert_kernels(4)}
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert "fwd/latent_qkv" in text and "fwd/kda" in text
    assert decoder_ops.row_bound(4096, 8, 8, 512) == 1024
    kdas = [op.attr("site") for op in main.global_block().ops
            if op.type == "kda"]
    assert [telemetry.REGISTRY.get("kda_chunks_per_step").value(site=s)
            for s in kdas] == [64] * 5
    assert [telemetry.REGISTRY.get("delta_grid_steps_per_step").value(
        site=s) for s in kdas] == [8 * 8] * 5
    routers = ling3_flash.router_biases(main)
    assert len(routers) == 4
    for name, want in (("moe_router_groups", 8),
                       ("moe_router_groups_kept", 4),
                       ("moe_router_biased", 1)):
        gauge = telemetry.REGISTRY.get(name)
        assert [gauge.value(site=s) for s in routers] == [want] * 4
    latent = [op.attr("site") for op in main.global_block().ops
              if op.type == "latent_qkv"]
    assert [telemetry.REGISTRY.get(n).value(site=latent[0])
            for n in ("mla_q_latent_width", "mla_rope_interleaved")] \
        == [2560, 0]
    assert 4e9 < _step_bytes("ling3_flash", compiled, 707798176) < 15.0e9
