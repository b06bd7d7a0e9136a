"""The main path's kernels, compiled at real widths by the TPU's own compiler
for a v5e chip that is described, not attached.

Interpret mode (every other kernel test here) cannot see what Mosaic refuses:
a slice off the tiling, too much fast memory, a shape it cannot partition.
These compiles can, in a few seconds and with no chip. Nothing runs, so they
say nothing about results or speed.

Only one process may load the TPU's library, and it keeps it until it exits:
the topology is described inside a module-scoped fixture (never at import, so
every xdist worker collects the same tests), all such tests live in this one
file (one worker gets it), and every compile happens in the test's own process.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops.nn import block_attention
from mxnet_tpu.ops.pallas.flash_attention import flash_attention
from mxnet_tpu.ops.pallas.fused_conv1x1 import conv1x1_bn_act
from mxnet_tpu.ops.pallas.paged_attention import paged_attention
from mxnet_tpu.serving.generate.kv_cache import write_prefill, write_step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to JAX's persistent
    # cache but never read back without the chip: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes_dtypes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes_dtypes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (B, H, S, D), causal, kernels in the backward: BERT-base at seq 512, whose
# backward is a dense recompute (S <= 1024), and a 16K-token causal decoder
# head, whose backward is the two Pallas kernels
FLASH = [pytest.param((8, 12, 512, 64), False, 0, id="bert_s512"),
         pytest.param((1, 8, 16384, 128), True, 2, id="causal_s16k")]


@pytest.mark.parametrize("shape,causal,bwd_kernels", FLASH)
def test_flash_forward_compiles_to_one_kernel(one_chip, shape, causal,
                                              bwd_kernels):
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        interpret=False),
        *[(shape, jnp.bfloat16)] * 3, sharding=one_chip)
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("shape,causal,bwd_kernels", FLASH)
def test_flash_gradient_compiles(one_chip, shape, causal, bwd_kernels):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, interpret=False)
        return out.astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)),
                          *[(shape, jnp.bfloat16)] * 3, sharding=one_chip)
    assert text.count("tpu_custom_call") == 1 + bwd_kernels


# (N*H*W, Cin, Cout) of ResNet-50's first and last 1x1 convolutions at b32
@pytest.mark.parametrize("m,k,n", [
    pytest.param(32 * 56 * 56, 64, 256, id="stage1_56x56_64to256"),
    pytest.param(32 * 7 * 7, 2048, 512, id="stage4_7x7_2048to512")])
def test_conv1x1_bn_act_compiles(one_chip, m, k, n):
    text = _compiled_text(
        lambda x, w, scale, shift: conv1x1_bn_act(x, w, scale, shift,
                                                  relu=True),
        ((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16),
        ((k,), jnp.float32), ((k,), jnp.float32), sharding=one_chip)
    assert text.count("tpu_custom_call") == 1


# cell gpt1.decode_chat's KV pools: 12 layers, 2049 pages of 16 positions,
# 768 wide, float32 — 1.21 GB each, 32 pages a sequence
POOL = (12, 2049, 16, 768)
PAGE, PAGES_PER_SEQ = 16, 32
I32 = jnp.int32


def _step_alone(B):
    def fn(k_pool, v_pool, ks, vs, tables, positions, valid):
        return write_step((k_pool, v_pool), (ks, vs), tables, positions,
                          valid, PAGE)
    return fn, [((12, B, 768), jnp.float32)] * 2 + [
        ((B, PAGES_PER_SEQ), I32), ((B,), I32), ((B,), jnp.bool_)]


def _prefill_alone(S):
    def fn(k_pool, v_pool, ks, vs, table_row, length):
        return write_prefill((k_pool, v_pool), (ks, vs), table_row, length,
                             PAGE)
    return fn, [((12, S, 768), jnp.float32)] * 2 + [
        ((PAGES_PER_SEQ,), I32), ((), I32)]


POOL_WRITES = [
    pytest.param(_step_alone(64), id="write_step_b64"),
    pytest.param(_step_alone(8), id="write_step_b8"),
    pytest.param(_prefill_alone(16), id="write_prefill_s16"),
    pytest.param(_prefill_alone(128), id="write_prefill_s128"),
    pytest.param(_prefill_alone(512), id="write_prefill_s512")]


@pytest.mark.parametrize("program", POOL_WRITES)
def test_pool_write_is_in_place(one_chip, program):
    """With the pools donated, the compiled program makes no pool-sized
    copy and the write takes under 64 MB of scratch (the advanced-index
    scatter compiled to a relayout copy before and after it, per pool, and
    1.6 GB). The alias alone, which hlolint's IR1000 checks in the lowered
    StableHLO, does not show that: the copies come from the TPU compiler's
    layout assignment, after lowering."""
    fn, rest = program
    _holds_the_pools_in_place(one_chip, fn, POOL, jnp.float32, rest)


def _holds_the_pools_in_place(one_chip, fn, pool, dtype, rest):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in [(pool, dtype)] * 2 + rest]
    comp = jax.jit(fn, donate_argnums=(0, 1)).lower(*args).compile()
    text = comp.as_text()
    assert "input_output_alias" in text.splitlines()[0]
    hlo = ("f32" if dtype == jnp.float32 else "bf16") + "[%d,%d,%d,%d]" % pool
    makers = set(re.findall(
        r"= " + re.escape(hlo) + r"\{[^}]*\} ([\w-]+)\(", text))
    assert makers and "copy" not in makers, makers
    temp = comp.memory_analysis().temp_size_in_bytes
    assert temp < 64 << 20, temp
    return text


# the decode cells' steps: (lanes, rows a lane, query heads, KV heads, the
# pools, pages a lane, dtype). gpt1.decode_chat and gpt1.decode_long at their
# largest and a small bucket; sdar_30b_a3b.gen256_s2: 6 layers, 4097 pages,
# rows of 4 KV heads of 128 in bfloat16 under 32 query heads, blocks of 4
DECODE = [
    pytest.param(64, 1, 12, 12, POOL, 32, jnp.float32, id="gpt1_b64"),
    pytest.param(8, 1, 12, 12, POOL, 32, jnp.float32, id="gpt1_b8"),
    pytest.param(64, 4, 32, 4, (6, 4097, 16, 512), 64, jnp.bfloat16,
                 id="sdar_b64")]


@pytest.mark.parametrize("B,L,heads,kv_heads,pool,P,dtype", DECODE)
def test_paged_attention_compiles_to_one_kernel(one_chip, B, L, heads,
                                                kv_heads, pool, P, dtype):
    """Under Mosaic, at the cells' shapes, on the whole pools: one kernel,
    and nothing of a pool's size beside it (no slice of the layer)."""
    D = pool[3] // kv_heads
    comp = jax.jit(lambda q, k, v, tables, lengths: paged_attention(
        q, k, v, tables, lengths, pool[0] - 1, heads=heads,
        kv_heads=kv_heads, interpret=False)).lower(*[
            jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
                ((B, L, heads * D), dtype), (pool, dtype), (pool, dtype),
                ((B, P), I32), ((B,), I32))]).compile()
    assert comp.as_text().count("tpu_custom_call") == 1
    assert comp.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("B,L,heads,kv_heads,pool,P,dtype",
                         [DECODE[0], DECODE[2]])
def test_decode_step_reads_and_writes_the_pools_in_place(
        one_chip, B, L, heads, kv_heads, pool, P, dtype):
    """The decode step's cache traffic without its model: every layer
    attends through the page table, to the context in the pools and to the
    step's own rows, then the rows are written. With the pools donated the
    program holds no pool-sized copy and under 64 MB of scratch: nothing of
    the size of all lanes' context (2 x 1.21 GB when it was gathered)."""
    layers = pool[0]

    def fn(k_pool, v_pool, x, tables, positions, valid):
        ks, vs = [], []
        for i in range(layers):
            ctx = paged_attention(x, k_pool, v_pool, tables, positions[:, 0],
                                  i, heads=heads, kv_heads=kv_heads,
                                  interpret=False)
            k, v = x[..., :pool[3]] * 2, x[..., :pool[3]] * 3
            x = block_attention(x, k, v, positions, *ctx, heads=heads,
                                kv_heads=kv_heads, block_length=L)
            ks.append(k)
            vs.append(v)
        return (x,) + write_step((k_pool, v_pool),
                                 (jnp.stack(ks), jnp.stack(vs)), tables,
                                 positions, valid, PAGE)

    D = pool[3] // kv_heads
    text = _holds_the_pools_in_place(one_chip, fn, pool, dtype, [
        ((B, L, heads * D), dtype), ((B, P), I32), ((B, L), I32),
        ((B,), jnp.bool_)])
    assert text.count("tpu_custom_call") == layers


# cell deepseek_v3.doc_qa_c64 at its published widths: the latent pool of 5
# layers, 20,481 pages, rows of 512 + 64 numbers stored 640 wide in bfloat16,
# 320 pages a lane; 128 query heads of 576 on the one shared row
LATENT_POOL, LATENT_P = (5, 20481, 16, 640), 320


def test_latent_paged_attention_compiles_to_one_kernel(one_chip):
    """One pool operand, one kernel, nothing of the pool's size beside it,
    the result 512 wide: each page is fetched once and used as K and as V."""
    comp = jax.jit(lambda q, pool, tables, lengths: paged_attention(
        q, pool, None, tables, lengths, 4, heads=128, kv_heads=1, v_dim=512,
        sm_scale=0.135, interpret=False)).lower(*[
            jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
                ((64, 1, 128 * 576), jnp.bfloat16),
                (LATENT_POOL, jnp.bfloat16), ((64, LATENT_P), I32),
                ((64,), I32))]).compile()
    text = comp.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "f32[64,1,128,512]" in text
    assert comp.memory_analysis().temp_size_in_bytes < 64 << 20


def test_a_latent_pool_is_written_in_place(one_chip):
    """The one array donated: a step's rows and a prefill's (576 numbers,
    padded to the stored 640) are written with no pool-sized copy."""
    def fn(pool, rows, tables, positions, valid, many, table_row, length):
        (pool,) = write_step((pool,), (rows,), tables, positions, valid, PAGE)
        return write_prefill((pool,), (many,), table_row, length, PAGE)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (LATENT_POOL, jnp.bfloat16), ((5, 64, 576), jnp.bfloat16),
        ((64, LATENT_P), I32), ((64,), I32), ((64,), jnp.bool_),
        ((5, 4096, 576), jnp.bfloat16), ((LATENT_P,), I32), ((), I32))]
    comp = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    text = comp.as_text()
    assert "input_output_alias" in text.splitlines()[0]
    makers = set(re.findall(
        r"= bf16\[5,20481,16,640\]\{[^}]*\} ([\w-]+)\(", text))
    assert makers and "copy" not in makers, makers
    assert comp.memory_analysis().temp_size_in_bytes < 64 << 20


# the plain form of latent attention in that cell's prefill: 128 heads, keys
# of 128 + 64 and values of 128, at the shortest rung the kernel takes, the
# longest a prompt fills and the ladder's last
@pytest.mark.parametrize("S", [512, 4096, 5120])
def test_flash_with_narrower_values_compiles_to_one_kernel(one_chip, S):
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True, sm_scale=0.135,
                                        interpret=False),
        ((1, 128, S, 192), jnp.bfloat16), ((1, 128, S, 192), jnp.bfloat16),
        ((1, 128, S, 128), jnp.bfloat16), sharding=one_chip)
    assert text.count("tpu_custom_call") == 1
    assert f"bf16[128,{S},128]" in text        # the result is the values' width


# that cell's routed experts: 16 of 256 held, 7168 x 2048, 8 a row under the
# sigmoid rule; a step's 64 rows take their 512 pairs in one pass, a
# 4,096-row prefill takes the pairs routed here 2,048 at a time
@pytest.mark.parametrize("rows,pairs", [(64, 512), (4096, 2048)])
def test_a_share_of_wide_experts_compiles_to_three_grouped_matmul_kernels(
        one_chip, rows, pairs, monkeypatch):
    from mxnet_tpu.ops import nn as ops
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    E, H, F, held = 256, 7168, 2048, 16
    bf = jnp.bfloat16
    args = [jax.ShapeDtypeStruct(s, bf, sharding=one_chip) for s in (
        (rows, H), (H, E), (held, H, F), (held, H, F), (held, F, H), (E,))]
    comp = jax.jit(lambda x, r, g, u, d, b: ops.moe_ffn(
        x, r, g, u, d, b, top_k=8, n_group=8, topk_group=4,
        routed_scale=2.5)).lower(*args).compile()
    kernels = re.findall(r"= (\S+) custom-call\([^\n]*tpu_custom_call",
                         comp.as_text())
    assert sorted(k.split("{")[0] for k in kernels) == sorted(
        [f"f32[{pairs},{F}]"] * 2 + [f"f32[{pairs},{H}]"])
    # the gathered rows and the float32 products of one pass, not of all
    # 8 x 4,096 pairs (1.9 GB)
    assert comp.memory_analysis().temp_size_in_bytes < 256 << 20


# the routed experts of the sdar_30b_a3b cell at its published widths: rows
# of one lane's block, of the full step (64 lanes x 4) and of an S=1,024
# prefill; 128 experts of 2048 x 768, 8 a row
@pytest.mark.parametrize("rows", [4, 256, 1024])
def test_moe_ffn_compiles_to_three_grouped_matmul_kernels(one_chip, rows,
                                                          monkeypatch):
    from mxnet_tpu.ops import nn as ops
    # the op picks the Pallas kernel from the backend at trace time; here
    # the backend is the CPU and the chip is only described
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    E, H, F = 128, 2048, 768
    text = _compiled_text(
        lambda x, r, g, u, d: ops.moe_ffn(x, r, g, u, d, top_k=8),
        ((rows, H), jnp.bfloat16), ((H, E), jnp.bfloat16),
        ((E, H, F), jnp.bfloat16), ((E, H, F), jnp.bfloat16),
        ((E, F, H), jnp.bfloat16), sharding=one_chip)
    kernels = re.findall(r"= (\S+) custom-call\([^\n]*tpu_custom_call", text)
    grouped = [k for k in kernels if k.startswith("f32[")]
    assert len(grouped) == 3
    pairs = rows * 8
    assert sorted(k.split("{")[0] for k in grouped) == sorted(
        [f"f32[{pairs},{F}]"] * 2 + [f"f32[{pairs},{H}]"])


# a data-parallel train step's dropout at the shape of the four-chip BERT
# cell (256 samples a chip, 128 tokens, 768 wide). The SPMD partitioner does
# not divide an rng-bit-generator: drawn whole, the mask of all 1,024 samples
# is drawn on every chip (12% of the cell's device time before PR 33)
DROPOUT_STEPS = [pytest.param(4, 0, id="dp4_step"),
                 pytest.param(4, 2, id="dp4_step_n"),
                 pytest.param(1, 0, id="one_chip_step")]


@pytest.mark.parametrize("chips,k", DROPOUT_STEPS)
def test_a_train_step_draws_dropout_bits_for_its_own_shard_only(
        topo, monkeypatch, chips, k):
    import mxnet_tpu as mx
    from jax.sharding import Mesh
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.mesh import DeviceMesh

    net = nn.HybridSequential()
    net.add(nn.Dense(768, in_units=768, flatten=False), nn.Dropout(0.1),
            nn.Dense(8, in_units=768, flatten=False))
    net.initialize()
    mesh = DeviceMesh(Mesh(topo.devices[:chips], ("dp",)))

    def described(a, sharding=None, **_):
        # nothing can be placed on a described chip: the step carries shapes
        return jax.tree_util.tree_map(
            lambda leaf, sh: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                                  sharding=sh), a, sharding)

    with monkeypatch.context() as patched:
        patched.setattr(jax, "device_put", described)
        step = parallel.ParallelTrainStep(
            net, gluon.loss.L2Loss(), mx.optimizer.SGD(learning_rate=0.1),
            mesh)

    rep, lead = mesh.replicated(), (k,) if k else ()

    def sds(shape, dtype=jnp.float32, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    data = step._stacked(step._data_sharding) if k else step._data_sharding
    n = len(step._trainable_idx)
    if k:
        fn = step._build_n(k)
    else:
        step._build()
        fn = step._step_fn
    # rbg, as random._prng_impl picks on the chip
    key = sds((), jax.random.key(0, impl="rbg").dtype)
    text = fn.lower(
        step.params, [], step._opt_states,
        sds(lead + (256 * chips, 128, 768), sharding=data),
        sds(lead + (256 * chips, 128, 8), sharding=data), (), key,
        sds(lead + (n,)), sds(lead + (n,)), sds(())).compile().as_text()
    draws = re.findall(r"= (u32\[[\d,]*\])\S* rng-bit-generator\(", text)
    assert draws and set(draws) == {"u32[256,128,768]"}
    if chips == 1:
        assert "partition-id" not in text
