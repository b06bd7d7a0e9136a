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
    kernels = [k.split("{")[0] for k in re.findall(
        r"= (\S+) custom-call\([^\n]*tpu_custom_call", comp.as_text())]
    assert sorted(k for k in kernels if k.startswith("f32[")) == sorted(
        [f"f32[{pairs},{F}]"] * 2 + [f"f32[{pairs},{H}]"])
    # a step's one pass: its rows read their pairs in one kernel more; a
    # prompt's passes add theirs to the rows by the 0/1 product
    assert [k for k in kernels if not k.startswith("f32[")] == (
        [f"bf16[{rows},{H}]"] if pairs == rows * 8 else [])
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


# ---------------------------------------------------------------------------
# cell mellum2_12b.repo_qa_c32 at its published widths: 32 query heads on 4 KV
# heads of 128 in bfloat16; the full group's pools of 2 layers and 32,769
# pages (1,024 a lane), the window group's of 6 layers and 32 rings of 65
# pages + the scratch page; a window of 1,024
# ---------------------------------------------------------------------------
FULL_POOL, RING_POOL, RING = (2, 32769, 16, 512), (6, 2081, 16, 512), 65


def test_paged_attention_with_a_bound_on_a_ring_compiles_to_one_kernel(
        one_chip):
    comp = jax.jit(lambda q, k, v, tables, lengths, starts: paged_attention(
        q, k, v, tables, lengths, 5, starts, heads=32, kv_heads=4,
        interpret=False)).lower(*[
            jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
                ((32, 1, 4096), jnp.bfloat16), (RING_POOL, jnp.bfloat16),
                (RING_POOL, jnp.bfloat16), ((32, RING), I32), ((32,), I32),
                ((32,), I32))]).compile()
    text = comp.as_text()
    assert text.count("tpu_custom_call") == 1
    # the name the new reader looks for in a trace
    assert "f32[32,1,32,512]" in text
    assert comp.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("S", [4096, 16384])
def test_flash_with_a_window_and_grouped_heads_compiles_to_one_kernel(
        one_chip, S):
    comp = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=1024, interpret=False)).lower(*[
            jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in ((1, 32, S, 128), (1, 4, S, 128), (1, 4, S, 128))]
    ).compile()
    text = comp.as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"bf16[32,{S},128]" in text
    # K and V are read through the index map: nothing repeats them eightfold
    # (the scratch is the log-sum-exp the forward drops, S x 32 x 128 float32;
    # K and V repeated would be as much again)
    assert comp.memory_analysis().temp_size_in_bytes < 1.25 * S * 32 * 128 * 4


@pytest.fixture(scope="module")
def cut_model():
    """The cut model of the cell as a block with no weights drawn (its
    shapes are all a compile needs), its parameters as shapes, and the two
    groups' pools."""
    from chipbench import harness
    from chipbench.models import mellum2 as family
    from mxnet_tpu.gluon.model_zoo.moe_lm import MoEDecoderLM
    cell, config = harness.load_cell("mellum2_12b.repo_qa_c32")
    lm = MoEDecoderLM(
        num_layers=config["num_hidden_layers"], units=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        expert_hidden=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        vocab_size=config["vocab_size"], dtype="bfloat16",
        layer_types=config["layer_types"],
        sliding_window=config["sliding_window"],
        rope_by_type=family.rope_by_type(config), prefix="cut_")
    plist = list(lm.collect_params().values())
    assert sum(int(onp.prod(p.shape)) for p in plist) == 3_794_968_832
    return cell, lm, plist


import numpy as onp  # noqa: E402  (the fixture above)


def _cut_model_args(one_chip, plist, lanes):
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    params = tuple(sds(tuple(p.shape), jnp.bfloat16) for p in plist)
    tables = (sds((lanes, 1024), I32), sds((lanes, RING), I32))
    pools = (sds(FULL_POOL, jnp.bfloat16),) * 2 \
        + (sds(RING_POOL, jnp.bfloat16),) * 2
    return sds, params, tables, pools


def test_the_cut_models_longest_prefill_compiles_within_the_chip(
        one_chip, cut_model, monkeypatch):
    """The 16,384-row ``jit_prefill`` at the published widths: eight flash
    kernels and the routed passes' grouped matmuls, under 3.5 GB of
    temporaries beside 10.15 GB of weights and pools, and no (S, V) array:
    the head multiplies the one row that is read. A pass's float32
    down-projection is the one array of its size: each row reads its own
    pairs from it (no weighted copy, no copy in pair order)."""
    import functools
    from mxnet_tpu.serving.generate import engine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell, lm, plist = cut_model
    S = cell["max_seq_len"]
    sds, params, tables, pools = _cut_model_args(one_chip, plist, 1)
    comp = jax.jit(functools.partial(engine._prefill, lm, plist, 16, True),
                   donate_argnums=(4, 5, 6, 7)).lower(
        params, sds((1, S), I32), sds((1,), I32), tables, *pools).compile()
    text, mem = comp.as_text(), comp.memory_analysis()
    assert mem.temp_size_in_bytes < 3.5e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    assert not re.search(r"\[(1,)?16384,98304\]", text)
    assert "f32[98304]" in text         # one row's logits
    flash = re.findall(
        r"= \(bf16\[32,16384,128\]\S*, f32\[32,16384,128\]\S*\) custom-call\(",
        text)
    assert len(flash) == 8
    # the routed pairs 32,768 at a time: no product over all 131,072
    assert "f32[32768,896]" in text and "f32[131072," not in text
    # beside each routed layer's grouped matmul result nothing of its size;
    # the rows of a pass are the pair-sum kernel's (8 routed layers)
    assert re.findall(r"= f32\[32768,2304\]\S* ([\w-]+)\(", text) \
        == ["custom-call"] * 8
    assert len(re.findall(r"= bf16\[4096,2304\]\S* custom-call\([^\n]*"
                          r"custom_call_target=\"tpu_custom_call\"", text)) == 8
    # the parent's tree compiles this program to 2,989,258,752 B of
    # temporaries (the weighted copy and the copy in pair order beside each
    # result; its blocks' results stacked, then cut); here 2,393,777,664
    assert mem.temp_size_in_bytes <= 2_989_258_752, mem.temp_size_in_bytes


def test_gpt1s_longest_prefill_multiplies_one_row_by_the_vocabulary(
        one_chip, monkeypatch):
    """``gpt1``'s S=512 ``jit_prefill`` at its published widths, no weight
    drawn: twelve flash kernels, the pools written in place, and no (S, V)
    array (83 MB of float32 a prefill, 31.8 GFLOP at ``highest``): a
    ``TransformerLM`` takes the row that is read before its tied head."""
    import functools
    from chipbench import harness
    from mxnet_tpu.gluon.model_zoo.bert import TransformerLM
    from mxnet_tpu.serving.generate import engine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell, config = harness.load_cell("gpt1.decode_long")
    S, V = cell["max_seq_len"], config["vocab_size"]
    lm = TransformerLM(
        num_layers=config["n_layer"], units=config["n_embd"],
        hidden_size=4 * config["n_embd"], num_heads=config["n_head"],
        vocab_size=V, max_length=config["n_positions"], prefix="gpt1_")
    plist = list(lm.collect_params().values())
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    params = tuple(sds(tuple(p.shape), jnp.float32) for p in plist)
    pool = (config["n_layer"], cell["num_pages"], 16, config["n_embd"])
    comp = jax.jit(functools.partial(engine._prefill, lm, plist, 16, True),
                   donate_argnums=(4, 5)).lower(
        params, sds((1, S), I32), sds((1,), I32), sds((1, S // 16), I32),
        sds(pool, jnp.float32), sds(pool, jnp.float32)).compile()
    text = comp.as_text()
    assert not re.search(r"\[(1,)?%d,%d\]" % (S, V), text)
    assert re.search(r"f32\[(1,)?%d\]" % V, text)     # one row's logits
    assert text.count("tpu_custom_call") == config["n_layer"]
    assert "input_output_alias" in text.splitlines()[0]
    # what the parent's took, the (512, V) logits among it: 46 MB
    assert comp.memory_analysis().temp_size_in_bytes < 42 << 20


def test_the_cut_models_step_compiles_in_place(one_chip, cut_model,
                                               monkeypatch):
    """The 32-lane ``jit_decode``: eight paged-attention kernels (two on the
    full group's pools, six bounded on the rings), 24 grouped matmuls and
    the eight layers' pair sums; the four pools donated and written in
    place."""
    import functools
    from mxnet_tpu.serving.generate import engine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _, lm, plist = cut_model
    sds, params, tables, pools = _cut_model_args(one_chip, plist, 32)
    comp = jax.jit(functools.partial(engine._decode, lm, plist, 16, None),
                   donate_argnums=(5, 6, 7, 8)).lower(
        params, sds((32,), I32), sds((32,), I32), tables,
        sds((32,), jnp.bool_), *pools).compile()
    text = comp.as_text()
    assert text.count("tpu_custom_call") == 8 + 24 + 8
    assert len(re.findall(r"= \(f32\[32,1,32,512\]", text)) == 8
    assert len(re.findall(r"= bf16\[32,2304\]\S* custom-call\(", text)) == 8
    assert "input_output_alias" in text.splitlines()[0]
    for pool in (FULL_POOL, RING_POOL):
        makers = set(re.findall(
            r"= bf16\[%d,%d,%d,%d\]\{[^}]*\} ([\w-]+)\(" % pool, text))
        assert makers and "copy" not in makers, makers
    assert comp.memory_analysis().temp_size_in_bytes < 128 << 20


def test_the_block_step_of_two_blocks_a_lane_compiles_in_place(one_chip,
                                                              monkeypatch):
    """``sdar_30b_a3b.gen256_s2``'s 64-lane ``jit_decode`` at its published
    widths, no weight drawn: eight rows a lane through the layers (six paged
    kernels, 18 grouped matmuls over 64 x 8 x 8 = 4,096 pairs), the rows of
    one block a lane through the head (no (64, 8, V) logits: 311 MB of
    float32 a step), each layer's 512 rows summing their pairs in one kernel,
    slot 0's rows written into the donated pools in place."""
    import functools
    from chipbench import harness
    from mxnet_tpu.gluon.model_zoo.moe_lm import MoEDecoderLM
    from mxnet_tpu.serving.generate import engine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell, config = harness.load_cell("sdar_30b_a3b.gen256_s2")
    lm = MoEDecoderLM(
        num_layers=config["num_hidden_layers"], units=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        expert_hidden=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        vocab_size=config["vocab_size"], block_length=config["block_length"],
        mask_token_id=config["mask_token_id"], dtype="bfloat16",
        prefix="sdar_")
    plist = list(lm.collect_params().values())
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    B, L, V = cell["max_batch_size"], config["block_length"], \
        config["vocab_size"]
    pool = (config["num_hidden_layers"], cell["num_pages"], PAGE,
            config["num_key_value_heads"] * config["head_dim"])
    assert (B, L, V, pool) == (64, 4, 151936, (6, 4097, 16, 512))
    comp = jax.jit(functools.partial(engine._decode, lm, plist, PAGE,
                                     config["mask_token_id"]),
                   donate_argnums=(5, 6)).lower(
        tuple(sds(tuple(p.shape), jnp.bfloat16) for p in plist),
        sds((B, 2 * L), I32), sds((B, 2 * L), I32),
        sds((B, cell["max_seq_len"] // PAGE), I32), sds((B,), jnp.bool_),
        sds(pool, jnp.bfloat16), sds(pool, jnp.bfloat16)).compile()
    text = comp.as_text()
    assert set(re.findall(r"f32\[64,\d+,151936\]", text)) \
        == {"f32[64,4,151936]"}
    kernels = re.findall(r"= (\S+?)\{[^}]*\} custom-call\([^\n]*tpu_custom_call",
                         text)
    assert text.count("tpu_custom_call") == 6 + 18 + 6
    assert len(re.findall(r"= bf16\[512,2048\]\S* custom-call\(", text)) == 6
    assert sorted(k for k in kernels if k.startswith("f32[4096")) == \
        ["f32[4096,2048]"] * 6 + ["f32[4096,768]"] * 12
    assert "input_output_alias" in text.splitlines()[0]
    makers = set(re.findall(
        r"= bf16\[%d,%d,%d,%d\]\{[^}]*\} ([\w-]+)\(" % pool, text))
    assert makers and "copy" not in makers, makers
    # what the step of one block a lane took (178 MB at the parent)
    assert comp.memory_analysis().temp_size_in_bytes < 256 << 20


# ---------------------------------------------------------------------------
# the other decode families' programs did not move: a pool of one group and
# the kernel without a bound lower, for the TPU, to the StableHLO text they
# had at the parent commit (PR 33, 2320329), held here as digests of that
# text. The digests were made by running ``_program_digests`` of this file
# with the parent's tree first on the path; source locations, the kernels'
# debug info and the numbers jax appends to private functions' names are
# taken out of the text first. ``deepseek_v3``'s prefill is not among them:
# its head now multiplies the one row that is read. ``sdar_30b_a3b.step`` is
# PR 35's own (two blocks a lane, the rows of one through the head; made by
# the same function on that PR's tree), and ``gpt1.prefill_s64`` and
# ``gpt1.prefill_s512`` are PR 37's own (a ``TransformerLM``'s head
# multiplies the one row that is read; re-made by the same function on that
# PR's tree): every other program is the parent's. ``mellum2_12b``'s, a
# windowed ``MoEDecoderLM`` of two cache groups, were made on PR 37's tree
# (the parent of PR 38, which gave that class the attention gate, the
# post-norms, NoPE layers and the FFN of ``MLADecoderLM``). The programs
# whose routed pass is one pass were re-made by the same function on the
# tree where each row reads its own pairs' results (``ops/pallas/
# pair_sum.py``): ``sdar_30b_a3b``'s three, ``deepseek_v3.step`` and
# ``mellum2_12b``'s three. ``trinity_large.share_passes`` (``ops.moe_ffn``
# at a held share's prefill-pass widths, whose pairs go 2,048 a pass and
# reach their rows by the 0/1 product) was made on that tree's parent
# (ae6f1e3) and holds that branch to the parent's text.
# ---------------------------------------------------------------------------
PARENT_DIGESTS = {
    "gpt1.step":
        "299ddc580175265bde6fac71701056159be0eb49e6ab1c5ec5e1fe5e1e8c1795",
    "gpt1.prefill_s64":
        "a565780d948362a0856bec11a711c3095883cc3f3f63f0022be2f71c0dc5704f",
    "gpt1.prefill_s512":
        "116731611874cfde70a33c878f216f62613223f886e6f8a84fee4e2c76a6bb9a",
    "sdar_30b_a3b.step":
        "de131264f9408b99162ea29f29d23889c537d80f526af245d81ba50010312f4f",
    "sdar_30b_a3b.prefill_s64":
        "97fe9a4be895531eb2c1c0885684ea33678a89413aa437ec1939b9f4e4d0349b",
    "sdar_30b_a3b.prefill_s512":
        "9ddd142acb479f772a12734b810af4c1376e78ef830c099b06e53a0551cc1b75",
    "deepseek_v3.step":
        "2248edf58773c697a95ef12914e67ec168fce99be918b383ead46a779dbe1c87",
    "mellum2_12b.step":
        "a37df800ad3afa62c67a04a888ea2835ed9a9aa69ce1ff9fcd4bb5aee94c9352",
    "mellum2_12b.prefill_s64":
        "cf43b5bca9caf982d3516dab5460fd8dc37c6dd6e1c04dc93ea72985ba27f021",
    "mellum2_12b.prefill_s512":
        "7ff7b839d57f4f874149be687376b59cf5367719dcab120896a27f4fbf2e6638",
    "trinity_large.share_passes":
        "d109a2a7903d0eaebc67c4e5af5e1d3d7e98c011b097ea6426f2405569e87764",
}


def _small_endpoints():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import TransformerLM
    from mxnet_tpu.gluon.model_zoo.mla_lm import MLADecoderLM
    from mxnet_tpu.gluon.model_zoo.moe_lm import MoEDecoderLM
    lm = TransformerLM(vocab_size=96, units=64, hidden_size=128, num_layers=2,
                       num_heads=4, max_length=1024, prefix="g_")
    yield "gpt1", lm
    lm = MoEDecoderLM(num_layers=2, units=256, num_heads=4, num_kv_heads=2,
                      head_dim=128, expert_hidden=128, num_experts=8,
                      experts_per_token=2, vocab_size=96, block_length=4,
                      mask_token_id=95, dtype="bfloat16", prefix="s_")
    yield "sdar_30b_a3b", lm
    lm = MLADecoderLM(
        num_layers=2, units=256, num_heads=4, q_rank=64, kv_rank=128,
        nope_dim=32, rope_dim=16, v_dim=32, dense_layers=1, dense_hidden=256,
        expert_hidden=128, num_experts=8, experts_per_token=2, n_group=2,
        topk_group=1, vocab_size=96, held_experts=(0, 4), dtype="bfloat16",
        rope_scaling={"factor": 40, "original_max_position_embeddings": 64,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                      "mscale_all_dim": 1}, prefix="d_")
    yield "deepseek_v3", lm
    lm = MoEDecoderLM(
        num_layers=4, units=256, num_heads=4, num_kv_heads=2, head_dim=128,
        expert_hidden=128, num_experts=8, experts_per_token=2, vocab_size=96,
        dtype="bfloat16", layer_types=["sliding_attention"] * 3
        + ["full_attention"], sliding_window=256,
        rope_by_type={"sliding_attention": {"theta": 5e5, "scaling": None},
                      "full_attention": {"theta": 5e5, "scaling": {
                          "factor": 16, "original_max_position_embeddings":
                          128, "beta_fast": 32, "beta_slow": 1,
                          "attention_factor": 1.277}}}, prefix="m_")
    yield "mellum2_12b", lm


def _program_digests(chip):
    """{program: sha256 of its lowered text} for the small endpoints' step
    and two prefill rungs (one dense, one through the flash kernel)."""
    import hashlib
    import io
    import jax._src.tpu_custom_call as tcc
    import mxnet_tpu as mx
    from mxnet_tpu import serving

    def stripped(module, *, ir_version=None):
        """``_lower_mosaic_module_to_asm`` with the kernel's source locations
        taken out before it is serialised."""
        has = tcc.tpu.private_has_communication(module.operation)
        with module.context as ctx, module.operation.location as _:
            op = module.operation.clone()
            was = ctx.allow_unregistered_dialects
            ctx.allow_unregistered_dialects = True
            version = f"target-version={ir_version}" \
                if ir_version is not None else ""
            try:
                tcc.PassManager.parse(
                    "builtin.module(strip-debuginfo,mosaic-serde{"
                    "serialize=true " + version + "})").run(op)
            finally:
                ctx.allow_unregistered_dialects = was
            buf = io.BytesIO()
            op.write_bytecode(buf, desired_version=0)
            return buf.getvalue(), has

    def text_of(fn, args):
        text = jax.jit(fn).lower(*args).as_text()
        text = re.sub(r"loc\(.*?\)|#loc.*", "", text)
        return re.sub(r"(@[A-Za-z_]\w*?)_\d+\b", r"\1", text)

    lower, tcc._lower_mosaic_module_to_asm = \
        tcc._lower_mosaic_module_to_asm, stripped
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    out = {}
    try:
        for name, lm in _small_endpoints():
            lm.initialize(mx.init.Normal(0.1))
            eng = serving.DecodeEndpoint(name, lm, max_seq_len=1024,
                                         max_batch_size=8, num_pages=129)
            s = lambda shape, d=I32: jax.ShapeDtypeStruct(shape, d,
                                                          sharding=chip)
            of = lambda arrays: tuple(s(tuple(a.shape), a.dtype)
                                      for a in arrays)
            params, pools = of(eng._param_datas()), of(eng.pool.arrays)
            P = eng.pool.pages_per_seq
            rows = eng._rows_shape(8)
            # one table, or a tuple of each cache group's
            tables = lambda B: jax.tree.map(
                lambda t: s(t.shape),
                eng.pool.split_tables(onp.zeros((B, P), onp.int32)))
            # the endpoint's own jit pins the CPU: the traced function anew
            out[f"{name}.step"] = text_of(
                eng._decode_fn().__wrapped__,
                (params, s(rows), s(rows), tables(8), s((8,), jnp.bool_),
                 *pools))
            for S in (64, 512):
                out[f"{name}.prefill_s{S}"] = text_of(
                    eng._prefill_fn().__wrapped__,
                    (params, s((1, S)), s((1,)), tables(1), *pools))
        # a held share's prompt past one pass, at trinity_large's widths:
        # 4,096 rows, 32 of 256 experts of 3,072 x 3,072, 4 a row under the
        # sigmoid rule (16,384 pairs, 2,048 a pass)
        from mxnet_tpu.ops import nn as ops
        widths = ((4096, 3072), (3072, 256)) + ((32, 3072, 3072),) * 3 \
            + ((256,),)
        out["trinity_large.share_passes"] = text_of(
            lambda *a: ops.moe_ffn(*a, top_k=4, n_group=1, topk_group=1,
                                   routed_scale=2.448),
            tuple(s(w, jnp.bfloat16) for w in widths))
    finally:
        tcc._lower_mosaic_module_to_asm = lower
        jax.default_backend = backend
    return {k: hashlib.sha256(t.encode()).hexdigest()
            for k, t in out.items()}


def test_the_other_families_programs_lower_to_the_parents_text(one_chip):
    got = _program_digests(one_chip)
    assert set(PARENT_DIGESTS) == set(got) - {
        "deepseek_v3.prefill_s64", "deepseek_v3.prefill_s512"}
    moved = sorted(k for k, v in PARENT_DIGESTS.items() if got[k] != v)
    assert not moved, moved


# ---------------------------------------------------------------------------
# cell trinity_large.agent_c24 at its published widths: 48 query heads on 8
# KV heads of 128 in bfloat16 (6 query rows a KV head), a window of 4,096; the
# full group's pools of 1 layer and 24,577 pages (1,024 a lane), the window
# group's of 4 layers and 24 rings of 257 pages + the scratch page
# ---------------------------------------------------------------------------
TR_FULL_POOL, TR_RING_POOL, TR_RING = (1, 24577, 16, 1024), \
    (4, 6169, 16, 1024), 257


@pytest.fixture(scope="module")
def trinity():
    """The cut model with no weights drawn, its parameters, the cell."""
    from chipbench import harness
    from chipbench.models import afmoe
    cell, config = harness.load_cell("trinity_large.agent_c24")
    lm = afmoe.block(config, prefix="tr_")
    plist = list(lm.collect_params().values())
    # 4.322 B matrices (chipbench/configs/trinity_large.json) + 20 norms of
    # 3,072, 10 of 128 and 4 correction biases of 256
    assert sum(int(onp.prod(p.shape)) for p in plist) == \
        4_321_837_056 + 21 * 3072 + 10 * 128 + 4 * 256
    from mxnet_tpu.serving.generate.kv_cache import ring_pages
    assert ring_pages(config["sliding_window"], PAGE) == TR_RING
    assert cell["num_pages"] == TR_FULL_POOL[1]
    assert cell["max_batch_size"] * TR_RING + 1 == TR_RING_POOL[1]
    return cell, lm, plist


def _trinity_args(one_chip, plist, lanes):
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    params = tuple(sds(tuple(p.shape), jnp.bfloat16) for p in plist)
    tables = (sds((lanes, 1024), I32), sds((lanes, TR_RING), I32))
    pools = (sds(TR_FULL_POOL, jnp.bfloat16),) * 2 \
        + (sds(TR_RING_POOL, jnp.bfloat16),) * 2
    return sds, params, tables, pools


def test_trinitys_longest_prefill_compiles_within_the_chip(one_chip, trinity,
                                                           monkeypatch):
    """The 16,384-row ``jit_prefill``: five flash kernels (one causal on the
    NoPE layer, four banded) and the routed passes' grouped matmuls, with
    the weights and both groups' pools under 15.5 GB in all, and no (S, V)
    array: the head multiplies the one row that is read."""
    import functools
    from mxnet_tpu.serving.generate import engine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell, lm, plist = trinity
    S = cell["max_seq_len"]
    sds, params, tables, pools = _trinity_args(one_chip, plist, 1)
    comp = jax.jit(functools.partial(engine._prefill, lm, plist, PAGE, True),
                   donate_argnums=(4, 5, 6, 7)).lower(
        params, sds((1, S), I32), sds((1,), I32), tables, *pools).compile()
    text, mem = comp.as_text(), comp.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9, \
        (mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    assert not re.search(r"\[(1,)?16384,25024\]", text)
    assert "f32[25024]" in text         # one row's logits
    flash = re.findall(
        r"= \(bf16\[48,16384,128\]\S*, f32\[48,16384,128\]\S*\) custom-call\(",
        text)
    assert len(flash) == 5
    # a held share's pairs 2,048 at a time: no product over all 65,536
    assert "f32[2048,3072]" in text and "f32[65536," not in text
    assert "input_output_alias" in text.splitlines()[0]


def test_trinitys_step_writes_both_groups_in_place(one_chip, trinity,
                                                   monkeypatch):
    """The 24-lane ``jit_decode``: five paged-attention kernels (one on the
    full group's pools, four bounded on the rings) of one name, and twelve
    grouped matmuls of one name (``moe_intermediate_size`` is
    ``hidden_size``) and the four routed layers' pair sums; the four pools
    donated and written in place, and the rows routed to each held expert
    brought home (4 layers x 32 experts)."""
    import functools
    from mxnet_tpu.serving.generate import engine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell, lm, plist = trinity
    B = cell["max_batch_size"]
    sds, params, tables, pools = _trinity_args(one_chip, plist, B)
    comp = jax.jit(functools.partial(engine._decode, lm, plist, PAGE, None,
                                     loads_home=True),
                   donate_argnums=(5, 6, 7, 8)).lower(
        params, sds((B,), I32), sds((B,), I32), tables,
        sds((B,), jnp.bool_), *pools).compile()
    text = comp.as_text()
    assert text.count("tpu_custom_call") == 5 + 12 + 4
    assert len(re.findall(r"= bf16\[24,3072\]\S* custom-call\(", text)) == 4
    assert len(re.findall(r"= \(f32\[24,1,48,1024\]", text)) == 5
    kernels = re.findall(
        r"= (\S+?)\{[^}]*\} custom-call\([^\n]*tpu_custom_call", text)
    assert sorted(k for k in kernels if k.startswith("f32[96")) == \
        ["f32[96,3072]"] * 12
    assert "input_output_alias" in text.splitlines()[0]
    for pool in (TR_FULL_POOL, TR_RING_POOL):
        makers = set(re.findall(
            r"= bf16\[%d,%d,%d,%d\]\{[^}]*\} ([\w-]+)\(" % pool, text))
        assert makers and "copy" not in makers, makers
    assert re.search(r"ROOT[^\n]*s32\[4,32\]", text)
    assert comp.memory_analysis().temp_size_in_bytes < 256 << 20
