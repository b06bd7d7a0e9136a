"""Latent attention and the sigmoid group-limited router through the decode
path (gluon.model_zoo.mla_lm.MLADecoderLM behind DecodeEndpoint, PagedKVPool,
DecodeScheduler and InferenceServer.generate) against the plain float32
reference (chipbench/reference/deepseek_v3.py) at a small size on the CPU:
logits of the full forward, of prefill and then every step through the one
latent pool, batched against serial, absorbed against plain, the router under
a bias that moves its choice, YaRN's numbers by hand, and the shares of the
experts summed to the uncut layer."""
import math

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from chipbench.reference import deepseek_v3 as ref
from mxnet_tpu import serving
from mxnet_tpu.gluon.model_zoo.mla_lm import MLADecoderLM
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.serving.generate import engine as engine_mod

VOCAB, EXPERTS = 96, 16
YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0}
DIMS = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, rms_norm_eps=1e-6, rope_theta=10000.0,
            rope_scaling=YARN, num_experts_per_tok=2, n_group=4, topk_group=2,
            routed_scaling_factor=2.5, norm_topk_prob=True)
TOL = dict(rtol=2e-4, atol=2e-4)      # float32 both sides, other op order


def build_lm(seed=3, **kw):
    lm = MLADecoderLM(
        num_layers=3, units=64, num_heads=4, q_rank=32, kv_rank=32,
        nope_dim=16, rope_dim=8, v_dim=16, dense_layers=1, dense_hidden=128,
        expert_hidden=32, num_experts=EXPERTS, experts_per_token=2,
        shared_experts=1, n_group=4, topk_group=2, routed_scale=2.5,
        vocab_size=VOCAB, rope_scaling=YARN, **kw)
    # a correction bias as wide as the scores' spread: it moves choices
    lm.initialize(mx.init.DeviceNormal(0.1, seed=seed,
                                       scales={"router_bias": 2.0}))
    lm.hybridize()
    return lm


def reference_params(lm):
    """The system's weights, float32, as the reference names them."""
    f32 = lambda p: jnp.asarray(p.data().data, jnp.float32)
    return {"embed": f32(lm.embed_weight), "final_norm": f32(lm.final_norm),
            "head": f32(lm.head_weight),
            "layers": [{k: f32(v) for k, v in layer.items()}
                       for layer in lm.layers]}


@pytest.fixture(scope="module")
def lm():
    return build_lm()


@pytest.fixture(scope="module")
def params(lm):
    return reference_params(lm)


@pytest.fixture(scope="module")
def eng(lm):
    return serving.DecodeEndpoint("latent", lm, max_seq_len=64,
                                  max_batch_size=4, num_pages=17)


def prompt_of(n, seed):
    return [int(t) for t in
            onp.random.default_rng(seed).integers(0, VOCAB, n)]


ROWS = 64
_forward = jax.jit(lambda params, tokens: ref.forward(params, tokens, DIMS))


def reference_logits(params, tokens):
    """The reference's full causal forward, compiled once: right-padded to
    ROWS rows, which no row of ``tokens`` sees."""
    padded = onp.zeros(ROWS, onp.int32)
    padded[:len(tokens)] = tokens
    return onp.asarray(_forward(params, padded))[:len(tokens)]


# ---------------------------------------------------------------------------
# (a) the model's full forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("length", [5, 19, 40])
def test_full_forward_matches_the_reference(lm, params, length):
    toks = onp.asarray([prompt_of(length, s) for s in (1, 2)], onp.int32)
    out = lm(mx.nd.array(toks, dtype="int32")).asnumpy()
    for row, got in zip(toks, out):
        onp.testing.assert_allclose(got, reference_logits(params, row), **TOL)


def test_the_reference_with_a_head_block_is_the_reference(params):
    x = jnp.asarray(onp.random.default_rng(0).normal(0, 1, (12, 64)),
                    jnp.float32)
    pos = jnp.arange(12)
    p = params["layers"][1]
    onp.testing.assert_allclose(
        ref.attention(x, p, pos, DIMS, head_block=2),
        ref.attention(x, p, pos, DIMS), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# (b) prefill, then steps through the endpoint and its one latent pool
# ---------------------------------------------------------------------------
def test_the_endpoint_allocates_one_latent_pool(eng):
    pool = eng.pool
    assert pool.latent and len(pool.arrays) == 1 and pool.v_pool is None
    # 32 + 8 numbers a position a layer, stored on one whole lane tile
    assert pool.kv_dim == 32 + 8
    assert pool.k_pool.shape == (3, 17, pool.page_size, 128)
    assert pool.nbytes == 17 * pool.page_size * 3 * 128 * 4
    assert pool.row_bytes == 3 * 40 * 4
    assert pool.snapshot()["bytes"] == pool.nbytes


def step_logits(eng):
    """The endpoint's traced step, stopped before the arg-max: logits of
    every lane, nothing installed in the pool."""
    fn = jax.jit(lambda *a: engine_mod._step(
        eng.block, eng._params, eng.block.num_layers, eng.pool.page_size,
        *a)[0])

    def run(ids, positions, tables):
        n = len(ids)
        return onp.asarray(fn(
            eng._param_datas(), onp.asarray(ids, onp.int32),
            onp.asarray(positions, onp.int32), onp.stack(tables),
            onp.zeros((n,), bool), *eng.pool.arrays))
    return run


@pytest.mark.parametrize("lengths", [(7,), (16, 33, 5)])
def test_prefill_then_decode_through_the_cache_matches_the_full_forward(
        eng, params, lengths):
    """Prefill (plain form, writes the latents), then greedy steps (absorbed
    form, through the page table), lanes batched: at every step each lane's
    logits are those of the reference's full forward over what it holds, and
    the token the endpoint returns is their arg-max."""
    logits_of = step_logits(eng)
    pool = eng.pool
    seqs = []
    for sid, n in enumerate(lengths):
        prompt = prompt_of(n, 30 + sid)
        pool.reserve(sid, n + 6)
        first = eng.prefill(prompt, pool.table(sid))
        want = reference_logits(params, prompt)[-1]
        assert first == int(want.argmax())
        seqs.append(prompt + [first])
    try:
        for _ in range(5):
            ids = [s[-1] for s in seqs]
            pos = [len(s) - 1 for s in seqs]
            tables = [pool.table(sid) for sid in range(len(seqs))]
            got = logits_of(ids, pos, tables)
            for lane, s in enumerate(seqs):
                onp.testing.assert_allclose(
                    got[lane], reference_logits(params, s)[-1], **TOL)
            nxt = eng.decode_step(list(zip(ids, pos, tables)))
            for lane, s in enumerate(seqs):
                assert nxt[lane] == int(got[lane].argmax())
                s.append(nxt[lane])
    finally:
        for sid in range(len(lengths)):
            pool.free(sid)


def test_batched_decode_is_serial_decode(lm):
    """Through InferenceServer.generate: four callers at once get, token for
    token, what each gets alone."""
    prompts = [prompt_of(n, 50 + n) for n in (5, 18, 33, 9)]

    def serve(batch):
        eng = serving.DecodeEndpoint("latent_bs", lm, max_seq_len=64,
                                     max_batch_size=4, num_pages=17)
        server = serving.InferenceServer()
        server.register_generator(eng)
        server.start()
        try:
            if batch:
                streams = [server.generate("latent_bs", p, max_new_tokens=8)
                           for p in prompts]
                return [s.result(timeout=120) for s in streams]
            return [server.generate("latent_bs", p, max_new_tokens=8)
                    .result(timeout=120) for p in prompts]
        finally:
            server.stop(drain=True)

    assert serve(True) == serve(False)


def test_a_step_reports_its_expert_loads_and_the_bytes_it_read(lm):
    eng = serving.DecodeEndpoint("latent_stats", lm, max_seq_len=64,
                                 max_batch_size=4, num_pages=17)
    pool = eng.pool
    prompt = prompt_of(11, 3)
    pool.reserve(0, 16)
    tok = eng.prefill(prompt, pool.table(0))
    eng.decode_step([(tok, 11, pool.table(0))])
    last = eng.last_step
    # one lane, two experts a row: 2 pairs over 16 experts a layer
    assert last["moe.expert_load_mean"] == pytest.approx(2 / EXPERTS)
    assert last["moe.expert_load_max"] >= 1.0
    assert last["ctx_live"] == 11
    counters = eng.stats.snapshot()["counters"]
    assert counters["ctx_bytes"] == 11 * pool.row_bytes
    assert counters["moe.expert_load_max"] == last["moe.expert_load_max"]
    pool.free(0)


def test_a_prefills_span_says_its_rung_and_the_prompts_rows(lm):
    from mxnet_tpu.telemetry import flight
    eng = serving.DecodeEndpoint("latent_span", lm, max_seq_len=64,
                                 max_batch_size=2, num_pages=9)
    server = serving.InferenceServer()
    server.register_generator(eng)
    server.start()
    try:
        server.generate("latent_span", prompt_of(19, 2),
                        max_new_tokens=3).result(timeout=120)
    finally:
        server.stop(drain=True)
    spans = [e for e in flight.recent_spans() if e["name"] == "decode.prefill"
             and e["attrs"].get("prompt_len") == 19]
    assert spans and spans[-1]["attrs"]["tokens"] == 19
    assert spans[-1]["attrs"]["bucket"] == 32       # 13 rows of padding
    steps = [e for e in flight.recent_spans() if e["name"] == "decode.step"
             and "moe.expert_load_max" in e["attrs"]]
    assert steps and steps[-1]["attrs"]["ctx_live"] >= 19


# ---------------------------------------------------------------------------
# (c) absorbed against plain, model alone
# ---------------------------------------------------------------------------
def test_the_absorbed_form_is_the_plain_form(lm):
    """``decode_step`` over a pool filled from ``prefill_collect``'s latents
    gives the logits ``forward`` gives at that position."""
    toks = onp.asarray([prompt_of(21, 7), prompt_of(21, 8)], onp.int32)
    whole = lm(mx.nd.array(toks, dtype="int32")).asnumpy()
    n, page = 20, 16
    _, *latents = lm.prefill_collect(mx.nd.array(toks[:, :n], dtype="int32"))
    pool = onp.zeros((3, 5, page, 128), onp.float32)
    tables = onp.asarray([[1, 2], [3, 4]], onp.int32)
    for layer, rows in enumerate(latents):
        for b in range(2):
            flat = onp.zeros((2 * page, 128), onp.float32)
            flat[:n, :40] = onp.asarray(rows)[b]
            pool[layer, tables[b]] = flat.reshape(2, page, 128)
    logits, *rest = lm.decode_step(
        toks[:, n], onp.full((2,), n, onp.int32), pool, tables)
    onp.testing.assert_allclose(logits, whole[:, n], **TOL)
    assert len(rest) == 3 + 1 and rest[0].shape == (2, 40)
    assert rest[-1].shape == (2, EXPERTS)       # the routed layers' loads


# ---------------------------------------------------------------------------
# (d) the router
# ---------------------------------------------------------------------------
def router_case(seed, bias_scale):
    rng = onp.random.default_rng(seed)
    h = jnp.asarray(rng.normal(0, 1, (33, 64)), jnp.float32)
    p = {"router": jnp.asarray(rng.normal(0, 0.2, (64, EXPERTS)),
                               jnp.float32),
         "router_bias": jnp.asarray(rng.normal(0, bias_scale, EXPERTS),
                                    jnp.float32)}
    return h, p


def routed_by_the_program(h, p):
    w, i = ops.route_grouped_sigmoid(
        h, p["router"], p["router_bias"], top_k=2, n_group=4, topk_group=2,
        scale=2.5)
    return onp.asarray(w), onp.asarray(i)


def test_the_bias_moves_the_choice_and_not_the_weight():
    h, p = router_case(5, bias_scale=1.0)
    want = onp.asarray(ref.route(h, p, DIMS))
    w, i = routed_by_the_program(h, p)
    dense = onp.zeros_like(want)
    onp.put_along_axis(dense, i, w, -1)
    assert ((dense > 0) == (want > 0)).all()            # the chosen experts
    onp.testing.assert_allclose(dense, want, rtol=1e-5, atol=1e-6)
    # without the bias other experts are chosen, so a bias left out fails
    _, plain = routed_by_the_program(h, {**p, "router_bias":
                                         jnp.zeros(EXPERTS)})
    assert (onp.sort(plain, -1) != onp.sort(i, -1)).any(-1).mean() > 0.5
    # the weights are the scores': the chosen's s over their sum, times 2.5
    s = onp.asarray(jax.nn.sigmoid(h @ p["router"]))
    chosen = onp.take_along_axis(s, i, -1)
    onp.testing.assert_allclose(
        w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    onp.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)


def test_only_the_best_groups_experts_are_chosen():
    h, p = router_case(6, bias_scale=0.3)
    _, i = routed_by_the_program(h, p)
    c = onp.asarray(jax.nn.sigmoid(h @ p["router"]) + p["router_bias"])
    score = onp.sort(c.reshape(33, 4, 4), -1)[..., -2:].sum(-1)
    kept = onp.argsort(-score, -1)[:, :2]
    assert all(set(i[t] // 4) <= set(kept[t]) for t in range(33))


# ---------------------------------------------------------------------------
# (e) YaRN
# ---------------------------------------------------------------------------
PUBLISHED = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
             "original_max_position_embeddings": 4096, "mscale": 1.0,
             "mscale_all_dim": 1.0}


def test_yarn_frequencies_and_mscale_by_hand():
    """At the published settings (64 rotated dimensions, base 10,000, factor
    40 over 4,096): the pair of 32 turns is 10.47 -> 10, the pair of one turn
    22.51 -> 23; pairs up to 10 keep their rate, pairs from 23 are divided by
    40, pair 16 lies 6/13 of the way."""
    inv, scale = ops.rotary_frequencies(64, 10000.0, PUBLISHED)
    base = [10000.0 ** (-2 * i / 64) for i in range(32)]
    assert scale == 1.0                  # mscale over mscale_all_dim
    onp.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    onp.testing.assert_allclose(inv[23:], [b / 40 for b in base[23:]],
                                rtol=1e-6)
    onp.testing.assert_allclose(
        inv[16], base[16] * (1 - 6 / 13) + base[16] / 40 * (6 / 13),
        rtol=1e-6)
    assert ops.yarn_mscale(40, 1.0) == pytest.approx(0.1 * math.log(40) + 1)
    assert ops.yarn_mscale(40, 1.0) == pytest.approx(1.3689, abs=1e-4)
    assert ops.yarn_mscale(1.0, 1.0) == 1.0
    lm = MLADecoderLM(nope_dim=128, rope_dim=64, rope_scaling=PUBLISHED)
    assert lm.sm_scale == pytest.approx(192 ** -0.5 * 1.3689 ** 2, rel=1e-4)
    # the reference computes the same numbers on its own
    dims = {**DIMS, "qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
            "rope_scaling": PUBLISHED}
    onp.testing.assert_allclose(ref.inverse_frequencies(dims), inv, rtol=1e-6)
    assert ref.softmax_scale(dims) == pytest.approx(lm.sm_scale)
    assert ops.rotary_frequencies(64, 10000.0)[1] == 1.0


def test_rotary_on_a_slice_with_yarn_matches_the_reference():
    rng = onp.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 1, (19, 4, 8)), jnp.float32)
    pos = jnp.arange(19) * 3
    got = ops.rotary_embedding(x[None], pos[None], theta=10000.0,
                               scaling=YARN)[0]
    onp.testing.assert_allclose(got, ref.rotate(x, pos, DIMS), rtol=1e-5,
                                atol=1e-6)
    unscaled = ops.rotary_embedding(x[None], pos[None], theta=10000.0)[0]
    assert float(jnp.abs(got - unscaled).max()) > 0.1


# ---------------------------------------------------------------------------
# (f) the shares add up
# ---------------------------------------------------------------------------
def ffn_weights(seed):
    rng = onp.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 0.1, shape), jnp.float32)
    return {"router": draw(64, EXPERTS), "router_bias": draw(EXPERTS) * 3,
            "w_gate": draw(EXPERTS, 64, 32), "w_up": draw(EXPERTS, 64, 32),
            "w_down": draw(EXPERTS, 32, 64), "mlp_gate": draw(64, 32),
            "mlp_up": draw(64, 32), "mlp_down": draw(32, 64)}


@pytest.mark.parametrize("shares", [2, 8])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """Every share's part (its own experts' rows), the shared expert counted
    once, sums to what the reference gives for the whole layer: at 8 shares
    a share holds half a group, as one chip of sixteen does."""
    p = ffn_weights(3)
    h = jnp.asarray(onp.random.default_rng(4).normal(0, 1, (23, 64)),
                    jnp.float32)
    whole = ref.ffn(h, p, DIMS)
    shared = ref.gated(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
    held = EXPERTS // shares
    total, pairs = shared, 0
    for first in range(0, EXPERTS, held):
        cut = slice(first, first + held)
        part, load = ops.moe_ffn(
            h, p["router"], p["w_gate"][cut], p["w_up"][cut],
            p["w_down"][cut], p["router_bias"], top_k=2, first_expert=first,
            n_group=4, topk_group=2, routed_scale=2.5)
        share = {**p, **{k: p[k][cut] for k in ("w_gate", "w_up", "w_down")}}
        onp.testing.assert_allclose(
            part, ref.routed(h, share, DIMS, held=(first, held)), **TOL)
        total, pairs = total + part, pairs + int(load.sum())
    assert pairs == 23 * 2                  # every (row, expert) pair, once
    onp.testing.assert_allclose(total, whole, **TOL)


def test_a_share_takes_its_pairs_a_block_at_a_time(monkeypatch):
    """More pairs than one pass holds: a share's passes over the pairs routed
    to it give what one pass over all pairs gives, loads and all."""
    p = ffn_weights(8)
    h = jnp.asarray(onp.random.default_rng(9).normal(0, 1, (200, 64)),
                    jnp.float32)
    call = lambda: ops.moe_ffn(
        h, p["router"], p["w_gate"][4:12], p["w_up"][4:12], p["w_down"][4:12],
        p["router_bias"], top_k=2, first_expert=4, n_group=4, topk_group=2,
        routed_scale=2.5)
    at_once, load = call()
    monkeypatch.setattr(ops, "_PAIR_BLOCK", 64)
    in_passes, load_again = call()
    assert int(load.sum()) > 2 * 64         # three passes at least
    onp.testing.assert_array_equal(load, load_again)
    onp.testing.assert_allclose(in_passes, at_once, rtol=1e-5, atol=1e-6)


def test_a_model_that_holds_a_share_of_the_experts(lm, params):
    """The model is told which experts it holds: a layer of the model that
    holds experts 4..7 of 16 is the reference's layer with that share."""
    part = build_lm(held_experts=(4, 4))
    for (name, p), src in zip(part.collect_params().items(),
                              lm.collect_params().values()):
        src = src.data().data
        p.set_data(mx.nd.array(src[4:8] if "experts" in name else src))
    toks = onp.asarray([prompt_of(14, 21)], onp.int32)
    ref_params = reference_params(part)
    want = ref.forward(ref_params, jnp.asarray(toks[0]), DIMS, held=(4, 4))
    onp.testing.assert_allclose(
        part(mx.nd.array(toks, dtype="int32")).asnumpy()[0], want, **TOL)
    whole = lm(mx.nd.array(toks, dtype="int32")).asnumpy()[0]
    assert onp.abs(whole - onp.asarray(want)).max() > 1e-2
