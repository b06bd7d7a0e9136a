"""Window and full attention layers in one model over a cache that gives each
kind its own pages: ``MoEDecoderLM(layer_types=..., sliding_window=...,
rope_by_type=...)`` against the plain reference (``chipbench/reference/
mellum2.py``: dense masked attention, no cache, its own YaRN table), through
``forward`` and, prefilled and then decoded through ``DecodeEndpoint`` and the
cache, against the reference's full forward: by logits, with windows of 8 to
32 so that contexts pass them many times over and sequences go round their
rings more than twice. And the pool of two groups itself: what a sequence
holds, all-or-nothing reservation, free, defrag, a ring reused."""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.gluon.model_zoo.moe_lm import MoEDecoderLM
from mxnet_tpu.serving.errors import KVPoolExhausted
from mxnet_tpu.serving.generate.kv_cache import PagedKVPool, ring_pages

from chipbench.models import mellum2 as family
from chipbench.reference import mellum2 as reference

S, F = "sliding_attention", "full_attention"
VOCAB = 96


def dims(pattern, window):
    return {
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 8,
        "num_experts_per_tok": 2, "norm_topk_prob": True,
        "num_hidden_layers": len(pattern), "vocab_size": VOCAB,
        "rms_norm_eps": 1e-6, "dtype": "float32", "init_std": 0.2,
        "layer_types": [S if c == "s" else F for c in pattern],
        "sliding_window": window,
        "rope_parameters": {
            F: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 32, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            S: {"rope_type": "default", "rope_theta": 500000}}}


@functools.lru_cache(maxsize=None)
def model(pattern, window):
    """(dims, the program's model with seeded weights, the same weights under
    the reference's names in float32)."""
    config = dims(pattern, window)
    lm = family.build_lm(config, seed=11)
    lm(mx.nd.array(onp.zeros((1, 4)), dtype="int32"))
    weights = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                           family._weights(lm))
    return config, lm, weights


def tokens(n, seed=0):
    return [int(t) for t in onp.random.default_rng(seed).integers(0, VOCAB, n)]


# float32 on both sides: the program's products run at the package's
# ``highest`` as the reference's do, so what is left is the order of sums
# (a logit of spread 1.5 agrees to 1e-4); bfloat16 in float32's place moves a
# logit by 1e-2 and fails it a hundred times over
TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("pattern", ["sssfsssf", "f", "s"])
def test_forward_is_the_references(pattern):
    config, lm, weights = model(pattern, 8)
    seq = tokens(70)
    got = lm(mx.nd.array(onp.asarray([seq]), dtype="int32")).asnumpy()[0]
    want = reference.forward(weights, jnp.asarray(seq), config)
    onp.testing.assert_allclose(got, onp.asarray(want), **TOL)
    if "s" in pattern:      # the window matters: every layer full differs
        full = reference.forward(weights, jnp.asarray(seq), config,
                                 every_layer_full=True)
        assert float(jnp.abs(full - want).max()) > 1e-2


def test_the_model_states_its_cache_groups():
    _, lm, _ = model("sssfsssf", 8)
    assert lm.cache_groups == (("full", (3, 7), None),
                               ("window", (0, 1, 2, 4, 5, 6), 8))
    assert model("f", 8)[1].cache_groups is None     # nothing keeps a window
    assert model("s", 8)[1].cache_groups == (("window", (0,), 8),)
    plain = MoEDecoderLM(num_layers=2, prefix="p_")
    assert plain.cache_groups is None and plain._windows == [None, None]
    with pytest.raises(ValueError):
        MoEDecoderLM(num_layers=2, layer_types=[S], sliding_window=8)
    with pytest.raises(ValueError):     # a window is the causal step's
        MoEDecoderLM(num_layers=1, layer_types=[S], sliding_window=8,
                     block_length=4, mask_token_id=5)


# ---------------------------------------------------------------------------
# prefill, then decoding through the cache, against the reference's forward
# ---------------------------------------------------------------------------
def step_logits(eng, rows, lanes=None):
    """One decode step of ``rows`` through the endpoint's own traced step,
    returning the logits it chose from (the executable returns ids alone).
    ``lanes``: the batch the rows are padded to, as a bucket's padding lanes
    are (no tokens, no pages, not valid)."""
    from mxnet_tpu.serving.generate import engine
    n = len(rows)
    B = lanes or n
    ids, pos = onp.zeros((2, B), onp.int32)
    tables = onp.zeros((B, eng.pool.pages_per_seq), onp.int32)
    for i, row in enumerate(rows):
        ids[i], pos[i], tables[i] = row
    tables = eng.pool.split_tables(tables)
    if not hasattr(eng, "_logits_step"):
        eng._logits_step = jax.jit(functools.partial(
            engine._step, eng.block, eng._params, int(eng.block.num_layers),
            eng.pool.page_size))
    logits, _, pools = eng._logits_step(
        eng._param_datas(), ids, pos, tables, onp.arange(B) < n,
        *eng.pool.arrays)
    eng.pool.update_arrays(*pools)
    return onp.asarray(logits)[:n]


def teacher_forced(eng, sid, seq, n_prompt):
    """Logits at positions ``n_prompt - 1 ..`` of ``seq``: the prompt
    prefilled through the endpoint's executable, the rest fed a token a step
    through the cache."""
    eng.pool.reserve(sid, len(seq) + 1)
    table = eng.pool.table(sid)
    eng.prefill(seq[:n_prompt], table)
    out = []
    for p in range(n_prompt, len(seq)):
        out.append(step_logits(eng, [(seq[p], p, table)])[0])
    return onp.stack(out)


# windows of 8, 16 and 32 on pages of 16: rings of 2, 2 and 3 pages; prompts
# shorter than the window, equal to it, ending inside a page and on its edge;
# 110 decoded positions carry a sequence round a ring of 32 positions three
# times and round one of 48 twice
@pytest.mark.parametrize("window,n_prompt", [
    (16, 5), (16, 16), (16, 37), (16, 48), (8, 23), (32, 33), (32, 20)])
def test_prefill_then_cached_decode_is_the_references_forward(window,
                                                              n_prompt):
    config, lm, weights = model("sssfsssf", window)
    eng = serving.DecodeEndpoint(f"w{window}p{n_prompt}", lm,
                                 max_seq_len=160, max_batch_size=2,
                                 num_pages=21)
    seq = tokens(n_prompt + 110, seed=window + n_prompt)
    want = onp.asarray(reference.forward(weights, jnp.asarray(seq), config))
    got = teacher_forced(eng, 1, seq, n_prompt)
    onp.testing.assert_allclose(got, want[n_prompt:], **TOL)
    # the prefill's own token is the reference's too
    eng.pool.free(1)
    eng.pool.reserve(2, len(seq))
    assert eng.prefill(seq[:n_prompt], eng.pool.table(2)) == \
        int(want[n_prompt - 1].argmax())
    ring = ring_pages(window, 16)
    full, win = eng.pool.groups
    assert (win.pages_per_seq, win.peak_seq_pages) == (ring, ring)
    assert full.peak_seq_pages == -(-(len(seq) + 1) // 16)


def test_batched_decode_is_serial_decode_bitwise():
    """Three sequences of different lengths stepped in one batch, each at
    another place of its ring, against each stepped alone beside padding
    lanes (one executable shape: XLA:CPU multiplies three rows and one row by
    other routes): the same bits, so a lane's logits depend on its own
    tokens, pages, bound and length alone."""
    _, lm, _ = model("sssfsssf", 16)
    both = []
    for batched in (True, False):
        eng = serving.DecodeEndpoint(f"b{batched}", lm, max_seq_len=160,
                                     max_batch_size=4, num_pages=41)
        seqs = {sid: tokens(n + 60, seed=sid)
                for sid, n in ((1, 7), (2, 40), (3, 65))}
        state = {}
        for sid, seq in seqs.items():
            n = len(seq) - 60
            eng.pool.reserve(sid, len(seq) + 1)
            eng.prefill(seq[:n], eng.pool.table(sid))
            state[sid] = n
        out = {sid: [] for sid in seqs}
        for _ in range(60):
            rows = [(seqs[sid][p], p, eng.pool.table(sid))
                    for sid, p in state.items()]
            if batched:
                logits = step_logits(eng, rows)
            else:
                logits = [step_logits(eng, [r], lanes=3)[0] for r in rows]
            for sid, row in zip(state, logits):
                out[sid].append(onp.asarray(row))
                state[sid] += 1
        both.append(out)
    for sid in both[0]:
        onp.testing.assert_array_equal(onp.stack(both[0][sid]),
                                       onp.stack(both[1][sid]))


def test_generate_through_the_server_is_greedy_under_the_reference():
    """``InferenceServer.generate``: scheduler, pool and endpoint with no side
    path; every served token is the reference's first choice at its position
    (float32 on both sides)."""
    config, lm, weights = model("sssfsssf", 16)
    eng = serving.DecodeEndpoint("srv", lm, max_seq_len=128, max_batch_size=4,
                                 num_pages=33)
    server = serving.InferenceServer()
    server.register_generator(eng)
    server.start()
    try:
        prompts = [tokens(n, seed=n) for n in (5, 30, 50, 17, 64)]
        streams = [server.generate("srv", p, max_new_tokens=60)
                   for p in prompts]
        answers = [s.result(timeout=300) for s in streams]
    finally:
        server.stop(drain=True)
    for prompt, answer in zip(prompts, answers):
        assert len(answer) == 60
        logits = onp.asarray(reference.forward(
            weights, jnp.asarray(prompt + answer), config))
        rows = logits[len(prompt) - 1:len(prompt) + 59]
        deficit = rows.max(-1) - rows[onp.arange(60), answer]
        assert float(deficit.max()) < 1e-3
    snap = eng.snapshot()
    full, win = snap["kv_pool"]["groups"]
    assert win["peak_seq_pages"] == win["pages_per_seq"] == 2
    assert snap["kv_pool"]["in_use"] == 0
    stats = snap["stats"]
    assert 0 < stats["ctx_window_live"] < stats["ctx_live"]
    assert eng.last_step["ctx_window_live"] <= 4 * 15


# ---------------------------------------------------------------------------
# the pool of two groups
# ---------------------------------------------------------------------------
def two_groups(**kw):
    return PagedKVPool("two", 4, 32, max_seq_len=256, page_size=16,
                       num_pages=33, groups=[("full", 1, None),
                                             ("window", 3, 24)],
                       max_seqs=2, **kw)


def test_a_window_group_never_holds_more_than_its_ring_a_sequence():
    pool = two_groups()
    full, win = pool.groups
    assert ring_pages(24, 16) == 3 == win.pages_per_seq
    assert (full.num_pages, win.num_pages) == (33, 2 * 3 + 1)
    assert [a.shape for a in pool.arrays] == [(1, 33, 16, 32)] * 2 \
        + [(3, 7, 16, 32)] * 2
    pool.reserve(1, 10 * 24)            # ten windows' worth of tokens
    assert (full.in_use, win.in_use) == (15, 3)
    assert win.peak_seq_pages == 3
    assert pool.pages_per_seq == 16 + 3 and pool.table(1).shape == (19,)
    whole, ring = pool.split_tables(pool.table(1)[None])
    assert whole.shape == (1, 16) and ring.shape == (1, 3)
    assert (ring > 0).all() and (whole[0, :15] > 0).all() and whole[0, 15] == 0
    # ten windows of steps open a ring page over the oldest 12 times
    assert pool.ring_overwrites(range(240)) == 240 // 16 - 3
    snap = pool.snapshot()
    assert [g["group"] for g in snap["groups"]] == ["full", "window"]
    assert snap["in_use"] == 18 and snap["bytes"] == pool.nbytes


def test_the_gauges_and_counters_carry_the_group():
    """A pool of two groups reports each under ``<pool>.<group>``; a pool of
    one group keeps the series it always had; the ring's counter counts the
    pages a step opens over the oldest."""
    from mxnet_tpu.serving.generate import kv_cache
    pool = PagedKVPool("lbl", 4, 32, max_seq_len=256, page_size=16,
                       num_pages=33, groups=[("full", 1, None),
                                             ("window", 3, 24)], max_seqs=2)
    pool.reserve(1, 100)
    in_use = lambda label: kv_cache._IN_USE.labels(label).value
    assert (in_use("lbl.full"), in_use("lbl.window")) == (7, 3)
    assert kv_cache._POOL_PAGES.labels("lbl.window").value == 6
    assert kv_cache._ALLOCATED.labels("lbl.window").value == 3
    over = kv_cache._RING_OVERWRITTEN.labels("lbl.window")
    before = over.value
    assert pool.ring_overwrites([47, 48, 64, 10, 96]) == 3
    assert over.value - before == 3
    pool.free(1)
    assert (in_use("lbl.full"), in_use("lbl.window")) == (0, 0)
    assert kv_cache._FREED.labels("lbl.window").value == 3
    one = PagedKVPool("lbl1", 2, 32, max_seq_len=64, page_size=16,
                      num_pages=9)
    one.reserve(1, 40)
    assert in_use("lbl1") == 3 and one.ring_overwrites([64, 128]) == 0


def test_reserve_is_all_or_nothing_across_groups():
    pool = two_groups()
    pool.reserve(1, 100)
    pool.reserve(2, 100)                # the window group's two rings
    before = [g.in_use for g in pool.groups]
    with pytest.raises(KVPoolExhausted, match="group window"):
        pool.reserve(3, 16)             # the full group has room; no ring
    assert [g.in_use for g in pool.groups] == before
    assert 3 not in pool.groups[0].tables
    assert pool.free(1) == 7 + 3        # both groups' pages come back
    pool.reserve(3, 16)
    assert [len(g.tables[3]) for g in pool.groups] == [1, 1]
    # the full group short: nothing taken from the window group either
    tight = PagedKVPool("tight", 2, 32, max_seq_len=64, page_size=16,
                        num_pages=6, groups=[("full", 1, None),
                                             ("window", 1, 8)], max_seqs=4)
    tight.reserve(1, 64)
    with pytest.raises(KVPoolExhausted, match="group full"):
        tight.reserve(2, 32)
    assert tight.groups[1].in_use == 2 and 2 not in tight.groups[1].tables


def test_one_group_of_everything_is_the_pool_as_it_was():
    pool = PagedKVPool("one", 3, 32, max_seq_len=64, page_size=16,
                       num_pages=9)
    assert len(pool.groups) == 1 and pool.groups[0].window is None
    assert [a.shape for a in pool.arrays] == [(3, 9, 16, 32)] * 2
    pool.reserve(5, 40)
    table = pool.table(5)
    assert table.shape == (4,) and pool.split_tables(table[None]).shape == \
        (1, 4)
    assert "groups" not in pool.snapshot()
    assert pool.row_bytes == 2 * 3 * 32 * 4 == sum(pool.group_row_bytes)


def test_defrag_moves_both_groups_and_decode_stays_bitwise():
    config, lm, _ = model("sssfsssf", 16)
    eng = serving.DecodeEndpoint("dfg", lm, max_seq_len=160,
                                 max_batch_size=4, num_pages=41)
    seq_a, seq_b = tokens(90, seed=3), tokens(70, seed=4)
    eng.pool.reserve(1, 60)             # low pages, freed below
    eng.pool.reserve(2, len(seq_a) + 1)
    eng.pool.reserve(3, len(seq_b) + 1)
    for sid, seq in ((2, seq_a), (3, seq_b)):
        eng.prefill(seq[:50], eng.pool.table(sid))
    rows = lambda: [(seq_a[50], 50, eng.pool.table(2)),
                    (seq_b[50], 50, eng.pool.table(3))]
    before_tables = [eng.pool.table(2).copy(), eng.pool.table(3).copy()]
    arrays = eng.pool.arrays
    want = step_logits(eng, rows())
    eng.pool.update_arrays(*arrays)     # undo the step's write
    eng.pool.free(1)
    moved = eng.pool.defrag()
    assert moved > 0
    after_tables = [eng.pool.table(2), eng.pool.table(3)]
    for before, after in zip(before_tables, after_tables):
        assert (before[:16] != after[:16]).any()        # the full group's
        assert (before[-2:] != after[-2:]).any()        # and the ring's
    onp.testing.assert_array_equal(step_logits(eng, rows()), want)


def test_a_freed_ring_reused_by_another_sequence_leaks_nothing():
    """The next owner's ring still holds the former one's rows; they lie
    behind its bound or past its length, so its logits are those of a
    sequence served from a pool that never held anything else."""
    config, lm, weights = model("sssfsssf", 16)
    outs = []
    for dirty in (True, False):
        eng = serving.DecodeEndpoint(f"reuse{dirty}", lm, max_seq_len=160,
                                     max_batch_size=1, num_pages=11)
        if dirty:
            first = tokens(150, seed=8)
            teacher_forced(eng, 1, first, 100)
            eng.pool.free(1)
        seq = tokens(60, seed=9)
        outs.append(teacher_forced(eng, 2, seq, 3))
        if dirty:       # the same physical ring, and it was not empty
            ring = eng.pool.groups[1].arrays[0]
            assert eng.pool.groups[1].num_pages == 3
            assert float(jnp.abs(ring[:, 1:]).min()) > 0
    onp.testing.assert_array_equal(*outs)


# ---------------------------------------------------------------------------
# the rotary tables
# ---------------------------------------------------------------------------
def test_yarn_frequencies_and_attention_factor_against_hand_values():
    import math
    from mxnet_tpu.ops import nn as ops
    rope = {"factor": 16, "original_max_position_embeddings": 8192,
            "beta_fast": 32, "beta_slow": 1}
    inv, scale = ops.rotary_frequencies(128, 500000.0, rope)
    # mscale 1 and mscale_all_dim 0: 0.1 ln 16 + 1, the config's
    # attention_factor to the last digit
    assert scale == 0.1 * math.log(16) + 1.0 == 1.2772588722239782
    stated = ops.rotary_frequencies(128, 500000.0, {
        **rope, "attention_factor": 1.2772588722239782})
    assert stated[1] == scale and (stated[0] == inv).all()
    # the ramp's ends: pair_of(t) = 128 ln(8192 / (2 pi t)) / (2 ln 500000):
    # 32 turns -> pair 18.09 (floor 18), 1 turn -> pair 34.99 (ceil 35)
    pair = lambda t: 128 * math.log(8192 / (t * 2 * math.pi)) \
        / (2 * math.log(500000))
    assert (math.floor(pair(32)), math.ceil(pair(1))) == (18, 35)
    plain = [500000.0 ** (-2 * i / 128) for i in range(64)]
    for i in (0, 18):                   # fast pairs: as they are
        assert inv[i] == pytest.approx(plain[i], rel=1e-6)
    for i in (35, 63):                  # slow pairs: divided by 16
        assert inv[i] == pytest.approx(plain[i] / 16, rel=1e-6)
    # halfway up the ramp, pair 26.5 -> pairs 26 and 27 blend 8/17 and 9/17
    assert inv[26] == pytest.approx(
        plain[26] * (1 - 8 / 17) + plain[26] / 16 * 8 / 17, rel=1e-6)
    # the reference's own table, written out apart, is the same
    ref_inv, ref_scale = reference.inverse_frequencies(
        {"rope_type": "yarn", "rope_theta": 500000,
         "attention_factor": 1.2772588722239782, **rope}, 128)
    onp.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
    assert ref_scale == scale
    # the sliding layers' table is plain
    assert family.rope_by_type(dims("sf", 8))[S] == {
        "theta": 500000.0, "scaling": None}
    inv, scale = ops.rotary_frequencies(128, 500000.0, None)
    onp.testing.assert_allclose(inv, plain, rtol=1e-6)
    assert scale == 1.0
    # and the model turns each kind by its own
    _, lm, _ = model("sssfsssf", 8)
    assert [r["scaling"] is None for r in lm._ropes] == [
        True, True, True, False] * 2
