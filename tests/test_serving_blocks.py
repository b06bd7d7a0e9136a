"""Generation by diffusion over blocks through the decode path
(gluon.model_zoo.moe_lm.MoEDecoderLM behind DecodeEndpoint, PagedKVPool,
DecodeScheduler and InferenceServer.generate) against the plain float32
reference (chipbench/reference/sdar_moe.py) at a small size on the CPU:
logits and confidences at every (block, step) through the cache, the
generation rule token for token and step for step, the expert layer and its
shares, and what a denoising step and a commit do to the pool. A forward is
two blocks a lane: the block in hand and the one behind it, whose first
denoising step rides with the commit of the block in hand."""
import threading

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from chipbench.reference import sdar_moe as ref
from mxnet_tpu import serving
from mxnet_tpu.gluon.model_zoo.moe_lm import MoEDecoderLM
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.serving.generate import DecodeScheduler
from mxnet_tpu.serving.generate import engine as engine_mod
from mxnet_tpu.serving.generate import scheduler as sched_mod
from three_copies import three_copies

L, MASK, VOCAB = 4, 95, 96
DIMS = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            rms_norm_eps=1e-6, rope_theta=1e6, num_experts=8,
            num_experts_per_tok=2, norm_topk_prob=True, block_length=L,
            mask_token_id=MASK)
TOL = dict(rtol=2e-4, atol=2e-4)      # float32 both sides, other op order


def build_lm(seed=3, **kw):
    lm = MoEDecoderLM(num_layers=2, units=64, num_heads=4, num_kv_heads=2,
                      head_dim=16, expert_hidden=32, num_experts=8,
                      experts_per_token=2, vocab_size=VOCAB, block_length=L,
                      mask_token_id=MASK, **kw)
    # a wide head: confidences spread between positions
    lm.initialize(mx.init.DeviceNormal(0.05, seed=seed,
                                       scales={"head_weight": 10}))
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
    return serving.DecodeEndpoint("blocks", lm, max_seq_len=64,
                                  max_batch_size=4, num_pages=17)


def prompt_of(n, seed):
    return [int(t) for t in
            onp.random.default_rng(seed).integers(0, MASK, n)]


ROWS = 64
_forward = jax.jit(lambda params, tokens, n: ref.forward(
    params, tokens, jnp.arange(ROWS),
    ref.block_mask(jnp.arange(ROWS), jnp.arange(ROWS), L)
    & (jnp.arange(ROWS) < n)[None, :] | jnp.eye(ROWS, dtype=bool), DIMS))


def reference_logits(params, tokens):
    """The reference's full forward, compiled once: padded to ROWS rows that
    no row of ``tokens`` sees."""
    padded = onp.zeros(ROWS, onp.int32)
    padded[:len(tokens)] = tokens
    return onp.asarray(_forward(params, padded, len(tokens)))[:len(tokens)]


def reference_generate(params, prompt, max_new, steps):
    return ref.generate(params, prompt, max_new, DIMS, steps,
                        logits_of=lambda t: reference_logits(params, t))


# ---------------------------------------------------------------------------
# (a) the model's full forward under the block mask
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("length", [4, 19, 32])
def test_full_forward_matches_the_reference(lm, params, length):
    toks = onp.asarray([prompt_of(length, s) for s in (1, 2)], onp.int32)
    out = lm(mx.nd.array(toks, dtype="int32")).asnumpy()
    for row, got in zip(toks, out):
        onp.testing.assert_allclose(got, reference_logits(params, row), **TOL)


def test_block_length_one_is_the_causal_mask():
    lm = MoEDecoderLM(num_layers=2, units=64, num_heads=4, num_kv_heads=2,
                      head_dim=16, expert_hidden=32, num_experts=8,
                      experts_per_token=2, vocab_size=VOCAB)
    lm.initialize(mx.init.DeviceNormal(0.05, seed=9))
    lm.hybridize()
    toks = onp.asarray([prompt_of(11, 5)], onp.int32)
    pos = onp.arange(11)
    want = ref.forward(reference_params(lm), toks[0], pos,
                       ref.block_mask(pos, pos, 1), DIMS)
    assert (ref.block_mask(pos, pos, 1) == onp.tril(onp.ones((11, 11)))).all()
    onp.testing.assert_allclose(
        lm(mx.nd.array(toks, dtype="int32")).asnumpy()[0], want, **TOL)


# ---------------------------------------------------------------------------
# (b) prefill, then block steps through the endpoint and the paged pool
# ---------------------------------------------------------------------------
def step_logits(eng):
    """The endpoint's traced step, stopped before the arg-max: logits of the
    L rows a lane it reads (the block behind where ``commit``, else the block
    in hand), nothing installed in the pool."""
    fn = jax.jit(lambda *a: engine_mod._step(
        eng.block, eng._params, eng.block.num_layers, eng.pool.page_size,
        *a)[0])

    def run(ids, start, table, commit):
        pos = start + onp.arange(2 * L, dtype=onp.int32)
        return onp.asarray(fn(
            eng._param_datas(), onp.asarray([ids], onp.int32), pos[None],
            table[None], onp.asarray([commit]), eng.pool.k_pool,
            eng.pool.v_pool))[0]
    return run


def blocks_of(prompt_len, max_new):
    """Open positions of each block a request generates."""
    tail, end = prompt_len % L, prompt_len % L + max_new
    return [min(L, end - at) - (tail if at == 0 else 0)
            for at in range(0, end, L)]


def forwards_of(prompt_len, max_new, steps):
    """Forwards a lone request takes: a block's denoising steps (fewer where
    a ragged tail or the budget's end leaves it fewer open positions), none
    to commit it."""
    return sum(-(-n // (L // steps)) for n in blocks_of(prompt_len, max_new))


# (prompt length, max_new_tokens) of a lone request, L = 4, max_seq_len 64
REQUESTS = [pytest.param(8, 16, id="whole_blocks"),
            pytest.param(14, 10, id="ragged_tail"),
            pytest.param(8, 7, id="budget_not_a_multiple"),
            pytest.param(3, 3, id="one_block"),
            pytest.param(40, 24, id="ends_at_max_seq_len")]


def by_hand(eng, params, prompt, max_new, steps, sid, check=None):
    """Generate through ``eng.decode_step`` alone, as the scheduler orders a
    block's forwards: a block's first denoising step in the forward that
    commits the block before it (the first block's with padding behind),
    the last block never committed. ``check(row, want)`` sees each forward's
    row and the reference's logits for the rows it reads. Returns (tokens,
    steps, forwards)."""
    prompt_len = len(prompt)
    eng.pool.reserve(sid, prompt_len + max_new)
    table = eng.pool.table(sid)
    try:
        start = prompt_len // L * L
        if start:
            eng.prefill(prompt[:start], table)
        seq, end = list(prompt), prompt_len + max_new
        got_toks, got_steps, forwards = [], [], 0
        whole = None            # the block before: whole, not yet committed
        while start < end:
            block = seq[start:] + [MASK] * (start + L - len(seq))
            masked = [prompt_len <= start + i < end for i in range(L)]
            at = [None] * L
            step = 0
            while any(masked):
                want = reference_logits(params, seq[:start] + block)[start:]
                row = (block + [MASK] * L, start, table, False) \
                    if whole is None else \
                    (whole + block, start - L, table, True)
                if check is not None:
                    check(row, want)
                [(ids, conf)] = eng.decode_step([row])
                forwards += 1
                whole = None
                tok, ref_conf = ref.candidates(want, MASK)
                onp.testing.assert_allclose(conf, ref_conf, rtol=2e-3)
                assert [int(t) for t in ids] == [int(t) for t in tok]
                for i in ref.place(conf, masked, L // steps):
                    block[i], masked[i], at[i] = int(ids[i]), False, step
                step += 1
            got_toks += [t for t, a in zip(block, at) if a is not None]
            got_steps += [a for a in at if a is not None]
            seq, whole = seq[:start] + block, block
            start += L
    finally:
        eng.pool.free(sid)
    return got_toks, got_steps, forwards


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("prompt_len", [8, 14])     # whole blocks; a tail of 2
def test_every_block_step_through_the_cache_matches_the_full_forward(
        eng, params, steps, prompt_len):
    """Every (block, step) state of a merged forward: the logits of the rows
    read, slot 1's beside a slot 0 that is committed in the same forward and
    slot 0's beside padding, against the reference's full forward; the ids
    and confidences the step returns; the rule's tokens and steps."""
    prompt, max_new = prompt_of(prompt_len, 7 + steps), 10
    want_toks, want_steps, _ = reference_generate(params, prompt, max_new,
                                                  steps)
    logits_of = step_logits(eng)
    got_toks, got_steps, forwards = by_hand(
        eng, params, prompt, max_new, steps, 100 + 10 * steps + prompt_len,
        check=lambda row, want: onp.testing.assert_allclose(
            logits_of(*row), want, **TOL))
    assert (got_toks, got_steps) == (want_toks, want_steps)
    assert forwards == forwards_of(prompt_len, max_new, steps)


@pytest.mark.parametrize("prompt_len,max_new", REQUESTS)
@pytest.mark.parametrize("steps", [1, 2])
def test_the_second_slot_is_dropped_where_there_is_no_block_to_hold(
        eng, params, steps, prompt_len, max_new):
    """Slot 1 behind a sequence's last block is padding (past ``max_seq_len``
    where the sequence ends there: 40 + 24 = 64): the rule's tokens all the
    same."""
    assert prompt_len + max_new <= eng.max_seq_len
    prompt = prompt_of(prompt_len, 50 + steps)
    want_toks, want_steps, _ = reference_generate(params, prompt, max_new,
                                                  steps)
    got_toks, got_steps, forwards = by_hand(
        eng, params, prompt, max_new, steps, 300 + prompt_len + steps)
    assert (got_toks, got_steps) == (want_toks, want_steps)
    assert forwards == forwards_of(prompt_len, max_new, steps)


# ---------------------------------------------------------------------------
# (c) generation through the server, lanes at different phases
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served(lm):
    eng = serving.DecodeEndpoint("blocks_served", lm, max_seq_len=64,
                                 max_batch_size=4, num_pages=17)
    server = serving.InferenceServer()
    server.register_generator(eng)
    server.start()
    yield server, eng
    server.stop(drain=True)


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_served_generation_equals_the_reference_rule(served, params, steps):
    server, eng = served
    was = eng.stats.snapshot()["counters"]
    # three lanes whose blocks start and end out of step with each other
    asks = [(prompt_of(9, 11), 9), (prompt_of(16, 12), 12),
            (prompt_of(3, 13), 7)]
    seen = [[] for _ in asks]
    streams = [server.generate(eng.name, p, max_new_tokens=n,
                               on_token=seen[i].append,
                               denoising_steps=steps)
               for i, (p, n) in enumerate(asks)]
    answers = [s.result(timeout=120) for s in streams]
    for (prompt, n), stream, answer, heard in zip(asks, streams, answers,
                                                  seen):
        want_toks, want_steps, want_sure = reference_generate(
            params, prompt, n, steps)
        assert answer == want_toks and len(answer) == n
        assert heard == answer                  # in sequence order
        assert stream.steps == want_steps
        # with the confidence each was placed with
        onp.testing.assert_allclose(stream.confidences, want_sure, rtol=2e-3)
        assert MASK not in answer
    counters = {k: v - was[k]
                for k, v in eng.stats.snapshot()["counters"].items()}
    assert counters["tokens"] == counters["tokens_placed"] == \
        sum(n for _, n in asks)
    assert counters["forwards"] == counters["steps"] > counters["commits"]
    # two blocks a lane a forward
    assert counters["rows"] >= 2 * L * counters["forwards"]
    # every block but a sequence's last is committed, each by the forward
    # that is the next block's first denoising step
    blocks = sum(len(blocks_of(len(p), n)) - 1 for p, n in asks)
    assert counters["blocks_committed"] == counters["commits_merged"] \
        == blocks
    # the lanes run side by side: fewer forwards than one after another
    assert counters["forwards"] <= sum(
        forwards_of(len(p), n, steps) for p, n in asks)
    assert counters["moe.expert_load_max"] >= counters["moe.expert_load_mean"] > 0


@pytest.mark.parametrize("prompt_len,max_new", REQUESTS)
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_a_lone_request_takes_its_denoising_steps_and_no_forward_to_commit(
        served, params, steps, prompt_len, max_new):
    """N blocks in N x ``denoising_steps`` forwards (fewer where a block has
    fewer open positions): N - 1 commits, each merged with the next block's
    first step, none after the last block."""
    server, eng = served
    was = eng.stats.snapshot()["counters"]
    prompt = prompt_of(prompt_len, 70 + steps)
    stream = server.generate(eng.name, prompt, max_new_tokens=max_new,
                             denoising_steps=steps)
    answer = stream.result(timeout=120)
    want_toks, want_steps, want_sure = reference_generate(
        params, prompt, max_new, steps)
    assert (answer, stream.steps) == (want_toks, want_steps)
    onp.testing.assert_allclose(stream.confidences, want_sure, rtol=2e-3)
    counters = {k: v - was[k]
                for k, v in eng.stats.snapshot()["counters"].items()}
    blocks = len(blocks_of(prompt_len, max_new))
    assert counters["forwards"] == forwards_of(prompt_len, max_new, steps)
    if (prompt_len, max_new) == (8, 16):
        assert counters["forwards"] == blocks * steps
    assert counters["commits"] == counters["blocks_committed"] \
        == counters["commits_merged"] == blocks - 1
    assert counters["rows"] == 2 * L * counters["forwards"]
    assert counters["tokens"] == counters["tokens_placed"] == max_new


def driven(eng, **kw):
    """A scheduler that runs no thread: the test calls ``_iteration(1)``."""
    sched = DecodeScheduler(eng, **kw)
    sched._state, sched._epoch = sched_mod._RUNNING, 1
    return sched


def served_alone(eng, asks, steps):
    """Each request by itself, one pass after another: (tokens, steps,
    confidences) of each."""
    sched = driven(eng)
    out = []
    try:
        for prompt, n in asks:
            stream = sched.submit(prompt, max_new_tokens=n,
                                  denoising_steps=steps)
            while sched._iteration(1) == sched_mod._AGAIN:
                pass
            out.append((stream.result(timeout=5), stream.steps,
                        stream.confidences))
    finally:
        sched.stop()
    return out


@pytest.fixture(scope="module")
def one_bucket(lm):
    """One step executable: a lane's numbers are then the same to the bit
    whatever batch it ran in (XLA:CPU may round a row otherwise at another
    batch size)."""
    eng = serving.DecodeEndpoint("blocks_one_bucket", lm, max_seq_len=64,
                                 max_batch_size=4, decode_buckets=(4,),
                                 num_pages=17)
    eng.warmup()
    return eng


ASKS = [(prompt_of(9, 11), 9), (prompt_of(16, 12), 12), (prompt_of(3, 7), 7),
        (prompt_of(6, 14), 6)]


@pytest.mark.parametrize("steps", [2, 4])
def test_lanes_at_different_phases_equal_one_at_a_time_to_the_bit(
        one_bucket, steps):
    """Lanes that commit beside lanes in the middle of a block, admitted a
    pass apart: ids, steps and confidences of each as when served alone."""
    eng = one_bucket
    alone = served_alone(eng, ASKS, steps)
    sched = driven(eng)
    try:
        streams = []
        for prompt, n in ASKS:
            streams.append(sched.submit(prompt, max_new_tokens=n,
                                        denoising_steps=steps))
            sched._iteration(1)
        phases = {(None not in s.placed, s.step) for s in sched._active}
        assert len(phases) > 1      # not in step with each other
        while sched._iteration(1) == sched_mod._AGAIN:
            pass
        together = [(s.result(timeout=5), s.steps, s.confidences)
                    for s in streams]
    finally:
        sched.stop()
    assert together == alone


def until_a_block_is_whole(sched):
    """Pass after pass until the one running sequence holds a whole block
    that no forward has committed yet; returns it."""
    for _ in range(40):
        sched._iteration(1)
        (seq,) = sched._active + list(sched._waiting)
        if None not in seq.placed:
            return seq
    raise AssertionError("no block became whole")


def test_a_paused_stream_holds_a_whole_block_and_commits_when_it_resumes(
        one_bucket):
    """The stream fills as a block's tokens reach it: the sequence pauses
    with the block whole and not committed, keeps it while it is not stepped,
    and its next forward commits it and places the next block's first
    tokens."""
    eng = one_bucket
    prompt, n = prompt_of(8, 21), 12
    [want] = served_alone(eng, [(prompt, n)], 2)
    was = dict(eng.stats.counters)
    sched = driven(eng, stream_buffer=2)
    try:
        stream = sched.submit(prompt, max_new_tokens=n, denoising_steps=2)
        seq = until_a_block_is_whole(sched)
        assert seq.state == sched_mod._S_PAUSED and seq.pos == 8
        held = (list(seq.block), list(seq.placed))
        pool = onp.asarray(eng.pool.k_pool)
        assert sched._iteration(1) == sched_mod._REST       # not stepped
        assert (seq.block, seq.placed) == held
        assert onp.array_equal(pool, onp.asarray(eng.pool.k_pool))
        heard = []
        while not stream.closed:
            while stream._dq:       # the consumer drains: it resumes
                heard.append(stream.get(timeout=0))
            sched._iteration(1)
        assert (heard, stream.steps, stream.confidences) == want
    finally:
        sched.stop()
    now = eng.stats.counters
    assert now["forwards"] - was["forwards"] == forwards_of(8, n, 2)
    assert now["commits_merged"] - was["commits_merged"] == 2
    assert now["seq_paused"] - was["seq_paused"] >= 1


def test_a_failover_requeue_holds_a_whole_block_and_commits_it_once(
        one_bucket):
    """The worker dies between the pass that made a block whole and the one
    that would commit it: the requeued sequence keeps the block on itself,
    and the new worker's first forward for it commits it, merged as ever:
    the same tokens, no forward twice."""
    eng = one_bucket
    prompt, n = prompt_of(10, 23), 10
    [want] = served_alone(eng, [(prompt, n)], 2)
    was = dict(eng.stats.counters)
    sched = driven(eng)
    try:
        stream = sched.submit(prompt, max_new_tokens=n, denoising_steps=2)
        seq = until_a_block_is_whole(sched)
        assert seq.state == sched_mod._S_RUNNING
        dead = threading.Thread(target=lambda: None)
        dead.start()
        dead.join()
        sched._thread = dead
        sched._check_worker()       # requeues; a new worker takes over
        assert sched.failovers == 1
        assert (stream.result(timeout=60), stream.steps,
                stream.confidences) == want
    finally:
        sched.stop()
    now = eng.stats.counters
    assert now["seq_requeued"] - was["seq_requeued"] == 1
    assert now["prefills"] - was["prefills"] == 1      # not prefilled again
    assert now["forwards"] - was["forwards"] == forwards_of(10, n, 2)
    assert now["commits_merged"] - was["commits_merged"] \
        == len(blocks_of(10, n)) - 1


def test_block_length_one_is_served_as_the_causal_step():
    """No mask token, one row a sequence a step: the same endpoint, pool and
    scheduler, greedy decoding equal to the reference's arg-max continuation
    (a lead under 1e-3 could go either way: none here)."""
    lm = MoEDecoderLM(num_layers=2, units=64, num_heads=4, num_kv_heads=2,
                      head_dim=16, expert_hidden=32, num_experts=8,
                      experts_per_token=2, vocab_size=VOCAB)
    lm.initialize(mx.init.DeviceNormal(0.05, seed=9,
                                       scales={"head_weight": 10}))
    lm.hybridize()
    eng = serving.DecodeEndpoint("causal_moe", lm, max_seq_len=32,
                                 max_batch_size=2, num_pages=5)
    assert (eng.block_length, eng.mask_token_id) == (1, None)
    server = serving.InferenceServer()
    server.register_generator(eng)
    server.start()
    try:
        prompt = prompt_of(7, 41)
        stream = server.generate(eng.name, prompt, max_new_tokens=6)
        answer = stream.result(timeout=120)
    finally:
        server.stop(drain=True)
    params, seq = reference_params(lm), list(prompt)
    for tok in answer:
        pos = onp.arange(len(seq))
        logits = onp.asarray(ref.forward(
            params, onp.asarray(seq, onp.int32), pos,
            ref.block_mask(pos, pos, 1), DIMS))[-1]
        assert logits[tok] > onp.sort(logits)[-2] - 1e-3
        assert logits[tok] >= logits.max() - 1e-3
        seq.append(tok)
    assert len(answer) == 6 and stream.steps == [0] * 6
    assert stream.confidences == []      # placed by no confidence
    counters = eng.stats.snapshot()["counters"]
    # the first token comes from the prefill; every step commits its row
    assert counters["forwards"] == counters["commits"] == 5
    assert counters["rows"] == counters["blocks_committed"] == 5


def test_denoising_steps_must_divide_the_block(eng):
    sched = serving.generate.DecodeScheduler(eng)
    with pytest.raises(mx.MXNetError, match="must divide"):
        sched.submit([1, 2, 3], max_new_tokens=4, denoising_steps=3)


def test_a_causal_endpoint_refuses_denoising_steps():
    from mxnet_tpu.gluon.model_zoo.bert import TransformerLM
    lm = TransformerLM(num_layers=1, units=16, hidden_size=32, num_heads=2,
                       vocab_size=32, max_length=32)
    lm.initialize()
    eng = serving.DecodeEndpoint("causal", lm, max_seq_len=32,
                                 max_batch_size=2, num_pages=5)
    assert (eng.block_length, eng.mask_token_id) == (1, None)
    assert eng.pool.kv_dim == 16
    with pytest.raises(mx.MXNetError, match="must divide"):
        serving.generate.DecodeScheduler(eng).submit(
            [1, 2], max_new_tokens=2, denoising_steps=2)


# ---------------------------------------------------------------------------
# (d) the expert layer
# ---------------------------------------------------------------------------
def layer_weights(seed, experts=8, hidden=64, width=32):
    rng = onp.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 0.1, shape), jnp.float32)
    return {"router": draw(hidden, experts),
            "w_gate": draw(experts, hidden, width),
            "w_up": draw(experts, hidden, width),
            "w_down": draw(experts, width, hidden)}


def test_no_row_is_dropped_when_all_rows_choose_one_expert():
    p = layer_weights(1)
    x = jnp.abs(jnp.asarray(onp.random.default_rng(2).normal(0, 1, (24, 64)),
                            jnp.float32))
    # positive rows and one router column far above the rest: every row's
    # first choice is expert 5
    p["router"] = p["router"].at[:, 5].set(1.0)
    out, load = ops.moe_ffn(x, p["router"], p["w_gate"], p["w_up"],
                            p["w_down"], top_k=2)
    assert int(load[5]) == 24 and int(load.sum()) == 48
    onp.testing.assert_allclose(out, ref.experts(x, p, DIMS), **TOL)


@pytest.mark.parametrize("rows", [48, 53, 16])
def test_a_long_prompts_pairs_go_a_block_of_whole_rows_at_a_time(
        rows, monkeypatch):
    """Where a chip holds every expert and the pairs pass the block
    (``_ALL_HELD_PAIRS``: 32,768 on the chip, 32 here), the rows go 16 at a
    time, the last block padded with rows that route nowhere: bitwise what
    each row gets alone in its block's place, the reference's result, and
    every pair counted once. 16 rows are one pass as ever."""
    p = layer_weights(7)
    x = jnp.asarray(onp.random.default_rng(8).normal(0, 1, (rows, 64)),
                    jnp.float32)
    args = (p["router"], p["w_gate"], p["w_up"], p["w_down"])
    whole, whole_load = ops.moe_ffn(x, *args, top_k=2)
    monkeypatch.setattr(ops, "_ALL_HELD_PAIRS", 32)
    out, load = ops.moe_ffn(x, *args, top_k=2)
    onp.testing.assert_array_equal(load, whole_load)
    assert int(load.sum()) == rows * 2
    onp.testing.assert_allclose(out, ref.experts(x, p, DIMS), **TOL)
    onp.testing.assert_array_equal(out, whole)      # a row's own order
    if rows > 16:
        text = str(jax.make_jaxpr(lambda x: ops.moe_ffn(x, *args, top_k=2))(
            x))
        assert "f32[16,64]" in text and "scan" in text


def _routed(rows, seed, first=0, held=8):
    p = layer_weights(seed)
    x = jnp.asarray(onp.random.default_rng(seed + 1).normal(0, 1, (rows, 64)),
                    jnp.float32)
    top_w, top_i = ops.route_softmax(x, p["router"], top_k=2)
    share = {k: p[k][first:first + held] for k in ("w_gate", "w_up",
                                                   "w_down")}
    return x, top_w, top_i, share


@pytest.mark.parametrize("form", ["one_pass", "blocks", "share"])
def test_a_pass_sums_each_rows_pairs_as_the_three_copies_did(
        form, monkeypatch):
    """Each row reads its own pairs where they sorted to and sums them in its
    own order: what the weighted copy, the copy in pair order and the row sum
    gave, to float32 rounding; a long prompt's blocks each as one pass, the
    rows that pad the last block routing nowhere and giving 0; a share's
    pass, whose pairs routed elsewhere were never computed."""
    first, held = (4, 4) if form == "share" else (0, 8)
    rows = 53 if form == "blocks" else 16
    x, top_w, top_i, p = _routed(rows, 11, first, held)
    args = (p["w_gate"], p["w_up"], p["w_down"])
    if form == "blocks":
        monkeypatch.setattr(ops, "_ALL_HELD_PAIRS", 32)
        pad = -rows % 16
        bx = jnp.pad(x, ((0, pad), (0, 0)))
        bw = jnp.pad(top_w, ((0, pad), (0, 0)))
        bi = jnp.pad(top_i, ((0, pad), (0, 0)), constant_values=-1)
        want = jnp.concatenate([
            three_copies(bx[b:b + 16], bw[b:b + 16], bi[b:b + 16], *args)
            for b in range(0, rows + pad, 16)])
        # a padded block's rows that route nowhere give exactly 0
        last, _ = ops.expert_ffn(bx[-16:], bw[-16:], bi[-16:], *args,
                                 all_held=True)
        assert not onp.asarray(last[16 - pad:]).any()
        onp.testing.assert_array_equal(want[rows:], 0.0)
        want = want[:rows]
    else:
        want = three_copies(x, top_w, top_i, *args, first)
    out, load = ops.expert_ffn(x, top_w, top_i, *args, first_expert=first,
                               all_held=form != "share")
    assert int(load.sum()) == int(((top_i >= first)
                                   & (top_i < first + held)).sum())
    onp.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-7)


# (rows, top_k, experts routed, held, first, width, rows a tile): one tile;
# tiles whose runs share granules; a share whose pairs routed elsewhere are
# never read; rows that are not a power of two
PAIR_SUMS = [pytest.param(64, 8, 16, 16, 0, 256, 64, id="one_tile"),
             pytest.param(256, 3, 8, 8, 0, 128, 16, id="shared_granules"),
             pytest.param(64, 4, 32, 8, 8, 128, 8, id="share"),
             pytest.param(24, 4, 256, 32, 0, 128, 8, id="rows_24")]


@pytest.mark.parametrize("T,top_k,E,held,first,H,rows", PAIR_SUMS)
def test_the_pair_sum_kernel_reads_each_rows_pairs(T, top_k, E, held, first,
                                                   H, rows):
    """The Pallas kernel under the interpreter against the plain expression
    and the three copies, the rows the products did not compute holding
    NaN: it fetches the granules of each tile's runs, copies a granule two
    tiles share across, and reads a dead pair nowhere."""
    from mxnet_tpu.ops.pallas import pair_sum as ps
    rng = onp.random.default_rng(T + held)
    top_i = onp.stack([rng.choice(E, top_k, replace=False)
                       for _ in range(T)]).astype(onp.int32)
    top_w = rng.random((T, top_k)).astype(onp.float32)
    expert = top_i.reshape(-1) - first
    here = (expert >= 0) & (expert < held)
    expert = onp.where(here, expert, held)
    order = onp.argsort(expert, kind="stable")
    sizes = onp.bincount(expert, minlength=held + 1)[:held].astype(onp.int32)
    y = rng.normal(0, 1, (T * top_k, H)).astype(onp.float32)
    y[sizes.sum():] = onp.nan
    weight = onp.where(here, top_w.reshape(-1), 0.0)[order]
    live = onp.arange(T * top_k) < sizes.sum()
    want = onp.where(live[:, None], y * weight[:, None], 0.0)[
        onp.argsort(order)].reshape(T, top_k, H).sum(1, dtype=onp.float32)
    args = (jnp.asarray(y), jnp.asarray(onp.argsort(order).reshape(T, top_k)),
            jnp.asarray(expert.reshape(T, top_k)), jnp.asarray(top_w),
            jnp.asarray(sizes), jnp.float32)
    dense = ps.pair_sum(*args)
    kernel = ps._pallas_sum(*args, rows, True)
    onp.testing.assert_allclose(dense, want, rtol=1e-6, atol=1e-6)
    onp.testing.assert_allclose(kernel, dense, rtol=1e-6, atol=1e-6)
    assert not onp.isnan(onp.asarray(kernel)).any()
    plan = ps._plan(*args[1:5], rows)
    assert int(plan[4].max()) <= ps._granules(rows, top_k, held) - 1
    if T // rows > 1 and held <= 8:
        assert int((plan[3] >= 0).sum()) > 0      # some granule copied across


def _pair_sums():
    return {form: ops._PAIR_SUMS.labels(form).value
            for form in ("row_gather", "share_passes")}


def test_the_counter_says_how_each_pass_reaches_its_rows(monkeypatch):
    """``mxtpu_moe_pair_sums_total``, counted when a pass is traced: a chip
    that holds every expert and a share's step read their rows' pairs
    (``row_gather``); a share's prompt past one pass adds its passes to the
    rows (``share_passes``)."""
    monkeypatch.setattr(ops, "_ALL_HELD_PAIRS", 32)
    p = layer_weights(5)
    args = (p["router"], p["w_gate"], p["w_up"], p["w_down"])
    cut = lambda k: p[k][2:4]

    def traced(rows, share=False):
        x = jnp.zeros((rows, 64), jnp.float32)
        before = _pair_sums()
        if share:
            jax.make_jaxpr(lambda x: ops.moe_ffn(
                x, p["router"], cut("w_gate"), cut("w_up"), cut("w_down"),
                top_k=2, first_expert=2))(x)
        else:
            jax.make_jaxpr(lambda x: ops.moe_ffn(x, *args, top_k=2))(x)
        return {k: v - before[k] for k, v in _pair_sums().items()}

    # a prompt of four blocks of whole rows: its one traced pass
    assert traced(53) == {"row_gather": 1, "share_passes": 0}
    assert traced(8) == {"row_gather": 1, "share_passes": 0}
    monkeypatch.setattr(ops, "_PAIR_BLOCK", 16)
    assert traced(8, share=True) == {"row_gather": 1, "share_passes": 0}
    assert traced(53, share=True) == {"row_gather": 0, "share_passes": 1}


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_of_the_experts_add_up_to_the_whole_layer(shares):
    p = layer_weights(3)
    x = jnp.asarray(onp.random.default_rng(4).normal(0, 1, (19, 64)),
                    jnp.float32)
    whole = ref.experts(x, p, DIMS)
    held = 8 // shares
    total, rows = 0.0, 0
    for first in range(0, 8, held):
        cut = slice(first, first + held)
        part, load = ops.moe_ffn(
            x, p["router"], p["w_gate"][cut], p["w_up"][cut],
            p["w_down"][cut], top_k=2, first_expert=first)
        share = {**p, **{k: p[k][cut] for k in ("w_gate", "w_up", "w_down")}}
        onp.testing.assert_allclose(
            part, ref.experts(x, share, DIMS, held=(first, held)), **TOL)
        total, rows = total + part, rows + int(load.sum())
    assert rows == 19 * 2                   # every (row, expert) pair, once
    onp.testing.assert_allclose(total, whole, **TOL)


def test_a_model_that_holds_a_share_of_the_experts(params):
    """The model is told which experts it holds: two halves' layers differ
    from the whole model's by exactly the other half's part."""
    whole = build_lm()
    x = onp.asarray([prompt_of(8, 21)], onp.int32)
    halves = []
    for first in (0, 4):
        half = build_lm(held_experts=(first, 4))
        for (name, p), src in zip(half.collect_params().items(),
                                  whole.collect_params().values()):
            src = src.data().data
            p.set_data(mx.nd.array(
                src[first:first + 4] if "experts" in name else src))
        halves.append(half)
    pos = onp.arange(8)
    mask = ref.block_mask(pos, pos, L)
    for first, half in zip((0, 4), halves):
        p = reference_params(half)
        want = ref.forward(p, x[0], pos, mask, DIMS, held=(first, 4))
        onp.testing.assert_allclose(
            half(mx.nd.array(x, dtype="int32")).asnumpy()[0], want, **TOL)


# ---------------------------------------------------------------------------
# (e) the pool: a denoising step writes nothing, a commit only its block
# ---------------------------------------------------------------------------
def test_a_denoising_step_leaves_the_pool_and_a_commit_writes_its_block(eng):
    """Of a forward's two blocks a commit writes slot 0's page rows and no
    other, slot 1's never; a forward without the flag leaves the pool."""
    sid, prompt = 900, prompt_of(8, 31)
    eng.pool.reserve(sid, 24)
    table = eng.pool.table(sid)
    try:
        eng.prefill(prompt, table)
        pools = lambda: (onp.asarray(eng.pool.k_pool),
                         onp.asarray(eng.pool.v_pool))
        before = pools()
        eng.decode_step([([MASK] * 2 * L, 8, table, False)])
        for was, now in zip(before, pools()):
            # page 0 is the scratch page the dropped rows are routed to
            assert onp.array_equal(was[:, 1:], now[:, 1:])
        # slot 1 lies in the same page here (8..11 and 12..15 of page 0 of
        # the sequence) and in the next one below (12..15 and 16..19)
        for start in (8, 12):
            before = pools()
            eng.decode_step([([5, 6, 7, 8] + [MASK] * L, start, table,
                              True)])
            page = int(table[start // eng.pool.page_size])
            slot = start % eng.pool.page_size
            for was, now in zip(before, pools()):
                changed = onp.argwhere((was != now).any(-1))
                assert {tuple(c[1:]) for c in changed if c[1] != 0} == \
                    {(page, slot + i) for i in range(L)}
                assert len({c[0] for c in changed}) == eng.block.num_layers
    finally:
        eng.pool.free(sid)


def test_a_step_returns_the_rows_of_one_block_a_lane(eng):
    """The head sees L rows a lane, slot 1's where the lane commits: lanes of
    one batch read different slots, and each gets what it gets alone."""
    sids = (910, 911)
    rows = []
    try:
        for sid, commit in zip(sids, (False, True)):
            eng.pool.reserve(sid, 16)
            table = eng.pool.table(sid)
            eng.prefill(prompt_of(8, sid), table)
            rows.append((prompt_of(4, sid + 1) + [MASK] * L, 8, table,
                         commit))
        text = str(jax.make_jaxpr(engine_mod._decode, static_argnums=(
            0, 1, 2, 3))(eng.block, eng._params, eng.pool.page_size, MASK,
                         eng._param_datas(), onp.zeros((2, 2 * L), onp.int32),
                         onp.zeros((2, 2 * L), onp.int32),
                         onp.zeros((2, eng.pool.pages_per_seq), onp.int32),
                         onp.zeros((2,), bool), *eng.pool.arrays))
        assert f"f32[2,{L},{VOCAB}]" in text
        assert f"f32[2,{2 * L},{VOCAB}]" not in text
        logits_of = step_logits(eng)
        want = [ref.candidates(logits_of(*row), MASK) for row in rows]
        for (ids, conf), (tok, sure) in zip(eng.decode_step(rows), want):
            assert ids.shape == conf.shape == (L,)
            assert [int(t) for t in ids] == [int(t) for t in tok]
            onp.testing.assert_allclose(conf, sure, rtol=1e-5)
    finally:
        for sid in sids:
            eng.pool.free(sid)


def test_the_pool_takes_its_row_and_dtype_from_the_model(eng):
    assert eng.pool.kv_dim == 2 * 16 != eng.block.units
    assert eng.pool.k_pool.dtype == jnp.float32
    low = serving.DecodeEndpoint("blocks_bf16", build_lm(dtype="bfloat16"),
                                 max_seq_len=32, max_batch_size=2,
                                 num_pages=9)
    assert low.pool.k_pool.dtype == jnp.bfloat16
    assert low.pool.k_pool.shape == (2, 9, low.pool.page_size, 32)
    [(ids, conf)] = low.decode_step([([MASK] * 2 * L, 0, low.pool.table(1),
                                      False)])
    assert conf.dtype == onp.float32 and ids.shape == (L,)
    assert MASK not in ids


def test_a_block_must_lie_in_one_page(lm):
    with pytest.raises(mx.MXNetError, match="must divide"):
        serving.DecodeEndpoint("odd", lm, max_seq_len=62, max_batch_size=2,
                               num_pages=9)
