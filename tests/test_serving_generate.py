"""Generative serving tests: paged KV cache, bucketed prefill/decode-step
executables, token-granularity continuous batching, streaming backpressure,
and decode fault injection (tier-1, JAX_PLATFORMS=cpu).

The load-bearing property is the acceptance criterion: batched continuous
decode — sequences joining and retiring mid-batch, KV pages freed and
reallocated between sequences — is BITWISE equal to one-sequence-at-a-time
greedy decode through the same executables.
"""
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config as mxconfig
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.bert import TransformerLM
from mxnet_tpu.resilience import faults
from mxnet_tpu.serving import KVPoolExhausted, bucketing
from mxnet_tpu.serving.generate import (DecodeEndpoint, DecodeScheduler,
                                        PagedKVPool, TokenStream,
                                        write_prefill, write_step)


def _lm(seed=0, cls=TransformerLM, **kw):
    # the initialisers draw from mx.random's key chain: left where the
    # worker's earlier tests put it, one state in two dozen gives weights
    # whose greedy decode is not history-sensitive (the oracle's own check)
    mx.random.seed(seed)
    onp.random.seed(seed)
    cfg = dict(num_layers=2, units=32, hidden_size=64, num_heads=2,
               vocab_size=50, max_length=64)
    cfg.update(kw)
    lm = cls(**cfg)
    # wide init so greedy argmax is history-sensitive: a decode path that
    # ignored or corrupted the KV context would emit different tokens
    lm.initialize(mx.init.Normal(0.5))
    return lm


@pytest.fixture(scope="module")
def engine():
    eng = DecodeEndpoint("tlm", _lm(), max_seq_len=64, max_batch_size=4,
                         page_size=8, num_pages=64)
    eng.warmup()
    return eng


def _serial_decode(eng, prompt, max_new, sid):
    """The oracle: one sequence at a time through the SAME executables."""
    eng.pool.reserve(sid, len(prompt) + max_new)
    toks = [eng.prefill(prompt, eng.pool.table(sid))]
    pos = len(prompt)
    for _ in range(max_new - 1):
        (t,) = eng.decode_step([(toks[-1], pos, eng.pool.table(sid))])
        toks.append(t)
        pos += 1
    eng.pool.free(sid)
    return toks


PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9, 10], [11], [12, 13],
           [14, 15, 16, 17]]
BUDGETS = [6, 9, 4, 8, 5, 7]


# ---------------------------------------------------------------------------
# the acceptance oracle
# ---------------------------------------------------------------------------
def test_continuous_batched_decode_bitwise_equals_serial(engine):
    """Sequences join and retire mid-batch (staggered submits, different
    budgets) and pages are freed/reallocated throughout — outputs must be
    BITWISE equal to serial greedy decode."""
    base = engine.pool.pages_in_use
    oracle = [_serial_decode(engine, p, b, 90000 + i)
              for i, (p, b) in enumerate(zip(PROMPTS, BUDGETS))]
    # the oracle must be discriminative: history-sensitive outputs
    assert any(len(set(t)) > 2 for t in oracle)
    assert engine.pool.pages_in_use == base     # oracle freed its pages

    sched = DecodeScheduler(engine, poll_s=0.02).start()
    try:
        streams = []
        for i, (p, b) in enumerate(zip(PROMPTS, BUDGETS)):
            streams.append(sched.submit(p, max_new_tokens=b))
            if i == 2:
                time.sleep(0.05)      # later submits join a running batch
        results = [s.result(timeout=60) for s in streams]
    finally:
        sched.stop()
    assert results == oracle
    assert engine.pool.pages_in_use == base     # all pages returned
    counters = engine.stats.snapshot()["counters"]
    assert counters["seq_finished"] >= len(PROMPTS)


def test_page_free_then_realloc_is_bitwise_clean(engine):
    """A second wave reuses pages the first wave dirtied (LIFO free list
    guarantees reuse); stale page contents must be invisible."""
    first = _serial_decode(engine, [21, 22, 23], 8, 91001)
    again = _serial_decode(engine, [21, 22, 23], 8, 91002)
    assert first == again
    # different sequence on the same physical pages
    other = _serial_decode(engine, [31, 32], 8, 91003)
    again2 = _serial_decode(engine, [21, 22, 23], 8, 91004)
    assert again2 == first and other != first


@pytest.mark.parametrize("length", [11, 16])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefill_collect_reads_the_row_it_is_given(seed, length):
    """``prefill_collect(tokens, last)``: the head over the one row ``last``
    gives that row of the (B, S, V) logits (a (1, U) x (U, V) product in
    place of one row of an (S, U) x (U, V) one: the last bits may differ,
    the arg-max does not), and every layer's K and V are bitwise the same."""
    lm = _lm(seed)
    rng = onp.random.default_rng(seed)
    ids = onp.zeros((2, 16), "int32")       # one rung, padded as a bucket is
    ids[:, :length] = rng.integers(1, 50, (2, length))
    tokens = mx.nd.array(ids, dtype="int32")
    last = onp.array([length - 1, length // 2], "int32")
    whole = [o.asnumpy() for o in lm.prefill_collect(tokens)]
    row = [o.asnumpy() for o in
           lm.prefill_collect(tokens, mx.nd.array(last, dtype="int32"))]
    assert whole[0].shape == (2, 16, 50) and row[0].shape == (2, 1, 50)
    want = whole[0][onp.arange(2), last]
    onp.testing.assert_allclose(row[0][:, 0], want, rtol=0, atol=1e-5)
    assert (row[0][:, 0].argmax(-1) == want.argmax(-1)).all()
    assert len(row) == len(whole) == 1 + 2 * lm.num_layers
    for a, b in zip(row[1:], whole[1:]):
        assert a.shape == (2, 16, 32) and onp.array_equal(a, b)


def test_a_block_that_takes_no_row_is_served_by_the_old_reading(engine):
    """A block without ``prefill_reads_row`` gets ``prefill_collect(tokens)``
    and row ``length - 1`` of its (1, S, V) logits is read: the same tokens
    as the row-taking prefill serves."""
    class WholeLogitsLM(TransformerLM):
        prefill_reads_row = False

        def prefill_collect(self, tokens):
            return super().prefill_collect(tokens)

    old = DecodeEndpoint("tlm_whole", _lm(cls=WholeLogitsLM), max_seq_len=64,
                         max_batch_size=4, page_size=8, num_pages=64)
    for i, (p, b) in enumerate(zip(PROMPTS[:3], BUDGETS[:3])):
        assert _serial_decode(old, p, b, 92000 + i) == \
            _serial_decode(engine, p, b, 92100 + i)


def test_defrag_is_bitwise_invisible(engine):
    """Compaction mid-generation relocates live pages; decode continues
    bitwise-identically through the remapped tables."""
    oracle = _serial_decode(engine, [41, 42, 43], 8, 92000)
    # fragment: allocate a victim before, free it mid-way
    engine.pool.reserve(92001, 30)              # 4 pages, low ids
    sid = 92002
    engine.pool.reserve(sid, 3 + 8)
    toks = [engine.prefill([41, 42, 43], engine.pool.table(sid))]
    pos = 3
    for i in range(7):
        if i == 3:
            engine.pool.free(92001)             # holes below sid's pages
            moved = engine.pool.defrag()
            assert moved > 0
        (t,) = engine.decode_step([(toks[-1], pos, engine.pool.table(sid))])
        toks.append(t)
        pos += 1
    engine.pool.free(sid)
    assert toks == oracle


# ---------------------------------------------------------------------------
# the jit-side pool writes against their plain reference
# ---------------------------------------------------------------------------
def _scatter_prefill(pool, vals, table_row, length, page_size):
    """The plain reference for write_prefill: the advanced-index scatter it
    replaced, padding positions routed to scratch page 0."""
    pos = jnp.arange(vals.shape[1], dtype=jnp.int32)
    page = jnp.where(pos < length, table_row[pos // page_size], 0)
    return pool.at[:, page, pos % page_size, :].set(vals)


def _scatter_step(pool, vals, tables, positions, valid, page_size):
    """The plain reference for write_step, invalid rows to scratch page 0."""
    page = tables[jnp.arange(tables.shape[0]), positions // page_size]
    page = jnp.where(valid, page, 0)
    return pool.at[:, page, positions % page_size, :].set(vals)


def _random_pools(rng, num_pages, page_size, layers=3, kv=8):
    shape = (layers, num_pages, page_size, kv)
    return (jnp.asarray(rng.standard_normal(shape), jnp.float32),
            jnp.asarray(rng.standard_normal(shape), jnp.float32))


def _same_but_scratch(got, want):
    onp.testing.assert_array_equal(onp.asarray(got)[:, 1:],
                                   onp.asarray(want)[:, 1:])


# the cell's buckets, a ladder ending off the page grid (S = 100), a prompt
# that fills its bucket, one-position pages, a page larger than the bucket
@pytest.mark.parametrize("S,page_size,length", [
    (16, 16, 1), (16, 16, 16), (128, 16, 97), (100, 16, 100), (100, 16, 33),
    (32, 1, 7), (16, 64, 5)])
def test_write_prefill_equals_scatter_reference(S, page_size, length):
    """Every real page bitwise what the scatter left there: positions past
    ``length`` in the last page keep the page's old contents, pages past it
    are untouched, whether the table holds reserved pages there or zeros."""
    rng = onp.random.default_rng(S * 1000 + page_size * 10 + length)
    P = -(-S // page_size)
    pools = _random_pools(rng, P + 4, page_size)
    vals = tuple(jnp.asarray(rng.standard_normal((3, S, 8)), jnp.float32)
                 for _ in pools)
    reserved = rng.permutation(onp.arange(1, P + 4))[:P].astype(onp.int32)
    padded = reserved.copy()
    padded[-(-length // page_size):] = 0
    write = jax.jit(write_prefill, static_argnums=4)
    for table in (reserved, padded):
        want = [_scatter_prefill(p, v, table, length, page_size)
                for p, v in zip(pools, vals)]
        both = write(pools, vals, table, jnp.int32(length), page_size)
        alone = write(pools[0], vals[0], table, jnp.int32(length), page_size)
        _same_but_scratch(both[0], want[0])
        _same_but_scratch(both[1], want[1])
        _same_but_scratch(alone, want[0])


# page_size 4, three pages a row: (positions, valid) per case
STEP_CASES = {
    "all_valid": ([0, 5, 10, 2, 7, 9], [1, 1, 1, 1, 1, 1]),
    "invalid_rows": ([0, 5, 10, 2, 7, 9], [1, 0, 1, 0, 1, 1]),
    "duplicate_scratch_writes": ([6, 6, 6, 1, 6, 6], [0, 0, 0, 1, 0, 0]),
    "last_slot_of_a_page": ([3, 7, 11, 3, 7, 11], [1, 1, 1, 1, 0, 1]),
    "warmup_all_invalid": ([0, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0]),
}


@pytest.mark.parametrize("positions,valid", STEP_CASES.values(),
                         ids=STEP_CASES.keys())
def test_write_step_equals_scatter_reference(positions, valid):
    page_size, B, P = 4, 6, 3
    rng = onp.random.default_rng(sum(positions))
    pools = _random_pools(rng, B * P + 3, page_size)
    vals = tuple(jnp.asarray(rng.standard_normal((3, B, 8)), jnp.float32)
                 for _ in pools)
    tables = rng.permutation(onp.arange(1, B * P + 3))[:B * P] \
        .reshape(B, P).astype(onp.int32)
    positions = onp.asarray(positions, onp.int32)
    valid = onp.asarray(valid, bool)
    write = jax.jit(write_step, static_argnums=5)
    want = [_scatter_step(p, v, tables, positions, valid, page_size)
            for p, v in zip(pools, vals)]
    both = write(pools, vals, tables, positions, valid, page_size)
    alone = write(pools[1], vals[1], tables, positions, valid, page_size)
    _same_but_scratch(both[0], want[0])
    _same_but_scratch(both[1], want[1])
    _same_but_scratch(alone, want[1])


# ---------------------------------------------------------------------------
# bucketing ladder (satellite 2)
# ---------------------------------------------------------------------------
def test_seq_buckets_ladder():
    assert bucketing.seq_buckets(64) == (16, 32, 64)
    assert bucketing.seq_buckets(100) == (16, 32, 64, 100)
    assert bucketing.seq_buckets(16) == (16,)
    assert bucketing.seq_buckets(8) == (8,)
    assert bucketing.seq_buckets(64, ladder=[8, 64]) == (8, 64)
    with pytest.raises(MXNetError):
        bucketing.seq_buckets(0)
    with pytest.raises(MXNetError):
        bucketing.seq_buckets(64, ladder=[8, 32])      # largest != max
    with pytest.raises(MXNetError):
        bucketing.seq_buckets(64, ladder=[32, 16, 64])  # not ascending


def test_bucket_for_edges():
    ladder = bucketing.seq_buckets(64)
    assert bucketing.bucket_for(1, ladder) == 16
    assert bucketing.bucket_for(16, ladder) == 16       # exact boundary
    assert bucketing.bucket_for(17, ladder) == 32
    assert bucketing.bucket_for(64, ladder) == 64
    with pytest.raises(MXNetError):
        bucketing.bucket_for(65, ladder)                # over-max rejected


# ---------------------------------------------------------------------------
# the paged pool
# ---------------------------------------------------------------------------
def test_pool_accounting_and_exhaustion():
    pool = PagedKVPool("acct", num_layers=1, kv_dim=4, max_seq_len=32,
                       page_size=8, num_pages=8)       # 7 usable pages
    assert pool.pages_per_seq == 4
    pool.reserve(1, 17)                  # ceil(17/8) = 3 pages
    assert pool.pages_in_use == 3
    pool.reserve(1, 17)                  # idempotent re-reserve
    assert pool.pages_in_use == 3
    pool.reserve(2, 32)                  # 4 more -> full
    assert pool.pages_in_use == 7
    with pytest.raises(KVPoolExhausted) as ei:
        pool.reserve(3, 9)
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    assert pool.free(1) == 3
    pool.reserve(3, 9)                   # freed pages immediately reusable
    assert pool.pages_in_use == 6
    # page 0 is never handed out
    assert 0 not in pool.table(2) or list(pool.table(2)).count(0) == 0
    with pytest.raises(MXNetError):
        pool.reserve(4, 33)              # beyond layout
    snap = pool.snapshot()
    assert snap["pages"] == 7 and snap["in_use"] == 6


def test_pool_rejects_undersized_layout():
    with pytest.raises(MXNetError):
        PagedKVPool("tiny", 1, 4, max_seq_len=64, page_size=8, num_pages=8)


# ---------------------------------------------------------------------------
# streaming: iterator, backpressure, cancel
# ---------------------------------------------------------------------------
def test_stream_backpressure_pauses_and_resumes(engine):
    sched = DecodeScheduler(engine, stream_buffer=2, poll_s=0.02).start()
    try:
        s = sched.submit([1, 2, 3], max_new_tokens=12)
        deadline = time.monotonic() + 30
        while engine.stats.snapshot()["counters"]["seq_paused"] < 1:
            assert time.monotonic() < deadline, "never paused"
            time.sleep(0.01)
        toks = []
        for t in s:                      # draining resumes the sequence
            toks.append(t)
        assert len(toks) == 12
        c = engine.stats.snapshot()["counters"]
        assert c["seq_resumed"] >= 1 and c["seq_finished"] >= 1
    finally:
        sched.stop()
    # backpressure must be lossless: same tokens as the serial oracle
    assert toks == _serial_decode(engine, [1, 2, 3], 12, 93000)


def test_stream_callback_and_cancel(engine):
    sched = DecodeScheduler(engine, poll_s=0.02).start()
    try:
        got = []
        s = sched.submit([5, 6], max_new_tokens=40, on_token=got.append)
        first = s.get(timeout=30)
        s.cancel()
        leftover = s.result(timeout=30)       # drains to close
        assert got[0] == first
        assert len(got) == 1 + len(leftover) < 40
        counters = engine.stats.snapshot()["counters"]
        assert counters["seq_cancelled"] >= 1
    finally:
        sched.stop()


def test_drain_finishes_inflight_and_refuses_new(engine):
    from mxnet_tpu.serving import ServerClosedError
    sched = DecodeScheduler(engine, poll_s=0.02).start()
    s = sched.submit([7, 8, 9], max_new_tokens=10)
    sched.stop(drain=True, timeout=60)
    assert s.result() == _serial_decode(engine, [7, 8, 9], 10, 94000)
    with pytest.raises(ServerClosedError):
        sched.submit([1], max_new_tokens=2)


def test_submit_validation(engine):
    sched = DecodeScheduler(engine, poll_s=0.02).start()
    try:
        with pytest.raises(MXNetError):
            sched.submit([], max_new_tokens=4)
        with pytest.raises(MXNetError):
            sched.submit([1] * 60, max_new_tokens=10)   # 70 > max_seq_len
        with pytest.raises(MXNetError):
            sched.submit([1], max_new_tokens=4, tenant="nope")
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# fault injection: stall-driven failover and pool exhaustion
# ---------------------------------------------------------------------------
def test_decode_failover_requeues_without_dup_or_drop(engine):
    oracle = [_serial_decode(engine, p, b, 95000 + i)
              for i, (p, b) in enumerate(zip(PROMPTS, BUDGETS))]
    sched = DecodeScheduler(engine, poll_s=0.02).start()
    try:
        with faults.inject("decode_stall", at=[5], times=1), \
                faults.inject("kv_exhausted", at=[2], times=1):
            streams = [sched.submit(p, max_new_tokens=b)
                       for p, b in zip(PROMPTS, BUDGETS)]
            results = [s.result(timeout=60) for s in streams]
        counters = engine.stats.snapshot()["counters"]
    finally:
        sched.stop()
    assert results == oracle             # no duplicated, no dropped tokens
    assert sched.failovers >= 1
    assert counters["seq_requeued"] >= 1
    assert sched.reports[-1]["reason"] == "worker_dead"


def test_server_facade_generate(engine):
    from mxnet_tpu import serving
    server = serving.InferenceServer()
    sched = server.register_generator(engine, warmup=False,
                                      tenants={"gold": 5.0})
    server.start()
    try:
        s = server.generate("tlm", [2, 4, 6], max_new_tokens=5,
                            tenant="gold")
        out = s.result(timeout=60)
        assert out == _serial_decode(engine, [2, 4, 6], 5, 96000)
        h = server.health()
        assert h["generators"]["tlm"]["state"] == "running"
        with pytest.raises(MXNetError):
            server.generate("nope", [1])
    finally:
        server.stop()
    assert sched.snapshot()["state"] == "stopped"
