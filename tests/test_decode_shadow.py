"""The order of a decode pass (tier-1, JAX_PLATFORMS=cpu): the step is launched
first, waiting sequences are admitted and their prefills launched in its
shadow, and only then is anything waited for.

Most tests make the scheduler's passes themselves, on the test's thread
(``_driven``), so that what arrives, is cancelled or is drained between which
passes is the test's to say. The oracle is the order a pass had before: admit,
prefill and wait, step and wait, emit, from ``engine.prefill`` and
``engine.decode_step`` alone, on a second endpoint over the same weights.
"""
import threading

import numpy as onp
import pytest

import mxnet_tpu as mx
from chipbench.layer_metrics import _program_spans
from chipbench import harness
from mxnet_tpu import telemetry
from mxnet_tpu.gluon.model_zoo.bert import TransformerLM
from mxnet_tpu.gluon.model_zoo.moe_lm import MoEDecoderLM
from mxnet_tpu.serving.generate import DecodeEndpoint, DecodeScheduler
from mxnet_tpu.serving.generate import scheduler as sched_mod
from mxnet_tpu.telemetry import flight

LANES = 4


def _endpoint(name, lm):
    # one step bucket: a row's K/V is the same to the bit whatever batch it
    # ran in only at one executable (XLA:CPU rounds a row otherwise at
    # another batch size), and the two orders form other batches
    eng = DecodeEndpoint(name, lm, max_seq_len=64, max_batch_size=LANES,
                         decode_buckets=(LANES,), page_size=8, num_pages=64)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def lm():
    # the initializer draws from the package's key chain, whose place depends
    # on what ran before in this process: seeded here, so that the weights
    # (and whether greedy decoding tells contexts apart) do not
    mx.random.seed(0)
    lm = TransformerLM(num_layers=2, units=32, hidden_size=64, num_heads=2,
                       vocab_size=50, max_length=64)
    # wide, so that greedy arg-max depends on the cached context
    lm.initialize(mx.init.Normal(0.5))
    return lm


@pytest.fixture(scope="module")
def engine(lm):
    return _endpoint("shadow", lm)


@pytest.fixture(scope="module")
def twin(lm):
    """The oracle's endpoint: the same weights, a pool of its own."""
    return _endpoint("shadow_oracle", lm)


def _driven(engine, **kw):
    """A scheduler that runs no thread: the test calls ``_iteration(1)``."""
    sched = DecodeScheduler(engine, **kw)
    sched._state, sched._epoch = sched_mod._RUNNING, 1
    return sched


def _drain(stream):
    """Take what the stream holds, as a consumer that keeps up would."""
    while stream._dq:
        stream.get(timeout=0)


def _ring():
    return [e for e in flight.recent_spans()
            if e["name"].startswith("decode.")]


def _top(e, ids):
    while e["parent_id"] in ids:
        e = ids[e["parent_id"]]
    return e


class _Ask:
    """One request of a script: what is sent, when, and what its client
    does with the stream."""

    def __init__(self, prompt, budget, at=0, eos=None, cancel_after=None,
                 drain_from=0):
        self.prompt, self.budget, self.at = prompt, budget, at
        self.eos, self.cancel_after = eos, cancel_after
        self.drain_from = drain_from    # the first pass its client reads at
        self.heard, self.stream = [], None

    def on_token(self, tok):
        self.heard.append(tok)
        if len(self.heard) == self.cancel_after:
            self.stream.cancel()


def _pages(pool, sid, n):
    """The first ``n`` cached positions of ``sid``: (K, V), each
    (layers, n, kv)."""
    table = pool.table(sid)
    return tuple(onp.asarray(a)[:, table].reshape(a.shape[0], -1, a.shape[-1])
                 [:, :n].copy() for a in (pool.k_pool, pool.v_pool))


def _keep_pages_at_free(pool, kept, name_of):
    """Wrap ``pool.free`` so that a sequence's pages are copied out, whole,
    before they go back: ``kept[name_of(sid)]`` = (K, V)."""
    free = pool.free

    def keeping(sid):
        if sid in pool._tables:
            kept[name_of(sid)] = _pages(pool, sid, 64)
        return free(sid)
    pool.free = keeping
    return free


def _parent_order(eng, asks, passes):
    """What the order of a pass before this change serves: admit, prefill
    each admitted sequence and wait for it, step every running lane and wait
    for it, emit. From ``prefill`` and ``decode_step`` alone; the client
    always keeps up (a paused stream only delays, and rows are independent
    of the batch they ran in). Returns (tokens per ask, pages per ask)."""
    waiting, active, served, kept = [], [], {}, {}

    def emit(i, tok):
        a = asks[i]
        served[i].append(tok)
        if len(served[i]) == a.cancel_after or tok == a.eos \
                or len(served[i]) >= a.budget:
            n = len(a.prompt) + len(served[i]) - 1
            kept[i] = _pages(eng.pool, 7000 + i, n)
            eng.pool.free(7000 + i)
            active.remove(i)

    for p in range(passes):
        waiting += [i for i, a in enumerate(asks) if a.at == p]
        while waiting and len(active) < LANES:
            i = waiting.pop(0)
            served[i] = []
            eng.pool.reserve(7000 + i, len(asks[i].prompt) + asks[i].budget)
            active.append(i)
            emit(i, eng.prefill(asks[i].prompt, eng.pool.table(7000 + i)))
        lanes = list(active)
        if lanes:
            toks = eng.decode_step(
                [(served[i][-1], len(asks[i].prompt) + len(served[i]) - 1,
                  eng.pool.table(7000 + i)) for i in lanes])
            for i, tok in zip(lanes, toks):
                emit(i, tok)
    assert not waiting and not active
    return [served[i] for i in range(len(asks))], kept


def _shadow_order(engine, asks, passes, **kw):
    """The same script through the scheduler, pass by pass. Returns (tokens
    per ask, pages per ask as they stood when the sequence retired)."""
    flight.RECORDER.clear()
    sched = _driven(engine, **kw)
    kept, of_sid = {}, {}
    free = _keep_pages_at_free(engine.pool, kept, of_sid.get)
    try:
        for p in range(passes):
            for i, a in enumerate(asks):
                if a.at == p:
                    a.stream = sched.submit(
                        a.prompt, max_new_tokens=a.budget, eos_id=a.eos,
                        on_token=a.on_token)
                    of_sid[a.stream.sid] = i
            assert sched._iteration(1) != sched_mod._EXIT
            for a in asks:
                if a.stream is not None and p >= a.drain_from:
                    _drain(a.stream)
    finally:
        engine.pool.free = free
        sched.stop()
    n = [len(a.prompt) + len(a.heard) - 1 for a in asks]
    return [a.heard for a in asks], \
        {i: tuple(x[:, :n[i]] for x in kv) for i, kv in kept.items()}


def _script():
    # C meets its EOS, D's client hangs up after 3 tokens, E's client reads
    # nothing until pass 12 (buffer 4: paused from its 4th token on); six
    # asks on four lanes, so slots turn over and the later ones are admitted
    # while a step is in flight
    return [_Ask([1, 2, 3], 6), _Ask([4, 5], 9),
            _Ask([6, 7, 8, 9, 10], 7),
            _Ask([11], 8, at=2, cancel_after=3),
            _Ask([12, 13], 7, at=3, drain_from=12),
            _Ask([14, 15, 16, 17], 7, at=5), _Ask([18, 19], 3, at=6)]


# ---------------------------------------------------------------------------
# the order within a pass
# ---------------------------------------------------------------------------
def test_a_prefill_is_launched_between_the_steps_launch_and_its_fetch(engine):
    _shadow_order(engine, _script(), 24)
    spans = _ring()
    ids = {e["span_id"]: e for e in spans}
    calls = {}      # pass -> kind -> [(launch t0, fetch t0, fetch end)]
    fetch = {(e["parent_id"], e["attrs"]["kind"]): e for e in spans
             if e["name"] == "decode.fetch"}
    for e in spans:
        if e["name"] == "decode.launch":
            f = fetch[e["parent_id"], e["attrs"]["kind"]]
            calls.setdefault(_top(e, ids)["span_id"], {}).setdefault(
                e["attrs"]["kind"], []).append(
                    (e["t0_us"], f["t0_us"], f["t0_us"] + f["dur_us"]))
    shadowed = [c for c in calls.values() if len(c) == 2]
    assert len(shadowed) >= 3       # passes that stepped and prefilled
    for c in shadowed:
        (launched, fetching, fetched), = c["step"]
        for at, fetch_at, _ in c["prefill"]:
            assert launched < at < fetching     # in the step's shadow
            assert fetch_at >= fetched          # waited for after the step
        # back to back: every prefill is launched before any is waited for
        assert max(p[0] for p in c["prefill"]) < \
            min(p[1] for p in c["prefill"])


def test_a_pass_with_nothing_running_prefills_with_no_step_in_flight(engine):
    sched = _driven(engine)
    flight.RECORDER.clear()
    try:
        stream = sched.submit([1, 2, 3], max_new_tokens=3)
        assert sched._iteration(1) == sched_mod._AGAIN
        (it,) = [e for e in _ring() if e["name"] == "decode.iteration"]
        assert (it["attrs"]["rows"], it["attrs"]["admits"]) == (0, 1)
        (pre,) = [e for e in _ring() if e["name"] == "decode.prefill"]
        assert pre["attrs"]["overlapped"] == 0
        assert not [e for e in _ring() if e["name"] == "decode.step"]
        assert len(stream._dq) == 1         # its first token, in this pass
        assert sched._iteration(1) == sched_mod._AGAIN
        assert sched._iteration(1) == sched_mod._REST     # budget met
        assert len(stream.result(timeout=5)) == 3
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# what must not change: the tokens and the pool
# ---------------------------------------------------------------------------
def test_a_mixed_run_serves_the_parents_tokens_and_leaves_its_pages(
        engine, twin):
    want, want_pages = _parent_order(twin, _script(), 40)
    # the oracle must tell contexts apart, and the script must be what its
    # comment says: an EOS that cuts an answer short is set from the oracle
    assert any(len(set(t)) > 2 for t in want)
    asks, oracle_asks = _script(), _script()
    asks[2].eos = oracle_asks[2].eos = want[2][3]
    want, want_pages = _parent_order(twin, oracle_asks, 40)
    assert len(want[2]) <= 4 and len(want[3]) == 3

    base = engine.pool.pages_in_use
    got, got_pages = _shadow_order(engine, asks, 40, stream_buffer=4)
    assert got == want
    assert sorted(got_pages) == sorted(want_pages) == list(range(len(asks)))
    for i in want_pages:
        for mine, theirs in zip(got_pages[i], want_pages[i]):
            assert mine.shape == theirs.shape and mine.shape[1] > 0
            assert onp.array_equal(mine, theirs)        # bitwise
    assert engine.pool.pages_in_use == base
    counters = engine.stats.snapshot()["counters"]
    assert counters["seq_paused"] >= 1 and counters["seq_cancelled"] >= 1
    assert asks[4].stream.result(timeout=5) == []       # drained, closed


def test_a_token_reaches_its_stream_in_the_pass_that_fetched_it(engine):
    """Back-pressure, cancel and retirement keep their step: after each pass
    every running sequence has one more token, the cancelled one is retired
    in the pass its client hung up in, and its pages are free."""
    sched = _driven(engine)
    try:
        a = sched.submit([1, 2, 3], max_new_tokens=8)
        assert sched._iteration(1) == sched_mod._AGAIN     # prefill
        for n in range(2, 5):
            sched._iteration(1)
            assert len(a._dq) == n
        held = engine.pool.pages_in_use
        a.cancel()
        sched._iteration(1)             # the next step boundary
        assert a.closed is False and len(a._dq) == 5
        assert engine.pool.pages_in_use < held
        assert sched.snapshot()["running"] == 0
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# failures stay with their own
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("half", ["launch_prefill", "finish_prefill"])
def test_a_prefill_that_fails_in_the_shadow_fails_its_sequence_only(
        engine, twin, monkeypatch, half):
    good = [_Ask([1, 2, 3], 6), _Ask([4, 5], 5), _Ask([9, 8], 4, at=2)]
    want, _ = _parent_order(twin, good, 12)
    launch, real = engine.launch_prefill, getattr(engine, half)
    doomed, handles = [21, 22, 23], []

    def tagging(prompt, table):
        # the handle the doomed prompt's launch gave back
        call = launch(prompt, table)
        if list(prompt) == doomed:
            handles.append(call)
        return call

    def failing(*args):
        if list(args[0]) == doomed if half == "launch_prefill" \
                else args[0] in handles:
            raise RuntimeError("boom")
        return real(*args)
    monkeypatch.setattr(engine, "launch_prefill", tagging)
    monkeypatch.setattr(engine, half, failing)

    base = engine.pool.pages_in_use
    flight.RECORDER.clear()
    sched = _driven(engine)
    try:
        streams = [sched.submit(a.prompt, max_new_tokens=a.budget)
                   for a in good[:2]]
        sched._iteration(1)
        sched._iteration(1)
        bad = sched.submit(doomed, max_new_tokens=5)    # in a step's shadow
        streams.append(sched.submit(good[2].prompt,
                                    max_new_tokens=good[2].budget))
        for _ in range(10):
            sched._iteration(1)
        with pytest.raises(RuntimeError, match="boom"):
            bad.result(timeout=5)
        assert [s.result(timeout=5) for s in streams] == want
    finally:
        sched.stop()
    assert engine.pool.pages_in_use == base
    (pre,) = [e for e in _ring() if e["name"] == "decode.prefill"
              and e["attrs"]["prompt_len"] == 3 and e["attrs"]["sid"] == bad.sid]
    assert pre["attrs"].get("overlapped", 1) == 1


def test_an_error_at_the_steps_fetch_fails_the_prefills_behind_it(
        engine, monkeypatch):
    """They read and donated the pools that step returned."""
    sched = _driven(engine)
    finish = engine.finish_step

    def failing(call):
        finish(call)
        raise RuntimeError("the chip's")
    base = engine.pool.pages_in_use
    try:
        running = sched.submit([1, 2, 3], max_new_tokens=6)
        sched._iteration(1)
        behind = sched.submit([4, 5], max_new_tokens=6)
        monkeypatch.setattr(engine, "finish_step", failing)
        assert sched._iteration(1) == sched_mod._REST
        monkeypatch.setattr(engine, "finish_step", finish)
        for stream in (running, behind):
            with pytest.raises(RuntimeError, match="the chip's"):
                stream.result(timeout=5)
        assert engine.pool.pages_in_use == base
        # the loop goes on
        after = sched.submit([7, 8], max_new_tokens=3)
        for _ in range(3):
            sched._iteration(1)
        assert len(after.result(timeout=5)) == 3
    finally:
        sched.stop()


@pytest.mark.parametrize("bump", ["launch_step", "finish_step"])
def test_a_generation_fenced_out_between_launch_and_finish_emits_nothing(
        engine, twin, monkeypatch, bump):
    """A failover while a pass is under way (the monitor's own: requeue, a
    new epoch, a new worker on its own thread): the pass that was fenced out
    emits nothing at its next lock take, and the generation that owns the
    sequences now serves each token once."""
    asks = [_Ask([1, 2, 3], 6), _Ask([4, 5], 6, at=1)]
    want, _ = _parent_order(twin, asks, 12)
    sched = _driven(engine)
    real = getattr(engine, bump)
    heard = [[], []]

    def fenced(arg):
        out = real(arg)
        monkeypatch.setattr(engine, bump, real)
        dead = threading.Thread(target=lambda: None)
        dead.start()
        dead.join()
        sched._thread = dead
        sched._check_worker()
        return out
    try:
        running = sched.submit([1, 2, 3], max_new_tokens=6,
                               on_token=heard[0].append)
        sched._iteration(1)
        waiting = sched.submit([4, 5], max_new_tokens=6,
                               on_token=heard[1].append)
        monkeypatch.setattr(engine, bump, fenced)
        assert sched._iteration(1) == sched_mod._EXIT
        assert sched.failovers == 1 and sched.snapshot()["epoch"] == 2
        assert [running.result(timeout=30), waiting.result(timeout=30)] \
            == want
        # each token once: one the fenced pass emitted would be here twice
        assert heard == want
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# generation by blocks
# ---------------------------------------------------------------------------
def test_blocks_of_four_place_the_same_tokens_in_the_shadow(engine):
    """Lanes admitted while a step is in flight, at other phases of their
    blocks than the lanes already running, against the same requests served
    one at a time (each admitted with nothing in flight): the same ids, the
    same step and the same confidence for each, to the bit."""
    L, MASK = 4, 95
    lm = MoEDecoderLM(num_layers=2, units=64, num_heads=4, num_kv_heads=2,
                      head_dim=16, expert_hidden=32, num_experts=8,
                      experts_per_token=2, vocab_size=96, block_length=L,
                      mask_token_id=MASK)
    lm.initialize(mx.init.DeviceNormal(0.05, seed=3,
                                       scales={"head_weight": 10}))
    lm.hybridize()
    eng = DecodeEndpoint("shadow_blocks", lm, max_seq_len=64,
                         max_batch_size=4, num_pages=17)
    eng.warmup()
    rng = onp.random.default_rng(5)
    asks = [([int(t) for t in rng.integers(0, MASK, n)], new, at)
            for n, new, at in ((9, 9, 0), (16, 12, 2), (3, 7, 3), (6, 6, 5))]

    def serve(together):
        sched = _driven(eng)
        streams = []
        try:
            if together:
                for p in range(40):
                    streams += [sched.submit(prompt, max_new_tokens=new,
                                             denoising_steps=2)
                                for prompt, new, at in asks if at == p]
                    sched._iteration(1)
            else:
                for prompt, new, _ in asks:
                    streams.append(sched.submit(prompt, max_new_tokens=new,
                                                denoising_steps=2))
                    while sched._iteration(1) == sched_mod._AGAIN:
                        pass
            return [(s.result(timeout=5), s.steps, s.confidences)
                    for s in streams]
        finally:
            sched.stop()
    was = dict(eng.stats.counters)
    alone = serve(together=False)
    assert eng.stats.counters["prefills_overlapped"] \
        == was["prefills_overlapped"]
    together = serve(together=True)
    assert eng.stats.counters["prefills_overlapped"] \
        - was["prefills_overlapped"] == 2     # 16 and 6: whole blocks to fill
    assert together == alone
    for (toks, steps, sure), (_, new, _) in zip(together, asks):
        assert len(toks) == len(steps) == len(sure) == new
        assert MASK not in toks and set(steps) <= {0, 1}


# ---------------------------------------------------------------------------
# the counters that say how often the order engages
# ---------------------------------------------------------------------------
def test_overlapped_prefills_and_the_steps_fetch_wait_are_counted(engine):
    was = dict(engine.stats.counters)
    asks = _script()
    _shadow_order(engine, asks, 40)
    now = engine.stats.snapshot()["counters"]
    spans = _ring()
    prefills = [e["attrs"] for e in spans if e["name"] == "decode.prefill"]
    steps = [e["attrs"] for e in spans if e["name"] == "decode.step"]
    assert now["prefills"] - was["prefills"] == len(prefills) == len(asks)
    # the first three arrive with nothing running; the rest meet a step
    assert [a["overlapped"] for a in prefills] == [0, 0, 0, 1, 1, 1, 1]
    assert now["prefills_overlapped"] - was["prefills_overlapped"] == 4
    assert now["steps"] - was["steps"] == len(steps)
    waits = [a["fetch_wait_us"] for a in steps]
    assert all(isinstance(w, int) and w >= 0 for w in waits)
    assert now["step_fetch_wait_us"] - was["step_fetch_wait_us"] == sum(waits)
    # the wait is inside what the step's cost observes: launch to result
    fetches = {e["parent_id"]: e["dur_us"] for e in spans
               if e["name"] == "decode.fetch"}
    for e in spans:
        if e["name"] == "decode.step":
            assert e["attrs"]["fetch_wait_us"] == fetches[e["span_id"]] \
                <= e["dur_us"]


# ---------------------------------------------------------------------------
# what the benchmark's readers rest on
# ---------------------------------------------------------------------------
def test_the_ring_keeps_the_readers_invariants(engine):
    """One ``decode.launch`` and one ``decode.fetch`` a parent, paired by
    ``_program_spans._launches`` in the order of the launches, which is the
    chip's; every fetch under the ``decode.iteration`` that launched it."""
    _shadow_order(engine, _script(), 40)
    spans = _program_spans.ring("decode.")
    launches = [s for s in spans if s["name"] == "decode.launch"]
    fetches = [s for s in spans if s["name"] == "decode.fetch"]
    assert len(launches) == len(fetches) > 15
    for group in (launches, fetches):       # one of each a parent
        assert len({s["parent_id"] for s in group}) == len(group)
    pairs = _program_spans._launches(spans)
    assert len(pairs) == len(launches)      # every launch has its fetch
    assert [p[0] for p in pairs] == sorted(s["start"] for s in launches)
    assert all(start < end for start, end in pairs)
    by_parent = {s["parent_id"]: s for s in fetches}
    ids = {s["span_id"]: s for s in spans}
    for s in launches:
        f = by_parent[s["parent_id"]]
        assert f["attrs"]["kind"] == s["attrs"]["kind"]
        assert f["start"] >= s["end"]
        top = _top(f, ids)
        assert top["name"] == "decode.iteration"
        assert top is _top(s, ids)
        assert top["start"] <= s["start"] and f["end"] <= top["end"]
    # the attrs the phase readers rest on
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert all(a["attrs"]["fetch_wait_us"] >= 0
               for a in by_name["decode.step"])
    for s in by_name["decode.prefill"]:
        assert 0 < s["attrs"]["tokens"] <= s["attrs"]["bucket"]
        assert s["attrs"]["overlapped"] in (0, 1)
    assert {s["attrs"]["kind"] for s in launches} == {"step", "prefill"}
    assert all(s["attrs"]["ready"] in (0, 1) for s in fetches)
    assert by_name["decode.emit"] and all(
        _top(s, ids)["name"] == "decode.iteration"
        for s in by_name["decode.emit"])
    # and the reader that walks up from the fetches reads a pass
    readers = {m.NAME: m for m in harness.layer_metric_modules()}
    host = readers["sched_host_ms_per_step.decode"].read({"trace": None})
    stepped = [s for s in spans if s["name"] == "decode.iteration"
               and s["attrs"]["rows"]]
    assert 0 < host < max(s["end"] - s["start"] for s in stepped) / 1e6


def test_a_span_takes_the_parent_it_is_given():
    with telemetry.span("test.launching") as first:
        pass
    with telemetry.span("test.around") as around:
        with telemetry.span("test.late", parent=first, n=1) as late:
            assert telemetry.current_span() is late
            with telemetry.span("test.below") as below:
                pass
        assert telemetry.current_span() is around
        with telemetry.span("test.named", parent=first,
                            trace_id="feedfacefeedface") as named:
            pass
    assert late.parent_id == first.span_id != around.span_id
    assert late.trace_id == first.trace_id != around.trace_id
    assert (below.parent_id, below.trace_id) == (late.span_id, late.trace_id)
    assert (named.parent_id, named.trace_id) == (first.span_id,
                                                 "feedfacefeedface")
    assert telemetry.current_span() is None


# ---------------------------------------------------------------------------
# the endpoint's halves
# ---------------------------------------------------------------------------
def test_a_prefill_launched_behind_a_step_reads_the_pools_it_returned(
        engine, twin):
    """``launch_step`` installs the pools its call returned at once; the
    prefill launched before ``finish_step`` chains on them. Against the two
    calls made one after the other on the twin: the same tokens, the same
    pages, and the compositions keep their results."""
    out = {}
    for eng, shadowed in ((engine, True), (twin, False)):
        eng.pool.reserve(501, 12)
        eng.pool.reserve(502, 12)
        first = eng.prefill([1, 2, 3], eng.pool.table(501))
        rows = [(first, 3, eng.pool.table(501))]
        if shadowed:
            step = eng.launch_step(rows)
            assert eng.pool.k_pool is not None
            pre = eng.launch_prefill([4, 5, 6, 7], eng.pool.table(502))
            assert pre.overlapped and pre.bucket in eng.prefill_buckets
            (tok,) = eng.finish_step(step)
            second = eng.finish_prefill(pre)
        else:
            (tok,) = eng.decode_step(rows)
            second = eng.prefill([4, 5, 6, 7], eng.pool.table(502))
        assert eng.last_step["fetch_wait_us"] >= 0
        out[shadowed] = (first, tok, second, _pages(eng.pool, 501, 4),
                         _pages(eng.pool, 502, 4))
        eng.pool.free(501)
        eng.pool.free(502)
    mine, theirs = out[True], out[False]
    assert mine[:3] == theirs[:3]
    for a, b in zip(mine[3] + mine[4], theirs[3] + theirs[4]):
        assert onp.array_equal(a, b)
    # a prefill with no step in flight is not counted as overlapped
    engine.pool.reserve(503, 8)
    call = engine.launch_prefill([1, 2], engine.pool.table(503))
    assert not call.overlapped
    engine.finish_prefill(call)
    engine.pool.free(503)


def test_the_loops_own_thread_serves_the_same_tokens(engine, twin):
    """The same asks through a started scheduler (its own thread, arrivals
    when they come): the tokens of the parent's order."""
    asks = [a for a in _script() if a.cancel_after is None]
    for a in asks:
        a.drain_from = 0
    want, _ = _parent_order(twin, [_Ask(a.prompt, a.budget) for a in asks], 40)
    sched = DecodeScheduler(engine, poll_s=0.02).start()
    try:
        streams = []
        for i, a in enumerate(asks):
            streams.append(sched.submit(a.prompt, max_new_tokens=a.budget))
            if i == 2:
                threading.Event().wait(0.05)    # the rest join a running batch
        assert [s.result(timeout=60) for s in streams] == want
    finally:
        sched.stop()
