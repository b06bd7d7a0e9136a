"""Spans and counts inside the decode scheduler (tier-1, JAX_PLATFORMS=cpu):
a tiny TransformerLM through DecodeEndpoint + DecodeScheduler, read back
from the flight recorder's span ring."""
import contextlib
import glob
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.gluon.model_zoo.bert import TransformerLM
from mxnet_tpu.resilience import faults
from mxnet_tpu.serving.generate import DecodeEndpoint, DecodeScheduler
from mxnet_tpu.telemetry import flight, tracing

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9, 10], [11], [12, 13],
           [14, 15, 16, 17]]
BUDGETS = [6, 9, 4, 8, 5, 7]

# what each span of the table may have directly under it
CHILDREN = {
    "decode.iteration": {"decode.admit", "decode.prefill", "decode.build",
                         "decode.step", "decode.emit"},
    "decode.prefill": {"decode.pack", "decode.launch", "decode.fetch",
                       "decode.emit"},
    "decode.step": {"decode.pack", "decode.launch", "decode.fetch"},
}


@pytest.fixture(scope="module")
def engine():
    onp.random.seed(0)
    lm = TransformerLM(num_layers=2, units=32, hidden_size=64, num_heads=2,
                       vocab_size=50, max_length=64)
    lm.initialize(mx.init.Normal(0.5))
    eng = DecodeEndpoint("tlm_traced", lm, max_seq_len=64, max_batch_size=4,
                         page_size=8, num_pages=64)
    eng.warmup()
    return eng


def _generate(engine, prompts, budgets, fault=contextlib.nullcontext()):
    """Run the requests through a fresh scheduler, each submitted under a
    span of the caller's own. Returns (tokens per request, the caller's
    span per request, seconds to the first token per request, the ring's
    ``decode.*`` entries, the stats snapshot, the scheduler)."""
    flight.RECORDER.clear()
    first, t0, mine = {}, {}, []
    sched = DecodeScheduler(engine, poll_s=0.02).start()
    try:
        with fault:
            streams = []
            for i, (p, b) in enumerate(zip(prompts, budgets)):
                with telemetry.span("test.client", request=i) as s:
                    t0[i] = time.perf_counter()
                    streams.append(sched.submit(
                        p, max_new_tokens=b, on_token=lambda tok, i=i:
                        first.setdefault(i, time.perf_counter())))
                mine.append(s)
            results = [s.result(timeout=60) for s in streams]
    finally:
        sched.stop()
    ttft_s = [first[i] - t0[i] for i in range(len(prompts))]
    spans = [e for e in flight.recent_spans()
             if e["name"].startswith("decode.")]
    return results, mine, ttft_s, spans, engine.stats.snapshot(), sched


def _by_parent(spans):
    kids = {}
    for e in spans:
        kids.setdefault(e["parent_id"], []).append(e)
    return kids


def test_every_iteration_has_the_tables_children_and_is_the_only_root(engine):
    results, _, _, spans, _, _ = _generate(engine, PROMPTS, BUDGETS)
    assert [len(r) for r in results] == BUDGETS
    kids = _by_parent(spans)
    # the loop's thread opens nothing else: every decode.* span without a
    # parent is an iteration, every other one hangs under the table's parent
    assert {e["name"] for e in kids[None]} == {"decode.iteration"}
    ids = {e["span_id"]: e for e in spans}
    for e in spans:
        if e["parent_id"] is not None:
            assert e["name"] in CHILDREN[ids[e["parent_id"]]["name"]], e
    prefills = 0
    for it in kids[None]:
        names = [c["name"] for c in kids.get(it["span_id"], [])]
        assert names.count("decode.admit") == 1
        assert names.count("decode.build") == 1
        assert names.count("decode.prefill") == it["attrs"]["admits"]
        stepped = it["attrs"]["rows"] > 0
        assert names.count("decode.step") == int(stepped)
        assert names.count("decode.emit") == int(stepped)
        prefills += it["attrs"]["admits"]
        for c in kids[it["span_id"]]:
            below = [g["name"] for g in kids.get(c["span_id"], [])]
            if c["name"] == "decode.step":
                assert below == ["decode.pack", "decode.launch",
                                 "decode.fetch"]
                assert c["attrs"]["rows"] == it["attrs"]["rows"]
                assert c["attrs"]["bucket"] in engine.decode_buckets
            elif c["name"] == "decode.prefill":
                assert below == ["decode.pack", "decode.launch",
                                 "decode.fetch", "decode.emit"]
                assert c["attrs"]["bucket"] in engine.prefill_buckets
            elif c["name"] == "decode.emit":
                assert c["attrs"]["tokens"] == it["attrs"]["rows"]
            else:
                assert below == []
    assert prefills == len(PROMPTS)
    kinds = {(e["name"], e["attrs"].get("kind")) for e in spans
             if e["name"] in ("decode.launch", "decode.fetch")}
    assert kinds == {(n, k) for n in ("decode.launch", "decode.fetch")
                     for k in ("prefill", "step")}
    # every token went out under an emit span
    assert sum(e["attrs"]["tokens"] for e in spans
               if e["name"] == "decode.emit") == sum(BUDGETS)


def test_prefill_carries_the_submitters_trace_and_its_queue_wait(engine):
    _, mine, ttft_s, spans, _, _ = _generate(engine, PROMPTS, BUDGETS)
    prefill = {e["trace_id"]: e for e in spans
               if e["name"] == "decode.prefill"}
    assert len(prefill) == len(PROMPTS)
    for i, client in enumerate(mine):
        e = prefill[client.trace_id]        # the span submit() ran under
        assert e["attrs"]["prompt_len"] == len(PROMPTS[i])
        assert 0 <= e["attrs"]["queue_wait_us"] <= ttft_s[i] * 1e6
        # what runs under the prefill stays in the request's trace, the
        # iteration around it in the loop's own
        below = [c for c in spans if c["parent_id"] == e["span_id"]]
        assert below and {c["trace_id"] for c in below} == {client.trace_id}
        parent = next(p for p in spans if p["span_id"] == e["parent_id"])
        assert parent["name"] == "decode.iteration"
        assert parent["trace_id"] != client.trace_id


def test_stats_count_queue_wait_and_ttft_once_per_request(engine):
    before = engine.stats.snapshot()
    snap = _generate(engine, PROMPTS, BUDGETS)[4]
    for key in ("queue_wait", "ttft"):
        assert snap[key]["count"] - before[key]["count"] == len(PROMPTS)
    assert snap["counters"]["seq_finished"] \
        - before["counters"]["seq_finished"] == len(PROMPTS)
    assert 0 < snap["queue_wait"]["max_us"] <= snap["ttft"]["max_us"]
    text = telemetry.prometheus_text()
    for family in ("mxtpu_decode_queue_wait_us", "mxtpu_decode_ttft_us"):
        assert f'{family}_count{{endpoint="tlm_traced"}}' in text


def test_a_step_counts_the_context_its_lanes_attend_to(engine):
    """``decode.step`` carries ``ctx_live``, the cached positions its lanes
    attended to, and ``ctx_capacity``, what their lanes could hold; the
    endpoint's stats sum both. A request of p prompt tokens and b new ones
    takes b - 1 steps, at p, p + 1, ... cached positions."""
    before = dict(engine.stats.counters)
    _, _, _, spans, snap, _ = _generate(engine, PROMPTS, BUDGETS)
    steps = [e["attrs"] for e in spans if e["name"] == "decode.step"]
    live = sum(len(p) + j for p, b in zip(PROMPTS, BUDGETS)
               for j in range(b - 1))
    assert sum(a["ctx_live"] for a in steps) == live
    assert all(a["ctx_capacity"] == a["rows"] * engine.max_seq_len
               and 0 < a["ctx_live"] < a["ctx_capacity"] for a in steps)
    capacity = sum(b - 1 for b in BUDGETS) * engine.max_seq_len
    assert snap["counters"]["ctx_live"] - before["ctx_live"] == live
    assert snap["counters"]["ctx_capacity"] - before["ctx_capacity"] \
        == capacity
    assert snap["ctx_live_share"] == pytest.approx(
        snap["counters"]["ctx_live"] / snap["counters"]["ctx_capacity"])


def test_the_live_share_is_one_when_every_lane_is_full():
    from mxnet_tpu.serving.generate import DecodeStats
    stats = DecodeStats("tlm_full")
    assert stats.snapshot()["ctx_live_share"] == 0.0    # no step yet
    for lanes in (4, 3):
        stats.record_step(100.0, lanes, 4, ctx=(lanes * 64, lanes * 64))
    snap = stats.snapshot()
    assert snap["counters"]["ctx_live"] == 7 * 64
    assert snap["counters"]["ctx_capacity"] == 7 * 64
    assert snap["ctx_live_share"] == 1.0


def test_failover_leaves_no_span_open_and_emits_nothing_twice(engine):
    clean = _generate(engine, PROMPTS, BUDGETS)[0]
    before = engine.stats.snapshot()
    results, _, _, spans, snap, sched = _generate(
        engine, PROMPTS, BUDGETS,
        fault=faults.inject("decode_stall", at=[5], times=1))
    assert sched.failovers >= 1
    assert results == clean              # no token twice, none dropped
    # the pass that died closed its spans on the way out: every parent a
    # span names is itself in the ring, finished
    ids = {e["span_id"] for e in spans}
    assert all(e["dur_us"] is not None for e in spans)
    assert all(e["parent_id"] in ids for e in spans if e["parent_id"])
    assert telemetry.current_span() is None
    # a token is emitted under exactly one emit span, requeue or not
    assert sum(e["attrs"]["tokens"] for e in spans
               if e["name"] == "decode.emit") == sum(BUDGETS)
    assert sum(1 for e in spans if e["name"] == "decode.prefill") \
        == len(PROMPTS)
    for key in ("queue_wait", "ttft"):      # a requeue is not a new request
        assert snap[key]["count"] - before[key]["count"] == len(PROMPTS)


def test_a_span_keeps_the_cpu_time_of_its_thread():
    """``cpu_us`` beside ``dur_us`` on a span that asks for it: a span that
    sleeps has next to none of its wall time, a span that spins until its
    thread has used 20 ms of the processor has those and, however loaded the
    host, a far larger share (relative: no speed is held to a number); a
    span that does not ask reads no clock and says None; the ring's entries
    and a flight bundle carry the field."""
    flight.RECORDER.clear()
    with telemetry.span("test.sleeps", cpu=True) as sleeps:
        assert sleeps.cpu_us is None and sleeps.dur_us is None
        time.sleep(0.05)
    with telemetry.span("test.spins", cpu=True, n=1) as spins:
        until = time.thread_time_ns() + 20_000_000
        while time.thread_time_ns() < until:
            pass
    assert 0 <= sleeps.cpu_us < 0.2 * sleeps.dur_us
    assert 20_000 <= spins.cpu_us <= spins.dur_us + 1000
    assert spins.cpu_us / spins.dur_us > 4 * sleeps.cpu_us / sleeps.dur_us
    # a span given a parent exits on the thread that opened it: the field
    # is that thread's
    with telemetry.span("test.late", parent=sleeps, cpu=True) as late:
        with telemetry.span("test.unasked") as unasked:
            pass
    assert 0 <= late.cpu_us <= late.dur_us + 1000
    assert unasked.cpu_us is None and unasked.dur_us is not None
    assert spins.attrs == {"n": 1}              # ``cpu`` is no attr
    ring = {e["name"]: e for e in flight.recent_spans()}
    assert ring["test.sleeps"]["cpu_us"] == sleeps.cpu_us
    assert ring["test.spins"]["cpu_us"] == spins.cpu_us
    assert ring["test.unasked"]["cpu_us"] is None
    bundle = {e["name"]: e for e in flight.RECORDER.bundle()["spans"]}
    assert bundle["test.spins"]["cpu_us"] == spins.cpu_us
    assert bundle["test.late"]["parent_id"] == sleeps.span_id


def test_every_span_of_a_decode_loop_has_its_cpu_time(engine):
    """Under a real loop every ``decode.*`` span carries ``cpu_us``: the
    passes took it, and it lies in their ``dur_us`` (the two clocks count
    whole microseconds apart: 1 ms of room); the others read no clock and
    say None. Every fetch says whether its result was ``ready``, and the
    ``fetch_wait_us`` of the ring's steps add up to the endpoint's counter."""
    before = engine.stats.snapshot()["counters"]["step_fetch_wait_us"]
    _, _, _, spans, snap, _ = _generate(engine, PROMPTS, BUDGETS)
    assert len(spans) > 50
    fetches = [e for e in spans if e["name"] == "decode.fetch"]
    assert fetches and all(e["attrs"]["ready"] in (0, 1) for e in fetches)
    for e in spans:
        if e["name"] == "decode.iteration":
            assert 0 <= e["cpu_us"] <= e["dur_us"] + 1000, e
        else:
            assert e["cpu_us"] is None, e
    steps = [e for e in spans if e["name"] == "decode.step"]
    assert sum(e["attrs"]["fetch_wait_us"] for e in steps) == \
        snap["counters"]["step_fetch_wait_us"] - before
    # a span's id is a string, one a span of the process
    assert len({e["span_id"] for e in spans}) == len(spans)
    assert all(isinstance(e["span_id"], str) for e in spans)


def test_self_times_on_a_hand_built_tree():
    def entry(sid, parent, t0, dur):
        return {"name": sid, "span_id": sid, "parent_id": parent,
                "trace_id": "t", "t0_us": t0, "dur_us": dur, "attrs": {}}
    spans = [entry("root", None, 100, 1000),
             entry("a", "root", 150, 200),        # 150-350
             entry("b", "root", 300, 200),        # 300-500, overlaps a
             entry("c", "root", 900, 400),        # 900-1300, runs past root
             entry("a1", "a", 160, 50),
             entry("orphan", "gone", 0, 70)]
    assert tracing.self_times(spans) == {
        "root": 1000 - (350 + 200), "a": 150, "b": 200, "c": 400,
        "a1": 50, "orphan": 70}
    assert tracing.self_times([]) == {}


def test_a_span_under_jax_profiler_lies_in_the_xplanes_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("decode.iteration", admits=0, rows=0) as s:
            with telemetry.span("decode.launch", kind="step", bucket=1):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("decode."):
                    found[e.name] = (e.start_ns, e.duration_ns,
                                     dict(e.stats))
    assert set(found) == {"decode.iteration", "decode.launch"}
    start, dur, stats = found["decode.iteration"]
    assert stats["trace_id"] == s.trace_id and stats["span_id"] == s.span_id
    inner = found["decode.launch"]
    assert start <= inner[0] and inner[0] + inner[1] <= start + dur
    # the annotation is held inside the span (whose clock counts whole us)
    assert 2e6 <= inner[1] <= dur <= (s.dur_us + 1) * 1e3
