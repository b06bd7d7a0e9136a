"""The readers of the program's own spans (chipbench/layer_metrics/
_program_spans.py and the four metrics on it): on hand-made ring entries, on
the recorded v5e decode trace with ``decode.*`` spans laid over its gaps, and
through the command's own entry point, rehearsed on the CPU."""
import itertools
import json
import os

import pytest

from chipbench import harness, xplane
from chipbench.layer_metrics import _program_spans

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
READERS = {m.NAME: m for m in harness.layer_metric_modules()}
DECODE = ["sched_host_ms_per_step.decode", "queue_wait_p95_ms.decode",
          "idle_outside_spans_pct.decode"]
TRAIN = "dispatch_host_ms.train"

# the ring's clock is perf_counter's: far from the trace's, which counts
# from the start of the profile session
OFFSET_NS = 7_000_123_456_789.0
_ids = itertools.count(1)


def entry(name, start_ns, end_ns, parent=None, **attrs):
    """One ``flight.recent_spans()`` entry, placed by its time on the
    trace's clock."""
    return {"name": name, "trace_id": "t", "span_id": f"s{next(_ids)}",
            "parent_id": parent and parent["span_id"],
            "t0_us": (start_ns - OFFSET_NS) / 1e3,
            "dur_us": (end_ns - start_ns) / 1e3, "attrs": attrs}


def iteration(launch_ns, device_ns, *, kind="step", wait_us=0,
              pre_ns=300e3, lag_ns=400e3, wake_ns=150e3, emit_ns=2e6,
              shift_launch_ns=0.0):
    """The ring entries of one pass that launches one executable ``lag_ns``
    before the chip starts it (at ``launch_ns + lag_ns``), is woken
    ``wake_ns`` after the chip ends it and then emits."""
    start = launch_ns - pre_ns
    done = launch_ns + lag_ns + device_ns + wake_ns
    it = entry("decode.iteration", start, done + emit_ns,
               admits=int(kind == "prefill"), rows=1)
    out = [it, entry("decode.admit", start, start + 50e3, it,
                     waiting=0, admitted=it["attrs"]["admits"])]
    if kind == "prefill":
        mid = entry("decode.prefill", start + 60e3, done + 100e3, it,
                    sid=1, prompt_len=96, bucket=128, queue_wait_us=wait_us)
    else:
        mid = entry("decode.step", start + 60e3, done, it, rows=1, bucket=1)
    launch = launch_ns + shift_launch_ns
    out += [mid,
            entry("decode.pack", start + 70e3, launch_ns, mid),
            entry("decode.launch", launch, launch + 200e3, mid, kind=kind,
                  bucket=1),
            entry("decode.fetch", launch + 200e3, done, mid, kind=kind),
            entry("decode.emit", done, done + emit_ns, it, tokens=1)]
    return out


@pytest.fixture
def ring(monkeypatch):
    """Puts entries where the readers look for them."""
    from mxnet_tpu.telemetry import flight
    entries = []
    monkeypatch.setattr(flight, "recent_spans", lambda: list(entries))
    return entries


# ---------------------------------------------------------------------------
# hand-made ring entries, no device trace (what a rehearsal has)
# ---------------------------------------------------------------------------
def test_a_program_without_the_spans_reports_none_of_them(ring):
    ring.append(entry("serving.batch", 0, 1e6))
    for name in DECODE + [TRAIN]:
        assert READERS[name].read({"trace": None}) is None


def test_decode_readers_on_hand_made_passes(ring):
    for i in range(12):
        # a pass of 0.3 + 0.4 + 40 + 0.15 + 2 ms of which 40.35 ms (the lag
        # after the launch's 0.2 ms, the device, the wake-up) are the fetch
        ring.extend(iteration(i * 50e6, 40e6, wait_us=1000 * (i + 1),
                              kind="prefill" if i % 2 else "step"))
    run = {"trace": None}
    # 42.85 ms less the fetch
    assert READERS["sched_host_ms_per_step.decode"].read(run) == \
        pytest.approx(42.85 - 40.35, abs=1e-6)
    assert READERS["queue_wait_p95_ms.decode"].read(run) is None   # 6 < 10
    assert READERS["idle_outside_spans_pct.decode"].read(run) is None
    for i in range(12, 40):
        ring.extend(iteration(i * 50e6, 40e6, wait_us=1000 * (i + 1),
                              kind="prefill"))
    waits = [i + 1 for i in range(40) if i % 2 or i >= 12]
    assert READERS["queue_wait_p95_ms.decode"].read(run) == \
        pytest.approx(sorted(waits)[int(0.95 * (len(waits) - 1))], abs=1.0)
    # a pass that found nothing to run is no step
    del ring[:]
    for i in range(12):
        for e in iteration(i * 50e6, 40e6):
            if e["name"] == "decode.iteration":
                e["attrs"]["rows"] = 0
            ring.append(e)
    assert READERS["sched_host_ms_per_step.decode"].read(run) is None


def test_dispatch_host_ms_leaves_the_traced_dispatches_out(ring):
    for i in range(11):
        ring.append(entry("train.step_n", i * 1e9, i * 1e9 + 20e6, steps=10))
    ring.append(entry("train.step", 0, 5e6))          # another span's name
    assert READERS[TRAIN].read({"trace": None}) == pytest.approx(20.0)
    # the recorded BERT trace holds two chipbench.dispatch spans: the ring's
    # last two calls ran under the profiler
    with open(os.path.join(FIXTURES, "trace_bert_base_v5e.json")) as f:
        trace = json.load(f)
    assert sum(s[0] == "chipbench.dispatch" for s in trace["spans"]) == 2
    for i in (11, 12):
        ring.append(entry("train.step_n", i * 1e9, i * 1e9 + 90e6, steps=10))
    assert READERS[TRAIN].read({"trace": trace}) == pytest.approx(20.0)
    del ring[-9:]                        # 4 left before the traced two
    assert READERS[TRAIN].read({"trace": trace}) is None


def test_what_ran_before_set_up_was_done_is_left_out(ring):
    """The ring's clock is perf_counter's, as ``chipbench.T0`` and the
    run's ``setup_s`` are: the warm-up dispatch, which compiles, and the
    first requests of a closed loop, which all wait, are not measured."""
    import chipbench
    done = (chipbench.T0 + 40.0) * 1e9 + OFFSET_NS     # set-up took 40 s
    ring.append(entry("train.step_n", done - 30e9, done - 1e9, steps=10))
    for i in range(6):
        ring.append(entry("train.step_n", done + i * 1e9,
                          done + i * 1e9 + 11e6, steps=10))
    calls = _program_spans.ring("train.step_n")
    assert len(_program_spans.measured({}, calls)) == 7
    assert len(_program_spans.measured({"setup_s": 40.0}, calls)) == 6
    assert READERS[TRAIN].read({"trace": None, "setup_s": 40.0}) == \
        pytest.approx(11.0)
    for i in range(30):
        ring.extend(iteration(done + (i - 10) * 50e6, 40e6, kind="prefill",
                              wait_us=900_000 if i < 10 else 50_000))
    name = "queue_wait_p95_ms.decode"
    assert READERS[name].read({"trace": None}) == pytest.approx(900.0)
    assert READERS[name].read({"trace": None, "setup_s": 40.0}) == \
        pytest.approx(50.0)


# ---------------------------------------------------------------------------
# the recorded decode trace, with the loop's spans laid over its gaps
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def decode_run():
    """The fixture as a run's ``trace``: its 59 modules, each also as the
    one op of its interval (the excerpt keeps only the window's first 400
    ops), so that the chip is idle exactly between modules."""
    with open(os.path.join(FIXTURES, "trace_gpt1_decode_v5e.json")) as f:
        raw = json.load(f)
    dev = raw["devices"]["0"]
    trace = {"devices": {0: {"modules": dev["modules"],
                             "ops": [list(m) for m in dev["modules"]]}},
             "spans": raw["spans"]}
    return {"trace": trace, "trace_summary": xplane.summary(trace)}


def lay_spans(ring, run, **how):
    """A pass per module of the trace, each emitting until the next one
    starts but for a turn of 10 us, and 30 passes before the window opened
    (profiler off): 80 ms each, 2.65 ms of them on the host."""
    modules = run["trace"]["devices"][0]["modules"]
    opened = run["trace_summary"]["window"][0]
    for i in range(30):
        ring.extend(iteration(opened - (31 - i) * 80e6, 76.3e6,
                              kind="prefill", wait_us=10_000 + i,
                              emit_ns=2.15e6))
    nxt = [m[1] for m in modules[1:]] + [None]
    for (name, start, dur), following in zip(modules, nxt):
        kind = "prefill" if name.startswith("jit_prefill") else "step"
        emit = 2e6 if following is None else \
            (following - 400e3 - 300e3) - (start + dur + 150e3) - 10e3
        ring.extend(iteration(start - 400e3, dur, kind=kind, wait_us=500_000,
                              emit_ns=emit, **how))


def test_the_clocks_join_on_the_recorded_trace(ring, decode_run):
    lay_spans(ring, decode_run)
    spans = _program_spans.ring("decode.")
    join = _program_spans.clock_join(decode_run, spans)
    assert join["modules"] == 59
    # launch 0.4 ms before each module, woken 0.15 ms after it: the offset
    # is known to that width and the truth lies inside it
    assert join["width_ns"] == pytest.approx(550e3, abs=1.0)
    assert abs(join["offset_ns"] - OFFSET_NS) <= join["width_ns"] / 2 + 1.0
    assert 0 <= join["median_lag_ns"] <= 5e6
    # profiler off: the 30 passes before the window, not the 59 inside it
    off = _program_spans.decode_profiler_off(decode_run)
    assert sum(s["name"] == "decode.iteration" for s in off) == 30
    assert READERS["sched_host_ms_per_step.decode"].read(decode_run) == \
        pytest.approx(0.3 + 0.2 + 2.15, abs=1e-3)
    assert READERS["queue_wait_p95_ms.decode"].read(decode_run) == \
        pytest.approx(10.0, abs=0.05)
    # every gap between modules lies under some pass but for the turn from
    # one pass to the next and the window's two ends
    pct = READERS["idle_outside_spans_pct.decode"].read(decode_run)
    outside, idle = _program_spans.idle_outside(decode_run, spans, join)
    assert idle / 1e9 == pytest.approx(
        decode_run["trace_summary"]["window_s"]
        - decode_run["trace_summary"]["busy_s"][0], rel=1e-3)
    assert pct == pytest.approx(100 * outside / idle) and 0 <= pct < 5


def test_spans_no_pass_covers_show_as_idle_outside(ring, decode_run):
    lay_spans(ring, decode_run)
    spans = [s for s in _program_spans.ring("decode.")]
    join = _program_spans.clock_join(decode_run, spans)
    launches_only = [s for s in spans if s["name"] == "decode.launch"]
    outside, idle = _program_spans.idle_outside(decode_run, launches_only,
                                                join)
    assert outside / idle > 0.9


@pytest.mark.parametrize("how", [
    {"shift_launch_ns": 50e6},       # launches 50 ms late against the chip
    {"wake_ns": 25e6},               # woken 25 ms late: pinned too loosely
])
def test_the_join_is_refused_where_launches_and_modules_do_not_meet(
        ring, decode_run, how):
    lay_spans(ring, decode_run, **how)
    spans = _program_spans.ring("decode.")
    assert _program_spans.clock_join(decode_run, spans) is None
    for name in DECODE:              # reported not at all, rather than wrong
        assert READERS[name].read(decode_run) is None


def test_no_join_without_enough_of_both_sides(ring, decode_run):
    assert _program_spans.clock_join({"trace": None}, []) is None
    assert _program_spans.clock_join(decode_run, []) is None
    lay_spans(ring, decode_run)
    del ring[-7 * 30:]               # fewer launches than the trace's modules
    assert _program_spans.clock_join(
        decode_run, _program_spans.ring("decode.")) is None


# ---------------------------------------------------------------------------
# one launch that the profiler's start stalls (PERF.md, PR 26)
# ---------------------------------------------------------------------------
def lay_stalled_window(ring, **how):
    """66 calls of the loop, prefills among steps as requests come (a
    prefill's call takes 12 ms under the profiler, a step's 41); calls 31 to
    62 are the
    traced window's 32 modules, and three follow while the trace is written.
    A module starts 8.6 to 9.4 ms after its launch began (the least lag is
    8.3 ms), but the window's first 60 ms after: ``start_trace`` stalled
    that launch, and that call took 114.2 ms. Returns the run."""
    kinds = [{"p": "prefill", "s": "step"}[c] for c in "pppppppppppp"
             "sspsssssssspspsppssssspspspsssspssssspssssspsppsssssps"]
    period = {"prefill": 12e6, "step": 41e6}
    device = {"prefill": 1.2e6, "step": 29e6}
    first, last = 31, 62
    lags = [(8.8e6, 9.4e6, 8.6e6, 9.0e6, 9.2e6)[i % 5]
            for i in range(len(kinds))]
    lags[first], lags[35] = 60e6, 8.3e6
    launch, modules = OFFSET_NS + 5e9, []
    for i, kind in enumerate(kinds):
        ring.extend(iteration(launch, device[kind], kind=kind,
                              lag_ns=lags[i], emit_ns=0.5e6, wait_us=20_000,
                              **how))
        if first <= i <= last:
            modules.append(["jit_decode" if kind == "step" else "jit_prefill",
                            launch + lags[i], device[kind]])
        launch += 114.2e6 if i == first else period[kind]
    trace = {"devices": {0: {"modules": modules,
                             "ops": [list(m) for m in modules]}},
             "spans": []}
    return {"trace": trace, "trace_summary": xplane.summary(trace)}


def test_one_stalled_launch_does_not_move_the_join_by_a_call(ring):
    run = lay_stalled_window(ring)
    spans = _program_spans.ring("decode.")
    calls = _program_spans._launches(spans)
    modules = sorted((m[1], m[1] + m[2])
                     for m in run["trace"]["devices"][0]["modules"])
    assert (len(calls), len(modules)) == (66, 32)

    def whole_spread(k):
        lags = [m[0] - c[0] for m, c in zip(modules, calls[k:k + 32])]
        return max(lags) - min(lags)

    # the case (PERF.md, PR 26): by the whole spread of lags the run one
    # call late reads smaller than the true one, and it leaves no interval
    assert whole_spread(31) == pytest.approx(51.7e6, abs=1.0)
    assert whole_spread(32) == pytest.approx(51.6e6, abs=1.0)
    assert min(range(35), key=whole_spread) == 32
    assert _program_spans._interval(modules, calls[32:64]) is None
    join = _program_spans.clock_join(run, spans)
    assert join["modules"] == 32
    # the least lag and the wake-up pin the offset: 8.3 + 0.15 ms wide
    assert join["width_ns"] == pytest.approx(8.45e6, abs=1.0)
    assert abs(join["offset_ns"] - OFFSET_NS) <= join["width_ns"] / 2 + 1.0
    assert 0 <= join["median_lag_ns"] <= _program_spans.MAX_LAG_NS
    for name in DECODE:                  # so all three are on the line
        assert READERS[name].read(run) is not None
    off = _program_spans.decode_profiler_off(run)
    assert sum(s["name"] == "decode.iteration" for s in off) == 31


@pytest.mark.parametrize("how", [{"shift_launch_ns": 50e6},
                                 {"wake_ns": 25e6}])
def test_a_stalled_window_is_still_refused_where_they_do_not_meet(ring, how):
    run = lay_stalled_window(ring, **how)
    assert _program_spans.clock_join(
        run, _program_spans.ring("decode.")) is None
    for name in DECODE:
        assert READERS[name].read(run) is None


# ---------------------------------------------------------------------------
# through the command
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell, reported", [
    ("gpt1.decode_chat", ["sched_host_ms_per_step.decode",
                          "queue_wait_p95_ms.decode"]),
    ("gpt1.decode_long", ["sched_host_ms_per_step.decode",
                          "queue_wait_p95_ms.decode"]),
    ("bert_base.pretrain_s128", [TRAIN]),
])
def test_a_traced_rehearsal_reports_the_profiler_off_metrics(
        capsys, monkeypatch, tmp_path, cell, reported):
    # the harness's one trace directory is test_cells.py's, in another worker
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    assert harness.main(["--workload", cell, "--seed", str(2**31 + 131),
                         "--seconds", "0.5", "--trace", "1",
                         "--rehearse"]) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    for name in reported:
        metric = last["metrics"][harness.REHEARSAL_PREFIX + name]
        assert metric["value"] > 0 and metric["unit"] == "ms"
    # no device trace on the CPU: nothing that needs the clocks joined
    assert not any("idle_outside" in name for name in last["metrics"])
