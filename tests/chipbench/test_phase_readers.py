"""The six readers that take a decode pass apart (chipbench/layer_metrics/:
``step_fetch_wait_ms``, ``launch_host_ms``, ``emit_host_ms_per_step``,
``loop_off_cpu_ms_per_step``, ``prefill_padding_pct``,
``prefill_overlap_pct``, all ``.decode``): on hand-made passes whose answers
are known, on spans that carry no ``cpu_us``, under ``MIN_SPANS``, on an
empty ring, and through the command's own entry point, rehearsed on the CPU."""
import itertools
import json
import os

import pytest

from chipbench import harness
from chipbench.layer_metrics import _program_spans

READERS = {m.NAME: m for m in harness.layer_metric_modules()}
PHASES = ["step_fetch_wait_ms.decode", "launch_host_ms.decode",
          "emit_host_ms_per_step.decode", "loop_off_cpu_ms_per_step.decode",
          "prefill_padding_pct.decode", "prefill_overlap_pct.decode"]
RUN = {"trace": None}                   # what a rehearsal has: no device trace
_ids = itertools.count(1)


def entry(name, t0_us, dur_us, parent=None, cpu_us=None, **attrs):
    """One ``flight.recent_spans()`` entry; ``cpu_us`` None is a span that
    did not take its thread's CPU time."""
    return {"name": name, "trace_id": "t", "span_id": f"p{next(_ids)}",
            "parent_id": parent and parent["span_id"], "t0_us": t0_us,
            "dur_us": dur_us, "cpu_us": cpu_us, "attrs": attrs}


def a_pass(i, *, cpu=True, rows=4, overlapped=1, wait_us=3_000,
           it_cpu_us=4_000, fetch_cpu_us=100):
    """The ring entries of pass ``i``, 10 ms long, of which its thread had
    the processor for 4: it launches a step (1.2 ms), admits one prompt of
    96 rows at the 128 rung and launches its prefill (1.0 ms), waits 2.9 of
    the step's 3.0 ms fetch off the processor (its result was not there),
    emits for 2.0 ms, takes 1.0 ms to fetch the prefill's result although it
    was there, and emits its first token for 0.5. With ``cpu`` the pass and
    the fetch that waited for the chip carry ``cpu_us``, the other spans
    none; the program's passes carry it and its fetches do not
    (``fetch_cpu_us`` None)."""
    t = 1_000_000 + i * 50_000
    it = entry("decode.iteration", t, 10_000,
               cpu_us=it_cpu_us if cpu else None, admits=1, rows=rows)
    step = entry("decode.step", t + 200, 5_800, it, rows=rows, bucket=4,
                 fetch_wait_us=wait_us)
    prefill = entry("decode.prefill", t + 1_600, 1_200, it, sid=i,
                    prompt_len=96, tokens=96, bucket=128, queue_wait_us=500,
                    overlapped=overlapped)
    return [
        it,
        entry("decode.build", t, 200, it, rows=rows),
        step,
        entry("decode.pack", t + 210, 90, step),
        entry("decode.launch", t + 300, 1_200, step, kind="step", bucket=4),
        entry("decode.admit", t + 1_500, 100, it, waiting=1, admitted=1),
        prefill,
        entry("decode.pack", t + 1_650, 50, prefill),
        entry("decode.launch", t + 1_700, 1_000, prefill, kind="prefill",
              bucket=128),
        entry("decode.fetch", t + 3_000, 3_000, step,
              cpu_us=fetch_cpu_us if cpu else None, kind="step", ready=0),
        entry("decode.emit", t + 6_000, 2_000, it, tokens=rows),
        entry("decode.fetch", t + 8_000, 1_000, prefill, kind="prefill",
              ready=1),
        entry("decode.emit", t + 9_000, 500, prefill, tokens=1),
    ]


@pytest.fixture
def ring(monkeypatch):
    """Puts entries where the readers look for them."""
    from mxnet_tpu.telemetry import flight
    entries = []
    monkeypatch.setattr(flight, "recent_spans", lambda: list(entries))
    return entries


def read_all():
    return {name: READERS[name].read(RUN) for name in PHASES}


def test_the_readers_say_what_benchmark_json_says_of_them():
    with open(os.path.join(os.path.dirname(harness.ROOT),
                           "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in PHASES:
        m, reader = listed[name], READERS[name]
        assert m["source"] == "program_span"
        assert (m["unit"], m["layer"], m["moves"]) == \
            (reader.UNIT, reader.LAYER, reader.MOVES)
        assert reader.KINDS == ("decode",)
        # four of the five decode cells: tests/chipbench/test_mellum2.py
        # holds the metrics that list mellum2_12b.repo_qa_c32 to PR 34's,
        # and that file is not this PR's to edit (the readers read there too)
        assert m["workloads"] == [
            "gpt1.decode_chat", "gpt1.decode_long", "sdar_30b_a3b.gen256_s2",
            "deepseek_v3.doc_qa_c64"]
    assert listed["prefill_padding_pct.decode"]["layer"] == "endpoints"
    assert {listed[n]["better"] for n in PHASES[:5]} == {"lower"}
    assert listed["prefill_overlap_pct.decode"]["better"] == "higher"


def test_each_reader_on_hand_made_passes(ring):
    for i in range(12):
        # the step's wait differs by pass: its median is read, 3.0055 ms;
        # one prefill in four was launched onto a chip with nothing to run
        ring.extend(a_pass(i, wait_us=3_000 + i, overlapped=int(i % 4 > 0)))
    assert read_all() == {
        "step_fetch_wait_ms.decode": pytest.approx(3.0055),
        "launch_host_ms.decode": pytest.approx(1.2),        # not the prefill's
        "emit_host_ms_per_step.decode": pytest.approx(2.0 + 0.5),
        # 10 - 4 ms off the processor, 3.0 - 0.1 of them for the chip; the
        # prefill's fetch found its result there: what it took stays
        "loop_off_cpu_ms_per_step.decode": pytest.approx(6.0 - 2.9),
        "prefill_padding_pct.decode": pytest.approx(25.0),
        "prefill_overlap_pct.decode": pytest.approx(75.0),
    }


def test_a_fetch_that_took_no_cpu_time_counts_whole_as_the_chips(ring):
    """As the program's spans are: the pass carries ``cpu_us``, its fetches
    none. The 3.0 ms of the fetch that waited for the chip are taken off
    whole, the 0.1 ms it was on the processor with them."""
    for i in range(12):
        ring.extend(a_pass(i, fetch_cpu_us=None))
    assert READERS["loop_off_cpu_ms_per_step.decode"].read(RUN) == \
        pytest.approx(6.0 - 3.0)


def test_a_cpu_clock_that_ticks_in_10_ms_still_reads_the_mean(ring):
    """The v5e host's thread clock: a pass's ``cpu_us`` is 0 or 10,000,
    whatever it used. Four passes in ten are charged a tick: 4 ms a pass in
    the mean, as on the precise clock; their median would read 10 - 2.9."""
    for i in range(20):
        ring.extend(a_pass(i, it_cpu_us=10_000 if i % 5 < 2 else 0))
    assert READERS["loop_off_cpu_ms_per_step.decode"].read(RUN) == \
        pytest.approx(6.0 - 2.9)


def test_a_pass_that_ran_no_step_is_no_step(ring):
    """A pass that only admitted (``rows`` 0) counts in no per-step median,
    its prefill of a prompt shorter than a block (``tokens`` 0, launched
    nothing) in no padding, and both in the share that overlapped."""
    for i in range(12):
        ring.extend(a_pass(i))
    for i in range(12, 20):
        entries = a_pass(i, rows=0, overlapped=0)
        step = next(e for e in entries if e["name"] == "decode.step")
        for e in entries:
            if e is step or e["parent_id"] == step["span_id"]:
                continue                # no step, nor its launch and fetch
            if e["name"] == "decode.prefill":
                e["attrs"]["tokens"] = 0
            if e["name"] == "decode.emit":
                e["dur_us"] *= 10
            ring.append(e)
    got = read_all()
    assert got["emit_host_ms_per_step.decode"] == pytest.approx(2.5)
    assert got["loop_off_cpu_ms_per_step.decode"] == pytest.approx(3.1)
    assert got["prefill_padding_pct.decode"] == pytest.approx(25.0)
    assert got["prefill_overlap_pct.decode"] == pytest.approx(100 * 12 / 20)


def test_spans_without_cpu_time_leave_one_reader_silent(ring):
    for i in range(12):
        ring.extend(a_pass(i, cpu=False))
    got = read_all()
    assert got.pop("loop_off_cpu_ms_per_step.decode") is None
    assert got == {"step_fetch_wait_ms.decode": pytest.approx(3.0),
                   "launch_host_ms.decode": pytest.approx(1.2),
                   "emit_host_ms_per_step.decode": pytest.approx(2.5),
                   "prefill_padding_pct.decode": pytest.approx(25.0),
                   "prefill_overlap_pct.decode": pytest.approx(100.0)}
    # an older program's entries have no such key at all
    for e in ring:
        del e["cpu_us"]
    assert READERS["loop_off_cpu_ms_per_step.decode"].read(RUN) is None
    # and the passes that carry it are read where the others do not
    for i in range(12, 24):
        ring.extend(a_pass(i, fetch_cpu_us=None))
    assert READERS["loop_off_cpu_ms_per_step.decode"].read(RUN) == \
        pytest.approx(3.0)


@pytest.mark.parametrize("name", PHASES)
def test_under_min_spans_a_reader_says_nothing(ring, name):
    assert _program_spans.MIN_SPANS == 10
    for i in range(9):
        ring.extend(a_pass(i))
    assert READERS[name].read(RUN) is None
    ring.extend(a_pass(9))
    assert READERS[name].read(RUN) is not None


@pytest.mark.parametrize("name", PHASES)
def test_an_empty_ring_or_another_programs_spans_read_none(ring, name):
    assert READERS[name].read(RUN) is None
    ring.append(entry("serving.batch", 0, 1_000, cpu_us=900))
    assert READERS[name].read(RUN) is None


def test_what_ran_before_set_up_was_done_is_left_out(ring):
    import chipbench
    done_us = (chipbench.T0 + 40.0) * 1e6
    for i in range(12):                 # warm-up's passes: slow launches
        for e in a_pass(i):
            e["t0_us"] = done_us - 5_000_000 + e["t0_us"] - 1_000_000
            if e["name"] == "decode.launch":
                e["dur_us"] *= 50
            ring.append(e)
    for i in range(12):
        for e in a_pass(i):
            e["t0_us"] = done_us + 1_000 + e["t0_us"] - 1_000_000
            ring.append(e)
    assert READERS["launch_host_ms.decode"].read(
        {"trace": None, "setup_s": 40.0}) == pytest.approx(1.2)


# ---------------------------------------------------------------------------
# through the command
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell", ["gpt1.decode_chat",
                                  "sdar_30b_a3b.gen256_s2"])
def test_a_traced_rehearsal_reports_all_six(capsys, monkeypatch, tmp_path,
                                            cell):
    # the harness's one trace directory is test_cells.py's, in another worker
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    assert harness.main(["--workload", cell, "--seed", str(2**31 + 136),
                         "--seconds", "0.5", "--trace", "1",
                         "--rehearse"]) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    got = {name: last["metrics"][harness.REHEARSAL_PREFIX + name]
           for name in PHASES}
    assert all(m["value"] >= 0 for m in got.values())
    assert {m["unit"] for m in got.values()} == {"ms", "%"}
    # the parts lie inside the whole that sched_host_ms_per_step.decode reads
    host = last["metrics"][harness.REHEARSAL_PREFIX
                           + "sched_host_ms_per_step.decode"]["value"]
    assert 0 < got["launch_host_ms.decode"]["value"] < host
    assert 0 < got["emit_host_ms_per_step.decode"]["value"] < host
    assert 0 <= got["prefill_padding_pct.decode"]["value"] < 50
    assert 0 <= got["prefill_overlap_pct.decode"]["value"] <= 100
