"""The ``sdar_moe`` family (chipbench/models/sdar_moe.py): what decides
``correct`` for a cell served by diffusion over blocks, on the rehearsal's
own finished requests (passes), on altered ones (every fifth placed token
changed; the lowest-confidence position placed in the highest's stead), and
with the float8 control in the program's place; the functions that
count a forward's work against a hand count; and the readers this
configuration brings, on hand-made runs and on the recorded decode trace."""
import contextlib
import io
import json
import os
import types

import pytest

from chipbench import harness
from chipbench.layer_metrics import _program_spans
from chipbench.models import sdar_moe as family
from chipbench.reference import sdar_moe as reference

CELL = "sdar_30b_a3b.gen256_s2"
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
READERS = {m.NAME: m for m in harness.layer_metric_modules()}


# ---------------------------------------------------------------------------
# the check, on what a rehearsal served
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    """One rehearsal of the cell through the command's entry point, with the
    arguments its check was given kept: (bench, lm, done, vocab)."""
    kept = {}
    check = family.check_requests

    def keep(bench, lm, done, vocab):
        kept.update(bench=bench, lm=lm, done=done, vocab=vocab)
        return check(bench, lm, done, vocab)

    family.check_requests = keep
    try:
        with contextlib.redirect_stdout(io.StringIO()) as out:
            assert harness.main(["--workload", CELL, "--seed",
                                 str(2**31 + 77), "--seconds", "0.5",
                                 "--trace", "0", "--rehearse"]) == 0
    finally:
        family.check_requests = check
    kept["last"] = json.loads(out.getvalue().splitlines()[-1])
    return types.SimpleNamespace(**kept)


def stand_in(request, tokens=None, steps=None, confidences=None):
    """A finished request as the check reads it, with other tokens, steps or
    confidences."""
    stream = request.stream
    return types.SimpleNamespace(
        prompt=request.prompt, budget=request.budget,
        tokens=list(request.tokens if tokens is None else tokens),
        stream=types.SimpleNamespace(
            steps=list(stream.steps if steps is None else steps),
            confidences=list(stream.confidences if confidences is None
                             else confidences)))


def check(served, done):
    with contextlib.redirect_stdout(io.StringIO()) as out:
        ok, seen = family.check_requests(served.bench, served.lm, done,
                                         served.vocab)
    lines = [json.loads(l) for l in out.getvalue().splitlines()]
    return ok, seen["compared"], lines


def over_their_limits(compared):
    return {name for name, row in compared.items()
            if row["value"] > row["limit"]}


def test_what_was_served_is_correct_and_names_what_it_compared(served):
    compared = served.last["compared"]
    assert served.last["correct"] is True
    assert {"worst_logit_deficit", "median_confidence_error_pct",
            "states_out_of_order_pct", "states_off_schedule",
            "ids_out_of_range_or_mask", "budgets_unmet",
            "compiles_after_warmup", "failed_requests"} == set(compared)
    # float32 on both sides at the rehearsal's size: rounding
    assert compared["worst_logit_deficit"]["value"] < 1e-3
    assert compared["median_confidence_error_pct"]["value"] < 1e-3
    assert compared["states_out_of_order_pct"]["value"] == 0
    ok, again, _ = check(served, served.done)
    assert ok and not over_their_limits(again)
    # every stream kept the step of each of its tokens, two a step, and the
    # confidence it was placed with
    for r in served.done:
        assert len(r.stream.steps) == len(r.tokens) == r.budget
        assert set(r.stream.steps) <= {0, 1}
        assert len(r.stream.confidences) == r.budget
        assert all(0.0 < c <= 1.0 for c in r.stream.confidences)


def test_every_fifth_placed_token_altered_is_not_correct(served):
    mask_id = served.bench.config["mask_token_id"]
    done = [stand_in(r, tokens=[(t + 7) % mask_id if i % 5 == 0 else t
                                for i, t in enumerate(r.tokens)])
            for r in served.done]
    ok, compared, _ = check(served, done)
    assert not ok
    assert "worst_logit_deficit" in over_their_limits(compared)
    assert compared["states_off_schedule"]["value"] == 0


def test_placing_the_lowest_confidence_first_is_not_correct(
        served, monkeypatch):
    """Generation by the reference's own forward, but each step places the
    masked positions it is *least* sure of: every token is still the best at
    its state (no logit deficit), the schedule's counts hold, and the
    share of states out of the reference's order shows it."""
    config = served.bench.config
    steps = served.bench.cell["generate"]["denoising_steps"]
    import jax.numpy as jnp
    import jax
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          family._weights(served.lm))
    monkeypatch.setattr(
        reference, "place", lambda conf, masked, n: sorted(sorted(
            (i for i, m in enumerate(masked) if m),
            key=lambda i: (float(conf[i]), i))[:n]))
    done = []
    for r in served.done[:3]:
        done.append(stand_in(r, *reference.generate(
            params, r.prompt, r.budget, config, steps)))
    monkeypatch.undo()
    ok, compared, _ = check(served, done)
    assert not ok
    assert over_their_limits(compared) == {"states_out_of_order_pct"}
    assert compared["worst_logit_deficit"]["value"] < 1e-3
    assert compared["median_confidence_error_pct"]["value"] < 1e-3
    # far over the real cell's limit too, not only the rehearsal's
    assert compared["states_out_of_order_pct"]["value"] > \
        2 * harness.load_cell(CELL)[0]["out_of_order_limit_pct"]


def test_a_step_that_places_more_than_the_schedule_says_is_not_correct(
        served):
    done = [stand_in(r, steps=[0] * len(r.tokens)) for r in served.done]
    ok, compared, _ = check(served, done)
    assert not ok and compared["states_off_schedule"]["value"] > 0


def test_the_mask_token_or_a_short_answer_is_not_correct(served):
    first, *rest = served.done
    mask_id = served.bench.config["mask_token_id"]
    ok, compared, _ = check(
        served, [stand_in(first, tokens=[mask_id] + first.tokens[1:])] + rest)
    assert not ok and compared["ids_out_of_range_or_mask"]["value"] == 1
    ok, compared, _ = check(
        served, [stand_in(first, tokens=first.tokens[:-1],
                          steps=first.stream.steps[:-1],
                          confidences=first.stream.confidences[:-1])] + rest)
    assert not ok and compared["budgets_unmet"]["value"] == 1


def test_a_confidence_that_is_off_by_a_tenth_is_not_correct(served):
    """The tokens and their steps as served, every confidence a tenth too
    high: what a program that computes them in a lower precision shows."""
    done = [stand_in(r, confidences=[1.1 * c for c in r.stream.confidences])
            for r in served.done]
    ok, compared, _ = check(served, done)
    assert not ok
    assert over_their_limits(compared) == {"median_confidence_error_pct"}
    assert compared["median_confidence_error_pct"]["value"] == \
        pytest.approx(10.0, rel=1e-3)
    # over the real cell's limit too
    assert 10.0 > harness.load_cell(CELL)[0]["confidence_error_limit_pct"]


def test_each_stand_in_comes_out_not_correct_by_its_own_limit(
        served, monkeypatch):
    """The control (the reference with every product's operands through
    float8), the least sure rows first, every fifth token altered: each is
    read at the states the served streams went through and lies over the
    limit that is there for it."""
    monkeypatch.setattr(family, "CONTROL", True)
    ok, compared, lines = check(served, served.done)
    assert ok                            # the program's own reading stands
    [control] = [l["control"] for l in lines if "control" in l]
    assert control["comes_out_not_correct"] is True
    assert control["limits"] == {
        name: compared[name]["limit"] for name in (
            "worst_logit_deficit", "median_confidence_error_pct",
            "states_out_of_order_pct")}
    low = control["float8"]
    assert "median_confidence_error_pct" in low["over"]
    assert low["median_confidence_error_pct"] > 100 * max(
        compared["median_confidence_error_pct"]["value"], 1e-3)
    assert low["rows_with_another_first_token"] > 0
    # over the real cell's limit too, which the experts' matrices alone in
    # float8 are not (and are held to nothing)
    limit = harness.load_cell(CELL)[0]["confidence_error_limit_pct"]
    assert low["median_confidence_error_pct"] > limit > \
        control["float8_expert_weights"]["median_confidence_error_pct"] > \
        compared["median_confidence_error_pct"]["value"]
    assert control["lowest_confidence_first"]["over"] == [
        "states_out_of_order_pct"]
    assert control["lowest_confidence_first"]["states_out_of_order_pct"] \
        == 100.0
    assert control["every_fifth_token_altered"]["over"] == [
        "worst_logit_deficit"]


def test_the_cell_is_the_issues_traffic():
    cell, config = harness.load_cell(CELL)
    assert (cell["clients"], cell["max_batch_size"], cell["max_seq_len"],
            cell["num_pages"]) == (64, 64, 1024, 64 * 64 + 1)
    assert cell["prompt_len"] == {"median": 256, "sigma": 0.5, "min": 64,
                                  "max": 768}
    assert cell["output_len"]["min"] == cell["output_len"]["max"] == 256
    assert cell["generate"] == {"denoising_steps": 2}
    assert cell["request_pool"] == 256
    for key in ("logit_tolerance", "confidence_error_limit_pct",
                "out_of_order_limit_pct"):
        assert cell[key] > 0 and len(cell[key + "_why"]) > 80
    # published widths, every expert, the whole vocabulary; depth alone cut
    assert config["reduced"] == ["num_hidden_layers"]
    assert (config["num_hidden_layers"], config["published"]) == \
        (6, {"num_hidden_layers": 48})
    assert (config["num_experts"], config["num_experts_per_tok"],
            config["vocab_size"], config["dtype"]) == \
        (128, 8, 151936, "bfloat16")
    assert {"block_length", "mask_token_id", "schedule", "qk_norm",
            "dtype"} <= set(config["assumed"])


def test_prompts_never_hold_the_mask_token():
    from chipbench.drivers import decode_closed
    cell, config = harness.load_cell(CELL, rehearse=True)
    requests = decode_closed.make_requests(cell, config["mask_token_id"], 5)
    assert max(t for p, _ in requests for t in p) < config["mask_token_id"]


# ---------------------------------------------------------------------------
# what a forward requires, against a hand count
# ---------------------------------------------------------------------------
def test_work_counts_against_a_hand_count_for_one_layer():
    _, config = harness.load_cell(CELL)
    one = {**config, "num_hidden_layers": 1}
    # a row multiplies, outside the experts: Wq 2048x4096, Wk and Wv
    # 2048x512, Wo 4096x2048, the router 2048x128; one expert is three
    # matrices of 2048x768; the head 2048x151936
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert attention == 18_874_368
    router, expert, head = 2048 * 128, 3 * 2048 * 768, 2048 * 151_936
    assert (expert, 128 * expert) == (4_718_592, 603_979_776)
    assert family._per_row(config) == (attention + router, expert, head)
    rows, context = 256, 400
    attend = 4 * 32 * 128 * context          # q.k and p.v: 2 x 2 x heads x D
    assert family.forward_flops(one, rows, context) == rows * (
        2 * (attention + router + 8 * expert) + attend + 2 * head)
    assert family.expert_flops(one, rows) == 2 * rows * 8 * expert
    # bytes: every expert's weights once; each of the 2,048 routed (row,
    # expert) pairs reads a row of 2048 and writes one, and writes and reads
    # its 768 activations; bfloat16
    assert family.expert_bytes(one, rows) == 2 * (
        128 * expert + rows * 8 * (2 * 2048 + 2 * 768))
    assert family.expert_bytes(one, rows) / 1e9 == pytest.approx(1.23103, 1e-5)
    # a forward: every weight once, the rows' embeddings, and the 64 lanes'
    # live keys and values (4 KV heads of 128, K and V)
    weights = attention + router + 128 * expert + head
    assert family.forward_bytes(one, rows, context) == 2 * (
        weights + rows * 2048 + (rows / 4) * context * 2 * 4 * 128)
    # the six layers of the cell: 4.05 B weights read (no embedding table:
    # 256 rows of it), 8.1 GB
    assert family.forward_bytes(config, 256, 0) / 1e9 == \
        pytest.approx(8.10, abs=0.01)
    # 2 x active parameters a token: 6 x (19.1 M + 8 x 4.72 M) + 311 M
    assert family.forward_flops(config, 1, 0) / 2 == pytest.approx(
        6 * (19_136_512 + 8 * 4_718_592) + 311_164_928)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
NEW = ["step_mfu_pct.decode", "denoise_step_roofline_pct.decode",
       "expert_ffn_roofline_pct.decode",
       "forwards_per_token.decode", "expert_load_max_over_mean.decode"]


@pytest.fixture
def ring(monkeypatch):
    from mxnet_tpu.telemetry import flight
    entries = []
    monkeypatch.setattr(flight, "recent_spans", lambda: list(entries))
    return entries


def step_span(i, **attrs):
    return {"name": "decode.step", "trace_id": "t", "span_id": f"s{i}",
            "parent_id": None, "t0_us": 1e6 * i, "dur_us": 3e4,
            "attrs": {"rows": 64, "bucket": 64, **attrs}}


@pytest.fixture(scope="module")
def decode_trace():
    """The recorded v5e decode trace: ``jit_decode`` modules to read."""
    from chipbench import xplane
    with open(os.path.join(FIXTURES, "trace_gpt1_decode_v5e.json")) as f:
        raw = json.load(f)
    dev = raw["devices"]["0"]
    trace = {"devices": {0: {"modules": dev["modules"],
                             "ops": [list(m) for m in dev["modules"]]}},
             "spans": raw["spans"]}
    return {"trace": trace, "trace_summary": xplane.summary(trace)}


def test_a_run_without_what_they_read_reports_none_of_them(ring,
                                                           decode_trace):
    """gpt1's runs: no work counts, no rows per forward, no expert loads."""
    ring.extend(step_span(i) for i in range(40))
    run = {**decode_trace, "decode_steps": 480, "later_tokens": 30_000,
           "max_batch_size": 64, "setup_s": None}
    for name in NEW:
        assert READERS[name].read(run) is None, name
        assert READERS[name].KINDS == ("decode",)
        assert READERS[name].MOVES == "decode_tokens_per_s"


def blocks_run(**extra):
    _, config = harness.load_cell(CELL)
    rows, context = 256, 384.0
    return {"device_kind": "TPU v5 lite", "chips": 1, "setup_s": None,
            "rows_per_forward": rows, "block_length": 4,
            "tokens_per_s": 2000.0, "tokens_in_window": 40_000.0,
            "decode_steps": 480,
            "flops_per_token": family.forward_flops(config, 1, context),
            "forward_flops": family.forward_flops(config, rows, context),
            "forward_bytes": family.forward_bytes(config, rows, context),
            **extra}


def test_step_mfu_is_required_flops_a_token_times_tokens_over_the_peak():
    run = blocks_run()
    want = 100 * run["flops_per_token"] * 2000.0 / 197e12
    assert READERS["step_mfu_pct.decode"].read(run) == pytest.approx(want)
    assert 1.0 < want < 2.0              # 1.3 GFLOP a token at 2,000 a second
    assert READERS["step_mfu_pct.decode"].read(
        {**run, "chips": 4}) == pytest.approx(want / 4)
    # a rehearsal has no chip and no share; an unknown chip is an error
    assert READERS["step_mfu_pct.decode"].read(
        {**run, "device_kind": "cpu"}) is None
    with pytest.raises(KeyError):
        READERS["step_mfu_pct.decode"].read({**run, "device_kind": "TPU v9"})


def test_denoise_step_roofline_sets_the_bytes_bound_against_the_module(
        decode_trace):
    from chipbench.layer_metrics._modules import median_ms
    run = blocks_run(**decode_trace)
    device_ms = median_ms(run, "jit_decode")
    assert device_ms > 10                # the fixture's step: tens of ms
    # bound by bytes: 8.3 GB at 819 GB/s is 10.1 ms, the FLOPs take 1.8 ms
    least_ms = 1e3 * run["forward_bytes"] / 819e9
    assert least_ms > 1e3 * run["forward_flops"] / 197e12
    got = READERS["denoise_step_roofline_pct.decode"].read(run)
    assert got == pytest.approx(100 * least_ms / device_ms)
    assert 0 < got < 100
    assert READERS["denoise_step_roofline_pct.decode"].read(
        blocks_run()) is None            # an untraced run: no device time


def test_forwards_per_token_counts_a_forward_once_a_lane():
    # 64 lanes busy in every forward, 3 forwards a block of 4: 0.75
    run = blocks_run(decode_steps=300, tokens_in_window=300 * 64 * 4 / 3)
    assert READERS["forwards_per_token.decode"].read(run) == \
        pytest.approx(0.75)
    assert READERS["forwards_per_token.decode"].read(
        {**run, "tokens_in_window": 0.0}) is None


def test_expert_load_is_the_ratio_of_the_sums_over_measured_steps(ring):
    run = blocks_run()
    ring.extend(step_span(i, **{"moe.expert_load_max": 28.0 + i % 3,
                                "moe.expert_load_mean": 16.0})
                for i in range(30))
    ring.append(step_span(99))           # a step of another endpoint: left out
    assert READERS["expert_load_max_over_mean.decode"].read(run) == \
        pytest.approx((28 + 29 + 30) / 3 / 16)
    del ring[_program_spans.MIN_SPANS - 1:]
    assert READERS["expert_load_max_over_mean.decode"].read(run) is None


def test_expert_ffn_roofline_reads_the_steps_grouped_matmuls_of_a_recorded_trace():
    """The recorded v5e trace of this cell, one whole step's ops (the first
    layer's in full, the other layers' custom calls) with every module of the
    window, pins what the trace prints for the step's grouped matmuls: in
    each of six layers two custom calls of (pairs, 768) and one of (pairs,
    2048), inside a ``jit_decode`` module."""
    import statistics
    from chipbench import xplane
    with open(os.path.join(FIXTURES, "trace_sdar_step_v5e.json")) as f:
        raw = json.load(f)
    dev = raw["devices"]["0"]
    trace = {"devices": {0: {"modules": dev["modules"], "ops": dev["ops"]}},
             "spans": raw["spans"]}
    _, config = harness.load_cell(CELL)
    rows = 256                           # 64 lanes of 4
    calls = family.expert_ops(config, rows)
    assert calls == {"custom-call[tpu_custom_call] -> f32[2048,768]": 2,
                     "custom-call[tpu_custom_call] -> f32[2048,2048]": 1}
    seen = {label: [d for text, _, d in dev["ops"]
                    if xplane.op_label(text) == label] for label in calls}
    assert {label: len(d) for label, d in seen.items()} == \
        {label: 6 * n for label, n in calls.items()}
    # every one of them lies inside one step's module
    [step] = [(t0, t0 + d) for name, t0, d in dev["modules"]
              if name.startswith("jit_decode(")
              and t0 <= dev["ops"][0][1] <= t0 + d]
    assert all(step[0] <= t0 <= step[1] for _, t0, _ in dev["ops"])
    run = {"trace": trace, "trace_summary": xplane.summary(trace),
           "device_kind": "TPU v5 lite", "chips": 1, "expert_ops": calls,
           "expert_flops": family.expert_flops(config, rows),
           "expert_bytes": family.expert_bytes(config, rows)}
    layer_s = sum(n * statistics.median(seen[label])
                  for label, n in calls.items()) / 1e9
    # 2,048 pairs: bound by the 1.208 GB of weights, 1.50 ms at the peak
    least_s = run["expert_bytes"] / 819e9
    assert least_s > run["expert_flops"] / 197e12
    got = READERS["expert_ffn_roofline_pct.decode"].read(run)
    assert got == pytest.approx(100 * least_s / layer_s)
    assert 50 < got < 100
    # a prefill of 256 rows prints the same labels: read by its module, its
    # ops are left out, and with no step there is nothing to read
    as_prefill = [[name.replace("jit_decode", "jit_prefill"), t0, d]
                  for name, t0, d in dev["modules"]]
    assert READERS["expert_ffn_roofline_pct.decode"].read(
        {**run, "trace": {**trace, "devices": {0: {
            "modules": as_prefill, "ops": dev["ops"]}}}}) is None
    # another size's shapes are not in this step: no number
    assert READERS["expert_ffn_roofline_pct.decode"].read(
        {**run, "expert_ops": family.expert_ops(config, 512)}) is None
    assert READERS["expert_ffn_roofline_pct.decode"].read(
        {k: v for k, v in run.items() if k != "expert_ops"}) is None
