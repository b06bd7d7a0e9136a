"""The ``mellum2`` family (chipbench/models/mellum2.py): what decides
``correct`` for the cell served through a pool of two cache groups, on the
rehearsal's own finished requests (passes), on altered ones and with the
stand-ins in the program's place; the check's layer-at-a-time reference
against the plain one; the functions that count a forward's, a kernel's and
the experts' work against hand counts at the published widths; the files; and
the reader this configuration brings, on a hand-made run and on the trace
kept from the builder's chip run."""
import contextlib
import io
import json
import os
import types

import numpy as onp
import pytest

from chipbench import harness, xplane
from chipbench.models import mellum2 as family
from chipbench.reference import mellum2 as reference

CELL = "mellum2_12b.repo_qa_c32"
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
READERS = {m.NAME: m for m in harness.layer_metric_modules()}
NEW = "paged_attention_roofline_pct.decode"


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
                                 str(2**31 + 91), "--seconds", "0.5",
                                 "--trace", "0", "--rehearse"]) == 0
    finally:
        family.check_requests = check
    kept["last"] = json.loads(out.getvalue().splitlines()[-1])
    return types.SimpleNamespace(**kept)


def stand_in(request, tokens):
    return types.SimpleNamespace(prompt=request.prompt, budget=request.budget,
                                 tokens=list(tokens))


def check(served, done, control=False):
    family.CONTROL = control
    try:
        with contextlib.redirect_stdout(io.StringIO()) as out:
            ok, seen = family.check_requests(served.bench, served.lm, done,
                                             served.vocab)
    finally:
        family.CONTROL = False
    lines = [json.loads(l) for l in out.getvalue().splitlines()]
    return ok, seen, lines


def over_their_limits(compared):
    return {name for name, row in compared.items()
            if row["value"] > row["limit"]}


def test_what_was_served_is_correct_and_names_what_it_compared(served):
    compared = served.last["compared"]
    assert served.last["correct"] is True
    assert {"worst_logit_deficit", "tokens_off_best_pct", "ids_out_of_range",
            "budgets_unmet", "compiles_after_warmup", "failed_requests",
            "window_pages_a_sequence_peak"} == set(compared)
    # no sequence ever held more of the window group than its ring: the
    # rehearsal's window of 16 and a page of 16
    assert compared["window_pages_a_sequence_peak"] == {"value": 2,
                                                        "limit": 2}
    # float32 on both sides at the rehearsal's size: rounding
    assert compared["worst_logit_deficit"]["value"] < 1e-3
    assert compared["tokens_off_best_pct"]["value"] == 0
    ok, seen, _ = check(served, served.done)
    assert ok and not over_their_limits(seen["compared"])
    # the longest finished sequence is always in the sample, and it has
    # passed the rehearsal's window of 16 several times over
    longest = max(len(r.prompt) + len(r.tokens) for r in served.done)
    assert longest in seen["checked_rows"] and longest > 4 * 16
    assert seen["checked_requests"] == 3


def test_every_fifth_token_altered_is_not_correct(served):
    done = [stand_in(r, [(t + 7) % served.vocab if i % 5 == 0 else t
                         for i, t in enumerate(r.tokens)])
            for r in served.done]
    ok, seen, _ = check(served, done)
    assert not ok
    assert over_their_limits(seen["compared"]) == {"worst_logit_deficit",
                                                   "tokens_off_best_pct"}


def test_a_short_answer_is_not_correct(served):
    first = served.done[0]
    ok, seen, _ = check(served, [stand_in(first, first.tokens[:-1])]
                        + served.done[1:])
    assert not ok and seen["compared"]["budgets_unmet"]["value"] == 1


def test_the_stand_ins_come_out_as_the_cell_says(served):
    """At the rehearsal's float32 the limits are rounding's, so the control
    (both operands of every product through float8) is far over the share's
    limit, and every fifth token altered over the worst deficit's. The third
    stand-in, the reference with no window, puts other tokens first wherever
    a sequence has passed the window, and has to fail as well."""
    ok, seen, lines = check(served, served.done, control=True)
    assert ok                            # the program itself still passes
    [control] = [l["control"] for l in lines if "control" in l]
    assert control["comes_out_not_correct"] is True
    assert "tokens_off_best_pct" in control["float8"]["over"]
    assert control["float8"]["tokens_off_best_pct"] > \
        2 * control["limits"]["tokens_off_best_pct"]
    altered = control["every_fifth_token_altered"]
    assert altered["over"] == ["worst_logit_deficit"]
    assert altered["smallest_logit_deficit"] <= \
        altered["median_logit_deficit"] <= altered["worst_logit_deficit"]
    assert altered["over_the_limit_pct"] > 90
    assert set(control["every_layer_full"]) == {
        "worst_logit_deficit", "tokens_off_best_pct", "over"}
    assert control["every_layer_full"]["over"] == [
        "tokens_off_best_pct", "worst_logit_deficit"]


def test_the_checks_reference_a_layer_at_a_time_is_the_plain_reference(
        served):
    """Upcast piece by piece, the experts one at a time, attention one head
    and a block of rows at a time: the same logits as ``reference.forward``
    on the float32 weights; with every layer full, as its stand-in."""
    import jax
    import jax.numpy as jnp
    cell, config = served.bench.cell, served.bench.config
    r = max(served.done, key=lambda r: len(r.prompt) + len(r.tokens))
    weights = family._weights(served.lm)
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), weights)
    seq = jnp.asarray(r.prompt + r.tokens, jnp.int32)
    first = len(r.prompt) - 1
    for stand, kw in ((None, {}), ("all_full", {"every_layer_full": True})):
        got = family.served_logits(cell, config, weights, r.prompt, r.tokens,
                                   stand)
        want = reference.forward(f32, seq, config, **kw)
        onp.testing.assert_allclose(
            got, onp.asarray(want)[first:first + len(r.tokens)], rtol=2e-4,
            atol=2e-4)
        assert got.shape == (len(r.tokens), config["vocab_size"])


# ---------------------------------------------------------------------------
# the files
# ---------------------------------------------------------------------------
def test_the_cell_is_the_issues_traffic():
    cell, config = harness.load_cell(CELL)
    assert cell["driver"] == "decode_windowed" and cell["chips"] == 1
    assert (cell["clients"], cell["max_batch_size"], cell["max_seq_len"],
            cell["num_pages"]) == (32, 32, 16384, 32 * 1024 + 1)
    assert cell["prompt_len"] == {"median": 4096, "sigma": 0.8, "min": 512,
                                  "max": 15872}
    assert cell["output_len"] == {"median": 256, "sigma": 0.4, "min": 128,
                                  "max": 512}
    assert (cell["request_pool"], cell["warmup_seconds"],
            cell["trace_seconds"]) == (32, 45.0, 3.0)
    assert cell["request_pool"] == cell["clients"]
    # the pool this makes: the full group's 2 layers x 32,769 pages and the
    # window group's 6 layers x (32 rings of 65 pages + the scratch page)
    from mxnet_tpu.serving.generate.kv_cache import ring_pages
    ring = ring_pages(config["sliding_window"], 16)
    assert ring == 65
    row = 2 * 4 * 128 * 2               # a K row and a V row, bfloat16
    assert cell["num_pages"] * 16 * 2 * row == 2_147_549_184
    assert (32 * ring + 1) * 16 * 6 * row == 409_141_248 < 0.45e9
    # ladders, page size and batch_timeout stay at the program's defaults
    assert not {"prefill_buckets", "decode_buckets", "page_size",
                "batch_timeout", "generate"} & set(cell)
    for key in ("logit_tolerance", "off_best_limit_pct"):
        assert cell[key] > 0 and len(cell[key + "_why"]) > 80
    from chipbench.drivers import decode_closed
    requests = decode_closed.make_requests(cell, config["vocab_size"], 5)
    assert max(len(p) + n for p, n in requests) <= cell["max_seq_len"]
    lens = sorted(len(p) for p, _ in requests)
    # short and long in one queue: most prompts pass twice the window, some
    # take the longest rung
    assert sum(n > 2 * config["sliding_window"] for n in lens) >= 24
    assert sum(n > 8192 for n in lens) == 7 and lens[0] < 1024
    from mxnet_tpu.serving import bucketing
    assert bucketing.seq_buckets(cell["max_seq_len"])[-3:] == (
        4096, 8192, 16384)


def test_the_configuration_is_the_catalogs_row_but_for_what_it_lists():
    _, config = harness.load_cell(CELL)
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "mlp_layer_types"]
    assert config["published"]["num_hidden_layers"] == 28
    assert config["num_hidden_layers"] == 8
    assert config["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 2
    assert config["mlp_layer_types"] == ["sparse"] * 8
    # every width, every expert and the whole vocabulary as published
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts"],
            config["num_experts_per_tok"], config["vocab_size"],
            config["sliding_window"], config["intermediate_size"]) == \
        (2304, 32, 4, 128, 896, 64, 8, 98304, 1024, 7168)
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert {"qk_norm", "mask", "dtype", "init_std", "intermediate_size",
            "prediction_module"} <= set(config["assumed"])
    assert "four pipeline stages" in config["deployment"]
    assert config["rehearse"]["sliding_window"] == 16
    # against the catalog's row, where the guide is installed
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            [row] = [r for r in map(json.loads, f)
                     if r["name"] == "Mellum2-12B-A2.5B-Instruct"]
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key


# ---------------------------------------------------------------------------
# what the work requires, against hand counts at the published widths
# ---------------------------------------------------------------------------
def test_work_counts_against_hand_counts():
    _, config = harness.load_cell(CELL)
    # a layer's attention: q and o 2,304 x 4,096 each, k and v 2,304 x 512
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    router, expert, head = 2304 * 64, 3 * 2304 * 896, 2304 * 98304
    assert (attention, router, expert) == (21_233_664, 147_456, 6_193_152)
    assert family._per_row(config) == (attention, router, expert, head)
    # the model's name: 12.15 B in all, 2.44 B active a token
    layer = attention + router + 64 * expert
    assert layer == 417_742_848
    assert 28 * layer + 2 * head == pytest.approx(12.15e9, rel=1e-3)
    assert 28 * (attention + router + 8 * expert) + 2 * head == \
        pytest.approx(2.44e9, rel=2e-3)
    # the cut: 8 layers, embedding and head: 3.795 B, 7.59 GB
    assert 8 * layer + 2 * head == 3_794_927_616   # + 41,216 of norms
    assert family.layers_by_kind(config) == (2, 6)
    # one token: 794 M active here (the embedding is a lookup), and attention
    # over its context by kind: all of it in 2 layers, the window's in 6
    active = 8 * (attention + router + 8 * expert) + head
    assert active == pytest.approx(794e6, rel=1e-3)
    assert family.forward_flops(config, 1, 5500.0) == pytest.approx(
        2 * active + 4 * 32 * 128 * (2 * 5500 + 6 * 1024))
    assert family.forward_flops(config, 1, 5500.0) / 1e9 == pytest.approx(
        1.868, abs=0.005)
    assert family.forward_flops(config, 1, 300.0) == pytest.approx(
        2 * active + 4 * 32 * 128 * 8 * 300)
    # the paged kernel: a position-layer is a K row and a V row, 2,048 B,
    # and 4 x 32 heads x 128 FLOP: 8 FLOP a byte, far under the chip's 240
    assert family.paged_attention_bytes(config, 1) == 2048
    assert family.paged_attention_flops(config, 1) == 4 * 32 * 128
    # the prefill's attention at a rung, the mean of a prefill's eight calls:
    # 2 causal (32 x S^2 / 2 x 512) and 6 banded (32 x (S x 1,024 - 1,024^2
    # / 2) x 512)
    S, w = 16384, 1024
    causal, banded = 32 * S * S / 2 * 512, 32 * (S * w - w * w / 2) * 512
    assert family.prefill_attention_flops(config, S) == pytest.approx(
        (2 * causal + 6 * banded) / 8)
    assert banded / causal == pytest.approx(0.121, abs=0.001)
    # under the window both kinds are the causal square
    assert family.prefill_attention_flops(config, 512) == \
        32 * 512 * 512 / 2 * 512
    assert family.prefill_attention_bytes(config, S) == \
        (2 * 32 + 2 * 4) * S * 128 * 2
    # a step's routed product in one layer: 32 rows x 8 = 256 pairs; 64 x (1 -
    # (7/8)^32) = 63.1 of the 64 experts draw a row
    assert family.expert_flops(config, 32) == 2 * 256 * expert
    drawn = 64 * (1 - (1 - 8 / 64) ** 32)
    assert drawn == pytest.approx(63.11, abs=0.01)
    assert family.expert_bytes(config, 32) == pytest.approx(
        2 * (drawn * expert + 256 * (2 * 2304 + 2 * 896)))
    assert family.expert_bytes(config, 32) / 1e9 == pytest.approx(
        0.785, abs=1e-3)


def test_the_kernels_names_in_a_trace():
    _, config = harness.load_cell(CELL)
    assert family.expert_ops(config, 32) == {
        "custom-call[tpu_custom_call] -> f32[256,896]": 2,
        "custom-call[tpu_custom_call] -> f32[256,2304]": 1}
    assert family.paged_attention_op(config, 32) == (
        "custom-call[tpu_custom_call] -> (f32[32,1,32,512], "
        "f32[32,1,32,1], f32[32,1,32,1])")
    assert len(family.paged_attention_op(config, 32)) <= 96   # not cut
    from mxnet_tpu.serving import bucketing
    rungs = bucketing.seq_buckets(16384)
    assert family.prefill_attention_ops(config, rungs) == {
        "custom-call[tpu_custom_call] -> (bf16[32,%d,128], "
        "f32[32,%d,128])" % (S, S): S
        for S in (512, 1024, 2048, 4096, 8192, 16384)}


def test_the_rotary_tables_as_the_model_takes_them():
    _, config = harness.load_cell(CELL)
    ropes = family.rope_by_type(config)
    assert ropes["sliding_attention"] == {"theta": 500000.0, "scaling": None}
    assert ropes["full_attention"] == {"theta": 500000.0, "scaling": {
        "factor": 16, "original_max_position_embeddings": 8192,
        "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2772588722239782}}


# ---------------------------------------------------------------------------
# the reader, on a hand-made run
# ---------------------------------------------------------------------------
@pytest.fixture
def ring(monkeypatch):
    from mxnet_tpu.telemetry import flight
    entries = []
    monkeypatch.setattr(flight, "recent_spans", lambda: list(entries))
    return entries


def step_span(i, at_s, **attrs):
    return {"name": "decode.step", "trace_id": "t", "span_id": f"s{i}",
            "parent_id": None, "t0_us": 1e6 * at_s, "dur_us": 2e4,
            "attrs": {"rows": 32, "bucket": 32, **attrs}}


def hand_made_run():
    """Two steps in a window of one second: each runs the paged kernel eight
    times, twice for 0.6 ms (the full layers) and six times for 0.2."""
    _, config = harness.load_cell(CELL)
    ms = 1e6
    paged = "%c = (f32[32,1,32,512]{3,2,1,0}, f32[32,1,32,1]{3,2,1,0}, " \
        "f32[32,1,32,1]{3,2,1,0}) custom-call(%a), " \
        'custom_call_target="tpu_custom_call"'
    other = "%m = bf16[32,2304]{1,0} fusion(%a), kind=kOutput"
    modules = [["jit_decode(1)", 100 * ms, 20 * ms],
               ["jit_prefill(2)", 130 * ms, 60 * ms],
               ["jit_decode(1)", 200 * ms, 20 * ms]]
    ops = []
    for start in (100, 200):
        ops += [[paged, (start + 2 * i) * ms, (0.6 if i % 4 == 3 else 0.2)
                 * ms] for i in range(8)]
        ops.append([other, (start + 17) * ms, 2 * ms])
    # the same kernel's name inside a prefill's program is not a step's
    ops.append([paged, 140 * ms, 5 * ms])
    trace = {"devices": {0: {"modules": modules, "ops": ops}},
             "spans": [[xplane.SPAN_PREFIX + "window", 0.0, 1000 * ms]]}
    return {
        "trace": trace, "trace_summary": xplane.summary(trace),
        "device_kind": "TPU v5 lite", "chips": 1,
        "paged_attention_op": family.paged_attention_op(config, 32),
        "paged_flops_per_position": family.paged_attention_flops(config, 1),
        "paged_bytes_per_position": family.paged_attention_bytes(config, 1),
        "paged_layers": {"full": 2, "window": 6},
        "traced_window_host_s": (50.0, 51.0)}


def test_paged_attention_roofline_charges_a_window_layer_its_window(ring):
    run = hand_made_run()
    # the steps of the window attended to 170,000 and 180,000 positions, of
    # which the window layers need 32 x 1,023 and 32 x 1,000; the ring also
    # holds a step from before the window, which is left out
    ring.extend([step_span(0, 49.0, ctx_live=10, ctx_window_live=10),
                 step_span(1, 50.2, ctx_live=170_000, ctx_window_live=32_736),
                 step_span(2, 50.6, ctx_live=180_000,
                           ctx_window_live=32_000)])
    got = READERS[NEW].read(run)
    # position-layers a step: 2 x 175,000 + 6 x 32,368 = 544,208, x 2,048 B =
    # 1.1145 GB: 1.361 ms at 819 GB/s (the FLOPs 0.045 ms); the kernel took
    # 2 x 0.6 + 6 x 0.2 = 2.4 ms a step
    required = 2 * 175_000 + 6 * 32_368
    least = required * 2048 / 819e9
    assert least == pytest.approx(1.361e-3, rel=1e-3)
    assert least > required * 4 * 32 * 128 / 197e12
    assert got == pytest.approx(100 * least / 2.4e-3)
    # a kernel that read the window layers' whole context would need 8 x
    # 175,000 position-layers' time for the same requirement
    assert required / (8 * 175_000) == pytest.approx(0.389, abs=0.001)
    # nothing to read: no trace, no kernel of that name, no step that says
    # what its window layers read (the parent's program), none in the window
    assert READERS[NEW].read(
        {k: v for k, v in run.items() if k != "trace"}) is None
    assert READERS[NEW].read(
        {**run, "paged_attention_op": "custom-call -> f32[1]"}) is None
    assert READERS[NEW].read(
        {k: v for k, v in run.items() if k != "paged_attention_op"}) is None
    ring[:] = [step_span(1, 50.2, ctx_live=170_000)]
    assert READERS[NEW].read(run) is None
    del ring[:]
    assert READERS[NEW].read(run) is None


def test_the_reader_is_one_of_the_benchmarks_and_its_cell_reports_the_rest():
    with open(os.path.join(os.path.dirname(harness.ROOT),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    [metric] = [m for m in bench["per_layer"] if m["name"] == NEW]
    assert metric == {
        "name": NEW, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels, embeddings",
        "moves": "decode_tokens_per_s", "workloads": [CELL]}
    module = READERS[NEW]
    assert (module.UNIT, module.LAYER, module.MOVES, module.KINDS) == (
        "%", "kernels, embeddings", "decode_tokens_per_s", ("decode",))
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {
        NEW, "decode_step_ms.decode", "prefill_ms.decode", "ttft_p95_ms",
        "tpot_p95_ms", "batch_occupancy_pct.decode", "device_idle_pct.decode",
        "sched_host_ms_per_step.decode", "queue_wait_p95_ms.decode",
        "idle_outside_spans_pct.decode", "step_mfu_pct.decode",
        "expert_ffn_roofline_pct.decode", "expert_load_max_over_mean.decode",
        "prefill_busy_share_pct.decode",
        "prefill_attention_roofline_pct.decode"}


# ---------------------------------------------------------------------------
# the readers, on the trace kept from the builder's chip run
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    """``trace_mellum2_v5e.json``: the cell's traced run on the chip (PR 34)
    as ``layer_metrics/_kernels.excerpt`` cut it: every module of the window
    and, of the ops, the Pallas kernels inside the first three step programs
    and inside every prefill program; beside it, under ``steps``, what the
    ``decode.step`` spans of the ring said of the steps while the window
    stood open: (``ctx_live``, ``ctx_window_live``)."""
    with open(os.path.join(FIXTURES, "trace_mellum2_v5e.json")) as f:
        raw = json.load(f)
    dev = raw["devices"]["0"]
    trace = {"devices": {0: dev}, "spans": raw["spans"]}
    _, config = harness.load_cell(CELL)
    from mxnet_tpu.serving import bucketing
    rungs = family.prefill_attention_ops(config, bucketing.seq_buckets(16384))
    run = {
        "trace": trace, "trace_summary": xplane.summary(trace),
        "device_kind": "TPU v5 lite", "chips": 1,
        "expert_ops": family.expert_ops(config, 32),
        "expert_flops": family.expert_flops(config, 32),
        "expert_bytes": family.expert_bytes(config, 32),
        "paged_attention_op": family.paged_attention_op(config, 32),
        "paged_flops_per_position": family.paged_attention_flops(config, 1),
        "paged_bytes_per_position": family.paged_attention_bytes(config, 1),
        "paged_layers": {"full": 2, "window": 6},
        "prefill_attention_ops": {
            label: (family.prefill_attention_flops(config, S),
                    family.prefill_attention_bytes(config, S))
            for label, S in rungs.items()},
        "traced_window_host_s": (50.0, 53.0)}
    return run, raw["steps"]


def test_the_recorded_trace_names_the_kernels_as_the_family_does(recorded,
                                                                 ring):
    run, steps = recorded
    ring.extend(step_span(i, 50.0 + 3.0 * i / len(steps), ctx_live=live,
                          ctx_window_live=window)
                for i, (live, window) in enumerate(steps))
    # lanes hold several windows each: the window layers' requirement is a
    # fraction of the live context, 32 lanes x 1,023 at most
    assert all(window <= 32 * 1023 < live for live, window in steps)
    got = READERS[NEW].read(run)
    # on the chip, over all the window's steps: 48.2; over the cut's three
    # steps, whose kernels took 2 x 0.882 + 6 x 0.207 = 3.0 ms each
    assert got == pytest.approx(48.0, abs=2.0)
    # eight calls a step, of one name: two on the full group's pools, six
    # bounded on the rings
    found = [op for op in run["trace"]["devices"][0]["ops"]
             if xplane.op_label(op[0]) == run["paged_attention_op"]]
    decode = [m for m in run["trace"]["devices"][0]["modules"]
              if m[0].startswith("jit_decode")][:3]
    inside = [op for op in found
              if any(m[1] <= op[1] <= m[1] + m[2] for m in decode)]
    assert len(inside) == 3 * 8
    by_step = sorted(op[2] for op in inside[:8])
    # the six window calls are the short ones, the two full ones the long
    assert by_step[5] < by_step[6]
    # the two full calls of a step take 0.88 ms each, the six bounded 0.21
    assert by_step[5] < 0.25e6 < 0.8e6 < by_step[6]
    assert READERS["expert_ffn_roofline_pct.decode"].read(run) == \
        pytest.approx(92.4, abs=1.0)
    assert READERS["prefill_attention_roofline_pct.decode"].read(run) == \
        pytest.approx(39.4, abs=1.0)
