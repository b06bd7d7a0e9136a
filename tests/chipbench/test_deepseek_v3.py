"""The ``deepseek_v3`` family (chipbench/models/deepseek_v3.py): what decides
``correct`` for the cell served through the latent pool, on the rehearsal's
own finished requests (passes), on altered ones and with the float8 control
in the program's place; the check's layer-at-a-time reference against the
plain one; the functions that count a forward's, a kernel's and the experts'
work against hand counts at the published widths; and the readers this
configuration brings, on hand-made runs and on the trace kept from the
builder's chip run."""
import contextlib
import io
import json
import os
import types

import numpy as onp
import pytest

from chipbench import harness, xplane
from chipbench.models import deepseek_v3 as family
from chipbench.reference import deepseek_v3 as reference

CELL = "deepseek_v3.doc_qa_c64"
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
READERS = {m.NAME: m for m in harness.layer_metric_modules()}
NEW = ["latent_attention_roofline_pct.decode",
       "prefill_attention_roofline_pct.decode",
       "prefill_busy_share_pct.decode"]


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
            "budgets_unmet", "compiles_after_warmup", "failed_requests"} \
        == set(compared)
    # float32 on both sides at the rehearsal's size: rounding
    assert compared["worst_logit_deficit"]["value"] < 1e-3
    assert compared["tokens_off_best_pct"]["value"] == 0
    ok, seen, _ = check(served, served.done)
    assert ok and not over_their_limits(seen["compared"])
    # the longest finished sequence is always in the sample
    longest = max(len(r.prompt) + len(r.tokens) for r in served.done)
    assert longest in seen["checked_rows"]
    assert seen["checked_requests"] == 3


def test_every_fifth_token_altered_is_not_correct(served):
    done = [stand_in(r, [(t + 7) % served.vocab if i % 5 == 0 else t
                         for i, t in enumerate(r.tokens)])
            for r in served.done]
    ok, seen, _ = check(served, done)
    assert not ok
    assert over_their_limits(seen["compared"]) == {"worst_logit_deficit",
                                                   "tokens_off_best_pct"}


def test_an_id_past_the_slice_or_a_short_answer_is_not_correct(served):
    first = served.done[0]
    ok, seen, _ = check(served, [stand_in(first, first.tokens[:-1])]
                        + served.done[1:])
    assert not ok and seen["compared"]["budgets_unmet"]["value"] == 1
    # the logits are over the slice of the vocabulary the chip holds
    beyond = stand_in(first, [served.vocab] + first.tokens[1:])
    with pytest.raises(IndexError):
        check(served, [beyond])


def test_the_float8_control_comes_out_not_correct_by_its_own_limit(served):
    """At the rehearsal's float32 the limits are rounding's, so the control
    (both operands of every product through float8) is far over the share's
    limit, and every fifth token altered over the worst deficit's."""
    ok, seen, lines = check(served, served.done, control=True)
    assert ok                            # the program itself still passes
    [control] = [l["control"] for l in lines if "control" in l]
    assert control["comes_out_not_correct"] is True
    assert "tokens_off_best_pct" in control["float8"]["over"]
    assert control["float8"]["tokens_off_best_pct"] > \
        2 * control["limits"]["tokens_off_best_pct"]
    assert control["every_fifth_token_altered"]["over"] == [
        "worst_logit_deficit"]
    altered = control["every_fifth_token_altered"]
    assert altered["worst_logit_deficit"] > \
        control["limits"]["worst_logit_deficit"]
    assert altered["smallest_logit_deficit"] <= \
        altered["median_logit_deficit"] <= altered["worst_logit_deficit"]
    assert altered["over_the_limit_pct"] > 90


def test_the_checks_reference_a_layer_at_a_time_is_the_plain_reference(
        served):
    """Upcast piece by piece, the dense MLP by columns, the experts one at a
    time, attention a few heads at a time: the same logits as
    ``reference.forward`` on the float32 weights."""
    import jax
    import jax.numpy as jnp
    cell, config = served.bench.cell, served.bench.config
    r = max(served.done, key=lambda r: len(r.prompt) + len(r.tokens))
    weights = family._weights(served.lm)
    got = family.served_logits(cell, config, weights, r.prompt, r.tokens)
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), weights)
    seq = jnp.asarray(r.prompt + r.tokens, jnp.int32)
    want = reference.forward(f32, seq, config,
                             held=tuple(config["held_experts"]))
    first = len(r.prompt) - 1
    onp.testing.assert_allclose(
        got, onp.asarray(want)[first:first + len(r.tokens)], rtol=2e-4,
        atol=2e-4)
    assert got.shape == (len(r.tokens), config["vocab_size"])


# ---------------------------------------------------------------------------
# the files
# ---------------------------------------------------------------------------
def test_the_cell_is_the_issues_traffic():
    cell, config = harness.load_cell(CELL)
    assert cell["driver"] == "decode_latent" and cell["chips"] == 1
    assert (cell["clients"], cell["max_batch_size"], cell["max_seq_len"],
            cell["num_pages"]) == (64, 64, 5120, 64 * 320 + 1)
    assert cell["prompt_len"] == {"median": 2048, "sigma": 0.5, "min": 512,
                                  "max": 4096}
    assert cell["output_len"] == {"median": 512, "sigma": 0.4, "min": 256,
                                  "max": 1024}
    # the two the issue lets the builder steady the cell by, each with why
    assert (cell["request_pool"], cell["warmup_seconds"],
            cell["trace_seconds"]) == (64, 90.0, 3.0)
    assert cell["request_pool"] == cell["clients"]
    # each says what the issue's own value read on the chip beside it
    for key, issues in (("request_pool_why", "128"),
                        ("warmup_seconds_why", "30")):
        assert "issue" in cell[key] and issues in cell[key]
        assert "six seeds" in cell[key] and len(cell[key]) > 80
    # ladders, page size and batch_timeout stay at the program's defaults
    assert not {"prefill_buckets", "decode_buckets", "page_size",
                "batch_timeout", "generate"} & set(cell)
    for key in ("logit_tolerance", "off_best_limit_pct"):
        assert cell[key] > 0 and len(cell[key + "_why"]) > 80
    from chipbench.drivers import decode_closed
    requests = decode_closed.make_requests(cell, config["vocab_size"], 5)
    assert max(len(p) + n for p, n in requests) <= cell["max_seq_len"]
    assert max(t for p, _ in requests for t in p) < config["vocab_size"]


def test_the_configuration_is_the_catalogs_row_but_for_what_it_lists():
    _, config = harness.load_cell(CELL)
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert config["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280,
        "num_nextn_predict_layers": 1}
    assert [config[k] for k in config["reduced"]] == [5, 1, 16, 16160, 0]
    assert config["held_experts"] == [0, 16]
    # every width as published
    assert (config["hidden_size"], config["num_attention_heads"],
            config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["n_group"], config["topk_group"],
            config["n_shared_experts"], config["routed_scaling_factor"]) == \
        (7168, 128, 1536, 512, 128, 64, 128, 18432, 2048, 8, 8, 4, 1, 2.5)
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert family.router_width(config) == 256
    assert {"dtype", "init_std", "router_bias_std", "rotary_pairing",
            "num_nextn_predict_layers", "latent_row"} <= set(
                config["assumed"])
    assert "sixteen chips" in config["deployment"]


# ---------------------------------------------------------------------------
# what the work requires, against hand counts at the published widths
# ---------------------------------------------------------------------------
def test_work_counts_against_hand_counts():
    _, config = harness.load_cell(CELL)
    # a layer's attention: q_a 7168x1536, q_b 1536x(128x192), kv_a 7168x576,
    # kv_b 512x(128x256), o 16384x7168
    attention = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
                 + 512 * 128 * 256 + 16384 * 7168)
    assert attention == 187_105_280
    mlp, router, expert, head = (3 * 7168 * 18432, 7168 * 256,
                                 3 * 7168 * 2048, 7168 * 16160)
    assert (mlp, expert) == (396_361_728, 44_040_192)
    assert family._per_row(config) == (attention, mlp, router, expert, head)
    # a layer outside its routed experts: the catalog's "about 233 M"
    assert attention + router + expert == 232_980_480
    # one token on this chip: five attentions, the dense MLP, and in each of
    # four routed layers the router, the shared expert and 8 x 16/256 = half
    # a routed expert; the head's slice; then the absorbed attention over its
    # context: 128 heads x (576 + 512) multiply-adds a position a layer
    context = 2700.0
    active = 5 * attention + mlp + 4 * (router + 1.5 * expert) + head
    assert active == pytest.approx(1.7193e9, rel=1e-4)
    assert family.forward_flops(config, 1, context) == pytest.approx(
        2 * active + 5 * 2 * context * 128 * (576 + 512))
    assert family.forward_flops(config, 1, context) / 1e9 == pytest.approx(
        7.199, abs=0.01)
    # the latent kernel: a position's row once, 1,152 bytes; the issue's
    # 2 x 128 x 1,088 FLOP: 242 FLOP a byte against the v5e's 240
    assert family.latent_row(config) == 576
    assert family.latent_attention_bytes(config, 1) == 1152
    assert family.latent_attention_flops(config, 1) == 2 * 128 * 1088
    assert family.latent_attention_flops(config, 1) / 1152 == \
        pytest.approx(241.8, abs=0.1)
    # the prefill's plain attention at a rung: 128 x S^2 / 2 x (192 + 128) x 2
    assert family.prefill_attention_flops(config, 4096) == \
        128 * 4096 ** 2 / 2 * 320 * 2
    assert family.prefill_attention_bytes(config, 4096) == \
        128 * 4096 * (192 + 192 + 128 + 128) * 2
    # a step's routed product in one layer: 64 rows x 8 / 16 = 32 pairs here;
    # 16 x (1 - (31/32)^64) = 13.9 of the 16 held experts draw a row
    assert family.expert_flops(config, 64) == 2 * 32 * expert
    drawn = 16 * (1 - (1 - 8 / 256) ** 64)
    assert drawn == pytest.approx(13.90, abs=0.01)
    assert family.expert_bytes(config, 64) == pytest.approx(
        2 * (drawn * expert + 32 * (2 * 7168 + 2 * 2048)))
    assert family.expert_bytes(config, 64) / 1e9 == pytest.approx(
        1.2256, abs=1e-3)


def test_the_kernels_names_in_a_trace():
    _, config = harness.load_cell(CELL)
    assert family.expert_ops(config, 64) == {
        "custom-call[tpu_custom_call] -> f32[512,2048]": 2,
        "custom-call[tpu_custom_call] -> f32[512,7168]": 1}
    # a prefill's passes hold 2,048 pairs whatever its rows
    assert set(family.expert_ops(config, 4096)) == {
        "custom-call[tpu_custom_call] -> f32[2048,2048]",
        "custom-call[tpu_custom_call] -> f32[2048,7168]"}
    assert family.latent_attention_op(config, 64) == (
        "custom-call[tpu_custom_call] -> (f32[64,1,128,512], "
        "f32[64,1,128,1], f32[64,1,128,1])")
    assert len(family.latent_attention_op(config, 64)) <= 96   # not cut
    from mxnet_tpu.serving import bucketing
    rungs = bucketing.seq_buckets(5120)
    assert rungs == (16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 5120)
    assert family.prefill_attention_ops(config, rungs) == {
        "custom-call[tpu_custom_call] -> (bf16[128,%d,128], "
        "f32[128,%d,128])" % (S, S): S for S in (512, 1024, 2048, 4096, 5120)}


# ---------------------------------------------------------------------------
# the readers, on hand-made runs
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
            "attrs": {"rows": 64, "bucket": 64, **attrs}}


def hand_made_run():
    """Two steps and one prefill in a window of one second: each step runs
    the latent kernel five times for 0.5 ms, the prefill the flash kernel at
    the 2,048-row rung five times for 4 ms."""
    _, config = harness.load_cell(CELL)
    ms = 1e6
    latent = "%c = (f32[64,1,128,512]{3,2,1,0}, f32[64,1,128,1]{3,2,1,0}, " \
        "f32[64,1,128,1]{3,2,1,0}) custom-call(%a), " \
        'custom_call_target="tpu_custom_call"'
    flash = "%f = (bf16[128,2048,128]{2,1,0}, f32[128,2048,128]{2,1,0}) " \
        'custom-call(%a), custom_call_target="tpu_custom_call"'
    other = "%m = bf16[64,7168]{1,0} fusion(%a), kind=kOutput"
    modules = [["jit_decode(1)", 100 * ms, 20 * ms],
               ["jit_prefill(2)", 130 * ms, 60 * ms],
               ["jit_decode(1)", 200 * ms, 20 * ms]]
    ops = []
    for start in (100, 200):
        ops += [[latent, (start + 2 * i) * ms, 0.5 * ms] for i in range(5)]
        ops.append([other, (start + 12) * ms, 7.5 * ms])
    ops += [[flash, (130 + 10 * i) * ms, 4 * ms] for i in range(5)]
    ops.append([other, 185 * ms, 5 * ms])
    trace = {"devices": {0: {"modules": modules, "ops": ops}},
             "spans": [[xplane.SPAN_PREFIX + "window", 0.0, 1000 * ms]]}
    rungs = family.prefill_attention_ops(config, (1024, 2048))
    return {
        "trace": trace, "trace_summary": xplane.summary(trace),
        "device_kind": "TPU v5 lite", "chips": 1,
        "latent_attention_op": family.latent_attention_op(config, 64),
        "latent_flops_per_position": 5 * 2 * 128 * 1088,
        "latent_bytes_per_position": 5 * 1152,
        "prefill_attention_ops": {
            label: (family.prefill_attention_flops(config, S),
                    family.prefill_attention_bytes(config, S))
            for label, S in rungs.items()},
        "traced_window_host_s": (50.0, 51.0)}


def test_latent_attention_roofline_counts_a_live_position_once(ring):
    run = hand_made_run()
    # the steps of the window attended to 160,000 and 180,000 positions; the
    # ring also holds steps from before the window, which are left out
    ring.extend([step_span(0, 49.0, ctx_live=10), step_span(1, 50.2,
                                                            ctx_live=160_000),
                 step_span(2, 50.6, ctx_live=180_000)])
    got = READERS["latent_attention_roofline_pct.decode"].read(run)
    # 170,000 positions x 5 layers: 1,152 B each is 1.196 ms at 819 GB/s,
    # 278,528 FLOP each 1.202 ms at 197 TFLOP/s: the ridge; the kernel took
    # 2.5 ms a step
    least = max(170_000 * 5 * 1152 / 819e9, 170_000 * 5 * 278_528 / 197e12)
    assert least == pytest.approx(1.2018e-3, rel=1e-3)
    assert got == pytest.approx(100 * least / 2.5e-3)
    # a kernel that read the pool as K and again as V would take twice the
    # time for the bytes counted once
    assert got < 50
    # nothing to read: no trace, no kernel of that name, no step in the ring
    assert READERS["latent_attention_roofline_pct.decode"].read(
        {k: v for k, v in run.items() if k != "trace"}) is None
    assert READERS["latent_attention_roofline_pct.decode"].read(
        {**run, "latent_attention_op": "custom-call -> f32[1]"}) is None
    del ring[:]
    assert READERS["latent_attention_roofline_pct.decode"].read(run) is None


def test_prefill_attention_roofline_reads_each_call_at_its_rung():
    run = hand_made_run()
    flops, nbytes = run["prefill_attention_ops"][
        "custom-call[tpu_custom_call] -> (bf16[128,2048,128], "
        "f32[128,2048,128])"]
    # 128 x 2048^2 / 2 x 320 x 2 = 172 GFLOP: 0.87 ms at the peak, the bytes
    # 0.41 ms; each call took 4 ms
    assert flops / 197e12 == pytest.approx(0.872e-3, rel=1e-3)
    assert nbytes / 819e9 < flops / 197e12
    assert READERS["prefill_attention_roofline_pct.decode"].read(run) == \
        pytest.approx(100 * (flops / 197e12) / 4e-3)
    # the same calls inside a step's program are not a prefill's
    as_steps = {**run["trace"], "devices": {0: {
        "ops": run["trace"]["devices"][0]["ops"],
        "modules": [[n.replace("jit_prefill", "jit_decode"), t, d]
                    for n, t, d in run["trace"]["devices"][0]["modules"]]}}}
    assert READERS["prefill_attention_roofline_pct.decode"].read(
        {**run, "trace": as_steps}) is None


def test_prefill_busy_share_is_the_prefill_modules_over_the_busy_time():
    run = hand_made_run()
    busy_s = run["trace_summary"]["busy_s"][0]
    assert busy_s == pytest.approx(2 * (5 * 0.5e-3 + 7.5e-3) + 5 * 4e-3
                                   + 5e-3)
    assert READERS["prefill_busy_share_pct.decode"].read(run) == \
        pytest.approx(100 * 60e-3 / busy_s)


def test_a_run_without_what_they_read_reports_none_of_them(ring):
    """The other decode cells' runs, and a parent that lacks the program's
    part: the driver's keys are not there, and nothing is raised."""
    ring.extend(step_span(i, 50.0 + i / 100, ctx_live=1000)
                for i in range(40))
    run = hand_made_run()
    bare = {k: run[k] for k in ("trace", "trace_summary", "device_kind",
                                "chips")}
    for name in NEW:
        assert READERS[name].read(bare) is None, name
        assert READERS[name].read({"device_kind": "cpu"}) is None, name
        assert READERS[name].KINDS == ("decode",)
        assert READERS[name].MOVES == "decode_tokens_per_s"
        assert READERS[name].UNIT == "%"


def test_the_fixtures_cut_keeps_what_the_kernels_readers_read():
    """``_kernels.excerpt`` drops every op but the Pallas kernels inside the
    first step programs and the prefill programs; the kernels' readers read
    the same from the cut as from the whole."""
    from chipbench.layer_metrics import _kernels
    run = hand_made_run()
    cut = _kernels.excerpt(run["trace"], steps=1)
    ops = cut["devices"]["0"]["ops"]
    assert len(ops) == 5 + 5             # one step's latent calls, the flash's
    assert all("tpu_custom_call" in text for text, _, _ in ops)
    assert cut["devices"]["0"]["modules"] == \
        run["trace"]["devices"][0]["modules"]
    trace = {"devices": {0: cut["devices"]["0"]}, "spans": cut["spans"]}
    again = {**run, "trace": trace, "trace_summary": xplane.summary(trace)}
    name = "prefill_attention_roofline_pct.decode"
    assert READERS[name].read(again) == READERS[name].read(run)


# ---------------------------------------------------------------------------
# the readers, on the trace kept from the builder's chip run
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    """``trace_deepseek_v3_v5e.json``: the cell's traced run on the chip
    (PR 32, seed 2147484003) as ``layer_metrics/_kernels.excerpt`` cut it:
    every module of the window and, of the ops, the Pallas kernels inside
    the first three step programs and inside every prefill program."""
    with open(os.path.join(FIXTURES, "trace_deepseek_v3_v5e.json")) as f:
        raw = json.load(f)
    dev = raw["devices"]["0"]
    trace = {"devices": {0: dev}, "spans": raw["spans"]}
    _, config = harness.load_cell(CELL)
    from mxnet_tpu.serving import bucketing
    rungs = family.prefill_attention_ops(config, bucketing.seq_buckets(5120))
    return {
        "trace": trace, "trace_summary": xplane.summary(trace),
        "device_kind": "TPU v5 lite", "chips": 1,
        "expert_ops": family.expert_ops(config, 64),
        "expert_flops": family.expert_flops(config, 64),
        "expert_bytes": family.expert_bytes(config, 64),
        "latent_attention_op": family.latent_attention_op(config, 64),
        "latent_flops_per_position": 5 * family.latent_attention_flops(
            config, 1),
        "latent_bytes_per_position": 5 * family.latent_attention_bytes(
            config, 1),
        "prefill_attention_ops": {
            label: (family.prefill_attention_flops(config, S),
                    family.prefill_attention_bytes(config, S))
            for label, S in rungs.items()},
        "traced_window_host_s": (50.0, 53.0)}


def test_the_recorded_trace_names_the_kernels_as_the_family_says(recorded):
    """What the chip's trace prints pins the names the readers look for: in a
    step program five calls of the latent kernel and, in each of four routed
    layers, two grouped matmuls of (512, 2048) and one of (512, 7168); in a
    prefill program five calls of the flash kernel at its rung."""
    import collections
    from chipbench.layer_metrics import _kernels
    run = recorded
    steps, ops = _kernels.inside_modules(run, "jit_decode")
    first = collections.Counter(name for name, at, _ in ops if at == 0)
    assert first == {run["latent_attention_op"]: 5,
                     "custom-call[tpu_custom_call] -> f32[512,2048]": 8,
                     "custom-call[tpu_custom_call] -> f32[512,7168]": 4}
    prefills, ops = _kernels.inside_modules(run, "jit_prefill")
    assert len(prefills) >= 3
    for at in range(len(prefills)):
        flash = [name for name, i, _ in ops
                 if i == at and name in run["prefill_attention_ops"]]
        assert len(flash) == 5 and len(set(flash)) == 1


def test_the_readers_on_the_recorded_trace(recorded, ring):
    run = recorded
    # the steps of that window attended to some 139,000 cached positions
    ring.extend(step_span(i, 50.1 + i / 50, ctx_live=139_000)
                for i in range(40))
    latent = READERS["latent_attention_roofline_pct.decode"].read(run)
    assert 15 < latent < 50              # bound by the MXU's 128-row passes
    flash = READERS["prefill_attention_roofline_pct.decode"].read(run)
    assert 25 < flash < 60
    experts = READERS["expert_ffn_roofline_pct.decode"].read(run)
    assert 70 < experts < 100
