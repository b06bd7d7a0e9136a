"""chipbench/xplane.py on a small recorded v5e trace (fixtures/, the first
ops of a traced bert_base.pretrain_s128 window) and on hand-made intervals."""
import json
import os

import pytest

from chipbench import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_bert_base_v5e.json")

# three op texts as XLA:TPU printed them in that trace
MATMUL_AS_CONVOLUTION = (
    "%fusion.3277 = bf16[32768,768]{1,0:T(8,128)(2,1)} fusion(bf16[30522,768]"
    "{1,0:T(8,128)(2,1)S(1)} %convert_element_type.7772, s32[32768]{0:T(1024)"
    "S(1)} %get-tuple-element.14715), kind=kOutput, calls=%fused_computation")
FUSION_FED_BY_CUSTOM_CALL = (
    "%convert_fusion.7 = bf16[512,768]{1,0:T(8,128)(2,1)S(1)} fusion(f32[512,"
    "768]{1,0:T(8,128)S(1)} %custom-call.172), kind=kLoop, "
    "calls=%fused_computation.12")
REAL_CUSTOM_CALL = (
    '%custom-call.125 = u64[2]{0:T(128)S(1)} custom-call(u32[2]{0:T(128)S(1)}'
    ' %get-tuple-element.14723, u32[2]{0:T(128)S(1)} %get-tuple-element.14724'
    '), custom_call_target="X64Combine"')
TUPLE_RESULT = (
    "%slice-start.180 = ((f32[512,768]{1,0:T(8,128)}), f32[128,768]{1,0:T(8,"
    "128)S(1)}, s32[]{:S(2)}) async-start(f32[512,768]{1,0:T(8,128)} "
    "%get-tuple-element.16491), calls=%async_computation.180")


@pytest.fixture(scope="module")
def trace():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.mark.parametrize("text, opcode, kind, target", [
    (MATMUL_AS_CONVOLUTION, "fusion", "kOutput", None),
    # PR 22's trap: the operand's name mentions a custom call, the op is a
    # loop fusion
    (FUSION_FED_BY_CUSTOM_CALL, "fusion", "kLoop", None),
    (REAL_CUSTOM_CALL, "custom-call", None, "X64Combine"),
    (TUPLE_RESULT, "async-start", None, None),
    ("%copy.1 = f32[10,157]{1,0:T(8,128)} copy(f32[10,157]{1,0:T(8,128)} "
     "%args_0_.1)", "copy", None, None),
    ("%all-reduce-done.3 = f32[768]{0} all-reduce-done(f32[768]{0} "
     "%all-reduce-start.3)", "all-reduce-done", None, None),
    ("jit_step_n(9812693698403077013)", "jit_step_n(9812693698403077013)",
     None, None),
])
def test_parse_op(text, opcode, kind, target):
    got = xplane.parse_op(text)
    assert (got[0], got[1], got[3]) == (opcode, kind, target)


def test_op_label_is_opcode_and_shape_without_layout():
    assert xplane.op_label(MATMUL_AS_CONVOLUTION) == \
        "fusion[kOutput] -> bf16[32768,768]"
    assert xplane.op_label(REAL_CUSTOM_CALL) == \
        "custom-call[X64Combine] -> u64[2]"
    assert len(xplane.op_label(TUPLE_RESULT * 3, width=40)) <= 40


@pytest.mark.parametrize("opcode, held", [
    ("all-reduce", True), ("all-reduce-done", True),
    ("all-reduce-start", False), ("reduce-scatter", True),
    ("collective-permute-done", True), ("all-gather-start", False),
    ("fusion", False), ("copy-done", False)])
def test_is_collective(opcode, held):
    assert xplane.is_collective(opcode) is held


def test_merge_and_seconds():
    merged = xplane.merge([(5, 7), (0, 2), (1, 3), (7, 8), (20, 21)])
    assert merged == [[0, 3], [5, 8], [20, 21]]
    assert xplane.seconds(merged) == pytest.approx(7e-9)


def _dev(ops, modules=()):
    return {"ops": [list(o) for o in ops], "modules": [list(m) for m in modules]}


def test_busy_union_skips_containers_and_clips_to_window():
    loop = "%while.4 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1), " \
           "condition=%cond, body=%body"
    a = "%copy.1 = f32[8]{0} copy(f32[8]{0} %x)"
    dev = _dev([(loop, 0, 1000), (a, 100, 100), (a, 150, 100), (a, 900, 300)])
    assert xplane.busy_intervals(dev) == [[100, 250], [900, 1200]]
    assert xplane.busy_intervals(dev, (200, 1000)) == [[200, 250], [900, 1000]]


def test_idle_gaps_go_to_the_span_that_covers_them():
    a = "%copy.1 = f32[8]{0} copy(f32[8]{0} %x)"
    dev = _dev([(a, 0, 10_000), (a, 110_000, 10_000), (a, 121_000, 79_000),
                (a, 500_000, 10_000)])
    trace = {"devices": {"0": dev}, "spans": [
        ["chipbench.window", 0, 600_000],
        ["chipbench.dispatch", 5_000, 100_000],
        ["chipbench.fetch", 400_000, 50_000]]}
    gaps = xplane.idle_gaps(trace, dev, (0, 600_000))
    assert gaps == pytest.approx({
        "dispatch": 100_000e-9,         # 10..110 us, under the dispatch span
        "between_ops": 1_000e-9,        # 120..121 us: under BETWEEN_OPS_NS
        "fetch": 300_000e-9,            # 200..500 us: fetch covers 50 of it
        "unattributed": 90_000e-9})     # 510..600 us: no span open
    assert sum(gaps.values()) + xplane.seconds(
        xplane.busy_intervals(dev, (0, 600_000))) == pytest.approx(600_000e-9)


def test_module_durations_by_function_name():
    dev = _dev([], [("jit_decode(123)", 0, 2e6), ("jit_decode(456)", 5e6, 4e6),
                    ("jit_prefill(9)", 9e6, 1e6), ("jit_decode(123)", 1e9, 1)])
    got = xplane.module_durations(dev, (0, 1e8))
    assert got == {"jit_decode": [0.002, 0.004], "jit_prefill": [0.001]}


# --- the recorded trace -----------------------------------------------------
def test_recorded_trace_has_one_chip_with_ops_modules_and_spans(trace):
    assert list(trace["devices"]) == ["0"]
    dev = trace["devices"]["0"]
    assert len(dev["ops"]) >= 200 and dev["modules"]
    assert any(s[0] == "chipbench.window" for s in trace["spans"])
    assert "jit_step_n" in xplane.module_durations(dev)


def test_recorded_trace_opcodes_are_hlo_opcodes(trace):
    """Every op of a real trace parses to a bare HLO opcode: lower-case words
    joined by dashes, nothing of the operand list left in it."""
    import re
    for text, _, _ in trace["devices"]["0"]["ops"]:
        opcode, kind, _, _ = xplane.parse_op(text)
        assert re.fullmatch(r"[a-z][a-z0-9]*(-[a-z0-9]+)*", opcode), text[:120]
        assert (kind is not None) == (opcode == "fusion"), text[:120]


def test_recorded_trace_custom_calls_are_not_matched_by_substring(trace):
    """The trap, on real text: ops that mention a custom call only through an
    operand's name are not custom calls."""
    texts = [t for t, _, _ in trace["devices"]["0"]["ops"]]
    mention = [t for t in texts if "custom-call" in t]
    real = [t for t in texts if xplane.parse_op(t)[0] == "custom-call"]
    assert real and len(mention) > len(real)
    assert all(xplane.parse_op(t)[3] for t in real)    # each names its target


def test_recorded_trace_busy_fits_its_window(trace):
    dev = trace["devices"]["0"]
    ops = dev["ops"]
    win = (min(s for _, s, _ in ops), max(s + d for _, s, d in ops))
    busy = xplane.seconds(xplane.busy_intervals(dev, win))
    assert 0 < busy <= (win[1] - win[0]) / 1e9
    top = xplane.top(xplane.op_seconds(dev, win), 10)
    assert len(top) <= 10 and top == sorted(top, key=lambda r: -r[1])
    assert not any(label.startswith("while") for label, _ in top)


def test_readers_and_breakdown_on_the_recorded_trace(trace, monkeypatch):
    """What a traced run on the chip does after its window, on the fixture."""
    from chipbench import harness
    from mxnet_tpu.telemetry import flight
    # the ring is the process's: an earlier test's train.step_n spans lie there
    monkeypatch.setattr(flight, "recent_spans", lambda: [])
    info = xplane.summary(trace)
    assert info["window_s"] == pytest.approx(3.506163991)
    run = {"trace": trace, "trace_summary": info, "chips": 1,
           "dispatch_s": [1.75, 1.74, 1.76], "steps_per_dispatch": 10,
           "samples_per_s": 1468.0, "flops_per_sample": 69.8e9,
           "device_kind": "TPU v5 lite",
           "setup_compile": {"trace_s": 6.0, "lower_s": 6.5, "backend_s": 3.5}}
    got = harness.read_layer_metrics(run, "train")
    assert set(got) == {"compile_s", "step_ms.train", "mfu_pct.train",
                        "device_idle_pct.train"}       # one chip: no collective
    assert got["step_ms.train"] == {"value": 175.0, "unit": "ms"}
    assert got["mfu_pct.train"]["value"] == pytest.approx(52.01, abs=0.01)
    assert got["compile_s"]["value"] == 16.0
    assert 0 < got["device_idle_pct.train"]["value"] <= 100
    shown = harness.breakdown(trace, info)
    assert set(shown) == {"device_ops", "idle_gaps"}
    assert 0 < len(shown["device_ops"]) <= 10 and shown["idle_gaps"]
    assert {name for name, _ in shown["idle_gaps"]} <= \
        {"dispatch", "fetch", "between_ops", "unattributed"}
    run["device_kind"] = "TPU v9"
    with pytest.raises(KeyError):
        harness.read_layer_metrics(run, "train")   # no peak, no default


def test_decode_readers_find_both_programs_in_a_recorded_decode_trace(
        monkeypatch):
    """fixtures/trace_gpt1_decode_v5e.json: the first ops, every module and
    span of a traced gpt1.decode_chat window."""
    from chipbench import harness
    from mxnet_tpu.telemetry import flight
    monkeypatch.setattr(flight, "recent_spans", lambda: [])
    with open(os.path.join(os.path.dirname(FIXTURE),
                           "trace_gpt1_decode_v5e.json")) as f:
        trace = json.load(f)
    info = xplane.summary(trace)
    mods = xplane.module_durations(trace["devices"]["0"], info["window"])
    assert set(mods) == {"jit_decode", "jit_prefill"}
    run = {"trace": trace, "trace_summary": info, "later_tokens": 15000,
           "decode_steps": 250, "max_batch_size": 64, "ttft_p95_ms": 165.0,
           "tpot_p95_ms": 118.0,
           "setup_compile": {"trace_s": 7.0, "lower_s": 2.0, "backend_s": 5.0}}
    got = harness.read_layer_metrics(run, "decode")
    assert set(got) == {
        "compile_s", "decode_step_ms.decode", "prefill_ms.decode",
        "batch_occupancy_pct.decode", "device_idle_pct.decode",
        "ttft_p95_ms", "tpot_p95_ms"}
    assert got["decode_step_ms.decode"]["value"] == pytest.approx(45.9, abs=0.5)
    assert 5 < got["prefill_ms.decode"]["value"] < \
        got["decode_step_ms.decode"]["value"]
    assert got["batch_occupancy_pct.decode"]["value"] == pytest.approx(93.75)
    assert any(s[0] == "chipbench.submit" for s in trace["spans"])
