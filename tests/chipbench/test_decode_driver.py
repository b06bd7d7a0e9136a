"""What the closed-loop decode driver takes from a cell and from a family it
has not met, on stubs (no model, no endpoint), and what decides ``correct``:
the family's own check where it brings one, the causal check otherwise, a
broken timed path, and the lower-precision control (chipbench/control.py)."""
import json
import sys
import threading
import types

import numpy as onp
import pytest

from chipbench import control, harness
from chipbench.drivers import decode_closed

VOCAB = 50


# ---------------------------------------------------------------------------
# stubs: a server that answers at once, a family with no model behind it
# ---------------------------------------------------------------------------
class StubStream:
    """What ``server.generate`` hands back; ``steps`` stands for whatever a
    system records of a generation step that is not one token."""

    def __init__(self, tokens, steps):
        self.tokens, self.steps = tokens, steps

    def result(self, timeout=None):
        return list(self.tokens)


class StubServer:
    calls = []                           # the keyword arguments of every call

    def register_generator(self, eng):
        pass

    def start(self):
        pass

    def stop(self, drain=True):
        pass

    def generate(self, name, prompt, **kwargs):
        StubServer.calls.append(kwargs)
        # "block" answers: two tokens a step, the prompt's last id counted up
        tokens = [(prompt[-1] + 1 + j) % VOCAB
                  for j in range(kwargs["max_new_tokens"])]
        for tok in tokens:
            kwargs["on_token"](tok)
        threading.Event().wait(0.002)    # a closed loop needs a turn-round
        return StubStream(tokens, steps=[tokens[i:i + 2]
                                         for i in range(0, len(tokens), 2)])


class StubEndpoint:
    prefill_buckets = decode_buckets = (1,)

    def __init__(self, name, lm, **sizes):
        self.stats = types.SimpleNamespace(snapshot=lambda: {
            "counters": {"compiles": 3, "steps": 7}})


def stub_family(**functions):
    return types.SimpleNamespace(build_lm=lambda config, seed: "weights",
                                 **functions)


@pytest.fixture
def run_stubbed(monkeypatch, capsys):
    """Runs ``decode_closed.run`` on the stubs for a rehearsal-sized cell
    with ``cell_extra`` laid over it; returns (run, lines printed)."""
    from mxnet_tpu import serving
    monkeypatch.setattr(serving, "InferenceServer", StubServer)
    monkeypatch.setattr(serving, "DecodeEndpoint", StubEndpoint)
    StubServer.calls = []

    def go(family, **cell_extra):
        bench = harness.Bench(harness.parse_args(
            ["--workload", "gpt1.decode_chat", "--seed", str(2**31 + 7),
             "--seconds", "0.2", "--rehearse"]))
        bench.start_jax()
        bench.cell = {**bench.cell, "warmup_seconds": 0.05, **cell_extra}
        bench.config = {**bench.config, "vocab_size": VOCAB}
        bench.family = lambda: family
        run = decode_closed.run(bench)
        return run, [json.loads(l) for l in
                     capsys.readouterr().out.splitlines()]
    return go


# ---------------------------------------------------------------------------
# the cell's ``generate`` group
# ---------------------------------------------------------------------------
def test_a_cells_generate_group_reaches_server_generate(run_stubbed):
    family = stub_family(check_requests=lambda *a: (True, {}))
    run_stubbed(family, generate={"denoise_steps": 4, "tenant": "batch"})
    assert StubServer.calls
    for kwargs in StubServer.calls:
        assert set(kwargs) == {"max_new_tokens", "on_token", "denoise_steps",
                               "tenant"}
        assert (kwargs["denoise_steps"], kwargs["tenant"]) == (4, "batch")


def test_without_the_group_the_call_is_as_it_was(run_stubbed):
    assert "generate" not in harness.load_cell("gpt1.decode_chat")[0]
    run_stubbed(stub_family(check_requests=lambda *a: (True, {})))
    assert StubServer.calls
    assert all(set(k) == {"max_new_tokens", "on_token"}
               for k in StubServer.calls)


# ---------------------------------------------------------------------------
# the family brings the answers' check
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("verdict", [True, False])
def test_a_familys_check_decides_correct_and_sees_the_streams(
        run_stubbed, verdict):
    handed = {}

    def check_requests(bench, lm, good, vocab):
        handed.update(lm=lm, good=good, vocab=vocab, cell=bench.name)
        return verdict, {"blocks_checked": len(good), "compared": {
            "worst_step_gap": {"value": 0.25, "limit": 0.5}}}

    # a reference_logits that would fail every token: it is not asked
    family = stub_family(
        check_requests=check_requests,
        reference_logits=lambda lm, config, toks: onp.zeros(
            toks.shape + (VOCAB,), onp.float32) - toks[..., None])
    run, lines = run_stubbed(family)
    assert run["correct"] is verdict and run["failed"] == 0
    assert (handed["lm"], handed["vocab"]) == ("weights", VOCAB)
    assert len(handed["good"]) == run["attempted"] > 0
    for req in handed["good"]:           # what the check needs is still there
        assert isinstance(req.stream, StubStream)
        assert [t for step in req.stream.steps for t in step] == req.tokens
        assert len(req.tokens) == req.budget and req.prompt
    # its numbers stand beside the driver's own, each with its limit
    assert run["compared"]["worst_step_gap"] == {"value": 0.25, "limit": 0.5}
    assert run["compared"]["failed_requests"] == {"value": 0, "limit": 0}
    assert run["compared"]["compiles_after_warmup"]["limit"] == 0
    check = next(l for l in lines if "check" in l)
    assert check["check"]["answers"] is verdict
    assert check["blocks_checked"] == run["attempted"]
    assert "worst_logit_deficit" not in check


def _logits_that_put_first(shift):
    """A reference under which the stub server's answers (the last id
    counted up) lead by one logit at every position, shifted by ``shift``
    ids: 0 agrees with the server, 1 puts another token first."""
    def reference_logits(lm, config, toks):
        logits = onp.zeros(toks.shape + (VOCAB,), onp.float32)
        nxt = (toks + 1 + shift) % VOCAB
        onp.put_along_axis(logits, nxt[..., None], 1.0, -1)
        return logits
    return reference_logits


@pytest.mark.parametrize("shift, correct, worst", [(0, True, 0.0),
                                                   (1, False, 1.0)])
def test_without_one_the_causal_check_runs(run_stubbed, shift, correct,
                                           worst):
    family = stub_family(reference_logits=_logits_that_put_first(shift))
    run, lines = run_stubbed(family)
    assert run["correct"] is correct
    check = next(l for l in lines if "check" in l)
    # the line and its keys are gpt1.decode_chat's
    for key in ("ids_in_range", "within_budget", "checked_requests",
                "checked_tokens", "worst_logit_deficit", "logit_tolerance",
                "compiles_after_warmup", "errors"):
        assert key in check
    assert check["worst_logit_deficit"] == worst
    assert check["checked_requests"] == 4 and check["checked_tokens"] > 0
    assert run["compared"]["worst_logit_deficit"] == {
        "value": worst, "limit": check["logit_tolerance"]}


def test_the_sample_holds_the_longest_finished_sequence(run_stubbed):
    seen = {}

    def check_requests(bench, lm, good, vocab):
        picks, toks = decode_closed.sampled_rows(bench, good)
        seen.update(picks=list(picks), good=good, toks=toks)
        return True, {}

    run_stubbed(stub_family(check_requests=check_requests))
    total = [len(r.prompt) + len(r.tokens) for r in seen["good"]]
    assert max(total) in [total[i] for i in seen["picks"]]
    assert len(set(seen["picks"])) == len(seen["picks"]) == 4
    for row, i in enumerate(seen["picks"]):
        seq = seen["good"][i].prompt + seen["good"][i].tokens
        assert list(seen["toks"][row, :len(seq)]) == seq
        assert not seen["toks"][row, len(seq):].any()


def test_logits_come_in_blocks_of_one_shape():
    toks = onp.arange(10 * 6, dtype=onp.int32).reshape(10, 6)
    shapes, rows = [], []
    for at, block in decode_closed.logits_in_blocks(
            lambda t: (shapes.append(t.shape), t[..., None] * 1.0)[1], toks):
        rows.extend(block[:len(toks) - at, :, 0].tolist())
    assert shapes == [(decode_closed.CHECK_ROWS, 6)] * 3
    assert rows == toks.tolist()


# ---------------------------------------------------------------------------
# a driver the harness has not met
# ---------------------------------------------------------------------------
def test_a_decode_driver_under_another_name_gets_the_decode_readers(
        capsys, monkeypatch, tmp_path):
    """Readers match a kind of run: a driver of kind ``decode`` that no
    reader names reports ``compile_s`` and the decode readers."""
    stub = types.ModuleType("chipbench.drivers.stub_decode_open")
    stub.KIND, stub.END_TO_END = "decode", decode_closed.END_TO_END
    stub.run = decode_closed.run
    monkeypatch.setitem(sys.modules, stub.__name__, stub)
    load_cell = harness.load_cell

    def load_stub_cell(name, rehearse=False):
        cell, config = load_cell("gpt1.decode_chat", rehearse)
        return {**cell, "driver": "stub_decode_open"}, config

    monkeypatch.setattr(harness, "load_cell", load_stub_cell)
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    assert harness.main(["--workload", "stub.cell", "--seed", "5",
                         "--seconds", "0.5", "--trace", "1",
                         "--rehearse"]) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last["correct"] is True
    names = {n[len(harness.REHEARSAL_PREFIX):] for n in last["metrics"]}
    assert {"compile_s", "ttft_p95_ms", "tpot_p95_ms",
            "batch_occupancy_pct.decode", "sched_host_ms_per_step.decode",
            "queue_wait_p95_ms.decode"} <= names
    assert not any(n.endswith(".train") for n in names)


def test_a_kind_no_reader_knows_reads_nothing(monkeypatch):
    from mxnet_tpu.telemetry import flight
    monkeypatch.setattr(flight, "recent_spans", lambda: [])
    spent = {"setup_compile": {"trace_s": 1.0, "lower_s": 1.0,
                               "backend_s": 1.0}}
    assert harness.read_layer_metrics(spent, "serve") == {}
    # a decode run that lacks what a reader reads: that reader says nothing
    assert harness.read_layer_metrics({**spent, "trace": None}, "decode") == {
        "compile_s": {"value": 3.0, "unit": "s"}}


# ---------------------------------------------------------------------------
# the timed path broken underneath, and the control
# ---------------------------------------------------------------------------
def test_a_token_altered_where_it_is_produced_is_not_correct(
        capfd, monkeypatch):
    """The rest of a run (no look for a chip: a rehearsal) with every fifth
    token altered as the scheduler hands it to the stream."""
    from mxnet_tpu.serving.generate.streams import TokenStream
    put, count = TokenStream.put, iter(range(10**9))

    def altered_put(self, tok):
        return put(self, tok + 1 if next(count) % 5 == 4 and tok < 90
                   else tok)

    monkeypatch.setattr(TokenStream, "put", altered_put)
    assert harness.main(["--workload", "gpt1.decode_long", "--seed",
                         str(2**31 + 133), "--seconds", "0.5", "--trace",
                         "0", "--rehearse"]) == 0
    out, err = capfd.readouterr()
    last = json.loads(out.splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    worst = last["compared"]["worst_logit_deficit"]
    assert worst["value"] > worst["limit"]
    assert list(last)[-1] == "compared"
    # the numbers compared are the last lines of standard error
    tail = err.strip().splitlines()[-len(last["compared"]):]
    assert [l.split()[1] for l in tail] == list(last["compared"])
    assert tail[0] == (f"compared worst_logit_deficit = {worst['value']!r} "
                       f"limit {worst['limit']!r}")


class _Bench:
    """As much of a run as ``sampled_rows`` and the control read."""
    seed = 2**31 + 9

    def __init__(self, cell, config):
        self.cell, self.config = cell, config


@pytest.fixture(scope="module")
def served():
    """A TransformerLM wide enough for bfloat16 to change its mind (256
    units, 8,192 ids), and 16 finished requests whose answers are the
    system's own greedy tokens from its float32 forward."""
    import mxnet_tpu as mx
    from chipbench.models import transformer_lm
    cell, config = harness.load_cell("gpt1.decode_long", rehearse=True)
    config = {**config, "n_embd": 256, "n_head": 4, "vocab_size": 8192}
    cell = {**cell, "checked_requests": 16, "logit_tolerance": 2e-4}
    lm = transformer_lm.build_lm(config, 3)
    rng = onp.random.default_rng(3)
    toks = onp.zeros((16, 24 + 32), "int32")
    toks[:, :24] = rng.integers(0, config["vocab_size"], (16, 24))
    for at in range(24, 24 + 32):        # causal: one shape, one compile
        logits = lm(mx.nd.array(toks, dtype="int32")).asnumpy()
        toks[:, at] = logits[:, at - 1].argmax(-1)
    done = []
    for row in toks.tolist():
        req = decode_closed._Request(row[:24], 32)
        req.tokens = row[24:]
        done.append(req)
    return _Bench(cell, config), lm, transformer_lm, done


def test_the_control_comes_out_not_correct(served):
    """The plain reference in bfloat16 in the program's place puts another
    token first often enough that its widest gap passes the limit, while the
    system's own float32 answers stay far inside it."""
    bench, lm, family, done = served
    ok, seen = decode_closed._check_requests(bench, lm, family, done,
                                             bench.config["vocab_size"])
    limit = bench.cell["logit_tolerance"]
    assert ok and seen["checked_tokens"] == 16 * 32
    assert seen["worst_logit_deficit"] <= limit / 10
    reading = control.control_reading(bench, lm, family, done)
    assert reading["positions"] == 16 * 32
    assert reading["positions_with_another_first_token"] >= 3
    assert reading["worst_logit_deficit"] >= 3 * limit
    assert reading["comes_out_not_correct"] is True


def test_the_control_runs_beside_a_cells_own_check(capsys):
    assert control.main(["--workload", "gpt1.decode_long", "--seed",
                         str(2**31 + 135), "--seconds", "0.5", "--trace",
                         "0", "--rehearse"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    reading = next(l["control"] for l in lines if "control" in l)
    check = next(l for l in lines if "check" in l)
    assert reading["positions"] == check["checked_tokens"] > 0
    assert reading["logit_tolerance"] == check["logit_tolerance"]
    assert lines[-1]["correct"] is True
    assert decode_closed._check_requests.__name__ == "_check_requests"
