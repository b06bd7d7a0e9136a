"""Every cell the benchmark has files for, rehearsed tiny on the CPU through
the command's own entry point; the rules a run keeps whatever the cell."""
import glob
import importlib
import json
import os
import re

import pytest

import chipbench
from chipbench import harness

ROOT = os.path.dirname(os.path.abspath(chipbench.__file__))
CELLS = sorted(os.path.basename(p)[:-len(".json")]
               for p in glob.glob(os.path.join(ROOT, "workloads", "*.json")))
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _rehearse(capsys, cell, trace):
    assert harness.main(["--workload", cell, "--seed", str(2**31 + 129),
                         "--seconds", "0.5", "--trace", str(trace),
                         "--rehearse"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    return lines[:-1], lines[-1]


def test_there_are_cells():
    assert {"bert_base.pretrain_s128", "gpt1.decode_chat", "gpt1.decode_long",
            "resnet50_v1.train_b128", "bert_base.pretrain_s128_dp4"} \
        <= set(CELLS)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_and_prints_the_contracts_last_line(capsys, cell, trace):
    earlier, last = _rehearse(capsys, cell, trace)
    # no device trace on the CPU; what was compared comes last
    assert list(last) == CONTRACT_KEYS + ["compared"]
    for row in last["compared"].values():
        assert set(row) == {"value", "limit"}
    assert last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": last["device"]["count"],
                              "memory_peak_bytes": 0}
    # a CPU number never carries a device metric's name
    assert last["metrics"]
    for name, metric in last["metrics"].items():
        assert name.startswith(harness.REHEARSAL_PREFIX)
        assert set(metric) == {"value", "unit"}
        assert isinstance(metric["value"], float)
    names = {n[len(harness.REHEARSAL_PREFIX):] for n in last["metrics"]}
    if trace:
        assert "compile_s" in names and "setup_s" not in names
    else:
        assert "setup_s" in names and len(names) >= 2
    # every earlier line names the cell and the device it ran on
    assert earlier
    for row in earlier:
        assert row["workload"] == cell
        assert (row["platform"], row["device_kind"]) == ("cpu", "cpu")


def test_measuring_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", CELLS[0], "--seed", "1",
                      "--seconds", "0.5", "--trace", "0"])
    assert "not the TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""      # no result line


def test_a_cell_that_needs_more_chips_than_there_are_is_refused(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:2])
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", "bert_base.pretrain_s128_dp4",
                      "--seed", "1", "--seconds", "0.5", "--trace", "0",
                      "--rehearse"])
    assert "needs 4 chip" in str(e.value.code)


@pytest.mark.parametrize("name, prompts, outputs, median", [
    ("gpt1.decode_chat", (16, 384), (8, 128), (80, 112)),     # about 96
    ("gpt1.decode_long", (384, 480), (16, 32), (420, 444)),   # about 432
])
def test_every_seed_gets_the_same_request_sizes_in_another_order(
        name, prompts, outputs, median):
    from chipbench.drivers import decode_closed
    cell, config = harness.load_cell(name)
    a = decode_closed.make_requests(cell, config["vocab_size"], 1)
    b = decode_closed.make_requests(cell, config["vocab_size"], 2)
    sizes = lambda reqs: sorted((len(p), n) for p, n in reqs)
    assert sizes(a) == sizes(b) and len(a) == cell["request_pool"]
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    assert a[0][0] != b[0][0]
    lens = [len(p) for p, _ in a]
    outs = [n for _, n in a]
    assert min(lens) >= prompts[0] and max(lens) <= prompts[1]
    assert min(outs) >= outputs[0] and max(outs) <= outputs[1]
    assert max(l + o for l, o in zip(lens, outs)) <= cell["max_seq_len"]
    assert median[0] <= sorted(lens)[len(lens) // 2] <= median[1]


def test_the_two_decode_cells_differ_in_traffic_alone():
    chat, long_ = (harness.load_cell(n)[0] for n in
                   ("gpt1.decode_chat", "gpt1.decode_long"))
    same = ("config", "driver", "chips", "clients", "max_batch_size",
            "max_seq_len", "num_pages", "request_pool", "warmup_seconds",
            "trace_seconds", "request_timeout_s", "logit_tolerance")
    assert [chat[k] for k in same] == [long_[k] for k in same]
    assert chat["pool_seed"] != long_["pool_seed"]
    # buckets, page size and batch_timeout stay at the program's defaults
    assert not {"prefill_buckets", "decode_buckets", "page_size",
                "batch_timeout", "generate"} & (set(chat) | set(long_))


def test_layer_metrics_are_one_file_each_and_name_a_kind():
    modules = list(harness.layer_metric_modules())
    assert len(modules) >= 9
    kinds = set()
    for path in glob.glob(os.path.join(ROOT, "drivers", "*.py")):
        name = os.path.basename(path)[:-3]
        if name != "__init__":
            kinds.add(importlib.import_module(
                f"chipbench.drivers.{name}").KIND)
    assert kinds == {"train", "decode"}
    for m in modules:
        assert m.UNIT and m.LAYER and m.MOVES and callable(m.read)
        assert m.KINDS and set(m.KINDS) <= kinds
        assert not hasattr(m, "DRIVERS")
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", m.NAME)
    by_name = {m.NAME: m for m in modules}
    assert set(by_name["compile_s"].KINDS) == kinds       # every kind of run


def test_nothing_describes_a_tpu_topology_or_runs_jax_at_import():
    """Importing chipbench touches no backend: the words that would are only
    inside functions (checked on the source, so that no worker has to load
    the TPU's library to find out)."""
    for path in glob.glob(os.path.join(ROOT, "**", "*.py"), recursive=True):
        with open(path) as f:
            src = f.read()
        assert "get_topology_desc" not in src, path
        top_level = [l for l in src.splitlines()
                     if re.match(r"(import|from) (jax|mxnet_tpu)", l)]
        if os.path.relpath(path, ROOT).startswith("reference"):
            continue      # plain jax.numpy modules, imported inside functions
        assert not top_level, (path, top_level)
