"""Performance observability (ISSUE 11): compile ledger with StableHLO
fingerprints, HBM memory attribution with OOM post-mortems, and the
perf-regression sentinel.

Covers: fingerprint canonicalization and cross-subprocess stability, ledger
records from all three AOT compile sites (serving bucket, ParallelTrainStep,
instrumented eager jit), duplicate-fingerprint waste accounting (in-process
and seeded from another process's JSONL), the memstats holder registry
(sizers, weakref pruning, reconciliation residuals), the oom flight trigger
with ranked holder breakdown rendered by tools/flight_inspect.py, the EWMA
drift sentinel (fires on sustained regression, never on spikes), the
/compilez and /memz debug pages, and tools/compile_report.py.
"""
import gc
import io
import json
import os
import subprocess
import sys
import textwrap
from contextlib import redirect_stdout

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import config, nd, serving, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import compile_ledger, memstats, perf_sentinel
from mxnet_tpu.telemetry import debug_server as dbg
from mxnet_tpu.telemetry import flight
from mxnet_tpu.telemetry.slo import MONITOR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _import_tool(name):
    sys.path.insert(0, TOOLS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


@pytest.fixture(autouse=True)
def _clean_ledger_state():
    compile_ledger.reset()
    memstats.reset()
    perf_sentinel.SENTINEL.reset()
    yield
    compile_ledger.reset()
    memstats.reset()
    perf_sentinel.SENTINEL.reset()


def _small_net(seed=0, in_shape=(3, 8, 8)):
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(4, kernel_size=3, padding=1))
        net.add(nn.GlobalAvgPool2D())
        net.add(nn.Dense(4))
    net.initialize()
    net(nd.array(onp.random.randn(2, *in_shape).astype("float32")))
    return net


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def test_fingerprint_strips_location_metadata():
    a = 'module { func @f(%x: f32) loc("a.py":10:0) }\n#loc1 = loc("a.py")'
    b = 'module { func @f(%x: f32) loc("b.py":99:7) }\n#loc1 = loc("zz.py")'
    assert compile_ledger.fingerprint_text(a) == \
        compile_ledger.fingerprint_text(b)
    c = 'module { func @g(%x: f32) loc("a.py":10:0) }'
    assert compile_ledger.fingerprint_text(a) != \
        compile_ledger.fingerprint_text(c)


_SUBPROC_FP = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp
    import sys
    sys.path.insert(0, {repo!r})
    from mxnet_tpu.telemetry import compile_ledger

    def f(x, y):
        return jnp.tanh(x @ y) * 2.0 + y.sum()

    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32),
        jax.ShapeDtypeStruct((8, 8), jnp.float32))
    print(compile_ledger.fingerprint_text(lowered.as_text()))
""").format(repo=REPO)


def test_fingerprint_stable_across_subprocesses():
    """ACCEPTANCE: the same function lowered at the same avals in two fresh
    interpreters produces the identical content address (what a persistent
    executable cache would key on)."""
    fps = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _SUBPROC_FP],
                             capture_output=True, text=True, cwd=REPO)
        assert out.returncode == 0, out.stderr
        fps.append(out.stdout.strip().splitlines()[-1])
    assert fps[0] == fps[1] and len(fps[0]) == 64, fps


# ---------------------------------------------------------------------------
# ledger records / duplicate accounting
# ---------------------------------------------------------------------------

def test_lower_and_compile_emits_record_and_flags_duplicates():
    jfn = jax.jit(lambda x: x * 3.0)
    aval = jax.ShapeDtypeStruct((4,), jnp.float32)
    comp = compile_ledger.lower_and_compile(
        jfn, (aval,), site="serving_bucket", key={"endpoint": "e", "bucket": 4})
    assert comp(jnp.ones((4,))).tolist() == [3.0] * 4
    compile_ledger.lower_and_compile(jfn, (aval,), site="train_step", key={})
    recs = compile_ledger.recent()
    assert [r["site"] for r in recs] == ["serving_bucket", "train_step"]
    assert recs[0]["fingerprint"] == recs[1]["fingerprint"]
    assert not recs[0]["duplicate"] and recs[1]["duplicate"]
    assert recs[0]["key"] == {"endpoint": "e", "bucket": 4}
    assert recs[0]["lower_s"] >= 0 and recs[0]["compile_s"] > 0
    s = compile_ledger.summary()
    assert s["compiles"] == 2 and s["distinct_fingerprints"] == 1
    assert s["duplicates"] == 1 and s["dup_waste_s"] > 0


def test_ledger_jsonl_and_cross_process_dup_seeding(tmp_path):
    config.set("MXNET_COMPILE_LEDGER_DIR", str(tmp_path))
    try:
        jfn = jax.jit(lambda x: x - 1.0)
        aval = jax.ShapeDtypeStruct((3,), jnp.float32)
        compile_ledger.lower_and_compile(jfn, (aval,), site="train_step")
        rows = compile_ledger.read_ledger(str(tmp_path))
        assert len(rows) == 1 and rows[0]["site"] == "train_step"
        assert not rows[0]["duplicate"]
        fp = rows[0]["fingerprint"]

        # simulate a second process: forget in-memory state, keep the files
        compile_ledger.reset()
        compile_ledger.lower_and_compile(jfn, (aval,), site="train_step")
        rows = compile_ledger.read_ledger(str(tmp_path))
        assert len(rows) == 2
        assert rows[1]["fingerprint"] == fp
        assert rows[1]["duplicate"], \
            "fingerprint written by 'another process' must count as duplicate"
    finally:
        config.set("MXNET_COMPILE_LEDGER_DIR", "")


def test_serving_bucket_compiles_land_in_ledger():
    """ACCEPTANCE: every endpoint bucket executable emits one record with
    site=serving_bucket and an endpoint/bucket key."""
    net = _small_net(seed=3)
    ep = serving.ModelEndpoint("t_ledger", net, input_shapes=(3, 8, 8),
                               max_batch_size=4)
    try:
        ep.warmup()
        recs = [r for r in compile_ledger.recent()
                if r["site"] == "serving_bucket"
                and r["key"].get("endpoint") == "t_ledger"]
        assert {r["key"]["bucket"] for r in recs} == set(ep.buckets)
        assert all(r["fingerprint"] for r in recs)
        # distinct bucket shapes are distinct programs: no false duplicates
        assert not any(r["duplicate"] for r in recs)
        # and the endpoint registered memstats holders for params + execs
        names = {h["holder"] for h in memstats.holders()}
        assert "t_ledger.params" in names
        assert any(n.startswith("t_ledger.exec_b") for n in names)
    finally:
        serving.unregister("t_ledger")


def test_train_step_compile_lands_in_ledger():
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import loss as gloss
    # the ledgered AOT path is the param_format="auto" one, which needs
    # jax.experimental.layout.Format (absent from some jax builds — the
    # default-jit path stays unledgered by design)
    try:
        from jax.experimental.layout import Format, Layout  # noqa: F401
        has_auto = True
    except ImportError:
        has_auto = False
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.Dense(2))
    net.initialize()
    net(mx.nd.array(onp.zeros((2, 4), "float32")))
    mesh = parallel.make_mesh({"dp": 8})
    step = parallel.ParallelTrainStep(
        net, gloss.L2Loss(), mx.optimizer.SGD(learning_rate=0.01), mesh,
        param_format="auto" if has_auto else None)
    xs = onp.random.randn(16, 4).astype("float32")
    ys = onp.random.randn(16, 2).astype("float32")
    step(xs, ys)
    if has_auto:
        recs = [r for r in compile_ledger.recent()
                if r["site"] == "train_step"]
        assert recs and recs[0]["fingerprint"]
        assert recs[0]["key"]["mesh_devices"] == 8
    # the donated train state registered a live-sized memstats holder
    # (constructor-time, independent of param_format)
    rows = [h for h in memstats.holders()
            if h["subsystem"] == "train" and h["bytes"] > 0]
    assert rows, "train_step state holder missing"


def test_eager_jit_instrumentation_opt_in():
    reg = pytest.importorskip("mxnet_tpu.ops.registry")
    # default: no ledger dir -> eager stays uninstrumented
    assert not compile_ledger.eager_active()
    config.set("MXNET_COMPILE_LEDGER_EAGER", "1")
    try:
        assert compile_ledger.eager_active()
        reg._JIT_CACHE.clear()
        x = nd.array(onp.random.rand(5, 5).astype("float32"))
        y1 = nd.exp(x)
        recs = [r for r in compile_ledger.recent()
                if r["site"] == "eager_jit"]
        assert recs and recs[-1]["key"]["op"] == "exp"
        n = len(recs)
        y2 = nd.exp(x)   # same avals: cached AOT executable, no new record
        assert len([r for r in compile_ledger.recent()
                    if r["site"] == "eager_jit"]) == n
        onp.testing.assert_allclose(y1.asnumpy(), y2.asnumpy())
        onp.testing.assert_allclose(y1.asnumpy(),
                                    onp.exp(x.asnumpy()), rtol=1e-6)
        # autograd still works through the instrumented wrapper (Tracer
        # inputs fall through to the plain jit path)
        from mxnet_tpu import autograd
        g = nd.array(onp.ones((5, 5), "float32"))
        g.attach_grad()
        with autograd.record():
            out = nd.exp(g)
        out.backward()
        onp.testing.assert_allclose(g.grad.asnumpy(),
                                    onp.exp(g.asnumpy()), rtol=1e-6)
    finally:
        config.set("MXNET_COMPILE_LEDGER_EAGER", "auto")
        reg._JIT_CACHE.clear()


# ---------------------------------------------------------------------------
# memstats
# ---------------------------------------------------------------------------

def test_memstats_reconcile_and_residual():
    class Owner:
        pass
    o = Owner()
    memstats.register("serving", "ep.params", nbytes=1_000, device="tpu:0",
                      owner=o)
    memstats.register("train", "state", owner=o, sizer=lambda _: 2_000)
    stats = {"tpu:0": {"bytes_in_use": 5_000, "peak_bytes_in_use": 6_000}}
    r = memstats.reconcile(device_stats=stats)
    assert r["tpu:0"]["attributed"] == 1_000
    assert r["tpu:0"]["unattributed"] == 4_000
    assert r["tpu:0"]["peak_bytes_in_use"] == 6_000
    # holders with no matching reported device stay honest: a pseudo-device,
    # never smeared over real residuals
    assert r["unassigned"]["attributed"] == 2_000
    bd = memstats.breakdown(device_stats=stats)
    assert bd["attributed_bytes"] == 3_000
    assert bd["holders"][0]["bytes"] == 2_000   # ranked desc


def test_memstats_weakref_pruning_and_sizer_liveness():
    class Owner:
        n = 100

    o = Owner()
    memstats.register("t", "live", owner=o, sizer=lambda ow: ow.n)
    assert memstats.holders()[0]["bytes"] == 100
    o.n = 900                       # sizer re-evaluates at every reconcile
    row = memstats.holders()[0]
    assert row["bytes"] == 900 and row["peak_bytes"] == 900
    del o
    gc.collect()
    assert memstats.holders() == [], "dead owner must prune its holder"


def test_memstats_nbytes_of_trees():
    x = onp.zeros((4, 4), "float32")
    tree = {"a": [x, (x, None)], "b": x}
    assert memstats.nbytes_of(tree) == 3 * x.nbytes
    assert memstats.nbytes_of(nd.array(x)) == x.nbytes


def test_memstats_disabled_is_noop():
    config.set("MXNET_MEM_TRACK", False)
    try:
        h = memstats.register("t", "x", nbytes=5)
        h.update(10)
        assert memstats.holders() == []
    finally:
        config.set("MXNET_MEM_TRACK", True)


# ---------------------------------------------------------------------------
# oom flight trigger + post-mortem rendering
# ---------------------------------------------------------------------------

def test_oom_classification():
    from mxnet_tpu.resilience import retry
    assert retry.is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 1073741824 bytes"))
    assert retry.is_oom_error(RuntimeError("Failed to allocate request"))
    assert not retry.is_oom_error(RuntimeError("UNAVAILABLE: worker gone"))
    assert not retry.is_oom_error(RuntimeError(
        "INVALID_ARGUMENT: shapes while allocating"))


def test_oom_fires_flight_bundle_with_holder_breakdown(tmp_path):
    """ACCEPTANCE: an injected RESOURCE_EXHAUSTED produces an `oom` bundle
    whose memstats section carries the ranked holder table, and
    tools/flight_inspect.py renders it."""
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.resilience.retry import RetryPolicy

    class Owner:
        pass
    o = Owner()
    memstats.register("serving", "big.params", nbytes=4 << 20, owner=o,
                      device="tpu:0")
    memstats.register("numerics", "snapshots", nbytes=1 << 20, owner=o)

    config.set("MXNET_FLIGHT_DIR", str(tmp_path))
    try:
        pol = RetryPolicy(max_attempts=2, base_ms=0.01, sleep=lambda s: None)
        with faults.inject("device_oom", site="train_step", every_n=1):
            with pytest.raises(Exception):
                pol.run(lambda: faults.check("train_step"),
                        site="train_step")
        bundles = flight.list_bundles(str(tmp_path))
        assert bundles, "oom trigger must dump a bundle"
        with open(bundles[-1]) as f:
            b = json.load(f)
        assert b["trigger"]["kind"] == "oom"
        assert "RESOURCE_EXHAUSTED" in b["trigger"]["attrs"]["message"]
        holders = {h["holder"]: h["bytes"] for h in b["memstats"]["holders"]}
        assert holders.get("big.params") == 4 << 20
        assert list(b["memstats"]["holders"])[0]["holder"] == "big.params", \
            "holder table must be ranked largest-first"

        fi = _import_tool("flight_inspect")
        text = fi.render(b, path=bundles[-1])
        assert "== memstats" in text and "big.params" in text
        assert "4.0MiB" in text
    finally:
        config.set("MXNET_FLIGHT_DIR", "")


def test_flight_bundle_carries_compile_records(tmp_path):
    """Satellite: bundles gain the last-K compile records, and
    flight_inspect renders the section with dup waste."""
    jfn = jax.jit(lambda x: x + 2.0)
    aval = jax.ShapeDtypeStruct((2,), jnp.float32)
    compile_ledger.lower_and_compile(jfn, (aval,), site="serving_bucket",
                                     key={"endpoint": "e", "bucket": 2})
    compile_ledger.lower_and_compile(jfn, (aval,), site="serving_bucket",
                                     key={"endpoint": "e", "bucket": 2})
    b = flight.RECORDER.bundle(trigger="manual")
    assert b["compile_records"]["summary"]["compiles"] == 2
    assert b["compile_records"]["summary"]["duplicates"] == 1
    assert len(b["compile_records"]["records"]) == 2
    fi = _import_tool("flight_inspect")
    text = fi.render(b)
    assert "== compile ledger" in text
    assert "dup waste" in text and "DUP" in text


# ---------------------------------------------------------------------------
# perf sentinel
# ---------------------------------------------------------------------------

def test_drift_detector_fires_on_sustained_regression_only():
    det = perf_sentinel.DriftDetector("s", alpha=0.2, ratio=1.5,
                                      sustain_n=4, warmup_n=10)
    for _ in range(40):
        assert not det.observe(100.0)
    # a single 10x spike: never fires (streak resets)
    assert not det.observe(1000.0)
    for _ in range(5):
        assert not det.observe(100.0)
    # sustained 3x regression: fires exactly once (edge-triggered)
    fired = [det.observe(300.0) for _ in range(30)]
    assert sum(fired) == 1
    assert det.baseline > 200.0, "re-baselines at the regressed level"
    # a FURTHER regression fires again
    fired = [det.observe(900.0) for _ in range(30)]
    assert sum(fired) == 1


def test_sentinel_emits_flight_event_and_metric(tmp_path):
    config.set("MXNET_PERF_WARMUP_N", 5)
    config.set("MXNET_PERF_SUSTAIN_N", 3)
    config.set("MXNET_FLIGHT_DIR", str(tmp_path))
    try:
        for _ in range(20):
            perf_sentinel.observe("train_step", 100.0)
        for _ in range(30):
            perf_sentinel.observe("train_step", 500.0)
        snap = perf_sentinel.SENTINEL.snapshot()["train_step"]
        assert snap["fired"] >= 1
        evs = [e for e in flight.recent_events()
               if e["kind"] == "perf_regression"]
        assert evs and evs[-1]["attrs"]["stream"] == "train_step"
        assert evs[-1]["attrs"]["ratio"] > 1.5
        assert flight.list_bundles(str(tmp_path))
    finally:
        config.set("MXNET_PERF_WARMUP_N", 50)
        config.set("MXNET_PERF_SUSTAIN_N", 8)
        config.set("MXNET_FLIGHT_DIR", "")
        perf_sentinel.SENTINEL.reset()


def test_sentinel_disabled_records_nothing():
    config.set("MXNET_PERF_SENTINEL", False)
    try:
        for _ in range(100):
            perf_sentinel.observe("off_stream", 100.0)
        assert "off_stream" not in perf_sentinel.SENTINEL.snapshot()
    finally:
        config.set("MXNET_PERF_SENTINEL", True)


# ---------------------------------------------------------------------------
# debug pages
# ---------------------------------------------------------------------------

def test_compilez_and_memz_pages():
    jfn = jax.jit(lambda x: x * 5.0)
    aval = jax.ShapeDtypeStruct((2,), jnp.float32)
    compile_ledger.lower_and_compile(jfn, (aval,), site="eager_jit",
                                     key={"op": "times5"})
    compile_ledger.lower_and_compile(jfn, (aval,), site="eager_jit",
                                     key={"op": "times5"})

    class Owner:
        pass
    o = Owner()
    memstats.register("serving", "pg.params", nbytes=2048, owner=o)

    page = dbg.compilez()
    assert "compiles=2" in page and "duplicates=1" in page
    assert "eager_jit" in page and "op=times5" in page

    page = dbg.memz()
    assert "pg.params" in page and "2.0KiB" in page

    # both served over HTTP, and listed on the index
    import urllib.request
    web = dbg.DebugServer(port=0).start()
    try:
        for p in ("/compilez", "/memz"):
            with urllib.request.urlopen(web.url + p, timeout=10) as r:
                assert r.status == 200
        with urllib.request.urlopen(web.url + "/", timeout=10) as r:
            idx = r.read().decode()
        assert "/compilez" in idx and "/memz" in idx
    finally:
        web.stop()


# ---------------------------------------------------------------------------
# tools: compile_report
# ---------------------------------------------------------------------------

def test_compile_report_rollup_and_render(tmp_path):
    config.set("MXNET_COMPILE_LEDGER_DIR", str(tmp_path))
    try:
        jfn = jax.jit(lambda x: x / 2.0)
        aval = jax.ShapeDtypeStruct((6,), jnp.float32)
        compile_ledger.lower_and_compile(jfn, (aval,), site="serving_bucket",
                                         key={"endpoint": "r", "bucket": 6})
        compile_ledger.lower_and_compile(jfn, (aval,), site="train_step")
    finally:
        config.set("MXNET_COMPILE_LEDGER_DIR", "")
    # a line an older build wrote for a measured step: no compile in it
    with open(tmp_path / f"ledger-{os.getpid()}.jsonl", "a") as f:
        f.write(json.dumps({"kind": "step", "site": "serving_step",
                            "key": {"endpoint": "r", "bucket": 6},
                            "step_us": 1500.0}) + "\n")
    cr = _import_tool("compile_report")
    records = compile_ledger.read_ledger(str(tmp_path))
    assert len(records) == 3
    agg = cr.rollup(records)
    assert agg["records"] == 2 and agg["distinct_fingerprints"] == 1
    assert agg["duplicate_fingerprints"] == 1 and agg["dup_waste_s"] > 0
    text = cr.render(records)
    assert "duplicate waste" in text and "serving_bucket" in text
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cr.main([str(tmp_path), "--json"])
    assert rc == 0
    assert json.loads(buf.getvalue())["records"] == 2
