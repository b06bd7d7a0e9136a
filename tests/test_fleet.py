"""Fleet observability (ISSUE 15): cross-process trace journeys, the fleet
collector, and the goodput ledger.

Covers: the per-pid span spool (flush, rotation, cross-process assembly,
MXNET_TRACE_ID inheritance), histogram merging whose quantiles are exactly
the quantiles of the concatenated observations (through the
``tools/metrics_dump.py`` multi-file path), goodput attribution invariants
(exclusive buckets, reconciliation against wall clock, non-negative idle),
the pooled debug pages (/statusz names every replica), and the acceptance
run: a request served through a 3-replica ServingPool with one autoscale
transition and one warm-restarted subprocess yields ONE ordered journey
from ``tools/trace_journey.py`` naming every process/replica crossed, and
``tools/fleet_report.py`` over the same run renders merged metrics plus a
goodput table whose buckets sum within 1% of wall clock.
"""
import gc
import json
import os
import subprocess
import sys
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, serving, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import fleet, goodput
from mxnet_tpu.telemetry import debug_server as dbg
from mxnet_tpu.telemetry import tracing

REPO = os.path.join(os.path.dirname(__file__), os.pardir)


def _tool(name):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def _set_env(pairs):
    """Set env vars, returning the saved values for _restore_env."""
    saved = {k: os.environ.get(k) for k in pairs}
    for k, v in pairs.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = str(v)
    tracing._reset_spool_for_tests()
    return saved


def _restore_env(saved):
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    tracing._reset_spool_for_tests()


def _mlp(seed, in_dim=6, out_dim=3):
    mx.random.seed(seed)
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"), nn.Dense(out_dim))
    net.initialize(mx.init.Xavier())
    net(nd.array(onp.zeros((2, in_dim), "float32")))
    return net


class _StubMonitor:
    burn_threshold = 14.0

    def __init__(self):
        self.fast_burn = 0.0
        self.alert = False

    def check_all(self):
        return [{"endpoint": "e", "fast_burn": self.fast_burn,
                 "slow_burn": self.fast_burn, "alert_active": self.alert}]


# ---------------------------------------------------------------------------
# span spool: flush, rotation, journey assembly, trace inheritance
# ---------------------------------------------------------------------------

def test_spool_flush_and_journey_roundtrip(tmp_path):
    spool = str(tmp_path / "spool")
    saved = _set_env({"MXNET_SPAN_SPOOL_DIR": spool})
    try:
        with telemetry.span("t.outer", step=3) as s:
            tid = s.trace_id
            with telemetry.span("t.inner"):
                pass
        telemetry.spool_flush()
    finally:
        _restore_env(saved)
    entries = telemetry.read_spool(spool)
    assert any(e["name"] == "t.outer" for e in entries)
    hops = telemetry.journey(tid, spool)
    assert [h["name"] for h in hops] == ["t.outer", "t.inner"]
    assert all(h["pid"] == os.getpid() for h in hops)
    # ordered by wall-clock start; parent/child linkage survives the spool
    assert hops[0]["t0_wall"] <= hops[1]["t0_wall"]
    assert hops[1]["parent_id"] == hops[0]["span_id"]
    assert hops[0]["attrs"] == {"step": 3}


def test_spool_rotation_under_size_cap(tmp_path):
    spool = str(tmp_path / "spool")
    saved = _set_env({"MXNET_SPAN_SPOOL_DIR": spool,
                      "MXNET_SPAN_SPOOL_MAX_BYTES": "600",
                      "MXNET_SPAN_SPOOL_FLUSH_N": "4"})
    try:
        telemetry.spool_flush()        # refresh the flush cadence knob
        for i in range(24):
            with telemetry.span("t.rot", i=i):
                pass
        telemetry.spool_flush()
        path = tracing.spool_path(spool)
    finally:
        _restore_env(saved)
    assert os.path.exists(path + ".1")           # cap forced a rotation
    # the live file never grows past the cap by more than one batch
    assert os.path.getsize(path) <= 600 + 1024
    # rotated lines still assemble into journeys: read_spool sees the .1
    # generation too (older generations are dropped by design)
    entries = telemetry.read_spool(spool)
    n = sum(1 for e in entries if e["name"] == "t.rot")
    assert 8 <= n <= 24
    in_rotated = 0
    with open(path + ".1") as f:
        in_rotated = sum(1 for _ in f)
    assert in_rotated >= 1


def test_trace_id_env_inheritance():
    saved = _set_env({"MXNET_TRACE_ID": "feedface00000001"})
    try:
        with telemetry.span("t.root_a") as a:
            assert a.trace_id == "feedface00000001"
            with telemetry.span("t.child") as c:
                assert c.trace_id == "feedface00000001"
        # EVERY root span of the process joins the inherited journey
        with telemetry.span("t.root_b") as b:
            assert b.trace_id == "feedface00000001"
        # explicit adoption still wins over inheritance
        with telemetry.span("t.adopted", trace_id="aa55aa55aa55aa55") as s:
            assert s.trace_id == "aa55aa55aa55aa55"
    finally:
        _restore_env(saved)
    with telemetry.span("t.root_c") as s:
        assert s.trace_id != "feedface00000001"


# ---------------------------------------------------------------------------
# cross-replica histogram merging (satellite: metrics_dump multi-file)
# ---------------------------------------------------------------------------

def test_merged_quantiles_equal_concatenated_observations(tmp_path):
    """The correctness pin: merging per-replica histograms by element-wise
    bucket-count sums yields EXACTLY the quantiles a single process would
    report had it observed every sample — proven through the
    tools/metrics_dump.py multi-file path."""
    from mxnet_tpu.telemetry.metrics import MetricsRegistry
    rng = onp.random.RandomState(5)
    obs_a = rng.gamma(2.0, 200.0, 400)
    obs_b = rng.gamma(3.0, 80.0, 250)

    def snap_with(obs):
        reg = MetricsRegistry()
        h = reg.histogram("mxtpu_test_lat_us", "t", labelnames=("endpoint",))
        child = h.labels("e")
        for v in obs:
            child.observe(float(v))
        return reg.snapshot()

    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    with open(pa, "w") as f:
        json.dump(snap_with(obs_a), f)
    with open(pb, "w") as f:
        json.dump(snap_with(obs_b), f)
    ref = snap_with(onp.concatenate([obs_a, obs_b]))
    ref_s = ref["metrics"]["mxtpu_test_lat_us"]["series"][0]

    metrics_dump = _tool("metrics_dump")
    merged = metrics_dump.load_merged([pa, pb])
    fam = merged["metrics"]["mxtpu_test_lat_us"]
    assert fam["label_names"][0] == "replica"
    per_rep = [s for s in fam["series"] if s["labels"]["replica"] != "ALL"]
    assert {s["labels"]["replica"] for s in per_rep} == {"a.json", "b.json"}
    all_row = [s for s in fam["series"]
               if s["labels"]["replica"] == "ALL"][0]
    assert all_row["labels"]["endpoint"] == "e"
    assert all_row["count"] == ref_s["count"]
    assert all_row["bucket_counts"] == ref_s["bucket_counts"]
    for q in ("p50", "p95", "p99"):
        assert all_row[q] == ref_s[q], q       # exact, not approximate
    assert all_row["min"] == ref_s["min"]
    assert all_row["max"] == ref_s["max"]
    assert all_row["sum"] == pytest.approx(ref_s["sum"])
    # the merged view renders through the unchanged single-process table
    table = metrics_dump.render_table(merged)
    assert "replica=ALL" in table


def test_merge_skips_mismatched_bucket_ladders():
    bounds = [1.0, 2.0]
    with pytest.raises(ValueError):
        fleet.merge_histogram_series(
            bounds, [{"bucket_counts": [1, 0, 0], "count": 1, "sum": 1.0,
                      "min": 1.0, "max": 1.0},
                     {"bucket_counts": [1, 0], "count": 1, "sum": 1.0,
                      "min": 1.0, "max": 1.0}])
    # merge_snapshots keeps per-replica rows but skips the ALL rollup
    fam = {"type": "histogram", "help": "", "label_names": [],
           "bucket_bounds": bounds}
    sa = {"metrics": {"mxtpu_test_m": dict(
        fam, series=[{"labels": {}, "count": 1, "sum": 1.0, "mean": 1.0,
                      "min": 1.0, "max": 1.0, "p50": 1, "p95": 1, "p99": 1,
                      "bucket_counts": [1, 0, 0]}])}}
    sb = {"metrics": {"mxtpu_test_m": dict(
        fam, bucket_bounds=[5.0, 9.0, 11.0], series=[
            {"labels": {}, "count": 1, "sum": 5.0, "mean": 5.0,
             "min": 5.0, "max": 5.0, "p50": 5, "p95": 5, "p99": 5,
             "bucket_counts": [1, 0, 0, 0]}])}}
    merged = fleet.merge_snapshots({"a": sa, "b": sb})
    series = merged["metrics"]["mxtpu_test_m"]["series"]
    assert {s["labels"]["replica"] for s in series} == {"a", "b"}  # no ALL


# ---------------------------------------------------------------------------
# goodput ledger invariants (satellite d)
# ---------------------------------------------------------------------------

def _synthetic_snap():
    return {"metrics": {
        "mxtpu_compile_wall_seconds_total": {
            "type": "counter", "series": [
                {"labels": {"site": "s", "phase": "p"}, "value": 1.5}]},
        "mxtpu_dataloader_wait_us": {
            "type": "histogram", "series": [
                {"labels": {}, "sum": 0.5e6, "count": 10}]},
        "mxtpu_train_step_latency_us": {
            "type": "histogram", "series": [
                {"labels": {}, "sum": 2.0e6, "count": 100}]},
        "mxtpu_checkpoint_save_duration_us": {
            "type": "histogram", "series": [
                {"labels": {}, "sum": 0.25e6, "count": 2}]},
        "mxtpu_span_duration_us": {
            "type": "histogram", "series": [
                {"labels": {"name": "checkpoint.restore"},
                 "sum": 0.2e6, "count": 1},
                {"labels": {"name": "serving.drain"},
                 "sum": 0.1e6, "count": 1},
                # step spans must NOT double-count into any bucket: the
                # step bucket reads the step-latency histograms only
                {"labels": {"name": "train.step"},
                 "sum": 123e6, "count": 1}]},
    }}


def test_goodput_buckets_exclusive_and_sum_to_wall():
    b = goodput.attribute(_synthetic_snap(), 10.0)
    assert set(b) == set(goodput.BUCKETS)
    assert b["compile"] == 1.5
    assert b["data_wait"] == pytest.approx(0.5)
    assert b["step"] == pytest.approx(2.0)          # not 125.0: exclusive
    assert b["checkpoint_flush"] == pytest.approx(0.25)
    assert b["retry_recovery"] == pytest.approx(0.2)
    assert b["drain"] == pytest.approx(0.1)
    assert b["idle"] == pytest.approx(10.0 - 4.55)
    assert sum(b.values()) == pytest.approx(10.0, rel=1e-9)


def test_goodput_idle_never_negative_rescales_overlap():
    # overlapped threads booked 4.55 active seconds into a 2 s wall window:
    # every active bucket scales down proportionally, idle clamps at 0
    b = goodput.attribute(_synthetic_snap(), 2.0)
    assert sum(b.values()) == pytest.approx(2.0, rel=1e-9)
    assert b["idle"] == 0.0
    assert all(v >= 0.0 for v in b.values())
    assert b["step"] / b["compile"] == pytest.approx(2.0 / 1.5)
    # no wall anchor: active buckets only, idle reports 0
    b3 = goodput.attribute(_synthetic_snap(), None)
    assert b3["idle"] == 0.0 and b3["step"] == pytest.approx(2.0)


def test_goodput_account_reconciles_live_run():
    """Scripted live run: the published counter series must sum to the
    published wall gauge within 1%."""
    goodput.reset()
    with telemetry.span("checkpoint.restore"):
        time.sleep(0.02)
    time.sleep(0.01)
    buckets = goodput.account()
    # the restore span lands in retry_recovery; its absolute share depends
    # on the registry's cumulative history (proportional rescale), so pin
    # presence, not magnitude
    assert buckets["retry_recovery"] > 0.0
    assert buckets["idle"] >= 0.0
    snap = telemetry.snapshot()
    fam = snap["metrics"]["mxtpu_goodput_seconds_total"]
    total = sum(s["value"] for s in fam["series"])
    wall = snap["metrics"]["mxtpu_goodput_wall_seconds"]["series"][0]["value"]
    assert wall > 0.0
    assert abs(total - wall) <= 0.01 * wall
    # repeated accounting stays reconciled (monotone counter, fresh deltas)
    time.sleep(0.01)
    goodput.account()
    snap = telemetry.snapshot()
    fam = snap["metrics"]["mxtpu_goodput_seconds_total"]
    total = sum(s["value"] for s in fam["series"])
    wall = snap["metrics"]["mxtpu_goodput_wall_seconds"]["series"][0]["value"]
    assert abs(total - wall) <= 0.01 * wall


# ---------------------------------------------------------------------------
# pooled debug pages (satellite a)
# ---------------------------------------------------------------------------

def _clear_attachments():
    for p in dbg.attached_pools():
        dbg.detach_pool(p)
    for a in dbg.attached_autoscalers():
        dbg.detach_autoscaler(a)
    for s in dbg.attached_servers():
        dbg.detach(s)
    gc.collect()


def test_pooled_statusz_names_every_replica():
    _clear_attachments()
    name = "t_statusz_ep"

    def factory(rid):
        srv = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=64)
        srv.register(serving.ModelEndpoint(
            name, _mlp(11), input_shapes=(6,), max_batch_size=4))
        return srv

    pool = serving.ServingPool(factory, initial_replicas=3)
    mon = _StubMonitor()
    asc = serving.Autoscaler(pool, monitor=mon, min_replicas=1,
                             max_replicas=3, up_n=2, down_n=3,
                             cooldown_s=5.0, queue_high=0.9, queue_low=0.5)
    try:
        page = dbg.statusz()
        assert "== serving pool ==" in page
        assert "pool: replicas=3" in page
        for rid in (0, 1, 2):                  # every replica named
            assert f"replica {rid}: state=running" in page
        assert "autoscaler: replicas [1..3]" in page
        assert "over_polls=0/2" in page and "idle_polls=0/3" in page
        assert "cooldown=no" in page and "cooldown_s=5.0" in page
        code, body = dbg.healthz()
        assert code == 200 and body["ok"]
        assert any(p.get("replicas") == 3
                   and sorted(p.get("rotation", [])) == [0, 1, 2]
                   for p in body.get("pools", []))
        # a transition shows up in the autoscaler section
        mon.alert = True
        asc.tick(now=0.0)
        act = asc.tick(now=1.0)
        assert act is None and pool.size() == 3   # already at max: no-op
    finally:
        pool.stop(drain=True)
        serving.unregister(name)
        _clear_attachments()


def test_fleetz_page_is_json_and_carries_goodput():
    doc = dbg.fleetz()
    json.dumps(doc)                                # must be serializable
    assert doc["processes"] >= 1
    assert "merged" in doc and "health" in doc
    assert set(doc["goodput"]["buckets"]) == set(goodput.BUCKETS)
    assert doc["health"]["status"] in ("ok", "degraded", "down")
    assert isinstance(doc["utilization"], list)


# ---------------------------------------------------------------------------
# acceptance: pooled run + warm restart -> one journey + fleet report
# ---------------------------------------------------------------------------

# the warm-restarted process: rebuilds the endpoint against the SHARED
# executable cache the pool replicas populated, serves one request, and
# leaves its snapshot dump + span-spool lines for the fleet tools
_RESTART_CHILD_SRC = """\
import os
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import nd, serving, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import goodput

mx.random.seed(11); onp.random.seed(11)
net = nn.HybridSequential()
with net.name_scope():
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
net.initialize(mx.init.Xavier())
net(nd.array(onp.zeros((2, 6), "float32")))
srv = serving.InferenceServer(batch_timeout_ms=1.0)
srv.register(serving.ModelEndpoint("t_fleet_ep", net, input_shapes=(6,),
                                   max_batch_size=4, buckets=(4,)))
srv.start()
x = onp.ones((1, 6), "float32")
srv.submit("t_fleet_ep", x).result(timeout=60)
srv.stop()
serving.unregister("t_fleet_ep")
goodput.account()
telemetry.dump(os.environ["FLEET_DUMP"])
telemetry.spool_flush()
"""


@pytest.mark.filterwarnings("ignore")
def test_fleet_acceptance_journey_and_report(tmp_path, capsys):
    spool = str(tmp_path / "spool")
    cache = str(tmp_path / "xcache")
    dumps = tmp_path / "dumps"
    dumps.mkdir()
    name = "t_fleet_ep"
    tid = telemetry.new_trace_id()
    saved = _set_env({"MXNET_SPAN_SPOOL_DIR": spool,
                      "MXNET_TRACE_ID": tid,
                      "MXNET_EXEC_CACHE_DIR": cache})
    goodput.reset()
    nets = {}

    def factory(rid):
        srv = serving.InferenceServer(batch_timeout_ms=20.0, max_queue=64)
        net = _mlp(11)
        nets[rid] = net
        # one bucket, so the oracle below knows the batch size of every
        # served row whatever the batcher coalesced
        srv.register(serving.ModelEndpoint(
            name, net, input_shapes=(6,), max_batch_size=4, buckets=(4,)))
        return srv

    try:
        pool = serving.ServingPool(factory, initial_replicas=2)
        mon = _StubMonitor()
        asc = serving.Autoscaler(pool, monitor=mon, min_replicas=1,
                                 max_replicas=3, up_n=2, down_n=3,
                                 cooldown_s=0.0, queue_high=0.9,
                                 queue_low=0.5)
        try:
            # one autoscale transition: 2 -> 3 replicas under synthetic burn
            mon.alert = True
            mon.fast_burn = 20.0
            asc.tick(now=0.0)
            act = asc.tick(now=1.0)
            assert act and act["action"] == "up" and pool.size() == 3
            # a burst of requests: least-loaded routing spreads them across
            # replicas (each submit parks rows in a replica's batch queue)
            xs = onp.random.RandomState(3).randn(12, 6).astype("float32")
            futs = [pool.submit(name, xs[i]) for i in range(12)]
            outs = [f.result(timeout=60).asnumpy() for f in futs]
            direct = onp.concatenate(
                [nets[0](nd.array(xs[i:i + 4])).asnumpy()
                 for i in range(0, 12, 4)])    # at the served bucket
            assert all(onp.array_equal(o, direct[i])
                       for i, o in enumerate(outs))
        finally:
            pool.stop(drain=True)
            serving.unregister(name)
        # one warm restart: a REAL subprocess sharing the executable cache,
        # inheriting the trace id + spool dir from the environment
        env = dict(os.environ)
        env["FLEET_DUMP"] = str(dumps / "child.json")
        child = subprocess.run([sys.executable, "-c", _RESTART_CHILD_SRC],
                               env=env, capture_output=True, text=True)
        assert child.returncode == 0, child.stderr[-2000:]
        telemetry.spool_flush()
        goodput.account()
        parent_dump = str(dumps / "parent.json")
        telemetry.dump(parent_dump)
    finally:
        _restore_env(saved)

    # -- tools/trace_journey.py: ONE ordered timeline across processes -----
    trace_journey = _tool("trace_journey")
    assert trace_journey.main([spool, "--trace", tid, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    hops, procs = doc["hops"], doc["processes"]
    walls = [h["t0_wall"] for h in hops]
    assert walls == sorted(walls)              # a single ordered timeline
    pids = {p for p in procs if p.startswith("pid=")}
    reps = {p for p in procs if p.startswith("replica=")}
    assert f"pid={os.getpid()}" in pids
    assert len(pids) == 2                      # parent + warm-restart child
    assert len(reps) >= 2                      # burst crossed >=2 replicas
    # ... and names exactly the replicas the routed submits touched
    served = {(h.get("attrs") or {}).get("replica") for h in hops
              if h["name"] == "pool.submit"}
    assert reps == {f"replica={r}" for r in served}
    # the human rendering names every hop
    assert trace_journey.main([spool, "--trace", tid]) == 0
    rendered = capsys.readouterr().out
    for p in sorted(pids | reps):
        assert p in rendered

    # -- tools/fleet_report.py over the same run ---------------------------
    fleet_report = _tool("fleet_report")
    paths = [str(dumps / "child.json"), parent_dump]
    report = fleet_report.build_report(paths, spool_dir=spool, trace=tid)
    # goodput: every process's buckets sum within 1% of its wall clock
    assert report["goodput_ok"]
    for label, gp in report["goodput"].items():
        assert gp["wall_s"] is not None and gp["wall_s"] > 0.0, label
        assert abs(gp["sum_s"] - gp["wall_s"]) <= 0.01 * gp["wall_s"], label
    # merged metrics: per-replica series + exact ALL rollups render
    fam = report["merged"]["metrics"]["mxtpu_span_duration_us"]
    assert any(s["labels"].get("replica") == "ALL" for s in fam["series"])
    assert report["journey"]["processes"] == procs
    # CLI end-to-end: --verify holds the 1% reconciliation
    rc = fleet_report.main(paths + ["--spool-dir", spool, "--trace", tid,
                                    "--verify"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "goodput ledger" in out and "trace journey" in out
    assert "MISMATCH" not in out
