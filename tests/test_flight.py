"""Flight recorder, live debug server, and SLO burn-rate monitor (ISSUE 10).

Covers: the bounded flight rings (spans from tracing, structured events,
completed serving requests), trigger-driven bundle dumps (directory gating,
per-kind rate limiting, rotation, atomic writes), the unhandled-exception
crash hooks, tools/flight_inspect.py rendering, the -z debug HTTP pages
(/metricsz /healthz /statusz /tracez /flightz) including the concurrent-
scrape-under-load bitwise gate, the multi-window SLO burn-rate monitor
(compliant run never alerts, regression trips the fast window, latching,
breaker escalation), the InferenceServer slo_ms wiring, the reporter's
idempotent final-tick stop, the shared log-histogram quantile estimator, the
metrics_dump --watch rate columns, and the chaos worker_kill acceptance
drill (fault -> parseable bundle -> human timeline).
"""
import io
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request
from contextlib import redirect_stdout

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, nd, serving, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import flight
from mxnet_tpu.telemetry import debug_server as dbg
from mxnet_tpu.telemetry.metrics import REGISTRY
from mxnet_tpu.telemetry.slo import MONITOR, SLOMonitor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _import_tool(name):
    sys.path.insert(0, TOOLS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def _counter_value(name, **labels):
    fam = REGISTRY.snapshot()["metrics"].get(name, {})
    for s in fam.get("series", []):
        if s.get("labels", {}) == labels:
            return s.get("value", 0.0)
    return 0.0


def _small_net(seed=0, in_shape=(3, 8, 8)):
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, kernel_size=3, padding=1))
        net.add(nn.Activation("relu"))
        net.add(nn.GlobalAvgPool2D())
        net.add(nn.Dense(10))
    net.initialize()
    net(nd.array(onp.random.randn(2, *in_shape).astype("float32")))
    return net


def _detach_all():
    """Earlier tests leave stopped InferenceServers attached to the debug
    registry (attach is on start, weakly held); healthz assertions need a
    clean slate. Returns the prior list so callers can re-attach."""
    prior = dbg.attached_servers()
    for s in prior:
        dbg.detach(s)
    return prior


# ---------------------------------------------------------------------------
# flight rings
# ---------------------------------------------------------------------------
def test_rings_are_bounded_and_ordered():
    rec = flight.FlightRecorder(span_capacity=4, event_capacity=4,
                                request_capacity=4, directory="")
    for i in range(10):
        rec.record_event("unit_kind", {"i": i})
    evs = rec.recent_events()
    assert len(evs) == 4
    assert [e["attrs"]["i"] for e in evs] == [6, 7, 8, 9]
    for i in range(10):
        rec.record_request(f"trace{i}", "ep", 100.0 + i, rows=1)
    reqs = rec.recent_requests()
    assert len(reqs) == 4
    assert reqs[-1]["trace_id"] == "trace9" and reqs[-1]["ok"] is True


def test_spans_feed_flight_ring_with_trace_ids():
    flight.RECORDER.clear()
    with telemetry.span("flightring.outer"):
        with telemetry.span("flightring.inner"):
            pass
    spans = {s["name"]: s for s in flight.recent_spans()}
    assert "flightring.outer" in spans and "flightring.inner" in spans
    # same trace, parent chain intact, inner finished (and recorded) first
    assert spans["flightring.inner"]["trace_id"] == \
        spans["flightring.outer"]["trace_id"]
    assert spans["flightring.inner"]["parent_id"] == \
        spans["flightring.outer"]["span_id"]
    assert spans["flightring.outer"]["dur_us"] is not None


def test_event_attrs_always_json_serializable():
    rec = flight.FlightRecorder(span_capacity=4, event_capacity=4,
                                request_capacity=4, directory="")
    entry = rec.record_event("unit_kind", {"obj": object(), "n": 3})
    json.dumps(entry)  # must never raise
    assert entry["attrs"]["n"] == 3
    assert "object" in entry["attrs"]["obj"]


def test_public_event_api_reaches_process_recorder():
    telemetry.event("unit_marker", detail=1)
    last = flight.recent_events()[-1]
    assert last["kind"] == "unit_marker" and last["attrs"]["detail"] == 1
    assert _counter_value("mxtpu_flight_events_total",
                          kind="unit_marker") >= 1


# ---------------------------------------------------------------------------
# bundles: dump, trigger gating, rate limit, rotation
# ---------------------------------------------------------------------------
def test_dump_writes_complete_bundle(tmp_path):
    rec = flight.FlightRecorder(span_capacity=8, event_capacity=8,
                                request_capacity=8, directory=str(tmp_path),
                                keep=8, min_interval_s=0.0)
    rec.record_event("boom", {"why": "unit"})
    rec.record_request("tid1", "ep1", 123.0, rows=2)
    path = rec.dump(trigger="unit_dump", attrs={"a": 1})
    assert os.path.dirname(path) == str(tmp_path)
    assert os.path.basename(path).startswith("flight-")
    b = flight.load_bundle(path)
    assert b["schema"] == 2
    assert b["trigger"] == {"kind": "unit_dump", "attrs": {"a": 1}}
    assert "compile_records" in b and "memstats" in b  # schema-2 sections
    assert b["events"][-1]["kind"] == "boom"
    assert b["requests"][-1]["trace_id"] == "tid1"
    assert b["fingerprint"]["pid"] == os.getpid()
    assert "MXNET_FLIGHT_DIR" in b["config"]
    assert b["metrics"]["metrics"]  # full registry snapshot rides along
    assert any("MainThread" in k for k in b["threads"])
    # atomic write: no tmp droppings
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


def test_trigger_records_always_dumps_only_with_directory(tmp_path):
    rec = flight.FlightRecorder(span_capacity=8, event_capacity=8,
                                request_capacity=8, directory="")
    assert rec.trigger("watchdog_stall", watch="w") is None
    assert rec.recent_events()[-1]["kind"] == "watchdog_stall"

    rec2 = flight.FlightRecorder(span_capacity=8, event_capacity=8,
                                 request_capacity=8, directory=str(tmp_path),
                                 keep=8, min_interval_s=60.0)
    before = _counter_value("mxtpu_flight_dumps_suppressed_total")
    p1 = rec2.trigger("circuit_open", scope="s")
    assert p1 and os.path.exists(p1)
    # same kind inside the interval: event recorded, dump suppressed
    assert rec2.trigger("circuit_open", scope="s") is None
    assert rec2.recent_events()[-1]["kind"] == "circuit_open"
    assert _counter_value("mxtpu_flight_dumps_suppressed_total") == before + 1
    # a different kind has its own limiter
    assert rec2.trigger("failover", reason="r")
    rec2.reset_rate_limit()
    assert rec2.trigger("circuit_open", scope="s")


def test_trigger_respects_live_config_directory(tmp_path):
    flight.RECORDER.reset_rate_limit()
    config.set("MXNET_FLIGHT_DIR", str(tmp_path))
    try:
        p = flight.trigger("unit_cfg_dir", note="x")
        assert p and p.startswith(str(tmp_path))
    finally:
        config.set("MXNET_FLIGHT_DIR", "")
    assert flight.trigger("unit_cfg_dir_off") is None


def test_rotation_keeps_newest(tmp_path):
    rec = flight.FlightRecorder(span_capacity=8, event_capacity=8,
                                request_capacity=8, directory=str(tmp_path),
                                keep=3, min_interval_s=0.0)
    paths = [rec.dump(trigger=f"t{i}") for i in range(6)]
    left = flight.list_bundles(str(tmp_path))
    assert len(left) == 3
    assert left == sorted(paths[-3:])


def test_unhandled_thread_exception_dumps_bundle(tmp_path):
    config.set("MXNET_FLIGHT_DIR", str(tmp_path))
    flight.RECORDER.reset_rate_limit()
    flight.install_excepthooks()
    flight.install_excepthooks()  # idempotent
    try:
        def boom():
            raise ValueError("synthetic crash for the flight recorder")
        t = threading.Thread(target=boom, name="flight-crash-test")
        t.start()
        t.join()
    finally:
        flight.uninstall_excepthooks()
        config.set("MXNET_FLIGHT_DIR", "")
    bundles = flight.list_bundles(str(tmp_path))
    assert bundles, "thread crash must leave a bundle"
    b = flight.load_bundle(bundles[-1])
    assert b["trigger"]["kind"] == "unhandled_exception"
    assert b["trigger"]["attrs"]["error"] == "ValueError"
    assert b["trigger"]["attrs"]["thread"] == "flight-crash-test"


def test_flight_inspect_renders_bundle(tmp_path):
    flight_inspect = _import_tool("flight_inspect")
    flight.RECORDER.clear()
    with telemetry.span("inspect.step", examples=4):
        pass
    telemetry.event("failover", reason="unit")
    flight.record_request("tidx", "epx", 1234.0, rows=2)
    path = flight.RECORDER.dump(
        path=str(tmp_path / "flight-unit-0000-failover.json"),
        trigger="failover", attrs={"reason": "unit"})
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert flight_inspect.main([str(tmp_path)]) == 0
    out = buf.getvalue()
    assert "trigger: failover" in out
    assert "inspect.step" in out and "trace " in out
    assert "== completed requests" in out and "tidx" in out
    assert "metrics snapshot" in out
    # --json emits the raw parseable bundle
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert flight_inspect.main([path, "--json"]) == 0
    assert json.loads(buf.getvalue())["trigger"]["kind"] == "failover"


# ---------------------------------------------------------------------------
# debug server
# ---------------------------------------------------------------------------
def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def test_debug_server_serves_all_pages():
    prior = _detach_all()
    telemetry.event("unit_page_probe")  # ensure a flight series exists
    srv = dbg.DebugServer(port=0).start()
    try:
        assert srv.port > 0
        st, body = _get(srv.url + "/")
        assert st == 200 and "/metricsz" in body
        st, body = _get(srv.url + "/metricsz")
        assert st == 200 and "mxtpu_flight_events_total" in body
        st, body = _get(srv.url + "/healthz")
        assert st == 200 and json.loads(body)["ok"] is True
        st, body = _get(srv.url + "/statusz")
        assert st == 200 and "== flight recorder ==" in body
        st, body = _get(srv.url + "/tracez")
        assert st == 200 and body.startswith("tracez:")
        st, body = _get(srv.url + "/flightz")
        assert st == 200 and "recent_events" in json.loads(body)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url + "/nope")
        assert ei.value.code == 404
        assert _counter_value("mxtpu_debug_requests_total",
                              page="metricsz") >= 1
    finally:
        srv.stop()
        for s in prior:
            dbg.attach(s)


def test_flightz_dump_writes_bundle(tmp_path):
    config.set("MXNET_FLIGHT_DIR", str(tmp_path))
    try:
        srv = dbg.DebugServer(port=0).start()
        try:
            st, body = _get(srv.url + "/flightz?dump=1")
        finally:
            srv.stop()
        body = json.loads(body)
        assert st == 200 and body["dumped"] and body["bundles"]
    finally:
        config.set("MXNET_FLIGHT_DIR", "")
    bundles = flight.list_bundles(str(tmp_path))
    assert bundles
    assert flight.load_bundle(bundles[-1])["trigger"]["kind"] == "flightz"


class _FakeServer:
    def __init__(self):
        self.h = {"state": "running", "circuit": "healthy", "endpoints": {}}

    def health(self):
        return self.h


def test_healthz_reflects_attached_server_state():
    prior = _detach_all()
    fake = _FakeServer()
    dbg.attach(fake)
    try:
        assert dbg.healthz()[0] == 200
        fake.h["circuit"] = "open"
        st, body = dbg.healthz()
        assert st == 503 and body["ok"] is False
        fake.h = {"state": "stopped", "circuit": "healthy", "endpoints": {}}
        assert dbg.healthz()[0] == 503
    finally:
        dbg.detach(fake)
        for s in prior:
            dbg.attach(s)


# ---------------------------------------------------------------------------
# ACCEPTANCE + satellite (d): concurrent scrapes during live serving do not
# perturb served outputs (bitwise) and every scrape answers 200
# ---------------------------------------------------------------------------
def test_concurrent_scrapes_do_not_perturb_serving():
    net = _small_net(seed=11)
    # one bucket, so the oracle below runs at the batch size that served
    # (the same program at another batch size is one ulp away on XLA:CPU)
    ep = serving.ModelEndpoint("t_scrape", net, input_shapes=(3, 8, 8),
                               max_batch_size=8, buckets=(8,))
    srv = serving.InferenceServer(batch_timeout_ms=2.0, max_queue=256)
    srv.register(ep, slo_ms=60_000.0)
    srv.start()
    web = dbg.DebugServer(port=0).start()
    stop = threading.Event()
    statuses, scrape_errors = [], []

    def scraper(page):
        while not stop.is_set():
            try:
                st, _ = _get(web.url + page)
                statuses.append(st)
            except Exception as e:  # noqa: BLE001 — record, assert later
                scrape_errors.append(repr(e))
                return

    scrapers = [threading.Thread(target=scraper, args=(p,), daemon=True)
                for p in ("/metricsz", "/statusz", "/metricsz", "/tracez",
                          "/compilez", "/memz")]
    for t in scrapers:
        t.start()
    try:
        rng = onp.random.RandomState(12)
        xs = [rng.randn(3, 8, 8).astype("float32") for _ in range(24)]
        results = [None] * len(xs)

        def client(i):
            results[i] = srv.predict("t_scrape", xs[i], timeout=60)

        clients = [threading.Thread(target=client, args=(i,))
                   for i in range(len(xs))]
        for t in clients:
            t.start()
        for t in clients:
            t.join()
    finally:
        stop.set()
        for t in scrapers:
            t.join(timeout=10)
        web.stop()
        srv.stop()
        MONITOR.unregister("t_scrape")
        dbg.detach(srv)
    assert not scrape_errors, scrape_errors
    assert statuses and all(s == 200 for s in statuses)
    net.hybridize()
    for i, x in enumerate(xs):
        direct = net(nd.array(
            serving.bucketing.pad_rows(x[None], 8))).asnumpy()[0]
        assert onp.array_equal(direct, results[i].asnumpy()), \
            f"client {i}: serving output changed under scrape load"


# ---------------------------------------------------------------------------
# SLO burn-rate monitor
# ---------------------------------------------------------------------------
class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


def test_slo_compliant_run_never_alerts():
    clk = _Clock()
    mon = SLOMonitor(target=0.999, fast_window_s=60.0, slow_window_s=600.0,
                     burn_threshold=10.0, min_events=10, escalate=False,
                     time_fn=clk)
    obj = mon.register("ep_unit_ok", threshold_us=50_000.0)
    for _ in range(200):
        mon.record("ep_unit_ok", 10_000.0)
        clk.advance(0.25)
    st = mon.check(obj)
    assert st["fast_burn"] == 0.0 and st["slow_burn"] == 0.0
    assert st["alert_active"] is False
    assert _counter_value("mxtpu_slo_alerts_total", endpoint="ep_unit_ok") == 0
    assert _counter_value("mxtpu_slo_good_total", endpoint="ep_unit_ok") == 200


def test_slo_regression_trips_fast_alert_latches_and_clears():
    clk = _Clock()
    mon = SLOMonitor(target=0.99, fast_window_s=60.0, slow_window_s=600.0,
                     burn_threshold=5.0, min_events=10, escalate=False,
                     time_fn=clk)
    obj = mon.register("ep_unit_bad", threshold_us=50_000.0)
    for _ in range(50):  # healthy baseline
        mon.record("ep_unit_bad", 1_000.0)
        clk.advance(0.1)
    assert not obj.alert_active
    for _ in range(50):  # synthetic latency regression: everything slow
        mon.record("ep_unit_bad", 500_000.0)
        clk.advance(0.1)
    assert obj.alert_active
    assert _counter_value("mxtpu_slo_alerts_total",
                          endpoint="ep_unit_bad") == 1
    assert _counter_value("mxtpu_slo_alert_active",
                          endpoint="ep_unit_bad") == 1
    # latched: continued burn is the same episode, not a firehose
    for _ in range(20):
        mon.record("ep_unit_bad", 500_000.0)
        clk.advance(0.1)
    assert _counter_value("mxtpu_slo_alerts_total",
                          endpoint="ep_unit_bad") == 1
    # recovery: bad events age out of the fast window -> alert clears
    clk.advance(120.0)
    mon.record("ep_unit_bad", 1_000.0)
    assert not obj.alert_active
    assert _counter_value("mxtpu_slo_alert_active",
                          endpoint="ep_unit_bad") == 0
    kinds = [e["kind"] for e in flight.recent_events()]
    assert "slo_burn_alert" in kinds and "slo_burn_clear" in kinds


def test_slo_escalation_degrades_offending_breaker():
    from mxnet_tpu.resilience.watchdog import CircuitBreaker
    br = CircuitBreaker(scope="slo_unit_esc")
    clk = _Clock()
    mon = SLOMonitor(target=0.99, fast_window_s=60.0, slow_window_s=600.0,
                     burn_threshold=5.0, min_events=5, escalate=True,
                     time_fn=clk)
    mon.register("ep_unit_esc", threshold_us=10_000.0, breaker=br)
    assert br.state() == "healthy"
    for _ in range(20):
        mon.record("ep_unit_esc", 1e6)
        clk.advance(0.1)
    assert br.state() == "degraded"
    assert _counter_value("mxtpu_slo_escalations_total",
                          endpoint="ep_unit_esc") == 1


def test_server_register_wires_slo_and_flight_requests():
    net = _small_net(seed=5)
    ep = serving.ModelEndpoint("t_slo_wire", net, input_shapes=(3, 8, 8),
                               max_batch_size=8)
    srv = serving.InferenceServer(batch_timeout_ms=2.0)
    srv.register(ep, slo_ms=10_000.0, slo_target=0.99)
    srv.start()
    try:
        obj = MONITOR.get("t_slo_wire")
        assert obj is not None
        assert obj.threshold_us == 10_000.0 * 1000.0
        assert obj.target == 0.99
        assert srv.health()["endpoints"]["t_slo_wire"]["slo_target"] == 0.99
        rng = onp.random.RandomState(6)
        for _ in range(6):
            srv.predict("t_slo_wire",
                        rng.randn(3, 8, 8).astype("float32"), timeout=60)
    finally:
        srv.stop()
        MONITOR.unregister("t_slo_wire")
        dbg.detach(srv)
    assert _counter_value("mxtpu_slo_good_total", endpoint="t_slo_wire") >= 6
    reqs = [r for r in flight.recent_requests()
            if r["endpoint"] == "t_slo_wire"]
    assert len(reqs) >= 6
    assert all(r["ok"] and r["trace_id"] for r in reqs)


# ---------------------------------------------------------------------------
# satellite (a): reporter final tick is exactly-once and stop is idempotent
# ---------------------------------------------------------------------------
def test_reporter_final_tick_once_and_stop_idempotent(tmp_path):
    path = str(tmp_path / "final.json")
    rep = telemetry.periodic_logger(9999.0, path=path)  # never ticks on its own
    rep.stop()
    assert os.path.exists(path), "stop() must flush one final snapshot"
    first = open(path).read()
    json.loads(first)
    rep.stop()  # double stop (e.g. explicit stop then atexit): no second tick
    assert open(path).read() == first


# ---------------------------------------------------------------------------
# satellite (b): serving histogram shares the telemetry quantile estimator
# ---------------------------------------------------------------------------
def test_latency_histogram_uses_shared_quantile_impl():
    from mxnet_tpu.serving.stats import _BOUNDS, LatencyHistogram
    from mxnet_tpu.telemetry.metrics import _quantile_from_buckets
    h = LatencyHistogram()
    rng = onp.random.RandomState(0)
    for v in rng.lognormal(mean=6.0, sigma=1.0, size=500):
        h.record(float(v))
    for p in (50, 90, 95, 99, 99.9):
        assert h.percentile(p) == _quantile_from_buckets(
            _BOUNDS, h.counts, h.n, p, h.max_us)
    assert h.percentile(50) <= h.percentile(95) <= h.percentile(99) <= h.max_us
    assert LatencyHistogram().percentile(50) == 0.0


# ---------------------------------------------------------------------------
# satellite (c): metrics_dump --watch rate columns
# ---------------------------------------------------------------------------
def test_metrics_dump_rates_and_watch_column():
    metrics_dump = _import_tool("metrics_dump")
    snap1 = {"ts": 100.0, "metrics": {
        "mxtpu_unit_total": {"type": "counter", "series": [
            {"labels": {"k": "a"}, "value": 10}]},
        "mxtpu_unit_gauge": {"type": "gauge", "series": [
            {"labels": {}, "value": 5}]}}}
    snap2 = {"ts": 110.0, "metrics": {
        "mxtpu_unit_total": {"type": "counter", "series": [
            {"labels": {"k": "a"}, "value": 30}]},
        "mxtpu_unit_gauge": {"type": "gauge", "series": [
            {"labels": {}, "value": 7}]}}}
    t1 = metrics_dump.counter_totals(snap1)
    t2 = metrics_dump.counter_totals(snap2)
    assert t1 == {"mxtpu_unit_total{k=a}": 10}  # gauges never rate
    rates = metrics_dump.compute_rates(t1, t2, 10.0)
    assert rates == {"mxtpu_unit_total{k=a}": 2.0}
    # counter reset (restart) reads as a fresh start, not a negative rate
    reset = metrics_dump.compute_rates({"mxtpu_unit_total{k=a}": 50}, t2, 10.0)
    assert reset["mxtpu_unit_total{k=a}"] == 3.0
    table = metrics_dump.render_table(snap2, rates=rates)
    assert "Δ/s" in table
    row = [ln for ln in table.splitlines() if "mxtpu_unit_total" in ln][0]
    assert row.rstrip().endswith("2/s")
    gauge_row = [ln for ln in table.splitlines()
                 if "mxtpu_unit_gauge" in ln][0]
    assert not gauge_row.rstrip().endswith("/s")
    assert "Δ/s" not in metrics_dump.render_table(snap2)


# ---------------------------------------------------------------------------
# ACCEPTANCE + satellite (f): chaos worker_kill leaves a parseable bundle the
# inspector renders as a human timeline
# ---------------------------------------------------------------------------
def test_chaos_worker_kill_leaves_renderable_flight_bundle():
    chaos_check = _import_tool("chaos_check")
    flight_inspect = _import_tool("flight_inspect")
    buf = io.StringIO()
    result = chaos_check.run_chaos(seed=7, requests=24,
                                   scenarios=["worker_kill"], out=buf)
    assert result["ok"], buf.getvalue()
    wk = result["worker_kill"]
    assert wk["flight_ok"]
    assert "failover" in wk["flight_triggers"]
    bundles = flight.list_bundles(wk["flight_dir"])
    assert bundles
    bundle = flight.load_bundle(bundles[-1])
    rendered = flight_inspect.render(bundle, path=bundles[-1])
    assert "trigger: failover" in rendered
    assert "trace " in rendered, "victim spans must group by trace id"
    assert "metrics snapshot" in rendered
    assert "mxtpu_serving_failovers_total" in rendered
