"""r6 serving rebuild tests: multi-tenant Router (EDF + measured step cost),
double-buffered host pipeline, per-tenant circuit breakers, and the batcher/
bucketing satellites — all on the CPU mesh (tier-1, JAX_PLATFORMS=cpu).

Load-bearing properties pinned here:
- pipelined double-buffered serving is BYTE-identical to the serial path
  (same executables, same padding, same concat);
- a slow large-bucket tenant cannot convoy a fast small-bucket tenant past
  its SLO (the convoy test), and nobody starves;
- one tenant's open breaker sheds that tenant only;
- resolve()/fail() swallow ONLY the Future's InvalidStateError — a broken
  result object surfaces instead of being eaten.
"""
import threading
import time
from concurrent.futures import Future

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, serving
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience.watchdog import CircuitBreaker
from mxnet_tpu.serving import ServerOverloadError, bucketing
from mxnet_tpu.serving.batcher import EndpointQueue, Request, fail, resolve
from mxnet_tpu.serving.router import Router, StepCostEWMA, Tenant
from mxnet_tpu.serving.stats import EndpointStats


def _mlp(seed=0, in_dim=16, out=10):
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(out))
    net.initialize()
    net(nd.array(onp.random.randn(2, in_dim).astype("float32")))
    return net


# ---------------------------------------------------------------------------
# satellite: resolve()/fail() narrowed to InvalidStateError
# ---------------------------------------------------------------------------
def test_resolve_fail_swallow_only_invalid_state():
    f = Future()
    assert f.cancel()
    resolve(f, 1)                        # cancelled future: swallowed
    f2 = Future()
    f2.set_result(1)
    resolve(f2, 2)                       # already-resolved: swallowed
    fail(f2, RuntimeError("late"))       # fail after resolve: swallowed

    class Broken(Future):
        def set_result(self, v):
            raise RuntimeError("broken result plumbing")

        def set_exception(self, e):
            raise RuntimeError("broken exception plumbing")

    with pytest.raises(RuntimeError, match="broken result"):
        resolve(Broken(), 1)
    with pytest.raises(RuntimeError, match="broken exception"):
        fail(Broken(), ValueError("x"))


# ---------------------------------------------------------------------------
# satellite: bucket edge cases + ladder validation
# ---------------------------------------------------------------------------
def test_bucket_for_edge_cases():
    assert bucketing.bucket_for(8, (1, 2, 4, 8)) == 8   # rows == largest
    assert bucketing.bucket_for(1, (1, 2, 4, 8)) == 1   # rows == 1
    # non-pow2 custom ladder
    assert bucketing.bucket_for(1, (3, 5, 9)) == 3
    assert bucketing.bucket_for(3, (3, 5, 9)) == 3
    assert bucketing.bucket_for(4, (3, 5, 9)) == 5
    assert bucketing.bucket_for(9, (3, 5, 9)) == 9
    with pytest.raises(mx.MXNetError):
        bucketing.bucket_for(10, (3, 5, 9))


def test_validate_buckets_accepts_good_ladders():
    assert bucketing.validate_buckets((1, 2, 4, 8), 8) == (1, 2, 4, 8)
    assert bucketing.validate_buckets((3, 5, 9), 9) == (3, 5, 9)
    assert bucketing.validate_buckets((7,), 7) == (7,)


def test_endpoint_rejects_bad_bucket_ladders():
    net = _mlp(seed=40)
    bad = [
        (1, 2, 2, 4),       # duplicate
        (4, 2, 8),          # non-ascending
        (0, 8),             # < 1
        (2, 4),             # largest != max_batch_size
        (),                 # empty
    ]
    for i, ladder in enumerate(bad):
        with pytest.raises(mx.MXNetError):
            serving.ModelEndpoint(f"t_badbuckets_{i}", net, input_shapes=(16,),
                                  max_batch_size=8, buckets=ladder)
        assert f"t_badbuckets_{i}" not in serving.list_endpoints()


# ---------------------------------------------------------------------------
# Router unit tests (deterministic: fabricated queues + seeded EWMAs)
# ---------------------------------------------------------------------------
class _StubEndpoint:
    def __init__(self, name, max_batch=8, buckets=(1, 2, 4, 8)):
        self.name = name
        self.max_batch_size = max_batch
        self.buckets = buckets
        self.stats = EndpointStats(name)
        self.step_cost = StepCostEWMA()


def _tenant(name, *, max_batch=8, slo_us=None, est_us=None, timeout_us=2000):
    ep = _StubEndpoint(name, max_batch=max_batch)
    if est_us is not None:
        for b in ep.buckets:
            ep.step_cost.observe(b, est_us)
    q = EndpointQueue(ep, 256, timeout_us)
    return Tenant(name, ep, q, CircuitBreaker(scope=f"test:{name}"),
                  slo_us=slo_us)


def _enqueue(tenant, rows, age_us, now_us, deadline_us=None):
    req = Request(tuple([onp.zeros((rows, 4), "float32")]), rows, False)
    req.enqueue_us = now_us - age_us
    req.deadline_us = deadline_us
    tenant.queue.offer(req)
    return req


def test_router_prefers_meetable_slo_over_late_convoy():
    """A saturated no-SLO tenant (head long past its batch deadline) must
    not convoy a tenant whose SLO is still meetable."""
    now = 10_000_000
    router = Router(batch_timeout_us=2000)
    slow = _tenant("r_slow", est_us=50_000)
    fast = _tenant("r_fast", max_batch=2, slo_us=30_000, est_us=1_000)
    router.add(slow)
    router.add(fast)
    _enqueue(slow, 8, age_us=1_000_000, now_us=now)   # ready + very late
    _enqueue(fast, 1, age_us=5_000, now_us=now)       # ready, slack ~24ms
    assert router.slack_us(fast, now) > 0
    assert router.slack_us(slow, now) < 0
    assert router.select(now).name == "r_fast"


def test_router_shortest_job_first_among_late_tenants():
    """When every ready tenant is already late, run the cheapest step first:
    the long batch is late regardless — it must not add its own step time to
    every short request's lateness."""
    now = 10_000_000
    router = Router(batch_timeout_us=2000)
    big = _tenant("r_big", est_us=50_000)
    small = _tenant("r_small", max_batch=2, est_us=1_000)
    router.add(big)
    router.add(small)
    # both late, neither starving (starvation needs 8x(timeout+est) wait)
    _enqueue(big, 8, age_us=100_000, now_us=now)
    _enqueue(small, 1, age_us=10_000, now_us=now)
    assert router.select(now).name == "r_small"


def test_router_starvation_escalation_oldest_first():
    """SJF among late tenants cannot starve the expensive one forever: past
    the starvation bound the oldest head wins regardless of step cost."""
    now = 10_000_000
    router = Router(batch_timeout_us=2000)
    big = _tenant("r_big2", est_us=50_000)     # starvation ~8*52ms = 416ms
    small = _tenant("r_small2", max_batch=2, est_us=1_000)
    router.add(big)
    router.add(small)
    _enqueue(big, 8, age_us=1_000_000, now_us=now)    # waited 1s: starving
    _enqueue(small, 1, age_us=10_000, now_us=now)     # late, not starving
    assert router.select(now).name == "r_big2"


def test_router_explicit_deadline_overrides_slo():
    now = 10_000_000
    router = Router(batch_timeout_us=2000)
    a = _tenant("r_dl_a", slo_us=500_000, est_us=1_000)
    b = _tenant("r_dl_b", slo_us=500_000, est_us=1_000)
    router.add(a)
    router.add(b)
    # same age; a's head carries a much tighter explicit client deadline
    _enqueue(a, 8, age_us=10_000, now_us=now, deadline_us=now + 5_000)
    _enqueue(b, 8, age_us=10_000, now_us=now)
    assert router.select(now).name == "r_dl_a"


def test_step_cost_ewma_estimates_and_fallback():
    m = StepCostEWMA(alpha=0.5)
    assert m.estimate(8) == 0.0                 # no data: pure EDF
    m.observe(8, 1000.0)
    assert m.estimate(8) == 1000.0
    m.observe(8, 2000.0)
    assert m.estimate(8) == 1500.0              # EWMA moved halfway
    # unobserved bucket: nearest observed, scaled by row ratio
    assert m.estimate(4) == pytest.approx(750.0)
    assert m.snapshot() == {8: 1500.0}
    # what /statusz shows of it: the measured mean and its count, no more
    assert m.snapshot_detail() == {
        "buckets": {8: {"measured_us": 1500.0, "n": 2}}}


def _decode_lm(block_length):
    from mxnet_tpu.gluon.model_zoo.moe_lm import MoEDecoderLM
    kw = {} if block_length == 1 else {"block_length": block_length,
                                       "mask_token_id": 96}
    lm = MoEDecoderLM(num_layers=1, units=32, num_heads=2, num_kv_heads=1,
                      head_dim=16, expert_hidden=16, num_experts=4,
                      experts_per_token=2, vocab_size=97, **kw)
    lm.initialize(mx.init.DeviceNormal(0.05, seed=5))
    lm.hybridize()
    return lm


def _cold_endpoint(kind):
    """(endpoint, [(one of its EWMAs, the ladder it prices)])."""
    if kind == "dense":
        ep = serving.ModelEndpoint("t_warm_dense", _mlp(seed=47),
                                   input_shapes=(16,), max_batch_size=8)
        return ep, [(ep.step_cost, ep.buckets)]
    ep = serving.DecodeEndpoint(
        f"t_warm_{kind}", _decode_lm(4 if kind == "decode_blocks" else 1),
        max_seq_len=32, max_batch_size=4, num_pages=9)
    return ep, [(ep.step_cost, ep.decode_buckets),
                (ep.prefill_cost, ep.prefill_buckets)]


@pytest.mark.parametrize("kind", ["dense", "decode_causal", "decode_blocks"])
def test_warmup_observes_every_bucket_of_the_ladder(kind):
    """The fact the schedulers' prices rest on: after ``warmup()`` no bucket
    is cold, so ``estimate`` never has to guess from a neighbour."""
    ep, ladders = _cold_endpoint(kind)
    try:
        assert all(ewma.snapshot() == {} for ewma, _ in ladders)
        ep.warmup()
        for ewma, ladder in ladders:
            detail = ewma.snapshot_detail()["buckets"]
            assert sorted(detail) == sorted(ladder)
            assert all(d["n"] == 1 and d["measured_us"] > 0
                       for d in detail.values())
            assert all(ewma.estimate(b) == detail[b]["measured_us"]
                       for b in ladder)
    finally:
        serving.unregister(ep.name)


def test_compile_trigger_keys_keep_their_fields():
    """``cache/executable_cache.py:build_key`` folds these dicts into the
    persistent key: a field dropped, added or renamed misses every stored
    executable, and every restart compiles again."""
    dense = serving.ModelEndpoint("t_key_dense", _mlp(seed=48),
                                  input_shapes=(16,), max_batch_size=2)
    dec = serving.DecodeEndpoint("t_key_dec", _decode_lm(1), max_seq_len=32,
                                 max_batch_size=2, num_pages=5)
    try:
        assert dense._compile_key(2) == {
            "endpoint": "t_key_dense", "bucket": 2, "dtype": "float32",
            "device": dense._device_label()}
        for kind, bucket in (("step", 2), ("prefill", 16)):
            assert dec._cost_key(kind, bucket) == {
                "endpoint": "t_key_dec", "kind": kind, "bucket": bucket,
                "dtype": str(dec.pool_dtype),
                "device": dec._device_label()}
    finally:
        serving.unregister("t_key_dense")
        serving.unregister("t_key_dec")


# ---------------------------------------------------------------------------
# tentpole: pipelined double-buffered path is byte-identical to serial
# ---------------------------------------------------------------------------
def test_pipelined_outputs_byte_identical_to_serial_path():
    net = _mlp(seed=41)
    ep_serial = serving.ModelEndpoint("t_serial", net, input_shapes=(16,),
                                      max_batch_size=8)
    ep_pipe = serving.ModelEndpoint("t_pipe", net, input_shapes=(16,),
                                    max_batch_size=8)
    srv_serial = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=64,
                                         pipeline=False)
    srv_pipe = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=64,
                                       pipeline=True)
    srv_serial.register(ep_serial)
    srv_pipe.register(ep_pipe)
    srv_serial.start()
    srv_pipe.start()
    rng = onp.random.RandomState(42)
    reqs = [rng.randn(r, 16).astype("float32") for r in (1, 3, 5, 8, 2, 7)]
    try:
        for xb in reqs:
            a = srv_serial.predict("t_serial", xb, timeout=60).asnumpy()
            b = srv_pipe.predict("t_pipe", xb, timeout=60).asnumpy()
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes(), \
                "pipelined output differs from serial path"
    finally:
        srv_serial.stop()
        srv_pipe.stop()
        serving.unregister("t_serial")
        serving.unregister("t_pipe")


def test_pipelined_concurrent_clients_bitwise_vs_direct():
    """Pipelined + concurrent: outputs still bitwise-equal the hybridized
    direct forward while the prep thread overlaps device steps."""
    net = _mlp(seed=43)
    # one bucket: the direct forward below runs at the batch size every
    # row was served at (another batch size is one ulp away on XLA:CPU)
    ep = serving.ModelEndpoint("t_pipe_conc", net, input_shapes=(16,),
                               max_batch_size=8, buckets=(8,))
    srv = serving.InferenceServer(batch_timeout_ms=3.0, max_queue=128,
                                  pipeline=True)
    srv.register(ep)
    srv.start()
    rng = onp.random.RandomState(44)
    xs = [rng.randn(16).astype("float32") for _ in range(24)]
    results = [None] * len(xs)
    try:
        def client(i):
            results[i] = srv.predict("t_pipe_conc", xs[i], timeout=60)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        srv.stop()
        serving.unregister("t_pipe_conc")
    net.hybridize()
    for i, x in enumerate(xs):
        direct = net(nd.array(bucketing.pad_rows(x[None], 8))).asnumpy()[0]
        assert onp.array_equal(results[i].asnumpy(), direct), f"client {i}"


# ---------------------------------------------------------------------------
# tentpole: convoy fairness under a saturating slow tenant
# ---------------------------------------------------------------------------
def test_convoy_slow_tenant_does_not_break_fast_tenant_slo():
    """One slow large-bucket tenant saturates the device; a fast small-bucket
    tenant with an SLO keeps its p95 well under that SLO's scheduling bound,
    and the slow tenant still makes progress (no starvation)."""
    slow_net = _mlp(seed=45)
    fast_net = _mlp(seed=46)
    ep_slow = serving.ModelEndpoint("t_convoy_slow", slow_net,
                                    input_shapes=(16,), max_batch_size=8)
    ep_fast = serving.ModelEndpoint("t_convoy_fast", fast_net,
                                    input_shapes=(16,), max_batch_size=2)
    # make the slow tenant's device step genuinely slow (CPU steps on an MLP
    # are microseconds; the convoy needs a step long enough to convoy behind)
    orig_execute = ep_slow.execute

    def slow_execute(*args, **kwargs):
        time.sleep(0.03)
        return orig_execute(*args, **kwargs)

    ep_slow.execute = slow_execute
    srv = serving.InferenceServer(batch_timeout_ms=2.0, max_queue=256)
    srv.register(ep_slow)
    srv.register(ep_fast, slo_ms=100.0)
    srv.start()
    stop_at = time.perf_counter() + 1.5
    fast_lat = []
    slow_done = [0]

    def slow_client():
        x = onp.zeros((8, 16), "float32")
        while time.perf_counter() < stop_at:
            srv.predict("t_convoy_slow", x, timeout=30)
            slow_done[0] += 1

    def fast_client():
        x = onp.zeros(16, "float32")
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            srv.predict("t_convoy_fast", x, timeout=30)
            fast_lat.append(time.perf_counter() - t0)

    try:
        threads = [threading.Thread(target=slow_client) for _ in range(3)] + \
                  [threading.Thread(target=fast_client)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        srv.stop()
        serving.unregister("t_convoy_slow")
        serving.unregister("t_convoy_fast")
    assert len(fast_lat) >= 10, "fast tenant barely ran"
    assert slow_done[0] >= 3, "slow tenant starved"
    fast_lat.sort()
    p95 = fast_lat[min(len(fast_lat) - 1, int(len(fast_lat) * 0.95))]
    # scheduling bound: at most the in-flight step + the prepared step +
    # own step + assembly deadline; 300 ms leaves CI headroom over the
    # ~65 ms expected worst case, and is far below the convoyed multi-second
    # FIFO alternative
    assert p95 < 0.300, f"fast tenant p95 {p95 * 1e3:.0f} ms blew its SLO " \
                        f"budget behind the slow tenant"


# ---------------------------------------------------------------------------
# tentpole: per-tenant shedding
# ---------------------------------------------------------------------------
def test_open_breaker_sheds_one_tenant_not_the_server():
    net_a, net_b = _mlp(seed=47), _mlp(seed=48)
    ep_a = serving.ModelEndpoint("t_shed_a", net_a, input_shapes=(16,),
                                 max_batch_size=4)
    ep_b = serving.ModelEndpoint("t_shed_b", net_b, input_shapes=(16,),
                                 max_batch_size=4)
    srv = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=16)
    srv.register(ep_a, breaker=CircuitBreaker(scope="serving:t_shed_a",
                                              degraded_after=1, open_after=1,
                                              cooldown_s=60.0))
    srv.register(ep_b)
    srv.start()
    try:
        x = onp.zeros(16, "float32")
        assert srv.predict("t_shed_a", x, timeout=30).shape == (10,)
        srv.breaker_for("t_shed_a").record_failure()      # -> OPEN
        with pytest.raises(ServerOverloadError):
            srv.submit("t_shed_a", x)
        # tenant B is untouched: full service while A sheds
        assert srv.predict("t_shed_b", x, timeout=30).shape == (10,)
        h = srv.health()
        assert h["endpoints"]["t_shed_a"]["circuit"] == "open"
        assert h["endpoints"]["t_shed_b"]["circuit"] == "healthy"
        assert h["circuit"] == "open"          # worst-of for the operator
        snap = serving.stats()["t_shed_a"]
        assert snap["shed"].get("circuit_open", 0) >= 1
    finally:
        srv.stop()
        serving.unregister("t_shed_a")
        serving.unregister("t_shed_b")


# ---------------------------------------------------------------------------
# observability: queue-wait + prep histograms, overlap gauge, shed counter
# ---------------------------------------------------------------------------
def test_queue_wait_prep_and_overlap_metrics():
    from mxnet_tpu import telemetry
    net = _mlp(seed=49)
    ep = serving.ModelEndpoint("t_qw", net, input_shapes=(16,),
                               max_batch_size=4)
    srv = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=64,
                                  pipeline=True)
    srv.register(ep)
    srv.start()
    try:
        rng = onp.random.RandomState(50)
        for _ in range(6):
            srv.predict("t_qw", rng.randn(2, 16).astype("float32"),
                        timeout=60)
    finally:
        srv.stop()
    snap = serving.stats()["t_qw"]
    serving.unregister("t_qw")
    assert snap["queue_wait"]["count"] == 6      # one per request
    assert snap["queue_wait"]["p99_us"] >= 0
    assert snap["prep"]["count"] == snap["counters"]["batches"] > 0
    qw = telemetry.REGISTRY.get("mxtpu_serving_queue_wait_us")
    assert qw.labels("t_qw").summary()["count"] == 6
    prep = telemetry.REGISTRY.get("mxtpu_serving_prep_latency_us")
    assert prep.labels("t_qw").summary()["count"] > 0
    ratio = telemetry.REGISTRY.get("mxtpu_serving_prep_overlap_ratio").value
    assert 0.0 <= ratio <= 1.0


def test_queue_full_shed_reason_counted():
    net = _mlp(seed=51)
    ep = serving.ModelEndpoint("t_shed_q", net, input_shapes=(16,),
                               max_batch_size=8)
    srv = serving.InferenceServer(batch_timeout_ms=60_000.0, max_queue=64)
    srv.register(ep, max_queue=2)            # per-tenant quota override
    srv.start()
    try:
        x = onp.zeros(16, "float32")
        futs = [srv.submit("t_shed_q", x) for _ in range(2)]
        with pytest.raises(ServerOverloadError):
            srv.submit("t_shed_q", x)
        snap = serving.stats()["t_shed_q"]
        assert snap["shed"].get("queue_full", 0) == 1
        assert snap["counters"]["rejected"] == 1
    finally:
        srv.stop(drain=True)
        for f in futs:
            assert f.result(timeout=5).shape == (10,)
        serving.unregister("t_shed_q")
