"""mxnet_tpu.serving tests: dynamic batching, shape-bucketed executable
cache, admission control, deadlines, drain, and observability — all on the
8-device CPU mesh (tier-1, JAX_PLATFORMS=cpu).

The load-bearing property is the acceptance criterion: outputs served through
the batcher (concatenated with other clients' rows, padded to a bucket, run
through a cached executable, sliced back out) are BITWISE equal to a direct
single-batch forward of the same rows, while the endpoint compiles exactly
once per shape bucket.
"""
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, serving
from mxnet_tpu.gluon import nn
from mxnet_tpu.serving import bucketing
from mxnet_tpu.gluon.block import HybridBlock
from mxnet_tpu.serving import (RequestTimeoutError, ServerClosedError,
                               ServerOverloadError)


def _small_net(seed=0, in_shape=(3, 8, 8)):
    """Conv+BN+Dense net: exercises moving-stats aux handling and both conv
    and matmul kernels in the served executable."""
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, kernel_size=3, padding=1))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.add(nn.GlobalAvgPool2D())
        net.add(nn.Dense(10))
    net.initialize()
    net(nd.array(onp.random.randn(2, *in_shape).astype("float32")))
    return net


def _mlp(seed=0, in_dim=16):
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(10))
    net.initialize()
    net(nd.array(onp.random.randn(2, in_dim).astype("float32")))
    return net


def _serve(ep, **kwargs):
    srv = serving.InferenceServer(**kwargs)
    srv.register(ep)
    srv.start()
    return srv


# ---------------------------------------------------------------------------
# correctness: concurrent clients vs direct forward
# ---------------------------------------------------------------------------
def test_concurrent_clients_bitwise_match_direct_forward():
    net = _small_net(seed=1)
    # one bucket, so that the batch size a row was served at is known: the
    # same program at another batch size is one ulp away on XLA:CPU (and on
    # the chip), which is rounding, not a serving fault
    ep = serving.ModelEndpoint("t_conc", net, input_shapes=(3, 8, 8),
                               max_batch_size=8, buckets=(8,))
    srv = _serve(ep, batch_timeout_ms=5.0, max_queue=64)
    try:
        rng = onp.random.RandomState(2)
        xs = [rng.randn(3, 8, 8).astype("float32") for _ in range(16)]
        results = [None] * len(xs)

        def client(i):
            results[i] = srv.predict("t_conc", xs[i], timeout=60)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        srv.stop()
    # the served executable is the same single-XLA-computation trace that
    # hybridize() produces, so the contract is BITWISE equality against the
    # hybridized direct forward at the served bucket (eager op-by-op
    # dispatch may differ by float rounding because XLA fuses the whole
    # graph differently)
    net.hybridize()
    for i, x in enumerate(xs):
        direct = net(nd.array(bucketing.pad_rows(x[None], 8))).asnumpy()[0]
        got = results[i].asnumpy()
        assert onp.array_equal(direct, got), \
            f"client {i}: served output != direct forward " \
            f"(max abs diff {onp.abs(direct - got).max()})"
    snap = serving.stats()["t_conc"]
    assert snap["counters"]["completed"] == len(xs)
    # 16 singles through an 8-row batcher: strictly fewer device steps than
    # requests proves dynamic batching actually batched
    assert snap["counters"]["batches"] < len(xs)


def test_batched_requests_bitwise_match_direct_forward():
    net = _small_net(seed=3)
    ep = serving.ModelEndpoint("t_batched", net, input_shapes=(3, 8, 8),
                               max_batch_size=8)
    srv = _serve(ep, batch_timeout_ms=2.0, max_queue=64)
    try:
        rng = onp.random.RandomState(4)
        xb = rng.randn(5, 3, 8, 8).astype("float32")
        out = srv.predict("t_batched", xb, timeout=60).asnumpy()
    finally:
        srv.stop()
    net.hybridize()
    direct = net(nd.array(xb)).asnumpy()
    assert out.shape == direct.shape
    assert onp.array_equal(out, direct)


def test_bucket_padding_equivalence_and_occupancy():
    """Odd-sized requests pad up to the next bucket; padded rows must not
    perturb real rows, and occupancy accounting must see the padding."""
    net = _mlp(seed=5)
    ep = serving.ModelEndpoint("t_pad", net, input_shapes=(16,),
                               max_batch_size=8)
    assert ep.buckets == (1, 2, 4, 8)
    srv = _serve(ep, batch_timeout_ms=1.0, max_queue=64)
    net.hybridize()
    try:
        rng = onp.random.RandomState(6)
        for rows in (3, 5, 7):           # none of these is a bucket size
            xb = rng.randn(rows, 16).astype("float32")
            out = srv.predict("t_pad", xb, timeout=60).asnumpy()
            direct = net(nd.array(xb)).asnumpy()
            assert onp.array_equal(out, direct), f"rows={rows}"
    finally:
        srv.stop()
    snap = serving.stats()["t_pad"]
    assert snap["counters"]["padded_rows"] > 0
    assert 0.0 < snap["batch_occupancy"] < 1.0


def test_single_example_resolves_unbatched():
    net = _mlp(seed=7)
    ep = serving.ModelEndpoint("t_squeeze", net, input_shapes=(16,),
                               max_batch_size=4)
    srv = _serve(ep, batch_timeout_ms=1.0, max_queue=16)
    try:
        x = onp.random.RandomState(8).randn(16).astype("float32")
        out = srv.predict("t_squeeze", x, timeout=60)
        assert out.shape == (10,)
        xb = x[None]
        outb = srv.predict("t_squeeze", xb, timeout=60)
        assert outb.shape == (1, 10)
        assert onp.array_equal(out.asnumpy(), outb.asnumpy()[0])
    finally:
        srv.stop()


def test_resnet_eight_clients_bitwise_and_one_compile():
    """Acceptance shape: a model-zoo ResNet endpoint under >= 8 concurrent
    clients must serve outputs bitwise-equal to a direct single-batch forward
    and compile exactly once for its (single) bucket."""
    from mxnet_tpu.gluon.model_zoo import vision
    net = vision.get_model("resnet18_v1", classes=10)
    net.initialize(mx.init.Xavier())
    net(nd.array(onp.zeros((1, 3, 32, 32), "float32")))
    ep = serving.ModelEndpoint("t_resnet", net, input_shapes=(3, 32, 32),
                               max_batch_size=8, buckets=(8,))
    srv = _serve(ep, batch_timeout_ms=20.0, max_queue=64)
    assert ep.stats.counters["compiles"] == 1
    try:
        rng = onp.random.RandomState(23)
        xs = [rng.randn(3, 32, 32).astype("float32") for _ in range(8)]
        results = [None] * 8

        def client(i):
            results[i] = srv.predict("t_resnet", xs[i], timeout=120)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        srv.stop()
    net.hybridize()
    direct = net(nd.array(onp.stack(xs))).asnumpy()
    for i in range(8):
        assert onp.array_equal(results[i].asnumpy(), direct[i]), f"client {i}"
    snap = serving.stats()["t_resnet"]
    assert snap["counters"]["compiles"] == 1     # never recompiled
    assert snap["latency"]["count"] == 8 and snap["latency"]["p99_us"] > 0


# ---------------------------------------------------------------------------
# executable cache: one compile per bucket, ever
# ---------------------------------------------------------------------------
def test_compiles_once_per_bucket_then_only_hits():
    net = _mlp(seed=9)
    ep = serving.ModelEndpoint("t_cache", net, input_shapes=(16,),
                               max_batch_size=8)
    srv = _serve(ep, batch_timeout_ms=1.0, max_queue=64)  # register() warms
    assert ep.stats.counters["compiles"] == len(ep.buckets)
    try:
        rng = onp.random.RandomState(10)
        for _ in range(3):
            for rows in (1, 2, 3, 4, 5, 6, 7, 8):
                srv.predict("t_cache", rng.randn(rows, 16).astype("float32"),
                            timeout=60)
    finally:
        srv.stop()
    snap = serving.stats()["t_cache"]
    assert snap["counters"]["compiles"] == len(ep.buckets), \
        "traffic after warmup must never recompile"
    assert snap["counters"]["cache_hits"] == snap["counters"]["batches"]


# ---------------------------------------------------------------------------
# admission control / deadlines / drain
# ---------------------------------------------------------------------------
def test_overload_rejected_then_drained():
    net = _mlp(seed=11)
    ep = serving.ModelEndpoint("t_over", net, input_shapes=(16,),
                               max_batch_size=8)
    # queue bound (4) below max_batch_size and a long batch timeout: the
    # worker never dispatches on its own, so submissions must hit the bound
    srv = _serve(ep, batch_timeout_ms=60_000.0, max_queue=4)
    futs = []
    try:
        x = onp.zeros(16, "float32")
        for _ in range(4):
            futs.append(srv.submit("t_over", x))
        with pytest.raises(ServerOverloadError):
            srv.submit("t_over", x)
        snap = serving.stats()["t_over"]
        assert snap["counters"]["rejected"] == 1
        assert snap["queue_depth"] == 4          # bound held, queue didn't grow
    finally:
        srv.stop(drain=True)
    # graceful drain flushed the admitted work through the device
    for f in futs:
        assert f.result(timeout=1).shape == (10,)
    snap = serving.stats()["t_over"]
    assert snap["counters"]["completed"] == 4
    assert snap["queue_depth"] == 0


def test_deadline_expired_request_is_dropped_not_computed():
    net = _mlp(seed=12)
    ep = serving.ModelEndpoint("t_dead", net, input_shapes=(16,),
                               max_batch_size=8)
    srv = _serve(ep, batch_timeout_ms=150.0, max_queue=16)
    try:
        x = onp.zeros(16, "float32")
        batches_before = ep.stats.counters["batches"]
        fut = srv.submit("t_dead", x, deadline_ms=1.0)
        with pytest.raises(RequestTimeoutError):
            fut.result(timeout=10)
        assert ep.stats.counters["deadline_drops"] == 1
        # the expired request must not have occupied a device step
        assert ep.stats.counters["batches"] == batches_before
        # endpoint still serves fresh work afterwards
        out = srv.predict("t_dead", x, timeout=60)
        assert out.shape == (10,)
    finally:
        srv.stop()


def test_stop_without_drain_fails_pending_and_refuses_new():
    net = _mlp(seed=13)
    ep = serving.ModelEndpoint("t_halt", net, input_shapes=(16,),
                               max_batch_size=8)
    srv = _serve(ep, batch_timeout_ms=60_000.0, max_queue=16)
    x = onp.zeros(16, "float32")
    fut = srv.submit("t_halt", x)
    srv.stop(drain=False)
    with pytest.raises(ServerClosedError):
        fut.result(timeout=1)
    with pytest.raises(ServerClosedError):
        srv.submit("t_halt", x)
    assert ep.stats.counters["cancelled"] == 1


def test_request_validation():
    net = _mlp(seed=14)
    ep = serving.ModelEndpoint("t_valid", net, input_shapes=(16,),
                               max_batch_size=4)
    srv = _serve(ep, batch_timeout_ms=1.0, max_queue=16)
    try:
        with pytest.raises(mx.MXNetError):       # unknown endpoint
            srv.submit("nope", onp.zeros(16, "float32"))
        with pytest.raises(mx.MXNetError):       # wrong per-example shape
            srv.submit("t_valid", onp.zeros((2, 15), "float32"))
        with pytest.raises(mx.MXNetError):       # oversized request
            srv.submit("t_valid", onp.zeros((5, 16), "float32"))
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# dtypes / quantized endpoints
# ---------------------------------------------------------------------------
def test_bf16_endpoint_matches_direct_forward():
    net = _mlp(seed=15)
    net.cast("bfloat16")
    net(nd.array(onp.zeros((1, 16), "float32")).astype("bfloat16"))
    ep = serving.ModelEndpoint("t_bf16", net, input_shapes=(16,),
                               dtype="bfloat16", max_batch_size=4)
    srv = _serve(ep, batch_timeout_ms=1.0, max_queue=16)
    try:
        rng = onp.random.RandomState(16)
        xb = rng.randn(3, 16).astype("float32")
        out = srv.predict("t_bf16", xb, timeout=60)
        assert str(out.dtype) == "bfloat16"
    finally:
        srv.stop()
    net.hybridize()
    direct = net(nd.array(xb).astype("bfloat16"))
    assert onp.array_equal(out.asnumpy().astype("float32"),
                           direct.asnumpy().astype("float32"))


def test_quantized_int8_endpoint_serves_and_matches_direct():
    from mxnet_tpu.contrib.quantization import quantize_net
    net = _mlp(seed=17)
    rng = onp.random.RandomState(18)
    calib = [nd.array(rng.randn(8, 16).astype("float32")) for _ in range(4)]
    qnet = quantize_net(net, calib_data=calib, calib_mode="naive")
    ep = serving.ModelEndpoint("t_int8", qnet, input_shapes=(16,),
                               max_batch_size=4)
    srv = _serve(ep, batch_timeout_ms=1.0, max_queue=16)
    try:
        xb = rng.randn(3, 16).astype("float32")
        out = srv.predict("t_int8", xb, timeout=60).asnumpy()
    finally:
        srv.stop()
    direct = qnet(nd.array(xb)).asnumpy()
    # int8 path: compare against the quantized net's own direct forward
    onp.testing.assert_allclose(out, direct, rtol=1e-5, atol=1e-5)


def test_endpoint_from_dynamic_batch_checkpoint(tmp_path):
    """An exported checkpoint (dynamic_batch=True) serves across buckets
    without the defining Python class, bitwise-equal to the source net."""
    net = _mlp(seed=30)
    net.hybridize()
    net(nd.array(onp.zeros((2, 16), "float32")))
    mf, pf = net.export(str(tmp_path / "mlp"), dynamic_batch=True)
    ep = serving.ModelEndpoint.from_checkpoint(
        "t_ckpt", mf, pf, input_shapes=(16,), max_batch_size=4)
    srv = _serve(ep, batch_timeout_ms=1.0, max_queue=16)
    try:
        xb = onp.random.RandomState(31).randn(3, 16).astype("float32")
        out = srv.predict("t_ckpt", xb, timeout=60).asnumpy()
    finally:
        srv.stop()
    assert ep.stats.counters["compiles"] == len(ep.buckets)
    import jax
    padded = bucketing.pad_rows(xb, bucketing.bucket_for(len(xb), ep.buckets))
    # like with like: a SymbolBlock has no Parameter, so the endpoint's
    # executables hold the checkpoint's weights as constants. The same
    # program with the weights closed over, at the served bucket, is what
    # served the rows: equal bit for bit.
    blk = ep.block
    same = jax.jit(lambda x: blk(mx.nd.NDArray(x)).data)
    assert onp.array_equal(out, onp.asarray(same(padded))[:len(xb)])
    # The source net takes its weights as arguments, which is another XLA
    # program: XLA:CPU multiplies by a constant matrix in another order.
    # Measured 1.5 float32 eps of the largest output; held to 4.
    direct = net(nd.array(padded)).asnumpy()[:len(xb)]
    assert onp.abs(out - direct).max() <= \
        4 * onp.finfo("float32").eps * onp.abs(direct).max()


def test_fixed_batch_checkpoint_rejected(tmp_path):
    net = _mlp(seed=32)
    net.hybridize()
    net(nd.array(onp.zeros((2, 16), "float32")))
    mf, pf = net.export(str(tmp_path / "mlp_fixed"))      # fixed batch
    with pytest.raises(mx.MXNetError):
        serving.ModelEndpoint.from_checkpoint(
            "t_ckpt_fixed", mf, pf, input_shapes=(16,), max_batch_size=4)


# ---------------------------------------------------------------------------
# multi-input / multi-output models
# ---------------------------------------------------------------------------
class _TwoInTwoOut(HybridBlock):
    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.fa = nn.Dense(6)
            self.fb = nn.Dense(4)

    def forward(self, a, b):
        return self.fa(a), self.fb(a + b)


def test_multi_input_multi_output_endpoint():
    net = _TwoInTwoOut()
    net.initialize()
    z = nd.array(onp.zeros((1, 5), "float32"))
    net(z, z)
    ep = serving.ModelEndpoint("t_mimo", net, input_shapes=((5,), (5,)),
                               max_batch_size=4)
    srv = _serve(ep, batch_timeout_ms=1.0, max_queue=16)
    try:
        rng = onp.random.RandomState(19)
        a = rng.randn(3, 5).astype("float32")
        b = rng.randn(3, 5).astype("float32")
        oa, ob = srv.predict("t_mimo", (a, b), timeout=60)
    finally:
        srv.stop()
    net.hybridize()
    da, db = net(nd.array(a), nd.array(b))
    assert onp.array_equal(oa.asnumpy(), da.asnumpy())
    assert onp.array_equal(ob.asnumpy(), db.asnumpy())


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------
def test_stats_snapshot_latency_and_profiler_integration():
    from mxnet_tpu import profiler
    net = _mlp(seed=20)
    ep = serving.ModelEndpoint("t_obs", net, input_shapes=(16,),
                               max_batch_size=4)
    srv = _serve(ep, batch_timeout_ms=1.0, max_queue=16)
    profiler.set_config(aggregate_stats=True)
    profiler.start()
    try:
        rng = onp.random.RandomState(21)
        for _ in range(5):
            srv.predict("t_obs", rng.randn(2, 16).astype("float32"),
                        timeout=60)
    finally:
        profiler.stop()
        srv.stop()
    snap = serving.stats()["t_obs"]
    lat = snap["latency"]
    assert lat["count"] == 5
    assert 0 < lat["p50_us"] <= lat["p95_us"] <= lat["p99_us"]
    assert lat["min_us"] > 0 and lat["max_us"] >= lat["p50_us"] * 0.5
    assert snap["step"]["count"] == snap["counters"]["batches"] > 0
    assert snap["queue_peak"] >= 2
    # serving steps landed in the profiler aggregate table alongside ops
    table = profiler.dumps(reset=True)
    assert "serving[t_obs]" in table


def test_latency_histogram_percentiles():
    from mxnet_tpu.serving.stats import LatencyHistogram
    h = LatencyHistogram()
    for us in (100, 200, 300, 400, 500, 600, 700, 800, 900, 10_000):
        h.record(us)
    # ~9%-wide geometric bins: p50 within a bin of the true median
    assert 400 <= h.percentile(50) <= 620
    assert h.percentile(99) >= 5_000
    assert h.snapshot()["count"] == 10


def test_endpoint_registry():
    net = _mlp(seed=22)
    serving.ModelEndpoint("t_reg", net, input_shapes=(16,), max_batch_size=2)
    assert "t_reg" in serving.list_endpoints()
    assert serving.get_endpoint("t_reg").max_batch_size == 2
    assert "t_reg" in serving.stats()
    serving.unregister("t_reg")
    assert "t_reg" not in serving.list_endpoints()
    with pytest.raises(mx.MXNetError):
        serving.get_endpoint("t_reg")
