"""SLO-driven autoscaler + serving replica pool (ISSUE 13).

Covers: the decision core (consecutive-poll hysteresis, cooldown, min/max
bounds — driven deterministically through ``tick(now=...)`` with a stub
monitor and pool), flight events per transition, and the real ServingPool:
replica cutover (scale-down removes from rotation before draining, no
request drops), submit failover to the surviving replica, queue pressure,
and the never-drain-the-last-replica guarantee.
"""
import threading

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, serving
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import flight


class _StubMonitor:
    burn_threshold = 14.0

    def __init__(self):
        self.fast_burn = 0.0
        self.alert = False

    def check_all(self):
        return [{"endpoint": "e", "fast_burn": self.fast_burn,
                 "slow_burn": self.fast_burn, "alert_active": self.alert}]


class _StubPool:
    def __init__(self, size=1):
        self._size = size
        self.pressure = 0.0
        self.ups = 0
        self.downs = 0

    def scale_up(self):
        self._size += 1
        self.ups += 1
        return self._size - 1

    def scale_down(self, drain_timeout_s=None):
        if self._size <= 1:
            return None
        self._size -= 1
        self.downs += 1
        return self._size

    def size(self):
        return self._size

    def queue_pressure(self):
        return self.pressure

    def snapshot(self):
        return {"size": self._size}


def _asc(pool, mon, **kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 3)
    kw.setdefault("up_n", 2)
    kw.setdefault("down_n", 3)
    kw.setdefault("cooldown_s", 5.0)
    kw.setdefault("queue_high", 0.5)
    kw.setdefault("queue_low", 0.05)
    return serving.Autoscaler(pool, monitor=mon, **kw)


# ---------------------------------------------------------------------------
# decision core
# ---------------------------------------------------------------------------

def test_scale_up_needs_consecutive_over_polls():
    pool, mon = _StubPool(), _StubMonitor()
    a = _asc(pool, mon)
    mon.alert = True
    assert a.tick(now=0.0) is None          # 1 of 2
    mon.alert = False                       # pressure clears: counter resets
    mon.fast_burn = 0.0
    a.tick(now=1.0)
    mon.alert = True
    assert a.tick(now=2.0) is None          # 1 of 2 again
    rep = a.tick(now=3.0)                   # 2 of 2 -> act
    assert rep and rep["action"] == "up" and pool.size() == 2


def test_burn_rate_alone_triggers_scale_up():
    pool, mon = _StubPool(), _StubMonitor()
    a = _asc(pool, mon)
    mon.fast_burn = 20.0                    # >= monitor.burn_threshold
    a.tick(now=0.0)
    rep = a.tick(now=1.0)
    assert rep and rep["action"] == "up"


def test_queue_pressure_alone_triggers_scale_up():
    pool, mon = _StubPool(), _StubMonitor()
    a = _asc(pool, mon)
    pool.pressure = 0.9
    a.tick(now=0.0)
    rep = a.tick(now=1.0)
    assert rep and rep["action"] == "up"
    assert rep["queue_pressure"] == 0.9


def test_cooldown_blocks_back_to_back_actions():
    pool, mon = _StubPool(), _StubMonitor()
    a = _asc(pool, mon, cooldown_s=10.0)
    mon.alert = True
    a.tick(now=0.0)
    assert a.tick(now=1.0)["action"] == "up"
    for t in (2.0, 5.0, 9.0):               # inside the settle window
        assert a.tick(now=t) is None
    assert a.tick(now=12.0)["action"] == "up"   # window passed
    assert pool.size() == 3


def test_max_and_min_replica_bounds():
    pool, mon = _StubPool(size=3), _StubMonitor()
    a = _asc(pool, mon, max_replicas=3, cooldown_s=0.0)
    mon.alert = True
    for t in range(4):
        assert a.tick(now=float(t)) is None, "at max: never scale up"
    mon.alert = False
    for t in range(10, 20):
        a.tick(now=float(t))
    assert pool.size() == 1, "idle drains to min_replicas"
    for t in range(30, 40):
        assert a.tick(now=float(t)) is None, "at min: never scale down"


def test_actions_leave_flight_events():
    pool, mon = _StubPool(), _StubMonitor()
    a = _asc(pool, mon, cooldown_s=0.0)
    n0 = len(flight.recent_events())
    mon.alert = True
    a.tick(now=0.0)
    a.tick(now=1.0)                          # up
    mon.alert = False
    for t in range(2, 6):
        a.tick(now=float(t))                 # down after 3 idle polls
    kinds = [e["kind"] for e in flight.recent_events()[n0:]]
    assert "autoscale_up" in kinds and "autoscale_down" in kinds
    up_ev = next(e for e in flight.recent_events()[n0:]
                 if e["kind"] == "autoscale_up")
    assert up_ev["attrs"]["action"] == "up"
    assert "max_fast_burn" in up_ev["attrs"]
    assert [r["action"] for r in a.actions] == ["up", "down"]


# ---------------------------------------------------------------------------
# the real pool
# ---------------------------------------------------------------------------

def _mlp(seed, in_dim=6, out_dim=3):
    mx.random.seed(seed)
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"), nn.Dense(out_dim))
    net.initialize(mx.init.Xavier())
    net(nd.array(onp.zeros((2, in_dim), "float32")))
    return net


@pytest.fixture
def pool3():
    """A real two-replica pool over one client-facing endpoint name."""
    name = "t_pool_ep"
    nets = {}

    def factory(rid):
        net = _mlp(11)
        nets[rid] = net
        srv = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=64)
        srv.register(serving.ModelEndpoint(
            name, net, input_shapes=(6,), max_batch_size=4))
        return srv

    pool = serving.ServingPool(factory, initial_replicas=2)
    try:
        yield pool, name, nets
    finally:
        pool.stop(drain=True)
        serving.unregister(name)


def test_pool_serves_from_rotation_bitwise(pool3):
    pool, name, nets = pool3
    assert pool.size() == 2
    xs = onp.random.RandomState(1).randn(8, 6).astype("float32")
    outs = [pool.predict(name, xs[i], timeout=60).asnumpy()
            for i in range(8)]
    # one blocking predict at a time: every row was served alone, in bucket
    # 1, so the direct forward runs at batch 1 too
    direct = [nets[0](nd.array(xs[i:i + 1])).asnumpy()[0] for i in range(8)]
    assert all(onp.array_equal(o, direct[i]) for i, o in enumerate(outs)), \
        "every replica serves bitwise-identical outputs"


def test_scale_down_drains_without_dropping(pool3):
    pool, name, nets = pool3
    xs = onp.random.RandomState(2).randn(16, 6).astype("float32")
    stop = threading.Event()
    errors = []
    served = {"n": 0}

    def client():
        i = 0
        while not stop.is_set():
            try:
                pool.predict(name, xs[i % 16], timeout=60)
                served["n"] += 1
            except Exception as e:
                errors.append(repr(e))
            i += 1

    t = threading.Thread(target=client)
    t.start()
    try:
        rid = pool.scale_down()
        assert rid is not None
        assert pool.size() == 1
        rid2 = pool.scale_down()
        assert rid2 is None, "the last replica is never drained"
    finally:
        stop.set()
        t.join()
    assert not errors, f"cutover dropped requests: {errors[:3]}"
    assert served["n"] > 0


def test_submit_fails_over_a_closed_replica(pool3):
    pool, name, nets = pool3
    # stop one replica behind the pool's back (mid-cutover window)
    victim = pool._rotation()[0]
    victim.server.stop(drain=True)
    x = onp.random.RandomState(3).randn(6).astype("float32")
    out = pool.predict(name, x, timeout=60)    # must fall through
    want = nets[0](nd.array(x[None, :])).asnumpy()[0]
    assert onp.array_equal(out.asnumpy(), want)


def test_scale_up_adds_live_replica(pool3):
    pool, name, nets = pool3
    rid = pool.scale_up()
    assert pool.size() == 3
    snap = pool.snapshot()
    assert {r["rid"] for r in snap["replicas"]} >= {rid}
    assert all(r["state"] == "running" for r in snap["replicas"])
    assert 0.0 <= snap["queue_pressure"] <= 1.0
