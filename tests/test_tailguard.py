"""serving.tailguard (r18) tests: end-to-end deadline propagation, hedged
requests under a token-bucket budget, per-tier retry budgets, and the
brownout degradation ladder — all on the 8-device CPU mesh (tier-1).

The load-bearing regressions pinned here:

- a retry loop handed a deadline NEVER sleeps past it (the 50 ms clamp
  regression: a 10 s backoff against a 50 ms budget sleeps <= ~50 ms), and a
  spent budget raises DeadlineExceeded chained under the last real error;
- RequestTimeoutError IS-A DeadlineExceeded — one taxonomy for "too late",
  so callers catching the new end-to-end deadline also catch the legacy
  per-request timeout;
- hedged pool results are bitwise-equal to unhedged serving, and hedge
  volume is bounded by the token bucket;
- the brownout ladder sheds bulk before silver and never gold, with
  hysteresis in both directions.
"""
import io
import os
import sys
import time
from contextlib import contextmanager

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, nd, serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience.retry import RetryPolicy
from mxnet_tpu.serving import tailguard
from mxnet_tpu.serving.errors import (DeadlineExceeded, RequestTimeoutError,
                                      ServerOverloadError, ServingError)


def _metric_total(name):
    """Sum a metric family across its label series (0.0 if unregistered)."""
    fam = telemetry.REGISTRY.get(name)
    if fam is None:
        return 0.0
    return float(sum(c.value for _, c in fam._series()))


@contextmanager
def _knobs(**vals):
    saved = {k: config.get(k) for k in vals}
    try:
        for k, v in vals.items():
            config.set(k, v)
        yield
    finally:
        for k, v in saved.items():
            config.set(k, v)


def _mlp(seed=7, in_dim=8, out_dim=4):
    mx.random.seed(seed)
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
    net.initialize(mx.init.Xavier())
    net(nd.array(onp.zeros((2, in_dim), "float32")))
    return net


# ---------------------------------------------------------------------------
# deadline propagation
# ---------------------------------------------------------------------------
def test_deadline_mint_check_and_metric():
    d = tailguard.Deadline(60_000.0)
    assert 0.0 < d.remaining_ms() <= 60_000.0
    assert not d.expired()
    d.check("t_dl_ok")                         # budget left: no raise

    spent = tailguard.Deadline(0.0)
    time.sleep(0.002)
    assert spent.expired()
    before = _metric_total("mxtpu_deadline_exceeded_total")
    with pytest.raises(DeadlineExceeded):
        spent.check("t_dl_spent")
    assert _metric_total("mxtpu_deadline_exceeded_total") - before == 1.0
    # objectless accounting (the batcher dropping expired heads)
    tailguard.deadline_expired("t_dl_counted", n=3)
    assert _metric_total("mxtpu_deadline_exceeded_total") - before == 4.0


def test_deadline_adopts_absolute_expiry():
    now = tailguard._now_us()
    d = tailguard.Deadline.at(now + 500_000)
    assert 0.0 < d.remaining_ms() <= 500.0
    assert tailguard.Deadline.at(now - 1).expired()


def test_deadline_taxonomy():
    # one "too late" family: legacy per-request timeouts ARE deadline
    # exceedances, so a caller catching the r18 error catches both
    assert issubclass(DeadlineExceeded, ServingError)
    assert issubclass(RequestTimeoutError, DeadlineExceeded)
    with pytest.raises(DeadlineExceeded):
        raise RequestTimeoutError("legacy timeout")


# ---------------------------------------------------------------------------
# retry backoff x deadline (the 50 ms clamp regression)
# ---------------------------------------------------------------------------
def test_retry_backoff_clamped_to_remaining_deadline():
    slept = []
    pol = RetryPolicy(max_attempts=3, base_ms=10_000.0, max_ms=10_000.0,
                      multiplier=1.0, jitter=0.0, sleep=slept.append)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("UNAVAILABLE: injected transient")
        return "served"

    deadline = tailguard.Deadline(50.0)
    assert pol.run(flaky, site="t_clamp",
                   deadline_us=deadline.deadline_us) == "served"
    # a 10 s configured backoff must be clamped to the ~50 ms the deadline
    # can afford — never oversleep what the client asked for
    assert len(slept) == 2
    assert all(0.0 < s <= 0.051 for s in slept)


def test_retry_spent_deadline_raises_deadline_exceeded_chained():
    pol = RetryPolicy(max_attempts=4, base_ms=1.0, max_ms=1.0,
                      jitter=0.0, sleep=lambda s: None)

    def always_down():
        raise RuntimeError("UNAVAILABLE: still down")

    d = tailguard.Deadline(0.0)
    time.sleep(0.002)
    with pytest.raises(DeadlineExceeded) as ei:
        pol.run(always_down, site="t_spent", deadline_us=d.deadline_us)
    assert isinstance(ei.value.__cause__, RuntimeError)


def test_retry_policy_budget_tier_gate():
    with _knobs(MXNET_RETRY_BUDGET_RATIO=0.001, MXNET_RETRY_BUDGET_MIN=1.0,
                MXNET_RETRY_BUDGET_CAP=1.0):
        tailguard.RETRY_BUDGETS.reset()
        calls = {"n": 0}

        def always_down():
            calls["n"] += 1
            raise RuntimeError("UNAVAILABLE: storm")

        pol = RetryPolicy(max_attempts=10, base_ms=0.1, max_ms=0.1,
                          jitter=0.0, sleep=lambda s: None)
        # 1 budget token -> exactly one retry, then the dry bucket
        # propagates the ORIGINAL error (bounded shed, classified)
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            pol.run(always_down, site="t_gate", budget_tier="t_gate_tier")
        assert calls["n"] == 2
    tailguard.RETRY_BUDGETS.reset()


# ---------------------------------------------------------------------------
# token buckets + retry budgets
# ---------------------------------------------------------------------------
def test_token_bucket_mechanics():
    b = tailguard.TokenBucket(2.0, 3.0)
    assert b.balance() == 2.0
    assert b.take() and b.take() and not b.take()
    b.deposit(10.0)
    assert b.balance() == 3.0                  # capped
    assert tailguard.TokenBucket(9.0, 4.0).balance() == 4.0  # seed capped


def test_retry_budgets_ratio_zero_disables():
    rb = tailguard.RetryBudgets()
    with _knobs(MXNET_RETRY_BUDGET_RATIO=0.0):
        assert all(rb.allow("t_frozen") for _ in range(100))


def test_retry_budgets_exhaust_and_rearm():
    rb = tailguard.RetryBudgets()
    with _knobs(MXNET_RETRY_BUDGET_RATIO=1.0, MXNET_RETRY_BUDGET_MIN=2.0,
                MXNET_RETRY_BUDGET_CAP=3.0):
        assert rb.allow("t_x") and rb.allow("t_x")
        before = _metric_total("mxtpu_retry_budget_exhausted_total")
        assert not rb.allow("t_x") and not rb.allow("t_x")
        assert _metric_total("mxtpu_retry_budget_exhausted_total") \
            - before == 2.0
        rb.on_work("t_x", units=1.0)           # ratio 1.0 -> one token back
        assert rb.allow("t_x")
        assert rb.balance("t_x") == 0.0
        rb.on_work("t_x", units=10.0)          # income is capped
        assert rb.balance("t_x") == 3.0


# ---------------------------------------------------------------------------
# hedging
# ---------------------------------------------------------------------------
def test_hedge_policy_adaptive_delay():
    p = tailguard.HedgePolicy()
    with _knobs(MXNET_HEDGE_DELAY_FACTOR=2.0, MXNET_HEDGE_DELAY_MIN_MS=10.0):
        assert p.delay_s() == pytest.approx(0.010)          # floor
        assert p.delay_s(predicted_step_us=20_000.0) \
            == pytest.approx(0.040)                          # predicted x2
        for _ in range(100):
            p.observe_latency(100_000.0)
        assert p.delay_s() == pytest.approx(0.100)           # measured p95


def test_hedge_budget_and_latch():
    tailguard.hedge_reset()
    try:
        with _knobs(MXNET_HEDGE_BUDGET_RATIO=0.5):
            assert tailguard.hedge_allowed()                 # seed token
            before = _metric_total("mxtpu_hedge_budget_exhausted_total")
            assert not tailguard.hedge_allowed()             # dry
            assert _metric_total("mxtpu_hedge_budget_exhausted_total") \
                - before == 1.0
            tailguard.hedge_deposit()
            tailguard.hedge_deposit()                        # 2 x 0.5 = 1.0
            assert tailguard.hedge_allowed()
    finally:
        tailguard.hedge_reset()


def test_hedged_pool_bitwise_and_accounting():
    svc = "t_hedge_pool"
    nets = {}

    def factory(rid):
        net = _mlp(seed=7)            # same seed: replicas serve bitwise-
        nets[rid] = net               # identical outputs, so hedging is safe
        srv = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=256)
        # one bucket: under load the batcher coalesces the burst into
        # batches of any size, and the oracle must know the size served
        srv.register(serving.ModelEndpoint(
            svc, net, input_shapes=(8,), max_batch_size=4, buckets=(4,)))
        return srv

    xs = onp.random.RandomState(11).randn(12, 8).astype("float32")
    counters = ("mxtpu_hedge_requests_total", "mxtpu_hedge_wins_total",
                "mxtpu_hedge_cancelled_total", "mxtpu_hedge_wasted_total")
    pool = serving.ServingPool(factory, initial_replicas=2)
    try:
        # zero delay + unit income: every submit hedges immediately — the
        # worst case for the first-response-wins settle path
        with _knobs(MXNET_HEDGE_ENABLE=True, MXNET_HEDGE_BUDGET_RATIO=1.0,
                    MXNET_HEDGE_DELAY_MIN_MS=0.0,
                    MXNET_HEDGE_DELAY_FACTOR=0.0):
            tailguard.hedge_reset()
            before = {m: _metric_total(m) for m in counters}
            futs = [pool.submit(svc, xs[i], deadline_ms=30_000.0)
                    for i in range(len(xs))]
            outs = [f.result(timeout=60).asnumpy() for f in futs]
            delta = {m: _metric_total(m) - before[m] for m in counters}
    finally:
        tailguard.hedge_reset()
        pool.stop(drain=True)
        serving.unregister(svc)

    direct = onp.concatenate([nets[0](nd.array(xs[i:i + 4])).asnumpy()
                              for i in range(0, len(xs), 4)])
    assert all(onp.array_equal(o, direct[i]) for i, o in enumerate(outs))
    hedges = delta["mxtpu_hedge_requests_total"]
    assert hedges >= 1
    # every settled hedge pair has exactly one loser, dropped at batch
    # assembly (cancelled) or after entering a batch (wasted)
    assert delta["mxtpu_hedge_cancelled_total"] \
        + delta["mxtpu_hedge_wasted_total"] <= hedges
    assert delta["mxtpu_hedge_wins_total"] <= hedges


# ---------------------------------------------------------------------------
# brownout ladder
# ---------------------------------------------------------------------------
class _BurnStub:
    """Injectable stand-in for the SLO monitor's burn surface."""
    burn_threshold = 14.0

    def __init__(self):
        self.burning = False

    def check_all(self):
        burn = 99.0 if self.burning else 0.0
        return [{"endpoint": "t_brown", "fast_burn": burn,
                 "slow_burn": burn, "alert_active": self.burning}]


def test_brownout_ladder_hysteresis_and_effects():
    mon = _BurnStub()
    bc = tailguard.BrownoutController(monitor=mon)
    with _knobs(MXNET_BROWNOUT_ENABLE=True, MXNET_BROWNOUT_UP_N=2,
                MXNET_BROWNOUT_DOWN_N=2, MXNET_BROWNOUT_MAX_NEW_TOKENS=8,
                MXNET_BROWNOUT_TIMEOUT_BOOST=4.0):
        assert bc.timeout_boost() == 1.0
        assert bc.clamp_max_new_tokens(100) == 100

        mon.burning = True
        assert bc.tick() is None               # hysteresis: one hot tick
        shift = bc.tick()
        assert shift["to_level"] == 1 and shift["direction"] == "degrade"
        # level 1 softens, sheds nobody
        assert bc.timeout_boost() == 4.0
        assert bc.clamp_max_new_tokens(100) == 8
        assert bc.shedding_tiers() == []

        bc.tick()
        assert bc.tick()["to_level"] == 2
        assert bc.shed_tier("bulk")
        assert not bc.shed_tier("silver") and not bc.shed_tier("gold")
        assert bc.shedding_tiers() == ["bulk"]

        bc.tick()
        assert bc.tick()["to_level"] == 3      # ceiling
        assert bc.shed_tier("silver") and not bc.shed_tier("gold")
        assert bc.shedding_tiers() == ["bulk", "silver"]
        bc.tick()
        assert bc.level == 3                   # never past _MAX_LEVEL

        mon.burning = False
        assert bc.tick() is None               # recovery hysteresis too
        shift = bc.tick()
        assert shift["to_level"] == 2 and shift["direction"] == "recover"
        snap = bc.snapshot()
        assert snap["level"] == 2 and snap["shedding"] == ["bulk"]
    bc.reset()
    assert bc.level == 0


def test_brownout_disabled_steps_down():
    bc = tailguard.BrownoutController(monitor=_BurnStub())
    bc.level = 2
    with _knobs(MXNET_BROWNOUT_ENABLE=False):
        shift = bc.tick()
        assert shift["direction"] == "recover" and shift["to_level"] == 1
        assert bc.tick()["to_level"] == 0
        assert bc.tick() is None               # level 0 stays quiet
    bc.reset()


def test_register_tier_validation_and_brownout_shed():
    srv = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=64)
    names = ("t_tier_gold", "t_tier_bulk", "t_tier_bad")
    eps = {n: serving.ModelEndpoint(n, _mlp(seed=3), input_shapes=(8,),
                                    max_batch_size=4) for n in names}
    x = onp.random.RandomState(4).randn(8).astype("float32")
    try:
        srv.register(eps["t_tier_gold"])                  # default tier gold
        srv.register(eps["t_tier_bulk"], tier="bulk")
        with pytest.raises(MXNetError, match="unknown tenant tier"):
            srv.register(eps["t_tier_bad"], tier="platinum")
        srv.start()
        tailguard.BROWNOUT.level = 2                      # force: shed bulk
        with pytest.raises(ServerOverloadError, match="brownout"):
            srv.predict("t_tier_bulk", x, timeout=30)
        out = srv.predict("t_tier_gold", x, timeout=30)   # gold always serves
        assert out is not None
    finally:
        tailguard.BROWNOUT.reset()
        srv.stop(drain=True)
        for n in names:
            serving.unregister(n)


# ---------------------------------------------------------------------------
# chaos matrix smoke (tools/chaos_check.py, fixed seed)
# ---------------------------------------------------------------------------
def test_chaos_retry_storm_smoke():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import chaos_check
    buf = io.StringIO()
    result = chaos_check.run_chaos(seed=5, requests=16,
                                   scenarios=["retry_storm"], out=buf)
    assert result["ok"], buf.getvalue()
    rs = result["retry_storm"]
    assert rs["amplification_budgeted"] < 2.0     # storm contained...
    assert rs["amplification_unbounded"] >= 2.0   # ...vs the control
    assert rs["shed_classified"]
    assert rs["outputs_bitwise_equal"]
    assert rs["flight_ok"]                        # bundle trigger matched
