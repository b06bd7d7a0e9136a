"""Tail-tolerance defense layer: deadlines, hedges, retry budgets, brownout.

Four mechanisms that turn the fleet's isolated per-tier defenses into one
coordinated overload-and-tail policy (the bounded-speculation / budgeted-
retry discipline of large-scale serving systems — cf. the distributed
fault-handling design in TensorFlow, arXiv:1605.08695):

**Deadline propagation** — a :class:`Deadline` is minted once at ingress
(FrontDoor.submit, or any tier a client enters at) and the SAME object rides
every hop: pool submit, endpoint queue, batch assembly, per-batch retry,
decode per-token. Every tier decrements the one budget instead of re-deriving
its own, and fails fast with :class:`~.errors.DeadlineExceeded` (bumping
``mxtpu_deadline_exceeded_total{site}``) the moment the budget is gone — a
request that cannot finish in time stops consuming capacity at the earliest
tier that can know.

**Hedged requests** — :class:`HedgePolicy` decides when a pending request is
"late enough" to duplicate onto the second-least-loaded replica: after an
adaptive delay that is the max of the observed p95 pool latency and the cost
model's predicted step cost × ``MXNET_HEDGE_DELAY_FACTOR`` (floored at
``MXNET_HEDGE_DELAY_MIN_MS``). Hedges draw from a token bucket refilled at
``MXNET_HEDGE_BUDGET_RATIO`` tokens per primary submit (default ≤5% of
traffic), so speculation can never amplify an overload: when the bucket is
dry the hedge is skipped and ``mxtpu_hedge_budget_exhausted_total`` latches
the ``hedge_budget_exhausted`` flight trigger. First response wins; the
loser is cancelled and dropped at batch assembly (never mid-step), and both
replicas run identical executables so hedged results are byte-identical to
unhedged ones.

**Retry budgets** — per-tier token buckets (``frontdoor`` resubmit,
``execute`` device-step retry, ``decode`` requeue) gate every retry through
:func:`retry_allowed`. Each unit of real work deposits
``MXNET_RETRY_BUDGET_RATIO`` tokens (min ``MXNET_RETRY_BUDGET_MIN`` so cold
tiers can still retry, cap ``MXNET_RETRY_BUDGET_CAP``); a retry takes one
whole token. Under a retry storm the bucket drains and further retries are
refused — the storm converts into bounded, classified shed instead of
cascading amplification — with ``mxtpu_retry_budget_exhausted_total{tier}``
latching the ``retry_budget_exhausted`` flight trigger once per episode.

**Brownout ladder** — :class:`BrownoutController` watches the SLO monitor's
burn state and degrades the fleet in criticality order, with hysteresis
(``MXNET_BROWNOUT_UP_N`` hot ticks to worsen, ``MXNET_BROWNOUT_DOWN_N``
calm ticks to recover) and one ``brownout_shift`` flight event per
transition:

  level 0  normal service
  level 1  soften: batch timeouts widen ×MXNET_BROWNOUT_TIMEOUT_BOOST
           (bigger batches, better goodput per step) and decode
           ``max_new_tokens`` clamps to MXNET_BROWNOUT_MAX_NEW_TOKENS
  level 2  shed bulk: tenants registered ``tier="bulk"`` are refused at
           admission (ServerOverloadError — retryable, the honest signal)
  level 3  shed bulk+silver: only gold serves — gold is never refused by
           the brownout ladder at any level

The controller is a pure decision core (``tick(now)``): the Autoscaler's
poll loop drives it for free, and chaos drills drive it deterministically
with a stubbed monitor.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from .. import config as _config
from .. import telemetry as _telemetry
from ..telemetry import flight as _flight
from .errors import DeadlineExceeded

__all__ = ["Deadline", "DeadlineExceeded", "TokenBucket", "RetryBudgets",
           "RETRY_BUDGETS", "retry_allowed", "retry_deposit", "HedgePolicy",
           "HEDGER", "BrownoutController", "BROWNOUT", "TIER_RANKS"]


def _now_us() -> int:
    return time.perf_counter_ns() // 1000


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------
_config.register("MXNET_HEDGE_ENABLE", True, bool,
                 "Tail hedging: allow ServingPool.submit to duplicate a "
                 "still-pending request onto the second-least-loaded replica "
                 "after the adaptive hedge delay. First response wins; the "
                 "loser is cancelled at batch assembly. 0 disables hedging "
                 "entirely (pure primary-only routing).")
_config.register("MXNET_HEDGE_BUDGET_RATIO", 0.05, float,
                 "Tail hedging: token-bucket refill per primary submit — the "
                 "steady-state ceiling on hedged traffic as a fraction of "
                 "total (default 5%). A dry bucket skips the hedge and "
                 "latches the hedge_budget_exhausted flight trigger. <= 0 "
                 "disables hedging.")
_config.register("MXNET_HEDGE_DELAY_FACTOR", 2.0, float,
                 "Tail hedging: multiplier on the measured (EWMA) step "
                 "cost when computing the adaptive hedge "
                 "delay (hedge fires only after max(observed p95 latency, "
                 "predicted_step * factor)).")
_config.register("MXNET_HEDGE_DELAY_MIN_MS", 10.0, float,
                 "Tail hedging: floor on the adaptive hedge delay, "
                 "milliseconds — never hedge faster than this however "
                 "cheap the predicted step.")
_config.register("MXNET_RETRY_BUDGET_RATIO", 0.1, float,
                 "Retry budgets: tokens deposited per unit of successful "
                 "work per tier (frontdoor submit, device batch, decode "
                 "step); one retry costs one token, so retries are bounded "
                 "to ~this fraction of real work in steady state. <= 0 "
                 "disables retry budgeting (every retry allowed).")
_config.register("MXNET_RETRY_BUDGET_MIN", 50.0, float,
                 "Retry budgets: floor on each tier's bucket — a cold or "
                 "low-traffic tier can always afford this many retries "
                 "before the ratio takes over.")
_config.register("MXNET_RETRY_BUDGET_CAP", 500.0, float,
                 "Retry budgets: ceiling on each tier's bucket, so a long "
                 "quiet period cannot bank an unbounded retry burst.")
_config.register("MXNET_BROWNOUT_ENABLE", True, bool,
                 "Brownout ladder: let the BrownoutController move off "
                 "level 0 under sustained SLO burn. 0 pins level 0 "
                 "(no degradation ever).")
_config.register("MXNET_BROWNOUT_UP_N", 2, int,
                 "Brownout hysteresis: consecutive burning ticks required "
                 "before the ladder degrades one level (one hot tick never "
                 "sheds).")
_config.register("MXNET_BROWNOUT_DOWN_N", 3, int,
                 "Brownout hysteresis: consecutive calm ticks required "
                 "before the ladder recovers one level (recovery is the "
                 "cautious direction).")
_config.register("MXNET_BROWNOUT_MAX_NEW_TOKENS", 32, int,
                 "Brownout level >= 1: clamp on decode max_new_tokens — "
                 "long generations are the first work shortened under "
                 "brownout, before any request is refused.")
_config.register("MXNET_BROWNOUT_TIMEOUT_BOOST", 4.0, float,
                 "Brownout level >= 1: multiplier on batch timeouts — wider "
                 "assembly windows build fuller batches (better goodput per "
                 "device step) at the cost of per-request latency, spending "
                 "latency headroom before refusing anyone.")


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
_DEADLINE_C = _telemetry.counter(
    "mxtpu_deadline_exceeded_total",
    "Requests failed fast because their propagated Deadline budget ran out, "
    "by the site that detected it (ingress/pool_submit/queue/assembly/"
    "retry_backoff/decode_token) — the earliest tier that could know, so "
    "expired work stops consuming capacity immediately.",
    labelnames=("site",))
_HEDGES_C = _telemetry.counter(
    "mxtpu_hedge_requests_total",
    "Hedge duplicates launched onto a second replica after the adaptive "
    "delay (the speculation volume; bounded by the hedge token bucket).")
_HEDGE_WINS_C = _telemetry.counter(
    "mxtpu_hedge_wins_total",
    "Hedged requests where the duplicate finished first — tail latency the "
    "hedge actually saved.")
_HEDGE_CANCELLED_C = _telemetry.counter(
    "mxtpu_hedge_cancelled_total",
    "Hedge losers cancelled before occupying device rows (dropped at batch "
    "assembly) — speculation that cost zero device work.")
_HEDGE_WASTED_C = _telemetry.counter(
    "mxtpu_hedge_wasted_total",
    "Hedge losers that had already entered a device batch when the winner "
    "resolved — the duplicate work hedging truly wasted.")
_HEDGE_EXHAUSTED_C = _telemetry.counter(
    "mxtpu_hedge_budget_exhausted_total",
    "Hedges skipped because the hedge token bucket was dry — speculation "
    "refusing to amplify an overload.")
_RETRY_TOKENS_G = _telemetry.gauge(
    "mxtpu_retry_budget_tokens",
    "Live token balance of each tier's retry budget bucket (frontdoor / "
    "execute / decode); zero means further retries are refused until real "
    "work deposits more.",
    labelnames=("tier",))
_RETRY_EXHAUSTED_C = _telemetry.counter(
    "mxtpu_retry_budget_exhausted_total",
    "Retries refused because the tier's budget bucket was dry — a retry "
    "storm converting into bounded shed instead of amplification.",
    labelnames=("tier",))
_BROWNOUT_LEVEL_G = _telemetry.gauge(
    "mxtpu_brownout_level",
    "Current brownout ladder level: 0 normal, 1 soften (timeout boost + "
    "decode clamp), 2 shed bulk, 3 shed bulk+silver (gold always serves).")
_BROWNOUT_TRANSITIONS_C = _telemetry.counter(
    "mxtpu_brownout_transitions_total",
    "Brownout ladder level changes, by direction (degrade / recover); one "
    "brownout_shift flight event accompanies each.",
    labelnames=("direction",))
_BROWNOUT_SHED_C = _telemetry.counter(
    "mxtpu_brownout_shed_total",
    "Requests refused at admission by the brownout ladder, by tenant tier "
    "(gold is never in this count by construction).",
    labelnames=("tier",))


# ---------------------------------------------------------------------------
# deadline propagation
# ---------------------------------------------------------------------------
class Deadline:
    """One end-to-end latency budget, minted at ingress and passed by
    reference through every tier. Absolute expiry on the shared
    ``perf_counter_ns()//1000`` microsecond clock (the clock every serving
    tier already timestamps with), so decrementing is implicit: each tier
    reads ``remaining_us()`` against the same wall.

    ``check(site)`` is the fail-fast hop: raises
    :class:`~.errors.DeadlineExceeded` (and bumps
    ``mxtpu_deadline_exceeded_total{site}``) once the budget is spent.
    """

    __slots__ = ("deadline_us", "born_us")

    def __init__(self, budget_ms: float, now_us: Optional[int] = None):
        self.born_us = _now_us() if now_us is None else int(now_us)
        self.deadline_us = self.born_us + int(float(budget_ms) * 1000.0)

    @classmethod
    def at(cls, deadline_us: int) -> "Deadline":
        """Adopt an absolute expiry already on the shared clock."""
        d = cls.__new__(cls)
        d.born_us = _now_us()
        d.deadline_us = int(deadline_us)
        return d

    def remaining_us(self, now_us: Optional[int] = None) -> int:
        now = _now_us() if now_us is None else now_us
        return self.deadline_us - now

    def remaining_ms(self, now_us: Optional[int] = None) -> float:
        return self.remaining_us(now_us) / 1e3

    def expired(self, now_us: Optional[int] = None) -> bool:
        return self.remaining_us(now_us) <= 0

    def check(self, site: str):
        """Fail fast: raise DeadlineExceeded when the budget is gone."""
        rem = self.remaining_us()
        if rem <= 0:
            _DEADLINE_C.labels(site).inc()
            raise DeadlineExceeded(
                f"deadline exceeded at {site}: budget of "
                f"{(self.deadline_us - self.born_us) / 1e3:.1f} ms overran "
                f"by {-rem / 1e3:.1f} ms")

    def __repr__(self):
        return (f"Deadline(remaining_ms={self.remaining_ms():.1f}, "
                f"deadline_us={self.deadline_us})")


def deadline_expired(site: str, n: int = 1):
    """Account deadline expiries detected without a Deadline object in hand
    (e.g. the batcher dropping expired heads at assembly)."""
    _DEADLINE_C.labels(site).inc(n)


# ---------------------------------------------------------------------------
# token buckets (hedge budget + per-tier retry budgets)
# ---------------------------------------------------------------------------
class TokenBucket:
    """A capped token bucket: ``deposit()`` is driven by units of real work,
    ``take()`` spends one token per speculative/retried unit. No time-based
    refill — the budget is a *fraction of actual traffic*, so an idle system
    banks nothing and a storm cannot outrun its own income."""

    __slots__ = ("_lock", "tokens", "cap")

    def __init__(self, initial: float, cap: float):
        self._lock = threading.Lock()
        self.cap = float(cap)
        self.tokens = min(float(initial), self.cap)

    def deposit(self, amount: float):
        with self._lock:
            self.tokens = min(self.tokens + float(amount), self.cap)

    def take(self, amount: float = 1.0) -> bool:
        with self._lock:
            if self.tokens >= amount:
                self.tokens -= amount
                return True
            return False

    def balance(self) -> float:
        with self._lock:
            return self.tokens


class RetryBudgets:
    """Per-tier retry token buckets with latched exhaustion triggers.

    Tiers are created lazily (``frontdoor`` / ``execute`` / ``decode`` are
    the wired ones). Each bucket starts at — and is floored by re-deposit
    at — ``MXNET_RETRY_BUDGET_MIN`` and capped at ``MXNET_RETRY_BUDGET_CAP``;
    ``on_work`` deposits ``MXNET_RETRY_BUDGET_RATIO`` per unit of real work.
    A ratio <= 0 disables budgeting: every ``allow`` succeeds (the
    pre-budget behavior, so existing retry semantics are opt-in unchanged).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._buckets: Dict[str, TokenBucket] = {}
        self._latched: Dict[str, bool] = {}

    @staticmethod
    def _ratio() -> float:
        return float(_config.get("MXNET_RETRY_BUDGET_RATIO"))

    def _bucket(self, tier: str) -> TokenBucket:
        with self._lock:
            b = self._buckets.get(tier)
            if b is None:
                b = TokenBucket(float(_config.get("MXNET_RETRY_BUDGET_MIN")),
                                float(_config.get("MXNET_RETRY_BUDGET_CAP")))
                self._buckets[tier] = b
                self._latched[tier] = False
            return b

    def on_work(self, tier: str, units: float = 1.0):
        """Deposit for real work done at ``tier`` (a submit routed, a batch
        stepped, a decode step advanced)."""
        if self._ratio() <= 0:
            return
        b = self._bucket(tier)
        b.deposit(self._ratio() * units)
        _RETRY_TOKENS_G.labels(tier).set(b.balance())

    def allow(self, tier: str) -> bool:
        """Spend one token for a retry at ``tier``. False means the budget
        is exhausted: the caller must NOT retry (propagate the last error —
        bounded shed). Exhaustion latches one flight trigger per episode;
        a later successful allow re-arms it."""
        if self._ratio() <= 0:
            return True
        b = self._bucket(tier)
        ok = b.take(1.0)
        _RETRY_TOKENS_G.labels(tier).set(b.balance())
        if ok:
            with self._lock:
                self._latched[tier] = False
            return True
        _RETRY_EXHAUSTED_C.labels(tier).inc()
        with self._lock:
            first = not self._latched[tier]
            self._latched[tier] = True
        if first:
            _flight.trigger("retry_budget_exhausted", tier=tier,
                            tokens=round(b.balance(), 3), cap=b.cap)
        return False

    def balance(self, tier: str) -> float:
        return self._bucket(tier).balance()

    def reset(self):
        """Forget every bucket (tests / chaos scenario isolation)."""
        with self._lock:
            self._buckets.clear()
            self._latched.clear()


#: the process-wide registry every wired tier consumes
RETRY_BUDGETS = RetryBudgets()


def retry_deposit(tier: str, units: float = 1.0):
    """Module-level convenience over ``RETRY_BUDGETS.on_work``."""
    RETRY_BUDGETS.on_work(tier, units)


def retry_allowed(tier: str) -> bool:
    """Module-level convenience over ``RETRY_BUDGETS.allow``."""
    return RETRY_BUDGETS.allow(tier)


# ---------------------------------------------------------------------------
# hedging policy
# ---------------------------------------------------------------------------
class HedgePolicy:
    """When (and whether) to duplicate a pending request.

    The delay is adaptive: ``max(observed p95 of recent end-to-end pool
    latencies, predicted_step_us * MXNET_HEDGE_DELAY_FACTOR)``, floored at
    ``MXNET_HEDGE_DELAY_MIN_MS`` — a hedge should fire only when the primary
    is *already late* relative to what this workload usually costs: the
    step's measured mean until the latency ring has warmed, the ring after.
    """

    _RING = 256

    def __init__(self):
        self._lock = threading.Lock()
        self._lat_us: list = []       # ring of recent pool latencies
        self._idx = 0

    @staticmethod
    def enabled() -> bool:
        return bool(_config.get("MXNET_HEDGE_ENABLE")) and \
            float(_config.get("MXNET_HEDGE_BUDGET_RATIO")) > 0.0

    def observe_latency(self, us: float):
        """Feed one completed pool submit's end-to-end latency."""
        with self._lock:
            if len(self._lat_us) < self._RING:
                self._lat_us.append(float(us))
            else:
                self._lat_us[self._idx] = float(us)
                self._idx = (self._idx + 1) % self._RING

    def p95_us(self) -> float:
        with self._lock:
            if not self._lat_us:
                return 0.0
            vals = sorted(self._lat_us)
        return vals[min(len(vals) - 1, int(0.95 * len(vals)))]

    def delay_s(self, predicted_step_us: float = 0.0) -> float:
        """Adaptive hedge delay in seconds for one request."""
        factor = float(_config.get("MXNET_HEDGE_DELAY_FACTOR"))
        floor_us = float(_config.get("MXNET_HEDGE_DELAY_MIN_MS")) * 1000.0
        delay_us = max(self.p95_us(), predicted_step_us * factor, floor_us)
        return delay_us / 1e6

    def reset(self):
        with self._lock:
            self._lat_us.clear()
            self._idx = 0


#: process-wide hedging policy + its budget bucket (lazily floored by knobs)
HEDGER = HedgePolicy()
_HEDGE_BUCKET = TokenBucket(1.0, 64.0)
_HEDGE_LATCH = threading.Event()


def hedge_deposit():
    """One primary submit's worth of hedge budget income."""
    _HEDGE_BUCKET.deposit(float(_config.get("MXNET_HEDGE_BUDGET_RATIO")))


def hedge_allowed() -> bool:
    """Spend one hedge token; False (latching one flight trigger per dry
    episode) refuses the hedge so speculation cannot amplify overload."""
    if _HEDGE_BUCKET.take(1.0):
        _HEDGE_LATCH.clear()
        return True
    _HEDGE_EXHAUSTED_C.inc()
    if not _HEDGE_LATCH.is_set():
        _HEDGE_LATCH.set()
        _flight.trigger("hedge_budget_exhausted",
                        tokens=round(_HEDGE_BUCKET.balance(), 3))
    return False


def hedge_launched():
    _HEDGES_C.inc()


def hedge_won():
    _HEDGE_WINS_C.inc()


def hedge_cancelled():
    _HEDGE_CANCELLED_C.inc()


def hedge_wasted():
    _HEDGE_WASTED_C.inc()


def hedge_reset():
    """Drain + re-seed the hedge bucket and latency ring (tests/chaos)."""
    global _HEDGE_BUCKET
    _HEDGE_BUCKET = TokenBucket(1.0, 64.0)
    _HEDGE_LATCH.clear()
    HEDGER.reset()


# ---------------------------------------------------------------------------
# brownout ladder
# ---------------------------------------------------------------------------
#: tenant criticality ranks — lower sheds LAST. register(tier=...) values.
TIER_RANKS = {"gold": 0, "silver": 1, "bulk": 2}

#: brownout level -> minimum tier rank refused at admission (None = nobody)
_SHED_RANK_AT_LEVEL = {0: None, 1: None, 2: 2, 3: 1}

_MAX_LEVEL = 3


class BrownoutController:
    """Fleet-level degradation ladder over the SLO monitor's burn state.

    ``tick(now)`` reads the monitor (injectable for drills; default the
    process-wide ``slo.MONITOR``): *burning* means any objective's latched
    alert is active or its fast burn exceeds the monitor's threshold.
    ``MXNET_BROWNOUT_UP_N`` consecutive burning ticks degrade one level;
    ``MXNET_BROWNOUT_DOWN_N`` consecutive calm ticks recover one. Each
    transition bumps ``mxtpu_brownout_transitions_total{direction}``, moves
    the ``mxtpu_brownout_level`` gauge and fires exactly one
    ``brownout_shift`` flight event.

    The ladder's effects are consumed by the tiers:

    - ``shed_tier(tier)`` — InferenceServer.submit refuses matching tenants
      with ServerOverloadError (bulk at level 2, bulk+silver at level 3;
      gold never).
    - ``timeout_boost()`` — the Router widens batch timeouts (>= level 1).
    - ``clamp_max_new_tokens(n)`` — DecodeScheduler.submit clamps the
      generation budget (>= level 1).
    """

    def __init__(self, monitor=None):
        self._monitor = monitor     # None -> slo.MONITOR, resolved lazily
        self._lock = threading.Lock()
        self.level = 0
        self._hot = 0
        self._calm = 0
        _BROWNOUT_LEVEL_G.set(0)

    def _resolve_monitor(self):
        if self._monitor is not None:
            return self._monitor
        from ..telemetry.slo import MONITOR
        return MONITOR

    def set_monitor(self, monitor):
        """Swap the burn-signal source (chaos drills use a stub); None
        restores the process-wide SLO monitor."""
        self._monitor = monitor

    @staticmethod
    def enabled() -> bool:
        return bool(_config.get("MXNET_BROWNOUT_ENABLE"))

    # -- burn signal -----------------------------------------------------
    def _burning(self) -> bool:
        mon = self._resolve_monitor()
        try:
            thr = float(mon.burn_threshold)
            for st in mon.check_all():
                if st.get("alert_active"):
                    return True
                if float(st.get("fast_burn", 0.0)) >= thr:
                    return True
        except Exception:
            return False
        return False

    # -- the decision ----------------------------------------------------
    def tick(self, now: Optional[float] = None) -> Optional[dict]:
        """One control turn: read the burn signal, apply hysteresis, move
        at most one level. Returns the transition report or None."""
        if not self.enabled():
            with self._lock:
                if self.level == 0:
                    return None
            return self._shift(-1, "disabled")
        burning = self._burning()
        up_n = max(1, int(_config.get("MXNET_BROWNOUT_UP_N")))
        down_n = max(1, int(_config.get("MXNET_BROWNOUT_DOWN_N")))
        with self._lock:
            if burning:
                self._hot += 1
                self._calm = 0
                if self._hot >= up_n and self.level < _MAX_LEVEL:
                    self._hot = 0
                    return self._shift_locked(+1, "slo_burn")
            else:
                self._calm += 1
                self._hot = 0
                if self._calm >= down_n and self.level > 0:
                    self._calm = 0
                    return self._shift_locked(-1, "burn_cleared")
        return None

    def _shift(self, direction: int, reason: str) -> dict:
        with self._lock:
            return self._shift_locked(direction, reason)

    def _shift_locked(self, direction: int, reason: str) -> dict:  # mxlint: disable=CONC200
        old = self.level
        self.level = min(max(self.level + direction, 0), _MAX_LEVEL)
        _BROWNOUT_LEVEL_G.set(self.level)
        word = "degrade" if direction > 0 else "recover"
        _BROWNOUT_TRANSITIONS_C.labels(word).inc()
        report = {"from_level": old, "to_level": self.level,
                  "direction": word, "reason": reason,
                  "shedding": self.shedding_tiers()}
        _flight.trigger("brownout_shift", **report)
        _telemetry.event("brownout_shift", **report)
        return report

    # -- effects consumed by the tiers ----------------------------------
    def shed_tier(self, tier: str) -> bool:
        """Should a request for a ``tier`` tenant be refused right now?
        Gold (rank 0) is never refused by the ladder."""
        rank = TIER_RANKS.get(tier, 0)
        shed_from = _SHED_RANK_AT_LEVEL.get(self.level)
        if shed_from is None or rank == 0:
            return False
        if rank >= shed_from:
            _BROWNOUT_SHED_C.labels(tier).inc()
            return True
        return False

    def shedding_tiers(self) -> list:
        shed_from = _SHED_RANK_AT_LEVEL.get(self.level)
        if shed_from is None:
            return []
        return sorted(t for t, r in TIER_RANKS.items()
                      if r >= shed_from and r > 0)

    def timeout_boost(self) -> float:
        """Batch-timeout multiplier the Router applies (1.0 at level 0)."""
        if self.level >= 1:
            return max(1.0, float(_config.get("MXNET_BROWNOUT_TIMEOUT_BOOST")))
        return 1.0

    def clamp_max_new_tokens(self, requested: int) -> int:
        """Decode generation budget under brownout (identity at level 0)."""
        if self.level >= 1:
            clamp = max(1, int(_config.get("MXNET_BROWNOUT_MAX_NEW_TOKENS")))
            return min(int(requested), clamp)
        return int(requested)

    def snapshot(self) -> dict:
        with self._lock:
            return {"level": self.level, "hot_ticks": self._hot,
                    "calm_ticks": self._calm, "enabled": self.enabled(),
                    "shedding": self.shedding_tiers(),
                    "timeout_boost": self.timeout_boost()}

    def reset(self):
        """Back to level 0 with counters cleared (tests/chaos isolation);
        no transition event — this is bookkeeping, not a recovery."""
        with self._lock:
            self.level = 0
            self._hot = 0
            self._calm = 0
            _BROWNOUT_LEVEL_G.set(0)


#: the process-wide ladder — Autoscaler.tick drives it; servers consult it
BROWNOUT = BrownoutController()
