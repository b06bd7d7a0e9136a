"""Router: SLO-aware batch selection across many endpoints on one device.

The InferenceServer multiplexes N ``ModelEndpoint``s (tenants) over a single
device-owning dispatch path. The Router decides *whose* batch runs next. The
policy is earliest-deadline-first corrected by each bucket's measured step
cost (a per-(endpoint, bucket) EWMA fed by every device step, seeded by
warmup) — the "pick by deadline, price by observed step time" discipline the
learned-TPU-cost-model line of work argues for (PAPERS.md):

1. A tenant's head request has an *effective deadline*: its explicit
   ``deadline_ms`` when set, else ``enqueue + slo_ms`` (per-tenant SLO), else
   ``enqueue + batch_timeout`` (the batching deadline).
2. Its *slack* is ``deadline - now - est_step``: how long scheduling can be
   deferred and the head still finish in time. ``est_step`` comes from the
   EWMA for the bucket this batch would actually run in, so a tenant whose
   next batch is expensive becomes urgent *earlier* — EDF that knows a big
   batch needs a head start.
3. Among tenants whose head is still meetable (slack >= 0), pick the
   smallest slack. When only already-late tenants remain, pick the
   *cheapest* estimated step (shortest-job-first): a long batch that is
   late regardless must not convoy short requests that are late too —
   running the short ones first strictly reduces total lateness.
4. Anti-starvation backstop: a late tenant whose head has waited more than
   ``starvation_factor x (batch_timeout + est_step)`` is escalated and
   served oldest-first, so SJF can never starve the expensive tenant.

Continuous batching falls out of *when* selection happens: the prep stage
(or the serial worker) assembles a batch at the last moment, after the
previous batch is already executing — rows that arrived during device step k
join the assembly for step k+1 instead of waiting out the in-flight
generation.

The Router owns no lock: every mutation and every ``select()`` happens under
the server's shared condition, exactly like the EndpointQueues it reads.
Only :class:`StepCostEWMA` is internally locked — it is fed from the worker
thread (outside the server lock) and read during selection (under it).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from . import bucketing
from .batcher import EndpointQueue
from ..telemetry.metrics import REGISTRY

__all__ = ["StepCostEWMA", "Tenant", "Router"]

_EST_G = REGISTRY.gauge(
    "mxtpu_step_cost_est_us",
    "Live per-(endpoint, bucket) step-cost estimate: the measured EWMA, "
    "seeded by warm-up's one execution of every bucket.",
    labelnames=("endpoint", "bucket"))


class StepCostEWMA:
    """Per-bucket exponentially-weighted moving average of device step time.

    ``observe(bucket, us)`` is fed by every executed batch (and by warmup's
    one execution per bucket, so estimates exist before the first request).
    ``estimate(bucket)`` falls back to the nearest observed bucket scaled by
    the row ratio — a crude linear-in-rows model that is only used until the
    real bucket has been observed once.
    ``name`` labels the live ``mxtpu_step_cost_est_us`` gauge; anonymous
    instances export nothing.
    """

    def __init__(self, alpha: float = 0.25, name: Optional[str] = None):
        self.alpha = float(alpha)
        self.name = name
        self._lock = threading.Lock()
        self._est: Dict[int, float] = {}
        self._n: Dict[int, int] = {}

    def _gauge(self, bucket: int, value: float):
        if self.name is None:
            return
        try:
            # bounded: buckets come from the fixed padding ladder
            _EST_G.labels(
                self.name, str(bucket)).set(value)  # mxlint: disable=MET301
        except Exception:
            pass

    def observe(self, bucket: int, step_us: float):
        with self._lock:
            prev = self._est.get(bucket)
            self._est[bucket] = step_us if prev is None else \
                prev + self.alpha * (step_us - prev)
            self._n[bucket] = self._n.get(bucket, 0) + 1
            est = self._est[bucket]
        self._gauge(bucket, est)

    def estimate(self, bucket: int) -> float:
        """Estimated step microseconds for ``bucket``: the measured EWMA,
        else the nearest observed bucket scaled by the row ratio, else 0.0
        on an empty table."""
        with self._lock:
            got = self._est.get(bucket)
            if got is not None:
                return got
            if not self._est:
                return 0.0
            nearest = min(self._est, key=lambda b: abs(b - bucket))
            return self._est[nearest] * (bucket / nearest)

    def snapshot(self) -> Dict[int, float]:
        with self._lock:
            return dict(self._est)

    def snapshot_detail(self) -> Dict[str, object]:
        """Measured mean and observation count per bucket, for /statusz
        (``snapshot()`` keeps its measured-only shape)."""
        with self._lock:
            detail = {int(b): {"measured_us": us, "n": self._n.get(b, 0)}
                      for b, us in sorted(self._est.items())}
        return {"buckets": detail}


class Tenant:
    """One endpoint's seat at the scheduler: its queue, its circuit breaker
    (per-tenant shedding: this tenant's overload degrades this tenant's
    admission, not the whole server), its optional SLO (``slo_us`` is
    both the scheduling deadline default and the latency objective the SLO
    monitor burns against ``slo_target``), and its brownout criticality
    ``tier`` (gold/silver/bulk — what the degradation ladder sheds first)."""

    __slots__ = ("name", "endpoint", "queue", "breaker", "slo_us",
                 "slo_target", "tier")

    def __init__(self, name: str, endpoint, queue: EndpointQueue,
                 breaker, slo_us: Optional[int] = None,
                 slo_target: Optional[float] = None, tier: str = "gold"):
        self.name = name
        self.endpoint = endpoint
        self.queue = queue
        self.breaker = breaker
        self.slo_us = slo_us
        self.slo_target = slo_target
        self.tier = tier


class Router:
    """EDF-with-measured-step-cost selection across registered tenants.

    All methods except nothing are called with the server's condition lock
    held; the Router adds no locking of its own.
    """

    def __init__(self, batch_timeout_us: int, starvation_factor: float = 8.0):
        self.batch_timeout_us = int(batch_timeout_us)
        self.starvation_factor = float(starvation_factor)
        self._tenants: Dict[str, Tenant] = {}

    # -- registry -----------------------------------------------------------
    def add(self, tenant: Tenant):
        self._tenants[tenant.name] = tenant

    def get(self, name: str) -> Tenant:
        return self._tenants[name]

    def find(self, name: str) -> Optional[Tenant]:
        return self._tenants.get(name)

    def names(self) -> List[str]:
        return sorted(self._tenants)

    def tenants(self) -> List[Tenant]:
        return list(self._tenants.values())

    def __contains__(self, name: str) -> bool:
        return name in self._tenants

    # -- scheduling inputs --------------------------------------------------
    def effective_batch_timeout_us(self) -> float:
        """The batching deadline in force right now: the configured timeout
        widened by the brownout ladder (level >= 1 trades per-request
        latency for fuller batches before anyone is refused)."""
        from .batcher import brownout_timeout_boost
        return self.batch_timeout_us * brownout_timeout_boost()

    def est_step_us(self, tenant: Tenant) -> float:
        """Estimated device time of the batch this tenant would run next:
        the EWMA for the bucket its pending prefix actually lands in."""
        ep = tenant.endpoint
        rows = min(max(tenant.queue.pending_rows, 1), ep.max_batch_size)
        return ep.step_cost.estimate(bucketing.bucket_for(rows, ep.buckets))

    def effective_deadline_us(self, tenant: Tenant) -> int:
        """Head request's deadline, or enqueue + SLO, or the batch deadline."""
        head_dl = tenant.queue.head_deadline_us()
        if head_dl is not None:
            return head_dl
        budget = tenant.slo_us if tenant.slo_us \
            else self.effective_batch_timeout_us()
        return int(tenant.queue.head_enqueue_us() + budget)

    def slack_us(self, tenant: Tenant, now_us: int) -> float:
        return self.effective_deadline_us(tenant) - now_us - \
            self.est_step_us(tenant)

    def _starvation_us(self, tenant: Tenant) -> float:
        return self.starvation_factor * \
            (self.effective_batch_timeout_us() + self.est_step_us(tenant))

    # -- the decision -------------------------------------------------------
    def select(self, now_us: int, flush: bool = False) -> Optional[Tenant]:
        """The next tenant to assemble a batch for, or None when no queue is
        ready. See the module docstring for the policy."""
        ready = [t for t in self._tenants.values()
                 if t.queue.ready(now_us, flush)]
        if not ready:
            return None
        if len(ready) == 1:
            return ready[0]
        meetable: List[Tuple[float, Tenant]] = []
        late: List[Tenant] = []
        for t in ready:
            s = self.slack_us(t, now_us)
            if s >= 0:
                meetable.append((s, t))
            else:
                late.append(t)
        if meetable:
            return min(meetable, key=lambda st: st[0])[1]
        starving = [t for t in late
                    if now_us - t.queue.head_enqueue_us() >
                    self._starvation_us(t)]
        if starving:
            return min(starving, key=lambda t: t.queue.head_enqueue_us())
        return min(late, key=self.est_step_us)

    # -- bookkeeping for the dispatch loops ---------------------------------
    def pending_requests(self) -> int:
        return sum(len(t.queue) for t in self._tenants.values())

    def next_wakeup_us(self) -> Optional[int]:
        wakeups = [w for t in self._tenants.values()
                   for w in (t.queue.next_wakeup_us(),) if w is not None]
        return min(wakeups) if wakeups else None

    def fail_all(self, exc: Exception) -> int:
        """Fail every queued request (non-drain stop / abandoned drain);
        returns how many requests were failed."""
        n = 0
        for t in self._tenants.values():
            n += len(t.queue)
            t.queue.fail_all(exc)
        return n
