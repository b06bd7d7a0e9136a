"""SLO-driven serving autoscaler over a replica pool of InferenceServers.

The persistent executable cache (``mxnet_tpu.cache``) makes replicas cheap:
a new ``InferenceServer`` warms its buckets compile-free by deserializing
the fleet's stored executables, so scale-up costs deserialize time, not an
XLA storm. This module closes the loop the PR 9 telemetry opened — the
per-tenant burn-rate monitor (``telemetry/slo.py``) and queue-depth gauges
become the *inputs* of a control loop that changes the fleet:

**ServingPool** owns N replicas built by a ``replica_factory(replica_id)``
callable (each returns an InferenceServer with its endpoints registered —
endpoint warmup hits the executable cache). Client traffic enters through
``pool.submit(...)`` which routes to the least-loaded replica *in
rotation*; a replica leaves rotation before it drains, so scale-down never
drops an admitted request, and an overloaded replica's rejection falls
through to the next one before the client ever sees it — the zero-downtime
cutover discipline of the hot-swap path, applied to whole replicas.

**Autoscaler** polls every ``MXNET_AUTOSCALE_POLL_S``: the worst fast-window
burn rate and active-alert count across SLO objectives, plus the pool's
queue pressure (worst-endpoint pending rows as a fraction of the queue
bound, averaged over replicas). The decision rule is deliberately boring —

  * over-pressure (alert latched, fast burn over the SLO monitor's
    threshold, or queue pressure over ``MXNET_AUTOSCALE_QUEUE_HIGH``) on
    ``MXNET_AUTOSCALE_UP_N`` *consecutive* polls scales up by one;
  * idleness (no alert, fast burn under 1.0, queue pressure under
    ``MXNET_AUTOSCALE_QUEUE_LOW``) on ``MXNET_AUTOSCALE_DOWN_N``
    consecutive polls scales down by one (drain via the bounded-drain
    path);
  * every action respects ``MXNET_AUTOSCALE_{MIN,MAX}_REPLICAS`` and a
    ``MXNET_AUTOSCALE_COOLDOWN_S`` settle period, and leaves an
    ``autoscale_up`` / ``autoscale_down`` flight event naming the signals
    that justified it — every decision is auditable post-hoc.

``Autoscaler.tick()`` is public and deterministic (pass ``now``), so tests
and chaos drills drive the loop without sleeping.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import CancelledError, Future
from typing import Callable, List, Optional, Tuple

from ..base import MXNetError
from .. import config as _config
from .. import telemetry as _telemetry
from ..resilience import faults as _faults
from ..telemetry import debug_server as _debug
from ..telemetry import flight as _flight
from ..telemetry.slo import MONITOR as _SLO_MONITOR
from . import tailguard as _tailguard
from .batcher import fail as _fail_fut, resolve as _resolve_fut
from .errors import ServerClosedError, ServerOverloadError
from .server import InferenceServer

__all__ = ["ServingPool", "Autoscaler"]


def _now_us() -> int:
    return time.perf_counter_ns() // 1000

_REPLICAS_G = _telemetry.gauge(
    "mxtpu_autoscale_replicas",
    "Serving replicas currently in the pool's rotation.")
_EVENTS = _telemetry.counter(
    "mxtpu_autoscale_events_total",
    "Autoscaler actions taken, by direction (up / down).",
    labelnames=("direction",))
_CAPACITY_G = _telemetry.gauge(
    "mxtpu_pool_replica_capacity",
    "Devices backing each pool replica (a mesh-sharded replica reports its "
    "slice size; single-chip replicas report 1) — the weight submit() "
    "divides queue load by.",
    labelnames=("rid",))


class _Replica:
    __slots__ = ("rid", "server", "capacity")

    def __init__(self, rid: int, server: InferenceServer, capacity: int = 1):
        self.rid = rid
        self.server = server
        self.capacity = max(int(capacity), 1)


class ServingPool:
    """A replica set of InferenceServers behind one submit() front door.

    Parameters
    ----------
    replica_factory : callable
        ``replica_factory(replica_id) -> InferenceServer`` builds one
        replica with its endpoints registered (warmup rides the executable
        cache, so this is deserialize-fast on a warm fleet). The pool
        starts the returned server if the factory did not.
    initial_replicas : int
        Replicas built immediately (default 1).
    """

    def __init__(self, replica_factory: Callable[[int], InferenceServer],
                 initial_replicas: int = 1):
        self._factory = replica_factory
        self._lock = threading.Lock()
        self._replicas: List[_Replica] = []
        self._next_rid = 0
        _debug.attach_pool(self)      # weak: /statusz + /fleetz render us
        for _ in range(max(int(initial_replicas), 0)):
            self.scale_up()

    # ------------------------------------------------------------------
    # elasticity
    # ------------------------------------------------------------------
    def scale_up(self) -> int:
        """Build, start, and put one new replica in rotation; returns its
        replica id. The heavy work (factory + warmup) happens before the
        pool lock is taken — traffic keeps flowing to existing replicas."""
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
        server = self._factory(rid)
        if server.state != "running":
            server.start()
        with server._cond:
            capacity = max((getattr(t.endpoint, "capacity", 1)
                            for t in server._router.tenants()), default=1)
        with self._lock:
            self._replicas.append(_Replica(rid, server, capacity))
            n = len(self._replicas)
        _REPLICAS_G.set(n)
        # bounded: rids recycle within MXNET_AUTOSCALE_MAX_REPLICAS
        _CAPACITY_G.labels(str(rid)).set(capacity)  # mxlint: disable=MET301
        return rid

    def scale_down(self, drain_timeout_s: Optional[float] = None
                   ) -> Optional[int]:
        """Remove the newest replica from rotation, THEN drain it — every
        admitted request completes, new traffic already routes elsewhere.
        Returns the drained replica id, or None when the pool is down to
        one replica (never drains the last)."""
        with self._lock:
            if len(self._replicas) <= 1:
                return None
            victim = self._replicas.pop()      # out of rotation first
            n = len(self._replicas)
        _REPLICAS_G.set(n)
        victim.server.stop(drain=True, timeout=drain_timeout_s)
        return victim.rid

    # ------------------------------------------------------------------
    # client surface
    # ------------------------------------------------------------------
    def _rotation(self) -> List[_Replica]:
        with self._lock:
            return list(self._replicas)

    def submit(self, name: str, inputs, deadline_ms: Optional[float] = None,
               deadline=None):
        """Route one request to the least-loaded replica in rotation,
        where load is queued rows divided by replica capacity — a 4-chip
        mesh-sharded replica keeps attracting traffic until it holds ~4x a
        single chip's queue, so heterogeneous pools utilize every chip.
        A replica that sheds (overload / mid-cutover close) falls through
        to the next-least-loaded one before the error reaches the client.

        With hedging enabled (``MXNET_HEDGE_ENABLE`` + a >=2 replica pool),
        a request still pending after the adaptive hedge delay is duplicated
        onto the next-least-loaded replica; the first response settles the
        returned Future and the loser is cancelled (dropped at batch
        assembly, never mid-step). ``deadline`` is the end-to-end
        :class:`~.tailguard.Deadline` minted at ingress; it rides into the
        replica's queue unchanged."""
        _faults.check("pool_submit")
        if deadline is not None:
            deadline.check("pool_submit")
        replicas = self._rotation()
        if not replicas:
            raise ServerClosedError("serving pool has no replicas")
        ranked = sorted(replicas, key=self._load_of)
        _tailguard.hedge_deposit()
        born_us = _now_us()
        primary, primary_rep = self._submit_ranked(
            name, inputs, deadline_ms, deadline, ranked)
        hedge_pool = [r for r in ranked if r is not primary_rep]
        if not (_tailguard.HEDGER.enabled() and hedge_pool):
            primary.add_done_callback(
                lambda f: _tailguard.HEDGER.observe_latency(
                    _now_us() - born_us))
            return primary
        return self._hedged(name, inputs, deadline_ms, deadline,
                            hedge_pool, primary, born_us)

    def _submit_ranked(self, name: str, inputs,
                       deadline_ms: Optional[float], deadline,
                       ranked: List[_Replica]) -> Tuple[Future, _Replica]:
        """The fallthrough core: try replicas in load order, returning the
        admitted Future and the replica that took it."""
        last_exc: Optional[Exception] = None
        for rep in ranked:
            try:
                # the span stamps this attempt's replica into the journey
                # AND hands its trace id to the request the batcher builds
                # inside submit() — the replica hop is traceable end to end
                with _telemetry.span("pool.submit", replica=rep.rid,
                                     endpoint=name):
                    return rep.server.submit(
                        name, inputs, deadline_ms=deadline_ms,
                        deadline=deadline), rep
            except (ServerOverloadError, ServerClosedError) as e:
                last_exc = e
        raise last_exc

    def _predicted_step_us(self, name: str) -> float:
        """Measured-EWMA device time of this endpoint's next batch (the
        Router's scheduling estimate) — the hedge delay's starting point
        for workloads the latency ring has not warmed yet. 0.0 when
        unknowable."""
        try:
            replicas = self._rotation()
            if not replicas:
                return 0.0
            srv = replicas[0].server
            with srv._cond:
                tenant = srv._router.find(name)
                if tenant is None:
                    return 0.0
                return float(srv._router.est_step_us(tenant))
        except Exception:
            return 0.0

    def _hedged(self, name: str, inputs, deadline_ms: Optional[float],
                deadline, hedge_pool: List[_Replica], primary: Future,
                born_us: int) -> Future:
        """Wrap an admitted primary with the hedge race: after the adaptive
        delay a budgeted duplicate goes to the next replica; the first
        *successful* arm settles the client Future (a failed arm defers to
        the other while it is still pending), the loser is cancelled."""
        out: Future = Future()
        lock = threading.Lock()
        state = {"done": False, "hedge": None, "timer": None}

        def settle(f: Future, is_hedge: bool):
            try:
                err = f.exception()
            except CancelledError:
                return                    # the cancelled loser reporting in
            with lock:
                if state["done"]:
                    return
                other = primary if is_hedge else state["hedge"]
                if err is not None and other is not None \
                        and not other.done():
                    return                # lost by failing; other arm decides
                state["done"] = True
                timer = state["timer"]
                loser = other
            if timer is not None:
                timer.cancel()
            _tailguard.HEDGER.observe_latency(_now_us() - born_us)
            if is_hedge and err is None:
                _tailguard.hedge_won()
            if loser is not None:
                if loser.cancel():
                    _tailguard.hedge_cancelled()
                else:
                    _tailguard.hedge_wasted()
            if err is not None:
                _fail_fut(out, err)
            else:
                _resolve_fut(out, f.result())

        def launch_hedge():
            with lock:
                if state["done"]:
                    return
            if deadline is not None and deadline.expired():
                return                    # no budget left to speculate into
            if not _tailguard.hedge_allowed():
                return
            try:
                hf, _rep = self._submit_ranked(
                    name, inputs, deadline_ms, deadline, hedge_pool)
            except Exception:
                return                    # no replica would take the hedge
            _tailguard.hedge_launched()
            lost_race = False
            with lock:
                if state["done"]:
                    lost_race = True
                else:
                    state["hedge"] = hf
            if lost_race:                 # primary settled while we admitted
                if hf.cancel():
                    _tailguard.hedge_cancelled()
                else:
                    _tailguard.hedge_wasted()
                return
            hf.add_done_callback(lambda f: settle(f, True))

        delay_s = _tailguard.HEDGER.delay_s(self._predicted_step_us(name))
        timer = threading.Timer(delay_s, launch_hedge)  # mxlint: disable=THR400
        timer.daemon = True
        state["timer"] = timer
        primary.add_done_callback(lambda f: settle(f, False))
        with lock:
            fast = state["done"]
        if not fast:                      # don't spawn timers for requests
            timer.start()                 # that already finished
        return out

    def predict(self, name: str, inputs, deadline_ms: Optional[float] = None,
                timeout: Optional[float] = None):
        return self.submit(name, inputs, deadline_ms).result(timeout=timeout)

    @staticmethod
    def _raw_load(rep: _Replica) -> int:
        srv = rep.server
        with srv._cond:
            return sum(len(t.queue) for t in srv._router.tenants())

    @classmethod
    def _load_of(cls, rep: _Replica) -> float:
        return cls._raw_load(rep) / rep.capacity

    # ------------------------------------------------------------------
    # signals / lifecycle
    # ------------------------------------------------------------------
    def queue_pressure(self) -> float:
        """Worst-endpoint pending rows over the queue bound, averaged over
        replicas in rotation — 0.0 idle, 1.0 every queue full."""
        replicas = self._rotation()
        if not replicas:
            return 0.0
        vals = []
        for rep in replicas:
            srv = rep.server
            with srv._cond:
                tenants = srv._router.tenants()
            worst = 0.0
            for t in tenants:
                cap = max(t.queue.max_queue_rows, 1)
                worst = max(worst, t.queue.pending_rows / cap)
            vals.append(worst)
        return sum(vals) / len(vals)

    def size(self) -> int:
        with self._lock:
            return len(self._replicas)

    def snapshot(self) -> dict:
        replicas = self._rotation()
        return {"replicas": [{"rid": r.rid, "state": r.server.state,
                              "capacity": r.capacity,
                              "load": self._raw_load(r),
                              "weighted_load": round(self._load_of(r), 4)}
                             for r in replicas],
                "size": len(replicas),
                "queue_pressure": round(self.queue_pressure(), 4)}

    def stop(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop every replica (drained by default)."""
        with self._lock:
            replicas, self._replicas = self._replicas, []
        _REPLICAS_G.set(0)
        for rep in replicas:
            rep.server.stop(drain=drain, timeout=timeout)

    def __enter__(self) -> "ServingPool":
        return self

    def __exit__(self, *exc):
        self.stop(drain=True)
        return False


class Autoscaler:
    """The control loop: SLO burn + queue pressure in, scale actions out.

    Every constructor argument pins the matching ``MXNET_AUTOSCALE_*`` knob
    (None = read it live each poll, the SLOMonitor convention). ``tick()``
    is the whole loop body — call it directly (with an explicit ``now``)
    for deterministic tests, or ``start()`` the poll thread.
    """

    def __init__(self, pool: ServingPool, monitor=None,
                 min_replicas: Optional[int] = None,
                 max_replicas: Optional[int] = None,
                 poll_s: Optional[float] = None,
                 up_n: Optional[int] = None,
                 down_n: Optional[int] = None,
                 cooldown_s: Optional[float] = None,
                 queue_high: Optional[float] = None,
                 queue_low: Optional[float] = None,
                 time_fn=time.monotonic):
        self.pool = pool
        self._monitor = monitor if monitor is not None else _SLO_MONITOR
        self._min = min_replicas
        self._max = max_replicas
        self._poll = poll_s
        self._up_n = up_n
        self._down_n = down_n
        self._cooldown = cooldown_s
        self._q_high = queue_high
        self._q_low = queue_low
        self._now = time_fn
        self._stop_ev = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._over_polls = 0
        self._idle_polls = 0
        self._last_action_ts: Optional[float] = None
        self.actions: list = []      # action report dicts, newest last
        _debug.attach_autoscaler(self)   # weak: /statusz + /fleetz

    # -- knob-backed settings (read live unless pinned) --------------------
    @property
    def min_replicas(self) -> int:
        return self._min if self._min is not None else \
            int(_config.get("MXNET_AUTOSCALE_MIN_REPLICAS", 1))

    @property
    def max_replicas(self) -> int:
        return self._max if self._max is not None else \
            int(_config.get("MXNET_AUTOSCALE_MAX_REPLICAS", 4))

    @property
    def poll_s(self) -> float:
        return self._poll if self._poll is not None else \
            float(_config.get("MXNET_AUTOSCALE_POLL_S", 1.0))

    @property
    def up_n(self) -> int:
        return self._up_n if self._up_n is not None else \
            int(_config.get("MXNET_AUTOSCALE_UP_N", 2))

    @property
    def down_n(self) -> int:
        return self._down_n if self._down_n is not None else \
            int(_config.get("MXNET_AUTOSCALE_DOWN_N", 5))

    @property
    def cooldown_s(self) -> float:
        return self._cooldown if self._cooldown is not None else \
            float(_config.get("MXNET_AUTOSCALE_COOLDOWN_S", 10.0))

    @property
    def queue_high(self) -> float:
        return self._q_high if self._q_high is not None else \
            float(_config.get("MXNET_AUTOSCALE_QUEUE_HIGH", 0.5))

    @property
    def queue_low(self) -> float:
        return self._q_low if self._q_low is not None else \
            float(_config.get("MXNET_AUTOSCALE_QUEUE_LOW", 0.05))

    # ------------------------------------------------------------------
    # signals + decision
    # ------------------------------------------------------------------
    def signals(self) -> dict:
        """One poll's worth of evidence: the worst fast-window burn rate and
        the active-alert count across SLO objectives, plus the pool's queue
        pressure."""
        max_fast = 0.0
        alerts = 0
        for st in self._monitor.check_all():
            max_fast = max(max_fast, float(st.get("fast_burn", 0.0)))
            alerts += 1 if st.get("alert_active") else 0
        return {"max_fast_burn": round(max_fast, 3),
                "alerts_active": alerts,
                "queue_pressure": round(self.pool.queue_pressure(), 4),
                "replicas": self.pool.size()}

    def _decide(self, sig: dict, now: float) -> Optional[str]:
        """Pure-ish decision core: updates hysteresis counters, returns
        'up' / 'down' / None. Cooldown and min/max bounds are enforced
        here so every caller of tick() gets the same discipline."""
        over = (sig["alerts_active"] > 0
                or sig["max_fast_burn"] >= self._monitor.burn_threshold
                or sig["queue_pressure"] >= self.queue_high)
        idle = (sig["alerts_active"] == 0
                and sig["max_fast_burn"] < 1.0
                and sig["queue_pressure"] <= self.queue_low)
        with self._lock:
            self._over_polls = self._over_polls + 1 if over else 0
            self._idle_polls = self._idle_polls + 1 if idle else 0
            in_cooldown = (self._last_action_ts is not None
                           and now - self._last_action_ts < self.cooldown_s)
            if in_cooldown:
                return None
            if over and self._over_polls >= self.up_n \
                    and sig["replicas"] < self.max_replicas:
                self._over_polls = 0
                self._last_action_ts = now
                return "up"
            if idle and self._idle_polls >= self.down_n \
                    and sig["replicas"] > self.min_replicas:
                self._idle_polls = 0
                self._last_action_ts = now
                return "down"
        return None

    def tick(self, now: Optional[float] = None) -> Optional[dict]:
        """One control-loop turn: read signals, decide, act. Returns the
        action report ({"action", "rid", **signals}) or None."""
        if now is None:
            now = self._now()
        # the brownout ladder rides this poll loop for free: same cadence,
        # same burn evidence, no thread of its own
        _tailguard.BROWNOUT.tick(now)
        sig = self.signals()
        verdict = self._decide(sig, now)
        if verdict is None:
            return None
        if verdict == "up":
            rid = self.pool.scale_up()
        else:
            rid = self.pool.scale_down()
            if rid is None:          # pool refused (last replica)
                return None
        report = dict(sig, action=verdict, rid=rid,
                      replicas=self.pool.size())
        _EVENTS.labels(verdict).inc()
        _flight.event(f"autoscale_{verdict}", **report)
        with self._lock:
            self.actions.append(report)
        return report

    # ------------------------------------------------------------------
    # poll thread
    # ------------------------------------------------------------------
    def start(self) -> "Autoscaler":
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self
            if self.poll_s <= 0:
                raise MXNetError("MXNET_AUTOSCALE_POLL_S must be > 0")
            self._stop_ev.clear()
            self._thread = threading.Thread(
                target=self._run, name="mxtpu-autoscaler", daemon=True)
            self._thread.start()
        return self

    def stop(self):
        with self._lock:
            self._stop_ev.set()
            t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=self.poll_s * 4 + 1.0)

    def _run(self):
        while not self._stop_ev.wait(self.poll_s):
            try:
                self.tick()
            except Exception:
                pass        # scaling must outlive any single bad poll

    def __enter__(self) -> "Autoscaler":
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def snapshot(self) -> dict:
        with self._lock:
            actions = list(self.actions)
            over, idle = self._over_polls, self._idle_polls
            last_ts = self._last_action_ts
        now = self._now()
        age = (now - last_ts) if last_ts is not None else None
        return {"pool": self.pool.snapshot(), "actions": actions,
                "over_polls": over, "idle_polls": idle,
                "up_n": self.up_n, "down_n": self.down_n,
                "cooldown_s": self.cooldown_s,
                "last_action_age_s": round(age, 3) if age is not None
                else None,
                "in_cooldown": bool(age is not None
                                    and age < self.cooldown_s),
                "min_replicas": self.min_replicas,
                "max_replicas": self.max_replicas}
