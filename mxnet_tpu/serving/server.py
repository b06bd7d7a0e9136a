"""InferenceServer: the request -> batch -> device -> response loop.

r6 rebuilt this from a one-endpoint-at-a-time, prepare-then-step loop into a
pipelined multi-tenant scheduler. Three coupled pieces:

**Router** (router.py): many ModelEndpoints (tenants) multiplex over the one
device-owning dispatch path. The next batch is picked earliest-deadline-first
across tenants, priced by each bucket's measured step-time EWMA, with
shortest-job-first among already-late tenants — a long batch cannot convoy
short requests — plus an anti-starvation escalation. Batches assemble at the
last moment (continuous batching): rows arriving during device step k join
the assembly for step k+1 instead of waiting out the in-flight step.

**Double-buffered host pipeline** (pipeline.py): a prep thread assembles and
``device_put``s batch k+1 into the next parity's input-buffer set while the
worker executes batch k, handing fully-built device buffers to the worker
under the shared condition — host time leaves the critical path (the
host/device overlap discipline of TensorFlow's dataflow executor). The
dispatch discipline stays single-owner: only the worker thread invokes
compiled executables; the prep thread touches JAX for host->device transfer
alone; client threads only validate, cast to host numpy, and enqueue.
``pipeline=False`` keeps the serial prepare-then-step path (same scheduler,
same executables — the bitwise reference for the pipelined path).

**Per-tenant shedding**: each tenant gets its own CircuitBreaker (unless the
server was built with an explicit shared ``breaker`` — the legacy
single-tenant contract), so one tenant's failures or stalls tighten *that
tenant's* admission (DEGRADED: half its queue bound; OPEN: shed all) while
the others keep serving. ``health()`` reports the worst circuit across
tenants plus per-tenant states.

Everything the serial server guaranteed still holds: bounded-queue
backpressure (ServerOverloadError at admission), per-request deadlines
enforced at assembly (expired work never occupies device rows), graceful
*bounded* drain (``stop(drain=True)`` flushes admitted work, abandons past
``drain_timeout_s`` — counted in ``mxtpu_drain_abandoned_total``), bitwise
per-request outputs (same executables, same padding), and per-batch
RetryPolicy + Watchdog + profiler integration on every device step.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Dict, Optional

import numpy as onp

from ..base import MXNetError
from .. import config as _config
from .. import telemetry as _telemetry
from ..telemetry import debug_server as _debug
from ..telemetry import flight as _flight
from ..telemetry.slo import MONITOR as _SLO
from ..ndarray.ndarray import NDArray
from ..resilience import faults as _faults
from ..resilience.retry import RetryPolicy
from ..resilience.watchdog import (CircuitBreaker, Watchdog, DEGRADED,
                                   HALF_OPEN, HEALTHY, OPEN)
from .batcher import EndpointQueue, Request, fail, resolve
from .endpoint import ModelEndpoint
from .errors import (HotSwapError, RequestTimeoutError, ServerClosedError,
                     ServerOverloadError)
from .pipeline import OverlapTracker, PreparedBatch, prepare_batch
from .router import Router, Tenant
from . import tailguard as _tailguard

__all__ = ["InferenceServer"]

_RUNNING, _DRAINING, _STOPPED = "running", "draining", "stopped"

#: returned by the wait loops to a worker/prep thread whose epoch was
#: superseded by a failover: exit silently, a replacement is already running
_SUPERSEDED = object()

#: how bad is a circuit state, for the worst-of health aggregation
_CIRCUIT_SEVERITY = {HEALTHY: 0, DEGRADED: 1, HALF_OPEN: 2, OPEN: 3}

_DRAIN_ABANDONED = _telemetry.counter(
    "mxtpu_drain_abandoned_total",
    "Requests abandoned because stop(drain=True) hit its timeout with the "
    "worker wedged: queued-never-batched ones failed with ServerClosedError, "
    "ones already inside a prepared/in-flight batch with "
    "RequestTimeoutError — never left to hang a waiting client.")

_FAILOVERS = _telemetry.counter(
    "mxtpu_serving_failovers_total",
    "Worker failovers performed, by reason: worker_dead (thread crashed) / "
    "worker_wedged (in-flight batch outlived the watchdog stall threshold) "
    "/ prep_dead (prep thread crashed).", labelnames=("reason",))
_FAILOVER_REQUEUED = _telemetry.counter(
    "mxtpu_serving_failover_requeued_total",
    "Requests returned to the front of their tenant queues by a failover "
    "(from prepared / in-flight batches of the dead worker); deadlines are "
    "re-checked at re-assembly.")


class _SwapRequest:
    """One routed hot-swap: host-staged weights + probe riding the worker's
    command path, applied between batches (the batch-boundary cutover)."""

    __slots__ = ("tenant", "host_params", "probe", "label", "future")

    def __init__(self, tenant, host_params, probe, label):
        self.tenant = tenant
        self.host_params = host_params
        self.probe = probe
        self.label = label
        self.future = Future()


def _now_us() -> int:
    return time.perf_counter_ns() // 1000


class InferenceServer:
    """Pipelined, multi-tenant dynamic-batching front-end over registered
    ModelEndpoints.

    Parameters
    ----------
    batch_timeout_ms : float
        Max time the oldest queued request waits before a partial batch is
        dispatched anyway (the latency half of the batching trade-off).
    max_queue : int
        Default admission-control bound, in rows, per endpoint (override
        per tenant at :meth:`register`). Submissions beyond it raise
        ServerOverloadError instead of growing the queue.
    retry_policy : resilience.RetryPolicy, optional
        Per-batch device-step retry (default: MXNET_RETRY_* config).
    breaker : resilience.CircuitBreaker, optional
        When given, ALL tenants share this breaker (the legacy single-tenant
        contract). When omitted, each tenant gets its own
        ``CircuitBreaker(scope="serving:<name>")`` — per-tenant shedding.
    watchdog_stall_s : float, optional
        Hang threshold for one device batch step (default
        MXNET_WATCHDOG_STALL_S). A stall degrades the stalled tenant's
        circuit breaker.
    drain_timeout_s : float, optional
        Bound on stop(drain=True) (default MXNET_SERVING_DRAIN_TIMEOUT_S).
    pipeline : bool
        True (default): double-buffered host pipeline — a prep thread
        overlaps batch k+1's concat/pad/device_put with device step k.
        False: serial prepare-then-step in the worker thread (bitwise
        reference path; same scheduler, same executables).
    pipeline_depth : int, optional
        Prepared batches allowed to wait for the worker. Depth d cycles
        d+1 staging/input parities, so the slot prep writes is never one a
        queued or in-flight batch still references; 1 (the default, via
        ``MXNET_SERVING_PIPELINE_DEPTH``) is classic double-buffering.
    """

    #: class default; instances resolve pipeline_depth/config in __init__
    _PIPELINE_DEPTH = 1

    def __init__(self, batch_timeout_ms: float = 2.0, max_queue: int = 256,
                 retry_policy: Optional[RetryPolicy] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 watchdog_stall_s: Optional[float] = None,
                 drain_timeout_s: Optional[float] = None,
                 pipeline: bool = True,
                 pipeline_depth: Optional[int] = None):
        self._batch_timeout_us = int(batch_timeout_ms * 1000)
        self._max_queue_rows = int(max_queue)
        self._pipeline = bool(pipeline)
        depth = int(pipeline_depth if pipeline_depth is not None
                    else _config.get("MXNET_SERVING_PIPELINE_DEPTH"))
        if depth < 1:
            raise MXNetError(f"pipeline_depth must be >= 1, got {depth}")
        self._PIPELINE_DEPTH = depth
        self._router = Router(self._batch_timeout_us)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._state = _STOPPED
        self._thread: Optional[threading.Thread] = None       # worker
        self._prep_thread: Optional[threading.Thread] = None  # prep stage
        self._prep_done = True
        self._prepared: "list[PreparedBatch]" = []
        # failover bookkeeping: which thread generation is current (stale
        # workers exit when superseded), what each stage is holding right now
        # (so a failover can requeue it), and pending hot-swap commands
        self._epoch = 0
        self._inflight: Optional[PreparedBatch] = None
        self._preparing = None          # (tenant, [requests]) during prep
        self._swaps: "list[_SwapRequest]" = []
        self._stall_listeners: list = []
        self.failovers = 0
        self._overlap = OverlapTracker()
        self._retry = retry_policy if retry_policy is not None \
            else RetryPolicy.from_config()
        self._shared_breaker = breaker          # None => per-tenant breakers
        self._breaker = breaker if breaker is not None \
            else CircuitBreaker(scope="serving")
        self._watchdog = Watchdog(stall_s=watchdog_stall_s,
                                  on_stall=self._on_stall)
        self._drain_timeout_s = float(
            drain_timeout_s if drain_timeout_s is not None
            else _config.get("MXNET_SERVING_DRAIN_TIMEOUT_S"))
        self._generators: Dict[str, object] = {}   # name -> DecodeScheduler

    # ------------------------------------------------------------------
    # endpoint management
    # ------------------------------------------------------------------
    def register(self, endpoint: ModelEndpoint, warmup: bool = True,
                 max_queue: Optional[int] = None,
                 slo_ms: Optional[float] = None,
                 slo_target: Optional[float] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 tier: str = "gold") -> ModelEndpoint:
        """Attach an endpoint as a tenant; by default compiles every shape
        bucket now so no request ever pays first-compile latency (warmup also
        seeds the scheduler's per-bucket step-cost EWMA).

        ``max_queue`` overrides the server default queue bound (the tenant's
        row quota); ``slo_ms`` sets the tenant's scheduling SLO — requests
        without an explicit deadline are scheduled as if due ``slo_ms`` after
        submit, and it doubles as the tenant's latency *objective*: the SLO
        monitor tracks the fraction of requests finishing under it against
        ``slo_target`` (default MXNET_SLO_TARGET) with burn-rate alerting;
        ``breaker`` overrides the tenant's circuit breaker; ``tier`` is the
        tenant's brownout criticality ("gold" / "silver" / "bulk") — under
        sustained SLO burn the brownout ladder refuses bulk tenants first,
        then silver; gold is never refused (default gold, so existing
        registrations are untouchable by the ladder)."""
        if tier not in _tailguard.TIER_RANKS:
            raise MXNetError(
                f"unknown tenant tier {tier!r}; expected one of "
                f"{sorted(_tailguard.TIER_RANKS)}")
        with self._cond:
            if endpoint.name in self._router:
                raise MXNetError(f"endpoint {endpoint.name!r} already registered")
            q = EndpointQueue(
                endpoint,
                int(max_queue) if max_queue is not None
                else self._max_queue_rows,
                self._batch_timeout_us)
            if breaker is None:
                breaker = self._shared_breaker if self._shared_breaker \
                    is not None else CircuitBreaker(
                        scope=f"serving:{endpoint.name}")
            self._router.add(Tenant(
                endpoint.name, endpoint, q, breaker,
                slo_us=int(slo_ms * 1000) if slo_ms is not None else None,
                slo_target=slo_target, tier=tier))
        if slo_ms is not None:
            _SLO.register(endpoint.name, threshold_us=slo_ms * 1000.0,
                          target=slo_target, breaker=breaker)
        if warmup:
            endpoint.warmup()
        return endpoint

    def register_generator(self, engine, warmup: bool = True,
                           tenants: Optional[Dict[str, float]] = None,
                           default_slo_ms: Optional[float] = None):
        """Attach a generative :class:`~.generate.DecodeEndpoint` behind its
        own continuous-batching DecodeScheduler (the decode loop owns its
        device work — it does not ride the request-batching worker).

        ``tenants`` maps tenant name -> inter-token SLO in ms/token (a
        ``default`` tenant always exists). With ``warmup`` every prefill and
        decode bucket compiles now and the step-cost EWMAs are seeded, so no
        sequence pays first-compile latency. Starts with the server (or
        immediately if the server is running); returns the scheduler."""
        from .generate import DecodeScheduler
        with self._cond:
            if engine.name in self._generators:
                raise MXNetError(
                    f"generator {engine.name!r} already registered")
        sched = DecodeScheduler(engine, default_slo_ms=default_slo_ms)
        for tname, slo_ms in (tenants or {}).items():
            sched.add_tenant(tname, slo_ms)
        if warmup:
            engine.warmup()
        with self._cond:
            self._generators[engine.name] = sched
            running = self._state == _RUNNING
        if running:
            sched.start()
        return sched

    def generate(self, name: str, prompt,
                 max_new_tokens: Optional[int] = None,
                 tenant: str = "default", eos_id: Optional[int] = None,
                 on_token=None, denoising_steps: Optional[int] = None):
        """Stream tokens from a registered generator: returns the
        :class:`~.generate.TokenStream` for one queued sequence.
        ``denoising_steps`` (a model generated by diffusion over blocks):
        forwards spent on each block, ``block_length / denoising_steps``
        tokens placed by each."""
        with self._cond:
            sched = self._generators.get(name)
        if sched is None:
            raise MXNetError(f"unknown generator {name!r}; registered: "
                             f"{sorted(self._generators)}")
        return sched.submit(prompt, max_new_tokens=max_new_tokens,
                            tenant=tenant, eos_id=eos_id, on_token=on_token,
                            denoising_steps=denoising_steps)

    def endpoints(self):
        with self._cond:
            return self._router.names()

    def breaker_for(self, name: str) -> CircuitBreaker:
        """The named tenant's circuit breaker (per-tenant shedding state)."""
        with self._cond:
            if name not in self._router:
                raise MXNetError(f"unknown endpoint {name!r}; registered: "
                                 f"{self._router.names()}")
            return self._router.get(name).breaker

    # ------------------------------------------------------------------
    # zero-downtime weight hot-swap (routed through the worker)
    # ------------------------------------------------------------------
    def hot_swap(self, name: str, source, timeout: Optional[float] = None
                 ) -> dict:
        """Swap the named endpoint's weights to ``source`` (a checkpoint
        directory or state tree) WITHOUT dropping a request.

        The heavy host work happens here on the caller's thread: the
        checkpoint is checksum-verified, shape-checked against the serving
        model, and staged into fresh device buffers (the in-flight batch
        keeps reading the old ones). The validation probe + cutover then
        ride the worker's command path and run *between* batches: every
        batch executes against either the complete old weights or the
        complete new ones, never a mixture, and the queue keeps flowing —
        the swap costs one probe step, not a drain.

        Validation failure (probe outputs differ from the ones recorded
        with the checkpoint, or are non-finite) rolls back: the old weights
        keep serving and HotSwapError is raised here. A corrupt checkpoint
        is refused before anything is staged. Blocks for the swap outcome
        (bounded by ``timeout`` seconds; None = wait)."""
        with self._cond:
            if name not in self._router:
                raise MXNetError(f"unknown endpoint {name!r}; registered: "
                                 f"{self._router.names()}")
            tenant = self._router.get(name)
        # verify + shape-check + stage on the caller's thread (host work
        # plus device_put — never a compiled executable)
        host_params, probe, label = tenant.endpoint.load_swap_source(source)
        req = _SwapRequest(tenant, host_params, probe, label)
        with self._cond:
            if self._state != _RUNNING:
                raise ServerClosedError(
                    f"server is {self._state}; hot_swap needs a running "
                    "worker (use endpoint.hot_swap() on a stopped one)")
            self._swaps.append(req)
            self._cond.notify_all()
        return req.future.result(timeout=timeout)

    def _apply_swap(self, req: _SwapRequest):
        """Worker-thread half of a routed hot-swap (between batches)."""
        ep = req.tenant.endpoint
        try:
            staged = ep.stage_weights(req.host_params)
            report = ep.validate_and_commit(staged, req.probe)
            report["source"] = req.label
            _telemetry.event("hot_swap", endpoint=ep.name, ok=True,
                             source=str(req.label),
                             weights_epoch=ep.weights_epoch)
            resolve(req.future, report)
        except Exception as e:
            exc = e if isinstance(e, HotSwapError) else HotSwapError(
                f"hot swap of {ep.name!r} failed validation: {e}")
            _telemetry.event("hot_swap", endpoint=ep.name, ok=False,
                             source=str(req.label), error=str(e)[:200])
            fail(req.future, exc)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self) -> "InferenceServer":
        with self._cond:
            if self._state != _STOPPED:
                raise MXNetError(f"server is {self._state}")
            for t in (self._thread, self._prep_thread):
                if t is not None and t.is_alive():
                    raise MXNetError(
                        "a previous worker is still wedged in a device call "
                        "(abandoned drain); this server cannot be restarted")
            self._state = _RUNNING
            self._prepared.clear()
            self._spawn_threads()
            gens = list(self._generators.values())
        for g in gens:
            g.start()
        _debug.attach(self)     # /healthz + /statusz see every live server
        return self

    def _spawn_threads(self):  # mxlint: disable=CONC200
        """Start a fresh worker (+prep) generation (caller holds the lock):
        used by start() and by failover(), which bumps the epoch first so
        any surviving stale thread exits at its next loop turn."""
        epoch = self._epoch
        self._prep_done = not self._pipeline
        self._inflight = None
        self._preparing = None
        self._thread = threading.Thread(
            target=self._loop_exec if self._pipeline
            else self._loop_serial, args=(epoch,),
            name=f"mxtpu-serving-worker-e{epoch}", daemon=True)
        if self._pipeline:
            self._prep_thread = threading.Thread(
                target=self._loop_prep, args=(epoch,),
                name=f"mxtpu-serving-prep-e{epoch}", daemon=True)
            self._prep_thread.start()
        else:
            self._prep_thread = None
        self._thread.start()

    def stop(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop serving. ``drain=True`` (default) processes every admitted
        request before returning, but never waits longer than ``timeout``
        seconds (default ``drain_timeout_s``): past it the remaining requests
        are abandoned and counted in ``mxtpu_drain_abandoned_total`` —
        queued-never-batched ones fail with ServerClosedError, requests
        already inside a prepared or in-flight batch with
        RequestTimeoutError (their latency budget died with the wedged
        worker) — so neither a wedged endpoint queue nor a hung device call
        can hang shutdown or leave a client waiting forever. ``drain=False``
        fails everything immediately."""
        timeout = self._drain_timeout_s if timeout is None else float(timeout)
        with self._cond:
            gens = list(self._generators.values())
        for g in gens:        # decode loops drain independently of the
            g.stop(drain=drain, timeout=timeout)   # request-batching worker
        with self._cond:
            if self._state == _STOPPED and self._thread is None and \
                    self._prep_thread is None:
                return
            # snapshot the thread handles under the lock: a concurrent stop()
            # (or a start() after abandon) must never see half-cleared
            # handles, so all joining below works on the locals
            worker, prep = self._thread, self._prep_thread
            if drain:
                self._state = _DRAINING
            else:
                self._state = _STOPPED
                exc = ServerClosedError("server stopped without drain")
                self._router.fail_all(exc)
                self._fail_prepared(exc)
                self._fail_swaps(ServerClosedError(
                    "server stopped without drain"))
            self._cond.notify_all()
        deadline = time.monotonic() + timeout
        if drain and (prep is not None or worker is not None):
            # the span is the goodput ledger's drain bucket: wall time spent
            # flushing admitted work during scale-down / shutdown
            with _telemetry.span("serving.drain",
                                 timeout_s=round(timeout, 3)):
                for t in (prep, worker):
                    if t is not None:
                        t.join(max(deadline - time.monotonic(), 0.0))
        else:
            for t in (prep, worker):
                if t is not None:
                    t.join(max(deadline - time.monotonic(), 0.0))
        if any(t is not None and t.is_alive() for t in (prep, worker)):
            # drain wedged (hung device step / endpoint queue): abandon.
            # The daemon threads may eventually finish their in-flight call;
            # they will find the state _STOPPED and exit, and resolve() on
            # already-failed futures is a no-op.
            with self._cond:
                self._state = _STOPPED
                abandoned = self._router.fail_all(ServerClosedError(
                    f"drain abandoned after {timeout:.1f}s (worker wedged)"))
                timed_out = RequestTimeoutError(
                    f"request abandoned inside a batch after the drain "
                    f"timeout ({timeout:.1f}s) with the worker wedged")
                abandoned += self._fail_prepared(timed_out)
                abandoned += self._fail_in_stage(timed_out)
                self._fail_swaps(ServerClosedError(
                    "drain abandoned (worker wedged)"))
                self._cond.notify_all()
            if abandoned:
                _DRAIN_ABANDONED.inc(abandoned)
            for t in (prep, worker):
                if t is not None:
                    t.join(1.0)
            if any(t is not None and t.is_alive() for t in (prep, worker)):
                # keep the handles: start() must refuse to run a second
                # worker beside a wedged one
                self._watchdog.stop()
                return
        with self._cond:
            if self._thread is worker:
                self._thread = None
            if self._prep_thread is prep:
                self._prep_thread = None
        self._watchdog.stop()

    @property
    def state(self) -> str:
        return self._state

    def health(self) -> dict:
        """Operator health snapshot: server lifecycle state, the worst
        circuit-breaker state across tenants (plus each tenant's own state
        and recent transitions), per-endpoint queue depth, and watchdog
        stall count."""
        with self._cond:
            state = self._state
            tenants = self._router.tenants()
        breakers = [self._breaker]
        endpoints = {}
        for t in tenants:
            if all(t.breaker is not b for b in breakers):
                breakers.append(t.breaker)
            endpoints[t.name] = {
                "pending_requests": len(t.queue),
                "pending_rows": t.queue.pending_rows,
                "circuit": t.breaker.state(),
                "slo_ms": t.slo_us / 1000.0 if t.slo_us else None,
                "slo_target": t.slo_target,
                "weights_epoch": t.endpoint.weights_epoch,
                # live step pricing: measured EWMA and count per bucket
                "step_cost": t.endpoint.step_cost.snapshot_detail(),
            }
        worst = max((b.state() for b in breakers),
                    key=lambda s: _CIRCUIT_SEVERITY[s])
        with self._cond:
            generators = {n: g.snapshot()
                          for n, g in self._generators.items()}
        return {"state": state,
                "circuit": worst,
                "breaker": self._breaker.snapshot(),
                "tenants": {t.name: t.breaker.snapshot() for t in tenants},
                "endpoints": endpoints,
                "generators": generators,
                "prep_overlap_ratio": self._overlap.ratio(),
                "watchdog_stalls": self._watchdog.stalls,
                "worker_epoch": self._epoch,
                "failovers": self.failovers}

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop(drain=True)
        return False

    # ------------------------------------------------------------------
    # client surface
    # ------------------------------------------------------------------
    def submit(self, name: str, inputs, deadline_ms: Optional[float] = None,
               deadline=None) -> Future:
        """Enqueue a request; returns a Future resolving to the endpoint's
        output (an NDArray, or a tuple for multi-output models). A single
        example (no batch axis) resolves without a batch axis; a batch of n
        rows resolves to n-row outputs.

        ``deadline`` is a propagated :class:`~.tailguard.Deadline` (minted
        once at ingress); when set it overrides ``deadline_ms`` — the
        request carries the SAME end-to-end budget through the queue instead
        of re-deriving a fresh one here, and an already-spent budget raises
        DeadlineExceeded before admission.

        Raises ServerOverloadError when the tenant's bounded queue is full,
        its circuit breaker is shedding load (OPEN: everything; HALF_OPEN:
        beyond the probe budget; DEGRADED: beyond half the queue bound), or
        the brownout ladder is refusing this tenant's tier, and
        ServerClosedError when the server is not accepting work."""
        if deadline is not None:
            deadline.check("ingress")
        with self._cond:
            if name not in self._router:
                raise MXNetError(f"unknown endpoint {name!r}; registered: "
                                 f"{self._router.names()}")
            tenant = self._router.get(name)
        q = tenant.queue
        if _tailguard.BROWNOUT.shed_tier(tenant.tier):
            q.endpoint.stats.bump("rejected")
            q.endpoint.stats.record_shed("brownout")
            raise ServerOverloadError(
                f"endpoint {name!r} (tier {tenant.tier!r}) shed by brownout "
                f"level {_tailguard.BROWNOUT.level}: the fleet is burning "
                "its SLO budget; retry with backoff")
        if not tenant.breaker.allow():
            q.endpoint.stats.bump("rejected")
            q.endpoint.stats.record_shed(f"circuit_{tenant.breaker.state()}")
            raise ServerOverloadError(
                f"endpoint {name!r} circuit {tenant.breaker.state()}: "
                "shedding load until the device recovers; retry with backoff")
        req = self._make_request(q.endpoint, inputs, deadline_ms, deadline)
        with self._cond:
            if self._state != _RUNNING:
                raise ServerClosedError(f"server is {self._state}")
            # graceful degradation: while DEGRADED admit only up to half the
            # tenant's queue bound, so a struggling device sees less queued
            # latency — per-tenant: other tenants keep their full bound
            if tenant.breaker.state() == DEGRADED and \
                    q.pending_rows + req.rows > q.max_queue_rows // 2:
                q.endpoint.stats.bump("rejected")
                q.endpoint.stats.record_shed("degraded")
                raise ServerOverloadError(
                    f"endpoint {name!r} degraded: admission tightened to "
                    f"{q.max_queue_rows // 2} rows; retry with backoff")
            if not q.offer(req):
                q.endpoint.stats.record_shed("queue_full")
                raise ServerOverloadError(
                    f"endpoint {name!r} queue full "
                    f"({q.pending_rows} rows >= {q.max_queue_rows}); retry with backoff")
            self._cond.notify_all()
        return req.future

    def predict(self, name: str, inputs, deadline_ms: Optional[float] = None,
                timeout: Optional[float] = None):
        """Blocking convenience wrapper over submit()."""
        return self.submit(name, inputs, deadline_ms).result(timeout=timeout)

    def _make_request(self, ep: ModelEndpoint, inputs,
                      deadline_ms: Optional[float],
                      deadline=None) -> Request:
        """Validate + host-normalize one request OUTSIDE the lock: every
        input becomes a contiguous numpy batch in the endpoint dtype."""
        if not isinstance(inputs, (tuple, list)):
            inputs = (inputs,)
        if len(inputs) != len(ep.input_shapes):
            raise MXNetError(f"endpoint {ep.name!r} takes "
                             f"{len(ep.input_shapes)} inputs, got {len(inputs)}")
        host = []
        rows = None
        squeeze = None
        for i, (x, shape, npdt) in enumerate(
                zip(inputs, ep.input_shapes, ep.np_dtypes)):
            a = x.asnumpy() if isinstance(x, NDArray) else onp.asarray(x)
            if a.shape == shape:
                a = a[None]
                sq = True
            elif a.shape[1:] == shape:
                sq = False
            else:
                raise MXNetError(
                    f"endpoint {ep.name!r} input {i}: expected per-example "
                    f"shape {shape} (optionally batched), got {a.shape}")
            if rows is None:
                rows, squeeze = a.shape[0], sq
            elif a.shape[0] != rows:
                raise MXNetError(f"endpoint {ep.name!r}: inputs disagree on "
                                 f"batch rows ({rows} vs {a.shape[0]})")
            if a.dtype != npdt:
                a = a.astype(npdt)
            host.append(onp.ascontiguousarray(a))
        if rows > ep.max_batch_size:
            raise MXNetError(
                f"request of {rows} rows exceeds endpoint {ep.name!r} "
                f"max_batch_size={ep.max_batch_size}; split the request")
        return Request(tuple(host), rows, squeeze, deadline_ms,
                       deadline=deadline)

    # ------------------------------------------------------------------
    # shared scheduling helpers (caller holds the condition lock)
    # ------------------------------------------------------------------
    def _next_assembly(self, epoch: int, take_swaps: bool = False):  # mxlint: disable=CONC200
        """Block (holding the lock) until the Router yields a tenant whose
        batch should assemble now, a drain can finish, or the server stops.
        Returns (tenant, requests); requests may be [] when all ready work
        had expired, None on exit (stopped, or drain complete), and
        _SUPERSEDED when a failover replaced this thread's generation.
        ``take_swaps`` (the serial worker, which is its own dispatcher)
        additionally returns pending _SwapRequests — ahead of batch
        assembly, so a swap lands at the next batch boundary."""
        while True:
            if self._state == _STOPPED:
                return None
            if self._epoch != epoch:
                return _SUPERSEDED
            if take_swaps and self._swaps:
                return self._swaps.pop(0)
            now = _now_us()
            flush = self._state == _DRAINING
            if len(self._prepared) >= self._PIPELINE_DEPTH:
                # handoff slot occupied: nothing to do until the worker pops
                # it (notify_all) — do NOT wake on batch deadlines, assembly
                # cannot proceed anyway (bounded wait in case the worker
                # dies mid-batch; stop() notifies too)
                self._cond.wait(timeout=0.25)
                continue
            tenant = self._router.select(now, flush)
            if tenant is not None:
                return tenant, tenant.queue.take_batch(now)
            if flush:
                # slot free + nothing ready under flush => queues are empty
                return None
            wakeup = self._router.next_wakeup_us()
            timeout = (max(wakeup - now, 0) / 1e6) if wakeup is not None \
                else None
            self._cond.wait(timeout=timeout)

    def _fail_prepared(self, exc: Exception) -> int:  # mxlint: disable=CONC200
        """Fail every prepared-but-unexecuted batch (caller holds the lock);
        returns the number of requests failed."""
        n = 0
        while self._prepared:
            pb = self._prepared.pop(0)
            for r in pb.requests:
                pb.tenant.endpoint.stats.bump("cancelled")
                fail(r.future, exc)
                n += 1
        return n

    def _fail_in_stage(self, exc: Exception) -> int:  # mxlint: disable=CONC200
        """Fail the requests held by the in-flight device step and the prep
        stage (caller holds the lock). The wedged daemon thread may
        eventually finish and try to resolve them; resolve() on a settled
        future is a no-op, the client already got this error."""
        n = 0
        for holder in (self._inflight, self._preparing):
            if holder is None:
                continue
            tenant, requests = (holder.tenant, holder.requests) \
                if isinstance(holder, PreparedBatch) else holder
            for r in requests:
                tenant.endpoint.stats.bump("cancelled")
                fail(r.future, exc)
                n += 1
        self._inflight = None
        self._preparing = None
        return n

    def _fail_swaps(self, exc: Exception):  # mxlint: disable=CONC200
        """Fail pending hot-swap commands (caller holds the lock)."""
        while self._swaps:
            fail(self._swaps.pop(0).future, exc)

    def _on_stall(self, name: str, dt: float):
        """Watchdog hook: a stalled device step degrades the *stalled
        tenant's* circuit (falling back to the server breaker when the watch
        name is not a tenant's), then notifies registered stall listeners
        (the PoolSupervisor confirms the wedge and fails the worker over)."""
        ep_name = name.partition("[")[2].rstrip("]")
        tenant = self._router.find(ep_name)
        br = tenant.breaker if tenant is not None else self._breaker
        br.force_degraded(f"stall {name} {dt:.1f}s")
        for cb in list(self._stall_listeners):
            try:
                cb(name, dt)
            except Exception:
                pass            # a broken listener must not kill the monitor

    def add_stall_listener(self, cb):
        """Subscribe to watchdog stall events: ``cb(watch_name, elapsed_s)``
        runs on the watchdog monitor thread and must not block."""
        self._stall_listeners.append(cb)

    def remove_stall_listener(self, cb):
        try:
            self._stall_listeners.remove(cb)
        except ValueError:
            pass

    # ------------------------------------------------------------------
    # worker failover (driven by the PoolSupervisor)
    # ------------------------------------------------------------------
    def failover(self, reason: str, tenant_name: Optional[str] = None
                 ) -> Optional[dict]:
        """Replace a dead or wedged worker generation without restarting the
        server: requeue every batch the old generation held (prepared
        batches, the prep stage's in-hand assembly, and the in-flight device
        batch) at the FRONT of their tenant queues — original order and
        deadlines preserved, so expired requests still fail with
        RequestTimeoutError at re-assembly instead of silently re-running —
        trip only the affected tenant's circuit breaker, bump the thread
        epoch (a surviving zombie exits at its next loop turn; its late
        future resolutions are no-ops), and start fresh worker/prep threads.

        Returns a report dict, or None when the server was not running (a
        racing stop() wins). Other tenants' queues, breakers and SLOs are
        untouched — one tenant's wedged batch is that tenant's problem."""
        with self._cond:
            if self._state != _RUNNING:
                return None
            self._epoch += 1
            requeued = 0
            # newest-assembled first, so the oldest work ends up at the head
            for pb in reversed(self._prepared):
                pb.tenant.queue.requeue_front(pb.requests)
                requeued += len(pb.requests)
            self._prepared.clear()
            if self._preparing is not None:
                tenant, batch = self._preparing
                tenant.queue.requeue_front(batch)
                requeued += len(batch)
                self._preparing = None
            inflight = self._inflight
            if inflight is not None:
                inflight.tenant.queue.requeue_front(inflight.requests)
                requeued += len(inflight.requests)
                if tenant_name is None:
                    tenant_name = inflight.tenant.name
                self._inflight = None
            affected = self._router.find(tenant_name) \
                if tenant_name is not None else None
            if affected is not None:
                affected.breaker.record_failure()
            self.failovers += 1
            epoch = self._epoch
            self._spawn_threads()
            self._cond.notify_all()
        _FAILOVERS.labels(reason).inc()
        if requeued:
            _FAILOVER_REQUEUED.inc(requeued)
        report = {"reason": reason, "epoch": epoch, "requeued": requeued,
                  "tenant": tenant_name}
        _flight.trigger("failover", **report)
        return report

    # ------------------------------------------------------------------
    # serial worker (pipeline=False): assemble -> prepare -> execute inline
    # ------------------------------------------------------------------
    def _loop_serial(self, epoch: int):
        while True:
            with self._cond:
                item = self._next_assembly(epoch, take_swaps=True)
                if item is _SUPERSEDED:
                    return                 # a failover replaced this worker
                if item is None:
                    self._state = _STOPPED
                    self._fail_swaps(ServerClosedError("server stopped"))
                    self._cond.notify_all()
                    return
            if isinstance(item, _SwapRequest):
                self._apply_swap(item)     # batch boundary by construction
                continue
            tenant, batch = item
            if not batch:
                continue
            with self._cond:
                self._preparing = (tenant, batch)
            # no finally: if a thread-killing BaseException escapes
            # _prepare, the _preparing record survives for failover to
            # requeue; ordinary prep failures return None (futures failed)
            pb = self._prepare(tenant, batch, 0)
            with self._cond:
                if self._preparing is not None and \
                        self._preparing[1] is batch:
                    self._preparing = None
            if pb is not None:
                self._execute(pb)

    # ------------------------------------------------------------------
    # pipelined prep stage: assemble + device_put batch k+1 during step k
    # ------------------------------------------------------------------
    def _loop_prep(self, epoch: int):
        parity = 0
        while True:
            with self._cond:
                item = self._next_assembly(epoch)
                if item is _SUPERSEDED:
                    return                 # a failover replaced this stage
                if item is None:
                    self._prep_done = True
                    self._cond.notify_all()
                    return
            tenant, batch = item
            if not batch:
                continue
            with self._cond:
                self._preparing = (tenant, batch)
            # no finally: see _loop_serial — a killed prep thread leaves the
            # _preparing record for failover to requeue
            pb = self._prepare(tenant, batch, parity)
            with self._cond:
                if self._preparing is not None and \
                        self._preparing[1] is batch:
                    self._preparing = None
            if pb is None:
                continue                  # prep failed; futures already failed
            # cycle over depth+1 parities: with d batches queued ahead plus
            # one in flight, the slot being rewritten is always retired
            parity = (parity + 1) % (self._PIPELINE_DEPTH + 1)
            with self._cond:
                if self._epoch != epoch:
                    # superseded mid-prepare: hand the rows back to their
                    # queue — the replacement generation re-assembles them
                    tenant.queue.requeue_front(pb.requests)
                    self._cond.notify_all()
                    return
                if self._state == _STOPPED:
                    exc = ServerClosedError("server stopped")
                    for r in pb.requests:
                        tenant.endpoint.stats.bump("cancelled")
                        fail(r.future, exc)
                    continue
                self._prepared.append(pb)
                self._cond.notify_all()

    def _prepare(self, tenant: Tenant, batch, parity: int
                 ) -> Optional[PreparedBatch]:
        """Run the host prep for one assembled batch (lock NOT held); on
        failure fail the batch's futures against the tenant's breaker."""
        try:
            return prepare_batch(tenant, batch, parity, self._overlap,
                                 self._retry)
        except Exception as e:
            tenant.breaker.record_failure()
            for r in batch:
                fail(r.future, e)
            return None

    # ------------------------------------------------------------------
    # pipelined worker: execute prepared batches (the only executable caller)
    # ------------------------------------------------------------------
    def _loop_exec(self, epoch: int):
        while True:
            with self._cond:
                item = self._next_prepared(epoch)
                if item is _SUPERSEDED:
                    return                 # a failover replaced this worker
                if item is None:
                    self._state = _STOPPED
                    self._fail_swaps(ServerClosedError("server stopped"))
                    self._cond.notify_all()
                    return
            if isinstance(item, _SwapRequest):
                self._apply_swap(item)     # between batches: the boundary
                continue
            self._execute(item)

    def _next_prepared(self, epoch: int):  # mxlint: disable=CONC200
        """Block (holding the lock) for the next prepared batch or hot-swap
        command (commands first: they cut over at the batch boundary);
        None on stop or a fully-flushed drain, _SUPERSEDED on failover."""
        while True:
            if self._state == _STOPPED:
                return None
            if self._epoch != epoch:
                return _SUPERSEDED
            if self._swaps:
                return self._swaps.pop(0)
            if self._prepared:
                pb = self._prepared.pop(0)
                self._cond.notify_all()    # the handoff slot is free again
                return pb
            if self._state == _DRAINING and self._prep_done:
                return None
            self._cond.wait()

    # ------------------------------------------------------------------
    # device dispatch (worker thread only)
    # ------------------------------------------------------------------
    def _execute(self, pb: PreparedBatch):
        from .. import telemetry
        ep = pb.tenant.endpoint
        from ..ops.registry import _profiler_running
        profiling = _profiler_running()
        t0 = _now_us()

        def run_step():
            _faults.check("serving_dispatch")
            step = lambda: ep.execute(pb.inputs, pb.bucket, pb.rows,
                                      padded_host=pb.padded_host)
            if profiling:
                from .. import profiler
                return profiler._dispatch_profiled(
                    f"serving[{ep.name}]b{pb.rows}", step, cat="serving")
            return step()

        with self._cond:
            self._inflight = pb
        # `killed` guards the in-flight record: a thread-killing
        # BaseException (worker_kill drill, interpreter death) must leave it
        # set so failover can requeue the orphaned batch; every caught path
        # clears it below
        killed = True
        self._overlap.step_begin()
        try:
            # adopt the oldest request's trace id for the whole batch step:
            # its end-to-end trace (submit -> batch -> device) is the one
            # closest to the latency budget, and the span records how many
            # requests/rows rode along
            with telemetry.span("serving.batch",
                                trace_id=pb.requests[0].trace_id,
                                endpoint=ep.name, rows=pb.rows,
                                requests=len(pb.requests)):
                with self._watchdog.watch(f"serving[{ep.name}]"):
                    # retries must respect what clients asked for: never back
                    # off past the earliest request deadline in the batch
                    outs = self._retry.run(run_step, site="serving_dispatch",
                                           deadline_us=pb.deadline_us,
                                           budget_tier="execute")
            killed = False
        except Exception as e:  # retries exhausted / fatal: fail the batch
            killed = False
            pb.tenant.breaker.record_failure()
            failed_at = _now_us()
            for r in pb.requests:
                fail(r.future, e)
                _flight.record_request(r.trace_id, ep.name,
                                       failed_at - r.enqueue_us,
                                       rows=r.rows, ok=False,
                                       error=type(e).__name__)
                _SLO.record(ep.name, failed_at - r.enqueue_us, ok=False)
            return
        finally:
            self._overlap.step_end()
            if not killed:
                with self._cond:
                    # guarded: after a failover this slot belongs to the
                    # replacement worker's batch, not to this zombie
                    if self._inflight is pb:
                        self._inflight = None
        pb.tenant.breaker.record_success()
        # one executed batch = one unit of real work funding the execute
        # tier's retry budget
        _tailguard.retry_deposit("execute")
        ep.stats.record_step(_now_us() - t0)
        off = 0
        done = _now_us()
        for r in pb.requests:
            sliced = tuple(
                NDArray(o[off] if r.squeeze else o[off:off + r.rows], ctx=ep.ctx)
                for o in outs)
            resolve(r.future, sliced[0] if ep.num_outputs == 1 else sliced)
            ep.stats.record_latency(done - r.enqueue_us)
            ep.stats.bump("completed")
            _flight.record_request(r.trace_id, ep.name, done - r.enqueue_us,
                                   rows=r.rows)
            _SLO.record(ep.name, done - r.enqueue_us)
            if profiling:
                from .. import profiler
                profiler.record_duration(f"serving[{ep.name}].request",
                                         r.enqueue_us, done - r.enqueue_us,
                                         cat="serving")
            off += r.rows
