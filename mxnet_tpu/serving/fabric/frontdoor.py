"""Multi-host serving front door: process groups, consistent-hash tenant
routing, and cross-host failover with zero client-visible errors.

A :class:`FrontDoor` fronts N *hosts*. Each host is one serving plane — an
``InferenceServer`` (built by the caller's ``host_factory``, endpoints
registered and started) plus a **host agent subprocess**: the CPU stand-in
for a ``jax.distributed`` process-group member. The agent runs a tiny real
workload at startup (so its goodput ledger is non-trivial), then heartbeats:
every tick it touches its heartbeat file, re-attributes goodput
(``goodput.account()`` — buckets always reconcile to wall exactly) and
rewrites its telemetry dump. A SIGKILLed host therefore leaves behind a
recent dump for the post-mortem pane, and a silent one is detected by
heartbeat age (:meth:`check_hosts`) rather than by an RPC that would hang.

Routing is a consistent-hash ring (``MXNET_FABRIC_VNODES`` virtual nodes
per host, md5 positions): a tenant maps to the first **alive** host at or
after its hash. Rebalancing is bounded by construction — when a host dies,
exactly the tenants whose walk landed on it move (to the next survivor
clockwise); every other tenant keeps its host. ``mxtpu_fabric_tenant_moves_total``
counts the moves so a test can pin the bound.

Failover rides the same fencing discipline as the intra-host supervisor
(each host also gets a :class:`~..supervisor.PoolSupervisor`): killing a
host bumps the front door's epoch, fails the host's queued work with
``ServerClosedError`` via ``stop(drain=False)``, and the front door's
wrapper future catches exactly that and resubmits on the rerouted survivor
— the client's future resolves normally. Zero dropped requests is the
acceptance bar, and :mod:`tools.chaos_check` ``--scenario host_down``
drills it.
"""
from __future__ import annotations

import hashlib
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Sequence

from ... import config as _config
from ... import telemetry as _telemetry
from ...base import MXNetError
from ...resilience import faults as _faults
from ...resilience.faults import FaultInjected
from ...telemetry import flight as _flight
from ...telemetry.fleet import FleetCollector
from .. import tailguard as _tailguard
from ..errors import ServerClosedError, ServerOverloadError
from ..supervisor import PoolSupervisor

__all__ = ["FrontDoor"]

_HOSTS_G = _telemetry.gauge(
    "mxtpu_fabric_hosts",
    "Front-door hosts by liveness ('alive'/'down').",
    labelnames=("state",))
_MOVES_C = _telemetry.counter(
    "mxtpu_fabric_tenant_moves_total",
    "Tenants rehashed to a different host after a membership change — "
    "bounded rebalancing means only a dead host's tenants ever move.")
_FAILOVERS_C = _telemetry.counter(
    "mxtpu_fabric_host_failovers_total",
    "Host-down failovers the front door executed, by host.",
    labelnames=("host",))
_RESUBMITS_C = _telemetry.counter(
    "mxtpu_fabric_resubmits_total",
    "In-flight requests resubmitted on a survivor after their host died.")
_REQS_C = _telemetry.counter(
    "mxtpu_fabric_requests_total",
    "Requests routed through the front door, by host.",
    labelnames=("host",))


# The process-group member: a real subprocess per host. Startup serves a
# tiny real workload (non-trivial goodput), then each tick touches the
# heartbeat file, re-attributes goodput and rewrites this host's telemetry
# dump. Spans join the parent's journey via the inherited MXNET_TRACE_ID.
_HOST_AGENT_SRC = """\
import os, time
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import nd, serving, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import goodput

host = os.environ["FABRIC_HOST"]
hb = os.environ["FABRIC_HB_PATH"]
dump = os.environ["FABRIC_DUMP_PATH"]
tick_s = float(os.environ.get("FABRIC_TICK_S", "0.2"))

mx.random.seed(0); onp.random.seed(0)
net = nn.HybridSequential()
with net.name_scope():
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
net.initialize(mx.init.Xavier())
net(nd.array(onp.zeros((2, 6), "float32")))
with telemetry.span("fabric.host_agent", host=host):
    srv = serving.InferenceServer(batch_timeout_ms=1.0)
    srv.register(serving.ModelEndpoint("fabric_probe_" + host, net,
                                       input_shapes=(6,), max_batch_size=4))
    srv.start()
    for _ in range(3):
        srv.submit("fabric_probe_" + host,
                   onp.zeros((2, 6), "float32")).result(timeout=30)
    srv.stop()
    serving.unregister("fabric_probe_" + host)
telemetry.spool_flush()
while True:
    with open(hb, "w") as f:
        f.write(str(time.time()))
    goodput.account()
    telemetry.dump(dump)
    time.sleep(tick_s)
"""


def _hash(s: str) -> int:
    return int.from_bytes(hashlib.md5(s.encode()).digest()[:8], "big")


class _Host:
    __slots__ = ("name", "server", "supervisor", "agent", "hb_path",
                 "dump_path", "alive")

    def __init__(self, name, server):
        self.name = name
        self.server = server
        self.supervisor = None
        self.agent = None
        self.hb_path = ""
        self.dump_path = ""
        self.alive = True


class FrontDoor:
    """Route tenants across host serving planes; survive a host dying.

    Parameters
    ----------
    hosts : sequence of str
        Host names (process-group members).
    host_factory : callable(name) -> InferenceServer
        Builds one host's serving plane: a STARTED server with this
        fabric's endpoints registered. Every host must register the same
        tenant set — the ring may land any tenant on any host.
    spawn_agents : bool
        Launch the per-host agent subprocess (heartbeat + dumps). On by
        default; tests that only exercise routing may turn it off.
    supervise : bool
        Attach a PoolSupervisor to each host's server for intra-host
        worker/prep failover. On by default.
    workdir : str, optional
        Where heartbeat and dump files live (default: a fresh tempdir).
    """

    def __init__(self, hosts: Sequence[str],
                 host_factory: Callable[[str], object],
                 spawn_agents: bool = True, supervise: bool = True,
                 workdir: Optional[str] = None):
        names = list(hosts)
        if len(set(names)) != len(names) or not names:
            raise MXNetError(f"need unique, non-empty host names: {names}")
        self.epoch = 0
        self._lock = threading.RLock()
        self._workdir = workdir or tempfile.mkdtemp(prefix="mxtpu-fabric-")
        self._vnodes = int(_config.get("MXNET_FABRIC_VNODES"))
        self._hosts: Dict[str, _Host] = {}
        self._owner: Dict[str, str] = {}      # tenant -> host, for move count
        for n in names:
            h = _Host(n, host_factory(n))
            h.hb_path = os.path.join(self._workdir, f"hb-{n}")
            h.dump_path = os.path.join(self._workdir, f"dump-host-{n}.json")
            if supervise:
                h.supervisor = PoolSupervisor(h.server).start()
            self._hosts[n] = h
        tenant_sets = {n: frozenset(h.server._router.names())
                       for n, h in self._hosts.items()}
        if len(set(tenant_sets.values())) != 1:
            raise MXNetError(
                f"hosts must register identical tenant sets, got "
                f"{ {n: sorted(s) for n, s in tenant_sets.items()} }")
        self._ring = self._build_ring()
        if spawn_agents:
            for h in self._hosts.values():
                self._spawn_agent(h)
        self._set_hosts_gauge()

    # -- membership -----------------------------------------------------
    def _build_ring(self) -> List:
        ring = []
        for n in self._hosts:
            for v in range(self._vnodes):
                ring.append((_hash(f"{n}#{v}"), n))
        ring.sort()
        return ring

    def _set_hosts_gauge(self):
        up = sum(1 for h in self._hosts.values() if h.alive)
        _HOSTS_G.labels("alive").set(up)
        _HOSTS_G.labels("down").set(len(self._hosts) - up)

    def _spawn_agent(self, h: _Host):
        env = dict(os.environ)
        env["FABRIC_HOST"] = h.name
        env["FABRIC_HB_PATH"] = h.hb_path
        env["FABRIC_DUMP_PATH"] = h.dump_path
        env["FABRIC_TICK_S"] = str(_config.get("MXNET_FABRIC_HEARTBEAT_S"))
        # the agent is a CPU stand-in whatever the parent runs on: the
        # front door's own process holds the chip, and a chip has room for
        # one process
        env["JAX_PLATFORMS"] = "cpu"
        h.agent = subprocess.Popen(
            [sys.executable, "-c", _HOST_AGENT_SRC], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    def hosts(self) -> List[str]:
        return list(self._hosts)

    def alive_hosts(self) -> List[str]:
        return [n for n, h in self._hosts.items() if h.alive]

    def tenants(self) -> List[str]:
        h = next(iter(self._hosts.values()))
        return list(h.server._router.names())

    # -- routing --------------------------------------------------------
    def route(self, tenant: str) -> str:
        """The first alive host at/after the tenant's ring position.
        Bounded rebalancing falls out of the walk: a dead host only
        reroutes the tenants that previously landed on it."""
        with self._lock:
            if not any(h.alive for h in self._hosts.values()):
                raise ServerClosedError("fabric: every host is down")
            pos = _hash(tenant)
            idx = 0
            for i, (p, _) in enumerate(self._ring):
                if p >= pos:
                    idx = i
                    break
            for step in range(len(self._ring)):
                _, name = self._ring[(idx + step) % len(self._ring)]
                if self._hosts[name].alive:
                    prev = self._owner.get(tenant)
                    if prev is not None and prev != name:
                        _MOVES_C.inc()
                    self._owner[tenant] = name
                    return name
            raise ServerClosedError("fabric: every host is down")

    def submit(self, tenant: str, inputs, deadline_ms: Optional[float] = None
               ) -> Future:
        """Route and enqueue; the returned future hides host death. When
        the serving host dies before this request resolves, the request is
        resubmitted on the rerouted survivor behind the same future —
        callers never see the dead host's ServerClosedError.

        ``deadline_ms`` mints the request's end-to-end
        :class:`~..tailguard.Deadline` HERE, at ingress — the one budget
        every downstream tier (routing, pool, queue, batch, retry backoff)
        decrements; no tier re-derives its own."""
        deadline = _tailguard.Deadline(deadline_ms) \
            if deadline_ms is not None else None
        out: Future = Future()
        self._submit_once(tenant, inputs, deadline_ms, out,
                          tries=len(self._hosts), deadline=deadline)
        return out

    def _submit_once(self, tenant, inputs, deadline_ms, out: Future,
                     tries: int, deadline=None):
        if deadline is not None:
            deadline.check("ingress")
        # the network hop between client and serving plane: net_delay
        # sleeps in place; net_drop (a partition) raises and is absorbed by
        # re-sending under the frontdoor retry budget — a drop storm
        # converts into bounded shed the moment the bucket runs dry
        while True:
            try:
                _faults.check("frontdoor")
                break
            except FaultInjected as e:
                if not e.retryable or not _tailguard.retry_allowed(
                        "frontdoor"):
                    raise
                if deadline is not None:
                    deadline.check("ingress")
        host = self.route(tenant)
        h = self._hosts[host]
        _REQS_C.labels(host).inc()
        # one routed request = one unit of real work funding the frontdoor
        # tier's retry budget
        _tailguard.retry_deposit("frontdoor")
        try:
            inner = h.server.submit(tenant, inputs, deadline_ms=deadline_ms,
                                    deadline=deadline)
        except (ServerClosedError, ServerOverloadError):
            # overload on a LIVE host is the caller's backpressure signal;
            # only a dead host's rejection reroutes (race with kill_host),
            # and the replay spends a frontdoor retry-budget token
            if h.alive or tries <= 1 or not self.alive_hosts() \
                    or not _tailguard.retry_allowed("frontdoor"):
                raise
            _RESUBMITS_C.inc()
            return self._submit_once(tenant, inputs, deadline_ms, out,
                                     tries - 1, deadline=deadline)

        def _done(f: Future):
            exc = f.exception()
            if exc is None:
                out.set_result(f.result())
                return
            # ServerClosedError from a host marked down == the host died
            # with this request in flight: replay it on a survivor (same
            # propagated deadline — the budget keeps burning), under the
            # frontdoor retry budget
            if isinstance(exc, ServerClosedError) and not h.alive \
                    and tries > 1 and self.alive_hosts() \
                    and _tailguard.retry_allowed("frontdoor"):
                _RESUBMITS_C.inc()
                try:
                    self._submit_once(tenant, inputs, deadline_ms, out,
                                      tries - 1, deadline=deadline)
                except Exception as e:          # survivors full/closed
                    out.set_exception(e)
                return
            out.set_exception(exc)

        inner.add_done_callback(_done)

    # -- failure handling -----------------------------------------------
    def kill_host(self, name: str, reason: str = "host_down") -> Dict:
        """Take one host out: SIGKILL its agent, fail its serving plane
        (queued work raises ServerClosedError → the wrapper futures replay
        on survivors), bump the epoch fence and rehash. Returns a report
        naming the host, the epoch and how many tenants moved."""
        with self._lock:
            h = self._hosts.get(name)
            if h is None:
                raise MXNetError(f"unknown host {name!r}: {self.hosts()}")
            if not h.alive:
                return {"host": name, "epoch": self.epoch, "moved": 0,
                        "already_down": True}
            before = dict(self._owner)
            h.alive = False              # routing excludes it from here on
            self.epoch += 1
            epoch = self.epoch
        if h.agent is not None and h.agent.poll() is None:
            try:
                h.agent.send_signal(signal.SIGKILL)
                h.agent.wait(timeout=10)
            except Exception:
                pass
        if h.supervisor is not None:
            h.supervisor.stop()
        h.server.stop(drain=False)       # fails inflight -> resubmission
        moved = 0
        for t in self.tenants():
            new = self.route(t)
            if before.get(t) == name and new != name:
                moved += 1
        _FAILOVERS_C.labels(name).inc()
        self._set_hosts_gauge()
        report = {"host": name, "reason": reason, "epoch": epoch,
                  "moved": moved, "survivors": self.alive_hosts()}
        _flight.trigger("host_down", **report)
        _telemetry.event("fabric_host_down", **report)
        return report

    def check_hosts(self) -> List[Dict]:
        """Heartbeat-age failure detector: a host whose agent has not
        ticked within MXNET_FABRIC_HOST_TIMEOUT_S is declared dead and
        failed over exactly like :meth:`kill_host`."""
        timeout_s = float(_config.get("MXNET_FABRIC_HOST_TIMEOUT_S"))
        reports = []
        for n, h in list(self._hosts.items()):
            if not h.alive or h.agent is None:
                continue
            age = None
            try:
                with open(h.hb_path) as f:
                    age = time.time() - float(f.read().strip())
            except (OSError, ValueError):
                pass                      # no beat yet: judge by spawn age
            dead_proc = h.agent.poll() is not None
            if dead_proc or (age is not None and age > timeout_s):
                reports.append(self.kill_host(
                    n, reason="agent_exit" if dead_proc else "heartbeat"))
        return reports

    # -- one pane of glass ----------------------------------------------
    def fleet_collect(self, include_local: bool = True) -> Dict:
        """The PR 15 fleet collector over every host agent's dump (plus
        this front-door process when ``include_local``)."""
        coll = FleetCollector(include_local=include_local,
                              local_label=f"frontdoor-{os.getpid()}",
                              glob="")
        for n, h in self._hosts.items():
            if os.path.exists(h.dump_path):
                coll.add_file(h.dump_path, label=f"host-{n}")
        return coll.collect()

    def goodput_reconcile(self, tol: float = 0.01) -> Dict[str, Dict]:
        """Per-host goodput ledger check from each host's own dump: the
        bucket seconds must sum to that host's wall clock within ``tol``."""
        import json
        out = {}
        for n, h in self._hosts.items():
            if not os.path.exists(h.dump_path):
                continue
            with open(h.dump_path) as f:
                snap = json.load(f)
            mets = snap.get("metrics", {})
            wall = max((float(s.get("value", 0.0)) for s in
                        mets.get("mxtpu_goodput_wall_seconds",
                                 {}).get("series", [])), default=0.0)
            total = sum(float(s.get("value", 0.0)) for s in
                        mets.get("mxtpu_goodput_seconds_total",
                                 {}).get("series", []))
            out[n] = {"wall_s": wall, "buckets_sum_s": total,
                      "ok": abs(total - wall) <= tol * max(wall, 1e-9)}
        return out

    # -- lifecycle ------------------------------------------------------
    def stop(self, drain: bool = True):
        """Stop every surviving host plane and reap the agents."""
        for h in self._hosts.values():
            if h.supervisor is not None:
                h.supervisor.stop()
            if h.agent is not None and h.agent.poll() is None:
                try:
                    h.agent.send_signal(signal.SIGKILL)
                    h.agent.wait(timeout=10)
                except Exception:
                    pass
            if h.alive:
                h.alive = False
                try:
                    h.server.stop(drain=drain)
                except Exception:
                    pass
        self._set_hosts_gauge()

    def __repr__(self):
        return (f"FrontDoor(hosts={self.hosts()}, "
                f"alive={self.alive_hosts()}, epoch={self.epoch})")
