"""Structured span tracing with cross-layer trace-id propagation.

``telemetry.span(name, **attrs)`` opens a nested, timed span:

  - spans nest via a contextvar; a child inherits its parent's ``trace_id``
    so one logical operation (a serving request, a training step) is one
    trace across layers, even when the layers are different subsystems.
  - a span can be *adopted* across threads by passing an explicit
    ``trace_id=...`` — the serving path stamps each admitted request with
    the submitter's trace id, and the worker thread re-opens the trace
    around batch assembly and the compiled device step, so a request's
    trace id survives the queue hop.
  - a span records the wall time it took (``dur_us``) and, where it is
    opened with ``cpu=True``, the CPU time of the thread that ran it
    (``cpu_us``, ``time.thread_time_ns``): the difference is the time that
    thread stood without the processor inside the span (a lock, the
    interpreter lock, a blocking call). Asked for and not taken on every
    span, because the thread's CPU clock is a system call: 0.35 us a read on
    a plain Linux host, but 5.6-6.8 us on the TPU v5e host this was measured
    on, where it also ticks in 10 ms (PERF.md, PR 36): there a single span's
    ``cpu_us`` is 0 or 10,000 and only sums over many spans say something.
  - on exit a span feeds the sinks that have a reader (OBSERVABILITY.md
    names each): the registry's ``mxtpu_span_duration_us{name=...}``
    histogram and the flight recorder's ring, always; the profiler's
    chrome-trace event stream while an ``mxnet_tpu.profiler`` session runs
    (the span lands in the same ``traceEvents`` timeline as per-op events,
    with the trace id in ``args``); the per-pid spool while a spool
    directory is set. A sink that is off costs a span one test.
  - once ``jax`` is imported a span also holds a
    ``jax.profiler.TraceAnnotation`` of the same name (``trace_id`` and
    ``span_id`` as its stats) for its whole life: a no-op while no
    ``jax.profiler`` trace runs, and under one the span lies in the
    XPlane's host plane, on the clock of the device's ops.

Span names are dot-scoped ``layer.operation`` (``serving.batch``,
``train.step``, ``dataloader.wait`` — see OBSERVABILITY.md for the
convention); attrs are small JSON-able values, never tensors.

Cross-process journeys (the fleet plane): while ``MXNET_SPAN_SPOOL_DIR`` is
set (read at the process's first span and again at every ``spool_flush()``)
every finished span also lands in a bounded in-memory spool buffer, which
drains — every ``MXNET_SPAN_SPOOL_FLUSH_N`` spans, and at interpreter
exit — into an append-only per-pid JSONL file (``spool-<pid>.jsonl``, the
compile-ledger file pattern: one ``O_APPEND`` write per batch, size-capped
and rotated). Each line carries the pid and a wall-clock anchor, so
``tools/trace_journey.py`` can assemble one ordered timeline for a trace id
across every process that touched it. A child process inherits its parent's
trace via the ``MXNET_TRACE_ID`` env knob: the first *root* span of the
process adopts it instead of minting a fresh id.
"""
from __future__ import annotations

import atexit
import contextvars
import itertools
import json
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from .metrics import REGISTRY
from .flight import RECORDER as _FLIGHT_RECORDER, _clean_attrs

__all__ = ["Span", "span", "current_span", "current_trace_id",
           "new_trace_id", "self_times", "spool_flush", "spool_path",
           "read_spool", "journey"]

# pre-bound deque.append: the flight span ring rides every span exit, so the
# hot path pays one bounded-deque append (GIL-atomic) and nothing else
_record_flight_span = _FLIGHT_RECORDER._spans.append

_CURRENT: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "mxtpu_current_span", default=None)

# per-process random source; seeded from urandom, independent of user PRNGs
_RNG = random.Random()
# span ids count up, "s1", "s2", ... in hex: unique in the process (``next``
# of a count is one step under the interpreter lock), and with the pid of a
# spool line across them. The letter keeps an id a string in the XPlane,
# whose stats read "10" back as a number
_SPAN_IDS = itertools.count(1)
_SPAN_DURATION = REGISTRY.histogram(
    "mxtpu_span_duration_us",
    "Duration of telemetry spans by span name (microseconds).",
    labelnames=("name",))
# name -> its histogram child's ``observe``, looked up once a name
_OBSERVE_DURATION: Dict[str, object] = {}


def new_trace_id() -> str:
    return f"{_RNG.getrandbits(64):016x}"


_perf_ns = time.perf_counter_ns
_thread_ns = time.thread_time_ns


def _cfg(name, default):
    """Knob read tolerating the partially initialized package (tracing can be
    imported before ``mxnet_tpu.config`` is bound during package init)."""
    try:
        from .. import config
        return config.get(name, default)
    except Exception:
        return default


# -- cross-process trace inheritance ------------------------------------------
# Resolved once per process: MXNET_TRACE_ID is the parent's trace id handed
# to a child at spawn (env), so the child's first root span joins the
# parent's journey instead of minting a fresh id.
_INHERITED_TRACE: Optional[str] = None
_INHERITED_RESOLVED = False


def _inherited_trace_id() -> Optional[str]:
    global _INHERITED_TRACE, _INHERITED_RESOLVED
    if not _INHERITED_RESOLVED:
        _INHERITED_TRACE = str(_cfg("MXNET_TRACE_ID", "") or "") or None
        _INHERITED_RESOLVED = True
    return _INHERITED_TRACE


class Span:
    """One timed region, and the context manager that times it:
    ``with span(name, **attrs) as s``. ``trace_id`` adopts an existing trace
    (cross-thread propagation); otherwise the parent's trace is inherited,
    or a fresh trace is started at the root. ``parent`` names the span this
    one hangs under where that is not the one open around it: the late half
    of work whose first half ran under a span that has closed since (a
    launch and the fetch of its result, with other work between them).
    ``.trace_id`` is the handle to stamp onto queue items / requests for
    later adoption. ``dur_us`` is the wall time it took, None until it ends.
    ``cpu_us`` is the CPU time of the thread that ran it (a span exits on
    the thread that opened it, whatever ``parent`` it was given), taken
    where ``cpu=True`` asks for it (two system calls) and None otherwise.
    Read-only for users."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "t0_us", "dur_us", "cpu_us", "_cpu0_ns", "_token",
                 "_annotation")

    def __init__(self, name: str, trace_id: Optional[str] = None,
                 parent: Optional["Span"] = None, cpu: bool = False,
                 **attrs):
        if parent is None:
            parent = _CURRENT.get()
        if parent is not None:
            self.parent_id = parent.span_id
            if trace_id is None:
                trace_id = parent.trace_id
        else:
            self.parent_id = None
            if trace_id is None:
                trace_id = _inherited_trace_id() or new_trace_id()
        self.trace_id = trace_id
        self.name = name
        self.span_id = f"s{next(_SPAN_IDS):x}"
        self.attrs = attrs
        self.dur_us = self.cpu_us = None
        self.t0_us = _perf_ns() // 1000
        self._cpu0_ns = _thread_ns() if cpu else None

    def __enter__(self):
        self._token = _CURRENT.set(self)
        # a ``jax.profiler.TraceAnnotation`` held for the span's life, once
        # ``jax`` is imported (telemetry never imports it: lightweight
        # processes stay off it; None too while ``import jax`` is still
        # running). No profile running, it is an inactive TraceMe.
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            self._annotation = None
        else:
            self._annotation = profiler.TraceAnnotation(
                self.name, trace_id=self.trace_id, span_id=self.span_id)
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        _CURRENT.reset(self._token)
        self._annotation = self._token = None
        self.dur_us = dur_us = _perf_ns() // 1000 - self.t0_us
        if self._cpu0_ns is not None:
            self.cpu_us = (_thread_ns() - self._cpu0_ns) // 1000
        observe = _OBSERVE_DURATION.get(self.name)
        if observe is None:
            observe = _OBSERVE_DURATION[self.name] = \
                _SPAN_DURATION.labels(self.name).observe
        observe(dur_us)
        _record_flight_span(self)
        if _SPOOL_DIR != "":         # a directory is set, or none was read yet
            _spool(self)
        profiler = sys.modules.get("mxnet_tpu.profiler")
        if profiler is not None and profiler._STATE["running"]:
            _emit_profiler(self, profiler)

    def __repr__(self):
        return (f"<Span {self.name} trace={self.trace_id} "
                f"dur={self.dur_us}us cpu={self.cpu_us}us "
                f"attrs={self.attrs}>")


span = Span


def current_span() -> Optional[Span]:
    return _CURRENT.get()


def current_trace_id() -> Optional[str]:
    s = _CURRENT.get()
    return s.trace_id if s is not None else None


def self_times(spans) -> Dict[str, int]:
    """``{span_id: self_us}`` for finished spans given as
    ``flight.recent_spans()`` entries (a bundle's ``spans`` list is the
    same): each span's duration minus the part of its interval that its
    child spans cover. A child whose parent is not among ``spans`` only
    counts for itself."""
    children: Dict[str, List] = {}
    for e in spans:
        if e["parent_id"] is not None:
            children.setdefault(e["parent_id"], []).append(e)
    out = {}
    for e in spans:
        start, end = e["t0_us"], e["t0_us"] + e["dur_us"]
        covered, upto = 0, start
        for c in sorted(children.get(e["span_id"], ()),
                        key=lambda c: c["t0_us"]):
            c0 = max(c["t0_us"], upto)
            c1 = min(c["t0_us"] + c["dur_us"], end)
            if c1 > c0:
                covered += c1 - c0
                upto = c1
        out[e["span_id"]] = e["dur_us"] - covered
    return out


# -- per-pid span spool (the fleet plane's raw material) ----------------------
#
# Hot-path discipline: with no spool directory a span's exit tests one
# module global and does nothing else. With one, it pays one bounded-deque
# append; file I/O happens only on a flush (every MXNET_SPAN_SPOOL_FLUSH_N
# spans, or at exit). The directory and the cadence are read at the
# process's first span exit and again at every ``spool_flush()``.

_SPOOL_BUF: deque = deque(maxlen=2048)  # bounded: backlog drops oldest
_SPOOL_LOCK = threading.Lock()
_SPOOL_DIR: Optional[str] = None    # None: not read yet; "": no spool
_SPOOL_FLUSH_N = 32
# perf_counter -> wall-clock anchor: spans are timed on the monotonic clock
# (in-proc ordering), but cross-process assembly needs wall time
_WALL_ANCHOR_S = time.time() - time.perf_counter()

_SPOOL_SPANS = REGISTRY.counter(
    "mxtpu_span_spool_spans_total",
    "Spans spilled to the per-pid spool file under MXNET_SPAN_SPOOL_DIR.")
_SPOOL_ROTATIONS = REGISTRY.counter(
    "mxtpu_span_spool_rotations_total",
    "Spool-file rotations forced by the MXNET_SPAN_SPOOL_MAX_BYTES size cap.")


def _read_spool_knobs():
    global _SPOOL_DIR, _SPOOL_FLUSH_N
    _SPOOL_DIR = str(_cfg("MXNET_SPAN_SPOOL_DIR", "") or "")
    try:
        _SPOOL_FLUSH_N = max(1, int(_cfg("MXNET_SPAN_SPOOL_FLUSH_N", 32)))
    except Exception:
        pass


def _spool(s: Span):
    """Buffer a finished span for the spool file; the first call of the
    process reads whether there is one."""
    if _SPOOL_DIR is None:
        _read_spool_knobs()
        if not _SPOOL_DIR:
            return
    _SPOOL_BUF.append(s)
    if len(_SPOOL_BUF) >= _SPOOL_FLUSH_N:
        spool_flush()


def spool_path(d: Optional[str] = None) -> str:
    """This process's spool file ('' when no spool directory is set)."""
    d = d if d is not None else str(_cfg("MXNET_SPAN_SPOOL_DIR", "") or "")
    return os.path.join(d, f"spool-{os.getpid()}.jsonl") if d else ""


def _spool_line(s: Span) -> Dict:
    return {
        "pid": os.getpid(),
        "name": s.name,
        "trace_id": s.trace_id,
        "span_id": s.span_id,
        "parent_id": s.parent_id,
        "t0_wall": round(_WALL_ANCHOR_S + s.t0_us / 1e6, 6),
        "dur_us": s.dur_us,
        "cpu_us": s.cpu_us,
        "attrs": _clean_attrs(s.attrs) if s.attrs else {},
    }


def spool_flush():
    """Read the spool's knobs anew and drain the buffered spans into
    ``spool-<pid>.jsonl`` (one ``O_APPEND`` write for the whole batch;
    atomic line appends even with several processes sharing the directory).
    Rotates the file to ``.1`` when it would exceed
    ``MXNET_SPAN_SPOOL_MAX_BYTES``. Never raises — a broken disk must not
    take down the span it is trying to record."""
    _read_spool_knobs()
    with _SPOOL_LOCK:
        if not _SPOOL_BUF:
            return
        batch = list(_SPOOL_BUF)
        _SPOOL_BUF.clear()
        path = spool_path(_SPOOL_DIR)
        if not path:
            return
        try:
            lines = [json.dumps(_spool_line(s), sort_keys=True) + "\n"
                     for s in batch if s.dur_us is not None]
            if not lines:
                return
            data = "".join(lines).encode("utf-8")
            os.makedirs(os.path.dirname(path), exist_ok=True)
            cap = int(_cfg("MXNET_SPAN_SPOOL_MAX_BYTES", 8 << 20))
            try:
                if cap > 0 and os.path.getsize(path) + len(data) > cap:
                    os.replace(path, path + ".1")
                    _SPOOL_ROTATIONS.inc()
            except OSError:
                pass
            fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
            try:
                os.write(fd, data)
            finally:
                os.close(fd)
            _SPOOL_SPANS.inc(len(lines))
        except Exception:
            pass


# short-lived children (loadgen restart phases, chaos subprocesses) must
# spill their tail before exiting, or the journey loses its last hop
atexit.register(spool_flush)


def read_spool(d: Optional[str] = None) -> List[Dict]:
    """Every span line in the spool directory — all processes, rotated
    ``.1`` files included — as dicts (file order within a file)."""
    d = d if d is not None else str(_cfg("MXNET_SPAN_SPOOL_DIR", "") or "")
    out: List[Dict] = []
    if not d or not os.path.isdir(d):
        return out
    for n in sorted(os.listdir(d)):
        if not (n.startswith("spool-") and
                (n.endswith(".jsonl") or n.endswith(".jsonl.1"))):
            continue
        try:
            with open(os.path.join(d, n)) as f:
                for line in f:
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        continue
        except OSError:
            continue
    return out


def journey(trace_id: str, d: Optional[str] = None) -> List[Dict]:
    """One ordered cross-process timeline for ``trace_id``: every spooled
    span carrying that id, across every process's spool file, sorted by
    wall-clock start. The raw material of ``tools/trace_journey.py``."""
    hops = [e for e in read_spool(d) if e.get("trace_id") == trace_id]
    hops.sort(key=lambda e: (e.get("t0_wall", 0.0), e.get("dur_us") or 0))
    return hops


def _reset_spool_for_tests():
    """Forget buffered spans, the spool directory and the cached inherited
    trace id: the next span reads them anew (tests that flip MXNET_TRACE_ID
    / spool knobs mid-process)."""
    global _INHERITED_RESOLVED, _INHERITED_TRACE, _SPOOL_DIR
    with _SPOOL_LOCK:
        _SPOOL_BUF.clear()
    _SPOOL_DIR = None
    _INHERITED_RESOLVED = False
    _INHERITED_TRACE = None


def _emit_profiler(s: Span, prof):
    """Mirror a finished span into the chrome trace of ``prof``, the
    ``mxnet_tpu.profiler`` module, whose session is running (a span's exit
    looks the module up in ``sys.modules``: telemetry never forces the
    profiler onto the import path of lightweight processes)."""
    args = {"trace_id": s.trace_id, "span_id": s.span_id}
    if s.parent_id:
        args["parent_id"] = s.parent_id
    for k, v in s.attrs.items():
        if isinstance(v, (str, int, float, bool)) or v is None:
            args[k] = v
    prof._record(s.name, "span", s.t0_us, s.dur_us, args=args)
