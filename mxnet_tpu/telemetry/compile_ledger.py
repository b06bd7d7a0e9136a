"""Compile ledger — content-addressed observability for every XLA compile.

Every AOT compile in this stack (serving bucket executables, the
ParallelTrainStep autoformat path, the eager jit cache) emits one
:class:`CompileRecord`: a sha256 fingerprint of the lowered StableHLO text
(the content address ROADMAP item 2's persistent executable cache will key
on), lowering + compile wall time, the backend's ``cost_analysis()`` flops /
bytes and ``memory_analysis()`` argument/output/temp/code bytes where
available, and the trigger key (endpoint/bucket/mesh/dtype/op) that explains
*why* the compile happened.

Records land in three places:

  - a bounded in-memory ring (``recent()``) — the flight recorder snapshots
    it into every bundle, and the ``/compilez`` debug page renders it live;
  - the shared metrics registry (``mxtpu_compile_*`` families);
  - when ``MXNET_COMPILE_LEDGER_DIR`` is set, an append-only JSONL file per
    process (single ``O_APPEND`` write per record: atomic line appends even
    with several processes sharing the directory).

Duplicate detection is the point: a fingerprint seen before — in this
process, or by any process that wrote into the ledger directory — means the
wall time of the new compile was *re-spent* on a program the fleet already
owned. That waste is quantified in
``mxtpu_compile_duplicate_waste_seconds_total`` and is exactly the win a
persistent executable cache would bank.

Fingerprints are canonicalized (MLIR location metadata stripped) so the same
function lowered at the same avals in two different processes hashes
identically — the property the cross-subprocess stability test pins. The
canonicalizer itself lives in :mod:`mxnet_tpu.analysis.ir.parser` now
(shared with hlolint, hardened for nested ``loc(...)`` and string attrs);
this module delegates.

Two growths ride the same seam (hlolint, see STATIC_ANALYSIS.md):

  - when a ledger directory is set, the canonicalized module *text* is
    retained beside the records as ``module-<fingerprint>.mlir`` (deduped
    by content address, byte-bounded by
    MXNET_COMPILE_LEDGER_TEXT_MAX_BYTES, atomic tmp+rename writes) so
    ``mxlint --ir`` runs offline against the very programs the fleet
    compiled;
  - an opt-in live guard (MXNET_IR_GUARD=warn|raise) checks each compile
    against the guarded IR rules — donation silently dropped by XLA
    (IR1000), weights baked in as constants (IR1001) — emitting
    ``mxtpu_ir_guard_total`` and an ``ir_guard`` flight event. Fail-open:
    guard *infrastructure* errors never fail the compile; only an actual
    finding under ``raise`` does.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
import warnings
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from .metrics import REGISTRY
from ..analysis.ir.guard import IRGuardError, live_findings as _ir_findings
from ..analysis.ir import parser as _irparser

__all__ = ["CompileRecord", "IRGuardError", "fingerprint_text",
           "lower_and_compile", "record", "recent",
           "summary", "instrument_eager_jit", "eager_active", "ledger_dir",
           "read_ledger", "reset"]

_RECORDS = REGISTRY.counter(
    "mxtpu_compile_records_total",
    "CompileRecords emitted, by compile site (serving_bucket / train_step / "
    "eager_jit).",
    labelnames=("site",))
_WALL = REGISTRY.counter(
    "mxtpu_compile_wall_seconds_total",
    "Wall seconds spent in XLA lowering/compilation, by site and phase "
    "(lower / compile).",
    labelnames=("site", "phase"))
_DUPS = REGISTRY.counter(
    "mxtpu_compile_duplicates_total",
    "Compiles whose StableHLO fingerprint was already in the ledger — a "
    "program the fleet had already paid to compile.",
    labelnames=("site",))
_DUP_WASTE = REGISTRY.counter(
    "mxtpu_compile_duplicate_waste_seconds_total",
    "Wall seconds re-spent compiling already-seen programs (the win a "
    "persistent executable cache keyed by StableHLO hash would bank).")
_IR_GUARD = REGISTRY.counter(
    "mxtpu_ir_guard_total",
    "Live IR-guard verdicts per compile, by rule (IR1000 donation-dropped, "
    "IR1001 baked-in-weights) and outcome (detected = guard off but the "
    "violation was seen / warn / raise).",
    labelnames=("rule", "outcome"))
_TEXT_RETAINED = REGISTRY.counter(
    "mxtpu_compile_text_retained_total",
    "Canonicalized StableHLO texts retained beside the ledger, by outcome "
    "(written / dedup = content address already on disk / over_budget = "
    "MXNET_COMPILE_LEDGER_TEXT_MAX_BYTES reached / error).",
    labelnames=("outcome",))

# ring larger than any MXNET_COMPILE_LEDGER_KEEP a page would ask for
_RING_CAP = 512

_LOCK = threading.Lock()
_RING: deque = deque(maxlen=_RING_CAP)
_SEEN: Dict[str, float] = {}        # fingerprint -> first-seen compile secs
_SCANNED: Dict[str, int] = {}       # ledger file path -> bytes consumed
_SCANNED_DIR: Optional[str] = None  # ledger dir the offsets belong to
_LAST_ERRORS: Dict[str, str] = {}   # where -> last swallowed error


def _note(where: str, exc: BaseException):
    """Instrumentation must never fail the compile it observes — errors are
    swallowed, but the last one per site stays inspectable here (an empty
    ledger with a populated _LAST_ERRORS is a bug report)."""
    _LAST_ERRORS[where] = f"{type(exc).__name__}: {exc}"


def _cfg(name, default):
    try:
        from .. import config
        return config.get(name, default)
    except Exception as e:
        _note("cfg", e)
        return default


def ledger_dir() -> str:
    """The JSONL ledger directory ('' = in-memory only), read live."""
    return str(_cfg("MXNET_COMPILE_LEDGER_DIR", "") or "")


def eager_active() -> bool:
    """Whether the eager jit cache should emit ledger records. 'auto' (the
    default) follows the ledger directory: instrumenting the eager path AOT
    compiles per aval signature, which is only worth doing when someone is
    collecting the records."""
    mode = str(_cfg("MXNET_COMPILE_LEDGER_EAGER", "auto")).lower()
    if mode in ("1", "true", "yes", "on"):
        return True
    if mode in ("0", "false", "no", "off"):
        return False
    return bool(ledger_dir())


class CompileRecord(dict):
    """One compile, as a plain JSON-able dict (subclass only for the name)."""
    __slots__ = ()


def fingerprint_text(text: str) -> str:
    """sha256 of canonicalized StableHLO text. MLIR location metadata
    (``loc(...)`` / ``#loc`` lines) is stripped so the hash depends on the
    program alone, not on where in the host source it was traced from —
    two processes lowering the same function at the same avals agree.
    Delegates to the shared hardened canonicalizer (balanced parens,
    string-attr aware — see :mod:`mxnet_tpu.analysis.ir.parser`); for
    location-free text the result is byte-identical to the original
    regex pass, so existing content addresses stay valid."""
    return _irparser.fingerprint(text)


def _cost_analysis(compiled) -> Dict[str, float]:
    """flops / bytes accessed from ``compiled.cost_analysis()``; {} when the
    backend doesn't provide it (CPU often reports partial numbers)."""
    out: Dict[str, float] = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if not isinstance(cost, dict):
            return out
        for src, dst in (("flops", "flops"),
                         ("bytes accessed", "bytes_accessed")):
            v = cost.get(src)
            if v is not None:
                out[dst] = float(v)
    except Exception as e:
        _note("cost_analysis", e)
    return out


def _memory_analysis(compiled) -> Dict[str, int]:
    """argument/output/temp/generated-code bytes from
    ``compiled.memory_analysis()`` where the backend provides them."""
    out: Dict[str, int] = {}
    try:
        mem = compiled.memory_analysis()
        if mem is None:
            return out
        for attr, dst in (("argument_size_in_bytes", "argument_bytes"),
                          ("output_size_in_bytes", "output_bytes"),
                          ("temp_size_in_bytes", "temp_bytes"),
                          ("generated_code_size_in_bytes", "code_bytes")):
            v = getattr(mem, attr, None)
            if v is not None:
                out[dst] = int(v)
    except Exception as e:
        _note("memory_analysis", e)
    return out


def _rescan_seen(d: str):  # mxlint: disable=CONC200
    """Fold fingerprints written into ``d`` by ANY process into ``_SEEN``
    (caller holds ``_LOCK``). Incremental: each ledger file is consumed from
    the byte offset the previous scan reached, so calling this on every
    fingerprint miss stays O(new bytes) — sibling processes that wrote
    *after* our first scan are still seen before a compile is (mis)judged
    fresh. Only complete lines are consumed; a line still being appended is
    left for the next scan."""
    global _SCANNED_DIR
    if _SCANNED_DIR != d:
        _SCANNED_DIR = d
        _SCANNED.clear()
    try:
        names = [n for n in os.listdir(d)
                 if n.startswith("ledger-") and n.endswith(".jsonl")]
    except OSError:
        return
    for n in names:
        path = os.path.join(d, n)
        off = _SCANNED.get(path, 0)
        try:
            with open(path, "rb") as f:
                f.seek(off)
                chunk = f.read()
        except OSError:
            continue
        nl = chunk.rfind(b"\n")
        if nl < 0:
            continue
        _SCANNED[path] = off + nl + 1
        for line in chunk[:nl + 1].splitlines():
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            fp = rec.get("fingerprint")
            if fp and fp not in _SEEN:
                _SEEN[fp] = float(rec.get("compile_s", 0.0) or 0.0)


def _append_jsonl(d: str, rec: Dict):
    """One O_APPEND write of one line: atomic for the short records we write
    even when multiple processes share the ledger directory."""
    try:
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"ledger-{os.getpid()}.jsonl")
        data = (json.dumps(rec, sort_keys=True) + "\n").encode("utf-8")
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        try:
            os.write(fd, data)
        finally:
            os.close(fd)
    except OSError:
        pass          # a broken disk must not take down the compile it logs


def _retain_text(d: str, fp: str, text: str):
    """Retain the canonicalized module text as ``module-<fp>.mlir`` beside
    the ledger records. Content-addressed, so dedup is a stat; the file
    re-hashes to its own name (``fingerprint_text(contents) == fp``), which
    is the integrity invariant hlolint's IR000 audits. Byte-bounded by
    MXNET_COMPILE_LEDGER_TEXT_MAX_BYTES over the directory's retained
    texts, and written tmp+rename (no O_APPEND: unlike the record stream
    this is a whole file, and a torn module text would fail its own
    content address)."""
    canon = _irparser.canonicalize(text)
    path = os.path.join(d, f"module-{fp}.mlir")
    if os.path.exists(path):
        _TEXT_RETAINED.labels("dedup").inc()
        return
    data = canon.encode("utf-8")
    budget = int(_cfg("MXNET_COMPILE_LEDGER_TEXT_MAX_BYTES", 32 << 20))
    if budget >= 0:
        used = 0
        for n in os.listdir(d):
            if n.startswith("module-") and n.endswith(".mlir"):
                try:
                    used += os.path.getsize(os.path.join(d, n))
                except OSError:
                    continue
        if used + len(data) > budget:
            _TEXT_RETAINED.labels("over_budget").inc()
            return
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _TEXT_RETAINED.labels("written").inc()


def _donation_summary(compiled, text: Optional[str],
                      expect_donation: bool) -> Optional[Dict[str, int]]:
    """``{"requested": n, "aliased": m}`` for a just-compiled executable,
    or None when nothing was donated. ``requested`` comes from the
    executable's own ``donate_argnums`` (present whether or not XLA kept
    the aliases), with the caller's ``expect_donation`` declaration as the
    floor — a site that *intends* donation but compiled a function with no
    donate_argnums is exactly the regression the guard exists to catch.
    ``aliased`` counts entry arguments whose alias survived into the text
    (``tf.aliasing_output`` / ``jax.buffer_donor``); omitted when the text
    was unavailable so IR1000 never fires on missing evidence."""
    requested = 0
    try:
        donated = getattr(compiled, "donate_argnums", None) or ()
        requested = len(tuple(donated))
    except Exception as e:
        _note("donation", e)
    if expect_donation and requested == 0:
        requested = 1
    if requested <= 0:
        return None
    out = {"requested": requested}
    if text is not None:
        out["aliased"] = _irparser.count_aliased_args(text)
    return out


def _guard_mode() -> str:
    mode = str(_cfg("MXNET_IR_GUARD", "") or "").strip().lower()
    return mode if mode in ("warn", "raise") else ""


def _run_ir_guard(site: str, key: Optional[Dict], text: Optional[str],
                  donation: Optional[Dict[str, int]]) -> List:
    """Evaluate the guarded IR rules against one fresh compile and emit
    metrics / flight event / warning. Returns the findings so the caller
    can raise *outside* this function — everything in here is fail-open
    (guard breakage must never fail a compile), but a real finding under
    MXNET_IR_GUARD=raise must."""
    mode = _guard_mode()
    # the donation assertion is metrics-free of cost (the summary already
    # exists for the record) so it runs even with the guard off — a
    # dropped donation always shows up in mxtpu_ir_guard_total
    donation_bad = bool(donation and donation.get("requested", 0) > 0
                        and donation.get("aliased", -1) == 0)
    if not mode and not donation_bad:
        return []
    findings = _ir_findings(text, site=site, donation=donation,
                            check_constants=bool(mode))
    if not findings:
        return []
    outcome = mode or "detected"
    for rule, message in findings:
        try:
            _IR_GUARD.labels(rule, outcome).inc()
        except Exception as e:
            _note("ir_guard_metric", e)
        warnings.warn(f"[{rule}] compile at site={site}: {message}",
                      RuntimeWarning, stacklevel=3)
    try:
        from . import flight as _flight
        _flight.trigger("ir_guard", site=site, outcome=outcome,
                        rules=",".join(sorted({r for r, _ in findings})),
                        key={str(k): v for k, v in (key or {}).items()})
    except Exception as e:
        _note("ir_guard_flight", e)
    return findings if mode == "raise" else []


def record(site: str, fingerprint: Optional[str], lower_s: float,
           compile_s: float, key: Optional[Dict[str, Any]] = None,
           compiled=None, cache_hit: bool = False,
           donation: Optional[Dict[str, int]] = None) -> CompileRecord:
    """Emit one CompileRecord (ring + metrics + JSONL). Never raises.

    ``cache_hit=True`` marks an executable answered by the persistent cache
    (``compile_s`` is then the deserialize time): such records are never
    duplicates and never charge ``mxtpu_compile_duplicate_waste_seconds_total``
    — nothing was re-spent, the fleet's copy was reused. ``donation`` is the
    optional ``{"requested": n, "aliased": m}`` summary: how many arguments the
    caller asked to donate vs how many aliases actually survived lowering —
    the durable evidence hlolint's IR1000 reads (the lowered text itself
    carries *no trace* of a dropped donation)."""
    rec = CompileRecord(
        ts=time.time(), pid=os.getpid(), site=str(site),
        fingerprint=fingerprint,
        lower_s=round(float(lower_s), 6), compile_s=round(float(compile_s), 6),
        key={str(k): v for k, v in (key or {}).items()},
        duplicate=False, cache_hit=bool(cache_hit),
    )
    if donation:
        rec["donation"] = {str(k): int(v) for k, v in donation.items()}
    if compiled is not None:
        rec.update(_cost_analysis(compiled))
        rec.update(_memory_analysis(compiled))
    d = ledger_dir()
    with _LOCK:
        if fingerprint is not None:
            if fingerprint not in _SEEN and d:
                # miss: re-scan sibling processes' ledger files before
                # judging this fingerprint fresh (they may have compiled
                # it after our last scan)
                _rescan_seen(d)
            if fingerprint in _SEEN:
                rec["duplicate"] = not rec["cache_hit"]
            else:
                _SEEN[fingerprint] = rec["lower_s"] + rec["compile_s"]
        _RING.append(rec)
    try:
        _RECORDS.labels(rec["site"]).inc()
        _WALL.labels(rec["site"], "lower").inc(rec["lower_s"])
        _WALL.labels(rec["site"], "compile").inc(rec["compile_s"])
        if rec["duplicate"]:
            _DUPS.labels(rec["site"]).inc()
            _DUP_WASTE.inc(rec["lower_s"] + rec["compile_s"])
    except Exception as e:
        _note("metrics", e)
    if d:
        _append_jsonl(d, rec)
    return rec


def lower_and_compile(jfn, args, *, site: str,
                      key: Optional[Dict[str, Any]] = None,
                      kwargs: Optional[Dict] = None,
                      expect_donation: bool = False):
    """The one-stop instrumentation for an AOT compile site: time
    ``jfn.lower(*args)``, fingerprint the lowered StableHLO, consult the
    persistent executable cache (``MXNET_EXEC_CACHE_DIR``), and only on a
    miss time ``.compile()`` and populate the cache. Emits the record
    (``cache_hit`` says which path ran) and returns the executable. Ledger
    and cache failures never fail the compile.

    ``expect_donation=True`` declares the site requested buffer donation
    (serving endpoints pass their platform decision): the record then
    carries the ``donation`` requested/aliased summary and the IR guard's
    donation assertion is armed. With MXNET_IR_GUARD=raise a guarded-rule
    violation raises :class:`IRGuardError` — the one deliberate exception
    to fail-open, and it fires only after the record, metrics, and flight
    event are already emitted, so the evidence outlives the refusal."""
    t0 = time.perf_counter()
    lowered = jfn.lower(*args, **(kwargs or {}))
    t1 = time.perf_counter()
    fp = None
    text = None
    try:
        text = lowered.as_text()
        fp = fingerprint_text(text)
    except Exception as e:
        _note("fingerprint", e)
    compiled = None
    ckey = None
    t2 = time.perf_counter()
    if fp is not None:
        try:
            from ..cache import executable_cache as _xcache
            if _xcache.enabled():
                ckey = _xcache.build_key(fp, lowered, extra=key)
                compiled = _xcache.load(ckey)
        except Exception as e:
            _note("exec_cache", e)
            ckey = None
    cache_hit = compiled is not None
    if compiled is None:
        compiled = lowered.compile()
    t3 = time.perf_counter()
    if not cache_hit and ckey is not None:
        try:
            from ..cache import executable_cache as _xcache
            _xcache.store(ckey, compiled)
        except Exception as e:
            _note("exec_cache_store", e)
    donation = None
    try:
        donation = _donation_summary(compiled, text, expect_donation)
    except Exception as e:
        _note("donation", e)
    try:
        record(site, fp, lower_s=t1 - t0, compile_s=t3 - t2, key=key,
               compiled=compiled, cache_hit=cache_hit, donation=donation)
    except Exception as e:
        _note("record", e)
    d = ledger_dir()
    if d and fp is not None and text is not None:
        try:
            os.makedirs(d, exist_ok=True)
            _retain_text(d, fp, text)
        except Exception as e:
            _note("retain_text", e)
    raising = []
    try:
        raising = _run_ir_guard(site, key, text, donation)
    except Exception as e:
        _note("ir_guard", e)
    if raising:
        raise IRGuardError(raising, site)
    return compiled


def instrument_eager_jit(jfn, op_name: str):
    """Wrap an eager ``jax.jit`` wrapper so each NEW aval signature compiles
    through the ledger (AOT) instead of lazily inside the jit call. Installed
    by ops/registry only when :func:`eager_active` — the default eager path
    is untouched, so the dispatch-latency gate never pays for bookkeeping it
    isn't using. Tracer inputs (op dispatched inside an outer trace) and
    non-array inputs fall through to the plain jit wrapper."""
    compiled: Dict[tuple, Any] = {}
    lock = threading.Lock()

    def wrapper(*args):
        import jax
        try:
            if any(isinstance(a, jax.core.Tracer) for a in args):
                return jfn(*args)
            sig = tuple((tuple(a.shape), str(a.dtype)) for a in args)
        except Exception:
            return jfn(*args)
        comp = compiled.get(sig)
        if comp is None:
            with lock:
                comp = compiled.get(sig)
                if comp is None:
                    comp = lower_and_compile(jfn, args, site="eager_jit",
                                             key={"op": op_name})
                    compiled[sig] = comp
        return comp(*args)

    wrapper._ledger_instrumented = True
    return wrapper


def recent(k: Optional[int] = None) -> List[Dict]:
    """The last ``k`` CompileRecords (default MXNET_COMPILE_LEDGER_KEEP),
    oldest first."""
    if k is None:
        k = int(_cfg("MXNET_COMPILE_LEDGER_KEEP", 64))
    with _LOCK:
        items = list(_RING)
    return [dict(r) for r in items[-max(0, k):]]


def summary() -> Dict[str, float]:
    """Process-lifetime totals over every record still in scope: compile
    counts, distinct programs, duplicate count and re-spent seconds."""
    with _LOCK:
        items = list(_RING)
    dups = [r for r in items if r.get("duplicate")]
    return {
        "compiles": len(items),
        "distinct_fingerprints": len({r["fingerprint"] for r in items
                                      if r.get("fingerprint")}),
        "duplicates": len(dups),
        "dup_waste_s": round(sum(r["lower_s"] + r["compile_s"]
                                 for r in dups), 6),
        "cache_hits": sum(1 for r in items if r.get("cache_hit")),
        "lower_s": round(sum(r["lower_s"] for r in items), 6),
        "compile_s": round(sum(r["compile_s"] for r in items), 6),
    }


def read_ledger(d: Optional[str] = None) -> List[Dict]:
    """Every record in the JSONL ledger directory (all processes), in file
    order. Used by tools/compile_report.py."""
    d = d or ledger_dir()
    out: List[Dict] = []
    if not d or not os.path.isdir(d):
        return out
    for n in sorted(os.listdir(d)):
        if not (n.startswith("ledger-") and n.endswith(".jsonl")):
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


def reset():
    """Forget ring + seen-set + scan offsets (tests; a changed ledger dir
    re-scans from the top)."""
    global _SCANNED_DIR
    with _LOCK:
        _RING.clear()
        _SEEN.clear()
        _SCANNED.clear()
        _SCANNED_DIR = None
