"""Persistent executable cache: compiled XLA programs, content-addressed.

The compile ledger (PR 10) already fingerprints every lowered program —
a sha256 of canonicalized StableHLO that is stable across processes and
machines. This module turns that fingerprint into a *cache key*: compiled
executables are serialized via ``jax.experimental.serialize_executable``
and stored under ``MXNET_EXEC_CACHE_DIR`` so the next process that lowers
the same program deserializes it instead of paying XLA again. Integration
happens once, inside ``compile_ledger.lower_and_compile()`` — every AOT
compile site (serving buckets, decode prefill/step pairs, the train-step
autoformat path, the opt-in eager ledger) hits the cache transparently.

Correctness before speed:

  * the key covers everything that could make a cached executable wrong on
    this process: the StableHLO fingerprint, backend platform + device kind
    + device count, the donation layout of the lowering, the caller's
    trigger key (endpoint/bucket/mesh/dtype), and the jax / jaxlib /
    backend runtime versions. Any mismatch is simply a different key — a
    miss, never a wrong load;
  * entries are two files, payload (``ent-<key>.bin``) and manifest
    (``ent-<key>.json``), each written tmp + fsync + rename so a reader
    only ever sees complete files; concurrent writers of one key each
    write until the payload on disk is the one its manifest names (two
    serializations of one executable differ in their bytes);
  * the manifest carries the payload's sha256; :func:`load` verifies it
    before unpickling, so a truncated or bit-flipped payload is detected,
    warned about, deleted, and answered with a miss — the caller falls
    back to a live compile. **Nothing in this module raises on the serving
    path**: every failure mode degrades to "compile it yourself";
  * the store is LRU byte-bounded (``MXNET_EXEC_CACHE_MAX_BYTES``):
    payload mtimes are the recency order, touched on every hit, and
    :func:`store` evicts oldest-first until the directory fits.

The ``exec_cache`` fault site lets chaos drills poison an entry on disk
(kind ``cache_poison``): the injected fault is *consumed* here and turned
into real on-disk corruption, so the genuine digest-verify path — not a
shortcut — proves the fallback.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..telemetry.metrics import REGISTRY

__all__ = ["enabled", "cache_dir", "max_bytes", "build_key", "key_digest",
           "load", "store", "stats", "entries", "clear", "reset_stats"]

log = logging.getLogger("mxnet_tpu.cache")

_HITS = REGISTRY.counter(
    "mxtpu_exec_cache_hits_total",
    "Executable-cache hits: compiles answered by deserializing a stored "
    "executable instead of running XLA.")
_MISSES = REGISTRY.counter(
    "mxtpu_exec_cache_misses_total",
    "Executable-cache misses, by reason: absent (never stored) / corrupt "
    "(payload digest mismatch — entry deleted) / key_mismatch (manifest "
    "disagrees with the requested key) / error (load machinery failed).",
    labelnames=("reason",))
_EVICTIONS = REGISTRY.counter(
    "mxtpu_exec_cache_evictions_total",
    "Entries evicted to keep the store under MXNET_EXEC_CACHE_MAX_BYTES "
    "(least-recently-used payload mtime first).")
_BYTES = REGISTRY.gauge(
    "mxtpu_exec_cache_bytes",
    "Total payload bytes currently in the on-disk executable cache "
    "(refreshed on every store/evict/load of this process).")
_DESER_S = REGISTRY.counter(
    "mxtpu_exec_cache_deserialize_seconds_total",
    "Wall seconds spent deserializing cached executables — the price of a "
    "hit (compare mxtpu_compile_wall_seconds_total, the price of a miss).")

_LOCK = threading.Lock()
# process-local stats for /compilez and tests (mirror of the counters)
_STATS = {"hits": 0, "misses": 0, "evictions": 0, "stores": 0,
          "deserialize_s": 0.0}


def _cfg(name, default):
    try:
        from .. import config
        return config.get(name, default)
    except Exception as e:      # fail-open: a broken config never blocks serving
        log.debug("config read %s failed: %s", name, e)
        return default


def cache_dir() -> str:
    """The store directory ('' = cache disabled), read live."""
    return str(_cfg("MXNET_EXEC_CACHE_DIR", "") or "")


def max_bytes() -> int:
    """LRU byte budget (0 = unbounded)."""
    try:
        return int(_cfg("MXNET_EXEC_CACHE_MAX_BYTES", 1 << 30))
    except (TypeError, ValueError):
        return 1 << 30


def enabled() -> bool:
    return bool(cache_dir())


# ---------------------------------------------------------------------------
# key construction
# ---------------------------------------------------------------------------

def _runtime_versions() -> Dict[str, str]:
    out: Dict[str, str] = {}
    try:
        import jax
        out["jax"] = str(getattr(jax, "__version__", "?"))
        import jaxlib
        out["jaxlib"] = str(getattr(jaxlib, "__version__", "?"))
    except Exception as e:      # unknown version still forms a valid key
        log.debug("runtime version probe failed: %s", e)
        out.setdefault("jax", "?")
    return out


def _device_identity() -> Dict[str, Any]:
    """Backend platform, device kind and count — a payload serialized for
    one topology must never load on another."""
    out: Dict[str, Any] = {}
    try:
        import jax
        devs = jax.devices()
        out["platform"] = str(devs[0].platform) if devs else "?"
        out["device_kind"] = str(devs[0].device_kind) if devs else "?"
        out["device_count"] = len(devs)
        from jax.extend import backend as _backend
        out["platform_version"] = str(_backend.get_backend().platform_version)
    except Exception as e:      # no backend yet: '?' keys still partition safely
        log.debug("device identity probe failed: %s", e)
        out["platform"] = "?"
    return out


def build_key(fingerprint: str, lowered=None,
              extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Assemble the full cache key for one lowered program.

    ``fingerprint`` is the canonicalized-StableHLO sha256 (the content
    address), ``lowered`` contributes the donation layout, ``extra`` is the
    compile site's trigger key (endpoint/bucket/mesh/dtype) — anything the
    fingerprint might not capture about how the executable will be driven.
    """
    key: Dict[str, Any] = {"fingerprint": str(fingerprint)}
    key.update(_device_identity())
    key["versions"] = _runtime_versions()
    if lowered is not None:
        try:
            key["donate_argnums"] = sorted(
                int(i) for i in getattr(lowered, "donate_argnums", ()) or ())
        except Exception as e:  # unknown layout -> conservative empty slot
            log.debug("donation layout probe failed: %s", e)
            key["donate_argnums"] = []
    if extra:
        key["extra"] = {str(k): str(v) for k, v in sorted(extra.items())}
    return key


def key_digest(key: Dict[str, Any]) -> str:
    """sha256 over the canonical JSON of the key — the entry's file name."""
    canon = json.dumps(key, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode("utf-8")).hexdigest()


def _paths(d: str, digest: str) -> Tuple[str, str]:
    return (os.path.join(d, f"ent-{digest}.bin"),
            os.path.join(d, f"ent-{digest}.json"))


# ---------------------------------------------------------------------------
# store / load
# ---------------------------------------------------------------------------

def _atomic_write(path: str, data: bytes):
    """tmp + fsync + rename in the destination directory: a reader sees the
    old entry, no entry, or the complete new one — never a torn write."""
    d = os.path.dirname(path)
    fd, tmp = tempfile.mkstemp(prefix=".tmp-", dir=d)
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _pair_agrees(bin_path: str, man_path: str) -> bool:
    """Does the payload on disk carry the digest its manifest names?"""
    try:
        with open(man_path, "rb") as f:
            want = json.loads(f.read().decode("utf-8")).get("payload_sha256")
        with open(bin_path, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest() == want
    except (OSError, ValueError):
        return False


def _total_bytes(d: str) -> int:
    total = 0
    try:
        for n in os.listdir(d):
            if n.startswith("ent-") and n.endswith(".bin"):
                try:
                    total += os.stat(os.path.join(d, n)).st_size
                except OSError:
                    pass
    except OSError:
        pass
    return total


def _drop_entry(d: str, digest: str):
    for p in _paths(d, digest):
        try:
            os.unlink(p)
        except OSError:
            pass


def _evict(d: str, budget: int) -> int:
    """Delete least-recently-used entries until the store fits ``budget``
    payload bytes; returns how many entries went."""
    if budget <= 0:
        return 0
    ents: List[Tuple[float, int, str]] = []   # (mtime, size, digest)
    try:
        for n in os.listdir(d):
            if not (n.startswith("ent-") and n.endswith(".bin")):
                continue
            try:
                st = os.stat(os.path.join(d, n))
            except OSError:
                continue
            ents.append((st.st_mtime, st.st_size, n[4:-4]))
    except OSError:
        return 0
    total = sum(sz for _, sz, _ in ents)
    if total <= budget:
        return 0
    evicted = 0
    for _, sz, digest in sorted(ents):
        if total <= budget:
            break
        _drop_entry(d, digest)
        total -= sz
        evicted += 1
    if evicted:
        _EVICTIONS.inc(evicted)
        with _LOCK:
            _STATS["evictions"] += evicted
    return evicted


def store(key: Dict[str, Any], compiled) -> bool:
    """Serialize ``compiled`` under ``key``. Best-effort: returns False (and
    stays silent beyond a debug log) on any failure — a full disk must not
    fail the compile that just succeeded."""
    d = cache_dir()
    if not d:
        return False
    try:
        from jax.experimental import serialize_executable as _jse
        payload, in_tree, out_tree = _jse.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree),
                            protocol=pickle.HIGHEST_PROTOCOL)
        digest = key_digest(key)
        os.makedirs(d, exist_ok=True)
        bin_path, man_path = _paths(d, digest)
        # the serialized executable and its shardings name their devices by
        # id; load() hands exactly these back as the execution devices
        manifest = {"key": key, "payload_sha256":
                    hashlib.sha256(blob).hexdigest(),
                    "payload_bytes": len(blob), "created": time.time(),
                    "device_ids": [int(dv.id) for dv in
                                   compiled.runtime_executable()
                                   .local_devices()]}
        man_blob = (json.dumps(manifest, sort_keys=True) + "\n").encode("utf-8")
        # two serializations of one executable are not the same bytes, so
        # writers racing on one key can leave one's payload under the
        # other's manifest. Write again until the pair on disk agrees
        # (anybody's pair will do: the key says they are interchangeable)
        for _ in range(16):
            _atomic_write(bin_path, blob)
            _atomic_write(man_path, man_blob)
            if _pair_agrees(bin_path, man_path):
                break
        _evict(d, max_bytes())
        _BYTES.set(_total_bytes(d))
        with _LOCK:
            _STATS["stores"] += 1
        return True
    except Exception as e:
        log.debug("executable cache store failed: %s", e)
        return False


def _miss(reason: str) -> None:
    _MISSES.labels(reason).inc()
    with _LOCK:
        _STATS["misses"] += 1
    return None


def load(key: Dict[str, Any]):
    """Deserialize the executable stored under ``key``, or None (a miss).

    Verifies the manifest digest against the payload bytes before
    unpickling; corrupt or mismatched entries are deleted and answered
    with a miss plus a warning — the caller recompiles, clients never see
    an error. Never raises.
    """
    d = cache_dir()
    if not d:
        return None
    digest = key_digest(key)
    bin_path, man_path = _paths(d, digest)
    _consume_poison_fault(bin_path)
    try:
        try:
            with open(man_path, "rb") as f:
                manifest = json.loads(f.read().decode("utf-8"))
        except (OSError, ValueError):
            return _miss("absent")
        if manifest.get("key") != key:
            # a digest collision or a hand-edited manifest: refuse it
            return _miss("key_mismatch")
        try:
            with open(bin_path, "rb") as f:
                blob = f.read()
        except OSError:
            return _miss("absent")
        if hashlib.sha256(blob).hexdigest() != manifest.get("payload_sha256"):
            log.warning("executable cache entry %s corrupt (payload digest "
                        "mismatch); deleting and recompiling", digest[:12])
            _drop_entry(d, digest)
            _BYTES.set(_total_bytes(d))
            return _miss("corrupt")
        import jax
        from jax.experimental import serialize_executable as _jse
        t0 = time.perf_counter()
        payload, in_tree, out_tree = pickle.loads(blob)
        # execution_devices=None would mean EVERY visible device: a
        # one-device or one-slice executable then refuses its arguments on
        # any host that has more
        by_id = {dv.id: dv for dv in jax.devices()}
        compiled = _jse.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in manifest["device_ids"]])
        dt = time.perf_counter() - t0
        _DESER_S.inc(dt)
        try:
            os.utime(bin_path)          # LRU touch
        except OSError:
            pass
        _HITS.inc()
        _BYTES.set(_total_bytes(d))
        with _LOCK:
            _STATS["hits"] += 1
            _STATS["deserialize_s"] += dt
        return compiled
    except Exception as e:
        # an undeserializable (stale-format, cross-runtime) payload is a
        # miss, not an error surface: drop it so the recompile re-stores
        log.warning("executable cache load of %s failed (%s); recompiling",
                    digest[:12], e)
        _drop_entry(d, digest)
        return _miss("error")


def _consume_poison_fault(bin_path: str):
    """Fault hook: a ``cache_poison`` injection at the ``exec_cache`` site
    is consumed here and converted into real on-disk corruption (payload
    truncated to half), so the genuine sha256-verify fallback path — not a
    simulated one — is what the chaos drill exercises."""
    try:
        from ..resilience import faults as _faults
    except Exception as e:      # no resilience layer -> no faults to consume
        log.debug("faults import failed: %s", e)
        return
    try:
        _faults.check("exec_cache")
    except Exception as e:
        if getattr(e, "kind", None) != "cache_poison":
            raise
        try:
            size = os.path.getsize(bin_path)
            with open(bin_path, "r+b") as f:
                f.truncate(max(size // 2, 1))
        except OSError:
            pass


# ---------------------------------------------------------------------------
# introspection
# ---------------------------------------------------------------------------

def entries() -> List[Dict[str, Any]]:
    """Manifests of every entry currently in the store (oldest first)."""
    d = cache_dir()
    out: List[Dict[str, Any]] = []
    if not d or not os.path.isdir(d):
        return out
    for n in sorted(os.listdir(d)):
        if not (n.startswith("ent-") and n.endswith(".json")):
            continue
        try:
            with open(os.path.join(d, n), "rb") as f:
                man = json.loads(f.read().decode("utf-8"))
        except (OSError, ValueError):
            continue
        man["digest"] = n[4:-5]
        try:
            man["mtime"] = os.stat(
                os.path.join(d, f"ent-{man['digest']}.bin")).st_mtime
        except OSError:
            man["mtime"] = 0.0
        out.append(man)
    out.sort(key=lambda m: m["mtime"])
    return out


def stats() -> Dict[str, Any]:
    """Process-local cache activity plus the store's current size."""
    with _LOCK:
        snap = dict(_STATS)
    d = cache_dir()
    snap["enabled"] = bool(d)
    snap["dir"] = d
    snap["bytes"] = _total_bytes(d) if d else 0
    snap["deserialize_s"] = round(snap["deserialize_s"], 6)
    total = snap["hits"] + snap["misses"]
    snap["hit_rate"] = round(snap["hits"] / total, 4) if total else None
    return snap


def clear():
    """Delete every entry in the store (tests / operator reset)."""
    d = cache_dir()
    if not d or not os.path.isdir(d):
        return
    for n in os.listdir(d):
        if n.startswith("ent-") and (n.endswith(".bin")
                                     or n.endswith(".json")):
            try:
                os.unlink(os.path.join(d, n))
            except OSError:
                pass
    _BYTES.set(0)


def reset_stats():
    """Zero the process-local stat mirror (tests)."""
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0.0 if k == "deserialize_s" else 0
