"""Environment flag registry (parity: the reference's MXNET_* env-var config
system — docs/faq/env_var.md over dmlc::GetEnv call sites in src/).

Typed, documented, centrally-registered flags: ``config.get("MXNET_...")``
reads the process environment with the registered default and type, and
``config.describe()`` lists every knob (the env_var.md analog). Subsystems
read through here so behavior-affecting env vars are discoverable instead of
scattered ad-hoc ``os.environ`` lookups.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict

from .base import MXNetError

__all__ = ["register", "get", "set", "describe", "list_flags"]

_REGISTRY: Dict[str, dict] = {}
_OVERRIDES: Dict[str, Any] = {}
_LOCK = threading.Lock()


def register(name, default, type_=None, doc=""):
    """Register a flag with its default, type and documentation."""
    if type_ is None:
        type_ = type(default) if default is not None else str
    with _LOCK:
        _REGISTRY[name] = {"default": default, "type": type_, "doc": doc}
    return name


def _coerce(name, raw, type_):
    try:
        if type_ is bool:
            return str(raw).lower() in ("1", "true", "yes", "on")
        return type_(raw)
    except (TypeError, ValueError) as e:
        raise MXNetError(f"{name}={raw!r}: expected {type_.__name__}") from e


def get(name, default=None):
    """Read a flag: set() override > process env > registered default."""
    spec = _REGISTRY.get(name)
    if name in _OVERRIDES:
        return _OVERRIDES[name]
    raw = os.environ.get(name)
    if raw is None:
        if spec is not None:
            return spec["default"]
        return default
    return _coerce(name, raw, spec["type"] if spec else
                   (type(default) if default is not None else str))


def set(name, value):  # noqa: A001 — mirrors the reference's setter naming
    """Override a flag for this process (takes precedence over the env)."""
    _OVERRIDES[name] = value


def list_flags():
    return sorted(_REGISTRY)


def describe():
    """Human-readable flag table (env_var.md analog)."""
    lines = []
    for name in list_flags():
        spec = _REGISTRY[name]
        cur = get(name)
        lines.append(f"{name} (default {spec['default']!r}, "
                     f"current {cur!r}): {spec['doc']}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# flags consumed by this framework (kept to knobs that actually do something)
# ---------------------------------------------------------------------------
register("MXNET_ENGINE_TYPE", "ThreadedEngine", str,
         "Engine for host tasks: ThreadedEngine (native C++ pool) or "
         "NaiveEngine (synchronous Python fallback).")
register("MXNET_CPU_WORKER_NTHREADS", 4, int,
         "Worker threads of the host-task dependency engine.")
register("MXNET_CPU_PRIORITY_NTHREADS", 4, int,
         "Decode/augment threads of the native image pipeline default.")
register("MXNET_EXEC_BULK_EXEC_TRAIN", True, bool,
         "Accepted for parity; op bulking is subsumed by XLA fusion.")
register("MXNET_PROFILER_AUTOSTART", False, bool,
         "Start the profiler at import (profiler.cc autostart parity).")
register("MXNET_USE_SIGNAL_HANDLER", True, bool,
         "Install the crash backtrace logger (faulthandler; the "
         "initialize.cc SegfaultLogger analog).")
register("MXNET_SAFE_ACCUMULATION", True, bool,
         "Accumulate reductions over bf16/fp16 inputs in fp32.")
register("MXNET_PRNG_IMPL", "auto", str,
         "PRNG generator: threefry2x32 (alias: threefry) | rbg | unsafe_rbg "
         "| auto. auto = rbg on accelerators (hardware-friendly; +13% "
         "measured BERT pretraining throughput vs threefry dropout-bit "
         "generation), threefry on CPU (bit-reproducible test runs).")
register("MXNET_STORAGE_FALLBACK_LOG_VERBOSE", True, bool,
         "Log when a sparse op densifies an operand (executor fallback log).")
register("MXNET_HOME", os.path.join("~", ".mxnet"), str,
         "Root for datasets/model downloads.")
register("MXNET_P3_SLICE_SIZE", 1 << 20, int,
         "p3 kvstore: elements per wire slice (priority propagation).")
register("MXNET_TRAIN_REMAT", "none", str,
         "ParallelTrainStep rematerialization policy: none | conv (save only "
         "conv outputs, recompute BN/ReLU chains in backward) | full.")
register("MXNET_BN_ONEPASS", "auto", str,
         "BatchNorm: compute batch moments in ONE pass over the input "
         "(f32-accumulated E[x^2]-mu^2, clamped) instead of the two-pass "
         "mean-then-variance form — saves a full activation read per BN "
         "layer in forward. Default 'auto': one-pass only for sub-f32 "
         "inputs (bf16/f16, which cannot represent the |mean|/std ratios "
         "where E[x^2]-mu^2 catastrophically cancels); f32/f64 inputs use "
         "the two-pass reference form (ADVICE r5: one-pass at f32 with "
         "mean~300/std~0.01 clamps var to 0 and silently mis-scales). Set "
         "1/0 to force one-pass/two-pass for every dtype. The bf16 fast "
         "path (MXNET_BN_BF16_REDUCE) is inherently one-pass and ignores "
         "this flag; to get the two-pass f32 formulation on bf16 inputs, "
         "set MXNET_BN_BF16_REDUCE=0 AND this flag to 0.")
register("MXNET_BN_BF16_REDUCE", True, bool,
         "BatchNorm: when the input is bfloat16, keep every materialized "
         "tensor bf16 and apply the normalize with f32 scale/shift "
         "in-register (cuDNN fp16-AMP BatchNorm semantics: half tensors, "
         "float stats and f32 gradient accumulation; always one-pass "
         "moments). Measured 2204->2660 img/s on ResNet-50 b128 v5e. Set 0 "
         "to run bf16 inputs through the f32-promoted path (whose moment "
         "form MXNET_BN_ONEPASS then controls).")
register("MXNET_FLASH_BWD_BLOCK_Q", 0, int,
         "Flash-attention Pallas BACKWARD kernels: q-block size override "
         "(0 = inherit the forward's block_q). The backward tiles carry "
         "~3x the forward's VMEM working set, so its optimum differs. "
         "Consulted at kernel-build time and the built executable is "
         "cached per op/shape signature — set BEFORE the first backward "
         "at a given shape; later changes do not rebuild cached kernels "
         "(same trace-time semantics as MXNET_TRAIN_REMAT).")
register("MXNET_FLASH_BWD_BLOCK_K", 0, int,
         "Flash-attention Pallas backward: k-block size override "
         "(0 = inherit the forward's block_k). Trace-time semantics: see "
         "MXNET_FLASH_BWD_BLOCK_Q.")
register("MXNET_OPT_BF16_MOMENTS", False, bool,
         "Adam/AdamW: store the first/second moments in bfloat16 (EMA "
         "arithmetic still runs on in-register f32 upcasts). Halves the "
         "optimizer-state HBM traffic per step. Off by default: the second "
         "moment's tiny EMA increments ((1-beta2)*g^2) round away against a "
         "bf16-stored v once v is ~2^9 times larger, biasing v low on long "
         "horizons. Short-horizon convergence gate: tests/test_optimizer_ops"
         ".py::test_adam_bf16_moments_close_and_converges.")
register("MXNET_JIT_CACHE_SIZE", 4096, int,
         "Capacity (entries) of the eager per-(op, static-attrs) jit "
         "executable LRU cache (ops/registry.py). Each entry retains a "
         "jax.jit wrapper plus its compiled executables; bounding it keeps "
         "long-running eager workloads with per-iteration-varying attrs "
         "(slice bounds, pad widths, reshape targets) from growing host "
         "memory without bound. Eviction recompiles on next use.")
register("MXNET_KVSTORE_ASYNC_MAX_STALENESS", -1, int,
         "dist_async: max whole-model push rounds a worker may run ahead of "
         "the slowest (SSP bound); -1 = unbounded, the reference's pure "
         "async-apply behavior.")
register("MXNET_KVSTORE_HEARTBEAT_DIR", "", str,
         "Shared dir for worker heartbeat files (ps-lite heartbeat analog); "
         "empty disables failure detection.")
register("MXNET_KVSTORE_HEARTBEAT_INTERVAL", 5, int,
         "Seconds between heartbeat file touches.")
register("MXNET_TELEMETRY_DUMP_PATH", "", str,
         "When set, start a background telemetry reporter at import that "
         "writes the full metrics snapshot to this path every "
         "MXNET_TELEMETRY_DUMP_INTERVAL seconds (JSON; Prometheus text "
         "exposition if the path ends in .prom). tools/metrics_dump.py "
         "reads/watches the file while the run is live.")
register("MXNET_TELEMETRY_DUMP_INTERVAL", 10.0, float,
         "Seconds between background telemetry snapshot dumps/log lines.")
register("MXNET_CKPT_KEEP", 3, int,
         "CheckpointManager: newest checkpoints retained after each save "
         "(the corrupt-fallback chain depth); 0 disables rotation.")
register("MXNET_CKPT_ASYNC", False, bool,
         "CheckpointManager default: snapshot synchronously but write/fsync "
         "in a background thread, overlapping checkpoint IO with compute "
         "(wait() joins and surfaces write errors).")
register("MXNET_CKPT_WAIT_TIMEOUT_S", 120.0, float,
         "CheckpointManager.wait()/save() bound on joining an outstanding "
         "async checkpoint write; past it wait() raises instead of hanging "
         "shutdown behind a wedged writer (<= 0 = unbounded).")
register("MXNET_PREEMPT_DEADLINE_S", 30.0, float,
         "PreemptionGuard grace budget: the preemption force-flush (join "
         "async checkpoint writes + final save + marker) is measured "
         "against this; a flush that cannot beat it is recorded as "
         "deadline_exceeded in PREEMPTED.json and "
         "mxtpu_preemptions_total.")
register("MXNET_SUPERVISOR_POLL_S", 0.05, float,
         "PoolSupervisor liveness-poll interval: how often the serving "
         "worker/prep threads are checked for death or a wedged in-flight "
         "batch (stall detection itself rides the Watchdog).")
register("MXNET_CKPT_FSYNC", True, bool,
         "CheckpointManager: fsync every checkpoint file and directory "
         "rename (the crash-consistency barrier). Disable only for "
         "throwaway test directories.")
register("MXNET_RETRY_MAX_ATTEMPTS", 3, int,
         "RetryPolicy: total attempts (1 = no retries) for retryable "
         "failures (device OOM, UNAVAILABLE, transient compile errors); "
         "fatal errors (shape/dtype mismatch) never retry.")
register("MXNET_RETRY_BASE_MS", 50.0, float,
         "RetryPolicy: backoff before the first retry, milliseconds.")
register("MXNET_RETRY_MAX_MS", 2000.0, float,
         "RetryPolicy: backoff cap, milliseconds.")
register("MXNET_RETRY_MULTIPLIER", 2.0, float,
         "RetryPolicy: exponential backoff multiplier per attempt.")
register("MXNET_RETRY_JITTER", 0.1, float,
         "RetryPolicy: relative jitter (+/- fraction) on each backoff, drawn "
         "from a seeded generator so chaos runs replay exactly.")
register("MXNET_WATCHDOG_STALL_S", 30.0, float,
         "Watchdog: a watched region (device step, serving batch) alive "
         "longer than this counts as a stall — mxtpu_watchdog_stalls_total "
         "fires and the owner's stall callback runs (the serving server "
         "degrades its circuit breaker).")
register("MXNET_WATCHDOG_POLL_S", 0.0, float,
         "Watchdog monitor poll interval; 0 = auto (stall_s/4, clamped to "
         "[0.01, 0.25]s).")
register("MXNET_CIRCUIT_DEGRADED_AFTER", 3, int,
         "CircuitBreaker: consecutive failures before HEALTHY -> DEGRADED "
         "(admission tightens to half the queue bound).")
register("MXNET_CIRCUIT_OPEN_AFTER", 6, int,
         "CircuitBreaker: consecutive failures before -> OPEN (all "
         "admissions shed with ServerOverloadError until cooldown).")
register("MXNET_CIRCUIT_COOLDOWN_S", 5.0, float,
         "CircuitBreaker: seconds OPEN before HALF_OPEN probing begins.")
register("MXNET_NUMERICS_CHECK_EVERY_N", 10, int,
         "NumericsGuard: steps between boundary reads of the retained "
         "on-device health scalars (loss / global grad norm / all-finite "
         "flag). Detection lags by up to this many steps; the read is a "
         "scalar D2H fetch of long-completed values, never a pipeline "
         "stall — lower it for tighter detection, raise it for less host "
         "chatter.")
register("MXNET_NUMERICS_POLICY", "auto", str,
         "NumericsGuard recovery policy: skip (rewind to the last clean "
         "boundary snapshot and replay the window minus the offending "
         "batch — bitwise-equal to never having trained on it) | "
         "quarantine (skip + fingerprint/dump the batch and positionally "
         "exclude it from the DataLoader forever) | rewind (restore the "
         "last good checkpoint and fast-forward the loader past the "
         "poisoned window) | auto (skip first offenders, quarantine a "
         "fingerprint's second offense, rewind when exclusion cannot "
         "repair the window).")
register("MXNET_NUMERICS_SPIKE_ZSCORE", 8.0, float,
         "NumericsGuard: EWMA z-score above which a loss/grad-norm reading "
         "counts as a spike (one-sided; falling loss never flags).")
register("MXNET_NUMERICS_WARMUP_STEPS", 20, int,
         "NumericsGuard: accepted readings before the spike detector arms "
         "(early-training loss is legitimately wild).")
register("MXNET_NUMERICS_EWMA_ALPHA", 0.05, float,
         "NumericsGuard: EWMA smoothing factor for the loss/grad-norm "
         "mean/variance band.")
register("MXNET_NUMERICS_MAX_RECOVERIES", 4, int,
         "NumericsGuard: exclusion-replay attempts per window before the "
         "guard gives up (raises NumericsError, or rewinds under "
         "policy=auto with a CheckpointManager attached).")
register("MXNET_NUMERICS_QUARANTINE_DIR", "", str,
         "NumericsGuard: directory where quarantined batches are dumped "
         "(npz + json fingerprint/position metadata) for postmortem; empty "
         "disables the dump (positional exclusion still happens).")
register("MXNET_SDC_CHECK_EVERY_N", 0, int,
         "NumericsGuard SDC screening: steps between window re-executions "
         "(restore snapshot, replay retained batches with their exact RNG "
         "keys, compare parameter digests — deterministic XLA makes any "
         "mismatch a silent-data-corruption suspect). 0 disables; the "
         "effective cadence rounds up to a multiple of "
         "MXNET_NUMERICS_CHECK_EVERY_N. Screening cost is one extra "
         "window of compute per cadence.")
register("MXNET_SDC_BUNDLE_DIR", "", str,
         "NumericsGuard: directory where SDC repro bundles land (pre-state "
         "+ batches + RNG keys + both digests; tools/replay_step.py "
         "re-executes them). Empty skips bundle writing.")
register("MXNET_SERVING_DRAIN_TIMEOUT_S", 30.0, float,
         "InferenceServer.stop(drain=True): max seconds to wait for the "
         "drain; past it pending requests are abandoned (failed with "
         "ServerClosedError, counted in mxtpu_drain_abandoned_total) so a "
         "wedged endpoint can never hang shutdown forever.")
register("MXNET_SERVING_PIPELINE_DEPTH", 1, int,
         "InferenceServer prep/execute overlap depth: how many prepared "
         "batches the prep loop may run ahead of the execute loop. Depth d "
         "keeps d+1 staging parities alive (host buffers + device inputs); "
         "1 reproduces classic double-buffering. The serial fallback "
         "(pipeline=False) ignores it.")
register("MXNET_SERVING_ZEROCOPY", True, bool,
         "Batch assembly writes request rows straight into preallocated "
         "per-(bucket, parity) staging buffers instead of numpy "
         "concatenate+pad — zero intermediate host copies on the ingest "
         "path. Off falls back to concat (the bitwise-identical slow "
         "path).")
register("MXNET_FABRIC_VNODES", 64, int,
         "Serving front door: virtual nodes per host on the consistent-"
         "hash tenant routing ring. More vnodes spread tenants more "
         "evenly; fewer make the ring cheaper to walk.")
register("MXNET_FABRIC_HEARTBEAT_S", 0.2, float,
         "Serving front door: host agent heartbeat/dump cadence (seconds). "
         "Each tick touches the host's heartbeat file, re-attributes "
         "goodput and rewrites its telemetry dump for the fleet pane.")
register("MXNET_FABRIC_HOST_TIMEOUT_S", 2.0, float,
         "Serving front door: FrontDoor.check_hosts() declares a host dead "
         "when its agent heartbeat is older than this many seconds (or the "
         "agent process exited) and fails it over like kill_host().")
register("MXNET_KV_PAGE_SIZE", 16, int,
         "Paged KV cache: token positions per page. Small pages waste less "
         "tail allocation per sequence but grow page tables; the page size "
         "is baked into the decode executables' scatter/gather indexing, "
         "so changing it recompiles.")
register("MXNET_KV_POOL_PAGES", 256, int,
         "Paged KV cache: total pages preallocated per pool (page 0 is the "
         "reserved scratch page, so usable pages are N-1). Bounds the "
         "number of concurrent sequences times their page footprint; "
         "reserve() past it raises KVPoolExhausted and the scheduler keeps "
         "the sequence queued.")
register("MXNET_KV_DEFRAG_RATIO", 0.0, float,
         "Paged KV cache: auto-compaction threshold on the fragmentation "
         "spread (highest live page id / pages in use); free() triggers "
         "defrag() when the spread exceeds it. 0 (default) disables "
         "auto-compaction (explicit defrag() still works; compaction is a "
         "pure page copy, bitwise-invisible to decode output).")
register("MXNET_DECODE_MAX_BATCH", 8, int,
         "Decode scheduler: max sequences advanced per decode step (top of "
         "the pow2 decode-bucket ladder; every bucket compiles one "
         "decode-step executable at warmup).")
register("MXNET_DECODE_MAX_TOKENS", 64, int,
         "Decode scheduler: default generation budget (max_new_tokens) for "
         "submit() calls that do not specify one. The whole budget's KV "
         "pages are reserved at admission, so a running sequence can never "
         "hit pool exhaustion mid-generation.")
register("MXNET_DECODE_STREAM_BUFFER", 64, int,
         "TokenStream: buffered tokens per client stream before "
         "backpressure pauses the sequence (pages kept, not stepped; "
         "resumes when the consumer drains below half).")
register("MXNET_DECODE_SLO_MS", 100.0, float,
         "Decode scheduler: default per-tenant inter-token SLO "
         "(milliseconds between consecutive tokens of one sequence) used "
         "for EDF admission slack; tenants can override at add_tenant(). "
         "0 disables deadline pricing (FIFO admission).")
register("MXNET_FLIGHT_DIR", "", str,
         "FlightRecorder: directory where trigger-driven flight bundles "
         "(ring contents + metrics snapshot + knob/env fingerprint + "
         "thread stacks) are written, with rotation. Empty keeps the rings "
         "recording but disables automatic bundle dumps; explicit "
         "flight.dump() still works. Also arms the unhandled-exception "
         "crash hooks at import when set.")
register("MXNET_FLIGHT_SPANS", 32768, int,
         "FlightRecorder: capacity of the finished-span ring buffer (half a "
         "minute of a decode loop at 15 ms a pass and 14 spans a pass).")
register("MXNET_FLIGHT_EVENTS", 256, int,
         "FlightRecorder: capacity of the structured-event ring buffer "
         "(telemetry.event: breaker transitions, retries, failovers, "
         "hot-swaps, numerics anomalies, preemptions, SLO alerts).")
register("MXNET_FLIGHT_REQUESTS", 128, int,
         "FlightRecorder: capacity of the completed-serving-request ring "
         "(keyed by trace id).")
register("MXNET_FLIGHT_KEEP", 8, int,
         "FlightRecorder: newest bundles retained per directory; older "
         "flight-*.json files are rotated away after each dump.")
register("MXNET_FLIGHT_MIN_INTERVAL_S", 1.0, float,
         "FlightRecorder: per-trigger-kind dump rate limit; a re-trigger "
         "of the same kind inside the interval records the event but "
         "skips the bundle (mxtpu_flight_dumps_suppressed_total).")
register("MXNET_DEBUG_PORT", 0, int,
         "Debug server: TCP port for the localhost HTTP introspection "
         "pages (/metricsz /healthz /statusz /tracez /flightz). 0 (the "
         "default) disables the server entirely.")
register("MXNET_DEBUG_HOST", "127.0.0.1", str,
         "Debug server: bind address. Keep it loopback unless a scrape "
         "sidecar genuinely lives off-host — the pages expose knobs and "
         "thread stacks.")
register("MXNET_SLO_TARGET", 0.999, float,
         "SLO monitor: default objective target (fraction of requests "
         "under the endpoint's slo_ms) when server.register() does not "
         "pass one explicitly.")
register("MXNET_SLO_FAST_WINDOW_S", 300.0, float,
         "SLO monitor: fast burn-rate window (seconds) — catches a sharp "
         "latency regression within minutes.")
register("MXNET_SLO_SLOW_WINDOW_S", 3600.0, float,
         "SLO monitor: slow burn-rate window (seconds) — de-bounces the "
         "fast window so blips never page.")
register("MXNET_SLO_BURN_THRESHOLD", 10.0, float,
         "SLO monitor: burn-rate multiple (bad_ratio / error_budget) both "
         "windows must exceed before the alert fires / the breaker "
         "escalates.")
register("MXNET_SLO_MIN_EVENTS", 10, int,
         "SLO monitor: minimum requests in the fast window before an "
         "alert may fire (no paging on a sample of three).")
register("MXNET_SLO_ESCALATE", False, bool,
         "SLO monitor: when a burn alert fires, force the offending "
         "tenant's circuit breaker to DEGRADED so admission tightens "
         "before the queue melts. Off by default (alert-only).")
register("MXNET_COMPILE_LEDGER_DIR", "", str,
         "Compile ledger: directory for the append-only per-process "
         "ledger-<pid>.jsonl files (one CompileRecord per XLA compile, "
         "atomic line appends, shared across processes for cross-process "
         "duplicate detection). Empty keeps the in-memory ring + metrics "
         "but writes no files.")
register("MXNET_COMPILE_LEDGER_KEEP", 64, int,
         "Compile ledger: CompileRecords served by recent() — the window "
         "the /compilez page and every flight bundle snapshot.")
register("MXNET_COMPILE_LEDGER_TEXT_MAX_BYTES", 32 << 20, int,
         "Compile ledger: byte budget for retained canonicalized module "
         "texts (module-<fingerprint>.mlir beside the ledger records — the "
         "offline corpus mxlint --ir reads). "
         "Content-addressed dedup means each distinct program is "
         "stored once; when the directory's retained texts would exceed "
         "the budget, new texts are skipped (counted in "
         "mxtpu_compile_text_retained_total{outcome=over_budget}). "
         "Negative disables the bound.")
register("MXNET_IR_GUARD", "", str,
         "Live IR guard over every lower_and_compile: '' (off — the "
         "zero-cost donation assertion still counts detections in "
         "mxtpu_ir_guard_total), 'warn' (check guarded rules IR1000/"
         "IR1001, emit RuntimeWarning + ir_guard flight event), 'raise' "
         "(same, then raise IRGuardError so a dropped donation or "
         "baked-in weights cannot ship). Guard infrastructure errors are "
         "always fail-open; only a real finding under 'raise' fails the "
         "compile. Rule catalog: STATIC_ANALYSIS.md.")
register("MXNET_COMPILE_LEDGER_EAGER", "auto", str,
         "Compile ledger: instrument the eager jit cache ('1'/'0'; 'auto' "
         "follows MXNET_COMPILE_LEDGER_DIR). Instrumentation AOT-compiles "
         "per aval signature to observe each compile; the default eager "
         "hot path is untouched when off.")
register("MXNET_MEM_TRACK", True, bool,
         "Memstats: maintain the HBM holder registry (endpoint params / "
         "bucket executables / donated train state / numerics snapshots) "
         "and reconcile it against device.memory_stats(). 0 turns "
         "register() into a no-op.")
register("MXNET_MEM_HOLDERS_KEEP", 32, int,
         "Memstats: ranked holders shown in breakdown() — the /memz page, "
         "OOM flight bundles; the rest fold into an omitted-bytes line.")
register("MXNET_PERF_SENTINEL", True, bool,
         "Perf sentinel: feed train-step and serving-step latencies into "
         "per-stream EWMA drift detectors that fire a perf_regression "
         "flight event on sustained regression. 0 disables.")
register("MXNET_PERF_EWMA_ALPHA", 0.05, float,
         "Perf sentinel: baseline EWMA smoothing factor (the fast 'now' "
         "track uses 4x this).")
register("MXNET_PERF_REGRESSION_RATIO", 1.5, float,
         "Perf sentinel: fast-track / baseline ratio that counts as "
         "regressed; must hold for MXNET_PERF_SUSTAIN_N consecutive "
         "observations to fire.")
register("MXNET_PERF_SUSTAIN_N", 8, int,
         "Perf sentinel: consecutive over-ratio observations required "
         "before the perf_regression trigger fires (one spike never "
         "pages).")
register("MXNET_PERF_WARMUP_N", 50, int,
         "Perf sentinel: observations per stream before the detector "
         "arms — compile-time outliers and cold caches train the "
         "baseline instead of firing it.")
register("MXNET_EXEC_CACHE_DIR", "", str,
         "Executable cache: directory for serialized compiled executables "
         "(content-addressed by StableHLO fingerprint + device topology + "
         "runtime versions; shareable across processes and hosts). Every "
         "lower_and_compile() site checks it before compiling and "
         "populates it after — a warm restart compiles nothing. Empty "
         "disables the cache.")
register("MXNET_EXEC_CACHE_MAX_BYTES", 1 << 30, int,
         "Executable cache: byte budget for the on-disk store. After "
         "every write the least-recently-used entries (payload mtime, "
         "touched on hit) are evicted until the store fits. 0 disables "
         "eviction.")
register("MXNET_AUTOSCALE_MIN_REPLICAS", 1, int,
         "Autoscaler: floor on the serving replica count — scale-down "
         "never drains below it.")
register("MXNET_AUTOSCALE_MAX_REPLICAS", 4, int,
         "Autoscaler: ceiling on the serving replica count — scale-up "
         "stops here however hard the SLO burns.")
register("MXNET_AUTOSCALE_POLL_S", 1.0, float,
         "Autoscaler: control-loop poll interval (seconds) between "
         "signal reads (SLO burn rate + queue depth).")
register("MXNET_AUTOSCALE_UP_N", 2, int,
         "Autoscaler hysteresis: consecutive over-pressure polls required "
         "before a scale-up (one hot poll never scales).")
register("MXNET_AUTOSCALE_DOWN_N", 5, int,
         "Autoscaler hysteresis: consecutive idle polls required before "
         "a scale-down (draining a replica is the expensive direction).")
register("MXNET_AUTOSCALE_COOLDOWN_S", 10.0, float,
         "Autoscaler: minimum seconds between scaling actions — the "
         "fleet settles (queues redistribute, burn windows refill) "
         "before the next decision.")
register("MXNET_AUTOSCALE_QUEUE_HIGH", 0.5, float,
         "Autoscaler: queue-pressure scale-up threshold as a fraction of "
         "the per-replica queue bound (pending rows / max rows, worst "
         "endpoint, averaged over replicas).")
register("MXNET_AUTOSCALE_QUEUE_LOW", 0.05, float,
         "Autoscaler: queue-pressure floor below which (with no active "
         "burn alert) idle polls count toward scale-down.")
register("MXNET_EMB_REPLICATE_MAX_BYTES", 1 << 20, int,
         "Embedding planner: tables at or under this footprint are "
         "replicated per shard instead of vocab-partitioned — a full copy "
         "is cheaper than any exchange for small tables.")
register("MXNET_EMB_ROWWISE_HOT_FRACTION", 0.25, float,
         "Embedding planner: when a table's observed top-K hot rows take "
         "at least this share of lookups, partition it row-wise (cyclic "
         "layout) so a frequency-sorted vocab's hot head spreads across "
         "shards instead of concentrating on shard 0.")
register("MXNET_EMB_HOT_TOPK", 64, int,
         "Embedding planner: K for the hot-row hit-rate statistic "
         "(mxtpu_emb_hot_row_hit_rate) the row-wise decision reads.")
register("MXNET_EMB_HOTNESS_CAP", 1 << 16, int,
         "Embedding planner: rows of the (frequency-sorted) vocab head "
         "the HotnessTracker keeps exact counters for; hits past the cap "
         "count only toward the total.")
register("MXNET_EMB_FEED_DEPTH", 2, int,
         "DeviceFeed: staged-batch buffer depth (2 = double-buffered; the "
         "stager runs at most this many batches ahead of the consumer).")
register("MXNET_SPAN_SPOOL_DIR", "", str,
         "Span spool: directory for per-pid append-only span JSONL files "
         "(spool-<pid>.jsonl) — the cross-process raw material "
         "tools/trace_journey.py assembles into one timeline per trace "
         "id. Empty (the default) keeps the spool in-memory only: span "
         "exits pay a bounded buffer append and no file I/O ever runs.")
register("MXNET_SPAN_SPOOL_MAX_BYTES", 8 << 20, int,
         "Span spool: size cap per spool file; exceeding it rotates the "
         "file to spool-<pid>.jsonl.1 (one generation kept) before the "
         "append. 0 disables rotation.")
register("MXNET_SPAN_SPOOL_FLUSH_N", 32, int,
         "Span spool: buffered spans per flush — the spool drains to disk "
         "in one O_APPEND write every this-many spans (and at interpreter "
         "exit), never per-span.")
register("MXNET_TRACE_ID", "", str,
         "Trace inheritance: a trace id handed to a child process at "
         "spawn (ServingPool warm restarts, loadgen --restart phases, "
         "chaos subprocesses). The child's first root span joins this "
         "trace instead of minting a fresh id, so one logical request is "
         "one journey across process boundaries. Read once per process.")
register("MXNET_FLEET_DUMP_GLOB", "", str,
         "Fleet collector: glob of telemetry snapshot JSON files "
         "(telemetry.dump() / MXNET_TELEMETRY_DUMP_PATH outputs) from "
         "sibling processes to merge into the fleet view alongside the "
         "live in-process registry.")
register("MXNET_GOODPUT_PEAK_FLOPS", 0.0, float,
         "Goodput ledger: peak device FLOP/s for the roofline fraction in "
         "the per-executable utilization estimate (achieved flops/s over "
         "this). 0 (the default) reports achieved rates only.")
register("MXNET_GOODPUT_PEAK_GBS", 0.0, float,
         "Goodput ledger: peak device memory bandwidth (bytes/s) for the "
         "roofline fraction of the bytes-accessed rate. 0 reports "
         "achieved rates only.")
