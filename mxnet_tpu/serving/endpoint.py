"""ModelEndpoint: a loaded model plus its shape-bucketed executable cache.

One endpoint owns one inference program — a HybridBlock (including
``quantize_net``-converted int8 graphs and bf16-cast nets) or a SymbolBlock
reloaded from an exported checkpoint — traced once through the same
``pure_apply`` primitive CachedOp uses (gluon/block.py), then AOT-compiled per
shape bucket with ``jax.jit(...).lower(avals).compile()``. Compiling through
the AOT path (instead of letting ``jax.jit`` cache internally) makes the
executable cache explicit: the endpoint counts every compile, so the
"recompiles only once per bucket" property is assertable, and ``warmup()``
can pre-build every bucket at load time so no request ever pays a compile.

Params ride as executable *arguments*, not closure constants (PERF.md round-4
lesson: constants bloat the compile payload), so a checkpoint reload swaps
weights without invalidating the compiled buckets.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as onp

from ..base import Context, DTypes, MXNetError, current_context
from .. import telemetry as _telemetry
from ..ndarray.ndarray import NDArray
from . import bucketing
from .errors import HotSwapError
from .router import StepCostEWMA
from .stats import EndpointStats

__all__ = ["ModelEndpoint"]

_HOT_SWAPS = _telemetry.counter(
    "mxtpu_serving_hot_swaps_total",
    "Weight hot-swap attempts by outcome: ok (staged, probe-validated, "
    "committed) / rolled_back (probe validation failed; old weights kept) / "
    "rejected (corrupt or mismatched checkpoint, refused before staging).",
    labelnames=("outcome",))

# name -> endpoint; the registry behind mxnet_tpu.serving.stats()
_ENDPOINTS: Dict[str, "ModelEndpoint"] = {}
_REG_LOCK = threading.Lock()


def _now_us() -> int:
    return time.perf_counter_ns() // 1000


class ModelEndpoint:
    """A named, servable model with bucketed compiled executables.

    Parameters
    ----------
    name : str
        Registry key; ``serving.stats()`` reports under this name.
    block : HybridBlock
        The model. Must be runnable in inference mode. bf16 nets (via
        ``block.cast('bfloat16')``) and ``quantize_net``-converted int8 nets
        are first-class — they trace like any other HybridBlock.
    input_shapes : shape | sequence of shapes
        Per-example shape (without the batch axis) of each model input.
        A single shape tuple means a single-input model.
    dtype : str | sequence of str
        Input dtype(s); requests are cast on the host before device transfer.
    max_batch_size : int
        Largest served batch; also the largest bucket.
    buckets : sequence of int, optional
        Ascending batch-size buckets. Default: powers of two up to
        ``max_batch_size``.
    ctx : Context, optional
        Device the endpoint serves from (default: current context).
    """

    #: devices one replica of this endpoint occupies — the weight
    #: ServingPool.submit divides queue load by, so a 4-chip sharded
    #: replica attracts ~4x a single-chip one's share
    capacity = 1

    def __init__(self, name: str, block, input_shapes, dtype="float32",
                 max_batch_size: int = 32,
                 buckets: Optional[Sequence[int]] = None,
                 ctx: Optional[Context] = None):
        self.name = name
        self.block = block
        self.ctx = ctx if ctx is not None else current_context()
        self.max_batch_size = int(max_batch_size)
        if self.max_batch_size < 1:
            raise MXNetError("max_batch_size must be >= 1")
        if buckets is None:
            buckets = bucketing.pow2_buckets(self.max_batch_size)
        self.buckets = bucketing.validate_buckets(buckets,
                                                  self.max_batch_size)

        if input_shapes and isinstance(input_shapes[0], int):
            input_shapes = (input_shapes,)
        self.input_shapes: Tuple[Tuple[int, ...], ...] = tuple(
            tuple(int(d) for d in s) for s in input_shapes)
        if isinstance(dtype, (list, tuple)):
            dts = tuple(dtype)
        else:
            dts = (dtype,) * len(self.input_shapes)
        if len(dts) != len(self.input_shapes):
            raise MXNetError("one dtype per input required")
        self._jnp_dtypes = tuple(DTypes.jnp(d) for d in dts)
        self.np_dtypes = tuple(onp.dtype(d) for d in self._jnp_dtypes)

        self.stats = EndpointStats(name)
        # per-bucket step time: the measured mean, seeded by warmup
        self.step_cost = StepCostEWMA(name=name)
        self._lock = threading.Lock()
        self._execs: Dict[int, object] = {}   # bucket -> compiled executable
        self._jfn = None
        self._params = None                   # ordered Parameter list
        # hot-swap state: once a swap commits, _active_params (device
        # arrays) is the weight set executables run with; the reference is
        # swapped atomically at a batch boundary by the dispatching thread,
        # so no batch ever sees a half-loaded model
        self._active_params: Optional[Tuple] = None
        self._weights_epoch = 0
        # parity slots of the host pipeline: the prep stage writes the
        # input-buffer set for parity p while the executable reads another;
        # a depth-d pipeline keeps at most d+1 batches alive, so slots are
        # keyed by parity mod (depth+1) — sized lazily as parities appear
        self._parity_bufs: Dict[int, tuple] = {}
        # zero-copy ingest: preallocated host staging buffers, one set per
        # (bucket, parity slot) — request rows are written in place instead
        # of concatenated, so steady state allocates nothing per batch
        self._staging: Dict[tuple, tuple] = {}
        self._probe()

        with _REG_LOCK:
            _ENDPOINTS[name] = self

    # ------------------------------------------------------------------
    # checkpoint loading
    # ------------------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, name: str, symbol_file: str, param_file: str,
                        input_shapes, **kwargs) -> "ModelEndpoint":
        """Load an endpoint from an exported checkpoint (HybridBlock.export's
        ``-symbol.json`` + ``.params``) — no defining Python class needed.
        The export must have been made with ``dynamic_batch=True`` so the
        embedded program accepts every bucket's batch size (a fixed-batch
        export can only ever run at its traced batch)."""
        import json as _json
        from ..gluon.block import SymbolBlock
        with open(symbol_file) as f:
            meta = _json.load(f)
        if not meta.get("dynamic_batch", False):
            raise MXNetError(
                f"{symbol_file} was exported with a fixed batch size; "
                "re-export with HybridBlock.export(..., dynamic_batch=True) "
                "to serve it across shape buckets")
        blk = SymbolBlock.imports(symbol_file, input_names=None,
                                  param_file=param_file)
        return cls(name, blk, input_shapes, **kwargs)

    # ------------------------------------------------------------------
    # model preparation
    # ------------------------------------------------------------------
    def _zeros_batch(self, rows: int):
        return tuple(
            NDArray(onp.zeros((rows,) + s, dt), ctx=self.ctx)
            for s, dt in zip(self.input_shapes, self.np_dtypes))

    def _probe(self):
        """One eager forward with a bucket-1 zero batch: triggers deferred
        parameter init, validates the declared input signature, and records
        the output arity for per-request slicing."""
        from .. import autograd
        dummy = self._zeros_batch(1)
        with autograd._RecordingStateScope(False, False):
            out = self.block(*dummy)
        outs = out if isinstance(out, (list, tuple)) else (out,)
        self.num_outputs = len(outs)
        for o in outs:
            if not (hasattr(o, "shape") and o.shape and o.shape[0] == 1):
                raise MXNetError(
                    f"endpoint {self.name!r}: every model output must be "
                    "batch-major (leading axis = batch) so per-request rows "
                    f"can be sliced back out; got output shape {getattr(o, 'shape', None)}")
        self._params = list(self.block.collect_params().values())
        # HBM attribution: the weight set actually served (post-hot-swap
        # device arrays when present) and the pipeline's double-buffered
        # input sets, sized live at every memstats reconcile
        from ..telemetry import memstats as _memstats
        _memstats.register(
            "serving", f"{self.name}.params", owner=self,
            device=self._device_label(),
            sizer=lambda ep: _memstats.nbytes_of(ep._param_datas()))
        _memstats.register(
            "serving", f"{self.name}.parity_bufs", owner=self,
            device=self._device_label(),
            sizer=lambda ep: _memstats.nbytes_of(
                [slot[1] for slot in ep._parity_bufs.values() if slot]))

    def _device_label(self) -> str:
        """The memstats/ledger device label ('cpu:0', 'tpu:3', ...)."""
        try:
            d = self.ctx.jax_device()
            return f"{d.platform}:{d.id}"
        except (AttributeError, RuntimeError, ValueError, ImportError):
            # no jax device behind this ctx (stub backends) — holders
            # registered with an empty label roll up under "unassigned"
            return ""

    def _donate_inputs(self) -> bool:
        """Donate input buffers to the executable on backends that implement
        buffer donation (TPU/GPU): the double-buffered pipeline then recycles
        each parity set's memory instead of allocating per step. CPU ignores
        donation (with a warning), so keep it off there. Decided once, before
        the first compile, so every bucket shares one executable signature —
        the compiled-once-per-bucket property is preserved."""
        return self._platform() in ("tpu", "gpu")

    def _platform(self) -> str:
        """Platform of the device(s) the executables run on. Sharded
        endpoints answer from their mesh, not from ``ctx``."""
        return self.ctx.jax_device().platform

    def _place_inputs(self, arrays):
        """Host->device placement of one batch's input arrays. The hook a
        mesh-sharded endpoint overrides (NamedSharding placement); the base
        endpoint puts everything on its single context device."""
        import jax
        dev = self.ctx.jax_device()
        return tuple(jax.device_put(a, dev) for a in arrays)

    def _jit_infer(self, infer, donate):
        """Wrap the traced inference function in ``jax.jit``, pinned to the
        context's device: lowered from bare ShapeDtypeStructs it would
        compile for JAX's default device, whatever ``ctx`` says. Sharded
        endpoints override to pin NamedSharding in/out shardings."""
        import jax
        dev = jax.sharding.SingleDeviceSharding(self.ctx.jax_device())
        return jax.jit(infer, donate_argnums=donate, in_shardings=dev,
                       out_shardings=dev)

    def _infer_fn(self):
        if self._jfn is None:
            from ..gluon.block import pure_apply
            block, plist = self.block, self._params

            def infer(param_datas, *input_datas):
                outs, _, _ = pure_apply(block, plist, param_datas, input_datas,
                                        None, training=False)
                return outs

            donate = tuple(range(1, 1 + len(self.input_shapes))) \
                if self._donate_inputs() else ()
            self._jfn = self._jit_infer(infer, donate)
        return self._jfn

    def _param_datas(self):
        if self._active_params is not None:
            return self._active_params
        return tuple(p.data(self.ctx).data for p in self._params)

    @property
    def weights_epoch(self) -> int:
        """Monotonic hot-swap generation of the weights currently served."""
        return self._weights_epoch

    # ------------------------------------------------------------------
    # the shape-bucketed executable cache
    # ------------------------------------------------------------------
    def _compile_key(self, bucket: int) -> Dict[str, object]:
        """The compile-ledger / executable-cache trigger key for one bucket.
        Everything in it must be stable across process restarts that should
        share cached executables — a sharded endpoint overrides the device
        entry with its slice *shape* so a restarted replica on the same
        slice topology hits the fleet cache instead of recompiling."""
        return {"endpoint": self.name, "bucket": bucket,
                "dtype": str(self._jnp_dtypes[0].__name__
                             if hasattr(self._jnp_dtypes[0], "__name__")
                             else self._jnp_dtypes[0]),
                "device": self._device_label()}

    def _get_executable(self, bucket: int):
        comp = self._execs.get(bucket)
        if comp is not None:
            self.stats.bump("cache_hits")
            return comp
        with self._lock:
            comp = self._execs.get(bucket)
            if comp is not None:
                self.stats.bump("cache_hits")
                return comp
            import jax
            from .. import telemetry
            from ..telemetry import compile_ledger as _ledger
            from ..telemetry import memstats as _memstats
            from ..resilience import faults as _faults
            t0 = _now_us()
            _faults.check("compile")
            with telemetry.span("serving.compile", endpoint=self.name,
                                bucket=bucket):
                param_sds = tuple(
                    jax.ShapeDtypeStruct(tuple(p.shape),
                                         p.data(self.ctx).data.dtype)
                    for p in self._params)
                in_sds = tuple(
                    jax.ShapeDtypeStruct((bucket,) + s, dt)
                    for s, dt in zip(self.input_shapes, self._jnp_dtypes))
                # compiling under the endpoint lock is the compile-once
                # gate: contenders need this bucket's executable and must
                # wait for it either way (a double-checked compile outside
                # the lock would just duplicate device compilations)
                comp = _ledger.lower_and_compile(  # mxlint: disable=CONC202
                    self._infer_fn(), (param_sds,) + in_sds,
                    site="serving_bucket", key=self._compile_key(bucket),
                    expect_donation=self._donate_inputs())
            self._adopt_compiled(comp)
            self._execs[bucket] = comp
            # attribute the executable's own device footprint (output +
            # scratch + generated code; arguments belong to params/inputs)
            mem = _ledger._memory_analysis(comp)
            _memstats.register(
                "serving", f"{self.name}.exec_b{bucket}", owner=self,
                device=self._device_label(),
                nbytes=sum(mem.get(k, 0) for k in
                           ("output_bytes", "temp_bytes", "code_bytes")))
            self.stats.record_compile(_now_us() - t0)
            return comp

    def warmup(self, execute: bool = True):
        """Compile (and by default execute once) every bucket, so serving
        traffic never hits a compile — first-request latency is steady-state
        latency. Each warmup execution is timed into ``step_cost``, seeding
        the scheduler's per-bucket EWMA before the first real request.
        Returns the number of buckets compiled."""
        import jax
        n = 0
        for b in self.buckets:
            fresh = b not in self._execs
            comp = self._get_executable(b)
            if fresh:
                n += 1
                if execute:
                    ins = self._warmup_inputs(b)
                    t0 = _now_us()
                    jax.block_until_ready(comp(self._param_datas(), *ins))
                    self.step_cost.observe(b, _now_us() - t0)
        return n

    def _warmup_inputs(self, bucket: int):
        """Zero inputs for one warmup execution of ``bucket``."""
        return tuple(a.data for a in self._zeros_batch(bucket))

    def _adopt_compiled(self, comp):
        """Hook: inspect a just-obtained executable before first use.
        Sharded endpoints adopt a cache-deserialized executable's device
        assignment here; the single-device path needs nothing."""

    # ------------------------------------------------------------------
    # execution: prepare (host half) / execute (device half)
    # ------------------------------------------------------------------
    def staging_buffers(self, bucket: int, parity: int):
        """Preallocated host staging buffers for one (bucket, parity slot):
        the zero-copy prep path writes request rows straight into these and
        zeroes the padding tail, instead of concat + pad allocating per
        batch. The parity discipline that protects the device-side buffer
        sets protects these too — the slot being written is never the slot
        an in-flight batch still references."""
        key = (int(bucket), int(parity))
        bufs = self._staging.get(key)
        if bufs is None:
            bufs = tuple(onp.zeros((bucket,) + s, dt)
                         for s, dt in zip(self.input_shapes, self.np_dtypes))
            self._staging[key] = bufs
        return bufs

    def prepare(self, host_inputs: Sequence[onp.ndarray], rows: int,
                parity: int = 0):
        """Host half of one batch step: pad pre-concatenated host inputs to
        the shape bucket and transfer them into the ``parity`` input-buffer
        set. Safe to run on the pipeline's prep thread while the worker
        executes the other parity — it never touches a compiled executable.

        Returns ``(device_inputs, bucket, padded_host)``; ``padded_host`` is
        kept with the prepared batch so a retry can rebuild donated buffers.
        """
        bucket = bucketing.bucket_for(rows, self.buckets)
        padded = tuple(bucketing.pad_rows(a, bucket) for a in host_inputs)
        ins = self._place_inputs(padded)
        self._parity_bufs[parity] = (bucket, ins)
        return ins, bucket, padded

    def execute(self, device_inputs, bucket: int, rows: int,
                padded_host: Optional[Sequence[onp.ndarray]] = None):
        """Device half: run the bucket's cached executable over prepared
        input buffers. Worker-thread only (the single-dispatcher rule).
        Returns a tuple of device output arrays with ``bucket`` rows each;
        callers slice [0:rows] back out per request."""
        import jax
        from .. import telemetry
        comp = self._get_executable(bucket)
        # a donated executable consumed these buffers on a previous (failed)
        # attempt: rebuild them from the retained padded host copy
        if padded_host is not None and any(
                getattr(a, "is_deleted", lambda: False)()
                for a in device_inputs):
            device_inputs = self._place_inputs(padded_host)
        # child of the caller's serving.batch span (same thread): the trace
        # id stamped at submit reaches the compiled device step
        with telemetry.span("serving.device_step", endpoint=self.name,
                            bucket=bucket, rows=rows):
            t0 = _now_us()
            outs = comp(self._param_datas(), *device_inputs)
            jax.block_until_ready(outs)
            self.step_cost.observe(bucket, _now_us() - t0)
        self.stats.bump("batches")
        self.stats.bump("real_rows", rows)
        self.stats.bump("padded_rows", bucket - rows)
        return outs

    def run_batch(self, host_inputs: Sequence[onp.ndarray], rows: int):
        """Serial prepare-then-step over pre-concatenated host inputs (the
        pre-pipeline dispatch path; kept for direct callers and as the
        bitwise reference the pipelined path is tested against).

        Returns (outputs, bucket) exactly as before the prepare/execute
        split."""
        ins, bucket, padded = self.prepare(host_inputs, rows)
        outs = self.execute(ins, bucket, rows, padded_host=padded)
        return outs, bucket

    # ------------------------------------------------------------------
    # zero-downtime weight hot-swap
    # ------------------------------------------------------------------
    def save_checkpoint(self, manager, step: int, probe_seed: int = 0):
        """Producer-side half of hot-swap: write this endpoint's weights as
        an atomic, checksummed serving checkpoint (CheckpointManager layout)
        *plus a recorded probe*: a seeded random smallest-bucket batch and
        the outputs these exact weights produce for it. A consumer's
        ``hot_swap`` replays the probe against the staged weights and
        requires bitwise-equal outputs before cutting over — corrupt bytes,
        a mixed-up param file, or a wrong-architecture checkpoint all fail
        validation instead of reaching clients.

        Call this from the training/export job (or a stopped endpoint) —
        it invokes a compiled executable, so inside a live server it belongs
        to the worker thread only."""
        from ..resilience.checkpoint import capture_state
        bucket = self.buckets[0]
        rng = onp.random.RandomState(probe_seed & 0x7FFFFFFF)
        probe_in = tuple(
            rng.standard_normal((bucket,) + s).astype(dt)
            for s, dt in zip(self.input_shapes, self.np_dtypes))
        import jax
        comp = self._get_executable(bucket)
        ins = self._place_inputs(probe_in)
        outs = comp(self._param_datas(), *ins)
        jax.block_until_ready(outs)
        state = capture_state(block=self.block, include_rng=False)
        state["serving"] = {
            "bucket": int(bucket), "probe_seed": int(probe_seed),
            "probe": {f"i{i}": a for i, a in enumerate(probe_in)},
            "expected": {f"o{i}": onp.asarray(jax.device_get(o))
                         for i, o in enumerate(outs)},
        }
        return manager.save(step, state=state)

    def load_swap_source(self, source):
        """Resolve a hot-swap source into ``(host_params, probe, label)``
        WITHOUT touching the served weights. ``source`` may be a checkpoint
        directory (a single ``ckpt-*`` dir or a CheckpointManager root, in
        which case the newest intact checkpoint is used — every file is
        checksum-verified first), or an explicit state tree as written by
        :meth:`save_checkpoint` / ``capture_state(block=...)``. Raises
        HotSwapError on corruption or model mismatch — the caller never
        stages bad weights."""
        import os
        from ..resilience.checkpoint import verify_checkpoint_dir
        label = "<state>"
        state = None
        if isinstance(source, str):
            label = source
            try:
                if os.path.isfile(os.path.join(source, "MANIFEST.json")):
                    state = verify_checkpoint_dir(source)
                else:
                    names = sorted(n for n in os.listdir(source)
                                   if n.startswith("ckpt-"))
                    for name in reversed(names):
                        try:
                            state = verify_checkpoint_dir(
                                os.path.join(source, name))
                            label = os.path.join(source, name)
                            break
                        except Exception:
                            continue
            except OSError as e:
                raise HotSwapError(f"cannot read swap source {source!r}: {e}")
            if state is None:
                _HOT_SWAPS.labels("rejected").inc()
                raise HotSwapError(
                    f"no intact checkpoint under {source!r}: every candidate "
                    "failed checksum verification")
        elif isinstance(source, dict):
            state = source
        else:
            raise HotSwapError(
                f"unsupported hot_swap source {type(source).__name__}; pass "
                "a checkpoint directory or a state tree")
        mod = state.get("model")
        if mod is None:
            _HOT_SWAPS.labels("rejected").inc()
            raise HotSwapError(
                f"swap source {label} has no 'model' component "
                f"(holds {sorted(state)})")
        try:
            n = int(mod["n_params"])
            if n != len(self._params):
                raise HotSwapError(
                    f"checkpoint holds {n} params, endpoint {self.name!r} "
                    f"serves {len(self._params)} ({mod.get('param_names')})")
            host = []
            for i, p in enumerate(self._params):
                arr = onp.asarray(mod["params"][f"p{i}"])
                if tuple(arr.shape) != tuple(p.shape):
                    raise HotSwapError(
                        f"checkpoint param {i} shape {arr.shape} != endpoint "
                        f"param shape {tuple(p.shape)}")
                host.append(arr)
        except (KeyError, TypeError, ValueError) as e:
            _HOT_SWAPS.labels("rejected").inc()
            raise HotSwapError(f"malformed swap source {label}: {e!r}")
        except HotSwapError:
            _HOT_SWAPS.labels("rejected").inc()
            raise
        probe = state.get("serving")
        return host, probe, label

    def _place_params(self, arrays):
        """Host->device placement of a full weight set (hot-swap staging).
        Sharded endpoints override with their per-param NamedShardings."""
        import jax
        dev = self.ctx.jax_device()
        return tuple(jax.device_put(a, dev) for a in arrays)

    def stage_weights(self, host_params):
        """Transfer new weights into fresh device buffers (the off-parity
        set: in-flight steps keep reading the old arrays untouched). Host
        work only — safe off the worker thread."""
        return self._place_params(tuple(
            a.astype(p.data(self.ctx).data.dtype, copy=False)
            if onp.dtype(a.dtype) != p.data(self.ctx).data.dtype else a
            for a, p in zip(host_params, self._params)))

    def validate_and_commit(self, staged, probe=None) -> dict:
        """Dispatcher-thread half of a hot-swap: run the validation probe
        against the STAGED weights (the serving weights are untouched), then
        cut over atomically. With a recorded probe (``save_checkpoint``),
        the staged outputs must be bitwise-equal to the recorded ones;
        without one, outputs must at least be finite. Any validation failure
        raises HotSwapError with nothing committed — automatic rollback."""
        import jax
        if probe is not None:
            bucket = int(probe["bucket"])
            ins_h = [onp.asarray(probe["probe"][f"i{i}"])
                     for i in range(len(self.input_shapes))]
            expected = [onp.asarray(probe["expected"][f"o{i}"])
                        for i in range(self.num_outputs)]
        else:
            bucket = self.buckets[0]
            ins_h = [onp.zeros((bucket,) + s, dt)
                     for s, dt in zip(self.input_shapes, self.np_dtypes)]
            expected = None
        comp = self._get_executable(bucket)
        ins = self._place_inputs(ins_h)
        try:
            outs = comp(staged, *ins)
            jax.block_until_ready(outs)
            outs_h = [onp.asarray(jax.device_get(o)) for o in outs]
        except Exception as e:
            _HOT_SWAPS.labels("rolled_back").inc()
            raise HotSwapError(
                f"staged weights failed the probe execution: {e}") from e
        if expected is not None:
            for i, (got, want) in enumerate(zip(outs_h, expected)):
                if not onp.array_equal(got, want):
                    _HOT_SWAPS.labels("rolled_back").inc()
                    raise HotSwapError(
                        f"probe output {i} does not match the recorded "
                        "outputs of the checkpointed weights; rolled back "
                        "(old weights keep serving)")
        else:
            for i, got in enumerate(outs_h):
                if not onp.all(onp.isfinite(got)):
                    _HOT_SWAPS.labels("rolled_back").inc()
                    raise HotSwapError(
                        f"probe output {i} contains non-finite values; "
                        "rolled back (old weights keep serving)")
        # commit: one reference assignment — the next batch's _param_datas()
        # sees the full new weight set, the in-flight one kept the old
        self._active_params = staged
        self._weights_epoch += 1
        # keep the block's Parameters in sync so direct block(...) forwards
        # and later save_checkpoint calls reflect the served weights
        for p, a in zip(self._params, staged):
            p.set_data(NDArray(onp.asarray(jax.device_get(a))))
        self.stats.bump("hot_swaps")
        _HOT_SWAPS.labels("ok").inc()
        return {"endpoint": self.name, "weights_epoch": self._weights_epoch,
                "probe": "recorded" if probe is not None else "finite",
                "bucket": bucket}

    def hot_swap(self, source) -> dict:
        """Inline hot-swap for a *stopped* (or never-served) endpoint: load +
        verify ``source``, stage, probe-validate, cut over; HotSwapError
        rolls back to the old weights. Inside a running InferenceServer use
        ``server.hot_swap(name, source)`` instead — it routes the validation
        and cutover through the worker thread at a batch boundary, so no
        request is ever dropped or served from a half-loaded model."""
        host, probe, label = self.load_swap_source(source)
        staged = self.stage_weights(host)
        report = self.validate_and_commit(staged, probe)
        report["source"] = label
        return report

    def __repr__(self):
        return (f"ModelEndpoint({self.name!r}, inputs={self.input_shapes}, "
                f"buckets={self.buckets})")


def get_endpoint(name: str) -> ModelEndpoint:
    with _REG_LOCK:
        if name not in _ENDPOINTS:
            raise MXNetError(f"unknown endpoint {name!r}; registered: "
                             f"{sorted(_ENDPOINTS)}")
        return _ENDPOINTS[name]


def list_endpoints():
    with _REG_LOCK:
        return sorted(_ENDPOINTS)


def unregister(name: str):
    with _REG_LOCK:
        _ENDPOINTS.pop(name, None)
