"""ParallelTrainStep: the fused multi-chip training step.

Reference mapping: one call to ParallelTrainStep.step() does what a whole
iteration of the reference's Gluon training loop does (SURVEY.md §3.4):
forward (cached_op.cc:765) + backward (imperative.cc:376) + gradient allreduce
(gluon/trainer.py:380-404 → kvstore_nccl.h:285) + optimizer update
(optimizer_op.cc) — but as ONE pjit'd XLA computation over a DeviceMesh.
Data-parallel gradient reduction is not coded anywhere: the batch is sharded
over 'dp' while parameters are replicated (or sharded over 'tp'/'fsdp'), so
GSPMD materializes the implied all-reduce/all-gather on ICI. Buffer donation of
params+optimizer state gives the reference's in-place update semantics
(kAddTo/static_alloc, cached_op.h:318) without aliasing hazards.

Parameters opt into model-parallel layouts via ``Parameter.shard(spec)`` (the
TPU replacement for ctx_group model parallelism, symbol.py:1562-1711).
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as onp

from ..base import Context, MXNetError
from ..ndarray.ndarray import NDArray
from .. import telemetry as _telemetry
from ..resilience import faults as _faults
from ..resilience.retry import RetryPolicy
from .mesh import DeviceMesh, batch_axes

__all__ = ["ParallelTrainStep", "pure_apply"]

# fleet training counters: is the chip stepping, how fast, and is the
# autoformat/donation machinery churning state placements
_STEPS = _telemetry.counter(
    "mxtpu_train_steps_total",
    "Optimizer steps executed (step_n counts its inner steps).")
_EXAMPLES = _telemetry.counter(
    "mxtpu_train_examples_total",
    "Training examples consumed (leading batch dim); rate = examples/s.")
_STEP_LATENCY = _telemetry.histogram(
    "mxtpu_train_step_latency_us",
    "Host-observed latency of one step()/step_n() dispatch (microseconds).")
_DONATED_REPLACE = _telemetry.counter(
    "mxtpu_train_donated_replace_total",
    "Times the autoformat path re-placed carried (donated) state into a "
    "different executable's layouts — the OOM-retryable transition; steady "
    "growth means step()/step_n() shape churn is thrashing layouts.")
_DROPOUT_DRAWS = _telemetry.counter(
    "mxtpu_train_dropout_draws_total",
    "Dropout masks traced under a train step whose batch is divided over "
    "more than one device: per_shard = drawn a shard at a time, whole = the "
    "whole mask drawn on every device (a leading dimension of 1, or one the "
    "devices do not divide). Counted when a step is traced, not when it "
    "runs.", labelnames=("draw",))


def _count_dropout_draw(kind):
    _DROPOUT_DRAWS.labels(kind).inc()


from ..gluon.block import pure_apply, _trace_nd as _mk_nd  # shared primitive


def _leading_dim(x, axis=0):
    shape = getattr(x, "shape", None)
    try:
        return int(shape[axis]) if shape is not None and len(shape) > axis else 0
    except TypeError:
        return 0


class ParallelTrainStep:
    """Fused forward+backward+allreduce+update step over a DeviceMesh.

    Usage::

        mesh = make_mesh({"dp": 4, "tp": 2})
        step = ParallelTrainStep(net, loss_fn, optimizer, mesh,
                                 data_spec=P("dp"), label_spec=P("dp"))
        for x, y in batches:
            loss = step(x, y)          # ONE XLA computation on all chips
        step.sync_to_block()           # write final weights back to net

    Parameters live on-mesh as sharded jax arrays across steps (donated each
    call); ``sync_to_block`` writes them back into the Gluon Parameters.
    """

    def __init__(self, block, loss, optimizer, mesh: DeviceMesh, *,
                 data_spec=None, label_spec=None, extra_specs: Sequence = (),
                 donate: bool = True, compute_dtype=None, param_format=None,
                 retry_policy=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        self._block = block
        self._loss = loss
        self._optimizer = optimizer
        self._mesh = mesh
        self._donate = donate
        # transient device failures (OOM on a shape transition, preempted
        # chip) retry with backoff; the on_retry hook refuses to retry once
        # donated carried state is gone and re-places it otherwise
        self._retry = retry_policy if retry_policy is not None \
            else RetryPolicy.from_config()
        self._step_fn = None
        self._step_n_fns: Dict[int, Callable] = {}
        self._t = 0
        # numerics guard (resilience.numerics.NumericsGuard.attach): while
        # attached, the compiled step also emits (grad_norm, all_finite)
        # device scalars and every step() reports its retained inputs
        self._guard = None
        # param_format="auto": let XLA choose the parameter/optimizer-state
        # memory layouts (AOT lower+compile with Layout.AUTO) and keep the
        # carried state in those layouts across steps — kills the per-step
        # re-layout copies XLA otherwise inserts at the jit boundary when its
        # preferred layout differs from the default row-major one
        if param_format not in (None, "auto"):
            raise MXNetError(f"param_format must be None or 'auto', "
                             f"got {param_format!r}")
        self._param_format = param_format
        self._autoformat_cache: Dict = {}

        params = list(block.collect_params().values())
        for p in params:
            if p._data is None:
                raise MXNetError(f"Parameter {p.name} is not initialized; call "
                                 "block.initialize() before ParallelTrainStep")
        self._plist = params
        self._trainable_idx = [i for i, p in enumerate(params)
                               if p.grad_req != "null"]
        self._aux_idx = [i for i, p in enumerate(params) if p.grad_req == "null"]

        # shardings: Parameter.shard(spec) opts into tp/fsdp layouts; default
        # replicated (pure data parallel)
        self._param_shardings = []
        for p in params:
            spec = getattr(p, "_sharding", None)
            if spec is None:
                sh = mesh.replicated()
            else:
                sh = mesh.sharding(*spec) if isinstance(spec, (tuple, list)) \
                    else mesh.sharding(spec) if isinstance(spec, str) \
                    else jax.sharding.NamedSharding(mesh.mesh, spec)
            self._param_shardings.append(sh)

        if compute_dtype is not None:
            compute_dtype = jnp.dtype(compute_dtype)
        self._compute_dtype = compute_dtype

        # place parameter values on the mesh. Never an alias of the block's
        # own array, which device_put may hand back when that array already
        # sits on a device of the mesh: the first donating step would
        # delete the block's parameters with it
        self._params = [jax.device_put(p.data().data, sh, may_alias=False)
                        for p, sh in zip(params, self._param_shardings)]

        # optimizer state per trainable param, sharded like its param
        self._opt_states = []
        self._state_shardings = []
        from ..optimizer.optimizer import _unwrap_state
        for i in self._trainable_idx:
            st = _unwrap_state(optimizer.create_state_multi_precision(
                i, params[i].data()))
            psh = self._param_shardings[i]
            st_sh = jax.tree_util.tree_map(
                lambda leaf: psh if getattr(leaf, "shape", None) ==
                tuple(params[i].shape) else mesh.replicated(), st)
            st = jax.tree_util.tree_map(
                lambda leaf, sh: jax.device_put(leaf, sh), st, st_sh)
            self._opt_states.append(st)
            self._state_shardings.append(st_sh)

        self._data_sharding = mesh.sharding(*data_spec) if data_spec is not None \
            else mesh.sharding("dp") if "dp" in mesh.axis_names else mesh.replicated()
        self._label_sharding = mesh.sharding(*label_spec) if label_spec is not None \
            else self._data_sharding
        self._extra_shardings = [mesh.sharding(*s) for s in extra_specs]
        self._aux_ids_cell: List = []
        # HBM attribution: the carried (donated) train state — params + aux
        # + optimizer moments — sized live at every memstats reconcile, so
        # the figure survives donation replacing the arrays each step
        from ..telemetry import memstats as _memstats
        _memstats.register(
            "train", f"train_step.state.{id(self):x}", owner=self,
            sizer=lambda ts: _memstats.nbytes_of(ts._params) +
            _memstats.nbytes_of(ts._opt_states))

    # ------------------------------------------------------------------
    def _make_raw_step(self, with_health: bool = False):
        """The pure one-step function shared by the single-step jit and the
        scan-based multi-step jit.

        ``with_health=True`` (a NumericsGuard is attached) additionally
        returns two device scalars fused into the same XLA computation: the
        f32 global gradient norm and an all-finite flag over the loss and
        every gradient leaf. The update math is untouched — the health
        outputs are extra consumers of values the step already computes, so
        a guarded run stays bitwise-identical to an unguarded one."""
        import jax
        import jax.numpy as jnp

        opt = self._optimizer
        plist = self._plist
        tidx = self._trainable_idx
        aidx = self._aux_idx
        loss_blk = self._loss
        block = self._block
        aux_cell = self._aux_ids_cell
        cdtype = self._compute_dtype
        batch_scope = self._batch_scope

        def step(train_params, aux_params, opt_states, x, y, extras, key,
                 lrs, wds, t):
            full = [None] * len(plist)
            for j, i in enumerate(tidx):
                full[i] = train_params[j]
            for j, i in enumerate(aidx):
                full[i] = aux_params[j]

            def loss_f(tp):
                cur = list(full)
                for j, i in enumerate(tidx):
                    cur[i] = tp[j].astype(cdtype) if cdtype is not None and \
                        jnp.issubdtype(tp[j].dtype, jnp.floating) else tp[j]
                xin = x.astype(cdtype) if cdtype is not None and \
                    jnp.issubdtype(x.dtype, jnp.floating) else x
                with batch_scope():
                    outs, aux_vals, aux_pids = pure_apply(
                        block, plist, cur, (xin,) + tuple(extras), key,
                        training=True)
                aux_cell.clear()
                aux_cell.extend(aux_pids)
                outs_nd = [_mk_nd(o) for o in outs]
                labels_nd = [_mk_nd(l) for l in (y if isinstance(y, (tuple, list))
                                                 else (y,))]
                loss_nd = loss_blk(*outs_nd, *labels_nd)
                loss_val = jnp.mean(loss_nd.data.astype(jnp.float32))
                return loss_val, aux_vals

            from .. import config as _config
            remat = _config.get("MXNET_TRAIN_REMAT")
            if remat == "conv":
                # save only conv outputs for backward; recompute the BN/ReLU
                # elementwise chains instead of storing+reloading them — the
                # flops-for-bytes trade that fits an HBM-bound convnet step
                loss_f = jax.checkpoint(
                    loss_f, policy=jax.checkpoint_policies.
                    save_only_these_names("conv_out"))
            elif remat == "full":
                loss_f = jax.checkpoint(loss_f)
            (loss_val, aux_vals), grads = jax.value_and_grad(
                loss_f, has_aux=True)(list(train_params))

            if with_health:
                # one extra read of each gradient (the sum of squares the
                # grad-norm needs anyway); finiteness falls out of it for
                # free — any NaN/Inf in any gradient propagates into gsq,
                # so no second isfinite pass over the gradients is needed
                gsq = jnp.float32(0.0)
                for g in grads:
                    g32 = g.astype(jnp.float32)
                    gsq = gsq + jnp.sum(g32 * g32)
                finite = jnp.logical_and(jnp.isfinite(loss_val),
                                         jnp.isfinite(gsq))
                health = (jnp.sqrt(gsq), finite)

            new_train, new_states = [], []
            for j, i in enumerate(tidx):
                w, g, s = train_params[j], grads[j], opt_states[j]
                g = g.astype(w.dtype) * opt.rescale_grad
                if opt.clip_gradient is not None:
                    g = jnp.clip(g, -opt.clip_gradient, opt.clip_gradient)
                nw, ns = opt._rule(w, g, s, lrs[j], wds[j], t)
                new_train.append(nw)
                new_states.append(ns)

            # aux write-back (BatchNorm moving stats) as pure outputs
            pid_to_val = dict(zip(aux_cell, aux_vals))
            new_aux = []
            for j, i in enumerate(aidx):
                upd = pid_to_val.get(id(plist[i]))
                new_aux.append(upd if upd is not None else aux_params[j])
            if with_health:
                return loss_val, new_train, new_aux, new_states, health
            return loss_val, new_train, new_aux, new_states

        return step

    def _batch_scope(self):
        """What the model's trace may know of the batch's division: the
        mesh axes in the data spec's leading entry, published
        (``mesh.batch_axes``) where they span more than one device. With one
        device, or a batch that is not divided, nothing is published and the
        trace is the one-device trace."""
        spec = self._data_sharding.spec
        lead = spec[0] if len(spec) else None
        axes = () if lead is None else (lead,) if isinstance(lead, str) \
            else tuple(lead)
        scope = batch_axes(self._mesh, axes, _count_dropout_draw)
        return scope if scope.size > 1 else contextlib.nullcontext()

    def _shardings(self):
        t_sh = [self._param_shardings[i] for i in self._trainable_idx]
        a_sh = [self._param_shardings[i] for i in self._aux_idx]
        rep = self._mesh.replicated()
        return t_sh, a_sh, rep

    def _build(self):
        import jax
        _faults.check("compile")
        with_health = self._guard is not None
        step = self._make_raw_step(with_health=with_health)
        t_sh, a_sh, rep = self._shardings()
        donate = (0, 1, 2) if self._donate else ()
        out_tail = ((rep, rep),) if with_health else ()
        if self._param_format == "auto":
            self._step_fn = self._autoformat_jit(
                step, t_sh, a_sh,
                (self._data_sharding, self._label_sharding,
                 tuple(self._extra_shardings), rep, rep, rep, rep),
                rep, donate, out_tail=out_tail)
            return
        in_shardings = (t_sh, a_sh, self._state_shardings,
                        self._data_sharding, self._label_sharding,
                        tuple(self._extra_shardings), rep, rep, rep, rep)
        out_shardings = (rep, t_sh, a_sh, self._state_shardings) + out_tail
        self._step_fn = jax.jit(step, in_shardings=in_shardings,
                                out_shardings=out_shardings,
                                donate_argnums=donate)

    def _stacked(self, sh):
        """Sharding for an input with a leading per-step (scan) axis."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self._mesh.mesh, P(None, *sh.spec))

    def _build_n(self, n):
        """jit(scan(step)) over n stacked microbatches: the training loop runs
        on-device, amortizing host dispatch across n steps (the standard
        'train loop inside jit' TPU pattern — compare the reference looping
        MXImperativeInvoke per op per step)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        _faults.check("compile")
        step = self._make_raw_step()

        def step_n(train_params, aux_params, opt_states, xs, ys, extras_s,
                   key, lrs_k, wds_k, t0):
            # lrs_k/wds_k are (n, n_trainable): per-inner-step schedules, so a
            # lr_scheduler sees the same update counts as n separate step()s
            keys = jax.random.split(key, n)

            def body(carry, inp):
                train, aux, states, t = carry
                x, y, extras, k, lrs, wds = inp
                loss, nt, na, ns = step(train, aux, states, x, y, extras, k,
                                        lrs, wds, t)
                return (nt, na, ns, t + 1.0), loss

            (train, aux, states, _), losses = lax.scan(
                body,
                (list(train_params), list(aux_params), list(opt_states), t0),
                (xs, ys, extras_s, keys, lrs_k, wds_k))
            return losses, train, aux, states

        t_sh, a_sh, rep = self._shardings()
        donate = (0, 1, 2) if self._donate else ()
        if self._param_format == "auto":
            fn = self._autoformat_jit(
                step_n, t_sh, a_sh,
                (self._stacked(self._data_sharding),
                 self._stacked(self._label_sharding),
                 tuple(self._stacked(s) for s in self._extra_shardings),
                 rep, rep, rep, rep),
                rep, donate)
            self._step_n_fns[n] = fn
            return fn
        in_shardings = (t_sh, a_sh, self._state_shardings,
                        self._stacked(self._data_sharding),
                        self._stacked(self._label_sharding),
                        tuple(self._stacked(s) for s in self._extra_shardings),
                        rep, rep, rep, rep)
        out_shardings = (rep, t_sh, a_sh, self._state_shardings)
        fn = jax.jit(step_n, in_shardings=in_shardings,
                     out_shardings=out_shardings, donate_argnums=donate)
        self._step_n_fns[n] = fn
        return fn

    def _autoformat_jit(self, fn, t_sh, a_sh, tail_shardings, loss_sh, donate,
                        out_tail=()):
        """AOT path for param_format='auto': compile with Layout.AUTO on the
        carried state (params/aux/opt states), re-place that state into the
        layouts XLA chose, and keep it there via donation + matching output
        formats — the boundary re-layout copies disappear from steady state.

        Executables are cached per data-signature (shapes/dtypes of the
        non-state args), so shape changes retrace like the default jit path
        instead of crashing; when a different executable than the last-used
        one runs, the carried state is re-placed into that executable's
        formats first (device_put is a no-op when the layout already
        matches), so step()/step_n() interleaving stays correct."""
        import jax
        from jax.experimental.layout import Format, Layout

        def fmtf(sh):
            return Format(Layout.AUTO, sh)

        jfn = jax.jit(fn,
                      in_shardings=([fmtf(s) for s in t_sh],
                                    [fmtf(s) for s in a_sh],
                                    jax.tree_util.tree_map(
                                        fmtf, self._state_shardings))
                      + tail_shardings,
                      out_shardings=(loss_sh, [fmtf(s) for s in t_sh],
                                     [fmtf(s) for s in a_sh],
                                     jax.tree_util.tree_map(
                                         fmtf, self._state_shardings))
                      + out_tail,
                      donate_argnums=donate)
        cache = self._autoformat_cache

        def wrapper(*args):
            leaves, treedef = jax.tree_util.tree_flatten(args[3:])
            key = (id(jfn), treedef,
                   tuple((l.shape, str(l.dtype)) for l in leaves))
            comp = cache.get(key)
            if comp is None:
                # AUTO-layout args must lower from abstract ShapeDtypeStructs,
                # not concrete arrays (which carry a fixed layout)
                def sds(a):
                    return jax.ShapeDtypeStruct(a.shape, a.dtype)
                abstract = tuple(jax.tree_util.tree_map(sds, args[i])
                                 for i in range(3))
                from ..telemetry import compile_ledger as _ledger
                try:
                    mesh_shape = dict(self._mesh.mesh.shape)
                except Exception:
                    mesh_shape = {}
                comp = _ledger.lower_and_compile(
                    jfn, tuple(abstract) + tuple(args[3:]),
                    site="train_step",
                    key={"mesh": mesh_shape,
                         "mesh_devices": int(self._mesh.size),
                         "dtype": str(self._compute_dtype),
                         "data_sig": repr(key[2])[:200]})
                cache[key] = comp
            if cache.get("owner") is not comp:
                # move the carried state into THIS executable's formats; keep
                # the re-placed arrays in locals until the donating call has
                # RETURNED — if it raises mid-step (e.g. device OOM), the
                # trainer still holds the original un-donated state and can
                # retry (ADVICE r5: persisting before the call left
                # self._params pointing at deleted donated buffers)
                _DONATED_REPLACE.inc()
                informats = comp.input_formats[0]
                placed = tuple(
                    jax.tree_util.tree_map(jax.device_put, args[i],
                                           informats[i])
                    for i in range(3))
                out = comp(*(placed + args[3:]))
                # persist only after success so later dispatches skip the
                # transfer (the caller immediately overwrites with outputs)
                for j, i in enumerate(self._trainable_idx):
                    self._params[i] = placed[0][j]
                for j, i in enumerate(self._aux_idx):
                    self._params[i] = placed[1][j]
                self._opt_states = list(placed[2])
                cache["owner"] = comp
                return out
            return comp(*args)

        return wrapper

    # ------------------------------------------------------------------
    def step(self, x, y, *extras):
        """Run one fused training step; returns the (scalar) loss NDArray."""
        from ..ops.registry import _profiler_running
        examples = _leading_dim(x)
        with _telemetry.span("train.step", examples=examples) as sp:
            if _profiler_running():
                from .. import profiler
                out = profiler._dispatch_profiled(
                    "ParallelTrainStep", lambda: self._step_impl(x, y, *extras))
            else:
                out = self._step_impl(x, y, *extras)
        _STEPS.inc()
        _EXAMPLES.inc(examples)
        _STEP_LATENCY.observe(sp.dur_us)
        _telemetry.perf_sentinel.observe("train_step", sp.dur_us)
        return out

    def _step_impl(self, x, y, *extras):
        import jax
        import jax.numpy as jnp
        if not isinstance(y, (tuple, list, NDArray)) and not hasattr(y, "shape"):
            raise MXNetError(
                "labels must be an array or a flat tuple/list of arrays "
                f"(matching the loss signature); got {type(y).__name__}")
        x = x.data if isinstance(x, NDArray) else jnp.asarray(x)
        y = jax.tree_util.tree_map(
            lambda a: a.data if isinstance(a, NDArray) else jnp.asarray(a), y,
            is_leaf=lambda a: isinstance(a, NDArray))
        extras = tuple(e.data if isinstance(e, NDArray) else jnp.asarray(e)
                       for e in extras)
        x = jax.device_put(x, self._data_sharding)
        y = jax.device_put(y, self._label_sharding)
        extras = tuple(jax.device_put(e, sh)
                       for e, sh in zip(extras, self._extra_shardings))
        injected = None
        if self._guard is not None:
            # the guard's input shim: consumes injected numerics faults and
            # applies the corruption they simulate (no-op in production)
            x, y, injected = self._guard.intercept(x, y)
        self._t += 1
        if self._optimizer.lr_scheduler is not None:
            self._optimizer.num_update = self._t
        lrs = jnp.asarray([self._optimizer._get_lr(i) for i in self._trainable_idx],
                          dtype=jnp.float32)
        wds = jnp.asarray([self._optimizer._get_wd(i) for i in self._trainable_idx],
                          dtype=jnp.float32)
        from .. import random as _rng
        key = _rng.take_key()

        # retryable device call: the key/lr/wd inputs are fixed before the
        # loop so a retried attempt is numerically identical; carried state
        # is re-read from self._params per attempt (persisted only after
        # success), so after _pre_retry re-places it the retry uses the
        # re-placed buffers
        def attempt():
            _faults.check("train_step")
            if self._step_fn is None:
                self._build()
            train = [self._params[i] for i in self._trainable_idx]
            aux = [self._params[i] for i in self._aux_idx]
            return self._step_fn(
                train, aux, self._opt_states, x, y, extras, key, lrs, wds,
                jnp.float32(self._t))

        out = self._retry.run(attempt, site="train_step",
                              on_retry=self._pre_retry)
        if self._guard is not None:
            loss, new_train, new_aux, new_states, health = out
        else:
            loss, new_train, new_aux, new_states = out
        for j, i in enumerate(self._trainable_idx):
            self._params[i] = new_train[j]
        for j, i in enumerate(self._aux_idx):
            self._params[i] = new_aux[j]
        self._opt_states = new_states
        if self._guard is not None:
            # report retained DEVICE values only — the guard reads them
            # lazily at its next boundary, never here on the hot path
            self._guard.observe(x=x, y=y, extras=extras, key=key, lrs=lrs,
                                wds=wds, t=self._t, loss=loss, health=health,
                                injected=injected)
        return _mk_nd(loss)

    __call__ = step

    def step_n(self, xs, ys, *extras_s):
        """Run K fused training steps as ONE XLA computation (lax.scan over
        the step body, carrying params/optimizer state on device).

        Inputs carry a leading K axis (K stacked microbatches); returns the
        per-step losses as a (K,) NDArray. Use for latency-sensitive loops:
        one host dispatch per K steps instead of per step.

        Matches K separate ``step()`` calls exactly for deterministic models
        (incl. lr schedules and Adam's t); models with in-graph randomness
        (Dropout) consume split subkeys of one key instead of K session keys,
        so the random streams differ (both are valid dropout masks). In the
        same way the same key gives other masks on another mesh size: where
        the batch is divided over n > 1 devices each shard's rows are drawn
        from the key folded with the shard's index (``ops/nn.py:dropout``).
        On one mesh the same key gives the same masks, here as in
        ``step``."""
        from ..ops.registry import _profiler_running
        k = _leading_dim(xs)
        examples = _leading_dim(xs, axis=1) * k if k else 0
        with _telemetry.span("train.step_n", steps=k,
                             examples=examples) as sp:
            if _profiler_running():
                from .. import profiler
                out = profiler._dispatch_profiled(
                    "ParallelTrainStep.step_n",
                    lambda: self._step_n_impl(xs, ys, *extras_s))
            else:
                out = self._step_n_impl(xs, ys, *extras_s)
        _STEPS.inc(k)
        _EXAMPLES.inc(examples)
        _STEP_LATENCY.observe(sp.dur_us)
        _telemetry.perf_sentinel.observe("train_step", sp.dur_us)
        return out

    def _step_n_impl(self, xs, ys, *extras_s):
        import jax
        import jax.numpy as jnp
        if self._guard is not None:
            raise MXNetError(
                "step_n() is not supported with a NumericsGuard attached: "
                "the guard's skip/rewind recovery needs per-step batch "
                "retention and key accounting — drive the loop with step()")
        xs = xs.data if isinstance(xs, NDArray) else jnp.asarray(xs)
        n = int(xs.shape[0])
        ys = jax.tree_util.tree_map(
            lambda a: a.data if isinstance(a, NDArray) else jnp.asarray(a), ys,
            is_leaf=lambda a: isinstance(a, NDArray))
        extras_s = tuple(e.data if isinstance(e, NDArray) else jnp.asarray(e)
                         for e in extras_s)
        xs = jax.device_put(xs, self._stacked(self._data_sharding))
        ys = jax.device_put(ys, self._stacked(self._label_sharding))
        extras_s = tuple(jax.device_put(e, self._stacked(sh))
                         for e, sh in zip(extras_s, self._extra_shardings))
        t0 = self._t
        self._t += n
        # per-inner-step lr/wd schedule rows, exactly as step() would see them
        lrs_rows, wds_rows = [], []
        for t in range(t0 + 1, t0 + n + 1):
            if self._optimizer.lr_scheduler is not None:
                self._optimizer.num_update = t
            lrs_rows.append([self._optimizer._get_lr(i)
                             for i in self._trainable_idx])
            wds_rows.append([self._optimizer._get_wd(i)
                             for i in self._trainable_idx])
        lrs_k = jnp.asarray(lrs_rows, dtype=jnp.float32)
        wds_k = jnp.asarray(wds_rows, dtype=jnp.float32)
        from .. import random as _rng
        key = _rng.take_key()

        def attempt():
            _faults.check("train_step")
            fn = self._step_n_fns.get(n) or self._build_n(n)
            train = [self._params[i] for i in self._trainable_idx]
            aux = [self._params[i] for i in self._aux_idx]
            return fn(train, aux, self._opt_states, xs, ys, extras_s, key,
                      lrs_k, wds_k, jnp.float32(t0 + 1))

        losses, new_train, new_aux, new_states = self._retry.run(
            attempt, site="train_step", on_retry=self._pre_retry)
        for j, i in enumerate(self._trainable_idx):
            self._params[i] = new_train[j]
        for j, i in enumerate(self._aux_idx):
            self._params[i] = new_aux[j]
        self._opt_states = new_states
        return _mk_nd(losses)

    def place_batch_n(self, xs, ys, *extras_s):
        """place_batch for stacked (K, ...) multi-step inputs."""
        import jax
        import jax.numpy as jnp
        xs = jax.device_put(
            jnp.asarray(xs.data if isinstance(xs, NDArray) else xs),
            self._stacked(self._data_sharding))
        ys = jax.tree_util.tree_map(
            lambda a: jax.device_put(
                jnp.asarray(a.data if isinstance(a, NDArray) else a),
                self._stacked(self._label_sharding)), ys,
            is_leaf=lambda a: isinstance(a, NDArray))
        extras_s = tuple(
            jax.device_put(jnp.asarray(e.data if isinstance(e, NDArray) else e),
                           self._stacked(sh))
            for e, sh in zip(extras_s, self._extra_shardings))
        return (xs, ys) + extras_s

    def place_batch(self, x, y, *extras):
        """Pre-place a batch on the mesh with the step's input shardings (for
        input pipelines/benchmarks: subsequent step() calls see already-placed
        arrays and skip the host transfer)."""
        import jax
        import jax.numpy as jnp
        x = jax.device_put(jnp.asarray(x.data if isinstance(x, NDArray) else x),
                           self._data_sharding)
        y = jax.tree_util.tree_map(
            lambda a: jax.device_put(
                jnp.asarray(a.data if isinstance(a, NDArray) else a),
                self._label_sharding), y,
            is_leaf=lambda a: isinstance(a, NDArray))
        extras = tuple(
            jax.device_put(jnp.asarray(e.data if isinstance(e, NDArray) else e), sh)
            for e, sh in zip(extras, self._extra_shardings))
        return (x, y) + extras

    # ------------------------------------------------------------------
    # resilience: numerics guard + retry guard + checkpoint surface
    # ------------------------------------------------------------------
    def _attach_numerics_guard(self, guard):
        """Bind a resilience.numerics.NumericsGuard (use ``guard.attach``).
        Invalidates the compiled step so the next dispatch rebuilds it with
        the fused health outputs."""
        self._guard = guard
        self._step_fn = None
        self._autoformat_cache.clear()

    def replay_exact(self, x, y, extras, key, lrs, wds, t):
        """Re-execute ONE step with explicit inputs (the retained batch, the
        exact RNG key and schedule rows it originally consumed) and persist
        the outputs — the SDC-screening / repro-bundle path. Unlike
        :meth:`step` this takes no key from the global chain and does not
        advance schedules beyond ``t``."""
        import jax.numpy as jnp
        if self._step_fn is None:
            self._build()
        train = [self._params[i] for i in self._trainable_idx]
        aux = [self._params[i] for i in self._aux_idx]
        out = self._step_fn(train, aux, self._opt_states, x, y,
                            tuple(extras), key, lrs, wds, jnp.float32(t))
        if self._guard is not None:
            loss, new_train, new_aux, new_states, _health = out
        else:
            loss, new_train, new_aux, new_states = out
        for j, i in enumerate(self._trainable_idx):
            self._params[i] = new_train[j]
        for j, i in enumerate(self._aux_idx):
            self._params[i] = new_aux[j]
        self._opt_states = new_states
        self._t = int(t)
        return _mk_nd(loss)

    def _pre_retry(self, exc, attempt, delay_s):
        """RetryPolicy hook: a retry is only sound while the carried state
        still exists — a real OOM that fired AFTER donation consumed the
        input buffers leaves nothing to re-run with (that state is only
        persisted post-success, so the checkpoint is the recovery path).
        Otherwise re-place the carried state onto its shardings (a no-op
        device_put when placement survived)."""
        import jax
        leaves = list(self._params)
        for st in self._opt_states:
            leaves.extend(jax.tree_util.tree_leaves(st))
        for a in leaves:
            if getattr(a, "is_deleted", None) is not None and a.is_deleted():
                raise MXNetError(
                    "cannot retry train step: donated carried state was "
                    "consumed by the failed call; restore from the latest "
                    "checkpoint (resilience.CheckpointManager) instead"
                ) from exc
        self._params = [jax.device_put(a, sh) for a, sh in
                        zip(self._params, self._param_shardings)]
        self._opt_states = [
            jax.tree_util.tree_map(jax.device_put, st, sh)
            for st, sh in zip(self._opt_states, self._state_shardings)]
        # the autoformat owner's layouts may no longer match the re-placed
        # state; drop ownership so the next call re-places into the
        # executable's formats
        self._autoformat_cache.pop("owner", None)

    def state_dict(self) -> Dict:
        """Host snapshot of the carried training state: every parameter
        (trainable + aux), the optimizer state trees, and the step counter
        ``t`` — the fused-step third of a full training checkpoint
        (CheckpointManager composes it with RNG/dataloader/meta state)."""
        import jax
        params = {f"p{i}": onp.asarray(jax.device_get(a))
                  for i, a in enumerate(self._params)}
        opt = {}
        for j, st in enumerate(self._opt_states):
            leaves = jax.tree_util.tree_leaves(st)
            opt[f"s{j}"] = {f"l{k}": onp.asarray(jax.device_get(leaf))
                            for k, leaf in enumerate(leaves)}
        return {"kind": "ParallelTrainStep", "version": 1, "t": int(self._t),
                "n_params": len(self._params),
                "param_names": ",".join(p.name for p in self._plist),
                "params": params, "opt": opt}

    def shard_state_dict(self) -> Dict:
        """Sharded twin of :meth:`state_dict`: every on-mesh leaf is captured
        as its per-device shards (``resilience.sharding.ShardedLeaf``) instead
        of a gathered host array — this process snapshots only the shards its
        own devices hold, so no host ever materializes the full state. The
        CheckpointManager writes these as per-device shard files;
        :meth:`load_state_dict` consumes the re-assembled restore unchanged
        (the assembled tree is layout-independent), re-sharding onto THIS
        step's mesh — which may be a different device count or shape than
        the mesh that saved (elastic restore)."""
        from ..resilience.sharding import ShardedLeaf
        devpos = self._mesh.device_positions()

        def leafcap(a):
            if hasattr(a, "addressable_shards"):
                return ShardedLeaf.from_array(a, devpos)
            return onp.asarray(a)

        import jax
        params = {f"p{i}": leafcap(a) for i, a in enumerate(self._params)}
        opt = {}
        for j, st in enumerate(self._opt_states):
            leaves = jax.tree_util.tree_leaves(st)
            opt[f"s{j}"] = {f"l{k}": leafcap(leaf)
                            for k, leaf in enumerate(leaves)}
        return {"kind": "ParallelTrainStep", "version": 1, "t": int(self._t),
                "n_params": len(self._params),
                "param_names": ",".join(p.name for p in self._plist),
                "mesh_devices": int(self._mesh.size),
                "params": params, "opt": opt}

    def load_state_dict(self, state: Dict):
        """Restore a :meth:`state_dict` snapshot into this step (same model
        topology/optimizer required). Carried state is re-placed onto the
        mesh with this step's shardings; a subsequent step continues
        bitwise-identically to the run that saved the snapshot."""
        import jax
        if state.get("kind") != "ParallelTrainStep":
            raise MXNetError(f"not a ParallelTrainStep state: "
                             f"{state.get('kind')!r}")
        if int(state["n_params"]) != len(self._params):
            raise MXNetError(
                "checkpoint does not match this model: expected "
                f"{len(self._params)} params, got {state['n_params']} "
                f"({state.get('param_names')})")
        loaded = []
        for i, (p, sh) in enumerate(zip(self._plist, self._param_shardings)):
            arr = onp.asarray(state["params"][f"p{i}"])
            if tuple(arr.shape) != tuple(p.shape):
                # param names carry per-process counters (dense0 vs dense1),
                # so identity is checked structurally: position + shape
                raise MXNetError(
                    f"checkpoint param {i} ({p.name}) shape mismatch: "
                    f"{arr.shape} vs {tuple(p.shape)}")
            loaded.append(jax.device_put(arr, sh))
        self._params = loaded
        new_states = []
        for j, (st, sh) in enumerate(zip(self._opt_states,
                                         self._state_shardings)):
            leaves, treedef = jax.tree_util.tree_flatten(st)
            saved = state["opt"][f"s{j}"]
            if len(saved) != len(leaves):
                raise MXNetError(f"optimizer state {j} arity mismatch: "
                                 f"{len(saved)} vs {len(leaves)}")
            sh_leaves = jax.tree_util.tree_flatten(sh)[0]
            placed = [jax.device_put(onp.asarray(saved[f"l{k}"]), s)
                      for k, s in enumerate(sh_leaves)]
            new_states.append(jax.tree_util.tree_unflatten(treedef, placed))
        self._opt_states = new_states
        self._t = int(state["t"])
        self._autoformat_cache.pop("owner", None)
        if self._guard is not None:
            # retained window records predate the restored state; replaying
            # them over it would corrupt the run — re-anchor instead
            self._guard.reset()

    # ------------------------------------------------------------------
    def sync_to_block(self):
        """Write the on-mesh parameter values back into the Gluon block
        (single-host gather; the checkpoint path)."""
        import jax
        for p, arr in zip(self._plist, self._params):
            gathered = jax.device_get(arr)
            for ctx, nd in p._data.items():
                nd._set_data(jax.numpy.asarray(gathered, dtype=nd.data.dtype))

    @property
    def params(self):
        return list(self._params)

    @property
    def mesh(self):
        return self._mesh
