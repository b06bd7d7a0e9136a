"""DecodeEndpoint: one generative model plus its paged KV pool and the two
AOT executable families decode needs.

Per the endpoint design (serving/endpoint.py), everything rides as
executable *arguments* — params, token ids, page tables, and the KV pool
arrays themselves — so the compiled programs are independent of weights and
cache contents. Two families, both routed through
``compile_ledger.lower_and_compile`` so the ledger's duplicate-fingerprint
accounting covers decode traffic:

- **prefill**, bucketed by sequence length (``seq_buckets`` ladder): one
  full causal forward of a single prompt (the block's
  ``prefill_collect(tokens, last)`` traced via ``pure_apply(...,
  method=...)``), writing every layer's K/V into the sequence's pages and
  returning the first generated token, the arg-max of the one row ``last``.
- **decode-step**, bucketed by batch size (pow2 ladder): one token for every
  running sequence — run ``TransformerLM.decode_step`` on the pools and the
  rows' page tables, write the new K/V row, greedy-argmax the next token on
  device. Every layer attends to its rows' cached context where it lies:
  ``ops/pallas/paged_attention`` reads each lane's live pages once, in the
  pool, up to the lane's own length, and the step's own rows, which are
  written only after the last layer, are a second, dense part of the same
  softmax. Nothing of the pool's or of all lanes' size is gathered, copied
  or rewritten in a step.

A block may state ``block_length`` L > 1 and a ``mask_token_id`` (generation
by diffusion over blocks, ``gluon.model_zoo.moe_lm``): the step is then 2L
rows a sequence under the block's own mask, for lanes at any phase of their
blocks: *slot 0*, the sequence's block at its position, in progress or whole,
and *slot 1*, the block behind it, which sees slot 0 and the cached context
as it would have seen them in the pool a forward later. Each lane's flag says
whether this forward *commits* slot 0 (writes its K/V in place; a whole
block's) or is a denoising step whose keys saw mask tokens and are dropped;
slot 1 is never written. What comes back is L rows a lane, slot 1's where the
forward commits slot 0 (the next block's first denoising step, in the
forward that commits this one) and slot 0's otherwise, chosen before the
final norm and the head, and per row the arg-max id other than the mask
token and its float32 softmax probability, never the logits. There is one
step program a bucket: a forward that reads slot 0 computes slot 1 and drops
it (the forward is bound by the weights it reads, whatever its rows). The pool's row
is the block's ``kv_units`` (default ``units``) and its dtype the
parameters'. A block that states ``kv_latent`` caches one row a position
that serves as keys and values (latent attention: ``gluon.model_zoo.mla_lm``):
its pool is one array and not two, its ``prefill_collect`` returns one row
set a layer and its ``decode_step`` takes the one pool, and the executables
carry, write and donate that one array; everything else is the same. A block
that states ``cache_groups`` (``(name, layers, window)`` a group: layers that
keep every position of a sequence and layers that keep a window of it) gets
a pool of as many groups (``kv_cache.py``): the executables then carry each
group's arrays and, in place of the one table a lane, each group's, a window
group's being a ring; a prefill writes a window group the prompt's last
window's pages alone. ``prefill_collect`` takes the row to read: every
language model of the zoo states ``prefill_reads_row`` and is given
``length - 1``, so that the head multiplies that one row and not the bucket's.

Bitwise contract: every model op is per-row and masked lanes carry exactly
zero softmax weight, so a row's output depends only on its own tokens and
pages — not on batch composition, bucket size, physical page placement, or
stale pool contents. That is what makes batched continuous decode
bitwise-equal to one-sequence-at-a-time greedy decode (the tier-1 oracle).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as onp

from ... import config as _config
from ... import telemetry as _telemetry
from ...base import Context, MXNetError, current_context
from .. import bucketing
from ..router import StepCostEWMA
from .kv_cache import PagedKVPool, write_prefill, write_step
from .stats import DecodeStats

__all__ = ["DecodeEndpoint"]


def _now_us() -> int:
    return time.perf_counter_ns() // 1000


def _step(block, plist, num_layers, page_size, param_datas, ids, positions,
          tables, valid, *pools):
    """One traced decode step: run the block's ``decode_step`` on its rows a
    lane (``ids``/``positions`` (B,) for L = 1, else (B, 2L): slot 0 and
    slot 1) against the ``pools`` (K and V, or the one latent array) as they
    stand, read through ``tables``; then write the rows' K/V (slot 0's L
    rows) in place for the lanes ``valid`` flags. Returns (logits (of L rows
    a lane: slot 1's where ``valid``, else slot 0's), whatever the block
    returned after its K/V, the pools)."""
    import jax.numpy as jnp
    from ...gluon.block import pure_apply
    by_group = tables if isinstance(tables, tuple) else (tables,)
    n = len(pools) // len(by_group)     # arrays a group: K and V, or one
    last = 1 + n * num_layers
    L = int(getattr(block, "block_length", 1))
    if L == 1:
        outs, _, _ = pure_apply(block, plist, param_datas,
                                (ids, positions, *pools, *by_group), None,
                                training=False, method="decode_step")
    else:
        reads = jnp.where(valid, L, 0)[:, None] + jnp.arange(L)
        outs, _, _ = pure_apply(block, plist, param_datas,
                                (ids, positions, reads, *pools, *by_group),
                                None, training=False,
                                method="decode_step_reading")
        # slot 0's rows alone are ever written
        positions = positions[:, :L]
        outs = outs[:1] + tuple(a[:, :L] for a in outs[1:last]) + outs[last:]
    written = ()
    for g, (layers, window) in enumerate(_groups_of(block, num_layers)):
        written += write_step(
            pools[g * n:(g + 1) * n], _group_rows(outs, layers, n),
            by_group[g], positions, valid, page_size,
            ring=window is not None)
    return outs[0], outs[last:], written


def _groups_of(block, num_layers):
    """((layers, window), ...) of the block's cache groups; one group of all
    layers that keeps everything where it states none."""
    stated = getattr(block, "cache_groups", None)
    if not stated:
        return ((tuple(range(num_layers)), None),)
    return tuple((tuple(layers), window) for _, layers, window in stated)


def _group_rows(outs, layers, n, of=slice(None)):
    """The rows ``layers`` return for the cache, stacked (layers, ...) per
    array of a group (``of``: of one sequence of the batch): ``outs`` holds,
    after its first entry, ``n`` row sets a layer, layer after layer."""
    import jax.numpy as jnp
    return tuple(jnp.stack([outs[1 + n * l + j] for l in layers], 0)[:, of]
                 for j in range(n))


def _candidates(logits, mask_id):
    """Per row of (..., V) logits: (the arg-max id other than the mask
    token, its softmax probability), both in float32 arithmetic."""
    import jax.numpy as jnp
    logits = logits.astype(jnp.float32)
    logits = jnp.where(jnp.arange(logits.shape[-1]) == mask_id, -jnp.inf,
                       logits)
    top = logits.max(-1, keepdims=True)
    conf = 1.0 / jnp.exp(logits - top).sum(-1)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), conf


def _prefill(block, plist, page_size, causal, param_datas, tokens, length,
             table, *pools):
    """The traced prefill of one prompt: ``prefill_collect`` over the bucket's
    rows, every layer's rows written into its group's pages, and the first
    generated token (of a causal model). Returns (next id (1,), the pools)."""
    import jax.numpy as jnp
    from ...gluon.block import pure_apply
    groups = _groups_of(block, int(block.num_layers))
    # the head for the row that is read, where the block takes it
    reads_row = causal and getattr(block, "prefill_reads_row", False)
    outs, _, _ = pure_apply(
        block, plist, param_datas,
        (tokens, length - 1) if reads_row else (tokens,),
        None, training=False, method="prefill_collect")
    logits = outs[0]            # (1, S, V); (1, 1, V) of the row read
    by_group = table if isinstance(table, tuple) else (table,)
    n = len(pools) // len(groups)   # K and V rows a layer, or the latent's
    written = ()
    for g, (layers, window) in enumerate(groups):
        rows = _group_rows(outs, layers, n, 0)         # (layers, S, kv)
        written += write_prefill(
            pools[g * n:(g + 1) * n], rows, by_group[g][0], length[0],
            page_size, window=window)
    if reads_row:
        next_id = jnp.argmax(logits[0, 0]).astype(jnp.int32)
    elif causal:
        next_id = jnp.argmax(logits[0, length[0] - 1]).astype(jnp.int32)
    else:
        # a block's first tokens come from its first denoising step: the
        # head's product is never computed here
        next_id = jnp.zeros((), jnp.int32)
    return (next_id.reshape(1), *written)


def _decode(block, plist, page_size, mask_id, param_datas, ids, positions,
            tables, valid, *pools):
    """The traced decode step: :func:`_step`, then what the host needs of the
    logits: the arg-max ids (of a block step the candidates and their
    confidences) and, where the model routes experts, two numbers of their
    load. Returns (that, the pools)."""
    import jax.numpy as jnp
    logits, aux, pools = _step(
        block, plist, int(block.num_layers), page_size, param_datas, ids,
        positions, tables, valid, *pools)
    if mask_id is None:
        picked = (jnp.argmax(logits, axis=-1).astype(jnp.int32),)
    else:
        picked = _candidates(logits, mask_id)
    if aux:     # rows routed to each expert, (layers, E):
        # the busiest's (mean over layers) and the mean
        load = aux[0].astype(jnp.float32)
        picked += (load.max(-1).mean(), load.mean())
    if len(picked) == 1:
        picked = picked[0]
    return (picked, *pools)


class _Launched:
    """An executable call whose result is still on the chip, from
    ``launch_prefill`` / ``launch_step`` to its ``finish_*``: the bucket it
    ran at, when it was launched (the cost EWMAs observe launch to result
    in hand, whatever the host did between), the span it was launched
    under, which its ``decode.fetch`` hangs under too (the benchmark's
    readers pair a launch with the fetch of the same parent), and what the
    counters report of it."""

    __slots__ = ("bucket", "t0", "under", "result", "overlapped", "lanes",
                 "commits", "ctx_live", "ctx_by_group")

    def __init__(self, bucket: int, *, overlapped: bool = False,
                 lanes: int = 1, commits: int = 0, ctx_live: int = 0,
                 ctx_by_group: tuple = ()):
        self.bucket = bucket
        self.t0 = _now_us()
        self.under = _telemetry.current_span()
        self.result = None
        self.overlapped = overlapped    # a prefill's: a step was in flight
        self.lanes = lanes
        self.commits = commits
        self.ctx_live = ctx_live
        self.ctx_by_group = ctx_by_group    # positions each group's layers read

    def arrays(self) -> tuple:
        return self.result if isinstance(self.result, tuple) \
            else (self.result,)

    def send_home(self):
        """Start the result's copy to the host now, behind its call on the
        chip: the fetch then waits for the chip alone. Asked for at the
        fetch, each array is a round trip of its own once the chip is done
        (0.6 ms on a v5e; a block step returns four)."""
        for a in self.arrays():
            a.copy_to_host_async()

    def ready(self) -> int:
        """1 where the whole result is computed already: a fetch that
        begins so waits for nothing on the chip, and what it still takes is
        the host's (the copy home, the interpreter lock); one that begins
        before is a wait for the chip, which a reader of the pass's time off
        the processor takes off it."""
        return int(all(a.is_ready() for a in self.arrays()))


class DecodeEndpoint:
    """A named generative model with bucketed prefill/decode executables.

    ``block`` must expose the incremental-decode protocol of
    ``gluon.model_zoo.bert.TransformerLM``: ``num_layers``/``units``
    attributes, ``prefill_collect(tokens, last)`` and
    ``decode_step(ids, positions, k_pool, v_pool, tables)``.
    ``prefill_collect(tokens, last)`` is the protocol: ``last`` (B,) is the
    row a prompt whose logits are read, (B, 1, V), and the block states
    ``prefill_reads_row``; a block without it is served by the old reading
    (``prefill_collect(tokens)``, ``logits[0, length - 1]``) for
    compatibility alone. Optionally ``kv_units``, ``kv_latent``,
    ``cache_groups``, ``block_length`` and ``mask_token_id`` (module
    docstring; with them
    ``decode_step_reading(ids, positions, rows, *cache)``, whose logits are
    of ``rows`` (B, L) alone), and after the
    layers' K/V a ``decode_step`` may return the rows routed to each expert,
    (layers, experts), which the step reduces to two numbers.

    Device work (``prefill``/``decode_step``/``warmup``/pool mutation)
    follows the serving single-dispatcher rule: one thread — the decode
    scheduler's worker — runs it.
    """

    def __init__(self, name: str, block, *, max_seq_len: int = 128,
                 max_batch_size: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 decode_buckets: Optional[Sequence[int]] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 ctx: Optional[Context] = None):
        self.name = name
        self.block = block
        self.ctx = ctx if ctx is not None else current_context()
        self.max_seq_len = int(max_seq_len)
        if max_batch_size is None:
            max_batch_size = int(_config.get("MXNET_DECODE_MAX_BATCH"))
        self.max_batch_size = int(max_batch_size)
        if self.max_batch_size < 1:
            raise MXNetError("max_batch_size must be >= 1")
        if decode_buckets is None:
            decode_buckets = bucketing.pow2_buckets(self.max_batch_size)
        self.decode_buckets = bucketing.validate_buckets(
            decode_buckets, self.max_batch_size)
        self.prefill_buckets = bucketing.seq_buckets(
            self.max_seq_len, ladder=prefill_buckets)
        self.block_length = int(getattr(block, "block_length", 1))
        self.mask_token_id = getattr(block, "mask_token_id", None)
        if (self.block_length > 1) != (self.mask_token_id is not None):
            raise MXNetError(
                f"decode endpoint {name!r}: blocks of {self.block_length} "
                f"positions with mask token {self.mask_token_id!r}; a model "
                "generated by diffusion over blocks states both")
        max_len = getattr(block, "max_length", None)
        if max_len is not None and self.max_seq_len > int(max_len):
            raise MXNetError(
                f"max_seq_len={self.max_seq_len} exceeds the model's "
                f"position-embedding table ({max_len})")

        self.stats = DecodeStats(name)
        # per-bucket measured means (us), seeded by warmup
        self.step_cost = StepCostEWMA(      # per decode batch bucket
            name=f"{name}.decode")
        self.prefill_cost = StepCostEWMA(   # per prefill seq bucket
            name=f"{name}.prefill")
        self._lock = threading.Lock()
        self._prefill_execs: Dict[int, object] = {}
        self._decode_execs: Dict[int, object] = {}
        self._pf_jfn = None
        self._dec_jfn = None
        self.last_step: Dict[str, object] = {}
        self._step_in_flight = False    # between launch_step and finish_step
        self._probe()
        stated = getattr(block, "cache_groups", None)
        if stated and self.block_length > 1:
            raise MXNetError(
                f"decode endpoint {name!r}: cache groups under blocks of "
                f"{self.block_length} positions (a window is the causal "
                "step's)")
        self.pool = PagedKVPool(name, int(block.num_layers),
                                int(getattr(block, "kv_units", block.units)),
                                self.max_seq_len,
                                page_size=page_size, num_pages=num_pages,
                                dtype=self._param_datas()[0].dtype,
                                device=self.ctx.jax_device(),
                                latent=bool(getattr(block, "kv_latent",
                                                    False)),
                                **({} if not stated else {
                                    "groups": [(g, len(layers), window) for
                                               g, layers, window in stated],
                                    "max_seqs": self.max_batch_size}))
        if self.max_seq_len % self.block_length \
                or self.pool.page_size % self.block_length:
            raise MXNetError(
                f"decode endpoint {name!r}: block_length "
                f"{self.block_length} must divide max_seq_len "
                f"{self.max_seq_len} and the page size "
                f"{self.pool.page_size} (a block lies in one page)")

    # ------------------------------------------------------------------
    def _probe(self):
        """Validates the block's decode protocol; one eager prefill-bucket
        forward where a parameter's initialisation is still deferred."""
        from ... import autograd
        from ...ndarray.ndarray import NDArray
        for attr in ("num_layers", "units", "prefill_collect", "decode_step"):
            if not hasattr(self.block, attr):
                raise MXNetError(
                    f"decode endpoint {self.name!r}: block lacks the "
                    f"incremental-decode protocol member {attr!r} "
                    "(see gluon.model_zoo.bert.TransformerLM)")
        self._params = list(self.block.collect_params().values())
        if any(p._data is None for p in self._params):
            dummy = NDArray(
                onp.zeros((1, self.prefill_buckets[0]), onp.int32),
                ctx=self.ctx)
            with autograd._RecordingStateScope(False, False):
                self.block(dummy)
        from ...telemetry import memstats as _memstats
        _memstats.register(
            "serving", f"{self.name}.params", owner=self,
            device=self._device_label(),
            sizer=lambda ep: _memstats.nbytes_of(ep._param_datas()))

    def _device_label(self) -> str:
        try:
            d = self.ctx.jax_device()
            return f"{d.platform}:{d.id}"
        except (AttributeError, RuntimeError, ValueError, ImportError):
            return ""

    def _donate_pools(self) -> bool:
        """Donate the KV pool arguments on backends with buffer donation:
        the pool is the largest recurring operand and every step consumes
        the previous step's arrays. Donation lets the output share the
        input's buffer; that the update is in place on TPU besides — no
        pool-sized copy in the program — is the doing of kv_cache's
        ``dynamic_update_slice`` writes. CPU warns on donation — keep it
        off there."""
        return self._platform() in ("tpu", "gpu")

    def _platform(self) -> str:
        """Platform of the device(s) the executables run on. Sharded twins
        answer from their mesh, not from ``ctx``."""
        return self.ctx.jax_device().platform

    def _param_datas(self):
        return tuple(p.data(self.ctx).data for p in self._params)

    def _adopt_compiled(self, comp):
        """Hook: inspect a just-obtained executable before first use.
        Sharded twins adopt a cache-deserialized executable's device
        assignment here; the single-device path needs nothing."""

    def _jit_prefill(self, fn, donate):
        """Wrap the traced prefill, pinned to the context's device (see
        ModelEndpoint._jit_infer); sharded twins pin their mesh instead."""
        import jax
        dev = jax.sharding.SingleDeviceSharding(self.ctx.jax_device())
        return jax.jit(fn, donate_argnums=donate, in_shardings=dev,
                       out_shardings=dev)

    def _jit_decode(self, fn, donate):
        """Wrap the traced decode step; same pinning as the prefill."""
        return self._jit_prefill(fn, donate)

    # ------------------------------------------------------------------
    # traced programs
    # ------------------------------------------------------------------
    def _prefill_fn(self):
        if self._pf_jfn is None:
            block, plist = self.block, self._params
            page_size = int(_config.get("MXNET_KV_PAGE_SIZE")) \
                if not hasattr(self, "pool") else self.pool.page_size
            causal = self.mask_token_id is None

            def prefill(param_datas, tokens, length, table, *pools):
                return _prefill(block, plist, page_size, causal, param_datas,
                                tokens, length, table, *pools)

            donate = self._pool_args(4) if self._donate_pools() else ()
            self._pf_jfn = self._jit_prefill(prefill, donate)
        return self._pf_jfn

    def _decode_fn(self):
        if self._dec_jfn is None:
            block, plist = self.block, self._params
            page_size = self.pool.page_size
            mask_id = self.mask_token_id

            def decode(param_datas, ids, positions, tables, valid, *pools):
                return _decode(block, plist, page_size, mask_id, param_datas,
                               ids, positions, tables, valid, *pools)

            donate = self._pool_args(5) if self._donate_pools() else ()
            self._dec_jfn = self._jit_decode(decode, donate)
        return self._dec_jfn

    # ------------------------------------------------------------------
    # the bucketed executable caches
    # ------------------------------------------------------------------
    def _pool_sds(self):
        import jax
        return tuple(jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
                     for a in self.pool.arrays)

    def _tables_sds(self, batch: int):
        """The page tables of ``batch`` lanes as an executable takes them:
        one (batch, P) array, or a tuple of them a cache group."""
        import jax
        import jax.numpy as jnp
        sds = tuple(jax.ShapeDtypeStruct((batch, g.pages_per_seq), jnp.int32)
                    for g in self.pool.groups)
        return sds[0] if len(sds) == 1 else sds

    def _pool_args(self, first: int):
        """Argument numbers of the pool's arrays, which follow an
        executable's other arguments from ``first`` on."""
        return tuple(range(first, first + len(self.pool.arrays)))

    @property
    def pool_dtype(self):
        return self.pool.k_pool.dtype

    def _cost_key(self, kind: str, bucket: int) -> Dict[str, object]:
        """The compile-ledger / executable-cache trigger key for one
        (kind, bucket) executable."""
        return {"endpoint": self.name, "kind": kind, "bucket": bucket,
                "dtype": str(self.pool_dtype),
                "device": self._device_label()}

    def _compile(self, cache, bucket, jfn, arg_sds, kind):
        comp = cache.get(bucket)
        if comp is not None:
            return comp
        with self._lock:
            comp = cache.get(bucket)
            if comp is not None:
                return comp
            import jax
            from ...resilience import faults as _faults
            from ...telemetry import compile_ledger as _ledger
            from ...telemetry import memstats as _memstats
            _faults.check("compile")
            param_sds = tuple(
                jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
                for a in self._param_datas())
            with _telemetry.span("serving.compile", endpoint=self.name,
                                 bucket=bucket, kind=kind):
                # compile-once gate (see ModelEndpoint._get_executable):
                # contenders need this executable and wait for it either way
                comp = _ledger.lower_and_compile(  # mxlint: disable=CONC202
                    jfn, (param_sds,) + arg_sds,
                    site=f"decode_{kind}",
                    key=self._cost_key(kind, bucket),
                    expect_donation=self._donate_pools())
            self._adopt_compiled(comp)
            cache[bucket] = comp
            mem = _ledger._memory_analysis(comp)
            _memstats.register(
                "serving", f"{self.name}.{kind}_b{bucket}", owner=self,
                device=self._device_label(),
                nbytes=sum(mem.get(k, 0) for k in
                           ("output_bytes", "temp_bytes", "code_bytes")))
            self.stats.record_compile()
            return comp

    def _get_prefill(self, seq_bucket: int):
        import jax
        import jax.numpy as jnp
        arg_sds = (jax.ShapeDtypeStruct((1, seq_bucket), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32),
                   self._tables_sds(1)) + self._pool_sds()
        return self._compile(self._prefill_execs, seq_bucket,
                             self._prefill_fn(), arg_sds, "prefill")

    @property
    def step_rows(self) -> int:
        """Rows a lane a step forwards: one, or two blocks of
        ``block_length``."""
        return 1 if self.block_length == 1 else 2 * self.block_length

    def _rows_shape(self, batch: int):
        """Shape of a step's ids and positions: a row a lane for one token a
        step, ``step_rows`` a lane otherwise."""
        return (batch,) if self.block_length == 1 \
            else (batch, self.step_rows)

    def _get_decode(self, batch_bucket: int):
        import jax
        import jax.numpy as jnp
        rows = self._rows_shape(batch_bucket)
        arg_sds = (jax.ShapeDtypeStruct(rows, jnp.int32),
                   jax.ShapeDtypeStruct(rows, jnp.int32),
                   self._tables_sds(batch_bucket),
                   jax.ShapeDtypeStruct((batch_bucket,), jnp.bool_)) \
            + self._pool_sds()
        return self._compile(self._decode_execs, batch_bucket,
                             self._decode_fn(), arg_sds, "step")

    def warmup(self, execute: bool = True) -> int:
        """Compile every prefill and decode bucket (and by default execute
        each once to seed the cost EWMAs). Warmup traffic only ever writes
        scratch page 0 — zero page tables, zero valid masks — so it cannot
        perturb a later sequence. Returns the number of executables built."""
        import jax
        n = 0
        P = self.pool.pages_per_seq
        for b in self.prefill_buckets:
            fresh = b not in self._prefill_execs
            comp = self._get_prefill(b)
            if fresh:
                n += 1
                if execute:
                    toks = onp.zeros((1, b), onp.int32)
                    length = onp.asarray([1], onp.int32)
                    table = self.pool.split_tables(
                        onp.zeros((1, P), onp.int32))
                    t0 = _now_us()
                    out = comp(self._param_datas(), toks, length, table,
                               *self.pool.arrays)
                    jax.block_until_ready(out)
                    self.pool.update_arrays(*out[1:])
                    self.prefill_cost.observe(b, _now_us() - t0)
        for b in self.decode_buckets:
            fresh = b not in self._decode_execs
            comp = self._get_decode(b)
            if fresh:
                n += 1
                if execute:
                    ids = onp.zeros(self._rows_shape(b), onp.int32)
                    pos = onp.zeros(self._rows_shape(b), onp.int32)
                    tables = self.pool.split_tables(
                        onp.zeros((b, P), onp.int32))
                    valid = onp.zeros((b,), bool)
                    t0 = _now_us()
                    out = comp(self._param_datas(), ids, pos, tables, valid,
                               *self.pool.arrays)
                    jax.block_until_ready(out)
                    self.pool.update_arrays(*out[1:])
                    self.step_cost.observe(b, _now_us() - t0)
        return n

    # ------------------------------------------------------------------
    # execution (decode-worker thread only)
    # ------------------------------------------------------------------
    def prefill(self, prompt: Sequence[int], table: onp.ndarray) -> int:
        """Run one prompt through its sequence-length bucket's prefill
        executable; the sequence's pages fill with K/V and the first
        generated token comes back."""
        return self.finish_prefill(self.launch_prefill(prompt, table))

    def launch_prefill(self, prompt: Sequence[int],
                       table: onp.ndarray) -> "_Launched":
        """The first half of :meth:`prefill`: pack and launch, nothing
        waited for. The pool is the call's output from here on (see
        :meth:`launch_step`); :meth:`finish_prefill` takes the handle."""
        n = len(prompt)
        S = bucketing.bucket_for(n, self.prefill_buckets)
        comp = self._get_prefill(S)
        with _telemetry.span("decode.pack"):
            toks = onp.zeros((1, S), onp.int32)
            toks[0, :n] = prompt
            length = onp.asarray([n], onp.int32)
        call = _Launched(S, overlapped=self._step_in_flight)
        with _telemetry.span("decode.launch", kind="prefill", bucket=S):
            call.result, *pools = comp(
                self._param_datas(), toks, length,
                self.pool.split_tables(table.reshape(1, -1)),
                *self.pool.arrays)
        self.pool.update_arrays(*pools)
        call.send_home()
        return call

    def finish_prefill(self, call: "_Launched") -> int:
        """The second half: wait for the first generated token."""
        with _telemetry.span("decode.fetch", parent=call.under,
                             kind="prefill", ready=call.ready()):
            out = int(onp.asarray(call.result)[0])     # sync point
            call.result = None      # the device buffer goes here, in a span
        dt = _now_us() - call.t0
        self.prefill_cost.observe(call.bucket, dt)
        self.stats.record_prefill(dt, call.overlapped)
        return out

    def decode_step(self, rows: Sequence[tuple]):
        """One batched decode step. ``rows`` is ``(input_id, position,
        page_table)`` per running sequence; returns the next token id per
        row. Padding rows (bucket fill) carry zero tables and a False valid
        mask — their writes land on scratch page 0.

        With ``block_length`` L > 1 a row is ``(ids, first position,
        page_table, commit)``: the 2L ids of the sequence's current block
        and of the block behind it (mask tokens; padding where the sequence
        ends with this one), and whether this forward writes the current
        block's K/V (a lane that does not is routed to the scratch page like
        a padding row). Returns ``(ids (L,), confidences (L,))`` per row, of
        the block behind where the forward commits and of the current block
        otherwise, and leaves on ``last_step`` what the step's span and
        counters report."""
        return self.finish_step(self.launch_step(rows))

    def launch_step(self, rows: Sequence[tuple]) -> "_Launched":
        """The first half of :meth:`decode_step`: pack and launch, nothing
        waited for. The pools the call returned are installed at once: they
        are futures of the step's writes, and whatever is launched before
        :meth:`finish_step` (a prefill, in the step's shadow) must read and
        donate those, not the arrays this call consumed. The chip runs the
        calls in the order of their launches."""
        n = len(rows)
        L, width = self.block_length, self.step_rows
        B = bucketing.bucket_for(n, self.decode_buckets)
        P = self.pool.pages_per_seq
        comp = self._get_decode(B)
        with _telemetry.span("decode.pack"):
            ids = onp.zeros(self._rows_shape(B), onp.int32)
            pos = onp.zeros(self._rows_shape(B), onp.int32)
            tables = onp.zeros((B, P), onp.int32)
            valid = onp.zeros((B,), bool)
            lanes = onp.arange(width, dtype=onp.int32)
            ctx_live = 0
            for i, row in enumerate(rows):
                ids[i] = row[0]
                pos[i] = row[1] if L == 1 else row[1] + lanes
                tables[i] = row[2]
                valid[i] = len(row) < 4 or row[3]
                ctx_live += row[1]      # the cached positions it attends to
            # the positions each cache group's layers must read of them: all,
            # or no more than the window less the row itself
            by_group = tuple(
                int(ctx_live) if g.window is None else
                sum(min(row[1], g.window - 1) for row in rows)
                for g in self.pool.groups)
            if len(by_group) > 1:
                self.pool.ring_overwrites(row[1] for row in rows)
            tables = self.pool.split_tables(tables)
        call = _Launched(B, lanes=n, commits=int(valid.sum()),
                         ctx_live=int(ctx_live), ctx_by_group=by_group)
        with _telemetry.span("decode.launch", kind="step", bucket=B):
            call.result, *pools = comp(
                self._param_datas(), ids, pos, tables, valid,
                *self.pool.arrays)
        self.pool.update_arrays(*pools)
        call.send_home()
        self._step_in_flight = True
        return call

    def finish_step(self, call: "_Launched"):
        """The second half: wait for the step's result; what
        :meth:`decode_step` returns."""
        n, L = call.lanes, self.block_length
        try:
            with _telemetry.span("decode.fetch", parent=call.under,
                                 kind="step", ready=call.ready()) as sp:
                out = [onp.asarray(a) for a in call.arrays()]  # sync point
                call.result = None  # the device buffers go here, in a span
        finally:
            self._step_in_flight = False
        dt = _now_us() - call.t0
        self.step_cost.observe(call.bucket, dt)
        ctx = (call.ctx_live, n * self.max_seq_len)
        self.last_step = {"commits": call.commits, "ctx_live": ctx[0],
                          "ctx_capacity": ctx[1],
                          "fetch_wait_us": sp.dur_us}
        # what the window layers must read of the live context (a window
        # group's positions; of a model without one, there is no such attr)
        ctx_window = [c for c, g in zip(call.ctx_by_group, self.pool.groups)
                      if g.window is not None]
        if ctx_window:
            self.last_step["ctx_window_live"] = ctx_window[0]
        # the straggler a grouped expert product waits for against the rows
        # an expert gets on average (every row the executable computes is
        # routed, padding lanes too): after the ids (and, of a block step,
        # their confidences)
        expert_load = tuple(float(a) for a in out[1 + (L > 1):])
        if expert_load:
            self.last_step.update(zip(
                ("moe.expert_load_max", "moe.expert_load_mean"), expert_load))
        self.stats.record_step(dt, n, call.bucket,
                               rows=n * self.step_rows,
                               commits=call.commits, expert_load=expert_load,
                               ctx=ctx, ctx_bytes=sum(
                                   c * b for c, b in zip(
                                       call.ctx_by_group,
                                       self.pool.group_row_bytes)),
                               ctx_window=sum(ctx_window),
                               fetch_wait_us=sp.dur_us)
        if L == 1:
            return tuple(int(x) for x in out[0][:n])
        return [(out[0][i], out[1][i]) for i in range(n)]

    def snapshot(self) -> Dict:
        return {
            "endpoint": self.name,
            "prefill_buckets": list(self.prefill_buckets),
            "decode_buckets": list(self.decode_buckets),
            "executables": len(self._prefill_execs) + len(self._decode_execs),
            "stats": self.stats.snapshot(),
            "kv_pool": self.pool.snapshot(),
        }

    def __repr__(self):
        return (f"DecodeEndpoint({self.name!r}, "
                f"prefill_buckets={self.prefill_buckets}, "
                f"decode_buckets={self.decode_buckets})")
