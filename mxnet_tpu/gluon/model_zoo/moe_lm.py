"""A present-day decoder-only language model: pre-norm, RMSNorm, grouped KV
heads with per-head q/k norm and rotary positions, routed SwiGLU experts, an
untied head, generated causally or by diffusion over blocks.

The block is the Qwen3-MoE shape (and that of the ``sdar_moe`` models derived
from it): ``h = x + Attn(RMSNorm(x)); y = h + MoE(RMSNorm(h))``, a final
RMSNorm, no biases. Attention is ``ops.nn.block_attention`` under the mask of
``block_length`` (position i sees position j iff ``j // L <= i // L``; L = 1
is the causal mask), the experts ``ops.nn.moe_ffn``. A layer's experts are
three stacked parameters, not one per expert, and ``held_experts`` = (first,
count) names the share of them this chip holds: the router still scores all
``num_experts`` and the layer computes the part of the result its own experts
give.

Three entry points share one parameter set, the incremental-decode protocol
of ``serving.generate.DecodeEndpoint`` (see ``bert.TransformerLM``), widened
to ``block_length`` rows a sequence:

- ``forward(tokens)``: (B, S) -> (B, S, V) float32 logits.
- ``prefill_collect(tokens)``: the same pass, returning every layer's
  (B, S, kv_units) keys and values for the cache.
- ``decode_step(ids, positions, k_pool, v_pool, tables)``: ``ids``/
  ``positions`` (B, L), one block per sequence against its cached context,
  which every layer reads in the paged KV pools through the sequences' page
  tables up to the block's first position (``ops/pallas/paged_attention``;
  (B,) for the causal step of ``block_length`` 1, whose outputs then lack the
  L axis too); returns (logits (B, L, V), k_0, v_0, ..., the rows routed to
  each expert (layers, E_held)). The block's keys and values come back for
  the caller to write, or not: a denoising step of block diffusion saw mask
  tokens and its keys are dropped.
- ``decode_step_reading(ids, positions, rows, k_pool, v_pool, tables)``: the
  same step with logits for ``rows`` (B, R) alone, the rows a sequence that
  are read, (B, R, V): the endpoint steps two blocks a sequence and reads
  one.

**Window and full layers in one model** (``layer_types``, ``sliding_window``,
``rope_by_type``; causal models): a layer of type ``sliding_attention`` sees
the last ``sliding_window`` positions, itself among them (``i - j <
window``), every other type all of them, and each type rotates by its own
table (``rope_by_type[type]`` = ``{"theta": ..., "scaling": None or YaRN's
settings}``). The model then states its ``cache_groups`` to the endpoint,
``(name, layers, window)``: the full layers, which keep every position, and
the sliding ones, which keep the window: the pool gives each kind its own
pages and ``decode_step``'s cache is (the first group's pools, the second's,
the first group's tables, the second's). A prompt of 512 rows or more goes
through ``ops/pallas/flash_attention`` (banded under a window, KV heads read
by their group of query heads); the shorter rungs and a step's own rows
through ``block_attention``. With none of the three the model traces what it
always did.

What the endpoint learns from the block: ``kv_units`` (the pool's row),
``block_length``, ``mask_token_id`` (None: causal, one token a step),
``cache_groups`` (None: every layer keeps everything), ``prefill_reads_row``
(``prefill_collect`` takes the position of the one row whose logits are read,
and the head multiplies that row alone).
Weights are (in, out). The math is ``jax.numpy`` on the parameters' arrays.

``DecoderLM`` is what such models share and ``mla_lm.MLADecoderLM`` builds
on too: embedding, the loop over layers, final RMSNorm, the untied head and
the three entry points; a subclass brings its parameters and ``_layer``.
"""
from __future__ import annotations

from ..block import HybridBlock
from ...ndarray.ndarray import NDArray

__all__ = ["DecoderLM", "MoEDecoderLM"]

# rows from which a causal prompt's attention goes through the flash kernel
# (ops/pallas/flash_attention.py: the compiled kernel's own floor)
_FLASH_ROWS = 512


def _one():
    from ... import initializer
    return initializer.One()


class DecoderLM(HybridBlock):
    """The trunk of a pre-norm decoder-only LM and its incremental-decode
    protocol. A subclass sets ``num_layers``, ``units``, ``vocab_size``,
    ``rms_eps``, ``kv_units``, creates ``embed_weight`` (:meth:`_embed`), its
    layers' parameters, then ``final_norm`` and ``head_weight``
    (:meth:`_head`), in that order inside its ``name_scope``, and gives
    ``_layer(i, x, positions, cache) -> (y, the rows to cache (a tuple: keys
    and values, or one latent), rows per held expert or None)``."""

    block_length = 1
    mask_token_id = None
    cache_groups = None         # every layer keeps every position
    prefill_reads_row = True    # prefill_collect(tokens, last)

    def _get(self, name, shape, dtype, **kw):
        p = self.params.get(name, shape=shape, dtype=dtype, **kw)
        self._reg_params[name] = p       # saved and loaded by name
        return p

    def _embed(self, dtype):
        self.embed_weight = self._get("embed_weight",
                                      (self.vocab_size, self.units), dtype)

    def _head(self, dtype):
        self.final_norm = self._get("final_norm_gamma", (self.units,), dtype,
                                    init=_one())
        self.head_weight = self._get("head_weight",
                                     (self.units, self.vocab_size), dtype)

    def _run(self, ids, positions, cache=None, last=None):
        """(logits (B, S, V) float32, the rows to cache layer by layer,
        loads (expert layers, E_held)). ``last`` (B,): the one row a
        sequence whose logits are wanted, (B, 1, V), or (B, R): its R rows,
        (B, R, V): the final norm and the head then see those rows alone."""
        import jax.numpy as jnp
        from ...ops import nn as ops
        x = self.embed_weight.data().data[ids]
        kept, loads = [], []
        for i in range(self.num_layers):
            x, rows, load = self._layer(i, x, positions, cache)
            kept += rows
            if load is not None:
                loads.append(load)
        if last is not None:
            x = jnp.take_along_axis(
                x, last[:, None, None] if last.ndim == 1 else last[..., None],
                axis=1)
        x = ops.rms_norm(x, self.final_norm.data().data, eps=self.rms_eps)
        logits = jnp.dot(x, self.head_weight.data().data,
                         preferred_element_type=jnp.float32)
        return logits, kept, jnp.stack(loads)

    @staticmethod
    def _raw(*arrays):
        import jax.numpy as jnp
        return [jnp.asarray(a.data if isinstance(a, NDArray) else a)
                for a in arrays]

    def _whole(self, tokens, last=None):
        import jax.numpy as jnp
        (ids,) = self._raw(tokens)
        ids = ids.astype(jnp.int32)
        positions = jnp.broadcast_to(
            jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape)
        if last is not None:
            (last,) = self._raw(last)
            last = last.astype(jnp.int32)
        return self._run(ids, positions, last=last)

    def forward(self, tokens):
        return NDArray(self._whole(tokens)[0])

    def prefill_collect(self, tokens, last=None):
        """``last`` (B,), if given: the position of the row a sequence whose
        logits are read (a prompt's last); the logits are then (B, 1, V)."""
        logits, kept, _ = self._whole(tokens, last)
        return (logits,) + tuple(kept)

    def decode_step(self, ids, positions, *cache):
        """``cache`` = (the pool's arrays..., tables)."""
        return self._decode(ids, positions, cache)

    def decode_step_reading(self, ids, positions, rows, *cache):
        """:meth:`decode_step` with logits for ``rows`` (B, R) alone: the
        rows a sequence that are read."""
        (rows,) = self._raw(rows)
        return self._decode(ids, positions, cache, rows.astype("int32"))

    def _decode(self, ids, positions, cache, rows=None):
        import jax.numpy as jnp
        ids, positions, *cache = self._raw(ids, positions, *cache)
        causal = ids.ndim == 1      # one row a sequence, as TransformerLM's
        if causal:
            ids, positions = ids[:, None], positions[:, None]
        logits, kept, loads = self._run(ids.astype(jnp.int32),
                                        positions.astype(jnp.int32), cache,
                                        last=rows)
        if causal:
            logits, kept = logits[:, 0], [a[:, 0] for a in kept]
        return (logits,) + tuple(kept) + (loads,)


class MoEDecoderLM(DecoderLM):
    def __init__(self, num_layers=2, units=64, num_heads=4, num_kv_heads=2,
                 head_dim=16, expert_hidden=32, num_experts=8,
                 experts_per_token=2, vocab_size=256, norm_topk=True,
                 rms_eps=1e-6, rope_theta=1e6, block_length=1,
                 mask_token_id=None, held_experts=None, dtype="float32",
                 layer_types=None, sliding_window=None, rope_by_type=None,
                 **kwargs):
        super().__init__(**kwargs)
        self.num_layers = num_layers
        self.units = units
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.kv_units = num_kv_heads * head_dim
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.vocab_size = vocab_size
        self.norm_topk = norm_topk
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        self.block_length = int(block_length)
        self.mask_token_id = mask_token_id
        self.held_experts = tuple(held_experts or (0, num_experts))
        # a layer's window (None: it sees everything) and rotary table
        self._windows = [None] * num_layers
        self._ropes = [{"theta": rope_theta}] * num_layers
        if layer_types is not None:
            if len(layer_types) != num_layers or self.block_length != 1:
                raise ValueError(
                    f"layer_types names {len(layer_types)} layers of "
                    f"{num_layers}, under blocks of {self.block_length} (a "
                    "window is the causal mask's)")
            sliding = [t == "sliding_attention" for t in layer_types]
            if any(sliding):
                self._windows = [int(sliding_window) if s else None
                                 for s in sliding]
                of = lambda kind: tuple(i for i, s in enumerate(sliding)
                                        if s == kind)
                self.cache_groups = tuple(
                    group for group in (
                        ("full", of(False), None),
                        ("window", of(True), int(sliding_window)))
                    if group[1])
            if rope_by_type is not None:
                self._ropes = [dict(rope_by_type[t]) for t in layer_types]
        held = self.held_experts[1]
        H, D, F = units, head_dim, expert_hidden

        def get(name, shape, **kw):
            return self._get(name, shape, dtype, **kw)

        with self.name_scope():
            self._embed(dtype)
            self.layers = []
            for i in range(num_layers):
                self.layers.append({
                    "ln1": get(f"l{i}_ln1_gamma", (H,), init=_one()),
                    "wq": get(f"l{i}_q_weight", (H, num_heads * D)),
                    "wk": get(f"l{i}_k_weight", (H, self.kv_units)),
                    "wv": get(f"l{i}_v_weight", (H, self.kv_units)),
                    "wo": get(f"l{i}_o_weight", (num_heads * D, H)),
                    "q_norm": get(f"l{i}_q_norm_gamma", (D,), init=_one()),
                    "k_norm": get(f"l{i}_k_norm_gamma", (D,), init=_one()),
                    "ln2": get(f"l{i}_ln2_gamma", (H,), init=_one()),
                    "router": get(f"l{i}_router_weight", (H, num_experts)),
                    "w_gate": get(f"l{i}_experts_gate_weight", (held, H, F)),
                    "w_up": get(f"l{i}_experts_up_weight", (held, H, F)),
                    "w_down": get(f"l{i}_experts_down_weight", (held, F, H)),
                })
            self._head(dtype)

    # ------------------------------------------------------------------
    def _cache_of(self, i, cache, positions):
        """What ``paged_attention`` takes after the queries for layer ``i``:
        its group's pools and tables, the lanes' lengths, its index among the
        group's layers and, of a window layer, the lanes' lower bounds."""
        import jax.numpy as jnp
        first = positions[:, 0]
        if self.cache_groups is None:
            return (*cache, first, i)
        n = len(self.cache_groups)
        pools, tables = cache[:-n], cache[-n:]
        per = len(pools) // n
        for g, (_, layers, window) in enumerate(self.cache_groups):
            if i in layers:
                bound = () if window is None else \
                    (jnp.maximum(first - (window - 1), 0),)
                return (*pools[g * per:(g + 1) * per], tables[g], first,
                        layers.index(i), *bound)
        raise ValueError(f"layer {i} is in no cache group")

    def _layer(self, i, x, positions, cache=None):
        """One block over x (B, S, H): (y, (k, v), rows per held expert).
        ``cache`` = (k_pool, v_pool, tables), of a model with cache groups
        every group's pools and then every group's tables: the rows also
        attend to their sequence's cached positions before
        ``positions[:, 0]``."""
        from ...ops import nn as ops
        from ...ops.pallas.paged_attention import paged_attention
        p = {name: w.data().data for name, w in self.layers[i].items()}
        B, S, H = x.shape
        window = self._windows[i]
        banded = {} if window is None else {"window": window}
        h = ops.rms_norm(x, p["ln1"], eps=self.rms_eps)
        heads = lambda t, n: t.reshape(B, S, n, self.head_dim)
        q = ops.rms_norm(heads(h @ p["wq"], self.num_heads), p["q_norm"],
                         eps=self.rms_eps)
        k = ops.rms_norm(heads(h @ p["wk"], self.num_kv_heads), p["k_norm"],
                         eps=self.rms_eps)
        q = ops.rotary_embedding(q, positions, **self._ropes[i])
        k = ops.rotary_embedding(k, positions, **self._ropes[i])
        k = k.reshape(B, S, self.kv_units)
        v = h @ p["wv"]
        if cache is None and self.block_length == 1 and S >= _FLASH_ROWS:
            # a long prompt: no (S, S) scores in HBM, and under a window the
            # band's blocks alone
            from ...ops.pallas.flash_attention import flash_attention
            by_head = lambda t: t.transpose(0, 2, 1, 3)
            att = flash_attention(
                by_head(q), by_head(heads(k, self.num_kv_heads)),
                by_head(heads(v, self.num_kv_heads)), causal=True, **banded)
            att = by_head(att).reshape(B, S, -1)
        else:
            q = q.reshape(B, S, -1)
            ctx = () if cache is None else paged_attention(
                q, *self._cache_of(i, cache, positions),
                heads=self.num_heads, kv_heads=self.num_kv_heads)
            att = ops.block_attention(
                q, k, v, positions, *ctx, heads=self.num_heads,
                kv_heads=self.num_kv_heads, block_length=self.block_length,
                **banded)
        x = x + att @ p["wo"]
        h = ops.rms_norm(x, p["ln2"], eps=self.rms_eps)
        y, load = ops.moe_ffn(
            h.reshape(B * S, H), p["router"], p["w_gate"], p["w_up"],
            p["w_down"], top_k=self.experts_per_token,
            norm_topk=self.norm_topk, first_expert=self.held_experts[0])
        return x + y.reshape(B, S, H), (k, v), load
