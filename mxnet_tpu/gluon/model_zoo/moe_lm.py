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

What the endpoint learns from the block: ``kv_units`` (the pool's row),
``block_length``, ``mask_token_id`` (None: causal, one token a step).
Weights are (in, out). The math is ``jax.numpy`` on the parameters' arrays.

``DecoderLM`` is what such models share and ``mla_lm.MLADecoderLM`` builds
on too: embedding, the loop over layers, final RMSNorm, the untied head and
the three entry points; a subclass brings its parameters and ``_layer``.
"""
from __future__ import annotations

from ..block import HybridBlock
from ...ndarray.ndarray import NDArray

__all__ = ["DecoderLM", "MoEDecoderLM"]


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

    def _run(self, ids, positions, cache=None):
        """(logits (B, S, V) float32, the rows to cache layer by layer,
        loads (expert layers, E_held))."""
        import jax.numpy as jnp
        from ...ops import nn as ops
        x = self.embed_weight.data().data[ids]
        kept, loads = [], []
        for i in range(self.num_layers):
            x, rows, load = self._layer(i, x, positions, cache)
            kept += rows
            if load is not None:
                loads.append(load)
        x = ops.rms_norm(x, self.final_norm.data().data, eps=self.rms_eps)
        logits = jnp.dot(x, self.head_weight.data().data,
                         preferred_element_type=jnp.float32)
        return logits, kept, jnp.stack(loads)

    @staticmethod
    def _raw(*arrays):
        import jax.numpy as jnp
        return [jnp.asarray(a.data if isinstance(a, NDArray) else a)
                for a in arrays]

    def _whole(self, tokens):
        import jax.numpy as jnp
        (ids,) = self._raw(tokens)
        ids = ids.astype(jnp.int32)
        positions = jnp.broadcast_to(
            jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape)
        return self._run(ids, positions)

    def forward(self, tokens):
        return NDArray(self._whole(tokens)[0])

    def prefill_collect(self, tokens):
        logits, kept, _ = self._whole(tokens)
        return (logits,) + tuple(kept)

    def decode_step(self, ids, positions, *cache):
        """``cache`` = (the pool's arrays..., tables)."""
        import jax.numpy as jnp
        ids, positions, *cache = self._raw(ids, positions, *cache)
        causal = ids.ndim == 1      # one row a sequence, as TransformerLM's
        if causal:
            ids, positions = ids[:, None], positions[:, None]
        logits, kept, loads = self._run(ids.astype(jnp.int32),
                                        positions.astype(jnp.int32), cache)
        if causal:
            logits, kept = logits[:, 0], [a[:, 0] for a in kept]
        return (logits,) + tuple(kept) + (loads,)


class MoEDecoderLM(DecoderLM):
    def __init__(self, num_layers=2, units=64, num_heads=4, num_kv_heads=2,
                 head_dim=16, expert_hidden=32, num_experts=8,
                 experts_per_token=2, vocab_size=256, norm_topk=True,
                 rms_eps=1e-6, rope_theta=1e6, block_length=1,
                 mask_token_id=None, held_experts=None, dtype="float32",
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
    def _layer(self, i, x, positions, cache=None):
        """One block over x (B, S, H): (y, (k, v), rows per held expert).
        ``cache`` = (k_pool, v_pool, tables): the rows also attend to their
        sequence's cached positions before ``positions[:, 0]``."""
        from ...ops import nn as ops
        from ...ops.pallas.paged_attention import paged_attention
        p = {name: w.data().data for name, w in self.layers[i].items()}
        B, S, H = x.shape
        h = ops.rms_norm(x, p["ln1"], eps=self.rms_eps)
        heads = lambda t, n: t.reshape(B, S, n, self.head_dim)
        q = ops.rms_norm(heads(h @ p["wq"], self.num_heads), p["q_norm"],
                         eps=self.rms_eps)
        k = ops.rms_norm(heads(h @ p["wk"], self.num_kv_heads), p["k_norm"],
                         eps=self.rms_eps)
        q = ops.rotary_embedding(q, positions, theta=self.rope_theta)
        k = ops.rotary_embedding(k, positions, theta=self.rope_theta)
        k = k.reshape(B, S, self.kv_units)
        v = h @ p["wv"]
        q = q.reshape(B, S, -1)
        ctx = () if cache is None else paged_attention(
            q, *cache, positions[:, 0], i, heads=self.num_heads,
            kv_heads=self.num_kv_heads)
        att = ops.block_attention(
            q, k, v, positions, *ctx, heads=self.num_heads,
            kv_heads=self.num_kv_heads, block_length=self.block_length)
        x = x + att @ p["wo"]
        h = ops.rms_norm(x, p["ln2"], eps=self.rms_eps)
        y, load = ops.moe_ffn(
            h.reshape(B * S, H), p["router"], p["w_gate"], p["w_up"],
            p["w_down"], top_k=self.experts_per_token,
            norm_topk=self.norm_topk, first_expert=self.held_experts[0])
        return x + y.reshape(B, S, H), (k, v), load
