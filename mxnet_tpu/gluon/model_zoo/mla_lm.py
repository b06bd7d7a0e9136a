"""A decoder-only language model with latent attention (MLA) and a
group-limited sigmoid router beside a shared expert: the DeepSeek-V3 block, on
the trunk and the decode protocol of ``moe_lm.DecoderLM``.

``h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h))``, no biases.

**Attention** projects queries and keys/values through low ranks with an
RMSNorm between the halves: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` (per
head ``nope`` + ``rope`` numbers); ``[c_kv | k_r] = x W_kva``, ``c_kv =
RMSNorm(c_kv)``, and ``k_rope = RoPE(k_r)`` is one for all heads; ``[k_nope_h |
v_h] = c_kv W_kvb``. Rotary positions turn the ``rope`` slice only
(rotate-half pairing; YaRN under ``rope_scaling``, the softmax scale then
times ``mscale ** 2``). What a position leaves behind is its **latent**
``(c_kv, k_rope)``, ``kv_rank + rope`` numbers a layer, once: ``kv_latent``
tells the endpoint that the cache's row is keys and values both
(``serving/generate/kv_cache.py``). Two forms of one function:

- *plain*, over rows that attend to each other alone (``forward``,
  ``prefill_collect``): keys and values are expanded per head and go through
  ``ops.nn.multi_head_attention`` (the flash kernel on a TPU, keys of
  ``nope + rope`` and values of ``v_dim``);
- *absorbed*, for a step's rows against their cache (``decode_step``):
  ``q~_h = q_nope_h (W_kvb^K,h)^T`` lives in the latent's space, so every head
  scores the one cached row, ``s_h = (q~_h . c_kv + q_rope_h . k_rope) *
  scale``, the weighted rows are ``c_kv`` itself (the latent's first
  ``kv_rank`` columns) and ``o_h = (softmax(s_h) c_kv) W_kvb^V,h``. The cache
  is read by ``ops/pallas/paged_attention`` in its latent mode; the step's own
  row, not in the pool yet, is the second part of the same softmax
  (``ops.nn.block_attention``).

**FFN**: the first ``dense_layers`` layers are one gated MLP of
``dense_hidden``; every later one is routed experts (``ops.nn.moe_ffn`` under
the sigmoid rule: a correction bias on the choice, ``n_group`` groups of which
``topk_group`` are kept, the chosen scores normalised and times
``routed_scale``) plus ``shared_experts`` experts every row takes. As in
``MoEDecoderLM``, ``held_experts`` = (first, count) names the routed experts
this chip holds: the router scores all ``num_experts``, the layer adds the
part its own experts give and the shared expert whole.

Entry points and what ``decode_step`` returns are ``DecoderLM``'s: the pool
is one array, a layer caches one row set, and the loads are those of the
routed layers (expert layers, E_held).
"""
from __future__ import annotations

from .moe_lm import DecoderLM, _one

__all__ = ["MLADecoderLM"]


def _gated(x, w_gate, w_up, w_down):
    import jax
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


class MLADecoderLM(DecoderLM):
    kv_latent = True        # the cache's row is keys and values both

    def __init__(self, num_layers=2, units=64, num_heads=4, q_rank=32,
                 kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
                 dense_layers=1, dense_hidden=128, expert_hidden=32,
                 num_experts=8, experts_per_token=2, shared_experts=1,
                 n_group=2, topk_group=1, routed_scale=1.0, norm_topk=True,
                 vocab_size=256, rms_eps=1e-6, rope_theta=10000.0,
                 rope_scaling=None, held_experts=None, dtype="float32",
                 **kwargs):
        super().__init__(**kwargs)
        self.num_layers = num_layers
        self.units = units
        self.num_heads = num_heads
        self.kv_rank, self.nope_dim = kv_rank, nope_dim
        self.rope_dim, self.v_dim = rope_dim, v_dim
        self.kv_units = kv_rank + rope_dim
        self.dense_layers = dense_layers
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.n_group, self.topk_group = n_group, topk_group
        self.routed_scale, self.norm_topk = routed_scale, norm_topk
        self.vocab_size = vocab_size
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.held_experts = tuple(held_experts or (0, num_experts))
        from ...ops.nn import yarn_mscale
        sc = self.rope_scaling or {}
        self.sm_scale = (nope_dim + rope_dim) ** -0.5 * yarn_mscale(
            sc.get("factor", 1.0), sc.get("mscale_all_dim", 0.0) or 0.0) ** 2
        held = self.held_experts[1]
        H, F = units, expert_hidden

        def get(name, shape, **kw):
            return self._get(name, shape, dtype, **kw)

        with self.name_scope():
            self._embed(dtype)
            self.layers = []
            for i in range(num_layers):
                p = {
                    "ln1": get(f"l{i}_ln1_gamma", (H,), init=_one()),
                    "wq_a": get(f"l{i}_q_a_weight", (H, q_rank)),
                    "q_norm": get(f"l{i}_q_a_norm_gamma", (q_rank,),
                                  init=_one()),
                    "wq_b": get(f"l{i}_q_b_weight",
                                (q_rank, num_heads * (nope_dim + rope_dim))),
                    "wkv_a": get(f"l{i}_kv_a_weight", (H, self.kv_units)),
                    "kv_norm": get(f"l{i}_kv_a_norm_gamma", (kv_rank,),
                                   init=_one()),
                    "wkv_b": get(f"l{i}_kv_b_weight",
                                 (kv_rank, num_heads * (nope_dim + v_dim))),
                    "wo": get(f"l{i}_o_weight", (num_heads * v_dim, H)),
                    "ln2": get(f"l{i}_ln2_gamma", (H,), init=_one()),
                }
                if i < dense_layers:
                    wide = dense_hidden
                else:
                    wide = shared_experts * F
                    p.update({
                        "router": get(f"l{i}_router_weight", (H, num_experts)),
                        "router_bias": get(f"l{i}_router_bias",
                                           (num_experts,)),
                        "w_gate": get(f"l{i}_experts_gate_weight",
                                      (held, H, F)),
                        "w_up": get(f"l{i}_experts_up_weight", (held, H, F)),
                        "w_down": get(f"l{i}_experts_down_weight",
                                      (held, F, H)),
                    })
                p.update({      # the dense layer's MLP, else the shared expert
                    "mlp_gate": get(f"l{i}_mlp_gate_weight", (H, wide)),
                    "mlp_up": get(f"l{i}_mlp_up_weight", (H, wide)),
                    "mlp_down": get(f"l{i}_mlp_down_weight", (wide, H)),
                })
                self.layers.append(p)
            self._head(dtype)

    # ------------------------------------------------------------------
    def _attend(self, i, h, p, positions, cache):
        """Attention over normalised rows ``h`` (B, S, H): (the heads' values
        (B, S, heads * v_dim), the rows' latents (B, S, kv_rank + rope))."""
        import jax.numpy as jnp
        from ...ops import nn as ops
        from ...ops.pallas.paged_attention import paged_attention
        B, S, _ = h.shape
        N, R = self.num_heads, self.kv_rank
        rope = dict(theta=self.rope_theta, scaling=self.rope_scaling)
        c_q = ops.rms_norm(h @ p["wq_a"], p["q_norm"], eps=self.rms_eps)
        q = (c_q @ p["wq_b"]).reshape(B, S, N, -1)
        q_nope, q_rope = q[..., :self.nope_dim], q[..., self.nope_dim:]
        q_rope = ops.rotary_embedding(q_rope, positions, **rope)
        kv = h @ p["wkv_a"]
        c_kv = ops.rms_norm(kv[..., :R], p["kv_norm"], eps=self.rms_eps)
        k_rope = ops.rotary_embedding(kv[..., None, R:], positions,
                                      **rope)[:, :, 0]
        latent = jnp.concatenate([c_kv, k_rope], -1)
        w_kvb = p["wkv_b"].reshape(R, N, self.nope_dim + self.v_dim)
        if cache is None:       # plain: the rows attend to each other alone
            kv_h = (c_kv @ p["wkv_b"]).reshape(B, S, N, -1)
            k = jnp.concatenate(
                [kv_h[..., :self.nope_dim],
                 jnp.broadcast_to(k_rope[:, :, None], q_rope.shape)], -1)
            q = jnp.concatenate([q_nope, q_rope], -1)
            att = ops.multi_head_attention(
                q.reshape(B, S, -1), k.reshape(B, S, -1),
                kv_h[..., self.nope_dim:].reshape(B, S, -1), heads=N,
                causal=True, sm_scale=self.sm_scale)
            return att, latent
        # absorbed: every head's query in the latent's space, against the
        # cached rows (the kernel) and the step's own (block_attention)
        q_lat = jnp.einsum("bsnd,rnd->bsnr", q_nope,
                           w_kvb[..., :self.nope_dim],
                           preferred_element_type=jnp.float32)
        q_lat = jnp.concatenate([q_lat.astype(q.dtype), q_rope], -1) \
            .reshape(B, S, -1)
        pool, tables = cache
        ctx = paged_attention(q_lat, pool, None, tables, positions[:, 0], i,
                              heads=N, kv_heads=1, v_dim=R,
                              sm_scale=self.sm_scale)
        o_lat = ops.block_attention(q_lat, latent, c_kv, positions, *ctx,
                                    heads=N, kv_heads=1,
                                    sm_scale=self.sm_scale)
        att = jnp.einsum("bsnr,rnd->bsnd", o_lat.reshape(B, S, N, R),
                         w_kvb[..., self.nope_dim:],
                         preferred_element_type=jnp.float32)
        return att.astype(h.dtype).reshape(B, S, -1), latent

    def _layer(self, i, x, positions, cache=None):
        """One block over x (B, S, H): (y, (latent,), rows per held expert;
        None of a dense layer). ``cache`` = (pool, tables): the rows also
        attend to their sequence's cached positions before
        ``positions[:, 0]``."""
        from ...ops import nn as ops
        p = {name: w.data().data for name, w in self.layers[i].items()}
        B, S, H = x.shape
        att, latent = self._attend(
            i, ops.rms_norm(x, p["ln1"], eps=self.rms_eps), p, positions,
            cache)
        x = x + att @ p["wo"]
        h = ops.rms_norm(x, p["ln2"], eps=self.rms_eps).reshape(B * S, H)
        y = _gated(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
        load = None
        if i >= self.dense_layers:
            routed, load = ops.moe_ffn(
                h, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                p["router_bias"], top_k=self.experts_per_token,
                norm_topk=self.norm_topk, first_expert=self.held_experts[0],
                n_group=self.n_group, topk_group=self.topk_group,
                routed_scale=self.routed_scale)
            y = y + routed
        return x + y.reshape(B, S, H), (latent,), load
