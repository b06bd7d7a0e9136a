"""Ring attention: sequence/context parallelism for long sequences.

The reference has NO long-context parallelism (SURVEY.md §5: "no ring attention,
no context/sequence parallelism" — its sequence tooling stops at fused attention
matmuls, contrib/transformer.cc:650-828, and bucketing). This module is the
TPU-native capability that subsumes that gap: the sequence axis is sharded over
the mesh's 'sp' axis; each device holds a Q block and rotates K/V blocks around
the ICI ring with ppermute, accumulating attention in the numerically-stable
blockwise (flash) form — running max `m`, running normalizer `l`, running
weighted values `o`. Peak memory per chip is O(S/n · S/n) instead of O(S²),
and the K/V transfer overlaps with the block matmuls (XLA overlaps the
CollectivePermute with compute since the next block's matmul doesn't depend
on the in-flight buffer).

ring_attention       — per-shard function; call inside shard_map over 'sp'.
ring_self_attention  — host-level wrapper: shards (B,H,S,D) q/k/v over the mesh
                       and runs the ring under shard_map.
"""
from __future__ import annotations

from functools import partial

__all__ = ["ring_attention", "ring_self_attention"]


def _block_attend(q, k, v, scale, mask=None):
    """One (Q-block, K-block) attention tile: returns (scores_max, exp_scores@v,
    exp_scores row-sum) in fp32 accumulation."""
    import jax.numpy as jnp
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, jnp.float32(-1e30))
    m = jnp.max(s, axis=-1)                          # (b,h,q)
    p = jnp.exp(s - m[..., None])                    # (b,h,q,k)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    l = jnp.sum(p, axis=-1)                          # (b,h,q)
    return m, o, l


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = False,
                   scale=None):
    """Blockwise ring attention over mesh axis ``axis_name``.

    q, k, v: (B, H, S_local, D) — the local sequence shard. Must be called
    inside shard_map (or pmap) with ``axis_name`` bound. Returns the local
    (B, H, S_local, D) output shard.

    Causal masking uses global positions: device i holds positions
    [i*S_local, (i+1)*S_local); a K/V block that started on device j carries
    offset j and is masked against the local Q offset.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    scale = jnp.float32(scale)

    q32 = q
    pos_q = my * S + jnp.arange(S)

    def mask_for(src_index):
        if not causal:
            return None
        pos_k = src_index * S + jnp.arange(S)
        return pos_q[:, None] >= pos_k[None, :]      # (Sq, Sk) -> broadcast

    def body(carry, step):
        (kb, vb, m_acc, l_acc, o_acc) = carry
        # after `step` rotations, the resident K/V block originated on
        # device (my - step) mod n
        src = jnp.mod(my - step, n)
        mask = mask_for(src)
        if mask is not None:
            mask = mask[None, None]
        m_blk, o_blk, l_blk = _block_attend(q32, kb, vb, scale, mask)
        m_new = jnp.maximum(m_acc, m_blk)
        alpha = jnp.exp(m_acc - m_new)               # rescale old accumulators
        beta = jnp.exp(m_blk - m_new)
        l_new = l_acc * alpha + l_blk * beta
        o_new = o_acc * alpha[..., None] + o_blk * beta[..., None]
        # rotate K/V to the next device on the ICI ring
        perm = [(i, (i + 1) % n) for i in range(n)]
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (kb, vb, m_new, l_new, o_new), None

    m0 = jnp.full((B, H, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    o0 = jnp.zeros((B, H, S, D), jnp.float32)
    # mark the fresh accumulators as varying over the ring axis so the scan
    # carry type matches its output (shard_map vma tracking)
    m0, l0, o0 = (lax.pcast(a, (axis_name,), to="varying")
                  for a in (m0, l0, o0))
    carry = (k, v, m0, l0, o0)
    carry, _ = lax.scan(body, carry, jnp.arange(n))
    _, _, m_f, l_f, o_f = carry
    out = o_f / jnp.maximum(l_f, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(q, k, v, mesh, *, causal: bool = False, scale=None,
                        axis_name: str = "sp"):
    """Host-level ring attention: q/k/v are (B, H, S, D) jax arrays (or NDArray
    .data); the sequence axis is sharded over ``axis_name`` of ``mesh`` and the
    ring runs under shard_map."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)
    fn = shard_map(
        partial(ring_attention, axis_name=axis_name, causal=causal, scale=scale),
        mesh=mesh.mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
