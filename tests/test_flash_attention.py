"""Flash attention kernel vs dense reference (fwd + grads)."""
import math

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.pallas.flash_attention import flash_attention


def _dense(q, k, v, causal=False):
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
    if causal:
        S = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    rng = onp.random.RandomState(0)
    B, H, S, D = 2, 2, 256, 64
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    out = flash_attention(q, k, v, causal=causal)
    ref = _dense(q, k, v, causal)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-3, atol=2e-3)


def test_flash_gradients_match_dense():
    rng = onp.random.RandomState(1)
    B, H, S, D = 1, 2, 128, 32
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=5e-3, atol=5e-3)


def test_flash_uneven_seq():
    rng = onp.random.RandomState(2)
    B, H, S, D = 1, 1, 192, 64  # not a multiple of the 128 block
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    out = flash_attention(q, k, v)
    ref = _dense(q, k, v)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_whole_padded_k_blocks(causal):
    """block_q > block_k pads S to a block_q multiple, creating ENTIRE
    k-blocks of padding; they must not leak into the softmax (regression:
    the has_tail check once only caught partial tail blocks)."""
    rng = onp.random.RandomState(0)
    B, H, S, D = 1, 2, 640, 64
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    out = flash_attention(q, k, v, causal=causal, block_q=512, block_k=128)
    ref = _dense(q, k, v, causal)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_backward_matches_dense(causal):
    """The O(S·D) blockwise backward (used past _BWD_BLOCKWISE_MIN_S) must
    produce the same gradients as the dense recompute, incl. a non-multiple
    S that exercises the q padding."""
    from mxnet_tpu.ops.pallas import flash_attention as fa
    rng = onp.random.RandomState(2)
    B, H, S, D = 1, 2, 1300, 32  # S > 1024 threshold, not a block multiple
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    g = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    out, lse = fa._flash_fwd(q, k, v, 1.0 / 8, causal, 256, 256, True)
    want = fa._dense_bwd(q, k, v, out, lse, g, 1.0 / 8, causal)
    got = fa._blockwise_bwd(q, k, v, out, lse, g, 1.0 / 8, causal, 512)
    for w, gt, name in zip(want, got, "q k v".split()):
        onp.testing.assert_allclose(onp.asarray(gt), onp.asarray(w),
                                    rtol=2e-4, atol=2e-4, err_msg=name)


def test_long_seq_gradient_through_op():
    """End-to-end autograd through the op at S past the blockwise threshold."""
    rng = onp.random.RandomState(3)
    B, H, S, D = 1, 1, 1100, 32
    x = mx.nd.array(rng.randn(B, H, S, D).astype("float32") * 0.3)
    k = mx.nd.array(rng.randn(B, H, S, D).astype("float32") * 0.3)
    v = mx.nd.array(rng.randn(B, H, S, D).astype("float32") * 0.3)
    x.attach_grad()
    with mx.autograd.record():
        out = mx.nd.flash_attention(x, k, v, causal=True)
        loss = (out * out).sum()
    loss.backward()
    gradn = x.grad.asnumpy()
    assert onp.isfinite(gradn).all() and onp.abs(gradn).max() > 0


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_matches_dense(causal):
    """The Pallas backward kernels (dq grid + dk/dv grid) must match the
    dense recompute, incl. q/k padding from a non-multiple S."""
    from mxnet_tpu.ops.pallas import flash_attention as fa
    rng = onp.random.RandomState(5)
    B, H, S, D = 1, 2, 1300, 32
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    g = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    out, lse = fa._flash_fwd(q, k, v, 1.0 / 8, causal, 256, 256, True)
    want = fa._dense_bwd(q, k, v, out, lse, g, 1.0 / 8, causal)
    got = fa._pallas_bwd(q, k, v, out, lse, g, 1.0 / 8, causal, 256, 256,
                         True)
    for w, gt, name in zip(want, got, "q k v".split()):
        onp.testing.assert_allclose(onp.asarray(gt), onp.asarray(w),
                                    rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_shipped_default_blocks_backward(causal):
    """Exercise the REGISTERED default configuration (block_q=512,
    block_k=1024) through the full fwd+bwd dispatch at S>1024 — the
    configuration production training actually runs (ADVICE r3 #5). S is a
    non-multiple of both blocks so the padding paths of the dq and dk/dv
    grids are on the hot path too."""
    rng = onp.random.RandomState(9)
    B, H, S, D = 1, 1, 1500, 32
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    g = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal) * g).sum()

    def f_dense(q, k, v):
        return (_dense(q, k, v, causal=causal) * g).sum()

    got = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for gt, w, name in zip(got, want, "q k v".split()):
        onp.testing.assert_allclose(onp.asarray(gt), onp.asarray(w),
                                    rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_short_seq_dense_route_and_fidelity(causal, monkeypatch):
    """Short sequences on the COMPILED TPU path must route to dense XLA
    attention: on real hardware Mosaic rejects sub-tile dot operands ("Bad
    lhs type" at S=16 — the BERT-tiny config crashed outright before the
    fallback), and the measured v5e crossover puts dense ahead of the kernel
    below S=512 anyway. Two properties pinned here:

    1. routing — with the TPU path forced, S < _MIN_PALLAS_S dispatches to
       _dense_attention (the kernel is never entered);
    2. fidelity — the dense fallback matches the kernel (interpret mode) in
       values and grads at the same small shapes, so the routing change can
       never change results.
    """
    from mxnet_tpu.ops.pallas import flash_attention as fa
    rng = onp.random.RandomState(11)
    B, H, S, D = 2, 2, 16, 32
    assert S < fa._MIN_PALLAS_S
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32") * 0.3)
    g = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))

    # 1. routing: pretend we are on the compiled TPU path
    hits = []
    real_dense = fa._dense_attention
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_dense_attention",
                        lambda *a: hits.append(1) or real_dense(*a))
    routed = fa.flash_attention(q, k, v, causal=causal)
    assert hits, "short-seq TPU dispatch did not take the dense path"
    monkeypatch.setattr(fa, "_dense_attention", real_dense)

    # 2. fidelity: dense fallback == kernel (interpret) at the same shape
    sm = 1.0 / D ** 0.5
    want = fa._flash(q, k, v, sm, causal, 16, 16, True)   # interpret kernel
    onp.testing.assert_allclose(onp.asarray(routed), onp.asarray(want),
                                rtol=2e-5, atol=2e-5)
    got_g = jax.grad(lambda *a: (real_dense(*a, sm, causal) * g).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(lambda *a: (fa._flash(*a, sm, causal, 16, 16, True)
                                  * g).sum(), argnums=(0, 1, 2))(q, k, v)
    for gt, w, name in zip(got_g, want_g, "q k v".split()):
        onp.testing.assert_allclose(onp.asarray(gt), onp.asarray(w),
                                    rtol=2e-4, atol=2e-4, err_msg=name)


# ---------------------------------------------------------------------------
# a window, and KV heads shared by a group of query heads (forward only)
# ---------------------------------------------------------------------------
def _dense_banded(q, k, v, window):
    """The dense mask built from positions: j <= i and i - j < window; K and
    V repeated by head group."""
    G = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, G, 1), jnp.repeat(v, G, 1)
    S = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / math.sqrt(q.shape[-1])
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


# S not a multiple of the block; the window smaller than a block, equal to
# it, larger than it, and larger than the sequence; blocks of unequal size
@pytest.mark.parametrize("S,bq,bk,window", [
    (200, 64, 64, 32), (200, 64, 64, 64), (200, 64, 64, 100),
    (256, 64, 128, 48), (300, 128, 64, 70), (150, 64, 64, 400),
    (192, 64, 64, None)])
def test_flash_window_and_grouped_heads_match_the_dense_mask(S, bq, bk,
                                                             window):
    from mxnet_tpu.ops.pallas import flash_attention as fa
    keys = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(keys[0], (2, 8, S, 32))
    k = jax.random.normal(keys[1], (2, 2, S, 32))
    v = jax.random.normal(keys[2], (2, 2, S, 32))
    out = flash_attention(q, k, v, causal=True, window=window, block_q=bq,
                          block_k=bk, interpret=True)
    onp.testing.assert_allclose(out, _dense_banded(q, k, v, window),
                                atol=2e-5)
    # the short rows' dense route masks the same
    short = flash_attention(q[:, :, :48], k[:, :, :48], v[:, :, :48],
                            causal=True, window=window)
    onp.testing.assert_allclose(
        short, _dense_banded(q[:, :, :48], k[:, :, :48], v[:, :, :48],
                             window), atol=2e-5)
    if window is None:
        return
    # blocks wholly behind the band are not visited: the grid's k axis is the
    # band's blocks alone
    Sp = -(-S // max(bq, bk)) * max(bq, bk)
    visited = fa._band_blocks(Sp, bq, bk, window)
    # (the band under a q block spans bq + window - 1 columns: window / block
    # + 2 blocks where q and k blocks are equal)
    assert visited <= -(-(bq + window - 1) // bk) + 1
    jaxpr = str(jax.make_jaxpr(lambda *a: flash_attention(
        *a, causal=True, window=window, block_q=bq, block_k=bk,
        interpret=True))(q, k, v))
    assert f"grid=(16, {Sp // bq}, {min(visited, Sp // bk)})" in jaxpr


def test_flash_window_costs_the_band_not_the_square():
    """At the served shapes (16,384 rows, blocks of 1,024, a window of 1,024)
    a q block visits 2 k blocks where the causal kernel's grid has 16: a
    window layer's prefill is an eighth of a full one's (half the square is
    8.5 blocks a q block)."""
    from mxnet_tpu.ops.pallas import flash_attention as fa
    assert fa._band_blocks(16384, 1024, 1024, 1024) == 2
    assert fa._band_blocks(4096, 512, 1024, 1024) == 2
    assert fa._band_blocks(8192, 512, 1024, 1024) == 2


def test_flash_window_is_forward_only_and_causal():
    q = jnp.ones((1, 2, 128, 16))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, window=8)
    with pytest.raises(Exception):      # no backward kernel takes a window
        jax.grad(lambda q: flash_attention(
            q, q, q, causal=True, window=8, interpret=True).sum())(q)
