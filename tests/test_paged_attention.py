"""The paged-attention kernel under the Pallas interpreter (tier-1,
JAX_PLATFORMS=cpu): the code the chip compiles, at both decode families'
shapes cut to small pools — float32 rows of fused heads of 64 with one row a
lane (few rows: one matmul over the whole row, the queries block-diagonal),
and bfloat16 rows of grouped heads of 128 with a block of four (many rows: a
matmul a KV head)."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops.nn import block_attention
from mxnet_tpu.ops.pallas.paged_attention import paged_attention

PAGE, P = 16, 4                       # positions a page, pages a lane
LAYERS, PAGES, LAYER = 2, 40, 1
LENGTHS = [0, 1, PAGE - 1, PAGE, PAGE + 1, PAGE * P]
GARBAGE = 1e30

# (dtype, L rows a lane, query heads, KV heads, head width, tolerance)
FAMILIES = [
    pytest.param(jnp.float32, 1, 4, 4, 64, 2e-5, id="fused_heads_f32"),
    pytest.param(jnp.bfloat16, 4, 24, 3, 128, 2e-2, id="grouped_heads_bf16")]


def _case(dtype, L, heads, kv_heads, D, lengths, seed=0):
    rng = onp.random.default_rng(seed)
    B = len(lengths)
    shape = (LAYERS, PAGES, PAGE, kv_heads * D)
    k_pool = jnp.asarray(rng.normal(size=shape), dtype)
    v_pool = jnp.asarray(rng.normal(size=shape), dtype)
    tables = rng.permutation(onp.arange(1, PAGES))[:B * P].reshape(B, P)
    q = jnp.asarray(rng.normal(size=(B, L, heads * D)), dtype)
    return (q, k_pool, v_pool, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


def _op(heads, kv_heads, interpret):
    return lambda *args: paged_attention(
        *args, LAYER, heads=heads, kv_heads=kv_heads, interpret=interpret)


def _f32(a):
    return a.astype(jnp.float32)


def _by_head(rows, kv_heads):
    """(B, C, kv_heads*D) rows, or a pool's pages through (B, P) tables, as
    float32 (B, C, kv_heads, D)."""
    return _f32(rows).reshape(rows.shape[0], -1, kv_heads,
                              rows.shape[-1] // kv_heads)


def _reference(q, k_pool, v_pool, tables, lengths, heads, kv_heads):
    """(acc, m, l) in float32 at ``highest`` over the same pages, dense."""
    B, L, _ = q.shape
    D = k_pool.shape[-1] // kv_heads
    k = _by_head(k_pool[LAYER][tables], kv_heads)
    v = _by_head(v_pool[LAYER][tables], kv_heads)
    qh = _f32(q).reshape(B, L, kv_heads, heads // kv_heads, D)
    s = jnp.einsum("blhgd,bchd->blhgc", qh, k,
                   precision="highest") / onp.sqrt(D)
    seen = (jnp.arange(k.shape[1])[None] < lengths[:, None])
    seen = seen[:, None, None, None]
    s = jnp.where(seen, s, -1e30)
    m = s.max(-1)
    p = jnp.where(seen, jnp.exp(s - m[..., None]), 0.0)
    acc = jnp.einsum("blhgc,bchd->blhgd", p, v, precision="highest")
    return (acc.reshape(B, L, heads, D), m.reshape(B, L, heads),
            p.sum(-1).reshape(B, L, heads))


@pytest.mark.parametrize("interpret", [True, None],
                         ids=["kernel", "plain_expression"])
@pytest.mark.parametrize("dtype,L,heads,kv_heads,D,tol", FAMILIES)
def test_context_part_matches_the_dense_reference(dtype, L, heads, kv_heads,
                                                  D, tol, interpret):
    args = _case(dtype, L, heads, kv_heads, D, LENGTHS)
    acc, m, l = _op(heads, kv_heads, interpret)(*args)
    want_acc, want_m, want_l = _reference(*args, heads, kv_heads)
    assert acc.dtype == m.dtype == l.dtype == jnp.float32
    # a lane of length 0: nothing seen, and nothing to merge
    assert float(jnp.abs(acc[0]).max()) == 0.0 and float(l[0].max()) == 0.0
    assert float(m[0].max()) == onp.float32(-1e30)
    onp.testing.assert_allclose(m[1:], want_m[1:], atol=tol * 10, rtol=tol)
    onp.testing.assert_allclose(l[1:], want_l[1:], rtol=tol * 5)
    onp.testing.assert_allclose(acc[1:] / l[1:, ..., None],
                                want_acc[1:] / want_l[1:, ..., None],
                                atol=tol)


@pytest.mark.parametrize("dtype,L,heads,kv_heads,D,tol", FAMILIES)
def test_what_lies_past_a_length_changes_no_bit(dtype, L, heads, kv_heads, D,
                                                tol):
    """The scratch page, the tail of a lane's last page and every page past
    it hold large finite garbage: their weight is exactly zero or they are
    never read, so the result is bitwise what zeros there give."""
    q, k_pool, v_pool, tables, lengths = _case(dtype, L, heads, kv_heads, D,
                                               LENGTHS)
    tables = tables.at[0].set(0)            # a padding lane: the scratch page
    pos = jnp.arange(P * PAGE).reshape(P, PAGE)

    def fill(pool, value):
        pool = pool.at[:, 0].set(value)
        for b, n in enumerate(LENGTHS):
            rows = jnp.where((pos >= n)[None, :, :, None], value,
                             pool[:, tables[b]])
            pool = pool.at[:, tables[b]].set(rows.astype(pool.dtype))
        return pool

    outs = [_op(heads, kv_heads, True)(
        q, fill(k_pool, value), fill(v_pool, -value), tables, lengths)
        for value in (0.0, GARBAGE)]
    for clean, dirty in zip(*outs):
        assert onp.array_equal(onp.asarray(clean), onp.asarray(dirty))


@pytest.mark.parametrize("dtype,L,heads,kv_heads,D,tol", FAMILIES)
def test_a_lane_depends_on_nothing_but_itself(dtype, L, heads, kv_heads, D,
                                              tol):
    """One lane's result, bitwise, alone in a bucket of one and among other
    lanes with other tables and lengths in buckets of four and eight."""
    q, k_pool, v_pool, tables, lengths = _case(
        dtype, L, heads, kv_heads, D, [PAGE * 2 + 3] * 8)
    op = _op(heads, kv_heads, True)
    alone = op(q[:1], k_pool, v_pool, tables[:1], lengths[:1])
    rng = onp.random.default_rng(1)
    for bucket, at in ((4, 2), (8, 7)):
        order = onp.concatenate([rng.permutation(onp.arange(1, bucket))[:at],
                                 [0], onp.arange(at + 1, bucket)])
        others = jnp.asarray(rng.integers(0, PAGE * P + 1, bucket),
                             jnp.int32).at[at].set(lengths[0])
        among = op(q[order], k_pool, v_pool, tables[order], others)
        for one, many in zip(alone, among):
            assert onp.array_equal(onp.asarray(one[0]), onp.asarray(many[at]))


@pytest.mark.parametrize("dtype,L,heads,kv_heads,D,tol", FAMILIES)
def test_the_two_parts_merge_into_one_softmax(dtype, L, heads, kv_heads, D,
                                              tol):
    """The context part from the kernel and the step's own rows through
    ``block_attention``: what dense attention over context + block gives."""
    lengths = [n - n % L for n in LENGTHS]
    q, k_pool, v_pool, tables, lens = _case(dtype, L, heads, kv_heads, D,
                                            lengths)
    rng = onp.random.default_rng(2)
    B = len(lengths)
    k = jnp.asarray(rng.normal(size=(B, L, kv_heads * D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, L, kv_heads * D)), dtype)
    positions = lens[:, None] + jnp.arange(L, dtype=jnp.int32)[None]
    ctx = _op(heads, kv_heads, True)(q, k_pool, v_pool, tables, lens)
    got = block_attention(q, k, v, positions, *ctx, heads=heads,
                          kv_heads=kv_heads, block_length=L)
    kc = jnp.concatenate([_by_head(k_pool[LAYER][tables], kv_heads),
                          _by_head(k, kv_heads)], 1)
    vc = jnp.concatenate([_by_head(v_pool[LAYER][tables], kv_heads),
                          _by_head(v, kv_heads)], 1)
    C = P * PAGE
    seen = jnp.concatenate([jnp.arange(C)[None] < lens[:, None],
                            jnp.ones((B, L), bool)], 1)
    qh = _f32(q).reshape(B, L, kv_heads, heads // kv_heads, D)
    s = jnp.einsum("blhgd,bchd->blhgc", qh, kc,
                   precision="highest") / onp.sqrt(D)
    s = jnp.where(seen[:, None, None, None], s, -jnp.inf)
    want = jnp.einsum("blhgc,bchd->blhgd", jax.nn.softmax(s, -1), vc,
                      precision="highest").reshape(B, L, heads * D)
    assert got.dtype == dtype
    onp.testing.assert_allclose(_f32(got), want, atol=tol)


# ---------------------------------------------------------------------------
# a latent pool: one array whose row is every head's key and, in its first
# columns, their value (latent attention's absorbed form, cut small: 8 heads
# on a row of 96 + 32 numbers; 200 = 160 + 40 stored 256 wide, in bfloat16)
# ---------------------------------------------------------------------------
LATENT = [
    pytest.param(jnp.float32, 8, 128, 96, 128, 2e-5, id="f32_row128"),
    pytest.param(jnp.bfloat16, 16, 200, 160, 256, 2e-2, id="bf16_row200in256")]


def _latent_case(dtype, heads, row, stored, lengths, seed=0):
    rng = onp.random.default_rng(seed)
    B = len(lengths)
    pool = onp.zeros((LAYERS, PAGES, PAGE, stored), onp.float32)
    pool[..., :row] = rng.normal(size=(LAYERS, PAGES, PAGE, row))
    tables = rng.permutation(onp.arange(1, PAGES))[:B * P].reshape(B, P)
    q = jnp.asarray(rng.normal(size=(B, 1, heads * row)), dtype)
    return (q, jnp.asarray(pool, dtype), jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


def _latent_op(heads, v_dim, interpret):
    return lambda q, pool, tables, lengths: paged_attention(
        q, pool, None, tables, lengths, LAYER, heads=heads, kv_heads=1,
        v_dim=v_dim, sm_scale=0.11, interpret=interpret)


@pytest.mark.parametrize("interpret", [True, None],
                         ids=["kernel", "plain_expression"])
@pytest.mark.parametrize("dtype,heads,row,v_dim,stored,tol", LATENT)
def test_a_latent_pool_is_read_as_keys_and_as_values(dtype, heads, row, v_dim,
                                                     stored, tol, interpret):
    """Lengths that end inside a page, on one and past one: the kernel's
    context part is the dense expression's, the values the row's first
    ``v_dim`` columns, queries narrower than the stored row padded to it."""
    q, pool, tables, lengths = _latent_case(dtype, heads, row, stored,
                                            LENGTHS)
    acc, m, l = _latent_op(heads, v_dim, interpret)(q, pool, tables, lengths)
    assert acc.shape == (len(LENGTHS), 1, heads, v_dim)
    k = _f32(pool[LAYER][tables]).reshape(len(LENGTHS), P * PAGE, stored)
    qh = _f32(q).reshape(len(LENGTHS), heads, row)
    s = jnp.einsum("bhd,bcd->bhc", qh, k[..., :row],
                   precision="highest") * 0.11
    seen = (jnp.arange(P * PAGE)[None] < lengths[:, None])[:, None]
    s = jnp.where(seen, s, -1e30)
    want_m = s.max(-1)
    p = jnp.where(seen, jnp.exp(s - want_m[..., None]), 0.0)
    want_acc = jnp.einsum("bhc,bcd->bhd", p, k[..., :v_dim],
                          precision="highest")
    want_l = p.sum(-1)
    assert float(jnp.abs(acc[0]).max()) == 0.0 and float(l[0].max()) == 0.0
    onp.testing.assert_allclose(m[1:, 0], want_m[1:], atol=tol * 10, rtol=tol)
    onp.testing.assert_allclose(l[1:, 0], want_l[1:], rtol=tol * 5)
    onp.testing.assert_allclose(acc[1:, 0] / l[1:, 0, :, None],
                                want_acc[1:] / want_l[1:, :, None], atol=tol)


def test_a_latent_lane_depends_on_its_own_pages_and_length_alone():
    """Bitwise: garbage on the scratch page, past a lane's length and on
    pages past it changes nothing, and a lane alone in a bucket of one reads
    what it reads among seven others."""
    heads, row, v_dim, stored = 8, 128, 96, 128
    q, pool, tables, lengths = _latent_case(
        jnp.float32, heads, row, stored, [PAGE * 2 + 3] * 8)
    op = _latent_op(heads, v_dim, True)
    alone = op(q[:1], pool, tables[:1], lengths[:1])
    pos = jnp.arange(P * PAGE).reshape(P, PAGE)
    dirty = pool.at[:, 0].set(GARBAGE)
    rows = jnp.where((pos >= lengths[0])[None, :, :, None], GARBAGE,
                     dirty[:, tables[0]])
    dirty = dirty.at[:, tables[0]].set(rows)
    others = jnp.asarray([5, 0, 64, 17, 33, 1, 48, 16], jnp.int32) \
        .at[3].set(lengths[0])
    order = onp.asarray([1, 2, 4, 0, 3, 5, 6, 7])
    among = op(q[order], dirty, tables[order], others)
    for one, many in zip(alone, among):
        assert onp.array_equal(onp.asarray(one[0]), onp.asarray(many[3]))


def test_a_latent_pool_states_its_value_width():
    q, pool, tables, lengths = _latent_case(jnp.float32, 8, 128, 128, [5])
    with pytest.raises(ValueError, match="latent pool"):
        paged_attention(q, pool, None, tables, lengths, LAYER, heads=8,
                        kv_heads=1)
    with pytest.raises(ValueError, match="latent pool"):
        paged_attention(q, pool, pool, tables, lengths, LAYER, heads=8,
                        kv_heads=1, v_dim=96)


# ---------------------------------------------------------------------------
# a lower bound a lane, and a table that is a ring
# ---------------------------------------------------------------------------
WINDOW = 40
RING = -(-(WINDOW + PAGE) // PAGE)          # 4 pages: the window and a page


def _ring_case(dtype, heads, kv_heads, D, lengths, starts=None, seed=0):
    """Lanes whose positions run round a ring of RING pages: position p of
    lane b lies in ``tables[b, p // PAGE % RING]``. Each lane's live
    positions hold fresh rows; the rest of its ring what it held before."""
    q, k_pool, v_pool, tables, _ = _case(dtype, 1, heads, kv_heads, D,
                                         lengths, seed)
    lengths = onp.asarray(lengths, onp.int32)
    if starts is None:
        starts = onp.maximum(lengths - WINDOW + 1, 0)
    return (q, k_pool, v_pool, tables[:, :RING],
            jnp.asarray(lengths), jnp.asarray(starts, jnp.int32))


def _ring_reference(q, k_pool, v_pool, tables, lengths, starts, heads,
                    kv_heads):
    """(out, m, l) a lane, float32 at ``highest``: positions ``starts[b] ..
    lengths[b] - 1`` gathered one by one through the ring."""
    D = k_pool.shape[-1] // kv_heads
    outs = []
    for b in range(len(lengths)):
        pos = onp.arange(int(starts[b]), int(lengths[b]))
        if not len(pos):
            outs.append(None)
            continue
        page = onp.asarray(tables)[b, pos // PAGE % tables.shape[1]]
        k = _f32(k_pool[LAYER, page, pos % PAGE]).reshape(-1, kv_heads, D)
        v = _f32(v_pool[LAYER, page, pos % PAGE]).reshape(-1, kv_heads, D)
        qh = _f32(q[b, 0]).reshape(kv_heads, heads // kv_heads, D)
        s = jnp.einsum("hgd,chd->hgc", qh, k,
                       precision="highest") / onp.sqrt(D)
        m = s.max(-1)
        p = jnp.exp(s - m[..., None])
        acc = jnp.einsum("hgc,chd->hgd", p, v, precision="highest")
        outs.append((acc.reshape(heads, D), m.reshape(heads),
                     p.sum(-1).reshape(heads)))
    return outs


# lengths whose bounds lie at 0, inside a page, at a page's edge (length 56:
# bound 17; 55: 16), and past the ring's 64 positions one and several times
BOUNDED = [0, 1, 40, 41, 55, 56, 65, 200, 1000]


@pytest.mark.parametrize("interpret", [True, None],
                         ids=["kernel", "plain_expression"])
@pytest.mark.parametrize("dtype,heads,kv_heads,D,tol", [
    pytest.param(jnp.float32, 4, 4, 64, 2e-5, id="fused_heads_f32"),
    pytest.param(jnp.bfloat16, 32, 4, 128, 2e-2, id="grouped_heads_bf16")])
def test_a_bound_and_a_ring_match_the_dense_expression(dtype, heads,
                                                       kv_heads, D, tol,
                                                       interpret):
    args = _ring_case(dtype, heads, kv_heads, D, BOUNDED)
    acc, m, l = paged_attention(*args[:5], LAYER, args[5], heads=heads,
                                kv_heads=kv_heads, interpret=interpret)
    want = _ring_reference(*args, heads, kv_heads)
    assert float(l[0].max()) == 0.0          # length 0: nothing seen
    for b, row in enumerate(want):
        if row is None:
            continue
        w_acc, w_m, w_l = row
        onp.testing.assert_allclose(m[b, 0], w_m, atol=tol * 10, rtol=tol)
        onp.testing.assert_allclose(l[b, 0], w_l, rtol=tol * 5)
        onp.testing.assert_allclose(acc[b, 0] / l[b, 0][:, None],
                                    w_acc / w_l[:, None], atol=tol)


def test_what_lies_behind_a_bound_changes_no_bit(monkeypatch):
    """Garbage behind each lane's bound (the head of its first live page, the
    ring's older pages) and past its length: bitwise what zeros there give.
    Blocks of two pages and chunks of one, so that a lane's live pages span
    blocks and the first block starts inside the ring."""
    from mxnet_tpu.ops.pallas import paged_attention as mod
    monkeypatch.setattr(mod, "_BLOCK_BYTES", 2 * PAGE * 64 * 4 * 4)
    monkeypatch.setattr(mod, "_CHUNK_BYTES", PAGE * 64 * 4 * 4)
    mod._jitted.cache_clear()
    lengths = [3, 41, 56, 65, 200, 1000]
    q, k_pool, v_pool, tables, lens, starts = _ring_case(
        jnp.float32, 4, 4, 64, lengths)

    def fill(pool, value):
        for b, n in enumerate(lengths):
            live = onp.zeros((RING, PAGE), bool)
            pos = onp.arange(int(starts[b]), n)
            live[pos // PAGE % RING, pos % PAGE] = True
            rows = jnp.where(jnp.asarray(live)[None, :, :, None],
                             pool[:, tables[b]], value)
            pool = pool.at[:, tables[b]].set(rows.astype(pool.dtype))
        return pool.at[:, 0].set(value)

    try:
        outs = [paged_attention(q, fill(k_pool, value), fill(v_pool, -value),
                                tables, lens, LAYER, starts, heads=4,
                                kv_heads=4, interpret=True)
                for value in (0.0, GARBAGE)]
        want = _ring_reference(q, k_pool, v_pool, tables, lens, starts, 4, 4)
    finally:
        mod._jitted.cache_clear()
    for clean, dirty in zip(*outs):
        assert onp.array_equal(onp.asarray(clean), onp.asarray(dirty))
    acc, _, l = outs[1]
    for b, (w_acc, _, w_l) in enumerate(want):
        onp.testing.assert_allclose(acc[b, 0] / l[b, 0][:, None],
                                    w_acc / w_l[:, None], atol=2e-5)


def test_a_bounded_lane_depends_on_nothing_but_itself():
    lengths = [200] * 8
    q, k_pool, v_pool, tables, lens, starts = _ring_case(
        jnp.bfloat16, 32, 4, 128, lengths)
    op = lambda q, t, n, s: paged_attention(
        q, k_pool, v_pool, t, n, LAYER, s, heads=32, kv_heads=4,
        interpret=True)
    alone = op(q[:1], tables[:1], lens[:1], starts[:1])
    rng = onp.random.default_rng(2)
    order = onp.asarray([3, 5, 0, 6, 1, 2, 4, 7])
    others = rng.integers(0, 400, 8)
    others[2] = 200
    among = op(q[order], tables[order], jnp.asarray(others, jnp.int32),
               jnp.asarray(onp.maximum(others - WINDOW + 1, 0), jnp.int32))
    for one, many in zip(alone, among):
        assert onp.array_equal(onp.asarray(one[0]), onp.asarray(many[2]))


@pytest.mark.parametrize("dtype,L,heads,kv_heads,D,tol", FAMILIES)
def test_with_no_bound_the_result_is_what_it_was(dtype, L, heads, kv_heads,
                                                 D, tol):
    """A bound of 0 on a table that never wraps reads what no bound reads,
    bit for bit; and no bound at all takes the kernel that was there (its
    traced program names no fourth prefetched scalar)."""
    args = _case(dtype, L, heads, kv_heads, D, LENGTHS)
    plain = _op(heads, kv_heads, True)(*args)
    bounded = paged_attention(*args, LAYER, jnp.zeros(len(LENGTHS),
                                                      jnp.int32),
                              heads=heads, kv_heads=kv_heads, interpret=True)
    for a, b in zip(plain, bounded):
        assert onp.array_equal(onp.asarray(a), onp.asarray(b))
    def prefetched(*extra):
        jaxpr = jax.make_jaxpr(lambda *a: paged_attention(
            *a[:5], LAYER, *a[5:], heads=heads, kv_heads=kv_heads,
            interpret=True))(*args, *extra)
        found = []

        def walk(j):
            for eqn in j.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append(eqn.params["grid_mapping"].num_index_operands)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        return found

    assert prefetched() == [3]          # layer, lengths, tables
    assert prefetched(jnp.zeros(len(LENGTHS), jnp.int32)) == [4]
