"""Multi-chip sharding tests on the virtual 8-device CPU mesh (conftest).

Pattern follows the reference's local-multiprocess distributed tests
(tests/nightly/dist_sync_kvstore.py via tools/launch.py --launcher local):
everything runs in one process, the mesh supplies the "cluster"."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.gluon import nn, loss as gloss


def test_make_mesh_shapes():
    mesh = parallel.make_mesh({"dp": 4, "tp": 2})
    assert mesh.size == 8
    assert mesh.shape == {"dp": 4, "tp": 2}
    mesh2 = parallel.make_mesh({"dp": -1, "tp": 2})
    assert mesh2.shape["dp"] == 4


def test_collectives_shard_map():
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = parallel.make_mesh({"dp": 8})

    def f(x):
        return parallel.all_reduce(x, "dp")

    fn = shard_map(f, mesh=mesh.mesh, in_specs=P("dp"), out_specs=P("dp"))
    x = jnp.arange(8.0)
    out = fn(x)
    assert float(out[0]) == float(jnp.sum(x))


def test_train_step_data_parallel_matches_single_device():
    """The fused dp step must agree with the single-device eager path."""
    import jax.numpy as jnp
    onp.random.seed(0)
    xs = onp.random.randn(16, 8).astype("float32")
    ys = onp.random.randn(16, 1).astype("float32")

    def build():
        net = nn.Dense(1, in_units=8)
        net.initialize(mx.init.Constant(0.05))
        return net

    # eager single-device reference
    net_ref = build()
    trainer = mx.gluon.Trainer(net_ref.collect_params(), "sgd",
                               {"learning_rate": 0.1}, kvstore=None)
    l2 = gloss.L2Loss()
    for _ in range(3):
        x, y = mx.nd.array(xs), mx.nd.array(ys)
        with mx.autograd.record():
            out = net_ref(x)
            L = l2(out, y).mean()
        L.backward()
        trainer.step(1, ignore_stale_grad=True)

    # fused multi-chip step
    net_par = build()
    mesh = parallel.make_mesh({"dp": 8})
    step = parallel.ParallelTrainStep(
        net_par, gloss.L2Loss(), mx.optimizer.SGD(learning_rate=0.1), mesh)
    for _ in range(3):
        loss = step(xs, ys)
    step.sync_to_block()

    w_ref = net_ref.weight.data().asnumpy()
    w_par = net_par.weight.data().asnumpy()
    onp.testing.assert_allclose(w_ref, w_par, rtol=2e-5, atol=2e-5)


def test_train_step_tensor_parallel():
    """Dense weight sharded over tp: GSPMD handles the all-gather; result must
    match the replicated run."""
    from jax.sharding import PartitionSpec as P
    onp.random.seed(1)
    xs = onp.random.randn(8, 16).astype("float32")
    ys = onp.random.randn(8, 32).astype("float32")

    def run(shard):
        net = nn.Dense(32, in_units=16)
        net.initialize(mx.init.Xavier(rnd_type="gaussian", magnitude=1))
        # deterministic init for comparison
        net.weight.set_data(mx.nd.array(
            onp.linspace(-0.1, 0.1, 32 * 16).reshape(32, 16).astype("float32")))
        net.bias.set_data(mx.nd.array(onp.zeros(32, "float32")))
        if shard:
            net.weight.shard(P("tp", None))
        mesh = parallel.make_mesh({"dp": 4, "tp": 2})
        step = parallel.ParallelTrainStep(
            net, gloss.L2Loss(), mx.optimizer.SGD(learning_rate=0.05), mesh)
        for _ in range(2):
            step(xs, ys)
        step.sync_to_block()
        return net.weight.data().asnumpy()

    onp.testing.assert_allclose(run(False), run(True), rtol=2e-5, atol=2e-5)


def test_train_step_batchnorm_aux_updates():
    """BatchNorm moving stats must update through the pure aux path."""
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.BatchNorm(), nn.Dense(2))
    net.initialize()
    net(mx.nd.array(onp.zeros((2, 4), "float32")))  # materialize deferred shapes
    mesh = parallel.make_mesh({"dp": 8})
    step = parallel.ParallelTrainStep(
        net, gloss.L2Loss(), mx.optimizer.SGD(learning_rate=0.01), mesh)
    bn = net[1]
    before = bn.running_mean.data().asnumpy().copy()
    xs = onp.random.randn(16, 4).astype("float32") * 3 + 5
    ys = onp.random.randn(16, 2).astype("float32")
    for _ in range(2):
        step(xs, ys)
    step.sync_to_block()
    after = bn.running_mean.data().asnumpy()
    assert not onp.allclose(before, after)


def test_donating_step_leaves_block_parameters_alive():
    """A block whose parameters sit on a device of the mesh (a net built
    under ``mx.tpu(0)`` and trained on that chip) keeps them: the step
    donates its own copies, never an alias of the block's arrays."""
    import jax
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4, activation="relu"), nn.Dense(2))
    net.initialize()
    x = onp.random.randn(4, 4).astype("float32")
    want = net(mx.nd.array(x)).asnumpy()
    mesh = parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = parallel.ParallelTrainStep(
        net, gloss.L2Loss(), mx.optimizer.SGD(learning_rate=0.1), mesh)
    step(x, onp.zeros((4, 2), "float32"))
    assert onp.array_equal(net(mx.nd.array(x)).asnumpy(), want)


def test_param_format_auto_matches_default():
    """param_format='auto' (XLA-chosen carried-state layouts via AOT
    compile) must train to the same weights as the default layout path."""
    def run(auto):
        onp.random.seed(5)
        mx.random.seed(5)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, in_units=8), nn.BatchNorm(), nn.Dense(4))
        net.initialize()
        net(mx.nd.array(onp.zeros((2, 8), "float32")))
        mesh = parallel.make_mesh({"dp": 8})
        step = parallel.ParallelTrainStep(
            net, gloss.L2Loss(), mx.optimizer.SGD(learning_rate=0.05), mesh,
            param_format="auto" if auto else None)
        xs = onp.random.randn(3, 16, 8).astype("float32")
        ys = onp.random.randn(3, 16, 4).astype("float32")
        losses = step.step_n(xs, ys)          # AOT path
        losses2 = step.step_n(xs, ys)         # steady state (cached compile)
        # single-step interleave + a batch-shape change: both must retrace /
        # re-own the carried state rather than crash or corrupt (r5 review)
        l_single = step(xs[0, :8], ys[0, :8])
        losses3 = step.step_n(xs[:, :8], ys[:, :8])
        step.sync_to_block()
        return (net[0].weight.data().asnumpy(), losses.asnumpy(),
                losses2.asnumpy(), float(l_single.asscalar()),
                losses3.asnumpy())

    w_ref, l_ref, l2_ref, ls_ref, l3_ref = run(False)
    w_auto, l_auto, l2_auto, ls_auto, l3_auto = run(True)
    onp.testing.assert_allclose(l_auto, l_ref, rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(l2_auto, l2_ref, rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(ls_auto, ls_ref, rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(l3_auto, l3_ref, rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(w_auto, w_ref, rtol=1e-5, atol=1e-6)


def test_ring_attention_matches_dense():
    import jax
    import jax.numpy as jnp
    onp.random.seed(2)
    B, H, S, D = 2, 4, 32, 16
    q = jnp.asarray(onp.random.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(onp.random.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(onp.random.randn(B, H, S, D).astype("float32"))

    mesh = parallel.make_mesh({"sp": 8})
    out_ring = parallel.ring_self_attention(q, k, v, mesh)

    scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = jax.nn.softmax(s, axis=-1)
    out_ref = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    onp.testing.assert_allclose(onp.asarray(out_ring), onp.asarray(out_ref),
                                rtol=2e-4, atol=2e-4)


def test_ring_attention_causal():
    import jax
    import jax.numpy as jnp
    onp.random.seed(3)
    B, H, S, D = 1, 2, 16, 8
    q = jnp.asarray(onp.random.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(onp.random.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(onp.random.randn(B, H, S, D).astype("float32"))

    mesh = parallel.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    out_ring = parallel.ring_self_attention(q, k, v, mesh, causal=True)

    scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    mask = onp.tril(onp.ones((S, S), bool))
    s = jnp.where(jnp.asarray(mask)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out_ref = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    onp.testing.assert_allclose(onp.asarray(out_ring), onp.asarray(out_ref),
                                rtol=2e-4, atol=2e-4)


def test_step_n_matches_step():
    """K fused steps via lax.scan == K separate step() calls, including an lr
    schedule and Adam's per-step t (deterministic model, no dropout)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.optimizer import lr_scheduler

    def build():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
        net.initialize(mx.init.Xavier())
        net(nd.array(onp.zeros((1, 8), "float32")))
        import jax
        mesh = parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        sched = lr_scheduler.FactorScheduler(step=2, factor=0.5)
        opt = mx.optimizer.Adam(learning_rate=0.05, lr_scheduler=sched)
        return net, parallel.ParallelTrainStep(
            net, gloss.SoftmaxCrossEntropyLoss(), opt, mesh)

    rng = onp.random.RandomState(5)
    X = rng.rand(6, 8, 8).astype("float32")
    Y = rng.randint(0, 4, (6, 8)).astype("float32")

    mx.random.seed(11)
    onp.random.seed(11)
    net1, s1 = build()
    losses1 = [float(s1(X[i], Y[i]).asscalar()) for i in range(6)]

    mx.random.seed(11)
    onp.random.seed(11)
    net2, s2 = build()
    losses2 = list(s2.step_n(X[:3], Y[:3]).asnumpy()) + \
        list(s2.step_n(X[3:], Y[3:]).asnumpy())
    onp.testing.assert_allclose(losses1, losses2, rtol=1e-4, atol=1e-5)

    s1.sync_to_block()
    s2.sync_to_block()
    for (n1, p1), (n2, p2) in zip(sorted(net1.collect_params().items()),
                                  sorted(net2.collect_params().items())):
        onp.testing.assert_allclose(p1.data().asnumpy(), p2.data().asnumpy(),
                                    rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# dropout under a train step whose batch is divided: the mask is drawn a shard
# at a time (ops/nn.py:_dropout_bits reads parallel.mesh.batch_axes)
# ---------------------------------------------------------------------------
_P = 0.25
# mesh, the published batch axes, the input's spec, its shape, dropout's
# broadcast axes, and what the draw has to be: (kind, shards of dimension 0)
DROPOUT_DRAWS = [
    pytest.param({"dp": 4}, ("dp",), ("dp",), (64, 8, 16), (),
                 ("per_shard", 4), id="dp4"),
    pytest.param({"dp": 2, "tp": 2}, ("dp",), ("dp", None, "tp"), (64, 8, 16),
                 (), ("per_shard", 2), id="dp2_tp2_leaves_tp_alone"),
    pytest.param({"dp": 2, "fsdp": 2}, ("dp", "fsdp"), (("dp", "fsdp"),),
                 (64, 8, 16), (), ("per_shard", 4), id="batch_over_two_axes"),
    pytest.param({"dp": 4}, ("dp",), ("dp",), (64, 8, 16), (0,),
                 ("whole", 1), id="mask_broadcast_over_the_batch"),
    pytest.param({"dp": 4}, ("dp",), (), (6, 8, 16), (),
                 ("whole", 1), id="rows_the_devices_do_not_divide"),
]


@pytest.mark.parametrize("axes,batch,spec,shape,drop_axes,want", DROPOUT_DRAWS)
def test_dropout_mask_under_published_batch_axes(axes, batch, spec, shape,
                                                 drop_axes, want):
    import re
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import nn as ops
    from mxnet_tpu.parallel.mesh import batch_axes

    mesh = parallel.make_mesh(axes, devices=jax.devices()[:4])
    said = []

    def masked(x, key):
        with batch_axes(mesh, batch, said.append):
            return ops.dropout(x, key, p=_P, axes=drop_axes, training=True)

    fn = jax.jit(masked, in_shardings=(mesh.sharding(*spec),
                                       mesh.replicated()))
    x = jnp.ones(shape, jnp.float32)
    key = jax.random.PRNGKey(3)
    kind, shards = want
    out = onp.asarray(fn(x, key))
    assert said == [kind]

    # a valid mask: every entry 0 or 1/keep, kept at the rate 1 - p
    keep = 1.0 - _P
    mask = out > 0
    assert set(onp.unique(out)) <= {0.0, onp.float32(1.0 / keep)}
    drawn = mask[0] if drop_axes else mask
    sd = (keep * _P / drawn.size) ** 0.5
    assert abs(drawn.mean() - keep) < 3 * sd
    # each shard draws from its own fold of the key
    parts = mask.reshape((shards, -1))
    for i in range(shards):
        for j in range(i):
            assert (parts[i] != parts[j]).any()
    # the same key on the same mesh: the same mask; another key: another
    onp.testing.assert_array_equal(onp.asarray(fn(x, key)), out)
    assert (onp.asarray(fn(x, jax.random.PRNGKey(4))) != out).any()
    # the backward uses the forward's mask
    up = jnp.arange(x.size, dtype=jnp.float32).reshape(shape) / x.size
    _, vjp = jax.vjp(lambda a: fn(a, key), x)
    onp.testing.assert_array_equal(onp.asarray(vjp(up)[0]),
                                   out * onp.asarray(up))

    # what is drawn where, in the program as lowered (an rbg key's draw is
    # one op there): the shard's rows under a shard_map over the batch axes
    # alone, or the whole mask and no shard_map
    text = fn.lower(x, jax.random.key(3, impl="rbg")).as_text()
    drawn_shapes = re.findall(r"rng_bit_generator.*-> \(tensor<2xui64>, "
                              r"tensor<([\dx]+)xui32>\)", text)
    manual = re.findall(r"manual_axes=\{([^}]*)\}", text)
    mask_shape = [1 if a in drop_axes else d for a, d in enumerate(shape)]
    mask_shape[0] //= shards
    assert drawn_shapes == ["x".join(map(str, mask_shape))]
    # (an axis left to the partitioner shows up only inside, where
    # axis_index of the batch axes is read)
    assert manual[:1] == ([", ".join(f'"{a}"' for a in batch)]
                          if kind == "per_shard" else [])


def _dropout_step(mesh_axes, n_devices):
    import jax
    from mxnet_tpu.gluon import nn as gnn
    net = gnn.HybridSequential()
    net.add(gnn.Dense(16, in_units=8, flatten=False), gnn.Dropout(_P),
            gnn.Dense(4, in_units=16, flatten=False))
    net.initialize(mx.init.Xavier())
    mesh = parallel.make_mesh(mesh_axes, devices=jax.devices()[:n_devices])
    return parallel.ParallelTrainStep(
        net, gloss.L2Loss(), mx.optimizer.SGD(learning_rate=0.1), mesh)


def _draws():
    from mxnet_tpu.parallel.train_step import _DROPOUT_DRAWS
    return {kind: _DROPOUT_DRAWS.labels(kind).value
            for kind in ("per_shard", "whole")}


@pytest.mark.parametrize("entry", ["step", "step_n"])
def test_a_data_parallel_step_draws_per_shard_and_repeats_bitwise(entry):
    """The retry in ``_step_impl`` and the NumericsGuard's replay rest on it:
    the same key on the same mesh gives the same masks."""
    rng = onp.random.RandomState(2)
    X = rng.randn(3, 32, 8).astype("float32")
    Y = rng.randn(3, 32, 4).astype("float32")
    step = _dropout_step({"dp": 4}, 4)
    start = step.state_dict()
    before = _draws()

    def run():
        mx.random.seed(5)
        if entry == "step_n":
            return step.step_n(X, Y).asnumpy()
        return onp.stack([step(x, y).asnumpy() for x, y in zip(X, Y)])

    first = run()
    step.load_state_dict(start)
    onp.testing.assert_array_equal(run(), first)
    assert len(set(first.tolist())) == 3
    # one Dropout, traced once: counted when traced, not when run
    after = _draws()
    assert after["per_shard"] - before["per_shard"] == 1
    assert after["whole"] == before["whole"]


def test_a_one_device_step_is_traced_as_with_nothing_published(monkeypatch):
    """Mesh {"dp": 1} divides nothing: the step lowers to the text it has
    with no batch axes published, and no draw is counted."""
    import contextlib
    import jax
    import jax.numpy as jnp

    def lowered(step):
        step._build()
        n = len(step._trainable_idx)
        return step._step_fn.lower(
            step.params, [], step._opt_states, jnp.zeros((32, 8)),
            jnp.zeros((32, 4)), (), jax.random.PRNGKey(0), jnp.zeros(n),
            jnp.zeros(n), jnp.float32(1)).as_text()

    before = _draws()
    text = lowered(_dropout_step({"dp": 1}, 1))
    assert _draws() == before
    bare = _dropout_step({"dp": 1}, 1)
    monkeypatch.setattr(bare, "_batch_scope", contextlib.nullcontext)
    assert text == lowered(bare)
    assert "manual_axes" not in text
    # four devices: the same model does publish, and the text says so
    assert "manual_axes" in lowered(_dropout_step({"dp": 4}, 4))
