"""Serving-fabric tests: slice carving, mesh-sharded endpoint twins, the
capacity-weighted pool, the sharded executable-cache trigger key, and the
multi-host front door (tier-1, 8-device CPU mesh via conftest).

The load-bearing acceptance oracle: a mesh-sharded replica's outputs are
BITWISE equal to the single-chip reference endpoint's through the batcher —
dense and decode paths both. Only the batch axis ever shards, so no
cross-device floating-point reduction exists to reorder.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, nd, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.cache import executable_cache as xcache
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import mesh as pmesh
from mxnet_tpu.serving import ServingPool
from mxnet_tpu.serving.fabric import (FrontDoor, ShardedDecodeEndpoint,
                                      ShardedEndpoint, SliceSpec, plan_slices)
from mxnet_tpu.telemetry import compile_ledger


def _devices(n=None):
    import jax
    devs = jax.devices()
    return devs if n is None else devs[:n]


def _mlp(seed=0, in_dim=8, out_dim=4):
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
    net.initialize()
    net(nd.array(onp.random.randn(2, in_dim).astype("float32")))
    return net


def _copy_weights(src, dst):
    for s, d in zip(src.collect_params().values(),
                    dst.collect_params().values()):
        d.set_data(nd.array(s.data().asnumpy()))


def _twin(seed=0, **kw):
    """Two blocks with IDENTICAL weights (deferred init draws are not
    reproducible across instances, so twinning must copy)."""
    a = _mlp(seed, **kw)
    b = _mlp(seed, **kw)
    _copy_weights(a, b)
    return a, b


# ---------------------------------------------------------------------------
# slice carving (parallel/mesh.py)
# ---------------------------------------------------------------------------
def test_carve_slices_asymmetric_sizes():
    devs = _devices()
    slices = pmesh.carve_slices([4, 2, 1], devices=devs)
    assert [len(s) for s in slices] == [4, 2, 1]
    flat = [d for s in slices for d in s]
    assert flat == devs[:7]                    # contiguous, no sharing
    assert len(set(id(d) for d in flat)) == 7


def test_carve_slices_count_not_dividing_leaves_tail_uncarved():
    devs = _devices()
    slices = pmesh.carve_slices([3, 3], devices=devs)
    assert [len(s) for s in slices] == [3, 3]
    used = {id(d) for s in slices for d in s}
    leftover = [d for d in devs if id(d) not in used]
    assert len(leftover) == len(devs) - 6      # tail stays available


def test_carve_slices_single_device_degenerate():
    slices = pmesh.carve_slices([1], devices=_devices())
    assert len(slices) == 1 and len(slices[0]) == 1
    spec = SliceSpec(0, slices[0])
    assert spec.capacity == 1
    assert spec.make_mesh().size == 1


def test_carve_slices_rejects_oversubscription_and_bad_sizes():
    devs = _devices()
    with pytest.raises(MXNetError):
        pmesh.carve_slices([len(devs), 1], devices=devs)
    with pytest.raises(MXNetError):
        pmesh.carve_slices([0], devices=devs)
    with pytest.raises(MXNetError):
        pmesh.carve_slices([], devices=devs)


def test_plan_slices_specs_and_stable_names():
    specs = plan_slices([4, 2])
    assert [s.capacity for s in specs] == [4, 2]
    assert specs[0].name == "slice[dp=4]"      # axis layout, no device ids
    with pytest.raises(MXNetError):
        plan_slices([2, 2], axes=[{"dp": 2}])  # axes/sizes length mismatch
    with pytest.raises(MXNetError):
        SliceSpec(0, _devices(4), axes={"dp": 2})  # 2 != 4 devices


# ---------------------------------------------------------------------------
# sharded bucket-ladder constraints
# ---------------------------------------------------------------------------
def test_sharded_buckets_must_divide_by_shard():
    sl = plan_slices([4])[0]
    net = _mlp(11)
    with pytest.raises(MXNetError):
        ShardedEndpoint("fab_bad1", net, input_shapes=[(8,)],
                        max_batch_size=6, slice_spec=sl)   # 6 % 4 != 0
    with pytest.raises(MXNetError):
        ShardedEndpoint("fab_bad2", net, input_shapes=[(8,)],
                        max_batch_size=8, buckets=[2, 8], slice_spec=sl)
    ep = ShardedEndpoint("fab_lad", net, input_shapes=[(8,)],
                         max_batch_size=8, slice_spec=sl)
    try:
        assert tuple(ep.buckets) == (4, 8)     # pow2 ladder, filtered
        assert ep.capacity == 4
    finally:
        serving.unregister("fab_lad")


# ---------------------------------------------------------------------------
# the acceptance oracle: sharded replica bitwise == single-chip reference,
# THROUGH THE BATCHER, dense and decode, on the 8-device CPU mesh
# ---------------------------------------------------------------------------
def test_sharded_dense_bitwise_through_batcher():
    ref_net, sh_net = _twin(21)
    # both sides serve every batch in bucket 8, so the same rows are
    # compared at the same batch size; at bucket 4 over dp=4 each device
    # would hold one row, which XLA:CPU computes with a matrix-vector
    # kernel that rounds one ulp away from the reference's batch of 4
    ref = serving.ModelEndpoint("fab_ref", ref_net, input_shapes=[(8,)],
                                max_batch_size=8, buckets=[8])
    sl = plan_slices([4])[0]
    ep = ShardedEndpoint("fab_sh", sh_net, input_shapes=[(8,)],
                         max_batch_size=8, buckets=[8], slice_spec=sl)
    srv_ref = serving.InferenceServer(batch_timeout_ms=1.0)
    srv_sh = serving.InferenceServer(batch_timeout_ms=1.0)
    try:
        srv_ref.register(ref)
        srv_sh.register(ep)
        srv_ref.start()
        srv_sh.start()
        rng = onp.random.RandomState(7)
        batches = [rng.randn(r, 8).astype("float32")
                   for r in (1, 3, 8, 5, 2)] + \
                  [rng.randn(8).astype("float32")]      # squeeze path
        fr = [srv_ref.submit("fab_ref", b) for b in batches]
        fs = [srv_sh.submit("fab_sh", b) for b in batches]
        for a, b in zip(fr, fs):
            av = a.result(timeout=60).asnumpy()
            bv = b.result(timeout=60).asnumpy()
            assert av.shape == bv.shape
            assert av.tobytes() == bv.tobytes()
    finally:
        srv_ref.stop()
        srv_sh.stop()
        serving.unregister("fab_ref")
        serving.unregister("fab_sh")


def _tlm(seed=0):
    from mxnet_tpu.gluon.model_zoo.bert import TransformerLM
    onp.random.seed(seed)
    lm = TransformerLM(num_layers=2, units=32, hidden_size=64, num_heads=2,
                       vocab_size=50, max_length=64)
    lm.initialize(mx.init.Normal(0.5))
    return lm


def test_sharded_decode_bitwise_vs_reference():
    from mxnet_tpu.serving.generate import DecodeEndpoint
    l_ref = _tlm(31)
    l_sh = _tlm(31)
    _copy_weights(l_ref, l_sh)
    ref = DecodeEndpoint("fab_dref", l_ref, max_seq_len=64, max_batch_size=4,
                         page_size=8, num_pages=64)
    sl = plan_slices([4])[0]
    sh = ShardedDecodeEndpoint("fab_dsh", l_sh, slice_spec=sl, max_seq_len=64,
                               max_batch_size=4, page_size=8, num_pages=64)
    try:
        ref.warmup()
        sh.warmup()
        assert sh.capacity == 4
        # serial greedy: prefill + stepwise decode, token-for-token equal
        def run(eng, prompt, budget, sid):
            eng.pool.reserve(sid, len(prompt) + budget)
            toks = [eng.prefill(prompt, eng.pool.table(sid))]
            pos = len(prompt)
            for _ in range(budget - 1):
                (t,) = eng.decode_step([(toks[-1], pos,
                                         eng.pool.table(sid))])
                toks.append(t)
                pos += 1
            eng.pool.free(sid)
            return toks
        assert run(ref, [1, 2, 3], 6, 900) == run(sh, [1, 2, 3], 6, 900)
        # batched decode step: the continuous-batching path, full bucket
        prompts = [[4, 5], [6, 7, 8], [9], [10, 11]]
        for i in range(4):
            ref.pool.reserve(1000 + i, 16)
            sh.pool.reserve(1000 + i, 16)
        fr = [ref.prefill(p, ref.pool.table(1000 + i))
              for i, p in enumerate(prompts)]
        fs = [sh.prefill(p, sh.pool.table(1000 + i))
              for i, p in enumerate(prompts)]
        assert fr == fs
        work_r = [(fr[i], len(prompts[i]), ref.pool.table(1000 + i))
                  for i in range(4)]
        work_s = [(fs[i], len(prompts[i]), sh.pool.table(1000 + i))
                  for i in range(4)]
        assert list(ref.decode_step(work_r)) == list(sh.decode_step(work_s))
    finally:
        serving.unregister("fab_dref")
        serving.unregister("fab_dsh")


# ---------------------------------------------------------------------------
# satellite: capacity-weighted pool placement
# ---------------------------------------------------------------------------
def test_pool_capacity_weighted_rotation():
    """A 4-chip sharded replica must attract ~4x the traffic share of its
    single-chip pool-mates: ranking divides queued rows by capacity."""
    net = _mlp(41)
    sl = plan_slices([4])[0]

    def factory(rid):
        srv = serving.InferenceServer(batch_timeout_ms=1.0)
        if rid == 0:
            srv.register(ShardedEndpoint("fab_pool", net, input_shapes=[(8,)],
                                         max_batch_size=8, slice_spec=sl))
        else:
            m = _mlp(41 + rid)
            srv.register(serving.ModelEndpoint("fab_pool", m,
                                               input_shapes=[(8,)],
                                               max_batch_size=8),
                         warmup=False)
        srv.start()
        return srv

    pool = ServingPool(factory, initial_replicas=2)
    try:
        snap = pool.snapshot()
        caps = {r["rid"]: r["capacity"] for r in snap["replicas"]}
        assert caps == {0: 4, 1: 1}
        # deterministic routing model: every routed request adds one queued
        # row to its replica; greedy least-weighted-load then converges to
        # the capacity ratio without timing dependence
        loads = {0: 0, 1: 0}
        counts = {0: 0, 1: 0}
        reps = pool._rotation()
        for _ in range(100):
            rep = min(reps, key=lambda r: loads[r.rid] / r.capacity)
            loads[rep.rid] += 1
            counts[rep.rid] += 1
        assert counts[0] == 80 and counts[1] == 20     # exactly 4:1
        # and the live ranking agrees with the model on a skewed state
        r0 = next(r for r in reps if r.rid == 0)
        r1 = next(r for r in reps if r.rid == 1)
        assert ServingPool._load_of(r0) == pytest.approx(0.0)
        orig = ServingPool.__dict__["_raw_load"]   # staticmethod object
        try:
            ServingPool._raw_load = staticmethod(
                lambda rep: {0: 3, 1: 1}[rep.rid])
            # 3 rows on 4 chips (0.75) still beats 1 row on 1 chip (1.0)
            assert ServingPool._load_of(r0) < ServingPool._load_of(r1)
        finally:
            ServingPool._raw_load = orig
    finally:
        while pool.scale_down(drain_timeout_s=5) is not None:
            pass
        pool._rotation()[0].server.stop()
        serving.unregister("fab_pool")


# ---------------------------------------------------------------------------
# satellite: sharded executable-cache trigger key is topology-stable
# ---------------------------------------------------------------------------
def test_sharded_cache_key_survives_restart_on_different_devices(tmp_path):
    compile_ledger.reset()
    xcache.reset_stats()
    config.set("MXNET_EXEC_CACHE_DIR", str(tmp_path / "xc"))
    devs = _devices()
    net0, net1 = _twin(51)
    try:
        sl_a = SliceSpec(0, devs[0:2])
        ep = ShardedEndpoint("fab_restart", net0, input_shapes=[(8,)],
                             max_batch_size=4, slice_spec=sl_a)
        label_a = ep._device_label()
        ep.warmup()
        cold = xcache.stats()
        assert cold["stores"] >= len(ep.buckets)
        serving.unregister("fab_restart")
        # "restart": same endpoint name + slice SHAPE, different chips
        sl_b = SliceSpec(0, devs[4:6])
        ep2 = ShardedEndpoint("fab_restart", net1, input_shapes=[(8,)],
                              max_batch_size=4, slice_spec=sl_b)
        assert ep2._device_label() == label_a  # no device ids in the label
        ep2.warmup()
        warm = xcache.stats()
        assert warm["misses"] == cold["misses"]    # zero fresh compiles
        assert warm["hits"] >= cold["hits"] + len(ep2.buckets)
    finally:
        serving.unregister("fab_restart")
        config.set("MXNET_EXEC_CACHE_DIR", "")
        compile_ledger.reset()
        xcache.reset_stats()


# ---------------------------------------------------------------------------
# front door: bounded rebalancing + cross-host failover
# ---------------------------------------------------------------------------
def _fd_factory(tenants, net, weights):
    def factory(name):
        m = _mlp(61)
        for p, w in zip(m.collect_params().values(), weights):
            p.set_data(nd.array(w))
        srv = serving.InferenceServer(batch_timeout_ms=1.0)
        for i, t in enumerate(tenants):
            srv.register(serving.ModelEndpoint(t, m, input_shapes=[(8,)],
                                               max_batch_size=8),
                         warmup=(i == 0))
        srv.start()
        return srv
    return factory


def test_frontdoor_bounded_rebalance_and_zero_drop_failover():
    tenants = [f"fab_t{i}" for i in range(6)]
    net = _mlp(61)
    weights = [p.data().asnumpy() for p in net.collect_params().values()]
    direct = net(nd.array(onp.ones((2, 8), "float32"))).asnumpy()
    fd = FrontDoor(["h0", "h1", "h2"], _fd_factory(tenants, net, weights),
                   spawn_agents=False, supervise=False)
    try:
        owner_before = {t: fd.route(t) for t in tenants}
        assert set(owner_before.values()) >= {"h0"} \
            or len(set(owner_before.values())) >= 1
        victim = owner_before[tenants[0]]
        x = onp.ones((2, 8), "float32")
        futs = [fd.submit(t, x) for t in tenants for _ in range(5)]
        rep = fd.kill_host(victim)
        futs += [fd.submit(t, x) for t in tenants for _ in range(3)]
        outs = [f.result(timeout=60) for f in futs]     # zero drops
        for o in outs:
            assert o.asnumpy().tobytes() == direct.tobytes()
        assert rep["epoch"] == 1 and victim not in fd.alive_hosts()
        owner_after = {t: fd.route(t) for t in tenants}
        for t in tenants:   # bounded: ONLY the dead host's tenants moved
            if owner_before[t] == victim:
                assert owner_after[t] != victim
            else:
                assert owner_after[t] == owner_before[t]
        moved = sum(1 for t in tenants
                    if owner_before[t] != owner_after[t])
        assert rep["moved"] == moved
        # idempotent kill
        assert fd.kill_host(victim).get("already_down") is True
    finally:
        fd.stop()
        for t in tenants:
            serving.unregister(t)


def test_frontdoor_rejects_mismatched_tenant_sets():
    def factory(name):
        m = _mlp(71)
        srv = serving.InferenceServer(batch_timeout_ms=1.0)
        srv.register(serving.ModelEndpoint(f"fab_only_{name}", m,
                                           input_shapes=[(8,)],
                                           max_batch_size=8), warmup=False)
        srv.start()
        return srv
    with pytest.raises(MXNetError):
        FrontDoor(["a", "b"], factory, spawn_agents=False, supervise=False)
    for n in ("a", "b"):
        try:
            serving.unregister(f"fab_only_{n}")
        except Exception:
            pass


# ---------------------------------------------------------------------------
# zero-copy ingest: staging reuse must never leak stale rows
# ---------------------------------------------------------------------------
def test_zerocopy_staging_no_stale_rows_across_batches():
    net = _mlp(81)
    x_big = onp.random.RandomState(1).randn(8, 8).astype("float32")
    x_small = onp.random.RandomState(2).randn(3, 8).astype("float32")
    direct_big = net(nd.array(x_big)).asnumpy()
    direct_small = net(nd.array(x_small)).asnumpy()
    config.set("MXNET_SERVING_ZEROCOPY", True)
    srv = serving.InferenceServer(batch_timeout_ms=1.0)
    try:
        srv.register(serving.ModelEndpoint("fab_zc", net, input_shapes=[(8,)],
                                           max_batch_size=8))
        srv.start()
        # big fills the bucket-8 staging slot with nonzero rows; small then
        # reuses a slot — its padded tail must be ZEROED, not stale
        for _ in range(4):
            assert srv.submit("fab_zc", x_big).result(timeout=60) \
                .asnumpy().tobytes() == direct_big.tobytes()
            assert srv.submit("fab_zc", x_small).result(timeout=60) \
                .asnumpy().tobytes() == direct_small.tobytes()
        srv.stop()
        # pipeline depth > 1 cycles depth+1 parities, still bitwise
        srv2 = serving.InferenceServer(batch_timeout_ms=1.0,
                                       pipeline_depth=3)
        ep2 = serving.get_endpoint("fab_zc")
        srv2.register(ep2, warmup=False)
        srv2.start()
        try:
            outs = [srv2.submit("fab_zc", x_small) for _ in range(8)]
            for f in outs:
                assert f.result(timeout=60).asnumpy().tobytes() \
                    == direct_small.tobytes()
        finally:
            srv2.stop()
    finally:
        srv.stop()
        serving.unregister("fab_zc")


def test_pipeline_depth_validation():
    with pytest.raises(MXNetError):
        serving.InferenceServer(pipeline_depth=0)
