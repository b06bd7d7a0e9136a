"""Elastic resilience tests (ISSUE r12): sharded/re-shardable checkpoints,
preemption-aware training, zero-downtime weight hot-swap, and worker
failover —

  - ELASTIC-RESTORE ACCEPTANCE: train on an 8-way fsdp mesh, sharded-save
    (one shard file per device, per-shard sha256 in the MANIFEST), restore
    onto a 4-way and a 1-way layout: gathered params bitwise-equal to the
    saved state, and the continued run bitwise-equal to an oracle handed
    the same state in-memory on the target layout;
  - PREEMPTION: an injected (and a SIGTERM) notice finishes the in-flight
    step, force-flushes within the deadline, writes the resumable marker;
  - HOT-SWAP ACCEPTANCE: >=3 routed swaps under continuous load with zero
    client errors; corrupt checkpoints and probe mismatches roll back;
  - FAILOVER ACCEPTANCE: a killed or wedged worker is declared dead by the
    PoolSupervisor, its batches requeue, a fresh worker serves them; only
    the victim tenant's breaker trips.

All on the 8-device CPU mesh (tier-1)."""
import json
import os
import shutil
import signal
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, serving
from mxnet_tpu import resilience
from mxnet_tpu.gluon import nn, loss as gloss
from mxnet_tpu.resilience import (CheckpointManager, PreemptionGuard,
                                  RetryPolicy, faults)
from mxnet_tpu.resilience.faults import PreemptionNotice, WorkerKilled
from mxnet_tpu.serving import (HotSwapError, PoolSupervisor,
                               RequestTimeoutError)


def _elastic_net(in_dim=8, out_dim=8):
    """MLP whose param dims divide 8 so it re-shards onto 8/4/1 devices."""
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
    net.initialize(mx.init.Xavier())
    net(nd.array(onp.zeros((2, in_dim), "float32")))
    for p in net.collect_params().values():
        p.shard(("fsdp",))
    return net


def _elastic_step(width, seed=11):
    import jax
    mx.random.seed(seed)
    onp.random.seed(seed)
    net = _elastic_net()
    mesh = parallel.make_mesh({"fsdp": width},
                              devices=jax.devices()[:width])
    step = parallel.ParallelTrainStep(
        net, gloss.L2Loss(), mx.optimizer.Adam(learning_rate=0.05), mesh,
        data_spec=(), label_spec=())
    return net, step


def _gather(step):
    import jax
    return [onp.asarray(jax.device_get(a)) for a in step.params]


def _mlp(seed=0, in_dim=6, out_dim=4):
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
    net.initialize(mx.init.Xavier())
    net(nd.array(onp.zeros((2, in_dim), "float32")))
    return net


# ---------------------------------------------------------------------------
# sharded checkpoint layout
# ---------------------------------------------------------------------------
def test_sharded_save_writes_per_device_shards(tmp_path):
    _, step = _elastic_step(8)
    step(onp.zeros((16, 8), "float32"), onp.zeros((16, 8), "float32"))
    cm = CheckpointManager(str(tmp_path), fsync=False)
    cm.save(1, train_step=step, sharded=True)
    ck = os.path.join(str(tmp_path), "ckpt-00000001")
    names = sorted(os.listdir(ck))
    shard_files = [n for n in names if n.startswith("shard-")]
    assert len(shard_files) == 8          # one per mesh device
    manifest = json.load(open(os.path.join(ck, "MANIFEST.json")))
    # every shard file is checksummed in the manifest (written last)
    for n in shard_files:
        assert "sha256" in manifest["files"][n]
    meta = json.load(open(os.path.join(ck, "meta.json")))
    assert meta["layout"]                 # placement map present
    # a sharded dense weight's shards tile dim 0 across the 8 writers
    key = next(k for k in meta["layout"] if k.endswith("params/p0"))
    entry = meta["layout"][key]
    starts = sorted(s["index"][0][0] for s in entry["shards"])
    assert len(entry["shards"]) == 8 and starts == [0, 2, 4, 6, 8, 10, 12, 14]


def test_sharded_restore_corrupt_shard_falls_back(tmp_path):
    _, step = _elastic_step(8)
    step(onp.zeros((16, 8), "float32"), onp.zeros((16, 8), "float32"))
    cm = CheckpointManager(str(tmp_path), fsync=False)
    cm.save(1, train_step=step, sharded=True)
    step(onp.zeros((16, 8), "float32"), onp.zeros((16, 8), "float32"))
    cm.save(2, train_step=step, sharded=True)
    # flip one bit in one shard of the newest checkpoint
    bad = os.path.join(str(tmp_path), "ckpt-00000002", "shard-00003.npz")
    raw = bytearray(open(bad, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(bad, "wb").write(bytes(raw))
    _, step2 = _elastic_step(4, seed=99)
    restored = cm.restore_latest(train_step=step2)
    assert restored is not None and restored[0] == 1      # fell back


# ---------------------------------------------------------------------------
# ACCEPTANCE: elastic restore 8 -> 4 and 8 -> 1
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("target_width", [4, 1])
def test_elastic_restore_resharding_bitwise(tmp_path, target_width):
    """Sharded-save on 8 devices, restore onto ``target_width``: restored
    gathered state bitwise-equal to the saved state, and N more steps are
    bitwise-equal to an oracle that got the same state handed over
    in-memory on the target layout — the checkpoint/re-shard round trip
    adds zero numeric perturbation."""
    STEPS, CUT = 8, 4
    rng = onp.random.RandomState(1)
    X = rng.randn(STEPS, 16, 8).astype("float32")
    Y = rng.randn(STEPS, 16, 8).astype("float32")

    _, step8 = _elastic_step(8)
    for i in range(CUT):
        step8(X[i], Y[i])
    cm = CheckpointManager(str(tmp_path), fsync=False)
    cm.save(CUT, train_step=step8, sharded=True)
    saved = _gather(step8)
    handoff = step8.state_dict()          # the in-memory oracle's source

    _, stepw = _elastic_step(target_width, seed=555)   # different RNG state
    restored = cm.restore_latest(train_step=stepw)
    assert restored is not None and restored[0] == CUT
    assert stepw._t == CUT
    for a, b in zip(saved, _gather(stepw)):
        onp.testing.assert_array_equal(a, b)           # restore fidelity

    _, stepo = _elastic_step(target_width, seed=777)
    stepo.load_state_dict(handoff)
    for i in range(CUT, STEPS):
        lw = float(stepw(X[i], Y[i]).asscalar())
        lo = float(stepo(X[i], Y[i]).asscalar())
        assert lw == lo                                # bitwise losses
    for a, b in zip(_gather(stepw), _gather(stepo)):
        onp.testing.assert_array_equal(a, b)           # bitwise final state


# ---------------------------------------------------------------------------
# preemption-aware training
# ---------------------------------------------------------------------------
def test_preemption_guard_injected_notice_flushes_and_marks(tmp_path):
    _, step = _elastic_step(8)
    X = onp.random.RandomState(2).randn(6, 16, 8).astype("float32")
    Y = onp.random.RandomState(3).randn(6, 16, 8).astype("float32")
    cm = CheckpointManager(str(tmp_path), keep=2, async_save=True,
                           fsync=False)
    guard = PreemptionGuard(cm, capture=dict(train_step=step), sharded=True,
                            deadline_s=30.0)
    stopped_at = None
    with guard, faults.inject("preempt", at=(3,)) as inj:
        for i in range(6):
            step(X[i], Y[i])
            if guard.should_stop(i + 1):
                stopped_at = i + 1
                break
    assert stopped_at == 3 and inj.fires == 1
    assert guard.requested and guard.reason == "injected:preempt"
    info = PreemptionGuard.resume_info(cm)
    assert info["step"] == 3 and info["saved"] and info["within_deadline"]
    assert info["sharded"] is True
    assert cm.preemption_marker() is None       # consumed
    # the flushed checkpoint restores elastically onto fewer devices
    _, step4 = _elastic_step(4, seed=888)
    restored = cm.restore_latest(train_step=step4)
    assert restored is not None and restored[0] == 3
    for a, b in zip(_gather(step), _gather(step4)):
        onp.testing.assert_array_equal(a, b)


def test_preemption_guard_sigterm_and_handler_restored(tmp_path):
    cm = CheckpointManager(str(tmp_path), fsync=False)
    _, step = _elastic_step(8)
    before = signal.getsignal(signal.SIGTERM)
    guard = PreemptionGuard(cm, capture=dict(train_step=step),
                            deadline_s=30.0)
    with guard:
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)
        assert guard.requested and guard.reason == "signal:SIGTERM"
        assert guard.should_stop(1)
    assert signal.getsignal(signal.SIGTERM) == before
    assert cm.preemption_marker()["step"] == 1


def test_preemption_deadline_exceeded_recorded(tmp_path):
    """A flush that cannot beat the grace budget is recorded honestly (the
    marker still lands; the outcome counter says deadline_exceeded)."""
    from mxnet_tpu.resilience.preemption import _PREEMPTIONS
    cm = CheckpointManager(str(tmp_path), fsync=False)
    _, step = _elastic_step(8)
    child = _PREEMPTIONS.labels("deadline_exceeded")
    before = child.value
    guard = PreemptionGuard(cm, capture=dict(train_step=step),
                            deadline_s=1e-9)
    guard.notify("test")
    assert guard.should_stop(5)
    info = cm.preemption_marker()
    assert info["saved"] is True and info["within_deadline"] is False
    assert child.value == before + 1


def test_preempt_fault_kind_raises_outside_guard():
    with faults.inject("preempt", every_n=1, times=1):
        with pytest.raises(PreemptionNotice):
            faults.check("preemption")


# ---------------------------------------------------------------------------
# satellites: async-writer surfacing, wait(timeout=), rotation vs async
# ---------------------------------------------------------------------------
def test_async_writer_error_surfaces_on_next_save(tmp_path):
    cm = CheckpointManager(str(tmp_path), async_save=True, fsync=False)
    with faults.inject("crash", every_n=1, times=1):
        cm.save(1, {"a": {"x": onp.ones((3,), "float32")}})
        with pytest.raises(faults.SimulatedCrash):
            cm.save(2, {"a": {"x": onp.ones((3,), "float32")}})
    # the failed step never became a checkpoint; the manager still works
    cm.save(3, {"a": {"x": onp.full((3,), 3.0, "float32")}})
    cm.wait()
    assert cm.steps() == [3]


def test_wait_timeout_on_wedged_writer(tmp_path):
    """Satellite: a wedged background writer cannot hang shutdown — wait()
    raises after MXNET_CKPT_WAIT_TIMEOUT_S (here passed explicitly)."""
    cm = CheckpointManager(str(tmp_path), async_save=True, fsync=False)
    with faults.inject("hang", site="checkpoint_write", seconds=1.5,
                       every_n=1, times=1):
        cm.save(1, {"a": {"x": onp.zeros((4,), "float32")}})
        t0 = time.monotonic()
        with pytest.raises(mx.base.MXNetError, match="still running"):
            cm.wait(timeout=0.2)
        assert time.monotonic() - t0 < 1.0
    cm.wait()                      # unbounded: joins the unwedged writer
    assert cm.steps() == [1]
    _, got = cm.restore_latest()
    assert got["a"]["x"].shape == (4,)


def test_rotation_never_deletes_inflight_or_newest(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=1, fsync=False)
    cm.save(5, {"a": {"x": onp.zeros((2,), "float32")}})
    cm.save(10, {"a": {"x": onp.ones((2,), "float32")}})
    # out-of-order re-save of an older step: the newest (10) must survive
    # even though keep=1 and the just-written step is 7
    cm.save(7, {"a": {"x": onp.full((2,), 7.0, "float32")}})
    assert 10 in cm.steps() and 7 in cm.steps()
    # a step registered as in-flight is never swept
    with cm._lock:
        cm._writing.add(7)
    cm.save(11, {"a": {"x": onp.full((2,), 11.0, "float32")}})
    assert 7 in cm.steps() and 11 in cm.steps()
    with cm._lock:
        cm._writing.discard(7)


def test_rotation_async_stress_seeded(tmp_path):
    """Satellite stress: rapid async saves with rotation keep=2 — the newest
    checkpoint is always intact and restore_latest never fails, whatever
    the writer/rotation interleaving (seeded jitter)."""
    rng = onp.random.RandomState(42)
    cm = CheckpointManager(str(tmp_path), keep=2, async_save=True,
                           fsync=False)
    for s in range(1, 26):
        cm.save(s, {"a": {"x": onp.full((8,), float(s), "float32")}})
        if rng.random() < 0.3:
            time.sleep(rng.random() * 0.005)
        got = cm.restore_latest()
        # whatever has landed on disk must be restorable (the first save
        # may still be in flight: no dirs yet is fine, a broken one is not)
        assert got is not None or not cm.steps()
    cm.wait()
    step, state = cm.restore_latest()
    assert step == 25 and state["a"]["x"][0] == 25.0
    assert len(cm.steps()) <= 3           # keep=2 (+ the newest guard)


# ---------------------------------------------------------------------------
# serving drain: abandoned-in-batch requests fail with RequestTimeoutError
# ---------------------------------------------------------------------------
def test_drain_abandon_fails_inflight_with_timeout_error():
    """Regression: a request INSIDE the in-flight batch of a wedged worker
    is failed with RequestTimeoutError at drain abandon — never left to
    hang the waiting client — and the abandon counter counts it."""
    from mxnet_tpu.serving.server import _DRAIN_ABANDONED
    net = _mlp(seed=31)
    ep = serving.ModelEndpoint("t_el_drain", net, input_shapes=(6,),
                               max_batch_size=2)
    srv = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=64)
    srv.register(ep)
    srv.start()
    before = _DRAIN_ABANDONED.value
    x = onp.random.RandomState(32).randn(6).astype("float32")
    try:
        with faults.inject("hang", site="serving_dispatch", seconds=3.0,
                           every_n=1, times=1):
            f1 = srv.submit("t_el_drain", x)
            time.sleep(0.3)              # worker picks f1's batch up, hangs
            srv.stop(drain=True, timeout=0.3)
        with pytest.raises(RequestTimeoutError):
            f1.result(timeout=0.1)
        assert _DRAIN_ABANDONED.value >= before + 1
    finally:
        time.sleep(3.2)                  # let the wedged worker unwind
        serving.unregister("t_el_drain")


# ---------------------------------------------------------------------------
# ACCEPTANCE: zero-downtime hot swap
# ---------------------------------------------------------------------------
def _serving_ckpt(tmp_path, tag, seed, in_dim=6, out_dim=4):
    """Producer side: a serving checkpoint (weights + recorded probe)."""
    d = os.path.join(str(tmp_path), tag)
    src = serving.ModelEndpoint(f"t_el_src_{tag}_{seed}",
                                _mlp(seed=seed, in_dim=in_dim,
                                     out_dim=out_dim),
                                input_shapes=(in_dim,), max_batch_size=4)
    try:
        src.save_checkpoint(CheckpointManager(d, fsync=False), 1,
                            probe_seed=seed)
    finally:
        serving.unregister(f"t_el_src_{tag}_{seed}")
    return d


def test_hot_swap_under_load_three_cycles_zero_errors(tmp_path):
    d1 = _serving_ckpt(tmp_path, "w1", seed=41)
    d2 = _serving_ckpt(tmp_path, "w2", seed=42)
    ep = serving.ModelEndpoint("t_el_swap", _mlp(seed=40), input_shapes=(6,),
                               max_batch_size=4)
    other = serving.ModelEndpoint("t_el_swap_other", _mlp(seed=43),
                                  input_shapes=(6,), max_batch_size=4)
    srv = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=256)
    srv.register(ep)
    srv.register(other)
    srv.start()
    xs = onp.random.RandomState(44).randn(16, 6).astype("float32")
    stop = threading.Event()
    errors = []
    served = {"n": 0}

    def load(name):
        i = 0
        while not stop.is_set():
            try:
                srv.predict(name, xs[i % 16], timeout=30)
                served["n"] += 1
            except Exception as e:
                errors.append(repr(e))
            i += 1

    threads = [threading.Thread(target=load, args=(n,))
               for n in ("t_el_swap", "t_el_swap_other")]
    for t in threads:
        t.start()
    try:
        for cycle, d in enumerate((d1, d2, d1)):
            rep = srv.hot_swap("t_el_swap", d, timeout=30)
            assert rep["weights_epoch"] == cycle + 1
            assert rep["probe"] == "recorded"
            time.sleep(0.03)
    finally:
        stop.set()
        for t in threads:
            t.join()
        srv.stop()
    assert errors == []                   # zero client errors, zero drops
    assert served["n"] > 0
    assert ep.weights_epoch == 3
    assert ep.stats.counters["hot_swaps"] == 3
    # post-swap outputs bitwise-equal to a fresh endpoint loaded from d1
    fresh = serving.ModelEndpoint("t_el_swap_fresh", _mlp(seed=49),
                                  input_shapes=(6,), max_batch_size=4)
    fresh.hot_swap(d1)
    srv2 = serving.InferenceServer(batch_timeout_ms=1.0)
    srv2.register(fresh, warmup=False)
    srv2.register(ep, warmup=False)
    srv2.start()
    try:
        want = srv2.predict("t_el_swap_fresh", xs[0], timeout=30).asnumpy()
        got = srv2.predict("t_el_swap", xs[0], timeout=30).asnumpy()
    finally:
        srv2.stop()
        serving.unregister("t_el_swap_fresh")
        serving.unregister("t_el_swap")
        serving.unregister("t_el_swap_other")
    onp.testing.assert_array_equal(got, want)


def test_hot_swap_corrupt_checkpoint_rolls_back(tmp_path):
    d1 = _serving_ckpt(tmp_path, "good", seed=51)
    bad_root = os.path.join(str(tmp_path), "bad")
    shutil.copytree(d1, bad_root)
    bad = os.path.join(bad_root, "ckpt-00000001", "state.npz")
    raw = bytearray(open(bad, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(bad, "wb").write(bytes(raw))

    ep = serving.ModelEndpoint("t_el_rb", _mlp(seed=50), input_shapes=(6,),
                               max_batch_size=4)
    srv = serving.InferenceServer(batch_timeout_ms=1.0)
    srv.register(ep)
    srv.start()
    x = onp.random.RandomState(52).randn(6).astype("float32")
    try:
        before = srv.predict("t_el_rb", x, timeout=30).asnumpy()
        with pytest.raises(HotSwapError):
            srv.hot_swap("t_el_rb", bad_root, timeout=30)
        after = srv.predict("t_el_rb", x, timeout=30).asnumpy()
        onp.testing.assert_array_equal(before, after)   # old weights serve on
        assert ep.weights_epoch == 0
        # and a good swap still works afterwards
        rep = srv.hot_swap("t_el_rb", d1, timeout=30)
        assert rep["weights_epoch"] == 1
    finally:
        srv.stop()
        serving.unregister("t_el_rb")


def test_hot_swap_probe_mismatch_rolls_back(tmp_path):
    """Weights that verify (checksums fine) but do not reproduce the probe's
    recorded outputs — a mixed-up param file — are rolled back."""
    d1 = _serving_ckpt(tmp_path, "src", seed=61)
    from mxnet_tpu.resilience.checkpoint import verify_checkpoint_dir
    state = verify_checkpoint_dir(os.path.join(d1, "ckpt-00000001"))
    state["model"]["params"]["p0"] = (
        onp.asarray(state["model"]["params"]["p0"]) + 1.0)   # wrong weights
    ep = serving.ModelEndpoint("t_el_pm", _mlp(seed=60), input_shapes=(6,),
                               max_batch_size=4)
    with pytest.raises(HotSwapError, match="rolled back"):
        ep.hot_swap(state)
    assert ep.weights_epoch == 0
    serving.unregister("t_el_pm")


def test_hot_swap_wrong_model_rejected(tmp_path):
    d1 = _serving_ckpt(tmp_path, "shape", seed=71, out_dim=3)   # mismatched
    ep = serving.ModelEndpoint("t_el_wm", _mlp(seed=70), input_shapes=(6,),
                               max_batch_size=4)
    with pytest.raises(HotSwapError):
        ep.hot_swap(d1)
    serving.unregister("t_el_wm")


# ---------------------------------------------------------------------------
# ACCEPTANCE: worker failover
# ---------------------------------------------------------------------------
def test_worker_kill_failover_completes_all_requests():
    """A BaseException kills the worker mid-stream; the supervisor restarts
    it, requeued batches re-run, every request on both tenants completes
    bitwise-correct; only the victim tenant's breaker recorded failures."""
    net_v = _mlp(seed=81)
    # one bucket: the oracle below forwards at the batch size that served
    # (the same program at another batch size is one ulp away on XLA:CPU)
    ep_v = serving.ModelEndpoint("t_el_fo", net_v, input_shapes=(6,),
                                 max_batch_size=4, buckets=(4,))
    ep_o = serving.ModelEndpoint("t_el_fo_other", _mlp(seed=82),
                                 input_shapes=(6,), max_batch_size=4)
    srv = serving.InferenceServer(
        batch_timeout_ms=1.0, max_queue=256,
        retry_policy=RetryPolicy(max_attempts=2, base_ms=0.5))
    srv.register(ep_v)
    srv.register(ep_o)
    srv.start()
    sup = PoolSupervisor(srv, poll_s=0.02).start()
    xs = onp.random.RandomState(83).randn(12, 6).astype("float32")
    try:
        with faults.inject("worker_kill", site="serving_dispatch",
                           at=(2,)) as inj:
            futs_v = [srv.submit("t_el_fo", xs[i]) for i in range(12)]
            futs_o = [srv.submit("t_el_fo_other", xs[i]) for i in range(12)]
            outs = [f.result(timeout=60).asnumpy() for f in futs_v]
            for f in futs_o:
                f.result(timeout=60)
        assert inj.fires == 1
        assert sup.failovers >= 1
        assert sup.reports[0]["reason"] == "worker_dead"
        direct = onp.concatenate([net_v(nd.array(xs[i:i + 4])).asnumpy()
                                  for i in range(0, 12, 4)])
        onp.testing.assert_array_equal(onp.stack(outs), direct)
        h = srv.health()
        assert h["worker_epoch"] >= 1 and h["failovers"] >= 1
        # only the victim tenant's breaker took the failure
        assert srv.breaker_for("t_el_fo_other").snapshot()[
            "consecutive_failures"] == 0
        # and the server still serves new traffic after the failover
        out = srv.predict("t_el_fo", xs[0], timeout=30).asnumpy()
        onp.testing.assert_array_equal(out, direct[0])
    finally:
        sup.stop()
        srv.stop()
        serving.unregister("t_el_fo")
        serving.unregister("t_el_fo_other")


def test_wedged_worker_failover_via_watchdog():
    """A hung device step past the stall threshold: the Watchdog flags it,
    the supervisor confirms the batch is still in flight, declares the
    worker wedged and fails over; the requeued batch completes on the
    replacement worker long before the zombie wakes."""
    net = _mlp(seed=91)
    ep = serving.ModelEndpoint("t_el_wedge", net, input_shapes=(6,),
                               max_batch_size=4)
    srv = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=64,
                                  watchdog_stall_s=0.15)
    srv.register(ep)
    srv.start()
    sup = PoolSupervisor(srv, poll_s=0.02).start()
    x = onp.random.RandomState(92).randn(6).astype("float32")
    try:
        with faults.inject("hang", site="serving_dispatch", seconds=2.5,
                           every_n=1, times=1):
            t0 = time.monotonic()
            out = srv.predict("t_el_wedge", x, timeout=30)
            elapsed = time.monotonic() - t0
        # served by the replacement worker, not the 2.5s zombie
        assert elapsed < 2.0
        assert sup.failovers >= 1
        assert any(r["reason"] == "worker_wedged" for r in sup.reports)
        direct = net(nd.array(x[None])).asnumpy()[0]
        onp.testing.assert_array_equal(out.asnumpy(), direct)
    finally:
        time.sleep(2.7)                  # let the zombie unwind
        sup.stop()
        srv.stop()
        serving.unregister("t_el_wedge")


# ---------------------------------------------------------------------------
# telemetry wiring
# ---------------------------------------------------------------------------
def test_elastic_metrics_registered():
    from mxnet_tpu import telemetry
    reg = telemetry.REGISTRY
    for name in ("mxtpu_preemptions_total",
                 "mxtpu_preempt_flush_duration_us",
                 "mxtpu_serving_hot_swaps_total",
                 "mxtpu_serving_failovers_total",
                 "mxtpu_serving_failover_requeued_total"):
        assert reg.get(name) is not None, name
    assert telemetry.lint_names() == []


# ---------------------------------------------------------------------------
# chaos matrix smoke (tools/chaos_check.py scenarios, fixed seed)
# ---------------------------------------------------------------------------
def test_chaos_elastic_smoke(tmp_path):
    import io
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import chaos_check
    buf = io.StringIO()
    result = chaos_check.run_chaos(
        seed=13, steps=8, requests=12, ckpt_dir=str(tmp_path),
        scenarios=["preempt", "worker_kill", "hot_swap"], out=buf)
    assert result["ok"], buf.getvalue()
    assert result["preempt"]["state_bitwise_equal"]
    assert result["preempt"]["marker"]["within_deadline"]
    assert result["worker_kill"]["failovers"] >= 1
    assert result["worker_kill"]["victim_unclassified_errors"] == []
    assert result["worker_kill"]["other_tenant_errors"] == 0
    assert result["hot_swap"]["swap_cycles"] >= 3
    assert result["hot_swap"]["client_errors"] == []
    assert result["hot_swap"]["corrupt_swap_rolled_back"]
