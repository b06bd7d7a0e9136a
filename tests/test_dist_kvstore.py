"""Multi-process dist_sync kvstore test: 2 real processes over jax.distributed
CPU (gloo collectives), launched through tools/launch.py --launcher local
(parity: tests/nightly/dist_sync_kvstore.py via tools/launch.py:1-135)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dist_sync_kvstore_two_processes():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", "--",
         sys.executable, os.path.join(REPO, "tests",
                                      "dist_sync_kvstore_worker.py")],
        env=env, capture_output=True, text=True, timeout=420)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, f"dist workers failed:\n{out}"
    assert "worker 0: OK" in out and "worker 1: OK" in out, out


def test_collective_backend_registered():
    """Second pluggable backend via KVStoreBase.register (horovod.py pattern)."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    kv = mx.kv.create("collective")
    assert kv.type == "collective"
    a = nd.array(onp.ones((2, 3), "float32"))
    b = nd.array(onp.full((2, 3), 2.0, "float32"))
    kv.pushpull("k", [a, b])
    onp.testing.assert_allclose(a.asnumpy(), onp.full((2, 3), 3.0))
    out = nd.zeros((2, 3))
    kv.broadcast("k", nd.array(onp.full((2, 3), 7.0, "float32")), out)
    onp.testing.assert_allclose(out.asnumpy(), onp.full((2, 3), 7.0))
    import pytest
    with pytest.raises(mx.MXNetError):
        kv.push("k", a)


def test_async_kvstore_single_process():
    """dist_async on one process: updater applies immediately, no averaging
    traffic (num_workers == 1)."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    kv = mx.kv.create("dist_async")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0, wd=0.0))
    kv.init("w", nd.zeros((3,)))
    kv.push("w", nd.ones((3,)))
    out = nd.zeros((3,))
    kv.pull("w", out=out)
    onp.testing.assert_allclose(out.asnumpy(), -onp.ones(3), rtol=1e-6)


def test_heartbeat_failure_detection(tmp_path):
    """num_dead_node counts stale/absent heartbeats (ps-lite scheduler
    GetDeadNodes analog over the launcher-shared heartbeat dir)."""
    import time
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    mx.config.set("MXNET_KVSTORE_HEARTBEAT_DIR", str(tmp_path))
    kv = None
    try:
        kv = mx.kv.create("dist_sync")
        assert kv.num_dead_node(timeout_sec=60) == 0  # own beat is fresh
        # a stale beat from a (simulated) second worker
        stale = tmp_path / "heartbeat_1"
        stale.write_text(str(time.time() - 3600))
        # single process: num_workers == 1, rank-1 file is out of range
        assert kv.num_dead_node(timeout_sec=60) == 0
        # simulate the scheduler view: scan as if world had 2 workers
        import types
        kv2 = kv
        real = type(kv).num_workers
        try:
            type(kv).num_workers = property(lambda self: 2)
            assert kv2.num_dead_node(timeout_sec=60) == 1
            stale.write_text(str(time.time()))
            assert kv2.num_dead_node(timeout_sec=60) == 0
        finally:
            type(kv).num_workers = real
    finally:
        if kv is not None:
            kv.close()  # stop the beat thread; a closed store must go dead
        mx.config.set("MXNET_KVSTORE_HEARTBEAT_DIR", "")


def test_dist_sync_kvstore_four_processes():
    """4-worker dist_sync (the reference's launch.py -n 4 config,
    tests/nightly/test_distributed_training-gpu.sh:27-34): dense pushpull
    sums across all four workers."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.pop("XLA_FLAGS", None)
    worker = os.path.join(REPO, "tests", "dist_four_worker.py")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "4", "--launcher", "local", "--", sys.executable, worker],
        env=env, capture_output=True, text=True, timeout=420)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, f"4-proc dist workers failed:\n{out}"
    for rank in range(4):
        assert f"worker {rank}/4: OK" in out, out


def test_dist_async_kvstore_four_processes_staleness(tmp_path):
    """True per-push async apply (kvstore_dist_server.h:336-382 semantics):
    rank 3 lags 3s; ranks 0-2 must observe applied updates BEFORE rank 3
    pushes anything (temporal proof that nothing barriers), and the final
    weight reflects every push. Distinguishes async from sync: dist_sync's
    allreduce cannot complete until all ranks contribute."""
    import json
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["ASYNC_TEST_DIR"] = str(tmp_path)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "4", "--launcher", "local", "--",
         sys.executable, os.path.join(REPO, "tests", "dist_async_worker.py")],
        env=env, capture_output=True, text=True, timeout=420)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, f"async workers failed:\n{out}"
    for r in range(4):
        assert f"worker {r}/4: ASYNC OK" in out, out
    records = {r: json.load(open(tmp_path / f"r{r}.json")) for r in range(4)}
    laggard_push = records[3]["pushed_at"]
    for r in range(3):
        assert records[r]["seen_nonzero_at"] < laggard_push, (
            f"rank {r} only saw updates after the laggard pushed — "
            f"that is sync, not async: {records}")
