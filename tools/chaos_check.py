"""Chaos checker: training + serving under seeded random fault injection.

The end-to-end resilience acceptance gate (ISSUE r8), runnable standalone or
from tier-1 (tests/test_resilience.py::test_chaos_smoke):

  1. TRAIN — run a short fused-step training loop twice: once fault-free,
     once under randomized device-OOM injection (probability ``--p``, seeded
     — the schedule replays exactly from the logged seed) PLUS one simulated
     crash at the midpoint (checkpoint -> throw everything away -> rebuild ->
     restore_latest -> continue). The chaos run's final loss and weights must
     be BITWISE equal to the fault-free run: retries and crash/restore are
     invisible to the numerics.

  2. SERVE — run a closed budget of requests through InferenceServer while
     dispatch faults (UNAVAILABLE) fire randomly under the same seeding.
     Every request must complete with its output bitwise equal to the direct
     forward — zero client-visible errors (no deadlines are set, so none are
     permitted).

  3. ELASTIC SCENARIOS (``--scenario {preempt,worker_kill,hot_swap}``,
     repeatable) — the r12 resilience drills: a preemption notice mid-run
     force-flushes a sharded checkpoint that restores onto HALF the devices
     (bitwise vs an in-memory-handoff oracle); a killed worker thread fails
     over via the PoolSupervisor with every request completing or failing
     classified and the other tenant untouched; >=3 weight hot-swaps under
     continuous load with zero client errors plus a corrupt-checkpoint
     rollback.

  4. GENERATIVE SCENARIO (``--scenario decode``) — the r16 drill: a
     decode_stall kills the generation worker with partially-generated
     sequences in flight and a kv_exhausted bounces a KV reservation; the
     failover must requeue the partial sequences (pages, position and
     emitted tokens intact) and finish every stream bitwise-equal to a
     fault-free serial greedy decode — no duplicated, no dropped tokens —
     leaving a parseable flight bundle triggered by ``decode_failover``.

  5. NUMERICS SCENARIOS (``--scenario {nan_grad,bad_batch,sdc}``) — the r13
     NumericsGuard drills: a 30-step run with injected NaN gradients must
     end BITWISE equal to a clean run trained on the same batches minus the
     skipped ones (detection is lagged — the guard reads its fused
     on-device health scalars only every check_every_n steps — yet
     skip-recovery re-derives every kept update exactly); a poisoned batch
     served by a real DataLoader is quarantined (fingerprinted, dumped,
     positionally excluded so replays never see it again) with the same
     bitwise bar; an injected SDC digest mismatch must write a repro bundle
     that tools/replay_step.py re-executes to the same verdict, twice.

  6. ELASTICITY SCENARIOS (``--scenario {cache_poison,autoscale}``) — the
     r17 drills: a ``cache_poison`` fault corrupts a persistent
     executable-cache entry on disk mid-warmup and the sha256-verify
     fallback must recompile with zero client errors and bitwise outputs;
     a synthetic SLO burn must scale the Autoscaler's replica pool up to
     max and recovery back down to min with no dropped requests across
     any cutover and an ``autoscale_*`` flight event per transition.
     Both drills additionally run under a private span-spool dir and must
     leave a parseable ``tools/fleet_report.py`` report whose journey for
     the drill's trace id names >=2 processes/replicas (cache_poison's
     warmer is a real subprocess; autoscale routes across pool replicas).

  7. FABRIC SCENARIO (``--scenario host_down``) — the r18 drill: a
     two-host serving-fabric FrontDoor under continuous client load loses
     one whole host (agent SIGKILLed, serving plane failed without drain).
     The consistent-hash ring must move exactly the dead host's tenants,
     the wrapper futures must replay the dead host's in-flight work on the
     survivor — zero client-visible errors, outputs bitwise-equal to the
     direct forward — and the post-mortem pane must hold: a ``host_down``
     flight bundle, a fleet report whose journey names both host agents,
     the collector still listing the dead host's last dump, and per-host
     goodput ledgers reconciling within 1%.

  8. TAIL-TOLERANCE SCENARIOS (``--scenario {retry_storm,straggler,
     partition}``) — the r18 tailguard drills. A ``net_drop`` storm at the
     front door under a nearly-dry retry budget must convert into bounded
     shed (retry amplification < 2x, classified client errors, a
     ``retry_budget_exhausted`` flight bundle) while the same storm under
     an effectively unbounded budget is fully absorbed at >=2x
     amplification — the difference is the defense. A replica-straggler
     stall at the device-step boundary must be cut by hedged requests:
     every request lands inside its deadline, outputs bitwise-equal to the
     unhedged fault-free oracle, speculation bounded by the hedge token
     bucket (a dry bucket latches ``hedge_budget_exhausted``). A front-door
     partition plus synthetic SLO burn must walk the brownout ladder in
     criticality order — bulk shed before silver, gold never refused, one
     ``brownout_shift`` flight bundle per transition, full recovery to
     level 0 — with the fleet pane intact (parseable report naming both
     host agents, per-host goodput ledgers reconciling within 1%).

Every run prints its seed; a failing seed is a deterministic repro::

    python tools/chaos_check.py --seed 1234 --steps 20 --requests 40
    python tools/chaos_check.py --seed 7 --scenario preempt \
        --scenario worker_kill --scenario hot_swap
    python tools/chaos_check.py --scenario nan_grad --scenario bad_batch \
        --scenario sdc

Prints one JSON line per phase and a final summary; exit 0 iff both phases
hold their invariant.
"""
import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def _build_train(seed, in_dim, hidden, out_dim):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.resilience import RetryPolicy

    mx.random.seed(seed)
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(hidden, activation="relu"), nn.Dense(out_dim))
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(onp.zeros((2, in_dim), "float32")))
    mesh = parallel.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = parallel.ParallelTrainStep(
        net, gloss.L2Loss(), mx.optimizer.Adam(learning_rate=0.05), mesh,
        retry_policy=RetryPolicy(max_attempts=8, base_ms=1.0, seed=seed))
    return net, step


def check_train(seed, steps, p, in_dim=8, hidden=16, out_dim=4,
                ckpt_dir=None):
    """Fault-free run vs (random OOM + midpoint crash/restore) run."""
    from mxnet_tpu.resilience import CheckpointManager, faults

    rng = onp.random.RandomState(seed)
    X = rng.randn(steps, 16, in_dim).astype("float32")
    Y = rng.randn(steps, 16, out_dim).astype("float32")

    # reference: uninterrupted
    net_ref, step_ref = _build_train(seed, in_dim, hidden, out_dim)
    ref_losses = [float(step_ref(X[i], Y[i]).asscalar()) for i in range(steps)]
    step_ref.sync_to_block()
    ref_w = [p_.data().asnumpy() for p_ in net_ref.collect_params().values()]

    # chaos: random OOM every attempt with prob p + crash at the midpoint
    ckpt_dir = ckpt_dir or tempfile.mkdtemp(prefix="chaos-ckpt-")
    cm = CheckpointManager(ckpt_dir, keep=2)
    crash_at = max(1, steps // 2)
    net_c, step_c = _build_train(seed, in_dim, hidden, out_dim)
    losses = []
    with faults.inject("device_oom", site="train_step", p=p,
                       seed=seed) as inj:
        for i in range(crash_at):
            losses.append(float(step_c(X[i], Y[i]).asscalar()))
        cm.save(crash_at, train_step=step_c)
        # simulated crash: lose the process state, rebuild, restore
        del net_c, step_c
        net_c, step_c = _build_train(seed + 999, in_dim, hidden, out_dim)
        restored = cm.restore_latest(train_step=step_c)
        assert restored is not None and restored[0] == crash_at
        for i in range(crash_at, steps):
            losses.append(float(step_c(X[i], Y[i]).asscalar()))
    step_c.sync_to_block()
    chaos_w = [p_.data().asnumpy() for p_ in net_c.collect_params().values()]

    loss_ok = losses[-1] == ref_losses[-1]
    w_ok = all(onp.array_equal(a, b) for a, b in zip(ref_w, chaos_w))
    return {"phase": "train", "seed": seed, "steps": steps, "p": p,
            "faults_fired": inj.fires, "fault_calls": inj.calls,
            "crash_at": crash_at, "final_loss": losses[-1],
            "final_loss_ref": ref_losses[-1],
            "loss_bitwise_equal": loss_ok, "weights_bitwise_equal": w_ok,
            "ok": loss_ok and w_ok}


def check_serving(seed, requests, p, in_dim=8, hidden=16, out_dim=4):
    """Every request completes, bitwise-equal to direct forward, despite
    random dispatch faults."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.resilience import RetryPolicy, faults

    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(hidden, activation="relu"), nn.Dense(out_dim))
    net.initialize(mx.init.Xavier())
    net(nd.array(onp.zeros((2, in_dim), "float32")))

    name = f"chaos_ep_{seed}_{requests}"
    ep = serving.ModelEndpoint(name, net, input_shapes=(in_dim,),
                               max_batch_size=8)
    srv = serving.InferenceServer(
        batch_timeout_ms=1.0, max_queue=max(64, requests * 2),
        retry_policy=RetryPolicy(max_attempts=8, base_ms=1.0, seed=seed))
    srv.register(ep)
    srv.start()
    xs = onp.random.RandomState(seed + 1).randn(
        requests, in_dim).astype("float32")
    errors = 0
    outs = [None] * requests
    try:
        with faults.inject("unavailable", site="serving_dispatch", p=p,
                           seed=seed + 1) as inj:
            futs = [srv.submit(name, xs[i]) for i in range(requests)]
            for i, f in enumerate(futs):
                try:
                    outs[i] = f.result(timeout=120).asnumpy()
                except Exception:
                    errors += 1
        fires = inj.fires
    finally:
        srv.stop()
        serving.unregister(name)
    direct = net(nd.array(xs)).asnumpy()
    bitwise = errors == 0 and all(
        o is not None and onp.array_equal(o, direct[i])
        for i, o in enumerate(outs))
    health = srv.health()
    return {"phase": "serving", "seed": seed, "requests": requests, "p": p,
            "faults_fired": fires, "client_errors": errors,
            "outputs_bitwise_equal": bitwise,
            "circuit": health["circuit"], "ok": bitwise}


def _build_elastic(seed, width, in_dim=8, hidden=16, out_dim=8):
    """fsdp-sharded trainer on a ``width``-device mesh (dims divisible by 8
    so the same net re-shards onto 8/4/1 devices)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon import nn, loss as gloss

    mx.random.seed(seed)
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(hidden, activation="relu"), nn.Dense(out_dim))
    net.initialize(mx.init.Xavier())
    net(nd.array(onp.zeros((2, in_dim), "float32")))
    for p_ in net.collect_params().values():
        p_.shard(("fsdp",))
    mesh = parallel.make_mesh({"fsdp": width}, devices=jax.devices()[:width])
    step = parallel.ParallelTrainStep(
        net, gloss.L2Loss(), mx.optimizer.Adam(learning_rate=0.05), mesh,
        data_spec=(), label_spec=())
    return net, step


def _gather(step):
    import jax
    return [onp.asarray(jax.device_get(a)) for a in step.params]


def check_preempt(seed, steps=8, p=0.0, ckpt_dir=None, in_dim=8, out_dim=8):
    """SCENARIO preempt: an 8-way fsdp run catches an injected preemption
    notice mid-run, force-flushes a SHARDED checkpoint + marker within the
    deadline, and the job resumes on a 4-way mesh (elastic restore). Final
    gathered train state must be bitwise-equal to an oracle that continued
    on 4-way from the same state handed over in-memory — the checkpoint
    round-trip and re-shard add zero numeric perturbation."""
    from mxnet_tpu.resilience import (CheckpointManager, PreemptionGuard,
                                      faults)

    rng = onp.random.RandomState(seed)
    X = rng.randn(steps, 16, in_dim).astype("float32")
    Y = rng.randn(steps, 16, out_dim).astype("float32")
    preempt_at = max(2, steps // 2)
    ckpt_dir = ckpt_dir or tempfile.mkdtemp(prefix="chaos-preempt-")
    cm = CheckpointManager(ckpt_dir, keep=2, async_save=True, fsync=False)

    # the preempted run: 8-way until the notice, then rebuilt 4-way
    net_a, step_a = _build_elastic(seed, 8, in_dim=in_dim, out_dim=out_dim)
    guard = PreemptionGuard(cm, capture=dict(train_step=step_a),
                            sharded=True, deadline_s=30.0)
    stopped_at = None
    with guard, faults.inject("preempt", at=(preempt_at,)) as inj:
        for i in range(steps):
            step_a(X[i], Y[i])
            if guard.should_stop(i + 1):
                stopped_at = i + 1
                break
    marker = PreemptionGuard.resume_info(cm)
    state_at_stop = step_a.state_dict()
    # resume on HALF the devices
    net_b, step_b = _build_elastic(seed + 999, 4, in_dim=in_dim,
                                   out_dim=out_dim)
    restored = cm.restore_latest(train_step=step_b)
    restore_ok = restored is not None and restored[0] == stopped_at
    fidelity = all(onp.array_equal(a, b) for a, b in zip(
        _gather(step_a), _gather(step_b)))
    for i in range(stopped_at, steps):
        step_b(X[i], Y[i])

    # oracle: 4-way continuation from the same state, no disk involved
    net_o, step_o = _build_elastic(seed + 777, 4, in_dim=in_dim,
                                   out_dim=out_dim)
    step_o.load_state_dict(state_at_stop)
    for i in range(stopped_at, steps):
        step_o(X[i], Y[i])
    bitwise = all(onp.array_equal(a, b) for a, b in zip(
        _gather(step_b), _gather(step_o)))

    ok = (stopped_at == preempt_at and marker is not None and
          marker.get("saved") and marker.get("within_deadline") and
          restore_ok and fidelity and bitwise)
    return {"phase": "preempt", "seed": seed, "steps": steps,
            "preempt_at": preempt_at, "stopped_at": stopped_at,
            "marker": marker, "faults_fired": inj.fires,
            "restore_ok": restore_ok, "restore_bitwise_fidelity": fidelity,
            "state_bitwise_equal": bitwise, "ok": bool(ok)}


def check_worker_kill(seed, requests=24, p=0.0, in_dim=8, out_dim=4):
    """SCENARIO worker_kill: a BaseException kills the serving worker thread
    mid-stream; the PoolSupervisor declares it dead, requeues its batches
    and restarts. Every request on the victim tenant must complete
    bitwise-correct or fail with a classified ServingError within its
    deadline; the OTHER tenant must see zero errors."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.resilience import RetryPolicy, faults

    def mlp(s):
        onp.random.seed(s)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
        net.initialize(mx.init.Xavier())
        net(nd.array(onp.zeros((2, in_dim), "float32")))
        return net

    net_v, net_o = mlp(seed), mlp(seed + 1)
    vname, oname = f"chaos_fo_{seed}", f"chaos_fo_other_{seed}"
    ep_v = serving.ModelEndpoint(vname, net_v, input_shapes=(in_dim,),
                                 max_batch_size=4)
    ep_o = serving.ModelEndpoint(oname, net_o, input_shapes=(in_dim,),
                                 max_batch_size=4)
    srv = serving.InferenceServer(
        batch_timeout_ms=1.0, max_queue=max(64, requests * 2),
        retry_policy=RetryPolicy(max_attempts=4, base_ms=1.0, seed=seed))
    srv.register(ep_v)
    srv.register(ep_o)
    srv.start()
    sup = serving.PoolSupervisor(srv, poll_s=0.02).start()
    xs = onp.random.RandomState(seed + 2).randn(
        requests, in_dim).astype("float32")
    victim_err, victim_unclassified, other_err = [], [], 0
    completed = {"victim": 0, "other": 0}
    outs = [None] * requests
    try:
        with faults.inject("worker_kill", site="serving_dispatch",
                           at=(2, 5), times=2) as inj:
            futs_v = [srv.submit(vname, xs[i], deadline_ms=60_000)
                      for i in range(requests)]
            futs_o = [srv.submit(oname, xs[i]) for i in range(requests)]
            for i, f in enumerate(futs_v):
                try:
                    outs[i] = f.result(timeout=120).asnumpy()
                    completed["victim"] += 1
                except serving.ServingError as e:
                    victim_err.append(type(e).__name__)
                except Exception as e:      # unclassified = a real bug
                    victim_unclassified.append(repr(e))
            for f in futs_o:
                try:
                    f.result(timeout=120)
                    completed["other"] += 1
                except Exception:
                    other_err += 1
        fires = inj.fires
    finally:
        sup.stop()
        srv.stop()
        serving.unregister(vname)
        serving.unregister(oname)
    direct = net_v(nd.array(xs)).asnumpy()
    bitwise = all(o is None or onp.array_equal(o, direct[i])
                  for i, o in enumerate(outs))
    ok = (fires >= 1 and sup.failovers >= 1 and not victim_unclassified and
          other_err == 0 and bitwise and
          completed["victim"] + len(victim_err) == requests)
    return {"phase": "worker_kill", "seed": seed, "requests": requests,
            "faults_fired": fires, "failovers": sup.failovers,
            "completed": completed, "victim_classified_errors": victim_err,
            "victim_unclassified_errors": victim_unclassified,
            "other_tenant_errors": other_err,
            "outputs_bitwise_equal": bitwise, "ok": bool(ok)}


def check_hot_swap(seed, requests=30, p=0.0, cycles=3, in_dim=8, out_dim=4):
    """SCENARIO hot_swap: under continuous two-tenant load, cycle the victim
    endpoint's weights >= ``cycles`` times between two checkpointed weight
    sets, plus one corrupt-checkpoint swap that must roll back. Zero client
    errors, zero dropped requests; post-swap outputs bitwise-equal to a
    fresh endpoint loaded from the same checkpoint."""
    import shutil
    import threading
    import time as _time
    import mxnet_tpu as mx
    from mxnet_tpu import nd, serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.resilience import CheckpointManager

    def mlp(s):
        onp.random.seed(s)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
        net.initialize(mx.init.Xavier())
        net(nd.array(onp.zeros((2, in_dim), "float32")))
        return net

    name = f"chaos_hs_{seed}"
    oname = f"chaos_hs_other_{seed}"
    ep = serving.ModelEndpoint(name, mlp(seed), input_shapes=(in_dim,),
                               max_batch_size=4)
    ep_o = serving.ModelEndpoint(oname, mlp(seed + 5),
                                 input_shapes=(in_dim,), max_batch_size=4)
    # producer side: two serving checkpoints with recorded probes
    dirs = []
    for k in (1, 2):
        d = tempfile.mkdtemp(prefix=f"chaos-hs-{k}-")
        src = serving.ModelEndpoint(f"{name}_src{k}", mlp(seed + k),
                                    input_shapes=(in_dim,), max_batch_size=4)
        src.save_checkpoint(CheckpointManager(d, fsync=False), k,
                            probe_seed=seed + k)
        serving.unregister(f"{name}_src{k}")
        dirs.append(d)
    # a corrupt copy of checkpoint 1
    corrupt = tempfile.mkdtemp(prefix="chaos-hs-bad-")
    shutil.copytree(os.path.join(dirs[0], "ckpt-00000001"),
                    os.path.join(corrupt, "ckpt-00000001"))
    bad = os.path.join(corrupt, "ckpt-00000001", "state.npz")
    raw = bytearray(open(bad, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(bad, "wb").write(bytes(raw))

    srv = serving.InferenceServer(batch_timeout_ms=1.0,
                                  max_queue=max(128, requests * 4))
    srv.register(ep)
    srv.register(ep_o)
    srv.start()
    xs = onp.random.RandomState(seed + 3).randn(
        requests, in_dim).astype("float32")
    stop_flag = threading.Event()
    client_errors = []
    served = {"n": 0}

    def load(tenant):
        i = 0
        while not stop_flag.is_set():
            try:
                srv.predict(tenant, xs[i % requests], timeout=60)
                served["n"] += 1
            except Exception as e:
                client_errors.append(repr(e))
            i += 1

    threads = [threading.Thread(target=load, args=(n,))
               for n in (name, oname)]
    for t in threads:
        t.start()
    swaps, rollback_ok = 0, False
    try:
        for c in range(cycles):
            srv.hot_swap(name, dirs[c % 2], timeout=60)
            swaps += 1
            _time.sleep(0.02)
        try:
            srv.hot_swap(name, corrupt, timeout=60)
        except serving.HotSwapError:
            rollback_ok = True
        epoch_after = ep.weights_epoch
        _time.sleep(0.05)
    finally:
        stop_flag.set()
        for t in threads:
            t.join()
        srv.stop()
    # post-swap weights = dirs[(cycles-1) % 2]; compare to a fresh endpoint
    # loaded from that checkpoint
    fresh = serving.ModelEndpoint(f"{name}_fresh", mlp(seed + 9),
                                  input_shapes=(in_dim,), max_batch_size=4)
    fresh.hot_swap(dirs[(cycles - 1) % 2])
    srv2 = serving.InferenceServer(batch_timeout_ms=1.0)
    srv2.register(fresh, warmup=False)
    srv2.register(ep, warmup=False)
    srv2.start()
    try:
        want = srv2.predict(f"{name}_fresh", xs[0], timeout=60).asnumpy()
        got = srv2.predict(name, xs[0], timeout=60).asnumpy()
    finally:
        srv2.stop()
        serving.unregister(f"{name}_fresh")
        serving.unregister(name)
        serving.unregister(oname)
    bitwise = onp.array_equal(got, want)
    ok = (swaps >= cycles and rollback_ok and not client_errors and
          bitwise and epoch_after == swaps and served["n"] > 0)
    return {"phase": "hot_swap", "seed": seed, "swap_cycles": swaps,
            "corrupt_swap_rolled_back": rollback_ok,
            "requests_served": served["n"],
            "client_errors": client_errors[:5],
            "post_swap_bitwise_equal": bitwise,
            "weights_epoch": epoch_after, "ok": bool(ok)}


def check_nan_grad(seed, steps=30, p=0.0, in_dim=8, hidden=16, out_dim=4):
    """SCENARIO nan_grad: NaN gradients injected mid-window; the guard's
    lagged boundary read finds them, rewinds to its on-device snapshot and
    replays the window minus the poisoned batches. The run must end BITWISE
    equal to a clean run trained on the same batches minus the skipped
    ones, and the guard must report exactly those skips."""
    from mxnet_tpu.resilience import NumericsGuard, faults

    rng = onp.random.RandomState(seed)
    X = rng.randn(steps, 16, in_dim).astype("float32")
    Y = rng.randn(steps, 16, out_dim).astype("float32")
    # two poisoned steps, one mid-window and one right on a boundary
    bad = sorted({max(2, steps // 4), max(3, (2 * steps) // 3)})

    # clean reference: never trains on the poisoned batches
    net_r, step_r = _build_train(seed, in_dim, hidden, out_dim)
    for i in range(steps):
        if i in bad:
            continue
        step_r(X[i], Y[i])
    step_r.sync_to_block()
    ref_w = [p_.data().asnumpy() for p_ in net_r.collect_params().values()]

    # guarded chaos: injection corrupts the very same step indices
    net_c, step_c = _build_train(seed, in_dim, hidden, out_dim)
    guard = NumericsGuard(check_every_n=5, policy="skip")
    guard.attach(step_c)
    with faults.inject("nan_grad", at=tuple(i + 1 for i in bad)) as inj:
        for i in range(steps):
            step_c(X[i], Y[i])
    guard.finalize()
    step_c.sync_to_block()
    chaos_w = [p_.data().asnumpy() for p_ in net_c.collect_params().values()]

    w_ok = all(onp.array_equal(a, b) for a, b in zip(ref_w, chaos_w))
    ok = (w_ok and inj.fires == len(bad) and
          guard.skipped_steps == len(bad) and guard.recoveries >= 1)
    return {"phase": "nan_grad", "seed": seed, "steps": steps,
            "poisoned_steps": bad, "faults_fired": inj.fires,
            "skipped_steps": guard.skipped_steps,
            "recoveries": guard.recoveries,
            "last_anomaly": guard.last_anomaly,
            "weights_bitwise_equal": w_ok, "ok": bool(ok)}


def check_bad_batch(seed, steps=30, p=0.0, in_dim=8, hidden=16, out_dim=4,
                    quarantine_dir=None):
    """SCENARIO bad_batch: a poisoned batch served by a real (seeded,
    shuffling) DataLoader is quarantined — fingerprinted, dumped to the
    quarantine dir, and positionally excluded so a resumed/rewound loader
    never serves it again. Training must end bitwise-equal to a clean run
    that skipped the same batch positions."""
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    from mxnet_tpu.resilience import NumericsGuard, faults

    rng = onp.random.RandomState(seed)
    n, bs = steps * 16, 16
    X = rng.randn(n, in_dim).astype("float32")
    Y = rng.randn(n, out_dim).astype("float32")
    bad = sorted({max(1, steps // 3), max(2, steps // 2)})
    quarantine_dir = quarantine_dir or tempfile.mkdtemp(prefix="chaos-quar-")

    def run(poisoned):
        net, step = _build_train(seed, in_dim, hidden, out_dim)
        loader = DataLoader(ArrayDataset(X, Y), batch_size=bs, shuffle=True)
        guard = None
        if poisoned:
            guard = NumericsGuard(check_every_n=5, policy="quarantine",
                                  quarantine_dir=quarantine_dir,
                                  dataloader=loader)
            guard.attach(step)
        onp.random.seed(seed + 77)          # epoch shuffle permutation
        if poisoned:
            with faults.inject("bad_batch",
                               at=tuple(i + 1 for i in bad)) as inj:
                for x, y in loader:
                    step(x, y)
            guard.finalize()
        else:
            inj = None
            for i, (x, y) in enumerate(loader):
                if i in bad:
                    continue
                step(x, y)
        step.sync_to_block()
        w = [p_.data().asnumpy() for p_ in net.collect_params().values()]
        return w, guard, loader, inj

    ref_w, _, _, _ = run(poisoned=False)
    chaos_w, guard, loader, inj = run(poisoned=True)

    w_ok = all(onp.array_equal(a, b) for a, b in zip(ref_w, chaos_w))
    quarantined = loader.quarantined
    dumps = sorted(f for f in os.listdir(quarantine_dir)
                   if f.endswith(".npz"))
    # the excluded positions must survive a state_dict round-trip (the
    # rewind/replay exclusion guarantee)
    st = loader.state_dict()
    loader2 = DataLoader(ArrayDataset(X, Y), batch_size=bs, shuffle=True)
    loader2.load_state_dict(st)
    ok = (w_ok and inj.fires == len(bad) and
          quarantined == [(0, i) for i in bad] and
          len(dumps) >= len(bad) and
          loader2.quarantined == quarantined)
    return {"phase": "bad_batch", "seed": seed, "steps": steps,
            "poisoned_positions": bad, "faults_fired": inj.fires,
            "quarantined": quarantined, "quarantine_dumps": len(dumps),
            "roundtrip_quarantine_ok": loader2.quarantined == quarantined,
            "weights_bitwise_equal": w_ok, "ok": bool(ok)}


def check_sdc(seed, steps=20, p=0.0, bundle_dir=None, in_dim=8, hidden=16,
              out_dim=4):
    """SCENARIO sdc: an injected digest divergence in the guard's window
    re-execution must (a) leave the live run untouched, (b) fire the
    suspect counter and write a repro bundle, and (c) have
    tools/replay_step.py re-execute that bundle to the same deterministic
    verdict — twice."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import replay_step
    from mxnet_tpu import telemetry
    from mxnet_tpu.resilience import NumericsGuard, faults

    rng = onp.random.RandomState(seed)
    X = rng.randn(steps, 16, in_dim).astype("float32")
    Y = rng.randn(steps, 16, out_dim).astype("float32")
    bundle_dir = bundle_dir or tempfile.mkdtemp(prefix="chaos-sdc-")

    net_c, step_c = _build_train(seed, in_dim, hidden, out_dim)
    guard = NumericsGuard(
        check_every_n=5, policy="skip", sdc_check_every_n=10,
        sdc_bundle_dir=bundle_dir,
        repro_meta=dict(builder="demo_mlp", seed=seed, in_dim=in_dim,
                        hidden=hidden, out_dim=out_dim, lr=0.05))
    guard.attach(step_c)
    before = telemetry.counter("mxtpu_sdc_suspect_total").value
    with faults.inject("sdc", at=(1,)) as inj:
        for i in range(steps):
            step_c(X[i], Y[i])
    guard.finalize()
    suspects = telemetry.counter("mxtpu_sdc_suspect_total").value - before

    # the screen must be invisible to training: bitwise vs a plain run
    net_r, step_r = _build_train(seed, in_dim, hidden, out_dim)
    for i in range(steps):
        step_r(X[i], Y[i])
    live_ok = all(
        onp.array_equal(onp.asarray(a), onp.asarray(b))
        for a, b in zip(_gather(step_c), _gather(step_r)))

    bundles = guard.sdc_bundles
    verdicts = []
    if bundles:
        verdicts = [replay_step.replay(bundles[0])["verdict"]
                    for _ in range(2)]
    ok = (inj.fires == 1 and suspects == 1 and live_ok and
          len(bundles) == 1 and verdicts == ["replay_corrupt"] * 2)
    return {"phase": "sdc", "seed": seed, "steps": steps,
            "faults_fired": inj.fires, "sdc_suspects": int(suspects),
            "live_run_unperturbed": live_ok, "bundles": bundles,
            "replay_verdicts": verdicts, "ok": bool(ok)}


def check_decode(seed, requests=6, p=0.0, max_new=18):
    """SCENARIO decode: generative serving under mid-generation faults. A
    ``decode_stall`` (WorkerKilled) takes the decode worker down with
    partially-generated sequences in flight, and a ``kv_exhausted`` bounces
    a reservation. The failover must requeue the partial sequences and
    continue them on the respawned worker with NO duplicated and NO dropped
    tokens: every stream's output must be bitwise-equal to a fault-free
    serial greedy decode of the same prompt through the same executables."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import TransformerLM
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.serving.generate import DecodeEndpoint, DecodeScheduler

    onp.random.seed(seed)
    rng = onp.random.RandomState(seed)
    lm = TransformerLM(num_layers=2, units=32, hidden_size=64, num_heads=2,
                       vocab_size=50, max_length=64)
    lm.initialize(mx.init.Normal(0.5))
    eng = DecodeEndpoint(f"chaos_dec_{seed}", lm, max_seq_len=64,
                         max_batch_size=4, page_size=8, num_pages=64)
    eng.warmup()
    prompts = [list(map(int, rng.randint(1, 49, size=rng.randint(1, 6))))
               for _ in range(requests)]
    budgets = [int(rng.randint(max_new // 2, max_new + 1))
               for _ in range(requests)]

    def serial(prompt, budget, sid):
        eng.pool.reserve(sid, len(prompt) + budget)
        toks = [eng.prefill(prompt, eng.pool.table(sid))]
        pos = len(prompt)
        for _ in range(budget - 1):
            (t,) = eng.decode_step([(toks[-1], pos, eng.pool.table(sid))])
            toks.append(t)
            pos += 1
        eng.pool.free(sid)
        return toks

    oracle = [serial(pr, b, 90000 + i)
              for i, (pr, b) in enumerate(zip(prompts, budgets))]

    sched = DecodeScheduler(eng, poll_s=0.02).add_tenant("gold", 5.0)
    sched.start()
    unclassified = []
    try:
        with faults.inject("decode_stall", at=(6,), times=1) as stall, \
                faults.inject("kv_exhausted", at=(2,), times=1) as exh:
            streams = [
                sched.submit(pr, max_new_tokens=b,
                             tenant="gold" if i % 2 else "default")
                for i, (pr, b) in enumerate(zip(prompts, budgets))]
            results = [None] * requests
            for i, s in enumerate(streams):
                try:
                    results[i] = s.result(timeout=120)
                except Exception as e:
                    unclassified.append(repr(e))
        counters = eng.stats.snapshot()["counters"]
        pool_leak = eng.pool.pages_in_use
    finally:
        sched.stop()
    # no dropped tokens (every stream ran to its budget) and no duplicated
    # tokens (bitwise equality to the serial oracle covers both)
    complete = all(r is not None and len(r) == b
                   for r, b in zip(results, budgets))
    bitwise = results == oracle
    ok = (stall.fires >= 1 and exh.fires >= 1 and sched.failovers >= 1 and
          counters["seq_requeued"] >= 1 and not unclassified and
          complete and bitwise and pool_leak == 0)
    return {"phase": "decode", "seed": seed, "requests": requests,
            "stalls_fired": stall.fires, "exhaustions_fired": exh.fires,
            "failovers": sched.failovers,
            "requeued": counters["seq_requeued"],
            "tokens_emitted": counters["tokens"],
            "unclassified_errors": unclassified,
            "all_sequences_complete": complete,
            "outputs_bitwise_equal": bitwise,
            "kv_pages_leaked": pool_leak, "ok": bool(ok)}


# phase A of cache_poison, run as a REAL separate process: the "previous
# server" that populates the executable cache. Its spans join the parent's
# cross-process journey via the inherited MXNET_TRACE_ID, and its registry
# snapshot lands next to the parent's for tools/fleet_report.py.
_CACHE_WARMER_SRC = """\
import os
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import nd, serving, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import goodput

mx.random.seed({seed}); onp.random.seed({seed})
net = nn.HybridSequential()
with net.name_scope():
    net.add(nn.Dense(16, activation="relu"), nn.Dense({out_dim}))
net.initialize(mx.init.Xavier())
net(nd.array(onp.zeros((2, {in_dim}), "float32")))
srv = serving.InferenceServer(batch_timeout_ms=1.0)
srv.register(serving.ModelEndpoint({name!r}, net,
                                   input_shapes=({in_dim},), max_batch_size=4))
srv.start()
srv.stop()
serving.unregister({name!r})
goodput.account()
dump = os.environ.get("CHAOS_DUMP_PATH", "")
if dump:
    telemetry.dump(dump)
telemetry.spool_flush()
"""


def check_cache_poison(seed, requests=16, p=0.0, in_dim=8, out_dim=4):
    """SCENARIO cache_poison (r17): a prior server populated the persistent
    executable cache; a ``cache_poison`` fault corrupts one entry ON DISK
    just as the next server warms from it. The genuine sha256-verify path
    must detect the corruption, delete the entry and fall back to a live
    recompile — zero client-visible errors, every served output bitwise
    equal to the direct forward, and the store healed (the recompile
    re-stored the entry). The prior server is a genuine subprocess, so the
    drill's trace journey crosses a real process boundary."""
    import subprocess
    import mxnet_tpu as mx
    from mxnet_tpu import config, nd, serving
    from mxnet_tpu.cache import executable_cache as xcache
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.telemetry.metrics import REGISTRY

    def mlp(s):
        mx.random.seed(s)
        onp.random.seed(s)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
        net.initialize(mx.init.Xavier())
        net(nd.array(onp.zeros((2, in_dim), "float32")))
        return net

    d = tempfile.mkdtemp(prefix="chaos-xcache-")
    prev = config.get("MXNET_EXEC_CACHE_DIR", "")
    config.set("MXNET_EXEC_CACHE_DIR", d)
    corrupt_ctr = REGISTRY.counter("mxtpu_exec_cache_misses_total",
                                   labelnames=("reason",)).labels("corrupt")
    # both phases register under ONE name: the compile trigger key carries
    # the endpoint name, so a restarted endpoint must keep its name to hit
    name_b = f"chaos_cp_{seed}"
    try:
        # phase A: the "previous process" — a real subprocess warms the
        # shared on-disk cache (compiles + stores) and exits; it inherits
        # the trace/spool env so its spans land in the same journey
        env = dict(os.environ)
        env["MXNET_EXEC_CACHE_DIR"] = d
        fleet_dir = env.get("CHAOS_FLEET_DIR", "")
        if fleet_dir:
            env["CHAOS_DUMP_PATH"] = os.path.join(
                fleet_dir, "dump-warmer.json")
        warmer = subprocess.run(
            [sys.executable, "-c", _CACHE_WARMER_SRC.format(
                seed=seed, in_dim=in_dim, out_dim=out_dim, name=name_b)],
            env=env, capture_output=True, text=True)
        warmer_ok = warmer.returncode == 0
        stored = len(xcache.entries())

        # phase B: warm restart under poison — first load hits a payload
        # the fault just truncated on disk
        before = xcache.stats()
        corrupt_before = corrupt_ctr.value
        errors = 0
        outs = [None] * requests
        net_b = mlp(seed)
        with faults.inject("cache_poison", site="exec_cache",
                           at=(1,)) as inj:
            ep_b = serving.ModelEndpoint(name_b, net_b,
                                         input_shapes=(in_dim,),
                                         max_batch_size=4)
            srv_b = serving.InferenceServer(
                batch_timeout_ms=1.0, max_queue=max(64, requests * 2))
            srv_b.register(ep_b)       # warmup: 1 poisoned, rest cache hits
            srv_b.start()
            xs = onp.random.RandomState(seed + 1).randn(
                requests, in_dim).astype("float32")
            futs = [srv_b.submit(name_b, xs[i]) for i in range(requests)]
            for i, f in enumerate(futs):
                try:
                    outs[i] = f.result(timeout=120).asnumpy()
                except Exception:
                    errors += 1
        srv_b.stop()
        serving.unregister(name_b)
        after = xcache.stats()
        healed = len(xcache.entries())
        corrupt_misses = int(corrupt_ctr.value - corrupt_before)
    finally:
        config.set("MXNET_EXEC_CACHE_DIR", prev)
    direct = net_b(nd.array(xs)).asnumpy()
    bitwise = errors == 0 and all(
        o is not None and onp.array_equal(o, direct[i])
        for i, o in enumerate(outs))
    hits = after["hits"] - before["hits"]
    ok = (warmer_ok and inj.fires >= 1 and corrupt_misses >= 1 and
          errors == 0 and bitwise and hits >= 1 and stored >= 2 and
          healed == stored)
    return {"phase": "cache_poison", "seed": seed, "requests": requests,
            "warmer_subprocess_ok": warmer_ok,
            "warmer_stderr_tail": "" if warmer_ok else warmer.stderr[-500:],
            "faults_fired": inj.fires, "entries_stored_cold": stored,
            "entries_after_heal": healed, "corrupt_misses": corrupt_misses,
            "warm_cache_hits": hits, "client_errors": errors,
            "outputs_bitwise_equal": bitwise, "ok": bool(ok)}


def check_autoscale(seed, requests=24, p=0.0, in_dim=8, out_dim=4):
    """SCENARIO autoscale (r17): under continuous client load through the
    ServingPool front door, a synthetic SLO burn drives the Autoscaler up
    to max_replicas and recovery drives it back down to min, with every
    transition leaving an ``autoscale_*`` flight event. Zero client-visible
    errors across every cutover (scale-down removes a replica from rotation
    BEFORE draining it), and served outputs stay bitwise-equal to the
    direct forward on every replica (identical seeded weights)."""
    import threading
    import mxnet_tpu as mx
    from mxnet_tpu import nd, serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.telemetry import flight

    svc = f"chaos_as_{seed}"

    def mlp():
        mx.random.seed(seed)
        onp.random.seed(seed)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
        net.initialize(mx.init.Xavier())
        net(nd.array(onp.zeros((2, in_dim), "float32")))
        return net

    nets = {}

    def factory(rid):
        net = mlp()                   # same seed: replicas serve bitwise-
        nets[rid] = net               # identical outputs
        srv = serving.InferenceServer(batch_timeout_ms=1.0, max_queue=128)
        srv.register(serving.ModelEndpoint(
            svc, net, input_shapes=(in_dim,), max_batch_size=4))
        return srv

    class _BurnStub:
        """Synthetic SLO monitor: one objective whose fast burn we flip."""
        burn_threshold = 14.0

        def __init__(self):
            self.burning = False

        def check_all(self):
            burn = 20.0 if self.burning else 0.0
            return [{"endpoint": svc, "fast_burn": burn, "slow_burn": burn,
                     "alert_active": self.burning}]

    mon = _BurnStub()
    events_before = len(flight.recent_events())
    pool = serving.ServingPool(factory, initial_replicas=1)
    asc = serving.Autoscaler(pool, monitor=mon, min_replicas=1,
                             max_replicas=3, up_n=2, down_n=3,
                             cooldown_s=0.0, queue_high=0.9, queue_low=0.5)
    xs = onp.random.RandomState(seed + 1).randn(
        requests, in_dim).astype("float32")
    stop_flag = threading.Event()
    client_errors = []
    served = {"n": 0}
    outs = []
    lock = threading.Lock()

    def load(ci):
        i = 0
        while not stop_flag.is_set():
            try:
                o = pool.predict(svc, xs[(ci + i) % requests],
                                 timeout=60).asnumpy()
                with lock:
                    outs.append(((ci + i) % requests, o))
                    served["n"] += 1
            except Exception as e:
                client_errors.append(repr(e))
            i += 1

    sizes = []
    threads = [threading.Thread(target=load, args=(c,)) for c in range(3)]
    for t in threads:
        t.start()
    try:
        # synthetic burn: two consecutive over-polls per scale-up
        mon.burning = True
        for tick in range(6):
            asc.tick(now=float(tick))
            sizes.append(pool.size())
        peak = pool.size()
        # recovery: three consecutive idle polls per scale-down
        mon.burning = False
        for tick in range(10):
            asc.tick(now=100.0 + tick)
            sizes.append(pool.size())
        settled = pool.size()
    finally:
        stop_flag.set()
        for t in threads:
            t.join()
        pool.stop(drain=True)
        serving.unregister(svc)
    direct = nets[0](nd.array(xs)).asnumpy()
    bitwise = all(onp.array_equal(o, direct[i]) for i, o in outs)
    kinds = [e.get("kind") for e in
             flight.recent_events()[events_before:]]
    ups = kinds.count("autoscale_up")
    downs = kinds.count("autoscale_down")
    actions = [a["action"] for a in asc.actions]
    flight_ok = (ups == actions.count("up")
                 and downs == actions.count("down"))
    ok = (peak == 3 and settled == 1 and ups >= 2 and downs >= 2 and
          flight_ok and not client_errors and served["n"] > 0 and bitwise)
    return {"phase": "autoscale", "seed": seed,
            "replica_sizes": sizes, "peak_replicas": peak,
            "settled_replicas": settled, "actions": actions,
            "flight_up_events": ups, "flight_down_events": downs,
            "requests_served": served["n"],
            "client_errors": client_errors[:5],
            "outputs_bitwise_equal": bitwise, "ok": bool(ok)}


def _metric_total(name):
    """Sum a metric family across its label series (0.0 if unregistered)."""
    from mxnet_tpu import telemetry
    fam = telemetry.REGISTRY.get(name)
    if fam is None:
        return 0.0
    return float(sum(c.value for _, c in fam._series()))


def check_dlrm(seed, steps=8, p=0.0):
    """DLRM over a vocab-sharded embedding: inject a retryable
    ``emb_exchange`` fault mid-epoch at the ``emb_dispatch`` site and assert
    the retried run converges BITWISE to the fault-free oracle (the step is
    functional — weights are inputs, so a replayed attempt is identical),
    with zero KVStore host-loop traffic while the on-mesh exchange counter
    moves."""
    import jax
    from mxnet_tpu import parallel
    from mxnet_tpu.embedding import (ShardedEmbedding, DLRMTrainStep,
                                     synthetic_dlrm_batches)
    from mxnet_tpu.resilience import RetryPolicy, faults

    n = min(4, len(jax.devices()))
    V, D, B, F, DIN = 64, 8, 16, 4, 6
    batches = synthetic_dlrm_batches(steps, B, DIN, F, V, seed=seed)
    w0 = onp.random.RandomState(seed).normal(0, 0.1, (V, D)).astype("float32")

    def build():
        mesh = parallel.make_mesh({"tp": n}, devices=jax.devices()[:n])
        emb = ShardedEmbedding(V, D, mesh, axis="tp", weight=w0)
        step = DLRMTrainStep(
            emb, DIN, F, lr=0.1, mode="replicated", seed=seed,
            retry=RetryPolicy(max_attempts=8, base_ms=1.0, seed=seed))
        return emb, step

    emb_ref, step_ref = build()
    ref_losses = [step_ref(b) for b in batches]
    ref_w = emb_ref.dense_weight()

    kv_before = (_metric_total("mxtpu_kvstore_push_bytes_total"),
                 _metric_total("mxtpu_kvstore_wire_bytes_total"))
    ex_before = _metric_total("mxtpu_emb_exchange_bytes_total")
    emb_c, step_c = build()
    mid = max(1, steps // 2)
    inject_kw = {"p": p, "seed": seed} if p else {"at": (mid,)}
    with faults.inject("emb_exchange", site="emb_dispatch",
                       **inject_kw) as inj:
        losses = [step_c(b) for b in batches]
    chaos_w = emb_c.dense_weight()
    kv_after = (_metric_total("mxtpu_kvstore_push_bytes_total"),
                _metric_total("mxtpu_kvstore_wire_bytes_total"))
    ex_after = _metric_total("mxtpu_emb_exchange_bytes_total")

    loss_ok = losses == ref_losses
    w_ok = onp.array_equal(ref_w, chaos_w)
    kv_ok = kv_after == kv_before
    ex_ok = ex_after > ex_before
    ok = (loss_ok and w_ok and kv_ok and ex_ok and inj.fires >= 1)
    return {"phase": "dlrm", "seed": seed, "steps": steps, "shards": n,
            "faults_fired": inj.fires, "fault_calls": inj.calls,
            "final_loss": losses[-1], "final_loss_ref": ref_losses[-1],
            "loss_bitwise_equal": loss_ok, "table_bitwise_equal": w_ok,
            "kvstore_bytes_flat": kv_ok,
            "exchange_bytes_moved": float(ex_after - ex_before),
            "ok": bool(ok)}


def check_host_down(seed, requests=24, p=0.0, in_dim=8, out_dim=4):
    """SCENARIO host_down (r18): the serving-fabric FrontDoor loses a whole
    host mid-load. Clients keep submitting through the consistent-hash ring
    while the victim (the host owning the most tenants) is taken out: its
    agent subprocess SIGKILLed, its serving plane failed with drain=False so
    queued work raises ServerClosedError — which the front door's wrapper
    futures must replay on survivors. Acceptance: zero client-visible
    errors, every output bitwise-equal to the direct forward, rebalancing
    bounded to exactly the victim's tenants, and the post-mortem pane
    intact — the fleet collector still names BOTH hosts (the dead agent
    left a recent dump behind) and every host's goodput ledger reconciles
    buckets-to-wall within 1%."""
    import threading
    import time
    import mxnet_tpu as mx
    from mxnet_tpu import config, nd, serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.serving.fabric import FrontDoor

    tenants = [f"chaos_fab_{seed}_{i}" for i in range(4)]

    def mlp():
        mx.random.seed(seed)
        onp.random.seed(seed)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
        net.initialize(mx.init.Xavier())
        net(nd.array(onp.zeros((2, in_dim), "float32")))
        return net

    ref = mlp()
    weights = [prm.data().asnumpy() for prm in ref.collect_params().values()]

    def factory(name):
        net = mlp()
        for prm, w in zip(net.collect_params().values(), weights):
            prm.set_data(nd.array(w))      # hosts serve identical weights
        srv = serving.InferenceServer(batch_timeout_ms=1.0,
                                      max_queue=max(256, requests * 8))
        for i, t in enumerate(tenants):
            srv.register(serving.ModelEndpoint(
                t, net, input_shapes=(in_dim,), max_batch_size=4),
                warmup=(i == 0))
        srv.start()
        return srv

    # host-agent dumps land in the fleet dir (dump-host-*.json), so
    # tools/fleet_report.py and the collector read the pane the drill
    # leaves behind
    workdir = os.environ.get("CHAOS_FLEET_DIR") or tempfile.mkdtemp(
        prefix="chaos-fabric-")
    resub_before = _metric_total("mxtpu_fabric_resubmits_total")
    fd = FrontDoor(["alpha", "beta"], factory, workdir=workdir)
    xs = onp.random.RandomState(seed + 1).randn(
        requests, in_dim).astype("float32")
    stop_flag = threading.Event()
    client_errors = []
    outs = []
    lock = threading.Lock()

    def load(ci):
        i = 0
        while not stop_flag.is_set():
            t = tenants[(ci + i) % len(tenants)]
            k = (ci + i) % requests
            try:
                o = fd.submit(t, xs[k]).result(timeout=120)
                with lock:
                    outs.append((k, o.asnumpy()))
            except Exception as e:
                client_errors.append(repr(e))
            i += 1

    threads = [threading.Thread(target=load, args=(c,)) for c in range(3)]
    agents_seen = False
    burst_errors = 0
    try:
        owner_before = {t: fd.route(t) for t in tenants}
        by_host = {n: [t for t in tenants if owner_before[t] == n]
                   for n in fd.hosts()}
        victim = max(by_host, key=lambda n: len(by_host[n]))
        survivor = next(n for n in fd.hosts() if n != victim)
        for t in threads:
            t.start()
        # the dead host must leave a dump for the post-mortem pane: wait
        # for both agents to boot and write one (spans flush just before)
        deadline = time.time() + 60
        while time.time() < deadline:
            if all(os.path.exists(os.path.join(
                    workdir, f"dump-host-{n}.json")) for n in fd.hosts()):
                agents_seen = True
                break
            time.sleep(0.1)
        # burst the victim's tenants so its queue is non-empty at the
        # kill, then take the host out mid-load
        burst = [fd.submit(by_host[victim][i % len(by_host[victim])],
                           xs[i % requests]) for i in range(requests * 2)]
        rep = fd.kill_host(victim)
        for i, f in enumerate(burst):
            try:
                o = f.result(timeout=120)
                with lock:
                    outs.append((i % requests, o.asnumpy()))
            except Exception:
                burst_errors += 1
        time.sleep(0.5)               # post-kill load rides the survivor
        owner_after = {t: fd.route(t) for t in tenants}
        rep2 = fd.kill_host(victim)   # idempotent: no double failover
        # let the survivor's agent write one more dump cycle
        time.sleep(max(0.3, 2 * float(
            config.get("MXNET_FABRIC_HEARTBEAT_S"))))
        pane = fd.fleet_collect()
        ledgers = fd.goodput_reconcile(tol=0.01)
    finally:
        stop_flag.set()
        for t in threads:
            t.join()
        fd.stop(drain=True)
        for t in tenants:
            serving.unregister(t)
    resubmits = _metric_total("mxtpu_fabric_resubmits_total") - resub_before
    direct = ref(nd.array(xs)).asnumpy()
    bitwise = bool(outs) and all(
        onp.array_equal(o, direct[k]) for k, o in outs)
    # bounded rebalance: exactly the victim's tenants moved, to survivors
    bounded = all(
        (owner_after[t] == owner_before[t]) if owner_before[t] != victim
        else owner_after[t] != victim for t in tenants)
    moved_ok = rep["moved"] == len(by_host[victim])
    idempotent = bool(rep2.get("already_down")) and rep2["moved"] == 0
    pane_hosts = [s for s in pane["sources"] if s.startswith("host-")]
    pane_ok = {f"host-{n}" for n in fd.hosts()} <= set(pane["sources"])
    ledgers_ok = (set(ledgers) == set(fd.hosts())
                  and all(v["ok"] for v in ledgers.values()))
    ok = (agents_seen and not client_errors and burst_errors == 0 and
          bitwise and bounded and moved_ok and idempotent and
          resubmits >= 1 and rep["survivors"] == [survivor] and
          pane_ok and ledgers_ok)
    return {"phase": "host_down", "seed": seed, "hosts": fd.hosts(),
            "victim": victim, "survivor": survivor,
            "tenants_on_victim": len(by_host[victim]),
            "tenants_moved": rep["moved"], "rebalance_bounded": bounded,
            "resubmits": resubmits, "requests_served": len(outs),
            "client_errors": client_errors[:5] + (
                [f"burst_errors={burst_errors}"] if burst_errors else []),
            "outputs_bitwise_equal": bitwise,
            "kill_idempotent": idempotent, "agents_seen": agents_seen,
            "fleet_pane_sources": pane_hosts,
            "goodput_ledgers": ledgers, "ok": bool(ok)}


def check_retry_storm(seed, requests=20, in_dim=8, out_dim=4):
    """SCENARIO retry_storm (r18): the same high-probability retryable
    ``net_drop`` storm is replayed twice through a single-host FrontDoor.
    With the frontdoor retry budget nearly dry the storm must convert into
    bounded, classified shed: retry amplification (fault-site attempts per
    client request) stays under 2x, some requests still serve, every shed
    error carries the honest UNAVAILABLE marker, and the latched
    ``retry_budget_exhausted`` flight trigger fires. With an effectively
    unbounded budget the identical storm is fully absorbed — zero client
    errors — at >=2x amplification: the gap between the two runs IS the
    defense. Served outputs stay bitwise-equal to the direct forward."""
    import mxnet_tpu as mx
    from mxnet_tpu import config, nd, serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.serving.fabric import FrontDoor
    from mxnet_tpu.serving.tailguard import RETRY_BUDGETS

    tenant = f"chaos_storm_{seed}"

    def mlp():
        mx.random.seed(seed)
        onp.random.seed(seed)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
        net.initialize(mx.init.Xavier())
        net(nd.array(onp.zeros((2, in_dim), "float32")))
        return net

    ref = mlp()
    weights = [prm.data().asnumpy() for prm in ref.collect_params().values()]

    def factory(name):
        net = mlp()
        for prm, w in zip(net.collect_params().values(), weights):
            prm.set_data(nd.array(w))
        srv = serving.InferenceServer(batch_timeout_ms=1.0,
                                      max_queue=max(256, requests * 8))
        srv.register(serving.ModelEndpoint(
            tenant, net, input_shapes=(in_dim,), max_batch_size=4))
        srv.start()
        return srv

    xs = onp.random.RandomState(seed + 1).randn(
        requests, in_dim).astype("float32")
    # the storm submits one request at a time, so every row is served
    # alone in bucket 1: the direct forward runs at batch 1 too (at
    # another batch size the same program rounds one ulp apart)
    direct = onp.concatenate([ref(nd.array(xs[i:i + 1])).asnumpy()
                              for i in range(requests)])
    knobs = ("MXNET_RETRY_BUDGET_RATIO", "MXNET_RETRY_BUDGET_MIN",
             "MXNET_RETRY_BUDGET_CAP")
    saved = {k: config.get(k) for k in knobs}

    def storm(tag, ratio, floor, cap):
        """One storm pass over a fresh front door + fresh retry buckets."""
        config.set("MXNET_RETRY_BUDGET_RATIO", ratio)
        config.set("MXNET_RETRY_BUDGET_MIN", floor)
        config.set("MXNET_RETRY_BUDGET_CAP", cap)
        RETRY_BUDGETS.reset()          # fresh bucket picks up the knobs
        ex_before = _metric_total("mxtpu_retry_budget_exhausted_total")
        fd = FrontDoor([f"{tag}_{seed}"], factory, spawn_agents=False,
                       supervise=False)
        served, errors = [], []
        try:
            with faults.inject("net_drop", site="frontdoor", p=0.75,
                               seed=seed) as inj:
                for i in range(requests):
                    try:
                        o = fd.submit(tenant, xs[i]).result(timeout=60)
                        served.append((i, o.asnumpy()))
                    except Exception as e:
                        errors.append(repr(e))
                attempts = inj.calls
        finally:
            fd.stop(drain=True)
            serving.unregister(tenant)
        return {"attempts": attempts, "served": len(served),
                "errors": errors,
                "exhausted": _metric_total(
                    "mxtpu_retry_budget_exhausted_total") - ex_before,
                "amplification": attempts / float(requests),
                "bitwise": all(onp.array_equal(o, direct[i])
                               for i, o in served)}

    try:
        # budgeted: a nearly-dry bucket (5 tokens, negligible income) must
        # convert the storm into bounded shed instead of absorbing it
        budgeted = storm("bud", 0.001, 5.0, 5.0)
        # unbounded: a bucket the storm cannot drain absorbs every drop
        unbounded = storm("unb", 0.1, 1e6, 1e6)
    finally:
        for k, v in saved.items():
            config.set(k, v)
        RETRY_BUDGETS.reset()
    amp_on = budgeted["amplification"]
    amp_off = unbounded["amplification"]
    shed_classified = all("UNAVAILABLE" in e for e in budgeted["errors"])
    ok = (amp_on < 2.0 and amp_off >= 2.0 and
          budgeted["exhausted"] >= 1 and budgeted["served"] > 0 and
          budgeted["errors"] and shed_classified and
          unbounded["served"] == requests and not unbounded["errors"] and
          budgeted["bitwise"] and unbounded["bitwise"])
    return {"phase": "retry_storm", "seed": seed, "requests": requests,
            "amplification_budgeted": round(amp_on, 3),
            "amplification_unbounded": round(amp_off, 3),
            "served_budgeted": budgeted["served"],
            "shed_budgeted": len(budgeted["errors"]),
            "shed_classified": bool(shed_classified),
            "budget_exhaustions": budgeted["exhausted"],
            "client_errors_unbounded": unbounded["errors"][:5],
            "outputs_bitwise_equal": bool(budgeted["bitwise"]
                                          and unbounded["bitwise"]),
            "ok": bool(ok)}


def check_straggler(seed, requests=24, in_dim=8, out_dim=4):
    """SCENARIO straggler (r18): the very first device dispatch of the
    burst stalls 0.4 s (``replica_straggler`` at the step boundary),
    wedging one replica of a two-replica ServingPool with its share of the
    deadline-carrying burst stuck behind it — the canonical straggling
    replica. The hedging policy must cut the tail:
    duplicates launch onto the other replica after the adaptive delay, at
    least one hedge wins, every request lands inside its deadline (zero
    client errors), outputs stay bitwise-equal to the unhedged fault-free
    oracle AND the direct forward, speculation stays inside the token
    bucket (hedges launched <= seed + ratio * submits) and the dry bucket
    latches the ``hedge_budget_exhausted`` flight trigger."""
    import mxnet_tpu as mx
    from mxnet_tpu import config, nd, serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.serving import tailguard

    svc = f"chaos_strag_{seed}"
    ratio = 0.2

    def mlp():
        mx.random.seed(seed)
        onp.random.seed(seed)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
        net.initialize(mx.init.Xavier())
        net(nd.array(onp.zeros((2, in_dim), "float32")))
        return net

    nets = {}

    def factory(rid):
        net = mlp()                   # same seed: replicas serve bitwise-
        nets[rid] = net               # identical outputs, so hedging is safe
        srv = serving.InferenceServer(batch_timeout_ms=1.0,
                                      max_queue=max(256, requests * 8))
        srv.register(serving.ModelEndpoint(
            svc, net, input_shapes=(in_dim,), max_batch_size=4))
        return srv

    xs = onp.random.RandomState(seed + 1).randn(
        requests, in_dim).astype("float32")
    knobs = ("MXNET_HEDGE_ENABLE", "MXNET_HEDGE_DELAY_MIN_MS",
             "MXNET_HEDGE_BUDGET_RATIO")
    saved = {k: config.get(k) for k in knobs}
    pool = serving.ServingPool(factory, initial_replicas=2)
    client_errors = []
    try:
        # oracle: hedging off, fault-free — the bitwise bar for the chaos run
        config.set("MXNET_HEDGE_ENABLE", False)
        oracle = [pool.predict(svc, xs[i], timeout=60).asnumpy()
                  for i in range(requests)]
        # chaos: hedge quickly (25 ms floor) under a deliberately tight
        # budget so the bucket runs dry mid-burst
        config.set("MXNET_HEDGE_ENABLE", True)
        config.set("MXNET_HEDGE_DELAY_MIN_MS", 25.0)
        config.set("MXNET_HEDGE_BUDGET_RATIO", ratio)
        tailguard.hedge_reset()
        before = {m: _metric_total(m) for m in
                  ("mxtpu_hedge_requests_total", "mxtpu_hedge_wins_total",
                   "mxtpu_hedge_cancelled_total", "mxtpu_hedge_wasted_total",
                   "mxtpu_hedge_budget_exhausted_total")}
        outs = [None] * requests
        with faults.inject("replica_straggler", site="serving_dispatch",
                           at=(1,), seconds=0.4) as inj:
            futs = [pool.submit(svc, xs[i], deadline_ms=30000.0)
                    for i in range(requests)]
            for i, f in enumerate(futs):
                try:
                    outs[i] = f.result(timeout=120).asnumpy()
                except Exception as e:
                    client_errors.append(repr(e))
        delta = {m: _metric_total(m) - before[m] for m in before}
    finally:
        for k, v in saved.items():
            config.set(k, v)
        tailguard.hedge_reset()
        pool.stop(drain=True)
        serving.unregister(svc)
    direct = nets[0](nd.array(xs)).asnumpy()
    oracle_ok = all(onp.array_equal(o, direct[i])
                    for i, o in enumerate(oracle))
    bitwise = all(o is not None and onp.array_equal(o, oracle[i])
                  for i, o in enumerate(outs))
    hedges = delta["mxtpu_hedge_requests_total"]
    wins = delta["mxtpu_hedge_wins_total"]
    wasted = delta["mxtpu_hedge_wasted_total"]
    exhausted = delta["mxtpu_hedge_budget_exhausted_total"]
    budget_cap = 1.0 + ratio * requests       # seed token + per-submit income
    ok = (not client_errors and oracle_ok and bitwise and inj.fires >= 1 and
          hedges >= 1 and wins >= 1 and exhausted >= 1 and
          hedges <= budget_cap + 1e-9 and wasted <= hedges)
    return {"phase": "straggler", "seed": seed, "requests": requests,
            "stalls_fired": inj.fires,
            "hedges_launched": hedges, "hedge_wins": wins,
            "hedges_cancelled": delta["mxtpu_hedge_cancelled_total"],
            "hedges_wasted": wasted, "budget_exhaustions": exhausted,
            "hedge_rate": round(hedges / float(requests), 3),
            "hedge_budget_cap": budget_cap,
            "client_errors": client_errors[:5],
            "outputs_bitwise_equal": bool(oracle_ok and bitwise),
            "ok": bool(ok)}


def check_partition(seed, requests=20, in_dim=8, out_dim=4):
    """SCENARIO partition (r18): a two-host FrontDoor serves gold, silver
    and bulk tenants while (a) a bounded ``net_drop`` partition fires at the
    front door — the frontdoor retry budget must absorb every drop with
    zero client errors on ANY tier — and (b) a synthetic SLO burn walks the
    brownout ladder deterministically: level 1 softens (timeout boost, no
    shed), level 2 sheds bulk at admission (ServerOverloadError) while
    silver and gold keep serving, recovery returns to level 0 and bulk
    serves again. Gold sees zero client errors across the whole drill, every
    transition leaves exactly one ``brownout_shift`` flight bundle, and the
    fleet pane survives: the collector names both host agents and every
    host's goodput ledger reconciles buckets-to-wall within 1%."""
    import time
    import mxnet_tpu as mx
    from mxnet_tpu import config, nd, serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.serving.errors import ServerOverloadError
    from mxnet_tpu.serving.fabric import FrontDoor
    from mxnet_tpu.serving.tailguard import BROWNOUT, RETRY_BUDGETS
    from mxnet_tpu.telemetry import flight

    tiers = {f"chaos_part_gold_{seed}": "gold",
             f"chaos_part_silver_{seed}": "silver",
             f"chaos_part_bulk_{seed}": "bulk"}
    gold, silver, bulk = list(tiers)

    def mlp():
        mx.random.seed(seed)
        onp.random.seed(seed)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
        net.initialize(mx.init.Xavier())
        net(nd.array(onp.zeros((2, in_dim), "float32")))
        return net

    ref = mlp()
    weights = [prm.data().asnumpy() for prm in ref.collect_params().values()]

    def factory(name):
        net = mlp()
        for prm, w in zip(net.collect_params().values(), weights):
            prm.set_data(nd.array(w))
        srv = serving.InferenceServer(batch_timeout_ms=1.0,
                                      max_queue=max(256, requests * 8))
        for i, (t, tier) in enumerate(tiers.items()):
            srv.register(serving.ModelEndpoint(
                t, net, input_shapes=(in_dim,), max_batch_size=4),
                warmup=(i == 0), tier=tier)
        srv.start()
        return srv

    class _BurnStub:
        burn_threshold = 14.0

        def __init__(self):
            self.burning = False

        def check_all(self):
            burn = 20.0 if self.burning else 0.0
            return [{"endpoint": gold, "fast_burn": burn, "slow_burn": burn,
                     "alert_active": self.burning}]

    workdir = os.environ.get("CHAOS_FLEET_DIR") or tempfile.mkdtemp(
        prefix="chaos-partition-")
    xs = onp.random.RandomState(seed + 1).randn(
        requests, in_dim).astype("float32")
    direct = ref(nd.array(xs)).asnumpy()
    errors = {t: [] for t in tiers}
    outs = []

    def send(tenant, i):
        try:
            outs.append((i, fd.submit(tenant, xs[i % requests],
                                      deadline_ms=30000.0).result(timeout=60)
                         .asnumpy()))
            return None
        except Exception as e:
            errors[tenant].append(repr(e))
            return e

    RETRY_BUDGETS.reset()
    mon = _BurnStub()
    trans_before = _metric_total("mxtpu_brownout_transitions_total")
    shed_before = _metric_total("mxtpu_brownout_shed_total")
    fd = FrontDoor(["alpha", "beta"], factory, workdir=workdir)
    agents_seen = False
    level_path = []
    shed_at_2 = {"bulk": None, "silver": None, "gold": None}
    try:
        # both agents must boot + dump before the drill (post-mortem pane)
        boot_deadline = time.time() + 60
        while time.time() < boot_deadline:
            if all(os.path.exists(os.path.join(
                    workdir, f"dump-host-{n}.json")) for n in fd.hosts()):
                agents_seen = True
                break
            time.sleep(0.1)
        # (a) bounded partition: every drop absorbed by the frontdoor
        # retry budget (12 drops << the 50-token floor) — zero errors
        with faults.inject("net_drop", site="frontdoor", p=0.6, times=12,
                           seed=seed) as inj:
            for i in range(requests):
                send([gold, silver, bulk][i % 3], i)
        drops = inj.fires
        # (b) the brownout ladder, driven deterministically
        BROWNOUT.set_monitor(mon)
        BROWNOUT.reset()
        mon.burning = True
        tick = 0
        for _ in range(2):            # -> level 1: soften, nobody refused
            flight.RECORDER.reset_rate_limit()
            BROWNOUT.tick(now=float(tick))
            tick += 1
        level_path.append(BROWNOUT.level)
        soften_ok = (BROWNOUT.level == 1 and BROWNOUT.timeout_boost() > 1.0
                     and send(bulk, 1) is None)
        for _ in range(2):            # -> level 2: shed bulk, serve the rest
            flight.RECORDER.reset_rate_limit()
            BROWNOUT.tick(now=float(tick))
            tick += 1
        level_path.append(BROWNOUT.level)
        shed_at_2["bulk"] = repr(send(bulk, 2))
        shed_at_2["silver"] = send(silver, 3) is None
        shed_at_2["gold"] = send(gold, 4) is None
        shed_ok = (BROWNOUT.level == 2
                   and len(errors[bulk]) == 1
                   and "ServerOverloadError" in errors[bulk][0]
                   and "brownout" in errors[bulk][0]
                   and shed_at_2["silver"] and shed_at_2["gold"])
        mon.burning = False
        for _ in range(6):            # calm: -> 1 -> 0 (down_n=3 each)
            flight.RECORDER.reset_rate_limit()
            BROWNOUT.tick(now=float(tick))
            tick += 1
        level_path.append(BROWNOUT.level)
        recovered_ok = BROWNOUT.level == 0 and send(bulk, 5) is None
        # the post-mortem pane: one more agent dump cycle, then collect
        time.sleep(max(0.3, 2 * float(
            config.get("MXNET_FABRIC_HEARTBEAT_S"))))
        pane = fd.fleet_collect()
        ledgers = fd.goodput_reconcile(tol=0.01)
    finally:
        BROWNOUT.set_monitor(None)
        BROWNOUT.reset()
        RETRY_BUDGETS.reset()
        fd.stop(drain=True)
        for t in tiers:
            serving.unregister(t)
    transitions = _metric_total(
        "mxtpu_brownout_transitions_total") - trans_before
    shed_total = _metric_total("mxtpu_brownout_shed_total") - shed_before
    # one brownout_shift bundle per transition (countable when the flight
    # dir is scoped by the harness wrapper)
    fdir = str(config.get("MXNET_FLIGHT_DIR") or "")
    bundles = None
    if fdir:
        bundles = 0
        for path in flight.list_bundles(fdir):
            try:
                if flight.load_bundle(path)["trigger"]["kind"] == \
                        "brownout_shift":
                    bundles += 1
            except (OSError, ValueError, KeyError):
                pass
    bundles_ok = bundles is None or bundles == transitions
    bitwise = bool(outs) and all(
        onp.array_equal(o, direct[i % requests]) for i, o in outs)
    pane_ok = {f"host-{n}" for n in fd.hosts()} <= set(pane["sources"])
    ledgers_ok = (set(ledgers) == set(fd.hosts())
                  and all(v["ok"] for v in ledgers.values()))
    ok = (agents_seen and drops >= 1 and not errors[gold]
          and not errors[silver] and len(errors[bulk]) == 1
          and soften_ok and shed_ok and recovered_ok
          and transitions == 4 and shed_total >= 1 and bundles_ok
          and bitwise and pane_ok and ledgers_ok)
    return {"phase": "partition", "seed": seed, "requests": requests,
            "drops_absorbed": drops, "level_path": level_path,
            "transitions": transitions, "brownout_bundles": bundles,
            "shed_counter": shed_total,
            "gold_errors": errors[gold][:5],
            "silver_errors": errors[silver][:5],
            "bulk_shed_error": (errors[bulk] or [None])[0],
            "requests_served": len(outs),
            "outputs_bitwise_equal": bitwise,
            "agents_seen": agents_seen,
            "fleet_pane_sources": [s for s in pane["sources"]
                                   if s.startswith("host-")],
            "goodput_ledgers": ledgers, "ok": bool(ok)}


SCENARIOS = {"preempt": check_preempt, "worker_kill": check_worker_kill,
             "hot_swap": check_hot_swap, "nan_grad": check_nan_grad,
             "bad_batch": check_bad_batch, "sdc": check_sdc,
             "decode": check_decode, "cache_poison": check_cache_poison,
             "autoscale": check_autoscale, "dlrm": check_dlrm,
             "host_down": check_host_down, "retry_storm": check_retry_storm,
             "straggler": check_straggler, "partition": check_partition}

# the flight-recorder trigger each injected fault must leave behind (a clean
# hot_swap is a structured event, not a dump trigger, so it has no entry)
EXPECTED_FLIGHT_TRIGGER = {
    "preempt": "preemption",
    "worker_kill": "failover",
    "nan_grad": "numerics_anomaly",
    "bad_batch": "numerics_anomaly",
    "sdc": "sdc_suspect",
    "decode": "decode_failover",
    "dlrm": "oom",   # retry's OOM classifier fires on the RESOURCE_EXHAUSTED
    "host_down": "host_down",
    "retry_storm": "retry_budget_exhausted",
    "straggler": "hedge_budget_exhausted",
    "partition": "brownout_shift",
}


def check_flight_bundle(name, fn):
    """Run one scenario with a private MXNET_FLIGHT_DIR and assert the
    injected fault left at least one parseable flight bundle whose trigger
    kind matches the fault — the black box must capture every drill."""
    from mxnet_tpu import config
    from mxnet_tpu.telemetry import flight

    expected = EXPECTED_FLIGHT_TRIGGER.get(name)
    if expected is None:
        return fn()
    fdir = tempfile.mkdtemp(prefix=f"chaos-flight-{name}-")
    flight.RECORDER.reset_rate_limit()   # prior scenarios must not suppress
    config.set("MXNET_FLIGHT_DIR", fdir)
    try:
        res = fn()
    finally:
        config.set("MXNET_FLIGHT_DIR", "")
    triggers = []
    parse_ok = True
    for path in flight.list_bundles(fdir):
        try:
            triggers.append(flight.load_bundle(path)["trigger"]["kind"])
        except (OSError, ValueError, KeyError):
            parse_ok = False
    flight_ok = parse_ok and expected in triggers
    res["flight_dir"] = fdir
    res["flight_expected"] = expected
    res["flight_triggers"] = triggers
    res["flight_ok"] = bool(flight_ok)
    res["ok"] = bool(res["ok"] and flight_ok)
    return res


def check_fleet_report(name, fn):
    """Run one scenario with a private span-spool + snapshot-dump dir and
    assert the fleet plane captured the drill: ``tools/fleet_report.py``
    over the dumps must build a machine-parseable report, and the journey
    of the scenario's trace id must name at least two distinct
    processes/replicas — the traced request really crossed a process or
    replica boundary. The env knobs (not config overrides) carry the trace:
    subprocesses the scenario spawns inherit them at fork."""
    import glob as _glob
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import goodput
    from mxnet_tpu.telemetry import tracing as _tracing

    fdir = tempfile.mkdtemp(prefix=f"chaos-fleet-{name}-")
    spool = os.path.join(fdir, "spool")
    trace_id = telemetry.new_trace_id()
    saved = {k: os.environ.get(k) for k in
             ("MXNET_SPAN_SPOOL_DIR", "MXNET_TRACE_ID", "CHAOS_FLEET_DIR")}
    os.environ["MXNET_SPAN_SPOOL_DIR"] = spool
    os.environ["MXNET_TRACE_ID"] = trace_id
    os.environ["CHAOS_FLEET_DIR"] = fdir
    _tracing._reset_spool_for_tests()   # re-resolve the inherited trace id
    try:
        res = fn()
    finally:
        telemetry.spool_flush()
        goodput.account()
        telemetry.dump(os.path.join(fdir, f"dump-parent-{os.getpid()}.json"))
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _tracing._reset_spool_for_tests()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import fleet_report
    finally:
        sys.path.pop(0)
    procs = []
    parse_ok = False
    try:
        report = fleet_report.build_report(
            sorted(_glob.glob(os.path.join(fdir, "dump-*.json"))),
            spool_dir=spool, trace=trace_id)
        json.dumps(report)          # parseable end-to-end, no repr leakage
        procs = report["journey"]["processes"]
        parse_ok = True
    except Exception as e:
        res["fleet_error"] = repr(e)
    pids = [x for x in procs if x.startswith("pid=")]
    reps = [x for x in procs if x.startswith("replica=")]
    fleet_ok = parse_ok and (len(pids) >= 2 or len(reps) >= 2)
    res["fleet_dir"] = fdir
    res["fleet_trace"] = trace_id
    res["fleet_journey_processes"] = procs
    res["fleet_ok"] = bool(fleet_ok)
    res["ok"] = bool(res["ok"] and fleet_ok)
    return res


def run_chaos(seed=0, steps=20, requests=40, p=0.3, ckpt_dir=None,
              scenarios=None, out=sys.stdout):
    """Legacy train+serving sweep (scenarios=None), or the elastic scenario
    matrix (scenarios=['preempt', ...])."""
    if scenarios:
        results = {}
        ok = True
        for name in scenarios:
            if name == "preempt":
                res = check_flight_bundle(name, lambda: check_preempt(
                    seed, steps=max(4, steps // 2), ckpt_dir=ckpt_dir))
            elif name == "worker_kill":
                res = check_flight_bundle(name, lambda: check_worker_kill(
                    seed, requests=requests))
            elif name == "hot_swap":
                res = check_hot_swap(seed, requests=requests)
            elif name == "nan_grad":
                res = check_flight_bundle(name, lambda: check_nan_grad(
                    seed, steps=max(10, steps)))
            elif name == "bad_batch":
                res = check_flight_bundle(name, lambda: check_bad_batch(
                    seed, steps=max(10, steps)))
            elif name == "sdc":
                res = check_flight_bundle(name, lambda: check_sdc(
                    seed, steps=max(10, steps)))
            elif name == "decode":
                res = check_flight_bundle(name, lambda: check_decode(
                    seed, requests=max(4, requests // 8)))
            elif name == "dlrm":
                res = check_flight_bundle(name, lambda: check_dlrm(
                    seed, steps=max(4, steps // 2)))
            elif name == "cache_poison":
                res = check_fleet_report(name, lambda: check_cache_poison(
                    seed, requests=max(8, requests // 2)))
            elif name == "autoscale":
                res = check_fleet_report(name, lambda: check_autoscale(
                    seed, requests=max(8, requests // 2)))
            elif name == "host_down":
                res = check_fleet_report(name, lambda: check_flight_bundle(
                    name, lambda: check_host_down(
                        seed, requests=max(8, requests // 2))))
            elif name == "retry_storm":
                res = check_flight_bundle(name, lambda: check_retry_storm(
                    seed, requests=max(8, requests // 2)))
            elif name == "straggler":
                res = check_flight_bundle(name, lambda: check_straggler(
                    seed, requests=max(8, requests // 2)))
            elif name == "partition":
                res = check_fleet_report(name, lambda: check_flight_bundle(
                    name, lambda: check_partition(
                        seed, requests=max(9, requests // 2))))
            else:
                raise SystemExit(f"unknown scenario {name!r}; known: "
                                 f"{sorted(SCENARIOS)}")
            print(json.dumps(res, default=str), file=out)
            results[name] = res
            ok = ok and res["ok"]
        summary = {"phase": "summary", "seed": seed, "ok": bool(ok)}
        print(json.dumps(summary), file=out)
        results["ok"] = bool(ok)
        return results
    train = check_train(seed, steps, p, ckpt_dir=ckpt_dir)
    print(json.dumps(train), file=out)
    serve = check_serving(seed, requests, p)
    print(json.dumps(serve), file=out)
    summary = {"phase": "summary", "seed": seed,
               "ok": bool(train["ok"] and serve["ok"])}
    print(json.dumps(summary), file=out)
    return {"train": train, "serving": serve, "ok": summary["ok"]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int,
                    default=int.from_bytes(os.urandom(2), "little"),
                    help="fault-schedule seed (logged; failing seeds replay)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--p", type=float, default=0.3,
                    help="per-boundary fault probability")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--scenario", action="append", default=None,
                    choices=sorted(SCENARIOS),
                    help="run this elastic-resilience scenario instead of "
                         "the legacy train+serving sweep (repeatable: "
                         "--scenario preempt --scenario hot_swap)")
    args = ap.parse_args(argv)
    result = run_chaos(seed=args.seed, steps=args.steps,
                       requests=args.requests, p=args.p,
                       ckpt_dir=args.ckpt_dir, scenarios=args.scenario)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
