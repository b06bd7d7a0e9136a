"""Closed-loop load generator for mxnet_tpu.serving (ISSUE r6 benchmark).

N closed-loop clients each keep exactly one request in flight against one or
more ModelEndpoints behind the dynamic batcher; at each concurrency level the
harness reports served img/s and request-latency p50/p95/p99 plus the
queue-wait share of the tail — the decomposition that shows whether extra
latency is scheduling (queue wait) or compute (step time). r6 adds
multi-tenant mode (``--tenants N --mix w1,w2,...``): N endpoints share the
device through the Router, traffic splits by the mix weights, and a
per-tenant latency table is emitted so SLO fairness is measurable, plus
``--serial`` to A/B the double-buffered pipeline against the serial
prepare-then-step path.

Two dtypes are exercised per single-tenant run: ResNet bf16 and (optionally)
the ``quantize_net``-produced int8 variant of the same weights — the
public-API int8 path VERDICT r5 asked to make servable.

Env knobs (benchmark/_timing.py conventions; CLI flags override env):

  SLG_MODEL=resnet50_v1   model-zoo name
  SLG_IMG=224             input H=W (smaller for CPU smoke runs)
  SLG_CLASSES=1000
  SLG_DTYPES=bf16,int8    comma list of {f32, bf16, int8}
  SLG_CONC=1,2,4,8,16     concurrency sweep
  SLG_SECONDS=5           measured window per level
  SLG_MAX_BATCH=32        endpoint max batch / largest bucket
  SLG_TIMEOUT_MS=5        batcher deadline
  SLG_CALIB=4             int8 calibration batches
  SLG_TELEMETRY=          when set, write the final telemetry snapshot JSON
                          here (readable live/after via tools/metrics_dump.py;
                          combine with MXNET_TELEMETRY_DUMP_PATH for
                          periodic in-run dumps)

r11 adds the generative phase (``--decode`` / SLG_DECODE=1): closed-loop
autoregressive clients against a DecodeEndpoint + DecodeScheduler (paged KV
cache, token-granularity continuous batching) split across a gold/bulk
tenant pair. Reports decode tok/s/chip, client-observed inter-token
p50/p95/p99 and KV-pool occupancy — the round-16 gate metrics.

  SLG_DECODE=1            run the decode phase after the image sweep
  SLG_DEC_CLIENTS=4       closed-loop decode clients (alternate gold/bulk)
  SLG_DEC_SECONDS=        measured decode window (default SLG_SECONDS)
  SLG_DEC_SEQ=64          max sequence length (prompt + generated)
  SLG_DEC_NEW=16          max new tokens per request (budgets drawn from
                          [SLG_DEC_NEW/2, SLG_DEC_NEW])
  SLG_DTYPES=none         skip the image sweep (decode-only run)

r19 adds the recommendation phase (``--dlrm`` / SLG_DLRM=1): closed-loop
single-example clients against the model-zoo DLRM behind the dynamic
batcher — the huge-QPS / tiny-compute serving profile. Reports served
req/s, embedding lookups/s, the latency/queue-wait decomposition and the
request stream's hot-row hit rate.

  SLG_DLRM=1              run the DLRM phase after the image sweep
  SLG_DLRM_CLIENTS=8      closed-loop DLRM clients
  SLG_DLRM_SECONDS=       measured DLRM window (default SLG_SECONDS)

r17 adds the elasticity benchmark (``--restart``): restart-to-first-request
time, cold (empty executable cache) vs warm (cache populated by the cold
run). The harness spawns one subprocess per phase sharing an executable
cache + compile ledger directory; each child builds the dense endpoint
(and, with the decode phase enabled, the decode engine), starts an
InferenceServer and times from process entry to the first served response.
The parent asserts the warm child performed ZERO fresh compiles (every
ledger record is a cache hit, the recompile-storm duplicate counter stays
0) and that first-request outputs are bitwise-identical across phases,
then emits the gate row ``{"restart_to_first_request_s": <warm>, ...}``.

r18 extends the restart benchmark to the serving fabric (``--fabric``):
each restart child additionally builds a mesh-sharded endpoint
(``serving.fabric.ShardedEndpoint`` on a 2-device slice) and serves one
request through it. The sharded compile trigger key carries the mesh
shape, so the warm child's zero-fresh-compiles assertion now also proves
a restarted sharded replica with the same slice shape deserializes every
bucket executable from the cache — and the sharded first-request digest
must match bitwise across phases.

r18 adds the tail-tolerance phases (``--hedge`` / ``--storm``) and
end-to-end deadlines (``--deadline-ms``). With a deadline every sweep
request carries the budget into the serving stack and the per-level row
grows a ``deadline_misses`` count (requests failed fast with
DeadlineExceeded instead of served late). ``--hedge`` runs a two-replica
ServingPool burst under an injected ``replica_straggler`` stall and emits
the hedging account the perf gate consumes: hedge rate, win rate, the
wasted-duplicate-work share (``hedge_wasted_work_pct`` — bounded by the
hedge token bucket, so the ceiling is enforced by construction) and budget
exhaustions. ``--storm`` replays a bounded retryable ``net_drop`` storm
through a single-host FrontDoor: the frontdoor retry budget must absorb
every drop, and the row carries ``storm_amplification`` (fault-site
attempts per request) and ``storm_client_error_rate`` (the ==0 gate row).

  SLG_DEADLINE_MS=0       end-to-end deadline per request (0 = none)
  SLG_HEDGE=1             run the hedged-burst phase
  SLG_STORM=1             run the retry-storm phase
  SLG_TAIL_REQUESTS=60    burst size for the hedge/storm phases

CLI:
  --tenants N       register N endpoints of the model (t0..tN-1) on ONE
                    server and emit a per-tenant latency table per level
  --mix w0,w1,...   client-traffic weights per tenant (default uniform)
  --slo-ms a,b,...  per-tenant scheduling SLO passed to register()
  --serial          pipeline=False (the pre-r6 prepare-then-step path)
  --restart         run the cold/warm restart benchmark instead of the
                    load sweep (uses the SLG_* model/size knobs)
  --fabric          with --restart: also run a mesh-sharded endpoint
                    (2-device slice) through both phases
  --conc / --seconds / --img / --max-batch / --timeout-ms / --dtypes
                    override the corresponding SLG_* env knobs

Prints one JSON line per (dtype, concurrency[, tenant]):
  {"dtype":..., "conc":..., "img_s":..., "p50_ms":..., "p99_ms":...,
   "queue_wait_p99_ms":..., "queue_wait_share_p99":..., "occupancy":...,
   "compiles":..., "batches":...}
and a final per-dtype summary line with the direct (unserved) single-batch
forward rate for reference.

Placement: every net, endpoint and pool is built under
``mxnet_tpu.runtime.measurement_context()`` — ``mx.tpu(0)`` when JAX's default
backend is the TPU, ``mx.cpu(0)`` only when the run was started with
``JAX_PLATFORMS=cpu``, an error otherwise — and every row carries
``platform`` / ``device_kind`` / ``device_count``. The ``--restart`` parent
never initialises a JAX backend: a chip belongs to one process, and its two
children need it in turn.
"""
import argparse
import json
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def _emit(row):
    """Print one result row; every row names the device it was taken on."""
    from mxnet_tpu import runtime
    print(json.dumps({**row, **runtime.device_row()}), flush=True)


def _build_net(name, classes, img, dtype):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.get_model(name, classes=classes)
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(onp.zeros((1, 3, img, img), "float32")))
    if dtype == "bf16":
        net.cast("bfloat16")
        net(mx.nd.array(onp.zeros((1, 3, img, img), "float32"))
            .astype("bfloat16"))
    elif dtype == "int8":
        from mxnet_tpu.contrib.quantization import quantize_net
        rng = onp.random.default_rng(7)
        calib_n = int(os.environ.get("SLG_CALIB", 4))
        calib = [mx.nd.array(rng.random((4, 3, img, img), dtype="float32"))
                 for _ in range(calib_n)]
        net = quantize_net(net, calib_data=calib, calib_mode="naive")
    return net


def _direct_rate(net, img, in_dtype, batch, reps=3):
    """Reference: direct full-batch forward img/s (no serving layer),
    chain-amortized per benchmark/_timing.py."""
    import mxnet_tpu as mx
    from benchmark._timing import time_chained

    x = mx.nd.array(onp.random.default_rng(0).random(
        (batch, 3, img, img), dtype="float32"))
    if in_dtype == "bfloat16":
        x = x.astype("bfloat16")
    net.hybridize()
    sec = time_chained(lambda a: net(a), (x,), reps=reps, chain=10)
    return batch / sec


def _queue_wait_fields(snap):
    """Queue-wait decomposition of the latency tail, from a stats snapshot."""
    qw_p99 = snap["queue_wait"]["p99_us"]
    lat_p99 = snap["latency"]["p99_us"]
    return {
        "queue_wait_p99_ms": round(qw_p99 / 1e3, 2),
        "queue_wait_share_p99": round(qw_p99 / lat_p99, 3) if lat_p99 else 0.0,
    }


def _percentiles(lat_ms):
    lat_ms = sorted(lat_ms)
    n = len(lat_ms)
    if not n:
        return {"p50_ms": None, "p95_ms": None, "p99_ms": None}
    return {
        "p50_ms": round(lat_ms[n // 2], 2),
        "p95_ms": round(lat_ms[min(n - 1, int(n * 0.95))], 2),
        "p99_ms": round(lat_ms[min(n - 1, int(n * 0.99))], 2),
    }


def _metric_total(name):
    """Sum a metric family across its label series (0.0 if unregistered)."""
    from mxnet_tpu import telemetry
    fam = telemetry.REGISTRY.get(name)
    if fam is None:
        return 0.0
    return float(sum(c.value for _, c in fam._series()))


def _run_level(server, names, img, np_dtype, conc, seconds, weights,
               deadline_ms=None):
    """Closed loop: ``conc`` clients, one in-flight request each, assigned
    to tenants proportionally to ``weights``. Returns (aggregate, per_tenant)
    where per_tenant maps name -> {latencies, served}. ``deadline_ms`` rides
    each request end-to-end; a DeadlineExceeded is counted as a miss, not a
    served request."""
    from mxnet_tpu.serving import DeadlineExceeded

    stop_at = time.perf_counter() + seconds
    lock = threading.Lock()
    per = {n: {"lat_ms": [], "served": 0, "misses": 0} for n in names}
    rng = onp.random.default_rng(42)
    frames = [rng.random((3, img, img), dtype="float32").astype(np_dtype)
              for _ in range(8)]
    # proportional client->tenant assignment (every tenant gets >= 1 client
    # when conc >= len(names))
    total_w = sum(weights)
    assign = []
    for ci in range(conc):
        acc = 0.0
        pick = names[-1]
        for name, w in zip(names, weights):
            acc += w / total_w
            if (ci + 0.5) / conc <= acc:
                pick = name
                break
        assign.append(pick)

    def client(ci):
        name = assign[ci]
        i = 0
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            try:
                server.predict(name, frames[(ci + i) % len(frames)],
                               deadline_ms=deadline_ms, timeout=120)
            except DeadlineExceeded:
                with lock:
                    per[name]["misses"] += 1
                i += 1
                continue
            dt = (time.perf_counter() - t0) * 1e3
            with lock:
                per[name]["lat_ms"].append(dt)
                per[name]["served"] += 1
            i += 1

    t_start = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,)) for c in range(conc)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    all_lat = [d for v in per.values() for d in v["lat_ms"]]
    agg = {"img_s": round(sum(v["served"] for v in per.values()) / wall, 1),
           "requests": len(all_lat)}
    if deadline_ms is not None:
        agg["deadline_ms"] = deadline_ms
        agg["deadline_misses"] = sum(v["misses"] for v in per.values())
    agg.update(_percentiles(all_lat))
    return agg, per


def _run_decode(args):
    """Generative phase: a small TransformerLM behind the paged-KV decode
    path under multi-tenant closed-loop load. One aggregate JSON row
    (``"decode": true``) plus one per-tenant row; the aggregate carries the
    round-16 gate metrics ``tok_s_chip`` and ``intertoken_p99_ms``."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo.bert import TransformerLM

    conc, seconds = args.dec_clients, args.dec_seconds
    seq_len, max_new = args.dec_seq, args.dec_new
    onp.random.seed(0)
    lm = TransformerLM(num_layers=2, units=32, hidden_size=64, num_heads=2,
                       vocab_size=64, max_length=seq_len)
    lm.initialize(mx.init.Normal(0.5))
    eng = serving.DecodeEndpoint("loadgen_lm", lm, max_seq_len=seq_len,
                                 max_batch_size=max(2, conc))
    eng.warmup()
    compiles_warm = eng.stats.snapshot()["counters"]["compiles"]
    sched = serving.DecodeScheduler(eng, poll_s=0.002) \
        .add_tenant("gold", slo_ms=20.0).add_tenant("bulk", slo_ms=200.0)
    sched.start()

    lock = threading.Lock()
    per = {t: {"gaps_ms": [], "tokens": 0, "seqs": 0}
           for t in ("gold", "bulk")}
    stop_at = time.perf_counter() + seconds

    def client(ci):
        tenant = "gold" if ci % 2 == 0 else "bulk"
        rng = onp.random.default_rng(100 + ci)
        while time.perf_counter() < stop_at:
            plen = int(rng.integers(2, max(3, seq_len // 4)))
            prompt = [int(t) for t in rng.integers(1, 64, size=plen)]
            budget = int(rng.integers(max(1, max_new // 2), max_new + 1))
            stream = sched.submit(prompt, max_new_tokens=budget,
                                  tenant=tenant)
            gaps, n, t_prev = [], 0, None
            for _ in stream:             # client-observed inter-token gaps
                now = time.perf_counter()
                if t_prev is not None:
                    gaps.append((now - t_prev) * 1e3)
                t_prev = now
                n += 1
            with lock:
                per[tenant]["gaps_ms"].extend(gaps)
                per[tenant]["tokens"] += n
                per[tenant]["seqs"] += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(conc)]
    for t in threads:
        t.start()
    occ_peak = occ_sum = 0.0
    occ_n = 0
    while any(t.is_alive() for t in threads):
        o = eng.pool.occupancy()
        occ_peak, occ_sum, occ_n = max(occ_peak, o), occ_sum + o, occ_n + 1
        time.sleep(0.02)
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    sched.stop(drain=True)

    chips = max(1, jax.device_count())
    tokens = sum(v["tokens"] for v in per.values())
    all_gaps = [g for v in per.values() for g in v["gaps_ms"]]
    snap = eng.stats.snapshot()
    assert snap["counters"]["compiles"] == compiles_warm, \
        "decode traffic recompiled beyond warmup buckets"
    row = {"decode": True, "clients": conc, "tenants": 2, "chips": chips,
           "seconds": round(wall, 2),
           "seqs": sum(v["seqs"] for v in per.values()), "tokens": tokens,
           "tok_s_chip": round(tokens / wall / chips, 1)}
    row.update({f"intertoken_{k}": v
                for k, v in _percentiles(all_gaps).items()})
    row.update({
        "kv_occupancy_peak": round(occ_peak, 3),
        "kv_occupancy_mean": round(occ_sum / max(1, occ_n), 3),
        "kv_pages": eng.pool.num_pages - 1,
        "prefill_p50_ms": round(snap["prefill"]["p50_us"] / 1e3, 2),
        "step_p50_ms": round(snap["step"]["p50_us"] / 1e3, 2),
        "compiles": compiles_warm,
    })
    _emit(row)
    for tenant in ("gold", "bulk"):     # the per-tenant inter-token table
        trow = {"decode": True, "tenant": tenant,
                "seqs": per[tenant]["seqs"],
                "tokens": per[tenant]["tokens"]}
        trow.update({f"intertoken_{k}": v
                     for k, v in _percentiles(per[tenant]["gaps_ms"]).items()})
        _emit(trow)


def _run_dlrm(args):
    """Recommendation phase: the model-zoo DLRM behind the dynamic batcher —
    the huge-QPS / tiny-compute profile (all embedding-memory traffic,
    almost no FLOPs) that stresses admission/batching from the opposite end
    of the spectrum from decode. Multi-input endpoint: (dense float32,
    sparse int32 ids) per request. One aggregate JSON row (``"dlrm": true``)
    carrying served req/s, embedding lookups/s (req/s x fields), the
    latency/queue-wait decomposition, and the observed hot-row hit rate of
    the request stream."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.embedding import HotnessTracker
    from mxnet_tpu.gluon.model_zoo import dlrm as dlrm_zoo

    conc, seconds = args.dlrm_clients, args.dlrm_seconds
    vocab, fields, dense_in = 1 << 14, 8, 13
    onp.random.seed(0)
    net = dlrm_zoo.dlrm_tiny(vocab_size=vocab, num_fields=fields,
                             dense_in=dense_in)
    net.initialize(mx.init.Normal(0.1))
    server = serving.InferenceServer(batch_timeout_ms=args.timeout_ms,
                                     max_queue=args.max_batch * 8)
    ep = serving.ModelEndpoint(
        "loadgen_dlrm", net, input_shapes=((dense_in,), (fields,)),
        dtype=("float32", "int32"), max_batch_size=args.max_batch)
    server.register(ep)
    compiles_warm = ep.stats.counters["compiles"]
    server.start()

    # skewed request stream (frequency-sorted vocab head), pre-generated
    rng = onp.random.default_rng(7)
    n_frames = 64
    head = max(1, vocab // 16)
    hot = rng.integers(0, head, (n_frames, fields))
    cold = rng.integers(0, vocab, (n_frames, fields))
    pick = rng.random((n_frames, fields)) < 0.7
    idx_frames = onp.where(pick, hot, cold).astype("int32")
    dense_frames = rng.standard_normal(
        (n_frames, dense_in)).astype("float32")
    tracker = HotnessTracker("loadgen_dlrm", vocab)
    tracker.observe(idx_frames)

    lock = threading.Lock()
    lat_ms, served = [], [0]
    stop_at = time.perf_counter() + seconds

    def client(ci):
        i = ci
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            server.predict("loadgen_dlrm",
                           (dense_frames[i % n_frames],
                            idx_frames[i % n_frames]), timeout=120)
            dt = (time.perf_counter() - t0) * 1e3
            with lock:
                lat_ms.append(dt)
                served[0] += 1
            i += 1

    t_start = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(conc)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    server.stop(drain=True)
    snap = serving.stats()["loadgen_dlrm"]
    assert snap["counters"]["compiles"] == compiles_warm, \
        "dlrm traffic recompiled beyond warmup buckets"
    qps = served[0] / wall
    row = {"dlrm": True, "clients": conc, "seconds": round(wall, 2),
           "requests": served[0], "req_s": round(qps, 1),
           "emb_lookups_s": round(qps * fields, 1),
           "fields": fields, "vocab": vocab,
           "hot_row_hit_rate": round(tracker.hot_hit_rate(), 3),
           "occupancy": round(snap["batch_occupancy"], 3),
           "compiles": compiles_warm}
    row.update(_percentiles(lat_ms))
    row.update(_queue_wait_fields(snap))
    _emit(row)
    serving.unregister("loadgen_dlrm")


def _tail_mlp(in_dim=8, out_dim=4, seed=0):
    """Identically-seeded tiny MLP for the tail phases — every replica
    serves bitwise-identical outputs, so hedging is numerics-safe."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn

    mx.random.seed(seed)
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(out_dim))
    net.initialize(mx.init.Xavier())
    net(nd.array(onp.zeros((2, in_dim), "float32")))
    return net


def _run_hedge(args):
    """Tail-tolerance hedge phase: a burst of deadline-carrying requests
    over a two-replica ServingPool while an injected ``replica_straggler``
    stalls the step boundary. Emits one ``{"tailguard": "hedge", ...}`` row
    with the perf-gate metrics: hedge rate, win rate, the wasted-duplicate-
    work share (bounded by the hedge token bucket) and budget
    exhaustions."""
    from mxnet_tpu import config, current_context, serving
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.serving import DeadlineExceeded, tailguard

    in_dim, n = 8, args.tail_requests
    deadline_ms = args.deadline_ms or 30000.0

    ctx = current_context()

    def factory(rid):
        with ctx:       # a pool may build a replica on another thread
            srv = serving.InferenceServer(batch_timeout_ms=1.0,
                                          max_queue=max(256, n * 8))
            srv.register(serving.ModelEndpoint(
                "loadgen_hedge", _tail_mlp(in_dim), input_shapes=(in_dim,),
                max_batch_size=4))
        return srv

    saved = config.get("MXNET_HEDGE_DELAY_MIN_MS")
    config.set("MXNET_HEDGE_DELAY_MIN_MS", 25.0)
    tailguard.hedge_reset()
    ratio = float(config.get("MXNET_HEDGE_BUDGET_RATIO"))
    before = {m: _metric_total(m) for m in
              ("mxtpu_hedge_requests_total", "mxtpu_hedge_wins_total",
               "mxtpu_hedge_wasted_total", "mxtpu_hedge_cancelled_total",
               "mxtpu_hedge_budget_exhausted_total")}
    xs = onp.random.default_rng(1).standard_normal(
        (n, in_dim)).astype("float32")
    pool = serving.ServingPool(factory, initial_replicas=2)
    lat_ms, misses, errors = [], 0, []
    t0 = time.perf_counter()
    try:
        with faults.inject("replica_straggler", site="serving_dispatch",
                           every_n=5, seconds=0.2) as inj:
            futs = [pool.submit("loadgen_hedge", xs[i],
                                deadline_ms=deadline_ms) for i in range(n)]
            for f in futs:
                t1 = time.perf_counter()
                try:
                    f.result(timeout=120)
                    lat_ms.append((time.perf_counter() - t1) * 1e3)
                except DeadlineExceeded:
                    misses += 1
                except Exception as e:
                    errors.append(repr(e))
        stalls = inj.fires
    finally:
        config.set("MXNET_HEDGE_DELAY_MIN_MS", saved)
        tailguard.hedge_reset()
        pool.stop(drain=True)
        serving.unregister("loadgen_hedge")
    wall = time.perf_counter() - t0
    d = {m: _metric_total(m) - before[m] for m in before}
    hedges = d["mxtpu_hedge_requests_total"]
    row = {"tailguard": "hedge", "requests": n, "replicas": 2,
           "seconds": round(wall, 2), "stalls": stalls,
           "deadline_ms": deadline_ms, "deadline_misses": misses,
           "client_errors": len(errors),
           "hedge_rate": round(hedges / n, 4),
           "hedge_win_rate": round(
               d["mxtpu_hedge_wins_total"] / max(1.0, hedges), 4),
           "hedge_wasted_work_pct": round(
               100.0 * d["mxtpu_hedge_wasted_total"] / n, 3),
           "hedge_cancelled": d["mxtpu_hedge_cancelled_total"],
           "hedge_budget_exhausted": d["mxtpu_hedge_budget_exhausted_total"],
           "hedge_budget_ratio": ratio}
    row.update(_percentiles(lat_ms))
    _emit(row)


def _run_storm(args):
    """Tail-tolerance storm phase: a bounded retryable ``net_drop`` storm
    at a single-host FrontDoor. The frontdoor retry budget must absorb
    every drop — ``storm_client_error_rate`` is the ==0 perf-gate row —
    and ``storm_amplification`` (fault-site attempts per request) shows the
    budget holding re-send traffic near 1x."""
    from mxnet_tpu import current_context, serving
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.serving.fabric import FrontDoor
    from mxnet_tpu.serving.tailguard import RETRY_BUDGETS

    in_dim, n = 8, args.tail_requests

    ctx = current_context()

    def factory(name):
        with ctx:       # a front door may rebuild a host on another thread
            srv = serving.InferenceServer(batch_timeout_ms=1.0,
                                          max_queue=max(256, n * 8))
            srv.register(serving.ModelEndpoint(
                "loadgen_storm", _tail_mlp(in_dim), input_shapes=(in_dim,),
                max_batch_size=4))
        srv.start()
        return srv

    RETRY_BUDGETS.reset()       # the production-default budget knobs
    ex_before = _metric_total("mxtpu_retry_budget_exhausted_total")
    xs = onp.random.default_rng(2).standard_normal(
        (n, in_dim)).astype("float32")
    fd = FrontDoor([f"storm_{os.getpid()}"], factory, spawn_agents=False,
                   supervise=False)
    lat_ms, errors = [], []
    t0 = time.perf_counter()
    try:
        # the drop volume stays under the budget floor, so absorption —
        # not shed — is the contract being measured
        with faults.inject("net_drop", site="frontdoor", p=0.6,
                           times=max(1, n // 5), seed=3) as inj:
            for i in range(n):
                t1 = time.perf_counter()
                try:
                    fd.submit("loadgen_storm", xs[i],
                              deadline_ms=args.deadline_ms) \
                        .result(timeout=120)
                    lat_ms.append((time.perf_counter() - t1) * 1e3)
                except Exception as e:
                    errors.append(repr(e))
            attempts, drops = inj.calls, inj.fires
    finally:
        fd.stop(drain=True)
        serving.unregister("loadgen_storm")
        RETRY_BUDGETS.reset()
    wall = time.perf_counter() - t0
    row = {"tailguard": "storm", "requests": n, "seconds": round(wall, 2),
           "drops_absorbed": drops,
           "storm_amplification": round(attempts / float(n), 3),
           "storm_client_error_rate": round(len(errors) / float(n), 4),
           "client_errors": len(errors),
           "retry_budget_exhausted": _metric_total(
               "mxtpu_retry_budget_exhausted_total") - ex_before}
    row.update(_percentiles(lat_ms))
    _emit(row)


def _run_restart_child(args, phase):
    """One restart-benchmark phase in THIS process: build the dense (and
    optionally decode) endpoints, start the server, serve one request each,
    and report time-from-entry plus the compile-ledger split (fresh
    compiles vs executable-cache hits). Weights and inputs are seeded so
    the first-request outputs are bitwise-comparable across phases."""
    import hashlib
    t0 = time.perf_counter()
    import mxnet_tpu as mx
    from mxnet_tpu import serving, telemetry

    onp.random.seed(0)
    net = _build_net(args.model, args.classes, args.img, "f32")
    ep = serving.ModelEndpoint(f"{args.model}_restart", net,
                               input_shapes=(3, args.img, args.img),
                               dtype="float32",
                               max_batch_size=args.max_batch)
    server = serving.InferenceServer(batch_timeout_ms=args.timeout_ms,
                                     max_queue=args.max_batch * 8)
    server.register(ep)          # warmup: compiles cold, deserializes warm
    server.start()
    frame = onp.arange(3 * args.img * args.img, dtype="float32") \
        .reshape(3, args.img, args.img) / (3 * args.img * args.img)
    out = server.predict(ep.name, frame, timeout=120)
    dense_t = time.perf_counter() - t0
    dense_digest = hashlib.sha256(
        onp.ascontiguousarray(out.asnumpy()).tobytes()).hexdigest()

    fab_t = fab_digest = None
    if args.fabric:
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.serving.fabric import ShardedEndpoint, plan_slices
        mx.random.seed(0)
        onp.random.seed(0)
        fnet = nn.HybridSequential()
        with fnet.name_scope():
            fnet.add(nn.Dense(32, activation="relu"), nn.Dense(8))
        fnet.initialize(mx.init.Xavier())
        fnet(mx.nd.array(onp.zeros((2, 16), "float32")))
        sep = ShardedEndpoint("restart_sharded", fnet, input_shapes=(16,),
                              dtype="float32", max_batch_size=4,
                              slice_spec=plan_slices([2])[0])
        server.register(sep)     # warmup: compiles cold, deserializes warm
        fout = server.predict("restart_sharded",
                              onp.arange(16, dtype="float32") / 16.0,
                              timeout=120)
        fab_t = time.perf_counter() - t0
        fab_digest = hashlib.sha256(
            onp.ascontiguousarray(fout.asnumpy()).tobytes()).hexdigest()

    dec_t = dec_digest = None
    if args.decode:
        from mxnet_tpu.gluon.model_zoo.bert import TransformerLM
        onp.random.seed(0)
        lm = TransformerLM(num_layers=2, units=32, hidden_size=64,
                           num_heads=2, vocab_size=64,
                           max_length=args.dec_seq)
        lm.initialize(mx.init.Normal(0.5))
        eng = serving.DecodeEndpoint("restart_lm", lm,
                                     max_seq_len=args.dec_seq,
                                     max_batch_size=2)
        server.register_generator(eng)
        toks = list(server.generate("restart_lm", [1, 2, 3, 4],
                                    max_new_tokens=4))
        dec_t = time.perf_counter() - t0
        dec_digest = hashlib.sha256(
            onp.asarray(toks, "int64").tobytes()).hexdigest()

    cls = telemetry.compile_ledger.summary()
    server.stop(drain=True)
    serving.unregister(ep.name)
    if args.fabric:
        serving.unregister("restart_sharded")
    if args.decode:
        serving.unregister("restart_lm")
    _emit({
        "restart_child": phase,
        "restart_to_first_request_s": round(
            max(dense_t, dec_t or 0.0, fab_t or 0.0), 3),
        "dense_first_s": round(dense_t, 3),
        "fabric_first_s": round(fab_t, 3) if fab_t is not None else None,
        "fabric_digest": fab_digest,
        "decode_first_s": round(dec_t, 3) if dec_t is not None else None,
        "compiles": cls["compiles"],
        "cache_hits": cls["cache_hits"],
        "fresh_compiles": cls["compiles"] - cls["cache_hits"],
        "duplicates": cls["duplicates"],
        "dense_digest": dense_digest,
        "decode_digest": dec_digest,
    })
    return 0


def _run_restart(args):
    """Parent half of ``--restart``: run the child phase twice against one
    shared executable-cache + ledger directory (cold populates, warm must
    compile nothing) and emit the perf-gate row."""
    import subprocess
    import tempfile
    from jax._src import xla_bridge
    # the device on this parent's rows is copied from its children's: asking
    # JAX here would take the chip from them
    from mxnet_tpu.runtime import DEVICE_ROW_KEYS as _DEVICE_KEYS
    cache_dir = tempfile.mkdtemp(prefix="slg-exec-cache-")
    ledger_dir = tempfile.mkdtemp(prefix="slg-ledger-")
    child_flags = ["--model", args.model, "--img", str(args.img),
                   "--classes", str(args.classes),
                   "--max-batch", str(args.max_batch),
                   "--timeout-ms", str(args.timeout_ms),
                   "--dec-seq", str(args.dec_seq),
                   "--dec-new", str(args.dec_new)]
    if args.fabric:
        child_flags.append("--fabric")
    rows = {}
    # both restart phases join the parent's trace journey: a child's root
    # spans adopt MXNET_TRACE_ID, and with a spool dir configured each
    # phase's spans land in its own spool-<pid>.jsonl next to the parent's
    from mxnet_tpu import telemetry
    from mxnet_tpu import config as _config
    # active span > operator-set MXNET_TRACE_ID > fresh id — so a harness
    # that pinned a trace id for the whole run keeps one journey
    trace_id = (telemetry.current_trace_id()
                or str(_config.get("MXNET_TRACE_ID", "") or "")
                or telemetry.new_trace_id())
    spool_dir = str(_config.get("MXNET_SPAN_SPOOL_DIR", "") or "")
    for phase in ("cold", "warm"):
        env = dict(os.environ)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)   # cold means cold
        env["MXNET_EXEC_CACHE_DIR"] = cache_dir
        env["MXNET_COMPILE_LEDGER_DIR"] = ledger_dir
        # only AOT serving compiles are the contract; keep the eager jit
        # cache un-instrumented so op-level compiles don't muddy the count
        env["MXNET_COMPILE_LEDGER_EAGER"] = "0"
        env["SLG_DECODE"] = "1" if args.decode else "0"
        if args.fabric and "xla_force_host_platform_device_count" \
                not in env.get("XLA_FLAGS", ""):
            # the 2-device slice needs >1 host device; the flag only
            # multiplies the CPU platform, so it is harmless on real chips
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count=8"
                                ).strip()
        env["MXNET_TRACE_ID"] = trace_id
        if spool_dir:
            env["MXNET_SPAN_SPOOL_DIR"] = spool_dir
        cmd = [sys.executable, os.path.abspath(__file__),
               "--restart-child", phase] + child_flags
        # a chip belongs to one process: this parent imports the package
        # for its trace id, but must never initialise a backend, or the
        # child would find the chip taken
        assert not xla_bridge.backends_are_initialized(), \
            "--restart parent initialised a JAX backend; its children " \
            "cannot have the chip"
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
        row = None
        for line in proc.stdout.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    cand = json.loads(line)
                except ValueError:
                    continue
                if cand.get("restart_child") == phase:
                    row = cand
        if proc.returncode != 0 or row is None:
            sys.stderr.write(proc.stdout[-2000:] + proc.stderr[-2000:])
            raise SystemExit(f"restart child ({phase}) failed "
                             f"rc={proc.returncode}")
        rows[phase] = row
        print(json.dumps({"restart": phase,
                          **{k: row[k] for k in
                             ("restart_to_first_request_s", "dense_first_s",
                              "fabric_first_s", "decode_first_s",
                              "compiles", "cache_hits",
                              "fresh_compiles", "duplicates") + _DEVICE_KEYS}}),
              flush=True)
    cold, warm = rows["cold"], rows["warm"]
    assert warm["fresh_compiles"] == 0, \
        f"warm restart performed {warm['fresh_compiles']} fresh compiles " \
        "(executable cache missed)"
    assert warm["duplicates"] == 0, \
        "warm restart tripped the recompile-storm counter " \
        f"({warm['duplicates']} duplicates)"
    assert warm["cache_hits"] == cold["compiles"], \
        f"warm hit {warm['cache_hits']} entries but cold compiled " \
        f"{cold['compiles']}"
    for k in ("dense_digest", "fabric_digest", "decode_digest") + _DEVICE_KEYS:
        assert cold[k] == warm[k], \
            f"{k}: warm phase differs from cold ({cold[k]} vs {warm[k]})"
    warm_s, cold_s = (warm["restart_to_first_request_s"],
                      cold["restart_to_first_request_s"])
    print(json.dumps({
        "restart_to_first_request_s": warm_s,
        "restart_cold_s": cold_s,
        "restart_speedup": round(cold_s / warm_s, 2) if warm_s else None,
        "warm_fresh_compiles": warm["fresh_compiles"],
        "warm_cache_hits": warm["cache_hits"],
        "outputs_bitwise_equal": True,
        **{k: warm[k] for k in _DEVICE_KEYS},
    }), flush=True)
    return 0


def _parse_args():
    env = os.environ.get
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--tenants", type=int, default=1)
    p.add_argument("--mix", default="",
                   help="comma client-traffic weights per tenant")
    p.add_argument("--slo-ms", default="",
                   help="comma per-tenant scheduling SLO (register slo_ms)")
    p.add_argument("--serial", action="store_true",
                   help="pipeline=False: serial prepare-then-step dispatch")
    p.add_argument("--model", default=env("SLG_MODEL", "resnet50_v1"))
    p.add_argument("--img", type=int, default=int(env("SLG_IMG", 224)))
    p.add_argument("--classes", type=int, default=int(env("SLG_CLASSES", 1000)))
    p.add_argument("--dtypes", default=env("SLG_DTYPES", "bf16,int8"))
    p.add_argument("--conc", default=env("SLG_CONC", "1,2,4,8,16"))
    p.add_argument("--seconds", type=float, default=float(env("SLG_SECONDS", 5)))
    p.add_argument("--max-batch", type=int,
                   default=int(env("SLG_MAX_BATCH", 32)))
    p.add_argument("--timeout-ms", type=float,
                   default=float(env("SLG_TIMEOUT_MS", 5)))
    p.add_argument("--decode", action="store_true",
                   default=env("SLG_DECODE", "") not in ("", "0"),
                   help="also run the generative decode phase "
                        "(env SLG_DECODE=1)")
    p.add_argument("--dec-clients", type=int,
                   default=int(env("SLG_DEC_CLIENTS", 4)))
    p.add_argument("--dec-seconds", type=float,
                   default=float(env("SLG_DEC_SECONDS",
                                     env("SLG_SECONDS", 5))))
    p.add_argument("--dec-seq", type=int, default=int(env("SLG_DEC_SEQ", 64)))
    p.add_argument("--dec-new", type=int, default=int(env("SLG_DEC_NEW", 16)))
    p.add_argument("--dlrm", action="store_true",
                   default=env("SLG_DLRM", "") not in ("", "0"),
                   help="run the DLRM recommendation phase after the image "
                        "sweep (env SLG_DLRM=1)")
    p.add_argument("--dlrm-clients", type=int,
                   default=int(env("SLG_DLRM_CLIENTS", 8)))
    p.add_argument("--dlrm-seconds", type=float,
                   default=float(env("SLG_DLRM_SECONDS",
                                     env("SLG_SECONDS", 5))))
    p.add_argument("--deadline-ms", type=float,
                   default=float(env("SLG_DEADLINE_MS", 0)) or None,
                   help="end-to-end deadline per request; sweep rows gain "
                        "deadline_misses (env SLG_DEADLINE_MS, 0 = none)")
    p.add_argument("--hedge", action="store_true",
                   default=env("SLG_HEDGE", "") not in ("", "0"),
                   help="run the hedged-burst tail phase (env SLG_HEDGE=1)")
    p.add_argument("--storm", action="store_true",
                   default=env("SLG_STORM", "") not in ("", "0"),
                   help="run the retry-storm tail phase (env SLG_STORM=1)")
    p.add_argument("--tail-requests", type=int,
                   default=int(env("SLG_TAIL_REQUESTS", 60)),
                   help="burst size for the hedge/storm phases")
    p.add_argument("--restart", action="store_true",
                   help="cold/warm restart-to-first-request benchmark "
                        "instead of the load sweep")
    p.add_argument("--fabric", action="store_true",
                   default=env("SLG_FABRIC", "0") == "1",
                   help="with --restart: run a mesh-sharded endpoint "
                        "(2-device slice) through both phases too")
    p.add_argument("--restart-child", default="", help=argparse.SUPPRESS)
    return p.parse_args()


def main():
    args = _parse_args()
    if args.restart:
        return _run_restart(args)    # parent only: stays off JAX
    from mxnet_tpu import cache, runtime
    # every net, endpoint and pool below is built on this thread, so the
    # scope places them all: the chip, or the CPU the user asked for
    with runtime.measurement_context():
        if args.restart_child:
            # no JAX compile cache here: the cold phase has to compile
            return _run_restart_child(args, args.restart_child)
        cache.enable_compile_cache()
        return _run_sweep(args)


def _run_sweep(args):
    model, img, classes = args.model, args.img, args.classes
    dtypes = [d for d in args.dtypes.split(",")
              if d.strip() and d.strip() != "none"]
    conc_levels = [int(c) for c in str(args.conc).split(",")]
    seconds, max_batch = args.seconds, args.max_batch
    timeout_ms = args.timeout_ms
    tenants = max(1, args.tenants)
    weights = [float(w) for w in args.mix.split(",")] if args.mix \
        else [1.0] * tenants
    if len(weights) != tenants:
        raise SystemExit(f"--mix needs {tenants} weights, got {len(weights)}")
    slo_ms = [float(s) for s in args.slo_ms.split(",")] if args.slo_ms \
        else [None] * tenants
    if len(slo_ms) != tenants:
        raise SystemExit(f"--slo-ms needs {tenants} values, got {len(slo_ms)}")

    import mxnet_tpu as mx  # noqa: F401  (context/init side effects)
    from mxnet_tpu import serving

    for dtype in dtypes:
        dtype = dtype.strip()
        in_dtype = "bfloat16" if dtype == "bf16" else "float32"
        server = serving.InferenceServer(batch_timeout_ms=timeout_ms,
                                         max_queue=max_batch * 8,
                                         pipeline=not args.serial)
        names, eps, nets = [], [], []
        for ti in range(tenants):
            net = _build_net(model, classes, img, dtype)
            name = f"{model}_{dtype}" if tenants == 1 \
                else f"{model}_{dtype}_t{ti}"
            ep = serving.ModelEndpoint(name, net, input_shapes=(3, img, img),
                                       dtype=in_dtype,
                                       max_batch_size=max_batch)
            server.register(ep, slo_ms=slo_ms[ti])   # warms every bucket
            names.append(name)
            eps.append(ep)
            nets.append(net)
        compiles_after_warmup = {n: e.stats.counters["compiles"]
                                 for n, e in zip(names, eps)}
        server.start()
        np_dtype = eps[0].np_dtypes[0]
        try:
            for conc in conc_levels:
                agg, per = _run_level(server, names, img, np_dtype, conc,
                                      seconds, weights,
                                      deadline_ms=args.deadline_ms)
                snaps = serving.stats()
                agg.update({
                    "dtype": dtype, "conc": conc, "tenants": tenants,
                    "pipeline": not args.serial,
                    "occupancy": round(statistics.mean(
                        snaps[n]["batch_occupancy"] for n in names), 3),
                    "compiles": sum(snaps[n]["counters"]["compiles"]
                                    for n in names),
                    "batches": sum(snaps[n]["counters"]["batches"]
                                   for n in names),
                })
                # queue-wait decomposition over all tenants' requests
                agg.update(_queue_wait_fields(
                    snaps[names[0]] if tenants == 1 else
                    max((snaps[n] for n in names),
                        key=lambda s: s["latency"]["p99_us"])))
                _emit(agg)
                if tenants > 1:
                    for name in names:        # the per-tenant latency table
                        row = {"tenant": name, "conc": conc,
                               "served": per[name]["served"]}
                        row.update(_percentiles(per[name]["lat_ms"]))
                        row.update(_queue_wait_fields(snaps[name]))
                        row["shed"] = snaps[name]["shed"]
                        _emit(row)
        finally:
            server.stop(drain=True)
        snaps = serving.stats()
        for name in names:
            assert snaps[name]["counters"]["compiles"] == \
                compiles_after_warmup[name], \
                "serving traffic recompiled beyond warmup buckets"
        direct = _direct_rate(nets[0], img, in_dtype, max_batch)
        _emit({
            "dtype": dtype, "summary": True,
            "direct_b{}_img_s".format(max_batch): round(direct, 1),
            "buckets": list(eps[0].buckets),
            "compiles": sum(snaps[n]["counters"]["compiles"] for n in names),
            "prep_overlap_ratio": round(
                server.health()["prep_overlap_ratio"], 3),
        })
        for name in names:
            serving.unregister(name)

    if args.decode:
        _run_decode(args)

    if args.dlrm:
        _run_dlrm(args)

    if args.hedge:
        _run_hedge(args)

    if args.storm:
        _run_storm(args)

    # one whole-process telemetry snapshot: serving latency histograms,
    # executable-cache hit/miss/compile-seconds, queue depth / occupancy,
    # train-step + dataloader families (zero here), device memory gauges
    from mxnet_tpu import telemetry
    tsnap = telemetry.snapshot()
    _emit({"telemetry_summary": telemetry.summary_line(),
           "metric_families": len(tsnap["metrics"])})
    # compile-ledger rollup: every serving-bucket compile of the run, the
    # distinct programs behind them, and the seconds re-spent on programs
    # the process had already compiled (what a persistent cache would save)
    cls = telemetry.compile_ledger.summary()
    _emit({"compile_ledger": {
        "compiles": cls["compiles"],
        "distinct_fingerprints": cls["distinct_fingerprints"],
        "duplicates": cls["duplicates"],
        "dup_waste_s": cls["dup_waste_s"],
        "wall_s": round(cls["lower_s"] + cls["compile_s"], 3),
    }})
    dump_path = os.environ.get("SLG_TELEMETRY", "")
    if dump_path:
        telemetry.dump(dump_path)
        _emit({"telemetry_snapshot": dump_path})
    return 0


if __name__ == "__main__":
    sys.exit(main())
