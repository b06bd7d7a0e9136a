"""Sharded-serving scaling curve: one endpoint, 1/2/4/8-chip slices.

The r18 serving-fabric acceptance sweep. For each slice size the harness
carves a fresh gang-scheduled slice out of the visible devices
(``serving.fabric.plan_slices``), builds a ``ShardedEndpoint`` over it for
the SAME seeded MLP, registers it on an ``InferenceServer`` and drives
closed-loop clients through the dynamic batcher for a measured window.
Every size's served probe outputs are checked BITWISE against the
single-chip (unsharded ``ModelEndpoint``) reference served through the
same batcher — the fabric's numerics contract (sharding the batch axis
only re-places rows, it never changes them) holds at every point on the
curve, so the throughput numbers are comparable by construction. The
default width stays in the regime where XLA:CPU's matmul kernel choice is
identical across per-shard batch shapes; very wide layers can pick a
different (equally deterministic) blocked kernel per shape, which is a
fusion artifact of the backend, not a fabric numerics break.

Prints one JSON row per slice size::

    {"slice": 4, "img_s": 15234.1, "p50_ms": 2.1, "p95_ms": 4.0,
     "requests": 1892, "bitwise_vs_ref": true}

and a final summary row (``"summary": true``) carrying
``fabric_sharded_img_s`` — the largest slice's served throughput — which
``tools/perf_gate.py`` gates against PERF_BUDGETS.json (source
``fabric``). On the CI container every "chip" is a forced XLA:CPU host
device sharing the same cores, so the curve certifies the mechanism
(collective-free batch sharding through one cached executable per bucket)
rather than real speedup; on a real slice the same sweep records the
hardware scaling curve.

``--write-multichip PATH`` additionally records the run in the
MULTICHIP_r{N}.json driver-artifact format (n_devices/rc/ok/skipped/tail).

CLI / env knobs:
  --sizes 1,2,4,8   slice sizes to sweep (FS_SIZES; sizes beyond the
                    visible device count are skipped)
  --seconds 2.0     measured window per size           (FS_SECONDS)
  --conc 4          closed-loop clients                (FS_CONC)
  --rows 8          rows per client request            (FS_ROWS)
  --hidden 128      MLP hidden width                   (FS_HIDDEN)
  --in-dim 64       input feature dim                  (FS_IN_DIM)
  --max-batch 32    endpoint max batch size            (FS_MAX_BATCH)
"""
import argparse
import json
import os
import sys
import threading
import time

# every "chip" is a forced host device on the CPU container; the flag only
# multiplies the CPU platform, so it is harmless where real chips exist
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def _build_net(seed, in_dim, hidden, out_dim=16):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn
    mx.random.seed(seed)
    onp.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(hidden, activation="relu"),
                nn.Dense(hidden, activation="relu"),
                nn.Dense(out_dim))
    net.initialize(mx.init.Xavier())
    net.hybridize()     # the bitwise reference is the TRACED forward — the
    net(nd.array(onp.zeros((2, in_dim), "float32")))  # contract's baseline
    return net


def _percentile(sorted_ms, q):
    if not sorted_ms:
        return None
    i = min(len(sorted_ms) - 1, int(round(q * (len(sorted_ms) - 1))))
    return round(sorted_ms[i], 3)


def run_slice(net, ref_out, probes, size, args):
    """One point on the curve: a ShardedEndpoint over a fresh ``size``-chip
    slice, closed-loop load for the measured window, bitwise probe check."""
    from mxnet_tpu import serving
    from mxnet_tpu.serving.fabric import ShardedEndpoint, plan_slices

    name = f"fab_scale_{size}"
    ep = ShardedEndpoint(name, net, input_shapes=(args.in_dim,),
                         dtype="float32", max_batch_size=args.max_batch,
                         slice_spec=plan_slices([size])[0])
    server = serving.InferenceServer(batch_timeout_ms=1.0,
                                     max_queue=args.max_batch * 16)
    server.register(ep)
    server.start()
    stop = threading.Event()
    lock = threading.Lock()
    lat_ms, served, errors = [], [0], [0]

    def client(ci):
        rng = onp.random.RandomState(1000 + ci)
        x = rng.randn(args.rows, args.in_dim).astype("float32")
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                server.submit(name, x).result(timeout=60)
            except Exception:
                with lock:
                    errors[0] += 1
                continue
            dt = (time.perf_counter() - t0) * 1e3
            with lock:
                lat_ms.append(dt)
                served[0] += args.rows
    try:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(args.conc)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(args.seconds)
        stop.set()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        # numerics: served probe rows bitwise vs the reference forward
        out = server.predict(name, probes, timeout=60).asnumpy()
        bitwise = bool(onp.array_equal(out, ref_out))
    finally:
        server.stop(drain=False)
        serving.unregister(name)
    lat_ms.sort()
    return {"slice": size, "img_s": round(served[0] / wall, 1),
            "p50_ms": _percentile(lat_ms, 0.50),
            "p95_ms": _percentile(lat_ms, 0.95),
            "requests": len(lat_ms), "client_errors": errors[0],
            "bitwise_vs_ref": bitwise}


def main():
    from mxnet_tpu import cache
    cache.enable_compile_cache()
    env = os.environ.get
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--sizes", default=env("FS_SIZES", "1,2,4,8"))
    p.add_argument("--seconds", type=float,
                   default=float(env("FS_SECONDS", 2.0)))
    p.add_argument("--conc", type=int, default=int(env("FS_CONC", 4)))
    p.add_argument("--rows", type=int, default=int(env("FS_ROWS", 8)))
    p.add_argument("--hidden", type=int, default=int(env("FS_HIDDEN", 128)))
    p.add_argument("--in-dim", type=int, default=int(env("FS_IN_DIM", 64)))
    p.add_argument("--max-batch", type=int,
                   default=int(env("FS_MAX_BATCH", 32)))
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--write-multichip", default="",
                   help="also record the run as a MULTICHIP_r{N}.json "
                        "driver artifact at this path")
    args = p.parse_args()

    import jax
    n_dev = len(jax.devices())
    sizes = [int(s) for s in args.sizes.split(",") if s.strip()]
    skipped = [s for s in sizes if s > n_dev]
    sizes = [s for s in sizes if s <= n_dev]
    if skipped:
        print(json.dumps({"skipped_sizes": skipped, "n_devices": n_dev}),
              flush=True)

    from mxnet_tpu import serving
    net = _build_net(args.seed, args.in_dim, args.hidden)
    probes = onp.random.RandomState(args.seed + 1).randn(
        args.rows * 2 + 1, args.in_dim).astype("float32")
    # the numerics baseline: the single-chip reference served THROUGH the
    # batcher (same bucketing/padding path every slice size rides)
    ref_srv = serving.InferenceServer(batch_timeout_ms=1.0)
    ref_srv.register(serving.ModelEndpoint(
        "fab_scale_ref", net, input_shapes=(args.in_dim,),
        dtype="float32", max_batch_size=args.max_batch))
    ref_srv.start()
    ref_out = ref_srv.predict("fab_scale_ref", probes, timeout=60).asnumpy()
    ref_srv.stop(drain=False)
    serving.unregister("fab_scale_ref")

    rows, tail_lines = [], []
    for size in sizes:
        row = run_slice(net, ref_out, probes, size, args)
        rows.append(row)
        print(json.dumps(row), flush=True)
        tail_lines.append(
            f"fabric_scaling(slice={size}): img_s={row['img_s']:.1f} "
            f"p95_ms={row['p95_ms']} bitwise="
            f"{'OK' if row['bitwise_vs_ref'] else 'MISMATCH'}")
    ok = (bool(rows) and all(r["bitwise_vs_ref"] for r in rows)
          and all(r["client_errors"] == 0 for r in rows))
    top = max(rows, key=lambda r: r["slice"]) if rows else None
    summary = {"summary": True, "ok": ok, "n_devices": n_dev,
               "fabric_sharded_img_s": top["img_s"] if top else None,
               "fabric_top_slice": top["slice"] if top else None,
               "scaling": {str(r["slice"]): r["img_s"] for r in rows}}
    print(json.dumps(summary), flush=True)
    tail_lines.append(
        f"fabric_scaling summary: top slice={summary['fabric_top_slice']} "
        f"img_s={summary['fabric_sharded_img_s']} "
        f"curve={summary['scaling']} {'OK' if ok else 'FAIL'}")
    if args.write_multichip:
        artifact = {"n_devices": n_dev, "rc": 0 if ok else 1, "ok": ok,
                    "skipped": False,
                    "tail": "\n".join(tail_lines) + "\n"}
        with open(args.write_multichip, "w") as f:
            json.dump(artifact, f, indent=2)
        print(json.dumps({"wrote": args.write_multichip}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    from mxnet_tpu import runtime
    # the one-device reference endpoint follows the context: the chip, or
    # the CPU only under an explicit JAX_PLATFORMS=cpu
    with runtime.measurement_context():
        sys.exit(main())
