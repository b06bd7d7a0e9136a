"""ResNet-50 b128 HBM byte-ledger experiments (VERDICT r4 #1).

The round-4 profile named the remaining non-conv traffic: thousands of small
f32[256] param copy-starts + bf16 {0,1,3,2} layout permutes (~5 GB/step) and
f32 BN-gradient reductions riding the conv fusions. This harness measures the
two named levers, separately and together:

  RBL_MODE=baseline   the shipped configuration (bench.py path)
  RBL_MODE=auto       param_format="auto" (XLA-chosen carried-state layouts)
  RBL_MODE=bnbf16     MXNET_BN_BF16_REDUCE=1 (bf16 normalize+backward)
  RBL_MODE=both       both levers

Prints one JSON line: {"mode":..., "img_s":..., "ms_step":...}.
Optional RBL_PROFILE=1 adds the per-category device-time/byte breakdown.
"""
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def main():
    from mxnet_tpu import cache
    cache.enable_compile_cache()
    mode = os.environ.get("RBL_MODE", "baseline")
    batch = int(os.environ.get("RBL_BATCH", 128))
    k = int(os.environ.get("RBL_K", 20))
    calls = int(os.environ.get("RBL_CALLS", 2))

    import mxnet_tpu as mx
    # every mode pins BOTH BN flags explicitly so the ablation table stays
    # reproducible after the round-5 default flip (r5 review):
    #   baseline/auto = round-4 shipped config (two-pass f32 promote)
    #   onepass32     = one-pass f32 moments only
    #   bnbf16/both   = the full bf16 fast path (now the package default)
    mx.config.set("MXNET_BN_BF16_REDUCE", mode in ("bnbf16", "both"))
    mx.config.set("MXNET_BN_ONEPASS", mode == "onepass32")
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.get_model("resnet50_v1", classes=1000)
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(onp.zeros((1, 3, 224, 224), "float32")))

    mesh = parallel.make_mesh({"dp": 1})
    step = parallel.ParallelTrainStep(
        net, gloss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.05, momentum=0.9), mesh,
        compute_dtype="bfloat16",
        param_format="auto" if mode in ("auto", "both") else None)

    rng = onp.random.default_rng(0)
    placed = step.place_batch_n(
        rng.random((k, batch, 3, 224, 224), dtype="float32").astype("bfloat16"),
        rng.integers(0, 1000, (k, batch)).astype("float32"))

    out = step.step_n(*placed)          # compile + warm
    float(out.asnumpy()[-1])
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = step.step_n(*placed)
        float(out.asnumpy()[-1])
        times.append(time.perf_counter() - t0)
    dt = statistics.median(times)
    img_s = batch * k * calls / dt
    print(json.dumps({"mode": mode, "img_s": round(img_s, 1),
                      "ms_step": round(1000 * dt / (k * calls), 2)}),
          flush=True)

    if os.environ.get("RBL_PROFILE") == "1":
        _profile(step, placed)
    return 0


def _profile(step, placed):
    import glob
    import tempfile
    from collections import defaultdict
    import jax

    tmp = tempfile.mkdtemp(prefix="xplane_rbl_")
    with jax.profiler.trace(tmp):
        out = step.step_n(*placed)
        float(out.asnumpy()[-1])
    pb = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    if not pb:
        print("no xplane written", tmp)
        return
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(open(pb[-1], "rb").read())
    plane = next(p for p in xs.planes if p.name == "/device:TPU:0")
    sm = plane.stat_metadata

    def meta_stats(em):
        out = {}
        for st in em.stats:
            w = st.WhichOneof("value")
            if w:
                out[sm[st.metadata_id].name] = getattr(st, w)
        return out

    em_cache = {mid: (em.name, meta_stats(em))
                for mid, em in plane.event_metadata.items()}
    cats = defaultdict(lambda: [0.0, 0.0, 0])   # ms, bytes, events
    ops = defaultdict(lambda: [0.0, 0.0, 0])
    line = next(l for l in plane.lines if l.name == "XLA Ops")
    for ev in line.events:
        name, stats = em_cache[ev.metadata_id]
        cat = stats.get("hlo_category", "?")
        nbytes = stats.get("bytes_accessed", 0)
        cats[cat][0] += ev.duration_ps / 1e9
        cats[cat][1] += nbytes
        cats[cat][2] += 1
        ops[name][0] += ev.duration_ps / 1e9
        ops[name][1] += nbytes
        ops[name][2] += 1
    n_steps = placed[0].shape[0]
    print(f"  {'hlo category':28s} {'ms/step':>8s} {'GB/step':>8s} "
          f"{'ev/step':>8s}")
    tot_ms = tot_gb = 0.0
    for cat, (ms, b, cnt) in sorted(cats.items(), key=lambda kv: -kv[1][0]):
        print(f"  {cat:28s} {ms / n_steps:8.2f} {b / n_steps / 1e9:8.2f} "
              f"{cnt // n_steps:8d}")
        tot_ms += ms / n_steps
        tot_gb += b / n_steps / 1e9
    print(f"  {'TOTAL':28s} {tot_ms:8.2f} {tot_gb:8.2f}   "
          f"-> {tot_gb / (tot_ms / 1e3):6.0f} GB/s apparent")
    print("  top 15 ops by time:")
    for name, (ms, b, cnt) in sorted(ops.items(), key=lambda kv: -kv[1][0])[:15]:
        print(f"    {ms / n_steps:7.3f} ms {b / n_steps / 1e9:7.3f} GB "
              f"x{cnt // n_steps:<4d} {name[:86]}")


if __name__ == "__main__":
    sys.exit(main())
