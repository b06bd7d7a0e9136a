"""Profile the fused ResNet-50 train step and break device time/bytes down by
fusion category (round-3 PERF.md methodology, re-runnable)."""
import glob
import os
import sys
import tempfile
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def build_step(batch=128):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.get_model(os.environ.get("BENCH_MODEL", "resnet50_v1"),
                           classes=1000)
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(onp.zeros((1, 3, 224, 224), "float32")))  # shapes
    mesh = parallel.make_mesh({"dp": 1})
    step = parallel.ParallelTrainStep(
        net, gloss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.05, momentum=0.9), mesh,
        compute_dtype="bfloat16")
    rng = onp.random.RandomState(0)
    x = rng.rand(batch, 3, 224, 224).astype("float32")
    y = rng.randint(0, 1000, (batch,)).astype("float32")
    return step, x, y


def main():
    from mxnet_tpu import cache
    cache.enable_compile_cache()
    import jax
    step, x, y = build_step(int(os.environ.get("BENCH_BATCH", 128)))
    placed = step.place_batch(x, y)
    for _ in range(3):  # warm up + compile
        out = step.step(*placed)
    _ = float(onp.asarray((out[0] if isinstance(out, (tuple, list)) else out)
                          .asnumpy()).ravel()[0])

    tmp = tempfile.mkdtemp(prefix="xplane_")
    with jax.profiler.trace(tmp):
        for _ in range(5):
            out = step.step(*placed)
        loss_val = out[0] if isinstance(out, (tuple, list)) else out
        _ = float(onp.asarray(loss_val.asnumpy()).ravel()[0])

    pb = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    if not pb:
        print("no xplane written", tmp)
        return 1
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(open(pb[-1], "rb").read())

    cats = defaultdict(lambda: [0.0, 0.0, 0])   # time_ms, bytes, count
    rows = defaultdict(lambda: [0.0, 0.0, 0])
    for plane in xs.planes:
        if "TPU" not in plane.name and "Device" not in plane.name:
            continue
        ev_meta = plane.event_metadata
        stat_meta = plane.stat_metadata
        for line in plane.lines:
            for ev in line.events:
                name = ev_meta[ev.metadata_id].name
                dur_ms = ev.duration_ps / 1e9
                nbytes = 0
                for st in ev.stats:
                    sname = stat_meta[st.metadata_id].name
                    if sname == "bytes_accessed":
                        nbytes = st.uint64_value or st.int64_value
                low = name.lower()
                if "conv" in low and "fusion" in low or low.startswith("%conv") \
                        or "convolution" in low:
                    cat = "conv fusions"
                elif "fusion" in low:
                    cat = "loop/other fusions"
                elif "copy" in low or "bitcast" in low or "transpose" in low:
                    cat = "copies/format"
                elif "select-and-scatter" in low or "reduce-window" in low:
                    cat = "pool bwd"
                elif "all-reduce" in low:
                    cat = "collectives"
                else:
                    cat = "misc"
                cats[cat][0] += dur_ms
                cats[cat][1] += nbytes
                cats[cat][2] += 1
                rows[name][0] += dur_ms
                rows[name][1] += nbytes
                rows[name][2] += 1

    steps = 5
    print(f"{'category':22s} {'ms/step':>9s} {'GB/step':>9s} {'events':>7s}")
    tot_ms = tot_gb = 0.0
    for cat, (ms, b, n) in sorted(cats.items(), key=lambda kv: -kv[1][0]):
        print(f"{cat:22s} {ms/steps:9.2f} {b/steps/1e9:9.2f} {n//steps:7d}")
        tot_ms += ms / steps
        tot_gb += b / steps / 1e9
    print(f"{'TOTAL':22s} {tot_ms:9.2f} {tot_gb:9.2f}")
    print("\ntop 25 ops by time:")
    for name, (ms, b, n) in sorted(rows.items(), key=lambda kv: -kv[1][0])[:25]:
        print(f"  {ms/steps:8.3f} ms {b/steps/1e9:7.3f} GB x{n//steps:<4d} {name[:90]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
