"""A/B the MXNET_TRAIN_REMAT policy on the ResNet-50 b128 train step."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def run(policy, batch=128, k=40, calls=3):
    import mxnet_tpu as mx
    mx.config.set("MXNET_TRAIN_REMAT", policy)
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
        compute_dtype="bfloat16")
    rng = onp.random.default_rng(0)
    placed = step.place_batch_n(
        rng.random((k, batch, 3, 224, 224), dtype="float32").astype("bfloat16"),
        rng.integers(0, 1000, (k, batch)).astype("float32"))
    out = step.step_n(*placed)
    _ = float(out.asnumpy()[-1])
    best = None
    for _ in range(calls):
        t0 = time.perf_counter()
        out = step.step_n(*placed)
        _ = float(out.asnumpy()[-1])
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    img_s = batch * k / best
    print(f"remat={policy:5s}  {img_s:8.1f} img/s  ({best/k*1e3:.2f} ms/step)",
          flush=True)
    return img_s


if __name__ == "__main__":
    from mxnet_tpu import cache
    cache.enable_compile_cache()
    for policy in sys.argv[1:] or ["none", "conv"]:
        run(policy)
