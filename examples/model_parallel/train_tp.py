"""Model (tensor) parallelism example (parity: reference
example/model-parallel/ — per-op ctx placement via group2ctx; here the
TPU-native equivalent is GSPMD sharding annotations on Parameters).

Shards a wide MLP Megatron-style across the `tp` mesh axis: the first
Dense's weight is column-sharded, the second row-sharded, so the activation
allreduce happens on ICI inside ONE XLA computation — no manual
cross-device copies (the reference inserts them at bind time,
src/operator/cross_device_copy.cc).

Run (any host; uses a virtual device mesh on CPU):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/model_parallel/train_tp.py --steps 5
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

if "--help" not in sys.argv and os.environ.get("JAX_PLATFORMS", "") == "cpu":
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split() if f]
    if not any("host_platform_device_count" in f for f in flags):
        flags.append("--xla_force_host_platform_device_count=8")
    os.environ["XLA_FLAGS"] = " ".join(flags)

import numpy as onp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--tp", type=int, default=2)
    args = ap.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    from jax.sharding import PartitionSpec as P

    net = nn.HybridSequential()
    net.add(nn.Dense(args.hidden, activation="relu", in_units=64),
            nn.Dense(10, in_units=args.hidden))
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(onp.zeros((1, 64), "float32")))

    # Megatron layout: fc1 column-parallel, fc2 row-parallel
    fc1, fc2 = net[0], net[1]
    fc1.weight.shard(P("tp", None))   # (hidden, in) split over hidden
    fc1.bias.shard(P("tp"))
    fc2.weight.shard(P(None, "tp"))   # (10, hidden) split over hidden
    fc2.bias.shard(P())

    mesh = parallel.make_mesh({"dp": -1, "tp": args.tp})
    step = parallel.ParallelTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.1, momentum=0.9), mesh)

    rng = onp.random.RandomState(0)
    x = rng.rand(args.batch_size, 64).astype("float32")
    y = rng.randint(0, 10, (args.batch_size,)).astype("float32")
    placed = step.place_batch(x, y)
    for i in range(args.steps):
        loss = step.step(*placed)
        print(f"step {i} loss={float(loss.asnumpy().mean()):.4f}", flush=True)
    step.sync_to_block()
    print("done: params synced back to the block", flush=True)


if __name__ == "__main__":
    main()
