"""SSD-300 detection-accuracy evidence (VERDICT r3 #8).

No detection dataset can be downloaded in this environment (zero egress), so
this trains on the synthetic shapes benchmark (three geometry classes,
rejection-sampled non-occluding placements — test_utils.get_shapes_detection)
and evaluates VOC07 11-point mAP@0.5 at the reference's threshold=0.01 eval
convention. Thin wrapper over examples/ssd/train_shapes.py — the ONE
detection-accuracy pipeline — that emits the committed-evidence JSON line.

Run on the TPU host:  python benchmark/ssd_accuracy.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples",
    "ssd"))


def main():
    from mxnet_tpu import cache
    cache.enable_compile_cache()
    from train_shapes import evaluate, train
    from mxnet_tpu.test_utils import get_shapes_detection

    steps = int(os.environ.get("SSD_STEPS", 1200))
    batch = int(os.environ.get("SSD_BATCH", 32))
    lr = float(os.environ.get("SSD_LR", 1e-3))
    bf16 = os.environ.get("SSD_DTYPE", "bfloat16") == "bfloat16"
    net, ctx, imgs_per_s = train(
        steps=steps, batch_size=batch, lr=lr, bf16=bf16,
        log=lambda *a: print(*a, flush=True))
    val_imgs, val_labels = get_shapes_detection(64, size=300, seed=12345)
    mAP = evaluate(net, val_imgs, val_labels, batch, ctx)
    print(json.dumps({"metric": "ssd300_synthetic_shapes_mAP",
                      "value": round(float(mAP), 4), "unit": "mAP@0.5",
                      "steps": steps,
                      "train_imgs_per_s": round(imgs_per_s, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
