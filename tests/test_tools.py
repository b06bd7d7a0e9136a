"""CLI tooling tests (parity: tools/im2rec.py list/pack modes,
tools/parse_log.py, tools/launch.py covered by test_dist_kvstore)."""
import os
import subprocess
import sys

import numpy as onp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_images(root):
    import cv2
    for cls in ("cats", "dogs"):
        os.makedirs(os.path.join(root, cls), exist_ok=True)
        for i in range(3):
            img = onp.random.RandomState(hash(cls) % 100 + i).randint(
                0, 255, (8, 8, 3), dtype=onp.uint8)
            cv2.imwrite(os.path.join(root, cls, f"im{i}.png"), img)


def test_im2rec_list_and_pack(tmp_path):
    root = str(tmp_path / "imgs")
    _write_images(root)
    prefix = str(tmp_path / "data")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
                        "--list", "--recursive", prefix, root],
                       env=env, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    lst = open(prefix + ".lst").read().strip().splitlines()
    assert len(lst) == 6
    labels = {line.split("\t")[1] for line in lst}
    assert labels == {"0.000000", "1.000000"} or labels == {"0", "1"}, labels

    r = subprocess.run([sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
                        prefix, root], env=env, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert os.path.exists(prefix + ".rec") and os.path.exists(prefix + ".idx")

    # read back through the framework's indexed reader
    from mxnet_tpu import recordio
    reader = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    keys = sorted(reader.keys)
    assert len(keys) == 6
    header, img = recordio.unpack_img(reader.read_idx(keys[0]))
    assert img.shape == (8, 8, 3)
    assert header.label in (0.0, 1.0)


def test_parse_log(tmp_path):
    log = tmp_path / "train.log"
    log.write_text(
        "INFO Epoch[0] Train-accuracy=0.512000\n"
        "INFO Epoch[0] Time cost=12.300\n"
        "INFO Epoch[0] Validation-accuracy=0.600000\n"
        "INFO Epoch[1] Train-accuracy=0.712000\n"
        "INFO Epoch[1] Time cost=11.100\n"
        "INFO Epoch[1] Validation-accuracy=0.800000\n")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "parse_log.py"), str(log)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith("epoch")
    assert "0.712000" in r.stdout and "0.800000" in r.stdout
    assert len(lines) == 4  # header + sep + 2 epochs


def test_bandwidth_tool():
    """tools/bandwidth.py runs on the virtual mesh and emits JSON rows
    (tools/bandwidth measure.py parity)."""
    import json
    import os
    import subprocess
    import sys
    inherited = [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
                 if p]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([REPO] + inherited))
    r = subprocess.run([sys.executable, os.path.join(REPO, "tools",
                                                     "bandwidth.py"),
                        "--sizes-mb", "0.5", "--iters", "2"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    rows = [json.loads(l) for l in r.stdout.splitlines()
            if l.startswith("{")]
    # the multi-device ring-allreduce branch must actually run
    assert rows and rows[0]["devices"] == 4 and rows[0]["algo_gbps"] > 0
