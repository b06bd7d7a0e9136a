"""Examples smoke tests: every shipped example must run end-to-end on
synthetic data (the reference CI's example-smoke discipline)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, os.path.join(REPO, script), *args],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"{script} failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


def test_train_cnn_example():
    out = _run("examples/image_classification/train_cnn.py",
               "--epochs", "1", "--steps", "3", "--batch-size", "8")
    assert "accuracy=" in out and "loss=" in out


def test_lstm_lm_example():
    out = _run("examples/rnn/lstm_lm.py", "--steps", "3",
               "--batch-size", "4", "--seq-len", "8")
    assert out.count("loss=") == 3


def test_bert_pretrain_example():
    out = _run("examples/bert/pretrain.py", "--layers", "2", "--hidden", "64",
               "--heads", "2", "--batch-size", "2", "--seq-len", "16",
               "--steps", "2", "--vocab", "200")
    assert out.count("loss=") == 2


def test_ssd_example():
    out = _run("examples/ssd/train_ssd.py", "--steps", "2", "--detect")
    assert out.count("loss=") == 2 and "detections kept" in out


def test_model_parallel_example():
    out = _run("examples/model_parallel/train_tp.py", "--steps", "3")
    assert "params synced back" in out


def test_distributed_training_example():
    # same env hygiene as test_dist_kvstore: plain CPU, no forced device
    # count
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"), "-n", "2",
         "--launcher", "local", sys.executable,
         os.path.join(REPO, "examples", "distributed_training",
                      "train_dist.py"), "--steps", "2"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "[worker 0] done" in r.stdout and "[worker 1] done" in r.stdout
