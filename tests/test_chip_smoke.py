"""chip_smoke.py is only ever run on the chip; these keep it from rotting in
between: the script refuses a host without a TPU, and its serving, decode and
restart phases still go through at tiny sizes on the CPU."""
import os
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu import config

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


def test_refuses_to_run_without_a_tpu(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(mx.MXNetError, match="needs a TPU"):
        chip_smoke.main()
    assert '"ok"' not in capsys.readouterr().out


def test_serving_phases_rehearse_tiny_on_cpu(tmp_path):
    ctx, clock = mx.cpu(0), chip_smoke._CompileClock()
    config.set("MXNET_EXEC_CACHE_DIR", str(tmp_path / "xc"))
    try:
        served = chip_smoke._run_phase(
            "serve", clock, chip_smoke.serve_resnet, ctx, 0,
            model="mobilenet0_25", classes=10, img=32, max_batch=2,
            clients=8, requests_per_client=1)
        chip_smoke._run_phase(
            "decode", clock, chip_smoke.decode, ctx, 0,
            prompt_lens=(3, 5, 7, 9), max_new=16, vocab=50, max_length=32,
            prefill_buckets=(32,), init_std=0.5,
            widths=dict(num_layers=2, units=32, hidden_size=64, num_heads=2))
        chip_smoke._run_phase("restart", clock, chip_smoke.restart_serving,
                              ctx, served)
    finally:
        config.set("MXNET_EXEC_CACHE_DIR", "")
    assert served["answer"].shape == (2, 10)
