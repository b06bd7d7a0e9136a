"""BERT-base pretraining step profile + lever experiments (VERDICT r4 #3).

Modes (BBL_MODE):
  baseline   the bench.py configuration (dense short-seq attention, Adam f32)
  bf16adam   Adam moments held in bf16 (halves optimizer-state HBM traffic)

BBL_PROFILE=1 adds the per-HLO-category device-time/byte ledger.
Prints one JSON line {"mode":..., "tok_s":..., "ms_step":...}.
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
    mode = os.environ.get("BBL_MODE", "baseline")
    batch = int(os.environ.get("BBL_BATCH", 64))
    seq = int(os.environ.get("BBL_SEQ", 128))
    k = int(os.environ.get("BBL_K", 40))
    calls = int(os.environ.get("BBL_CALLS", 2))

    import mxnet_tpu as mx
    if mode == "bf16adam":
        mx.config.set("MXNET_OPT_BF16_MOMENTS", True)
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo import bert
    from jax.sharding import PartitionSpec as P

    backbone = bert.bert_base(max_length=seq)
    model = bert.BERTForPretraining(backbone)
    model.initialize(mx.init.Normal(0.02))
    # A/B hook for the PERF.md round-5 GELU finding: gelu_tanh is the model
    # default now, so reproducing the erf arm requires BBL_GELU=gelu
    if "BBL_GELU_TANH" in os.environ:
        raise SystemExit("BBL_GELU_TANH is gone: gelu_tanh is the model "
                         "default now; use BBL_GELU=gelu for the erf arm")
    gelu = os.environ.get("BBL_GELU")
    if gelu:
        for layer in backbone.encoder._layers:
            layer.ffn._act = gelu
    n_pred = max(1, int(seq * 0.15))

    class _PretrainStep(HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, tokens, token_types, positions):
            return self.inner(tokens, token_types, None, positions)

    wrapper = _PretrainStep(model)
    mesh = parallel.make_mesh({"dp": 1})
    step = parallel.ParallelTrainStep(
        wrapper, bert.BERTPretrainingLoss(),
        mx.optimizer.Adam(learning_rate=1e-4), mesh,
        compute_dtype="bfloat16", extra_specs=(P("dp"), P("dp")))

    rng = onp.random.RandomState(0)
    toks = rng.randint(0, 30522, (k, batch, seq)).astype("int32")
    tt = onp.zeros((k, batch, seq), "int32")
    positions = onp.sort(
        rng.rand(k, batch, seq).argsort(-1)[..., :n_pred], -1).astype("int32")
    mlm_lab = rng.randint(0, 30522, (k, batch, n_pred)).astype("int32")
    nsp_lab = rng.randint(0, 2, (k, batch)).astype("int32")
    placed = step.place_batch_n(toks, (mlm_lab, nsp_lab), tt, positions)

    out = step.step_n(*placed)
    float(out.asnumpy()[-1])
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = step.step_n(*placed)
        float(out.asnumpy()[-1])
        times.append(time.perf_counter() - t0)
    dt = statistics.median(times)
    tok_s = batch * seq * k * calls / dt
    print(json.dumps({"mode": mode, "tok_s": round(tok_s, 0),
                      "ms_step": round(1000 * dt / (k * calls), 2)}),
          flush=True)

    if os.environ.get("BBL_PROFILE") == "1":
        from resnet_byteledger import _profile
        _profile(step, placed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
