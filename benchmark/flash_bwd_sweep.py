"""Flash-attention backward block-size sweep at long context (VERDICT r4 #8).

Times fwd-only and fwd+bwd at S=FSW_S (default 32768), B=1, H=12, D=64,
causal bf16, for a list of backward (block_q, block_k) pairs, and reports
useful-FLOP rates. "Useful" flops follow the round-3 accounting: the
algorithmically necessary matmul flops (2 matmuls fwd, 5 bwd — the s/dp
recomputes are overhead), causal halves everything.

Usage: FSW_SWEEP="512x1024,512x512,256x512" python benchmark/flash_bwd_sweep.py
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
    S = int(os.environ.get("FSW_S", 32768))
    B, H, D = 1, 12, 64
    reps = int(os.environ.get("FSW_REPS", 3))
    chain = int(os.environ.get("FSW_CHAIN", 4))
    sweep = os.environ.get("FSW_SWEEP", "0x0,512x512,256x512,256x1024,"
                                        "1024x512,512x256")

    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    rng = onp.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D) * 0.1, jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, S, D) * 0.1, jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, S, D) * 0.1, jnp.bfloat16)

    # useful flops (causal): fwd 2 matmuls, bwd 5
    per_matmul = 2.0 * B * H * S * S * D / 2.0
    fwd_fl = 2 * per_matmul
    bwd_fl = 5 * per_matmul

    from _timing import time_chained

    def fetch(out):
        return jax.tree_util.tree_map(
            lambda a: float(jnp.asarray(a).ravel()[0].astype(jnp.float32)),
            out)

    def timed(fn, *args):
        return time_chained(fn, args, reps=reps, chain=chain, fetch=fetch)

    @jax.jit
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    t_fwd = timed(fwd, q, k, v)
    print(json.dumps({"which": "fwd", "ms": round(t_fwd * 1e3, 1),
                      "tf_s": round(fwd_fl / t_fwd / 1e12, 1)}), flush=True)

    for pair in sweep.split(","):
        bq, bk = (int(x) for x in pair.split("x"))
        mx.config.set("MXNET_FLASH_BWD_BLOCK_Q", bq)
        mx.config.set("MXNET_FLASH_BWD_BLOCK_K", bk)

        @jax.jit
        def step(q, k, v):
            def f(q_, k_, v_):
                return flash_attention(q_, k_, v_, causal=True) \
                    .astype(jnp.float32).sum()
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        try:
            t = timed(step, q, k, v)
        except Exception as e:  # noqa: BLE001 — sweep survives bad configs
            print(json.dumps({"bwd_blocks": pair,
                              "error": str(e)[:120]}), flush=True)
            continue
        t_bwd = t - t_fwd
        print(json.dumps({
            "bwd_blocks": pair, "fwdbwd_ms": round(t * 1e3, 1),
            "bwd_ms": round(t_bwd * 1e3, 1),
            "bwd_tf_s": round(bwd_fl / t_bwd / 1e12, 1),
            "total_useful_tf_s": round((fwd_fl + bwd_fl) / t / 1e12, 1)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
