"""Regenerate the committed hlolint fixture corpora (tests/fixtures/hlolint).

Every module text in the corpora is REAL — lowered by jax on the CPU
backend through ``compile_ledger.lower_and_compile`` with a ledger
directory set, so the ledger records (donation summaries, trigger keys,
sites) and the retained ``module-<fingerprint>.mlir`` texts are exactly
what production emits, not hand-written MLIR. Two corpora:

  bad/    one reproduced violation per IR rule — including the actual
          donation-drop (donate an f32 input into an int32-output program:
          XLA finds no usable alias and silently drops it) and actual
          baked-in weights (params captured by closure)
  clean/  the corrected twin of each — kept donation, params as
          arguments, bf16 kept bf16, no callback, truthful mesh key, a
          ladder below the IR1005 threshold

The script is self-verifying: after writing both corpora it runs the IR
rules over them and asserts bad/ fires exactly the expected rule set and
clean/ is silent. Run it only to regenerate after a rule or canonicalizer
change:

    python tools/gen_hlolint_fixtures.py
"""
import os
import shutil
import sys
import warnings

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        f"{flags} --xla_force_host_platform_device_count=8".strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FIXDIR = os.path.join(REPO, "tests", "fixtures", "hlolint")


def _gen_corpus(d, bad):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.telemetry import compile_ledger as cl

    os.makedirs(d, exist_ok=True)
    os.environ["MXNET_COMPILE_LEDGER_DIR"] = d
    cl.reset()

    def compile_(jfn, sds, site, key, expect_donation=False):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return cl.lower_and_compile(jfn, tuple(sds), site=site, key=key,
                                        expect_donation=expect_donation)

    f32 = jnp.float32
    sd = jax.ShapeDtypeStruct

    # IR1000 — donation. bad: donated f32 input, int32 output (no usable
    # alias; XLA drops the donation with only a lower-time warning).
    # clean: f32 -> f32 same shape, alias kept.
    if bad:
        jfn = jax.jit(lambda x: jnp.argmax(x, axis=-1).astype(jnp.int32),
                      donate_argnums=(0,))
    else:
        jfn = jax.jit(lambda x: x * 2.0, donate_argnums=(0,))
    compile_(jfn, (sd((8, 128), f32),), "serving_bucket",
             {"endpoint": "donor", "bucket": 8, "dtype": "float32"},
             expect_donation=True)

    # IR1001 — weights. bad: a 128x128 f32 params block captured by
    # closure (lowered as a 64 KiB dense constant). clean: same math with
    # params as an argument.
    w = np.full((128, 128), 0.5, np.float32)
    if bad:
        wj = jnp.asarray(w)
        jfn = jax.jit(lambda x: x @ wj)
        compile_(jfn, (sd((4, 128), f32),), "serving_bucket",
                 {"endpoint": "baked", "bucket": 4, "dtype": "float32"})
    else:
        jfn = jax.jit(lambda p, x: x @ p)
        compile_(jfn, (sd((128, 128), f32), sd((4, 128), f32)),
                 "serving_bucket",
                 {"endpoint": "baked", "bucket": 4, "dtype": "float32"})

    # IR1002 — precision. bad: f32 dot in a program whose key declares
    # bfloat16. clean: the dot actually computes in bf16.
    dt = f32 if bad else jnp.bfloat16
    jfn = jax.jit(lambda a, b: a @ b)
    compile_(jfn, (sd((8, 64), dt), sd((64, 32), dt)), "serving_bucket",
             {"endpoint": "lowp", "bucket": 8, "dtype": "bfloat16"})

    # IR1003 — host round-trip. bad: a debug pure_callback left inside a
    # decode-step program (lowers to custom_call @xla_python_cpu_callback).
    # clean: the same program without it.
    def step(ids):
        out = ids + 1
        if bad:
            out = jax.pure_callback(
                lambda v: np.asarray(v), sd((4,), jnp.int32), out)
        return out
    compile_(jax.jit(step), (sd((4,), jnp.int32),), "decode_step",
             {"endpoint": "cbk", "kind": "step", "bucket": 4})

    # IR1004 — topology. Both corpora compile the same 2-device psum; the
    # bad key claims a 4-device mesh, the clean key tells the truth.
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    pf = jax.shard_map(lambda x: jax.lax.psum(x * 2.0, "dp"), mesh=mesh,
                       in_specs=P("dp"), out_specs=P())
    jfn = jax.jit(pf)
    compile_(jfn, (sd((8, 16), f32),), "serving_bucket",
             {"endpoint": "shard", "bucket": 8,
              "mesh": "dp=4" if bad else "dp=2"})

    # IR1005 — bucket ladder: one program re-lowered per batch size. bad:
    # 9 variants (above min_variants=8); clean: 6 (the serving default
    # pow2 ladder, which must stay silent). The clean fn differs (extra
    # multiply) so the two ladders can never share fingerprints.
    if bad:
        ladder, fn, ep = (1, 2, 4, 8, 16, 32, 64, 128, 256), \
            (lambda p, x: x @ p), "ladder9"
    else:
        ladder, fn, ep = (1, 2, 4, 8, 16, 32), \
            (lambda p, x: (x @ p) * 3.0), "ladder6"
    jfn = jax.jit(fn)
    for b in ladder:
        compile_(jfn, (sd((16, 16), f32), sd((b, 16), f32)),
                 "serving_bucket",
                 {"endpoint": ep, "bucket": b, "dtype": "float32"})

    # stable committed filename (the pid in the live name is per-process)
    src = os.path.join(d, f"ledger-{os.getpid()}.jsonl")
    os.replace(src, os.path.join(d, "ledger-fixtures.jsonl"))


def main():
    for sub in ("bad", "clean"):
        d = os.path.join(FIXDIR, sub)
        if os.path.isdir(d):
            shutil.rmtree(d)
        _gen_corpus(d, bad=(sub == "bad"))

    # self-verify before anyone commits: bad fires all six, clean is silent
    from mxnet_tpu.analysis import lint_ir_paths
    bad = lint_ir_paths([os.path.join(FIXDIR, "bad")], root=REPO)
    fired = sorted({f.rule for f in bad})
    expected = ["IR1000", "IR1001", "IR1002", "IR1003", "IR1004", "IR1005"]
    assert fired == expected, f"bad corpus fired {fired}, want {expected}"
    clean = lint_ir_paths([os.path.join(FIXDIR, "clean")], root=REPO)
    assert not clean, "clean corpus not silent:\n" + "\n".join(
        f.format() for f in clean)
    print(f"hlolint fixtures regenerated under {FIXDIR}")
    print(f"  bad:   {len(bad)} finding(s) across rules {fired}")
    print(f"  clean: 0 findings")


if __name__ == "__main__":
    main()
