#!/usr/bin/env python
"""Run the operator test families under the TPU context — the CPU<->TPU
portability oracle (reference pattern: tests/python/gpu/test_operator_gpu.py
re-imports the whole CPU operator suite under the GPU default context).

Three layers, all in ONE process with both PJRT backends registered:
  1. tests/test_cross_context.py — same op, same host inputs, executed on
     mx.cpu(0) AND mx.tpu(0); outputs and input grads compared at tolerance.
  2. tests/test_ops_breadth.py + tests/test_contrib_breadth.py — the breadth
     families re-run with default ctx = tpu(0); every host-numpy `want`
     comparison becomes a TPU-vs-host check.
  3. tests/test_numeric_gradients.py — autograd VJPs (computed on TPU) vs
     central finite differences (evaluated through the TPU forward).

Usage (on the machine with the chip, through the chip tool; this parent never
touches JAX, so its one pytest child gets the chip):
    python tools/cross_context_check.py            # all three layers
    python tools/cross_context_check.py --quick    # layer 1 only
"""
import argparse
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAMILIES = [
    "tests/test_cross_context.py",
    "tests/test_ops_breadth.py",
    "tests/test_contrib_breadth.py",
    "tests/test_numeric_gradients.py",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="only the direct cpu-vs-tpu comparison layer")
    ap.add_argument("-k", default=None, help="pytest -k filter")
    args = ap.parse_args()

    env = dict(os.environ)
    env["MXNET_TPU_CROSS_CTX"] = "1"
    # both platforms must register: drop any platform pin
    env.pop("JAX_PLATFORMS", None)

    files = FAMILIES[:1] if args.quick else FAMILIES
    cmd = [sys.executable, "-m", "pytest", "-q", *files]
    if args.k:
        cmd += ["-k", args.k]
    print("+", " ".join(cmd))
    return subprocess.call(cmd, cwd=REPO, env=env)


if __name__ == "__main__":
    sys.exit(main())
