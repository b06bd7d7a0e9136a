#!/usr/bin/env python
"""Launch a distributed mxnet_tpu job (parity: tools/launch.py:1-135 over the
dmlc-core tracker).

TPU-native mapping: there are no parameter-server processes — sync SGD is
allreduce-native over jax.distributed — so ``-s`` is accepted for CLI parity
but ignored. The ``local`` launcher spawns ``-n`` worker processes on this
machine and wires the jax.distributed coordinator through environment
variables (MXNET_TPU_COORDINATOR / MXNET_TPU_NUM_WORKERS / MXNET_TPU_WORKER_ID,
the DMLC_PS_ROOT_URI / DMLC_NUM_WORKER / DMLC_ROLE analog) which
``mxnet_tpu.parallel.initialize_distributed()`` — and any ``dist_*`` kvstore —
reads at startup. On real multi-host TPU pods the runtime provides its own
launcher; this tool covers local multi-process runs on the CPU
(``JAX_PLATFORMS=cpu``: tests, CPU simulation). Do not run it on a machine
with chips: it gives no worker a chip of its own, so every worker would reach
for all of them, and a chip belongs to one process. There one process drives
all the chips of a host through a mesh (``chip_smoke.py --chips 4``).

Usage:
    python tools/launch.py -n 2 [--launcher local] [--env K=V ...] CMD...
"""
import argparse
import os
import signal
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch_local(num_workers, command, extra_env=(), port=None):
    """Spawn num_workers local processes; returns the max exit code."""
    port = port or _free_port()
    procs = []
    for wid in range(num_workers):
        env = dict(os.environ)
        env["MXNET_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
        env["MXNET_TPU_NUM_WORKERS"] = str(num_workers)
        env["MXNET_TPU_WORKER_ID"] = str(wid)
        # DMLC-compatible names so scripts written for the reference read
        # sensible values
        env["DMLC_NUM_WORKER"] = str(num_workers)
        env["DMLC_ROLE"] = "worker"
        for kv in extra_env:
            k, _, v = kv.partition("=")
            env[k] = v
        procs.append(subprocess.Popen(command, env=env))

    def _kill(signum, frame):
        for p in procs:
            if p.poll() is None:
                p.terminate()
        sys.exit(1)

    prev = signal.signal(signal.SIGINT, _kill)
    try:
        codes = [p.wait() for p in procs]
    finally:
        signal.signal(signal.SIGINT, prev)
    # signal deaths are negative returncodes; any nonzero is failure
    return 0 if all(c == 0 for c in codes) else 1


def main():
    parser = argparse.ArgumentParser(description="Launch a distributed job")
    parser.add_argument("-n", "--num-workers", required=True, type=int,
                        help="number of worker processes to launch")
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="accepted for parity; allreduce needs no servers")
    parser.add_argument("--launcher", default="local",
                        choices=["local"],
                        help="only 'local' is meaningful on TPU (pods use the "
                             "platform launcher)")
    parser.add_argument("--env", action="append", default=[],
                        help="extra K=V environment for every worker")
    parser.add_argument("-p", "--port", type=int, default=None,
                        help="coordinator port (default: pick a free one)")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="command to run on every worker")
    args = parser.parse_args()
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        parser.error("no command given")
    if args.num_servers:
        print("note: -s ignored — allreduce over jax.distributed has no "
              "server processes", file=sys.stderr)
    sys.exit(launch_local(args.num_workers, args.command, args.env, args.port))


if __name__ == "__main__":
    main()
