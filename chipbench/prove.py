"""Prove one cell in one chip call:

    python -m chipbench.prove --workload <cell> --sets 2 --runs 6 --seconds 20

runs the cell's processes one after another (this parent never touches JAX, so
each child has the chip to itself and all share the compile cache): one cold
run that compiles, then ``--sets`` sets of ``--runs`` untraced runs with the
same seeds in every set, then one traced run. Writes
``chiprun_out/prove_<cell>.json`` (every run's last line and wall, and per
set each metric's median and quartile spread) and the children's whole output
to ``chiprun_out/prove_<cell>.log``.
"""
import argparse
import json
import os
import subprocess
import sys
import time

from .stats import median, quartile_spread

OUT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chiprun_out")


def run_once(workload, seed, seconds, trace, log, extra_env=None):
    cmd = [sys.executable, "-m", "chipbench", "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          env={**os.environ, **(extra_env or {})})
    wall = time.perf_counter() - t0
    log.write(f"\n$ {' '.join(cmd)}  # rc {proc.returncode}, {wall:.1f} s\n")
    log.write(proc.stdout)
    log.write(proc.stderr[-4000:])
    log.flush()
    last = None
    lines = proc.stdout.strip().splitlines()
    if proc.returncode == 0 and lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            pass
    return {"seed": seed, "trace": trace, "rc": proc.returncode,
            "wall_s": wall, "result": last, "earlier": lines[:-1][-6:]}


def values_of(runs, metric):
    return [r["result"]["metrics"][metric]["value"] for r in runs
            if r["result"] and metric in r["result"]["metrics"]]


def summarise(runs):
    """{metric: {median, spread, values}} over one set's runs."""
    names = sorted({m for r in runs if r["result"]
                    for m in r["result"]["metrics"]})
    out = {}
    for name in names:
        vals = values_of(runs, name)
        out[name] = {"median": median(vals), "spread": quartile_spread(vals),
                     "values": vals}
    return out


def main(argv=None):
    p = argparse.ArgumentParser(prog="python -m chipbench.prove")
    p.add_argument("--workload", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--seconds", type=float, default=20)
    p.add_argument("--seed0", type=int, default=2147483777,
                   help="first seed; the driver's are large, so is this")
    p.add_argument("--cold", type=int, default=1,
                   help="1: a first run apart from the sets, which compiles")
    p.add_argument("--traced", type=int, default=1)
    args = p.parse_args(argv)

    os.makedirs(OUT_DIR, exist_ok=True)
    stem = os.path.join(OUT_DIR, f"prove_{args.workload}")
    report = {"workload": args.workload, "seconds": args.seconds,
              "cold": None, "sets": [], "traced": None}
    with open(stem + ".log", "w") as log:
        if args.cold:
            report["cold"] = run_once(args.workload, args.seed0, args.seconds,
                                      0, log)
            if report["cold"]["result"] is None:
                # a cell that does not run costs its whole proof in chip time
                print(f"the cold run printed no result: see {stem}.log",
                      file=sys.stderr)
                args.sets = args.traced = 0
        for _ in range(args.sets):
            runs = [run_once(args.workload, args.seed0 + i, args.seconds, 0,
                             log) for i in range(args.runs)]
            report["sets"].append({"runs": runs, "summary": summarise(runs)})
        if args.traced:
            report["traced"] = run_once(
                args.workload, args.seed0, args.seconds, 1, log,
                {"CHIPBENCH_TRACE_EXCERPT": stem + ".trace_excerpt.json"})
    widest = {}
    for s in report["sets"]:
        for name, row in s["summary"].items():
            if row["spread"] is not None:
                widest[name] = max(widest.get(name, 0.0), row["spread"])
    report["widest_spread"] = widest
    with open(stem + ".json", "w") as f:
        json.dump(report, f, indent=1)

    # what the chip call's tail shows
    every = ([report["cold"]] if report["cold"] else []) + \
        [r for s in report["sets"] for r in s["runs"]] + \
        ([report["traced"]] if report["traced"] else [])
    for r in every:
        res = r["result"] or {}
        print(json.dumps({
            "seed": r["seed"], "trace": r["trace"], "rc": r["rc"],
            "wall_s": round(r["wall_s"], 1), "correct": res.get("correct"),
            "failed": res.get("failed"),
            "metrics": {k: v["value"] for k, v in
                        res.get("metrics", {}).items()},
            "device": res.get("device")}))
    for i, s in enumerate(report["sets"]):
        print(json.dumps({"set": i, **{k: {"median": v["median"],
                                            "spread": v["spread"]}
                                       for k, v in s["summary"].items()}}))
    print(json.dumps({"widest_spread": widest}))
    bad = [r for r in every if r["rc"] != 0 or not (r["result"] or {})
           .get("correct")]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
