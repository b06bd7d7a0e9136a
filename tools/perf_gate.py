"""Release perf gate: measure the standard benchmarks, compare to budgets.

``PERF_BUDGETS.json`` (committed at the repo root) is the perf contract:
one entry per standardized metric with a budget value, a direction
(``min`` = throughput floor, ``max`` = latency ceiling) and a tolerance
band wide enough to absorb shared-CI jitter. This tool measures the
metrics and enforces the contract:

    # measure + report only (no gating)
    python tools/perf_gate.py

    # CI gate: rc 0 when every metric is inside its band, 1 on any
    # violation or missing measurement, 2 on a broken budgets file
    python tools/perf_gate.py --check

    # fast CI self-test: validate the budgets schema and the gate logic
    # on canned numbers; runs no real benchmark (sub-second)
    python tools/perf_gate.py --check --smoke

    # also record the run as the next BENCH_rNN.json at the repo root
    python tools/perf_gate.py --check --write-bench

Measurement sources (selectable with ``--only``):

  bench     bench.py in a subprocess under the canonical env pinned inside
            PERF_BUDGETS.json["env"]; metrics are its "summary": true rows
  loadgen   benchmark/serving_loadgen.py likewise; per-concurrency
            ``serving_img_s_c<N>`` / ``serving_p99_ms_c<N>`` plus the
            compile-ledger rollup
  eager     in-process p95 eager-dispatch probe (the
            test_eager_latency.py gate, expressed as a budget)
  restart   serving_loadgen.py --restart --fabric in a subprocess: warm
            restart-to-first-request seconds (the executable-cache
            elasticity contract — a warm process must compile nothing,
            including the mesh-sharded fabric endpoint's bucket
            executables)
  fabric    benchmark/fabric_scaling.py in a subprocess: the sharded-
            serving scaling sweep's top-slice served throughput
            (``fabric_sharded_img_s``), valid only when every slice size
            served bitwise-equal to the single-chip reference
  tailguard serving_loadgen.py --hedge --storm in a subprocess: the
            tail-tolerance contract rows — hedged duplicate work stays
            under its token-bucket ceiling (``hedge_wasted_work_pct``)
            and a retry storm reaches zero clients
            (``storm_client_error_rate``, budget 0: the retry budget
            must absorb every injected drop)

Exit status mirrors tools/mxlint.py --check: 0 clean, 1 findings,
2 operational error.
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_BUDGETS = os.path.join(REPO, "PERF_BUDGETS.json")
_SOURCES = ("bench", "loadgen", "eager", "restart", "fabric", "tailguard")


# ---------------------------------------------------------------------------
# budgets schema
# ---------------------------------------------------------------------------

def validate_budgets(budgets):
    """Schema errors in a PERF_BUDGETS dict (empty list = valid)."""
    errs = []
    if not isinstance(budgets, dict):
        return ["budgets root must be an object"]
    if budgets.get("schema") != 1:
        errs.append(f"unsupported schema: {budgets.get('schema')!r}")
    env = budgets.get("env", {})
    if not isinstance(env, dict) or \
            not all(isinstance(k, str) and isinstance(v, str)
                    for k, v in env.items()):
        errs.append("env must map str -> str")
    metrics = budgets.get("metrics")
    if not isinstance(metrics, dict) or not metrics:
        errs.append("metrics must be a non-empty object")
        return errs
    for name, m in metrics.items():
        where = f"metrics[{name!r}]"
        if not isinstance(m, dict):
            errs.append(f"{where} must be an object")
            continue
        budget = m.get("budget")
        if not isinstance(budget, (int, float)) or budget < 0 or \
                (budget == 0 and m.get("direction") != "max"):
            errs.append(f"{where}.budget must be a positive number "
                        "(or zero for a max-direction ceiling)")
        tol = m.get("tolerance")
        if not isinstance(tol, (int, float)) or not 0 <= tol < 1:
            errs.append(f"{where}.tolerance must be in [0, 1)")
        if m.get("direction") not in ("min", "max"):
            errs.append(f"{where}.direction must be 'min' or 'max'")
        if m.get("source") not in _SOURCES:
            errs.append(f"{where}.source must be one of {_SOURCES}")
    return errs


def load_budgets(path):
    try:
        with open(path) as f:
            budgets = json.load(f)
    except (OSError, ValueError) as e:
        raise SystemExit(f"perf_gate: cannot read budgets {path}: {e}")
    errs = validate_budgets(budgets)
    if errs:
        for e in errs:
            print(f"perf_gate: budgets schema: {e}", file=sys.stderr)
        raise SystemExit(2)
    return budgets


# ---------------------------------------------------------------------------
# gate logic (pure: canned numbers in tests / --smoke)
# ---------------------------------------------------------------------------

def gate(budgets, measured):
    """Compare ``measured`` {metric: value} against the budgets.

    Returns a list of per-metric verdicts. ``min`` direction fails below
    ``budget * (1 - tolerance)``; ``max`` fails above
    ``budget * (1 + tolerance)``. A budgeted metric with no measurement is
    a failure (the gate must not silently pass on a broken bench).
    """
    out = []
    for name, m in sorted(budgets["metrics"].items()):
        budget, tol = float(m["budget"]), float(m["tolerance"])
        bound = budget * (1.0 - tol) if m["direction"] == "min" \
            else budget * (1.0 + tol)
        v = measured.get(name)
        if v is None:
            out.append({"metric": name, "ok": False, "measured": None,
                        "budget": budget, "bound": round(bound, 4),
                        "direction": m["direction"],
                        "error": "not measured"})
            continue
        ok = v >= bound if m["direction"] == "min" else v <= bound
        # a zero ceiling has no relative headroom: report the absolute
        # overshoot instead of dividing by the bound
        margin = round((v / bound - 1.0) * 100.0, 1) if bound \
            else round(float(v), 4)
        out.append({"metric": name, "ok": bool(ok),
                    "measured": round(float(v), 4), "budget": budget,
                    "bound": round(bound, 4), "direction": m["direction"],
                    "margin": margin})
    return out


# ---------------------------------------------------------------------------
# measurement sources
# ---------------------------------------------------------------------------

def _run(cmd, env_extra):
    env = dict(os.environ)
    env.update(env_extra)       # the budgets file pins the platform
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True)
    return proc.returncode, proc.stdout, proc.stderr


def _json_lines(text):
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            yield json.loads(line)
        except ValueError:
            continue


def measure_bench(env):
    """bench.py summary rows -> {metric: value}; also returns the raw run
    for BENCH_rNN.json."""
    cmd = [sys.executable, "bench.py"]
    rc, out, err = _run(cmd, env)
    measured = {}
    for row in _json_lines(out):
        if "metric" in row and isinstance(row.get("value"), (int, float)):
            # summary rows re-emit the same measurement; either wins
            measured[row["metric"]] = float(row["value"])
    return measured, {"cmd": " ".join(cmd), "rc": rc, "stdout": out,
                      "stderr": err[-2000:]}


def measure_loadgen(env):
    """serving_loadgen rows -> serving_img_s_c<N> / serving_p99_ms_c<N>,
    the generative-phase decode_tok_s_chip / decode_intertoken_p99_ms
    (emitted when the env pins SLG_DECODE=1), plus the compile-ledger
    rollup fields."""
    cmd = [sys.executable, os.path.join("benchmark", "serving_loadgen.py")]
    rc, out, err = _run(cmd, env)
    measured = {}
    for row in _json_lines(out):
        if "conc" in row and "img_s" in row and "tenant" not in row:
            c = row["conc"]
            measured[f"serving_img_s_c{c}"] = float(row["img_s"])
            for q in ("p95", "p99"):
                if row.get(f"{q}_ms") is not None:
                    measured[f"serving_{q}_ms_c{c}"] = float(row[f"{q}_ms"])
        if row.get("decode") and "tok_s_chip" in row and "tenant" not in row:
            measured["decode_tok_s_chip"] = float(row["tok_s_chip"])
            if row.get("intertoken_p99_ms") is not None:
                measured["decode_intertoken_p99_ms"] = \
                    float(row["intertoken_p99_ms"])
            measured["decode_kv_occupancy_peak"] = \
                float(row.get("kv_occupancy_peak", 0.0))
        if "compile_ledger" in row:
            cl = row["compile_ledger"]
            measured["serving_compile_dup_waste_s"] = float(
                cl.get("dup_waste_s", 0.0))
    return measured, {"cmd": " ".join(cmd), "rc": rc, "stdout": out,
                      "stderr": err[-2000:]}


def measure_restart(env):
    """serving_loadgen --restart final row -> restart_to_first_request_s
    (the warm phase; the loadgen parent already asserted zero fresh
    compiles and bitwise-equal first-request outputs, so a row at all
    means the correctness half of the contract held)."""
    cmd = [sys.executable, os.path.join("benchmark", "serving_loadgen.py"),
           "--restart", "--fabric"]
    rc, out, err = _run(cmd, env)
    measured = {}
    for row in _json_lines(out):
        # the summary row: restart_to_first_request_s without the
        # per-phase "restart"/"restart_child" tags
        if "restart_to_first_request_s" in row and "restart" not in row \
                and "restart_child" not in row:
            measured["restart_to_first_request_s"] = \
                float(row["restart_to_first_request_s"])
    return measured, {"cmd": " ".join(cmd), "rc": rc, "stdout": out,
                      "stderr": err[-2000:]}


def measure_fabric(env):
    """benchmark/fabric_scaling.py summary row -> fabric_sharded_img_s
    (the largest slice's served throughput). The metric is only reported
    when the sweep's own acceptance held — every slice size bitwise-equal
    to the single-chip reference with zero client errors — so a numerics
    or reliability break gates as 'not measured'."""
    cmd = [sys.executable, os.path.join("benchmark", "fabric_scaling.py")]
    rc, out, err = _run(cmd, env)
    measured = {}
    for row in _json_lines(out):
        if row.get("summary") and row.get("ok") \
                and row.get("fabric_sharded_img_s") is not None:
            measured["fabric_sharded_img_s"] = \
                float(row["fabric_sharded_img_s"])
    return measured, {"cmd": " ".join(cmd), "rc": rc, "stdout": out,
                      "stderr": err[-2000:]}


def measure_tailguard(env):
    """serving_loadgen --hedge --storm tailguard rows ->
    hedge_wasted_work_pct / storm_client_error_rate. Both phases embed
    their own correctness oracles (bitwise outputs, bounded hedge volume,
    drop volume under the retry-budget floor), so the parsed numbers are
    the residual perf contract: duplicate work stays under the
    token-bucket ceiling and the storm never reaches a client. Skips the
    image sweep and the decode phase — only the tailguard phases run."""
    tg_env = dict(env)
    tg_env["SLG_DECODE"] = "0"
    cmd = [sys.executable, os.path.join("benchmark", "serving_loadgen.py"),
           "--dtypes", "none", "--hedge", "--storm"]
    rc, out, err = _run(cmd, tg_env)
    measured = {}
    for row in _json_lines(out):
        if row.get("tailguard") == "hedge" \
                and row.get("hedge_wasted_work_pct") is not None:
            measured["hedge_wasted_work_pct"] = \
                float(row["hedge_wasted_work_pct"])
        if row.get("tailguard") == "storm" \
                and row.get("storm_client_error_rate") is not None:
            measured["storm_client_error_rate"] = \
                float(row["storm_client_error_rate"])
    return measured, {"cmd": " ".join(cmd), "rc": rc, "stdout": out,
                      "stderr": err[-2000:]}


def measure_eager():
    """p95 eager dispatch (us) over the representative op set, best of 3
    windows — the test_eager_latency gate as a number."""
    import numpy as onp
    import mxnet_tpu as mx
    x = mx.nd.array(onp.random.rand(64, 64).astype("float32"))
    y = mx.nd.array(onp.random.rand(64, 64).astype("float32"))
    ops = (lambda: mx.nd.exp(x), lambda: mx.nd.broadcast_add(x, y),
           lambda: mx.nd.sum(x, axis=1))
    worst = 0.0
    for f in ops:
        for _ in range(30):
            f()
        best_p95 = None
        for _ in range(3):
            ts = []
            for _ in range(300):
                t0 = time.perf_counter_ns()
                f()
                ts.append(time.perf_counter_ns() - t0)
            ts.sort()
            p95 = ts[int(len(ts) * 0.95)] / 1e3
            best_p95 = p95 if best_p95 is None else min(best_p95, p95)
        worst = max(worst, best_p95)
    return {"eager_dispatch_p95_us": round(worst, 1)}


# ---------------------------------------------------------------------------
# BENCH_rNN.json
# ---------------------------------------------------------------------------

def next_bench_path():
    n = 0
    for name in os.listdir(REPO):
        m = re.match(r"BENCH_r(\d+)\.json$", name)
        if m:
            n = max(n, int(m.group(1)))
    return os.path.join(REPO, f"BENCH_r{n + 1:02d}.json"), n + 1


def write_bench_file(bench_run, measured):
    path, n = next_bench_path()
    tail = "\n".join(bench_run.get("stdout", "").splitlines()[-12:])
    with open(path, "w") as f:
        json.dump({"n": n, "cmd": bench_run.get("cmd", ""),
                   "rc": bench_run.get("rc", 0), "tail": tail + "\n",
                   "parsed": measured}, f, indent=2)
        f.write("\n")
    return path


# ---------------------------------------------------------------------------
# smoke mode
# ---------------------------------------------------------------------------

def smoke(budgets):
    """No benchmarks: prove the budgets file parses/validates and the gate
    logic distinguishes pass from fail on canned numbers."""
    # pass case: every metric measured exactly at budget
    canned = {name: float(m["budget"])
              for name, m in budgets["metrics"].items()}
    results = gate(budgets, canned)
    if not all(r["ok"] for r in results):
        print("perf_gate: smoke: at-budget values must pass",
              file=sys.stderr)
        return None
    # fail case: every metric well out of band in its bad direction
    # (+1 keeps zero-budget ceilings out of band too)
    bad = {name: float(m["budget"]) * 0.25 if m["direction"] == "min"
           else float(m["budget"]) * 4.0 + 1.0
           for name, m in budgets["metrics"].items()}
    if not all(not r["ok"] for r in gate(budgets, bad)):
        print("perf_gate: smoke: out-of-band values must fail",
              file=sys.stderr)
        return None
    # missing-measurement case must fail too
    if gate(budgets, {})[0]["ok"]:
        print("perf_gate: smoke: missing measurements must fail",
              file=sys.stderr)
        return None
    return results


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Measure the standard benchmarks and gate them against "
                    "PERF_BUDGETS.json.")
    ap.add_argument("--budgets", default=DEFAULT_BUDGETS)
    ap.add_argument("--check", action="store_true",
                    help="gate mode: nonzero exit on any violation")
    ap.add_argument("--smoke", action="store_true",
                    help="no real benchmarks: schema validation + gate "
                         "logic on canned numbers")
    ap.add_argument("--only", default="",
                    help="comma subset of sources to run "
                         f"(default: all of {','.join(_SOURCES)})")
    ap.add_argument("--write-bench", action="store_true",
                    help="record this run as the next BENCH_rNN.json")
    args = ap.parse_args(argv)

    budgets = load_budgets(args.budgets)

    if args.smoke:
        results = smoke(budgets)
        if results is None:
            return 1
        for r in results:
            print(json.dumps({**r, "smoke": True}))
        print(json.dumps({"perf_gate": "smoke", "metrics": len(results),
                          "ok": True}))
        return 0

    sources = [s.strip() for s in args.only.split(",") if s.strip()] \
        if args.only else list(_SOURCES)
    for s in sources:
        if s not in _SOURCES:
            raise SystemExit(f"perf_gate: unknown source {s!r}")
    wanted = {m["source"] for m in budgets["metrics"].values()}
    env = {str(k): str(v) for k, v in budgets.get("env", {}).items()}

    measured = {}
    bench_run = {}
    if "bench" in sources and "bench" in wanted:
        vals, bench_run = measure_bench(env)
        measured.update(vals)
    if "loadgen" in sources and "loadgen" in wanted:
        vals, _ = measure_loadgen(env)
        measured.update(vals)
    if "restart" in sources and "restart" in wanted:
        vals, _ = measure_restart(env)
        measured.update(vals)
    if "fabric" in sources and "fabric" in wanted:
        vals, _ = measure_fabric(env)
        measured.update(vals)
    if "tailguard" in sources and "tailguard" in wanted:
        vals, _ = measure_tailguard(env)
        measured.update(vals)
    # last: the one in-process source. Until here this parent has not
    # touched JAX, so no child found its device taken
    if "eager" in sources and "eager" in wanted:
        measured.update(measure_eager())

    # metrics whose source was excluded by --only are reported, not gated
    gated_budgets = {
        "schema": 1, "env": env,
        "metrics": {k: v for k, v in budgets["metrics"].items()
                    if v["source"] in sources}}
    if not gated_budgets["metrics"]:
        raise SystemExit("perf_gate: --only excluded every budgeted metric")
    results = gate(gated_budgets, measured)
    violations = [r for r in results if not r["ok"]]
    for r in results:
        print(json.dumps(r))
    print(json.dumps({"perf_gate": "check" if args.check else "report",
                      "metrics": len(results),
                      "violations": len(violations)}))

    if args.write_bench and bench_run:
        path = write_bench_file(bench_run, measured)
        print(json.dumps({"bench_file": os.path.relpath(path, REPO)}))

    if args.check and violations:
        for r in violations:
            print(f"perf_gate: FAIL {r['metric']}: measured "
                  f"{r['measured']} vs bound {r['bound']} "
                  f"({r['direction']} budget {r['budget']})",
                  file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
