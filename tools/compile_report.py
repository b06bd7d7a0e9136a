"""Render a compile-ledger directory into the recompile post-mortem.

Pairs with ``mxnet_tpu.telemetry.compile_ledger``: every AOT compile site
(serving bucket executables, ParallelTrainStep autoformat, the eager jit
cache when instrumented) appends one CompileRecord per compile to
``MXNET_COMPILE_LEDGER_DIR/ledger-<pid>.jsonl``. This tool reads the whole
directory — every process that shared it — and answers the questions a
recompile storm raises:

    python tools/compile_report.py /var/log/mxtpu-ledger
    python tools/compile_report.py            # $MXNET_COMPILE_LEDGER_DIR
    python tools/compile_report.py DIR --top 30
    python tools/compile_report.py DIR --json # machine-readable rollup

  * where did the wall time go — top-N records by lower+compile seconds;
  * what was wasted — fingerprints compiled more than once, ranked by the
    seconds re-spent on them (the win a persistent executable cache keyed
    by StableHLO hash would bank);
  * what is the hardware doing — flops vs bytes-accessed ratios per record
    where the backend's cost_analysis() reported them (low flops/byte =
    memory-bound, the program to fuse first).

A ledger written by an older build may hold ``kind="step"`` lines (measured
step wall, no compile; hlolint's committed corpus of records does): they
are left out of the rollup.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fmt_s(v):
    return f"{v * 1e3:.1f}ms" if v < 1.0 else f"{v:.2f}s"


def rollup(records):
    """Aggregate a record list into the report dict (also the --json body).
    An older ledger's ``kind="step"`` records carry no compile wall and
    are excluded up front."""
    records = [r for r in records if r.get("kind") != "step"]
    sites = {}
    by_fp = {}
    cache_hits = 0
    cache_hit_s = 0.0
    for r in records:
        site = r.get("site", "?")
        st = sites.setdefault(site, {"n": 0, "dup": 0, "hit": 0,
                                     "wall_s": 0.0})
        wall = float(r.get("lower_s", 0.0)) + float(r.get("compile_s", 0.0))
        st["n"] += 1
        st["dup"] += 1 if r.get("duplicate") else 0
        st["hit"] += 1 if r.get("cache_hit") else 0
        st["wall_s"] += wall
        if r.get("cache_hit"):
            # a hit pays lower + deserialize, never an XLA compile: it is
            # neither a duplicate nor waste, count it separately
            cache_hits += 1
            cache_hit_s += wall
        fp = r.get("fingerprint")
        if fp and not r.get("cache_hit"):
            f = by_fp.setdefault(fp, {"n": 0, "wall_s": 0.0, "sites": set(),
                                      "first_key": r.get("key", {})})
            f["n"] += 1
            f["wall_s"] += wall
            f["sites"].add(site)
    dup_fps = {fp: f for fp, f in by_fp.items() if f["n"] > 1}
    # waste = everything after the first compile of each fingerprint
    waste_s = sum(f["wall_s"] * (f["n"] - 1) / f["n"]
                  for f in dup_fps.values())
    for f in by_fp.values():
        f["sites"] = sorted(f["sites"])
    total_wall = sum(st["wall_s"] for st in sites.values())
    return {
        "records": len(records),
        "distinct_fingerprints": len(by_fp),
        "duplicate_fingerprints": len(dup_fps),
        "wall_s": round(total_wall, 3),
        "dup_waste_s": round(waste_s, 3),
        "cache_hits": cache_hits,
        "cache_hit_s": round(cache_hit_s, 3),
        "cache_hit_rate": round(cache_hits / len(records), 4)
        if records else None,
        "sites": {k: {"n": v["n"], "dup": v["dup"], "hit": v["hit"],
                      "wall_s": round(v["wall_s"], 3)}
                  for k, v in sorted(sites.items())},
        "dup_fingerprints": {
            fp: {"n": f["n"], "wall_s": round(f["wall_s"], 3),
                 "sites": f["sites"], "first_key": f["first_key"]}
            for fp, f in sorted(dup_fps.items(),
                                key=lambda kv: kv[1]["wall_s"],
                                reverse=True)},
    }


def render(records, top=20):
    agg = rollup(records)
    lines = [f"compile report: {agg['records']} records, "
             f"{agg['distinct_fingerprints']} distinct programs, "
             f"wall {_fmt_s(agg['wall_s'])}"]
    lines.append(f"  duplicate waste: {agg['duplicate_fingerprints']} "
                 f"programs recompiled, {_fmt_s(agg['dup_waste_s'])} "
                 "re-spent (a persistent executable cache saves this)")
    if agg["cache_hits"]:
        lines.append(f"  executable cache: {agg['cache_hits']} compiles "
                     f"served from the store in {_fmt_s(agg['cache_hit_s'])} "
                     f"(hit rate {agg['cache_hit_rate']:.1%} of records)")
    lines.append("")
    lines.append("== per site ==")
    for site, st in agg["sites"].items():
        lines.append(f"  {site:<16} n={st['n']:<5} dup={st['dup']:<5} "
                     f"hit={st['hit']:<5} wall={_fmt_s(st['wall_s'])}")

    ranked = sorted((r for r in records if r.get("kind") != "step"),
                    key=lambda r: r.get("lower_s", 0) + r.get("compile_s", 0),
                    reverse=True)[:top]
    if ranked:
        lines.append("")
        lines.append(f"== top {len(ranked)} by wall seconds ==")
        for r in ranked:
            fp = (r.get("fingerprint") or "?")[:12]
            flops = r.get("flops")
            ba = r.get("bytes_accessed")
            ratio = f" flops/byte={flops / ba:7.2f}" if flops and ba else ""
            dup = " DUP" if r.get("duplicate") else ""
            dup += " HIT" if r.get("cache_hit") else ""
            key = ",".join(f"{k}={v}" for k, v in
                           sorted(r.get("key", {}).items()))
            lines.append(
                f"  {fp} {r.get('site', '?'):<14} pid={r.get('pid', '?'):<7} "
                f"lower={_fmt_s(r.get('lower_s', 0)):>8} "
                f"compile={_fmt_s(r.get('compile_s', 0)):>8}"
                f"{ratio}{dup} [{key}]")

    if agg["dup_fingerprints"]:
        lines.append("")
        lines.append(f"== recompiled programs "
                     f"({len(agg['dup_fingerprints'])}) ==")
        for fp, f in list(agg["dup_fingerprints"].items())[:top]:
            key = ",".join(f"{k}={v}" for k, v in
                           sorted(f["first_key"].items()))
            lines.append(f"  {fp[:12]} x{f['n']} wall={_fmt_s(f['wall_s'])} "
                         f"sites={'/'.join(f['sites'])} [{key}]")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Render a mxnet_tpu compile-ledger directory "
                    "(ledger-*.jsonl) into a recompile report.")
    ap.add_argument("dir", nargs="?", default="",
                    help="ledger directory (default: "
                         "$MXNET_COMPILE_LEDGER_DIR)")
    ap.add_argument("--top", type=int, default=20,
                    help="rows in the ranked tables (default 20)")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable rollup instead")
    args = ap.parse_args(argv)

    from mxnet_tpu.telemetry import compile_ledger
    d = args.dir or compile_ledger.ledger_dir()
    if not d:
        raise SystemExit("no ledger directory: pass one or set "
                         "MXNET_COMPILE_LEDGER_DIR")
    records = compile_ledger.read_ledger(d)
    if not records:
        raise SystemExit(f"no ledger-*.jsonl records under {d}")
    if args.json:
        print(json.dumps(rollup(records), indent=1, sort_keys=True))
        return 0
    print(render(records, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
