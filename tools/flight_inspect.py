"""Render a flight-recorder bundle into a human post-mortem timeline.

Pairs with ``mxnet_tpu.telemetry.flight``: when a trigger fires (watchdog
stall, circuit OPEN, failover, numerics anomaly, SDC suspect, preemption,
device OOM, sustained perf regression, unhandled exception, or an explicit
``flight.dump()``), the process writes a
``flight-*.json`` bundle to ``MXNET_FLIGHT_DIR``. This tool reads one from
the outside and renders what an on-call human asks first:

    # newest bundle in a directory (or give an explicit bundle path)
    python tools/flight_inspect.py /var/log/mxtpu-flight
    python tools/flight_inspect.py flight-20260805-093011-0003-failover.json

    # sections on demand
    python tools/flight_inspect.py DIR --threads     # include thread stacks
    python tools/flight_inspect.py DIR --json        # raw bundle, pretty

    # cross-process journey of one trace id: here PATH is a span-spool
    # directory (MXNET_SPAN_SPOOL_DIR), not a flight bundle — the same
    # rendering tools/trace_journey.py gives, reachable mid-post-mortem
    python tools/flight_inspect.py /tmp/spool --trace 4fa1b2c3d4e5f607

The timeline groups spans by trace id (a serving request's id survives
submit -> batch assembly -> device step, so one group is one logical
request), orders groups by first activity, and interleaves the structured
events and completed requests by wall time.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fmt_us(v):
    if v is None:
        return "?"
    v = float(v)
    if v >= 1e6:
        return f"{v / 1e6:.2f}s"
    if v >= 1e3:
        return f"{v / 1e3:.2f}ms"
    return f"{v:.0f}us"


def _fmt_bytes(v):
    v = float(v or 0)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(v) < 1024.0 or unit == "GiB":
            return f"{v:.1f}{unit}" if unit != "B" else f"{v:.0f}B"
        v /= 1024.0
    return f"{v:.1f}GiB"


def _fmt_ts(ts):
    return time.strftime("%H:%M:%S", time.localtime(ts)) + f".{int(ts % 1 * 1000):03d}"


def resolve_bundle(path):
    """An explicit bundle file, or the newest flight-*.json in a directory."""
    if os.path.isdir(path):
        bundles = sorted(
            os.path.join(path, f) for f in os.listdir(path)
            if f.startswith("flight-") and f.endswith(".json"))
        if not bundles:
            raise SystemExit(f"no flight-*.json bundles in {path}")
        return bundles[-1]
    return path


def load(path):
    with open(path) as f:
        try:
            return json.load(f)
        except json.JSONDecodeError as e:
            raise SystemExit(
                f"{path} is not a flight bundle ({e}); was it written by "
                "mxnet_tpu.telemetry.flight?") from e


def render(bundle, path="", threads=False, max_traces=50):
    lines = []
    trig = bundle.get("trigger", {})
    fp = bundle.get("fingerprint", {})
    lines.append(f"flight bundle {path or '(inline)'}")
    lines.append(f"  trigger: {trig.get('kind', '?')}  "
                 f"{trig.get('attrs', {})}")
    ts = bundle.get("ts")
    if ts:
        lines.append(f"  written: "
                     f"{time.strftime('%Y-%m-%d %H:%M:%S', time.localtime(ts))}")
    lines.append(f"  process: pid={fp.get('pid')} python={fp.get('python')} "
                 f"platform={fp.get('platform')}")
    if fp.get("argv"):
        lines.append(f"  argv: {' '.join(fp['argv'])}")

    events = bundle.get("events", [])
    if events:
        lines.append("")
        lines.append(f"== events ({len(events)}) ==")
        for ev in events:
            lines.append(f"  {_fmt_ts(ev['ts'])} {ev['kind']:<22} "
                         f"{ev.get('attrs', {})}")

    requests = bundle.get("requests", [])
    if requests:
        lines.append("")
        lines.append(f"== completed requests ({len(requests)}) ==")
        for r in requests:
            ok = "ok " if r.get("ok", True) else "FAIL"
            lines.append(f"  {_fmt_ts(r['ts'])} [{ok}] "
                         f"trace={r.get('trace_id')} "
                         f"{r.get('endpoint')}: "
                         f"{_fmt_us(r.get('latency_us'))} "
                         f"rows={r.get('rows')}"
                         + (f" error={r['error']}" if r.get("error") else ""))

    spans = bundle.get("spans", [])
    if spans:
        by_trace = {}
        for s in spans:
            by_trace.setdefault(s.get("trace_id", "?"), []).append(s)
        groups = sorted(by_trace.items(),
                        key=lambda kv: min(s.get("t0_us", 0) for s in kv[1]))
        lines.append("")
        lines.append(f"== spans: {len(spans)} in {len(by_trace)} traces "
                     f"(showing {min(len(groups), max_traces)}, "
                     "ordered by first activity) ==")
        for trace_id, group in groups[:max_traces]:
            group.sort(key=lambda s: s.get("t0_us", 0))
            t0 = group[0].get("t0_us", 0)
            lines.append(f"trace {trace_id}")
            for s in group:
                attrs = s.get("attrs") or {}
                extra = f" {attrs}" if attrs else ""
                # the CPU time of its thread, on the spans that took it
                cpu = f" (cpu {_fmt_us(s['cpu_us'])})" \
                    if s.get("cpu_us") is not None else ""
                lines.append(f"  +{(s.get('t0_us', 0) - t0) / 1e3:9.3f}ms "
                             f"{_fmt_us(s.get('dur_us')):>10}{cpu} "
                             f"{s.get('name')}{extra}")

    metrics = bundle.get("metrics", {}).get("metrics", {})
    if metrics:
        lines.append("")
        nonzero = 0
        for fam in metrics.values():
            for s in fam.get("series", []):
                if s.get("value") or s.get("count"):
                    nonzero += 1
        lines.append(f"== metrics snapshot: {len(metrics)} families, "
                     f"{nonzero} non-zero series ==")
        for name in ("mxtpu_serving_requests_total",
                     "mxtpu_serving_failovers_total",
                     "mxtpu_watchdog_stalls_total",
                     "mxtpu_numerics_anomalies_total",
                     "mxtpu_flight_events_total",
                     "mxtpu_slo_bad_total"):
            fam = metrics.get(name)
            if not fam:
                continue
            for s in fam.get("series", []):
                v = s.get("value", 0)
                if v:
                    label = ",".join(f"{k}={val}" for k, val in
                                     sorted(s.get("labels", {}).items()))
                    lines.append(f"  {name}{{{label}}} = {v:g}")
        lines.append("  (full snapshot: pipe --json into "
                     "tools/metrics_dump.py)")

    comp = bundle.get("compile_records", {})
    if comp.get("records") or comp.get("summary", {}).get("compiles"):
        s = comp.get("summary", {})
        lines.append("")
        lines.append(
            f"== compile ledger ({s.get('compiles', 0)} compiles, "
            f"{s.get('distinct_fingerprints', 0)} distinct, "
            f"{s.get('duplicates', 0)} duplicate, "
            f"dup waste {s.get('dup_waste_s', 0.0):.3f}s) ==")
        ranked = sorted(comp.get("records", []),
                        key=lambda r: r.get("lower_s", 0) + r.get("compile_s", 0),
                        reverse=True)[:15]
        for r in ranked:
            fp = (r.get("fingerprint") or "?")[:12]
            dup = " DUP" if r.get("duplicate") else ""
            key = ",".join(f"{k}={v}" for k, v in
                           sorted(r.get("key", {}).items()))
            lines.append(
                f"  {fp} {r.get('site', '?'):<14} "
                f"lower={r.get('lower_s', 0) * 1e3:8.1f}ms "
                f"compile={r.get('compile_s', 0) * 1e3:8.1f}ms{dup} [{key}]")

    mem = bundle.get("memstats", {})
    if mem.get("holders") or mem.get("devices"):
        lines.append("")
        lines.append(
            f"== memstats ({mem.get('holders_total', 0)} holders, "
            f"{_fmt_bytes(mem.get('attributed_bytes', 0))} attributed) ==")
        for dev, st in sorted(mem.get("devices", {}).items()):
            lines.append(
                f"  device {dev}: in_use={_fmt_bytes(st.get('bytes_in_use', 0))} "
                f"attributed={_fmt_bytes(st.get('attributed', 0))} "
                f"unattributed={_fmt_bytes(st.get('unattributed', 0))}")
        for h in mem.get("holders", []):
            dev = f" dev={h['device']}" if h.get("device") else ""
            lines.append(f"  {_fmt_bytes(h.get('bytes', 0)):>10}  "
                         f"peak={_fmt_bytes(h.get('peak_bytes', 0)):>10}  "
                         f"{h.get('subsystem')}/{h.get('holder')}{dev}")

    stacks = bundle.get("threads", {})
    if stacks:
        lines.append("")
        lines.append(f"== threads at trigger ({len(stacks)}) ==")
        if threads:
            for name, stack in sorted(stacks.items()):
                lines.append(f"-- {name}")
                for frame in stack:
                    lines.extend("    " + ln for ln in
                                 frame.rstrip().splitlines())
        else:
            for name in sorted(stacks):
                lines.append(f"  {name}")
            lines.append("  (--threads for full stacks)")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Render a mxnet_tpu flight-recorder bundle as a "
                    "post-mortem timeline.")
    ap.add_argument("path", help="bundle file, or a MXNET_FLIGHT_DIR "
                                 "(newest bundle wins)")
    ap.add_argument("--json", action="store_true",
                    help="emit the raw bundle JSON, pretty-printed")
    ap.add_argument("--threads", action="store_true",
                    help="include full thread stacks in the rendering")
    ap.add_argument("--max-traces", type=int, default=50,
                    help="max trace groups to render (default 50)")
    ap.add_argument("--trace", metavar="ID", default=None,
                    help="treat PATH as a MXNET_SPAN_SPOOL_DIR and render "
                         "this trace id's cross-process journey")
    args = ap.parse_args(argv)

    if args.trace:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        try:
            import trace_journey
        finally:
            sys.path.pop(0)
        from mxnet_tpu import telemetry
        hops = telemetry.journey(args.trace, args.path)
        if args.json:
            print(json.dumps({"trace_id": args.trace, "hops": hops},
                             indent=1, sort_keys=True))
        else:
            print(trace_journey.render_journey(args.trace, hops))
        return 0 if hops else 1

    path = resolve_bundle(args.path)
    bundle = load(path)
    if args.json:
        print(json.dumps(bundle, indent=1, sort_keys=True))
        return 0
    print(render(bundle, path=path, threads=args.threads,
                 max_traces=args.max_traces))
    return 0


if __name__ == "__main__":
    sys.exit(main())
