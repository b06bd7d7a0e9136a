"""Shared by the readers that take the program's own spans
(``mxnet_tpu.telemetry.span``) from the flight recorder's ring, where every
finished span of the process lies with its ``perf_counter`` start and
duration in microseconds, its parent and its attrs.

Two things are decided here. Which spans ran with the profiler off: those
that began once set-up was done (the first requests of a closed loop arrive
all at once and wait a second; a first dispatch compiles) and ended before
the traced window opened. And, for the decode loop, where a ring span lies
on the device trace's clock. ``ProfileData`` counts its nanoseconds from the
start of the profile session (PERF.md, PR 25), and the trace as the harness
loads it keeps neither that start nor the program's own annotations, so the
offset between the two clocks is taken from events that both sides hold: the
loop's thread launches every XLA module the chip runs, one at a time and in
order, so the modules of the trace are a run of consecutive ``decode.launch``
spans. The run is found by its rhythm: the lags from launch to module start
spread least over the true run. One launch that the profiler's start stalls
(the window's first: 51 ms against 9, PR 26) made the true run read 51.7 ms
and the run shifted by one call 51.6, so the spread leaves out the largest
lag, and of the few runs that spread least the first that leaves an interval
is taken. Then each launch must start before
its module does and each ``decode.fetch`` must end after its module has: the
offsets that allow both are an interval, and the join is its middle. On the
chip, under the profiler, a module starts 4 to 11 ms after its launch began
and the fetch returns 1.2 to 2.7 ms after the module ended, so the interval
is 6 to 7 ms wide and its middle 0.7 to 2.4 ms from the truth (two runs of PR
25, against the XPlane's own annotations): enough to lay passes of 70 to 100
ms over idle gaps.
Where there is no such interval (a wrong run leaves none), or it is wider
than MAX_LAG_NS, or the median lag from launch to module start is longer
than that, there is no join and what would rest on it is not reported.

A program without these spans (an older commit) gives empty lists and every
reader returns None.
"""
import bisect
import statistics

from chipbench import T0, xplane

MIN_SPANS = 10               # fewer profiler-off samples than this: None
MAX_LAG_NS = 20e6            # launch start -> module start: the median, and
                             # the width the offset is known to; twice what
                             # the chip showed under the profiler
STALLED = 1                  # launches left out of a run's spread of lags:
                             # ``start_trace`` stalls the window's first
BEST_RUNS = 3                # runs tried, in order of spread, for one that
                             # leaves an interval


def ring(prefix):
    """The ring's finished spans whose name starts with ``prefix``, oldest
    first, times in nanoseconds on ``perf_counter``'s clock: dicts with
    ``name``, ``start``, ``end``, ``span_id``, ``parent_id``, ``attrs``."""
    from mxnet_tpu.telemetry import flight
    out = [{"name": e["name"], "start": e["t0_us"] * 1e3,
            "end": (e["t0_us"] + e["dur_us"]) * 1e3, "span_id": e["span_id"],
            "parent_id": e["parent_id"], "attrs": e["attrs"]}
           for e in flight.recent_spans() if e["name"].startswith(prefix)]
    out.sort(key=lambda s: s["start"])
    return out


def _launches(spans):
    """[(launch start, fetch end)...] per executable call, in order: a
    ``decode.launch`` and the ``decode.fetch`` under the same parent."""
    fetch_end = {s["parent_id"]: s["end"] for s in spans
                 if s["name"] == "decode.fetch"}
    return [(s["start"], fetch_end[s["parent_id"]]) for s in spans
            if s["name"] == "decode.launch" and s["parent_id"] in fetch_end]


def clock_join(run, spans):
    """``{"offset_ns", "width_ns", "median_lag_ns", "modules"}`` where
    trace time = ring time + ``offset_ns``, or None (see the module's
    docstring). ``spans`` is ``ring("decode.")``."""
    trace = run.get("trace")
    if not trace:
        return None
    dev = trace["devices"][min(trace["devices"])]
    modules = sorted((start, start + dur) for _, start, dur in dev["modules"])
    calls = _launches(spans)
    n = len(modules)
    if n < 3 or len(calls) < n:
        return None
    # the run of n consecutive launches whose starts keep the modules' rhythm
    spreads = []
    for k in range(len(calls) - n + 1):
        lags = sorted(m[0] - c[0] for m, c in zip(modules, calls[k:k + n]))
        spreads.append((lags[-1 - STALLED] - lags[0], k))
    for _, k in sorted(spreads)[:BEST_RUNS]:
        join = _interval(modules, calls[k:k + n])
        if join is not None:
            return join
    return None


def _interval(modules, calls):
    """The join of ``modules`` onto as many ``calls``, or None where no
    offset lets every launch start before its module and every fetch end
    after it, or it is known too loosely, or the launches lag too far."""
    hi = min(m[0] - c[0] for m, c in zip(modules, calls))   # launch first
    lo = max(m[1] - c[1] for m, c in zip(modules, calls))   # fetch last
    if not 0 <= hi - lo <= MAX_LAG_NS:
        return None
    offset = (lo + hi) / 2
    lag = statistics.median(m[0] - c[0] - offset
                            for m, c in zip(modules, calls))
    if lag > MAX_LAG_NS:
        return None
    return {"offset_ns": offset, "width_ns": hi - lo, "median_lag_ns": lag,
            "modules": len(modules)}


def measured(run, spans):
    """The spans that began once the run's set-up was done, on
    ``perf_counter``'s clock like the ring; all of them for a run that
    names no ``setup_s``."""
    if run.get("setup_s") is None:
        return spans
    began = (T0 + run["setup_s"]) * 1e9
    return [s for s in spans if s["start"] >= began]


def decode_profiler_off(run):
    """The ``measured`` ``decode.*`` spans that ended before the traced
    window opened; all of them where the run has no device trace (a
    rehearsal); None where it has one and the clocks could not be joined."""
    spans = ring("decode.")
    if not run.get("trace"):
        return measured(run, spans)
    join = clock_join(run, spans)
    if join is None:
        return None
    opened = run["trace_summary"]["window"][0] - join["offset_ns"]
    return [s for s in measured(run, spans) if s["end"] <= opened]


def idle_outside(run, spans, join):
    """(idle ns covered by no span, idle ns) of the traced window on the
    first chip: gaps between ops of at least ``xplane.BETWEEN_OPS_NS``
    against the union of ``spans`` moved onto the trace's clock."""
    trace, win = run["trace"], run["trace_summary"]["window"]
    busy = xplane.busy_intervals(trace["devices"][min(trace["devices"])], win)
    edges = [win[0]] + [t for iv in busy for t in iv] + [win[1]]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
            if b - a >= xplane.BETWEEN_OPS_NS]
    cover = xplane.merge((s["start"] + join["offset_ns"],
                          s["end"] + join["offset_ns"]) for s in spans)
    starts = [c[0] for c in cover]
    idle = outside = 0.0
    for a, b in gaps:
        idle += b - a
        outside += b - a
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(cover) and cover[i][0] < b:
            outside -= max(0.0, min(b, cover[i][1]) - max(a, cover[i][0]))
            i += 1
    return outside, idle
