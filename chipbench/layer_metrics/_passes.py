"""Shared by the readers that take a decode pass apart: the spans of one
name under each ``decode.iteration`` that ran a step, whatever lies between
(a prefill's emit hangs under its ``decode.prefill``, a fetch under the span
it was launched under), and what the ring knows of a span beyond its times:
the CPU time of its thread."""


def stepped(off):
    """``{span_id: span}`` of the ``decode.iteration`` spans of ``off`` that
    ran a step (``rows`` > 0)."""
    return {s["span_id"]: s for s in off
            if s["name"] == "decode.iteration" and s["attrs"].get("rows")}


def under(off, name):
    """``{iteration span_id: [spans named ``name`` under it]}`` for the
    passes of ``off`` that ran a step: each such span's ``parent_id`` walked
    to the top, as ``sched_host_ms_per_step.decode`` walks its fetches."""
    parent = {s["span_id"]: s["parent_id"] for s in off}
    out = {top: [] for top in stepped(off)}
    for s in off:
        if s["name"] != name:
            continue
        top = s["parent_id"]
        while parent.get(top) is not None:
            top = parent[top]
        if top in out:
            out[top].append(s)
    return out


def cpu_ns():
    """``{span_id: ns}`` of CPU time the thread of a finished span of the
    ring used inside it, for the spans that took it (``cpu_us``: the
    program's ``decode.iteration``); empty for a hand-made ring or an older
    program."""
    from mxnet_tpu.telemetry import flight
    return {e["span_id"]: e["cpu_us"] * 1e3
            for e in flight.recent_spans() if e.get("cpu_us") is not None}
