"""The one reduction from a profiler trace to numbers.

``load()`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists (so a small recorded trace can be kept as a JSON fixture and every
function below runs on it without JAX): the device planes' ``XLA Ops`` and
``XLA Modules`` lines and the host spans the harness opened
(``jax.profiler.TraceAnnotation`` named ``chipbench.<what>``). All times are
nanoseconds on the trace's one clock.

An op's event is named by its HLO text, ``%name = <shape> <opcode>(<operands>)
, <attr>=<value>...``. It is classified by the *parsed* opcode and ``kind=``
attribute only, never by a substring of the text: a fusion whose operand is
called ``%custom-call.172`` is not a custom call, and XLA:TPU prints a matrix
multiplication as a convolution inside a ``kind=kOutput`` fusion, which no
name on the event tells apart from a real convolution.
"""
import functools
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
_DEVICE_PLANE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")

# ops that only contain other ops of the same line: their event spans their
# children and the gaps between them, so they say nothing about busy time
CONTAINER_OPCODES = frozenset({"while", "conditional", "call"})
COLLECTIVE_OPCODES = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all"})


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------
def find_xplane(trace_dir):
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` trace directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path):
    """``{"devices": {chip_id: {"ops": [[name, start_ns, dur_ns]...],
    "modules": [...]}}, "spans": [[name, start_ns, dur_ns]...]}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is not None:
                    dev[key] = [[e.name, float(e.start_ns), float(e.duration_ns)]
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def excerpt(trace, max_ops=400):
    """A cut small enough to keep as a fixture: the first ``max_ops`` ops of
    each chip, with every module and span (they are short)."""
    return {"devices": {str(chip): {"ops": dev["ops"][:max_ops],
                                    "modules": dev["modules"]}
                        for chip, dev in trace["devices"].items()},
            "spans": trace["spans"]}


# ---------------------------------------------------------------------------
# HLO text of one op
# ---------------------------------------------------------------------------
def _skip_balanced(text, i):
    """Index just past the parenthesis group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


@functools.lru_cache(maxsize=1 << 16)   # a step's ops recur every step
def parse_op(text):
    """``(opcode, kind, result_shape, target)`` of one op's HLO text.
    ``kind`` is a fusion's ``kind=`` attribute and ``target`` a custom call's
    ``custom_call_target``, both read from the attributes that follow the
    operand list and None where absent. Text that is not HLO (a kernel or a
    step name) gives ``(text, None, "", None)``."""
    head, sep, rest = text.partition(" = ")
    if not sep or not head.startswith("%"):
        return text.strip(), None, "", None
    rest = rest.lstrip()
    if rest.startswith("("):                      # tuple-shaped result
        end = _skip_balanced(rest, 0)
    else:
        end = rest.find(" ")
        end = len(rest) if end < 0 else end
    shape, rest = rest[:end], rest[end:].lstrip()
    paren = rest.find("(")
    if paren < 0:
        return rest.strip() or text.strip(), None, shape, None
    opcode = rest[:paren].strip()
    attrs = rest[_skip_balanced(rest, paren):]
    kind = re.search(r"(?:^|,\s*)kind=(k\w+)", attrs)
    target = re.search(r'(?:^|,\s*)custom_call_target="([^"]*)"', attrs)
    return (opcode, kind.group(1) if kind else None, shape,
            target.group(1) if target else None)


def op_label(text, width=96):
    """What the breakdown calls an op: opcode (with the fusion kind or the
    custom call's target) and result shape, layouts dropped, cut to
    ``width``."""
    opcode, kind, shape, target = parse_op(text)
    tag = kind or target
    label = f"{opcode}[{tag}]" if tag else opcode
    shape = re.sub(r"\{[^{}]*\}", "", shape)      # drop layouts and tilings
    if shape:
        label = f"{label} -> {shape}"
    return label if len(label) <= width else label[:width - 3] + "..."


def is_collective(opcode):
    """True for a collective that holds the device: the op itself, or the
    ``-done`` of an asynchronous pair (the ``-start`` only launches it)."""
    if opcode.endswith("-start"):
        return False
    base = opcode[:-len("-done")] if opcode.endswith("-done") else opcode
    return base in COLLECTIVE_OPCODES


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def window(trace):
    """(start_ns, end_ns) of the traced window: the harness's ``window`` span
    where the trace holds it, else first op start to last op end."""
    for span, start, dur in trace["spans"]:
        if span == SPAN_PREFIX + "window":
            return start, start + dur
    starts = [s for dev in trace["devices"].values() for _, s, _ in dev["ops"]]
    ends = [s + d for dev in trace["devices"].values()
            for _, s, d in dev["ops"]]
    return (min(starts), max(ends)) if starts else (0.0, 0.0)


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def busy_intervals(dev, win=None):
    """Disjoint intervals in which some op ran on the chip, container ops
    left out, clipped to ``win``."""
    spans = []
    for text, start, dur in dev["ops"]:
        if parse_op(text)[0] in CONTAINER_OPCODES:
            continue
        end = start + dur
        if win is not None:
            start, end = max(start, win[0]), min(end, win[1])
        if end > start:
            spans.append((start, end))
    return merge(spans)


def seconds(intervals):
    return sum(end - start for start, end in intervals) / 1e9


def module_durations(dev, win=None):
    """``{module: [seconds...]}`` with the module named as the jitted
    function was (``jit_step_n``), its fingerprint dropped. Modules that
    start outside ``win`` are left out."""
    out = {}
    for text, start, dur in dev["modules"]:
        if win is not None and not win[0] <= start <= win[1]:
            continue
        out.setdefault(text.split("(")[0], []).append(dur / 1e9)
    return out


def op_seconds(dev, win=None, keep=None):
    """``{label: seconds}`` summed over the chip's ops (containers left out),
    for the ops whose opcode ``keep`` accepts (all by default)."""
    out = {}
    for text, start, dur in dev["ops"]:
        if win is not None and not win[0] <= start <= win[1]:
            continue
        opcode = parse_op(text)[0]
        if opcode in CONTAINER_OPCODES or (keep and not keep(opcode)):
            continue
        label = op_label(text)
        out[label] = out.get(label, 0.0) + dur / 1e9
    return out


# a gap this short is the chip stepping from one op to the next, not the
# host keeping it waiting
BETWEEN_OPS_NS = 5000.0


def idle_gaps(trace, dev, win):
    """``{what: seconds}``: the chip's idle time inside the window, each gap
    given to the harness span that covers most of it (``dispatch``,
    ``fetch``, ``submit``...), else to ``unattributed``; gaps under
    BETWEEN_OPS_NS go to ``between_ops``."""
    import bisect
    busy = busy_intervals(dev, win)
    edges = [win[0]] + [t for iv in busy for t in iv] + [win[1]]
    spans = [(s, s + d, n[len(SPAN_PREFIX):]) for n, s, d in trace["spans"]
             if n != SPAN_PREFIX + "window"]
    starts = [s[0] for s in spans]
    out = {}
    for i in range(0, len(edges), 2):
        g0, g1 = edges[i], edges[i + 1]
        if g1 <= g0:
            continue
        best, cover = "unattributed", 0.0
        if g1 - g0 < BETWEEN_OPS_NS:
            best = "between_ops"
        else:
            # spans are sorted by start and few are open at once: whatever
            # overlaps the gap is among the last ones that start before g1
            hi = bisect.bisect_left(starts, g1)
            for s0, s1, name in spans[max(0, hi - 256):hi]:
                overlap = min(g1, s1) - max(g0, s0)
                if overlap > cover:
                    best, cover = name, overlap
        out[best] = out.get(best, 0.0) + (g1 - g0) / 1e9
    return out


def top(table, n=10):
    """The ``n`` largest entries of ``{name: seconds}`` as [[name, s]...]."""
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def summary(trace):
    """What every traced run reports: per chip the busy seconds inside the
    window, and the window's length."""
    win = window(trace)
    busy = {chip: seconds(busy_intervals(dev, win))
            for chip, dev in trace["devices"].items()}
    return {"window": win, "window_s": (win[1] - win[0]) / 1e9, "busy_s": busy}
