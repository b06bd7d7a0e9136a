"""Shared by the readers that take a kernel's device time inside one jitted
program from the trace: the ops of the traced window that lie inside a module
of that name, by the name the breakdown gives them."""
import bisect

from chipbench import xplane


def inside_modules(run, module):
    """(modules, ops): the (start, end) of every XLA module named ``module``
    that started in the traced window, sorted, and ``[(label, index of its
    module, duration ns)...]`` for every op on the first chip that lies
    inside one; None where the run has no device trace."""
    trace = run.get("trace")
    if not trace:
        return None
    dev = trace["devices"][min(trace["devices"])]
    first, last = run["trace_summary"]["window"]
    modules = sorted((start, start + dur) for text, start, dur
                     in dev["modules"] if text.split("(")[0] == module
                     and first <= start <= last)
    ops = []
    for text, start, dur in dev["ops"]:
        at = bisect.bisect_right(modules, (start, float("inf"))) - 1
        if at >= 0 and start <= modules[at][1]:
            ops.append((xplane.op_label(text), at, dur))
    return modules, ops


def excerpt(trace, steps=3):
    """A cut of a traced run small enough to keep as a fixture and whole
    enough for the kernels' readers: every module and span, and of the ops
    the Pallas kernels (custom calls of target ``tpu_custom_call``) that lie
    inside the first ``steps`` step programs or inside any prefill program
    (``xplane.excerpt`` keeps a chip's first 400 ops, which end inside the
    first layer of the first step). Not called by a run: the fixtures under
    ``tests/chipbench/fixtures`` were cut with it from ``Bench.trace_data``
    of a traced run on the chip."""
    out = {"devices": {}, "spans": trace["spans"]}
    for chip, dev in trace["devices"].items():
        def named(prefix):
            return sorted((start, start + dur)
                          for text, start, dur in dev["modules"]
                          if text.split("(")[0] == prefix)
        kept = named("jit_decode")[:steps] + named("jit_prefill")
        ops = [op for op in dev["ops"]
               if xplane.parse_op(op[0])[3] == "tpu_custom_call"
               and any(a <= op[1] <= b for a, b in kept)]
        out["devices"][str(chip)] = {"ops": ops, "modules": dev["modules"]}
    return out
