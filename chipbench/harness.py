"""What every cell shares: arguments, the cell's files, the device check, the
set-up clock, the trace, the per-layer readers and the lines a run prints."""
import argparse
import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

from . import T0, xplane

ROOT = os.path.dirname(os.path.abspath(__file__))
# fixed and inside the checkout (the repo's .gitignore lists it)
TRACE_DIR = os.path.join(os.path.dirname(ROOT), ".chipbench_trace")
# a rehearsal's numbers are the CPU's: they never carry a device metric's name
REHEARSAL_PREFIX = "cpu_rehearsal."


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(name, rehearse=False):
    """(cell, config): ``workloads/<name>.json`` and the configuration it
    names. A rehearsal lays each file's ``rehearse`` group over it: tiny
    widths and batches, for the CPU."""
    cell = load_json("workloads", name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    if rehearse:
        cell = {**cell, **cell.get("rehearse", {})}
        config = {**config, **config.get("rehearse", {})}
    return cell, config


def seed32(seed, stream=0):
    """A 31-bit seed for ``stream`` from any whole number: the driver's seeds
    pass 2**31, which a 32-bit PRNG key does not take."""
    import numpy as onp
    state = onp.random.SeedSequence([int(seed), int(stream)]).generate_state(1)
    return int(state[0]) & 0x7FFFFFFF


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from its
    persistent cache) and how often that cache answered, from JAX's own
    monitoring events (the pattern of chip_smoke.py's _CompileClock)."""

    _SECONDS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
                "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
                "/jax/core/compile/backend_compile_duration": "backend_s"}
    _COUNTS = {"/jax/compilation_cache/compile_requests_use_cache":
               "cache_requests",
               "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self._sums = dict.fromkeys(
            [*self._SECONDS.values(), *self._COUNTS.values(), "compiles"], 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._on_seconds)
        jax.monitoring.register_event_listener(
            lambda event, **_: self._add(self._COUNTS.get(event), 1))

    def _on_seconds(self, event, secs, **_):
        key = self._SECONDS.get(event)
        self._add(key, secs)
        if key == "backend_s":        # fires for a cache hit as for a compile
            self._add("compiles", 1)

    def _add(self, key, amount):
        if key is not None:
            with self._lock:
                self._sums[key] += amount

    def read(self):
        with self._lock:
            return dict(self._sums)


class Bench:
    """One run of one cell: what a driver is handed."""

    def __init__(self, args):
        self.name = args.workload
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.cell, self.config = load_cell(self.name, self.rehearse)
        self.chips = int(self.cell["chips"])
        self.phases = {}                 # set-up seconds by phase
        self._phase_t = T0
        self.devices = None
        self.clock = None
        self.device_row = None
        self.trace_data = None
        self.setup_s = None
        self._memory_peak = None

    # -- set-up accounting ------------------------------------------------
    def phase_done(self, name):
        """Close a set-up phase: the seconds since the last one closed."""
        now = time.perf_counter()
        self.phases[name] = self.phases.get(name, 0.0) + now - self._phase_t
        self._phase_t = now

    def setup_done(self):
        """Called by the driver just before its first measured dispatch or
        request; prints where set-up went."""
        self.phase_done("warmup")
        self.setup_s = time.perf_counter() - T0
        compile_ = self.clock.read()
        self.setup_compile = compile_
        self.say({"setup_s": self.setup_s, "phases_s": self.phases,
                  "compile": compile_,
                  "note": "phases include the compile seconds spent in them"})

    # -- output -------------------------------------------------------------
    def say(self, row):
        """An earlier line: names the cell and the device it ran on."""
        print(json.dumps({"workload": self.name, **row, **self.device_row}),
              flush=True)

    # -- the device -----------------------------------------------------------
    def start_jax(self):
        """Import JAX and the program, take the devices, refuse the CPU unless
        rehearsing, and turn the persistent compile cache on."""
        import jax
        from mxnet_tpu import cache, runtime
        self.device_row = runtime.device_row()
        platform = jax.default_backend()
        if platform != "tpu" and not self.rehearse:
            raise SystemExit(
                f"chipbench: JAX's backend is {platform!r}, not the TPU; a "
                "measurement needs the chip (--rehearse runs tiny on the CPU "
                "and reports no device metric)")
        if len(jax.devices()) < self.chips:
            raise SystemExit(
                f"chipbench: cell {self.name} needs {self.chips} chip(s), "
                f"JAX found {len(jax.devices())}")
        self.devices = jax.devices()[:self.chips]
        self.context = runtime.measurement_context()
        self.cache_dir = None
        if not self.rehearse:
            self.cache_dir = cache.enable_compile_cache()
            # sub-second programs are most of a cell's 50-odd: cache them too
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        self.clock = CompileClock()
        self.phase_done("import")

    def family(self):
        return importlib.import_module(
            f"chipbench.models.{self.config['family']}")

    # -- tracing --------------------------------------------------------------
    @contextlib.contextmanager
    def traced_window(self):
        """Profile the block into TRACE_DIR under one ``window`` span, then
        load the trace into ``self.trace_data``."""
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
        try:
            with span("window"):
                yield
        finally:
            jax.profiler.stop_trace()
        t0 = time.perf_counter()
        self.trace_data = xplane.load(xplane.find_xplane(TRACE_DIR))
        self.say({"trace": {
            "chips": sorted(self.trace_data["devices"]),
            "ops": sum(len(d["ops"]) for d in
                       self.trace_data["devices"].values()),
            "spans": len(self.trace_data["spans"]),
            "parse_s": time.perf_counter() - t0}})
        excerpt_to = os.environ.get("CHIPBENCH_TRACE_EXCERPT")
        if excerpt_to:                   # prove.py keeps one for the fixture
            with open(excerpt_to, "w") as f:
                json.dump(xplane.excerpt(self.trace_data), f)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    def memory_peak_bytes(self):
        """Peak bytes held on the fullest chip. The TPU runtime counts live
        arrays (``peak_bytes_in_use``) apart from what a running program
        reserves for its scratch (``peak_bytes_reserved``); a step holds both
        at once, so the peak is their sum (for bert_base.pretrain_s128 1.45 +
        11.83 GB against 13.11 GB from the step's ``memory_analysis()``). 0
        where the backend keeps no count (the CPU). A process's peak never
        falls, so the first reading stands: a driver whose check runs a
        reference on the chip reads the peak before it does."""
        if self._memory_peak is None:
            stats = [d.memory_stats() or {} for d in self.devices]
            self.say({"memory_stats": stats[0]})
            self._memory_peak = max(
                int(s.get("peak_bytes_in_use", 0))
                + int(s.get("peak_bytes_reserved", 0)) for s in stats)
        return self._memory_peak


def span(name):
    """A host span in the profiler's own trace, for gap attribution."""
    import jax
    return jax.profiler.TraceAnnotation(xplane.SPAN_PREFIX + name)


# ---------------------------------------------------------------------------
# per-layer readers
# ---------------------------------------------------------------------------
def layer_metric_modules():
    """Every ``layer_metrics/<metric>.py``, loaded by path (a metric's name
    may hold a dot, which an import statement cannot)."""
    folder = os.path.join(ROOT, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            "chipbench.layer_metrics." + fname[:-3].replace(".", "_"),
            os.path.join(folder, fname))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        if module.NAME != fname[:-3]:
            raise RuntimeError(f"{fname} names its metric {module.NAME!r}")
        yield module


def read_layer_metrics(run, kind):
    """``{name: {"value", "unit"}}`` from every reader whose ``KINDS`` hold
    this kind of run (a driver's ``KIND``) and that finds something to read
    in it."""
    out = {}
    for module in layer_metric_modules():
        if kind not in module.KINDS:
            continue
        value = module.read(run)
        if value is not None:
            out[module.NAME] = {"value": float(value), "unit": module.UNIT}
    return out


def breakdown(trace, info):
    """Top device ops and idle gaps of the traced window, from the chip that
    was busy least."""
    chip = min(info["busy_s"], key=info["busy_s"].get)
    dev = trace["devices"][chip]
    return {"device_ops": xplane.top(xplane.op_seconds(dev, info["window"])),
            "idle_gaps": xplane.top(xplane.idle_gaps(trace, dev,
                                                     info["window"]))}


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------
def parse_args(argv=None):
    p = argparse.ArgumentParser(prog="python -m chipbench")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny widths on the CPU: checks the path, reports no "
                        "device metric")
    return p.parse_args(argv)


def main(argv=None):
    bench = Bench(parse_args(argv))
    bench.start_jax()
    bench.say({"seed": bench.seed, "seconds": bench.seconds,
               "trace": int(bench.trace), "rehearse": bench.rehearse,
               "compile_cache": bench.cache_dir})
    driver = importlib.import_module(
        f"chipbench.drivers.{bench.cell['driver']}")
    run = driver.run(bench)
    trace = bench.trace_data if bench.trace_data and \
        bench.trace_data["devices"] else None       # none on the CPU
    run["trace"] = trace
    run["trace_summary"] = info = xplane.summary(trace) if trace else None
    run["setup_s"] = bench.setup_s
    run["setup_compile"] = bench.setup_compile

    device = {"platform": bench.device_row["platform"],
              "kind": bench.device_row["device_kind"],
              "count": bench.device_row["device_count"],
              "memory_peak_bytes": bench.memory_peak_bytes()}
    if bench.trace:
        metrics = read_layer_metrics(run, driver.KIND)
    else:
        metrics = {name: {"value": float(run["end_to_end"][name]),
                          "unit": unit}
                   for name, unit in driver.END_TO_END.items()}
        metrics["setup_s"] = {"value": bench.setup_s, "unit": "s"}
    result = {"correct": bool(run["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"])}
    if trace:
        device["busy_s"] = sum(info["busy_s"].values()) / len(info["busy_s"])
        device["window_s"] = info["window_s"]
        result["breakdown"] = breakdown(trace, info)
    if bench.rehearse:
        metrics = {REHEARSAL_PREFIX + k: v for k, v in metrics.items()}
    result.update(metrics=metrics, device=device)
    # every number ``correct`` was decided by, beside its limit: the last
    # lines of standard error, and the result line's last key
    compared = run.get("compared")
    if compared:
        result["compared"] = compared
        for name, row in compared.items():
            print(f"compared {name} = {row['value']!r} limit {row['limit']!r}",
                  file=sys.stderr, flush=True)
    # key order as the contract shows it
    order = ("correct", "attempted", "failed", "metrics", "device",
             "breakdown", "compared")
    print(json.dumps({k: result[k] for k in order if k in result}), flush=True)
    return 0
