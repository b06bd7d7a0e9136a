"""mxnet_tpu.analysis — TPU-pitfall linter & concurrency checker (mxlint).

Static enforcement of the invariants the rest of the stack is built on
(STATIC_ANALYSIS.md is the rule catalog):

  TPU100  host sync reachable from traced code (hybrid_forward / @jit),
          through any chain of helper/method calls (via-chain reported)
  TPU101  python control flow on a traced value, incl. helpers that
          branch on an argument's value (recompile storms)
  TPU102  use-after-donate (reads of buffers consumed by donate_argnums,
          directly or by a helper that donates its argument)
  CONC200 instance attribute mutated with and without its owning lock
  CONC201 lock-order cycles in the acquisition graph (potential deadlock)
  CONC202 blocking ops (sleep/join/.result()/file IO/device sync) while
          an owning lock is held, through helper indirection
  MET300  telemetry metric names failing ^mxtpu_[a-z0-9_]+$ statically
  MET301  metric label values built from f-strings/str(id) — unbounded
          time-series cardinality
  THR400  thread lifecycle: started-never-joined non-daemon threads,
          restart-after-stop races
  EXC500  broad excepts that swallow the transient/fatal classification
          in RetryPolicy-wrapped / checkpoint paths (call-graph marked)
  ENV600  MXNET_* knob / mxtpu_* metric drift between code and the
          operator docs, both directions
  MESH700 collective/PartitionSpec axis names undeclared by the mesh in
          scope, duplicate spec axes, shard_map in-specs never reduced
  TAIL800 request-path deadline discipline: unclamped sleeps and hops
          that drop the propagated Deadline (call-graph seeded)
  RES900  bare open(path, "w") in persistence subsystems bypassing the
          tmp+fsync+os.replace idiom (split-helper aware)
  DRIFT601 fault/chaos/flight registry drift: SITES/kinds vs call sites
          vs chaos scenarios vs the RESILIENCE/OBSERVABILITY runbooks
  IR1000-IR1005 hlolint (:mod:`.ir`): IR-level rules over the compile
          ledger's StableHLO corpus — dropped donation, baked-in weights,
          f32 creep, host round-trips, collective/mesh mismatch, bucket
          duplication (``mxlint --ir``; live guard via MXNET_IR_GUARD)

v2 analyzes the scan set as one program: project symbol table + call graph
(:mod:`.callgraph`), per-function effect summaries propagated to a fixpoint
(:mod:`.summaries`), an incremental mtime+content-keyed cache
(:mod:`.cache`), and SARIF 2.1.0 output (:mod:`.sarif`); v3 rides the same
engine for the distributed-systems effects (blocking, bare writes,
collective axis uses).

Deliberately dependency-free (stdlib ``ast`` only) and import-light: the
package never imports jax or the rest of mxnet_tpu, so the linter runs in
any python — CI images, pre-commit hooks — without the accelerator stack.

CLI: ``python tools/mxlint.py [paths ...]`` (text/JSON/SARIF output,
``--changed-only`` git-scoped scans, per-line ``# mxlint: disable=RULE``
suppressions, committed baseline in ``tools/mxlint_baseline.json``).
"""
from __future__ import annotations

from .core import (Checker, Finding, SourceFile, LAST_SCAN_STATS, VERSION,
                   all_checkers, get_checker, iter_python_files, lint_file,
                   lint_paths, register)
from .baseline import apply_baseline, load_baseline, save_baseline
from .sarif import to_sarif

# importing the rule modules populates the registry
from . import tpu_rules    # noqa: F401  (TPU100/TPU101/TPU102)
from . import conc_rules   # noqa: F401  (CONC200/CONC201/CONC202)
from . import met_rules    # noqa: F401  (MET300/MET301)
from . import thr_rules    # noqa: F401  (THR400)
from . import exc_rules    # noqa: F401  (EXC500)
from . import env_rules    # noqa: F401  (ENV600)
from . import mesh_rules   # noqa: F401  (MESH700)
from . import tail_rules   # noqa: F401  (TAIL800)
from . import res_rules    # noqa: F401  (RES900)
from . import drift_rules  # noqa: F401  (DRIFT601)
from . import ir           # noqa: F401  (IR1000..IR1005 — hlolint)
from .ir import lint_ir_paths

__all__ = [
    "Checker", "Finding", "SourceFile", "register",
    "all_checkers", "get_checker", "iter_python_files",
    "lint_file", "lint_paths", "LAST_SCAN_STATS",
    "apply_baseline", "load_baseline", "save_baseline",
    "to_sarif", "VERSION", "DEFAULT_SCAN_SET",
    "lint_ir_paths", "DEFAULT_IR_SCAN_SET",
]

#: what `python tools/mxlint.py` scans when given no paths: the package
#: itself plus the operational CLIs that ride along with it in CI
DEFAULT_SCAN_SET = ("mxnet_tpu", "tools/chaos_check.py",
                    "tools/metrics_dump.py", "tools/mxlint.py")

#: what ``mxlint --ir`` scans when given no corpus directories: the
#: committed fixture ledgers — a serving run's records (no retained
#: texts: exercises the missing-text tolerance) and the hlolint
#: clean corpus (retained texts that must stay silent)
DEFAULT_IR_SCAN_SET = ("tests/fixtures/costmodel/ledger",
                       "tests/fixtures/hlolint/clean")
