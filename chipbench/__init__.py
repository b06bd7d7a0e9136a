"""chipbench: the repo's yardstick on the chip (BENCHMARK.json).

``python -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell and prints, as the last line of standard output, one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``.

Driven by data: a cell is ``workloads/<cell>.json``, a configuration
``configs/<config>.json``; model builders, FLOP functions and plain references
go by family (``models/``, ``reference/``), traffic by driver (``drivers/``),
and every per-layer metric is one file in ``layer_metrics/``. Nothing here
enumerates cells, so a later PR adds files and edits none.
"""
import time

# as early as the package can read a clock: ``setup_s`` runs from here to the
# first measured dispatch or request
T0 = time.perf_counter()
