"""Pallas TPU kernels for ops XLA can't fuse optimally (SURVEY.md §7 step 3:
"custom kernels that XLA can't express well → Pallas").

Kernels register into the same op registry as everything else; each has an
XLA-composite fallback for CPU/interpret execution so the test suite runs on
the virtual CPU mesh.
"""
from . import flash_attention  # noqa: F401
from . import paged_attention  # noqa: F401
