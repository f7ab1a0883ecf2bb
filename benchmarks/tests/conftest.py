"""The benchmark's own tests: run by hand (``python -m pytest benchmarks/tests
-q``), not collected by tier-1's ``pytest tests/``.  They run on the CPU, also
on a machine that holds a chip: several whole runs share one process, which
the harness refuses once a device backend is trusted."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
