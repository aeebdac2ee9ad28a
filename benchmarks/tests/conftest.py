"""Run by hand and in the CPU rehearsal: ``JAX_PLATFORMS=cpu python -m
pytest benchmarks/tests -q``.  Not part of the repo's ``tests/``."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)
