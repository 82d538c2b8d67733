"""Tests of the chip benchmark's own code, run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
