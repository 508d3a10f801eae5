"""The benchmark's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They import the benchmark's modules from ``bench/`` and the program from
``src/``.
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
