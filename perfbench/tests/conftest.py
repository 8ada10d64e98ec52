"""Tests of the benchmark harness, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
