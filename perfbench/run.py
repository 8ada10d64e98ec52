"""Run one benchmark cell once, on the chips of this machine.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

From the root of a checkout. Prints progress, then as its last line one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` the trace ``breakdown``), and the
numbers compared with the reference under ``checks``. Exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""

import pathlib
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
