#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Prints the check's numbers beside their limits on standard error and, as the
last line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
``window_compiles``, then ``checks``.  Exits non-zero, printing no result,
when JAX finds no accelerator or fewer chips than the cell asks for, or
when the program is not beside the benchmark.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
