"""The precision control, and the readings the check's limits are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> [--control bf16]

runs the cell's traffic once per seed in this one process, with a short
window and no warm-up, and prints one JSON line per seed with the numbers
the check compares.  Without ``--control`` these are sound runs of the
program (the lower readings); with ``--control bf16`` the program is served
its data rounded to bfloat16, the nearest precision below the float32 the
configurations state, and the reference still reads the float32 data (the
upper readings).  Like ``run.py`` it needs the chip.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def served(X: np.ndarray, control: str | None) -> np.ndarray:
    """X as the program receives it: as generated, or, under the ``bf16``
    control, rounded to bfloat16 (what one bf16 pass of the MXU reads)."""
    if control is None:
        return X
    if control != "bf16":
        raise ValueError(f"unknown control {control!r}")
    import ml_dtypes

    return X.astype(ml_dtypes.bfloat16).astype(X.dtype)


def readings(cell, seeds, seconds: float, control: str | None):
    """Yield (seed, checks) for each seed: one short window, then the
    check, exactly as a benchmark run makes it."""
    for seed in seeds:
        driver = cell.traffic.Driver(cell, seed, seconds, control=control)
        window = driver.run(seconds)
        driver.close()
        yield seed, driver.check(window), window


def main(argv=None) -> int:
    import argparse

    from bench import harness

    ap = argparse.ArgumentParser(description="Readings of the check, sound or under the control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", choices=("bf16",), default=None)
    args = ap.parse_args(argv)
    try:
        cell = harness.find_cell(args.workload)
        harness.import_program()
        import jax

        devices = harness.accelerator(cell.chips)
        harness.use_compile_cache()
        jax.config.update("jax_enable_x64", True)
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed, checks, window in readings(cell, seeds, args.seconds, args.control):
        print(json.dumps({
            "workload": cell.name, "seed": seed, "control": args.control,
            "device": devices[0].device_kind, "at": time.perf_counter(),
            "attempted": window.attempted, "failed": window.failed,
            "end_to_end": window.end_to_end, "checks": checks,
        }), flush=True)
    return 0


if __name__ == "__main__":
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path[0] = str(root)
    sys.exit(main())
