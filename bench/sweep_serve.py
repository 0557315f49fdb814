"""Find the knee of a served cell: the highest offered rate it sustains.

    python3 bench/sweep_serve.py --workload hcp_s400.serve_poisson \
        --rates 4,8,16,32 --seconds 30 --seed 7

runs the cell's open-loop traffic once per rate, in this one process (so
compiled programs carry over), each with its own set-up and warm-up at
that rate, and prints one JSON line per rate: offered and completed
requests per second, p50 and p95 latency from when each request was due,
the median latency of the first and the last third of the requests, and
whether the rate is sustained.  A rate is sustained when every request
completes, the completed rate is at least 95% of the offered one, and the
last third's median latency is at most twice the first third's plus 50 ms
(the backlog does not grow over the window).  The knee is the highest rate
sustained; the cell's file records the rate it runs at, 0.8 of the knee.
Like ``run.py`` it needs the chip.
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path


def sustained(row: dict) -> bool:
    return (
        row["failed"] == 0
        and row["completed_per_s"] >= 0.95 * row["offered_per_s"]
        and row["p50_last_third_s"] <= 2.0 * row["p50_first_third_s"] + 0.05
    )


def sweep(cell, rates, seconds: float, seed: int):
    from bench.harness import CompileCounter
    from bench.traffic.serve_open import quantile

    compiles = CompileCounter()
    for rate in rates:
        cell.workload = dict(cell.workload, rate=float(rate))
        driver = cell.traffic.Driver(cell, seed, seconds)
        driver.warmup()
        compiles.count, compiles.seconds, compiles.active = 0, 0.0, True
        window = driver.run(seconds)
        compiles.active = False
        driver.close()
        reqs = sorted(driver.requests, key=lambda r: r.due)
        lat = [
            r.done - r.due if r.error is None and r.done is not None else math.inf
            for r in reqs
        ]
        third = max(1, len(lat) // 3)
        row = {
            "rate": float(rate),
            "requests": len(lat),
            "failed": window.failed,
            **window.extra["rates"],
            "p50_s": window.end_to_end["serve_p50_s"],
            "p95_s": window.end_to_end["serve_p95_s"],
            "p50_first_third_s": quantile(lat[:third], 0.5),
            "p50_last_third_s": quantile(lat[-third:], 0.5),
            "generator_late_p95_s": window.extra["generator"]["late_p95_s"],
            "window_compiles": compiles.count,
            "window_compile_s": compiles.seconds,
            "errors": window.extra["errors"],
        }
        row["sustained"] = sustained(row)
        yield row


def main(argv=None) -> int:
    import argparse

    from bench import harness

    ap = argparse.ArgumentParser(description="Sweep the offered rate of a served cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    try:
        cell = harness.find_cell(args.workload)
        harness.import_program()
        import jax

        harness.accelerator(cell.chips)
        harness.use_compile_cache()
        jax.config.update("jax_enable_x64", True)
    except harness.BenchError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    knee = None
    for row in sweep(cell, [float(r) for r in args.rates.split(",")], args.seconds, args.seed):
        print(json.dumps(row), flush=True)
        if row["sustained"]:
            knee = row["rate"]
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(Path(__file__).resolve().parents[1])
    sys.exit(main())
