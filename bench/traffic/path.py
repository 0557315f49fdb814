"""Traffic ``path``: descending lambda paths, back to back, from data.

The workload file gives ``data`` (a generator under ``bench/data/``),
``lambdas`` (the grid, fixed numbers) and ``kkt_sample`` (how many of the
window's paths, drawn from the seed, the KKT check reads; the partition and
the assembly of every path are checked).  One call is what a user makes:
``glasso_path(X=X, lambdas=grid, from_data=True)`` with default options.

Paths start one after another from the window's start while time is left;
the path in flight when the time runs out finishes.  ``lambda_s`` is the
window's elapsed time over the lambda solutions of all its paths.  A traced
run traces whole paths, at most ``trace_paths`` of them (the workload
file's), and its per-layer metrics are over those.
"""

from __future__ import annotations

import time

import numpy as np

from bench.control import served
from bench.harness import Window, annotate


def _traces(results):
    seen, out = set(), []
    for r in results:
        tr = getattr(r, "trace", None)
        if tr is not None and id(tr) not in seen:
            seen.add(id(tr))
            out.append(tr)
    return out


class Driver:
    def __init__(self, cell, seed: int, seconds: float, control: str | None = None):
        from repro.core import glasso_path
        from repro.engine import EngineOptions
        from repro.stream.config import StreamConfig

        wl = cell.workload
        self.seed = int(seed)
        self.grid = [float(v) for v in wl["lambdas"]]
        self.kkt_sample = int(wl["kkt_sample"])
        self.trace_paths = int(wl["trace_paths"])
        with annotate("bench.data"):
            self.X = cell.data_module(wl["data"]).make(cell.config, self.seed)
        options = EngineOptions()
        X_in = served(self.X, control)
        self._solve = lambda: glasso_path(
            X=X_in, lambdas=self.grid, from_data=True, options=options
        )
        # the screen runs at the engine's default stream configuration
        cfg = StreamConfig()
        n = self.X.shape[0]
        self.kernel_shape = {"n_pad": -(-n // cfg.chunk) * cfg.chunk, "tile": cfg.tile}
        self.paths: list[list] = []
        self.error: BaseException | None = None

    def warmup(self) -> None:
        with annotate("bench.warmup"):
            self._solve()

    def run(self, seconds: float, profiler=None) -> Window:
        from repro.core.instrument import counts

        before = counts()
        if profiler is not None:
            profiler.start()
        t0 = time.perf_counter()
        deadline = t0 + seconds
        bounds = []
        while True:
            a = time.perf_counter()
            try:
                with annotate("bench.path"):
                    self.paths.append(self._solve())
            except Exception as e:  # noqa: BLE001 - a failed path fails the run
                self.error = e
                break
            bounds.append((a, time.perf_counter()))
            if time.perf_counter() >= deadline or (
                profiler is not None and len(self.paths) >= self.trace_paths
            ):
                break
        if profiler is not None:
            profiler.stop()
        elapsed = time.perf_counter() - t0
        after = counts()
        results = [r for path in self.paths for r in path]
        traces = _traces(results)
        spans = [("bench.path", a, b) for a, b in bounds]
        spans += [(s.name, s.t0, s.t1) for tr in traces for s in tr.spans if s.t1 is not None]
        attempted = len(self.grid) * (len(self.paths) + (self.error is not None))
        return Window(
            end_to_end={"lambda_s": elapsed / max(len(results), 1)},
            attempted=attempted,
            failed=attempted - len(results),
            ctx={
                "units": len(results),
                "results": results,
                "traces": traces,
                "spans": spans,
                "counters": {k: v - before.get(k, 0) for k, v in after.items()},
                "kernel_shape": {"covgram_screen": self.kernel_shape},
            },
        )

    def close(self) -> None:
        self._solve = None

    def check(self, window: Window) -> dict:
        from bench.reference.check import Reference, check_solution, merge_checks

        if not self.paths:
            return {"partition": 1, "offblock": 1, "kkt": float("inf")}
        ref = Reference(self.X, self.grid)
        rng = np.random.default_rng(self.seed)
        sample = set(
            rng.choice(len(self.paths), size=min(self.kkt_sample, len(self.paths)), replace=False)
        )
        readings = []
        for k, path in enumerate(self.paths):
            lams = [r.lam for r in path]
            if lams != self.grid:
                readings.append({"partition": 1, "offblock": 1, "kkt": float("inf")})
                continue
            for r in path:
                readings.append(check_solution(ref, r.lam, r.labels, r.Theta, kkt=k in sample))
        return merge_checks(readings)

