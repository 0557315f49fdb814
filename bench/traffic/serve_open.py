"""Traffic ``serve_open``: open-loop arrivals at a fixed rate to one server.

The workload file gives ``data`` (a generator under ``bench/data/`` with
``bank`` and ``relabel``), ``lambdas``, ``rate`` (requests per second),
``clients`` (submitting threads), ``tenant``, ``warmup_seconds``,
``settle_seconds`` and ``trace_seconds`` (how much of a traced run's window
the profiler records, from its start).

A run serves ``round(rate * seconds)`` requests.  Their gaps are the
quantiles (k + 1/2) / N of the exponential distribution at ``rate``, in an
order drawn from the seed: Poisson-like arrivals whose count and total span
are the same for every seed.  Request k is submitted when it is due, by one
of ``clients`` threads, as ``GlassoServer.submit(DataSpec(X, lam))``; its
latency runs from when it was due to when its result is in hand.  A request
that fails, is refused or has not resolved ``settle_seconds`` after the
window closed counts as infinitely late.  ``serve_p50_s`` and
``serve_p95_s`` are nearest-rank quantiles over every request of the run.

Set-up serves ``warmup_seconds`` of the same traffic (other subjects of the
bank) at the same rate, so the window meets compiled programs.
"""

from __future__ import annotations

import math
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.control import served
from bench.harness import Window, annotate


def gaps(count: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """Exponential quantiles at ``rate``, in an order drawn from ``rng``."""
    q = (np.arange(count) + 0.5) / count
    return rng.permutation(-np.log1p(-q) / rate)


def quantile(values, q: float) -> float:
    """Nearest-rank quantile (an observed value; inf counts as a value)."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        return math.inf
    return float(v[min(v.size - 1, max(0, math.ceil(q * v.size) - 1))])


class _Request:
    __slots__ = ("X", "lam", "due", "submitted", "done", "future", "error")

    def __init__(self, X, lam):
        self.X, self.lam = X, lam
        self.due = self.submitted = self.done = None
        self.future = self.error = None


class Driver:
    def __init__(self, cell, seed: int, seconds: float, control: str | None = None):
        from repro.engine import EngineOptions
        from repro.launch.serve_glasso import GlassoServer

        wl = cell.workload
        self.seed = int(seed)
        self.rate = float(wl["rate"])
        self.clients = int(wl["clients"])
        self.tenant = str(wl["tenant"])
        self.settle = float(wl["settle_seconds"])
        self.trace_seconds = float(wl["trace_seconds"])
        lambdas = [float(v) for v in wl["lambdas"]]
        count = max(1, int(round(self.rate * seconds)))
        n_warm = max(len(lambdas), int(round(self.rate * float(wl["warmup_seconds"]))))
        rng = np.random.default_rng(self.seed)
        data = cell.data_module(wl["data"])
        with annotate("bench.data"):
            bank = data.bank(cell.config, lambdas, count + n_warm)
            order = rng.permutation(count)
            self.requests = [
                _Request(data.relabel(bank[k][0], rng), bank[k][1]) for k in order
            ]
            self.warm = [_Request(X, lam) for X, lam in bank[count:]]
        self.gaps = gaps(count, self.rate, rng)
        self.warm_gaps = gaps(len(self.warm), self.rate, rng)
        self.control = control
        self.server = GlassoServer(options=EngineOptions(), result_cache=0).start()

    # -- the open loop ------------------------------------------------------

    def _submit(self, req: _Request) -> None:
        from repro.launch.control_plane import DataSpec, RequestMeta

        req.submitted = time.perf_counter()
        try:
            fut = self.server.submit(
                DataSpec(served(req.X, self.control), req.lam),
                meta=RequestMeta(tenant=self.tenant),
            )
        except Exception as e:  # noqa: BLE001 - a refused request is a failed one
            req.error = e
            req.done = time.perf_counter()
            return
        req.future = fut

        def _done(_f, req=req):
            req.done = time.perf_counter()

        fut.add_done_callback(_done)

    def _serve(self, reqs, gaps_s, profiler=None) -> float:
        """Submit every request when it is due; returns the start instant.
        A profiler, if given, runs from the start for ``trace_seconds``."""
        due = np.concatenate([[0.0], np.cumsum(gaps_s)[:-1]])
        with ThreadPoolExecutor(self.clients, thread_name_prefix="bench-client") as pool:
            if profiler is not None:
                profiler.start()
            t0 = time.perf_counter()
            for req, d in zip(reqs, due):
                req.due = t0 + float(d)
                if profiler is not None and req.due >= t0 + self.trace_seconds:
                    profiler.stop()
                wait = req.due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                pool.submit(self._submit, req)
        if profiler is not None:
            profiler.stop()
        return t0

    def _settle(self, reqs, deadline: float) -> None:
        for req in reqs:
            if req.future is None:
                continue
            try:
                req.future.result(timeout=max(0.0, deadline - time.perf_counter()))
            except Exception as e:  # noqa: BLE001 - failed or never came
                req.error = e

    def warmup(self) -> None:
        with annotate("bench.warmup"):
            self._serve(self.warm, self.warm_gaps)
            self._settle(self.warm, time.perf_counter() + 600.0)
        bad = [r.error for r in self.warm if r.error is not None]
        if bad:
            raise RuntimeError(f"{len(bad)} warm-up requests failed: {bad[0]!r}")

    def run(self, seconds: float, profiler=None) -> Window:
        from repro.core.instrument import counts

        before = counts()
        t0 = self._serve(self.requests, self.gaps, profiler)
        self._settle(self.requests, time.perf_counter() + self.settle)
        after = counts()
        lat = [
            r.done - r.due
            if r.error is None and r.future is not None and r.done is not None
            else math.inf
            for r in self.requests
        ]
        late = np.array([r.submitted - r.due for r in self.requests])
        # per-layer readers read the requests due in the traced part (all of
        # them in an untraced run)
        traced = [
            r for r in self.requests
            if profiler is None or profiler.t1 is None or r.due < profiler.t1
        ]
        traces = [
            r.future.trace for r in traced
            if r.future is not None and getattr(r.future, "trace", None) is not None
        ]
        spans = [(s.name, s.t0, s.t1) for tr in traces for s in tr.spans if s.t1 is not None]
        failed = sum(1 for v in lat if not math.isfinite(v))
        last = max((r.done for r in self.requests if r.done is not None), default=t0)
        errors = [r.error for r in self.requests if r.error is not None]
        return Window(
            end_to_end={
                "serve_p50_s": quantile(lat, 0.50),
                "serve_p95_s": quantile(lat, 0.95),
            },
            attempted=len(self.requests),
            failed=failed,
            ctx={
                "units": len(traced),
                "traces": traces,
                "spans": spans,
                "counters": {k: v - before.get(k, 0) for k, v in after.items()},
            },
            extra={
                "rates": {
                    "offered_per_s": len(lat) / max(self.requests[-1].due - t0, 1e-9),
                    "completed_per_s": (len(lat) - failed) / max(last - t0, 1e-9),
                },
                "generator": {
                    "late_p95_s": quantile(late, 0.95),
                    "late_max_s": float(late.max()),
                },
                "errors": {
                    "count": len(errors),
                    "first": repr(errors[0])[:500] if errors else None,
                },
            },
        )

    def close(self) -> None:
        """Stop the server and wait for its batcher to end: it may still
        be finishing a batch after the settle time."""
        self.server.stop()
        thread = self.server._thread
        if thread is not None:
            thread.join(timeout=600)

    def check(self, window: Window) -> dict:
        from bench.reference.check import Reference, check_solution, merge_checks

        readings, unresolved = [], 0
        for r in self.requests:
            if r.error is not None or r.future is None or not r.future.done():
                unresolved += 1
                continue
            res = r.future.result()
            readings.append(check_solution(Reference(r.X, [r.lam]), r.lam, res.labels, res.Theta))
        out = merge_checks(readings)
        out["unresolved"] = unresolved
        return out
