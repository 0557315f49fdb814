"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a run
(set-up, window, check) at tiny size on the CPU, with one fault planted in
the program: a solver step that returns its state unchanged, an answer
altered where it is produced, and, for the served cell, half of a batch
left out.  (The cells run on one chip, so no exchange between chips can
be left out.)"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.test_bench_rehearsal import CANDIDATES, CELLS, tiny


def run(cell):
    return harness.run_cell(
        cell, seed=2**31 + 3, seconds=0.5, trace=False, devices=jax.devices(),
        t_start=time.perf_counter(),
    )


@pytest.fixture
def fresh_compiles():
    """Planted faults must be traced anew, and must not leak compiled code
    into later tests."""
    from repro.engine import executor

    def clear():
        executor._COMPILED.clear()
        jax.clear_caches()

    clear()
    yield
    clear()


def _frozen_sweeps(ST, W, BT, lam, thr, *, max_sweeps, n_cd, node_screen, masked=False):
    """BCD that returns its starting state: no sweep is made."""
    eye = jnp.eye(W.shape[0], dtype=bool)
    dots = jnp.sum(jnp.where(eye, 0.0, W) * BT, axis=1, keepdims=True)
    wdiag = jnp.sum(jnp.where(eye, W, 0.0), axis=1, keepdims=True)
    t22 = 1.0 / (wdiag - dots)
    return jnp.where(eye, t22, -BT * t22), jnp.int32(0)


@pytest.mark.parametrize("name", CELLS + CANDIDATES)
def test_solver_state_unchanged_is_not_correct(name, monkeypatch, fresh_compiles, bench_root):
    from repro.core.solvers import bcd

    monkeypatch.setattr(bcd, "bcd_sweeps", _frozen_sweeps)
    res = run(tiny(name, bench_root))
    assert not res["correct"]
    assert res["checks"]["kkt"]["value"] > res["checks"]["kkt"]["limit"]


@pytest.mark.parametrize("name", CELLS + CANDIDATES)
def test_answer_altered_where_produced_is_not_correct(name, monkeypatch, fresh_compiles,
                                                      bench_root):
    from repro.engine import api

    real = api._result

    def altered(plan, labels, screen_stats, Theta, *args, **kwargs):
        if isinstance(Theta, np.ndarray):
            Theta = Theta * (1.0 + 1e-3)
        else:
            Theta.isolated_values = Theta.isolated_values * (1.0 + 1e-3)
        return real(plan, labels, screen_stats, Theta, *args, **kwargs)

    monkeypatch.setattr(api, "_result", altered)
    res = run(tiny(name, bench_root))
    assert not res["correct"]
    assert res["checks"]["kkt"]["value"] > res["checks"]["kkt"]["limit"]


def test_half_of_a_batch_left_out_is_not_correct(monkeypatch, fresh_compiles, bench_root):
    from repro.launch.serve_glasso import GlassoServer

    real = GlassoServer.solve_batch

    def half(self, requests):
        return real(self, requests[: (len(requests) + 1) // 2])

    monkeypatch.setattr(GlassoServer, "solve_batch", half)
    cell = tiny("hcp_s400.serve_poisson", bench_root)
    cell.workload = dict(cell.workload, settle_seconds=3, warmup_seconds=0.0)
    res = run(cell)
    assert not res["correct"]
    assert res["failed"] > 0 and res["checks"]["unresolved"]["value"] > 0
