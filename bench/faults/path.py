"""Faults planted in the timed path of a ``path`` cell (the contract of a
faults file is in ``bench/test_bench_faults.py``): the BCD returns its
starting state, and every Θ the engine assembles is altered."""


def _frozen_sweeps(ST, W, BT, lam, thr, *, max_sweeps, n_cd, node_screen, masked=False):
    """BCD that returns its starting state: no sweep is made."""
    import jax.numpy as jnp

    eye = jnp.eye(W.shape[0], dtype=bool)
    dots = jnp.sum(jnp.where(eye, 0.0, W) * BT, axis=1, keepdims=True)
    wdiag = jnp.sum(jnp.where(eye, W, 0.0), axis=1, keepdims=True)
    t22 = 1.0 / (wdiag - dots)
    return jnp.where(eye, t22, -BT * t22), jnp.int32(0)


def solver_state_unchanged(monkeypatch, cell):
    from repro.core.solvers import bcd

    monkeypatch.setattr(bcd, "bcd_sweeps", _frozen_sweeps)
    return "kkt"


def answer_altered(monkeypatch, cell):
    """Every Θ the engine assembles, scaled by 1 + 1e-3."""
    import numpy as np
    from repro.engine import api

    real = api._result

    def altered(plan, labels, screen_stats, Theta, *args, **kwargs):
        if isinstance(Theta, np.ndarray):
            Theta = Theta * (1.0 + 1e-3)
        else:
            Theta.isolated_values = Theta.isolated_values * (1.0 + 1e-3)
        return real(plan, labels, screen_stats, Theta, *args, **kwargs)

    monkeypatch.setattr(api, "_result", altered)
    return "kkt"


FAULTS = {
    "solver_state_unchanged": solver_state_unchanged,
    "answer_altered": answer_altered,
}
