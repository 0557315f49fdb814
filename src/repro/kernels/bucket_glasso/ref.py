"""jnp reference for the fused bucket BCD — ``glasso_bcd`` per packed lane.

``fused_bcd_single`` is ``core.solvers.bcd.glasso_bcd`` with two deltas that
make it PACKABLE across bucket boundaries without changing any lane's bits:

* **Warm inputs are mandatory.**  Every lane carries a (W0, Theta0) pair, so
  one compiled signature covers a megabatch that mixes warm and cold source
  buckets.  Cold lanes pass W0 = S + lam*I (bitwise-identical to the cold
  init: the diagonal is reset from S either way and lam*0 adds nothing
  off-diagonal) and Theta0 = I (B_init off-diagonal becomes -0.0 where the
  cold path had +0.0 — equal under ``==``, the repo's bitwise gate).

* **The convergence scale is an input.**  ``glasso_bcd`` derives its sweep
  and CD tolerances from ``mean|S - diag S| + 1e-12`` of ITS OWN padded
  block.  Re-padding a (s, s) lane into a (bin, bin) slot keeps every other
  quantity exact (padded columns are screened no-ops, the cross region stays
  exactly zero, extra zeros drop out of max-reductions) but changes the mean
  denominator from s^2 to bin^2 — so the packer precomputes the scale at the
  SOURCE shape (``engine.waves.bucket_scales``) and each lane solves against
  the tolerance its unfused dispatch would have used.

Everything else — inner coordinate descent, column update, sweep loop, Theta
recovery — is ``bcd.bcd_sweeps``, the code ``glasso_bcd`` runs;
tests/test_fused.py pins the lane-for-lane ``==``-equality against
per-bucket ``glasso_bcd``.

The second return is the per-lane SWEEP COUNT: under ``vmap`` the while_loop
is select-masked (converged lanes freeze, so packing cannot change results)
but every lane still pays the slowest lane's sweeps in compute — the count
is what lets the executor report ``solver.fused.lockstep_sweeps_saved``, the
work the Pallas kernel's genuine per-block early exit avoids.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.solvers.bcd import bcd_sweeps


def fused_bcd_init(
    S: jax.Array,
    lam: jax.Array,
    scale: jax.Array,
    W0: jax.Array,
    Theta0: jax.Array,
    *,
    tol: float,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One lane's sweep-loop state: (S^T, W_init, B_init^T, tol * scale).

    Shared by the reference and the Pallas wrapper, which runs it in XLA
    ahead of the kernel, so both start every lane from the same bits."""
    b = S.shape[0]
    dtype = S.dtype
    lam = jnp.asarray(lam, dtype)
    # Diagonal KKT is exact at the solution; enforce from the start.
    W_init = jnp.where(jnp.eye(b, dtype=bool), jnp.diag(S) + lam, W0)
    d = jnp.diagonal(Theta0)
    d = jnp.where(d > 0, d, jnp.ones((), dtype))  # PD => d > 0; belt+braces
    B_init = jnp.where(jnp.eye(b, dtype=bool), 0.0, -(Theta0 / d[None, :]))
    return S.T, W_init, B_init.T, jnp.asarray(tol, dtype) * scale


def fused_bcd_single(
    S: jax.Array,
    lam: jax.Array,
    scale: jax.Array,
    W0: jax.Array,
    Theta0: jax.Array,
    *,
    max_sweeps: int = 100,
    n_cd: int = 100,
    tol: float = 1e-6,
    node_screen: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """One packed lane: ``glasso_bcd`` with injected warm pair + scale.

    Returns (Theta, sweeps).  ``S`` may be a source block re-padded into a
    larger bin (identity diagonal, zero off-diagonal): padded columns are
    eq.-(10)-screened exactly and the [:s, :s] slice of the result equals
    the unfused solve of the (s, s) block bit for bit (up to zero signs).
    """
    lam = jnp.asarray(lam, S.dtype)
    ST, W, BT, thr = fused_bcd_init(S, lam, scale, W0, Theta0, tol=tol)
    ThetaT, sweeps = bcd_sweeps(
        ST, W, BT, lam, thr,
        max_sweeps=max_sweeps, n_cd=n_cd, node_screen=node_screen,
    )
    return 0.5 * (ThetaT.T + ThetaT), sweeps


@functools.partial(
    jax.jit, static_argnames=("max_sweeps", "n_cd", "tol", "node_screen")
)
def fused_bcd_ref_stack(
    blocks: jax.Array,
    lams: jax.Array,
    scales: jax.Array,
    W0: jax.Array,
    T0: jax.Array,
    *,
    max_sweeps: int = 100,
    n_cd: int = 100,
    tol: float = 1e-6,
    node_screen: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """vmapped reference over a packed (N, bin, bin) megabatch.

    Returns (Theta (N, bin, bin), sweeps (N,) int32).  Under vmap the sweep
    while_loop runs to the batch max with converged lanes select-frozen, so
    per-lane results are independent of what the lane is packed with — the
    property the wave packer's bitwise gate rests on."""
    fn = functools.partial(
        fused_bcd_single,
        max_sweeps=max_sweeps, n_cd=n_cd, tol=tol, node_screen=node_screen,
    )
    return jax.vmap(fn)(blocks, lams, scales, W0, T0)
