"""ADMM for the graphical lasso [Boyd et al. 2011, Section 6.5].

    Theta-update:  rho*Theta - Theta^{-1} = rho*(Z - U) - S
                   -> eigendecompose the RHS, theta_i = (d_i + sqrt(d_i^2 + 4 rho)) / (2 rho)
    Z-update:      Z = soft(Theta + U, lam/rho)      (diagonal penalized too —
                   criterion (1) includes i = j, hence W_ii = S_ii + lam)
    U-update:      U += Theta - Z

Per-iteration cost is one (b, b) eigh — O(b^3), same class as one GLASSO
sweep.  Most robust solver on ill-conditioned blocks; the tests use it with a
tight tolerance as the cross-check oracle.  Returns Z (the sparse iterate), so
the support is exactly sparse — important for Theorem-1 pattern checks.

rho is adapted online (Boyd Section 3.4.1: x2 when the primal residual runs
10x ahead of the dual, /2 in the opposite case, with the scaled dual variable
U rescaled accordingly) — fixed rho=1 stalls far from the optimum on
ill-conditioned blocks well inside the default iteration budget.

WARM STARTS: ``W0`` (a covariance iterate, W ~= Theta*^{-1} — the executor's
path/repair currency) seeds BOTH halves of the splitting:

    Z0 = W0^{-1}                 the primal candidate
    U0 = (W0 - S) / rho          the scaled dual — from the Theta-update
                                 optimality rho*Theta - Theta^{-1} = rho*(Z-U)-S
                                 at the fixed point Theta = Z

Seeding Z alone is nearly worthless: ADMM then spends as many iterations
rebuilding U from zero as a cold start spends on everything (the dual IS the
memory of the splitting).  With both seeded, an exact W0 is a fixed point —
the KKT conditions (11)/(12) make soft(Z0 + U0, lam/rho) return Z0 exactly —
and a near-solution W0 (path step, executor repair, serving re-solve)
converges in a handful of sweeps.  A singular/non-finite W0 falls back to the
cold start inside the jit.

Callers that HOLD the Theta-side iterate (executor repairs hold the rejected
candidate, the path warm start holds the previous padded solution) pass it
as ``Theta0`` alongside W0: Z0 then comes straight from Theta0 and the
``inv(W0)`` above is skipped — they already paid one O(b^3) inversion to
build W0 from it, and inverting back would waste a second one (plus
precision on ill-conditioned blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def theta_eigenvalues(d, rho):
    """Roots theta > 0 of rho theta - 1 / theta = d, the Theta-update's
    eigenvalues.  The textbook (d + sqrt(d^2 + 4 rho)) / (2 rho) cancels
    for d << 0 (in float32 it loses every digit once |d| ~ 1e3 sqrt(rho)
    and stalls ADMM far from the optimum); there the equal form
    2 / (sqrt(d^2 + 4 rho) - d) is exact to rounding."""
    r = jnp.sqrt(d * d + 4.0 * rho)
    return jnp.where(d >= 0, (d + r) / (2.0 * rho), 2.0 / (r - d))


def _soft(x, t):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


@functools.partial(jax.jit, static_argnames=("max_iter",))
def glasso_admm_info(
    S: jax.Array,
    lam: jax.Array,
    *,
    rho: float = 1.0,
    max_iter: int = 2000,
    tol: float = 1e-7,
    W0: jax.Array | None = None,
    Theta0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """ADMM returning (Theta, iterations) — the iteration count backs the
    warm-start acceptance tests and the executor's repair accounting."""
    b = S.shape[0]
    dtype = S.dtype
    lam = jnp.asarray(lam, dtype)
    rho0 = jnp.asarray(rho, dtype)

    def theta_update(Z, U, rho):
        rhs = rho * (Z - U) - S
        d, Q = jnp.linalg.eigh(rhs)
        theta_d = theta_eigenvalues(d, rho)
        return jnp.matmul(
            Q * theta_d[None, :], Q.T, precision=jax.lax.Precision.HIGHEST
        )

    def body(carry):
        Z, U, rho, _, _, it = carry
        Theta = theta_update(Z, U, rho)
        Z_new = _soft(Theta + U, lam / rho)
        U_new = U + Theta - Z_new
        r_prim = jnp.linalg.norm(Theta - Z_new)
        r_dual = rho * jnp.linalg.norm(Z_new - Z)
        # adaptive rho; U is the SCALED dual, so it rescales inversely
        factor = jnp.where(
            r_prim > 10.0 * r_dual,
            jnp.asarray(2.0, dtype),
            jnp.where(r_dual > 10.0 * r_prim, jnp.asarray(0.5, dtype), jnp.asarray(1.0, dtype)),
        )
        return Z_new, U_new / factor, rho * factor, r_prim, r_dual, it + 1

    def cond(carry):
        _, _, _, r_prim, r_dual, it = carry
        eps = tol * b
        return jnp.logical_and(
            jnp.logical_or(r_prim > eps, r_dual > eps), it < max_iter
        )

    cold_Z = jnp.where(
        jnp.eye(b, dtype=bool), 1.0 / (jnp.diag(S) + lam), jnp.zeros_like(S)
    )
    if W0 is None:
        Z0, U0 = cold_Z, jnp.zeros_like(S)
    else:
        Z0c = Theta0 if Theta0 is not None else jnp.linalg.inv(W0)
        Z0c = 0.5 * (Z0c + Z0c.T)
        usable = jnp.all(jnp.isfinite(Z0c)) & jnp.all(jnp.isfinite(W0))
        Z0 = jnp.where(usable, Z0c, cold_Z)
        U0 = jnp.where(usable, (W0 - S) / rho0, jnp.zeros_like(S))
    init = (
        Z0,
        U0,
        rho0,
        jnp.asarray(jnp.inf, dtype),
        jnp.asarray(jnp.inf, dtype),
        jnp.int32(0),
    )
    Z, U, _, _, _, it = jax.lax.while_loop(cond, body, init)
    return 0.5 * (Z + Z.T), it


def glasso_admm(
    S: jax.Array,
    lam: jax.Array,
    *,
    rho: float = 1.0,
    max_iter: int = 2000,
    tol: float = 1e-7,
    W0: jax.Array | None = None,
    Theta0: jax.Array | None = None,
) -> jax.Array:
    """Single-block solver contract ``solve(S, lam, **opts) -> Theta``."""
    Theta, _ = glasso_admm_info(
        S, lam, rho=rho, max_iter=max_iter, tol=tol, W0=W0, Theta0=Theta0
    )
    return Theta
