"""ADMM for the joint multi-class graphical lasso (problem (J) in
``repro.joint.screen``).

The splitting is the single-class one (``core.solvers.admm``) lifted to a
(K, b, b) stack with the Z-update coupled across classes:

    Theta-update:  per class, the SAME eigh-based update as single-class
                   ADMM — rho*Theta_k - Theta_k^{-1} = rho*(Z_k - U_k) - S_k
                   — batched over K with one vmapped eigh;
    Z-update:      the JOINT prox of lam1*l1 + lam2*P2 applied entrywise to
                   the K-vector at every (i, j) — the fused
                   ``kernels/joint_prox`` pass (Pallas on TPU, jnp ref
                   off-TPU), which also returns both residual partials;
                   diagonal entries take the l1 piece only;
    U-update:      U += Theta - Z (inside the same fused pass).

rho is shared across classes (the coupled prox needs one lam/rho) and
adapted online exactly like the single-class solver (Boyd Section 3.4.1);
the stopping criterion scales the single-class eps by sqrt(K) to keep the
per-entry tolerance comparable.  Warm starts mirror ``glasso_admm``: a
(K, b, b) covariance stack W0 seeds Z0 = W0^{-1} (or Theta0 directly when
the caller holds it — the ``theta_warm`` contract) and U0 = (W0 - S)/rho
per class; a non-finite seed falls back to the cold start inside the jit.

Returns Z — exactly sparse off-support (the prox output), which is what the
union-support property tests and the K-class Theorem-1 check need.
Registered as the capability-tagged ``SolverSpec`` "joint_admm"
(``repro.joint.__init__``): batched=False keeps it out of the single-class
``SOLVERS`` view (its contract is (K, b, b), not (b, b)); the joint
executor vmaps it over bucket stacks itself through the shared compiled
cache with K in the key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.solvers.admm import theta_eigenvalues
from repro.kernels.joint_prox.ops import joint_prox_step


@functools.partial(jax.jit, static_argnames=("penalty", "max_iter"))
def joint_admm_info(
    S: jax.Array,
    lam1: jax.Array,
    lam2: jax.Array,
    *,
    penalty: str = "group",
    rho: float = 1.0,
    max_iter: int = 2000,
    tol: float = 1e-7,
    W0: jax.Array | None = None,
    Theta0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Joint ADMM returning (Theta (K, b, b), iterations)."""
    K, b, _ = S.shape
    dtype = S.dtype
    lam1 = jnp.asarray(lam1, dtype)
    lam2 = jnp.asarray(lam2, dtype)
    rho0 = jnp.asarray(rho, dtype)

    def theta_update(Z, U, rho):
        rhs = rho * (Z - U) - S
        d, Q = jnp.linalg.eigh(rhs)  # batched over the class axis
        theta_d = theta_eigenvalues(d, rho)
        return jnp.einsum(
            "kij,kj,klj->kil", Q, theta_d, Q, precision=jax.lax.Precision.HIGHEST
        )

    def body(carry):
        Z, U, rho, _, _, it = carry
        Theta = theta_update(Z, U, rho)
        Z_new, U_new, rp2, rd2 = joint_prox_step(
            Theta, U, Z, lam1 / rho, lam2 / rho, penalty=penalty
        )
        r_prim = jnp.sqrt(rp2)
        r_dual = rho * jnp.sqrt(rd2)
        # adaptive rho; U is the SCALED dual, so it rescales inversely
        factor = jnp.where(
            r_prim > 10.0 * r_dual,
            jnp.asarray(2.0, dtype),
            jnp.where(
                r_dual > 10.0 * r_prim,
                jnp.asarray(0.5, dtype),
                jnp.asarray(1.0, dtype),
            ),
        )
        return Z_new, U_new / factor, rho * factor, r_prim, r_dual, it + 1

    def cond(carry):
        _, _, _, r_prim, r_dual, it = carry
        eps = tol * b * jnp.sqrt(jnp.asarray(float(K), dtype))
        return jnp.logical_and(
            jnp.logical_or(r_prim > eps, r_dual > eps), it < max_iter
        )

    eye = jnp.eye(b, dtype=bool)
    diag = jnp.diagonal(S, axis1=1, axis2=2)  # (K, b)
    cold_Z = jnp.where(
        eye[None], (1.0 / (diag + lam1))[:, :, None], jnp.zeros_like(S)
    )
    if W0 is None:
        Z0, U0 = cold_Z, jnp.zeros_like(S)
    else:
        Z0c = Theta0 if Theta0 is not None else jnp.linalg.inv(W0)
        Z0c = 0.5 * (Z0c + jnp.swapaxes(Z0c, -1, -2))
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
    return 0.5 * (Z + jnp.swapaxes(Z, -1, -2)), it


def joint_admm(
    S: jax.Array,
    lam1: jax.Array,
    lam2: jax.Array,
    *,
    penalty: str = "group",
    rho: float = 1.0,
    max_iter: int = 2000,
    tol: float = 1e-7,
    W0: jax.Array | None = None,
    Theta0: jax.Array | None = None,
) -> jax.Array:
    """Joint-block solver contract ``solve(S (K,b,b), lam1, lam2) -> Theta``."""
    Theta, _ = joint_admm_info(
        S, lam1, lam2, penalty=penalty, rho=rho, max_iter=max_iter, tol=tol,
        W0=W0, Theta0=Theta0,
    )
    return Theta
