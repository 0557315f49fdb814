"""GLASSO block coordinate descent [Friedman, Hastie, Tibshirani 2007].

Maintains W ~= Theta^{-1}.  One sweep updates every row/column j:

    beta_j = argmin_beta  1/2 beta' W11 beta - beta' s12 + lam ||beta||_1   (9)
    w12    = W11 beta_j

with the inner lasso solved by cyclic coordinate descent.  On convergence the
precision matrix is recovered column-wise:

    theta_22 = 1 / (w22 - w12' beta),    theta_12 = -beta * theta_22

KKT sanity (paper eq. (11)-(12)): W_ii = S_ii + lam exactly, and
|S_ij - W_ij| <= lam wherever Theta_ij = 0.

Node screening (paper eq. (10)): ||s12||_inf <= lam  =>  beta_j = 0.  The
paper observes this check is an immediate consequence of the block updates yet
was *missing* from GLASSO 1.4 — we make it explicit: the inner CD loop is
skipped entirely for screened columns (a lax.cond on the hot path).

Everything is expressed with full-matrix ops on 2-D rows and columns (no
row/col deletion, no matrix products), so the solver jits once per block
size, vmaps across a bucket of same-size components, and the same sweep code
runs inside the ``bucket_glasso`` Pallas kernel (``bcd_sweeps``).  The
products are elementwise multiplies with reductions, which keep full f32
accuracy on the TPU, where an f32 matmul at default precision would not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _soft(x, t):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


# -- exact data movement ------------------------------------------------------
#
# The sweep arithmetic below is written once, on 2-D values: rows (1, b),
# columns (b, 1) and (1, 1) scalars.  Reading or writing one row, column or
# entry at a traced index is pure data movement and has two exact spellings:
# dynamic slices (``masked=False``: O(b) per access, what XLA runs) and iota
# masks with a one-hot reduction (``masked=True``: what a Pallas TPU kernel
# can lower, since Mosaic has no dynamic_slice on values).  Both move the same
# bits (a one-hot sum adds only zeros; the sign of a zero is the one thing it
# may drop), so ``glasso_bcd``, the fused reference and the bucket_glasso
# kernel agree lane for lane under ``==``.


def _take(M, k, axis: int, masked: bool):
    """Row (axis=0, -> (1, b)) or column (axis=1, -> (b, 1)) k of M."""
    if not masked:
        return jax.lax.dynamic_slice_in_dim(M, k, 1, axis=axis)
    idx = jax.lax.broadcasted_iota(jnp.int32, M.shape, axis)
    return jnp.sum(jnp.where(idx == k, M, 0.0), axis=axis, keepdims=True)


def _put(M, k, v, axis: int, masked: bool):
    """M with row (axis=0) or column (axis=1) k replaced by v."""
    if not masked:
        return jax.lax.dynamic_update_slice_in_dim(M, v, k, axis=axis)
    idx = jax.lax.broadcasted_iota(jnp.int32, M.shape, axis)
    return jnp.where(idx == k, v, M)


def _flip(v, masked: bool):
    """(1, b) <-> (b, 1) transpose of a vector."""
    if not masked:
        return v.reshape(v.shape[::-1])
    n = max(v.shape)
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) == (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    )
    axis = 1 if v.shape[0] == 1 else 0
    return jnp.sum(jnp.where(eye, v, 0.0), axis=axis, keepdims=True)


def _lasso_cd(W, s12, lam, beta0, j, *, n_cd: int, tol, masked: bool):
    """Cyclic coordinate descent for (9) on column j; vectors are (1, b) rows.

    beta[j] is pinned to 0.  Coordinate update:
        beta_k <- soft(s12_k - sum_{l != k} W_kl beta_l, lam) / W_kk
    Runs until the sweep-wise max update < tol or n_cd sweeps.
    """
    b = W.shape[0]
    zero = jnp.zeros((1, 1), W.dtype)

    def sweep(beta):
        def coord(k, carry):
            beta, delta = carry
            wk = _take(W, k, 0, masked)
            wkk = _take(wk, k, 1, masked)
            bk = _take(beta, k, 1, masked)
            dot = jnp.sum(wk * beta, axis=1, keepdims=True)
            r = _take(s12, k, 1, masked) - (dot - wkk * bk)
            new = _soft(r, lam) / wkk
            new = jnp.where(k == j, zero, new)
            delta = jnp.maximum(delta, jnp.abs(new - bk))
            return _put(beta, k, new, 1, masked), delta

        return jax.lax.fori_loop(0, b, coord, (beta, zero))

    def cond(c):
        _, delta, it = c
        return jnp.logical_and(jnp.max(delta) > tol, it < n_cd)

    def body(c):
        beta, _, it = c
        beta, delta = sweep(beta)
        return beta, delta, it + 1

    beta, delta = sweep(_put(beta0, j, zero, 1, masked))
    beta, _, _ = jax.lax.while_loop(cond, body, (beta, delta, jnp.int32(1)))
    return beta


def bcd_sweeps(
    ST: jax.Array,
    W: jax.Array,
    BT: jax.Array,
    lam,
    thr,
    *,
    max_sweeps: int,
    n_cd: int,
    node_screen: bool,
    masked: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """The BCD sweep loop from an initialized state; returns (Theta^T, sweeps).

    ``ST`` is S transposed (row j is column j of S), ``W`` the initial
    covariance iterate with its diagonal already at S_ii + lam, ``BT`` the
    initial lasso coefficients transposed (row j is beta for column j), and
    ``thr`` = tol * scale, both the sweep and the inner CD tolerance.  The
    caller symmetrizes: Theta = (Theta^T + Theta^T') / 2.
    """
    b = W.shape[0]
    dtype = W.dtype
    zero = jnp.zeros((1, 1), dtype)
    eye = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0) == (
        jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    )

    def column_update(j, W, BT):
        s12 = _put(_take(ST, j, 0, masked), j, zero, 1, masked)
        screened = jnp.max(jnp.abs(s12)) <= lam

        def solve_col(operand):
            W, beta0 = operand
            return _lasso_cd(
                W, s12, lam, beta0, j, n_cd=n_cd, tol=thr, masked=masked
            )

        def zero_col(operand):
            _, beta0 = operand
            return jnp.zeros_like(beta0)

        beta0 = _take(BT, j, 0, masked)
        if node_screen:
            beta = jax.lax.cond(screened, zero_col, solve_col, (W, beta0))
        else:
            beta = solve_col((W, beta0))
        w12 = jnp.sum(W * beta, axis=1, keepdims=True)  # W @ beta, (b, 1)
        wjj = _take(_take(W, j, 0, masked), j, 1, masked)
        w12 = _put(w12, j, wjj, 0, masked)
        W = _put(W, j, w12, 1, masked)
        W = _put(W, j, _flip(w12, masked), 0, masked)
        return W, _put(BT, j, beta, 0, masked)

    def sweep(carry):
        W, BT, _, it = carry
        W_old = W
        W, BT = jax.lax.fori_loop(
            0, b, lambda j, wb: column_update(j, *wb), (W, BT)
        )
        delta = jnp.max(jnp.abs(W - W_old))
        return W, BT, delta, it + 1

    def cond(carry):
        _, _, delta, it = carry
        return jnp.logical_and(delta > thr, it < max_sweeps)

    W, BT, delta, _ = sweep(
        (W, BT, jnp.asarray(jnp.inf, dtype), jnp.int32(0))
    )
    W, BT, _, sweeps = jax.lax.while_loop(
        cond, sweep, (W, BT, delta, jnp.int32(1))
    )

    # Recover Theta column-wise from the final (W, B): W is exactly
    # symmetric after a full sweep, so w12 of column j is row j of W.
    #   theta_22 = 1 / (w22 - w12' beta),   theta_12 = -beta * theta_22
    dots = jnp.sum(jnp.where(eye, 0.0, W) * BT, axis=1, keepdims=True)
    wdiag = jnp.sum(jnp.where(eye, W, 0.0), axis=1, keepdims=True)
    t22 = 1.0 / (wdiag - dots)
    return jnp.where(eye, t22, -BT * t22), sweeps


@functools.partial(
    jax.jit, static_argnames=("max_sweeps", "n_cd", "node_screen")
)
def glasso_bcd(
    S: jax.Array,
    lam: jax.Array,
    *,
    max_sweeps: int = 100,
    n_cd: int = 100,
    tol: float = 1e-6,
    node_screen: bool = True,
    W0: jax.Array | None = None,
    Theta0: jax.Array | None = None,
) -> jax.Array:
    """Solve the graphical lasso on one (b, b) block. Returns Theta.

    W0 warm-starts the covariance iterate (lambda-path reuse, Theorem 2);
    default is the cold start W = S + lam*I.  Theta0 additionally seeds the
    inner-lasso coefficients: column j of (9) relates to the precision column
    via theta_12 = -beta * theta_22, so beta_j = -Theta0[:, j] / Theta0[j, j]
    (diagonal pinned to 0).  Without it every column's coordinate descent —
    the dominant cost — restarts from beta = 0 no matter how good W0 is.
    """
    b = S.shape[0]
    dtype = S.dtype
    lam = jnp.asarray(lam, dtype)
    eye = jnp.eye(b, dtype=dtype)
    W_init = (S + lam * eye) if W0 is None else W0
    # Diagonal KKT is exact at the solution; enforce from the start.
    W_init = jnp.where(jnp.eye(b, dtype=bool), jnp.diag(S) + lam, W_init)
    if Theta0 is None:
        B_init = jnp.zeros((b, b), dtype)
    else:
        d = jnp.diagonal(Theta0)
        d = jnp.where(d > 0, d, jnp.ones((), dtype))  # PD => d > 0; belt+braces
        B_init = jnp.where(jnp.eye(b, dtype=bool), 0.0, -(Theta0 / d[None, :]))
    scale = jnp.mean(jnp.abs(S - jnp.diag(jnp.diag(S)))) + jnp.asarray(1e-12, dtype)
    thr = jnp.asarray(tol, dtype) * scale
    ThetaT, _ = bcd_sweeps(
        S.T, W_init, B_init.T, lam, thr,
        max_sweeps=max_sweeps, n_cd=n_cd, node_screen=node_screen,
    )
    return 0.5 * (ThetaT.T + ThetaT)
