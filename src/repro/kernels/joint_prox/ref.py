"""Reference (pure jnp) fused prox step for the joint multi-class ADMM.

One joint-ADMM iteration ends with the Z-update: at every matrix entry
(i, j) the K class values are proximal-mapped JOINTLY under the composite
penalty lam1 * l1 + lam2 * P2, where P2 couples the classes:

    group  P2 = sqrt(sum_k theta_k^2)            (off-diagonal entries)
    fused  P2 = sum_{k<k'} |theta_k - theta_k'|  (off-diagonal entries)

Both composite proxes have EXACT closed forms built from two monotone
coordinate-wise-compatible pieces, so no inner iteration is needed:

    group  prox_{t1 l1 + t2 l2}   = group-shrink  o  soft(., t1)
           (sparse-group-lasso order: l1 first, then v * (1 - t2/||v||)_+)
    fused  prox_{t1 l1 + t2 TV_K} = soft(., t1)  o  prox_{t2 TV_K}
           (soft-thresholding is monotone, so the TV subgradient chosen at
           the TV prox stays valid after shrinkage — the Friedman et al.
           2007 fused-lasso argument, which only needs monotonicity)

with TV_K the complete-graph total variation over the K classes.  Its prox
is computed WITHOUT a data-dependent sort primitive (the same code must run
inside the Pallas kernel): stable ranks from K^2 pairwise comparisons, the
rank-r order statistics via one-hot contractions, the stationarity shift
b_r = a_(r) - t(2r - K + 1), and the isotonic regression of b via the exact
minimax formula  y_r = max_{j<=r} min_{l>=r} mean(b_j..b_l)  (pool-adjacent-
violators in closed form; K is small and static, so the K^3 broadcast is a
handful of VPU ops).  Tied inputs produce tied outputs (the prox of a
permutation-symmetric function maps equal coordinates to equal values), so
the arbitrary stable tie-break in the rank is sound.

Diagonal entries take only the l1 piece: the cross-class penalty is
OFF-DIAGONAL by construction (coupling the diagonals would break the
per-class diagonal KKT W_ii = S_ii + lam1 that padding and isolated-vertex
assembly rely on).

The residual reductions ride along exactly like ``shard_prox``:
rp2 = sum((Theta - Z_new)^2), rd2 = sum((Z_new - Z_old)^2), both over all K
classes — the Pallas kernel fuses prox + both reductions into one HBM pass;
this module is the semantics, the off-TPU dispatch target, and the
pallas-vs-ref test oracle.
"""

from __future__ import annotations

import jax.numpy as jnp

PENALTIES = ("group", "fused")


def _soft(x, t):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


def group_prox(a: jnp.ndarray, t1, t2) -> jnp.ndarray:
    """prox of t1*||.||_1 + t2*||.||_2 along axis 0 of a (K, ...) array."""
    v = _soft(a, t1)
    nrm = jnp.sqrt(jnp.sum(v * v, axis=0, keepdims=True))
    scale = jnp.where(
        nrm > 0.0, jnp.maximum(1.0 - t2 / jnp.where(nrm > 0.0, nrm, 1.0), 0.0), 0.0
    )
    return v * scale


def tv_complete_prox(a: jnp.ndarray, t) -> jnp.ndarray:
    """prox of t * sum_{k<k'} |x_k - x_k'| along axis 0 of a (K, ...) array."""
    K = a.shape[0]
    if K == 1:
        return a
    return jnp.stack(tv_complete_prox_classes([a[k] for k in range(K)], t))


def tv_complete_prox_classes(xs: list, t) -> list:
    """``tv_complete_prox`` on a list of K same-shape per-class arrays.

    Sort-free formulation (see module docstring): ranks via pairwise
    comparisons, order statistics via one-hot sums, minimax isotonic fit,
    rank-gather back.  Every loop is over the STATIC class axis K and every
    op is elementwise on one class's array, so the same code lowers inside
    the Pallas kernel, which has no gather."""
    K = len(xs)
    if K == 1:
        return list(xs)
    dtype = xs[0].dtype
    t = jnp.asarray(t, dtype)
    # stable rank: #(strictly smaller) + #(equal with smaller class index)
    ranks = []
    for i in range(K):
        r = jnp.zeros_like(xs[i])
        for j in range(K):
            if j != i:
                less = (xs[j] <= xs[i]) if j < i else (xs[j] < xs[i])
                r = r + less.astype(dtype)
        ranks.append(r)  # values 0..K-1
    # one-hot of each class's rank, and the order statistics a_(r)
    onehot = [[(ranks[k] == r).astype(dtype) for k in range(K)] for r in range(K)]
    asort = []
    for r in range(K):
        acc = onehot[r][0] * xs[0]
        for k in range(1, K):
            acc = acc + onehot[r][k] * xs[k]
        asort.append(acc)  # ascending in r
    # stationarity shift for strictly ordered coordinates, and prefix sums
    # P[r] = sum of the first r shifted values
    prefix = [jnp.zeros_like(xs[0])]
    for r in range(K):
        prefix.append(prefix[-1] + (asort[r] - t * (2.0 * r - (K - 1))))
    # segment means M[j][l] = mean(b_j..b_l) for j <= l, and the isotonic
    # fit via minimax: y_r = max_{j<=r} min_{l>=r} M[j][l]
    M = [
        {l: (prefix[l + 1] - prefix[j]) / float(l - j + 1) for l in range(j, K)}
        for j in range(K)
    ]
    ys = []
    for r in range(K):
        best = None
        for j in range(r + 1):
            inner = M[j][r]
            for l in range(r + 1, K):
                inner = jnp.minimum(inner, M[j][l])
            best = inner if best is None else jnp.maximum(best, inner)
        ys.append(best)  # nondecreasing in r
    # gather back by rank
    out = []
    for k in range(K):
        acc = onehot[0][k] * ys[0]
        for r in range(1, K):
            acc = acc + onehot[r][k] * ys[r]
        out.append(acc)
    return out


def fused_prox(a: jnp.ndarray, t1, t2) -> jnp.ndarray:
    """prox of t1*||.||_1 + t2*TV_complete along axis 0 of a (K, ...) array."""
    return _soft(tv_complete_prox(a, t2), t1)


def joint_prox_entries(a: jnp.ndarray, t1, t2, *, penalty: str) -> jnp.ndarray:
    """Off-diagonal joint prox along the class axis (axis 0)."""
    if penalty == "group":
        return group_prox(a, t1, t2)
    if penalty == "fused":
        return fused_prox(a, t1, t2)
    raise ValueError(f"unknown joint penalty {penalty!r}; available: {PENALTIES}")


def joint_prox_classes(xs: list, t1, t2, *, penalty: str) -> list:
    """``joint_prox_entries`` on a list of K per-class arrays (the Pallas
    kernel's form: one (rows, b) tile per class, no stacked indexing)."""
    if penalty == "group":
        vs = [_soft(x, t1) for x in xs]
        sq = vs[0] * vs[0]
        for v in vs[1:]:
            sq = sq + v * v
        nrm = jnp.sqrt(sq)
        scale = jnp.where(
            nrm > 0.0,
            jnp.maximum(1.0 - t2 / jnp.where(nrm > 0.0, nrm, 1.0), 0.0),
            0.0,
        )
        return [v * scale for v in vs]
    if penalty == "fused":
        return [_soft(y, t1) for y in tv_complete_prox_classes(xs, t2)]
    raise ValueError(f"unknown joint penalty {penalty!r}; available: {PENALTIES}")


def joint_prox_ref(
    theta: jnp.ndarray,
    u: jnp.ndarray,
    z_old: jnp.ndarray,
    t1,
    t2,
    *,
    penalty: str,
):
    """(Z_new, U_new, rp2, rd2) for one (K, b, b) block.

    Diagonal entries take soft(., t1) only (lam2 is off-diagonal); both
    residual partials sum over all K classes."""
    t1 = jnp.asarray(t1, theta.dtype)
    t2 = jnp.asarray(t2, theta.dtype)
    a = theta + u
    z_off = joint_prox_entries(a, t1, t2, penalty=penalty)
    eye = jnp.eye(theta.shape[-1], dtype=bool)
    z_new = jnp.where(eye[None], _soft(a, t1), z_off)
    u_new = a - z_new
    rp2 = jnp.sum((theta - z_new) ** 2)
    rd2 = jnp.sum((z_new - z_old) ** 2)
    return z_new, u_new, rp2, rd2
