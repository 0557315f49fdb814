"""Mesh-spanning graphical lasso for oversize components.

Every other solver in this package runs one block on one device, holding the
(b, b) iterate (and, for ADMM, an O(b^3) eigh workspace) in a single HBM.
For moderate rho the paper's largest component stays near size p, so the
single-device cap on b IS the system's scale cap.  This solver removes it:

* the (b, b) iterates stay ROW-SHARDED across the mesh for the whole solve —
  no stage ever materializes a full (b, b) array on one device (matmuls are
  the ring-algorithm ``core.distributed.ring_matmul``, transposes one-shard
  ``transpose_rowsharded`` all_to_alls, spectral estimates distributed
  matvec power iterations);

* the outer loop is the SAME ADMM as the single-device oracle (Boyd 6.5,
  adaptive rho 3.4.1) — but the O(b^3) eigh of its Theta-update

      Theta = (M + sqrt(M^2 + 4 rho I)) / (2 rho),   M = rho (Z - U) - S

  is replaced by inner MATRIX ITERATIONS built from distributed matvecs:
  a warm-vector power iteration bounds ||M||_2, and a coupled Newton-Schulz
  square-root iteration (Higham 1997: Y <- Y T, Zc <- T Zc with
  T = (3 I - Zc Y) / 2 on the spectrally-scaled argument) computes the sqrt
  with ring matmuls only.  The inner tolerance is tied to the outer primal
  residual (inexact ADMM with vanishing errors), so early outer iterations
  are cheap and late ones exact.  Unlike a proximal-gradient linearization
  of the Theta-step — which stalls: the tiny trust-region step keeps the
  primal residual artificially small and drives the adaptive rho into the
  floor — this keeps the oracle's iteration count (~1x) while making every
  FLOP a shardable GEMM;

* the Z/U prox tail (soft-threshold + dual update + both residual
  reductions) is fused into one HBM pass by ``kernels/shard_prox`` (jnp
  reference off-TPU — the tree_glasso trade-off);

* the returned Z (exactly sparse, like the dense ADMM's) is KKT-verified IN
  PLACE against the sharded S: a warm-started column-wise block-CG solves
  Z W = I (the "distributed matvec/CG inner solve" proper — CG also detects
  a non-PD candidate via negative curvature and reports residual = inf),
  then eq. (11)-(12) reduce shard-locally with one pmax.  The executor
  compares the returned residual to ``route_check_tol`` and falls back to
  the single-device iterative tail on failure, so the sharded route obeys
  the same "changes cost, never the answer" contract as every PR-2 route.

Theta-update PD holds by construction (theta_i = (d_i + sqrt(d_i^2 +
4 rho)) / (2 rho) > 0), so there is no line search and no PD safeguard in
the hot loop; the only defensive state is a spectral-scale boost that
doubles when a Newton-Schulz pass fails to contract (non-finite or err
growth), reverting that outer step.

Counters:  solver.oversize.dispatched / .cg_iters (inner matrix-iteration
steps: Newton-Schulz + verification CG), plus the
``solver.oversize.device_bytes_peak`` watermark — the accounting model is
_BUFFERS_PER_DEVICE row-shards of (b_pad/d, b_pad) (DESIGN.md Section 11).
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.distributed import (
    matvec_rowsharded,
    ring_matmul,
    transpose_rowsharded,
)
from repro.core.instrument import bump, set_peak
from repro.core.jax_compat import local_device_mesh, shard_map
from repro.kernels.shard_prox import fused_prox_residual

#: exact-sparsity zero classification for the returned Z (same as closed_form)
_ZERO_TOL = 1e-12

#: per-device resident f64 row-shards during a solve: S, the ADMM pair
#: (Z, U), the Theta-update working set (M, M^2 + 4 rho I, Y, Zc, T) and the
#: prox outputs — the memory-model constant behind the bytes watermark
_BUFFERS_PER_DEVICE = 12

_CACHE_LOCK = threading.Lock()
_COMPILED: dict[tuple, Any] = {}


def mesh_axis_size(mesh, axis: str = "data") -> int:
    return int(np.prod([mesh.shape[a] for a in ([axis] if isinstance(axis, str) else axis)]))


def sharded_pad_size(b: int, n_shards: int) -> int:
    """Padded size for the sharded solver: the smallest multiple of
    8 * n_shards >= b, so every device owns an equal, sublane-aligned row
    shard.  Identity padding is exact (Theorem-1 corollary, see blocks.py)."""
    unit = 8 * n_shards
    return max(unit, -(-b // unit) * unit)


@dataclass
class ShardedSolve:
    """One oversize solve: the dense (b, b) Theta plus the verification and
    accounting facts the executor / benchmarks consume."""

    Theta: np.ndarray
    iters: int                 # outer ADMM iterations
    inner_iters: int           # Newton-Schulz + verification-CG steps
    retries: int               # outer steps reverted by the NS safeguard
    stalls: int                # ADMM loops ended by the float32 stall stop
    admm_residual: float       # max(primal, dual) residual where ADMM stopped
    admm_eps: float            # the eps that last loop was running to
    kkt_residual: float        # distributed eq.-(11)/(12) residual of Theta
    s_max: float               # max |S| over the padded block (KKT scale)
    rho: float                 # final (adapted) ADMM penalty
    b: int
    padded: int
    n_shards: int
    device_bytes: int          # accounting-model per-device peak


def _build_sharded(
    bp: int,
    d: int,
    axis: str,
    dtype,
    max_iter: int,
    ns_max: int,
    cg_max: int,
    pow_steps: int,
    warm: bool,
    mesh,
):
    """Compile the shard_map solve for one (padded size, mesh) family."""
    rl = bp // d
    spec = P(axis, None)
    in_specs = (spec, P()) + ((spec,) if warm else ())

    def run(S_rows, scalars, *warm_args):
        lam = scalars[0]
        rho0 = scalars[1]
        tol = scalars[2]
        idx = jax.lax.axis_index(axis)
        gi = idx * rl + jnp.arange(rl)
        eye_loc = gi[:, None] == jnp.arange(bp)[None, :]
        eyef = eye_loc.astype(S_rows.dtype)
        mm = functools.partial(ring_matmul, axis=axis, n_shards=d)
        tr = functools.partial(transpose_rowsharded, axis=axis, n_shards=d)
        mv = functools.partial(matvec_rowsharded, axis=axis, n_shards=d)

        def psum(x):
            return jax.lax.psum(x, axis) if d > 1 else x

        def pmax(x):
            return jax.lax.pmax(x, axis) if d > 1 else x

        def power_norm(A_rows, v):
            """(||A||_2 estimate, refreshed vector) for symmetric A."""

            def body(_, v):
                u = mv(A_rows, v)
                return u / (jnp.linalg.norm(u) + 1e-30)

            v = jax.lax.fori_loop(0, pow_steps, body, v)
            u = mv(A_rows, v)
            return (
                jnp.abs(jnp.dot(v, u, precision=jax.lax.Precision.HIGHEST)),
                u / (jnp.linalg.norm(u) + 1e-30),
            )

        def sqrt_ns(A_rows, c, ns_tol):
            """sqrt(A) via the coupled Newton-Schulz iteration on A / c.

            Requires spectrum(A / c) in (0, 3); the caller scales c from the
            power-iteration bound with margin.  Returns (sqrt, steps, ok)."""
            Y0 = A_rows / c
            Zc0 = eyef

            def cond(carry):
                _, _, err, prev_err, k = carry
                return (err > ns_tol) & (k < ns_max) & (err <= prev_err * 4.0)

            def body(carry):
                Y, Zc, err, _, k = carry
                T = 0.5 * (3.0 * eyef - mm(Zc, Y))
                err_new = pmax(jnp.max(jnp.abs(T - eyef)))
                return mm(Y, T), mm(T, Zc), err_new, err, k + 1

            init = (
                Y0, Zc0, jnp.asarray(jnp.inf, S_rows.dtype),
                jnp.asarray(jnp.inf, S_rows.dtype), jnp.int32(0),
            )
            Y, _, err, _, k = jax.lax.while_loop(cond, body, init)
            ok = (err <= ns_tol) & jnp.all(jnp.isfinite(Y))
            return jnp.sqrt(c) * Y, k, ok

        def cg_inverse(A_rows, W_init, cg_tol):
            """Column-wise block-CG on A W = I; returns (W, iters, neg)."""
            R = eyef - mm(A_rows, W_init)
            rs = psum(jnp.sum(R * R, axis=0))
            tol2 = cg_tol * cg_tol

            def cond(c):
                _, _, _, rs, it, neg = c
                return jnp.any(rs > tol2) & (it < cg_max) & ~neg

            def body(c):
                W, R, Pc, rs, it, neg = c
                AP = mm(A_rows, Pc)
                pAp = psum(jnp.sum(Pc * AP, axis=0))
                active = rs > tol2
                neg = neg | jnp.any(active & (pAp <= 0.0))
                alpha = jnp.where(
                    active & (pAp > 0.0), rs / jnp.where(pAp > 0.0, pAp, 1.0), 0.0
                )
                W = W + Pc * alpha[None, :]
                Rn = R - AP * alpha[None, :]
                rsn = psum(jnp.sum(Rn * Rn, axis=0))
                beta = jnp.where(active, rsn / jnp.where(rs > 0.0, rs, 1.0), 0.0)
                Pc = Rn + Pc * beta[None, :]
                return W, Rn, Pc, rsn, it + 1, neg

            W, _, _, _, it, neg = jax.lax.while_loop(
                cond, body, (W_init, R, R, rs, jnp.int32(0), jnp.bool_(False))
            )
            return W, it, neg

        kkt_rel = scalars[3]  # relative KKT target (inf = single attempt)
        diag_own = jnp.sum(jnp.where(eye_loc, S_rows, 0.0), axis=1)
        # Rounding floors.  Newton-Schulz stalls a few ulps (growing like
        # sqrt(bp)) from I, and the ADMM residuals stall at rounding noise
        # well above the eps a float64 tolerance asks for.  A tolerance under
        # its floor never passes: Newton-Schulz would run to ns_max with
        # every outer step reverted, ADMM to max_iter.  So the Newton-Schulz
        # tolerance is floored at the unit roundoff's scale, and in float32
        # the ADMM loop also stops once its residuals have stopped falling
        # (no 1% gain in ``stall`` steps).  Both bind only in float32; the
        # stall stops are counted and the residual they stopped at returned.
        unit = float(jnp.finfo(dtype).eps)
        ns_floor = max(1e-11, 4.0 * unit * bp**0.5)
        stall = max_iter if unit < 1e-10 else 50
        if warm:
            # At the ADMM fixed point U* = (Theta*^{-1} - S) / rho (the
            # Theta-update optimality rho Theta - Theta^{-1} = rho (Z - U) - S
            # at Theta = Z): seeding BOTH Z and U from Theta0 makes an exact
            # warm start a fixed point — Z alone leaves the dual to be
            # rebuilt from zero, which costs as many iterations as a cold
            # start.  One CG inverse buys that dual.  Same argument as the
            # dense ``glasso_admm`` W0 warm start.
            (theta0_rows,) = warm_args
            diag_t0 = jnp.sum(jnp.where(eye_loc, theta0_rows, 0.0), axis=1)
            Wt0 = jnp.where(eye_loc, (1.0 / diag_t0)[:, None], 0.0)
            Wt, _, neg0 = cg_inverse(theta0_rows, Wt0, jnp.asarray(1e-8, S_rows.dtype))
            usable = ~neg0 & jnp.all(jnp.isfinite(Wt))
            cold = jnp.where(eye_loc, (1.0 / (diag_own + lam))[:, None], 0.0)
            Z0 = jnp.where(usable, theta0_rows, cold)
            U0 = jnp.where(usable, (Wt - S_rows) / rho0, jnp.zeros_like(S_rows))
        else:
            Z0 = jnp.where(eye_loc, (1.0 / (diag_own + lam))[:, None], 0.0)
            U0 = jnp.zeros_like(S_rows)
        v0 = jnp.ones((bp,), S_rows.dtype) / jnp.sqrt(jnp.asarray(bp, S_rows.dtype))

        def admm_cond(c):
            _, _, _, _, _, rp, rd, it, _, retries, eps, _, since = c
            return (
                ((rp > eps) | (rd > eps))
                & (it < max_iter)
                & (retries < 30)
                & (since < stall)
            )

        def admm_body(c):
            Z, U, v, rho, boost, rp, rd, it, inner, retries, eps, best, since = c
            M = rho * (Z - U) - S_rows
            m, vn = power_norm(M, v)
            cscale = boost * (m * m + 4.0 * rho)
            ns_tol = jnp.clip(1e-3 * rp / bp, ns_floor, 1e-2)
            A = mm(M, M) + 4.0 * rho * eyef
            R_sqrt, ns_k, ns_ok = sqrt_ns(A, cscale, ns_tol)
            Theta = (M + R_sqrt) / (2.0 * rho)
            Zn, Un, rp2_l, rd2_l = fused_prox_residual(Theta, U, Z, lam / rho)
            rp_n = jnp.sqrt(psum(rp2_l))
            rd_n = rho * jnp.sqrt(psum(rd2_l))
            factor = jnp.where(
                rp_n > 10.0 * rd_n,
                jnp.asarray(2.0, S_rows.dtype),
                jnp.where(
                    rd_n > 10.0 * rp_n,
                    jnp.asarray(0.5, S_rows.dtype),
                    jnp.asarray(1.0, S_rows.dtype),
                ),
            )
            ok = ns_ok & jnp.isfinite(rp_n) & jnp.isfinite(rd_n)
            r = jnp.maximum(rp_n, rd_n)
            gained = ok & (r < 0.99 * best)
            return (
                jnp.where(ok, Zn, Z),
                jnp.where(ok, Un / factor, U),
                vn,
                jnp.where(ok, rho * factor, rho),
                jnp.where(ok, boost, 2.0 * boost),
                jnp.where(ok, rp_n, rp),
                jnp.where(ok, rd_n, rd),
                it + 1,
                inner + ns_k,
                retries + jnp.where(ok, 0, 1).astype(jnp.int32),
                eps,
                jnp.where(gained, r, best),
                jnp.where(gained, 0, since + 1),
            )

        def kkt_of(Zf, W_warm, inner_tol):
            """Distributed eq.-(11)/(12) residual of a symmetrized iterate."""
            Wz, cg_k, neg = cg_inverse(Zf, W_warm, inner_tol)
            Wz = 0.5 * (Wz + tr(Wz))
            zero = jnp.abs(Zf) <= _ZERO_TOL
            off = ~eye_loc
            v_zero = jnp.max(
                jnp.where(
                    zero & off, jnp.maximum(jnp.abs(S_rows - Wz) - lam, 0.0), 0.0
                )
            )
            v_act = jnp.max(
                jnp.where(~zero & off, jnp.abs(Wz - S_rows - lam * jnp.sign(Zf)), 0.0)
            )
            v_diag = jnp.max(jnp.where(eye_loc, jnp.abs(Wz - S_rows - lam), 0.0))
            res = pmax(jnp.maximum(jnp.maximum(v_zero, v_act), v_diag))
            return jnp.where(neg, jnp.asarray(jnp.inf, S_rows.dtype), res), Wz, cg_k

        s_max = pmax(jnp.max(jnp.abs(S_rows)))
        kkt_target = kkt_rel * jnp.maximum(s_max, 1.0)

        # ADMM-until-verified: each attempt runs the ADMM loop to its eps,
        # then VERIFIES the KKT residual in place; a miss tightens eps 20x
        # and continues warm (same Z/U/rho — no restart).  The stopping rule
        # the caller actually cares about is the KKT acceptance, and the
        # mapping eps -> KKT residual is problem-dependent — iterating on
        # eps makes the acceptance self-fulfilling within the max_iter
        # budget instead of a post-hoc coin flip.
        def attempt_cond(c):
            st, _, res, _, att, _ = c
            it, retries = st[7], st[9]
            # att == 0 forces the first attempt even with no KKT target
            # (kkt_target = inf, where `res > inf` is already False)
            return (
                ((res > kkt_target) | (att == 0))
                & (it < max_iter)
                & (retries < 30)
                & (att < 4)
            )

        def attempt_body(c):
            st, W_warm, _, eps, att, stalls = c
            st = jax.lax.while_loop(
                admm_cond,
                admm_body,
                st[:10] + (eps, jnp.asarray(jnp.inf, S_rows.dtype), jnp.int32(0)),
            )
            (Z, U, v, rho, boost, rp, rd, it, inner, retries) = st[:10]
            stalled = (st[12] >= stall) & ((rp > eps) | (rd > eps))
            Zf = 0.5 * (Z + tr(Z))
            res, Wz, cg_k = kkt_of(Zf, W_warm, jnp.minimum(1e-8, tol))
            st_out = (
                Zf, U, v, rho, boost, rp, rd, it, inner + cg_k, retries,
            )
            return (
                st_out + (eps,), Wz, res, 0.05 * eps, att + 1,
                stalls + stalled.astype(jnp.int32),
            )

        W_init = jnp.where(eye_loc, (1.0 / (diag_own + lam))[:, None], 0.0)
        init_state = (
            Z0,
            U0,
            v0,
            rho0,
            jnp.asarray(1.5, S_rows.dtype),
            jnp.asarray(jnp.inf, S_rows.dtype),
            jnp.asarray(jnp.inf, S_rows.dtype),
            jnp.int32(0),
            jnp.int32(0),
            jnp.int32(0),
            tol * bp,
        )
        (st, _, res, _, _, stalls) = jax.lax.while_loop(
            attempt_cond,
            attempt_body,
            (init_state, W_init, jnp.asarray(jnp.inf, S_rows.dtype), tol * bp,
             jnp.int32(0), jnp.int32(0)),
        )
        Zf, _, _, rho, _, rp, rd, it, inner, retries, eps = st
        stats = jnp.stack(
            [
                it.astype(S_rows.dtype),
                inner.astype(S_rows.dtype),
                res,
                s_max,
                rho,
                retries.astype(S_rows.dtype),
                stalls.astype(S_rows.dtype),
                jnp.maximum(rp, rd),
                eps,
            ]
        )
        return Zf, stats

    return jax.jit(
        shard_map(run, mesh=mesh, in_specs=in_specs, out_specs=(spec, P()))
    )


def compiled_sharded_solver(
    bp: int,
    d: int,
    *,
    axis: str,
    dtype,
    max_iter: int,
    ns_max: int,
    cg_max: int,
    pow_steps: int,
    warm: bool,
    mesh,
):
    key = (
        bp,
        d,
        axis,
        jnp.dtype(dtype).name,
        max_iter,
        ns_max,
        cg_max,
        pow_steps,
        warm,
        id(mesh),
    )
    with _CACHE_LOCK:
        fn = _COMPILED.get(key)
        if fn is None:
            fn = _build_sharded(
                bp, d, axis, dtype, max_iter, ns_max, cg_max, pow_steps, warm,
                mesh,
            )
            _COMPILED[key] = fn
        return fn


def pad_rowsharded(S: np.ndarray, mesh, *, axis: str = "data", dtype=None):
    """Identity-pad a host (b, b) block to the sharded pad size and place it
    row-sharded on the mesh.  Dense-host convenience — the streamed oversize
    path uses ``stream.materialize.shard_gather`` instead, which never holds
    the full block on the host."""
    d = mesh_axis_size(mesh, axis)
    b = S.shape[0]
    bp = sharded_pad_size(b, d)
    np_dtype = np.dtype(jnp.dtype(dtype or S.dtype).name)
    S_pad = np.eye(bp, dtype=np_dtype)
    S_pad[:b, :b] = S
    return jax.device_put(S_pad, NamedSharding(mesh, P(axis, None)))


def glasso_sharded(
    S,
    lam: float,
    *,
    mesh=None,
    axis: str = "data",
    b: int | None = None,
    rho: float = 1.0,
    max_iter: int = 6000,
    tol: float = 1e-9,
    kkt_target: float | None = None,
    ns_max: int = 60,
    cg_max: int | None = None,
    pow_steps: int = 10,
    dtype=None,
    Theta0: np.ndarray | None = None,
) -> ShardedSolve:
    """Solve one oversize block across the mesh; see the module docstring.

    ``S`` is either a host (b, b) array (padded + sharded here) or an
    already row-sharded padded (bp, bp) jax array (then ``b`` gives the true
    block size — the shard-direct streaming gather's calling convention).
    ``Theta0`` warm-starts Z (a previous solution on the same support, e.g.
    a path step or serving session).  ``kkt_target`` is the caller's
    RELATIVE acceptance tolerance (the executor's ``route_check_tol``):
    after the ADMM loop reaches ``tol``, the in-place KKT residual is
    checked against ``kkt_target * max(1, max|S|)`` and a miss tightens the
    stopping eps 20x and continues warm (up to 4 attempts within
    ``max_iter``) — the eps -> KKT mapping is problem-dependent, so the
    solver iterates on the acceptance criterion itself rather than leaving
    it a post-hoc coin flip.  Returns a ``ShardedSolve``; ``Theta`` is the
    host (b, b) solution and ``kkt_residual`` the distributed
    eq.-(11)/(12) verification the caller compares to its acceptance
    tolerance."""
    if mesh is None:
        mesh = local_device_mesh(axis)
    d = mesh_axis_size(mesh, axis)
    if isinstance(S, jax.Array):
        bp = S.shape[0]
        if b is None:
            raise ValueError("pre-sharded S needs the true block size (b=...)")
        if bp != sharded_pad_size(b, d):
            raise ValueError(
                f"pre-sharded S is {bp}x{bp}; expected padded size "
                f"{sharded_pad_size(b, d)} for b={b} on {d} shards"
            )
        S_sh = S
        dt = jnp.dtype(S.dtype) if dtype is None else jnp.dtype(dtype)
    else:
        S = np.asarray(S)
        b = S.shape[0]
        dt = jnp.dtype(dtype or jnp.float64)
        S_sh = pad_rowsharded(S, mesh, axis=axis, dtype=dt)
        bp = S_sh.shape[0]
    if cg_max is None:
        cg_max = bp
    warm = Theta0 is not None
    fn = compiled_sharded_solver(
        bp, d, axis=axis, dtype=dt, max_iter=int(max_iter), ns_max=int(ns_max),
        cg_max=int(cg_max), pow_steps=int(pow_steps), warm=warm, mesh=mesh,
    )
    scalars = jnp.asarray(
        [lam, rho, tol, np.inf if kkt_target is None else float(kkt_target)], dt
    )
    if warm:
        T_pad = np.eye(bp, dtype=np.dtype(dt.name)) / (1.0 + float(lam))
        T_pad[:b, :b] = np.asarray(Theta0)
        theta_sh = jax.device_put(T_pad, NamedSharding(mesh, P(axis, None)))
        Z, stats = fn(S_sh, scalars, theta_sh)
    else:
        Z, stats = fn(S_sh, scalars)
    stats = np.asarray(stats)
    itemsize = jnp.dtype(dt).itemsize
    device_bytes = _BUFFERS_PER_DEVICE * (bp // d) * bp * itemsize
    bump("solver.oversize.dispatched")
    bump("solver.oversize.cg_iters", int(stats[1]))
    bump("solver.oversize.stalls", int(stats[6]))
    set_peak("solver.oversize.device_bytes_peak", device_bytes)
    Theta = np.asarray(Z)[:b, :b]
    return ShardedSolve(
        Theta=Theta,
        iters=int(stats[0]),
        inner_iters=int(stats[1]),
        retries=int(stats[5]),
        stalls=int(stats[6]),
        admm_residual=float(stats[7]),
        admm_eps=float(stats[8]),
        kkt_residual=float(stats[2]),
        s_max=float(stats[3]),
        rho=float(stats[4]),
        b=int(b),
        padded=int(bp),
        n_shards=int(d),
        device_bytes=int(device_bytes),
    )
