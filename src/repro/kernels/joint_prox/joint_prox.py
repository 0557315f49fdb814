"""Pallas kernel: fused K-way joint prox + residual reduction per row tile.

The joint ADMM's hot elementwise tail.  Unfused, the Z-update costs many HBM
round-trips of the (K, b, b) iterate (add, the K-way coupled prox with its
rank/order-statistic broadcasts, the dual update, two squared-difference
reductions); the kernel does one read of (Theta, U, Z_old) and one write of
(Z_new, U_new) per row tile, accumulating both residual partials in a (1, 2)
SMEM block that every grid step maps to the same output tile (TPU grids
are sequential, so the accumulation is race-free — the ``shard_prox`` /
``covgram_screen`` pattern).

    grid (b // row_tile,)
    in:  Theta (K, rt, b), U (K, rt, b), Z_old (K, rt, b), t (1, 2) in SMEM
    out: Z_new (K, rt, b), U_new (K, rt, b), acc (1, 2) = [rp2, rd2] in SMEM

t = [lam1/rho, lam2/rho] is a TRACED SMEM block: adaptive-rho steps never
recompile.  Scalars live in SMEM because the TPU cannot store a scalar to
VMEM.  The class axis K rides as the leading block dimension (the
tiling constraint binds the trailing (rt, b) dims); the kernel loads one
(rt, b) tile per class and runs the SAME sort-free prox math as the jnp
reference on that list (``ref.joint_prox_classes``) — K is static, so the
rank/one-hot/minimax loops unroll into O(K^3) elementwise VPU ops with no
gather.  The diagonal (lam1-only) entries are detected in-kernel from the
row-tile offset via iota, so no mask input is streamed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.joint_prox.ref import _soft, joint_prox_classes
from repro.kernels.mosaic import mosaic_trace


def _kernel(penalty, theta_ref, u_ref, z_ref, t_ref, zn_ref, un_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[0, 0] = jnp.zeros((), acc_ref.dtype)
        acc_ref[0, 1] = jnp.zeros((), acc_ref.dtype)

    K, rt, b = theta_ref.shape
    t1 = t_ref[0, 0]
    t2 = t_ref[0, 1]
    a = [theta_ref[k] + u_ref[k] for k in range(K)]
    rows = jax.lax.broadcasted_iota(jnp.int32, (rt, b), 0) + i * rt
    cols = jax.lax.broadcasted_iota(jnp.int32, (rt, b), 1)
    diag = rows == cols
    off = joint_prox_classes(a, t1, t2, penalty=penalty)
    rp2 = jnp.zeros((), acc_ref.dtype)
    rd2 = jnp.zeros((), acc_ref.dtype)
    for k in range(K):
        zn = jnp.where(diag, _soft(a[k], t1), off[k])
        zn_ref[k] = zn
        un_ref[k] = a[k] - zn
        dp = theta_ref[k] - zn
        dd = zn - z_ref[k]
        rp2 = rp2 + jnp.sum(dp * dp)
        rd2 = rd2 + jnp.sum(dd * dd)
    acc_ref[0, 0] += rp2
    acc_ref[0, 1] += rd2


@functools.partial(
    jax.jit, static_argnames=("penalty", "row_tile", "interpret")
)
def joint_prox_pallas(
    theta: jax.Array,
    u: jax.Array,
    z_old: jax.Array,
    t: jax.Array,
    *,
    penalty: str,
    row_tile: int = 0,
    interpret: bool = False,
):
    """theta/u/z_old: (K, b, b) with b a multiple of row_tile (and ideally of
    the lane width); t: (1, 2) = [[t1, t2]].  Returns (Z_new, U_new,
    acc (1, 2))."""
    K, b, _ = theta.shape
    rt = row_tile or b
    grid = (b // rt,)
    blk = pl.BlockSpec((K, rt, b), lambda i: (0, i, 0))
    with mosaic_trace(interpret):
        return pl.pallas_call(
            functools.partial(_kernel, penalty),
            grid=grid,
            in_specs=[blk, blk, blk, pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=[blk, blk, pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_shape=[
                jax.ShapeDtypeStruct((K, b, b), theta.dtype),
                jax.ShapeDtypeStruct((K, b, b), theta.dtype),
                jax.ShapeDtypeStruct((1, 2), theta.dtype),
            ],
            interpret=interpret,
        )(theta, u, z_old, t.reshape(1, 2).astype(theta.dtype))


def joint_prox_slabs(K: int, penalty: str) -> int:
    """(rt, b) slabs one grid step keeps in VMEM: five double-buffered
    (K, rt, b) blocks plus the prox temporaries (O(K) for the group prox,
    O(K^2) one-hots and segment means for the fused one)."""
    temps = 4 * K if penalty == "group" else 3 * K * K + 4 * K
    return 10 * K + temps
