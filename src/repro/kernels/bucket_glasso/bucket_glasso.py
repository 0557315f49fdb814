"""Pallas kernel: full BCD glasso solve inside the kernel, one program per
packed lane.

    grid (N,)   in:  S^T (N, b, b), W_init (N, b, b), B_init^T (N, b, b),
                     lam (N, 1, 1), thr (N, 1, 1)
                out: Theta^T (N, b, b), sweeps (N, 1, 1) int32

The per-lane scalars (lam, thr = tol * scale, sweeps) live in SMEM as
(1, 1, 1) blocks of (N, 1, 1) arrays: the trailing block dims equal the
array's, which the TPU tiling rule accepts for any N.  The wrapper builds
each lane's initial state in XLA (``ref.fused_bcd_init``) and symmetrizes
the returned Theta^T, exactly as the reference does.

Unlike the vmapped reference — where ``lax.while_loop`` is select-masked and
every lane pays the batch-max sweep count in compute — grid programs on a
TensorCore execute one after another, so the per-program sweep loop is a REAL
early exit: a block converged after 3 sweeps costs 3 sweeps, full stop.
That is the lockstep saving ``solver.fused.lockstep_sweeps_saved`` measures
(the megabatch's sum over lanes of ``max(sweeps) - sweeps_i``).

The working set per program is a handful of (b, b) tiles — at the bin cap
b = 64 in f32 that is a few tens of KiB of VMEM.  The body is
``core.solvers.bcd.bcd_sweeps`` with ``masked=True``: the same arithmetic as
the reference, with every traced-index row/column/entry access spelled as an
iota mask and a one-hot reduction, because Mosaic cannot lower a dynamic
slice of a value.  Off-TPU the ops wrapper never reaches this kernel
(interpret mode is exercised by the parity tests only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import mosaic_trace

from repro.core.solvers.bcd import bcd_sweeps
from repro.kernels.bucket_glasso.ref import fused_bcd_init


def _make_kernel(*, max_sweeps: int, n_cd: int, node_screen: bool):
    def kernel(st_ref, w_ref, bt_ref, lam_ref, thr_ref, o_ref, sweeps_ref):
        theta_t, sweeps = bcd_sweeps(
            st_ref[0],
            w_ref[0],
            bt_ref[0],
            lam_ref[0, 0, 0],
            thr_ref[0, 0, 0],
            max_sweeps=max_sweeps,
            n_cd=n_cd,
            node_screen=node_screen,
            masked=True,
        )
        o_ref[0] = theta_t
        sweeps_ref[0, 0, 0] = sweeps

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("max_sweeps", "n_cd", "tol", "node_screen", "interpret"),
)
def fused_bcd_pallas(
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
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """blocks/W0/T0: (N, b, b) with b a multiple of 8; lams/scales: (N, 1).
    Returns (Theta (N, b, b), sweeps (N, 1) int32)."""
    N, b, _ = blocks.shape
    lams = lams.reshape(N)
    ST, W, BT, thr = jax.vmap(functools.partial(fused_bcd_init, tol=tol))(
        blocks, lams, scales.reshape(N), W0, T0
    )
    mat = pl.BlockSpec((1, b, b), lambda n: (n, 0, 0))
    scalar = pl.BlockSpec((1, 1, 1), lambda n: (n, 0, 0), memory_space=pltpu.SMEM)
    with mosaic_trace(interpret):
        theta_t, sweeps = pl.pallas_call(
            _make_kernel(max_sweeps=max_sweeps, n_cd=n_cd, node_screen=node_screen),
            grid=(N,),
            in_specs=[mat, mat, mat, scalar, scalar],
            out_specs=[mat, scalar],
            out_shape=[
                jax.ShapeDtypeStruct((N, b, b), blocks.dtype),
                jax.ShapeDtypeStruct((N, 1, 1), jnp.int32),
            ],
            interpret=interpret,
        )(ST, W, BT, lams.reshape(N, 1, 1), thr.reshape(N, 1, 1))
    theta = 0.5 * (jnp.swapaxes(theta_t, 1, 2) + theta_t)
    return theta, sweeps.reshape(N, 1)
