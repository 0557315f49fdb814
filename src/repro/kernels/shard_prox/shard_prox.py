"""Pallas kernel: fused soft-threshold + residual reduction for one shard.

The sharded oversize solver's hot elementwise tail.  Without fusion the
prox step costs ~7 HBM round-trips of the (rows_local, b) shard (add, abs,
sign, subtract, two squared-difference reductions, dual update); the kernel
does one read of (X_new, U, Z_old) and one write of (Z_new, U_new) per row
tile, accumulating both residual partials in a (1, 2) SMEM block that
every grid step maps to the same output tile (TPU grids are sequential, so
the accumulation is race-free — same pattern as the covgram_screen bounds).

    grid (n_row_tiles,)
    in:  X_new (rl, b), U (rl, b), Z_old (rl, b), t (1, 1) in SMEM
    out: Z_new (rl, b), U_new (rl, b), acc (1, 2) = [rp2, rd2] in SMEM

t = lam / rho is a TRACED SMEM scalar: adaptive-rho steps never recompile.
Scalars live in SMEM because the TPU cannot store a scalar to VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import mosaic_trace

#: (tr, b) slabs one grid step keeps in VMEM: five double-buffered blocks
#: plus the elementwise temporaries
SHARD_PROX_SLABS = 14


def _kernel(x_ref, u_ref, z_ref, t_ref, zn_ref, un_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[0, 0] = jnp.zeros((), acc_ref.dtype)
        acc_ref[0, 1] = jnp.zeros((), acc_ref.dtype)

    x = x_ref[...]
    a = x + u_ref[...]
    t = t_ref[0, 0]
    zn = jnp.sign(a) * jnp.maximum(jnp.abs(a) - t, 0.0)
    zn_ref[...] = zn
    un_ref[...] = a - zn
    dp = x - zn
    dd = zn - z_ref[...]
    acc_ref[0, 0] += jnp.sum(dp * dp)
    acc_ref[0, 1] += jnp.sum(dd * dd)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def fused_prox_pallas(
    x_new: jax.Array,
    u: jax.Array,
    z_old: jax.Array,
    t: jax.Array,
    *,
    row_tile: int = 0,
    interpret: bool = False,
):
    """x_new/u/z_old: (rl, b) with rl a multiple of row_tile and b a multiple
    of 8; t: (1, 1).  Returns (Z_new, U_new, acc (1, 2))."""
    rl, b = x_new.shape
    tr = row_tile or rl
    grid = (rl // tr,)
    shard = pl.BlockSpec((tr, b), lambda i: (i, 0))
    with mosaic_trace(interpret):
        return pl.pallas_call(
            _kernel,
            grid=grid,
            in_specs=[shard, shard, shard, pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=[shard, shard, pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_shape=[
                jax.ShapeDtypeStruct((rl, b), x_new.dtype),
                jax.ShapeDtypeStruct((rl, b), x_new.dtype),
                jax.ShapeDtypeStruct((1, 2), x_new.dtype),
            ],
            interpret=interpret,
        )(x_new, u, z_old, t.reshape(1, 1).astype(x_new.dtype))
