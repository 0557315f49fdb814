"""Pallas kernel: batched closed-form forest glasso over a bucket stack.

One program per padded block — the whole (b, b) tile lives in VMEM, the math
is elementwise soft-thresholding plus a single row reduction (VPU work, no
MXU), so the kernel is memory-bound and fuses what would otherwise be ~10
separate HBM round-trips (mask, soft, denominators, two divisions, row sum,
diagonal scatter) into one read and one write of the stack.

    grid (B,)   in: S (B, b, b), lam (B, 1, 1) in SMEM   out: Theta (B, b, b)

lam is a PER-BLOCK scalar in SMEM (a (1, 1, 1) block of a (B, 1, 1)
array: the trailing block dims equal the array's, which the TPU tiling
rule accepts for any B) — the serving path coalesces blocks with
different lambdas into one stack, and a lambda path never recompiles.  Tree
buckets are small by nature (large components are rarely acyclic), so the
one-tile-per-program layout holds comfortably within VMEM; the ops wrapper
falls back to the jnp reference above a size cap.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import mosaic_trace


def _kernel(s_ref, lam_ref, o_ref):
    s = s_ref[0]
    lam = lam_ref[0, 0, 0]
    b = s.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    eye = rows == cols
    abss = jnp.abs(s)
    mask = (abss > lam) & ~eye
    a = jnp.where(mask, jnp.sign(s) * (abss - lam), 0.0)
    d = jnp.sum(jnp.where(eye, s, 0.0), axis=1) + lam  # diag(S) + lam, (b,)
    den = jnp.where(mask, d[:, None] * d[None, :] - a * a, 1.0)
    theta_off = jnp.where(mask, -a / den, 0.0)
    contrib = jnp.where(mask, (a * a) / (d[:, None] * den), 0.0)
    theta_diag = 1.0 / d + jnp.sum(contrib, axis=1)
    o_ref[0] = theta_off + jnp.where(eye, theta_diag[:, None], 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def glasso_forest_pallas(
    blocks: jax.Array, lams: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """blocks: (B, b, b) with b a multiple of 8; lams: (B, 1)."""
    B, b, _ = blocks.shape
    with mosaic_trace(interpret):
        return pl.pallas_call(
            _kernel,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, b, b), lambda n: (n, 0, 0)),
                pl.BlockSpec((1, 1, 1), lambda n: (n, 0, 0), memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((1, b, b), lambda n: (n, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, b, b), blocks.dtype),
            interpret=interpret,
        )(blocks, lams.reshape(B, 1, 1))
