"""Fused threshold + min-label-propagation "hook" step.

One round of the paper's graph-partition stage, adapted for TPU (DESIGN.md
Section 3):

    new_label_i = min(label_i, min_{j != i, |S_ij| > lam} label_j)

Grid (ni, nj): i tiles the rows (and the output vector), j streams column
tiles.  The |S|>lam adjacency is formed tile-locally inside VMEM and consumed
immediately by the masked min-reduce — the p x p boolean matrix never exists
in HBM, which is the whole point: the screening stage stays O(p^2) streamed
reads with O(p) state, "orders of magnitude" cheaper than the solve stage
(paper Section 3), even at p ~ 10^5.

Labels are int32 and the min-reduce runs on the VPU; the row-tile accumulator
persists across the j axis (sequential innermost grid).  lam arrives as a
(1, 1) array block so a lambda path never recompiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import mosaic_trace


def _kernel(s_ref, lab_j_ref, lab_i_ref, lam_ref, o_ref, acc_ref, *, nj, block, p):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = lab_i_ref[...]

    lam = lam_ref[0, 0]
    rows = i * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cols = j * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    mask = (jnp.abs(s_ref[...]) > lam) & (rows != cols) & (cols < p)
    big = jnp.int32(2**30)
    neigh = jnp.where(mask, lab_j_ref[...], big)  # lab_j broadcast over rows
    acc_ref[...] = jnp.minimum(acc_ref[...], jnp.min(neigh, axis=1, keepdims=True))

    @pl.when(j == nj - 1)
    def _finish():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("true_p", "block", "interpret"))
def labelprop_step_pallas(
    S: jax.Array,
    labels: jax.Array,
    lam: jax.Array,
    *,
    true_p: int,
    block: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """One hook step. S: (P, P) padded square, labels: (P,) int32, lam: (1,1).
    P must be a block multiple (ops.labelprop_step pads); columns >= true_p
    are masked out of the min-reduce."""
    P = S.shape[0]
    nt = P // block
    lab_row = labels.reshape(P, 1)
    lab_col = labels.reshape(1, P)

    with mosaic_trace(interpret):
        out = pl.pallas_call(
            functools.partial(_kernel, nj=nt, block=block, p=true_p),
            grid=(nt, nt),
            in_specs=[
                pl.BlockSpec((block, block), lambda i, j: (i, j)),
                pl.BlockSpec((1, block), lambda i, j: (0, j)),
                pl.BlockSpec((block, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            ],
            out_specs=pl.BlockSpec((block, 1), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((P, 1), jnp.int32),
            scratch_shapes=[pltpu.VMEM((block, 1), jnp.int32)],
            interpret=interpret,
        )(S, lab_col, lab_row, lam)
    return out[:, 0]
