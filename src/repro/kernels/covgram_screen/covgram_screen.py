"""Fused centered-Gram + threshold + edge-emit Pallas kernel.

The out-of-core screening variant of ``kernels/covgram``: instead of writing
the dense (p, p) covariance, the kernel computes ONE (block_p, block_p) tile
S_IJ = (X_I - mu_I)'(X_J - mu_J) / n per requested tile PAIR, thresholds it
at lambda in VMEM, and emits

  * ``vals``   the tile with sub-threshold entries zeroed (the compaction
               source: an entry survives iff it is an edge of eq. (4)),
  * ``count``  the number of surviving entries (diagonal excluded),
  * ``stats``  [max off-diagonal |S_ij| in the tile,
                max off-diagonal |S_ij| <= lambda] — the bounds the streaming
               session layer needs to re-validate a tile after a rank-k data
               update without recomputing it.

The dense S never exists in HBM: only the in-flight batch of tile pairs
(``npairs`` x block_p^2 f32) plus the O(#edges) compacted output survive the
call.  Tile pairs arrive as scalar-prefetched index lists (i_idx, j_idx), so
the driver's Cauchy-Schwarz tile-skip (sqrt(S_ii,max * S_jj,max) <= lambda)
simply omits a pair from the grid — skipped tiles cost zero FLOPs and zero
HBM traffic.

Grid (npairs, nk): k streams (block_n, block_p) row-chunks of the SAME padded
X at two column offsets (rank-block_n MXU updates accumulated in an f32 VMEM
scratch, exactly the covgram schedule); the threshold/emit epilogue runs at
k == nk-1.  lam rides in a (1, 1) SMEM block so a lambda sweep never
recompiles, and the per-pair count and stats are SMEM scalars ((1, 1, 1) and
(1, 1, 2) blocks of (npairs, 1, ·) arrays — the TPU tiling rule accepts
trailing block dims equal to the array's);
the true n and p are static (one compile per dataset shape family).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import mosaic_trace


def _kernel(
    i_idx_ref,
    j_idx_ref,
    x_i_ref,
    x_j_ref,
    mu_i_ref,
    mu_j_ref,
    lam_ref,
    vals_ref,
    cnt_ref,
    stat_ref,
    acc_ref,
    *,
    nk: int,
    n_true: int,
    p_true: int,
    block_p: int,
):
    t = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = x_i_ref[...].astype(jnp.float32) - mu_i_ref[...].astype(jnp.float32)
    b = x_j_ref[...].astype(jnp.float32) - mu_j_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        a,
        b,
        (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _emit():
        S = acc_ref[...] / n_true
        rows = i_idx_ref[t] * block_p + jax.lax.broadcasted_iota(
            jnp.int32, (block_p, block_p), 0
        )
        cols = j_idx_ref[t] * block_p + jax.lax.broadcasted_iota(
            jnp.int32, (block_p, block_p), 1
        )
        valid = (rows < p_true) & (cols < p_true) & (rows != cols)
        absS = jnp.abs(S)
        lam = lam_ref[0, 0]
        mask = valid & (absS > lam)  # strict: eq. (4), ties are NOT edges
        vals_ref[0] = jnp.where(mask, S, 0.0)
        # one axis at a time: Mosaic lowers a reduction straight to a scalar
        # by retracing it with the caller's x64 setting, which widens an
        # int32 sum to int64; row sums then a (1,) column sum stay int32
        cnt = jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)
        cnt_ref[0, 0, 0] = jnp.sum(cnt, axis=0)[0]
        stat_ref[0, 0, 0] = jnp.max(jnp.where(valid, absS, 0.0))
        stat_ref[0, 0, 1] = jnp.max(jnp.where(valid & ~mask, absS, 0.0))


@functools.partial(
    jax.jit,
    static_argnames=("n_true", "p_true", "block_n", "block_p", "interpret"),
)
def covgram_screen_pallas(
    x: jax.Array,
    mu: jax.Array,
    i_idx: jax.Array,
    j_idx: jax.Array,
    lam: jax.Array,
    *,
    n_true: int,
    p_true: int,
    block_n: int = 512,
    block_p: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: (N, P) pre-padded (rows with copies of mu — zero centered
    contribution — to a block_n multiple; columns with zeros to a block_p
    multiple); mu: (P,) zero-padded; i_idx/j_idx: (npairs,) int32 tile
    indices; lam: (1, 1) f32.

    Returns (vals (npairs, block_p, block_p) f32 thresholded tiles,
    counts (npairs, 1) int32, stats (npairs, 2) f32 [tile max |S_ij|,
    max |S_ij| <= lam])."""
    N, P = x.shape
    nk = N // block_n
    npairs = i_idx.shape[0]
    mu2 = mu.reshape(1, P)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(npairs, nk),
        in_specs=[
            pl.BlockSpec((block_n, block_p), lambda t, k, ii, jj: (k, ii[t])),
            pl.BlockSpec((block_n, block_p), lambda t, k, ii, jj: (k, jj[t])),
            pl.BlockSpec((1, block_p), lambda t, k, ii, jj: (0, ii[t])),
            pl.BlockSpec((1, block_p), lambda t, k, ii, jj: (0, jj[t])),
            pl.BlockSpec(
                (1, 1), lambda t, k, ii, jj: (0, 0), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_p, block_p), lambda t, k, ii, jj: (t, 0, 0)),
            pl.BlockSpec(
                (1, 1, 1), lambda t, k, ii, jj: (t, 0, 0), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec(
                (1, 1, 2), lambda t, k, ii, jj: (t, 0, 0), memory_space=pltpu.SMEM
            ),
        ],
        scratch_shapes=[pltpu.VMEM((block_p, block_p), jnp.float32)],
    )
    with mosaic_trace(interpret):
        vals, counts, stats = pl.pallas_call(
            functools.partial(
                _kernel, nk=nk, n_true=n_true, p_true=p_true, block_p=block_p
            ),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((npairs, block_p, block_p), jnp.float32),
                jax.ShapeDtypeStruct((npairs, 1, 1), jnp.int32),
                jax.ShapeDtypeStruct((npairs, 1, 2), jnp.float32),
            ],
            interpret=interpret,
        )(i_idx, j_idx, x, x, mu2, mu2, lam)
    return vals, counts.reshape(npairs, 1), stats.reshape(npairs, 2)
