"""Dispatch wrapper for the fused sharded prox step (padding + backend).

Called on the LOCAL shard inside the sharded solver's shard_map body: on TPU
the Pallas kernel fuses the whole prox tail into one HBM pass (rows padded to
a sublane multiple, columns to a lane multiple; zero padding soft-thresholds
to zero and contributes nothing to either residual partial, so the padded
coordinates are exact no-ops); off TPU the jnp reference wins — interpret
mode would emulate the fusion at 2-6x the cost, the same trade-off recorded
for ``tree_glasso`` and ``covgram_screen``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.shard_prox.ref import fused_prox_ref
from repro.kernels.shard_prox.shard_prox import SHARD_PROX_SLABS, fused_prox_pallas
from repro.kernels.mosaic import row_tile


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_prox_residual(
    x_new: jax.Array, u: jax.Array, z_old: jax.Array, t
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(Z_new, U_new, rp2_partial, rd2_partial) for one (rl, b) shard."""
    if not _is_tpu():
        return fused_prox_ref(x_new, u, z_old, t)
    rl, b = x_new.shape
    pad_r = (-rl) % 8
    pad_c = (-b) % 128
    if pad_r or pad_c:
        padder = lambda m: jnp.pad(m, ((0, pad_r), (0, pad_c)))
        x_new, u, z_old = padder(x_new), padder(u), padder(z_old)
    rows, cols = rl + pad_r, b + pad_c
    tr = row_tile(rows, cols * x_new.dtype.itemsize, SHARD_PROX_SLABS)
    zn, un, acc = fused_prox_pallas(x_new, u, z_old, jnp.asarray(t), row_tile=tr)
    if pad_r or pad_c:
        zn, un = zn[:rl, :b], un[:rl, :b]
    return zn, un, acc[0, 0], acc[0, 1]
