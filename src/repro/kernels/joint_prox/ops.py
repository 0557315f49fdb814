"""Dispatch wrapper for the fused joint prox step (padding + backend).

Called inside the joint ADMM's Z-update: on TPU the Pallas kernel fuses the
K-way coupled prox and both residual reductions into one HBM pass (rows and
columns padded to sublane/lane multiples; a zero-padded entry proxes to zero
in every penalty — group and fused proxes both fix the origin — and
contributes nothing to either residual partial, so padding is an exact
no-op).  Off TPU the jnp reference wins — interpret mode would emulate the
fusion at 2-6x the cost, the same trade-off recorded for ``tree_glasso``,
``covgram_screen`` and ``shard_prox``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.joint_prox.joint_prox import joint_prox_pallas, joint_prox_slabs
from repro.kernels.mosaic import row_tile
from repro.kernels.joint_prox.ref import (  # noqa: F401  (re-export surface)
    PENALTIES,
    fused_prox,
    group_prox,
    joint_prox_entries,
    joint_prox_ref,
    tv_complete_prox,
)


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def joint_prox_step(
    theta: jax.Array,
    u: jax.Array,
    z_old: jax.Array,
    t1,
    t2,
    *,
    penalty: str,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(Z_new, U_new, rp2, rd2) for one (K, b, b) iterate block."""
    if not _is_tpu():
        return joint_prox_ref(theta, u, z_old, t1, t2, penalty=penalty)
    K, b, _ = theta.shape
    pad = (-b) % 128
    if pad:
        def padder(m):
            return jnp.pad(m, ((0, 0), (0, pad), (0, pad)))

        theta, u, z_old = padder(theta), padder(u), padder(z_old)
    t = jnp.stack([jnp.asarray(t1), jnp.asarray(t2)]).reshape(1, 2)
    bp = b + pad
    rt = row_tile(bp, bp * theta.dtype.itemsize, joint_prox_slabs(K, penalty))
    zn, un, acc = joint_prox_pallas(
        theta, u, z_old, t, penalty=penalty, row_tile=rt
    )
    if pad:
        zn, un = zn[:, :b, :b], un[:, :b, :b]
    return zn, un, acc[0, 0], acc[0, 1]
