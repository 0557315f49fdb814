"""Distributed screening + block solving over a device mesh.

Two stages, mirroring the paper's consequence 2-4:

1. ``distributed_components``  — the only stage that communicates.  The
   adjacency mask (fused from S and lambda) is *row-sharded* across the mesh's
   data axis; each label-propagation round does a device-local masked
   min-reduce over owned rows followed by one all-gather of the p-vector of
   labels (p * 4 bytes — negligible next to the p^2/d mask scan, matching the
   paper's Section-3 claim that partitioning cost is dominated by solving).

2. ``distributed_bucket_solve`` — ZERO-communication batched solves: Theorem 1
   guarantees the subproblems are independent, so same-size padded blocks are
   sharded across devices and solved with a vmapped block solver inside
   shard_map with no collective at all.  This is the paper's "split across
   machines" made literal on a pod.

Both functions are mesh-agnostic: they take any mesh and the name of the axis
to shard over (launch/mesh.py builds the production meshes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.jax_compat import shard_map

#: f32 products at full precision on the TPU (its default is one bf16 pass)
_HIGHEST = jax.lax.Precision.HIGHEST


def distributed_components(
    S: jax.Array, lam, mesh, *, axis: str = "data", max_rounds: int | None = None
) -> jax.Array:
    """Row-sharded min-label propagation. Returns labels (p,), replicated."""
    p = S.shape[0]
    n_shard = np.prod([mesh.shape[a] for a in ([axis] if isinstance(axis, str) else axis)])
    if p % n_shard != 0:
        pad = int(n_shard - p % n_shard)
        # padded vertices carry no edges -> isolated, labels >= p, harmless
        S = jnp.pad(S, ((0, pad), (0, pad)))
    pp = S.shape[0]
    spec_rows = P(axis, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec_rows, P()), out_specs=P()
    )
    def run(S_rows, lam_arr):
        rows = S_rows.shape[0]
        axis_idx = jax.lax.axis_index(axis)
        row0 = axis_idx * rows
        ii = row0 + jnp.arange(rows)
        jj = jnp.arange(pp)
        mask = (jnp.abs(S_rows) > lam_arr) & (ii[:, None] != jj[None, :])
        big = jnp.int32(pp)

        def round_(labels):
            neigh = jnp.where(mask, labels[None, :], big)
            owned = jax.lax.dynamic_slice(labels, (row0,), (rows,))
            local = jnp.minimum(owned, jnp.min(neigh, axis=1))
            labels = jax.lax.all_gather(local, axis, tiled=True)
            labels = labels[labels]
            labels = labels[labels]
            return labels

        init = jnp.arange(pp, dtype=jnp.int32)

        def cond(c):
            labels, prev, it = c
            limit = max_rounds if max_rounds is not None else pp + 2
            return jnp.logical_and(jnp.any(labels != prev), it < limit)

        def body(c):
            labels, _, it = c
            return round_(labels), labels, it + 1

        labels, _, _ = jax.lax.while_loop(
            cond, body, (round_(init), init, jnp.int32(0))
        )
        return labels

    labels = run(S, jnp.asarray(lam, S.dtype))
    return labels[:p]


def distributed_bucket_solve(
    blocks: np.ndarray | jax.Array,
    lam: float,
    solver,
    mesh,
    *,
    axis: str = "data",
    **solver_opts,
):
    """Shard a (n, b, b) stack of padded same-size blocks across ``axis`` and
    solve with vmap(solver) per device.  No collectives — independence is
    exactly what Theorem 1 bought us.

    n is padded up to a multiple of the axis size with identity blocks (whose
    solution is (1/(1+lam)) I); callers slice the first n results.
    """
    blocks = jnp.asarray(blocks)
    n, b, _ = blocks.shape
    n_shard = int(np.prod([mesh.shape[a] for a in ([axis] if isinstance(axis, str) else axis)]))
    pad = (-n) % n_shard
    if pad:
        blocks = jnp.concatenate(
            [blocks, jnp.broadcast_to(jnp.eye(b, dtype=blocks.dtype), (pad, b, b))]
        )

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(axis, None, None),), out_specs=P(axis, None, None)
    )
    def run(local):
        return jax.vmap(lambda Sb: solver(Sb, lam, **solver_opts))(local)

    out = run(blocks)
    return out[:n]


def put_sharded_blocks(blocks: np.ndarray, mesh, *, axis: str = "data"):
    """Device_put a block stack with first-axis sharding (for benchmarks that
    want the transfer outside the timed region)."""
    return jax.device_put(
        jnp.asarray(blocks), NamedSharding(mesh, P(axis, None, None))
    )


# ---------------------------------------------------------------------------
# Row-sharded matrix primitives (the sharded oversize solver's vocabulary)
# ---------------------------------------------------------------------------
#
# All three helpers run INSIDE a shard_map body: operands are the local
# (rows_local, p) shard of a row-sharded square matrix, and — crucially for
# the oversize memory model — none of them ever materializes a full (p, p)
# operand on any one device.  Peak per-device scratch is one extra shard.


def ring_matmul(a_rows: jax.Array, b_rows: jax.Array, *, axis: str, n_shards: int):
    """C = A @ B with A, B, C all row-sharded over ``axis``.

    Classic 1-D ring algorithm: at step k each device multiplies its local
    column slab A[:, rows-of-shard-s] (s = my_index + k) by the B shard
    currently in its ring buffer, then passes the buffer along the ring.
    n_shards steps of (rl, rl) @ (rl, p) work — the same b^3 / d FLOPs as the
    gathered product, but the only extra buffer is one (rl, p) shard instead
    of the full (p, p) all-gather."""
    if n_shards == 1:
        return jnp.matmul(a_rows, b_rows, precision=_HIGHEST)
    rl = a_rows.shape[0]
    idx = jax.lax.axis_index(axis)
    perm = [(j, (j - 1) % n_shards) for j in range(n_shards)]

    def step(k, carry):
        acc, b_cur = carry
        s = jax.lax.rem((idx + k).astype(jnp.int32), jnp.int32(n_shards))
        col0 = (s * rl).astype(jnp.int32)
        a_cols = jax.lax.dynamic_slice(a_rows, (jnp.int32(0), col0), (rl, rl))
        acc = acc + jnp.matmul(a_cols, b_cur, precision=_HIGHEST)
        b_cur = jax.lax.ppermute(b_cur, axis, perm)
        return acc, b_cur

    acc0 = jnp.zeros_like(b_rows)
    acc, _ = jax.lax.fori_loop(0, n_shards, step, (acc0, b_rows))
    return acc


def transpose_rowsharded(a_rows: jax.Array, *, axis: str, n_shards: int):
    """(A^T) row-sharded from A row-sharded, via one all_to_all.

    Device i sends its column block j to device j and receives every
    device's column block i — i.e. the full column slab A[:, cols_i] —
    whose transpose is exactly the rows of A^T this device owns.  Per-device
    traffic and scratch are one shard, never the full matrix."""
    if n_shards == 1:
        return a_rows.T
    col_slab = jax.lax.all_to_all(
        a_rows, axis, split_axis=1, concat_axis=0, tiled=True
    )  # (p, rows_local) — global rows arrive in shard order, already aligned
    return col_slab.T


def matvec_rowsharded(a_rows: jax.Array, v: jax.Array, *, axis: str, n_shards: int):
    """(A @ v) replicated, from A row-sharded and v replicated."""
    if n_shards == 1:
        return jnp.matmul(a_rows, v, precision=_HIGHEST)
    return jax.lax.all_gather(
        jnp.matmul(a_rows, v, precision=_HIGHEST), axis, tiled=True
    )


def device_memory_budget_mb() -> float | None:
    """Per-device accelerator memory in MB, or None when the backend does
    not report it (CPU).  The planner's ``oversize_budget_mb="auto"`` hook."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except (RuntimeError, AttributeError, TypeError):
        return None
    if not stats or "bytes_limit" not in stats:
        return None
    return float(stats["bytes_limit"]) / 2**20
