"""Public wrapper for the covgram_screen kernel family: backend dispatch,
padding convention, and edge compaction.

Dispatch follows the ``tree_glasso`` precedent: on TPU the fused Pallas
kernel computes the requested tile pairs; off-TPU the numpy oracle wins
(interpret-mode emulation costs per-grid-step overhead on exactly the
many-tile pattern the kernel accelerates, and the numpy path keeps the input
dtype — f64 tiles match a dense f64 estimator exactly on representable
data).  ``backend="pallas"`` forces the kernel (interpret mode off-TPU) for
the equivalence tests.

An entry of the kernel's thresholded ``vals`` is nonzero iff it is an
eq.-(4) edge (|S_ij| > lam >= 0 implies S_ij != 0 in the same arithmetic).
``covgram_screen_edges`` turns one batch of tile pairs into the compacted
global (i, j, S_ij) edge arrays every screen accumulates — the dense (p, p)
matrix never exists.  On the Pallas path the batch's tiles never leave the
device: ``compact_tiles`` compacts them there into a few padded edge
triples, sized from the kernel's own per-pair counts.  On the host oracle
``compact_edges`` does the same with one ``np.nonzero`` over the batch.
Both emit the same arrays in the same (row-major) order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.instrument import bump
from repro.kernels.covgram_screen.covgram_screen import covgram_screen_pallas
from repro.kernels.covgram_screen.ref import covgram_screen_ref
from repro.obs.trace import span


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pad_for_screen(
    x: np.ndarray, mu: np.ndarray, *, block_n: int, block_p: int
) -> tuple[np.ndarray, np.ndarray]:
    """Pad rows to a block_n multiple with copies of mu (centered
    contribution exactly zero) and columns to a block_p multiple with zeros
    (mu padded with zeros, so padded columns contribute exact zeros).

    mu is cast to x's dtype FIRST and the cast copy is what both the padding
    and the returned mean use: the padded rows then center to exactly zero in
    every backend (an f64 mu against f32-padded rows would not — the cast
    does not round-trip), at the cost of the mean carrying x's precision."""
    n, p = x.shape
    mu = np.asarray(mu, dtype=x.dtype)
    pad_n = (-n) % block_n
    pad_p = (-p) % block_p
    if pad_n:
        x = np.concatenate([x, np.broadcast_to(mu, (pad_n, p)).astype(x.dtype)])
    if pad_p:
        x = np.pad(x, ((0, 0), (0, pad_p)))
        mu = np.pad(mu, (0, pad_p))
    return x, mu


def covgram_screen_tiles(
    x_pad,
    mu_pad,
    i_idx: np.ndarray,
    j_idx: np.ndarray,
    lam: float,
    *,
    n_true: int,
    p_true: int,
    block_p: int,
    block_n: int = 512,
    backend: str = "auto",
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Compute + threshold the requested tile pairs of the centered Gram.

    x_pad/mu_pad follow ``pad_for_screen``'s convention.  Returns host
    arrays (vals (B, bp, bp), counts (B,), stats (B, 2)) — see the kernel
    docstring for the stats layout.  The screens themselves go through
    ``covgram_screen_edges``, which keeps ``vals`` on the device.

    Under an active trace the call records ``screen.upload`` (inputs to
    the device), ``screen.kernel`` (the kernel, to completion) and
    ``screen.fetch`` (results back to the host) spans; the bytes moved
    each way count in ``stream.upload_bytes`` / ``stream.fetch_bytes``
    (0 on the host oracle)."""
    backend = resolve_backend(backend)
    i_idx = np.asarray(i_idx, np.int32)
    j_idx = np.asarray(j_idx, np.int32)
    if backend == "ref":
        with span("screen.upload"):
            x_host, mu_host = np.asarray(x_pad), np.asarray(mu_pad)
        with span("screen.kernel"):
            vals, counts, stats = covgram_screen_ref(
                x_host,
                mu_host,
                i_idx,
                j_idx,
                float(lam),
                n_true=n_true,
                p_true=p_true,
                block_p=block_p,
            )
        with span("screen.fetch"):
            counts = counts[:, 0]
        bump("stream.upload_bytes", 0)
        bump("stream.fetch_bytes", 0)
        return vals, counts, stats
    out = _kernel_on_device(
        x_pad, mu_pad, i_idx, j_idx, lam,
        n_true=n_true, p_true=p_true, block_p=block_p, block_n=block_n,
    )
    with span("screen.fetch"):
        vals, counts, stats = (np.asarray(a) for a in out)
    bump("stream.fetch_bytes", vals.nbytes + counts.nbytes + stats.nbytes)
    return vals, counts[:, 0], stats


def covgram_screen_edges(
    x_pad,
    mu_pad,
    i_idx: np.ndarray,
    j_idx: np.ndarray,
    lam: float,
    *,
    n_true: int,
    p_true: int,
    block_p: int,
    block_n: int = 512,
    backend: str = "auto",
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Screen one batch of tile pairs straight to its edges.

    Returns host arrays (gi, gj, v, counts (B,), stats (B, 2)): the global
    upper-triangle edges (int64 gi < gj, signed float64 S_ij) in the order
    ``compact_edges_signed`` gives over the batch's ``vals``, and the
    kernel's per-pair counts and stats.

    On the Pallas path ``vals`` stays on the device: the counts and stats
    come back first, a batch with no edge ends there, and otherwise
    ``compact_tiles`` compacts ``vals`` into ``_capacity(sum(counts))``
    slots — never fewer than the batch's edges, so no edge is dropped —
    and only those slots come back (``stream.compact_batches``,
    ``stream.compact_slots``).  Spans: those of ``covgram_screen_tiles``,
    with ``screen.fetch`` around each copy back and ``screen.compact``
    around the compaction (to ``block_until_ready`` on the device, the
    ``np.nonzero`` on the host oracle)."""
    backend = resolve_backend(backend)
    i_idx = np.asarray(i_idx, np.int32)
    j_idx = np.asarray(j_idx, np.int32)
    if backend == "ref":
        vals, counts, stats = covgram_screen_tiles(
            x_pad, mu_pad, i_idx, j_idx, lam,
            n_true=n_true, p_true=p_true, block_p=block_p, block_n=block_n,
            backend=backend,
        )
        with span("screen.compact"):
            gi, gj, v = compact_edges_signed(vals, i_idx, j_idx, block_p=block_p)
        bump("stream.compact_batches", 0)
        bump("stream.compact_slots", 0)
        return gi, gj, v, counts, stats
    vals, counts, stats = _kernel_on_device(
        x_pad, mu_pad, i_idx, j_idx, lam,
        n_true=n_true, p_true=p_true, block_p=block_p, block_n=block_n,
    )
    with span("screen.fetch"):
        counts, stats = np.asarray(counts)[:, 0], np.asarray(stats)
    bump("stream.fetch_bytes", counts.nbytes + stats.nbytes)
    gi, gj, v = compact_edges_device(vals, counts, i_idx, j_idx, block_p=block_p)
    return gi, gj, v, counts, stats


def compact_edges_device(
    vals: jax.Array, counts: np.ndarray, i_idx, j_idx, *, block_p: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``compact_edges_signed`` for a batch whose ``vals`` are on the
    device, given the batch's per-pair counts (host, ``counts[t]`` >= the
    nonzeros of ``vals[t]``).  Only the compacted slots cross to the host;
    a batch whose counts sum to 0 touches the device not at all."""
    nnz = int(np.sum(counts))
    if nnz == 0:
        gi = np.empty(0, np.int64)
        return gi, gi.copy(), np.empty(0, np.float64)
    capacity = _capacity(nnz)
    with span("screen.compact"):
        packed = jax.block_until_ready(compact_tiles(vals, capacity=capacity))
    with span("screen.fetch"):
        trc, v, n = (np.asarray(a) for a in packed)
    bump("stream.compact_batches")
    bump("stream.compact_slots", capacity)
    bump("stream.fetch_bytes", trc.nbytes + v.nbytes + n.nbytes)
    t, r, c = trc[:, :n]
    return _upper_global(t, r, c, v[:n], i_idx, j_idx, block_p=block_p)


def resolve_backend(backend: str) -> str:
    """``"auto"`` to the backend it runs on: the kernel on a TPU, the numpy
    oracle elsewhere."""
    if backend == "auto":
        return "pallas" if _is_tpu() else "ref"
    if backend not in ("pallas", "ref"):
        raise ValueError(f"unknown covgram_screen backend {backend!r}")
    return backend


def _kernel_on_device(
    x_pad, mu_pad, i_idx, j_idx, lam, *, n_true, p_true, block_p, block_n
):
    """Upload one batch's inputs and run the kernel to completion; the
    outputs (vals, counts, stats) stay on the device."""
    with span("screen.upload"):
        args = jax.block_until_ready((
            jnp.asarray(x_pad, jnp.float32),
            jnp.asarray(mu_pad, jnp.float32),
            jnp.asarray(i_idx),
            jnp.asarray(j_idx),
            jnp.asarray(float(lam), jnp.float32).reshape(1, 1),
        ))
    bump("stream.upload_bytes", sum(a.nbytes for a in args))
    with span("screen.kernel"):
        return jax.block_until_ready(covgram_screen_pallas(
            *args,
            n_true=n_true,
            p_true=p_true,
            block_n=block_n,
            block_p=block_p,
            interpret=not _is_tpu(),
        ))


#: smallest compaction capacity; each further one is 4x the last, so a
#: batch's edge count picks one of a few compiled sizes
MIN_CAPACITY = 256


def _capacity(nnz: int) -> int:
    """The smallest ``MIN_CAPACITY * 4**k`` that holds ``nnz`` entries."""
    cap = MIN_CAPACITY
    while cap < nnz:
        cap *= 4
    return cap


def _first_true(mask: jax.Array, size: int) -> jax.Array:
    """Positions of the first ``size`` True entries of a 1-D mask, in
    ascending order; slots past the last True hold ``mask.size - 1``.

    A cumulative count and a binary search per slot: no scatter over the
    mask (``jnp.nonzero(size=)`` scatter-adds one update per entry)."""
    rank = jnp.cumsum(mask, dtype=jnp.int32)
    want = jnp.arange(1, size + 1, dtype=jnp.int32)
    pos = jnp.searchsorted(rank, want, side="left", method="scan_unrolled")
    return jnp.minimum(pos, mask.size - 1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames="capacity")
def compact_tiles(
    vals: jax.Array, *, capacity: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The nonzero entries of a (B, bp, bp) batch of thresholded tiles, in
    row-major order (``np.nonzero``'s), padded to ``capacity`` slots.

    Returns (trc (3, capacity) int32 [tile pair; row; column], v (capacity,)
    float32 as the kernel wrote it, n () int32 the number of nonzeros).
    The first ``min(n, capacity)`` slots hold them; the caller picks
    ``capacity >= n``.  Two stages, so no step touches every entry more
    than once: flag the rows that hold a nonzero (one read of ``vals``),
    gather at most ``capacity`` flagged rows, then find the nonzero
    columns inside the gathered rows alone."""
    npairs, bp, _ = vals.shape
    rows_all = vals.reshape(npairs * bp, bp)
    row_nnz = jnp.sum(rows_all != 0, axis=1, dtype=jnp.int32)
    n_rows = min(capacity, npairs * bp)
    rows = _first_true(row_nnz > 0, n_rows)
    # slots past the flagged rows repeat the last row: their entries
    # follow all n real ones, in the slots the caller drops
    picked = rows_all[rows]
    flat = _first_true((picked != 0).reshape(-1), capacity)
    row = rows[flat // bp]
    trc = jnp.stack([row // bp, row % bp, flat % bp]).astype(jnp.int32)
    return trc, picked.reshape(-1)[flat], jnp.sum(row_nnz, dtype=jnp.int32)


def compact_edges(
    vals: np.ndarray, i_idx: np.ndarray, j_idx: np.ndarray, *, block_p: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Compact a batch of thresholded tiles into global (i, j, |S_ij|) edge
    arrays, upper triangle only (diagonal tile pairs emit both orientations;
    off-diagonal pairs are scheduled with tile_i < tile_j)."""
    gi, gj, v = compact_edges_signed(vals, i_idx, j_idx, block_p=block_p)
    return gi, gj, np.abs(v)


def compact_edges_signed(
    vals: np.ndarray, i_idx: np.ndarray, j_idx: np.ndarray, *, block_p: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``compact_edges`` keeping the SIGNED covariance values.

    The joint hybrid screen needs signs: the fused-penalty subset condition
    bounds |sum_A S_k,ij| across classes, which |S_ij| alone cannot
    evaluate.  The single-class screen keeps using the absolute view."""
    t, r, c = np.nonzero(vals)
    return _upper_global(t, r, c, vals[t, r, c], i_idx, j_idx, block_p=block_p)


def _upper_global(t, r, c, v, i_idx, j_idx, *, block_p: int):
    """Tile-local entries (pair t, row r, column c, value v) to global
    upper-triangle edges (int64 gi < gj, float64 v), order kept."""
    gi = i_idx[t].astype(np.int64) * block_p + r
    gj = j_idx[t].astype(np.int64) * block_p + c
    keep = gi < gj
    return gi[keep], gj[keep], v[keep].astype(np.float64)


def covgram_screen_tiles_stacked(
    xs_pad,
    mus_pad,
    i_idx_per_class,
    j_idx_per_class,
    lam: float,
    *,
    n_trues,
    p_true: int,
    block_p: int,
    block_n: int = 512,
    backend: str = "auto",
    pair_batch: int = 64,
) -> list[tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """K-stacked screen: one ``covgram_screen_edges`` pass PER CLASS.

    The joint screener's entry point: each class streams its OWN kept-tile
    schedule (the Cauchy-Schwarz certificates are per class — a tile proven
    edge-free for class k cannot contribute a |S_k,ij| > lam1 candidate, so
    skipping it per class is exact) in bounded ``pair_batch`` flights
    through the same kernel/oracle the single-class screener uses, and the
    compacted SIGNED per-class edges come back stacked for the hybrid-rule
    evaluation.  Per-class row counts n_k (and their padding) legitimately
    differ, which is why this is a schedule-stacked wrapper rather than one
    K-batched kernel launch."""
    out = []
    for x_pad, mu_pad, bi, bj, n_true in zip(
        xs_pad, mus_pad, i_idx_per_class, j_idx_per_class, n_trues
    ):
        bi = np.asarray(bi, np.int32)
        bj = np.asarray(bj, np.int32)
        gi_parts, gj_parts, v_parts = [], [], []
        for b0 in range(0, bi.size, max(1, int(pair_batch))):
            sl = slice(b0, b0 + max(1, int(pair_batch)))
            gi, gj, v, _, _ = covgram_screen_edges(
                x_pad,
                mu_pad,
                bi[sl],
                bj[sl],
                lam,
                n_true=int(n_true),
                p_true=p_true,
                block_p=block_p,
                block_n=block_n,
                backend=backend,
            )
            gi_parts.append(gi)
            gj_parts.append(gj)
            v_parts.append(v)
        def cat(parts, dt):
            return np.concatenate(parts) if parts else np.empty(0, dt)

        out.append(
            (
                cat(gi_parts, np.int64),
                cat(gj_parts, np.int64),
                cat(v_parts, np.float64),
            )
        )
    return out
