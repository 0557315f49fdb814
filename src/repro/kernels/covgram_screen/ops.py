"""Public wrapper for the covgram_screen kernel family: backend dispatch,
padding convention, and edge compaction.

Dispatch follows the ``tree_glasso`` precedent: on TPU the fused Pallas
kernel computes the requested tile pairs; off-TPU the numpy oracle wins
(interpret-mode emulation costs per-grid-step overhead on exactly the
many-tile pattern the kernel accelerates, and the numpy path keeps the input
dtype — f64 tiles match a dense f64 estimator exactly on representable
data).  ``backend="pallas"`` forces the kernel (interpret mode off-TPU) for
the equivalence tests.

``compact_edges`` turns a batch of thresholded tiles into the compacted
(i, j, |S_ij|) edge arrays the streaming screener accumulates: an entry of
``vals`` is nonzero iff it is an eq.-(4) edge (|S_ij| > lam >= 0 implies
S_ij != 0 in the same arithmetic), so compaction is one ``np.nonzero`` over
the in-flight batch — the dense (p, p) matrix never exists.
"""

from __future__ import annotations

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
    docstring for the stats layout.

    Under an active trace the call records ``screen.upload`` (inputs to
    the device), ``screen.kernel`` (the kernel, to completion) and
    ``screen.fetch`` (results back to the host) spans; the bytes moved
    each way count in ``stream.upload_bytes`` / ``stream.fetch_bytes``
    (0 on the host oracle)."""
    if backend == "auto":
        backend = "pallas" if _is_tpu() else "ref"
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
    if backend != "pallas":
        raise ValueError(f"unknown covgram_screen backend {backend!r}")
    with span("screen.upload"):
        args = jax.block_until_ready((
            jnp.asarray(x_pad, jnp.float32),
            jnp.asarray(mu_pad, jnp.float32),
            jnp.asarray(i_idx),
            jnp.asarray(j_idx),
            jnp.asarray(float(lam), jnp.float32).reshape(1, 1),
        ))
    with span("screen.kernel"):
        out = jax.block_until_ready(covgram_screen_pallas(
            *args,
            n_true=n_true,
            p_true=p_true,
            block_n=block_n,
            block_p=block_p,
            interpret=not _is_tpu(),
        ))
    with span("screen.fetch"):
        vals, counts, stats = (np.asarray(a) for a in out)
    bump("stream.upload_bytes", sum(a.nbytes for a in args))
    bump("stream.fetch_bytes", vals.nbytes + counts.nbytes + stats.nbytes)
    return vals, counts[:, 0], stats


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
    t, ri, ci = np.nonzero(vals)
    gi = i_idx[t].astype(np.int64) * block_p + ri
    gj = j_idx[t].astype(np.int64) * block_p + ci
    keep = gi < gj
    v = vals[t[keep], ri[keep], ci[keep]].astype(np.float64)
    return gi[keep], gj[keep], v


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
    """K-stacked screen: one fused gram+threshold+compact pass PER CLASS.

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
            vals, _, _ = covgram_screen_tiles(
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
            gi, gj, v = compact_edges_signed(
                vals, bi[sl], bj[sl], block_p=block_p
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
