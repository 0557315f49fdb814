"""The out-of-core screening driver: Theorem-1 partitions straight from X.

``stream_screen(X, lambdas)`` computes, without ever materializing the
(p, p) covariance:

  1. MOMENTS   one chunked pass over X -> mu, S_ii, column norms (tiler);
  2. SCHEDULE  upper-triangular column-tile pairs, minus every pair the
               Cauchy-Schwarz bound  max_I sqrt(S_ii) * max_J sqrt(S_jj)
               <= min(lambdas)  proves edge-free (``stream.tiles_skipped``);
  3. STREAM    surviving pairs flow in bounded batches through the fused
               covgram_screen kernel (Pallas on TPU, numpy oracle off-TPU);
               each batch compacts to (i, j, |S_ij|) triples in the edge
               accumulator — on the device on the Pallas path, so only
               the triples reach the host (``covgram_screen_edges``);
  4. SNAPSHOT  the retained edges, sorted once, replay the planner's nested
               Theorem-2 sweep (``labels_at_thresholds_from_edges``) — one
               incremental union-find pass labeling every requested lambda,
               the coarsest (grid-minimum) partition included;
  5. MATERIALIZE  per-component covariance sub-blocks of the coarsest
               partition are gathered from X — the only entries any plan on
               the grid can request.

Peak memory is  O(p * tile + #edges)  (in-flight tile batch + edge store +
O(p) moments/labels), recorded live in the ``stream.bytes_peak`` watermark.
On the Pallas path the in-flight batch lives on the device and the host
holds O(#edges) beside X and its moments;
the exactness story is unchanged — the emitted partition is property-tested
identical to ``thresholded_components`` on a dense S, ties included.

Under an active trace each stage records a span: ``screen.moments``
(moments, schedule, padding), per batch ``screen.upload`` /
``screen.kernel`` / ``screen.fetch`` / ``screen.compact``
(``covgram_screen_edges``; ``screen.compact`` again around the
accumulator), then ``screen.sweep`` and ``screen.materialize``;
``stream.tiles_with_edges`` counts the computed tile pairs that held an
edge.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from repro.core.instrument import bump, set_peak
from repro.core.partition import labels_at_thresholds_from_edges
from repro.core.screening import ScreenStats
from repro.kernels.covgram_screen import (
    covgram_screen_edges,
    pad_for_screen,
    resolve_backend,
)
from repro.obs.trace import span
from repro.stream.accumulate import EdgeAccumulator
from repro.stream.config import StreamConfig, as_config
from repro.stream.materialize import MaterializedCovariance, materialize_components
from repro.stream.tiler import (
    Moments,
    column_moments,
    tile_maxima,
    tile_pair_schedule,
)


@dataclass
class StreamScreen:
    """Everything downstream stages need, and nothing dense."""

    p: int
    n: int
    lambdas: list[float]                    # descending
    labels: list[np.ndarray]                # per lambda, canonical
    stats: list[ScreenStats]                # per lambda
    edges: tuple                            # (i, j, w) sorted by w descending
    S: MaterializedCovariance | None
    moments: Moments
    config: StreamConfig
    seconds: float
    tiles: dict = field(default_factory=dict)   # (ti, tj) -> TileRecord
    tiles_total: int = 0
    tiles_skipped: int = 0


def stream_screen(
    X: np.ndarray,
    lambdas,
    *,
    config=None,
    keep_tile_stats: bool = False,
    materialize: bool = True,
    oversize: int | None = None,
) -> StreamScreen:
    """Screen (X, every lambda) out-of-core; see the module docstring.

    ``oversize`` is the planner's single-device block-size cap: components
    larger than it are materialized DEFERRED (no host block — the sharded
    solve route streams them chunk-wise into device shards via
    ``materialize.shard_gather``)."""
    from repro.select.grid import normalize_lambda_grid  # lazy: select imports engine

    cfg = as_config(config)
    t0 = time.perf_counter()
    X = np.asarray(X)
    n, p = X.shape
    lams = normalize_lambda_grid(lambdas)
    lam_min = lams[-1]

    with span("screen.moments"):
        moments = column_moments(X, chunk=cfg.chunk)
        norms_max = tile_maxima(moments.norms, cfg.tile)
        ti, tj, keep = tile_pair_schedule(
            norms_max, lam_min, slack=cfg.skip_slack
        )
        x_pad, mu_pad = pad_for_screen(
            X, moments.mu, block_n=cfg.chunk, block_p=cfg.tile
        )
    bump("stream.tiles_total", int(ti.size))
    bump("stream.tiles_skipped", int((~keep).sum()))

    acc = EdgeAccumulator(keep_tiles=keep_tile_stats)
    acc.add_skipped(zip(ti[~keep], tj[~keep]))

    backend = resolve_backend(cfg.backend)
    itemsize = 4 if backend == "pallas" else x_pad.dtype.itemsize
    batch = cfg.resolved_pair_batch(itemsize)
    i_keep = ti[keep].astype(np.int32)
    j_keep = tj[keep].astype(np.int32)
    # stream.bytes_peak: padded X and the moments vectors (host), the
    # in-flight tile batch (on the device on the Pallas path, on the host
    # on the oracle) and the edge store (host)
    base_bytes = x_pad.nbytes + 4 * p * 8
    local_peak = base_bytes
    for b0 in range(0, i_keep.size, batch):
        bi = i_keep[b0 : b0 + batch]
        bj = j_keep[b0 : b0 + batch]
        gi, gj, v, counts, stats = covgram_screen_edges(
            x_pad,
            mu_pad,
            bi,
            bj,
            lam_min,
            n_true=n,
            p_true=p,
            block_p=cfg.tile,
            block_n=cfg.chunk,
            backend=backend,
        )
        bump("stream.tiles_with_edges", int(np.count_nonzero(counts)))
        with span("screen.compact"):
            acc.add_batch(bi, bj, gi, gj, np.abs(v), stats, tile=cfg.tile)
        batch_bytes = bi.size * cfg.tile**2 * itemsize
        local_peak = max(local_peak, base_bytes + batch_bytes + acc.bytes_held())
        set_peak("stream.bytes_peak", local_peak)
    bump("stream.edges_emitted", acc.n_edges)

    with span("screen.sweep"):
        ei, ej, ew = acc.edges()
        order = np.argsort(-ew, kind="stable")
        edges = (ei[order], ej[order], ew[order])
        labels = labels_at_thresholds_from_edges(p, lams, edges)
        stats_list = []
        for lam, lab in zip(lams, labels):
            _, sizes = np.unique(lab, return_counts=True)
            stats_list.append(
                ScreenStats(
                    lam=lam,
                    n_components=int(sizes.size),
                    max_comp=int(sizes.max()),
                    n_isolated=int((sizes == 1).sum()),
                    # edges sorted descending; strict |S_ij| > lam (eq. (4))
                    n_edges=int(np.searchsorted(-edges[2], -lam, side="left")),
                    seconds=0.0,  # filled below: the stream's share per lambda
                    tiles_total=int(ti.size),
                    tiles_skipped=int((~keep).sum()),
                    edges_emitted=acc.n_edges,
                    bytes_peak=0,  # filled below once materialization lands
                )
            )
    per_lam = (time.perf_counter() - t0) / max(len(lams), 1)

    S = None
    if materialize:
        # the coarsest partition is the grid-minimum snapshot of the same
        # Theorem-2 sweep (lams is descending, so labels[-1]); every finer
        # plan gathers sub-blocks of these blocks.  Merging edges into a
        # live union-find DURING the stream would duplicate the sweep's
        # O(#edges) work per call — that incremental structure is the
        # session layer's tool, where edge sets arrive per-tile
        # (stream.session / stream.unionfind).
        with span("screen.materialize"):
            S = materialize_components(
                X, moments.mu, moments.diag, labels[-1], oversize=oversize
            )
        local_peak = max(local_peak, base_bytes + acc.bytes_held() + S.nbytes())
        set_peak("stream.bytes_peak", local_peak)
    for st in stats_list:
        st.seconds = per_lam
        st.bytes_peak = local_peak
    seconds = time.perf_counter() - t0
    return StreamScreen(
        p=p,
        n=n,
        lambdas=lams,
        labels=labels,
        stats=stats_list,
        edges=edges,
        S=S,
        moments=moments,
        config=cfg,
        seconds=seconds,
        tiles=acc.tiles,
        tiles_total=int(ti.size),
        tiles_skipped=int((~keep).sum()),
    )
