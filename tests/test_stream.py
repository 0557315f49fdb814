"""Streamed-vs-dense equivalence for the out-of-core screening subsystem.

The streaming screener must reproduce the dense Theorem-1 pipeline EXACTLY:
same partitions (all four dense cc backends, ties |S_ij| == lam included),
same edge weights, same materialized covariance sub-blocks, same glasso
solutions — while never building a (p, p) array.  Exact-tie cases use
integer-valued X with a power-of-two row count, so every covariance entry is
a dyadic rational computed exactly in f64 by ANY summation order: dense and
tiled arithmetic agree bit-for-bit and lam can be set to an off-diagonal
value itself.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import lambda_between_edges
from repro.core.components import component_lists, partitions_equal
from repro.core.screening import (
    count_edges,
    screen_stats_from_labels,
    thresholded_components,
)
from repro.stream import DataSession, StreamConfig, stream_screen

BACKENDS = ("host", "jax", "pallas", "shard_map")
CFG = {"tile": 32, "chunk": 16, "pair_batch": 3}  # 32 does not divide the ps below


def _data(rng, n, p, hetero=False):
    scales = 0.1 + rng.random(p) if not hetero else np.where(
        np.arange(p) < p // 3, 1.0, 0.03
    )
    return rng.standard_normal((n, p)) * scales


def _dense_S(X):
    Xc = X - X.mean(axis=0)
    return Xc.T @ Xc / X.shape[0]


def _integer_data(rng, n, p):
    """Integer X with power-of-two n: S entries are exact dyadic rationals
    identical under any tiling of the accumulation."""
    assert n & (n - 1) == 0
    return rng.integers(-4, 5, size=(n, p)).astype(np.float64)


@settings(max_examples=6, deadline=None)
@given(
    p=st.sampled_from([21, 50, 70]),   # never a multiple of tile=32
    n=st.sampled_from([16, 40]),
    seed=st.integers(0, 10_000),
    q=st.floats(0.3, 0.95),
)
def test_streamed_partition_matches_all_dense_backends(p, n, seed, q):
    rng = np.random.default_rng(seed)
    X = _data(rng, n, p)
    S = _dense_S(X)
    lam = lambda_between_edges(S, q)
    lam_lo = lambda_between_edges(S, q * 0.5)
    sc = stream_screen(X, [lam, lam_lo], config=CFG)
    for backend in BACKENDS:
        labels, stats = thresholded_components(S, lam, backend=backend, block=8)
        assert partitions_equal(sc.labels[0], labels), backend
        assert sc.stats[0].n_edges == stats.n_edges
    labels_lo, stats_lo = thresholded_components(S, lam_lo)
    assert partitions_equal(sc.labels[1], labels_lo)
    assert sc.stats[1].n_edges == stats_lo.n_edges


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_streamed_ties_are_not_edges(seed):
    rng = np.random.default_rng(seed)
    X = _integer_data(rng, 16, 40)
    S = _dense_S(X)
    iu, ju = np.triu_indices(40, 1)
    vals = np.abs(S[iu, ju])
    pos = np.sort(vals[vals > 0])
    lam = float(pos[pos.size // 2])  # an exact |S_ij|: a true tie
    assert (vals == lam).any()
    sc = stream_screen(X, [lam], config=CFG)
    labels, stats = thresholded_components(S, lam)
    assert partitions_equal(sc.labels[0], labels)
    assert sc.stats[0].n_edges == stats.n_edges == int((vals > lam).sum())


def test_streamed_edge_weights_match_dense(rng):
    X = _data(rng, 32, 50)
    S = _dense_S(X)
    lam = lambda_between_edges(S, 0.4)
    sc = stream_screen(X, [lam], config=CFG)
    gi, gj, w = sc.edges
    iu, ju = np.triu_indices(50, 1)
    dense_w = np.abs(S[iu, ju])
    keep = dense_w > lam
    assert gi.size == int(keep.sum())
    # same weight multiset, descending
    assert np.allclose(np.sort(w), np.sort(dense_w[keep]), atol=1e-12)
    assert np.all(np.diff(w) <= 0)
    assert np.allclose(np.abs(S[gi, gj]), w, atol=1e-12)


def test_materialized_blocks_and_diag_match_dense(rng):
    X = _data(rng, 32, 70)
    S = _dense_S(X)
    lam = lambda_between_edges(S, 0.5)
    sc = stream_screen(X, [lam], config=CFG)
    assert np.allclose(sc.S.diag_at(np.arange(70)), np.diag(S), atol=1e-12)
    for comp in component_lists(sc.labels[0]):
        assert np.allclose(
            sc.S.gather_block(comp), S[np.ix_(comp, comp)], atol=1e-12
        )


def test_cross_component_gather_raises(rng):
    X = _data(rng, 32, 40, hetero=True)
    S = _dense_S(X)
    lam = lambda_between_edges(S, 0.8)
    sc = stream_screen(X, [lam], config=CFG)
    comps = [c for c in component_lists(sc.labels[0]) if len(c) > 1]
    if len(comps) < 2:
        pytest.skip("partition has < 2 nontrivial components")
    mixed = np.array([comps[0][0], comps[1][0]])
    with pytest.raises(ValueError, match="across components"):
        sc.S.gather_block(mixed)


def test_tile_skip_prunes_and_stays_exact(rng):
    X = _data(rng, 48, 96, hetero=True)
    S = _dense_S(X)
    lam = lambda_between_edges(S, 0.9)
    sc = stream_screen(X, [lam], config=CFG)
    assert sc.tiles_skipped > 0, "heterogeneous scales must prune tiles"
    assert sc.tiles_skipped < sc.tiles_total
    labels, stats = thresholded_components(S, lam)
    assert partitions_equal(sc.labels[0], labels)
    assert sc.stats[0].n_edges == stats.n_edges
    assert sc.stats[0].tiles_skipped == sc.tiles_skipped
    # the memory watermark is accounted (the p-scaled claim is gated by
    # benchmarks/bench_stream.py's peak-RSS measurement at p=8k/16k)
    assert sc.stats[0].bytes_peak > 0


def test_streamed_glasso_path_equals_dense(rng):
    from repro.core import glasso_path

    X = _data(rng, 40, 60)
    S = _dense_S(X)
    lams = [lambda_between_edges(S, q) for q in (0.9, 0.7, 0.5)]
    dense = glasso_path(S, lams, tol=1e-8)
    streamed = glasso_path(
        X=X, lambdas=lams, from_data=True, tol=1e-8, stream=CFG
    )
    for d, s in zip(dense, streamed):
        assert partitions_equal(d.labels, s.labels)
        assert d.block_sizes == s.block_sizes
        assert d.route_mix == s.route_mix
        assert np.abs(d.Theta - s.Theta).max() < 1e-6
        assert s.screen.tiles_total > 0


def test_streamed_glasso_single_equals_dense(rng):
    from repro.core import glasso

    X = _data(rng, 40, 50)
    S = _dense_S(X)
    lam = lambda_between_edges(S, 0.6)
    d = glasso(S, lam, tol=1e-8)
    s = glasso(X=X, lam=lam, from_data=True, tol=1e-8, stream=CFG)
    assert partitions_equal(d.labels, s.labels)
    assert np.abs(d.Theta - s.Theta).max() < 1e-6


def test_glasso_input_validation():
    from repro.core import glasso, glasso_path

    with pytest.raises(ValueError, match="needs"):
        glasso(lam=0.5)
    with pytest.raises(ValueError, match="not both"):
        glasso(np.eye(3), 0.5, X=np.zeros((4, 3)))
    with pytest.raises(ValueError, match="needs"):
        glasso_path(X=np.zeros((4, 3)), from_data=True)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000), small=st.booleans())
def test_session_append_matches_scratch(seed, small):
    rng = np.random.default_rng(seed)
    X = _data(rng, 32, 48, hetero=True)
    lam = lambda_between_edges(_dense_S(X), 0.6)
    ses = DataSession(X, lam, config=StreamConfig(**CFG))
    scale = 0.02 if small else 1.0
    Y = rng.standard_normal((3, 48)) * scale
    up = ses.append_rows(Y)
    S2 = _dense_S(np.vstack([X, Y]))
    labels2, stats2 = thresholded_components(S2, lam)
    assert partitions_equal(up.labels, labels2)
    assert up.stats.n_edges == stats2.n_edges
    assert up.tiles_rescreened + up.tiles_revalidated == len(ses.tiles)
    # blocks re-materialize exactly from the updated data
    for comp in component_lists(up.labels):
        assert np.allclose(
            up.S.gather_block(comp), S2[np.ix_(comp, comp)], atol=1e-12
        )


def test_session_small_update_revalidates_tiles(rng):
    X = _data(rng, 48, 96, hetero=True)
    lam = lambda_between_edges(_dense_S(X), 0.6)
    ses = DataSession(X, lam, config=StreamConfig(**CFG))
    Y = 0.01 * rng.standard_normal((2, 96)) * np.where(np.arange(96) < 32, 1.0, 0.03)
    up = ses.append_rows(Y)
    assert up.tiles_revalidated > 0, "a tiny perturbation must keep most tiles"
    S2 = _dense_S(ses.X)
    labels2, _ = thresholded_components(S2, lam)
    assert partitions_equal(up.labels, labels2)
    # stacked updates: certificates shrank but must stay sound
    up2 = ses.append_rows(0.01 * rng.standard_normal((1, 96)))
    labels3, _ = thresholded_components(_dense_S(ses.X), lam)
    assert partitions_equal(up2.labels, labels3)


def test_session_merges_components(rng):
    X = _data(rng, 32, 48, hetero=True)
    lam = lambda_between_edges(_dense_S(X), 0.7)
    ses = DataSession(X, lam, config=StreamConfig(**CFG))
    k0 = ses.stats.n_components
    # rows strongly correlating two columns in different tiles force a merge
    Y = np.zeros((8, 48))
    Y[:, 5] = 8.0 * np.arange(8)
    Y[:, 40] = 8.0 * np.arange(8)
    up = ses.append_rows(Y)
    S2 = _dense_S(ses.X)
    labels2, _ = thresholded_components(S2, lam)
    assert partitions_equal(up.labels, labels2)
    assert up.labels[5] == up.labels[40], "planted correlation must merge"
    assert up.stats.n_components < k0 or up.components_touched > 0


# ---------------------------------------------------------------------------
# screen_stats_from_labels: no dense mask, streamed count reuse
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(
    p=st.sampled_from([5, 33, 64, 101]),
    seed=st.integers(0, 10_000),
    q=st.floats(0.1, 0.9),
)
def test_count_edges_matches_dense_mask(p, seed, q):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((p, p))
    S = A + A.T
    lam = float(np.quantile(np.abs(S), q))
    off = ~np.eye(p, dtype=bool)
    expected = int((np.abs(S)[off] > lam).sum() // 2)
    assert count_edges(S, lam, row_chunk=17) == expected
    assert count_edges(S, lam) == expected


def test_screen_stats_reuses_provided_edge_count(rng):
    labels = np.zeros(6, dtype=np.int64)

    class Boom:
        """Dense S stand-in that fails if stats touch it."""
        gather_block = None  # truthy attr: routes around the dense count

        def __getattr__(self, name):
            raise AssertionError("stats must not touch S when n_edges given")

    stats = screen_stats_from_labels(Boom(), 0.5, labels, seconds=0.0, n_edges=7)
    assert stats.n_edges == 7
    assert stats.n_components == 1
