"""Device compaction of the screen's thresholded tiles: ``compact_tiles``
and ``compact_edges_device`` return exactly what ``compact_edges_signed``
returns over the same tiles on the host (same arrays, same order), and every
screen built on ``covgram_screen_edges`` (the path screen, the session
re-screen, the joint screen's stacked schedule) comes out identical on the
Pallas path (interpret mode) with the device compaction and with the host
compaction over the kernel's own tiles."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.instrument import counts
from repro.covariance import microarray_like
from repro.kernels.covgram_screen import (
    compact_edges,
    compact_edges_signed,
    covgram_screen_tiles_stacked,
    pad_for_screen,
)
from repro.kernels.covgram_screen import ops
from repro.stream import DataSession, StreamConfig, stream_screen
from repro.stream.tiler import column_moments

BP = 16


def _vals(B, entries):
    """A (B, BP, BP) float32 batch with ``entries`` {(t, r, c): value}."""
    vals = np.zeros((B, BP, BP), np.float32)
    for (t, r, c), v in entries.items():
        vals[t, r, c] = v
    return vals


def _scattered(B, nnz, seed, diag=True):
    """``nnz`` nonzeros of both signs at distinct seeded positions (off
    the diagonal of a diagonal tile pair, as the kernel never emits it)."""
    rng = np.random.default_rng(seed)
    pos = rng.permutation(B * BP * BP)
    t, r, c = np.unravel_index(pos, (B, BP, BP))
    ok = (r != c) if diag else np.ones(pos.size, bool)
    t, r, c = t[ok][:nnz], r[ok][:nnz], c[ok][:nnz]
    v = rng.uniform(0.5, 2.0, nnz) * rng.choice([-1.0, 1.0], nnz)
    return _vals(B, dict(zip(zip(t, r, c), v.astype(np.float32))))


def _pairs(B):
    """Tile pairs (i, j) for a batch of B: diagonal and off-diagonal."""
    i = np.arange(B, dtype=np.int32) // 2
    return i, i + np.arange(B, dtype=np.int32) % 3


# 24 pairs hold 384 rows, more than the smallest capacity: the row stage
# then selects among more rows than it keeps
CASES = {
    "empty": _vals(4, {}),
    "one_off_diagonal_pair": _vals(4, {(1, 2, 7): 0.75}),
    "diagonal_both_orientations": _vals(4, {(0, 3, 5): 1.5, (0, 5, 3): 1.5}),
    "several_in_one_row": _vals(
        4, {(3, 4, c): (-1.0) ** c * (c + 1.0) for c in (0, 2, 3, 9, 15)}
    ),
    "negative_values": _vals(
        4, {(1, 0, 1): -0.25, (1, 1, 0): -0.25, (2, 15, 0): -3.0, (3, 0, 15): -1e-7}
    ),
    "at_capacity_boundary": _scattered(24, ops.MIN_CAPACITY, seed=1),
    "one_past_capacity_boundary": _scattered(24, ops.MIN_CAPACITY + 1, seed=2),
    "every_entry_of_one_pair": _vals(
        4, {(1, r, c): float(r - c) or 1.0 for r in range(BP) for c in range(BP)}
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_compaction_matches_host_compaction(case):
    vals = CASES[case]
    ii, jj = _pairs(vals.shape[0])
    nnz = (vals != 0).sum(axis=(1, 2)).astype(np.int32)
    before = counts("stream.")
    got = ops.compact_edges_device(jnp.asarray(vals), nnz, ii, jj, block_p=BP)
    after = counts("stream.")
    want = compact_edges_signed(vals, ii, jj, block_p=BP)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    total = int(nnz.sum())
    slots = after.get("stream.compact_slots", 0) - before.get("stream.compact_slots", 0)
    batches = after.get("stream.compact_batches", 0) - before.get("stream.compact_batches", 0)
    if total == 0:
        assert (batches, slots) == (0, 0)
    else:
        assert batches == 1
        assert slots >= total and (slots == ops.MIN_CAPACITY or slots < 4 * total)


@pytest.mark.parametrize("nnz, capacity", [
    (1, 256), (256, 256), (257, 1024), (1024, 1024), (1025, 4096), (70000, 262144),
])
def test_capacity_ladder(nnz, capacity):
    assert ops._capacity(nnz) == capacity


def test_compact_tiles_pads_and_counts():
    vals = CASES["several_in_one_row"]
    trc, v, n = (np.asarray(a) for a in ops.compact_tiles(jnp.asarray(vals), capacity=256))
    assert trc.shape == (3, 256) and trc.dtype == np.int32
    assert v.shape == (256,) and v.dtype == np.float32
    assert n.dtype == np.int32 and int(n) == 5
    t, r, c = np.nonzero(vals)
    np.testing.assert_array_equal(trc[:, :5], np.stack([t, r, c]))
    np.testing.assert_array_equal(v[:5], vals[t, r, c])


# ---------------------------------------------------------------------------
# the screens on the Pallas path: device compaction == host compaction of
# the kernel's own tiles
# ---------------------------------------------------------------------------

CFG = StreamConfig(tile=32, chunk=16, pair_batch=3, backend="pallas")


def _host_compaction(vals, counts, i_idx, j_idx, *, block_p):
    """The compaction before it moved to the device: fetch the tiles and
    ``compact_edges`` them."""
    return compact_edges_signed(np.asarray(vals), i_idx, j_idx, block_p=block_p)


def _lam(X, rank):
    S = np.cov(X, rowvar=False, bias=True)
    v = np.sort(np.abs(S[np.triu_indices(S.shape[0], 1)]))[::-1]
    return float(0.5 * (v[rank] + v[rank + 1]))


def _path(X, lams):
    sc = stream_screen(X, lams, config=CFG, materialize=False)
    return list(sc.edges) + list(sc.labels)


def _session(X, lams):
    ses = DataSession(X, lams[-1], config=CFG)
    rng = np.random.default_rng(5)
    up = ses.append_rows(rng.standard_normal((4, X.shape[1])) * X.std(axis=0))
    assert up.tiles_rescreened > 0
    recs = [ses.tiles[k] for k in sorted(ses.tiles) if not ses.tiles[k].skipped]
    return [up.labels] + [a for r in recs for a in (r.gi, r.gj, r.w)]


def _stacked(X, lams):
    Xs = [X, X[::-1][:30] * 1.1]
    xs, mus, sched_i, sched_j = [], [], [], []
    for Xk in Xs:
        x_pad, mu_pad = pad_for_screen(
            Xk, column_moments(Xk, chunk=CFG.chunk).mu,
            block_n=CFG.chunk, block_p=CFG.tile,
        )
        ti, tj = np.triu_indices(x_pad.shape[1] // CFG.tile)
        xs.append(x_pad)
        mus.append(mu_pad)
        sched_i.append(ti)
        sched_j.append(tj)
    out = covgram_screen_tiles_stacked(
        xs, mus, sched_i, sched_j, lams[-1],
        n_trues=[Xk.shape[0] for Xk in Xs], p_true=X.shape[1],
        block_p=CFG.tile, block_n=CFG.chunk, backend="pallas", pair_batch=2,
    )
    return [a for triple in out for a in triple]


@pytest.mark.parametrize("screen", [_path, _session, _stacked])
def test_pallas_screens_match_host_compaction(screen, monkeypatch):
    X = microarray_like(40, 70, n_modules=5, seed=3)
    lams = [_lam(X, 10), _lam(X, 40)]
    before = counts("stream.")
    got = screen(X, lams)
    assert counts("stream.").get("stream.compact_batches", 0) > before.get(
        "stream.compact_batches", 0
    )
    monkeypatch.setattr(ops, "compact_edges_device", _host_compaction)
    want = screen(X, lams)
    assert len(got) == len(want)
    assert sum(a.size for a in got) > 0
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


def test_path_screen_edges_match_compact_edges_of_kernel_tiles():
    """The path screen's sorted edges are ``compact_edges`` over the
    kernel's tiles of every scheduled pair, sorted the same way."""
    X = microarray_like(40, 70, n_modules=5, seed=4)
    lam = _lam(X, 30)
    sc = stream_screen(X, [lam], config=CFG, materialize=False)
    x_pad, mu_pad = pad_for_screen(
        X, column_moments(X, chunk=CFG.chunk).mu, block_n=CFG.chunk, block_p=CFG.tile
    )
    ti, tj = np.triu_indices(x_pad.shape[1] // CFG.tile)
    vals, _, _ = ops.covgram_screen_tiles(
        x_pad, mu_pad, ti, tj, lam, n_true=X.shape[0], p_true=X.shape[1],
        block_p=CFG.tile, block_n=CFG.chunk, backend="pallas",
    )
    gi, gj, w = compact_edges(vals, ti, tj, block_p=CFG.tile)
    # the screen skips pairs Cauchy-Schwarz proves edge-free: they hold none
    order = np.argsort(-w, kind="stable")
    for g, e in zip(sc.edges, (gi[order], gj[order], w[order])):
        np.testing.assert_array_equal(g, e)
