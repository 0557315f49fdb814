"""Bitwise property tests for the fused wave packer (DESIGN.md Section 16).

The wave packer's contract is EXACTNESS, not closeness: re-packing small
iterative buckets across bucket boundaries into size-binned megabatches and
solving each bin with one ``kernels.bucket_glasso`` launch must reproduce the
per-bucket unfused dispatches bit for bit (``==`` / ``np.array_equal``, the
repo's bitwise gate — -0.0 == +0.0 by design).  That rests on three pinned
invariants, each exercised here:

* bin re-padding with an identity diagonal is screened-exact and the
  convergence scale is injected at the SOURCE shape;
* cold lanes synthesize the warm pair the solver would have built, so warm
  and cold source buckets share one executable;
* no launch has leading dim 1 (``waves.min_batch2``) — XLA's unit-batch
  codegen differs by 1 ulp, the only batch-size dependence there is.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import EngineOptions, glasso, glasso_path
from repro.core.instrument import count, reset
from repro.engine.registry import ROUTES, set_route
from repro.engine.waves import FUSED_BINS, fused_bin


def planted_general_blocks(sizes, seed=0, cross=0.0):
    """Block-diagonal S whose blocks are chordless cycles (structure
    "general" for size >= 4, so they route to the iterative tail).  Entries
    are dyadic (multiples of 1/64) so |S_ij| == lam ties are exact in every
    cc backend's arithmetic.  ``cross`` plants dyadic couplings between
    consecutive blocks — below-threshold at high lambda, merging at low."""
    rng = np.random.default_rng(seed)
    p = int(sum(sizes))
    S = np.zeros((p, p))
    off = 0
    starts = []
    for b in sizes:
        starts.append(off)
        for i in range(b):
            j = (i + 1) % b
            mag = rng.integers(24, 33) / 64.0  # in [0.375, 0.5], dyadic
            sgn = 1.0 if rng.random() < 0.5 else -1.0
            S[off + i, off + j] = S[off + j, off + i] = sgn * mag
        off += b
    if cross:
        for a, b in zip(starts, starts[1:]):
            S[a, b] = S[b, a] = cross
    np.fill_diagonal(S, 1.0)
    return S


MIXED_SIZES = [4, 4, 4, 5, 7, 7, 12, 13, 20, 40]  # spans every bin, and
# includes single-block buckets (5, 12, 13, 20, 40) — the min-batch-2 rule


def _path_bitwise_equal(pa, pb):
    for ra, rb in zip(pa, pb):
        assert np.array_equal(ra.labels, rb.labels)
        if not np.array_equal(ra.Theta, rb.Theta):
            return False
    return True


@pytest.mark.parametrize("backend", ("host", "jax", "pallas", "shard_map"))
def test_fused_bitwise_equals_unfused_per_backend(backend):
    """One plan step, mixed bucket sizes, a dyadic tie |S_ij| == lam: the
    fused megabatch reproduces the per-bucket dispatches bit for bit under
    every screening backend."""
    S = planted_general_blocks(MIXED_SIZES, seed=1, cross=0.25)
    lam = 0.25  # == the planted cross coupling: an exact eq.-(4) tie
    base = EngineOptions(solver="bcd", cc_backend=backend)
    r_un = glasso(S, lam, options=base)
    r_f = glasso(S, lam, options=base.replace(fused=True))
    assert np.array_equal(r_un.labels, r_f.labels)
    assert np.array_equal(r_un.Theta, r_f.Theta)


def test_fused_warm_path_with_midgrid_merges_bitwise():
    """A descending grid whose components MERGE mid-path (cross couplings
    activate): warm-started fused == warm-started unfused bitwise at every
    grid point — reused-bucket warm stacks, merged-component blockwise
    inverses, and cold first points all pack transparently."""
    S = planted_general_blocks([4, 5, 6, 7, 4, 9], seed=2, cross=0.25)
    lams = [0.45, 0.35, 0.25, 0.2]  # merges activate at the 0.25 tie point
    opts = EngineOptions(solver="bcd", solver_opts={"tol": 1e-7})
    p_un = glasso_path(S, lams, options=opts)
    p_f = glasso_path(S, lams, options=opts.replace(fused=True))
    # sanity: the grid really merges (fewer components at the tail)
    n_first = len(np.unique(p_un[0].labels))
    n_last = len(np.unique(p_un[-1].labels))
    assert n_last < n_first
    assert _path_bitwise_equal(p_un, p_f)


def test_fused_solver_and_route_are_bitwise_too():
    """The two other opt-in surfaces — solver="fused_bcd" and
    registry.set_route("general", "fused") — produce the same bits as the
    plain unfused solve."""
    S = planted_general_blocks([4, 4, 6, 11], seed=3)
    lam = 0.3
    r_un = glasso(S, lam, options=EngineOptions(solver="bcd"))
    r_solver = glasso(S, lam, options=EngineOptions(solver="fused_bcd"))
    assert np.array_equal(r_un.Theta, r_solver.Theta)
    set_route("general", "fused")
    try:
        r_route = glasso(S, lam, options=EngineOptions(solver="bcd"))
    finally:
        set_route("general", "iterative")
    assert np.array_equal(r_un.Theta, r_route.Theta)


def test_single_lane_buckets_fuse_bitwise():
    """Buckets of ONE block each (every size unique) stress the
    min-batch-2 rule on both arms: a fused megabatch of singletons must
    equal the unfused one-bucket dispatches."""
    S = planted_general_blocks([4, 5, 6, 7], seed=4)
    r_un = glasso(S, 0.3, options=EngineOptions(solver="bcd"))
    r_f = glasso(S, 0.3, options=EngineOptions(solver="bcd", fused=True))
    assert np.array_equal(r_un.Theta, r_f.Theta)


def test_fused_counters_and_dispatch_collapse():
    """One launch per occupied bin per wave: solver.fused.dispatches equals
    the number of occupied bins, blocks_packed counts every general block,
    and the dispatch stage is attributed on the result."""
    sizes = MIXED_SIZES
    S = planted_general_blocks(sizes, seed=5)
    bins_occupied = {fused_bin(s) for s in sizes}
    reset("solver.fused.")
    reset("engine.dispatch.")
    r = glasso(S, 0.3, options=EngineOptions(solver="bcd", fused=True))
    assert count("solver.fused.dispatches") == len(bins_occupied)
    assert count("solver.fused.blocks_packed") == len(sizes)
    assert count("engine.dispatch.count") >= len(bins_occupied)
    assert count("engine.dispatch.us") > 0
    assert r.dispatch_seconds > 0.0
    assert "dispatch_us" in r.stages_us


def test_fused_options_and_registry_surface():
    assert "fused" in ROUTES
    for s in (1, 8, 9, 64):
        b = fused_bin(s)
        assert b in FUSED_BINS and b >= s
    assert fused_bin(65) is None
    with pytest.raises(ValueError, match="fused must be"):
        EngineOptions(fused="yes")
    # fused=True demands the fused_stack capability ("pg" lacks it)
    from repro.engine.api import Engine

    with pytest.raises(ValueError, match="fused_stack"):
        Engine(options=EngineOptions(solver="pg", fused=True))


def test_bucket_glasso_pallas_interpret_matches_ref():
    """The Pallas kernel (interpret mode off-TPU) and the vmapped jnp
    reference agree bitwise lane for lane on a warm/cold mixed stack."""
    from repro.kernels.bucket_glasso import fused_bcd_ref_stack
    from repro.kernels.bucket_glasso.bucket_glasso import fused_bcd_pallas

    rng = np.random.default_rng(6)
    N, b = 3, 8
    A = rng.standard_normal((N, b, b)) * (rng.random((N, b, b)) < 0.4)
    S = A @ A.transpose(0, 2, 1) / b + np.eye(b)[None]
    lams = np.full(N, 0.3)
    scales = np.abs(S - np.eye(b)[None] * np.diagonal(
        S, axis1=1, axis2=2
    )[:, None, :] * np.eye(b)[None]).mean(axis=(1, 2)) + 1e-12
    W0 = S + lams[:, None, None] * np.eye(b)[None]
    T0 = np.broadcast_to(np.eye(b), (N, b, b)).copy()
    args = tuple(jnp.asarray(x) for x in (S, lams, scales, W0, T0))
    t_ref, sw_ref = fused_bcd_ref_stack(*args)
    t_pl, sw_pl = fused_bcd_pallas(
        args[0], args[1].reshape(N, 1), args[2].reshape(N, 1),
        args[3], args[4], interpret=True,
    )
    assert np.array_equal(np.asarray(t_ref), np.asarray(t_pl))
    assert np.array_equal(
        np.asarray(sw_ref), np.asarray(sw_pl).reshape(N)
    )
    # and the reference really solves the problem: KKT spot check
    from repro.core.solvers.kkt import kkt_residual

    for i in range(N):
        res = float(kkt_residual(jnp.asarray(S[i]), t_ref[i], 0.3))
        assert res < 1e-4


def test_fused_from_serving_routes_unchanged():
    """A "fused"-routed structure reaching the serving batcher falls through
    to its iterative group — same bits as the offline solve."""
    from repro.launch.serve_glasso import GlassoServer

    S = planted_general_blocks([4, 6, 5], seed=7)
    lam = 0.3
    opts = EngineOptions(solver="bcd", output="dense")
    offline = glasso(S, lam, options=opts)
    set_route("general", "fused")
    try:
        with GlassoServer(options=opts) as server:
            served = server.submit(S, lam).result(timeout=300)
    finally:
        set_route("general", "iterative")
    assert np.array_equal(np.asarray(offline.Theta), np.asarray(served.Theta))



@pytest.mark.parametrize("fused", [False, True])
def test_warm_paths_never_read_a_donated_w0(monkeypatch, fused):
    """Warm W0 stacks are donated to the solver call off the CPU.  Force
    donation on here (the CPU backend honours it: a donated buffer is
    deleted) and run a warm path whose closed-form and chordal candidates
    are all rejected (route_check_tol=0), so repairs warm-start from donated
    stacks too: no W0 may be read after its call, and the answers equal the
    undonated run's."""
    from repro.covariance import structured_synthetic
    from repro.engine import executor

    S = structured_synthetic(12, 8, seed=5)
    lams = [0.7, 0.55, 0.4, 0.32]
    opts = EngineOptions(fused=fused, route_check_tol=0.0)
    reset("router")
    plain = glasso_path(S, lams, options=opts)
    assert count("router.fallback.tree") + count("router.fallback.chordal") > 0

    monkeypatch.setattr(executor, "_COMPILED", {})
    monkeypatch.setattr(executor, "_donate_supported", lambda: True)
    donated = glasso_path(S, lams, options=opts)
    assert _path_bitwise_equal(plain, donated)
