"""Sharded oversize-solver subsystem on the single real device.

The genuine 8-device semantics live in test_distributed_multidevice.py (a
subprocess with faked devices); everything here exercises the same code
paths on the 1-device mesh — the ring matmul / all_to_all fast paths, the
shard_prox kernel (interpret mode vs ref), the shard-direct gather, the
planner's oversize class, the Solver protocol, and the executor's
cost-model placement — cheaply enough for the main suite.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import lambda_between_edges, random_covariance
from repro.core import blocks as blocks_mod
from repro.core.instrument import counts, reset
from repro.core.solvers import (
    SOLVERS,
    WARM_START_SOLVERS,
    glasso_admm,
    glasso_sharded,
    solver_spec,
)
from repro.core.solvers.closed_form import kkt_residual_host
from repro.core.solvers.sharded import sharded_pad_size
from repro.kernels.shard_prox.ref import fused_prox_ref
from repro.kernels.shard_prox.shard_prox import fused_prox_pallas


# ------------------------------------------------------------ the solver


@settings(max_examples=6, deadline=None)
@given(p=st.integers(6, 28), seed=st.integers(0, 1000), q=st.floats(0.2, 0.7))
def test_sharded_matches_admm_oracle(p, seed, q):
    rng = np.random.default_rng(seed)
    S = random_covariance(rng, p)
    lam = lambda_between_edges(S, q)
    res = glasso_sharded(S, lam)
    ref = np.asarray(glasso_admm(jnp.asarray(S), lam, tol=1e-9))
    assert res.kkt_residual <= 1e-6 * max(1.0, res.s_max)
    np.testing.assert_allclose(res.Theta, ref, atol=1e-6)
    assert ((np.abs(res.Theta) > 1e-9) == (np.abs(ref) > 1e-9)).all()


def test_sharded_float32_stops_at_its_rounding_floor():
    """float32 (the chip's dtype) cannot reach float64's inner tolerances:
    the solve must stop on its rounding floors, not spin to max_iter or
    revert every outer step, and still verify at a float32 KKT target."""
    rng = np.random.default_rng(3)
    X = rng.standard_normal((256, 128))
    X[:, 1:] += 0.8 * X[:, :-1]  # a chain: one connected component
    S = np.cov(X, rowvar=False, bias=True)
    lam = 0.2
    res = glasso_sharded(S, lam, dtype=jnp.float32, kkt_target=1e-4)
    assert res.iters < 6000 and res.retries == 0
    assert res.kkt_residual <= 1e-4 * max(1.0, res.s_max)
    kkt = kkt_residual_host(S, lam, np.asarray(res.Theta, dtype=np.float64))
    assert kkt <= 1e-3 * max(1.0, np.abs(S).max())
    # the float32 eps (tol * b) is under rounding noise: the stall stop ended
    # the ADMM loop, above that eps, and says so
    assert res.stalls >= 1 and res.admm_residual > res.admm_eps
    res64 = glasso_sharded(S, lam, kkt_target=1e-6)
    assert res64.stalls == 0


def test_sharded_pad_size():
    assert sharded_pad_size(5, 1) == 8
    assert sharded_pad_size(8, 1) == 8
    assert sharded_pad_size(9, 1) == 16
    assert sharded_pad_size(100, 8) == 128
    assert sharded_pad_size(64, 8) == 64
    assert sharded_pad_size(1, 8) == 64


def test_sharded_presharded_input_validates():
    S = np.eye(16)
    arr = jnp.asarray(S)
    with pytest.raises(ValueError, match="true block size"):
        glasso_sharded(arr, 0.1)
    with pytest.raises(ValueError, match="padded size"):
        glasso_sharded(arr, 0.1, b=3)  # 3 pads to 8, not 16


def test_sharded_solver_spec():
    spec = solver_spec("sharded")
    assert spec.sharded and not spec.batched and spec.warm_startable
    assert "sharded" not in SOLVERS          # not a user-pickable block solver
    assert "sharded" not in WARM_START_SOLVERS  # no vmapped W0 stacks
    with pytest.raises(ValueError, match="unknown solver"):
        solver_spec("nope")


# --------------------------------------------------- shard_prox kernels


@pytest.mark.parametrize("rl,b", [(8, 8), (16, 24), (32, 128), (8, 136)])
def test_shard_prox_pallas_vs_ref(rl, b):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((rl, b)))
    u = jnp.asarray(rng.standard_normal((rl, b)))
    z = jnp.asarray(rng.standard_normal((rl, b)))
    t = 0.3
    zr, ur, rp2, rd2 = fused_prox_ref(x, u, z, t)
    zp, up, acc = fused_prox_pallas(x, u, z, jnp.asarray(t), interpret=True)
    np.testing.assert_allclose(np.asarray(zp), np.asarray(zr), atol=1e-12)
    np.testing.assert_allclose(np.asarray(up), np.asarray(ur), atol=1e-12)
    np.testing.assert_allclose(float(acc[0, 0]), float(rp2), rtol=1e-10)
    np.testing.assert_allclose(float(acc[0, 1]), float(rd2), rtol=1e-10)


def test_shard_prox_row_tiled_accumulation():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((32, 16)))
    u = jnp.asarray(rng.standard_normal((32, 16)))
    z = jnp.asarray(rng.standard_normal((32, 16)))
    _, _, rp2, rd2 = fused_prox_ref(x, u, z, 0.2)
    _, _, acc = fused_prox_pallas(
        x, u, z, jnp.asarray(0.2), row_tile=8, interpret=True
    )  # 4 grid steps accumulate into one (1, 2) block
    np.testing.assert_allclose(float(acc[0, 0]), float(rp2), rtol=1e-10)
    np.testing.assert_allclose(float(acc[0, 1]), float(rd2), rtol=1e-10)


# ------------------------------------------------- shard-direct gather


def test_shard_gather_dense_matches_pad():
    from repro.core.jax_compat import local_device_mesh
    from repro.core.solvers.sharded import mesh_axis_size
    from repro.stream.materialize import shard_gather

    rng = np.random.default_rng(0)
    S = random_covariance(rng, 30)
    comp = np.arange(3, 25)  # b=22 -> pads to 24 on 1 shard, 64 on 8
    mesh = local_device_mesh("data")
    arr = np.asarray(shard_gather(S, comp, mesh))
    bp = sharded_pad_size(comp.size, mesh_axis_size(mesh))
    assert arr.shape == (bp, bp)
    np.testing.assert_allclose(arr[: comp.size, : comp.size], S[np.ix_(comp, comp)])
    pad = np.arange(comp.size, bp)
    assert (arr[pad, pad] == 1.0).all()
    assert arr[comp.size :, : comp.size].sum() == 0.0


def test_materialize_deferred_oversize():
    """Oversize components keep NO host block; gathers recompute from X."""
    from repro.stream import stream_screen

    rng = np.random.default_rng(0)
    n, p = 64, 48
    f = rng.standard_normal((n, 1))
    X = 0.3 * rng.standard_normal((n, p))
    X[:, :30] += f * (0.8 + 0.2 * rng.random(30))
    lam = 0.1
    full = stream_screen(X, [lam])
    deferred = stream_screen(X, [lam], oversize=20)
    assert counts("stream.").get("stream.deferred_components", 0) >= 1
    # same labels, and every gather identical to the materialized blocks
    np.testing.assert_array_equal(full.labels[0], deferred.labels[0])
    from repro.core.components import component_lists

    for comp in component_lists(full.labels[0]):
        if comp.size == 1:
            continue
        np.testing.assert_allclose(
            deferred.S.gather_block(comp), full.S.gather_block(comp), atol=1e-12
        )
        np.testing.assert_allclose(
            deferred.S.gather_block_rows(comp[:3], comp),
            full.S.gather_block(comp)[:3, :],
            atol=1e-12,
        )


# ----------------------------------------- planner / engine integration


def test_oversize_threshold_model():
    # 8 buffers * 8 bytes * b^2 <= budget  ->  b = sqrt(budget/64)
    assert blocks_mod.oversize_threshold(64.0) == int(
        np.sqrt(64 * 2**20 / 64)
    )
    assert blocks_mod.oversize_threshold(0.001) >= 1


def test_resolve_oversize():
    from repro.engine.api import resolve_oversize

    assert resolve_oversize(None, None, np.float64) is None
    assert resolve_oversize(123, None, np.float64) == 123
    assert resolve_oversize(123, 64.0, np.float64) == 123  # explicit wins
    assert resolve_oversize(None, 64.0, np.float64) == blocks_mod.oversize_threshold(64.0)
    # "auto" on CPU: backend reports no memory -> route disabled
    assert resolve_oversize(None, "auto", np.float64) is None
    with pytest.raises(ValueError, match="route=True"):
        resolve_oversize(123, None, np.float64, route=False)


def test_oversize_bucket_has_no_host_blocks():
    from repro.engine.planner import build_plan_incremental

    rng = np.random.default_rng(0)
    S = random_covariance(rng, 24)
    lam = lambda_between_edges(S, 0.2)  # dense-ish: one big component
    plan, _ = build_plan_incremental(S, lam, np.zeros(24, dtype=np.int64) , oversize=10)
    # labels all-zero is the single-component case (it IS connected here in
    # spirit; the classifier is bypassed by the oversize short-circuit)
    big = [b for b in plan.buckets if b.structure == "oversize"]
    assert big and all(b.blocks is None for b in big)


def test_engine_oversize_route_equivalence():
    reset("solver.oversize")
    from repro.core.glasso import glasso

    rng = np.random.default_rng(3)
    S = random_covariance(rng, 26)
    lam = lambda_between_edges(S, 0.3)
    base = glasso(S, lam, solver="admm", tol=1e-9)
    over = glasso(S, lam, solver="admm", tol=1e-9, oversize_threshold=12)
    np.testing.assert_allclose(over.Theta, base.Theta, atol=1e-6)
    if "oversize" in over.route_mix:
        assert over.oversize["dispatched"] >= 1
        assert counts("solver.oversize.")["solver.oversize.dispatched"] >= 1
        assert over.noniterative_fraction > 0.0


def test_path_oversize_warm_reuse():
    """A reused oversize bucket warm-starts from its previous solution."""
    from repro.core.glasso import glasso_path

    rng = np.random.default_rng(5)
    S = random_covariance(rng, 24)
    lams = [lambda_between_edges(S, 0.45), lambda_between_edges(S, 0.4)]
    res = glasso_path(S, lams, solver="admm", tol=1e-9, oversize_threshold=10)
    ref = glasso_path(S, lams, solver="admm", tol=1e-9)
    for r, b in zip(res, ref):
        np.testing.assert_allclose(r.Theta, b.Theta, atol=1e-6)


# ------------------------------------------------------ serving admission


def test_serving_oversize_admission():
    """An oversize request is admitted, skips the synchronous fast path,
    solves via the batcher's sharded group, and reports its counters."""
    from repro.core.glasso import glasso
    from repro.launch.serve_glasso import GlassoServer, serve_stats

    rng = np.random.default_rng(7)
    S = random_covariance(rng, 22)
    lam = lambda_between_edges(S, 0.3)
    ref = glasso(S, lam, solver="admm", tol=1e-9)
    reset("serve")
    with GlassoServer(solver="admm", tol=1e-9, oversize_threshold=10) as srv:
        res = srv.submit(S, lam).result(timeout=600)
    np.testing.assert_allclose(res.Theta, ref.Theta, atol=1e-6)
    if "oversize" in res.route_mix:
        assert res.oversize["dispatched"] >= 1
        stats = serve_stats()
        assert stats.get("serve.fastpath_requests", 0) == 0  # queued, not sync
        assert stats["solver.oversize.dispatched"] >= 1


# ------------------------------------------------ executor placement cost


def test_place_weighs_routes_not_just_size():
    """LPT placement must weight device cost by route: a chordal bucket
    solves on the HOST and must not claim a device's worth of b^3."""
    from repro.engine.executor import BucketExecutor

    ex = BucketExecutor(devices=["d0", "d1"])
    mk = lambda size, n, structure: blocks_mod.Bucket(
        size=size,
        comps=[np.arange(size)] * n,
        blocks=np.zeros((n, size, size)),
        structure=structure,
    )
    # route-aware costs: chordal -> 0, closed_form -> n*b^2, general -> n*b^3
    assert ex._bucket_cost(mk(16, 2, "chordal")) == 0.0
    assert ex._bucket_cost(mk(16, 2, "tree")) == 2 * 16.0**2
    assert ex._bucket_cost(mk(16, 2, "general")) == 2 * 16.0**3
    assert ex._bucket_cost(
        blocks_mod.Bucket(size=64, comps=[np.arange(64)], blocks=None,
                          structure="oversize")
    ) == 0.0
    # two iterative buckets of equal size + one huge chordal bucket: the
    # iterative pair must land on DIFFERENT devices (the chordal bucket is
    # free); a size-only model would pair one iterative with the chordal.
    chordal_big = mk(32, 4, "chordal")
    it_a = mk(16, 1, "general")
    it_b = mk(16, 1, "general")
    placed = ex._place([chordal_big, it_a, it_b])
    assert placed[1] != placed[2]
    # with routing off, everything is iterative again
    ex_off = BucketExecutor(devices=["d0", "d1"], route=False)
    assert ex_off._bucket_cost(mk(16, 2, "chordal")) == 2 * 16.0**3
