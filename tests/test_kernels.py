"""Per-kernel allclose sweeps: Pallas (interpret=True on CPU) vs pure-jnp
oracle, across shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.covgram.ops import covgram
from repro.kernels.covgram.ref import covgram_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.prox_l1.ops import prox_step
from repro.kernels.prox_l1.ref import prox_step_ref
from repro.kernels.threshold_cc.ops import connected_components_kernel, labelprop_step
from repro.kernels.threshold_cc.ref import labelprop_step_ref
from repro.kernels.tree_glasso.ref import glasso_forest_ref
from repro.kernels.tree_glasso.tree_glasso import glasso_forest_pallas


# ---------------------------------------------------------------- covgram
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "n,p,bn,bp",
    [(64, 32, 16, 8), (100, 17, 32, 8), (33, 64, 8, 16), (256, 96, 64, 32)],
)
def test_covgram_shapes(n, p, bn, bp, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, p)), dtype)
    out = covgram(x, block_n=bn, block_p=bp)
    ref = covgram_ref(x)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol, rtol=tol)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(4, 80), p=st.integers(2, 40), seed=st.integers(0, 100))
def test_covgram_property(n, p, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(covgram(x, block_n=16, block_p=8)),
        np.asarray(covgram_ref(x)),
        atol=1e-4, rtol=1e-4,
    )


# --------------------------------------------------------- covgram_screen
@settings(max_examples=8, deadline=None)
@given(
    n=st.integers(6, 60),
    p=st.integers(5, 50),
    seed=st.integers(0, 100),
    q=st.floats(0.2, 0.9),
)
def test_covgram_screen_pallas_matches_ref(n, p, seed, q):
    """The fused threshold+edge-emit kernel (interpret mode) and the numpy
    oracle emit the same edge set, counts, and tile stats."""
    from repro.kernels.covgram_screen import (
        compact_edges,
        covgram_screen_tiles,
        pad_for_screen,
    )

    bn, bp = 16, 16
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, p))
    mu = X.mean(axis=0)
    Xc = X - mu
    S = Xc.T @ Xc / n
    iu, ju = np.triu_indices(p, 1)
    # the kernel thresholds in float32 and the oracle in float64, so lam sits
    # midway between two adjacent |S_ij| (an exact tie could round either way)
    a = np.sort(np.abs(S[iu, ju]))
    k = int(q * (a.size - 1))
    lam = float(0.5 * (a[k] + a[k + 1]))
    x_pad, mu_pad = pad_for_screen(X, mu, block_n=bn, block_p=bp)
    nt = x_pad.shape[1] // bp
    ti, tj = np.triu_indices(nt)
    outs = {}
    for backend in ("ref", "pallas"):
        vals, counts_, stats = covgram_screen_tiles(
            x_pad, mu_pad, ti, tj, lam,
            n_true=n, p_true=p, block_p=bp, block_n=bn, backend=backend,
        )
        gi, gj, w = compact_edges(vals, ti, tj, block_p=bp)
        outs[backend] = (set(zip(gi.tolist(), gj.tolist())), counts_, stats)
    dense = set(zip(*(a.tolist() for a in (iu[np.abs(S[iu, ju]) > lam],
                                           ju[np.abs(S[iu, ju]) > lam]))))
    assert outs["ref"][0] == dense
    assert outs["pallas"][0] == dense
    np.testing.assert_array_equal(outs["ref"][1], outs["pallas"][1])
    np.testing.assert_allclose(
        outs["ref"][2], outs["pallas"][2], atol=1e-5, rtol=1e-4
    )


# ----------------------------------------------------------- threshold_cc
@settings(max_examples=15, deadline=None)
@given(p=st.integers(2, 70), seed=st.integers(0, 100), lam=st.floats(0.0, 2.0))
def test_labelprop_step_matches_ref(p, seed, lam):
    rng = np.random.default_rng(seed)
    S = rng.standard_normal((p, p))
    S = S + S.T
    labels = jnp.asarray(rng.integers(0, p, size=p), jnp.int32)
    out = labelprop_step(jnp.asarray(S, jnp.float32), labels, lam, block=16)
    ref = labelprop_step_ref(jnp.asarray(S, jnp.float32), labels, lam)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@settings(max_examples=10, deadline=None)
@given(p=st.integers(2, 50), seed=st.integers(0, 100), density=st.floats(0.01, 0.3))
def test_cc_kernel_matches_host(p, seed, density):
    from repro.core.components import components_from_covariance_host, partitions_equal

    rng = np.random.default_rng(seed)
    A = rng.random((p, p)) < density
    A = np.triu(A, 1)
    S = (A | A.T).astype(np.float32)
    labels = np.asarray(connected_components_kernel(jnp.asarray(S), 0.5, block=16))
    assert partitions_equal(labels, components_from_covariance_host(S, 0.5))


# ---------------------------------------------------------------- prox_l1
@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("B,b,blk", [(1, 8, 8), (3, 20, 8), (5, 64, 32), (2, 100, 64)])
def test_prox_shapes(B, b, blk, dtype):
    rng = np.random.default_rng(1)
    theta = jnp.asarray(rng.standard_normal((B, b, b)), dtype)
    grad = jnp.asarray(rng.standard_normal((B, b, b)), dtype)
    out = prox_step(theta, grad, 0.1, 0.5, block=blk)
    ref = prox_step_ref(theta, grad, 0.1, 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(2, 40),
    t=st.floats(1e-4, 2.0),
    lam=st.floats(0.0, 2.0),
    seed=st.integers(0, 100),
)
def test_prox_property(b, t, lam, seed):
    rng = np.random.default_rng(seed)
    theta = jnp.asarray(rng.standard_normal((2, b, b)), jnp.float32)
    grad = jnp.asarray(rng.standard_normal((2, b, b)), jnp.float32)
    out = prox_step(theta, grad, t, lam, block=16)
    ref = prox_step_ref(theta, grad, t, lam)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # prox output is exactly sparse where |theta - t g| <= t lam
    z = np.asarray(theta) - t * np.asarray(grad)
    assert np.all(np.asarray(out)[np.abs(z) <= t * lam] == 0.0)


# --------------------------------------------------------- flash_attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Skv,d",
    [
        (1, 4, 4, 64, 64, 16),    # MHA square
        (2, 8, 2, 32, 32, 8),     # GQA 4:1
        (1, 4, 1, 40, 72, 16),    # MQA, ragged + cross lengths
    ],
)
def test_flash_attention_matches_ref(B, Hq, Hkv, Sq, Skv, d, causal, dtype):
    if causal and Sq != Skv:
        pytest.skip("causal requires aligned self-attention lengths here")
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, Hq, Sq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Hkv, Skv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Hkv, Skv, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@settings(max_examples=8, deadline=None)
@given(
    sq=st.integers(2, 48),
    d=st.sampled_from([4, 8, 16]),
    group=st.sampled_from([1, 2, 4]),
    seed=st.integers(0, 100),
)
def test_flash_attention_property(sq, d, group, seed):
    rng = np.random.default_rng(seed)
    Hkv = 2
    q = jnp.asarray(rng.standard_normal((1, Hkv * group, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, Hkv, sq, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, Hkv, sq, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------- tree_glasso
@pytest.mark.parametrize("B,b", [(1, 8), (7, 8), (3, 16), (2, 32)])
def test_tree_glasso_kernel_matches_ref(B, b):
    """Pallas forest closed form (interpret mode) == jnp reference, with
    per-block lambdas (the serving mixed-lambda batch layout)."""
    rng = np.random.default_rng(0)
    blocks = rng.standard_normal((B, b, b))
    blocks = 0.5 * (blocks + blocks.transpose(0, 2, 1))
    blocks += (np.abs(blocks).sum(axis=2).max(axis=1)[:, None, None]) * np.eye(b)
    lams = rng.uniform(0.1, 0.6, size=B)
    out = glasso_forest_pallas(
        jnp.asarray(blocks), jnp.asarray(lams)[:, None], interpret=True
    )
    ref = jax.vmap(glasso_forest_ref)(jnp.asarray(blocks), jnp.asarray(lams))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-12)


@settings(max_examples=10, deadline=None)
@given(b=st.integers(2, 12), seed=st.integers(0, 500))
def test_tree_glasso_kernel_property(b, seed):
    """Padded shapes: ops-level zero padding must not change the sliced
    result (zero padding adds no |S_ij| > lam edges)."""
    from repro.kernels.tree_glasso.ops import glasso_forest_stack

    rng = np.random.default_rng(seed)
    S = rng.standard_normal((b, b))
    S = 0.5 * (S + S.T)
    np.fill_diagonal(S, 1.0 + np.abs(S).sum(axis=1))
    lam = float(rng.uniform(0.05, 0.5))
    out = glasso_forest_stack(jnp.asarray(S)[None], jnp.asarray([lam]))[0]
    ref = glasso_forest_ref(jnp.asarray(S), lam)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-12)
