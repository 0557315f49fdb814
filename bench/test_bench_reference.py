"""The reference check at tiny sizes on the CPU: it passes sound answers of
the program and fails a perturbed Theta, a merged partition and a broken
assembly."""

import copy
import json
from pathlib import Path

import numpy as np
import pytest

from bench.control import served
from bench.data import fmri, microarray
from bench.reference import check

BENCH = Path(__file__).resolve().parent


def tiny_expression(seed=0):
    cfg = json.loads((BENCH / "configs" / "expr_nki.json").read_text())
    cfg["n_samples"], cfg["n_genes"] = 60, 700
    cfg["assumed"]["module_sizes"] = [40, 20, 12, 6, 4]
    return microarray.make(cfg, seed)


@pytest.fixture(scope="module")
def path_answers():
    from repro.core import glasso_path

    X = tiny_expression()
    grid = [0.6, 0.45, 0.3]
    return X, grid, glasso_path(X=X, lambdas=grid, from_data=True)


def test_gram_edges_blockwise_equal_dense(monkeypatch):
    X = tiny_expression()
    monkeypatch.setattr(check, "GRAM_BLOCK", 64)
    i, j, w = check.Covariance(X).edges_above(0.2)
    Xc = X.astype(np.float64) - X.astype(np.float64).mean(axis=0)
    S = np.abs(Xc.T @ Xc / X.shape[0])
    ri, rj = np.nonzero(np.triu(S > 0.2, 1))
    assert sorted(zip(i.tolist(), j.tolist())) == sorted(zip(ri.tolist(), rj.tolist()))
    np.testing.assert_allclose(np.sort(w), np.sort(S[ri, rj]), rtol=1e-12)


def test_kkt_formula_agrees_with_program_host_kkt():
    from repro.core.solvers.closed_form import kkt_residual_host

    rng = np.random.default_rng(1)
    A = rng.standard_normal((40, 8))
    S = A.T @ A / 40
    Theta = np.linalg.inv(S + 0.3 * np.eye(8))
    Theta[np.abs(Theta) < 0.05] = 0.0
    assert check.kkt_residual(S, 0.1, Theta) == pytest.approx(
        kkt_residual_host(S, 0.1, Theta), rel=1e-12
    )


def test_sound_path_passes(path_answers):
    X, grid, results = path_answers
    ref = check.Reference(X, grid)
    got = check.merge_checks(
        [check.check_solution(ref, r.lam, r.labels, r.Theta) for r in results]
    )
    assert got["partition"] == 0 and got["offblock"] == 0
    assert got["kkt"] < 1e-6
    assert ref.largest(grid[-1]) > ref.largest(grid[0]) > 1


def test_perturbed_theta_fails(path_answers):
    X, grid, results = path_answers
    r = results[-1]
    ref = check.Reference(X, grid)
    want = ref.labels(r.lam)
    comp = np.flatnonzero(want == np.bincount(want).argmax())
    Theta = np.array(r.Theta, dtype=np.float64, copy=True)
    i, j = comp[0], comp[1]
    Theta[i, j] += 1e-3
    Theta[j, i] += 1e-3
    assert check.check_solution(ref, r.lam, r.labels, Theta)["kkt"] > 1e-4


def test_merged_partition_fails(path_answers):
    X, grid, results = path_answers
    r = results[0]
    ref = check.Reference(X, grid)
    labels = np.array(r.labels, copy=True)
    a, b = np.unique(labels)[:2]
    labels[labels == b] = a
    assert check.check_solution(ref, r.lam, labels, r.Theta)["partition"] == 1


def test_cross_component_entry_fails_assembly(path_answers):
    X, grid, results = path_answers
    r = results[0]
    ref = check.Reference(X, grid)
    want = ref.labels(r.lam)
    i = 0
    j = int(np.flatnonzero(want != want[0])[0])
    Theta = np.array(r.Theta, dtype=np.float64, copy=True)
    Theta[i, j] = Theta[j, i] = 1e-9
    assert check.check_solution(ref, r.lam, r.labels, Theta)["offblock"] == 1


def test_sparse_answer_checked_blockwise():
    from repro.core import glasso_path
    from repro.engine import EngineOptions

    X = tiny_expression()
    grid = [0.45, 0.3]
    results = glasso_path(X=X, lambdas=grid, from_data=True, options=EngineOptions(output="sparse"))
    ref = check.Reference(X, grid)
    for r in results:
        got = check.check_solution(ref, r.lam, r.labels, r.Theta)
        assert got == {"partition": 0, "offblock": 0, "kkt": pytest.approx(got["kkt"])}
        assert got["kkt"] < 1e-6
    # a sparse answer whose blocks are not the reference components
    fake = copy.copy(results[-1].Theta)
    other = ref.labels(grid[0])
    assert not check.same_partition(other, ref.labels(grid[-1]))
    blocks, sound = check.solution_blocks(fake, other)
    assert not sound


def test_bf16_control_fails_the_check():
    """The precision control at test size: the program served bfloat16
    data is judged against the float32 data's reference, and fails."""
    from repro.core import glasso_path

    X = tiny_expression()
    grid = [0.6, 0.45, 0.3]
    ref = check.Reference(X, grid)
    results = glasso_path(X=served(X, "bf16"), lambdas=grid, from_data=True)
    got = check.merge_checks(
        [check.check_solution(ref, r.lam, r.labels, r.Theta) for r in results]
    )
    assert got["kkt"] > 1e-4 or got["partition"] > 0


def test_fmri_bank_is_fixed_and_standardised():
    cfg = json.loads((BENCH / "configs" / "hcp_s400.json").read_text())
    cfg = dict(cfg, n_frames=200)
    a = fmri.bank(cfg, [0.6, 0.3], 3)
    b = fmri.bank(cfg, [0.6, 0.3], 3)
    assert [lam for _, lam in a] == [0.6, 0.3, 0.6]
    for (xa, _), (xb, _) in zip(a, b):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_allclose(xa.std(axis=0), 1.0, rtol=1e-4)
    net, system = fmri.network_of(cfg)
    assert net.size == 400 and system.max() == 6


def test_expression_seeds_relabel_one_problem():
    cfg = json.loads((BENCH / "configs" / "expr_nki.json").read_text())
    cfg["n_samples"], cfg["n_genes"] = 30, 200
    cfg["assumed"]["module_sizes"] = [20, 10]
    a, b = microarray.make(cfg, 1), microarray.make(cfg, 2**31 + 7)
    assert a.dtype == np.float32 and not np.array_equal(a, b)
    sa = np.sort(np.abs(np.corrcoef(a.T.astype(np.float64))).ravel())
    sb = np.sort(np.abs(np.corrcoef(b.T.astype(np.float64))).ravel())
    np.testing.assert_allclose(sa, sb, atol=1e-5)
