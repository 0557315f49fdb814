"""``chip_smoke.py`` phases at tiny sizes on the CPU.

The script runs on the chip only; these tests keep its phases from rotting
between chip runs.  Each phase runs through the same entry points and host
checks as on the chip, in float32 like the chip, minus the
``tpu_custom_call`` assertions that ``main()`` owns.  ``main()`` itself must
refuse to run without a TPU.
"""

from __future__ import annotations

import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

from repro.covariance import microarray_like  # noqa: E402
from repro.engine import EngineOptions  # noqa: E402

F32 = EngineOptions(dtype=jnp.float32)


@pytest.fixture(scope="module")
def dense():
    return chip_smoke.dense_workload(K=6, p1=8, n_lambdas=3)


def _checked(rep, tol=chip_smoke.KKT_TOL_F32):
    assert rep["worst_kkt"] <= tol
    assert rep["first_s"] > 0 and rep["steady_s"] > 0
    return rep


def test_phase_a_dense_path(dense):
    S, grid = dense
    rep = _checked(chip_smoke.phase_dense_path(S, grid, options=F32))
    assert rep["lambdas"] == len(grid)
    assert sum(rep["route_mix"].values()) > 0


def test_phase_b_fused_and_pallas_screen(dense):
    S, grid = dense
    opts = EngineOptions(dtype=jnp.float32, fused=True, cc_backend="pallas")
    _checked(chip_smoke.phase_dense_path(S, grid, options=opts))
    rep = _checked(chip_smoke.phase_screened_solves(S, grid[:1], opts))
    assert rep["partitions_exact"] == 1


def test_phase_c_from_data():
    X = microarray_like(40, 200, seed=0)
    rep = _checked(chip_smoke.phase_from_data(X, 0.7, options=F32))
    assert rep["p"] == 200 and rep["partition_exact"]


def test_phase_d_served(dense):
    S, grid = dense
    rep = _checked(
        chip_smoke.phase_served(
            [(S, grid[0]), (S, grid[-1])],
            [(microarray_like(40, 120, seed=1), 0.7)],
            options=F32,
        )
    )
    assert rep["requests"] == 3
    assert rep["worst_rel_diff"] <= chip_smoke.SERVE_REL


@pytest.mark.parametrize("penalty", ["group", "fused"])
def test_phase_e_joint(penalty):
    Ss = chip_smoke.joint_workload(K=3, blocks=4, p1=6)
    rep = _checked(
        chip_smoke.phase_joint(Ss, 0.5, 0.05, penalty, options=F32),
        chip_smoke.JOINT_KKT_TOL_F32,
    )
    assert rep["K"] == 3 and rep["penalty"] == penalty


def test_main_refuses_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok": true' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Copied without the repository, the script exits non-zero and prints
    no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
