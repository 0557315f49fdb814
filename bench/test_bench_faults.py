"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a run
(set-up, window, check) at tiny size on the CPU, with one fault planted in
the program.  A traffic kind's faults are a file of their own,
``bench/faults/<traffic>.py``: ``FAULTS`` maps a fault's name to
``plant(monkeypatch, cell)``, which plants it and returns the name of the
check the run must then fail.  Every kind plants at least a solver step
that returns its state unchanged and an answer altered where it is
produced; a kind adds what else its cells can have (the served cell: half
of a batch left out).  The cells run on one chip, so no exchange between
chips can be left out."""

import json
import time
from pathlib import Path

import jax
import pytest

from bench import harness
from bench.test_bench_rehearsal import CANDIDATES, CELLS, ROOT, tiny

#: the faults every traffic kind's file plants
REQUIRED = ("solver_state_unchanged", "answer_altered")
TRAFFIC = {
    w["name"]: w["traffic"]
    for f in (ROOT / "BENCHMARK.json", ROOT / "bench" / "candidates.json")
    for w in json.loads(f.read_text())["workloads"]
}


def faults_of(traffic: str, root: Path = ROOT) -> dict:
    """``FAULTS`` of ``root``'s ``bench/faults/<traffic>.py``, which has to
    plant every fault of ``REQUIRED``."""
    path = Path(root) / "bench" / "faults" / f"{traffic}.py"
    faults = getattr(harness.load_module(path, f"bench_faults_{traffic}"), "FAULTS", {})
    missing = [f for f in REQUIRED if f not in faults]
    if missing:
        raise harness.BenchError(f"{path} plants no {', '.join(missing)}")
    return faults


def _pairs() -> list[tuple[str, str]]:
    """(cell, fault) for every cell and each fault its kind's file plants;
    a kind without a sound faults file adds none here and fails
    ``test_every_cell_has_its_faults_file``."""
    out = []
    for name in CELLS + CANDIDATES:
        try:
            out += [(name, fault) for fault in faults_of(TRAFFIC[name])]
        except harness.BenchError:
            pass
    return out


def run(cell):
    return harness.run_cell(
        cell, seed=2**31 + 3, seconds=0.5, trace=False, devices=jax.devices(),
        t_start=time.perf_counter(),
    )


def run_with_fault(cell: harness.Cell, fault: str, monkeypatch) -> None:
    """Plant ``fault`` from the cell's faults file, run the cell, and see
    the check the fault names fail."""
    check = faults_of(cell.workload["traffic"], cell.root)[fault](monkeypatch, cell)
    res = run(cell)
    assert not res["correct"]
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]
    if check == "unresolved":  # requests that never resolved count as failed
        assert res["failed"] > 0


@pytest.fixture
def fresh_compiles():
    """Planted faults must be traced anew, and must not leak compiled code
    into later tests."""
    from repro.engine import executor

    def clear():
        executor._COMPILED.clear()
        jax.clear_caches()

    clear()
    yield
    clear()


@pytest.mark.parametrize("name, fault", _pairs())
def test_planted_fault_is_not_correct(name, fault, monkeypatch, fresh_compiles, bench_root):
    run_with_fault(tiny(name, bench_root), fault, monkeypatch)


@pytest.mark.parametrize("name", CELLS + CANDIDATES)
def test_every_cell_has_its_faults_file(name):
    faults_of(TRAFFIC[name])  # a missing or short file raises, naming it


@pytest.mark.parametrize("text, named", [
    (None, "missing file"),
    ("FAULTS = {'solver_state_unchanged': None}", "plants no answer_altered"),
    ("FAULTS = {'answer_altered': None, 'other': None}", "plants no solver_state_unchanged"),
    ("", "plants no solver_state_unchanged, answer_altered"),
], ids=["no_file", "no_answer_fault", "no_solver_fault", "no_faults"])
def test_faults_file_missing_or_short_is_refused(text, named, tmp_path):
    if text is not None:
        (tmp_path / "bench" / "faults").mkdir(parents=True)
        (tmp_path / "bench" / "faults" / "some_kind.py").write_text(text)
    with pytest.raises(harness.BenchError, match=named):
        faults_of("some_kind", tmp_path)
