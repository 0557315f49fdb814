"""CPU rehearsal of the harness: every cell end to end at tiny size, the
refusal without a chip, and a cell added as files alone.

A cell's CPU size is a file of its own, ``bench/tiny/<cell>.json``:
``{"config": {...}, "workload": {...}}``, each key a dotted path into the
cell's configuration or workload whose value replaces what stands there
(nothing is merged)."""

import copy
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from bench import harness, roofline, trace_reduce

ROOT = Path(__file__).resolve().parents[1]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
CANDIDATES = [
    w["name"] for w in json.loads((ROOT / "bench" / "candidates.json").read_text())["workloads"]
]


def _replace(tree: dict, overrides: dict, where: str) -> dict:
    """``tree`` with the value at each dotted path of ``overrides`` replaced;
    a path that names nothing in ``tree`` is an error, not a new key."""
    out = copy.deepcopy(tree)
    for path, value in overrides.items():
        *parents, last = path.split(".")
        node = out
        for key in parents:
            node = node.get(key) if isinstance(node, dict) else None
        if not isinstance(node, dict) or last not in node:
            raise harness.BenchError(f"{where}: {path!r} names nothing there")
        node[last] = value
    return out


def tiny(name: str, root: Path = ROOT) -> harness.Cell:
    """The cell as ``root``'s BENCHMARK.json defines it, at the size its
    ``bench/tiny/<cell>.json`` gives for the CPU."""
    cell = harness.find_cell(name, root=root)
    path = Path(root) / "bench" / "tiny" / f"{name}.json"
    preset = harness.load_json(path)
    unknown = set(preset) - {"config", "workload"}
    if unknown:
        raise harness.BenchError(f"{path}: keys {sorted(unknown)}, not config or workload")
    cell.config = _replace(cell.config, preset.get("config", {}), f"{path}, config")
    cell.workload = _replace(cell.workload, preset.get("workload", {}), f"{path}, workload")
    return cell


class FakeProfiler:
    """The CPU writes no device plane (and its while loops fill gigabytes
    of host events), so the rehearsal stands in for the profiler."""

    def __init__(self):
        self.t0 = self.t1 = None

    def start(self):
        self.t0 = time.perf_counter()

    def stop(self):
        if self.t1 is None:
            self.t1 = time.perf_counter()

    def xplane(self):
        return "trace.xplane.pb"

    def close(self):
        pass


def fake_reduce(path, *, t0, t1, spans=(), chips=1):
    """Stands in for the reduction of a chip's trace."""
    assert path == "trace.xplane.pb" and t1 > t0 and spans
    return {
        "busy_s": 0.25 * (t1 - t0),
        "window_s": t1 - t0,
        "modules": {"jit_covgram_screen_pallas": 0.5 * (t1 - t0)},
        "idle_by_span": {},
        "breakdown": {"device_ops": [["op", 0.1]], "idle_gaps": [["engine.plan", 0.2]]},
    }


def rehearse(cell: harness.Cell, monkeypatch) -> None:
    """One untraced and one traced run of ``cell`` on the CPU, the trace's
    profiler and reduction stood in for: both correct, each with the
    metrics the cell reports."""
    res = harness.run_cell(
        cell, seed=2**31 + 11, seconds=0.5, trace=False,
        devices=jax.devices(), t_start=time.perf_counter(),
    )
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(res)[-1] == "checks"
    assert all(v["value"] > 0 for v in res["metrics"].values())

    monkeypatch.setattr(harness, "Profiler", FakeProfiler)
    monkeypatch.setattr(trace_reduce, "reduce", fake_reduce)
    monkeypatch.setattr(roofline, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
    })
    res = harness.run_cell(
        cell, seed=5, seconds=0.5, trace=True,
        devices=jax.devices(), t_start=time.perf_counter(),
    )
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer}
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert res["breakdown"]["idle_gaps"]


@pytest.mark.parametrize("name", CELLS + CANDIDATES)
def test_cell_end_to_end_on_cpu(name, monkeypatch, bench_root):
    rehearse(tiny(name, bench_root), monkeypatch)


@pytest.mark.parametrize("name", CELLS + CANDIDATES)
def test_every_cell_has_its_tiny_file(name, bench_root):
    tiny(name, bench_root)  # a missing file raises, naming it


def _checkout(tmp_path: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


@pytest.mark.parametrize("preset, named", [
    ({"config": {"n_sample": 60}}, "'n_sample'"),
    ({"config": {"assumed.module_size": [4]}}, "'assumed.module_size'"),
    ({"config": {"n_samples.rows": 60}}, "'n_samples.rows'"),
    ({"workload": {"lambda": [0.5]}}, "'lambda'"),
    ({"configs": {"n_samples": 60}}, "'configs'"),
], ids=["config_key", "nested_key", "below_a_leaf", "workload_key", "top_level_key"])
def test_tiny_key_that_names_nothing_is_refused(preset, named, tmp_path):
    root = _checkout(tmp_path)
    (root / "bench" / "tiny" / f"{CELLS[0]}.json").write_text(json.dumps(preset))
    with pytest.raises(harness.BenchError, match=named):
        tiny(CELLS[0], root)


def test_cell_without_tiny_file_is_refused(tmp_path):
    root = _checkout(tmp_path)
    path = root / "bench" / "tiny" / f"{CELLS[0]}.json"
    path.unlink()
    with pytest.raises(harness.BenchError, match=re.escape(str(path))):
        tiny(CELLS[0], root)


def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, capture_output=True,
        text=True, timeout=300, env={**os.environ, **env},
    )


def test_run_refuses_without_a_chip():
    out = _run(
        ["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        ROOT, JAX_PLATFORMS="cpu",
    )
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "accelerator" in out.stderr


def test_run_refuses_without_the_program(tmp_path):
    out = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
               _checkout(tmp_path))
    assert out.returncode != 0
    assert "{" not in out.stdout


TOY_TRAFFIC = '''
from bench.harness import Window

class Driver:
    def __init__(self, cell, seed, seconds, control=None):
        self.n = int(cell.workload["count"]) + int(cell.config["offset"])
    def warmup(self):
        pass
    def run(self, seconds, profiler=None):
        return Window(end_to_end={"toy_s": 1.5}, attempted=self.n, failed=0,
                      ctx={"units": self.n})
    def close(self):
        pass
    def check(self, window):
        return {"wrong": 0}
'''

TOY_METRIC = '''
def read(ctx):
    return 2.0 * ctx["units"]
'''


def test_cell_added_as_files_is_found(tmp_path):
    """A new configuration, traffic kind, cell and per-layer metric need
    new files and new entries in BENCHMARK.json, and no edit of a file the
    benchmark has."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "toy", "source": "a test", "file": "bench/configs/toy.json",
        "reduced": [], "why": "a test"})
    bench["workloads"].append({
        "name": "toy.cell", "config": "toy", "traffic": "toy_kind", "chips": 1, "why": "a test"})
    bench["end_to_end"].append({
        "name": "toy_s", "unit": "s", "better": "lower", "bound": 0.1,
        "source": "host_clock", "workloads": ["toy.cell"]})
    bench["per_layer"].append({
        "name": "toy_count.layer", "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "serve", "moves": "toy_s", "workloads": ["toy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench/configs/toy.json").write_text('{"offset": 1}')
    (tmp_path / "bench/workloads/toy.cell.json").write_text(
        '{"config": "toy", "traffic": "toy_kind", "count": 3, "limits": {"wrong": 0}}')
    (tmp_path / "bench/traffic/toy_kind.py").write_text(TOY_TRAFFIC)
    (tmp_path / "bench/metrics/toy_count.layer.py").write_text(TOY_METRIC)

    cell = harness.find_cell("toy.cell", root=tmp_path)
    assert sorted(m["name"] for m in cell.end_to_end) == ["setup_s", "toy_s"]
    assert [m["name"] for m in cell.per_layer] == ["toy_count.layer"]
    res = harness.run_cell(cell, seed=1, seconds=1, trace=False, devices=jax.devices(),
                           t_start=time.perf_counter())
    assert res["correct"] and res["attempted"] == 4
    assert res["metrics"]["toy_s"]["value"] == 1.5 and "setup_s" in res["metrics"]
    assert cell.metric_reader("toy_count.layer").read({"units": 4}) == 8.0
    # every cell already in the benchmark is still found as before
    for name in CELLS:
        assert harness.find_cell(name, root=tmp_path).name == name


TOY_SOLVE = '''
"""Traffic ``toy_solve``: square roots by Newton's method in NumPy, work
that goes through no part of the program."""

import time

import numpy as np

from bench.harness import Window


def step(x, a):
    return 0.5 * (x + a / x)


def answer(x):
    return x


class Driver:
    def __init__(self, cell, seed, seconds, control=None):
        rng = np.random.default_rng(seed)
        self.a = rng.uniform(1.0, 4.0, int(cell.config["size"]))
        self.sweeps = int(cell.workload["sweeps"])
    def warmup(self):
        pass
    def run(self, seconds, profiler=None):
        if profiler is not None:
            profiler.start()
        t0 = time.perf_counter()
        x = np.ones_like(self.a)
        for _ in range(self.sweeps):
            x = step(x, self.a)
        self.out = answer(x)
        t1 = time.perf_counter()
        if profiler is not None:
            profiler.stop()
        return Window(end_to_end={"lambda_s": (t1 - t0) / self.a.size}, attempted=self.a.size,
                      failed=0, ctx={"units": self.a.size, "sweeps": self.sweeps,
                                     "spans": [("toy.solve", t0, t1)]})
    def close(self):
        pass
    def check(self, window):
        return {"wrong": int(np.abs(self.out - np.sqrt(self.a)).max() > 1e-12)}
'''

TOY_SOLVE_FAULTS = '''
def solver_state_unchanged(monkeypatch, cell):
    monkeypatch.setattr(cell.traffic, "step", lambda x, a: x)
    return "wrong"


def answer_altered(monkeypatch, cell):
    monkeypatch.setattr(cell.traffic, "answer", lambda x: x * (1.0 + 1e-3))
    return "wrong"


FAULTS = {"solver_state_unchanged": solver_state_unchanged, "answer_altered": answer_altered}
'''


def test_cell_of_a_new_kind_is_rehearsed_and_faulted(tmp_path, monkeypatch):
    """A cell whose work bypasses the BCD and the engine's answer, added as
    new files, entries appended to BENCHMARK.json and its name appended to
    ``lambda_s``'s workloads: the generic rehearsal and the generic fault
    test take it as they find it."""
    from bench.test_bench_faults import faults_of, run_with_fault

    root = _checkout(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "toy", "source": "a test", "file": "bench/configs/toy.json",
        "reduced": [], "why": "a test"})
    bench["workloads"].append({
        "name": "toy.solve", "config": "toy", "traffic": "toy_solve", "chips": 1, "why": "a test"})
    next(m for m in bench["end_to_end"] if m["name"] == "lambda_s")["workloads"].append("toy.solve")
    bench["per_layer"].append({
        "name": "toy_sweeps.solve", "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "execute", "moves": "lambda_s", "workloads": ["toy.solve"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    files = {
        "configs/toy.json": '{"size": 100000}',
        "workloads/toy.solve.json":
            '{"config": "toy", "traffic": "toy_solve", "sweeps": 40, "limits": {"wrong": 0}}',
        "traffic/toy_solve.py": TOY_SOLVE,
        "metrics/toy_sweeps.solve.py": "def read(ctx):\n    return float(ctx['sweeps'])\n",
        "tiny/toy.solve.json": '{"config": {"size": 50}, "workload": {"sweeps": 30}}',
        "faults/toy_solve.py": TOY_SOLVE_FAULTS,
    }
    for rel, text in files.items():
        assert not (root / "bench" / rel).exists()
        (root / "bench" / rel).write_text(text)

    cell = tiny("toy.solve", root)
    assert cell.config["size"] == 50 and cell.workload["sweeps"] == 30
    assert sorted(m["name"] for m in cell.end_to_end) == ["lambda_s", "setup_s"]
    with monkeypatch.context() as mp:
        rehearse(cell, mp)
    faults = faults_of("toy_solve", root)
    assert sorted(faults) == ["answer_altered", "solver_state_unchanged"]
    for fault in faults:
        with monkeypatch.context() as mp:
            run_with_fault(tiny("toy.solve", root), fault, mp)

    # the cells already in the benchmark report what they reported before
    for name in CELLS:
        before, after = harness.find_cell(name), harness.find_cell(name, root=root)
        for kind in ("end_to_end", "per_layer"):
            assert [m["name"] for m in getattr(after, kind)] == [
                m["name"] for m in getattr(before, kind)]


def test_unknown_cell_is_refused():
    with pytest.raises(harness.BenchError):
        harness.find_cell("no.such_cell")
