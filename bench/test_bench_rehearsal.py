"""CPU rehearsal of the harness: every cell end to end at tiny size, the
refusal without a chip, and a cell added as files alone."""

import copy
import json
import os
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

TINY_NETWORKS = {
    "01_a": {"parcels": 12, "system": "A"},
    "02_b": {"parcels": 8, "system": "A"},
    "03_c": {"parcels": 10, "system": "B"},
}


def tiny(name: str, root: Path = ROOT) -> harness.Cell:
    """The cell as ``root``'s BENCHMARK.json defines it, at a size the CPU
    can run."""
    cell = harness.find_cell(name, root=root)
    cfg = copy.deepcopy(cell.config)
    wl = dict(cell.workload)
    if wl["traffic"] == "path":
        cfg["n_samples"], cfg["n_genes"] = 60, 700
        cfg["assumed"]["module_sizes"] = [40, 20, 12, 6, 4]
        wl["lambdas"] = [0.6, 0.45] if "sparse" in name else [0.45, 0.3]
    else:
        cfg["n_frames"], cfg["n_parcels"] = 200, 30
        cfg["assumed"]["networks"] = TINY_NETWORKS
        wl.update(lambdas=[0.6, 0.4, 0.2], rate=20.0, warmup_seconds=0.3, settle_seconds=60,
                  trace_seconds=0.2)
    cell.config, cell.workload = cfg, wl
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


@pytest.mark.parametrize("name", CELLS + CANDIDATES)
def test_cell_end_to_end_on_cpu(name, monkeypatch, bench_root):
    cell = tiny(name, bench_root)
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
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"], tmp_path)
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


def test_unknown_cell_is_refused():
    with pytest.raises(harness.BenchError):
        harness.find_cell("no.such_cell")
