"""The benchmark's CPU tests: float64 enabled as the program's own tests
have it, and the program importable from ``src``."""

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)


import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def bench_root(tmp_path_factory):
    """A checkout whose BENCHMARK.json also holds the cells of
    ``bench/candidates.json``, so the tests rehearse those too."""
    root = Path(__file__).resolve().parents[1]
    out = tmp_path_factory.mktemp("checkout")
    shutil.copytree(root / "bench", out / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cand = json.loads((root / "bench" / "candidates.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] = bench[key] + cand[key]
    (out / "BENCHMARK.json").write_text(json.dumps(bench))
    return out
