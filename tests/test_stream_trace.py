"""The streamed screen traced from inside: the span tree of a path from
data, the screen's transfer and tile counters on both covgram_screen
backends, the spans as ``jax.profiler`` host events, and the benchmark's
readers of those spans and counters."""

import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core import glasso_path
from repro.core.instrument import counts
from repro.covariance import microarray_like
from repro.engine.options import EngineOptions
from repro.kernels.covgram_screen import pad_for_screen
from repro.kernels.covgram_screen.ops import _capacity
from repro.stream import StreamConfig, stream_screen
from repro.stream.tiler import column_moments

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import load_module  # noqa: E402

CFG = {"tile": 32, "chunk": 16, "pair_batch": 3}
SCREEN_CHILDREN = {
    "screen.moments", "screen.upload", "screen.kernel", "screen.fetch",
    "screen.compact", "screen.sweep", "screen.materialize",
}


def _lams(X, ranks=(20, 60)):
    """Lambdas halfway between consecutive distinct off-diagonal |S_ij|."""
    S = np.cov(X, rowvar=False, bias=True)
    v = np.sort(np.abs(S[np.triu_indices(S.shape[0], 1)]))[::-1]
    return [float(0.5 * (v[k] + v[k + 1])) for k in ranks]


@pytest.fixture(scope="module")
def data():
    X = microarray_like(40, 90, n_modules=6, seed=1)
    return X, _lams(X)


@pytest.fixture(scope="module")
def path_trace(data):
    X, lams = data
    res = glasso_path(X=X, lambdas=lams, from_data=True, stream=CFG)
    assert all(r.trace is res[0].trace for r in res)
    return res[0].trace


def _one(tr, name):
    found = [s for s in tr.spans if s.name == name]
    assert len(found) == 1, (name, [s.name for s in tr.spans])
    return found[0]


def test_path_from_data_screen_and_plan_are_siblings(path_trace):
    tr = path_trace
    assert tr.name == "engine.path"
    screen, plan = _one(tr, "engine.screen"), _one(tr, "engine.plan")
    assert screen.parent_id == tr.root_id and plan.parent_id == tr.root_id
    assert screen.attrs == {"backend": "stream"}
    assert screen.t1 <= plan.t0
    # the planner alone: nothing of the screen runs under engine.plan
    assert not {s.name for s in tr.children(plan.span_id)} & SCREEN_CHILDREN


def test_screen_spans_are_children_of_engine_screen(path_trace):
    tr = path_trace
    screen = _one(tr, "engine.screen")
    children = tr.children(screen.span_id)
    assert {s.name for s in children} == SCREEN_CHILDREN
    assert all(s.name.startswith("screen.") for s in children)
    by_id = {s.span_id: s for s in tr.spans}
    for s in tr.spans:
        if s.name.startswith("screen."):
            assert s.parent_id == screen.span_id
        if s.parent_id is not None:
            assert s.seconds <= by_id[s.parent_id].seconds
    assert sum(s.seconds for s in children) <= screen.seconds
    # one upload / kernel / fetch per tile batch (the host oracle on the
    # CPU), and two compact spans: the compaction, then the accumulator
    per_batch = [
        sum(s.name == n for s in children)
        for n in ("screen.upload", "screen.kernel", "screen.fetch")
    ]
    assert len(set(per_batch)) == 1 and per_batch[0] >= 2
    assert sum(s.name == "screen.compact" for s in children) == 2 * per_batch[0]


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_screen_counters_match_shapes_and_counts(backend):
    X = microarray_like(40, 70, n_modules=5, seed=2)
    lam = _lams(X, ranks=(30,))[0]
    cfg = StreamConfig(**CFG, backend=backend)
    before = counts("stream.")
    sc = stream_screen(X, [lam], config=cfg, keep_tile_stats=True)
    after = counts("stream.")
    delta = {k: v - before.get(k, 0) for k, v in after.items()}

    computed = [rec for rec in sc.tiles.values() if not rec.skipped]
    assert len(computed) == sc.tiles_total - sc.tiles_skipped > 0
    with_edges = sum(rec.n_edges > 0 for rec in computed)
    assert 0 < with_edges < len(computed)
    assert delta["stream.tiles_with_edges"] == with_edges

    # the same tiles from the dense covariance: off-diagonal |S_ij| > lam
    S = np.cov(X, rowvar=False, bias=True)
    edge = np.abs(S) > lam
    np.fill_diagonal(edge, False)
    t = cfg.tile
    dense = sum(
        edge[i * t:(i + 1) * t, j * t:(j + 1) * t].any()
        for i, j in sc.tiles if not sc.tiles[i, j].skipped
    )
    assert dense == with_edges

    if backend == "ref":
        assert delta["stream.upload_bytes"] == 0
        assert delta["stream.fetch_bytes"] == 0
        assert delta.get("stream.compact_batches", 0) == 0
        assert delta.get("stream.compact_slots", 0) == 0
        return
    x_pad, mu_pad = pad_for_screen(
        X, column_moments(X, chunk=cfg.chunk).mu, block_n=cfg.chunk, block_p=t
    )
    pairs = len(computed)
    batches = -(-pairs // cfg.pair_batch)
    upload = batches * (x_pad.size * 4 + mu_pad.size * 4 + 4) + pairs * 2 * 4
    # the kernel's entries above lam per pair, both orientations on the
    # diagonal; each batch with any is compacted into _capacity(sum) slots
    per_pair = [
        int(edge[i * t:(i + 1) * t, j * t:(j + 1) * t].sum())
        for (i, j), rec in sc.tiles.items() if not rec.skipped
    ]
    nnz = [sum(per_pair[b:b + cfg.pair_batch]) for b in range(0, pairs, cfg.pair_batch)]
    slots = [_capacity(k) for k in nnz if k]
    # counts and stats of every pair, then (3 + 1) * 4 B per slot and the
    # 4-B length of each compacted batch
    fetch = pairs * (4 + 2 * 4) + sum(16 * c + 4 for c in slots)
    assert slots and len(slots) <= batches
    assert delta["stream.upload_bytes"] == upload
    assert delta["stream.fetch_bytes"] == fetch
    assert delta["stream.compact_batches"] == len(slots)
    assert delta["stream.compact_slots"] == sum(slots)


def test_every_span_is_a_profiler_host_event(data, tmp_path):
    X, lams = data
    glasso_path(X=X, lambdas=lams, from_data=True, stream=CFG)  # compile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = glasso_path(X=X, lambdas=lams, from_data=True, stream=CFG)
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(xplane))
    host = {
        ev.name
        for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
    }
    names = {s.name for s in res[0].trace.spans}
    assert {"engine.path", "engine.screen", "engine.plan"} | SCREEN_CHILDREN <= names
    assert names <= host


class _NullCtx:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_trace_false_records_nothing(data, monkeypatch):
    from repro.obs import trace as obs_trace

    made = []
    monkeypatch.setattr(
        obs_trace, "TraceAnnotation", lambda name: made.append(name) or _NullCtx()
    )
    X, lams = data
    before = counts("stream.")
    res = glasso_path(
        X=X, lambdas=lams, from_data=True, stream=CFG,
        options=EngineOptions(trace=False),
    )
    assert all(r.trace is None for r in res)
    assert made == []
    # the counters are process-wide and still count
    assert counts("stream.")["stream.tiles_total"] > before.get("stream.tiles_total", 0)


# ---------------------------------------------------------------------------
# the benchmark's readers of these spans and counters, on a hand-built ctx
# ---------------------------------------------------------------------------


def _span(name, sid, parent, t0, t1):
    return SimpleNamespace(name=name, span_id=sid, parent_id=parent, t0=t0, t1=t1)


TRACE = SimpleNamespace(spans=[
    _span("engine.path", 0, None, 0.0, 10.0),
    _span("engine.screen", 1, 0, 0.0, 6.0),
    _span("screen.moments", 2, 1, 0.0, 0.5),
    _span("screen.upload", 3, 1, 0.5, 0.75),
    _span("screen.kernel", 4, 1, 0.75, 1.0),
    _span("screen.fetch", 5, 1, 1.0, 1.5),
    _span("screen.compact", 6, 1, 1.5, 3.5),
    _span("screen.upload", 7, 1, 3.5, 3.75),
    _span("screen.fetch", 8, 1, 3.75, 4.25),
    _span("screen.sweep", 9, 1, 4.25, 5.0),
    _span("screen.materialize", 10, 1, 5.0, 6.0),
    _span("engine.plan", 11, 0, 6.0, 7.0),
    _span("engine.solve", 12, 0, 7.0, 10.0),
])
PARENT_TRACE = SimpleNamespace(spans=[
    _span("engine.path", 0, None, 0.0, 10.0),
    _span("engine.plan", 1, 0, 0.0, 7.0),
])


def _ctx(trace=TRACE, counters=None):
    return {
        "units": 4,
        "results": [object()] * 4,
        "traces": [trace],
        "counters": {
            "stream.tiles_total": 30, "stream.tiles_skipped": 10,
            "stream.tiles_with_edges": 5, "stream.upload_bytes": 1000,
            "stream.fetch_bytes": 3000, "engine.dispatch.count": 9,
        } if counters is None else counters,
        "trace": {
            "window_s": 20.0, "busy_s": 5.0, "modules": {},
            "idle_by_span": {
                "engine.plan": 1.0, "engine.screen": 0.5, "screen.compact": 4.0,
                "screen.fetch": 1.5, "engine.solve": 2.0,
            },
        },
    }


def _read(name, ctx):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py", "t_" + name.replace(".", "_")).read(ctx)


@pytest.mark.parametrize("name, expected", [
    ("screen_upload_s.offline", 0.5 / 4),
    ("screen_fetch_s.offline", 1.0 / 4),
    ("screen_compact_s.offline", 2.0 / 4),
    ("screen_sweep_s.offline", 0.75 / 4),
    ("materialize_s.offline", 1.0 / 4),
    ("planner_s.offline", 1.0 / 4),
    ("screen_transfer_bytes.offline", 4000 / 4),
    ("useful_tiles.offline", 100.0 * 5 / 20),
    ("screen_idle.offline", 100.0 * 6.0 / 20.0),
])
def test_metric_readers(name, expected):
    assert _read(name, _ctx()) == pytest.approx(expected)
    assert _read(name, dict(_ctx(), results=[], units=0)) is None
    # a program that does not time the screen apart leaves each one out
    assert _read(name, _ctx(PARENT_TRACE, counters={"stream.tiles_total": 30})) is None


@pytest.mark.parametrize("name", [
    "screen_upload_s.offline", "screen_fetch_s.offline", "screen_compact_s.offline",
    "screen_sweep_s.offline", "materialize_s.offline", "planner_s.offline",
    "screen_idle.offline",
])
def test_span_readers_read_absent_spans_as_zero(name):
    trace = SimpleNamespace(spans=[
        _span("engine.path", 0, None, 0.0, 2.0), _span("engine.screen", 1, 0, 0.0, 1.0),
    ])
    ctx = _ctx(trace)
    ctx["trace"]["idle_by_span"] = {}
    assert _read(name, ctx) == 0.0


def test_useful_tiles_reads_zero_without_computed_pairs():
    ctx = _ctx(counters={"stream.tiles_total": 4, "stream.tiles_skipped": 4,
                         "stream.tiles_with_edges": 0})
    assert _read("useful_tiles.offline", ctx) == 0.0
