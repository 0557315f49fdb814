"""The trace reduction and the roofline arithmetic, on a synthetic trace
laid out as the TPU profiler lays out its planes."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import roofline, trace_reduce

BENCH = Path(__file__).resolve().parent


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v) for k, v in lines.items()])


def profile(*planes):
    return NS(planes=list(planes))


T0 = 2.0  # perf_counter seconds at which the window opened
HOST = plane("/host:CPU", python=[ev("bench.window", 1_000, 10_000), ev("bench.path", 1_000, 9_000)])


def tpu(k, modules):
    return NS(name=f"/device:TPU:{k}", lines=[NS(name="XLA Ops", events=[]),
                                              NS(name="XLA Modules", events=modules)])


def test_busy_idle_modules_and_gaps():
    mods = [ev("jit_run(1)", 500, 1_000), ev("jit_run(1)", 1_500, 1_000),
            ev("jit_covgram_screen_pallas(7)", 2_000, 2_000),
            ev("jit_solve(3)", 8_000, 1_000), ev("jit_late(4)", 10_500, 2_000)]
    # spans on perf_counter: the window opens at T0 <-> 1000 ns in the trace
    spans = [("engine.plan", T0 + 3e-6, T0 + 7e-6), ("engine.path", T0, T0 + 9e-6),
             ("engine.solve", T0 + 6.5e-6, T0 + 6.9e-6)]
    out = trace_reduce.reduce_profile(profile(HOST, tpu(0, mods)), t0=T0, t1=T0 + 1e-5,
                                      spans=spans)
    # busy: [1000,4000] and [8000,9000] and [10500,11000] clipped -> 3000+1000+500
    assert out["window_s"] == pytest.approx(10e-6)
    assert out["busy_s"] == pytest.approx(4.5e-6)
    # whole runs inside the window count; the first starts before it
    assert out["modules"] == {"jit_run": pytest.approx(1e-6),
                              "jit_covgram_screen_pallas": pytest.approx(2e-6),
                              "jit_solve": pytest.approx(1e-6)}
    gaps = out["breakdown"]["idle_gaps"]
    # the longest gap [4000, 8000] has its middle at 6000 ns = T0 + 5e-6: in
    # engine.plan (inside engine.path); the solve span ends before it
    assert gaps[0] == ["engine.plan", pytest.approx(4e-6)]
    assert gaps[1] == ["engine.path", pytest.approx(1.5e-6)]
    assert trace_reduce._label(1.0, []) == "no span open"
    assert out["breakdown"]["device_ops"][0] == ["jit_covgram_screen_pallas", pytest.approx(2e-6)]
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["window_s"] - out["busy_s"])


def test_busy_is_the_mean_over_chips():
    a = tpu(0, [ev("x", 1_000, 5_000)])
    b = tpu(1, [ev("x", 1_000, 1_000)])
    out = trace_reduce.reduce_profile(profile(HOST, b, a), t0=T0, t1=T0 + 1e-5, chips=2)
    assert out["busy_s"] == pytest.approx(3e-6)
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.reduce_profile(profile(HOST, a), t0=T0, t1=T0 + 1e-5, chips=2)


def test_trace_without_window_or_ops_is_refused():
    dev = tpu(0, [ev("x", 1_000, 5_000)])
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.reduce_profile(profile(dev), t0=T0, t1=T0 + 1)
    bare = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[])])
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.reduce_profile(profile(HOST, bare), t0=T0, t1=T0 + 1)


def test_union_and_clip():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert trace_reduce.clip([(0, 2), (3, 9), (10, 11)], 1, 5) == [(1, 2), (3, 5)]


def test_covgram_screen_roofline():
    flops, nbytes = roofline.covgram_screen_work(pairs=3, n_pad=512, tile=512)
    assert flops == 3 * 2 * 512 * 512 * 512
    assert nbytes == 3 * (2 * 512 * 512 * 4 + 512 * 512 * 4)
    pk = roofline.peaks("TPU v5 lite")
    t_min = nbytes / pk["hbm_bytes_per_s"]
    share, bound = roofline.share(flops, nbytes, 4 * t_min, "TPU v5 lite")
    assert bound == "bandwidth" and share == pytest.approx(25.0)
    assert roofline.share(1e15, 1.0, 10.0, "TPU v5 lite")[1] == "compute"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("cpu")


def test_trace_recorded_on_the_chip():
    """A two-lambda path at p=3000 traced on one v5e with the benchmark's
    trace flags (bench/testdata; about 3.4 MB unpacked)."""
    import gzip

    import jax

    data = gzip.decompress((BENCH / "testdata" / "path_small.xplane.pb.gz").read_bytes())
    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    out = trace_reduce.reduce_profile(pd, t0=100.0, t1=101.0)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["window_s"] == pytest.approx(1.687149720, rel=1e-6)
    assert out["modules"]["jit_covgram_screen_pallas"] > 0
    names = [n for n, _ in out["breakdown"]["device_ops"]]
    assert names[0] == "jit_run" and len(names) <= trace_reduce.TOP
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps and all(s > 0 for _, s in gaps)
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["window_s"] - out["busy_s"])
