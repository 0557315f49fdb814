"""Validate the trip-count-weighted HLO analyzer against ground truth."""

import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze_hlo


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_dot_flops_exact():
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    compiled = _compile(lambda x, y: x @ y, a, b)
    out = analyze_hlo(compiled.as_text())
    expect = 2 * 128 * 256 * 64
    assert abs(out["flops"] - expect) / expect < 0.05, out["flops"]


def test_scan_weighting_matches_unrolled():
    """flops(scan of 8 matmuls) must equal flops(unrolled 8 matmuls)."""
    def body(x, w):
        return jnp.tanh(x @ w), None

    def scanned(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    def unrolled(x, ws):
        for i in range(8):
            x, _ = body(x, ws[i])
        return x

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    f_scan = analyze_hlo(_compile(scanned, x, ws).as_text())["flops"]
    f_unroll = analyze_hlo(_compile(unrolled, x, ws).as_text())["flops"]
    # XLA's own module-level count is ~8x off here; ours must agree within 10%
    assert abs(f_scan - f_unroll) / f_unroll < 0.10, (f_scan, f_unroll)
    expect_dots = 8 * 2 * 64 * 128 * 128
    assert f_scan > expect_dots * 0.95


def test_nested_scan_weighting():
    def inner(x, w):
        return x @ w, None

    def outer(x, ws):
        def obody(c, _):
            return jax.lax.scan(inner, c, ws)[0], None
        return jax.lax.scan(obody, x, None, length=4)[0]

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 64, 64), jnp.float32)
    f = analyze_hlo(_compile(outer, x, ws).as_text())["flops"]
    expect = 4 * 3 * 2 * 32 * 64 * 64
    assert f > expect * 0.9, (f, expect)
    assert f < expect * 1.5, (f, expect)


def test_matches_cost_analysis_on_scanfree_graph():
    def fn(x, w1, w2):
        return jax.nn.relu(x @ w1) @ w2

    x = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    w1 = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
    w2 = jax.ShapeDtypeStruct((1024, 256), jnp.float32)
    compiled = _compile(fn, x, w1, w2)
    ours = analyze_hlo(compiled.as_text())["flops"]
    xla = compiled.cost_analysis()["flops"]
    assert abs(ours - xla) / xla < 0.05, (ours, xla)


def test_collective_weighting_in_loop():
    """A psum inside a scan must count once per iteration."""
    from repro.core.jax_compat import make_mesh, shard_map

    mesh = make_mesh((1,), ("d",))

    @jax.jit
    def fn(x):
        def body(c, _):
            s = shard_map(
                lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                in_specs=jax.sharding.PartitionSpec(), out_specs=jax.sharding.PartitionSpec(),
            )(c)
            return s, None
        return jax.lax.scan(body, x, None, length=5)[0]

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = fn.lower(x).compile()
    out = analyze_hlo(compiled.as_text())
    coll = out["collective"]
    if coll["total"] > 0:  # single-device psum may fold away entirely
        assert coll.get("all-reduce_count", 0) >= 5
