"""Ahead-of-time compiles of the engine's Pallas kernels for a TPU v5e.

Each case lowers a kernel family's dispatching wrapper, float32, at the
shapes ``chip_smoke.py`` runs, for one chip of a described (not attached)
``v5e:2x2`` topology and compiles it with the TPU compiler: misaligned
blocks, VMEM overruns and primitives Mosaic cannot lower fail here, at no
chip time.  ``jax.default_backend()`` still reports the CPU in this
process, so the tests patch that one query to take the TPU branches.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and each test worker imports every
test file.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch, one_chip):
    """Route the wrappers down their TPU branches; compile silently (a
    persistent-cache entry written here could not be read back without a
    chip) and from fresh traces (a trace cached on the CPU branch must not
    be reused)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield one_chip
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(sharding, fn, *shapes):
    """Compile ``fn`` for the described chip; returns the compiled text."""
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32 = jnp.float32
I32 = jnp.int32


@pytest.mark.parametrize("B,b", [(64, 16), (256, 16)])
def test_tree_glasso_compiles(on_tpu, B, b):
    from repro.kernels.tree_glasso.ops import glasso_forest_stack

    _compile(on_tpu, glasso_forest_stack, ((B, b, b), F32), ((B,), F32))


@pytest.mark.parametrize("bin_", [8, 16, 32, 64])
def test_bucket_glasso_compiles(on_tpu, bin_):
    from repro.kernels.bucket_glasso import fused_bcd_stack

    mat = ((64, bin_, bin_), F32)
    vec = ((64,), F32)
    _compile(
        on_tpu,
        lambda b, l, s, w, t: fused_bcd_stack(b, l, s, w, t),
        mat, vec, vec, mat, mat,
    )


@pytest.mark.parametrize(
    "n,p,block_p", [(512, 4096, 512), (200, 20000, 512)]
)
def test_covgram_screen_compiles(on_tpu, n, p, block_p):
    from repro.kernels.covgram_screen.covgram_screen import covgram_screen_pallas

    N = -(-n // 512) * 512
    P = -(-p // block_p) * block_p
    fn = functools.partial(
        covgram_screen_pallas, n_true=n, p_true=p, block_n=512, block_p=block_p
    )
    _compile(
        on_tpu, fn, ((N, P), F32), ((P,), F32), ((64,), I32), ((64,), I32),
        ((1, 1), F32),
    )


@pytest.mark.parametrize("npairs,capacity", [(64, 256), (24, 4096), (64, 2**20)])
def test_compact_tiles_compiles(on_tpu, npairs, capacity):
    """The device compaction of a kernel batch (plain XLA, no kernel)."""
    from repro.kernels.covgram_screen.ops import compact_tiles

    sds = jax.ShapeDtypeStruct((npairs, 512, 512), F32, sharding=on_tpu)
    fn = functools.partial(compact_tiles, capacity=capacity)
    text = jax.jit(fn).lower(sds).compile().as_text()
    assert "scatter" not in text


@pytest.mark.parametrize("p", [2400, 2560, 40])
def test_threshold_cc_compiles(on_tpu, p):
    from repro.kernels.threshold_cc.ops import connected_components_kernel

    _compile(
        on_tpu, lambda S, lam: connected_components_kernel(S, lam),
        ((p, p), F32), ((), F32),
    )


def test_shard_prox_compiles(on_tpu):
    from repro.kernels.shard_prox.ops import fused_prox_residual

    shard = ((1024, 4096), F32)
    _compile(
        on_tpu, lambda x, u, z: fused_prox_residual(x, u, z, 0.1),
        shard, shard, shard,
    )


@pytest.mark.parametrize("penalty", ["group", "fused"])
@pytest.mark.parametrize("K,b", [(4, 256), (3, 600)])
def test_joint_prox_compiles(on_tpu, penalty, K, b):
    from repro.kernels.joint_prox.ops import joint_prox_step

    blk = ((K, b, b), F32)
    _compile(
        on_tpu,
        lambda th, u, z: joint_prox_step(th, u, z, 0.1, 0.05, penalty=penalty),
        blk, blk, blk,
    )


def test_float64_on_tpu_raises_value_error(monkeypatch):
    """An explicit float64 request on the TPU is refused up front, naming
    float32 — not an UNIMPLEMENTED from Mosaic or LU deep in a solve; the
    unset dtype resolves to float32 there and stays float64 elsewhere."""
    from repro.core import glasso
    from repro.engine import EngineOptions

    assert EngineOptions().resolved_dtype() == jnp.float64
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert EngineOptions().resolved_dtype() == jnp.float32
    with pytest.raises(ValueError, match="float32"):
        glasso(np.eye(4), 0.1, options=EngineOptions(dtype=jnp.float64))
