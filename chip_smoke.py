#!/usr/bin/env python3
"""Bring-up smoke test: the screened glasso engine's main path on a TPU.

Runs the engine through the entry points a user calls, at the sizes of the
repository's own workloads, and checks every answer against references that
never touch the device:

* the partition against ``components_from_covariance_host`` on the same S at
  the same lambda (float64 numpy union-find);
* every returned block's KKT residual, recomputed in float64 numpy on the
  host (``kkt_residual_host``), against the phase's float32 bound.

One chip (the default) runs phases A-E in this one process:

    A  dense path   glasso_path on structured_synthetic(K=150, p1=16)
                    (p=2400) over a 20-lambda grid, default options
    B  fused        the same S and grid with EngineOptions(fused=True,
                    cc_backend="pallas"), plus single solves screened by the
                    Pallas threshold_cc kernel
    C  from data    glasso(X=..., from_data=True) on microarray_like(n=200,
                    p=20000): the covgram_screen kernel screens straight
                    from X
    D  served       a GlassoServer answers 8 DenseSpec (p=2400) and 2
                    DataSpec requests; each equals the offline result
    E  joint        joint_glasso, K=3 classes at p=600, group and fused
                    penalties (the joint_prox kernel)

``--four-chips`` instead runs only what exists across chips: one b=4096
giant block forced onto the sharded oversize route over a 4-chip mesh (all
four chips' ``peak_bytes_in_use`` must be nonzero and within 2x), compared
with the same block solved on a one-chip mesh, and the ``shard_map`` screen
compared with the host partition.  Its giant-block solves are timed once.

Before each phase the kernel wrapper is lowered at that phase's shape and
the HLO must hold ``tpu_custom_call``: the Pallas path ran, not a
reference.  Each phase prints its first-call seconds (compile included) and
its steady seconds (second call), the route mix, the ``router.fallback.*``
counts and the device's ``peak_bytes_in_use``.  These are bring-up
observations, not benchmark numbers.

The script exits non-zero, printing no result, when JAX finds no TPU; it
never falls back to the CPU.  Its last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

    python chip_smoke.py [--four-chips]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# Float32 bounds.  Each sits above what a v5e measured on the sound code
# and below what one default-precision (single bf16 pass)
# matmul leaves: 1e-3 absolute on a 512-wide Gram, against 2e-7 at HIGHEST.
# KKT residuals are host float64, relative to max(1, max|S_block|).

#: A-D (single-class solves): measured 3.4e-7 .. 1.2e-6
KKT_TOL_F32 = 1e-5
#: E (joint ADMM, group and fused): measured 4.4e-5 .. 5.3e-5
JOINT_KKT_TOL_F32 = 5e-4
#: partition sandwich: entries within this relative distance of lambda may
#: land on either side under float32 rounding (the Gram error above is
#: ~2e-7), so the device partition must lie between the host partitions at
#: lam * (1 + TIE_REL) and lam * (1 - TIE_REL)
TIE_REL = 2e-6
#: served results against the offline engine, relative to max|Theta|:
#: measured 2.8e-7
SERVE_REL = 1e-5
#: --four-chips: KKT acceptance of the giant block's routes.  The engine's
#: default route_check_tol (1e-6 relative) is below what float32 reaches on
#: a 4096 block, and a rejected sharded solve would fall back to a
#: single-device repair of the whole block; restating the default for
#: float32 is separate work
ROUTE_TOL_F32 = 1e-4
#: --four-chips, the b=4096 block on either mesh: measured 5.4e-6 .. 5.6e-6.
#: The sharded solver accepts its own device KKT at ROUTE_TOL_F32, and its
#: float32 floor is problem-dependent (8e-5 on a b=256 block of the same
#: family), so the host bound is that acceptance
GIANT_KKT_TOL_F32 = ROUTE_TOL_F32
#: --four-chips: sharded vs one-chip Theta, relative to max(1, max|Theta|):
#: measured 6.0e-7; two solves accepted at ROUTE_TOL_F32 may differ by
#: about that much
ONE_CHIP_REL = 1e-4

#: phase sizes (one chip); tests/test_chip_smoke.py runs the phases smaller
DENSE_K, DENSE_P1, N_LAMBDAS = 150, 16, 20
DATA_N, DATA_P, DATA_LAM = 200, 20000, 0.7
SERVE_DENSE, SERVE_DATA, SERVE_DATA_P = 8, 2, 4000
JOINT_K, JOINT_BLOCKS, JOINT_P1 = 3, 40, 15
GIANT_B = 4096


class SmokeFailure(AssertionError):
    """A phase's answer disagreed with its host reference."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def _import_repro():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def _tpu_devices():
    """The TPU devices, or exit non-zero: this script never times a CPU."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chip_smoke: JAX found no accelerator ({e})")
    if not devices or devices[0].platform != "tpu":
        raise SystemExit(
            "chip_smoke: needs a TPU; JAX reports "
            f"{devices[0].platform if devices else 'no devices'}"
        )
    return devices


# ---------------------------------------------------------------------------
# host references
# ---------------------------------------------------------------------------


def check_partition(labels, S, lam: float) -> bool:
    """Device partition vs the host float64 union-find at lam.

    Returns True when equal.  Otherwise the device partition must still lie
    between the host partitions at lam*(1+TIE_REL) and lam*(1-TIE_REL)
    (only near-ties moved), or this raises."""
    from repro.core.components import (
        components_from_covariance_host,
        is_refinement,
        partitions_equal,
    )

    ref = components_from_covariance_host(S, lam)
    if partitions_equal(labels, ref):
        return True
    finer = components_from_covariance_host(S, lam * (1 + TIE_REL))
    coarser = components_from_covariance_host(S, lam * (1 - TIE_REL))
    if not (is_refinement(finer, labels) and is_refinement(labels, coarser)):
        labels = np.asarray(labels)
        absS = np.abs(np.asarray(S, dtype=np.float64))
        i, j = np.nonzero(np.triu(absS > lam, 1))
        cut = labels[i] != labels[j]

        def pairs(*keys):
            counts = np.unique(np.stack(keys), axis=1, return_counts=True)[1]
            return int((counts * (counts - 1) // 2).sum())

        raise SmokeFailure(
            f"partition at lam={lam} differs from the host's: "
            f"{int(cut.sum())} host edges cut, "
            f"{pairs(labels) - pairs(labels, ref)} pairs joined that the "
            f"host keeps apart; smallest |S_ij| cut "
            f"{np.sort(absS[i[cut], j[cut]])[:4].tolist()}; "
            f"{len(np.unique(labels))} vs {len(np.unique(ref))} components"
        )
    return False


def _theta_blocks(Theta, labels):
    """(members, Theta block) for every component, dense or sparse."""
    from repro.core.components import component_lists
    from repro.core.sparse import SparseTheta

    if isinstance(Theta, SparseTheta):
        for c, blk in Theta.blocks():
            yield np.asarray(c), np.asarray(blk, dtype=np.float64)
        for i, v in zip(Theta.isolated, Theta.isolated_values):
            yield np.asarray([i]), np.asarray([[v]], dtype=np.float64)
        return
    Theta = np.asarray(Theta, dtype=np.float64)
    for c in component_lists(labels):
        yield c, Theta[np.ix_(c, c)]


def worst_kkt(
    Theta, labels, lam: float, S_block, tol: float = KKT_TOL_F32
) -> float:
    """Largest host float64 KKT residual over the result's blocks, each
    relative to max(1, max|S_block|); raises past ``tol``.
    ``S_block(members)`` returns the float64 covariance block."""
    from repro.core.solvers.closed_form import kkt_residual_host

    worst = 0.0
    for c, blk in _theta_blocks(Theta, labels):
        Sb = S_block(c)
        res = kkt_residual_host(Sb, float(lam), blk)
        rel = res / max(1.0, float(np.abs(Sb).max()))
        if not rel <= tol:  # NaN-safe
            raise SmokeFailure(
                f"KKT residual {rel:.3e} > {tol} on a block of "
                f"{len(c)} at lam={lam}"
            )
        worst = max(worst, rel)
    return worst


def dense_block(S):
    return lambda c: np.asarray(S, dtype=np.float64)[np.ix_(c, c)]


def data_covariance(X) -> np.ndarray:
    """The streaming estimator in float64: centered Gram over n."""
    Xc = np.asarray(X, dtype=np.float64)
    Xc = Xc - Xc.mean(axis=0)
    return Xc.T @ Xc / Xc.shape[0]


# ---------------------------------------------------------------------------
# instrumentation
# ---------------------------------------------------------------------------


def _fallbacks() -> dict:
    from repro.core.instrument import tail_counts

    return dict(tail_counts("router.fallback."))


def _reset_counters() -> None:
    from repro.core.instrument import reset

    reset("router")


def _peak_bytes() -> list[int]:
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out.append(int(stats.get("peak_bytes_in_use", 0)))
    return out


def _merge_mix(total: dict, mix: dict) -> None:
    for k, v in mix.items():
        total[k] = total.get(k, 0) + int(v)


def timed_twice(fn):
    """(first-call seconds, steady seconds, result of the steady call).
    The engine returns host arrays, so each call has finished on the device
    when it returns."""
    t0 = time.perf_counter()
    fn()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    return first, time.perf_counter() - t0, out


def assert_pallas(fn, *shapes) -> None:
    """Lower ``fn`` at ``shapes`` and require a Mosaic custom call in it."""
    import jax

    hlo = jax.jit(fn).lower(*shapes).as_text()
    if "tpu_custom_call" not in hlo:
        raise SmokeFailure(f"{getattr(fn, '__name__', fn)}: no tpu_custom_call")


def _f32(shape, dtype=None):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, dtype or jnp.float32)


# ---------------------------------------------------------------------------
# phases (each returns a report dict; tests call them at tiny sizes)
# ---------------------------------------------------------------------------


def dense_workload(K: int = DENSE_K, p1: int = DENSE_P1, n_lambdas: int = N_LAMBDAS):
    """The bench_routes workload: structured_synthetic over its lambda grid."""
    from repro.covariance import structured_synthetic

    S = structured_synthetic(K, p1, seed=1)
    return S, [float(v) for v in np.linspace(0.75, 0.32, n_lambdas)]


def phase_dense_path(S, grid, options=None) -> dict:
    """A (and B's path): glasso_path, then the host checks per lambda."""
    from repro.core import glasso_path

    _reset_counters()
    first, steady, results = timed_twice(
        lambda: glasso_path(S, grid, options=options)
    )
    mix: dict = {}
    exact = 0
    worst = 0.0
    for r in results:
        _merge_mix(mix, r.route_mix)
        exact += check_partition(r.labels, S, r.lam)
        worst = max(worst, worst_kkt(r.Theta, r.labels, r.lam, dense_block(S)))
    return {
        "first_s": first, "steady_s": steady, "lambdas": len(results),
        "partitions_exact": exact, "worst_kkt": worst, "route_mix": mix,
        "fallbacks": _fallbacks(),
    }


def phase_screened_solves(S, lams, options) -> dict:
    """B's single solves: glasso(S, lam) screened by options.cc_backend."""
    from repro.core import glasso

    _reset_counters()
    first, steady, results = timed_twice(
        lambda: [glasso(S, lam, options=options) for lam in lams]
    )
    exact = 0
    worst = 0.0
    for lam, r in zip(lams, results):
        exact += check_partition(r.labels, S, lam)
        worst = max(worst, worst_kkt(r.Theta, r.labels, lam, dense_block(S)))
    return {
        "first_s": first, "steady_s": steady, "solves": len(results),
        "partitions_exact": exact, "worst_kkt": worst,
        "fallbacks": _fallbacks(),
    }


def phase_from_data(X, lam: float, options=None) -> dict:
    """C: glasso(X=..., from_data=True); S exists only on the host, for the
    reference."""
    from repro.core import glasso

    _reset_counters()
    first, steady, r = timed_twice(
        lambda: glasso(X=X, lam=lam, from_data=True, options=options)
    )
    S = data_covariance(X)
    exact = check_partition(r.labels, S, lam)
    worst = worst_kkt(r.Theta, r.labels, lam, dense_block(S))
    del S
    sizes = np.unique(r.labels, return_counts=True)[1]
    return {
        "first_s": first, "steady_s": steady, "p": int(X.shape[1]),
        "n": int(X.shape[0]), "largest_block": int(sizes.max()),
        "partition_exact": bool(exact), "worst_kkt": worst,
        "route_mix": dict(r.route_mix), "output": r.output,
        "fallbacks": _fallbacks(),
    }


def _same_theta(a, b) -> tuple[bool, float]:
    """(bitwise equal, max |a - b| / max(1, max|a|)) of two results."""
    from repro.core.sparse import SparseTheta

    def dense(T):
        return T.toarray(force=True) if isinstance(T, SparseTheta) else np.asarray(T)

    A = np.asarray(dense(a), dtype=np.float64)
    B = np.asarray(dense(b), dtype=np.float64)
    diff = float(np.abs(A - B).max()) / max(1.0, float(np.abs(A).max()))
    return bool(np.array_equal(A, B)), diff


def phase_served(dense_reqs, data_reqs, options=None) -> dict:
    """D: a GlassoServer answers every request; each result equals the
    offline engine's for the same request and passes the host checks."""
    from repro.core import glasso
    from repro.launch.control_plane import DataSpec, DenseSpec, RequestMeta
    from repro.launch.serve_glasso import GlassoServer

    _reset_counters()
    specs = [DenseSpec(S, lam) for S, lam in dense_reqs]
    specs += [DataSpec(X, lam) for X, lam in data_reqs]

    def serve():
        with GlassoServer(options=options) as server:
            futs = [
                server.submit(s, meta=RequestMeta(tenant="smoke")) for s in specs
            ]
            return [f.result(timeout=900) for f in futs]

    first, steady, served = timed_twice(serve)
    bitwise = 0
    worst_diff = 0.0
    worst = 0.0
    for spec, r in zip(specs, served):
        if isinstance(spec, DenseSpec):
            offline = glasso(spec.S, spec.lam, options=options)
            S = np.asarray(spec.S)
        else:
            offline = glasso(X=spec.X, lam=spec.lam, from_data=True, options=options)
            S = data_covariance(spec.X)
        same, diff = _same_theta(offline.Theta, r.Theta)
        if not diff <= SERVE_REL:
            raise SmokeFailure(f"served result differs from offline by {diff:.3e}")
        bitwise += same
        worst_diff = max(worst_diff, diff)
        check_partition(r.labels, S, spec.lam)
        worst = max(worst, worst_kkt(r.Theta, r.labels, spec.lam, dense_block(S)))
    return {
        "first_s": first, "steady_s": steady, "requests": len(served),
        "bitwise_equal": bitwise, "worst_rel_diff": worst_diff,
        "worst_kkt": worst, "fallbacks": _fallbacks(),
    }


def joint_workload(K: int = JOINT_K, blocks: int = JOINT_BLOCKS, p1: int = JOINT_P1):
    from repro.covariance import structured_synthetic

    return structured_synthetic(blocks, p1, classes=K, shared_fraction=0.5, seed=3)


def phase_joint(Ss, lam1: float, lam2: float, penalty: str, options=None) -> dict:
    """E: joint_glasso; partition vs the host hybrid screen, joint KKT per
    block in float64 on the host."""
    from repro.core.components import component_lists, partitions_equal
    from repro.joint import joint_glasso
    from repro.joint.kkt import joint_kkt_residual
    from repro.joint.screen import joint_thresholded_components

    _reset_counters()
    first, steady, r = timed_twice(
        lambda: joint_glasso(Ss, lam1, lam2, penalty=penalty, options=options)
    )
    ref, _ = joint_thresholded_components(Ss, lam1, lam2, penalty=penalty)
    if not partitions_equal(r.labels, ref):
        raise SmokeFailure(f"joint {penalty} partition differs from the host's")
    Ss64 = np.asarray(Ss, dtype=np.float64)
    Theta = np.asarray(r.Theta, dtype=np.float64)
    worst = 0.0
    for c in component_lists(r.labels):
        ix = np.ix_(c, c)
        Sb = np.stack([s[ix] for s in Ss64])
        res = joint_kkt_residual(
            Sb, np.stack([t[ix] for t in Theta]), lam1, lam2, penalty=penalty
        )
        rel = res / max(1.0, float(np.abs(Sb).max()))
        if not rel <= JOINT_KKT_TOL_F32:
            raise SmokeFailure(
                f"joint {penalty} KKT residual {rel:.3e} > {JOINT_KKT_TOL_F32}"
            )
        worst = max(worst, rel)
    return {
        "first_s": first, "steady_s": steady, "penalty": penalty,
        "p": int(Ss64.shape[1]), "K": int(Ss64.shape[0]), "worst_kkt": worst,
        "route_mix": dict(r.route_mix), "fallbacks": _fallbacks(),
    }


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def run_phases(phases) -> dict:
    """Run every (name, fn) phase, even after one fails, so one chip run
    reports them all; each report gains the devices' peak_bytes_in_use.
    Raises at the end if any phase failed."""
    import traceback

    reports, failed = {}, []
    for name, fn in phases:
        try:
            rep = fn()
        except Exception as e:  # noqa: BLE001 - reported, then re-raised below
            failed.append(name)
            if not isinstance(e, SmokeFailure):
                traceback.print_exc()
            log(f"{name}: FAILED {type(e).__name__}: {e}")
            continue
        rep["peak_bytes_in_use"] = _peak_bytes()
        reports[name] = rep
        log(f"{name}: {rep}")
    if failed:
        raise SmokeFailure(f"phases failed: {failed}")
    return reports


def run_one_chip() -> dict:
    import jax.numpy as jnp

    from repro.covariance import microarray_like
    from repro.engine import EngineOptions
    from repro.kernels.bucket_glasso import fused_bcd_stack
    from repro.kernels.covgram_screen.covgram_screen import covgram_screen_pallas
    from repro.kernels.joint_prox.ops import joint_prox_step
    from repro.kernels.threshold_cc.ops import connected_components_kernel
    from repro.kernels.tree_glasso.ops import glasso_forest_stack
    from repro.stream.config import StreamConfig

    S, grid = dense_workload()
    p = S.shape[0]
    fused = EngineOptions(fused=True, cc_backend="pallas")

    def dense():
        log(f"A: glasso_path p={p}, {len(grid)} lambdas, default options")
        assert_pallas(glasso_forest_stack, _f32((64, DENSE_P1, DENSE_P1)), _f32((64,)))
        return phase_dense_path(S, grid)

    def fused_path():
        log("B: fused=True, cc_backend='pallas'")
        assert_pallas(
            lambda b, l, s, w, t: fused_bcd_stack(b, l, s, w, t),
            *([_f32((64, 16, 16))] + [_f32((64,))] * 2 + [_f32((64, 16, 16))] * 2),
        )
        return phase_dense_path(S, grid, options=fused)

    def screened():
        log("B: single solves screened by the threshold_cc kernel")
        assert_pallas(
            lambda S_, lam: connected_components_kernel(S_, lam),
            _f32((p, p)), _f32(()),
        )
        return phase_screened_solves(
            S, [grid[0], grid[len(grid) // 2], grid[-1]], fused
        )

    def from_data():
        log(f"C: glasso(X=..., from_data=True) n={DATA_N} p={DATA_P} lam={DATA_LAM}")
        cfg = StreamConfig()
        pp = -(-DATA_P // cfg.tile) * cfg.tile
        assert_pallas(
            lambda x, mu, i, j, lam: covgram_screen_pallas(
                x, mu, i, j, lam, n_true=DATA_N, p_true=DATA_P,
                block_n=cfg.chunk, block_p=cfg.tile,
            ),
            _f32((-(-DATA_N // cfg.chunk) * cfg.chunk, pp)), _f32((pp,)),
            _f32((8,), jnp.int32), _f32((8,), jnp.int32), _f32((1, 1)),
        )
        return phase_from_data(microarray_like(DATA_N, DATA_P, seed=0), DATA_LAM)

    def served():
        log(f"D: GlassoServer, {SERVE_DENSE} DenseSpec p={p} + {SERVE_DATA} DataSpec")
        assert_pallas(glasso_forest_stack, _f32((64, DENSE_P1, DENSE_P1)), _f32((64,)))
        return phase_served(
            [(S, grid[(3 * i) % len(grid)]) for i in range(SERVE_DENSE)],
            [
                (microarray_like(DATA_N, SERVE_DATA_P, seed=10 + i), DATA_LAM)
                for i in range(SERVE_DATA)
            ],
        )

    Ss = joint_workload()

    def joint(penalty):
        log(f"E: joint_glasso K={Ss.shape[0]} p={Ss.shape[1]} penalty={penalty}")
        assert_pallas(
            lambda th, u, z: joint_prox_step(th, u, z, 0.1, 0.05, penalty=penalty),
            *([_f32((Ss.shape[0], 16, 16))] * 3),
        )
        return phase_joint(Ss, 0.5, 0.05, penalty)

    return run_phases(
        [
            ("A", dense),
            ("B", fused_path),
            ("B_screen", screened),
            ("C", from_data),
            ("D", served),
            ("E_group", lambda: joint("group")),
            ("E_fused", lambda: joint("fused")),
        ]
    )


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def giant_block(b: int = GIANT_B, seed: int = 0) -> tuple[np.ndarray, float]:
    """One connected b x b covariance (a chain of overlapping factor
    modules) and a lambda at which it screens into ONE component."""
    rng = np.random.default_rng(seed)
    n = 2 * b
    X = rng.standard_normal((n, b))
    step = 8
    for start in range(0, b - step, step):
        z = rng.standard_normal((n, 1))
        X[:, start:start + 2 * step] += 0.9 * z
    Xc = X - X.mean(axis=0)
    S = Xc.T @ Xc / n
    return S, 0.35


def run_four_chips() -> dict:
    import jax

    from repro.core import glasso
    from repro.covariance import structured_synthetic
    from repro.engine import EngineOptions
    from repro.kernels.shard_prox.ops import fused_prox_residual

    devices = jax.local_devices()
    if len(devices) != 4:
        raise SystemExit(f"--four-chips needs 4 TPU chips, found {len(devices)}")
    S, lam = giant_block()
    b = S.shape[0]
    rl = b // len(devices)
    solved = {}

    def sharded():
        log(f"sharded: b={b} on a {len(devices)}-chip mesh, lam={lam}")
        assert_pallas(
            lambda x, u, z: fused_prox_residual(x, u, z, 0.1), *([_f32((rl, b))] * 3)
        )
        opts = EngineOptions(
            oversize_threshold=b // 2, route_check_tol=ROUTE_TOL_F32
        )
        _reset_counters()
        t0 = time.perf_counter()  # one call: a second would double the 4-chip cost
        sh = glasso(S, lam, options=opts)
        first = time.perf_counter() - t0
        peaks = _peak_bytes()
        solved["sharded"] = sh
        rep = {
            "first_s": first, "oversize": dict(sh.oversize),
            "largest_block": int(max(sh.block_sizes or [0])),
            "partition_exact": bool(check_partition(sh.labels, S, lam)),
            "worst_kkt": worst_kkt(
                sh.Theta, sh.labels, lam, dense_block(S), GIANT_KKT_TOL_F32
            ),
            "peaks_after_solve": peaks, "fallbacks": _fallbacks(),
        }
        if not sh.oversize.get("dispatched") or sh.oversize.get("fallbacks"):
            raise SmokeFailure(f"the giant block did not stay on the sharded route: {rep}")
        if min(peaks) <= 0 or max(peaks) > 2 * min(peaks):
            raise SmokeFailure(f"unbalanced peak_bytes_in_use across chips: {rep}")
        return rep

    def one_chip():
        # The same block through the sharded solver on a one-device mesh (no
        # collectives), which is what an oversize block takes on one chip.
        # The engine's single-device solvers cannot be the reference at
        # b=4096 in float32: BCD updates 4096 columns one after another;
        # ADMM's eigh, compiled ahead of time for a v5e, took 4.9 GB of host
        # memory and 78 s at b=1024 and 11.8 GB and 222 s at b=2048, growing
        # past a one-chip host at 4096; proximal gradient's line search
        # stalls in float32 (host KKT 8e-4 at b=512).  The host float64 KKT
        # check is the independent reference.
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from repro.core.solvers.sharded import glasso_sharded

        log("one chip: the same block on a one-device mesh")
        mesh = Mesh(np.array(devices[:1]), ("data",))
        t0 = time.perf_counter()
        one = glasso_sharded(
            S, lam, mesh=mesh, dtype=jnp.float32, kkt_target=ROUTE_TOL_F32
        )
        rep = {
            "first_s": time.perf_counter() - t0, "iters": one.iters,
            "stalls": one.stalls, "admm_residual": one.admm_residual,
            "admm_eps": one.admm_eps, "device_kkt": one.kkt_residual / max(1.0, one.s_max),
            "theta_fro": float(np.linalg.norm(one.Theta)),
            "worst_kkt": worst_kkt(
                one.Theta, np.zeros(b, np.int64), lam, dense_block(S),
                GIANT_KKT_TOL_F32,
            ),
        }
        if "sharded" in solved:
            same, diff = _same_theta(one.Theta, solved["sharded"].Theta)
            rep.update(rel_diff_vs_sharded=diff, bitwise_equal=same)
            if not diff <= ONE_CHIP_REL:
                raise SmokeFailure(f"sharded and one-chip results differ by {diff:.3e}")
        return rep

    def screen():
        log("shard_map screen vs the host partition")
        Sg = structured_synthetic(DENSE_K, DENSE_P1, seed=1)
        lams = (0.75, 0.5, 0.32)
        opts = EngineOptions(cc_backend="shard_map")
        first, steady, res = timed_twice(
            lambda: [glasso(Sg, lam_, options=opts) for lam_ in lams]
        )
        return {
            "first_s": first, "steady_s": steady, "p": int(Sg.shape[0]),
            "lambdas": len(lams),
            "partitions_exact": sum(
                check_partition(r.labels, Sg, lam_) for r, lam_ in zip(res, lams)
            ),
        }

    return run_phases(
        # sharded first: peak_bytes_in_use is a high-water mark, and the
        # screen phase's single-device block solves would set device 0's
        [("sharded", sharded), ("one_chip", one_chip), ("shard_map", screen)]
    )


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded giant block and the shard_map screen on 4 chips",
    )
    args = ap.parse_args(argv)

    _import_repro()
    import jax

    devices = _tpu_devices()
    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache(ROOT)
    jax.config.update("jax_enable_x64", True)
    dev = devices[0]
    log(
        f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}"
    )
    try:
        run_four_chips() if args.four_chips else run_one_chip()
    except SmokeFailure as e:
        log(f"chip_smoke: {e}")
        return 1
    log(f"peak_bytes_in_use: {_peak_bytes()}")
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
