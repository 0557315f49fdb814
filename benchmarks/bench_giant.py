"""Giant-component benchmark: the sharded oversize route vs the
single-device dense solve.

The paper's regime of interest for this PR: moderate rho leaves one
connected component near size p, so the solve stage is ONE giant block and
the per-device memory of the solver is the scale cap.  Two arms, each in
its OWN subprocess (per-arm ``ru_maxrss``, like bench_stream):

  * ``dense``    single-device ADMM oracle on the giant block (the eigh
                 path every PR-2 route bottoms out in);
  * ``sharded``  the local devices — 8 emulated ones
                 (``xla_force_host_platform_device_count``) when
                 ``JAX_PLATFORMS=cpu`` was asked for, the chips otherwise —
                 through the full engine path with an oversize threshold
                 below the giant block: screen -> oversize class ->
                 shard-direct gather -> mesh-spanning no-eigh ADMM ->
                 distributed KKT verification.

MEMORY METRIC.  Under host-device emulation every "device" shares one
process, so OS RSS cannot see per-device footprints; the acceptance metric
is the ACCOUNTING per-device peak both arms publish (DESIGN.md Section 11
memory model): dense = blocks.SINGLE_DEVICE_BUFFERS * b^2 * 8 bytes on its
one device, sharded = the ``solver.oversize.device_bytes_peak`` watermark
(12 row-shards of (b_pad/d, b_pad)).  Subprocess RSS is reported alongside
as the whole-process sanity number.

Acceptance facts recorded per run (gated by --check against the committed
``baseline_giant.json``; >20% regression fails):

  * Theta of the sharded arm matches the dense ADMM oracle within
    route_check_tol * max(1, max|S|)   (max_diff, kkt_residual)
  * zero unexplained fallbacks         (oversize.fallbacks == 0)
  * sharded per-device bytes strictly below the dense arm's single-device
    bytes                              (device_bytes_ratio < 1)

    PYTHONPATH=src python -m benchmarks.bench_giant [--smoke] \
        [--json BENCH_giant.json] [--check benchmarks/baseline_giant.json]
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import time

import numpy as np

P = 256            # total vertices; the giant component covers most of them
N_ROWS = 320
LAM = 0.12
DEVICES = 8
TOL = 1e-6         # route_check_tol for the sharded arm's KKT acceptance


def _workload(p: int = P, seed: int = 0) -> np.ndarray:
    """(p, p) covariance with one giant factor-coupled component plus a
    fringe of small/isolated blocks — Figure-1-style heavy tail.  Loadings
    are kept moderate: ADMM iteration counts grow with the giant block's
    conditioning, and the bench should measure the sharded machinery, not
    an adversarial spectrum (the multidevice tests cover harder blocks)."""
    rng = np.random.default_rng(seed)
    n = N_ROWS
    X = 0.8 * rng.standard_normal((n, p))
    giant = int(0.8 * p)
    f = rng.standard_normal((n, 3))
    load = 0.5 + 0.2 * rng.random(giant)
    X[:, :giant] += f[:, rng.integers(0, 3, giant)] * load
    # a few planted pairs in the fringe
    for k in range(giant, p - 1, 6):
        X[:, k + 1] += 0.9 * X[:, k]
    S = np.cov(X, rowvar=False, bias=True)
    return 0.5 * (S + S.T)


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _giant_block(S: np.ndarray, lam: float) -> np.ndarray:
    from repro.core.components import component_lists, components_from_covariance_host

    labels = components_from_covariance_host(S, lam)
    comps = component_lists(labels)
    comp = max(comps, key=len)
    return S[np.ix_(comp, comp)]


def run_arm(arm: str, p: int, seed: int = 0) -> dict:
    """One arm in THIS process (the parent spawns each in a subprocess)."""
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from repro.core import blocks as blocks_mod

    S = _workload(p, seed)
    blk = _giant_block(S, LAM)
    b = blk.shape[0]
    t0 = time.perf_counter()
    if arm == "dense":
        from repro.core.solvers.admm import glasso_admm_info

        Theta, iters = glasso_admm_info(jnp.asarray(blk), LAM, tol=1e-9)
        Theta = np.asarray(jax.block_until_ready(Theta))
        rec = {
            "iters": int(iters),
            "device_bytes": int(
                blocks_mod.SINGLE_DEVICE_BUFFERS * b * b * 8
            ),
            "theta_trace": float(np.trace(Theta)),
            "theta_absum": float(np.abs(Theta).sum()),
        }
    elif arm == "sharded":
        from repro.core import EngineOptions
        from repro.core.glasso import glasso
        from repro.core.instrument import counts

        if _emulated():
            assert jax.device_count() == DEVICES, (
                f"sharded arm expected {DEVICES} emulated devices, got "
                f"{jax.device_count()} — spawn via the parent"
            )
        res = glasso(
            S, LAM,
            options=EngineOptions(
                solver="admm", route_check_tol=TOL,
                oversize_threshold=b - 1,  # giant block is oversize, rest not
                solver_opts={"tol": 1e-9},
            ),
        )
        c = counts("solver.oversize.")
        # oracle comparison runs in the PARENT via the theta fingerprints +
        # cross-arm max_diff on the giant block
        comp_theta = _giant_theta(res)
        rec = {
            "oversize": res.oversize,
            "fallbacks": int(c.get("solver.oversize.fallbacks", 0)),
            "dispatched": int(c["solver.oversize.dispatched"]),
            "inner_iters": int(c["solver.oversize.cg_iters"]),
            "device_bytes": int(c["solver.oversize.device_bytes_peak"]),
            "theta_trace": float(np.trace(comp_theta)),
            "theta_absum": float(np.abs(comp_theta).sum()),
            "theta_file": _dump_theta(comp_theta),
            "devices": jax.device_count(),
        }
    else:
        raise ValueError(arm)
    rec.update(
        {
            "arm": arm,
            "p": p,
            "b_giant": b,
            "seconds": round(time.perf_counter() - t0, 2),
            "rss_mb": round(_rss_mb(), 1),
        }
    )
    return rec


def _giant_theta(res) -> np.ndarray:
    from repro.core.components import component_lists

    comp = max(component_lists(res.labels), key=len)
    return res.Theta[np.ix_(comp, comp)]


def _dump_theta(theta: np.ndarray) -> str:
    path = os.path.join(
        os.environ.get("TMPDIR", "/tmp"), f"bench_giant_theta_{os.getpid()}.npy"
    )
    np.save(path, theta)
    return path


def _emulated() -> bool:
    """The mesh is emulated only where the CPU platform was asked for
    explicitly; on an accelerator host the sharded arm uses its chips."""
    return os.environ.get("JAX_PLATFORMS") == "cpu"


def _spawn_arm(arm: str, p: int) -> dict:
    env = dict(os.environ)
    if arm == "sharded" and _emulated():
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={DEVICES} "
            + env.get("XLA_FLAGS", "")
        ).strip()
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_giant", "--arm", arm,
         "--p", str(p)],
        capture_output=True, text=True, check=True, env=env,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(p: int = P, log=print) -> dict:
    dense = _spawn_arm("dense", p)
    sharded = _spawn_arm("sharded", p)
    # cross-arm equivalence: the sharded giant-block Theta vs the oracle's
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from repro.core.solvers.admm import glasso_admm

    S = _workload(p)
    blk = _giant_block(S, LAM)
    oracle = np.asarray(glasso_admm(jnp.asarray(blk), LAM, tol=1e-9))
    theta_sharded = np.load(sharded["theta_file"])
    os.unlink(sharded["theta_file"])
    max_diff = float(np.abs(theta_sharded - oracle).max())
    scale = max(1.0, float(np.abs(blk).max()))
    rec = {
        "p": p,
        "b_giant": dense["b_giant"],
        "devices": sharded["devices"],
        "lam": LAM,
        "max_diff": max_diff,
        "tol_scaled": TOL * scale,
        "fallbacks": sharded["fallbacks"],
        "dispatched": sharded["dispatched"],
        "inner_iters": sharded["inner_iters"],
        "dense_iters": dense["iters"],
        "dense_device_bytes": dense["device_bytes"],
        "sharded_device_bytes": sharded["device_bytes"],
        "device_bytes_ratio": round(
            sharded["device_bytes"] / dense["device_bytes"], 4
        ),
        "dense_seconds": dense["seconds"],
        "sharded_seconds": sharded["seconds"],
        "dense_rss_mb": dense["rss_mb"],
        "sharded_rss_mb": sharded["rss_mb"],
    }
    log(
        f"p={p} giant b={rec['b_giant']}: dense {dense['seconds']}s "
        f"({dense['iters']} eigh iters, {dense['device_bytes']/2**20:.1f}MB "
        f"on 1 device)  vs  sharded {sharded['seconds']}s "
        f"({sharded['inner_iters']} inner iters across {sharded['devices']} devices, "
        f"{sharded['device_bytes']/2**20:.1f}MB/device, ratio "
        f"{rec['device_bytes_ratio']}); max|dTheta|={max_diff:.2e} "
        f"(accept {rec['tol_scaled']:.2e}), fallbacks={rec['fallbacks']}"
    )
    if max_diff > rec["tol_scaled"]:
        raise AssertionError(
            f"sharded Theta diverged from the ADMM oracle: {max_diff:.3e} > "
            f"{rec['tol_scaled']:.3e}"
        )
    if rec["fallbacks"]:
        raise AssertionError(
            f"{rec['fallbacks']} unexplained sharded fallbacks on the bench "
            "workload"
        )
    if rec["device_bytes_ratio"] >= 1.0:
        raise AssertionError(
            "sharded per-device bytes not below the dense single-device arm: "
            f"ratio {rec['device_bytes_ratio']}"
        )
    return rec


def smoke(log=print) -> None:
    """In-process sharded == dense equivalence on the 1-device mesh (the CI
    gate's cheap arm: same code path, no emulation)."""
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from repro.core import EngineOptions
    from repro.core.glasso import glasso
    from repro.core.instrument import counts, reset
    from repro.core.solvers.admm import glasso_admm

    p = 96
    S = _workload(p, seed=3)
    blk = _giant_block(S, LAM)
    reset("solver.oversize")
    base = glasso(
        S, LAM,
        options=EngineOptions(solver="admm", solver_opts={"tol": 1e-9}),
    )
    over = glasso(
        S, LAM,
        options=EngineOptions(solver="admm", solver_opts={"tol": 1e-9},
                              oversize_threshold=blk.shape[0] - 1),
    )
    c = counts("solver.oversize.")
    assert c.get("solver.oversize.dispatched", 0) >= 1, "oversize never routed"
    assert c.get("solver.oversize.fallbacks", 0) == 0, "smoke: fallbacks"
    diff = float(np.abs(over.Theta - base.Theta).max())
    assert diff < 1e-6, f"smoke: sharded != dense ({diff:.3e})"
    oracle = np.asarray(glasso_admm(jnp.asarray(blk), LAM, tol=1e-9))
    from repro.core.components import component_lists

    comp = max(component_lists(over.labels), key=len)
    diff2 = float(np.abs(over.Theta[np.ix_(comp, comp)] - oracle).max())
    assert diff2 < 1e-6, f"smoke: giant block vs oracle ({diff2:.3e})"
    log(
        f"giant smoke OK: p={p}, giant b={blk.shape[0]}, "
        f"max|dTheta|={diff:.2e}, {c['solver.oversize.cg_iters']} inner iters, "
        "0 fallbacks"
    )


def check(rec: dict, baseline_path: str, log=print) -> int:
    """CI gate: correctness facts are hard asserts in run(); this gates the
    QUANTITIES against the committed baseline (>20% regression fails)."""
    with open(baseline_path) as f:
        base = json.load(f)
    failures = []
    max_ratio = base["device_bytes_ratio"] * 1.2
    if rec["device_bytes_ratio"] > max_ratio:
        failures.append(
            f"device-bytes ratio {rec['device_bytes_ratio']} > {max_ratio:.3f}"
            f" (baseline {base['device_bytes_ratio']} + 20%)"
        )
    max_inner = base["inner_iters"] * 1.2
    if rec["inner_iters"] > max_inner:
        failures.append(
            f"inner iterations {rec['inner_iters']} > {max_inner:.0f} "
            f"(baseline {base['inner_iters']} + 20%)"
        )
    for msg in failures:
        log(f"REGRESSION: {msg}")
    if not failures:
        log(f"giant bench within baseline ({baseline_path})")
    return 1 if failures else 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arm", choices=("dense", "sharded"), default=None)
    ap.add_argument("--p", type=int, default=P)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--check", default=None)
    args = ap.parse_args()

    if args.arm:  # subprocess mode: one arm, JSON on stdout
        print(json.dumps(run_arm(args.arm, args.p)))
        return
    if args.smoke:
        smoke()
        return
    rec = run(args.p)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    if args.check:
        sys.exit(check(rec, args.check))


if __name__ == "__main__":
    main()
