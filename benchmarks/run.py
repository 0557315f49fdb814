"""Benchmark harness — one module per paper table/figure + the kernel
microbench + the LM dry-run roofline summary.  Prints ``name,us_per_call,
derived`` CSV rows at the end for machine consumption.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--smoke]

``--smoke`` is the CI gate: a fast fixed-seed equivalence check that the
engine path (screen -> plan -> async batched solve) produces the same Theta
as the dense unscreened path, for single solves and for an incremental
warm-started lambda path.  Exits non-zero on any mismatch.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path


def smoke() -> None:
    """Engine-vs-dense equivalence on fixed seeds; asserts, no timing."""
    import jax

    jax.config.update("jax_enable_x64", True)
    import numpy as np

    from repro.core import glasso, glasso_path
    from repro.core.instrument import count, reset
    from repro.covariance import lambda_interval_for_k, paper_synthetic
    from repro.engine import EngineOptions, available_cc_backends

    S = paper_synthetic(3, 12, seed=0)
    lam_min, lam_max = lambda_interval_for_k(S, 3)
    lam = 0.5 * (lam_min + lam_max)

    # route=False pins the reference arm to the iterative dense path — the
    # gate must compare the engine against the pre-ladder behavior, not two
    # arms of the new closed-form code
    dense = glasso(S, lam, screen=False,
                   options=EngineOptions(route=False, solver_opts={"tol": 1e-9}))
    for backend in available_cc_backends():
        res = glasso(S, lam,
                     options=EngineOptions(cc_backend=backend,
                                           solver_opts={"tol": 1e-9}))
        err = float(np.abs(res.Theta - dense.Theta).max())
        assert err < 1e-6, f"backend {backend}: engine vs dense diff {err:.2e}"
        print(f"smoke: cc_backend={backend:10s} matches dense (diff {err:.2e})")

    lams = sorted(np.linspace(lam_min * 0.8, lam_max * 1.05, 6), reverse=True)
    reset()
    path = glasso_path(S, lams, options=EngineOptions(solver_opts={"tol": 1e-9}))
    assert count("partition.unionfind_passes") == 1, "path planner must plan in one pass"
    for r in path:
        ref = glasso(S, r.lam, screen=False,
                     options=EngineOptions(route=False, solver_opts={"tol": 1e-9}))
        err = float(np.abs(r.Theta - ref.Theta).max())
        assert err < 1e-5, f"path lam={r.lam:.4f}: engine vs dense diff {err:.2e}"
    print(f"smoke: {len(path)}-lambda warm-started path matches dense "
          f"(1 union-find pass)")

    # routing ladder: every structure class exercised, routed == unrouted.
    # One deterministic matrix with a singleton (vertex 0), a pair, a path
    # tree, a chorded 4-cycle (chordal) and a CHORDLESS 4-cycle on vertices
    # 11-14 (general — no (11,13)/(12,14) chord is ever set) at lam=0.3.
    from repro.core.instrument import route_mix_counts

    Ss = np.eye(15) * 2.0
    ladder_edges = [
        (1, 2, 0.8),                                              # pair
        (3, 4, 0.7), (4, 5, -0.6), (5, 6, 0.5),                   # tree
        (7, 8, 0.45), (8, 9, -0.45), (9, 10, 0.45),
        (10, 7, -0.45), (7, 9, 0.45),                             # chordal
        (11, 12, 0.5), (12, 13, 0.5), (13, 14, 0.5), (14, 11, 0.5),
    ]
    for i, j, v in ladder_edges:
        Ss[i, j] = Ss[j, i] = v
    reset()
    routed = glasso(Ss, 0.3, options=EngineOptions(solver_opts={"tol": 1e-9}))
    unrouted = glasso(
        Ss, 0.3, options=EngineOptions(route=False, solver_opts={"tol": 1e-9})
    )
    err = float(np.abs(routed.Theta - unrouted.Theta).max())
    assert err < 1e-6, f"ladder: routed vs unrouted diff {err:.2e}"
    mix = route_mix_counts()
    for cls in ("singleton", "pair", "tree", "chordal", "general"):
        assert mix.get(cls, 0) > 0, f"ladder class {cls!r} never routed"
    print(f"smoke: routing ladder matches iterative on all classes ({mix})")

    # joint multi-class gates: lam2=0 == K independent glasso; hybrid-
    # screened == unscreened joint (both penalties, zero fallbacks)
    from benchmarks import bench_joint

    bench_joint.smoke()

    # sparse-native results: sparse == dense on the from-data path, sparse-
    # aware KKT verification, no (p, p) allocation in the sparse container
    from benchmarks import bench_sparse

    bench_sparse.smoke()

    # serving control plane: typed specs == engine, tenant quota Overload,
    # deadline drop, result-cache hit, legacy-verb shim equivalence
    from benchmarks import bench_serve

    bench_serve.smoke()

    # model selection: EBIC recovers a planted chain's support on a small
    # grid, and submit(PathSpec) is bitwise-equal to offline select_path
    from benchmarks import bench_select

    bench_select.smoke()

    # fused wave packer: megabatched in-kernel BCD == per-bucket dispatches
    # bitwise, and the iterative tail collapses to one launch per bin per wave
    from benchmarks import bench_fused

    bench_fused.smoke()
    print("smoke: OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller Table-1 grid")
    ap.add_argument("--smoke", action="store_true",
                    help="CI equivalence gate (engine path == dense path)")
    args = ap.parse_args()
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache()

    if args.smoke:
        smoke()
        return

    rows = []

    print("=" * 72)
    print("Table 1 analog: screen vs no-screen, synthetic blocks (Section 4.1)")
    print("=" * 72)
    from benchmarks import bench_table1

    grid = [(2, 40), (5, 30)] if args.quick else None
    for r in bench_table1.run(rows=grid):
        rows.append((f"table1/K{r['K']}p{r['p1']}/{r['lambda']}/{r['solver']}",
                     r["with_screen_s"] * 1e6, f"speedup={r['speedup']}"))

    print("=" * 72)
    print("Tables 2-3 analog: microarray-like lambda grids (Section 4.2)")
    print("=" * 72)
    from benchmarks import bench_table23

    for r in bench_table23.run():
        key = f"table{r['table']}/" + (r.get("regime") or r.get("example", ""))
        rows.append((key, (r.get("with_screen_s") or r.get("avg_solve_s", 0)) * 1e6,
                     f"max_comp={r['avg_max_component']:.0f}"))

    print("=" * 72)
    print("Routing ladder: structure-routed vs all-iterative path solving")
    print("=" * 72)
    from benchmarks import bench_routes

    route_rec = bench_routes.run(
        K=40 if args.quick else 150, n_lambdas=8 if args.quick else 12
    )
    rows.append((f"routes/p{route_rec['p']}", route_rec["solve_routed_s"] * 1e6,
                 f"solve_speedup={route_rec['solve_speedup']}"))

    print("=" * 72)
    print("Engine planner: incremental path planning vs per-lambda replanning")
    print("=" * 72)
    plan_rec = bench_table23.run_planning(p=1200 if args.quick else 2400,
                                          n=100 if args.quick else 80)
    rows.append((f"planner/p{plan_rec['p']}", plan_rec["incremental_s"] * 1e6,
                 f"speedup={plan_rec['speedup']}"))

    print("=" * 72)
    print("Model selection: warm homotopy path vs per-lambda cold restarts")
    print("=" * 72)
    from benchmarks import bench_select

    sel_rec = (bench_select.run(K=20, p1=32, n_lambdas=10, reps=2)
               if args.quick else bench_select.run())
    rows.append((f"select/p{sel_rec['p']}", sel_rec["wall_warm_s"] * 1e6,
                 f"warm_speedup={sel_rec['warm_speedup']}"))

    print("=" * 72)
    print("Fused wave packer: one launch per bin per wave vs per-bucket dispatch")
    print("=" * 72)
    from benchmarks import bench_fused

    fus_rec = (bench_fused.run(K=24, n_lambdas=8, reps=2)
               if args.quick else bench_fused.run())
    rows.append((f"fused/p{fus_rec['p']}", fus_rec["wall_fused_s"] * 1e6,
                 f"fused_speedup={fus_rec['fused_speedup']}"))

    print("=" * 72)
    print("Figure 1 analog: component-size profile across lambda")
    print("=" * 72)
    from benchmarks import bench_fig1

    fig_rows = bench_fig1.run(cap=200, n_lambdas=8)
    for name in ("A-like", "B-like", "C-like"):
        sub = [r for r in fig_rows if r["example"] == name]
        rows.append((f"fig1/{name}", 0.0,
                     f"ncomp_range={sub[0]['n_components']}..{sub[-1]['n_components']}"))

    print("=" * 72)
    print("Kernel microbenchmarks (interpret-mode on CPU)")
    print("=" * 72)
    from benchmarks import bench_kernels

    for r in bench_kernels.run():
        rows.append((f"kernels/{r['bench']}", r["us_per_call"], ""))

    print("=" * 72)
    print("LM pillar: dry-run roofline summary (see EXPERIMENTS.md for full table)")
    print("=" * 72)
    dry = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    if dry.exists():
        sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
        from repro.launch.roofline import load_records, roofline_row

        recs = [roofline_row(r) for r in load_records()]
        ok = [r for r in recs if r["status"] == "ok"]
        print(f"cells ok={len(ok)} skipped={sum(1 for r in recs if r['status']=='skipped')}")
        for r in ok:
            if r["mesh"] == "single" and r["shape"] == "train_4k":
                print(f"  {r['arch']:24s} dominant={r['dominant']:10s} "
                      f"useful={r['useful_ratio']:.2f} frac={r['roofline_frac']:.3f}")
                rows.append((f"roofline/{r['arch']}/train_4k",
                             r["compute_s"] * 1e6, f"dominant={r['dominant']}"))

    print()
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
