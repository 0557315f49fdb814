"""The Plan->Execute engine: one object owning the whole pipeline

    screen -> partition -> bucket -> place -> solve -> assemble

``Engine.run``       one (S, lam) solve through a registry screening backend,
                     the bucket planner, and the async executor.
``Engine.run_path``  a descending lambda grid with ONE partition pass
                     (planner.plan_path) and bucket-level reuse of padded
                     arrays + warm starts between consecutive lambdas.

``repro.core.glasso.glasso/glasso_path`` are thin wrappers over this module —
the public API is unchanged, the engine is the implementation.  Serving
(``repro.launch.serve_glasso``) drives the same executor/compiled-cache with
cross-request coalescing.
"""

from __future__ import annotations

import time
from contextlib import nullcontext

import jax.numpy as jnp
import numpy as np

from repro.core import schedule as schedule_mod
from repro.core.components import component_lists
from repro.core.instrument import bump
from repro.core.screening import ScreenStats, thresholded_components
from repro.core.sparse import SparseTheta, resolve_output, result_nbytes
from repro.engine.executor import BucketExecutor
from repro.engine.options import EngineOptions, normalize_options
from repro.engine.planner import build_plan_incremental, plan_path
from repro.obs.trace import Trace, current_trace, span, trace_request

#: canonical stage order of the ``result.stages()`` view
STAGES = ("screen", "solve", "dispatch", "assemble")


class GlassoResult:
    """One solve's answer + attribution.

    Timing lives in ONE place — the ``stages()`` view (seconds per
    canonical stage: screen / solve / dispatch / assemble) — and the
    historical per-stage attributes (``solve_seconds``,
    ``assemble_seconds``, ``dispatch_seconds``, ``screen_seconds``,
    ``stages_us``) are properties over it.  ``trace`` carries the full
    request :class:`repro.obs.Trace` (span tree, per-wave dispatch
    detail, cross-thread attribution) when the solve ran traced;
    ``trace.to_chrome_json(path)`` exports it for Perfetto."""

    def __init__(
        self,
        lam: float,
        Theta,                     # dense (p, p) — or a SparseTheta when
                                   # output resolved to "sparse"
        labels: np.ndarray,
        screen: ScreenStats | None,
        solve_seconds: float,      # device solve + verify (assembly and
                                   # dispatch-issue overhead EXCLUDED)
        solver: str,
        block_sizes: list[int] | None = None,
        route_mix: dict | None = None,  # structure class -> #blocks
        routed: bool = True,       # was the routing ladder enabled?
        # sharded-route accounting for THIS solve: {dispatched, inner_iters,
        # stalls, fallbacks} (empty when no block took the oversize route,
        # stalls = ADMM loops ended by the float32 stall stop); the
        # process-wide view is instrument counts("solver.oversize.")
        oversize: dict | None = None,
        assemble_seconds: float = 0.0,  # scatter/index-build slice
        # host seconds spent ISSUING async solver launches — the per-dispatch
        # overhead the wave packer collapses.  Reported as its own stage:
        # before it existed this time was silently folded into solve_seconds,
        # which is how a warm homotopy pass (many small reused buckets, ~6x
        # the dispatch count of a cold pass) showed a LARGER solve stage than
        # cold despite a faster wall clock (the bench_select anomaly)
        dispatch_seconds: float = 0.0,
        bytes_peak: int = 0,       # resident bytes of Theta as assembled
        output: str = "dense",     # the representation actually returned
        trace: Trace | None = None,
    ):
        self.lam = lam
        self.Theta = Theta
        self.labels = labels
        self.screen = screen
        self.solver = solver
        self.block_sizes = list(block_sizes) if block_sizes is not None else []
        self.route_mix = dict(route_mix) if route_mix is not None else {}
        self.routed = routed
        self.oversize = dict(oversize) if oversize is not None else {}
        self.bytes_peak = bytes_peak
        self.output = output
        self.trace = trace
        self._stage_seconds = {
            "screen": float(screen.seconds) if screen is not None else 0.0,
            "solve": float(solve_seconds),
            "dispatch": float(dispatch_seconds),
            "assemble": float(assemble_seconds),
        }

    def __repr__(self) -> str:
        return (
            f"GlassoResult(lam={self.lam!r}, p={len(self.labels)}, "
            f"solver={self.solver!r}, output={self.output!r})"
        )

    # -- unified timing view ------------------------------------------------

    def stages(self) -> dict[str, float]:
        """Seconds per canonical stage for THIS result: ``screen`` /
        ``solve`` / ``dispatch`` / ``assemble`` — the single source the
        legacy ``*_seconds`` properties and ``stages_us`` read from.  The
        attached ``trace`` (when present) holds the same stages as spans
        plus the nested detail no scalar can carry."""
        return dict(self._stage_seconds)

    @property
    def solve_seconds(self) -> float:
        return self._stage_seconds["solve"]

    @property
    def assemble_seconds(self) -> float:
        return self._stage_seconds["assemble"]

    @property
    def dispatch_seconds(self) -> float:
        return self._stage_seconds["dispatch"]

    @property
    def screen_seconds(self) -> float:
        """Screening-stage seconds (0.0 when screening was skipped or the
        labels were precomputed)."""
        return self._stage_seconds["screen"]

    @property
    def stages_us(self) -> dict[str, int]:
        """Per-result stage attribution in microseconds — the same values
        this result bumped into the process-wide ``engine.screen_us`` /
        ``engine.solve_us`` / ``engine.assemble_us`` counters, kept on the
        result so path consumers (``repro.select``, bench_select) can
        report where homotopy saves time per grid point."""
        return {f"{k}_us": int(v * 1e6) for k, v in self._stage_seconds.items()}

    @property
    def support(self) -> np.ndarray:
        """Estimated concentration-graph adjacency (eq. (2)).

        Sparse results derive it from per-block nonzeros — dense bool up to
        the densify cap, scipy bool CSR above it — so calling this on a
        large result does not recreate the O(p^2) allocation."""
        if isinstance(self.Theta, SparseTheta):
            return self.Theta.support()
        A = np.abs(self.Theta) > 0
        np.fill_diagonal(A, False)
        return A

    def support_edges(self) -> np.ndarray:
        """(E, 2) off-diagonal upper-triangular support edges — the payload
        form sparse serving responses carry at any p."""
        if isinstance(self.Theta, SparseTheta):
            return self.Theta.support_edges()
        r, c = np.nonzero(np.triu(self.support, k=1))
        return np.stack([r, c], axis=1).astype(np.int64) if r.size else np.zeros(
            (0, 2), dtype=np.int64
        )

    @property
    def noniterative_fraction(self) -> float:
        """Share of this solve's blocks ROUTED to a non-iterative solver
        (the routing-ladder acceptance metric; singletons included).

        0.0 when the solve ran with route=False; honors ``registry.set_route``
        re-routing.  The rare KKT-rejected blocks repaired by the iterative
        tail are NOT subtracted — track those via the ``router.fallback.*``
        counters."""
        from repro.engine.registry import route_for

        if not self.routed:
            return 0.0
        total = sum(self.route_mix.values())
        if not total:
            return 1.0
        iterative = sum(
            n for cls, n in self.route_mix.items()
            if route_for(cls) in ("iterative", "fused")
        )
        return 1.0 - iterative / total


def resolve_oversize(
    threshold: int | None, budget_mb: float | str | None, np_dtype, *,
    route: bool = True,
) -> int | None:
    """Resolve the single-device block-size cap for the oversize route.

    An explicit ``threshold`` wins; otherwise it is derived from a per-device
    memory budget in MB (``blocks.oversize_threshold``), where ``"auto"``
    asks the backend for its HBM size (``distributed.
    device_memory_budget_mb`` — None on CPU, disabling the route).  Returns
    None when oversize routing is off.  Oversize is a ROUTE, so it requires
    the routing ladder."""
    if threshold is None and budget_mb is None:
        return None
    if not route:
        raise ValueError(
            "oversize_threshold / oversize_budget_mb require route=True "
            "(the oversize class is a routing-ladder rung)"
        )
    if threshold is not None:
        return int(threshold)
    if budget_mb == "auto":
        from repro.core.distributed import device_memory_budget_mb

        budget_mb = device_memory_budget_mb()
        if budget_mb is None:
            return None
    from repro.core.blocks import oversize_threshold as _threshold_from_budget

    return _threshold_from_budget(float(budget_mb), np_dtype)


def _as_cov_operand(S):
    """Dense arrays pass through np.asarray; materialized streamed
    covariances (the gather protocol: ``gather_block``/``diag_at``) are used
    as-is — wrapping them in an object array would defeat the point."""
    return S if hasattr(S, "gather_block") else np.asarray(S)


def blockwise_inverse(
    labels: np.ndarray, Theta: np.ndarray, needed: np.ndarray | None = None
) -> np.ndarray:
    """Dense W = inv(Theta) computed block-by-block over ``labels``'
    components (Theta is block-diagonal over them by Theorem 1).

    ``needed`` (bool mask over vertices) restricts the work to components
    that intersect it.  Shared by the path warm start (merged components:
    the restriction of the old Theta is block-diagonal over its old
    sub-components, hence PD — a valid W iterate) and the serving data
    sessions (rank-k updates warm-start every surviving component).

    A block-sparse ``Theta`` produces a block-sparse W over the SAME
    components (inverses per block, reciprocal isolated diagonal) — no
    (p, p) buffer appears anywhere on the warm-start path; the executor
    gathers merged-component restrictions through ``gather_block``, whose
    cross-component entries are exact zeros."""
    if isinstance(Theta, SparseTheta):
        return _blockwise_inverse_sparse(Theta, needed)
    W = np.zeros_like(Theta)
    for comp in component_lists(labels):
        if needed is not None and not needed[comp].any():
            continue
        W[np.ix_(comp, comp)] = np.linalg.inv(Theta[np.ix_(comp, comp)])
    return W


def _blockwise_inverse_sparse(
    Theta: SparseTheta, needed: np.ndarray | None
) -> SparseTheta:
    """Block-diagonal W = inv(Theta) of a sparse result, as another
    ``SparseTheta`` (one single-row stack per needed component)."""
    from repro.core.sparse import _build_index

    stacks: list[np.ndarray] = []
    comps: list[np.ndarray] = []
    loc: list[tuple[int, int]] = []
    for c, blk in Theta.blocks():
        if needed is not None and not needed[c].any():
            continue
        comps.append(c)
        loc.append((len(stacks), 0))
        stacks.append(np.linalg.inv(blk)[None])
    iso = Theta.isolated
    vals = Theta.isolated_values
    if needed is not None and iso.size:
        keep = needed[iso]
        iso, vals = iso[keep], vals[keep]
    comp_id, pos_in = _build_index(Theta.p, comps, iso)
    return SparseTheta(
        Theta.p, Theta.dtype, stacks, comps, loc, comp_id, pos_in,
        iso, (1.0 / vals).astype(Theta.dtype, copy=False),
        densify_max=Theta.densify_max,
    )


def _result(
    plan, labels, screen_stats, Theta, seconds, solver, lam, *,
    routed: bool = True, oversize: dict | None = None,
    assemble_seconds: float = 0.0, dispatch_seconds: float = 0.0,
) -> GlassoResult:
    route_mix = {"singleton": len(plan.isolated)} if len(plan.isolated) else {}
    for b in plan.buckets:
        route_mix[b.structure] = route_mix.get(b.structure, 0) + len(b.comps)
    solve_seconds = max(
        0.0, float(seconds) - float(assemble_seconds) - float(dispatch_seconds)
    )
    bump("engine.solve_us", int(solve_seconds * 1e6))
    if screen_stats is not None:
        bump("engine.screen_us", int(float(screen_stats.seconds) * 1e6))
    return GlassoResult(
        trace=current_trace(),
        lam=float(lam),
        Theta=Theta,
        labels=labels,
        screen=screen_stats,
        solve_seconds=solve_seconds,
        solver=solver,
        block_sizes=sorted(
            (len(c) for b in plan.buckets for c in b.comps), reverse=True
        ),
        route_mix=route_mix,
        routed=routed,
        oversize=dict(oversize or {}),
        assemble_seconds=float(assemble_seconds),
        dispatch_seconds=float(dispatch_seconds),
        bytes_peak=result_nbytes(Theta),
        output="sparse" if isinstance(Theta, SparseTheta) else "dense",
    )


class Engine:
    """Reusable pipeline instance: fixed (solver, dtype, cc_backend, opts).

    Holds the per-stream executor (and thus the warm-start bucket state); the
    compiled-solver cache underneath is process-global, so engines are cheap
    to construct."""

    def __init__(
        self,
        *,
        options: EngineOptions | None = None,
        devices=None,
        **legacy_engine_kwargs,
    ):
        """``options=EngineOptions(...)`` is the configuration surface; the
        historical kwargs (``solver=``, ``route=``, ``tol=``, ...) still
        work through the shared normalization chokepoint (they warn at the
        PUBLIC wrappers — ``glasso``/``glasso_path`` — not here, so internal
        constructions stay quiet)."""
        from repro.core.solvers import WARM_START_SOLVERS, solver_spec

        opts = normalize_options(options, legacy_engine_kwargs, context="Engine")
        self.options = opts
        self.output = opts.output
        self.solver = opts.resolved_solver("bcd")
        self.dtype = opts.resolved_dtype()
        self.np_dtype = np.dtype(jnp.dtype(self.dtype).name)  # host-side twin
        self.cc_backend = opts.cc_backend
        self.stream = opts.stream   # default StreamConfig for from-data runs
        self.warm_capable = self.solver in WARM_START_SOLVERS
        self.oversize = resolve_oversize(
            opts.oversize_threshold, opts.oversize_budget_mb, self.np_dtype,
            route=opts.route,
        )
        # wave-packer resolution (EngineOptions.fused): True demands the
        # capability, "auto" turns on only for solvers that force it
        # ("fused_bcd") — buckets ROUTED "fused" via registry.set_route fuse
        # in the executor regardless of this flag
        meta = solver_spec(self.solver).meta
        if opts.fused is True and not meta.get("fused_stack"):
            raise ValueError(
                f"fused=True requires a solver with the 'fused_stack' "
                f"capability; {self.solver!r} lacks it"
            )
        fused = (
            bool(meta.get("force_fused")) if opts.fused == "auto"
            else bool(opts.fused)
        )
        self.executor = BucketExecutor(
            solver=self.solver,
            dtype=self.dtype,
            solver_opts=dict(opts.solver_opts),
            devices=devices,
            route=opts.route,
            route_check_tol=opts.route_check_tol,
            fused=fused,
        )

    def _trace_ctx(self, name: str, **attrs):
        """Root a request trace for this run — or join the ambient one
        (serving owns the root for submitted work).  ``EngineOptions
        (trace=False)`` makes the engine span-free: nothing roots, and
        ``span()`` calls below degrade to no-ops unless an outer layer
        (the server) is tracing."""
        if not self.options.trace:
            return nullcontext()
        return trace_request(name, **attrs)

    # -- stages ------------------------------------------------------------

    def screen(self, S: np.ndarray, lam: float) -> tuple[np.ndarray, ScreenStats]:
        with span("engine.screen", backend=self.cc_backend):
            return thresholded_components(S, lam, backend=self.cc_backend)

    # -- single solve ------------------------------------------------------

    def run(
        self,
        S: np.ndarray,
        lam: float,
        *,
        screen: bool = True,
        p_max: int | None = None,
        warm_W: np.ndarray | None = None,
        labels: np.ndarray | None = None,
        screen_stats: ScreenStats | None = None,
        output: str | None = None,
    ) -> GlassoResult:
        """``labels`` short-circuits the screening stage with a precomputed
        canonical partition (callers that already screened, e.g. to report
        stage timings, should not pay for the partition twice);
        ``screen_stats`` rides along when the caller has them (the streaming
        screener's stats carry tile counters a dense recount would lose).
        ``S`` may be a materialized streamed covariance (gather protocol) —
        then ``labels`` is required, since dense screening needs dense S."""
        S = _as_cov_operand(S)
        p = S.shape[0]
        with self._trace_ctx("engine.run", lam=float(lam), p=int(p)):
            screened = True
            if labels is not None:
                labels = np.asarray(labels)
                if screen_stats is None:
                    from repro.core.screening import screen_stats_from_labels

                    screen_stats = screen_stats_from_labels(
                        S, lam, labels, seconds=0.0
                    )
            elif hasattr(S, "gather_block"):
                raise ValueError(
                    "materialized covariances cannot be re-screened densely; "
                    "pass the streamed labels (see Engine.run_from_data)"
                )
            elif screen:
                labels, screen_stats = self.screen(S, lam)
            else:
                labels = np.zeros(p, dtype=np.int64)  # one global component
                screen_stats = None
                screened = False
            # classify only when routing can use the tags AND the labels are
            # a real screening partition (the screen=False pseudo-component
            # is not connected, which the classifier requires — the
            # unscreened baseline must stay on the dense iterative path)
            with span("engine.plan"):
                plan, _ = build_plan_incremental(
                    S, lam, labels, dtype=self.np_dtype,
                    classify_structures=self.executor.route and screened,
                    oversize=self.oversize if screened else None,
                )
            schedule_mod.check_capacity(
                [len(c) for b in plan.buckets for c in b.comps] or [1], p_max
            )
            out_mode = resolve_output(
                self.output if output is None else output, p
            )
            t0 = time.perf_counter()
            with span("engine.solve", lam=float(lam)):
                Theta = self.executor.solve_plan(
                    plan, float(lam), S, warm_W=warm_W, output=out_mode
                )
            seconds = time.perf_counter() - t0
            return _result(
                plan, labels, screen_stats, Theta, seconds, self.solver, lam,
                routed=self.executor.route,
                oversize=self.executor.last_oversize,
                assemble_seconds=self.executor.last_assemble_seconds,
                dispatch_seconds=self.executor.last_dispatch_seconds,
            )

    # -- lambda path -------------------------------------------------------

    def run_path(
        self,
        S: np.ndarray,
        lambdas,
        *,
        warm_start: bool = True,
        p_max: int | None = None,
        output: str | None = None,
    ) -> list[GlassoResult]:
        """Descending path: one union-find pass, diffed plans, warm starts.

        Theorem 2 guarantees nested partitions, so (a) the planner can
        snapshot every lambda from a single pass, and (b) the previous Theta
        restricted to a merged component is block-diagonal over its old
        sub-components — a valid PD warm start.  Buckets unchanged between
        consecutive lambdas skip re-padding entirely and warm-start from their
        own previous padded solutions on device."""
        S = _as_cov_operand(S)
        lambdas = list(lambdas)
        with self._trace_ctx(
            "engine.path", n_lams=len(lambdas), p=int(S.shape[0])
        ):
            with span("engine.plan"):
                path = plan_path(
                    S, lambdas, dtype=self.np_dtype,
                    classify_structures=self.executor.route,
                    oversize=self.oversize,
                )
            return self._execute_path(
                S, path, warm_start=warm_start, p_max=p_max, output=output
            )

    def _execute_path(
        self, S, path, *, warm_start: bool, p_max: int | None,
        output: str | None = None,
    ) -> list[GlassoResult]:
        """Run an already-planned path (dense or streamed) through the
        executor with bucket-level reuse and warm starts."""
        from repro.engine.registry import route_for  # local: avoid cycle at import

        results: list[GlassoResult] = []
        prev: GlassoResult | None = None
        out_mode = resolve_output(
            self.output if output is None else output, S.shape[0]
        )
        for step in path.steps:
            schedule_mod.check_capacity(
                [len(c) for b in step.plan.buckets for c in b.comps] or [1], p_max
            )
            warm_W = warm_Theta = None
            if warm_start and prev is not None and self.warm_capable:
                # warm starts only matter for iterative-routed buckets; a
                # closed-form/chordal block is solved directly regardless
                fresh = [
                    b
                    for b in step.plan.buckets
                    if not step.is_reused(b)
                    and (
                        not self.executor.route
                        or route_for(b.structure) in ("iterative", "fused")
                    )
                ]
                if fresh:
                    # the previous Theta rides along untouched: merged
                    # buckets gather their block-diagonal restriction from
                    # it (cross-component entries are exact zeros) and the
                    # executor inverts the gathered stacks batched on device
                    # — no dense (p, p) W is ever built on the host.
                    # theta_warm solvers additionally seed their inner
                    # iterates from the same stack.
                    warm_Theta = prev.Theta
            # selection-layer warm accounting (select.warm.*): one count per
            # solver-bound bucket — iterative/sharded routes only; closed-
            # form and chordal blocks are solved directly either way.
            # "reused" = the bucket resumes from its own previous padded
            # solution, "merged" = a fresh iterative bucket starting from
            # the merged-component blockwise inverse, "cold" = no warm
            # source (first grid point, warm_start=False, a solver outside
            # WARM_START_SOLVERS, or a fresh sharded block).
            warmable = warm_start and prev is not None and self.warm_capable
            for b in step.plan.buckets:
                route = (
                    route_for(b.structure) if self.executor.route else "iterative"
                )
                if route not in ("iterative", "fused", "sharded"):
                    continue
                if warmable and step.is_reused(b):
                    bump("select.warm.reused")
                elif warmable and route in ("iterative", "fused"):
                    bump("select.warm.merged")
                else:
                    bump("select.warm.cold")
            t0 = time.perf_counter()
            with span("engine.solve", lam=float(step.lam)):
                Theta = self.executor.solve_plan(
                    step.plan,
                    step.lam,
                    S,
                    warm_W=warm_W,
                    warm_Theta=warm_Theta,
                    reused_keys=step.reused_keys if warm_start else frozenset(),
                    keep_solutions=warm_start,
                    output=out_mode,
                )
            seconds = time.perf_counter() - t0
            res = _result(
                step.plan, step.labels, step.screen, Theta, seconds, self.solver,
                step.lam, routed=self.executor.route,
                oversize=self.executor.last_oversize,
                assemble_seconds=self.executor.last_assemble_seconds,
                dispatch_seconds=self.executor.last_dispatch_seconds,
            )
            results.append(res)
            prev = res
        return results

    # -- data-matrix input (out-of-core screening) -------------------------

    def run_from_data(
        self,
        X: np.ndarray,
        lam: float,
        *,
        stream=None,
        p_max: int | None = None,
        warm_W: np.ndarray | None = None,
        output: str | None = None,
    ) -> GlassoResult:
        """One solve screened straight from the (n, p) data matrix.

        The dense S never exists: ``repro.stream`` screens tile-by-tile,
        materializes only the per-component blocks, and the solve proceeds
        through the ordinary plan/execute stages (``stream`` takes a
        ``StreamConfig`` or kwargs dict)."""
        from repro.stream import stream_screen

        if stream is None:
            stream = self.stream
        with self._trace_ctx(
            "engine.run", lam=float(lam), p=int(np.shape(X)[1]), source="data"
        ):
            with span("engine.screen", backend="stream"):
                sc = stream_screen(
                    X, [lam], config=stream, oversize=self.oversize
                )
            return self.run(
                sc.S,
                lam,
                labels=sc.labels[0],
                screen_stats=sc.stats[0],
                p_max=p_max,
                warm_W=warm_W,
                output=output,
            )

    def run_path_from_data(
        self,
        X: np.ndarray,
        lambdas,
        *,
        stream=None,
        warm_start: bool = True,
        p_max: int | None = None,
        output: str | None = None,
    ) -> list[GlassoResult]:
        """A descending lambda path screened straight from X: one streaming
        screen covers the whole grid (Theorem 2 — the compacted edges above
        the grid minimum determine every partition), then the standard
        diffed-plan execution runs over materialized blocks."""
        from repro.stream import plan_path_from_screen, stream_screen

        if stream is None:
            stream = self.stream
        lambdas = list(lambdas)
        with self._trace_ctx(
            "engine.path", n_lams=len(lambdas), p=int(np.shape(X)[1]),
            source="data",
        ):
            with span("engine.screen", backend="stream"):
                sc = stream_screen(
                    X, lambdas, config=stream, oversize=self.oversize
                )
            with span("engine.plan", backend="stream"):
                path = plan_path_from_screen(
                    sc,
                    dtype=self.np_dtype,
                    classify_structures=self.executor.route,
                    oversize=self.oversize,
                )
            return self._execute_path(
                sc.S, path, warm_start=warm_start, p_max=p_max, output=output
            )
