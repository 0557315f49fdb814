"""The joint Plan->Execute engine: K-class screen -> plan -> route -> solve.

Mirrors ``repro.engine`` on the class axis:

* **Compiled cache gains K.**  Joint executables live in the SAME
  process-global compiled cache as the single-class solvers
  (``engine.executor.compiled_cached``), keyed ("__joint__", solver, size,
  K, dtype, penalty, warm, opts) — a serving mix of single-class and joint
  requests shares one cache, one lock, one hit/miss telemetry.  lam1/lam2
  are TRACED per-block vectors, so coalesced batches with mixed penalty
  strengths never recompile.

* **Async wave.**  Every bucket is dispatched (jitted vmap over the
  (n, K, size, size) stack) before anything blocks; chronologically the
  same submit-then-sync shape as ``BucketExecutor.solve_plan``.

* **Routing ladder.**  "singleton" assembles closed-form (per class
  1/(S_ii + lam1); lam2 never touches the diagonal).  IDENTICAL class
  blocks reduce the joint problem on the component exactly to ONE
  single-class problem at an effective lambda, so they fan out by union
  shape like the single-class ladder: "joint_forest" (batched forest
  closed form), "joint_chordal" (host clique-tree direct solve),
  "joint_shared" (one single-class iterative solve — 1/K of the coupled
  work).  The reduction,

      fused  lam_eff = lam1            (the symmetric optimum zeroes every
                                        difference; y = 0 is admissible)
      group  lam_eff = lam1 + lam2/sqrt(K)   off-diagonal (the group
                                        subgradient at a symmetric point is
                                        forced to sign/sqrt(K)); the
                                        DIAGONAL keeps lam1, folded in by
                                        shifting the input diagonal by
                                        lam1 - lam_eff before the solve

  is solved once and replicated across classes.  The candidate is accepted
  only on per-class sufficiency: canonical KKT against EVERY class's own
  (shifted) block at lam_eff — for a symmetric candidate that per-class
  certificate implies joint optimality (DESIGN.md Section 12), so
  near-identical misroutes can only fall back, never corrupt.
  "joint_general" (class-specific blocks) takes the K-coupled joint ADMM.

* **Verified, with fallback.**  Every CONDITIONAL route — the shared
  forest/chordal/single-class candidates, whose optimality rests on the
  identical-block reduction — is per-class KKT-certified, and rejections
  re-dispatch to the joint ADMM warm-started from the rejected candidate
  (``joint.fallbacks`` + per-class ``router.fallback.*``).  The joint ADMM
  tail itself is TRUSTED on convergence, the same contract as the
  single-class executor's bcd/pg/admm tail: an absolute W-space KKT gate at
  tol*max|S| is unreachable for iterative solves on badly-scaled blocks
  (dW ~ W dTheta W amplifies a Theta-space residual by ||W||^2 ~ max|S|^2),
  so gating the tail would misfire exactly where the solver is fine.
  ``verify_tail=True`` opts in to the exact host joint-KKT check of every
  tail block (``repro.joint.kkt``; failures re-dispatch with a 10x
  iteration budget, counted as above) — the property tests run with it on
  well-scaled problems.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.instrument import bump, timed_dispatch
from repro.obs.trace import span, trace_request
from repro.core.solvers.closed_form import kkt_ok_stack
from repro.core.solvers.protocol import solver_spec
from repro.core.sparse import resolve_output
from repro.engine.executor import compiled_cached
from repro.engine.options import EngineOptions, normalize_options
from repro.joint.blocks import (
    JointPlan,
    assemble_joint,
    assemble_joint_sparse,
    build_joint_plan,
)
from repro.joint.kkt import joint_kkt_residual
from repro.joint.screen import (
    JointScreenStats,
    _check_penalty,
    joint_thresholded_components,
)
from repro.kernels.tree_glasso.ops import glasso_forest_stack


def joint_effective_lambda(lam1, lam2, K: int, *, penalty: str):
    """Effective single-class lambda of an identical-block joint component."""
    if penalty == "group":
        return lam1 + lam2 / float(np.sqrt(K))  # a weak scalar keeps the dtype
    return lam1 + 0.0 * lam2


def compiled_joint_solver(
    solver: str, size: int, K: int, dtype, penalty: str, *,
    warm: bool = False, opts_key: tuple = (),
):
    """Fetch-or-build the jitted batched joint solver for one (size, K)
    bucket family.  Signature: fn(blocks (n, K, size, size), lam1s (n,),
    lam2s (n,)[, W0, Theta0])."""
    key = (
        "__joint__", solver, int(size), int(K), jnp.dtype(dtype).name,
        penalty, bool(warm), opts_key,
    )

    def build():
        solver_fn = solver_spec(solver).fn
        opts = dict(opts_key)
        if warm:

            def run(blocks, lam1s, lam2s, W0, T0):
                return jax.vmap(
                    lambda Sb, l1, l2, w0, t0: solver_fn(
                        Sb, l1, l2, penalty=penalty, W0=w0, Theta0=t0, **opts
                    )
                )(blocks, lam1s, lam2s, W0, T0)

        else:

            def run(blocks, lam1s, lam2s):
                return jax.vmap(
                    lambda Sb, l1, l2: solver_fn(
                        Sb, l1, l2, penalty=penalty, **opts
                    )
                )(blocks, lam1s, lam2s)

        return jax.jit(run)

    return compiled_cached(key, build)


def compiled_joint_symmetric(
    size: int, K: int, dtype, penalty: str, *, tol: float,
    inner: str = "forest", opts_key: tuple = (),
):
    """Fetch-or-build the batched shared-component solver + per-class
    verifier.

    Returned callable: fn(blocks (n, K, size, size), lam1s (n,), lam2s (n,))
    -> (thetas (n, K, size, size), ok (n,)).  ONE single-class solve of the
    class-mean (diag-shifted) block at lam_eff — the forest closed form for
    ``inner="forest"``, else the named single-class iterative solver (the
    "iterative single-class" path: 1/K of the coupled work) — replicated
    across K; ok certifies the canonical KKT residual of the SAME candidate
    against every class's own shifted block, which for a symmetric
    candidate implies JOINT optimality (module docstring)."""
    key = (
        "__joint_symmetric__", inner, int(size), int(K),
        jnp.dtype(dtype).name, penalty, float(tol), opts_key,
    )

    def build():
        if inner == "forest":
            solve = glasso_forest_stack
        else:
            solver_fn = solver_spec(inner).fn
            opts = dict(opts_key)

            def solve(eff, lam_eff):
                return jax.vmap(
                    lambda Sb, lm: solver_fn(Sb, lm, **opts)
                )(eff, lam_eff)

        def run(blocks, lam1s, lam2s):
            n = blocks.shape[0]
            lam_eff = joint_effective_lambda(lam1s, lam2s, K, penalty=penalty)
            shift = lam1s - lam_eff  # 0 for fused
            eye = jnp.eye(size, dtype=blocks.dtype)
            adjusted = blocks + shift[:, None, None, None] * eye
            eff = jnp.mean(adjusted, axis=1)
            theta = solve(eff, lam_eff)
            flat = adjusted.reshape(n * K, size, size)
            flat_theta = jnp.broadcast_to(
                theta[:, None], (n, K, size, size)
            ).reshape(n * K, size, size)
            ok = kkt_ok_stack(
                flat, jnp.repeat(lam_eff, K), flat_theta, tol=tol
            ).reshape(n, K).all(axis=1)
            return (
                jnp.broadcast_to(theta[:, None], (n, K, size, size)),
                ok,
            )

        return jax.jit(run)

    return compiled_cached(key, build)


def solve_joint_chordal_bucket(
    bucket, plan, *, tol: float
) -> tuple[np.ndarray, np.ndarray]:
    """Host clique-tree direct solve of one identical-block chordal bucket.

    Per block: the class-mean (diag-shifted) sub-block solves ONCE through
    the single-class chordal machinery at lam_eff; the candidate replicates
    across classes and must pass the canonical host KKT against EVERY
    class's own shifted block.  Returns (padded (n, K, size, size) stack,
    per-block ok) — failures join the caller's joint-ADMM fallback."""
    from repro.core.solvers.closed_form import (
        glasso_chordal_host,
        kkt_residual_host,
    )

    n = len(bucket.comps)
    K = plan.K
    lam_eff = float(
        joint_effective_lambda(plan.lam1, plan.lam2, K, penalty=plan.penalty)
    )
    shift = plan.lam1 - lam_eff
    out = np.empty_like(np.asarray(bucket.blocks))
    ok = np.zeros(n, dtype=bool)
    for i, comp in enumerate(bucket.comps):
        b = len(comp)
        cls_blocks = np.asarray(bucket.blocks[i][:, :b, :b], dtype=np.float64)
        cls_blocks = cls_blocks + shift * np.eye(b)
        eff = cls_blocks.mean(axis=0)
        padded = np.broadcast_to(
            np.eye(bucket.size, dtype=out.dtype) / (1.0 + plan.lam1),
            (K, bucket.size, bucket.size),
        ).copy()
        try:
            theta = glasso_chordal_host(eff, lam_eff)
            res = max(
                kkt_residual_host(cls_blocks[k], lam_eff, theta)
                for k in range(K)
            )
            scale = max(1.0, float(np.abs(cls_blocks).max()))
            ok[i] = res <= tol * scale
            padded[:, :b, :b] = theta
        except (ValueError, np.linalg.LinAlgError):
            ok[i] = False
        out[i] = padded
    return out, ok


class JointEngine:
    """Reusable K-class pipeline: fixed (solver, dtype, cc_backend, route).

    The penalty and (lam1, lam2) are per-call — they are request data, like
    lambda on the single-class path."""

    def __init__(
        self,
        *,
        options: EngineOptions | None = None,
        **legacy_engine_kwargs,
    ):
        """Configured by one ``EngineOptions`` (``options=``); the historical
        kwargs (``solver=``, ``route=``, ``verify_tail=``, solver opts)
        normalize through the shared chokepoint without warning — the public
        ``joint_glasso`` wrapper owns the deprecation signal."""
        opts = normalize_options(
            options, legacy_engine_kwargs, context="JointEngine"
        )
        self.options = opts
        solver = opts.resolved_solver("joint_admm")
        spec = solver_spec(solver)
        if not spec.meta.get("joint"):
            raise ValueError(
                f"solver {solver!r} is not a joint solver (spec.meta['joint'])"
            )
        self.output = opts.output
        self.last_assemble_seconds = 0.0
        self.solver = solver
        self.dtype = opts.resolved_dtype()
        self.np_dtype = np.dtype(jnp.dtype(self.dtype).name)
        self.cc_backend = opts.cc_backend
        self.route = opts.route
        self.route_check_tol = opts.route_check_tol
        self.verify_tail = opts.verify_tail
        self.stream = opts.stream
        solver_opts = dict(opts.solver_opts)
        self.solver_opts = solver_opts
        self._opts_key = tuple(sorted(solver_opts.items()))
        # the "joint_shared" rung's single-class solver (identical blocks,
        # general union shape): bcd — the same solver the per-class
        # baseline would pay K times — fed the subset of the joint solver's
        # options it understands (tol travels; admm-specific knobs do not)
        self.effective_solver = "bcd"
        import inspect

        from repro.core.solvers import SOLVERS

        eff_accept = set(
            inspect.signature(SOLVERS[self.effective_solver]).parameters
        )
        self._effective_opts_key = tuple(
            sorted(
                (k, v) for k, v in solver_opts.items() if k in eff_accept
            )
        )

    def _trace_ctx(self, name: str, **attrs):
        """Root a request trace — or join the ambient one (the serving
        batcher owns the root for submitted joint work).  Mirrors
        ``Engine._trace_ctx``; ``EngineOptions(trace=False)`` keeps the
        joint engine span-free."""
        from contextlib import nullcontext

        if not self.options.trace:
            return nullcontext()
        return trace_request(name, **attrs)

    # -- stages ------------------------------------------------------------

    def screen(
        self, Ss, lam1: float, lam2: float, *, penalty: str
    ) -> tuple[np.ndarray, JointScreenStats]:
        with span("engine.screen", backend=self.cc_backend, kind="joint"):
            return joint_thresholded_components(
                Ss, lam1, lam2, penalty=penalty, backend=self.cc_backend
            )

    def plan(
        self, Ss, lam1: float, lam2: float, labels, *, penalty: str,
        classify: bool | None = None,
    ) -> JointPlan:
        if classify is None:
            classify = self.route
        with span("engine.plan", kind="joint"):
            return build_joint_plan(
                Ss, lam1, lam2, labels, penalty=penalty, dtype=self.np_dtype,
                classify_structures=classify,
            )

    # -- solve -------------------------------------------------------------

    def run(
        self,
        Ss,
        lam1: float,
        lam2: float = 0.0,
        *,
        penalty: str = "group",
        screen: bool = True,
        labels: np.ndarray | None = None,
        screen_stats: JointScreenStats | None = None,
        output: str | None = None,
    ):
        """One joint solve; see ``repro.joint.api.joint_glasso`` for the
        user-facing wrapper and result object."""
        from repro.joint.api import _joint_result

        _check_penalty(penalty)
        Ss = [S if hasattr(S, "gather_block") else np.asarray(S) for S in Ss]
        if len({S.shape for S in Ss}) != 1:
            raise ValueError("all class covariances must share one shape")
        p = Ss[0].shape[0]
        with self._trace_ctx(
            "engine.joint", lam1=float(lam1), lam2=float(lam2),
            K=len(Ss), p=int(p),
        ):
            screened = True
            if labels is not None:
                labels = np.asarray(labels)
            elif any(hasattr(S, "gather_block") for S in Ss):
                raise ValueError(
                    "materialized covariances cannot be re-screened densely; "
                    "pass the streamed labels (see JointEngine.run_from_data)"
                )
            elif screen:
                labels, screen_stats = self.screen(
                    Ss, lam1, lam2, penalty=penalty
                )
            else:
                labels = np.zeros(p, dtype=np.int64)
                screen_stats = None
                screened = False
            plan = self.plan(
                Ss, lam1, lam2, labels, penalty=penalty,
                classify=self.route and screened,
            )
            out_mode = resolve_output(
                self.output if output is None else output, p
            )
            t0 = time.perf_counter()
            with span("engine.solve", kind="joint"):
                Theta, fallbacks = self.solve_plan(plan, Ss, output=out_mode)
            seconds = time.perf_counter() - t0
            return _joint_result(
                plan, labels, screen_stats, Theta, seconds, self.solver,
                routed=self.route, fallbacks=fallbacks,
                assemble_seconds=self.last_assemble_seconds,
            )

    def run_from_data(
        self,
        Xs,
        lam1: float,
        lam2: float = 0.0,
        *,
        penalty: str = "group",
        stream=None,
        output: str | None = None,
    ):
        """One joint solve screened straight from the per-class (n_k, p)
        data matrices — no class's dense S ever exists (``repro.joint.
        stream``)."""
        from repro.joint.stream import joint_stream_screen

        if stream is None:
            stream = self.stream
        with self._trace_ctx(
            "engine.joint", lam1=float(lam1), lam2=float(lam2), K=len(Xs),
            source="data",
        ):
            with span("engine.screen", backend="stream", kind="joint"):
                sc = joint_stream_screen(
                    Xs, lam1, lam2, penalty=penalty, config=stream
                )
            return self.run(
                sc.S, lam1, lam2, penalty=penalty,
                labels=sc.labels, screen_stats=sc.stats, output=output,
            )

    def solve_plan(
        self, plan: JointPlan, Ss, *, output: str = "dense"
    ) -> tuple[np.ndarray, int]:
        """Dispatch all buckets async, verify, repair, assemble.

        Returns (Theta, fallbacks for THIS solve) — Theta is the dense
        (K, p, p) stack, or a ``JointSparseTheta`` over the bucket solution
        stacks when ``output="sparse"`` (no (K, p, p) allocation)."""
        from repro.engine.registry import route_for

        if self.route and len(plan.isolated):
            bump("router.route.singleton", int(len(plan.isolated)))
        pending = []  # (bucket, out, ok)
        for bucket in plan.buckets:
            n = len(bucket.comps)
            route = route_for(bucket.structure) if self.route else "iterative"
            if self.route:
                bump(f"router.route.{bucket.structure}", n)
            if route == "chordal" and bucket.structure == "joint_chordal":
                # host direct solve: no device round-trip for the candidate
                # (the padded class stack is only re-read on fallback, from
                # the host copy the bucket already holds)
                (out, ok), _ = timed_dispatch(
                    solve_joint_chordal_bucket,
                    bucket, plan, tol=self.route_check_tol,
                )
                bump("joint.dispatches")
                bump("joint.closed_form_blocks", n)
                pending.append([bucket, out, ok])
                continue
            stacked = jnp.asarray(bucket.blocks, self.dtype)
            lam1s = jnp.full((n,), plan.lam1, self.dtype)
            lam2s = jnp.full((n,), plan.lam2, self.dtype)
            if route == "closed_form" and bucket.structure == "joint_forest":
                fn = compiled_joint_symmetric(
                    bucket.size, plan.K, self.dtype, plan.penalty,
                    tol=self.route_check_tol, inner="forest",
                )
                (out, ok), _ = timed_dispatch(fn, stacked, lam1s, lam2s)
                bump("joint.dispatches")
                bump("joint.closed_form_blocks", n)
            elif bucket.structure == "joint_shared" and self.route:
                # identical blocks, general union shape: ONE single-class
                # iterative solve at lam_eff instead of the K-coupled ADMM
                fn = compiled_joint_symmetric(
                    bucket.size, plan.K, self.dtype, plan.penalty,
                    tol=self.route_check_tol, inner=self.effective_solver,
                    opts_key=self._effective_opts_key,
                )
                (out, ok), _ = timed_dispatch(fn, stacked, lam1s, lam2s)
                bump("joint.dispatches")
                bump("joint.shared_blocks", n)
            else:
                fn = compiled_joint_solver(
                    self.solver, bucket.size, plan.K, self.dtype,
                    plan.penalty, opts_key=self._opts_key,
                )
                out, _ = timed_dispatch(fn, stacked, lam1s, lam2s)
                ok = None
                bump("joint.dispatches")
            pending.append([bucket, out, ok])

        # single synchronization point for the primary wave
        with span("engine.barrier"):
            jax.block_until_ready(
                [p[1] for p in pending if isinstance(p[1], jax.Array)]
            )
        # verify every bucket, DISPATCH all repairs, only then block once
        # more — repairs form their own async wave instead of serializing
        # (the single-class executor's repair shape)
        fallbacks = 0
        solutions = []
        repairs = []  # (solutions index, row idx, in-flight re-solve)
        for bucket, out, ok in pending:
            out = np.asarray(out)
            if ok is not None:  # conditional-route candidates: verdicts
                idx = np.flatnonzero(~np.asarray(ok))
            elif self.verify_tail:  # opt-in: exact host joint-KKT verdicts
                bad = [
                    i
                    for i in range(out.shape[0])
                    if not self._admm_ok(bucket.blocks[i], out[i], plan)
                ]
                idx = np.asarray(bad, dtype=np.int64)
            else:  # the iterative tail is trusted on convergence
                idx = np.empty(0, dtype=np.int64)
            if idx.size:
                fallbacks += int(idx.size)
                bump("joint.fallbacks", int(idx.size))
                bump(f"router.fallback.{bucket.structure}", int(idx.size))
                fixed = self._dispatch_fallback(
                    bucket, plan, np.asarray(bucket.blocks)[idx],
                    np.full(idx.size, plan.lam1), np.full(idx.size, plan.lam2),
                    out[idx],
                )
                out = np.array(out)
                repairs.append((len(solutions), idx, fixed))
            solutions.append(out)
        if repairs:
            jax.block_until_ready([r[2] for r in repairs])
            for pos, idx, fixed in repairs:
                solutions[pos][idx] = np.asarray(fixed)
        t0 = time.perf_counter()
        with span("engine.assemble", output=output):
            if output == "sparse":
                Theta = assemble_joint_sparse(plan, solutions, Ss)
            else:
                Theta = assemble_joint(plan, solutions, Ss)
        self.last_assemble_seconds = time.perf_counter() - t0
        bump("engine.assemble_us", int(self.last_assemble_seconds * 1e6))
        return Theta, fallbacks

    def _admm_ok(self, S_stack: np.ndarray, theta: np.ndarray, plan) -> bool:
        scale = max(1.0, float(np.abs(S_stack).max()))
        res = joint_kkt_residual(
            S_stack, theta, plan.lam1, plan.lam2, penalty=plan.penalty
        )
        return res <= self.route_check_tol * scale

    def _dispatch_fallback(
        self, bucket, plan, blocks, lam1s, lam2s, candidates
    ):
        """Re-dispatch rejected candidates to the joint ADMM, warm-started
        from the rejected candidate (its per-class inverse is the W seed,
        the candidate itself the Theta seed), with a 10x iteration budget
        and 10x tighter inner tolerance — the joint analog of
        ``executor.dispatch_repair``.  With lam2 = 0 this IS K independent
        single-class re-solves (the prox decouples), i.e. the iterative
        single-class fallback."""
        opts = dict(self._opts_key)
        # 10x the configured budget, floored at a full default budget — a
        # starved caller's repair must not inherit the starvation
        opts["max_iter"] = max(10 * int(opts.get("max_iter", 2000)), 5000)
        opts["tol"] = min(float(opts.get("tol", 1e-7)), 1e-7) / 10.0
        sub = jnp.asarray(blocks, self.dtype)
        cand = jnp.asarray(candidates, self.dtype)
        W0 = jnp.linalg.inv(cand)
        finite = jnp.all(jnp.isfinite(W0), axis=(1, 2, 3), keepdims=True)
        eye = jnp.eye(bucket.size, dtype=self.dtype)
        cold_W = sub + jnp.asarray(lam1s, self.dtype)[:, None, None, None] * eye
        diag = jnp.diagonal(sub, axis1=2, axis2=3)
        cold_T = jnp.where(
            jnp.eye(bucket.size, dtype=bool),
            (1.0 / (diag + jnp.asarray(lam1s, self.dtype)[:, None, None]))[
                ..., None
            ]
            * jnp.eye(bucket.size, dtype=self.dtype),
            0.0,
        )
        W0 = jnp.where(finite, W0, cold_W)
        T0 = jnp.where(finite, cand, cold_T)
        fn = compiled_joint_solver(
            self.solver, bucket.size, plan.K, self.dtype, plan.penalty,
            warm=True, opts_key=tuple(sorted(opts.items())),
        )
        bump("joint.dispatches")
        out, _ = timed_dispatch(
            fn,
            sub, jnp.asarray(lam1s, self.dtype), jnp.asarray(lam2s, self.dtype),
            W0, T0,
        )
        return out
