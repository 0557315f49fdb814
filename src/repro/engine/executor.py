"""Async bucket executor: place -> dispatch -> (only then) block -> assemble.

Design points, each mapped to a paper/ROADMAP concern:

* **Compiled-solver cache.**  One jitted ``vmap``-ed solver per
  (solver, bucket size, dtype, warm?, opts) key, shared process-wide — a
  lambda path, a benchmark sweep, and every concurrent serving request reuse
  the same executables.  lam is a TRACED per-block vector, so neither a new
  lambda nor a coalesced batch with mixed lambdas recompiles.  Hits/misses are
  counted (``executor.compiled_hit`` / ``executor.compiled_miss``).

* **Async dispatch.**  JAX dispatch is asynchronous; the executor submits
  every bucket of a plan (LPT-placed across local devices when there are
  several — ``schedule.lpt_assign`` with the b^3 cost model, the paper's
  footnote-4 clubbing) and only synchronizes at assembly
  (``jax.block_until_ready`` on the batch of results).  Serial host loops
  around one-bucket-at-a-time ``np.asarray`` calls are gone.

* **Warm-start donation.**  W0 stacks are donated to the solver call on
  backends that support buffer donation (TPU/GPU), so a lambda path does not
  hold two copies of the largest bucket's iterate.

* **Structure-routed solver ladder.**  Each bucket carries the structure
  class the planner assigned (``engine.structure``); ``registry.route_for``
  maps it to a route: "closed_form" (pair/tree — the batched Pallas forest
  kernel plus an in-jit KKT check), "chordal" (host clique-tree direct
  solve), or "iterative" (the configured bcd/pg/admm solver).  Non-iterative
  routes are VERIFIED: the closed forms satisfy the edge KKT by
  construction, but non-edge dual feasibility can fail on adversarial
  matrices, so blocks whose residual exceeds ``route_check_tol`` are
  re-dispatched to the iterative solver (``router.fallback.*`` counters).
  Routing changes cost, never the answer.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blocks as blocks_mod
from repro.core.instrument import bump, counts, timed_dispatch
from repro.core.schedule import lpt_assign
from repro.obs.trace import span
from repro.core.solvers import SOLVERS, WARM_START_SOLVERS
from repro.core.solvers.closed_form import (
    glasso_chordal_host,
    glasso_forest_stack,
    kkt_ok_stack,
    kkt_residual_host,
)

_CACHE_LOCK = threading.Lock()
_COMPILED: dict[tuple, Any] = {}


def _donate_supported() -> bool:
    return jax.default_backend() not in ("cpu",)


def _validate_solver_opts(solver: str, opts: dict) -> None:
    """Reject unknown solver kwargs up front — inside jit/vmap they surface
    as an opaque TypeError at the first bucket dispatch."""
    import inspect

    try:
        params = inspect.signature(SOLVERS[solver]).parameters
    except (TypeError, ValueError):  # jit wrapper without a signature
        return
    accepted = {
        n for n, p in params.items()
        if p.kind in (p.KEYWORD_ONLY, p.POSITIONAL_OR_KEYWORD)
    } - {"S", "lam"}
    unknown = sorted(set(opts) - accepted)
    if unknown:
        raise TypeError(
            f"solver {solver!r} does not accept option(s) {unknown}; "
            f"accepted: {sorted(accepted)}"
        )


def _theta_warm(solver: str) -> bool:
    """Does this solver consume a Theta-side seed alongside W0?  (Spec meta;
    saves the solver re-inverting a W0 the caller derived from a Theta it
    already held.)"""
    from repro.core.solvers import solver_spec

    return bool(solver_spec(solver).meta.get("theta_warm"))


def compiled_bucket_solver(
    solver: str, size: int, dtype, *, warm: bool, warm_theta: bool = False,
    opts_key: tuple = ()
):
    """Fetch-or-build the jitted batched solver for one bucket shape family.

    Signature of the returned callable:
        fn(blocks[n,size,size], lams[n])                 warm=False
        fn(blocks[n,size,size], lams[n], W0[n,...])      warm=True (W0 donated
                                                         off-CPU)
        fn(blocks[n,size,size], lams[n], W0, Theta0)     warm_theta=True too —
                                                         solvers whose spec
                                                         consumes the Theta
                                                         seed directly

    Every returned callable enforces the MIN-BATCH-2 rule (``waves.
    min_batch2``): a single-lane stack is duplicated to 2 and the result
    sliced back, because XLA specializes away unit batch dims and the
    resulting codegen differs from the same lane at batch >= 2 by 1 ulp.
    Pinning every launch to batch >= 2 is what makes results independent of
    batch size — the invariant the wave packer's bitwise fused == unfused
    equality stands on.
    """
    key = (
        solver, int(size), jnp.dtype(dtype).name, bool(warm), bool(warm_theta),
        opts_key,
    )
    with _CACHE_LOCK:
        fn = _COMPILED.get(key)
        if fn is not None:
            bump("executor.compiled_hit")
            return fn
        bump("executor.compiled_miss")
        from repro.engine.waves import min_batch2  # local: avoid cycle

        solver_fn = SOLVERS[solver]
        opts = dict(opts_key)
        if warm and warm_theta:

            def run(blocks, lams, W0, T0):
                return jax.vmap(
                    lambda Sb, lm, w0, t0: solver_fn(
                        Sb, lm, W0=w0, Theta0=t0, **opts
                    )
                )(blocks, lams, W0, T0)

            jitted = jax.jit(run, donate_argnums=(2,) if _donate_supported() else ())
        elif warm:

            def run(blocks, lams, W0):
                return jax.vmap(
                    lambda Sb, lm, w0: solver_fn(Sb, lm, W0=w0, **opts)
                )(blocks, lams, W0)

            jitted = jax.jit(run, donate_argnums=(2,) if _donate_supported() else ())
        else:

            def run(blocks, lams):
                return jax.vmap(lambda Sb, lm: solver_fn(Sb, lm, **opts))(
                    blocks, lams
                )

            jitted = jax.jit(run)

        def fn(*args, _jitted=jitted):
            return min_batch2(_jitted, *args)

        _COMPILED[key] = fn
        return fn


def compiled_closed_form(size: int, dtype, *, tol: float, verify: bool = True):
    """Fetch-or-build the jitted batched closed-form forest solver + verifier.

    Returned callable: fn(blocks[n,size,size], lams[n]) -> (Theta[n,...],
    ok[n]) where ok certifies the KKT residual within tol (scaled by max|S|).
    ``verify=False`` skips the batched-inverse check and returns ok=True —
    sound ONLY for the "pair" class, where the closed form has no non-edge
    dual constraints to violate (a 2x2 support is complete), so it is exact
    by construction.  Shares the process-global compiled cache with the
    iterative solvers, so serving, paths, and benchmarks reuse one
    executable per (size, dtype)."""
    key = (
        "__closed_form__", int(size), jnp.dtype(dtype).name, float(tol), verify
    )
    with _CACHE_LOCK:
        fn = _COMPILED.get(key)
        if fn is not None:
            bump("executor.compiled_hit")
            return fn
        bump("executor.compiled_miss")

        def run(blocks, lams):
            thetas = glasso_forest_stack(blocks, lams)
            if verify:
                ok = kkt_ok_stack(blocks, lams, thetas, tol=tol)
            else:
                ok = jnp.ones(blocks.shape[0], dtype=bool)
            return thetas, ok

        fn = jax.jit(run)
        _COMPILED[key] = fn
        return fn


def dispatch_repair(
    solver: str,
    dtype,
    opts_key: tuple,
    size: int,
    blocks: np.ndarray,
    lams: np.ndarray,
    candidates,
):
    """Async re-dispatch of rejected fast-path blocks to the iterative tail.

    Shared by the executor and the serving batcher so repairs behave
    identically everywhere: the rejected candidate is PD (the KKT check
    treats non-PD as an infinite residual), just dual-infeasible — so its
    inverse is an excellent W iterate to warm-start from, typically cutting
    the repair to a few sweeps.  ``lams`` is per-block (serving repairs can
    mix lambdas)."""
    sub = jnp.asarray(np.asarray(blocks), dtype)
    lams_d = jnp.asarray(np.asarray(lams), dtype)
    warm = solver in WARM_START_SOLVERS
    theta_warm = warm and _theta_warm(solver)
    W0 = T0 = None
    if warm:
        cand = jnp.asarray(np.asarray(candidates), dtype)
        W0 = jnp.linalg.inv(cand)
        # a candidate can be rejected BECAUSE it is singular: those rows
        # get the cold start W = S + lam*I instead of a NaN iterate
        finite = jnp.all(jnp.isfinite(W0), axis=(1, 2), keepdims=True)
        cold = sub + lams_d[:, None, None] * jnp.eye(size, dtype=dtype)
        W0 = jnp.where(finite, W0, cold)
        if theta_warm:
            # the candidate IS the Theta seed — passing it spares the solver
            # inverting W0 right back (a second O(size^3) for nothing);
            # fallen-back rows get the matching cold Theta seed
            eye = jnp.eye(size, dtype=bool)
            diag = jnp.diagonal(sub, axis1=1, axis2=2)
            cold_T = jnp.where(
                eye[None], (1.0 / (diag + lams_d[:, None]))[:, :, None], 0.0
            )
            T0 = jnp.where(finite, cand, cold_T)
    fn = compiled_bucket_solver(
        solver, size, dtype, warm=warm, warm_theta=theta_warm, opts_key=opts_key
    )
    bump("executor.dispatches")
    if theta_warm:
        out, _ = timed_dispatch(fn, sub, lams_d, W0, T0)
    elif warm:
        out, _ = timed_dispatch(fn, sub, lams_d, W0)
    else:
        out, _ = timed_dispatch(fn, sub, lams_d)
    return out


def solve_sharded_bucket(
    bucket: blocks_mod.Bucket,
    lams: np.ndarray,
    S,
    *,
    solver: str,
    dtype,
    opts_key: tuple,
    tol: float,
    warm_thetas: list | None = None,
) -> tuple[np.ndarray, dict]:
    """Mesh-spanning solve of one oversize bucket (route "sharded").

    Per block: shard-direct gather (``stream.materialize.shard_gather`` —
    the (b, b) block streams row-chunk by row-chunk into device shards, a
    full host copy never exists), the sharded ADMM
    (``core.solvers.glasso_sharded``), and its distributed KKT verdict.
    Blocks whose residual exceeds ``tol * max(1, max|S|)`` fall back to a
    SINGLE-DEVICE iterative solve warm-started from the rejected candidate
    (the shared ``dispatch_repair``) — correct, but memory-bound, so it is
    counted loudly: ``solver.oversize.fallbacks`` + ``router.fallback.
    oversize``.  Returns (padded (n, size, size) Theta stack, info dict
    {dispatched, inner_iters, stalls, fallbacks} for ``GlassoResult.oversize``).

    Shared by the engine executor and the serving batcher, like
    ``dispatch_repair`` — oversize admission behaves identically everywhere.
    """
    from repro.core.jax_compat import local_device_mesh
    from repro.core.solvers.sharded import glasso_sharded
    from repro.stream.materialize import shard_gather

    mesh = local_device_mesh()
    np_dtype = np.dtype(jnp.dtype(dtype).name)
    n = len(bucket.comps)
    out = np.zeros((n, bucket.size, bucket.size), dtype=np_dtype)
    info = {"dispatched": 0, "inner_iters": 0, "stalls": 0, "fallbacks": 0}
    failed: list[int] = []
    for i, comp in enumerate(bucket.comps):
        b = len(comp)
        lam = float(lams[i])
        S_sh = shard_gather(S, comp, mesh, dtype=np_dtype)
        theta0 = None if warm_thetas is None else warm_thetas[i]
        res, _ = timed_dispatch(
            glasso_sharded, S_sh, lam, mesh=mesh, b=b, Theta0=theta0,
            kkt_target=tol,
        )
        info["dispatched"] += 1
        info["inner_iters"] += res.inner_iters
        info["stalls"] += res.stalls
        padded = np.eye(bucket.size, dtype=np_dtype) / (1.0 + lam)
        padded[:b, :b] = res.Theta
        out[i] = padded
        scale = max(1.0, res.s_max)
        if not res.kkt_residual <= tol * scale:  # NaN-safe: not (nan <= x)
            failed.append(i)
    if failed:
        idx = np.asarray(failed)
        info["fallbacks"] = int(idx.size)
        bump("solver.oversize.fallbacks", int(idx.size))
        bump(f"router.fallback.{bucket.structure}", int(idx.size))
        blocks = np.stack(
            [
                blocks_mod.pad_block(
                    blocks_mod.gather_submatrix(
                        S, bucket.comps[k], dtype=np_dtype
                    ),
                    bucket.size,
                )
                for k in idx
            ]
        )
        fixed = dispatch_repair(
            solver, dtype, opts_key, bucket.size, blocks,
            np.asarray(lams)[idx], out[idx],
        )
        out[idx] = np.asarray(jax.block_until_ready(fixed))
    return out, info


def solve_chordal_bucket(
    bucket: blocks_mod.Bucket, lams: np.ndarray, *, tol: float
) -> tuple[np.ndarray, np.ndarray]:
    """Host clique-tree direct solve of one chordal bucket.

    Returns (padded Theta stack, per-block ok).  Cost is sum |C|^3 over
    maximal cliques per block — the chordal analog of the zero-fill sparse
    Cholesky — versus hundreds of O(size^3) iterations on the iterative
    path.  Verification failures are left to the caller's fallback."""
    n = bucket.blocks.shape[0]
    thetas = np.empty_like(np.asarray(bucket.blocks))
    ok = np.zeros(n, dtype=bool)
    for i, comp in enumerate(bucket.comps):
        b = len(comp)
        lam = float(lams[i])
        blk = np.asarray(bucket.blocks[i][:b, :b])
        padded = np.eye(bucket.size, dtype=thetas.dtype) / (1.0 + lam)
        try:
            theta = glasso_chordal_host(blk, lam)
            res = kkt_residual_host(blk, lam, theta)
            scale = max(1.0, float(np.abs(blk).max()))
            ok[i] = res <= tol * scale
            padded[:b, :b] = theta
        except (ValueError, np.linalg.LinAlgError):
            ok[i] = False
        thetas[i] = padded
    return thetas, ok


def compiled_cached(key: tuple, builder):
    """Fetch-or-build an arbitrary executable in the process-global compiled
    cache (hit/miss counted like every other entry).  The extension point
    the JOINT executor uses: its bucket keys gain the class count K and the
    penalty, but the cache, its lock, and its stats stay one thing — a
    serving mix of single-class and joint requests shares one steady
    state."""
    with _CACHE_LOCK:
        fn = _COMPILED.get(key)
        if fn is not None:
            bump("executor.compiled_hit")
            return fn
        bump("executor.compiled_miss")
        fn = builder()
        _COMPILED[key] = fn
        return fn


def compiled_cache_stats() -> dict[str, int]:
    return {
        "entries": len(_COMPILED),
        "hits": counts().get("executor.compiled_hit", 0),
        "misses": counts().get("executor.compiled_miss", 0),
    }


@dataclass
class _Pending:
    bucket: blocks_mod.Bucket
    out: Any                       # jax array (device routes) or np (chordal)
    ok: Any = None                 # per-block KKT flags for verified routes
    stacked: Any = None            # device input stack (reuse cache)
    key: tuple = ()
    repair: Any = None             # (row idx, in-flight iterative re-solve)


@dataclass
class _FusedLane:
    """One fused-eligible bucket deferred into a (device, bin) megabatch.

    The wave packer collects these during the bucket loop and launches one
    ``kernels.bucket_glasso`` call per group; ``pending.out`` receives the
    lane's (n, size, size) slice of the packed result."""

    pending: _Pending
    size: int                      # source bucket size (bin >= size)
    n: int                         # blocks in the bucket
    lams: Any                      # (n,) device lambda vector
    W0: Any = None                 # warm covariance stack or None (cold)
    T0: Any = None                 # warm Theta stack or None (cold)
    scales: Any = None             # (n,) source-shape convergence scales


@dataclass
class BucketExecutor:
    """Solves plans; owns the per-path warm-start state.

    One instance per logical stream of related solves (a ``glasso`` call, a
    ``glasso_path``, one serving batch); the compiled cache underneath is
    global."""

    solver: str = "bcd"
    dtype: Any = jnp.float64
    solver_opts: dict = field(default_factory=dict)
    devices: list | None = None
    route: bool = True             # structure-routed ladder; False = PR-1 path
    route_check_tol: float = 1e-6  # KKT acceptance for closed-form candidates
    # wave packer: fuse all small iterative buckets of a plan step into one
    # bucket_glasso launch per size bin (resolved to a bool by the Engine
    # from EngineOptions.fused; buckets routed "fused" fuse regardless)
    fused: bool = False
    # bucket_key -> previous padded solution / input stacks (device arrays):
    # reused buckets warm-start from their own previous solution and skip the
    # host->device re-upload of their bit-identical padded blocks.
    _prev_solutions: dict = field(default_factory=dict)
    _prev_blocks: dict = field(default_factory=dict)
    # oversize accounting of the MOST RECENT solve_plan call (dispatched /
    # inner_iters / stalls / fallbacks) — surfaced as GlassoResult.oversize
    last_oversize: dict = field(default_factory=dict)
    # assembly-stage seconds of the MOST RECENT solve_plan call — surfaced
    # as GlassoResult.assemble_seconds (process-wide: engine.assemble_us)
    last_assemble_seconds: float = 0.0
    # host seconds spent ISSUING async dispatches (closed-form, iterative,
    # fused, repairs) in the MOST RECENT solve_plan call — surfaced as
    # GlassoResult.dispatch_seconds so the launch overhead the wave packer
    # targets is attributed to its own stage, not folded into solve time
    last_dispatch_seconds: float = 0.0

    def __post_init__(self):
        from repro.core.solvers import solver_spec
        from repro.engine.waves import FUSED_BINS

        if self.solver not in SOLVERS:
            raise ValueError(
                f"unknown solver {self.solver!r}; available: {sorted(SOLVERS)}"
            )
        _validate_solver_opts(self.solver, self.solver_opts)
        if self.devices is None:
            self.devices = list(jax.local_devices())
        self._opts_key = tuple(sorted(self.solver_opts.items()))
        # fused eligibility: the solver must declare the fused_stack
        # capability AND every solver opt must be one the fused kernel
        # replays (anything else would silently change the packed solve)
        meta = solver_spec(self.solver).meta
        self._max_fused = int(meta.get("max_fused_size", FUSED_BINS[-1]))
        self._fused_capable = bool(meta.get("fused_stack")) and set(
            self.solver_opts
        ) <= {"max_sweeps", "n_cd", "tol", "node_screen"}

    # -- placement ---------------------------------------------------------

    def _bucket_cost(self, bucket: blocks_mod.Bucket) -> float:
        """Estimated DEVICE solve cost of one bucket: count x size^3 scaled
        by the structure class's route, not just padded size.  A chordal
        bucket solves on the HOST (zero device time — placing it as if it
        cost n*b^3 starves a device for nothing), a closed-form bucket is
        one fused elementwise pass (~b^2 per block), only the iterative tail
        actually pays b^3-per-sweep on its device.  Sharded buckets span the
        whole mesh and are not LPT-placed at all (cost 0 here; their device
        time is accounted by the sharded dispatch itself)."""
        from repro.engine.registry import route_for  # local: avoid cycle

        route = route_for(bucket.structure) if self.route else "iterative"
        n = len(bucket.comps)
        if route in ("chordal", "sharded", "assemble"):
            return 0.0
        if route == "closed_form":
            return n * float(bucket.size) ** 2
        return n * float(bucket.size) ** 3

    def _place(
        self, buckets: list[blocks_mod.Bucket], priorities=None
    ) -> list:
        """LPT assignment of buckets to local devices by estimated cost.

        ``priorities`` (per-bucket, higher = more urgent) seats urgent
        buckets first — the serving control plane passes its SLO class
        through here so an interactive request's buckets dispatch ahead of
        best-effort co-travellers on every device queue."""
        if len(self.devices) <= 1 or not buckets:
            return [None] * len(buckets)
        cost = [self._bucket_cost(b) for b in buckets]
        assign = lpt_assign(
            cost, len(self.devices), cost=float, priorities=priorities
        )
        return [self.devices[w] for w in assign.worker_of]

    # -- warm starts -------------------------------------------------------

    def _warm_stack(
        self,
        bucket: blocks_mod.Bucket,
        key,
        lam: float,
        warm_W: np.ndarray | None,
        warm_Theta: np.ndarray | None = None,
    ):
        """(W0 stack, Theta0 stack or None) for one bucket, or (None, None).

        Reused bucket with a cached previous solution: W0 = inv(prev Theta)
        batched on device (the padded block of Theta is blkdiag, so its
        inverse's padded diagonal is finite; it is then reset to 1+lam), and
        the previous Theta itself rides along as the Theta0 seed for solvers
        whose spec consumes it (no second inversion inside the solver).
        Merged/fresh buckets prefer ``warm_Theta`` (the previous solution
        itself, dense or block-sparse — its cross-component entries are exact
        zeros, so each gathered restriction is the Theorem-2 block-diagonal
        PD warm start): the Theta stack is gathered once and W0 = inv(T0) is
        computed batched on device, so no dense (p, p) W ever exists on the
        host.  ``warm_W`` remains the fallback for callers that hold a W
        iterate but no Theta (the single-solve ``warm_W=`` API) — no Theta
        stack there."""
        T0 = None
        prev = self._prev_solutions.get(key)
        np_dtype = np.dtype(jnp.dtype(self.dtype).name)
        if prev is not None:
            prev = jnp.asarray(prev, self.dtype)
            W0 = jnp.linalg.inv(prev)
            T0 = prev
        elif warm_Theta is not None:
            tstacks = [
                blocks_mod.pad_block(
                    blocks_mod.gather_submatrix(warm_Theta, c, dtype=np_dtype),
                    bucket.size,
                )
                for c in bucket.comps
            ]
            T0 = jnp.asarray(np.stack(tstacks), self.dtype)
            # padded T0 diagonal is the identity (pad_block), so the batched
            # inverse is finite; the padded W diagonal is reset below anyway
            W0 = jnp.linalg.inv(T0)
        elif warm_W is not None:
            # gather through the protocol: warm_W may be a dense array or a
            # block-sparse previous result (whose cross-component entries
            # are exact zeros — the merged-component block-diagonal restriction)
            stacks = [
                blocks_mod.pad_block(
                    blocks_mod.gather_submatrix(warm_W, c, dtype=np_dtype),
                    bucket.size,
                )
                for c in bucket.comps
            ]
            W0 = jnp.asarray(np.stack(stacks), self.dtype)
        else:
            return None, None
        # padded diagonal of a W iterate must be 1 + lam (diagonal KKT)
        idx = jnp.arange(bucket.size)
        pad_mask = jnp.stack(
            [idx >= len(c) for c in bucket.comps]
        )  # (n, size) True on padded coords
        eye = jnp.eye(bucket.size, dtype=bool)
        fix = pad_mask[:, :, None] & eye[None, :, :]
        W0 = jnp.where(fix, jnp.asarray(1.0 + lam, W0.dtype), W0)
        off = pad_mask[:, :, None] ^ pad_mask[:, None, :]
        return jnp.where(off, jnp.zeros((), W0.dtype), W0), T0

    # -- solve -------------------------------------------------------------

    def solve_plan(
        self,
        plan: blocks_mod.Plan,
        lam: float,
        S: np.ndarray,
        *,
        warm_W: np.ndarray | None = None,
        warm_Theta: np.ndarray | None = None,
        reused_keys: frozenset = frozenset(),
        keep_solutions: bool = False,
        output: str = "dense",
        priorities=None,
    ) -> np.ndarray:
        """Dispatch all buckets, then assemble Theta.

        ``priorities`` (optional, per-bucket, higher = more urgent) makes
        the multi-device placement priority-aware — see ``_place``.

        ``output="sparse"`` hands the per-bucket solution stacks to
        ``blocks.assemble_sparse`` — the result is a ``SparseTheta`` built
        on zero-copy views of those stacks, and no (p, p) buffer is ever
        allocated; ``"dense"`` (default) scatters into the global matrix as
        before.

        ``reused_keys`` marks buckets whose padded arrays were carried over by
        the planner; their previous solutions (if retained via
        ``keep_solutions``) seed the warm start without touching the host.

        Routing ladder: buckets take the route their structure class maps to
        (``registry.route_for``), every non-iterative candidate is
        KKT-verified, and failures are re-dispatched to the iterative solver
        before assembly — see ``_verify_and_fallback``."""
        from repro.engine.planner import bucket_key  # local: avoid cycle at import
        from repro.engine.registry import route_for  # local: avoid cycle at import

        from repro.engine.waves import fused_bin

        if self.route and len(plan.isolated):
            bump("router.route.singleton", int(len(plan.isolated)))
        self.last_oversize = {}
        self.last_dispatch_seconds = 0.0
        placements = self._place(plan.buckets, priorities=priorities)
        pending: list[_Pending] = []
        sharded_pending: list[_Pending] = []
        fused_groups: dict[tuple, list[_FusedLane]] = {}
        for bucket, device in zip(plan.buckets, placements):
            key = bucket_key(bucket)
            n = len(bucket.comps)
            route = route_for(bucket.structure) if self.route else "iterative"
            if self.route:
                bump(f"router.route.{bucket.structure}", n)
            if route == "sharded":
                # mesh-spanning blocking solve: queued after the async small
                # buckets below so their dispatches are in flight first
                p = _Pending(bucket=bucket, out=None, key=key)
                pending.append(p)
                sharded_pending.append(p)
                continue
            if route == "chordal":
                # host direct solve: no device round-trip for the candidate.
                # KKT failures are known IMMEDIATELY (host), so their repair
                # dispatches into the same async wave as everything else
                # instead of serializing after the barrier.
                (out, ok), _ = timed_dispatch(
                    solve_chordal_bucket,
                    bucket, np.full(n, lam), tol=self.route_check_tol,
                )
                p = _Pending(bucket=bucket, out=out, ok=None, key=key)
                if not ok.all():
                    idx = np.flatnonzero(~ok)
                    bump(f"router.fallback.{bucket.structure}", int(idx.size))
                    p.repair = self._dispatch_repair(bucket, idx, out[idx], lam)
                pending.append(p)
                continue
            stacked = self._prev_blocks.get(key) if key in reused_keys else None
            if stacked is None:
                stacked = jnp.asarray(bucket.blocks, self.dtype)
                if device is not None:
                    stacked = jax.device_put(stacked, device)
            elif device is not None and list(stacked.devices()) != [device]:
                # LPT may move a reused bucket between lambdas; a D2D copy
                # still beats re-uploading from host
                stacked = jax.device_put(stacked, device)
            lams = jnp.full((n,), lam, self.dtype)
            if device is not None:
                lams = jax.device_put(lams, device)
            if route == "closed_form":
                fn = compiled_closed_form(
                    bucket.size,
                    self.dtype,
                    tol=self.route_check_tol,
                    verify=bucket.structure != "pair",
                )
                (theta, ok), dt = timed_dispatch(fn, stacked, lams)
                self.last_dispatch_seconds += dt
                bump("executor.dispatches")
                pending.append(
                    _Pending(bucket=bucket, out=theta, ok=ok, stacked=stacked, key=key)
                )
                continue
            if self.solver in WARM_START_SOLVERS:
                use_key = key if key in reused_keys else None
                W0, T0 = self._warm_stack(
                    bucket, use_key, lam, warm_W, warm_Theta
                )
            else:
                W0 = T0 = None  # solver discards W0: skip the inversions
            if not (T0 is not None and _theta_warm(self.solver)):
                T0 = None
            if device is not None and W0 is not None:
                W0 = jax.device_put(W0, device)
                if T0 is not None:
                    T0 = jax.device_put(T0, device)
            fuse = (
                route == "fused" or (route == "iterative" and self.fused)
            ) and self._fused_capable and bucket.size <= self._max_fused
            bin_ = fused_bin(bucket.size) if fuse else None
            if bin_ is not None:
                # wave packer: defer into the (device, bin) megabatch — the
                # launch happens once per group after this loop
                p = _Pending(bucket=bucket, out=None, stacked=stacked, key=key)
                pending.append(p)
                fused_groups.setdefault((device, bin_), []).append(
                    _FusedLane(
                        pending=p, size=bucket.size, n=n, lams=lams,
                        W0=W0, T0=T0,
                    )
                )
                continue
            fn = compiled_bucket_solver(
                self.solver,
                bucket.size,
                self.dtype,
                warm=W0 is not None,
                warm_theta=T0 is not None,
                opts_key=self._opts_key,
            )
            if T0 is not None:
                out, dt = timed_dispatch(fn, stacked, lams, W0, T0)
            elif W0 is not None:
                out, dt = timed_dispatch(fn, stacked, lams, W0)
            else:
                out, dt = timed_dispatch(fn, stacked, lams)
            self.last_dispatch_seconds += dt
            bump("executor.dispatches")
            pending.append(_Pending(bucket=bucket, out=out, stacked=stacked, key=key))

        fused_sweeps = self._dispatch_fused(fused_groups, lam)

        # oversize buckets: mesh-spanning sharded solves, one blocking call
        # per giant block, while the small async dispatches above are already
        # in flight.  Warm start: a bucket reused from the previous lambda
        # seeds Theta0 from its own previous padded solution (the dense
        # warm_W path would require inverting a giant block on the host —
        # exactly the allocation the route avoids).
        totals = {"dispatched": 0, "inner_iters": 0, "stalls": 0, "fallbacks": 0}
        for p in sharded_pending:
            bucket = p.bucket
            prev = (
                self._prev_solutions.get(p.key) if p.key in reused_keys else None
            )
            warm_thetas = None
            if prev is not None:
                prev = np.asarray(prev)
                warm_thetas = [
                    prev[i][: len(c), : len(c)]
                    for i, c in enumerate(bucket.comps)
                ]
            n = len(bucket.comps)
            p.out, info = solve_sharded_bucket(
                bucket,
                np.full(n, lam),
                S,
                solver=self.solver,
                dtype=self.dtype,
                opts_key=self._opts_key,
                tol=self.route_check_tol,
                warm_thetas=warm_thetas,
            )
            for k in totals:
                totals[k] += info[k]
        if totals["dispatched"]:
            self.last_oversize = totals

        # single synchronization point: everything above was async dispatch
        with span("engine.barrier"):
            jax.block_until_ready(
                [p.out for p in pending if isinstance(p.out, jax.Array)]
                + [p.repair[1] for p in pending if p.repair is not None]
            )
        for sw in fused_sweeps:
            # per-launch sweeps are ready (same barrier); the saving is what
            # the megabatch's slowest lane would have cost every other lane
            # had they iterated in lockstep without in-kernel early exit
            sw = np.asarray(sw)
            if sw.size:
                bump(
                    "solver.fused.lockstep_sweeps_saved",
                    int(sw.max()) * int(sw.size) - int(sw.sum()),
                )
        for p in pending:
            if p.repair is not None:
                idx, fixed = p.repair
                p.out = np.array(p.out)
                p.out[idx] = np.asarray(fixed)
        self._verify_and_fallback(pending, lam)

        new_solutions: dict = {}
        new_blocks: dict = {}
        if keep_solutions:
            for p in pending:
                new_solutions[p.key] = p.out
                if p.stacked is not None:
                    new_blocks[p.key] = p.stacked
        self._prev_solutions = new_solutions
        self._prev_blocks = new_blocks
        t0 = time.perf_counter()
        with span("engine.assemble", output=output):
            sols = [np.asarray(p.out) for p in pending]
            if output == "sparse":
                Theta = blocks_mod.assemble_sparse(plan, sols, S)
            else:
                Theta = blocks_mod.assemble_dense(plan, sols, S)
        self.last_assemble_seconds = time.perf_counter() - t0
        bump("engine.assemble_us", int(self.last_assemble_seconds * 1e6))
        return Theta

    def _dispatch_fused(
        self, groups: dict[tuple, list[_FusedLane]], lam: float
    ) -> list:
        """Launch every (device, bin) megabatch: ONE fused solver call per
        group per wave, scattered back into each lane's ``pending.out``.

        Packing is bitwise-transparent (see ``engine.waves``): blocks re-pad
        with an identity diagonal, warm W stacks with 1+lam (the diagonal
        KKT of padded coordinates, matching ``_warm_stack``), cold lanes
        synthesize the pair the solver would have built (W0 = S + lam*I,
        Theta0 = I), and each lane's convergence scale is computed at its
        SOURCE shape — one batched launch per (device, size) — so packing
        changes which executable runs, never any lane's tolerance or bits.
        Returns the per-launch sweep-count arrays (read after the barrier
        for ``solver.fused.lockstep_sweeps_saved``)."""
        if not groups:
            return []
        from repro.engine.waves import (
            bucket_scales,
            compiled_fused_solver,
            min_batch2,
            repad_stack,
        )

        by_size: dict[tuple, list[_FusedLane]] = {}
        for (device, _), lanes in groups.items():
            for ln in lanes:
                by_size.setdefault((device, ln.size), []).append(ln)
        for lanes in by_size.values():
            stacks = (
                lanes[0].pending.stacked
                if len(lanes) == 1
                else jnp.concatenate([ln.pending.stacked for ln in lanes])
            )
            scales = bucket_scales(stacks)
            off = 0
            for ln in lanes:
                ln.scales = scales[off:off + ln.n]
                off += ln.n

        lam_c = jnp.asarray(lam, self.dtype)
        one = jnp.ones((), self.dtype)
        sweeps_out = []
        for (device, bin_), lanes in sorted(
            groups.items(), key=lambda kv: (str(kv[0][0]), kv[0][1])
        ):
            blk_p, lam_p, sc_p, w_p, t_p = [], [], [], [], []
            for ln in lanes:
                stacked = ln.pending.stacked
                blk_p.append(repad_stack(stacked, bin_, one))
                lam_p.append(ln.lams)
                sc_p.append(ln.scales)
                if ln.W0 is None:
                    # cold init at SOURCE shape — off-diagonal S + 0 is
                    # exact; the diagonal is reset in-solver either way
                    w = stacked + lam_c * jnp.eye(ln.size, dtype=self.dtype)
                else:
                    w = ln.W0
                w_p.append(repad_stack(w, bin_, one + lam_c))
                if ln.T0 is None:
                    t = jnp.zeros((ln.n, bin_, bin_), self.dtype) + jnp.eye(
                        bin_, dtype=self.dtype
                    )
                else:
                    t = repad_stack(ln.T0, bin_, one)
                t_p.append(t)

            def cat(xs):
                return xs[0] if len(xs) == 1 else jnp.concatenate(xs)

            fn = compiled_fused_solver(bin_, self.dtype, self._opts_key)
            (theta, sweeps), dt = timed_dispatch(
                min_batch2, fn, cat(blk_p), cat(lam_p), cat(sc_p),
                cat(w_p), cat(t_p),
            )
            self.last_dispatch_seconds += dt
            bump("executor.dispatches")
            bump("solver.fused.dispatches")
            bump("solver.fused.blocks_packed", sum(ln.n for ln in lanes))
            off = 0
            for ln in lanes:
                ln.pending.out = theta[off:off + ln.n, :ln.size, :ln.size]
                off += ln.n
            sweeps_out.append(sweeps)
        return sweeps_out

    def _dispatch_repair(
        self, bucket: blocks_mod.Bucket, idx: np.ndarray, candidates, lam: float
    ):
        """Bucket-shaped wrapper over the shared ``dispatch_repair``."""
        t0 = time.perf_counter()
        out = dispatch_repair(
            self.solver,
            self.dtype,
            self._opts_key,
            bucket.size,
            np.asarray(bucket.blocks)[idx],
            np.full(int(idx.size), lam),
            candidates,
        )
        self.last_dispatch_seconds += time.perf_counter() - t0
        return (idx, out)

    def _verify_and_fallback(self, pending: list[_Pending], lam: float) -> None:
        """Re-dispatch every closed-form block whose KKT check failed to the
        iterative solver (the ladder's tail) and splice the repaired rows
        into the pending stacks.  Rare by design — the fast-path classes
        satisfy the KKT by construction except for non-edge dual feasibility
        on adversarial matrices — but this is what makes routing SAFE."""
        repairs = []
        for p in pending:
            if p.ok is None:
                continue
            ok = np.asarray(p.ok)
            if ok.all():
                continue
            idx = np.flatnonzero(~ok)
            bump(f"router.fallback.{p.bucket.structure}", int(idx.size))
            repairs.append((p, self._dispatch_repair(p.bucket, idx, np.asarray(p.out)[idx], lam)))
        if not repairs:
            return
        jax.block_until_ready([r[1][1] for r in repairs])
        for p, (idx, fixed) in repairs:
            out = np.array(p.out)  # copy: np.asarray of a jax array is read-only
            out[idx] = np.asarray(fixed)
            p.out = out
