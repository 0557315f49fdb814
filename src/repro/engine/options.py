"""One typed options object for the whole engine surface.

Before this module, ``glasso``/``glasso_path``/``joint_glasso`` (and
``Engine``/``JointEngine``/``GlassoServer`` underneath) each re-declared the
same overlapping engine kwargs — ``route``, ``cc_backend``, ``oversize_*``,
``output``, ``stream``, plus the free-form solver opts — and a request could
not carry that configuration as a value (the serving control plane needs to
ship it inside a spec).  ``EngineOptions`` collapses them into one frozen
dataclass accepted everywhere as ``options=``.

The legacy kwargs still work through a SINGLE normalization chokepoint,
``normalize_options``: the public wrappers call it with ``warn=True`` so
kwarg-style configuration raises a ``DeprecationWarning`` (tests pin this),
while internal constructors normalize silently.  Passing both ``options=``
and legacy kwargs is an error — there is exactly one source of truth per
call.

Field split (what belongs here vs. a call site):

* **EngineOptions** — how solves are CONFIGURED: solver choice, dtype,
  screening backend, routing ladder, oversize route, result representation,
  stream defaults, joint tail verification, solver opts (``tol``,
  ``max_iter``, ...).
* **call kwargs** — what is being SOLVED: ``S``/``X``/``lam``/``lambdas``,
  ``screen=False`` baselines, ``p_max``, ``warm_W``/``warm_start``,
  ``penalty``, serving ``session``.  These are not deprecated.

Model-selection knobs (the lambda grid, the criterion and its parameters)
are neither: they describe a QUESTION about the path, not how solves run,
and travel on ``repro.select.select_path(...)`` arguments — or, over the
serving surface, on ``launch.control_plane.PathSpec`` — always alongside
an ``EngineOptions`` that configures the underlying solves.  One
``EngineOptions`` therefore serves every grid point of a selection path
unchanged (which is what lets the homotopy executor reuse compiled
solvers and warm starts across the whole grid).
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field, fields, replace
from typing import Any, Mapping

__all__ = ["EngineOptions", "ENGINE_OPTION_KEYS", "normalize_options"]


@dataclass(frozen=True)
class EngineOptions:
    """Engine configuration as a value.

    ``solver=None`` means "the context default" — "bcd" for single-class
    engines, "joint_admm" for the joint engine; ``dtype=None`` resolves to
    ``float32`` on a TPU (which has no float64 kernels or LU) and to
    ``float64`` elsewhere, the CPU reference precision.  ``solver_opts`` holds the free-form per-solver knobs
    (``tol``, ``max_iter``, ``rho``, ...) that used to travel as ``**kwargs``.
    """

    solver: str | None = None
    dtype: Any = None
    cc_backend: str = "host"
    route: bool = True
    route_check_tol: float = 1e-6
    oversize_threshold: int | None = None
    oversize_budget_mb: float | str | None = None
    output: str = "auto"
    stream: Any = None             # StreamConfig / kwargs dict default
    verify_tail: bool = False      # joint-only: exact tail KKT verification
    # wave packer (DESIGN.md Section 16): True fuses all small iterative
    # buckets into one bucket_glasso megabatch launch per size bin per wave
    # (requires a solver with the "fused_stack" capability); False never
    # fuses; "auto" fuses when the solver forces it ("fused_bcd") or a
    # structure class is routed to "fused" (registry.set_route)
    fused: bool | str = "auto"
    # observability (DESIGN.md Section 17): True roots a request Trace per
    # run/run_path (spans: screen -> plan -> per-step solve -> dispatch ->
    # assemble) attached as ``GlassoResult.trace``, each span also a
    # ``jax.profiler.TraceAnnotation`` so device profiles show the host
    # span tree; False makes the engine span-free (the <5%-overhead bench
    # arm); "jax" is accepted and means the same as True
    trace: bool | str = True
    solver_opts: Mapping[str, Any] = field(default_factory=dict)

    def __post_init__(self):
        if self.output not in ("dense", "sparse", "auto"):
            raise ValueError(
                f"output must be 'dense', 'sparse' or 'auto', got {self.output!r}"
            )
        if self.fused not in (True, False, "auto"):
            raise ValueError(
                f"fused must be True, False or 'auto', got {self.fused!r}"
            )
        if self.trace not in (True, False, "jax"):
            raise ValueError(
                f"trace must be True, False or 'jax', got {self.trace!r}"
            )
        object.__setattr__(self, "solver_opts", dict(self.solver_opts))

    # -- derived views ----------------------------------------------------

    def resolved_solver(self, default: str) -> str:
        return self.solver if self.solver is not None else default

    def resolved_dtype(self):
        import jax
        import jax.numpy as jnp

        on_tpu = jax.default_backend() == "tpu"
        if self.dtype is None:
            return jnp.float32 if on_tpu else jnp.float64
        if on_tpu and jnp.dtype(self.dtype) == jnp.float64:
            raise ValueError(
                "dtype float64 is not supported on the TPU (Pallas kernels "
                "and LU have no float64 path); use dtype=float32 or leave "
                "dtype unset"
            )
        return self.dtype

    def np_dtype(self):
        """The numpy dtype mirroring ``resolved_dtype()`` (host-side
        gathers/assembly use numpy; devices use the jax dtype)."""
        import jax.numpy as jnp
        import numpy as np

        return np.dtype(jnp.dtype(self.resolved_dtype()).name)

    def replace(self, **changes) -> "EngineOptions":
        """``dataclasses.replace`` with solver_opts MERGED, not clobbered:
        unknown keys in ``changes`` update solver_opts entry-wise (the same
        absorption rule as the legacy kwargs layer)."""
        known = {f.name for f in fields(self)}
        direct = {k: v for k, v in changes.items() if k in known}
        extra = {k: v for k, v in changes.items() if k not in known}
        if extra:
            merged = dict(self.solver_opts)
            merged.update(extra)
            direct.setdefault("solver_opts", merged)
        return replace(self, **direct)


#: Engine-configuration keys the legacy kwarg layer recognizes; anything
#: else a caller passes is absorbed into ``solver_opts`` (the historical
#: ``**solver_opts`` behavior — validated downstream by the executor).
ENGINE_OPTION_KEYS = frozenset(
    f.name for f in fields(EngineOptions) if f.name != "solver_opts"
)

_DEPRECATION_MSG = (
    "configuring {context} via bare engine kwargs ({keys}) is deprecated; "
    "pass options=EngineOptions(...) instead (repro.engine.EngineOptions)"
)


def normalize_options(
    options: EngineOptions | None,
    kwargs: Mapping[str, Any],
    *,
    warn: bool = False,
    context: str = "the engine",
) -> EngineOptions:
    """THE normalization chokepoint: every options-accepting surface funnels
    its ``options=``/legacy-kwargs pair through here.

    * ``options`` given and ``kwargs`` empty — pass-through (validated).
    * ``kwargs`` only — build an ``EngineOptions``, splitting recognized
      engine keys from free-form solver opts; with ``warn=True`` (the public
      wrappers) this is the deprecation layer and raises a
      ``DeprecationWarning`` naming the legacy keys.
    * both — ``TypeError``: one source of truth per call.
    """
    if options is not None:
        if kwargs:
            raise TypeError(
                f"pass options=EngineOptions(...) OR legacy engine kwargs "
                f"({sorted(kwargs)}), not both"
            )
        if not isinstance(options, EngineOptions):
            raise TypeError(
                f"options must be an EngineOptions, got {type(options).__name__}"
            )
        return options
    if not kwargs:
        return EngineOptions()
    if warn:
        warnings.warn(
            _DEPRECATION_MSG.format(context=context, keys=sorted(kwargs)),
            DeprecationWarning,
            stacklevel=3,
        )
    direct = {k: v for k, v in kwargs.items() if k in ENGINE_OPTION_KEYS}
    solver_opts = {k: v for k, v in kwargs.items() if k not in ENGINE_OPTION_KEYS}
    return EngineOptions(solver_opts=solver_opts, **direct)
