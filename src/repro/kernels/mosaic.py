"""What every Pallas kernel of the engine needs to compile with Mosaic.

* **32-bit tracing.**  The engine's entry points turn on x64, under which a
  grid index map's literal ``0`` and a loop counter become int64; Mosaic
  cannot lower those (``failed to legalize operation 'func.return'``, or an
  endless int64 -> int32 conversion).  ``mosaic_trace`` traces a
  ``pallas_call`` with x64 off.  The kernels' operands are float32/int32 on
  the chip, so nothing else changes; interpret mode keeps the caller's
  setting, so the float64 parity tests still run in float64.
* **VMEM sizing** for the row-tiled kernels (``shard_prox``,
  ``joint_prox``).  A kernel's pipelined blocks and its temporaries live in
  VMEM under a scoped limit (16 MiB by default on v5e, of 128 MiB
  physical).  The wrappers pick the largest row tile whose working set fits
  ``VMEM_BUDGET``, so every kernel compiles under the default limit.
"""

from __future__ import annotations

import contextlib

import jax

#: working set the wrappers size their row tiles to (under the 16 MiB default)
VMEM_BUDGET = 12 * 2**20


def row_tile(rows: int, row_bytes: int, slabs: int) -> int:
    """Largest multiple-of-8 divisor of ``rows`` (itself a multiple of 8)
    whose ``slabs`` live (tile, ·) slabs of ``row_bytes`` each fit
    ``VMEM_BUDGET``; at least 8."""
    best = 8
    for tr in range(8, rows + 1, 8):
        if rows % tr == 0 and tr * row_bytes * slabs <= VMEM_BUDGET:
            best = tr
    return best


def mosaic_trace(interpret: bool):
    """Context to trace a ``pallas_call`` in: x64 off unless interpreting."""
    return contextlib.nullcontext() if interpret else jax.enable_x64(False)
