"""Configuration for the out-of-core streaming screener.

One knob object threads through the whole subsystem (tiler, driver, path
adapter, serving sessions).  The memory model it controls (DESIGN.md
Section 10):

    peak screening bytes  ~=  pair_batch * tile^2 * itemsize   (in-flight tiles;
                                                                device on the kernel)
                            + 3 * 8 * #edges                   (compacted edges, host)
                            + O(p)                             (moments, labels, host)

so ``memory_budget_mb`` simply solves for ``pair_batch``.  The dense (p, p)
covariance never exists; ``stream.bytes_peak`` (instrument watermark) records
what actually did.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class StreamConfig:
    """Knobs for one streaming screen.

    tile           column-tile width (the covgram_screen block_p); need not
                   divide p — the last tile is padded and masked.
    chunk          row-chunk height streamed per Gram accumulation step
                   (covgram_screen block_n on TPU; the numpy path reduces
                   whole columns at once and only uses it for the moments
                   pass).
    pair_batch     tile PAIRS computed in flight per kernel/oracle call; the
                   dominant peak-memory term.  Derived from
                   ``memory_budget_mb`` when that is set.
    memory_budget_mb  optional cap on the in-flight tile batch; overrides
                   pair_batch.
    backend        covgram_screen dispatch: "auto" (pallas on TPU, numpy
                   oracle elsewhere), "pallas", or "ref".
    skip_slack     relative inflation of the Cauchy-Schwarz tile-skip bound
                   sqrt(max_I S_ii * max_J S_jj) <= lam: floating-point Gram
                   accumulation can overshoot the exact bound by a few ulps,
                   so the skip test uses bound * (1 + skip_slack) <= lam.
                   Ties |S_ij| == lam are not edges (strict eq. (4)), so a
                   tile whose inflated bound equals lam is still computed,
                   never mis-skipped.
    """

    tile: int = 512
    chunk: int = 512
    pair_batch: int = 64
    memory_budget_mb: float | None = None
    backend: str = "auto"
    skip_slack: float = 1e-6

    def resolved_pair_batch(self, itemsize: int) -> int:
        if self.memory_budget_mb is None:
            return max(1, int(self.pair_batch))
        budget = self.memory_budget_mb * 2**20
        per_pair = self.tile * self.tile * itemsize
        return max(1, int(budget // max(per_pair, 1)))


def as_config(config) -> StreamConfig:
    """None -> defaults; dict -> kwargs; StreamConfig passes through."""
    if config is None:
        return StreamConfig()
    if isinstance(config, StreamConfig):
        return config
    if isinstance(config, dict):
        return StreamConfig(**config)
    raise TypeError(f"expected StreamConfig, dict, or None; got {type(config)!r}")
