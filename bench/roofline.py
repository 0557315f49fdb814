"""Operations, bytes and roofline shares of the kernels the cells time.

Peaks come from ``peaks.json``, keyed by the ``device_kind`` JAX reports; a
device that is not in the table is an error, never a default.

``covgram_screen``: per computed tile pair, the kernel streams the two
(n_pad, tile) column panels of the padded X (float32) through a
(tile, tile) Gram, and writes the thresholded (tile, tile) float32 tile
back.  So per pair

    operations = 2 * n_pad * tile**2
    bytes      = 2 * n_pad * tile * 4 + tile**2 * 4

The pairs are the ones handed to the kernel: the tile pairs the screen
scheduled less those it skipped.  The least time is the larger of
operations over the bf16 peak and bytes over HBM bandwidth.  The kernel
computes float32 at ``HIGHEST``, several bf16 passes, so a share bound by
compute reads low by that factor.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """A device kind with no row in ``peaks.json``."""


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def covgram_screen_work(pairs: int, n_pad: int, tile: int) -> tuple[float, float]:
    """(operations, bytes) of ``pairs`` computed tile pairs."""
    flops = 2.0 * n_pad * tile * tile * pairs
    nbytes = (2.0 * n_pad * tile * 4 + tile * tile * 4) * pairs
    return flops, nbytes


def share(flops: float, nbytes: float, seconds: float, device_kind: str) -> tuple[float, str]:
    """(percent of the roofline, which bound sets it) for work that took
    ``seconds`` of device time."""
    pk = peaks(device_kind)
    t_flops = flops / pk["bf16_flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "bandwidth"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
