from repro.kernels.covgram_screen.ops import (
    compact_edges,
    compact_edges_signed,
    covgram_screen_edges,
    covgram_screen_tiles,
    covgram_screen_tiles_stacked,
    pad_for_screen,
    resolve_backend,
)

__all__ = [
    "covgram_screen_edges",
    "covgram_screen_tiles",
    "covgram_screen_tiles_stacked",
    "compact_edges",
    "compact_edges_signed",
    "pad_for_screen",
    "resolve_backend",
]
