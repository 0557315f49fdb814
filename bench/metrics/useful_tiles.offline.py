"""useful_tiles.offline: percent of the computed tile pairs that held an edge,
the change of ``stream.tiles_with_edges`` over the change of
``stream.tiles_total - stream.tiles_skipped`` (0 when no pair was computed).

A program without the counter leaves the metric out."""


def read(ctx):
    c = ctx["counters"]
    if not ctx.get("units") or "stream.tiles_with_edges" not in c:
        return None
    computed = c.get("stream.tiles_total", 0) - c.get("stream.tiles_skipped", 0)
    if computed <= 0:
        return 0.0
    return 100.0 * c["stream.tiles_with_edges"] / computed
