"""screen_compact_s.offline: seconds of the program's ``screen.compact`` spans
per lambda solution: each batch of thresholded tiles compacted to (i, j,
|S_ij|) edges on the host and stored.

A program that records no ``engine.screen`` span does not time the screen
apart from the planner, and the metric is left out."""

from bench.spans import self_seconds


def read(ctx):
    traces = ctx.get("traces")
    if not ctx.get("results") or not any(
        s.name == "engine.screen" for tr in traces or () for s in tr.spans
    ):
        return None
    return self_seconds(traces, "screen.compact") / ctx["units"]
