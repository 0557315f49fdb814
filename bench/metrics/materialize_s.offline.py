"""materialize_s.offline: seconds of the program's ``screen.materialize`` spans
per lambda solution: the covariance blocks of the coarsest partition
gathered from the data.

A program that records no ``engine.screen`` span does not time the screen
apart from the planner, and the metric is left out."""

from bench.spans import self_seconds


def read(ctx):
    traces = ctx.get("traces")
    if not ctx.get("results") or not any(
        s.name == "engine.screen" for tr in traces or () for s in tr.spans
    ):
        return None
    return self_seconds(traces, "screen.materialize") / ctx["units"]
