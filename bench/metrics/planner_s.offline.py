"""planner_s.offline: self time of the program's ``engine.plan`` spans per
lambda solution. On a path from data the screen has a span of its own beside
``engine.plan``, so this is the planner alone.

A program that records no ``engine.screen`` span does not time the screen
apart from the planner, and the metric is left out."""

from bench.spans import self_seconds


def read(ctx):
    traces = ctx.get("traces")
    if not ctx.get("results") or not any(
        s.name == "engine.screen" for tr in traces or () for s in tr.spans
    ):
        return None
    return self_seconds(traces, "engine.plan") / ctx["units"]
