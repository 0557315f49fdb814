"""plan_s.offline: self time of the program's ``engine.plan`` spans per
lambda solution, less the screen stage.

On a path from data the streamed screen runs inside ``engine.plan`` and
records no span of its own, so its seconds (``stages()["screen"]``, timed by
the program) are taken off to leave the planner's own time."""

from bench.spans import self_seconds


def read(ctx):
    if not ctx.get("results") or not ctx.get("traces"):
        return None
    plan = self_seconds(ctx["traces"], "engine.plan")
    screen = sum(r.stages()["screen"] for r in ctx["results"])
    return max(0.0, plan - screen) / ctx["units"]
