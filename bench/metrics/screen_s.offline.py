"""screen_s.offline: seconds of the screen stage per lambda solution, the
program's own ``stages()["screen"]`` summed over the window's results."""


def read(ctx):
    if not ctx.get("results"):
        return None
    return sum(r.stages()["screen"] for r in ctx["results"]) / ctx["units"]
