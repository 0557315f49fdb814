"""dispatches.offline: solver launches per lambda solution, the change of
the program's ``engine.dispatch.count`` over the window."""


def read(ctx):
    if not ctx.get("units"):
        return None
    return ctx["counters"].get("engine.dispatch.count", 0) / ctx["units"]
