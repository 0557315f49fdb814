"""solve_s.offline: seconds of the solve and dispatch stages per lambda
solution (``stages()["solve"] + stages()["dispatch"]``)."""


def read(ctx):
    if not ctx.get("results"):
        return None
    total = sum(r.stages()["solve"] + r.stages()["dispatch"] for r in ctx["results"])
    return total / ctx["units"]
