"""assemble_s.offline: seconds of the assemble stage per lambda solution."""


def read(ctx):
    if not ctx.get("results"):
        return None
    return sum(r.stages()["assemble"] for r in ctx["results"]) / ctx["units"]
