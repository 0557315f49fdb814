"""fallbacks.offline: blocks the executor re-solved after a failed KKT route
check, per lambda solution (change of the ``router.fallback.*`` counters)."""


def read(ctx):
    if not ctx.get("units"):
        return None
    n = sum(v for k, v in ctx["counters"].items() if k.startswith("router.fallback."))
    return n / ctx["units"]
