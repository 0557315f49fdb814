"""plan_s.serve: self time of the server's ``serve.plan`` spans (the
admission-time screen and plan of each request) per request."""

from bench.spans import self_seconds


def read(ctx):
    if not ctx.get("traces"):
        return None
    return self_seconds(ctx["traces"], "serve.plan") / ctx["units"]
