"""screen_idle.offline: percent of the traced window in which the device was
idle while the screen's host side ran: the idle time whose gaps the trace
reduction labels with ``engine.screen`` or a ``screen.*`` span.

A program that records no ``engine.screen`` span does not time the screen
apart from the planner, and the metric is left out."""


def read(ctx):
    traces = ctx.get("traces")
    if not ctx.get("results") or not any(
        s.name == "engine.screen" for tr in traces or () for s in tr.spans
    ):
        return None
    t = ctx["trace"]
    if t["window_s"] <= 0:
        return None
    idle = sum(
        sec for name, sec in t["idle_by_span"].items()
        if name == "engine.screen" or name.startswith("screen.")
    )
    return 100.0 * idle / t["window_s"]
