"""screen_transfer_bytes.offline: bytes the screen moved between host and
device per lambda solution, the change of ``stream.upload_bytes +
stream.fetch_bytes`` over the window (0 on the host oracle).

A program without these counters leaves the metric out."""


def read(ctx):
    c = ctx["counters"]
    if not ctx.get("units") or not {"stream.upload_bytes", "stream.fetch_bytes"} & set(c):
        return None
    return (c.get("stream.upload_bytes", 0) + c.get("stream.fetch_bytes", 0)) / ctx["units"]
