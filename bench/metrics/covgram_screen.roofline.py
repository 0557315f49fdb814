"""covgram_screen.roofline: percent of the roofline that the covgram_screen
kernel reached over the window.

Work: the tile pairs handed to the kernel (change of
``stream.tiles_total - stream.tiles_skipped``) at the kernel's padded row
count and tile width (``bench/roofline.py``).  Time: the device time of the
kernel's program, ``jit_covgram_screen_pallas``, in the trace.  Nothing to
read (no pair computed, or no such program in the trace) leaves it out."""

from bench import roofline

PROGRAM = "jit_covgram_screen_pallas"


def read(ctx):
    shape = ctx.get("kernel_shape", {}).get("covgram_screen")
    seconds = ctx["trace"]["modules"].get(PROGRAM, 0.0)
    c = ctx["counters"]
    pairs = c.get("stream.tiles_total", 0) - c.get("stream.tiles_skipped", 0)
    if shape is None or pairs <= 0 or seconds <= 0:
        return None
    flops, nbytes = roofline.covgram_screen_work(pairs, shape["n_pad"], shape["tile"])
    return roofline.share(flops, nbytes, seconds, ctx["device_kind"])[0]
