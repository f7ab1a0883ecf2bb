"""The whole request path's share of the chip's peak over the traced slice:
the textbook operations of every signature answered in it over the slice's
time times the chip's int8 peak.  It bounds the kernel's roofline share from
below and still reads when a later change takes the kernel off the path."""

from benchmarks import roofline

NAME, UNIT, BETTER = "verify_mfu", "%", "higher"
LAYER, SOURCE, MOVES = "device", "device_trace", "sigs_per_s"


def read(ctx):
    if ctx.trace is None or not ctx.traced_records:
        return None
    sigs = sum(r.signatures for r in ctx.traced_records)
    return roofline.share_pct(sigs, ctx.trace.window_s, ctx.peaks, ctx.chips)
