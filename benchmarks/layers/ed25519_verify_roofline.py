"""The verify kernel's share of its roofline: the least time the chip could
take for the traced slice's real signatures (``benchmarks/roofline.py``: the
textbook verification in int8 multiply-adds against the chip's published
int8 peak, an MXU figure) over the kernel's device time.  Operations bound
it, not bytes.  The kernel multiplies on the vector unit, for which no peak
is published, so this reads far below 1%; it is never rounded to 0 and
never clipped."""

from benchmarks import roofline
from benchmarks.layers import kernel_us_per_sig

NAME, UNIT, BETTER = "ed25519_verify_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "kernel", "device_trace", "sigs_per_s"


def read(ctx):
    seconds, sigs = kernel_us_per_sig.kernel_seconds(ctx)
    if not seconds or not sigs:
        return None
    return roofline.share_pct(sigs, seconds, ctx.peaks, ctx.chips)
