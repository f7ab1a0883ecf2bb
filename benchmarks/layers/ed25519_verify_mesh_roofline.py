"""The verify kernel's share of the MESH's roofline: the least time the
cell's chips together could take for the traced slice's real signatures
(``benchmarks/roofline.py``, the same textbook work, over ``chips`` times one
chip's int8 peak) over the kernel's device time, mean over the chips.  No new
operations are counted.  Like the one-chip share it reads far below 1%, is
never rounded to 0 and never clipped."""

from benchmarks import roofline
from benchmarks.layers import kernel_us_per_sig

NAME, UNIT, BETTER = "ed25519_verify_mesh_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "kernel", "device_trace", "sigs_per_s"


def read(ctx):
    seconds, sigs = kernel_us_per_sig.kernel_seconds(ctx)
    if not seconds or not sigs:
        return None
    return roofline.share_pct(sigs, seconds, ctx.peaks, ctx.chips)
