"""Chip-microseconds of the verify kernel a real signature: the kernel's
device time SUMMED over the mesh's chips (``Trace.op_seconds`` is a mean
over chips, so times ``Trace.chips``) over the real signatures of the
requests answered in the traced slice.  The number to hold against the
one-chip cell's ``kernel_us_per_sig``: equal where dividing the lanes costs
the kernel nothing."""

from benchmarks.layers import kernel_us_per_sig

NAME, UNIT, BETTER = "mesh_kernel_us_per_sig", "us/sig", "lower"
LAYER, SOURCE, MOVES = "kernel", "device_trace", "sigs_per_s"


def read(ctx):
    seconds, sigs = kernel_us_per_sig.kernel_seconds(ctx)
    if not seconds or not sigs:
        return None
    return 1e6 * seconds * ctx.trace.chips / sigs
