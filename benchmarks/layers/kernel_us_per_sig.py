"""Device time of the verify kernel's events in the traced slice over the
real signatures of the requests answered in it."""

NAME, UNIT, BETTER = "kernel_us_per_sig", "us/sig", "lower"
LAYER, SOURCE, MOVES = "kernel", "device_trace", "sigs_per_s"

# The program gives its kernel no stable name (``pl.pallas_call`` in
# ``ops/pallas_verify._build`` has no ``name=``): the profiler prints it as
# ``%_pallas_core.1 = ... custom-call(...), custom_call_target="tpu_custom_call"``
# inside the ``jit__pallas_core`` modules (looked at by hand, PR 25), and
# ``trace_reduce.short_name`` marks every Pallas kernel by that target.  The
# verify executables hold one kernel, so the mark is enough today.
KERNEL_PATTERNS = (r" tpu_custom_call$",)


def kernel_seconds(ctx):
    if ctx.trace is None or not ctx.traced_records:
        return None, 0
    seconds, _ = ctx.trace.seconds_of(KERNEL_PATTERNS)
    return (seconds or None), sum(r.signatures for r in ctx.traced_records)


def read(ctx):
    seconds, sigs = kernel_seconds(ctx)
    return 1e6 * seconds / sigs if seconds and sigs else None
