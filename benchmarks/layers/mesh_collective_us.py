"""Device time of the mesh's collective (the ``psum`` of the per-shard accept
counts, an all-reduce) for one dispatch: the all-reduce events' device
seconds, mean over chips, over the dispatches of the traced slice (verify
kernel events over chips).  None, never 0, where the trace shows no such
event: a one-chip launch has none."""

from benchmarks.layers import kernel_us_per_sig

NAME, UNIT, BETTER = "mesh_collective_us", "us", "lower"
LAYER, SOURCE, MOVES = "device", "device_trace", "verify_p50_ms"

# as the trace prints them (``trace_reduce.short_name`` keeps the result's
# name, which XLA takes from the JAX primitive): ``psum.7 s32[]`` on the
# chip (looked at by hand, PR 34); ``all-reduce.N``, or the pair
# ``all-reduce-start`` / ``-done``, where the compiler names it itself
COLLECTIVE_PATTERNS = (r"^psum(\.|$| )", r"^all-reduce")


def read(ctx):
    if ctx.trace is None or not ctx.traced_records:
        return None
    seconds, events = ctx.trace.seconds_of(COLLECTIVE_PATTERNS)
    _, kernels = ctx.trace.seconds_of(kernel_us_per_sig.KERNEL_PATTERNS)
    dispatches = kernels / ctx.trace.chips
    if not events or not seconds or not dispatches:
        return None
    return 1e6 * seconds / dispatches
