"""Share of the traced slice in which no operation ran on the device: 1 less
the union of the device-busy intervals over the slice."""

NAME, UNIT, BETTER = "device_idle_pct", "%", "lower"
LAYER, SOURCE, MOVES = "device", "device_trace", "sigs_per_s"


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
