"""95th percentile (nearest rank) over ALL requests of the window, the
tampered heights included, on the caller's clock."""

import math

NAME, UNIT, BETTER, SOURCE = "verify_p95_ms", "ms", "lower", "host_clock"


def read(ctx):
    if not ctx.records:
        return None
    times = sorted(r.end - r.start for r in ctx.records)
    return times[math.ceil(0.95 * len(times)) - 1] * 1e3
