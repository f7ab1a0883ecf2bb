"""Median over ALL requests of the window, on the caller's clock, from the
call to the verdict (or to the raised rejection)."""

import statistics

NAME, UNIT, BETTER, SOURCE = "verify_p50_ms", "ms", "lower", "host_clock"


def read(ctx):
    if not ctx.records:
        return None
    return statistics.median(r.end - r.start for r in ctx.records) * 1e3
