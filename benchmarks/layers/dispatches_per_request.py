"""Device dispatches over requests (``dispatch_stats.dispatches``): a
skipping step makes two, one a pass, the second waiting for the first's
verdicts and write-back."""

NAME, UNIT, BETTER = "dispatches_per_request", "count", "lower"
LAYER, SOURCE, MOVES = "supervisor", "program_counter", "verify_p50_ms"


def read(ctx):
    if not ctx.records or not ctx.counters.get("dispatches"):
        return None
    return ctx.counters["dispatches"] / len(ctx.records)
