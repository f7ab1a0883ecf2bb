"""Mean wall time of one device dispatch, dispatch to fetched bits, over all
tiers and buckets (``dispatch_stats.dispatch_hist``, host clock).  A mean
per dispatch: dispatches in flight overlap, so their sum says nothing
against request time."""

NAME, UNIT, BETTER = "dispatch_wall_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "supervisor", "program_counter", "verify_p50_ms"


def read(ctx):
    c = ctx.counters
    if not c["dispatch_wall_n"]:
        return None
    return 1e3 * c["dispatch_wall_s"] / c["dispatch_wall_n"]
