"""Scheduler flushes over requests: into how many pieces a request is cut."""

NAME, UNIT, BETTER = "flushes_per_request", "count", "lower"
LAYER, SOURCE, MOVES = "scheduler", "program_counter", "verify_p50_ms"


def read(ctx):
    if not ctx.records or not ctx.counters["sched_flushes"]:
        return None
    return ctx.counters["sched_flushes"] / len(ctx.records)
