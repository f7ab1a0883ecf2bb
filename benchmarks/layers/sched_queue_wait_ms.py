"""Mean time an item waited in the scheduler's queue before its flush, over
all classes (``verifysched/stats.queue_wait_hist``, host clock)."""

NAME, UNIT, BETTER = "sched_queue_wait_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "scheduler", "program_counter", "verify_p50_ms"


def read(ctx):
    c = ctx.counters
    if not c["sched_queue_wait_n"]:
        return None
    return 1e3 * c["sched_queue_wait_s"] / c["sched_queue_wait_n"]
