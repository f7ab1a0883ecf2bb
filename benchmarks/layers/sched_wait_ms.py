"""Mean time the caller of one request was blocked on its futures, first to last
(span ``sched.wait``)."""

from benchmarks import spans

NAME, UNIT, BETTER = "sched_wait_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "scheduler", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "sched.wait")
