"""Mean time of the ``submit_many`` loop of one request (span ``sched.submit``):
one future, one lock and one notify a signature."""

from benchmarks import spans

NAME, UNIT, BETTER = "sched_submit_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "scheduler", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "sched.submit")
