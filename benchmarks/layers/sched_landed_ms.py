"""Mean time of giving a flush's slot back (span ``sched.landed``): two locks, one
after the other, and their notifies, between the fetch and the resolve."""

from benchmarks import spans

NAME, UNIT, BETTER = "sched_landed_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "scheduler", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "sched.landed")
