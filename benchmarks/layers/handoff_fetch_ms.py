"""Mean time of one hand-off from the dispatcher to the completion thread (span
``sched.handoff.fetch``): the append to the fetch queue, stamped on the
dispatcher, to the completion thread holding the flush."""

from benchmarks import spans

NAME, UNIT, BETTER = "handoff_fetch_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "scheduler", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "sched.handoff.fetch")
