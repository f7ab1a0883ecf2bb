"""Mean time of the last hand-off of a request (span ``sched.handoff.wake``): the
stamp the completion thread takes before an entry's statistics and
``set_result``, to the caller back from ``Future.result()``."""

from benchmarks import spans

NAME, UNIT, BETTER = "handoff_wake_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "scheduler", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "sched.handoff.wake")
