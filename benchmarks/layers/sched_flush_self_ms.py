"""The dispatcher's own time a flush: ``sched.flush`` less ``sched.dispatch``,
which leaves the dedup keys, the list rebuilds and the wait for an in-flight
slot."""

from benchmarks import spans

NAME, UNIT, BETTER = "sched_flush_self_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "scheduler", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.self_ms(ctx, "sched.flush", ("sched.dispatch",))
