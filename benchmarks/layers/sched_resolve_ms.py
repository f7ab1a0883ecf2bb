"""Mean time a flush spent after its fetch (span ``sched.resolve``): the verdict
map, the cache puts and ``set_result`` for every item."""

from benchmarks import spans

NAME, UNIT, BETTER = "sched_resolve_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "scheduler", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "sched.resolve")
