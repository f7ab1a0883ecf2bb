"""Mean time of one launch on the watchdog thread (span ``verify.launch``): the
executable's lookup, the five transfers and the call returning."""

from benchmarks import spans

NAME, UNIT, BETTER = "launch_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "executable", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "verify.launch")
