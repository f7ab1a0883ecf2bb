"""Mean time of the executable's call returning (span ``verify.launch.call``): the
enqueue of the kernel, not its run; the third of ``launch_ms``'s three parts."""

from benchmarks import spans

NAME, UNIT, BETTER = "launch_call_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "executable", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "verify.launch.call")
