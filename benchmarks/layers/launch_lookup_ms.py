"""Mean time of resolving one launch's executable on the watchdog thread (span
``verify.launch.lookup``: ``bucket_executable``, mesh-wide
``sharded_verify_call``); the first of ``launch_ms``'s three parts."""

from benchmarks import spans

NAME, UNIT, BETTER = "launch_lookup_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "executable", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "verify.launch.lookup")
