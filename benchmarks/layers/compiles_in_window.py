"""Compilations the program counted between the end of warm-up and the end
of the window (``ops/warm_stats``).  0 is expected."""

NAME, UNIT, BETTER = "compiles_in_window", "count", "lower"
LAYER, SOURCE, MOVES = "executable", "program_counter", "verify_p95_ms"


def read(ctx):
    return ctx.counters["compiles"]
