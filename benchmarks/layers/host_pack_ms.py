"""Mean time of ``prepare_batch`` for one dispatch (span ``verify.pack``): the
host pack and SHA-512."""

from benchmarks import spans

NAME, UNIT, BETTER = "host_pack_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "supervisor", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "verify.pack")
