"""Mean time of the native sign-bytes call of one request (span ``commit.sign_bytes``)."""

from benchmarks import spans

NAME, UNIT, BETTER = "entry_sign_bytes_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "entry", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "commit.sign_bytes")
