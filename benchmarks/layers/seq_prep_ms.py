"""One header's host pass in the sequential light client: span
``light.chain.prep`` (its checks and set root, its sign-bytes, its cache
look-up and the queueing of its misses), a mean a header."""

from benchmarks import spans

NAME, UNIT, BETTER = "seq_prep_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "light client", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "light.chain.prep")
