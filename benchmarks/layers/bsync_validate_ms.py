"""One ``validate_block`` of the frontier height: span
``blocksync.validate`` (its body and header checks and the full check of
its LastCommit, whose verdicts the window put in the cache), a mean."""

from benchmarks import spans

NAME, UNIT, BETTER = "bsync_validate_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "blocksync", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "blocksync.validate")
