"""Mean time of one segment's write-back (span ``batch.writeback``: the fresh
verdicts into the bits and, in one visit, into the cache).  No span where every
look-up was a hit: the validator's LastCommit makes none."""

from benchmarks import spans

NAME, UNIT, BETTER = "seam_writeback_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "batch seam", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "batch.writeback")
