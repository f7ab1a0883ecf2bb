"""Mean time of one segment's look-up (span ``batch.lookup``: the ``_get_many``
visit to the cache and the partition into hits and misses)."""

from benchmarks import spans

NAME, UNIT, BETTER = "seam_lookup_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "batch seam", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "batch.lookup")
