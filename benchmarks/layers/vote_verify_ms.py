"""One ``Vote.verify`` as the vote set calls it: span ``consensus.vote``, the
sign-bytes, the cache look-up, the scheduler's n = 1 entry and the wait on
its future.  A copy answered before any signature is looked at has none."""

from benchmarks import spans

NAME, UNIT, BETTER = "vote_verify_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "entry", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "consensus.vote")
