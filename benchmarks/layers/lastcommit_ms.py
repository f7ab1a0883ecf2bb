"""One ``verify_commit`` of a LastCommit made of votes verified a moment ago:
span ``verify.commit`` (mode ``full``): the scan, the sign-bytes, one cache
key and one look-up a signature, the tally, and no dispatch."""

from benchmarks import spans

NAME, UNIT, BETTER = "lastcommit_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "entry", "program_span", "sigs_per_s"


def read(ctx):
    return spans.mean_ms(ctx, "verify.commit")
