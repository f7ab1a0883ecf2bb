"""The entry's own time a request: ``verify.commit`` less ``commit.sign_bytes`` and
``batch.verify``, which leaves the basic checks, the collection of entries, the
verifier's construction, judge and tally."""

from benchmarks import spans

NAME, UNIT, BETTER = "entry_self_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "entry", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.self_ms(ctx, "verify.commit", ("commit.sign_bytes", "batch.verify"))
