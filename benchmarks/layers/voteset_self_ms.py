"""The vote set's own time a vote: span ``voteset.add`` (every
``VoteSet.add_vote``, copies included) less ``consensus.vote``, which leaves
the checks, the look for a held vote, the conflict handling and the tally.
A program before the span (the parent of PR 32) gives nothing."""

from benchmarks import spans

NAME, UNIT, BETTER = "voteset_self_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "entry", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.self_ms(ctx, "voteset.add", ("consensus.vote",))
