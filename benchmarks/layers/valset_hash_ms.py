"""One ``ValidatorSet.hash``: span ``valset.hash`` (the SimpleValidator
encodings and the Merkle root, on whichever tier the plane sends it to)."""

from benchmarks import spans

NAME, UNIT, BETTER = "valset_hash_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "hash plane", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "valset.hash")
