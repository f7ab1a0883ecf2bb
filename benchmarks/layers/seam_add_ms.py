"""Mean time of the ``bv.add`` loop of one ``batch.verify`` (span ``batch.add``,
``types/validation.py``): one call a signature into the collecting verifier."""

from benchmarks import spans

NAME, UNIT, BETTER = "seam_add_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "batch seam", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "batch.add")
