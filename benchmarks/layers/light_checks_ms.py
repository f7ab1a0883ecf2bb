"""The light verifier's header, time and hash-link checks a request: span
``light.checks`` (a header hash and the new set's Merkle root among them)."""

from benchmarks import spans

NAME, UNIT, BETTER = "light_checks_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "light verifier", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "light.checks")
