"""Signatures in the verified prefix of a request, counted by the harness
over the window's requests: the work a request is."""

NAME, UNIT, BETTER = "sigs_per_request", "count", "higher"
LAYER, SOURCE, MOVES = "entry", "program_counter", "sigs_per_s"


def read(ctx):
    if not ctx.records:
        return None
    return sum(r.signatures for r in ctx.records) / len(ctx.records)
