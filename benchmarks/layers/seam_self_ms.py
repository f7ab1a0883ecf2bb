"""The batch seam's own time a request: ``batch.verify`` less ``sched.segment``,
which leaves the adds, the cache keys and lookups, the list rebuilds and the
write-back."""

from benchmarks import spans

NAME, UNIT, BETTER = "seam_self_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "batch seam", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.self_ms(ctx, "batch.verify", ("sched.segment",))
