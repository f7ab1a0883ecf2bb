"""The frontier's wait for a window that had not landed when it got
there: the seconds of span ``blocksync.wait`` over the heights applied
(``blocksync.apply``).  What the overlap with the apply did not hide."""

from benchmarks import spans

NAME, UNIT, BETTER = "bsync_wait_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "blocksync", "program_span", "verify_p95_ms"


def read(ctx):
    t = spans.totals(ctx)
    heights = t.get("blocksync.apply", (0, 0.0))[0] if t else 0
    if not heights:
        return None
    return 1e3 * t.get("blocksync.wait", (0, 0.0))[1] / heights
