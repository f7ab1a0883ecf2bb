"""A height's share of blocksync's window: the seconds of span
``blocksync.window`` (the host pass that prepares, partitions and queues a
window of commits as one segment) over the count of ``blocksync.apply``,
the heights applied."""

from benchmarks import spans

NAME, UNIT, BETTER = "bsync_window_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "blocksync", "program_span", "verify_p95_ms"


def read(ctx):
    t = spans.totals(ctx)
    heights = t.get("blocksync.apply", (0, 0.0))[0] if t else 0
    if not heights:
        return None
    return 1e3 * t.get("blocksync.window", (0, 0.0))[1] / heights
