"""Heights a scheduler flush carried in blocksync: the count of
``blocksync.apply`` over the count of ``sched.flush``.  About 1 where each
height's commit leaves alone; the window's size, 7, where a window leaves in
one flush and the frontier needs nothing else."""

from benchmarks import spans

NAME, UNIT, BETTER = "bsync_heights_per_flush", "count", "higher"
LAYER, SOURCE, MOVES = "scheduler", "program_span", "sigs_per_s"


def read(ctx):
    t = spans.totals(ctx)
    if t is None:
        return None
    heights = t.get("blocksync.apply", (0, 0.0))[0]
    flushes = t.get("sched.flush", (0, 0.0))[0]
    return heights / flushes if heights and flushes else None
