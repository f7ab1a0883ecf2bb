"""Signatures a scheduler flush carried (``flush_items`` over ``flushes``).
One caller sends one vote and waits for it, so 1.0: what votes verified
ahead of the receive routine would raise (``ROADMAP.md`` W1)."""

NAME, UNIT, BETTER = "sigs_per_flush", "count", "higher"
LAYER, SOURCE, MOVES = "scheduler", "program_counter", "sigs_per_s"


def read(ctx):
    c = ctx.counters
    return c["sched_flush_items"] / c["sched_flushes"] if c["sched_flushes"] else None
