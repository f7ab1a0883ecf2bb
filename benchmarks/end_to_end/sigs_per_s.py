"""Signatures in the verified prefix of every request answered in the
window, accepted or rejected, over the whole window's time."""

NAME, UNIT, BETTER, SOURCE = "sigs_per_s", "sigs/s", "higher", "host_clock"


def read(ctx):
    if not ctx.records or ctx.window_s <= 0:
        return None
    return sum(r.signatures for r in ctx.records) / ctx.window_s
