"""What of a caller's wait no span names: ``sched.wait`` less everything named
that happens during it, over the waits.  Exact where one caller waits at a
time, which is every cell today (with concurrent callers one flush's stages
would be counted once and waited for several times).  What is left is the
recorder's own lines between two neighbours; a small negative reading is
``sched.queue`` beginning inside ``sched.submit``, before the wait does."""

from benchmarks import spans

NAME, UNIT, BETTER = "wait_unseen_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "scheduler", "program_span", "verify_p50_ms"

NAMED = (
    "sched.queue", "sched.flush", "sched.handoff.fetch", "sched.fetch",
    "sched.landed", "sched.resolve", "sched.handoff.wake",
)


def read(ctx):
    t = spans.totals(ctx)
    if t is None or "sched.wait" not in t or "sched.handoff.wake" not in t:
        return None
    n, waited = t["sched.wait"]
    named = sum(t.get(s, (0, 0.0))[1] for s in NAMED)
    return 1e3 * (waited - named) / n if n else None
