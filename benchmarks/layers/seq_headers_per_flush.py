"""Headers a scheduler flush carried in the sequential light client: the
count of ``light.chain.prep`` over the count of ``sched.flush``.  1.0 where
each header's segment leaves alone; more where headers queue behind a flush
in flight and leave together."""

from benchmarks import spans

NAME, UNIT, BETTER = "seq_headers_per_flush", "count", "higher"
LAYER, SOURCE, MOVES = "scheduler", "program_span", "sigs_per_s"


def read(ctx):
    t = spans.totals(ctx)
    if t is None:
        return None
    headers, flushes = t.get("light.chain.prep", (0, 0.0))[0], t.get("sched.flush", (0, 0.0))[0]
    return headers / flushes if headers and flushes else None
