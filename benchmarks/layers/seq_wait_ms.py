"""The sequential light client's wait for one header's verdicts after its
own work: span ``light.chain.wait``, a mean a header whose misses were
queued.  What the overlap with the next header's host pass did not hide."""

from benchmarks import spans

NAME, UNIT, BETTER = "seq_wait_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "light client", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "light.chain.wait")
