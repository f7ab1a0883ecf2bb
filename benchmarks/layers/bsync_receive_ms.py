"""One BlockResponse decoded on the receive thread: span
``blocksync.receive``, a mean."""

from benchmarks import spans

NAME, UNIT, BETTER = "bsync_receive_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "blocksync", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "blocksync.receive")
