"""One height stored and applied: span ``blocksync.apply`` (the block
store's save, then ``apply_verified_block``: the app's FinalizeBlock and
Commit, the state's save, the events), a mean."""

from benchmarks import spans

NAME, UNIT, BETTER = "bsync_apply_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "blocksync", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "blocksync.apply")
