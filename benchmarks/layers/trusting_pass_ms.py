"""One trusting pass (``verify_commit_light_trusting``: by address against
the trusted set): span ``verify.commit.trusting``, a stage of its own beside
the light pass's ``verify.commit``."""

from benchmarks import spans

NAME, UNIT, BETTER = "trusting_pass_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "entry", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "verify.commit.trusting")
