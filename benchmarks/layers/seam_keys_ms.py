"""Mean time of hashing one segment's cache keys (span ``batch.keys``:
``SigCache.hash_keys``, one SHA-256 a signature)."""

from benchmarks import spans

NAME, UNIT, BETTER = "seam_keys_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "batch seam", "program_span", "verify_p50_ms"


def read(ctx):
    return spans.mean_ms(ctx, "batch.keys")
