"""Share of the signature cache's lookups in the window that hit.  Every
height is fresh, so 0 is expected: a hit is a signature that never reached
the device."""

NAME, UNIT, BETTER = "sigcache_hit_pct", "%", "lower"
LAYER, SOURCE, MOVES = "batch seam", "program_counter", "sigs_per_s"


def read(ctx):
    c = ctx.counters
    lookups = c["sigcache_hits"] + c["sigcache_misses"]
    return 100.0 * c["sigcache_hits"] / lookups if lookups else None
