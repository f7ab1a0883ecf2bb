"""Lanes that carried a real signature over the padded lanes dispatched in
the window (``dispatch_stats.record_dispatch``)."""

NAME, UNIT, BETTER = "lane_occupancy_pct", "%", "higher"
LAYER, SOURCE, MOVES = "executable", "program_counter", "sigs_per_s"


def read(ctx):
    c = ctx.counters
    return 100.0 * c["lanes_used"] / c["lanes_total"] if c["lanes_total"] else None
