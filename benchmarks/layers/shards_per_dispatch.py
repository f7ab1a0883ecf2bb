"""Shards fetched over dispatches in the window (spans ``mesh.shard`` over
``verify.dispatch``): how many chips one dispatch's lanes were divided over.
The mesh's width where every dispatch went mesh-wide and no chip was lost;
lower where a shrink happened or a dispatch ran on one chip."""

from benchmarks import spans

NAME, UNIT, BETTER = "shards_per_dispatch", "count", "higher"
LAYER, SOURCE, MOVES = "supervisor", "program_span", "sigs_per_s"


def read(ctx):
    t = spans.totals(ctx)
    if t is None or "mesh.shard" not in t:
        return None
    dispatches = t.get("verify.dispatch", (0, 0.0))[0]
    return t["mesh.shard"][0] / dispatches if dispatches else None
