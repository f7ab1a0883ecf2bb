"""The signature cache's hits in the window over the signatures of the
window's ``last_commit`` requests.  No vote of the traffic is a hit (a copy
is answered before the cache is asked), so 100 says that every LastCommit
signature was answered from the cache and none reached the device; under 100,
some did."""

NAME, UNIT, BETTER = "lastcommit_hit_pct", "%", "higher"
LAYER, SOURCE, MOVES = "batch seam", "program_counter", "sigs_per_s"


def read(ctx):
    votes = getattr(ctx.chain, "votes", None)  # ``votechain.Votes``
    if votes is None:
        return None
    asked = sum(r.signatures for r in ctx.records
                if votes.request(r.key).kind == "last_commit")
    return 100.0 * ctx.counters["sigcache_hits"] / asked if asked else None
