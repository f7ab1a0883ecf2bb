"""What the supervisor's watchdog costs one dispatch: a fresh thread and two
switches, twice.  (``verify.dispatch`` less ``verify.launch``) is the launch's
trip; (``verify.fetch`` less ``verify.fetch.pull``) the fetch's.  A mesh-wide
fetch has no pull of its own: its ``mesh.shard`` spans are subtracted, each of
which HOLDS its shard's watchdog trip, so there the second term is the loop's
own lines only.  The records of the laps inside the two spans are in it too."""

from benchmarks import spans

NAME, UNIT, BETTER = "watchdog_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "supervisor", "program_span", "verify_p50_ms"


def read(ctx):
    t = spans.totals(ctx)
    spanned = ("verify.dispatch", "verify.launch", "verify.fetch")
    if t is None or any(s not in t for s in spanned):
        return None
    pulls = [s for s in ("verify.fetch.pull", "mesh.shard") if s in t]
    n = t["verify.dispatch"][0]
    if not pulls or not n:
        return None
    trips = (
        t["verify.dispatch"][1] - t["verify.launch"][1]
        + t["verify.fetch"][1] - sum(t[s][1] for s in pulls)
    )
    return 1e3 * trips / n
