"""The light client's store a request: the seconds of span ``light.store``
(the look-up of the target, the save of the verified target) over the count
of ``light.sync``, the request's root."""

from benchmarks import spans

NAME, UNIT, BETTER = "seq_store_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "light client", "program_span", "verify_p50_ms"


def read(ctx):
    t = spans.totals(ctx)
    if t is None or not t.get("light.sync", (0, 0.0))[0] or "light.store" not in t:
        return None
    return 1e3 * t["light.store"][1] / t["light.sync"][0]
