"""Share of the window's signatures answered by any tier but the one
``select_impl()`` chose: dispatches routed to another backend and
signatures the host reference re-verified.  0 is expected."""

NAME, UNIT, BETTER = "offtier_sigs_pct", "%", "lower"
LAYER, SOURCE, MOVES = "supervisor", "program_counter", "sigs_per_s"


def read(ctx):
    c = ctx.counters
    by_tier = {
        k.split(":", 1)[1]: v for k, v in c.items() if k.startswith("tier_sigs:")
    }
    total = sum(by_tier.values()) + c["fallback_signatures"]
    if not total or ctx.tier is None:
        return None
    off = total - by_tier.get(ctx.tier, 0)
    return 100.0 * off / total
