"""What the benchmark takes from the program's span recorder
(``cometbft_tpu/libs/tracing.py``): per-stage counts and summed durations
over the window, read AFTER the window from the totals the recorder keeps by
whole second (the harness calls nothing of the program before the window
that a reader could hook, and the ring of whole spans wraps in seconds).

The interval is ``[records[0].start, records[-1].end]`` on
``time.perf_counter``, which is the recorder's default clock too; only the
whole seconds inside it are read, so a mean divides a stage's seconds by the
stage's OWN count over the same seconds, and the second cut off at either
end costs nothing.  A program without the store (the parent of PR 26), or
with the recorder off, gives ``None`` and the readers leave their metric out.

Stages and what reads them (the names are the program's contract, listed in
``PERF.md`` §3): ``layers/entry_sign_bytes_ms``, ``entry_self_ms``,
``seam_self_ms``, ``sched_submit_ms``, ``sched_wait_ms``,
``sched_flush_self_ms``, ``sched_resolve_ms``, ``host_pack_ms``,
``launch_ms``.  By hand: ``span_gaps.py`` (idle gaps of the device by the
span open at the time) and ``recorder_cost.py`` (a run with the recorder
off, and the stages' means by tenth of the window).
"""

from __future__ import annotations

from types import SimpleNamespace

REQUEST = "verify.commit"
FLUSH = "sched.flush"


def totals(ctx) -> "dict | None":
    """``{stage: (count, seconds)}`` over the window's whole seconds; None
    where the program keeps no such store or recorded nothing."""
    if not hasattr(ctx, "_stage_totals"):
        ctx._stage_totals = _read(ctx)
    return ctx._stage_totals


def _read(ctx):
    if not ctx.records:
        return None
    from cometbft_tpu.libs import tracing

    read = getattr(tracing.get_tracer(), "stage_totals", None)
    if read is None:
        return None
    return read(ctx.records[0].start, ctx.records[-1].end) or None


def counts(ctx) -> "tuple[int, int] | None":
    """Requests and flushes in the interval."""
    t = totals(ctx)
    if t is None:
        return None
    return t.get(REQUEST, (0, 0.0))[0], t.get(FLUSH, (0, 0.0))[0]


def mean_ms(ctx, stage: str) -> "float | None":
    """Mean duration of one span of ``stage``."""
    t = totals(ctx)
    if t is None or stage not in t:
        return None
    n, seconds = t[stage]
    return 1e3 * seconds / n if n else None


def self_ms(ctx, stage: str, children: "tuple[str, ...]") -> "float | None":
    """Mean self time of one span of ``stage``: its seconds less those of
    ``children``, each of which has no other parent on the path the cells
    take, over the stage's own count.  A child with no span counts as 0 (a
    request answered from the cache has no ``sched.segment``)."""
    t = totals(ctx)
    if t is None or stage not in t:
        return None
    n, seconds = t[stage]
    if not n:
        return None
    inside = sum(t.get(c, (0, 0.0))[1] for c in children)
    return 1e3 * (seconds - inside) / n


def run_by_hand(workload: str, seed: int, seconds: float, trace: bool,
                started: float, recorder: bool = True):
    """One run as ``run.py`` makes it (same refusals, same set-up, same
    window) for the two by-hand commands, which need what the result line
    does not hold: returns (result, the loop's ``Window``).  ``recorder``
    False sets ``COMETBFT_TPU_TRACE=0``, the one variable ``run.py`` would
    refuse that a by-hand run may set, and only from here."""
    import os

    from benchmarks import chain, harness, manifest, program
    from benchmarks.run import find_chips

    forced = program.forced_variables()
    if forced:
        raise SystemExit(f"unset {forced}: nothing may be forced here")
    if not recorder:
        os.environ["COMETBFT_TPU_TRACE"] = "0"
    cell = manifest.Cell(manifest.load(), workload)
    kept = {}
    loop = cell.loop

    def run_and_keep(*a, **kw):
        kept["window"] = loop.run(*a, **kw)
        return kept["window"]

    cell.loop = SimpleNamespace(run=run_and_keep)
    pool = chain.SignPool()
    try:
        signing = harness.start_signing(cell, seed, pool)
        program.enable_caches()
        device = find_chips(cell.chips)
        result = harness.run_cell(
            cell, seed, seconds, trace, started, device,
            pool=pool, signing=signing, require_backend="tpu",
        )
    finally:
        pool.close()
    return result, kept["window"]


def mean_request_ms(window) -> float:
    return 1e3 * sum(r.end - r.start for r in window.records) / len(window.records)
