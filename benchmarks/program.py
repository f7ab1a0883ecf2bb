"""Everything the benchmark takes from the program besides the entry it
times: the cache root, the warm-up of executables, the counters.

``reachable_buckets`` and ``warm_buckets`` are copies from ``chip_smoke.py``
(PR 22, proven on the chip).  ``counters`` flattens the program's own
counters into one dict of numbers, so that a per-layer reader takes a
difference over the window and never imports the program.
"""

from __future__ import annotations

import os
import time

# what a node selects by itself is what is under test (chip_smoke.py's
# _MUST_BE_UNSET, and the switches of scheduler, signature cache and tracing)
MUST_BE_UNSET = (
    "COMETBFT_TPU_CRYPTO_BACKEND",
    "COMETBFT_TPU_VERIFY_IMPL",
    "COMETBFT_TPU_SUPERVISOR",
    "COMETBFT_TPU_AOT",
    "COMETBFT_TPU_MESH",
    "COMETBFT_TPU_VERIFY_SCHED",
    "COMETBFT_TPU_SIGCACHE",
    "COMETBFT_TPU_TRACE",
)


def forced_variables() -> "list[str]":
    return [v for v in MUST_BE_UNSET if v in os.environ]


def enable_caches() -> str:
    """The program's one cache root: ``JAX_COMPILATION_CACHE_DIR`` where it
    is set, else ``.cache/`` in the checkout.  The background warm-boot
    matrix (ten buckets times two tiers) would compile for minutes behind
    the window; the cell's own buckets are warmed in the foreground."""
    os.environ["COMETBFT_TPU_WARMBOOT"] = "0"
    from cometbft_tpu.libs import cachedir

    return cachedir.enable()


def reachable_buckets(largest: int, tier: str) -> "list[int]":
    from cometbft_tpu.ops import verify as ov

    low = ov._PALLAS_MIN_BUCKET if tier == "pallas" else ov._BUCKETS[0]
    top = ov.bucket_size(largest, low)
    return [b for b in ov._BUCKETS if low <= b <= top]


def warm_buckets(buckets, tier: str) -> dict:
    """Resolve each bucket's executable in the foreground: compile or cache
    load, as the program's ``bucket_executable`` decides."""
    from cometbft_tpu.ops import verify as ov

    out = {}
    for lanes in buckets:
        t0 = time.perf_counter()
        _, info = ov.bucket_executable(tier, lanes)
        out[str(lanes)] = {**info, "seconds": round(time.perf_counter() - t0, 2)}
    return out


def warm_verify(largest: int) -> dict:
    """Resolve the batch backend (the program runs its known-answer
    self-check) and warm what a batch of up to ``largest`` signatures can
    reach.  With no trusted device the host path serves and nothing is
    warmed; ``run.py`` refuses such a machine before it gets here."""
    from cometbft_tpu.crypto import batch as cbatch

    backend = cbatch.default_backend()
    info = {"backend": backend, "tier": None, "buckets": {}}
    if backend != "tpu":
        return info
    from cometbft_tpu.ops import verify as ov

    info["tier"] = tier = ov.select_impl()
    info["buckets"] = warm_buckets(reachable_buckets(largest, tier), tier)
    return info


def device_backend_is_resolved() -> bool:
    """Once a device backend is trusted, ``ValidatorSet.hash`` and other host
    work of set-up would go to the device: the entries build their objects
    before that."""
    from cometbft_tpu.crypto import batch as cbatch

    return cbatch._DEFAULT_BACKEND not in (None, "cpu")


def counters() -> dict:
    """The program's counters, flat.  Times are host-clock sums."""
    from cometbft_tpu.crypto import backend_health, sigcache
    from cometbft_tpu.ops import dispatch_stats, warm_stats
    from cometbft_tpu.verifysched import stats as sched_stats

    ds = dispatch_stats.snapshot()
    ss = sched_stats.snapshot()
    sc = sigcache.get_cache().stats()
    bh = backend_health.snapshot()
    ws = warm_stats.snapshot()
    out = {
        "sigcache_hits": sc["hits"],
        "sigcache_misses": sc["misses"],
        "sched_submitted": sum(ss["submitted"].values()),
        "sched_shed": sum(ss["shed"].values()),
        "sched_queue_wait_s": sum(h["sum"] for h in ss["queue_wait_hist"].values()),
        "sched_queue_wait_n": sum(h["count"] for h in ss["queue_wait_hist"].values()),
        "sched_flushes": sum(ss["flushes"].values()),
        "sched_flush_items": ss["flush_items"],
        "sched_flush_misses": ss["flush_misses"],
        "sched_flush_lanes": ss["flush_lanes"],
        "dispatches": ds["dispatches"],
        "lanes_total": ds["lanes_total"],
        "lanes_used": ds["lanes_used"],
        "dispatch_wall_s": sum(h["sum"] for h in ds["dispatch_hist"].values()),
        "dispatch_wall_n": sum(h["count"] for h in ds["dispatch_hist"].values()),
        "verify_calls": ds["verify_calls"],
        "fallback_signatures": bh["fallback_signatures"],
        "demotions": bh["demotions"],
        "watchdog_fires": bh["watchdog_fires"],
        "quarantined": bh["quarantined"],
        "breaker_failures": sum(
            b["failures_total"] for b in bh["breakers"].values()
        ),
        "compiles": ws["compiles"],
        "compile_failures": ws["compile_failures"],
        "exec_hits": ws["exec_hits"],
        "exec_misses": ws["exec_misses"],
    }
    for key, h in ds["dispatch_hist"].items():
        out[f"dispatch_n:{key}"] = h["count"]
    for lane, n in ds["lane_lanes_used"].items():
        out[f"tier_sigs:{lane}"] = n
    return out


def delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def shut_down() -> None:
    """Stop the program's threads (the scheduler's dispatcher and fetcher)."""
    import sys

    sched = sys.modules.get("cometbft_tpu.verifysched")
    if sched is not None:
        sched.reset_scheduler()
