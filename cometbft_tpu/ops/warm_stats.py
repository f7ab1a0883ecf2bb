"""Process-wide counters for the AOT executable cache and warm-boot pass.

Deliberately free of jax imports, exactly like ``ops/dispatch_stats``:
``libs/metrics.NodeMetrics`` reads these through callback gauges and a
/metrics scrape must never be the thing that initializes an accelerator
backend.  ``ops/aot_cache.py`` and ``ops/warmboot.py`` write them (and the
tier-1 conftest prints a one-line summary that
``scripts/check_tier1_budget.py`` parses into a compile-time share).

Counters (all guarded by one lock):
  * ``compiles`` / ``compile_seconds``   — executables built by tracing +
    XLA compilation (the cost warm-boot exists to amortize)
  * ``compile_failures``                 — lowerings/compiles the device's
    compiler refused (``ops.verify.bucket_executable``; each is logged at
    error and demotes its tier)
  * ``exec_hits`` / ``exec_load_seconds`` — executables deserialized from
    the on-disk cache (no tracing, no compilation)
  * ``exec_misses``                      — cache probes that found nothing
  * ``exec_stale``                       — cache entries rejected as
    corrupt/truncated/wrong-format (recompiled)
  * ``exec_unsupported``                 — serialize/deserialize not
    supported by the PJRT plugin (degraded to plain jit, never an error)
  * ``exec_writes`` / ``exec_write_bytes`` — executables persisted
  * ``exec_evicted``                     — stale-fingerprint entries
    removed by the cache-dir bound
  * ``warm_runs`` / ``warm_seconds``     — warm-boot passes and their wall
    time
  * ``shapes_warmed`` / ``shapes_pruned`` / ``warm_failures`` — warm-boot
    matrix outcomes (pruned = shapes the collapsed matrix skipped)
"""

from __future__ import annotations

import threading

_LOCK = threading.Lock()


def _zero() -> dict:
    return {
        "compiles": 0,
        "compile_seconds": 0.0,
        "compile_failures": 0,
        "exec_hits": 0,
        "exec_load_seconds": 0.0,
        "exec_misses": 0,
        "exec_stale": 0,
        "exec_unsupported": 0,
        "exec_writes": 0,
        "exec_write_bytes": 0,
        "exec_evicted": 0,
        "warm_runs": 0,
        "warm_seconds": 0.0,
        "shapes_warmed": 0,
        "shapes_pruned": 0,
        "warm_failures": 0,
    }


_STATS = _zero()


def record_compile(seconds: float) -> None:
    with _LOCK:
        _STATS["compiles"] += 1
        _STATS["compile_seconds"] += float(seconds)


def record_compile_failure() -> None:
    with _LOCK:
        _STATS["compile_failures"] += 1


def record_hit(load_seconds: float) -> None:
    with _LOCK:
        _STATS["exec_hits"] += 1
        _STATS["exec_load_seconds"] += float(load_seconds)


def record_miss() -> None:
    with _LOCK:
        _STATS["exec_misses"] += 1


def record_stale() -> None:
    with _LOCK:
        _STATS["exec_stale"] += 1


def record_unsupported() -> None:
    with _LOCK:
        _STATS["exec_unsupported"] += 1


def record_write(n_bytes: int) -> None:
    with _LOCK:
        _STATS["exec_writes"] += 1
        _STATS["exec_write_bytes"] += int(n_bytes)


def record_evicted(n: int = 1) -> None:
    if n:
        with _LOCK:
            _STATS["exec_evicted"] += int(n)


def record_warm_run(seconds: float, warmed: int, pruned: int,
                    failures: int) -> None:
    with _LOCK:
        _STATS["warm_runs"] += 1
        _STATS["warm_seconds"] += float(seconds)
        _STATS["shapes_warmed"] += int(warmed)
        _STATS["shapes_pruned"] += int(pruned)
        _STATS["warm_failures"] += int(failures)


def snapshot() -> dict:
    with _LOCK:
        return dict(_STATS)


def reset() -> None:
    global _STATS
    with _LOCK:
        _STATS = _zero()


def summary_line() -> str:
    """One parseable line for test logs (scripts/check_tier1_budget.py
    reads the compile share of tier-1 wall time from it)."""
    s = snapshot()
    return (
        "tier1-exec-cache: compiles=%d compile_s=%.1f hits=%d load_s=%.1f "
        "stale=%d unsupported=%d writes=%d write_mb=%.1f"
        % (
            s["compiles"],
            s["compile_seconds"],
            s["exec_hits"],
            s["exec_load_seconds"],
            s["exec_stale"],
            s["exec_unsupported"],
            s["exec_writes"],
            s["exec_write_bytes"] / 1e6,
        )
    )
