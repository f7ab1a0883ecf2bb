"""Process-wide counters for the batched-verify pipeline.

Deliberately free of jax imports: ``libs/metrics.NodeMetrics`` reads these
through callback gauges, and a /metrics scrape must never be the thing that
initializes an accelerator backend.  ``ops/verify.py`` (and anything else
that launches verify kernels) writes them.

Counters:
  * ``dispatches``       — device kernel launches
  * ``lanes_total``      — bucket-padded lanes shipped across all dispatches
  * ``lanes_used``       — lanes carrying a real signature (occupancy)
  * ``fused_batches``    — verify_segments calls that fused >1 segment
  * ``fused_segments``   — segments that rode in a fused dispatch
  * ``verify_calls`` / ``verify_seconds`` — commit-verification latency
    aggregate (observed by types/validation)
  * ``watchdog_calls[parked|fresh]`` — calls under the dispatch watchdog
    by the worker that served them (a parked one, or a thread started)

Histograms (docs/observability.md) — real distributions on /metrics, not
just cumulative sums:
  * ``buckets[lanes]``           — dispatch count per padding bucket (the
    per-bucket histogram the bucket-ladder pruning decisions read)
  * ``dispatch_hist[tier-lanes]`` — device dispatch WALL time per
    (supervisor tier, padding bucket), as the one fetch sees it
    (``ops/supervisor._fetch_launched``: from the launch's return to the
    accept bits on the host): a sick lane is attributable to a shape and
    a tier from one scrape
  * ``shard_hist[device]``       — per-device shard fetch wall time on the
    mesh-sharded verify path (``parallel/mesh.fetch_sharded``): one sick
    chip is ONE outlier series, visible per lane before multi-lane
    flushing exists (ROADMAP item 1)
  * ``verify_hist``              — commit verification latency
"""

from __future__ import annotations

import threading

from cometbft_tpu.libs.histo import DISPATCH_BUCKETS_S, Histo

_LOCK = threading.Lock()


def _zero() -> dict:
    return {
        "dispatches": 0,
        "lanes_total": 0,
        "lanes_used": 0,
        "fused_batches": 0,
        "fused_segments": 0,
        "verify_calls": 0,
        "verify_seconds": 0.0,
        "buckets": {},  # lanes -> dispatch count
        "dispatch_hist": {},  # "tier-lanes" -> Histo (wall seconds)
        "shard_hist": {},  # device ordinal (str) -> Histo (wall seconds)
        "verify_hist": Histo(),
        # elastic mesh supervision (parallel/elastic): width the last
        # dispatch targeted (0 = mesh inactive), shrink/restore counts
        "mesh_width": 0,
        "mesh_shrinks": 0,
        "mesh_restores": 0,
        # mesh-wide launches (ops/supervisor._launch_verify over a mesh)
        # and the shards they were divided into
        "mesh_dispatches": 0,
        "mesh_shards": 0,
        # in-flight pipeline (docs/verify-scheduler.md "In-flight
        # pipeline"): dispatches whose fetch has not resolved yet, the
        # high-water mark since reset, and per-lane dispatch/lane-usage
        # tallies.  A lane is who verified the signatures: a supervisor
        # backend's name (a mesh-wide launch counts under its TIER; the
        # chips' own tallies are ``shard_hist``), or a mesh ordinal for a
        # small batch pinned at one chip
        "inflight_depth": 0,
        "inflight_hwm": 0,
        "lane_dispatches": {},  # lane (str) -> dispatches routed there
        "lane_lanes_total": {},  # lane (str) -> padded lanes shipped
        "lane_lanes_used": {},  # lane (str) -> lanes carrying a signature
        # calls under the dispatch watchdog (ops/supervisor._Watchdog) by
        # the worker that served them: one that was parked, or a thread
        # started for the call.  Steady traffic reads ``fresh`` level
        "watchdog_calls": {"parked": 0, "fresh": 0},
    }


_STATS = _zero()


def record_dispatch(lanes_total: int, lanes_used: int) -> None:
    with _LOCK:
        _STATS["dispatches"] += 1
        _STATS["lanes_total"] += int(lanes_total)
        _STATS["lanes_used"] += int(lanes_used)
        b = _STATS["buckets"]
        b[int(lanes_total)] = b.get(int(lanes_total), 0) + 1


def record_dispatch_time(impl: str, lanes: int, seconds: float) -> None:
    """Wall time of one device dispatch (dispatch + fetch), keyed by
    (supervisor tier, padding bucket) — written by the supervisor's
    dispatch path and the raw ``verify_batch`` fallback."""
    key = f"{impl}-{int(lanes)}"
    with _LOCK:
        h = _STATS["dispatch_hist"].get(key)
        if h is None:
            h = _STATS["dispatch_hist"][key] = Histo(DISPATCH_BUCKETS_S)
        h.observe(float(seconds))


def record_shard_time(
    impl: str, device: int, lanes: int, seconds: float
) -> None:
    """Wall time of one per-device shard fetch on the mesh path, keyed by
    device ordinal — written by ``parallel/mesh.fetch_sharded``, rendered
    as ``cometbft_crypto_shard_dispatch_seconds{device=}``.  ``impl`` and
    ``lanes`` ride the span attribution; the histogram key stays the
    device so a sick chip is one series regardless of bucket."""
    del impl, lanes  # span attrs only; the metric dimension is the device
    key = str(int(device))
    with _LOCK:
        h = _STATS["shard_hist"].get(key)
        if h is None:
            h = _STATS["shard_hist"][key] = Histo(DISPATCH_BUCKETS_S)
        h.observe(float(seconds))


def record_mesh_width(width: int) -> None:
    """Width of the elastic mesh's current membership — written by
    ``parallel/elastic`` on every reconfiguration, rendered as the
    ``cometbft_crypto_mesh_width`` gauge.  jax-free reads, like all of
    this module: a scrape must never initialize a backend to learn the
    mesh shrank."""
    with _LOCK:
        _STATS["mesh_width"] = int(width)


def record_mesh_dispatch(shards: int) -> None:
    """One mesh-wide launch, divided over ``shards`` chips."""
    with _LOCK:
        _STATS["mesh_dispatches"] += 1
        _STATS["mesh_shards"] += int(shards)


def record_mesh_shrink() -> None:
    with _LOCK:
        _STATS["mesh_shrinks"] += 1


def record_mesh_restore() -> None:
    with _LOCK:
        _STATS["mesh_restores"] += 1


def mesh_width() -> int:
    with _LOCK:
        return _STATS["mesh_width"]


def record_inflight_enter() -> int:
    """A dispatch left for the device without blocking on its verdict.
    Returns the depth INCLUDING this dispatch (for span attribution)."""
    with _LOCK:
        _STATS["inflight_depth"] += 1
        d = _STATS["inflight_depth"]
        if d > _STATS["inflight_hwm"]:
            _STATS["inflight_hwm"] = d
        return d


def record_inflight_exit() -> None:
    """The matching fetch resolved (or failed definitively)."""
    with _LOCK:
        _STATS["inflight_depth"] = max(0, _STATS["inflight_depth"] - 1)


def inflight_hwm() -> int:
    with _LOCK:
        return _STATS["inflight_hwm"]


def record_lane_dispatch(lane: str, lanes_total: int, lanes_used: int) -> None:
    """Per-lane tally of who verified what: ``lane`` is a supervisor
    backend name (the tier, for a mesh-wide launch too) or the mesh
    ordinal (str) a small batch was pinned at.  Occupancy per lane
    (lanes_used / lanes_total) derives at snapshot time, rendered as
    ``cometbft_crypto_lane_occupancy{lane=}``."""
    key = str(lane)
    with _LOCK:
        d = _STATS["lane_dispatches"]
        d[key] = d.get(key, 0) + 1
        t = _STATS["lane_lanes_total"]
        t[key] = t.get(key, 0) + int(lanes_total)
        u = _STATS["lane_lanes_used"]
        u[key] = u.get(key, 0) + int(lanes_used)


def record_watchdog_call(worker: str) -> None:
    """One call under the dispatch watchdog, served by a ``parked`` worker
    or by a ``fresh`` thread."""
    with _LOCK:
        _STATS["watchdog_calls"][worker] += 1


def record_fused(n_segments: int) -> None:
    with _LOCK:
        _STATS["fused_batches"] += 1
        _STATS["fused_segments"] += int(n_segments)


def record_verify_latency(seconds: float) -> None:
    with _LOCK:
        _STATS["verify_calls"] += 1
        _STATS["verify_seconds"] += float(seconds)
        _STATS["verify_hist"].observe(float(seconds))


def dispatch_count() -> int:
    with _LOCK:
        return _STATS["dispatches"]


def snapshot() -> dict:
    with _LOCK:
        out = {}
        for k, v in _STATS.items():
            if isinstance(v, Histo):
                out[k] = v.to_dict()
            elif isinstance(v, dict):
                out[k] = {
                    kk: (vv.to_dict() if isinstance(vv, Histo) else vv)
                    for kk, vv in v.items()
                }
            else:
                out[k] = v
    out["occupancy"] = (
        out["lanes_used"] / out["lanes_total"] if out["lanes_total"] else 0.0
    )
    out["lane_occupancy"] = {
        lane: (
            out["lane_lanes_used"].get(lane, 0) / total if total else 0.0
        )
        for lane, total in out["lane_lanes_total"].items()
    }
    return out


def reset() -> None:
    global _STATS
    with _LOCK:
        _STATS = _zero()
