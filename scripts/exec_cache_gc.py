#!/usr/bin/env python
"""Garbage-collect the AOT executable cache (docs/warm-boot.md).

Entries are keyed ``<tag>-<platform>-<fingerprint>.jexec`` where the
fingerprint covers the compute-path sources, the jax version and the
trace/compile env vars (ops/aot_cache.py).  A kernel edit or toolchain
bump strands every old-fingerprint entry as dead weight; the cache evicts
them opportunistically on each write, and this script does the same thing
on demand (cron, CI cleanup, disk pressure):

    python scripts/exec_cache_gc.py                # TTL-respecting prune
    python scripts/exec_cache_gc.py --all-stale    # every dead fingerprint
    python scripts/exec_cache_gc.py --dry-run      # report only

Current-fingerprint entries are NEVER removed — they are the working set
the warm boot exists to preserve.  The TTL grace (default 7 days,
COMETBFT_TPU_EXEC_CACHE_TTL_DAYS) protects entries belonging to OTHER
live configurations (a different XLA_FLAGS topology, a flipped trace env
var) that simply haven't been rewritten recently.

``--blackbox DIR`` switches to black-box journal GC instead
(docs/observability.md "Black box"): every journal found under DIR (a
node home, a fleet's data root, a sim scratch tree) keeps its newest
``--segments`` segments (default COMETBFT_TPU_BLACKBOX_SEGMENTS) and —
with --ttl-days — loses rolled segments older than the TTL.  Head
segments are never removed: the newest forensics survive any prune.

    python scripts/exec_cache_gc.py --blackbox /var/cometbft  [--dry-run]
    python scripts/exec_cache_gc.py --blackbox . --segments 2 --ttl-days 3
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--dir",
        default=None,
        help="cache dir (default: the executable cache of libs/cachedir)",
    )
    ap.add_argument(
        "--ttl-days",
        type=float,
        default=None,
        help="grace period for non-current-fingerprint entries "
        "(default: COMETBFT_TPU_EXEC_CACHE_TTL_DAYS or 7)",
    )
    ap.add_argument(
        "--all-stale",
        action="store_true",
        help="ignore the TTL: remove EVERY entry whose fingerprint is not "
        "current (other live configurations must re-compile)",
    )
    ap.add_argument(
        "--dry-run", action="store_true", help="report, remove nothing"
    )
    ap.add_argument(
        "--blackbox",
        default=None,
        metavar="DIR",
        help="prune black-box journals under DIR instead of the exec cache",
    )
    ap.add_argument(
        "--segments",
        type=int,
        default=None,
        help="segments to keep per journal in --blackbox mode "
        "(default: COMETBFT_TPU_BLACKBOX_SEGMENTS or 4)",
    )
    args = ap.parse_args()

    if args.blackbox is not None:
        from cometbft_tpu.libs import blackbox

        removed, freed = blackbox.gc_dir(
            args.blackbox,
            max_segments=args.segments,
            ttl_days=args.ttl_days,
            dry_run=args.dry_run,
        )
        verb = "would remove" if args.dry_run else "removed"
        print(
            f"blackbox-gc: {args.blackbox}: {verb} {removed} rolled "
            f"segment(s), {freed / 1e6:.2f} MB"
        )
        return 0

    if args.dir:
        os.environ["COMETBFT_TPU_EXEC_CACHE"] = args.dir

    from cometbft_tpu.ops import aot_cache

    d = aot_cache.cache_dir()
    fingerprint = aot_cache._fingerprint()
    try:
        names = sorted(os.listdir(d))
    except OSError:
        print(f"exec-cache-gc: {d}: no cache dir, nothing to do")
        return 0

    live = stale = tmp = 0
    total_bytes = stale_bytes = 0
    for fn in names:
        full = os.path.join(d, fn)
        try:
            size = os.path.getsize(full)
        except OSError:
            continue
        total_bytes += size
        if fn.endswith(".tmp"):
            tmp += 1
            stale_bytes += size
        elif fn.endswith(".jexec"):
            if fn.rsplit(".", 1)[0].endswith(fingerprint):
                live += 1
            else:
                stale += 1
                stale_bytes += size
    print(
        f"exec-cache-gc: {d}: {live} live / {stale} dead-fingerprint / "
        f"{tmp} abandoned tmp entries ({total_bytes / 1e6:.1f} MB total, "
        f"{stale_bytes / 1e6:.1f} MB reclaimable)"
    )
    if args.dry_run:
        print("exec-cache-gc: dry run, nothing removed")
        return 0
    if args.all_stale:
        # a 'now' far in the future makes every non-current entry older
        # than any TTL — removal without touching the eviction logic twice
        removed = aot_cache.evict_stale(ttl_days=0.0, now=time.time() + 1.0)
    else:
        removed = aot_cache.evict_stale(ttl_days=args.ttl_days)
    print(f"exec-cache-gc: removed {removed} entries")
    return 0


if __name__ == "__main__":
    sys.exit(main())
