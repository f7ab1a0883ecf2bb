"""Tier-1 wall-time budget gate.

The tier-1 suite runs under a hard 870 s timeout (ROADMAP.md) and has
twice brushed it; this gate fails CI at a SOFT budget of 800 s so a creep
past the margin shows up as a red check with headroom to fix it, instead
of as a flaky timeout.

Usage:
    # after the tier-1 invocation that tees to /tmp/_t1.log:
    python scripts/check_tier1_budget.py [/tmp/_t1.log] [--budget 800]
    # or gate an externally measured number:
    python scripts/check_tier1_budget.py --seconds 812.4

Parses the wall time from the LAST pytest summary line in the log
("=== 123 passed, 4 skipped in 682.33s ==="; "(0:11:22)" forms included).
An unparsable log is a FAILURE, not a pass — a truncated log usually
means the suite died or timed out.
"""

from __future__ import annotations

import argparse
import re
import sys

DEFAULT_BUDGET_S = 800.0
DEFAULT_LOG = "/tmp/_t1.log"

# "in 682.33s", "in 682.33s (0:11:22)"
_SUMMARY_RE = re.compile(r"\bin\s+([0-9]+(?:\.[0-9]+)?)s(?:\s+\([0-9:]+\))?\s*=*\s*$")

# "12.34s call     tests/test_sim.py::TestScenarios::test_x" — emitted when
# the suite runs with --durations=N (scripts/gate.sh does)
_DURATION_RE = re.compile(
    r"^\s*([0-9]+(?:\.[0-9]+)?)s\s+(call|setup|teardown)\s+(\S+)"
)

# "tier1-exec-cache: compiles=3 compile_s=61.2 hits=9 load_s=14.1 ..." —
# printed by tests/conftest.py's terminal summary (ops/warm_stats)
_EXEC_RE = re.compile(
    r"tier1-exec-cache:\s+compiles=(\d+)\s+compile_s=([0-9.]+)\s+"
    r"hits=(\d+)\s+load_s=([0-9.]+)"
)

# "tier1-trace: spans=1234 dropped=0 anomalies=2 dumps=1 overhead_s=0.04"
# — printed by tests/conftest.py's terminal summary (libs/tracing); the
# overhead share is gated: the flight recorder is default-on, so a
# regression in its record path would silently tax every verify
_TRACE_RE = re.compile(
    r"tier1-trace:\s+spans=(\d+)\s+dropped=(\d+)\s+anomalies=(\d+)\s+"
    r"dumps=(\d+)\s+overhead_s=([0-9.]+)"
)

# the recorder's measured in-process overhead must stay a rounding error
# of tier-1 wall time (benchmarks/recorder_cost.py reads the precise cost
# on the chip; this is the coarse suite-wide backstop)
TRACE_OVERHEAD_MAX_SHARE = 0.05

# tests whose dominant cost is a device-kernel compile (the population the
# warm-boot PR targets); used for the durations-table compile share
_COMPILE_HEAVY = (
    "test_bls_g1",
    "test_secp_batch",
    "test_pallas",
    "test_ed25519_jax",
    "test_ops",
    "test_mesh",
    "test_verify_stream",
)


def parse_wall_seconds(text: str) -> float | None:
    """Wall seconds from the last pytest summary line, or None."""
    last = None
    for line in text.splitlines():
        m = _SUMMARY_RE.search(line.strip())
        if m:
            last = float(m.group(1))
    return last


def parse_durations(text: str) -> list[tuple[float, str]]:
    """(seconds, test id) pairs from a --durations=N section, [] if the
    log has none."""
    out = []
    for line in text.splitlines():
        m = _DURATION_RE.match(line)
        if m:
            out.append((float(m.group(1)), m.group(3)))
    return out


def sim_share(text: str, wall: float) -> str | None:
    """One-line report of the sim-scenario share of tier-1 wall time, or
    None when the log carries no --durations section.  A lower bound: the
    durations table only lists the slowest N items."""
    durations = parse_durations(text)
    if not durations or wall <= 0:
        return None
    sim_s = sum(s for s, tid in durations if "test_sim" in tid)
    listed_s = sum(s for s, _ in durations)
    return (
        f"tier1-budget: sim scenarios >= {sim_s:.1f}s of {wall:.1f}s wall "
        f"({100.0 * sim_s / wall:.1f}%; durations table covers "
        f"{listed_s:.1f}s)"
    )


def compile_share(text: str, wall: float) -> "list[str]":
    """Report lines for the compile-time share of tier-1 wall time.

    Two complementary views (both lower bounds):
      * the exec-cache summary line (exact in-process trace+compile
        seconds, but blind to spawned node subprocesses);
      * the durations table restricted to the compile-heavy kernel test
        files (captures a test's whole wall time, compile included)."""
    out = []
    if wall <= 0:
        return out
    m = None
    for m in _EXEC_RE.finditer(text):
        pass  # keep the LAST summary line, like the wall-time parse
    if m is not None:
        compiles, compile_s = int(m.group(1)), float(m.group(2))
        hits, load_s = int(m.group(3)), float(m.group(4))
        out.append(
            f"tier1-budget: kernel compiles {compiles} "
            f"({compile_s:.1f}s, {100.0 * compile_s / wall:.1f}% of wall); "
            f"exec-cache hits {hits} ({load_s:.1f}s loading)"
        )
    durations = parse_durations(text)
    if durations:
        heavy = sum(
            s for s, tid in durations
            if any(name in tid for name in _COMPILE_HEAVY)
        )
        out.append(
            f"tier1-budget: compile-heavy kernel tests >= {heavy:.1f}s of "
            f"{wall:.1f}s wall ({100.0 * heavy / wall:.1f}%; durations "
            "table lower bound)"
        )
    return out


def trace_share(text: str, wall: float) -> "tuple[list[str], bool]":
    """(report lines, ok) for the flight-recorder summary line.  A log
    with no line simply reports nothing (older logs, subprocess-only
    runs); a parsed overhead share past ``TRACE_OVERHEAD_MAX_SHARE``
    fails the gate."""
    m = None
    for m in _TRACE_RE.finditer(text):
        pass  # keep the LAST summary line, like the wall-time parse
    if m is None or wall <= 0:
        return [], True
    spans, dropped, anomalies, dumps = (int(m.group(i)) for i in range(1, 5))
    overhead_s = float(m.group(5))
    share = overhead_s / wall
    ok = share <= TRACE_OVERHEAD_MAX_SHARE
    lines = [
        f"tier1-budget: flight recorder {spans} spans ({dropped} dropped), "
        f"{anomalies} anomalies, {dumps} dumps; recorder overhead "
        f"{overhead_s:.3f}s = {100.0 * share:.2f}% of wall"
        + ("" if ok else
           f" -> FAIL (> {100.0 * TRACE_OVERHEAD_MAX_SHARE:g}%)")
    ]
    return lines, ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "log", nargs="?", default=DEFAULT_LOG,
        help=f"tier-1 pytest log (default {DEFAULT_LOG})",
    )
    ap.add_argument(
        "--budget", type=float, default=DEFAULT_BUDGET_S,
        help=f"soft wall-time budget in seconds (default {DEFAULT_BUDGET_S:g})",
    )
    ap.add_argument(
        "--seconds", type=float, default=None,
        help="gate this wall time directly instead of parsing a log",
    )
    args = ap.parse_args()

    text = ""
    if args.seconds is not None:
        wall = args.seconds
    else:
        try:
            with open(args.log, errors="replace") as f:
                text = f.read()
        except OSError as e:
            print(f"tier1-budget: cannot read {args.log}: {e}", file=sys.stderr)
            return 1
        wall = parse_wall_seconds(text)
        if wall is None:
            print(
                f"tier1-budget: no pytest summary line found in {args.log} "
                "(suite died or the log is truncated) -> FAIL",
                file=sys.stderr,
            )
            return 1
    share = sim_share(text, wall) if text else None
    if share:
        print(share)
    trace_ok = True
    if text:
        for line in compile_share(text, wall):
            print(line)
        trace_lines, trace_ok = trace_share(text, wall)
        for line in trace_lines:
            print(line)

    margin = args.budget - wall
    if wall > args.budget:
        print(
            f"tier1-budget: FAIL wall={wall:.1f}s exceeds budget "
            f"{args.budget:g}s by {-margin:.1f}s (hard timeout is 870s — "
            "slow-mark the new heaviest tests or shrink fixtures)"
        )
        return 1
    if not trace_ok:
        return 1
    print(
        f"tier1-budget: ok wall={wall:.1f}s budget={args.budget:g}s "
        f"(margin {margin:.1f}s)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
