"""CI lint: keep future code on the verification-scheduler seam.

The continuous-batching scheduler (``cometbft_tpu/verifysched/``,
docs/verify-scheduler.md) only fills device batches if callers go through
it — a new subsystem that calls ``ops.verify.verify_batch`` /
``verify_segments`` / ``verify_batches_overlapped`` directly re-creates
the per-caller-dispatch problem this repo just engineered away.  This
gate fails on any DIRECT call site of those functions in production code
(``cometbft_tpu/``) outside:

  * ``cometbft_tpu/ops/``        — the seam's own implementation layer
    (verify/supervisor/mesh plumbing);
  * ``cometbft_tpu/verifysched/`` — the scheduler itself;
  * ``cometbft_tpu/crypto/batch.py`` — the BatchVerifier seam (it bridges
    to the scheduler when active and is the sanctioned fallback);
  * ``cometbft_tpu/txingest/`` — batched tx admission submits whole
    gossip bursts through the scheduler's bulk class
    (``envelope.verify_envelopes``; docs/tx-ingest.md);

plus a PINNED allowlist of pre-scheduler legacy sites (each justified in
docs/verify-scheduler.md).  Growing a legacy file's call-site count — or
adding one anywhere else — is a failure: new code submits to the
scheduler (``verifysched.verify_cached`` / ``verify_segment_sync``) or
tags work with ``verifysched.priority_class`` instead.

Usage (wired into tier-1 next to check_tier1_budget.py):
    python scripts/check_verify_callsites.py [--repo-root PATH]
"""

from __future__ import annotations

import argparse
import ast
import pathlib
import sys

_SEAM_NAMES = frozenset(
    (
        "verify_batch",
        "verify_segments",
        "verify_batches_overlapped",
        # in-flight pipeline halves + the chunked large-batch entry
        # (docs/verify-scheduler.md "In-flight pipeline"): same rule —
        # production code reaches them through verifysched, not directly
        "dispatch_segments",
        "fetch_segments",
        "verify_pipelined",
    )
)

ALLOWED_DIRS = (
    "cometbft_tpu/ops",
    "cometbft_tpu/verifysched",
    "cometbft_tpu/parallel",  # mesh-sharded analogue lives below the seam
    # txingest rides the scheduler (envelope.verify_envelopes submits the
    # whole burst as the PRIO_MEMPOOL bulk class); its shed fallback is
    # allowed to dispatch one supervised batch directly, mirroring
    # verifysched.verify_segment_sync (docs/tx-ingest.md)
    "cometbft_tpu/txingest",
)
ALLOWED_FILES = ("cometbft_tpu/crypto/batch.py",)

# Legacy direct call sites that predate the scheduler, pinned at their
# current counts: the sim scenario file only warms the kernel.  Anything
# above these counts is NEW direct usage.
LEGACY_MAX = {
    "cometbft_tpu/sim/scenarios.py": 1,
}


def _call_sites(source: str) -> "list[tuple[int, str]]":
    """(lineno, call text) for every AST Call whose callee name is one of
    the seam functions — comments, docstrings and string literals can
    mention the names freely without tripping the gate."""
    hits = []
    for node in ast.walk(ast.parse(source)):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = (
            fn.id
            if isinstance(fn, ast.Name)
            else fn.attr
            if isinstance(fn, ast.Attribute)
            else None
        )
        if name in _SEAM_NAMES:
            hits.append((node.lineno, f"{name}(...)"))
    return sorted(hits)


def scan(repo_root: pathlib.Path) -> "list[str]":
    """Return violation messages (empty = clean)."""
    violations = []
    pkg = repo_root / "cometbft_tpu"
    for path in sorted(pkg.rglob("*.py")):
        rel = path.relative_to(repo_root).as_posix()
        if any(
            rel == d or rel.startswith(d + "/") for d in ALLOWED_DIRS
        ) or rel in ALLOWED_FILES:
            continue
        try:
            hits = _call_sites(path.read_text(errors="replace"))
        except SyntaxError as e:
            violations.append(f"{rel}: unparsable ({e}) — cannot lint")
            continue
        cap = LEGACY_MAX.get(rel, 0)
        if len(hits) > cap:
            for lineno, line in hits:
                violations.append(f"{rel}:{lineno}: {line}")
            violations.append(
                f"{rel}: {len(hits)} direct verify call site(s), "
                f"allowed {cap} — route new work through "
                "cometbft_tpu/verifysched (see docs/verify-scheduler.md)"
            )
    return violations


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--repo-root",
        default=str(pathlib.Path(__file__).resolve().parent.parent),
        help="repository root (default: this script's parent's parent)",
    )
    args = ap.parse_args(argv)
    violations = scan(pathlib.Path(args.repo_root))
    if violations:
        print("verify-callsites: FAIL", file=sys.stderr)
        for v in violations:
            print(f"  {v}", file=sys.stderr)
        return 1
    print("verify-callsites: OK (all callers on the scheduler seam)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
