#!/usr/bin/env python3
"""The control of ``correct`` for the joiner's blocksync cell, run by hand
(``seq_control.py``'s pattern): the plain reference put in the program's
place with ONE guarantee of the configuration broken.  It has to come out
as not correct, and ``none`` (the reference whole) as correct.

    python3 benchmarks/bsync_control.py --seed <n> [--heights 400]
                  [--control no_body_check|prefix_only_last_commit|none]

  no_body_check            the block's data hash is not checked against its
                           transactions.  Breaks "H's body matches its
                           header": a body forged after its header was made
                           (and signed again by the next commit) is applied.
  prefix_only_last_commit  ``validate_block`` reads only the 2/3 prefix of
                           H's own LastCommit, as the light check does.
                           Breaks "a rejection names the height and, for a
                           signature, the commit index": a signature altered
                           past the prefix is never seen.

Needs no chip and nothing of the program: the cell's chain is generated at
``--heights`` from the seed, the control replays a joiner over every tick
of the pool (``bsync_ref.replay``, with the copies each peer serves in the
order the joiner receives them), and the harness's own ``judge`` compares.
The reference itself takes no option: each control wraps one of its checks
for the length of the replay (``broken``).
Honest signatures are checked with the host library; what it rejects is
judged by the plain reference.

Exit code 0 where the control came out as NOT correct, 1 where it passed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import bsync_ref, bsyncchain, harness, manifest  # noqa: E402
from benchmarks import chain as chainlib  # noqa: E402
from benchmarks.light_control import host_then_reference  # noqa: E402
from benchmarks.loops.closed import Record  # noqa: E402

CONTROLS = ("no_body_check", "prefix_only_last_commit", "none")
WORKLOAD = "rotating-blocksync"


@contextlib.contextmanager
def broken(control: str):
    """``bsync_ref`` with the control's guarantee broken while the block
    runs: ``no_body_check`` hands ``validate_block`` a header whose data
    hash is that of the transactions received; ``prefix_only_last_commit``
    makes every commit check the light one, ``validate_block``'s too."""
    validate_block, verify_commit = bsync_ref.validate_block, bsync_ref.verify_commit

    def unchecked_body(state, block, verify_sig):
        header = dict(block.header)
        header[7] = bsync_ref.merkle_root(block.txs)
        return validate_block(state, block._replace(header=header), verify_sig)

    def light_only(*args, light):
        return verify_commit(*args, light=True)

    if control == "no_body_check":
        bsync_ref.validate_block = unchecked_body
    elif control == "prefix_only_last_commit":
        bsync_ref.verify_commit = light_only
    try:
        yield
    finally:
        bsync_ref.validate_block, bsync_ref.verify_commit = validate_block, verify_commit


def served(bc: bsyncchain.BlockChain) -> "dict[int, list[bytes]]":
    """Each height's copies in the order the joiner receives them: the
    faulty peer's first, where there is one."""
    out = {h: [wire] for h, wire in enumerate(bc.honest) if h}
    for h, (_, wire) in bc.faulty.items():
        out[h] = [wire] + out[h]
    return out


def control_verdicts(bc: bsyncchain.BlockChain, control: str) -> "list[tuple]":
    """The verdicts of the control's joiner over every tick, warm-up first."""
    ticks = len(bc.warm) + len(bc.pool)
    with broken(control):
        return bsync_ref.replay(bc.genesis(), served(bc), ticks, host_then_reference)


def run_control(cell, seed: int, heights: int, control: str, pool) -> dict:
    """The pool cut to ``heights``, answered by the control, judged."""
    cell.traffic = dict(cell.traffic, sync_heights=heights)
    chain = chainlib.build(cell.config, cell.traffic, cell.config_name, seed, pool)
    chain.bsync = bsyncchain.build(cell.config, cell.traffic, seed)
    verdicts = control_verdicts(chain.bsync, control)[len(chain.bsync.warm):]
    records = [Record(t.key, 0.0, 0.0, v, t.signatures)
               for t, v in zip(chain.bsync.pool, verdicts)]
    return harness.judge(cell, chain, records, pool, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--heights", type=int, default=400)
    ap.add_argument("--control", choices=CONTROLS, default="no_body_check")
    args = ap.parse_args(argv)
    cell = manifest.Cell(manifest.load(), WORKLOAD)
    pool = chainlib.SignPool()
    try:
        verdict = run_control(cell, args.seed, args.heights, args.control, pool)
    finally:
        pool.close()
    print(json.dumps({
        "control": args.control, "workload": WORKLOAD, "seed": args.seed,
        "heights": args.heights, "correct": verdict["correct"],
        "sampled_requests": verdict["sampled_requests"],
        "compared": verdict["compared"], "first_unexpected": verdict["first_unexpected"],
    }, default=str))
    return 1 if verdict["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
