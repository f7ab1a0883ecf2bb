#!/usr/bin/env python3
"""The control of ``correct`` for the validator's cell, run by hand
(``light_control.py``'s pattern): the plain reference put in the program's
place with ONE guarantee of the configuration broken.  It has to come out as
not correct.

    python3 benchmarks/vote_control.py --seed <n> [--heights 19]
          [--control duplicates_by_block_id|no_check_before_conflict|none]

  duplicates_by_block_id     a vote already held for the same block id
                             answers "duplicate" whatever its signature (the
                             program before PR 32).  Breaks "a copy of a held
                             vote under another signature is
                             nondeterministic_signature": a forged copy is
                             waved through as if it were the vote.
  no_check_before_conflict   a vote of a validator that holds a vote for
                             another block id is raised as conflicting BEFORE
                             its signature is looked at (one verification
                             saved on the rarest path: what would tempt a
                             later PR).  Breaks "an equivocating vote is
                             verified FIRST": a forged vote frames an honest
                             validator for equivocation.

Needs no chip and nothing of the program: the cell's votes are generated at
its own size from the seed, the control answers the first ``--heights``
heights of the pool (19: what a 51 s window holds at 7.5 ms a vote), and the
harness's own ``judge`` compares.  Honest signatures are checked with the
host library; what it rejects is judged by the plain reference.

Exit code 0 where the control came out as NOT correct, 1 where it passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import chain as chainlib  # noqa: E402
from benchmarks import harness, manifest, votechain, voteset_ref  # noqa: E402
from benchmarks.entries import consensus_votes  # noqa: E402
from benchmarks.light_control import host_then_reference  # noqa: E402
from benchmarks.loops.closed import Record  # noqa: E402

CONTROLS = ("duplicates_by_block_id", "no_check_before_conflict")
WORKLOAD = "val175-receive-routine"


class DuplicatesByBlockID(voteset_ref.VoteSet):
    def add_vote(self, vote):
        got = super().add_vote(vote)
        return ("duplicate",) if got == ("nondeterministic_signature",) else got


class NoCheckBeforeConflict(voteset_ref.VoteSet):
    def add_vote(self, vote):
        held = self.votes[vote.index]
        if held is not None and held.block_id != vote.block_id:
            return ("conflicting", vote.index)
        return super().add_vote(vote)


SETS = {"duplicates_by_block_id": DuplicatesByBlockID,
        "no_check_before_conflict": NoCheckBeforeConflict,
        "none": voteset_ref.VoteSet}


def control_records(votes: votechain.Votes, heights: int, control: str) -> list:
    """What the control answers to every request of the pool's first
    ``heights`` heights, in order."""
    records = []
    for h in sorted(votes.by_height)[:heights]:
        replay = consensus_votes._Replay(
            votes, h, lambda type_, h=h: SETS[control](
                votes.chain_id, h, 0, type_, votes.validators(), host_then_reference))
        replay._bit = host_then_reference  # the LastCommit's signatures too
        for req in votes.by_height[h]:
            records.append(Record(req.key, 0.0, 0.0, replay.step(req), req.signatures))
    return records


def run_control(cell, seed: int, heights: int, control: str, pool) -> dict:
    cell.traffic = dict(cell.traffic, heights=heights)
    chain = chainlib.build(cell.config, cell.traffic, cell.config_name, seed, pool)
    chain.votes = votechain.build(chain, cell.traffic, pool)
    records = control_records(chain.votes, heights, control)
    return harness.judge(cell, chain, records, pool, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--heights", type=int, default=19)
    ap.add_argument("--control", choices=CONTROLS + ("none",), default=CONTROLS[0])
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
    }))
    return 1 if verdict["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
