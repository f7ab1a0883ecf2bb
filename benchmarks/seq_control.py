#!/usr/bin/env python3
"""The control of ``correct`` for the sequential light client's cell, run by
hand (``light_control.py``'s pattern): the plain reference put in the
program's place with ONE guarantee of the configuration broken.  It has to
come out as not correct.

    python3 benchmarks/seq_control.py --seed <n> [--requests 200]
                  [--control no_next_vals_link|later_error_first|none]

  no_next_vals_link  a header is not held to the set the header before it
                     announced (``validators_hash ==
                     trusted.next_validators_hash`` left out: one compare,
                     but the one link that chains the sets).  Breaks "a
                     header is accepted only if ... that set is the one the
                     header before it announced": a set that signs for
                     itself is followed.
  later_error_first  every header's checks first, then the signatures: the
                     order a pipelined window is tempted into (the parent's
                     ``verify_adjacent_chain`` documented it).  Breaks "a
                     rejection names the first bad header by height and its
                     class": a tampered header before a broken link is
                     answered as the link.

Needs no chip and nothing of the program: the cell's chain is generated at
its own size from the seed, the control answers the first ``--requests``
requests of the pool over every header of each (the header and its
predecessor are the program's whole input), and the harness's own ``judge``
compares.  Honest signatures are checked with the host library; what it
rejects is judged by the plain reference.

Exit code 0 where the control came out as NOT correct, 1 where it passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import chain as chainlib  # noqa: E402
from benchmarks import harness, light_ref, light_seq_ref, manifest, seqchain  # noqa: E402
from benchmarks.light_control import host_then_reference  # noqa: E402
from benchmarks.loops.closed import Record  # noqa: E402

CONTROLS = ("no_next_vals_link", "later_error_first")
WORKLOAD = "light1k-sequential"


def _checks(chain_id, trusted, new, period_s, now_s, with_link=True):
    """``light_ref.verify_adjacent`` up to its light pass: None where the
    header passes."""
    if new.header.height != trusted.header.height + 1:
        return ("invalid_header", "headers must be adjacent in height")
    if light_ref._expired(trusted, period_s, now_s):
        return ("expired",)
    bad = light_ref._check_new_header_and_vals(
        chain_id, trusted, new, now_s, light_ref.MAX_CLOCK_DRIFT_S)
    if bad:
        return bad
    if with_link and new.header.validators_hash != trusted.header.next_validators_hash:
        return ("invalid_header", "validators_hash is not the trusted next_validators_hash")
    return None


def control_sequential(seq, req, control: str) -> tuple:
    """``light_seq_ref.verify_sequential`` over every header of the request,
    or the control's broken version of it."""
    chain_id, period_s, now_s = seq.chain_id, seq.trusting_period_s, req.now_s
    trusted = seq.light_block(req.trusted)
    news = [seq.light_block(req.served(h)) for h in range(req.trusted + 1, req.target + 1)]
    if control == "none":
        return light_seq_ref.verify_sequential(
            chain_id, trusted, news, period_s, now_s, verify_sig=host_then_reference)
    if control == "later_error_first":  # every header's checks first
        current = trusted
        for new in news:
            bad = _checks(chain_id, current, new, period_s, now_s)
            if bad:
                return bad, new.header.height
            current = new
    current = trusted
    for new in news:
        bad = control == "no_next_vals_link" and _checks(
            chain_id, current, new, period_s, now_s, with_link=False)
        got = bad or light_ref._light_pass(chain_id, new, host_then_reference)
        if got != ("accepted",):
            return got, new.header.height
        current = new
    return ("accepted",)


def control_verdict(seq, req, control: str) -> tuple:
    """The entry's verdict tuple of what the control answered."""
    got = control_sequential(seq, req, control)
    if got == ("accepted",):
        return got
    (verdict, *detail), height = got
    if verdict == "invalid_signature":
        return ("invalid_signature", height, detail[0])
    if verdict == "invalid_header":
        return ("invalid_header", height)
    return ("error", f"{height}: {verdict}: {detail}")


def run_control(cell, seed: int, requests: int, control: str, pool) -> dict:
    """The first ``requests`` requests of the pool (the traffic's pool cut
    to the headers they walk), answered by the control, judged."""
    planned = [r for r in seqchain.plan(cell.config, cell.traffic, seed)
               if not isinstance(r.key, tuple)][:requests]
    headers = max(r.target for r in planned) - planned[0].trusted
    cell.traffic = dict(cell.traffic, headers=headers)
    chain = chainlib.build(cell.config, cell.traffic, cell.config_name, seed, pool)
    chain.seq = seqchain.build(cell.config, cell.traffic, seed, pool)
    records = [
        Record(req.key, 0.0, 0.0, control_verdict(chain.seq, req, control),
               req.signatures)
        for req in chain.seq.pool
    ]
    return harness.judge(cell, chain, records, pool, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--control", choices=CONTROLS + ("none",), default=CONTROLS[0])
    args = ap.parse_args(argv)
    cell = manifest.Cell(manifest.load(), WORKLOAD)
    pool = chainlib.SignPool()
    try:
        verdict = run_control(cell, args.seed, args.requests, args.control, pool)
    finally:
        pool.close()
    print(json.dumps({
        "control": args.control, "workload": WORKLOAD, "seed": args.seed,
        "requests": args.requests, "correct": verdict["correct"],
        "sampled_requests": verdict["sampled_requests"],
        "compared": verdict["compared"], "first_unexpected": verdict["first_unexpected"],
    }))
    return 1 if verdict["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
