#!/usr/bin/env python3
"""The control of ``correct`` for the light client's cell, run by hand
(``control.py``'s pattern): the plain reference put in the program's place
with ONE guarantee of the configuration broken.  It has to come out as not
correct.

    python3 benchmarks/light_control.py --seed <n> [--requests 64]
                          [--control no_trusting_pass|no_link_checks|none]

  no_trusting_pass   the trusting pass is left out: a new header is accepted
                     on more than 2/3 of its OWN set alone (one pass, one
                     dispatch, no look-ups by address: what would tempt a
                     later PR).  Breaks "accepted only if MORE THAN 1/3 of
                     the TRUSTED set's power signed it": a block that jumps
                     too far is accepted, and a light client follows any
                     chain whose validators sign for themselves.
  no_link_checks     the three hash links are not checked (5 ms of a 35 ms
                     request, a header hash and a 1,000-leaf Merkle root:
                     tempting too).  Breaks "the links ... are checked on
                     every request": a header that the commit did not sign, a
                     set that the header does not name, or a set that the
                     trusted header did not announce is accepted.

Needs no chip and nothing of the program: the cell's chain is generated at
its own size from the seed, the control answers the first ``--requests``
requests of the pool, and the harness's own ``judge`` compares.  Honest
signatures are checked with the host library; what it rejects is judged by
the plain reference.

Exit code 0 where the control came out as NOT correct, 1 where it passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import chain as chainlib  # noqa: E402
from benchmarks import ed25519_ref as ref  # noqa: E402
from benchmarks import harness, light_ref, lightchain, manifest  # noqa: E402
from benchmarks.loops.closed import Record  # noqa: E402

CONTROLS = ("no_trusting_pass", "no_link_checks")
WORKLOAD = "light1k-skipping-sync"


def host_then_reference(pub: bytes, msg: bytes, sig: bytes) -> bool:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return ref.verify_zip215(pub, msg, sig)


def _checks_without_links(chain_id, trusted, new, now_s, drift_s):
    """``light_ref._check_new_header_and_vals`` less its two hash links."""
    h, c = new.header, new.commit
    if not h.chain_id or len(h.chain_id) > 50 or h.chain_id != chain_id:
        return ("invalid_header", "chain id")
    if h.proposer_address and len(h.proposer_address) != light_ref.ADDRESS_LEN:
        return ("invalid_header", "proposer address")
    if c.height != h.height:
        return ("invalid_header", "commit height differs from the header's")
    if h.height <= trusted.header.height:
        return ("invalid_header", "height not above the trusted one")
    if h.time_ns <= trusted.header.time_ns:
        return ("invalid_header", "time not after the trusted header's")
    if h.time_ns / 1e9 > now_s + drift_s:
        return ("invalid_header", "from the future")
    return None


def without_link_checks(light, trusted, new, now_s: float) -> tuple:
    """``light_ref.verify`` with the three hash links left out; every other
    check, and both passes over the commit as it was sent, as they are."""
    if light_ref._expired(trusted, light.trusting_period_s, now_s):
        return ("expired",)
    bad = _checks_without_links(
        light.chain_id, trusted, new, now_s, light_ref.MAX_CLOCK_DRIFT_S)
    if bad:
        return bad
    if new.header.height != trusted.header.height + 1:
        got = light_ref.verify_commit_light_trusting(
            light.chain_id, trusted.validators, new.commit, *light.trust,
            verify_sig=host_then_reference)
        if got != ("accepted",):
            return got
    return light_ref._light_pass(light.chain_id, new, host_then_reference)


def control_verdict(light, req, control: str) -> tuple:
    trusted, new = light.light_block(req.trusted), light.light_block(req.new)
    if control == "no_link_checks":
        got = without_link_checks(light, trusted, new, req.now_s)
    elif control != "no_trusting_pass" or new.header.height == trusted.header.height + 1:
        got = light_ref.verify(
            light.chain_id, trusted, new, light.trusting_period_s, req.now_s,
            *light.trust, verify_sig=host_then_reference)
    else:  # verify_non_adjacent with its trusting pass left out
        got = (
            (("expired",) if light_ref._expired(
                trusted, light.trusting_period_s, req.now_s) else None)
            or light_ref._check_new_header_and_vals(
                light.chain_id, trusted, new, req.now_s, light_ref.MAX_CLOCK_DRIFT_S)
            or light_ref._light_pass(light.chain_id, new, host_then_reference)
        )
    if got[0] == "invalid_header":
        return ("invalid_header",)
    return got if got[0] in ("accepted", "invalid_signature", "cant_be_trusted") \
        else ("error", f"{got[0]}: {got[1:]}")


def run_control(cell, seed: int, requests: int, control: str, pool) -> dict:
    cell.traffic = dict(cell.traffic, requests=requests)
    chain = chainlib.build(cell.config, cell.traffic, cell.config_name, seed, pool)
    chain.light = lightchain.build(cell.config, cell.traffic, seed, pool)
    records = [
        Record(req.key, 0.0, 0.0, control_verdict(chain.light, req, control),
               req.signatures)
        for req in chain.light.pool
    ]
    return harness.judge(cell, chain, records, pool, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=64)
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
