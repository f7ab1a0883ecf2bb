#!/usr/bin/env python3
"""The control of ``correct``: the reference put in the program's place with
ONE guarantee of the configuration broken.  It has to come out as not
correct.  The benchmark's own runs do not run this.

    python3 benchmarks/control.py --workload <name> --seed <n> [--requests 48]
                                  [--control no_attribution|noncanonical_s]

The system states no precision, so there is no lower one to compute in; the
controls are the two steps that would tempt a later PR:

  no_attribution   a failed batch is reported without the index of the wrong
                   signature (one AND-ed bit a flush instead of a bit a lane
                   saves the read-back; the Go reference's batch verifier
                   works so and needs a second pass).  Breaks "a rejected
                   commit names the index the host path names".
  noncanonical_s   the check s < L is dropped (one comparison a signature on
                   the host).  Breaks "every verdict is the ZIP-215 accept
                   set": a signature with s + L is accepted.

Needs no chip and imports nothing of the program's device path: the cell's
chain is generated at its own size from the seed, the control answers the
first ``--requests`` heights of the pool as a short window would, and the
harness's own ``judge`` compares.  Honest signatures are checked with the
host library in the workers (the plain reference would take a quarter of an
hour at 10,240 validators); every signature the host library rejects is
then judged by the plain reference with the guarantee broken.

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
from benchmarks import harness, manifest  # noqa: E402
from benchmarks.loops.closed import Record  # noqa: E402

CONTROLS = ("no_attribution", "noncanonical_s")


def _host_library_bits(items):
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

    out = []
    for pub, msg, sig in items:
        try:
            Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
            out.append(True)
        except (InvalidSignature, ValueError):
            out.append(False)
    return out


def verify_without_canonical_s(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """``verify_zip215`` with the check s < L left out: [s]B wraps mod L."""
    s = int.from_bytes(sig[32:], "little")
    if s >= ref.L and s - ref.L < ref.L:
        sig = sig[:32] + (s - ref.L).to_bytes(32, "little")
    return ref.verify_zip215(pub, msg, sig)


def control_verdict(entry, chain, hgt, bits, control: str) -> tuple:
    """The verdict of the reference with one guarantee broken, from the host
    library's accept bits over the request's items."""
    items = entry.reference_items(chain, hgt)
    fixed = list(bits)
    for i, ok in enumerate(bits):
        if not ok:  # the host library rejects: let the reference decide
            judge = (
                verify_without_canonical_s if control == "noncanonical_s"
                else ref.verify_zip215
            )
            fixed[i] = judge(*items[i])
    # "none" (the tests): the reference as it is, which has to pass
    verdict = entry.reference_verdict(chain, hgt, fixed)
    if control == "no_attribution" and verdict[0] == "invalid_signature":
        return ("error", "CommitVerificationError: batch verification failed")
    return verdict


def run_control(cell, seed: int, requests: int, control: str, pool) -> dict:
    traffic = dict(cell.traffic, heights=requests, warmup_heights=0,
                   warmup_tampered=0)
    chain = chainlib.build(cell.config, traffic, cell.config_name, seed, pool)
    entry = cell.entry
    items, spans = [], []
    for hgt in chain.pool:
        got = entry.reference_items(chain, hgt)
        spans.append((len(items), len(items) + len(got)))
        items.extend(got)
    bits = pool.map_chunks(_host_library_bits, items, 2048)
    records = [
        Record(hgt.key, 0.0, 0.0,
               control_verdict(entry, chain, hgt, bits[a:b], control),
               entry.signatures(chain, hgt))
        for hgt, (a, b) in zip(chain.pool, spans)
    ]
    cell.traffic = traffic
    return harness.judge(cell, chain, records, pool, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--control", choices=CONTROLS, default=CONTROLS[0])
    args = ap.parse_args(argv)
    cell = manifest.Cell(manifest.load(), args.workload)
    pool = chainlib.SignPool()
    try:
        verdict = run_control(cell, args.seed, args.requests, args.control, pool)
    finally:
        pool.close()
    print(json.dumps({
        "control": args.control, "workload": args.workload, "seed": args.seed,
        "requests": args.requests, "correct": verdict["correct"],
        "sampled_requests": verdict["sampled_requests"],
        "compared": verdict["compared"],
    }))
    return 1 if verdict["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
