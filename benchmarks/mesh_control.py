#!/usr/bin/env python3
"""The control of ``correct`` for a cell whose lanes are divided over chips:
the reference put in the program's place with the accept bits of TWO SHARDS
EXCHANGED on the way back.  It has to come out as not correct.  The
benchmark's own runs do not run this.

    python3 benchmarks/mesh_control.py --workload val10k-commit-stream-x4 \
        --seed <n> [--requests 48] [--control shards_exchanged|none]

What it breaks is the configuration's guarantee "a signature's verdict is
attributed to its own commit index whatever chip verified it".  A mesh-wide
launch pads a request's signatures to its bucket, cuts the lanes into one
contiguous shard a chip and writes each shard's bits back at the shard's own
offset; a fetch that wrote shard 0's bits at shard 1's offset and the
reverse would still accept every honest commit (both shards all true) and
would still reject every tampered one, but naming an index a whole shard
off.  Only the comparison of the NAMED index shows it: with the index
dropped from the verdict this control would pass.

Needs no chip and imports nothing of the program: the chain is generated at
the cell's own size from the seed, the lanes are cut as the configuration's
``chips_layout`` says (the padded bucket over the cell's chips), and the
harness's own ``judge`` compares.  One height in four is tampered here (the
cell's own traffic has one in 32): the exchange shows only where the tampered
index lies in one of the two shards.  ``none`` exchanges nothing and has to
come out correct.

Exit code 0 where the control came out as NOT correct, 1 where it passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import chain as chainlib  # noqa: E402
from benchmarks import control, harness, manifest  # noqa: E402
from benchmarks import ed25519_ref as ref  # noqa: E402
from benchmarks.loops.closed import Record  # noqa: E402

CONTROLS = ("shards_exchanged", "none")
BUCKETS = (128, 256, 512, 1024, 4096, 8192, 10240, 32768)  # the program's ladder
EXCHANGED = (0, 1)


def padded_lanes(n: int, chips: int) -> int:
    """The bucket a request of ``n`` signatures is padded to, then to a
    multiple of the chips."""
    lanes = next((b for b in BUCKETS if n <= b), n)
    return lanes + (-lanes) % chips


def exchange_shards(bits: list, chips: int) -> list:
    """``bits`` as a mesh-wide launch over ``chips`` would return them if the
    shards ``EXCHANGED`` were written back at each other's offset."""
    n = len(bits)
    lanes = padded_lanes(n, chips)
    per = lanes // chips
    full = list(bits) + [False] * (lanes - n)
    a, b = (slice(k * per, (k + 1) * per) for k in EXCHANGED)
    full[a], full[b] = full[b], full[a]
    return full[:n]


def run_control(cell, seed: int, requests: int, which: str, pool,
                chips: "int | None" = None) -> dict:
    chips = chips or cell.chips
    traffic = dict(cell.traffic, heights=requests, warmup_heights=0,
                   warmup_tampered=0, tamper_every=4, tamper_phase=2)
    chain = chainlib.build(cell.config, traffic, cell.config_name, seed, pool)
    entry = cell.entry
    items, spans = [], []
    for hgt in chain.pool:
        got = entry.reference_items(chain, hgt)
        spans.append((len(items), len(items) + len(got)))
        items.extend(got)
    bits = pool.map_chunks(control._host_library_bits, items, 2048)
    records = []
    for hgt, (a, b) in zip(chain.pool, spans):
        # what the host library rejects, the plain reference decides
        got = [ok or ref.verify_zip215(*items[a + i])
               for i, ok in enumerate(bits[a:b])]
        if which == "shards_exchanged":
            got = exchange_shards(got, chips)
        records.append(Record(
            hgt.key, 0.0, 0.0, entry.reference_verdict(chain, hgt, got),
            entry.signatures(chain, hgt),
        ))
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
        "requests": args.requests, "chips": cell.chips,
        "correct": verdict["correct"],
        "sampled_requests": verdict["sampled_requests"],
        "first_wrong": verdict["first_wrong"],
        "compared": verdict["compared"],
    }))
    return 1 if verdict["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
