"""Light-client sync benchmark: 1k-validator sequential header sync
through the TPU batch-verify seam (BASELINE config #3; reference harness
light/client_benchmark_test.go — there the mock chain comes from
GenMockNode and the measured op is VerifyLightBlockAtHeight under
SequentialVerification).

Builds a synthetic chain — one validator set of V ed25519 validators, H
signed headers with consistent hashes — behind a mock Provider, then
times LightClient sequential sync from trust height 1 to H.  Every
commit verification routes through ``crypto.batch`` (the TPU seam), so
the measured number is the consensus-verify path end to end: sign-bytes
reconstruction, batch packing, device ladder, tally.

Standalone: JAX_PLATFORMS=cpu python scripts/bench_light.py
Knobs: BENCH_LIGHT_VALS (default 1000), BENCH_LIGHT_HEIGHTS (default 4).
Also callable from bench.py's staged TPU worker via ``run(emit)``.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHAIN_ID = "light-bench-chain"


def build_chain(n_vals: int, heights: int):
    """(provider, trust_options) for a synthetic H-height chain signed by
    one V-validator set.  Commits are assembled directly (the host just
    signed them; VoteSet's per-add verification would re-verify V·H sigs
    in pure python)."""
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.light.provider import Provider
    from cometbft_tpu.light.verifier import TrustOptions
    from cometbft_tpu.types.basic import BlockID, PartSetHeader, Timestamp
    from cometbft_tpu.types.block import Commit, ConsensusVersion, Header
    from cometbft_tpu.types.light import LightBlock, SignedHeader
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.types.vote import (
        BLOCK_ID_FLAG_COMMIT,
        PRECOMMIT_TYPE,
        CommitSig,
        canonical_vote_sign_bytes,
    )

    privs = [
        Ed25519PrivKey.from_seed(
            hashlib.sha256(b"light-bench-val-%d" % i).digest()
        )
        for i in range(n_vals)
    ]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    # commit signatures must follow the set's canonical validator order
    by_addr = {p.pub_key().address(): p for p in privs}
    privs = [by_addr[v.address] for v in vals.validators]
    vhash = vals.hash()
    base_ns = 1_700_000_000 * 10**9

    blocks = {}
    prev_bid = BlockID(
        hash=hashlib.sha256(b"genesis").digest(),
        part_set_header=PartSetHeader(1, hashlib.sha256(b"gp").digest()),
    )
    for h in range(1, heights + 1):
        ts = Timestamp.from_ns(base_ns + h * 10**9)
        header = Header(
            version=ConsensusVersion(block=11, app=1),
            chain_id=CHAIN_ID,
            height=h,
            time=ts,
            last_block_id=prev_bid,
            validators_hash=vhash,
            next_validators_hash=vhash,
            proposer_address=vals.validators[h % n_vals].address,
        )
        bid = BlockID(
            hash=header.hash(),
            part_set_header=PartSetHeader(
                1, hashlib.sha256(b"parts-%d" % h).digest()
            ),
        )
        sigs = []
        for priv in privs:
            sb = canonical_vote_sign_bytes(
                CHAIN_ID, PRECOMMIT_TYPE, h, 0, bid, ts
            )
            sigs.append(
                CommitSig(
                    block_id_flag=BLOCK_ID_FLAG_COMMIT,
                    validator_address=priv.pub_key().address(),
                    timestamp=ts,
                    signature=priv.sign(sb),
                )
            )
        commit = Commit(height=h, round_=0, block_id=bid, signatures=sigs)
        blocks[h] = LightBlock(SignedHeader(header, commit), vals)
        prev_bid = bid

    class ChainProvider(Provider):
        def chain_id(self) -> str:
            return CHAIN_ID

        def light_block(self, height: int):
            return blocks[height if height else heights]

        def report_evidence(self, ev) -> None:
            pass

    trust = TrustOptions(
        period_s=10**9, height=1, hash=blocks[1].hash()
    )
    return ChainProvider(), trust, base_ns


def run(emit, n_vals: int | None = None, heights: int | None = None) -> dict:
    """Build the chain, run sequential sync, emit one JSON record."""
    from cometbft_tpu.light import SEQUENTIAL, LightClient, LightStore
    from cometbft_tpu.store.kv import MemKV

    n_vals = n_vals or int(os.environ.get("BENCH_LIGHT_VALS", "1000"))
    heights = heights or int(os.environ.get("BENCH_LIGHT_HEIGHTS", "4"))
    t0 = time.perf_counter()
    provider, trust, base_ns = build_chain(n_vals, heights)
    setup_s = time.perf_counter() - t0

    now = base_ns / 1e9 + heights + 60
    client = LightClient(
        CHAIN_ID,
        trust,
        provider,
        [provider],
        LightStore(MemKV()),
        mode=SEQUENTIAL,
        now_fn=lambda: now,
    )
    t0 = time.perf_counter()
    lb = client.verify_light_block_at_height(heights, now=now)
    sync_s = time.perf_counter() - t0
    assert lb is not None and lb.height == heights
    n_commits = heights - 1  # height 1 is trusted, 2..H verified
    sigs = n_commits * n_vals
    rec = {
        "metric": "light_client_sync",
        "value": round(sigs / sync_s, 1),
        "unit": "sig-verifies/s",
        "validators": n_vals,
        "heights_verified": n_commits,
        "sync_s": round(sync_s, 3),
        "per_commit_ms": round(sync_s / max(n_commits, 1) * 1e3, 1),
        "setup_s": round(setup_s, 1),
    }
    emit(rec)
    return rec


def main() -> None:
    from cometbft_tpu.libs import cachedir

    cachedir.enable()
    run(lambda rec: print(json.dumps(rec), flush=True))


if __name__ == "__main__":
    main()
