#!/usr/bin/env python3
"""Does the commit-verify path still start on the chip?

Drives the ed25519 commit-verify path once through the entry points a node
calls — ``types.validation.verify_commit_light``, ``Vote.verify``,
``crypto.batch.create_batch_verifier`` — on the attached TPU, with nothing
forced: backend, kernel tier, supervisor, AOT cache and mesh are whatever
the program selects by itself.  It then reads the program's own counters and
FAILS unless the Pallas tier did all the work: the supervisor's fallback
chain (pallas -> xla -> host) keeps a validator verifying on the wrong tier
and exiting 0, which is right for a node and wrong for this script.

    python chip_smoke.py            one chip: known-answer vectors, a 175- and
                                    a 10,240-validator commit, 350 votes
    python chip_smoke.py --chips 4  the 10,240-validator commit over the
                                    four-chip mesh (scheduler on, one
                                    mesh-wide launch a flush), nothing else

One process, no children that need the chip.  Prints one JSON line per phase
(times are for the reader, not results) and, as its last line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase or check ends the run: last line ``"ok": false``, exit 1.
There is no CPU mode and no size option; the phases are importable so a
scratch script can rehearse them on the CPU at a tiny size.

The background warm-boot pass (ten buckets times two tiers, many minutes of
compiles) is switched off for this process; the buckets the phases can reach
are warmed in the foreground through ``ops.verify.bucket_executable`` and
reported as set-up.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import sys
import threading
import time
import traceback
from typing import NamedTuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# what a node selects by itself is what is under test
_MUST_BE_UNSET = (
    "COMETBFT_TPU_CRYPTO_BACKEND",
    "COMETBFT_TPU_VERIFY_IMPL",
    "COMETBFT_TPU_MESH",
)

CHAIN_ID = "chip-smoke-chain"
R1_VALIDATORS = 175  # ROADMAP R1: upstream QA-v1 testnet
R2_VALIDATORS = 10240  # ROADMAP R2: BASELINE.json config 2
HEIGHTS = 5
VOTE_THREADS = 7


class SmokeFailure(Exception):
    """A phase ran and the outcome is wrong."""


def emit(rec: dict) -> None:
    print(json.dumps(rec, sort_keys=True), flush=True)


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- health: the program's own counters ---------------------------------------


class Health:
    """Reads ``dispatch_stats``, ``backend_health``, ``warm_stats`` and
    ``ops.verify._AOT_BROKEN`` after a phase and fails unless the expected
    tier did all of the work."""

    def __init__(self, backend: str, tiers: "set[str]", buckets: "set[int]"):
        self.backend = backend
        self.tiers = set(tiers)
        self.buckets = set(buckets)
        self.compiles = None  # warm_stats compiles after warm-up
        self._seen: dict = {}

    def mark_warm(self) -> None:
        from cometbft_tpu.ops import warm_stats

        self.compiles = warm_stats.snapshot()["compiles"]

    def check(self) -> dict:
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.crypto import batch as cbatch
        from cometbft_tpu.ops import dispatch_stats, warm_stats
        from cometbft_tpu.ops import verify as ov

        problems = []
        backend = cbatch.default_backend()
        if backend != self.backend:
            problems.append(
                f"default_backend() is {backend!r}, want {self.backend!r}"
            )
        hist = dispatch_stats.snapshot()["dispatch_hist"]
        new = {}
        for key, h in hist.items():
            tier, _, lanes = key.rpartition("-")
            count = int(h["count"])
            if count > self._seen.get(key, 0):
                new[key] = count - self._seen.get(key, 0)
            if tier not in self.tiers:
                problems.append(f"dispatch on tier {tier!r} ({key})")
            if int(lanes) not in self.buckets:
                problems.append(f"dispatch at an unwarmed bucket ({key})")
        self._seen = {k: int(h["count"]) for k, h in hist.items()}
        bh = backend_health.snapshot()
        for name in ("demotions", "watchdog_fires", "fallback_signatures",
                     "quarantined"):
            if bh[name]:
                problems.append(f"backend_health {name}={bh[name]}")
        for name, st in bh["breakers"].items():
            if st["state"] != backend_health.CLOSED or st["failures_total"]:
                problems.append(
                    f"breaker {name}: state={st['state']} "
                    f"failures={st['failures_total']} "
                    f"last={st['last_error']}"
                )
        ws = warm_stats.snapshot()
        for name in ("warm_failures", "compile_failures"):
            if ws[name]:
                problems.append(f"warm_stats {name}={ws[name]}")
        if self.compiles is not None and ws["compiles"] != self.compiles:
            problems.append(
                f"{ws['compiles'] - self.compiles} compile(s) after warm-up"
            )
        if ov._AOT_BROKEN:
            problems.append(f"_AOT_BROKEN: {sorted(ov._AOT_BROKEN.values())}")
        if problems:
            raise SmokeFailure("; ".join(problems))
        return {"dispatches": new, "tiers": sorted(self.tiers)}


# -- set-up --------------------------------------------------------------------


def native_sidecar() -> str:
    """built / loaded / absent — the host-pack library is compiled from
    ``native/csrc`` when no fresh ``.so`` lies beside it."""
    from cometbft_tpu import native

    fresh = native._fresh(native._SO, native._SRC)
    if native.lib() is None:
        return "absent"
    return "loaded" if fresh else "built"


def reachable_buckets(largest: int) -> "list[int]":
    """Every Pallas-tier padding bucket a batch of up to ``largest``
    signatures can land in: the scheduler drains whatever is queued when a
    flush fires, so a large commit reaches the device in pieces of any
    size."""
    from cometbft_tpu.ops import verify as ov

    top = ov.bucket_size(largest)
    return [b for b in ov._BUCKETS if ov._PALLAS_MIN_BUCKET <= b <= top]


def warm_buckets(buckets, tier: str) -> dict:
    """Resolve each bucket's executable in the foreground — compile or
    cache load, as the program's ``bucket_executable`` decides — and read
    the executable's text for the kernel call."""
    from cometbft_tpu.ops import verify as ov

    out = {}
    for lanes in buckets:
        t0 = time.perf_counter()
        call, info = ov.bucket_executable(tier, lanes)
        rec = dict(info)
        rec["seconds"] = round(time.perf_counter() - t0, 1)
        if tier == "pallas":
            _require(
                "tpu_custom_call" in call.as_text(),
                f"no tpu_custom_call in the {tier}-{lanes} executable",
            )
            rec["tpu_custom_call"] = True
        out[str(lanes)] = rec
    return out


def _full_mesh():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from cometbft_tpu.parallel import mesh as pmesh

    return Mesh(np.array(jax.devices()), (pmesh.SIG_AXIS,))


def cache_counts() -> dict:
    from cometbft_tpu.ops import warm_stats

    ws = warm_stats.snapshot()
    return {
        "compiles": ws["compiles"],
        "compile_s": round(ws["compile_seconds"], 1),
        "hits": ws["exec_hits"],
        "misses": ws["exec_misses"],
        "stale": ws["exec_stale"],
        "writes": ws["exec_writes"],
    }


# -- chain building -----------------------------------------------------------


class Chain(NamedTuple):
    privs: list  # in the set's canonical order
    vals: object  # ValidatorSet
    commits: dict  # height -> (block_id, Commit)


def _fast_signer():
    """Ed25519 is deterministic, so any correct signer gives the same
    bytes as ``priv.sign`` (pure Python, ~1 ms): use the host library
    where it is installed, and hold it to the reference on a sample."""
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )
    except ImportError:
        return None
    return lambda seed: Ed25519PrivateKey.from_private_bytes(seed).sign


def build_chain(n_vals: int, heights: int, tag: bytes) -> Chain:
    """One ``n_vals``-validator set with distinct keys and ``heights``
    commits signed by all of them, assembled directly."""
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.types.basic import BlockID, PartSetHeader, Timestamp
    from cometbft_tpu.types.block import Commit, ConsensusVersion, Header
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.types.vote import (
        BLOCK_ID_FLAG_COMMIT,
        PRECOMMIT_TYPE,
        CommitSig,
        canonical_vote_sign_bytes,
    )

    privs = [
        Ed25519PrivKey.from_seed(
            hashlib.sha256(b"chip-smoke-%s-val-%d" % (tag, i)).digest()
        )
        for i in range(n_vals)
    ]
    pubs = [p.pub_key() for p in privs]  # derived once: ~0.5 ms each
    vals = ValidatorSet([Validator(pub, 10) for pub in pubs])
    by_addr = {pub.address(): p for pub, p in zip(pubs, privs)}
    privs = [by_addr[v.address] for v in vals.validators]
    fast = _fast_signer()
    signers = [fast(p.seed) if fast else p.sign for p in privs]
    vhash = vals.hash()
    base_ns = 1_700_000_000 * 10**9
    commits = {}
    prev_bid = BlockID(
        hash=hashlib.sha256(b"genesis" + tag).digest(),
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
        sb = canonical_vote_sign_bytes(CHAIN_ID, PRECOMMIT_TYPE, h, 0, bid, ts)
        sigs = [
            CommitSig(
                block_id_flag=BLOCK_ID_FLAG_COMMIT,
                validator_address=v.address,
                timestamp=ts,
                signature=sign(sb),
            )
            for v, sign in zip(vals.validators, signers)
        ]
        _require(
            sigs[h % n_vals].signature == privs[h % n_vals].sign(sb),
            "the host library's signature differs from the reference's",
        )
        commits[h] = (
            bid,
            Commit(height=h, round_=0, block_id=bid, signatures=sigs),
        )
        prev_bid = bid
    return Chain(privs, vals, commits)


def flip_signature(chain: Chain, height: int, index: int) -> None:
    sigs = chain.commits[height][1].signatures
    sig = sigs[index].signature
    sigs[index].signature = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]


# -- phases --------------------------------------------------------------------


def phase_vectors() -> dict:
    """The 18 known-answer vectors of ``scripts/chip_validate._vectors``
    (valid, every tamper class, the ZIP-215 edge encodings) through
    ``create_batch_verifier``."""
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.crypto import keys as ck
    from scripts.chip_validate import _vectors

    pubs, msgs, sigs, expect, labels = _vectors()
    bv = cbatch.create_batch_verifier(ck.Ed25519PubKey(pubs[0]))
    for p, m, s in zip(pubs, msgs, sigs):
        bv.add(p, m, s)
    t0 = time.perf_counter()
    ok, bits = bv.verify()
    wall = time.perf_counter() - t0
    wrong = [
        lbl for lbl, w, g in zip(labels, expect, bits) if bool(w) != bool(g)
    ]
    _require(not wrong, f"wrong verdicts on vectors {wrong}")
    _require(not ok, "a batch with rejects reported ok")
    return {"vectors": len(labels), "wall_ms": round(wall * 1e3, 2)}


def phase_commit(chain: Chain, sample: int = 32) -> dict:
    """``HEIGHTS`` good commits through ``verify_commit_light`` under one
    validator set, then one with a flipped signature: it must raise what
    the host path raises for that index, and the device's accept bits must
    equal ``ed25519_ref.verify_zip215`` on a sample around the flip."""
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.crypto import ed25519_ref as ref
    from cometbft_tpu.types import validation

    n = len(chain.vals.validators)
    walls = []
    for h in range(1, HEIGHTS + 1):
        bid, commit = chain.commits[h]
        t0 = time.perf_counter()
        validation.verify_commit_light(CHAIN_ID, chain.vals, bid, h, commit)
        walls.append(round((time.perf_counter() - t0) * 1e3, 2))

    bad_h = HEIGHTS + 1
    flipped = n // 3  # inside the +2/3 prefix the light path verifies
    flip_signature(chain, bad_h, flipped)
    bid, commit = chain.commits[bad_h]
    t0 = time.perf_counter()
    try:
        validation.verify_commit_light(CHAIN_ID, chain.vals, bid, bad_h, commit)
    except validation.InvalidSignatureError as e:
        got = e
    else:
        raise SmokeFailure(f"flipped signature {flipped} was accepted")
    bad_wall = round((time.perf_counter() - t0) * 1e3, 2)
    try:
        validation.verify_commit_light(
            CHAIN_ID, chain.vals, bid, bad_h, commit, backend="cpu"
        )
    except validation.CommitVerificationError as e:
        want = e
    else:
        raise SmokeFailure("the host path accepted the flipped signature")
    _require(
        type(got) is type(want) and str(got) == str(want),
        f"device path raised {got!r}, host path {want!r}",
    )
    _require(str(flipped) in str(got), f"{got!r} does not name {flipped}")

    # accept bits of the whole flipped commit, against the reference
    sign_bytes = commit.all_vote_sign_bytes(CHAIN_ID, list(range(n)))
    bv = cbatch.create_batch_verifier(chain.vals.validators[0].pub_key)
    for v, cs, sb in zip(chain.vals.validators, commit.signatures, sign_bytes):
        bv.add(v.pub_key, sb, cs.signature)
    _, bits = bv.verify()
    _require(
        [i for i, b in enumerate(bits) if not b] == [flipped],
        "accept bits differ from all-true-but-the-flipped-lane",
    )
    rng = random.Random(n)
    picks = {0, n - 1, flipped - 1, flipped, flipped + 1}
    picks.update(rng.randrange(n) for _ in range(sample - len(picks)))
    for i in sorted(picks):
        host = ref.verify_zip215(
            chain.vals.validators[i].pub_key.bytes(),
            sign_bytes[i],
            commit.signatures[i].signature,
        )
        _require(
            bool(bits[i]) == bool(host),
            f"lane {i}: device says {bits[i]}, reference says {host}",
        )
    return {
        "validators": n,
        "heights_ok": HEIGHTS,
        "wall_ms": walls,
        "flipped_index": flipped,
        "flipped_error": repr(got),
        "flipped_wall_ms": bad_wall,
        "reference_sample": len(picks),
    }


def phase_votes(chain: Chain, threads: int = VOTE_THREADS) -> dict:
    """One height's gossip for the set: a prevote and a precommit from
    every validator, each through ``Vote.verify`` (the scheduler's
    consensus class), submitted from a few threads."""
    from cometbft_tpu import verifysched
    from cometbft_tpu.types.basic import Timestamp
    from cometbft_tpu.types.vote import PRECOMMIT_TYPE, PREVOTE_TYPE, Vote

    _require(
        verifysched.scheduler_active(),
        "the verify scheduler is not active on the trusted backend",
    )
    h = HEIGHTS + 2
    bid = chain.commits[1][0]
    ts = Timestamp.from_ns(1_700_000_100 * 10**9)
    votes = []
    for type_ in (PREVOTE_TYPE, PRECOMMIT_TYPE):
        for i, (v, p) in enumerate(zip(chain.vals.validators, chain.privs)):
            vote = Vote(type_, h, 0, bid, ts, v.address, i)
            vote.signature = p.sign(vote.sign_bytes(CHAIN_ID))
            votes.append((vote, v.pub_key))
    results = [None] * len(votes)
    errors = []

    def work(k: int) -> None:
        try:
            for j in range(k, len(votes), threads):
                vote, pub = votes[j]
                results[j] = vote.verify(CHAIN_ID, pub)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    pool = [threading.Thread(target=work, args=(k,)) for k in range(threads)]
    t0 = time.perf_counter()
    for t in pool:
        t.start()
    for t in pool:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    _require(
        all(r is True for r in results),
        f"{sum(r is not True for r in results)} of {len(votes)} votes "
        "did not verify",
    )
    return {
        "votes": len(votes),
        "threads": threads,
        "wall_ms": round(wall * 1e3, 2),
    }


def phase_mesh_record(width: int, lanes_seen: "set[int]") -> dict:
    """The program's own per-ordinal record of the mesh-wide dispatches:
    every ordinal is a member, carried lanes that held real signatures
    (``mesh.shard`` spans), no shard breaker moved, and the executable that
    ran holds the kernel and the one collective."""
    from cometbft_tpu.crypto import backend_health
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.ops import dispatch_stats
    from cometbft_tpu.parallel import elastic
    from cometbft_tpu.parallel import mesh as pmesh

    want = list(range(width))
    _require(elastic.active(), "the elastic mesh did not enable itself")
    _require(
        elastic.healthy_ordinals() == want,
        f"mesh membership is {elastic.healthy_ordinals()}, want {want}",
    )
    per_ordinal = {o: {"shards": 0, "lanes": 0, "accepted": 0} for o in want}
    for sp in tracing.get_tracer().tail(4096):
        if sp["stage"] != "mesh.shard":
            continue
        a = sp.get("attrs", {})
        rec = per_ordinal.get(a.get("device"))
        _require(rec is not None, f"mesh.shard span on ordinal {a}")
        rec["shards"] += 1
        rec["lanes"] += int(a["lanes"])
        rec["accepted"] += int(a.get("ok", 0))
    for o, rec in per_ordinal.items():
        _require(
            rec["lanes"] > 0 and rec["accepted"] > 0,
            f"ordinal {o} carried no signatures: {per_ordinal}",
        )
    snap = dispatch_stats.snapshot()
    _require(snap["mesh_shrinks"] == 0, f"{snap['mesh_shrinks']} mesh shrinks")
    _require(snap["mesh_width"] == width, f"mesh width {snap['mesh_width']}")
    for o in want:
        st = backend_health.registry().breaker(elastic.breaker_name(o)).stats()
        _require(
            st["state"] == backend_health.CLOSED and not st["failures_total"],
            f"shard breaker {o} moved: {st}",
        )
    m = _full_mesh()
    for lanes in sorted(lanes_seen):
        call, info = pmesh.sharded_verify_call(m, lanes)
        _require(
            info["exec_cache"] == "memo",
            f"the {lanes}-lane mesh executable did not run ({info})",
        )
        text = call.as_text()
        _require("tpu_custom_call" in text, f"mesh-{lanes}: no tpu_custom_call")
        _require("all-reduce" in text, f"mesh-{lanes}: no all-reduce")
    return {
        "ordinals": {str(o): r for o, r in per_ordinal.items()},
        "mesh_lanes": sorted(lanes_seen),
        "tpu_custom_call": True,
        "all_reduce": True,
    }


# -- the two runs ---------------------------------------------------------------


def _timed(fn, *args) -> "tuple[float, object]":
    t0 = time.perf_counter()
    out = fn(*args)
    return round(time.perf_counter() - t0, 1), out


def build_chains(sizes) -> dict:
    """Built before the batch backend is resolved: once it is trusted,
    ``ValidatorSet.hash`` routes through the device SHA-256 tree plane,
    which is not what this script is about."""
    from cometbft_tpu.crypto import batch as cbatch

    _require(
        cbatch._DEFAULT_BACKEND is None,
        "the batch backend was resolved before the chains were built",
    )
    out = {}
    for name, n_vals in sizes:
        t_build, chain = _timed(
            build_chain, n_vals, HEIGHTS + 1, name.encode()
        )
        out[name] = (t_build, chain)
    return out


def run_one_chip() -> None:
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.ops import verify as ov

    chains = build_chains(
        (("commit_r1", R1_VALIDATORS), ("commit_r2", R2_VALIDATORS))
    )
    tier = ov.select_impl()
    _require(tier == "pallas", f"select_impl() chose {tier!r} on a TPU")
    buckets = reachable_buckets(R2_VALIDATORS)
    health = Health("tpu", {"pallas"}, set(buckets))
    t_warm, warmed = _timed(warm_buckets, buckets, tier)
    emit({
        "phase": "setup", "tier": tier, "native": native_sidecar(),
        "backend": cbatch.default_backend(), "warm_s": t_warm,
        "buckets": warmed, "cache": cache_counts(),
        "build_s": {k: t for k, (t, _) in chains.items()},
    })
    health.mark_warm()
    health.check()

    emit({"phase": "vectors", **phase_vectors(), **health.check()})
    for name, (_, chain) in chains.items():
        emit({"phase": name, **phase_commit(chain), **health.check()})
        if name == "commit_r1":
            emit({"phase": "votes", **phase_votes(chain), **health.check()})
    emit({"phase": "teardown", "cache": cache_counts()})


def run_four_chips() -> None:
    """Only the mesh, on the path a node takes: the 10,240-validator commit
    through ``verify_commit_light`` with the elastic mesh enabled by the
    program itself and the scheduler ON.  A commit's segment is one flush,
    and a flush the mesh takes is ONE mesh-wide launch
    (``ops/supervisor.dispatch_verify``): the sharded executable with its
    collective is what every commit dispatch of this run goes through.
    ``bucket_executable`` resolves that executable beside the one-chip one
    on a mesh-active host, so ``warm_buckets`` warms both."""
    import jax

    from cometbft_tpu import verifysched
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.ops import dispatch_stats
    from cometbft_tpu.ops import verify as ov
    from cometbft_tpu.parallel import elastic

    width = len(jax.devices())
    t_build, chain = build_chains((("commit_r2", R2_VALIDATORS),))["commit_r2"]
    backend = cbatch.default_backend()
    tier = ov.select_impl()
    buckets = reachable_buckets(R2_VALIDATORS)
    health = Health("tpu", {"pallas"}, set(buckets))
    t_warm, warmed = _timed(warm_buckets, buckets, tier)
    _require(
        verifysched.scheduler_active(),
        "the verify scheduler is not active on the trusted backend",
    )
    emit({
        "phase": "setup", "tier": tier, "native": native_sidecar(),
        "build_s": t_build, "backend": backend, "scheduler": "on",
        "warm_s": t_warm, "buckets": warmed, "cache": cache_counts(),
    })
    # the self-check's two signatures run on one chip's smallest bucket;
    # every commit dispatch after it is mesh-wide
    health.mark_warm()
    health.check()
    rec = phase_commit(chain)
    mesh_lanes = {
        int(k) for k in dispatch_stats.snapshot()["buckets"]
        if int(k) >= elastic.min_batch()
    }
    emit({
        "phase": "mesh_commit_r2", **rec, **health.check(),
        "cache": cache_counts(),
    })
    emit({"phase": "mesh_record", **phase_mesh_record(width, mesh_lanes)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    device = None
    phase = "start"
    try:
        forced = [v for v in _MUST_BE_UNSET if v in os.environ]
        _require(not forced, f"unset {forced}: nothing may be forced here")
        # the default warm-boot matrix would compile for many minutes
        # behind this script's back; its buckets are warmed explicitly
        os.environ["COMETBFT_TPU_WARMBOOT"] = "0"
        from cometbft_tpu.libs import cachedir

        cache_root = cachedir.enable()
        import jax

        devs = jax.devices()
        device = {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        }
        _require(
            device["platform"] == "tpu",
            f"JAX found no TPU (platform {device['platform']!r})",
        )
        _require(
            device["count"] == args.chips,
            f"{device['count']} chips visible, --chips {args.chips} asked",
        )
        emit({"phase": "start", "device": device, "cache_root": cache_root,
              "jax": jax.__version__,
              "platform_version": str(devs[0].client.platform_version)})
        phase = "run"
        run_four_chips() if args.chips == 4 else run_one_chip()
    except BaseException as e:  # noqa: BLE001 — the one handler: it ends the run
        traceback.print_exc()
        emit({"ok": False, "phase": phase, "device": device,
              "error": f"{type(e).__name__}: {e}"})
        return 1
    finally:
        if "cometbft_tpu.verifysched" in sys.modules:
            sys.modules["cometbft_tpu.verifysched"].reset_scheduler()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
