"""``light/verifier.verify`` and ``Vote.verify`` against the plain reference
(``cometbft_tpu/light/reference.py``) over every request class of a skipping
light client, seeded, at 16 and 64 validators.

The device path is stubbed as the scheduler's tests do: a trusted ``tpu``
backend whose device runner is the host oracle, and the host stand-in for the
SHA-256 tree kernel (XLA-CPU does not return from it).  Everything above the
two seams runs as on a chip: the trusting pass by address, the signature
cache between the two passes, the segment scheduler at ``PRIO_LIGHT``.
"""

import hashlib
import os
import random
import re
import threading

import numpy as np
import pytest

from cometbft_tpu import verifysched
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.libs import tracing
from cometbft_tpu.light import reference, verifier
from cometbft_tpu.ops import dispatch_stats, sha256_tree, supervisor
from cometbft_tpu.types import validation
from cometbft_tpu.types.basic import (
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
    BlockID,
    PartSetHeader,
    Timestamp,
)
from cometbft_tpu.types.block import Commit, ConsensusVersion, Header
from cometbft_tpu.types.light import LightBlock, SignedHeader
from cometbft_tpu.types.validator import Validator, ValidatorSet
from cometbft_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, CommitSig, Vote
from cometbft_tpu.verifysched import stats as sstats

CHAIN = "light-ref-test"
PERIOD_S = 14 * 86400
BASE_NS = 1_700_000_000 * 10**9
SIZES = (16, 64)


def _oracle_runner(backend, pubs, msgs, sigs, lanes):
    out = np.zeros(lanes, dtype=bool)
    out[: len(pubs)] = [
        ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)
    ]
    return out


@pytest.fixture
def device_stub(monkeypatch):
    from cometbft_tpu.crypto import backend_health

    monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "tpu")
    monkeypatch.delenv("COMETBFT_TPU_VERIFY_SCHED", raising=False)
    supervisor.set_device_runner(_oracle_runner)
    sha256_tree.set_tree_runner(sha256_tree.host_tree_runner)
    for reset in (sigcache.reset_cache, sstats.reset, dispatch_stats.reset,
                  backend_health.reset, verifysched.reset_scheduler,
                  tracing.reset_tracer):
        reset()
    yield
    verifysched.reset_scheduler()
    supervisor.clear_device_runner()
    sha256_tree.clear_tree_runner()
    backend_health.reset()
    sigcache.reset_cache()
    sstats.reset()


# -- a chain whose set changes, as the program's objects ---------------------------


class Universe:
    def __init__(self, seed: int, size: int):
        self.keys = [
            Ed25519PrivKey.from_seed(hashlib.sha256(b"lr-%d-%d" % (seed, i)).digest())
            for i in range(size)
        ]
        self.by_address = {k.pub_key().address(): k for k in self.keys}
        self.rng = random.Random(seed)

    def vals(self, ids) -> ValidatorSet:
        return ValidatorSet([Validator(self.keys[i].pub_key(), 10) for i in ids])

    def replace(self, ids, out: int) -> list:
        leaving = set(self.rng.sample(sorted(ids), out))
        outside = [i for i in range(len(self.keys)) if i not in set(ids)]
        return [i for i in ids if i not in leaving] + self.rng.sample(outside, out)

    def block(self, height: int, ids, next_ids=None) -> LightBlock:
        vals = self.vals(ids)
        t_ns = BASE_NS + height * 10**9
        header = Header(
            ConsensusVersion(11, 1), CHAIN, height, Timestamp.from_ns(t_ns),
            BlockID(_h(b"last", height), PartSetHeader(1, _h(b"lp", height))),
            last_commit_hash=_h(b"lc", height), data_hash=_h(b"d", height),
            validators_hash=vals.hash(),
            next_validators_hash=self.vals(next_ids or ids).hash(),
            consensus_hash=_h(b"c", 0), app_hash=_h(b"a", height),
            last_results_hash=_h(b"r", height),
            evidence_hash=hashlib.sha256(b"").digest(),
            proposer_address=vals.validators[0].address,
        )
        commit = Commit(height, 0, BlockID(header.hash(), PartSetHeader(1, _h(b"p", height))), [
            CommitSig(BLOCK_ID_FLAG_COMMIT, v.address,
                      Timestamp.from_ns(t_ns + 1 + self.rng.randrange(10**8)), b"")
            for v in vals.validators
        ])
        for i in range(len(commit.signatures)):
            self.sign(commit, i)
        return LightBlock(SignedHeader(header, commit), vals)

    def sign(self, commit: Commit, index: int) -> None:
        cs = commit.signatures[index]
        cs.signature = self.by_address[cs.validator_address].sign(
            commit.vote_sign_bytes(CHAIN, index))


def _h(tag: bytes, n: int) -> bytes:
    return hashlib.sha256(tag + b"%d" % n).digest()


def _flip(commit: Commit, index: int) -> None:
    s = commit.signatures[index].signature
    commit.signatures[index].signature = s[:32] + bytes([s[32] ^ 1]) + s[33:]


def plain(lb: LightBlock) -> reference.LightBlock:
    """The program's light block as the reference's plain bytes."""
    def bid(b):
        return reference.BlockID(b.hash, b.part_set_header.total, b.part_set_header.hash)

    h, c = lb.signed_header.header, lb.signed_header.commit
    return reference.LightBlock(
        reference.Header(
            h.version.block, h.version.app, h.chain_id, h.height, h.time.to_ns(),
            bid(h.last_block_id), h.last_commit_hash, h.data_hash, h.validators_hash,
            h.next_validators_hash, h.consensus_hash, h.app_hash, h.last_results_hash,
            h.evidence_hash, h.proposer_address),
        reference.Commit(c.height, c.round_, bid(c.block_id), [
            reference.CommitSig(s.block_id_flag, s.validator_address,
                                s.timestamp.to_ns(), s.signature)
            for s in c.signatures]),
        [(v.pub_key.bytes(), v.voting_power) for v in lb.validator_set.validators],
    )


def program_verdict(trusted, new, now_s, period_s=PERIOD_S) -> tuple:
    try:
        verifier.verify(CHAIN, trusted, new, period_s, now_s)
    except validation.InvalidSignatureError as e:
        return ("invalid_signature", e.index)
    except verifier.ErrNewValSetCantBeTrusted:
        return ("cant_be_trusted",)
    except verifier.ErrOldHeaderExpired:
        return ("expired",)
    except verifier.ErrInvalidHeader:
        return ("invalid_header",)
    except validation.CommitVerificationError:
        return ("invalid_commit",)
    return ("accepted",)


def reference_verdict(trusted, new, now_s, period_s=PERIOD_S) -> tuple:
    got = reference.verify(CHAIN, plain(trusted), plain(new), period_s, now_s)
    return got if got[0] == "invalid_signature" else got[:1]


def trusting_indices(trusted: LightBlock, new: LightBlock) -> list:
    """Commit indices the trusting pass verifies."""
    inside = {v.address for v in trusted.validator_set.validators}
    needed = trusted.validator_set.total_voting_power() // 3
    tallied, picked = 0, []
    for i, cs in enumerate(new.signed_header.commit.signatures):
        if cs.validator_address in inside:
            picked.append(i)
            tallied += 10
            if tallied > needed:
                break
    return picked


def _case(kind: str, n: int, salt: int = 0):
    """(trusted, new, now, verdict wanted) of one request class."""
    u = Universe(n * 31 + len(kind) + 1000 * salt, 2 * n)
    ids = u.rng.sample(range(2 * n), n)
    prefix = n * 2 // 3 + 1
    now_after = 5.0
    want = ("accepted",)
    if kind in ("adjacent", "wrong_next_validators_hash"):
        nxt = u.replace(ids, 1)
        trusted = u.block(10, ids, next_ids=nxt if kind == "adjacent" else ids)
        new = u.block(11, nxt)
        if kind != "adjacent":
            want = ("invalid_header",)
    else:
        trusted = u.block(10, ids)
        kept = n * 3 // 10 if kind == "too_far" else n - max(1, n // 10)
        new = u.block(10 + u.rng.randrange(2, 500), u.replace(ids, n - kept))
    commit = new.signed_header.commit
    picked = trusting_indices(trusted, new)
    if kind == "too_far":
        want = ("cant_be_trusted",)
    elif kind == "tampered_in_trusting_prefix":
        index = u.rng.choice(picked)
        _flip(commit, index)
        want = ("invalid_signature", index)
    elif kind == "tampered_outside_trusting_prefix":
        index = u.rng.choice([i for i in range(prefix) if i not in picked])
        _flip(commit, index)
        want = ("invalid_signature", index)
    elif kind == "duplicate_address":
        a, b = picked[0], picked[1]
        commit.signatures[b] = CommitSig(
            BLOCK_ID_FLAG_COMMIT, commit.signatures[a].validator_address,
            commit.signatures[a].timestamp, commit.signatures[a].signature)
        want = ("invalid_commit",)
    elif kind.startswith("unknown_signer"):
        inside = {v.address for v in trusted.validator_set.validators}
        strangers = [i for i, cs in enumerate(commit.signatures)
                     if cs.validator_address not in inside]
        if kind == "unknown_signer_in_light_prefix":
            index = next(i for i in strangers if i < prefix)
            want = ("invalid_signature", index)
        else:  # never read by either pass
            index = next((i for i in reversed(strangers) if i >= prefix), None)
            if index is None:  # no stranger there on this seed
                return _case(kind, n, salt + 1)
        _flip(commit, index)
    elif kind == "expired_trusted_header":
        now_after = PERIOD_S + 1.0
        want = ("expired",)
    elif kind == "header_from_the_future":
        now_after = -3600.0
        want = ("invalid_header",)
    return trusted, new, new.signed_header.header.time.to_ns() / 1e9 + now_after, want


CLASSES = (
    "ordinary_skip", "adjacent", "tampered_in_trusting_prefix",
    "tampered_outside_trusting_prefix", "too_far", "duplicate_address",
    "unknown_signer_in_light_prefix", "unknown_signer_beyond_light_prefix",
    "expired_trusted_header", "wrong_next_validators_hash",
    "header_from_the_future",
)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("kind", CLASSES)
def test_program_equals_reference(device_stub, kind, n):
    trusted, new, now_s, want = _case(kind, n)
    got = program_verdict(trusted, new, now_s)
    assert got == reference_verdict(trusted, new, now_s) == want


@pytest.mark.parametrize("n", SIZES)
def test_second_pass_hits_what_the_first_pass_verified(device_stub, n):
    """The light pass over the same commit looks up its whole prefix; the
    triples the trusting pass just wrote back are hits and ship nothing."""
    trusted, new, now_s, _ = _case("ordinary_skip", n)
    picked = trusting_indices(trusted, new)
    prefix = n * 2 // 3 + 1
    tracing.reset_tracer()  # building the blocks hashed their sets
    assert program_verdict(trusted, new, now_s) == ("accepted",)
    seam = [s["attrs"] for s in tracing.get_tracer().tail(64)
            if s["stage"] == "batch.verify"]
    assert [(a["sigs"], a["hits"], a["keys"]) for a in seam] == [
        (len(picked), 0, len(picked)),  # the trusting pass: every triple new
        # the light pass: what the first wrote back; one key a look-up
        (prefix, len(picked), prefix),
    ]
    assert len([i for i in picked if i < prefix]) == len(picked)
    assert dispatch_stats.snapshot()["dispatches"] == 2  # both passes shipped misses
    totals = tracing.get_tracer().stage_totals()
    for stage in ("light.verify", "light.checks", "valset.hash",
                  "verify.commit.trusting", "verify.commit"):
        assert totals[stage][0] == 1, stage
    spans = {s["stage"]: s.get("attrs") for s in tracing.get_tracer().tail(64)}
    assert spans["verify.commit.trusting"]["mode"] == "trusting"
    assert spans["verify.commit.trusting"]["scanned"] == picked[-1] + 1
    assert spans["verify.commit.trusting"]["skipped"] == picked[-1] + 1 - len(picked)
    assert spans["verify.commit"]["mode"] == "light"
    assert spans["valset.hash"] == {"leaves": n, "tier": "host", "path": "native"}
    assert spans["light.verify"]["adjacent"] is False


def test_valset_hash_is_computed_once_a_call(device_stub, monkeypatch):
    trusted, new, now_s, _ = _case("ordinary_skip", 16)
    calls = []
    real = ValidatorSet.hash_with_path
    monkeypatch.setattr(
        ValidatorSet, "hash_with_path", lambda self: calls.append(1) or real(self)
    )
    assert program_verdict(trusted, new, now_s) == ("accepted",)
    assert len(calls) == 1


@pytest.mark.parametrize("n", SIZES)
def test_reference_hashes_and_sign_bytes_are_the_programs(n):
    u = Universe(n, n + 4)
    lb = u.block(7, list(range(n)), next_ids=list(range(1, n + 1)))
    p = plain(lb)
    assert reference.header_hash(p.header) == lb.signed_header.header.hash()
    assert reference.validators_hash(p.validators) == lb.validator_set.hash()
    assert p.header.next_validators_hash != p.header.validators_hash
    for i in (0, n - 1):
        assert reference.vote_sign_bytes(CHAIN, p.commit, i) == \
            lb.signed_header.commit.vote_sign_bytes(CHAIN, i)
        assert reference.address(p.validators[i][0]) == \
            lb.validator_set.validators[i].address


def test_reference_imports_nothing_of_the_device_path():
    src = open(reference.__file__).read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", src, flags=re.M)
    assert sorted(set(imports)) == [
        "__future__", "cometbft_tpu.crypto", "hashlib", "typing"]


def test_benchmarks_copy_is_the_reference():
    """``benchmarks/light_ref.py`` differs in its one import line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    copy = open(os.path.join(root, "benchmarks", "light_ref.py")).read()
    mine = open(reference.__file__).read()
    assert copy == mine.replace(
        "from cometbft_tpu.crypto import ed25519_ref as _ed",
        "from benchmarks import ed25519_ref as _ed")
    assert copy != mine


# -- single votes ------------------------------------------------------------------


def _votes(n: int, tampered: set):
    u = Universe(n + 99, n)
    vals = u.vals(range(n))
    bid = BlockID(_h(b"vb", 1), PartSetHeader(1, _h(b"vp", 1)))
    out = []
    for i, v in enumerate(vals.validators):
        vote = Vote(PREVOTE_TYPE if i % 2 else PRECOMMIT_TYPE, 5, 0, bid,
                    Timestamp.from_ns(BASE_NS + i * 1000 + 1), v.address, i)
        vote.signature = u.by_address[v.address].sign(vote.sign_bytes(CHAIN))
        if i in tampered:
            vote.signature = bytes([vote.signature[0] ^ 1]) + vote.signature[1:]
        out.append((vote, v.pub_key))
    return out


def test_one_vote_is_one_entry_of_one_signature(device_stub):
    (good, pk), (bad, pk2) = _votes(2, {1})
    for vote, key, want in ((good, pk, True), (bad, pk2, False)):
        assert vote.verify(CHAIN, key) is want
        assert want == ref.verify_zip215(key.bytes(), vote.sign_bytes(CHAIN),
                                         vote.signature)
    ss = sstats.snapshot()
    assert ss["segments"]["consensus"] == ss["submitted"]["consensus"] == 2
    assert sum(ss["flushes"].values()) == 2
    assert good.verify(CHAIN, pk) is True  # again: the cache answers
    spans = [s for s in tracing.get_tracer().tail(64) if s["stage"] == "consensus.vote"]
    assert [s["attrs"]["hit"] for s in spans] == [False, False, True]


def test_a_burst_of_votes_coalesces(device_stub):
    """Sixteen senders, one vote each, held until all are queued: one flush,
    one dispatch, every verdict the reference's."""
    votes = _votes(16, {3, 11})
    sched = verifysched.get_scheduler()
    sched.pause()
    got = [None] * len(votes)

    def send(k):
        got[k] = votes[k][0].verify(CHAIN, votes[k][1])

    threads = [threading.Thread(target=send, args=(k,)) for k in range(len(votes))]
    for th in threads:
        th.start()
    while sched.pending() < len(votes):
        pass
    sched.resume()
    for th in threads:
        th.join(30)
    assert got == [
        ref.verify_zip215(pk.bytes(), v.sign_bytes(CHAIN), v.signature)
        for v, pk in votes
    ] == [k not in (3, 11) for k in range(16)]
    ss = sstats.snapshot()
    assert sum(ss["flushes"].values()) == 1 and ss["flush_items"] == 16
    assert dispatch_stats.snapshot()["dispatches"] == 1
