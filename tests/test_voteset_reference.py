"""``VoteSet.add_vote`` / ``make_commit``, ``Vote.verify`` and
``validation.verify_commit`` against the plain reference
(``cometbft_tpu/types/voteset_reference.py``) over every class of vote a
validator's receive routine meets and every error branch, seeded, at 4, 7
and 16 validators.

The device path is stubbed as ``tests/test_light_reference.py`` does: a
trusted ``tpu`` backend whose device runner is the host oracle.  Everything
above that seam runs as on a chip: ``Vote.verify`` through the scheduler's
n = 1 entry, the signature cache, the batch seam under ``verify_commit``.
"""

import hashlib
import os
import random
import re

import numpy as np
import pytest

from cometbft_tpu import verifysched
from cometbft_tpu.consensus.types import HeightVoteSet
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.libs import tracing
from cometbft_tpu.ops import dispatch_stats, supervisor
from cometbft_tpu.types import validation, vote_set
from cometbft_tpu.types import voteset_reference as reference
from cometbft_tpu.types.basic import (
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
    BlockID,
    PartSetHeader,
    Timestamp,
)
from cometbft_tpu.types.validator import Validator, ValidatorSet
from cometbft_tpu.types.vote import Vote
from cometbft_tpu.verifysched import stats as sstats

CHAIN = "voteset-ref-test"
HEIGHT = 9
BASE_NS = 1_700_000_000 * 10**9
SIZES = (4, 7, 16)


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
    for reset in (sigcache.reset_cache, sstats.reset, dispatch_stats.reset,
                  backend_health.reset, verifysched.reset_scheduler,
                  tracing.reset_tracer):
        reset()
    yield
    verifysched.reset_scheduler()
    supervisor.clear_device_runner()
    backend_health.reset()
    sigcache.reset_cache()
    sstats.reset()


def _h(tag: str, n: int = 0) -> bytes:
    return hashlib.sha256(f"{tag}/{n}".encode()).digest()


BLOCK = BlockID(_h("block"), PartSetHeader(1, _h("parts")))
OTHER = BlockID(_h("other"), PartSetHeader(2, _h("other-parts")))
THIRD = BlockID(_h("third"), PartSetHeader(1, _h("third-parts")))
NIL = BlockID()


def plain_block_id(b: BlockID) -> reference.BlockID:
    return reference.BlockID(b.hash, b.part_set_header.total, b.part_set_header.hash)


def plain_vote(v: Vote) -> reference.Vote:
    return reference.Vote(v.type_, v.height, v.round_, plain_block_id(v.block_id),
                          v.timestamp.to_ns(), v.validator_address,
                          v.validator_index, v.signature)


class Pair:
    """One vote set of the program and one of the reference over the same
    validators; ``send`` gives both the same vote and holds them to the same
    verdict and the same state."""

    def __init__(self, n: int, type_: int = PRECOMMIT_TYPE, seed: int = 0):
        self.rng = random.Random(n * 1000 + seed)
        keys = [Ed25519PrivKey.from_seed(_h(f"vsr-{n}-{seed}", i)) for i in range(n)]
        self.vals = ValidatorSet([Validator(k.pub_key(), 10) for k in keys])
        by_address = {k.pub_key().address(): k for k in keys}
        self.keys = [by_address[v.address] for v in self.vals.validators]
        self.plain_vals = [(v.pub_key.bytes(), v.voting_power)
                           for v in self.vals.validators]
        self.n, self.type_ = n, type_
        self.quorum = n * 10 * 2 // 3 // 10 + 1  # votes of power 10
        self.program = vote_set.VoteSet(CHAIN, HEIGHT, 0, type_, self.vals)
        self.reference = reference.VoteSet(CHAIN, HEIGHT, 0, type_, self.plain_vals)

    def vote(self, index: int, block_id: BlockID = BLOCK, **over) -> Vote:
        v = Vote(over.pop("type_", self.type_), over.pop("height", HEIGHT),
                 over.pop("round_", 0), block_id,
                 Timestamp.from_ns(BASE_NS + 1 + self.rng.randrange(10**8)),
                 self.vals.validators[index].address, index)
        v.signature = self.keys[index].sign(v.sign_bytes(CHAIN))
        for field, value in over.items():
            setattr(v, field, value)
        return v

    def send(self, vote) -> tuple:
        got = program_verdict(self.program, vote)
        want = self.reference.add_vote(None if vote is None else plain_vote(vote))
        assert got == want
        self.same_state()
        return got

    def claim(self, peer: str, block_id: BlockID) -> None:
        self.program.set_peer_maj23(peer, block_id)
        self.reference.set_peer_maj23(peer, plain_block_id(block_id))
        self.same_state()

    def same_state(self) -> None:
        p, r = self.program, self.reference
        assert [None if v is None else plain_vote(v) for v in p.votes] == r.votes
        assert p.sum == r.sum
        assert (None if p.maj23 is None else plain_block_id(p.maj23)) == r.maj23
        blocks = {bv_key: bv for bv_key, bv in p.votes_by_block.items()}
        assert len(blocks) == len(r.votes_by_block)
        for bid, rbv in r.votes_by_block.items():
            pbv = blocks[_key(bid)]
            assert (pbv.sum, pbv.peer_maj23) == (rbv.sum, rbv.peer_maj23)
            assert {i: plain_vote(v) for i, v in pbv.votes.items()} == {
                i: v for i, v in enumerate(rbv.votes) if v is not None}


def _key(bid: reference.BlockID) -> bytes:
    return BlockID(bid.hash, PartSetHeader(bid.parts_total, bid.parts_hash)).key()


def program_verdict(vs: vote_set.VoteSet, vote) -> tuple:
    """The program's answer under the reference's names."""
    try:
        added = vs.add_vote(vote)
    except vote_set.ConflictingVoteError as e:
        assert e.conflicting is vote and e.existing is not vote
        return ("conflicting_added" if e.added else "conflicting",
                vote.validator_index)
    except vote_set.VoteError as e:
        assert type(e) is not vote_set.VoteError  # a class of its own
        return (e.outcome,)
    if not added:
        return ("duplicate",)
    return ("added", vs.two_thirds_majority() == vote.block_id)


def _tampered(pair: Pair, vote: Vote, cls: str) -> bytes:
    sig = vote.signature
    if cls == "flip_s":
        return sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
    if cls == "flip_r":
        return bytes([sig[0] ^ 1]) + sig[1:]
    if cls == "noncanonical_s":
        s = int.from_bytes(sig[32:], "little") + ref.L
        return sig[:32] + s.to_bytes(32, "little")
    assert cls == "wrong_msg"  # the validator's sound signature over another block
    return pair.vote(vote.validator_index, THIRD).signature


# -- VoteSet.add_vote, class by class ----------------------------------------------


@pytest.mark.parametrize("type_", (PREVOTE_TYPE, PRECOMMIT_TYPE))
@pytest.mark.parametrize("n", SIZES)
def test_majority_appears_at_the_vote_that_crosses_it(device_stub, n, type_):
    pair = Pair(n, type_)
    order = pair.rng.sample(range(n), n)
    nil_voter = order.pop(1)
    assert pair.send(pair.vote(nil_voter, NIL)) == ("added", False)
    for k, index in enumerate(order):
        assert pair.send(pair.vote(index)) == ("added", k + 1 >= pair.quorum)
    assert pair.program.two_thirds_majority() == BLOCK
    assert pair.program.has_all()
    # every honest vote was one entry of one signature through the scheduler
    ss = sstats.snapshot()
    assert ss["submitted"]["consensus"] == n == sum(ss["flushes"].values())


@pytest.mark.parametrize("n", SIZES)
def test_a_byte_identical_copy_is_a_duplicate_and_changes_nothing(device_stub, n):
    pair = Pair(n)
    vote = pair.vote(2)
    assert pair.send(vote) == ("added", False)
    submitted = sstats.snapshot()["submitted"]["consensus"]
    assert pair.send(vote.copy()) == ("duplicate",)
    assert pair.send(pair.vote(1, NIL)) == ("added", False)
    assert pair.send(pair.program.votes[1].copy()) == ("duplicate",)
    # no signature was looked at for either copy
    assert sstats.snapshot()["submitted"]["consensus"] == submitted + 1


@pytest.mark.parametrize("cls", ("noncanonical_s", "flip_s", "wrong_msg", "flip_r"))
@pytest.mark.parametrize("n", SIZES)
def test_a_wrong_signature_is_never_added_and_the_honest_vote_still_is(
        device_stub, n, cls):
    pair = Pair(n)
    vote = pair.vote(n - 1)
    forged = vote.copy()
    forged.signature = _tampered(pair, vote, cls)
    assert pair.send(forged) == ("invalid_signature",)
    assert pair.program.votes[n - 1] is None and pair.program.sum == 0
    assert pair.send(vote) == ("added", False)


@pytest.mark.parametrize("n", SIZES)
def test_a_copy_under_another_signature_is_nondeterministic(device_stub, n):
    """Same validator, same block id, another signature: refused BEFORE any
    verification, whatever the signature is worth (here: sound, another
    timestamp)."""
    pair = Pair(n)
    assert pair.send(pair.vote(0)) == ("added", False)
    held = pair.program.votes[0]
    submitted = sstats.snapshot()["submitted"]["consensus"]
    assert pair.send(pair.vote(0)) == ("nondeterministic_signature",)
    broken = held.copy()
    broken.signature = bytes(64)
    assert pair.send(broken) == ("nondeterministic_signature",)
    assert pair.program.votes[0] is held
    assert sstats.snapshot()["submitted"]["consensus"] == submitted


@pytest.mark.parametrize("n", SIZES)
def test_equivocation_is_verified_first_and_then_raised_with_both_votes(
        device_stub, n):
    pair = Pair(n)
    first = pair.vote(1)
    assert pair.send(first) == ("added", False)
    # a forged equivocation frames nobody: its signature fails first
    forged = pair.vote(1, OTHER)
    forged.signature = _tampered(pair, forged, "flip_s")
    assert pair.send(forged) == ("invalid_signature",)
    second = pair.vote(1, OTHER)
    with pytest.raises(vote_set.ConflictingVoteError) as e:
        pair.program.add_vote(second.copy())
    assert (e.value.existing, e.value.added) == (first, False)
    assert pair.send(second) == ("conflicting", 1)
    assert pair.program.votes[1] is first and pair.program.sum == 10
    assert OTHER.key() not in pair.program.votes_by_block
    # for nil after a block, and for a block after nil, alike
    assert pair.send(pair.vote(1, NIL)) == ("conflicting", 1)
    assert pair.send(pair.vote(2, NIL)) == ("added", False)
    assert pair.send(pair.vote(2)) == ("conflicting", 2)


@pytest.mark.parametrize("n", SIZES)
def test_a_conflicting_vote_is_admitted_only_under_a_peers_claim(device_stub, n):
    pair = Pair(n)
    assert pair.send(pair.vote(0)) == ("added", False)
    pair.claim("peer-a", OTHER)
    pair.claim("peer-a", THIRD)  # a peer's second claim is ignored
    assert pair.send(pair.vote(0, THIRD)) == ("conflicting", 0)
    admitted = pair.vote(0, OTHER)
    assert pair.send(admitted) == ("conflicting_added", 0)
    # kept with its block, not in the set's own votes; power counted once
    assert pair.program.votes[0].block_id == BLOCK and pair.program.sum == 10
    assert pair.program.votes_by_block[OTHER.key()].votes[0] is admitted
    # the copy of a vote held with its block only is still a copy
    assert pair.send(admitted.copy()) == ("duplicate",)
    assert pair.send(pair.vote(0, OTHER)) == ("nondeterministic_signature",)


@pytest.mark.parametrize("n", SIZES)
def test_the_majoritys_block_replaces_the_other_votes(device_stub, n):
    """Validator 0 votes BLOCK, the rest OTHER under a peer's claim: at the
    quorum OTHER's votes become the set's, and validator 0's own vote for
    OTHER then replaces its first."""
    pair = Pair(n)
    pair.claim("peer-a", OTHER)
    assert pair.send(pair.vote(0)) == ("added", False)
    for k in range(1, pair.quorum + 1):
        assert pair.send(pair.vote(k, OTHER)) == ("added", k >= pair.quorum)
    assert pair.program.two_thirds_majority() == OTHER
    assert pair.send(pair.vote(0, OTHER)) == ("conflicting_added", 0)
    assert pair.program.votes[0].block_id == OTHER
    # a later quorum for another block does not move the majority
    assert pair.program.sum == 10 * (pair.quorum + 1)


ERRORS = {
    "nil_vote": lambda p: None,
    "negative_index": lambda p: p.vote(1, validator_index=-1),
    "empty_address": lambda p: p.vote(1, validator_address=b""),
    "another_height": lambda p: p.vote(1, height=HEIGHT + 1),
    "another_round": lambda p: p.vote(1, round_=1),
    "another_type": lambda p: p.vote(1, type_=PREVOTE_TYPE),
    "no_valid_type": lambda p: p.vote(1, type_=7),
    "index_beyond_the_set": lambda p: p.vote(1, validator_index=p.n),
    "address_of_another_validator": lambda p: p.vote(
        1, validator_address=p.vals.validators[2].address),
    "address_of_another_size": lambda p: p.vote(1, validator_address=b"\x01" * 19),
    "no_signature": lambda p: p.vote(1, signature=b""),
    "signature_too_long": lambda p: p.vote(1, signature=bytes(97)),
    "signed_for_another_chain": lambda p: p.vote(
        1, signature=p.keys[1].sign(p.vote(1).sign_bytes("another-chain"))),
    "signed_with_another_key": lambda p: p.vote(
        1, signature=p.keys[2].sign(p.vote(1).sign_bytes(CHAIN))),
}
WANT = {
    "nil_vote": "nil_vote", "negative_index": "invalid_validator_index",
    "empty_address": "invalid_validator_address",
    "another_height": "unexpected_step", "another_round": "unexpected_step",
    "another_type": "unexpected_step", "no_valid_type": "unexpected_step",
    "index_beyond_the_set": "invalid_validator_index",
    "address_of_another_validator": "invalid_validator_address",
    "address_of_another_size": "invalid_validator_address",
}


@pytest.mark.parametrize("error", sorted(ERRORS))
@pytest.mark.parametrize("n", SIZES)
def test_every_error_branch_answers_as_the_reference(device_stub, n, error):
    pair = Pair(n)
    assert pair.send(ERRORS[error](pair)) == (WANT.get(error, "invalid_signature"),)
    assert pair.program.sum == 0 and not pair.program.votes_by_block


def test_a_votes_order_of_checks_is_upstreams(device_stub):
    """A vote wrong in several ways answers with the FIRST of vote_set.go's
    checks: index below zero, address empty, step, index, address."""
    pair = Pair(4)
    wrong = dict(validator_index=-1, validator_address=b"", height=HEIGHT + 1,
                 signature=b"")
    want = ["invalid_validator_index", "invalid_validator_address",
            "unexpected_step", "invalid_signature"]
    for fixed, outcome in zip(("validator_index", "validator_address", "height", None),
                              want):
        assert pair.send(pair.vote(1, **wrong)) == (outcome,)
        wrong.pop(fixed, None)


@pytest.mark.parametrize("n", SIZES)
def test_vote_verify_holds_the_key_to_the_votes_address(device_stub, n):
    pair = Pair(n)
    vote = pair.vote(1)
    for index, want in ((1, ("ok",)), (2, ("invalid_validator_address",))):
        pub = pair.vals.validators[index].pub_key
        assert reference.vote_verify(CHAIN, pub.bytes(), plain_vote(vote)) == want
        assert vote.verify(CHAIN, pub) is (want == ("ok",))
    vote.signature = _tampered(pair, vote, "flip_r")
    assert reference.vote_verify(
        CHAIN, pair.plain_vals[1][0], plain_vote(vote)) == ("invalid_signature",)
    assert vote.verify(CHAIN, pair.vals.validators[1].pub_key) is False


def test_height_vote_set_routes_by_type_and_drops_no_valid_type(device_stub):
    pair = Pair(4)
    hvs = HeightVoteSet(CHAIN, HEIGHT, pair.vals)
    assert hvs.add_vote(pair.vote(0, type_=PREVOTE_TYPE), "peer") is True
    assert hvs.add_vote(pair.vote(0), "peer") is True
    assert hvs.add_vote(pair.vote(1, type_=7), "peer") is False
    assert (hvs.prevotes(0).sum, hvs.precommits(0).sum) == (10, 10)


def test_voteset_add_is_the_parent_of_consensus_vote(device_stub):
    pair = Pair(4, PREVOTE_TYPE)
    vote = pair.vote(0)
    forged = pair.vote(1)
    forged.signature = _tampered(pair, forged, "flip_s")
    tracing.reset_tracer()
    pair.send(vote)
    pair.send(vote.copy())
    pair.send(forged)
    pair.send(pair.vote(0, OTHER))
    spans = tracing.get_tracer().tail(64)
    adds = [s for s in spans if s["stage"] == "voteset.add"]
    assert [(s["attrs"]["t"], s["attrs"]["outcome"]) for s in adds] == [
        (PREVOTE_TYPE, "added"), (PREVOTE_TYPE, "duplicate"),
        (PREVOTE_TYPE, "invalid_signature"), (PREVOTE_TYPE, "conflicting")]
    verifies = [s for s in spans if s["stage"] == "consensus.vote"]
    # the copy is answered before any signature is looked at
    assert [s["parent"] for s in verifies] == [
        adds[0]["span"], adds[2]["span"], adds[3]["span"]]
    assert [s["attrs"]["ok"] for s in verifies] == [True, False, True]


# -- make_commit and verify_commit --------------------------------------------------


def _filled(n: int, absent=(), nil=(), seed: int = 0) -> Pair:
    pair = Pair(n, PRECOMMIT_TYPE, seed)
    for index in pair.rng.sample(range(n), n):
        if index not in absent:
            pair.send(pair.vote(index, NIL if index in nil else BLOCK))
    return pair


def _plain_commit(commit) -> reference.Commit:
    return reference.Commit(
        commit.height, commit.round_, plain_block_id(commit.block_id),
        [reference.CommitSig(s.block_id_flag, s.validator_address,
                             s.timestamp.to_ns(), s.signature)
         for s in commit.signatures])


def commit_verdict(pair: Pair, block_id, height, commit) -> tuple:
    try:
        validation.verify_commit(CHAIN, pair.vals, block_id, height, commit)
    except validation.InvalidSignatureError as e:
        return ("invalid_signature", e.index)
    except validation.NotEnoughPowerError:
        return ("not_enough_power",)
    except validation.CommitVerificationError:
        return ("invalid_commit",)
    return ("accepted",)


def reference_commit_verdict(pair: Pair, block_id, height, commit) -> tuple:
    got = reference.verify_commit(
        CHAIN, pair.plain_vals, plain_block_id(block_id), height,
        None if commit is None else _plain_commit(commit))
    return got[:1] if got[0] == "invalid_commit" else got


@pytest.mark.parametrize("n", SIZES)
def test_the_commit_made_of_verified_votes_is_all_hits(device_stub, n):
    """The LastCommit of the precommits just verified: ABSENT skipped, NIL
    verified and not tallied, and no signature reaches the device again."""
    absent, nil = {4: ((3,), ()), 7: ((0,), (2,)), 16: ((0, 15), (2,))}[n]
    pair = _filled(n, absent, nil)
    commit = pair.program.make_commit()
    assert _plain_commit(commit) == pair.reference.make_commit()
    flags = [s.block_id_flag for s in commit.signatures]
    assert [i for i, f in enumerate(flags) if f == reference.FLAG_ABSENT] == list(absent)
    assert [i for i, f in enumerate(flags) if f == reference.FLAG_NIL] == list(nil)
    before = dispatch_stats.snapshot()["dispatches"], sstats.snapshot()["flush_items"]
    tracing.reset_tracer()
    assert commit_verdict(pair, BLOCK, HEIGHT, commit) == ("accepted",) == \
        reference_commit_verdict(pair, BLOCK, HEIGHT, commit)
    assert before == (dispatch_stats.snapshot()["dispatches"],
                      sstats.snapshot()["flush_items"])
    spans = {s["stage"]: s.get("attrs", {}) for s in tracing.get_tracer().tail(16)}
    assert spans["verify.commit"]["mode"] == "full"
    assert spans["verify.commit"]["entries"] == n - len(absent)
    if n - len(absent) >= 2:  # one signature is not batched
        assert spans["batch.verify"]["hits"] == n - len(absent)


COMMIT_FAULTS = ("wrong_signature_for_the_block", "wrong_signature_for_nil",
                 "not_enough_power", "another_height", "another_block_id",
                 "another_size", "address_of_another_validator", "nil_commit")


@pytest.mark.parametrize("fault", COMMIT_FAULTS)
@pytest.mark.parametrize("n", SIZES)
def test_verify_commit_answers_as_the_reference(device_stub, n, fault):
    nil = (1, 2) if n == 16 else (1,)
    pair = _filled(n, absent=(0,) if n > 4 else (), nil=nil)
    commit = pair.program.make_commit()
    block_id, height, want = BLOCK, HEIGHT, ("invalid_commit",)
    if fault.startswith("wrong_signature"):
        index = nil[-1] if fault.endswith("nil") else n - 1
        s = commit.signatures[index].signature
        commit.signatures[index].signature = s[:32] + bytes([s[32] ^ 1]) + s[33:]
        want = ("invalid_signature", index)
    elif fault == "not_enough_power":
        pair = _filled(n, nil=tuple(range(n - pair.quorum + 1)), seed=1)
        commit = _commit_of(pair, BLOCK)
        want = ("not_enough_power",)
    elif fault == "another_height":
        height += 1
    elif fault == "another_block_id":
        block_id = OTHER
    elif fault == "another_size":
        commit.signatures.pop()
    elif fault == "address_of_another_validator":
        commit.signatures[n - 1].validator_address = pair.vals.validators[1].address
    elif fault == "nil_commit":
        commit = None
    assert commit_verdict(pair, block_id, height, commit) == want == \
        reference_commit_verdict(pair, block_id, height, commit)


def _commit_of(pair: Pair, block_id: BlockID):
    """A commit for ``block_id`` of whatever the set holds (``make_commit``
    refuses without the majority)."""
    from cometbft_tpu.types.block import Commit
    from cometbft_tpu.types.vote import CommitSig

    return Commit(HEIGHT, 0, block_id, [
        CommitSig.absent_sig() if v is None else CommitSig.from_vote(v)
        for v in pair.program.votes])


# -- the reference itself -----------------------------------------------------------


@pytest.mark.parametrize("n", SIZES)
def test_reference_sign_bytes_and_addresses_are_the_programs(n):
    pair = Pair(n)
    for index, block_id, type_ in ((0, BLOCK, PREVOTE_TYPE), (n - 1, NIL, PRECOMMIT_TYPE),
                                   (1, OTHER, PRECOMMIT_TYPE)):
        vote = pair.vote(index, block_id, type_=type_)
        assert reference.vote_sign_bytes(CHAIN, plain_vote(vote)) == vote.sign_bytes(CHAIN)
        assert reference.address(pair.plain_vals[index][0]) == vote.validator_address
    whole = Vote(PRECOMMIT_TYPE, HEIGHT, 0, BLOCK, Timestamp.from_ns(BASE_NS),
                 pair.vals.validators[0].address, 0)  # zero nanos are left out
    assert reference.vote_sign_bytes(CHAIN, plain_vote(whole)) == whole.sign_bytes(CHAIN)


def test_reference_imports_nothing_of_the_device_path():
    src = open(reference.__file__).read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", src, flags=re.M)
    assert sorted(set(imports)) == [
        "__future__", "cometbft_tpu.crypto", "hashlib", "typing"]


def test_benchmarks_copy_is_the_reference():
    """``benchmarks/voteset_ref.py`` differs in its one import line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    copy = open(os.path.join(root, "benchmarks", "voteset_ref.py")).read()
    mine = open(reference.__file__).read()
    assert copy == mine.replace(
        "from cometbft_tpu.crypto import ed25519_ref as _ed",
        "from benchmarks import ed25519_ref as _ed")
    assert copy != mine
