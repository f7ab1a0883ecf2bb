"""BLS12-381 in the batch-verification seam: RLC aggregate verification,
attribution fallback, and a bls validator-set commit verified end-to-end
through the same ``verify_commit`` path ed25519 uses.

Reference behavior: crypto/bls12381/key_bls12381.go:160-188 (verification
semantics) + types/validation.go:220-324 (the commit seam); the RLC batch
trick itself matches the reference's ed25519 batching strategy
(crypto/ed25519/ed25519.go:189-222) transplanted to pairings.
"""

import hashlib

import pytest

from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import bls12381 as bls
from cometbft_tpu.crypto.keys import Bls12381PrivKey
from cometbft_tpu.types.basic import (
    PRECOMMIT_TYPE,
    BlockID,
    PartSetHeader,
    Timestamp,
)
from cometbft_tpu.types.validation import verify_commit, verify_commit_light
from cometbft_tpu.types.validator import Validator, ValidatorSet
from cometbft_tpu.types.vote import Vote
from cometbft_tpu.types.vote_set import VoteSet

CHAIN_ID = "bls-chain"


def _mk_bls_validators(n, power=10):
    privs = [
        Bls12381PrivKey.from_secret(b"bls-val-%d" % i) for i in range(n)
    ]
    vals = ValidatorSet([Validator(p.pub_key(), power) for p in privs])
    return privs, vals


def _triples(n, tamper=()):
    privs = [Bls12381PrivKey.from_secret(b"t-%d" % i) for i in range(n)]
    pubs = [p.pub_key().bytes() for p in privs]
    msgs = [b"bls batch message %d" % i for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    for i in tamper:
        sigs[i] = sigs[i][:-1] + bytes([sigs[i][-1] ^ 1])
    return pubs, msgs, sigs


class TestBlsBatchVerifier:
    def test_seam_routes_bls(self):
        priv = Bls12381PrivKey.from_secret(b"route")
        assert cbatch.supports_batch_verifier(priv.pub_key())
        bv = cbatch.create_batch_verifier(priv.pub_key())
        assert isinstance(bv, cbatch.BlsBatchVerifier)

    def test_all_valid(self):
        pubs, msgs, sigs = _triples(4)
        bv = cbatch.BlsBatchVerifier()
        for p, m, s in zip(pubs, msgs, sigs):
            bv.add(p, m, s)
        ok, bits = bv.verify()
        assert ok and bits == [True] * 4

    def test_attribution_on_tamper(self):
        pubs, msgs, sigs = _triples(4, tamper=(2,))
        bv = cbatch.BlsBatchVerifier()
        for p, m, s in zip(pubs, msgs, sigs):
            bv.add(p, m, s)
        ok, bits = bv.verify()
        assert not ok
        assert bits == [True, True, False, True]

    def test_malformed_inputs_rejected_individually(self):
        pubs, msgs, sigs = _triples(3)
        bv = cbatch.BlsBatchVerifier()
        bv.add(pubs[0][:40], msgs[0], sigs[0])  # short pubkey
        bv.add(pubs[1], msgs[1], sigs[1][:40])  # short signature
        bv.add(pubs[2], msgs[2], sigs[2])  # valid
        ok, bits = bv.verify()
        assert not ok
        assert bits == [False, False, True]

    def test_single_entry_path(self):
        pubs, msgs, sigs = _triples(1)
        bv = cbatch.BlsBatchVerifier()
        bv.add(pubs[0], msgs[0], sigs[0])
        ok, bits = bv.verify()
        assert ok and bits == [True]

    def test_repeated_message_is_fine(self):
        """RLC has no distinct-message requirement (unlike the basic-scheme
        aggregate_verify)."""
        privs = [Bls12381PrivKey.from_secret(b"r-%d" % i) for i in range(2)]
        msg = b"same message"
        bv = cbatch.BlsBatchVerifier()
        for p in privs:
            bv.add(p.pub_key().bytes(), msg, p.sign(msg))
        ok, bits = bv.verify()
        assert ok and bits == [True, True]


class TestMixedKeySets:
    def test_mixed_set_falls_back_to_per_signature(self):
        """A validator set mixing ed25519 and bls12_381 must NOT take the
        batch path (one batch verifier handles one key type) — the commit
        still verifies, per-signature."""
        from cometbft_tpu.crypto.keys import Ed25519PrivKey
        from cometbft_tpu.types import validation as tv

        bls_privs = [Bls12381PrivKey.from_secret(b"mx-%d" % i) for i in range(2)]
        ed_privs = [
            Ed25519PrivKey.from_seed(hashlib.sha256(b"mx-ed-%d" % i).digest())
            for i in range(2)
        ]
        privs = bls_privs + ed_privs
        vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
        bid = BlockID(
            hash=hashlib.sha256(b"mixed block").digest(),
            part_set_header=PartSetHeader(
                total=1, hash=hashlib.sha256(b"p").digest()
            ),
        )
        vs = VoteSet(CHAIN_ID, 3, 0, PRECOMMIT_TYPE, vals)
        for priv in privs:
            addr = priv.pub_key().address()
            idx = vals.get_by_address(addr)[0]
            vote = Vote(
                type_=PRECOMMIT_TYPE,
                height=3,
                round_=0,
                block_id=bid,
                timestamp=Timestamp(1700000000, 42),
                validator_address=addr,
                validator_index=idx,
            )
            vote.signature = priv.sign(vote.sign_bytes(CHAIN_ID))
            assert vs.add_vote(vote)
        commit = vs.make_commit()
        assert not tv._should_batch(vals, len(commit.signatures))
        verify_commit(CHAIN_ID, vals, bid, 3, commit)

    def test_cpu_backend_pins_bls_to_host(self):
        bv = cbatch.create_batch_verifier(
            Bls12381PrivKey.from_secret(b"ks").pub_key(), backend="cpu"
        )
        assert isinstance(bv, cbatch.BlsBatchVerifier)
        assert bv._backend == "cpu"


class TestBlsCommitVerify:
    def test_commit_roundtrip(self):
        privs, vals = _mk_bls_validators(4)
        bid = BlockID(
            hash=hashlib.sha256(b"bls block").digest(),
            part_set_header=PartSetHeader(
                total=1, hash=hashlib.sha256(b"p").digest()
            ),
        )
        vs = VoteSet(CHAIN_ID, 3, 0, PRECOMMIT_TYPE, vals)
        for priv in privs:
            addr = priv.pub_key().address()
            idx = vals.get_by_address(addr)[0]
            vote = Vote(
                type_=PRECOMMIT_TYPE,
                height=3,
                round_=0,
                block_id=bid,
                timestamp=Timestamp(1700000000, 42),
                validator_address=addr,
                validator_index=idx,
            )
            vote.signature = priv.sign(vote.sign_bytes(CHAIN_ID))
            assert vs.add_vote(vote)
        commit = vs.make_commit()
        verify_commit(CHAIN_ID, vals, bid, 3, commit)
        verify_commit_light(CHAIN_ID, vals, bid, 3, commit)

    def test_commit_bad_signature_raises(self):
        privs, vals = _mk_bls_validators(4)
        bid = BlockID(
            hash=hashlib.sha256(b"bls block").digest(),
            part_set_header=PartSetHeader(
                total=1, hash=hashlib.sha256(b"p").digest()
            ),
        )
        vs = VoteSet(CHAIN_ID, 3, 0, PRECOMMIT_TYPE, vals)
        for priv in privs:
            addr = priv.pub_key().address()
            idx = vals.get_by_address(addr)[0]
            vote = Vote(
                type_=PRECOMMIT_TYPE,
                height=3,
                round_=0,
                block_id=bid,
                timestamp=Timestamp(1700000000, 42),
                validator_address=addr,
                validator_index=idx,
            )
            vote.signature = priv.sign(vote.sign_bytes(CHAIN_ID))
            assert vs.add_vote(vote)
        commit = vs.make_commit()
        commit.signatures[1].signature = bytes(96)
        with pytest.raises(Exception):
            verify_commit(CHAIN_ID, vals, bid, 3, commit)
