"""The plain reference of light-client verification: ``verify`` /
``verify_adjacent`` / ``verify_non_adjacent`` over plain bytes (reference:
light/verifier.go:30,91,128; types/validation.go:63,129).

Straightforward Python: its own proto encoder, header and validator-set
encodings and RFC 6962 Merkle root (``hashlib``), its own by-address tally,
one ``verify_zip215`` a signature.  No signature cache, no batching, no
scheduler, no device: it imports nothing of them, and nothing of jax.  The
tests (``tests/test_light_reference.py``) hold ``light/verifier.py`` to it
over every request class; ``benchmarks/light_ref.py`` is a copy that differs
in the one import line below.

A verdict is a tuple: ``("accepted",)``, ``("invalid_signature", i)`` with
the COMMIT index of the first wrong signature, ``("cant_be_trusted",)``
(ErrNewValSetCantBeTrusted: bisect), ``("expired",)`` (ErrOldHeaderExpired),
``("invalid_header", why)`` (a header, time or hash-link check) and
``("invalid_commit", why)`` (any other commit check).  ``why`` is for the
reader; compare ``verdict[:1]`` there.

Departures from light/verifier.go and types/validation.go, each on purpose:

  * errors are verdict tuples, not wrapped error values (Go wraps the light
    pass's error in ErrInvalidHeader; the class here names the check);
  * header fields are encoded as ``types/block.py`` encodes them (chain id
    and height bare, where Go wraps them in StringValue / Int64Value): the
    hash links are what is under test, not the wire format;
  * equal ``max_clock_drift`` semantics as the program: a header is from the
    future when its time is AFTER now + drift (Go: not before);
  * the tally comes BEFORE the signatures are checked, as in Go's
    ``verifyCommitBatch``; the program checks the signatures it collected
    first.  The verdicts differ only for a commit that both lacks the power
    and carries a wrong signature, which answers ``invalid_signature`` there;
  * signatures whose flag is not COMMIT are skipped in both light passes, as
    Go's ``ignoreSig`` does; the program skips ABSENT ones only.
"""

from __future__ import annotations

import hashlib
from typing import Callable, NamedTuple

from benchmarks import ed25519_ref as _ed

FLAG_ABSENT, FLAG_COMMIT, FLAG_NIL = 1, 2, 3
PRECOMMIT = 2
ADDRESS_LEN = 20
MAX_CLOCK_DRIFT_S = 10.0


class BlockID(NamedTuple):
    hash: bytes
    parts_total: int
    parts_hash: bytes


class Header(NamedTuple):
    version_block: int
    version_app: int
    chain_id: str
    height: int
    time_ns: int
    last_block_id: BlockID
    last_commit_hash: bytes
    data_hash: bytes
    validators_hash: bytes
    next_validators_hash: bytes
    consensus_hash: bytes
    app_hash: bytes
    last_results_hash: bytes
    evidence_hash: bytes
    proposer_address: bytes


class CommitSig(NamedTuple):
    flag: int
    address: bytes
    time_ns: int
    signature: bytes


class Commit(NamedTuple):
    height: int
    round: int
    block_id: BlockID
    sigs: "list[CommitSig]"


class LightBlock(NamedTuple):
    header: Header
    commit: Commit
    validators: "list[tuple[bytes, int]]"  # (public key, power), set order


# -- proto3 and Merkle, from their definitions -------------------------------------


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _varint(field: int, value: int) -> bytes:
    return bytes([field << 3]) + _uvarint(value) if value else b""


def _sfixed64(field: int, value: int) -> bytes:
    return bytes([(field << 3) | 1]) + value.to_bytes(8, "little") if value else b""


def _bytes(field: int, value: bytes) -> bytes:
    return bytes([(field << 3) | 2]) + _uvarint(len(value)) + value if value else b""


def _timestamp(ns: int) -> bytes:
    seconds, nanos = divmod(ns, 1_000_000_000)
    return _varint(1, seconds) + _varint(2, nanos)


def _block_id(b: BlockID) -> bytes:
    psh = _varint(1, b.parts_total) + _bytes(2, b.parts_hash)
    return _bytes(1, b.hash) + _bytes(2, psh)


def merkle_root(items: "list[bytes]") -> bytes:
    """RFC 6962: leaves ``SHA-256(0x00 | item)``, inner nodes
    ``SHA-256(0x01 | left | right)``, split at the largest power of two
    below the count."""
    if not items:
        return hashlib.sha256(b"").digest()
    if len(items) == 1:
        return hashlib.sha256(b"\x00" + items[0]).digest()
    k = 1
    while k * 2 < len(items):
        k *= 2
    return hashlib.sha256(
        b"\x01" + merkle_root(items[:k]) + merkle_root(items[k:])
    ).digest()


def address(pub: bytes) -> bytes:
    return hashlib.sha256(pub).digest()[:ADDRESS_LEN]


def header_hash(h: Header) -> bytes:
    return merkle_root([
        _varint(1, h.version_block) + _varint(2, h.version_app),
        h.chain_id.encode(),
        _uvarint(h.height),
        _timestamp(h.time_ns),
        _block_id(h.last_block_id),
        h.last_commit_hash, h.data_hash,
        h.validators_hash, h.next_validators_hash,
        h.consensus_hash, h.app_hash, h.last_results_hash,
        h.evidence_hash, h.proposer_address,
    ])


def validators_hash(validators: "list[tuple[bytes, int]]") -> bytes:
    """Root over SimpleValidator{PublicKey{ed25519 = 1}, voting_power = 2}."""
    return merkle_root(
        [_bytes(1, _bytes(1, pub)) + _varint(2, power) for pub, power in validators]
    )


def vote_sign_bytes(chain_id: str, commit: Commit, index: int) -> bytes:
    """The CanonicalVote a validator signed as its precommit, delimited."""
    cs = commit.sigs[index]
    body = (
        _varint(1, PRECOMMIT)
        + _sfixed64(2, commit.height)
        + _sfixed64(3, commit.round)
        + (_bytes(4, _block_id(commit.block_id)) if cs.flag == FLAG_COMMIT else b"")
        + _bytes(5, _timestamp(cs.time_ns))
        + _bytes(6, chain_id.encode())
    )
    return _uvarint(len(body)) + body


# -- the two commit passes ---------------------------------------------------------

VerifySig = Callable[[bytes, bytes, bytes], bool]


def _check_signatures(chain_id, commit, picked, verify_sig):
    for index, pub in picked:
        msg = vote_sign_bytes(chain_id, commit, index)
        if not verify_sig(pub, msg, commit.sigs[index].signature):
            return ("invalid_signature", index)
    return None


def verify_commit_light(chain_id: str, validators, block_id: BlockID, height: int,
                        commit: Commit,
                        verify_sig: VerifySig = _ed.verify_zip215) -> tuple:
    """types/validation.go:63: by index against the commit's own set, stop
    past 2/3 of its power."""
    if len(validators) != len(commit.sigs):
        return ("invalid_commit", "commit size differs from the set's")
    if height != commit.height:
        return ("invalid_commit", "commit height")
    if block_id != commit.block_id:
        return ("invalid_commit", "commit is for another block id")
    needed = sum(p for _, p in validators) * 2 // 3
    tallied, picked = 0, []
    for index, cs in enumerate(commit.sigs):
        if cs.flag != FLAG_COMMIT:
            continue
        pub, power = validators[index]
        if cs.address != address(pub):
            return ("invalid_commit", f"address mismatch at {index}")
        picked.append((index, pub))
        tallied += power
        if tallied > needed:
            break
    if tallied <= needed:
        return ("invalid_commit", "not enough voting power signed")
    return _check_signatures(chain_id, commit, picked, verify_sig) or ("accepted",)


def verify_commit_light_trusting(chain_id: str, validators, commit: Commit,
                                 trust_num: int = 1, trust_den: int = 3,
                                 verify_sig: VerifySig = _ed.verify_zip215) -> tuple:
    """types/validation.go:129: by ADDRESS against a set that need not have
    signed the commit, each validator once, stop past the trust level."""
    if not commit.sigs:
        return ("invalid_commit", "empty commit")
    by_address = {address(pub): (pub, power) for pub, power in validators}
    needed = sum(p for _, p in validators) * trust_num // trust_den
    tallied, picked, seen = 0, [], set()
    for index, cs in enumerate(commit.sigs):
        if cs.flag != FLAG_COMMIT or cs.address not in by_address:
            continue
        if cs.address in seen:
            return ("invalid_commit", f"double vote from {cs.address.hex()}")
        seen.add(cs.address)
        pub, power = by_address[cs.address]
        picked.append((index, pub))
        tallied += power
        if tallied > needed:
            break
    if tallied <= needed:
        return ("cant_be_trusted",)
    return _check_signatures(chain_id, commit, picked, verify_sig) or ("accepted",)


# -- light/verifier.go -------------------------------------------------------------


def _expired(trusted: LightBlock, trusting_period_s: float, now_s: float) -> bool:
    return trusted.header.time_ns / 1e9 + trusting_period_s <= now_s


def _check_new_header_and_vals(chain_id, trusted, new, now_s, drift_s):
    """verifyNewHeaderAndVals, with SignedHeader.ValidateBasic's links."""
    h, c = new.header, new.commit
    if not h.chain_id or len(h.chain_id) > 50 or h.chain_id != chain_id:
        return ("invalid_header", "chain id")
    if h.proposer_address and len(h.proposer_address) != ADDRESS_LEN:
        return ("invalid_header", "proposer address")
    if c.height != h.height:
        return ("invalid_header", "commit height differs from the header's")
    if c.block_id.hash != header_hash(h):
        return ("invalid_header", "commit signs a different header")
    if h.height <= trusted.header.height:
        return ("invalid_header", "height not above the trusted one")
    if h.time_ns <= trusted.header.time_ns:
        return ("invalid_header", "time not after the trusted header's")
    if h.time_ns / 1e9 > now_s + drift_s:
        return ("invalid_header", "from the future")
    if h.validators_hash != validators_hash(new.validators):
        return ("invalid_header", "validator set does not match validators_hash")
    return None


def _light_pass(chain_id, new, verify_sig):
    return verify_commit_light(chain_id, new.validators, new.commit.block_id,
                               new.header.height, new.commit, verify_sig)


def verify_adjacent(chain_id: str, trusted: LightBlock, new: LightBlock,
                    trusting_period_s: float, now_s: float,
                    drift_s: float = MAX_CLOCK_DRIFT_S,
                    verify_sig: VerifySig = _ed.verify_zip215) -> tuple:
    """light/verifier.go:91."""
    if new.header.height != trusted.header.height + 1:
        return ("invalid_header", "headers must be adjacent in height")
    if _expired(trusted, trusting_period_s, now_s):
        return ("expired",)
    bad = _check_new_header_and_vals(chain_id, trusted, new, now_s, drift_s)
    if bad:
        return bad
    if new.header.validators_hash != trusted.header.next_validators_hash:
        return ("invalid_header", "validators_hash is not the trusted next_validators_hash")
    return _light_pass(chain_id, new, verify_sig)


def verify_non_adjacent(chain_id: str, trusted: LightBlock, new: LightBlock,
                        trusting_period_s: float, now_s: float,
                        trust_num: int = 1, trust_den: int = 3,
                        drift_s: float = MAX_CLOCK_DRIFT_S,
                        verify_sig: VerifySig = _ed.verify_zip215) -> tuple:
    """light/verifier.go:30: more than the trust level of the TRUSTED set
    signed the new header, and more than 2/3 of its own set."""
    if new.header.height == trusted.header.height + 1:
        return ("invalid_header", "headers must be non adjacent in height")
    if _expired(trusted, trusting_period_s, now_s):
        return ("expired",)
    bad = _check_new_header_and_vals(chain_id, trusted, new, now_s, drift_s)
    if bad:
        return bad
    got = verify_commit_light_trusting(
        chain_id, trusted.validators, new.commit, trust_num, trust_den, verify_sig
    )
    if got != ("accepted",):
        return got
    return _light_pass(chain_id, new, verify_sig)


def verify(chain_id: str, trusted: LightBlock, new: LightBlock,
           trusting_period_s: float, now_s: float,
           trust_num: int = 1, trust_den: int = 3,
           verify_sig: VerifySig = _ed.verify_zip215) -> tuple:
    """light/verifier.go:128."""
    if new.header.height == trusted.header.height + 1:
        return verify_adjacent(chain_id, trusted, new, trusting_period_s, now_s,
                               verify_sig=verify_sig)
    return verify_non_adjacent(chain_id, trusted, new, trusting_period_s, now_s,
                               trust_num, trust_den, verify_sig=verify_sig)
