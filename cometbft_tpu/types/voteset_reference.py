"""The plain reference of the validator's receive path: ``VoteSet.AddVote`` /
``addVerifiedVote`` / ``MakeCommit`` (reference: types/vote_set.go:169-330,
:628), ``Vote.Verify`` (types/vote.go:227) and ``VerifyCommit``
(types/validation.go:28) over plain records.

Straightforward Python: its own CanonicalVote encoder, dictionaries and
lists, one ``verify_sig(pub, msg, sig)`` a signature, handed in from outside
(the plain ``verify_zip215`` by default).  No signature cache, no batching,
no scheduler, no device, no spans: it imports nothing of them, and nothing
of jax.  The tests (``tests/test_voteset_reference.py``) hold
``types/vote_set.py``, ``types/vote.py`` and ``types/validation.verify_commit``
to it over every class of vote and every error branch;
``benchmarks/voteset_ref.py`` is a copy that differs in the one import line
below.

A verdict is a tuple.  Of ``VoteSet.add_vote``: ``("added", maj23)`` with
``maj23`` whether, AFTER this call, the set holds its +2/3 majority for this
vote's block id; ``("duplicate",)`` (Go: ``false, nil``);
``("conflicting", index)`` (ErrVoteConflictingVotes, the vote not added) and
``("conflicting_added", index)`` (the same error, the vote added under a
peer's maj23 claim: Go returns ``true`` AND the error); and one class an
error: ``("nil_vote",)``, ``("invalid_validator_index",)``,
``("invalid_validator_address",)``, ``("unexpected_step",)``,
``("nondeterministic_signature",)``, ``("invalid_signature",)``.  Of
``verify_commit``: ``("accepted",)``, ``("invalid_signature", i)`` with the
commit index of the first wrong signature, ``("not_enough_power",)`` and
``("invalid_commit", why)``; ``why`` is for the reader.

What holding the program to this found (PR 32), and what became of it:

  repaired in ``types/vote_set.py`` / ``types/vote.py`` / ``consensus``:
  * a vote already held for the same block id answered ``False`` whatever
    its signature; Go answers ``false, nil`` for the SAME signature only and
    ErrVoteNonDeterministicSignature for another;
  * the look for a held vote read ``votes[index]`` only; Go's ``getVote``
    also reads the block's own votes, where a conflicting vote admitted
    under a peer's claim lives;
  * every error was one ``VoteError`` told apart by its text; each is a
    class of its own now (``VoteError.outcome`` carries the names above);
  * ``Vote.validate_basic`` ran first, inside ``add_vote``, and so answered
    before the set's own checks with other errors (a negative index, an
    address of another size, a signature too long); Go makes that check at
    the wire (``VoteMessage.ValidateBasic`` in the reactor's ``Receive``),
    which is where ``consensus/reactor.py`` makes it now;
  * a conflicting vote admitted under a peer's maj23 claim was added and
    NOT reported; Go adds it and returns the conflict all the same
    (``ConflictingVoteError.added``; ``consensus/state._add_vote`` reports
    the evidence and goes on with the vote);
  * such a vote replaced ``votes[index]`` at once; Go replaces it only once
    that block IS the set's maj23, and copies the block's votes over
    ``votes`` at the call that first reaches the quorum;
  * ``Vote.verify`` did not compare the key's address with the vote's
    (vote.go:228); inside the set the index check has made that comparison
    already, so it shows only to a direct caller;
  * ``HeightVoteSet.add_vote`` sent a vote of no valid type to the
    precommits; Go answers ``false, nil`` before it looks for a set.

  kept, each on purpose:
  * errors are verdict tuples here and exceptions in the program;
  * vote extensions are not this module's: the program checks them in
    ``consensus/state._check_vote_extension`` BEFORE the vote reaches the
    set (Go: ``VerifyVoteAndExtension`` inside ``addVote``, after the
    duplicate check), and a set with extensions off does not look for
    extension bytes;
  * ``set_peer_maj23`` answers nothing: Go returns an error when one peer
    claims two block ids, the program and this reference keep the first;
  * ``HeightVoteSet.add_vote`` answers ``False`` for a peer's third
    catch-up round where Go answers ErrGotVoteFromUnwantedRound;
  * ``verify_commit`` holds each entry's address to the validator at its
    index, as the program does; Go reads the key by index and never looks
    at the address, which the signature does not cover;
  * ``verify_commit`` tallies BEFORE it checks the signatures, as Go's
    ``verifyCommitBatch`` does; the program checks the signatures it
    collected first (``ROADMAP.md`` M9).  The verdicts differ only for a
    commit that both lacks the power and carries a wrong signature;
  * the program runs ``Commit.validate_basic`` inside ``verify_commit``;
    Go's callers have run it before.
"""

from __future__ import annotations

import hashlib
from typing import Callable, NamedTuple, Optional

from cometbft_tpu.crypto import ed25519_ref as _ed

PREVOTE, PRECOMMIT = 1, 2
FLAG_ABSENT, FLAG_COMMIT, FLAG_NIL = 1, 2, 3
ADDRESS_LEN = 20


class BlockID(NamedTuple):
    hash: bytes = b""
    parts_total: int = 0
    parts_hash: bytes = b""


NIL = BlockID()


class Vote(NamedTuple):
    type: int
    height: int
    round: int
    block_id: BlockID  # ``NIL``: a vote for nil
    time_ns: int
    address: bytes
    index: int
    signature: bytes


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


VerifySig = Callable[[bytes, bytes, bytes], bool]


# -- proto3, from its definition ---------------------------------------------------


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


def address(pub: bytes) -> bytes:
    return hashlib.sha256(pub).digest()[:ADDRESS_LEN]


def sign_bytes(chain_id: str, type_: int, height: int, round_: int,
               block_id: BlockID, time_ns: int) -> bytes:
    """CanonicalVote, length-delimited; a vote for nil has no block id."""
    seconds, nanos = divmod(time_ns, 1_000_000_000)
    psh = _varint(1, block_id.parts_total) + _bytes(2, block_id.parts_hash)
    body = (
        _varint(1, type_)
        + _sfixed64(2, height)
        + _sfixed64(3, round_)
        + (_bytes(4, _bytes(1, block_id.hash) + _bytes(2, psh))
           if block_id != NIL else b"")
        + _bytes(5, _varint(1, seconds) + _varint(2, nanos))
        + _bytes(6, chain_id.encode())
    )
    return _uvarint(len(body)) + body


def vote_sign_bytes(chain_id: str, vote: Vote) -> bytes:
    return sign_bytes(chain_id, vote.type, vote.height, vote.round,
                      vote.block_id, vote.time_ns)


def commit_sign_bytes(chain_id: str, commit: Commit, index: int) -> bytes:
    """The precommit that entry ``index`` of the commit was made from."""
    cs = commit.sigs[index]
    return sign_bytes(chain_id, PRECOMMIT, commit.height, commit.round,
                      commit.block_id if cs.flag == FLAG_COMMIT else NIL, cs.time_ns)


# -- types/vote.go:227 -------------------------------------------------------------


def vote_verify(chain_id: str, pub: bytes, vote: Vote,
                verify_sig: VerifySig = _ed.verify_zip215) -> tuple:
    if address(pub) != vote.address:
        return ("invalid_validator_address",)
    if not verify_sig(pub, vote_sign_bytes(chain_id, vote), vote.signature):
        return ("invalid_signature",)
    return ("ok",)


# -- types/vote_set.go -------------------------------------------------------------


class _BlockVotes:
    """blockVotes: the votes for one block id, by index, and their power."""

    def __init__(self, peer_maj23: bool, size: int):
        self.peer_maj23 = peer_maj23
        self.votes: "list[Optional[Vote]]" = [None] * size
        self.sum = 0


class VoteSet:
    """The votes of one (height, round, type).  ``validators`` are (public
    key, power) in the set's order."""

    def __init__(self, chain_id: str, height: int, round_: int, type_: int,
                 validators: "list[tuple[bytes, int]]",
                 verify_sig: VerifySig = _ed.verify_zip215):
        self.chain_id = chain_id
        self.height, self.round, self.type = height, round_, type_
        self.validators = validators
        self.verify_sig = verify_sig
        self.votes: "list[Optional[Vote]]" = [None] * len(validators)
        self.sum = 0
        self.maj23: Optional[BlockID] = None
        self.votes_by_block: "dict[BlockID, _BlockVotes]" = {}
        self.peer_maj23s: "dict[str, BlockID]" = {}

    def total_power(self) -> int:
        return sum(power for _, power in self.validators)

    def _get_vote(self, index: int, block_id: BlockID) -> Optional[Vote]:
        held = self.votes[index]
        if held is not None and held.block_id == block_id:
            return held
        by_block = self.votes_by_block.get(block_id)
        return by_block.votes[index] if by_block is not None else None

    def add_vote(self, vote: Optional[Vote]) -> tuple:
        """vote_set.go:169 addVote, check by check in its order."""
        if vote is None:
            return ("nil_vote",)
        if vote.index < 0:
            return ("invalid_validator_index",)
        if not vote.address:
            return ("invalid_validator_address",)
        if (vote.height, vote.round, vote.type) != (self.height, self.round, self.type):
            return ("unexpected_step",)
        if vote.index >= len(self.validators):
            return ("invalid_validator_index",)
        pub, power = self.validators[vote.index]
        if vote.address != address(pub):
            return ("invalid_validator_address",)
        held = self._get_vote(vote.index, vote.block_id)
        if held is not None:
            if held.signature == vote.signature:
                return ("duplicate",)
            return ("nondeterministic_signature",)
        # the signature BEFORE any conflict handling: a forged vote cannot
        # frame a validator for equivocation
        bad = vote_verify(self.chain_id, pub, vote, self.verify_sig)
        if bad != ("ok",):
            return bad
        added, conflicting = self._add_verified(vote, power)
        if conflicting is not None:
            return ("conflicting_added" if added else "conflicting", vote.index)
        return ("added", self.maj23 is not None and self.maj23 == vote.block_id)

    def _add_verified(self, vote: Vote, power: int):
        """vote_set.go:243 addVerifiedVote: (added, the conflicting vote)."""
        index, conflicting = vote.index, None
        held = self.votes[index]
        if held is not None:
            conflicting = held
            # replace the held vote only if this block IS the majority's
            if self.maj23 is not None and self.maj23 == vote.block_id:
                self.votes[index] = vote
        else:
            self.votes[index] = vote
            self.sum += power
        by_block = self.votes_by_block.get(vote.block_id)
        if by_block is not None:
            if conflicting is not None and not by_block.peer_maj23:
                return False, conflicting  # no peer says this block is special
        else:
            if conflicting is not None:
                return False, conflicting  # a block nobody tracks: forget it
            by_block = _BlockVotes(False, len(self.validators))
            self.votes_by_block[vote.block_id] = by_block
        before = by_block.sum
        quorum = self.total_power() * 2 // 3 + 1
        if by_block.votes[index] is None:
            by_block.votes[index] = vote
            by_block.sum += power
        if before < quorum <= by_block.sum and self.maj23 is None:
            # only the first quorum reached counts; its votes are THE votes
            self.maj23 = vote.block_id
            for i, v in enumerate(by_block.votes):
                if v is not None:
                    self.votes[i] = v
        return True, conflicting

    def set_peer_maj23(self, peer_id: str, block_id: BlockID) -> None:
        """vote_set.go:310 SetPeerMaj23; a peer's second claim is ignored."""
        if peer_id in self.peer_maj23s:
            return
        self.peer_maj23s[peer_id] = block_id
        by_block = self.votes_by_block.get(block_id)
        if by_block is None:
            self.votes_by_block[block_id] = _BlockVotes(True, len(self.validators))
        else:
            by_block.peer_maj23 = True

    def make_commit(self) -> Commit:
        """vote_set.go:628 MakeCommit: a precommit for another block than
        the majority's is left out as ABSENT."""
        if self.type != PRECOMMIT or self.maj23 is None or self.maj23 == NIL:
            raise ValueError("no +2/3 majority of precommits for a block")
        sigs = []
        for vote in self.votes:
            if vote is None or (vote.block_id != NIL and vote.block_id != self.maj23):
                sigs.append(CommitSig(FLAG_ABSENT, b"", 0, b""))
            else:
                flag = FLAG_NIL if vote.block_id == NIL else FLAG_COMMIT
                sigs.append(CommitSig(flag, vote.address, vote.time_ns, vote.signature))
        return Commit(self.height, self.round, self.maj23, sigs)


# -- types/validation.go:28 --------------------------------------------------------


def verify_commit(chain_id: str, validators: "list[tuple[bytes, int]]",
                  block_id: BlockID, height: int, commit: Optional[Commit],
                  verify_sig: VerifySig = _ed.verify_zip215) -> tuple:
    """VerifyCommit: ABSENT entries skipped, every other signature checked
    (NIL ones too), COMMIT flags alone tallied, more than 2/3 needed."""
    if not validators:
        return ("invalid_commit", "empty validator set")
    if commit is None:
        return ("invalid_commit", "nil commit")
    if len(validators) != len(commit.sigs):
        return ("invalid_commit", "commit size differs from the set's")
    if height != commit.height:
        return ("invalid_commit", "commit height")
    if block_id != commit.block_id:
        return ("invalid_commit", "commit is for another block id")
    needed = sum(power for _, power in validators) * 2 // 3
    tallied, picked = 0, []
    for index, cs in enumerate(commit.sigs):
        if cs.flag == FLAG_ABSENT:
            continue
        pub, power = validators[index]
        if cs.address != address(pub):
            return ("invalid_commit", f"address mismatch at {index}")
        picked.append((index, pub))
        if cs.flag == FLAG_COMMIT:
            tallied += power
    if tallied <= needed:
        return ("not_enough_power",)
    for index, pub in picked:
        msg = commit_sign_bytes(chain_id, commit, index)
        if not verify_sig(pub, msg, commit.sigs[index].signature):
            return ("invalid_signature", index)
    return ("accepted",)
