"""VoteSet: 2/3-majority tallying for one (height, round, type)
(reference: types/vote_set.go:169-243).

Tracks votes by validator index, per-block tallies, and conflicting votes
(equivocation evidence).  A vote set "has 2/3 majority" for a block once the
voting power of votes for that exact BlockID exceeds 2/3 of the total.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from cometbft_tpu.libs import tracing
from cometbft_tpu.types.basic import BlockID
from cometbft_tpu.types.vote import CommitSig, Vote
from cometbft_tpu.types.validator import ValidatorSet


class VoteError(Exception):
    """A vote the set refuses.  Each of ``VoteSet.AddVote``'s errors
    (reference: types/vote_set.go:169, types/errors.go) is a class of its
    own; ``outcome`` names it as ``types/voteset_reference.py`` does, for the
    ``voteset.add`` span and for a caller that has to tell them apart."""

    outcome = "error"


class NilVoteError(VoteError):
    outcome = "nil_vote"


class InvalidValidatorIndexError(VoteError):
    outcome = "invalid_validator_index"


class InvalidValidatorAddressError(VoteError):
    outcome = "invalid_validator_address"


class UnexpectedStepError(VoteError):
    outcome = "unexpected_step"


class NonDeterministicSignatureError(VoteError):
    """A copy of a held vote (same validator, same block id) under ANOTHER
    signature (ErrVoteNonDeterministicSignature).  Never verified, never
    added."""

    outcome = "nondeterministic_signature"


class VoteSignatureError(VoteError):
    """``Vote.verify`` failed (ErrVoteInvalidSignature); never added."""

    outcome = "invalid_signature"


class ConflictingVoteError(VoteError):
    """Equivocation: same validator, same (H,R,type), different block; the
    new vote's signature has been verified.  ``added`` says whether the set
    took the vote all the same (a peer claims +2/3 for its block): Go
    returns ``true`` AND the error there."""

    outcome = "conflicting"

    def __init__(self, existing: Vote, conflicting: Vote, added: bool = False):
        super().__init__("conflicting votes from validator")
        self.existing = existing
        self.conflicting = conflicting
        self.added = added


@dataclass
class _BlockVotes:
    peer_maj23: bool = False
    votes: dict[int, Vote] = field(default_factory=dict)
    sum: int = 0


class VoteSet:
    def __init__(
        self,
        chain_id: str,
        height: int,
        round_: int,
        type_: int,
        val_set: ValidatorSet,
    ):
        self.chain_id = chain_id
        self.height = height
        self.round_ = round_
        self.type_ = type_
        self.val_set = val_set
        self.votes: list[Optional[Vote]] = [None] * len(val_set)
        self.sum = 0
        self.maj23: Optional[BlockID] = None
        self.votes_by_block: dict[bytes, _BlockVotes] = {}
        self.peer_maj23s: dict[str, BlockID] = {}

    def size(self) -> int:
        return len(self.val_set)

    # -- adding votes -----------------------------------------------------

    def add_vote(self, vote: Vote, verify: bool = True) -> bool:
        """Returns True if the vote was added, False for a byte-identical
        copy of a held vote.  Raises a ``VoteError`` of the check's own
        class for a vote the set refuses, ``ConflictingVoteError`` on
        equivocation (``.added`` where the vote went in under a peer's
        maj23 claim).  ``Vote.validate_basic`` is the wire's to run
        (``consensus/reactor``), as in the reference.  Span ``voteset.add``
        (``t``, ``outcome``) is the set's own work around ``Vote.verify``'s
        ``consensus.vote``."""
        with tracing.span("voteset.add", t=self.type_) as sp:
            try:
                added = self._add_vote(vote, verify)
            except VoteError as e:
                sp.set(outcome=e.outcome)
                raise
            sp.set(outcome="added" if added else "duplicate")
            return added

    def _add_vote(self, vote: Vote, verify: bool) -> bool:
        """vote_set.go:169 addVote, check by check in its order."""
        if vote is None:
            raise NilVoteError("nil vote")
        idx = vote.validator_index
        if idx < 0:
            raise InvalidValidatorIndexError("index < 0")
        if not vote.validator_address:
            raise InvalidValidatorAddressError("empty address")
        if (
            vote.height != self.height
            or vote.round_ != self.round_
            or vote.type_ != self.type_
        ):
            raise UnexpectedStepError(
                f"vote (H,R,T)=({vote.height},{vote.round_},{vote.type_}) "
                f"does not match set ({self.height},{self.round_},{self.type_})"
            )
        val = self.val_set.get_by_index(idx)
        if val is None:
            raise InvalidValidatorIndexError(
                f"cannot find validator {idx} in a set of {len(self.val_set)}"
            )
        if val.address != vote.validator_address:
            raise InvalidValidatorAddressError(
                "validator address does not match index"
            )

        key = vote.block_id.key()
        held = self._get_vote(idx, key)
        if held is not None:
            if held.signature == vote.signature:
                return False  # duplicate
            raise NonDeterministicSignatureError(
                f"existing vote: {held}; new vote: {vote}"
            )

        # Verify the signature BEFORE any conflict handling, so a forged vote
        # cannot frame an honest validator for equivocation (reference:
        # vote_set.go verifies in addVote before addVerifiedVote).
        if verify and not vote.verify(self.chain_id, val.pub_key):
            raise VoteSignatureError("invalid signature")

        added, conflicting = self._add_verified(vote, key, val.voting_power)
        if conflicting is not None:
            raise ConflictingVoteError(conflicting, vote, added)
        return True

    def _get_vote(self, idx: int, key: bytes) -> Optional[Vote]:
        """The held vote of validator ``idx`` for the block ``key``: the
        set's own, or one kept with its block only (a conflicting vote
        admitted under a peer's claim)."""
        held = self.votes[idx]
        if held is not None and held.block_id.key() == key:
            return held
        bv = self.votes_by_block.get(key)
        return bv.votes.get(idx) if bv is not None else None

    def _add_verified(self, vote: Vote, key: bytes, power: int):
        """vote_set.go:243 addVerifiedVote: (added, the conflicting vote).
        The signature is valid; the vote is no copy of a held one."""
        idx = vote.validator_index
        conflicting = self.votes[idx]
        if conflicting is None:
            self.votes[idx] = vote
            self.sum += power
        elif self.maj23 is not None and self.maj23.key() == key:
            self.votes[idx] = vote  # the majority's block replaces the other

        bv = self.votes_by_block.get(key)
        if bv is None:
            if conflicting is not None:
                return False, conflicting  # a block nobody tracks: forget it
            bv = self.votes_by_block[key] = _BlockVotes()
        elif conflicting is not None and not bv.peer_maj23:
            return False, conflicting  # no peer says this block is special

        before = bv.sum
        quorum = self.val_set.total_voting_power() * 2 // 3 + 1
        if idx not in bv.votes:
            bv.votes[idx] = vote
            bv.sum += power
        if before < quorum <= bv.sum and self.maj23 is None:
            # only the first quorum reached counts; its votes are THE votes
            self.maj23 = vote.block_id
            for i, v in bv.votes.items():
                self.votes[i] = v
        return True, conflicting

    # -- queries ----------------------------------------------------------

    def two_thirds_majority(self) -> Optional[BlockID]:
        return self.maj23

    def has_two_thirds_majority(self) -> bool:
        return self.maj23 is not None

    def has_two_thirds_any(self) -> bool:
        # integer arithmetic: voting powers can exceed float's 2^53 range
        return self.sum * 3 > self.val_set.total_voting_power() * 2

    def has_all(self) -> bool:
        return self.sum == self.val_set.total_voting_power()

    def get_by_index(self, idx: int) -> Optional[Vote]:
        if 0 <= idx < len(self.votes):
            return self.votes[idx]
        return None

    def get_by_address(self, address: bytes) -> Optional[Vote]:
        found = self.val_set.get_by_address(address)
        if found is None:
            return None
        return self.votes[found[0]]

    def bit_array(self) -> list[bool]:
        return [v is not None for v in self.votes]

    def bit_array_by_block_id(self, block_id: BlockID) -> list[bool]:
        bv = self.votes_by_block.get(block_id.key())
        out = [False] * len(self.votes)
        if bv:
            for i in bv.votes:
                out[i] = True
        return out

    def set_peer_maj23(self, peer_id: str, block_id: BlockID) -> None:
        """A peer claims 2/3 majority for block_id (reference:
        vote_set.go SetPeerMaj23)."""
        if peer_id in self.peer_maj23s:
            return
        self.peer_maj23s[peer_id] = block_id
        bv = self.votes_by_block.get(block_id.key())
        if bv is None:
            bv = _BlockVotes(peer_maj23=True)
            self.votes_by_block[block_id.key()] = bv
        else:
            bv.peer_maj23 = True

    # -- commit construction ---------------------------------------------

    def make_commit(self) -> "Commit":
        from cometbft_tpu.types.block import Commit

        if self.maj23 is None or self.maj23.is_zero():
            raise VoteError("cannot make commit: no 2/3 majority for a block")
        sigs = []
        for vote in self.votes:
            if vote is None:
                sigs.append(CommitSig.absent_sig())
                continue
            cs = CommitSig.from_vote(vote)
            # A precommit for a *different* block cannot be represented in a
            # Commit; record it as absent (reference: vote_set.go MakeCommit).
            if cs.for_block() and vote.block_id != self.maj23:
                cs = CommitSig.absent_sig()
            sigs.append(cs)
        return Commit(
            height=self.height,
            round_=self.round_,
            block_id=self.maj23,
            signatures=sigs,
        )

    def make_extended_commit(self) -> "ExtendedCommit":
        """Like ``make_commit`` but retaining each precommit's vote
        extension (reference: vote_set.go MakeExtendedCommit)."""
        from cometbft_tpu.types.block import ExtendedCommit
        from cometbft_tpu.types.vote import ExtendedCommitSig

        if self.maj23 is None or self.maj23.is_zero():
            raise VoteError("cannot make commit: no 2/3 majority for a block")
        sigs = []
        for vote in self.votes:
            if vote is None:
                sigs.append(ExtendedCommitSig.absent_ext_sig())
                continue
            cs = ExtendedCommitSig.from_extended_vote(vote)
            if cs.for_block() and vote.block_id != self.maj23:
                cs = ExtendedCommitSig.absent_ext_sig()
            sigs.append(cs)
        return ExtendedCommit(
            height=self.height,
            round_=self.round_,
            block_id=self.maj23,
            extended_signatures=sigs,
        )
