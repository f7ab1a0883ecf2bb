"""Entry ``consensus_votes``: what a validator's receive routine does between
proposals (reference: internal/consensus/state.go:795 receiveRoutine, :2296
addVote).  A request is one call into the consensus entry, of two kinds:

  a vote         ``HeightVoteSet(chain_id, h, vals).add_vote(vote, peer_id)``
                 (-> ``VoteSet.add_vote`` -> ``Vote.verify``), one vote at a
                 time on the one thread the receive routine is;
  last_commit    once a height, after its last vote:
                 ``precommits(0).make_commit()`` (the program's own) and then
                 ``validation.verify_commit(chain_id, vals, block_id, h,
                 commit)``, as ``state/execution.validate_block`` does with the
                 LastCommit of the next block: every signature of it was
                 verified a moment ago, one by one.

The votes are ``votechain.py``'s; the harness's own ``chain`` (``chain.py``:
the set and each validator's precommit for the block) carries them as
``chain.votes``.  The verdict of a request is ``("added", maj23)``,
``("duplicate",)``, ``("invalid_signature",)``,
``("nondeterministic_signature",)``, ``("conflicting", index)``,
``("accepted",)`` for a LastCommit, or ``("error", text)``.

The plain reference (``voteset_ref.py``) answers a sampled request by
replaying its height from the first vote: each height is replayed once,
forward, as far as the furthest request asked of it, and a signature's bit is
asked of ``ed25519_ref.verify_zip215`` once (``_Replay``).
"""

from __future__ import annotations

from benchmarks import ed25519_ref as ref
from benchmarks import program, votechain, voteset_ref
from benchmarks.entries.verify_commit_light import known_answers  # noqa: F401

NAME = "consensus_votes"
PEER = "bench-peer"


def _votes(chain) -> votechain.Votes:
    if not hasattr(chain, "votes"):
        _, traffic = votechain.cell_files(chain.chain_id)
        chain.votes = votechain.build(chain, traffic)
        votechain.spot_check(chain.votes)
    return chain.votes


def requests(chain) -> list:
    """The window's requests in the order they are sent: height by height,
    each height's votes and then its LastCommit."""
    return _votes(chain).pool


def warmup_requests(chain) -> list:
    return _votes(chain).warm


def signatures(chain, req) -> int:
    """The signatures whose verdict the request needed: one a vote that is
    looked at, none for a copy answered before any signature is, every entry
    that is not absent for a LastCommit (answered from the cache or not)."""
    return req.signatures


def warm(chain) -> dict:
    """A vote is one signature in the smallest bucket; the LastCommit ships
    nothing.  Nothing else is warmed: a LastCommit that reached the device
    with more than a bucket would compile in the window, and say so."""
    return program.warm_verify(1)


class State:
    """The program's validator set, block ids and votes, built in set-up,
    before the batch backend is resolved; the height's vote sets are made as
    the node makes them, when the height starts."""

    def __init__(self, chain):
        from cometbft_tpu.crypto.keys import Ed25519PubKey
        from cometbft_tpu.types.basic import BlockID, PartSetHeader, Timestamp
        from cometbft_tpu.types.validator import Validator, ValidatorSet
        from cometbft_tpu.types.vote import Vote

        votes = _votes(chain)
        self.chain_id = chain.chain_id
        self.vals = ValidatorSet(
            [Validator(Ed25519PubKey(p), w) for p, w in zip(chain.pubs, chain.powers)]
        )
        if [v.pub_key.bytes() for v in self.vals.validators] != chain.pubs:
            raise RuntimeError(
                "the program orders the validator set otherwise than the generator"
            )
        addresses = [v.address for v in self.vals.validators]
        self.height_votes = None  # the HeightVoteSet of the height in progress
        self.block_ids = {}
        self.requests = {}
        for h, of_height in votes.by_height.items():
            ids = [
                BlockID(b.hash, PartSetHeader(b.parts_total, b.parts_hash))
                for b in (votes.block_id(h, k) for k in
                          (votechain.BLOCK, votechain.NIL, votechain.OTHER))
            ]
            self.block_ids[h] = ids[votechain.BLOCK]
            for r in of_height:
                self.requests[r.key] = None if r.kind == "last_commit" else Vote(
                    r.type, h, 0, ids[r.block], Timestamp.from_ns(r.time_ns),
                    addresses[r.index], r.index, r.signature)


def call(state: State, req) -> tuple:
    """The timed call.  Every request is sent once."""
    from cometbft_tpu.consensus.types import HeightVoteSet
    from cometbft_tpu.types import validation, vote_set

    vote = state.requests.pop(req.key)
    hvs = state.height_votes
    if hvs is None or hvs.height != req.height:  # updateToState: a new height
        hvs = state.height_votes = HeightVoteSet(state.chain_id, req.height, state.vals)
    try:
        if vote is None:
            commit = hvs.precommits(0).make_commit()
            validation.verify_commit(
                state.chain_id, state.vals, state.block_ids[req.height],
                req.height, commit)
            return ("accepted",)
        if not hvs.add_vote(vote, PEER):
            return ("duplicate",)
        return ("added", hvs.votes(0, vote.type_).two_thirds_majority() == vote.block_id)
    except vote_set.ConflictingVoteError as e:
        return ("conflicting", e.conflicting.validator_index)
    except vote_set.VoteError as e:
        # each of the set's errors is a class that names itself (``outcome``,
        # since PR 32; a program before it has the one class and its text)
        outcome = getattr(e, "outcome", None)
        return (outcome,) if outcome else ("error", f"{type(e).__name__}: {e}")
    except validation.InvalidSignatureError as e:
        return ("invalid_signature", e.index)
    except Exception as e:  # noqa: BLE001 — a verdict, judged by the harness
        return ("error", f"{type(e).__name__}: {e}")


def expected(chain, req) -> tuple:
    """The verdict by construction: what the generator did to this request."""
    return req.expected


# -- the plain reference ------------------------------------------------------------


class _Replay:
    """One height under the plain reference, from its first vote on."""

    def __init__(self, votes: votechain.Votes, height: int, make_sets=None):
        self.votes = votes
        self.requests = votes.by_height[height]
        self.first_key = self.requests[0].key
        self.asked = 0  # requests whose triples have been looked at
        self.handed = set()  # the triples handed out
        self.pending = {}  # request key -> the triples its call handed out
        self.bits = {}  # triple -> the reference's accept bit
        self.verdicts = []
        validators = votes.validators()
        make = make_sets or (lambda type_: voteset_ref.VoteSet(
            votes.chain_id, height, 0, type_, validators, self._bit))
        self.sets = {t: make(t) for t in (votechain.PREVOTE, votechain.PRECOMMIT)}

    def _bit(self, pub: bytes, msg: bytes, sig: bytes) -> bool:
        triple = (pub, msg, sig)
        if triple not in self.bits:  # not handed out: asked of the reference here
            self.bits[triple] = ref.verify_zip215(pub, msg, sig)
        return self.bits[triple]

    def triple(self, req) -> tuple:
        vote = self.votes.plain(req)
        return (self.votes.pubs[req.index],
                voteset_ref.vote_sign_bytes(self.votes.chain_id, vote), req.signature)

    def ask(self, req) -> list:
        """The triples of every vote sent up to ``req`` that no earlier call
        handed out: all a replay up to there can read (a LastCommit reads
        its height's precommits again)."""
        place = req.key - self.first_key
        fresh = {}
        for r in self.requests[self.asked:place + 1]:
            if r.kind != "last_commit":
                fresh.setdefault(self.triple(r))
        self.asked = max(self.asked, place + 1)
        items = [t for t in fresh if t not in self.handed]
        self.handed.update(items)
        self.pending[req.key] = items
        return items

    def verdict(self, req, bits) -> tuple:
        self.bits.update(zip(self.pending.pop(req.key, ()), bits))
        place = req.key - self.first_key
        while len(self.verdicts) <= place:
            self.verdicts.append(self.step(self.requests[len(self.verdicts)]))
        return self.verdicts[place]

    def step(self, req) -> tuple:
        if req.kind != "last_commit":
            return self.sets[req.type].add_vote(self.votes.plain(req))
        try:
            commit = self.sets[votechain.PRECOMMIT].make_commit()
        except ValueError as e:
            return ("error", str(e))
        got = voteset_ref.verify_commit(
            self.votes.chain_id, self.votes.validators(),
            self.votes.block_id(req.height, votechain.BLOCK), req.height, commit,
            self._bit)
        return got if got[0] in ("accepted", "invalid_signature") \
            else ("error", f"{got[0]}: {got[1:]}")


def _replay(chain, req) -> _Replay:
    votes = _votes(chain)
    if not hasattr(votes, "replays"):
        votes.replays = {}
    if req.height not in votes.replays:
        votes.replays[req.height] = _Replay(votes, req.height)
    return votes.replays[req.height]


def reference_items(chain, req) -> list:
    """The (public key, sign-bytes, signature) triples the replay of this
    request's height can read up to it, each handed out once a run."""
    return _replay(chain, req).ask(req)


def reference_verdict(chain, req, bits) -> tuple:
    """The plain reference's answer to this request after every vote its
    height sent before it, with the reference's accept bits."""
    return _replay(chain, req).verdict(req, bits)
