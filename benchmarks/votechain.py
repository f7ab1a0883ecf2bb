"""The validator's generator, beside ``chain.py``: every prevote and precommit
of a height as single votes, in the order a validator's receive routine meets
them, then the height's LastCommit, from ``--seed``.

A pure function of (configuration, traffic file, seed), whatever the number
of workers.  Nothing here imports the program or jax.  ``chain.py`` has signed
each validator's precommit for the height's block (its own clock); what it
cannot make is signed here with the host library in ``chain.SignPool``'s
spawned workers: the prevotes, the votes for nil, the second vote of an
equivocation, an altered vote signed over another block.  Sign-bytes come
from ``canonical.py``'s field helpers, put together in ``sign_bytes`` below.

A height h of n validators, all drawn from the seed:

    absent_per_height     validators that send nothing (ABSENT in the
                          LastCommit)
    nil_per_height        validators whose prevote and precommit are for nil
    the prevotes of (h, 0) in a shuffled order, then the precommits in another,
    then ONE ``last_commit`` request

and, by a vote's ordinal g (the sound votes counted in arrival order over the
whole pool: the honest ones and an equivocator's second), on top of that
order:

    duplicate      g % duplicate_every == duplicate_phase: the same bytes a
                   second time, ``redelivery_after`` requests later
    altered        g % altered_every == altered_phase: a copy with an altered
                   signature (``altered_classes`` cycled) in the vote's place,
                   the honest vote ``redelivery_after`` requests later
    forged_copy    g % forged_copy_every == forged_copy_phase: a copy of the
                   vote, by then held, under another signature, later
    equivocation   heights with h % equivocation_every_heights ==
                   equivocation_phase: one validator's second, soundly signed
                   vote for ANOTHER block id, after its first; prevote and
                   precommit alternately.  It goes in where the altered rule
                   hits its ordinal, so its altered copy arrives first, while
                   the validator's first vote is held: what tells a set that
                   checks the signature BEFORE it looks for a conflict from
                   one that does not

A later delivery never leaves its height: it is clamped before the
``last_commit``.  Warm-up heights are the generator's own (``warmup_heights``,
after the pool's) and carry every class at fixed places.

The verdict of a request is what the generator did (``Request.expected``):
``("added", maj23)`` with ``maj23`` whether this vote's block holds +2/3 of
its set after the call (the generator counts the power it has sent),
``("duplicate",)``, ``("invalid_signature",)``,
``("nondeterministic_signature",)``, ``("conflicting", index)``,
``("accepted",)`` for the LastCommit.
"""

from __future__ import annotations

import functools
import hashlib
import os
import random
from dataclasses import dataclass, field
from typing import NamedTuple

from benchmarks import canonical, chain as chainlib, manifest, voteset_ref
from benchmarks import ed25519_ref as ref

ENTRY = "consensus_votes"
PREVOTE, PRECOMMIT = 1, 2
BLOCK, NIL, OTHER = 0, 1, 2  # what a vote is for: the height's block, nil, another
SLOTS = 1024  # a request's key is height * SLOTS + its place in the height
ALTERED_CLASSES = ("noncanonical_s", "flip_s", "wrong_msg", "flip_r")


def _h(*parts) -> bytes:
    return hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()


class Request(NamedTuple):
    key: int
    height: int
    kind: str  # honest | duplicate | altered | forged_copy | equivocation | last_commit
    type: int  # PREVOTE or PRECOMMIT; 0 for the last_commit
    index: int  # the validator's place in the set's order; -1 for the last_commit
    block: int  # BLOCK, NIL or OTHER
    time_ns: int
    signature: bytes
    expected: tuple
    signatures: int  # whose verdict the request needed: 1, 0 or the LastCommit's
    cls: str = ""  # the altered class


@dataclass
class Votes:
    seed: int
    chain_id: str
    pubs: "list[bytes]"  # in the set's order
    powers: "list[int]"
    pool: "list[Request]" = field(default_factory=list)
    warm: "list[Request]" = field(default_factory=list)
    by_height: "dict[int, list[Request]]" = field(default_factory=dict)

    def request(self, key: int) -> Request:
        return self.by_height[key // SLOTS][key % SLOTS]

    def block_id(self, height: int, block: int) -> voteset_ref.BlockID:
        return block_id(self.seed, height, block)

    def plain(self, req: Request) -> voteset_ref.Vote:
        """A vote request as the plain reference's record."""
        return voteset_ref.Vote(
            req.type, req.height, 0, self.block_id(req.height, req.block),
            req.time_ns, voteset_ref.address(self.pubs[req.index]), req.index,
            req.signature)

    def validators(self) -> "list[tuple[bytes, int]]":
        return list(zip(self.pubs, self.powers))


def block_id(seed: int, height: int, block: int) -> voteset_ref.BlockID:
    if block == NIL:
        return voteset_ref.NIL
    if block == BLOCK:
        return voteset_ref.BlockID(chainlib.block_hash(seed, height), 1,
                                   chainlib.parts_hash(seed, height))
    return voteset_ref.BlockID(_h("tpu-bft-bench", seed, "other-block", height), 1,
                               _h("tpu-bft-bench", seed, "other-parts", height))


def sign_bytes(chain_id: str, type_: int, height: int,
               bid: voteset_ref.BlockID, time_ns: int) -> bytes:
    """CanonicalVote of round 0 (a zero round is left out), delimited; a vote
    for nil has no block id."""
    head = canonical._varint_field(1, type_) + canonical._sfixed64_field(2, height)
    if bid != voteset_ref.NIL:
        psh = (canonical._varint_field(1, bid.parts_total)
               + canonical._bytes_field(2, bid.parts_hash))
        head += canonical._bytes_field(
            4, canonical._bytes_field(1, bid.hash) + canonical._bytes_field(2, psh))
    return canonical.sign_bytes(head, time_ns, canonical.vote_tail(chain_id))


def cell_files(chain_id: str) -> "tuple[dict, dict]":
    """The configuration whose chain id this is and the traffic file of the
    cell that runs it through ``consensus_votes``: the harness hands an entry
    the chain only (``lightchain.cell_files``; ``PERF.md`` section 7).  The
    tests put small files here."""
    m = manifest.load()
    found = {}
    for w in m["workloads"]:
        cfg = next(c for c in m["configs"] if c["name"] == w["config"])
        config = manifest._json(os.path.join(manifest.ROOT, cfg["file"]))
        traffic = manifest._json(
            os.path.join(manifest.HERE, "traffic", w["traffic"] + ".json"))
        if config.get("chain_id") == chain_id and traffic["entry"] == ENTRY:
            found[w["traffic"]] = (config, traffic)
    if len(found) != 1:
        raise KeyError(
            f"{len(found)} {ENTRY} cells for chain id {chain_id!r}: {sorted(found)}")
    return next(iter(found.values()))


# -- workers: the host library only -------------------------------------------------


@functools.lru_cache(maxsize=1)
def _keys(seed: int, n: int):
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    return [
        Ed25519PrivateKey.from_private_bytes(chainlib.validator_seed(seed, i))
        for i in range(n)
    ]


def _sign_messages(args) -> bytes:
    """The signatures of [(validator identity, message)], one blob."""
    seed, n, todo = args
    keys = _keys(seed, n)
    return b"".join(keys[i].sign(msg) for i, msg in todo)


# -- the plan -----------------------------------------------------------------------


@dataclass
class _Draft:
    """One delivery before its signature is known."""
    kind: str
    type: int
    index: int
    block: int
    time_ns: int
    slot: "tuple[int, int]"  # (place among the height's honest votes, order there)
    signature: bytes = b""
    source: "_Draft | None" = None  # the honest vote this one is made from
    cls: str = ""


def _vote_times_ns(seed: int, height: int, n: int, jitter_ms: int, what: str):
    rng = random.Random(f"tpu-bft-bench/{seed}/{what}/{height}")
    base = chainlib.BASE_TIME_NS + height * 10**9
    span = max(jitter_ms, 1) * 10**6
    return [base + 1 + rng.randrange(span - 1) for _ in range(n)]


def _special_at(traffic: dict, g: int) -> str:
    """What the traffic file does to the sound vote of ordinal ``g``."""
    found = ""
    for kind in ("duplicate", "altered", "forged_copy"):
        every = int(traffic.get(kind + "_every", 0))
        if every and g % every == int(traffic.get(kind + "_phase", every // 2)):
            if found:
                raise ValueError(f"{kind} and {found} on one vote: choose other phases")
            found = kind
    return found


WARM_HEIGHTS = (
    # what a warm-up height carries, so that the two carry every class once:
    # {place among the sound votes: (kind, altered class)}, the type of the
    # equivocation, and where its second vote goes in (None: by the rules)
    ({3: ("duplicate", ""), 5: ("altered", "noncanonical_s"), 7: ("altered", "flip_s"),
      9: ("forged_copy", "")}, PREVOTE, 4),
    ({2: ("altered", "wrong_msg"), 4: ("altered", "flip_r"), 6: ("duplicate", ""),
      8: ("forged_copy", "")}, PRECOMMIT, None),
)


def _plan_height(votes: Votes, traffic: dict, hgt: chainlib.Height, g0: int,
                 warm: "tuple | None"):
    """The height's deliveries in arrival order, unsigned where ``chain.py``
    has no signature for them; returns (drafts, sound votes).

    The SOUND votes are the honest ones in their order and, in an
    equivocation height, the equivocator's second vote, which goes in after
    its first at a place whose ordinal the altered rule hits: so a copy of it
    with an altered signature arrives first, while the validator's first vote
    is held, and the set has to check that signature BEFORE it looks for a
    conflict (a program that did not would frame the validator)."""
    seed, h, n = votes.seed, hgt.height, len(votes.pubs)
    rng = random.Random(f"tpu-bft-bench/{seed}/votes/{h}")
    jitter = int(traffic.get("time_jitter_ms", 500))
    n_absent, n_nil = int(traffic["absent_per_height"]), int(traffic["nil_per_height"])
    quiet = rng.sample(range(n), n_absent + n_nil)
    absent, nil = set(quiet[:n_absent]), set(quiet[n_absent:])
    voters = [i for i in range(n) if i not in absent]
    prevote_times = _vote_times_ns(seed, h, n, jitter, "prevote-time")
    sound = []
    for type_ in (PREVOTE, PRECOMMIT):
        order = voters[:]
        rng.shuffle(order)
        for i in order:
            d = _Draft("honest", type_, i, NIL if i in nil else BLOCK,
                       prevote_times[i] if type_ == PREVOTE else hgt.times_ns[i], (0, 0))
            if type_ == PRECOMMIT and i not in nil:
                d.signature = hgt.sigs[i]  # chain.py's
            sound.append(d)

    classes = traffic.get("altered_classes", ALTERED_CLASSES)
    if warm is None:
        every = int(traffic.get("equivocation_every_heights", 0))
        phase = int(traffic.get("equivocation_phase", every // 2))
        equivocates, at = None, None
        if every and h % every == phase:
            equivocates = PREVOTE if (h // every) % 2 == 0 else PRECOMMIT

        def rule(p: int) -> "tuple[str, str]":
            kind = _special_at(traffic, g0 + p)
            if kind != "altered":
                return kind, ""
            turn = (g0 + p) // int(traffic["altered_every"])
            return kind, classes[turn % len(classes)]
    else:
        fixed, equivocates, at = dict(warm[0]), warm[1], warm[2]

        def rule(p: int) -> "tuple[str, str]":
            return fixed.get(p, ("", ""))

    if equivocates is not None:
        firsts = [p for p, d in enumerate(sound)
                  if d.type == equivocates and d.block == BLOCK and not rule(p)[0]]
        if not firsts:
            raise ValueError(f"height {h}: no plain vote for the block to equivocate on")
        after = range(firsts[0] + 1, len(sound) + 1)
        # the places after a first vote that the altered rule hits, else any
        # place there that no rule hits
        places = [at] if at is not None else (
            [q for q in after if rule(q)[0] == "altered"]
            or [q for q in after if not rule(q)[0]])
        q = rng.choice(places)
        first = sound[rng.choice([p for p in firsts if p < q])]
        sound.insert(q, _Draft(
            "equivocation", first.type, first.index, OTHER,
            first.time_ns + 1 + rng.randrange(10**6), (0, 0)))
        if at is not None:
            fixed[q] = ("altered", "flip_r")
    for p, d in enumerate(sound):
        d.slot = (p, 0)

    lo, hi = traffic.get("redelivery_after", (1, 40))
    last = len(sound) - 1
    drafts = list(sound)
    for p, src in enumerate(sound):
        kind, cls = rule(p)
        if not kind:
            continue
        later = (min(p + rng.randint(lo, hi), last), 1 + len(drafts))
        if kind == "altered":
            # the altered copy takes the vote's place, the sound vote comes later
            drafts.append(_Draft("altered", src.type, src.index, src.block,
                                 src.time_ns, src.slot, source=src, cls=cls))
            src.slot = later
        else:
            drafts.append(_Draft(kind, src.type, src.index, src.block, src.time_ns,
                                 later, source=src))
    drafts.sort(key=lambda d: d.slot)
    return drafts, sound


def _to_sign(votes: Votes, h: int, d: _Draft) -> "bytes | None":
    """The message a worker has to sign for this delivery, if any."""
    if d.kind in ("honest", "equivocation"):
        if d.signature:
            return None
        return sign_bytes(votes.chain_id, d.type, h, votes.block_id(h, d.block), d.time_ns)
    if d.kind == "altered" and d.cls == "wrong_msg":
        # a sound signature of the same validator over another block
        forged = voteset_ref.BlockID(_h("forged", h), 1, chainlib.parts_hash(votes.seed, h))
        return sign_bytes(votes.chain_id, d.type, h, forged, d.time_ns)
    return None


def _derived(d: _Draft) -> bytes:
    sig = d.source.signature
    if d.kind == "duplicate":
        return sig
    if d.kind == "forged_copy" or d.cls == "flip_s":
        return sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
    if d.cls == "flip_r":
        return bytes([sig[0] ^ 1]) + sig[1:]
    if d.cls == "noncanonical_s":
        s = int.from_bytes(sig[32:], "little") + ref.L
        return sig[:32] + s.to_bytes(32, "little")
    raise ValueError(f"unknown altered class {d.cls!r}")


def _finish_height(votes: Votes, h: int, drafts: "list[_Draft]") -> "list[Request]":
    """Keys and verdicts: the generator counts the power it has sent for the
    height's block, by type."""
    if len(drafts) + 1 > SLOTS:
        raise ValueError(f"{len(drafts)} deliveries a height: raise SLOTS")
    quorum = sum(votes.powers) * 2 // 3 + 1
    sent = {PREVOTE: 0, PRECOMMIT: 0}
    out = []
    for k, d in enumerate(drafts):
        signatures = 1
        if d.kind == "honest":
            if d.block == BLOCK:
                sent[d.type] += votes.powers[d.index]
            expected = ("added", d.block == BLOCK and sent[d.type] >= quorum)
        elif d.kind == "duplicate":
            expected, signatures = ("duplicate",), 0
        elif d.kind == "altered":
            expected = ("invalid_signature",)
        elif d.kind == "forged_copy":
            expected, signatures = ("nondeterministic_signature",), 0
        else:
            expected = ("conflicting", d.index)
        out.append(Request(h * SLOTS + k, h, d.kind, d.type, d.index, d.block,
                           d.time_ns, d.signature, expected, signatures, d.cls))
    if sent[PRECOMMIT] < quorum:
        raise ValueError(f"height {h}: the precommits sent carry no +2/3 for the block")
    present = len({d.index for d in drafts if d.type == PRECOMMIT})
    out.append(Request(h * SLOTS + len(drafts), h, "last_commit", 0, -1, BLOCK, 0,
                       b"", ("accepted",), present))
    return out


def build(chain: chainlib.Chain, traffic: dict, pool=None) -> Votes:
    """Every request of the pool and of warm-up, over ``chain.py``'s heights
    (its precommits and their times).  ``pool`` is a ``chain.SignPool``;
    without one, one is started and closed here."""
    votes = Votes(chain.seed, chain.chain_id, chain.pubs, chain.powers)
    planned, todo = [], []  # per height; (identity, message, draft)
    g0 = 0
    for k, hgt in enumerate(chain.pool + chain.warm):
        warm = None
        if k >= len(chain.pool):
            warm = WARM_HEIGHTS[(k - len(chain.pool)) % len(WARM_HEIGHTS)]
        drafts, sound = _plan_height(votes, traffic, hgt, g0, warm)
        g0 += len(sound)
        planned.append((hgt.height, drafts))
        for d in drafts:
            msg = _to_sign(votes, hgt.height, d)
            if msg is not None:
                todo.append((chain.ids[d.index], msg, d))
    own = pool is None
    pool = pool or chainlib.SignPool()
    try:
        step = max(1, -(-len(todo) // (8 * pool.workers)))
        tasks = [
            (chain.seed, len(chain.pubs), [(i, m) for i, m, _ in todo[k:k + step]])
            for k in range(0, len(todo), step)
        ]
        blob = b"".join(pool.map_async(_sign_messages, tasks).get())
    finally:
        if own:
            pool.close()
    for k, (_, _, d) in enumerate(todo):
        d.signature = blob[64 * k:64 * k + 64]
    for h, drafts in planned:
        for d in drafts:
            if not d.signature:
                d.signature = _derived(d)
        requests = _finish_height(votes, h, drafts)
        votes.by_height[h] = requests
        (votes.pool if h <= len(chain.pool) else votes.warm).extend(requests)
    return votes


def spot_check(votes: Votes, sample: int = 4) -> None:
    """Hold the host library's signatures to the plain reference on a seeded
    sample of the first height's honest votes, over THIS file's sign-bytes."""
    rng = random.Random(f"tpu-bft-bench/{votes.seed}/votes-spot")
    honest = [r for r in votes.pool[:2 * len(votes.pubs)] if r.kind == "honest"]
    for r in rng.sample(honest, min(sample, len(honest))):
        msg = sign_bytes(votes.chain_id, r.type, r.height,
                         votes.block_id(r.height, r.block), r.time_ns)
        if not ref.verify_zip215(votes.pubs[r.index], msg, r.signature):
            raise RuntimeError(f"the host library's signature of {r.key} does not verify")


def fingerprint(votes: Votes) -> str:
    """One hash over everything generated: same seed, same bytes."""
    h = hashlib.sha256()
    h.update(b"".join(votes.pubs))
    for r in votes.warm + votes.pool:
        h.update(repr(tuple(r)).encode())
    return h.hexdigest()
