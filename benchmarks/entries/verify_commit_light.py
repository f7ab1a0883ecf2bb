"""Entry ``verify_commit_light``: one request is one call of
``types.validation.verify_commit_light`` on one fresh height (reference:
``types/validation.go:63`` VerifyCommitLight).

How a request of this kind is made from the generator's bytes, called and
judged.  The verdict of a request is a tuple: ``("accepted",)``,
``("invalid_signature", index)`` or ``("error", text)``.
"""

from __future__ import annotations

from benchmarks import program

NAME = "verify_commit_light"


def requests(chain) -> list:
    """The window's requests, in the order they are sent: one a height of the
    pool.  A request has a ``key`` that no other has."""
    return chain.pool


def warmup_requests(chain) -> list:
    """Heights of their own, verified before the window."""
    return chain.warm


def signatures(chain, hgt) -> int:
    """Signatures in the verified prefix of one request, accepted or
    rejected: the light variant stops past 2/3 of the power."""
    return chain.light_prefix()


def warm(chain) -> dict:
    """Resolve the backend and the executables this traffic can reach: the
    scheduler drains whatever is queued when a flush fires, so a request of
    ``prefix`` signatures reaches the device in pieces of any size up to
    its bucket."""
    return program.warm_verify(chain.light_prefix())


def known_answers() -> "list[str]":
    """The 18 known-answer vectors through the program's batch verifier,
    once in set-up: the labels it judged otherwise than the reference."""
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.crypto import keys as ck

    from benchmarks.vectors import vectors

    pubs, msgs, sigs, expect, labels = vectors()
    bv = cbatch.create_batch_verifier(ck.Ed25519PubKey(pubs[0]))
    for p, m, s in zip(pubs, msgs, sigs):
        bv.add(p, m, s)
    ok, bits = bv.verify()
    wrong = [lb for lb, w, g in zip(labels, expect, bits) if bool(w) != bool(g)]
    if ok:
        wrong.append("a batch with rejects reported ok")
    return wrong


class State:
    """The program's objects for one chain: built in set-up, before the
    batch backend is resolved."""

    def __init__(self, chain):
        from cometbft_tpu.crypto.keys import Ed25519PubKey
        from cometbft_tpu.types.basic import BlockID, PartSetHeader, Timestamp
        from cometbft_tpu.types.block import Commit
        from cometbft_tpu.types.validator import Validator, ValidatorSet
        from cometbft_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, CommitSig

        self.chain_id = chain.chain_id
        self.vals = ValidatorSet(
            [Validator(Ed25519PubKey(p), w) for p, w in zip(chain.pubs, chain.powers)]
        )
        got = [v.pub_key.bytes() for v in self.vals.validators]
        if got != chain.pubs:
            raise RuntimeError(
                "the program orders the validator set otherwise than the "
                "generator (CometBFT: power descending, then address)"
            )
        addrs = [v.address for v in self.vals.validators]
        self.requests = {}
        for hgt in chain.pool + chain.warm:
            bid = BlockID(
                hash=hgt.block_hash,
                part_set_header=PartSetHeader(1, hgt.parts_hash),
            )
            sigs = [
                CommitSig(BLOCK_ID_FLAG_COMMIT, a, Timestamp.from_ns(t), s)
                for a, t, s in zip(addrs, hgt.times_ns, hgt.sigs)
            ]
            self.requests[hgt.height] = (
                bid, Commit(height=hgt.height, round_=0, block_id=bid,
                            signatures=sigs),
            )


def call(state: State, hgt) -> tuple:
    """The timed call.  Every height is verified once: the request is
    taken out of the state as it is sent."""
    from cometbft_tpu.types import validation

    bid, commit = state.requests.pop(hgt.height)
    try:
        validation.verify_commit_light(
            state.chain_id, state.vals, bid, hgt.height, commit
        )
    except validation.InvalidSignatureError as e:
        return ("invalid_signature", e.index)
    except Exception as e:  # noqa: BLE001 — a verdict, judged by the harness
        return ("error", f"{type(e).__name__}: {e}")
    return ("accepted",)


def expected(chain, hgt) -> tuple:
    """The verdict by construction: what the generator did to this height."""
    if hgt.tamper is None:
        return ("accepted",)
    return ("invalid_signature", hgt.tamper[0])


def reference_items(chain, hgt) -> list:
    """(pub, sign-bytes, signature) of the verified prefix, in order, for
    the plain reference.  Sign-bytes are the benchmark's own."""
    return [
        (chain.pubs[i], chain.sign_bytes(hgt, i), hgt.sigs[i])
        for i in range(chain.light_prefix())
    ]


def reference_verdict(chain, hgt, bits) -> tuple:
    """What VerifyCommitLight answers, given the reference's accept bits
    over the prefix: the first wrong signature by index, else accepted
    (the prefix carries more than 2/3 of the power by construction)."""
    for i, ok in enumerate(bits):
        if not ok:
            return ("invalid_signature", i)
    tallied = sum(chain.powers[:len(bits)])
    if tallied * 3 <= sum(chain.powers) * 2:
        return ("error", "not enough voting power")
    return ("accepted",)
