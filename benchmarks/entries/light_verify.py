"""Entry ``light_verify``: one request is one
``cometbft_tpu.light.verifier.verify(chain_id, trusted, new, trusting_period,
now)`` (reference: light/verifier.go:128 Verify), as a skipping light client
makes it: a header hash and a validator-set Merkle root, then the trusting
pass over the new commit by address, then the light pass over the same
commit.

The chain is ``lightchain.py``'s; the harness's own ``chain`` (one height of
``chain.py``, spot-checked there) carries it as ``chain.light``.  The verdict
of a request is ``("accepted",)``, ``("invalid_signature", commit index)``,
``("cant_be_trusted",)``, ``("invalid_header",)`` (a hash link or another
check of the header failed) or ``("error", text)``.
"""

from __future__ import annotations

from benchmarks import light_ref, lightchain, program
from benchmarks.entries.verify_commit_light import known_answers  # noqa: F401

NAME = "light_verify"


def _light(chain) -> lightchain.Light:
    if not hasattr(chain, "light"):
        config, traffic = lightchain.cell_files(chain.chain_id)
        chain.light = lightchain.build(config, traffic, chain.seed)
        lightchain.spot_check(chain.light)
    return chain.light


def requests(chain) -> list:
    """The window's requests in the order they are sent: request k's trusted
    block is the newest block accepted before it."""
    return _light(chain).pool


def warmup_requests(chain) -> list:
    return _light(chain).warm


def signatures(chain, req) -> int:
    """The distinct (key, sign-bytes, signature) triples whose verdict the
    request needed: a cache hit is not a second verification."""
    return req.signatures


def warm(chain) -> dict:
    """An adjacent step ships its whole light prefix in one piece; a skipping
    step ships two smaller ones.  The set's Merkle root is hashed on the
    host (``proofserve/plane.DEFAULT_MIN_BATCH``): nothing to warm there."""
    n = len(_light(chain).blocks["root"].ids)
    return program.warm_verify(lightchain.light_prefix(n))


class State:
    """The program's light blocks, built in set-up, before the batch backend
    is resolved.  A block's hashes are the generator's own: nothing is hashed
    here."""

    def __init__(self, chain):
        from cometbft_tpu.crypto.keys import Ed25519PubKey
        from cometbft_tpu.types.basic import BlockID, PartSetHeader, Timestamp
        from cometbft_tpu.types.block import Commit, ConsensusVersion, Header
        from cometbft_tpu.types.light import LightBlock, SignedHeader
        from cometbft_tpu.types.validator import Validator, ValidatorSet
        from cometbft_tpu.types.vote import CommitSig

        light = _light(chain)
        self.chain_id = light.chain_id
        self.trusting_period_s = light.trusting_period_s
        vals = [Validator(Ed25519PubKey(p), light.power) for p in light.pubs]

        def block_id(b):
            return BlockID(hash=b.hash,
                           part_set_header=PartSetHeader(b.parts_total, b.parts_hash))

        def program_block(block):
            h, c = block.header, block.commit
            header = Header(
                ConsensusVersion(h.version_block, h.version_app), h.chain_id,
                h.height, Timestamp.from_ns(h.time_ns), block_id(h.last_block_id),
                h.last_commit_hash, h.data_hash, h.validators_hash,
                h.next_validators_hash, h.consensus_hash, h.app_hash,
                h.last_results_hash, h.evidence_hash, h.proposer_address,
            )
            commit = Commit(c.height, c.round, block_id(c.block_id), [
                CommitSig(s.flag, s.address, Timestamp.from_ns(s.time_ns), s.signature)
                for s in c.sigs
            ])
            vset = ValidatorSet([vals[i] for i in block.ids])
            got = [v.pub_key.bytes() for v in vset.validators]
            if got != [light.pubs[i] for i in block.ids]:
                raise RuntimeError(
                    "the program orders the validator set otherwise than the generator"
                )
            return LightBlock(SignedHeader(header, commit), vset)

        blocks = {key: program_block(b) for key, b in light.blocks.items()}
        self.requests = {
            r.key: (blocks[r.trusted], blocks[r.new], r.now_s)
            for r in light.pool + light.warm
        }


def call(state: State, req) -> tuple:
    """The timed call.  Every new block is verified once."""
    from cometbft_tpu.light import verifier
    from cometbft_tpu.types import validation

    trusted, new, now_s = state.requests.pop(req.key)
    try:
        verifier.verify(state.chain_id, trusted, new, state.trusting_period_s, now_s)
    except validation.InvalidSignatureError as e:
        return ("invalid_signature", e.index)
    except verifier.ErrNewValSetCantBeTrusted:
        return ("cant_be_trusted",)
    except verifier.ErrInvalidHeader:
        return ("invalid_header",)
    except Exception as e:  # noqa: BLE001 — a verdict, judged by the harness
        return ("error", f"{type(e).__name__}: {e}")
    return ("accepted",)


def expected(chain, req) -> tuple:
    """The verdict by construction: what the generator did to this request."""
    return req.expected


def _reference(chain, req, verify_sig) -> tuple:
    light = _light(chain)
    got = light_ref.verify(
        light.chain_id, light.light_block(req.trusted), light.light_block(req.new),
        light.trusting_period_s, req.now_s, *light.trust, verify_sig=verify_sig,
    )
    if got[0] == "invalid_header":
        return ("invalid_header",)
    return got if got[0] in ("accepted", "invalid_signature", "cant_be_trusted") \
        else ("error", f"{got[0]}: {got[1:]}")


def reference_items(chain, req) -> list:
    """The (public key, sign-bytes, signature) triples ``light_ref.verify``
    asks for when every signature holds, each once, in the order asked."""
    asked = {}

    def note(pub, msg, sig):
        asked.setdefault((pub, msg, sig))
        return True

    _reference(chain, req, note)
    return list(asked)


def reference_verdict(chain, req, bits) -> tuple:
    """``light_ref.verify`` with the plain reference's accept bits."""
    verdicts = dict(zip(reference_items(chain, req), bits))
    return _reference(chain, req, lambda pub, msg, sig: verdicts[(pub, msg, sig)])
