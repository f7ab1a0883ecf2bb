"""Entry ``light_sequential``: one request is one
``LightClient(mode=SEQUENTIAL).verify_light_block_at_height(target, now)``
(reference: light/client.go:469 VerifyLightBlockAtHeight → :608
verifySequential), as ``cometbft light --sequential`` makes it: every header
from the block the client holds to the target verified adjacently, then the
target saved.  One client for the whole run, over ``LightStore(MemKV())``
and a primary written here that serves the request's blocks.

The chain is ``seqchain.py``'s; the harness's own ``chain`` (one height of
``chain.py``, spot-checked there) carries it as ``chain.seq``.  The verdict
of a request is ``("accepted",)``, ``("invalid_signature", height, commit
index)``, ``("invalid_header", height)`` (a hash link or another check of
that header failed) or ``("error", text)``; the height is the first header
that was not accepted (``ErrVerificationFailed.to``).
"""

from __future__ import annotations

from benchmarks import light_seq_ref, lightchain, program, seqchain
from benchmarks.entries.verify_commit_light import known_answers  # noqa: F401

NAME = "light_sequential"
LANES = 8192  # a window of 8 headers of 667 misses, if all leave in one flush


def _seq(chain) -> lightchain.Light:
    if not hasattr(chain, "seq"):
        config, traffic = seqchain.cell_files(chain.chain_id)
        chain.seq = seqchain.build(config, traffic, chain.seed)
        seqchain.spot_check(chain.seq)
    return chain.seq


def requests(chain) -> list:
    """The window's requests in the order they are sent: request k starts
    from the newest block accepted before it."""
    return _seq(chain).pool


def warmup_requests(chain) -> list:
    return _seq(chain).warm


def signatures(chain, req) -> int:
    """The distinct (key, sign-bytes, signature) triples whose verdict the
    request needed and no request before it had: a cache hit is not a
    second verification."""
    return req.signatures


def warm(chain) -> dict:
    """Headers of one window may leave in one flush: every bucket up to
    ``LANES``.  The set's Merkle root is hashed on the host."""
    return program.warm_verify(LANES)


class State:
    """The program's light blocks, built in set-up, before the batch backend
    is resolved; the client is made at the first call (it verifies its
    trusted block, which resolves the backend)."""

    def __init__(self, chain):
        from cometbft_tpu.light import verifier

        if not hasattr(verifier, "ErrVerificationFailed"):
            # a program that cannot name the bad header cannot give this
            # cell's verdicts: fail now, before the chain is signed
            raise SystemExit(
                "the program's light client names no failing header "
                "(no light.verifier.ErrVerificationFailed)")
        from cometbft_tpu.crypto.keys import Ed25519PubKey
        from cometbft_tpu.light.provider import Provider
        from cometbft_tpu.types.basic import BlockID, PartSetHeader, Timestamp
        from cometbft_tpu.types.block import Commit, ConsensusVersion, Header
        from cometbft_tpu.types.light import LightBlock, SignedHeader
        from cometbft_tpu.types.validator import Validator, ValidatorSet
        from cometbft_tpu.types.vote import CommitSig

        seq = _seq(chain)
        self.seq = seq
        vals = [Validator(Ed25519PubKey(p), seq.power) for p in seq.pubs]
        checked = set()

        def block_id(b):
            return BlockID(hash=b.hash,
                           part_set_header=PartSetHeader(b.parts_total, b.parts_hash))

        def program_block(block):
            """A block of its own, set included, as a provider decodes it."""
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
            if block.ids not in checked:
                got = [v.pub_key.bytes() for v in vset.validators]
                if got != [seq.pubs[i] for i in block.ids]:
                    raise RuntimeError("the program orders the validator set "
                                       "otherwise than the generator")
                checked.add(block.ids)
            return LightBlock(SignedHeader(header, commit), vset)

        blocks = {key: program_block(b) for key, b in seq.blocks.items()}
        self.root = blocks[1]
        # what the primary serves a request: its heights, the faulty one
        # replaced; the target too (the client asks for it first)
        self.requests = {
            r.key: (r.target, r.now_s, {
                h: blocks[r.served(h)] for h in range(r.trusted + 1, r.target + 1)})
            for r in seq.warm + seq.pool
        }

        class Primary(Provider):
            def chain_id(self):
                return seq.chain_id

            def light_block(self, height):
                return self.served[height]

            def report_evidence(self, ev):
                pass

        self.primary = Primary()
        self.primary.served = {1: self.root}  # the trust root, asked at start
        self.client = None

    def make_client(self):
        from cometbft_tpu.light import SEQUENTIAL, LightClient, LightStore, TrustOptions
        from cometbft_tpu.store.kv import MemKV

        period = int(self.seq.trusting_period_s)
        self.client = LightClient(
            self.seq.chain_id, TrustOptions(period, 1, self.root.hash()),
            self.primary, [], LightStore(MemKV()), mode=SEQUENTIAL,
        )


def call(state: State, req) -> tuple:
    """The timed call.  The client verifies forward from the block it holds."""
    from cometbft_tpu.light import verifier
    from cometbft_tpu.types import validation

    if state.client is None:
        state.make_client()
    target, now_s, served = state.requests.pop(req.key)
    state.primary.served = served
    try:
        state.client.verify_light_block_at_height(target, now_s)
    except verifier.ErrVerificationFailed as e:
        if isinstance(e.reason, validation.InvalidSignatureError):
            return ("invalid_signature", e.to, e.reason.index)
        if isinstance(e.reason, verifier.ErrInvalidHeader):
            return ("invalid_header", e.to)
        return ("error", f"{e.to}: {type(e.reason).__name__}: {e.reason}")
    except Exception as e:  # noqa: BLE001 — a verdict, judged by the harness
        return ("error", f"{type(e).__name__}: {e}")
    return ("accepted",)


def expected(chain, req) -> tuple:
    """The verdict by construction: what the generator did to this request."""
    return req.expected


def _reference(chain, req, verify_sig) -> tuple:
    """``light_seq_ref.verify_sequential`` over what the request replays:
    each checked header against the one before it (a header's check reads
    its predecessor only), the failing header of a rejection alone."""
    seq = _seq(chain)
    for h in req.checked:
        got = light_seq_ref.verify_sequential(
            seq.chain_id, seq.light_block(req.served(h - 1)),
            [seq.light_block(req.served(h))], seq.trusting_period_s, req.now_s,
            verify_sig=verify_sig)
        if got != ("accepted",):
            (verdict, *detail), height = got
            if verdict == "invalid_signature":
                return ("invalid_signature", height, detail[0])
            if verdict == "invalid_header":
                return ("invalid_header", height)
            return ("error", f"{height}: {verdict}: {detail}")
    return ("accepted",)


def reference_items(chain, req) -> list:
    """The (public key, sign-bytes, signature) triples the replay asks for
    when every signature holds, each once, in the order asked."""
    asked = {}

    def note(pub, msg, sig):
        asked.setdefault((pub, msg, sig))
        return True

    _reference(chain, req, note)
    return list(asked)


def reference_verdict(chain, req, bits) -> tuple:
    """The replay with the plain reference's accept bits."""
    verdicts = dict(zip(reference_items(chain, req), bits))
    return _reference(chain, req, lambda pub, msg, sig: verdicts[(pub, msg, sig)])
