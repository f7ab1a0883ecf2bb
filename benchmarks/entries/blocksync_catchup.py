"""Entry ``blocksync_catchup``: one request is one tick of a joiner's
blocksync frontier, ``BlocksyncReactor.tick()`` until the frontier height
is applied or rejected (reference: internal/blocksync/reactor.go:520
poolRoutine), as a node that joins a running chain makes it: H checked
with H + 1's LastCommit, ``validate_block``, the store's save and the
kvstore's FinalizeBlock and Commit.  One joiner for the whole run: a whole
node's block executor over ``MemKV`` stores, the kvstore app over a local
ABCI client, no consensus reactor.

The chain is ``bsyncchain.py``'s; the harness's own ``chain`` (one height of
``chain.py``, spot-checked there) carries it as ``chain.bsync``.  The pool
asks its peers for blocks as the reactor does; four in-process helpers
answer each request with the block's BlockResponse bytes, which ONE
receive thread of the entry's own hands to ``reactor.receive``, as a p2p
receive routine would: the joiner decodes every block.  A faulty block is
served by a peer of its own, which connects before its height is asked
for; the stopped peers of a rejection are disconnected, an honest helper
reconnects at once.

A verdict is ``("applied", H, app_hash, store height, stored)`` or
``("rejected", H, class, commit index or None)``, the class that of what
the reactor stopped the providers for (``invalid_signature``,
``invalid_commit``, ``invalid_block``); ``("error", text)`` where the
faulty peer was not stopped or the frontier did not move.  The store's
height is read in the tick; ``stored`` is the block store read back when
the verdict is compared, after the window (``_Stored``): H's block hash
and the hash of its seen commit.
"""

from __future__ import annotations

import queue
import random
import threading
import time

from benchmarks import bsync_ref, bsyncchain, program
from benchmarks.entries.verify_commit_light import known_answers  # noqa: F401

NAME = "blocksync_catchup"
LANES = 128  # a window of 7 commits of 10 signatures
HELPERS = 4
STALL_S = 30.0  # a tick that has not moved by then is an error


def _bs(chain) -> bsyncchain.BlockChain:
    if not hasattr(chain, "bsync"):
        config, traffic = bsyncchain.cell_files(chain.chain_id)
        chain.bsync = bsyncchain.build(config, traffic, chain.seed)
        bsyncchain.spot_check(chain.bsync)
    return chain.bsync


def requests(chain) -> list:
    return _bs(chain).pool


def warmup_requests(chain) -> list:
    return _bs(chain).warm


def signatures(chain, req) -> int:
    """The distinct (key, sign-bytes, signature) triples the tick's checks
    needed and no tick before it had."""
    return req.signatures


def warm(chain) -> dict:
    """A window's commits leave in one flush: every bucket up to ``LANES``."""
    return program.warm_verify(LANES)


class _Peer:
    """A peer as the reactor sees it: ``id`` and ``try_send``; what it is
    sent goes to the receive thread, which answers for it."""

    def __init__(self, peer_id: str, serves, base: int, height: int, inbox):
        self.id = peer_id
        self.serves = serves  # height -> BlockResponse bytes
        self.base, self.height = base, height
        self.inbox = inbox

    def try_send(self, chan_id: int, msg: bytes) -> bool:
        self.inbox.put((self, msg))
        return True

    def status(self) -> bytes:
        from cometbft_tpu.libs import protoenc as pe

        return bytes([5]) + pe.t_varint(1, self.height) + pe.t_varint(2, self.base)


class _Switch:
    """What the reactor asks of a p2p switch: peers by id, a broadcast, and
    the stop of a peer for an error (recorded; an honest helper reconnects
    at once)."""

    def __init__(self, state):
        self.state = state
        self.peers: dict = {}
        self.stopped: list = []  # (peer id, error) since the last tick began

    def get_peer(self, peer_id: str):
        return self.peers.get(peer_id)

    def broadcast(self, chan_id: int, msg: bytes) -> None:
        for p in list(self.peers.values()):
            p.try_send(chan_id, msg)

    def stop_peer_for_error(self, peer, err) -> None:
        self.stopped.append((peer.id, err))
        if self.peers.pop(peer.id, None) is None:
            return
        self.state.reactor.remove_peer(peer, err)
        if peer.id.startswith("helper-"):
            self.state.connect(peer)


class _Choice(random.Random):
    """The pool's choice of a peer for a height: a faulty peer where one
    can serve it (it connected for that height alone), else seeded."""

    def choice(self, seq):
        for p in seq:
            if not p.peer_id.startswith("helper-"):
                return p
        return super().choice(seq)


class State:
    """The joiner, built in set-up, before the batch backend is resolved."""

    def __init__(self, chain):
        from cometbft_tpu.abci.kvstore import KVStoreApplication
        from cometbft_tpu.blocksync.reactor import BLOCKSYNC_CHANNEL, BlocksyncReactor
        from cometbft_tpu.config.config import MempoolConfig
        from cometbft_tpu.consensus.replay import Handshaker
        from cometbft_tpu.crypto.keys import Ed25519PubKey
        from cometbft_tpu.evidence.pool import EvidencePool
        from cometbft_tpu.mempool.clist_mempool import CListMempool
        from cometbft_tpu.proxy.multi_app_conn import AppConns, local_client_creator
        from cometbft_tpu.state.execution import BlockExecutor
        from cometbft_tpu.state.state import state_from_genesis
        from cometbft_tpu.state.store import StateStore
        from cometbft_tpu.store.block_store import BlockStore
        from cometbft_tpu.store.kv import MemKV
        from cometbft_tpu.types.basic import Timestamp
        from cometbft_tpu.types.events import EventBus
        from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
        from cometbft_tpu.types.params import (
            BlockParams, ConsensusParams, EvidenceParams, FeatureParams, ValidatorParams,
        )

        bc = _bs(chain)
        self.bc = bc
        self.chan = BLOCKSYNC_CHANNEL
        self.requests = {}  # what the harness clears after the window
        p = bc.consensus
        params = ConsensusParams(
            block=BlockParams(p["block_max_bytes"], p["block_max_gas"]),
            evidence=EvidenceParams(p["evidence_max_age_num_blocks"],
                                    p["evidence_max_age_duration_ns"],
                                    p["evidence_max_bytes"]),
            validator=ValidatorParams(tuple(p["pub_key_types"])),
            feature=FeatureParams(p["vote_extensions_enable_height"],
                                  p["pbts_enable_height"]),
        )
        gdoc = GenesisDoc(
            chain_id=bc.chain_id, genesis_time=Timestamp(0, 0),
            validators=[GenesisValidator(Ed25519PubKey(pub), power)
                        for pub, power in zip(bc.pubs, bc.powers)],
            consensus_params=params,
        )
        db = MemKV()
        self.app = KVStoreApplication()
        self.conns = AppConns(local_client_creator(self.app))
        self.conns.start()
        state_store, self.block_store = StateStore(db), BlockStore(db)
        event_bus = EventBus()
        evidence_pool = EvidencePool(db, state_store, self.block_store)
        state = Handshaker(state_store, self.block_store, gdoc, event_bus=event_bus,
                           evidence_pool=evidence_pool).handshake(
            state_from_genesis(gdoc), self.conns)
        evidence_pool.state = state
        got = [v.pub_key.bytes() for v in state.validators.validators]
        if got != bc.pubs:
            raise RuntimeError("the program orders the validator set otherwise "
                               "than the generator")
        mempool = CListMempool(MempoolConfig(recheck=False), self.conns.mempool,
                               height=state.last_block_height)
        block_exec = BlockExecutor(state_store, self.block_store, self.conns.consensus,
                                   mempool, evidence_pool=evidence_pool,
                                   event_bus=event_bus)
        self.reactor = BlocksyncReactor(
            state, block_exec, self.block_store, enabled=True,
            rng=_Choice(f"tpu-bft-bench/{bc.seed}/bsync-peers"))
        self.switch = _Switch(self)
        self.reactor.switch = self.switch
        self.inbox: "queue.SimpleQueue" = queue.SimpleQueue()
        self.arrived = threading.Event()
        self.thread = threading.Thread(target=self._receive, name="bench-p2p-recv",
                                       daemon=True)
        self.thread.start()
        honest = dict(enumerate(bc.honest))
        for k in range(HELPERS):
            self.connect(_Peer(f"helper-{k}", honest, 1, bc.top, self.inbox))
        self.faulty_at = sorted(bc.faults)
        self.faulty_next = 0  # the next faulty peer to connect

    def connect(self, peer: _Peer) -> None:
        """A peer connects: the reactor's ``add_peer``, and its status
        handled at once, so that the pool knows its range before it asks."""
        self.switch.peers[peer.id] = peer
        self.reactor.add_peer(peer)
        self.reactor.receive(self.chan, peer, peer.status())

    def connect_faulty(self, frontier: int) -> None:
        """Every faulty peer whose height the pool may ask for next: before
        it enters the pool's request window."""
        from cometbft_tpu.blocksync.pool import REQUEST_WINDOW

        while (self.faulty_next < len(self.faulty_at)
               and self.faulty_at[self.faulty_next] < frontier + 2 * REQUEST_WINDOW):
            b = self.faulty_at[self.faulty_next]
            self.faulty_next += 1
            serves = {h: self.bc.faulty[h][1] for h in (b, b + 1)
                      if self.bc.faulty.get(h, ("",))[0] == bsyncchain.peer_id(b)}
            self.connect(_Peer(bsyncchain.peer_id(b), serves, b, max(serves), self.inbox))

    def _receive(self) -> None:
        """The p2p receive routine: a peer's answer to what it was sent."""
        from cometbft_tpu.libs import protoenc as pe

        while True:
            peer, msg = self.inbox.get()
            kind = msg[0]
            if kind == 1:  # BlockRequest
                f = pe.fields_dict(msg[1:])
                height = pe.to_int64(f.get(1, [0])[-1])
                wire = peer.serves.get(height)
                if wire is None:
                    wire = bytes([3]) + pe.t_varint(1, height)  # NoBlockResponse
                self.reactor.receive(self.chan, peer, wire)
                self.arrived.set()
            elif kind == 4:  # StatusRequest
                self.reactor.receive(self.chan, peer, peer.status())


class _Stored:
    """Height H as the joiner's block store holds it, read when compared:
    ``(hash of the stored block, hash of its seen commit)``, ``None`` for
    what is missing.  The store keeps a saved height as it was, so the read
    after the window is the tick's, and costs the timed call nothing."""

    __slots__ = ("store", "height", "_read")
    __hash__ = None

    def __init__(self, store, height: int):
        self.store, self.height, self._read = store, height, None

    def read(self) -> tuple:
        if self._read is None:
            block = self.store.load_block(self.height)
            seen = self.store.load_seen_commit(self.height)
            self._read = (block.hash() if block else None, seen.hash() if seen else None)
        return self._read

    def __eq__(self, other) -> bool:
        return self.read() == other

    def __repr__(self) -> str:
        return repr(self.read())


def call(state: State, req) -> tuple:
    """The timed call: ticks until the frontier height is applied or
    rejected."""
    from cometbft_tpu.state.execution import InvalidBlockError
    from cometbft_tpu.types import validation

    r = state.reactor
    h = r.pool.height
    state.connect_faulty(h)
    state.switch.stopped.clear()
    deadline = time.perf_counter() + STALL_S
    while True:
        state.arrived.clear()
        r.tick()
        if r.pool.height > h:
            return ("applied", h, r.state.app_hash, r.block_store.height(),
                    _Stored(r.block_store, h))
        if state.switch.stopped:
            stopped = dict(state.switch.stopped)
            err = state.switch.stopped[0][1]
            if req.provider and req.provider not in stopped:
                return ("error", f"{req.provider} was not stopped at {h}")
            if isinstance(err, validation.InvalidSignatureError):
                return ("rejected", h, "invalid_signature", err.index)
            if isinstance(err, InvalidBlockError):
                return ("rejected", h, "invalid_block", None)
            if isinstance(err, validation.CommitVerificationError):
                return ("rejected", h, "invalid_commit", None)
            return ("error", f"{h}: {type(err).__name__}: {err}")
        if time.perf_counter() > deadline:
            return ("error", f"the frontier stayed at {h}")
        state.arrived.wait(0.05)


def expected(chain, req) -> tuple:
    """The verdict by construction: what the generator did to this height."""
    return req.expected


# -- the plain reference (``bsync_ref``) --------------------------------------------


def _states(chain, height: int) -> "list[bsync_ref.State]":
    """The reference's own states after 0 .. height - 1 applied heights:
    its ticks over the honest copies, with every signature taken as sound
    (a tick's signatures are judged in ``reference_verdict``)."""
    bc = _bs(chain)
    states = bc.__dict__.setdefault("ref_states", [bc.genesis()])
    while len(states) < height:
        s = states[-1]
        h = s.height + 1
        verdict, s = bsync_ref.check_tick(s, bc.honest[h], bc.honest[h + 1],
                                          lambda *a: True)
        if verdict[0] != "applied":
            raise RuntimeError(f"the reference rejects honest height {h}: {verdict}")
        states.append(s)
    return states


def _reference(chain, req, verify_sig) -> tuple:
    bc = _bs(chain)
    state = _states(chain, req.height)[req.height - 1]
    verdict, _ = bsync_ref.check_tick(state, bc.copy(req.height, req.first),
                                      bc.copy(req.height + 1, req.second), verify_sig)
    return verdict


def reference_items(chain, req) -> list:
    """The (public key, sign-bytes, signature) triples the reference's tick
    asks for when every signature holds, each once, in the order asked."""
    asked = {}

    def note(pub, msg, sig):
        asked.setdefault((pub, msg, sig))
        return True

    _reference(chain, req, note)
    return list(asked)


def reference_verdict(chain, req, bits) -> tuple:
    """The reference's tick with the plain reference's accept bits."""
    verdicts = dict(zip(reference_items(chain, req), bits))
    return _reference(chain, req, lambda pub, msg, sig: verdicts[(pub, msg, sig)])
