"""A joiner's blocksync frontier (``BlocksyncReactor.tick``) against the
plain reference (``cometbft_tpu/blocksync/reference.py``) over every fault
class, seeded, at 4 validators and 40 heights.

The chain is made by the program (``make_block``, a producer's
``apply_block``, precommits signed through a ``VoteSet``); the faulty copies
alter it as a faulty peer would: a LastCommit signature inside the light
prefix, one past it (the header rehashed and the next commit signed over
it), a transaction after the header was made (the next commit signed over
the new part set).  The joiner takes the blocks as wire bytes from
in-process peers, a faulty copy from a peer of its own, on the CPU backend
and on the served path (a trusted ``tpu`` backend whose device runner is
the host oracle, the scheduler on).  The reference replays a joiner over the
same copies in the order they were received.
"""

import copy
import hashlib
import random

import numpy as np
import pytest

from cometbft_tpu import verifysched
from cometbft_tpu.blocksync import reference
from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.libs import protoenc as pe
from cometbft_tpu.ops import sha256_tree, supervisor
from cometbft_tpu.state.execution import InvalidBlockError, make_block
from cometbft_tpu.types import codec, validation
from cometbft_tpu.types.basic import PRECOMMIT_TYPE, BlockID, Timestamp
from cometbft_tpu.types.block import empty_commit
from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
from cometbft_tpu.types.vote import Vote
from cometbft_tpu.types.vote_set import VoteSet

CHAIN_ID = "bsync-ref-test"
N_VALS = 4
HEIGHTS = 40
FAULT_AT = 20
CLASSES = ("honest", "prefix", "past", "body")


def _oracle_runner(backend, pubs, msgs, sigs, lanes):
    out = np.zeros(lanes, dtype=bool)
    out[: len(pubs)] = [
        ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)
    ]
    return out


@pytest.fixture(params=["cpu", "served"])
def backend(request, monkeypatch):
    monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", request.param.replace("served", "tpu"))
    monkeypatch.delenv("COMETBFT_TPU_VERIFY_SCHED", raising=False)
    supervisor.set_device_runner(_oracle_runner)
    sha256_tree.set_tree_runner(sha256_tree.host_tree_runner)
    cbatch.set_default_backend(None)
    verifysched.reset_scheduler()
    sigcache.reset_cache()
    yield request.param
    verifysched.reset_scheduler()
    cbatch.set_default_backend(None)
    supervisor.clear_device_runner()
    sha256_tree.clear_tree_runner()
    sigcache.reset_cache()


def _node(gdoc):
    """A node's block executor over MemKV stores and the kvstore app, as
    the joiner of a chain needs one, and its state after InitChain."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config.config import MempoolConfig
    from cometbft_tpu.consensus.replay import Handshaker
    from cometbft_tpu.mempool.clist_mempool import CListMempool
    from cometbft_tpu.proxy.multi_app_conn import AppConns, local_client_creator
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.state import state_from_genesis
    from cometbft_tpu.state.store import StateStore
    from cometbft_tpu.store.block_store import BlockStore
    from cometbft_tpu.store.kv import MemKV

    db = MemKV()
    conns = AppConns(local_client_creator(KVStoreApplication()))
    conns.start()
    state_store, block_store = StateStore(db), BlockStore(db)
    state = Handshaker(state_store, block_store, gdoc).handshake(
        state_from_genesis(gdoc), conns)
    mempool = CListMempool(MempoolConfig(recheck=False), conns.mempool,
                           height=state.last_block_height)
    return BlockExecutor(state_store, block_store, conns.consensus, mempool), \
        block_store, state


def _sign(privs, vals, height, bid, times):
    vs = VoteSet(CHAIN_ID, height, 0, PRECOMMIT_TYPE, vals)
    for p, t in zip(privs, times):
        addr = p.pub_key().address()
        idx = vals.get_by_address(addr)[0]
        v = Vote(type_=PRECOMMIT_TYPE, height=height, round_=0, block_id=bid,
                 timestamp=t, validator_address=addr, validator_index=idx)
        v.signature = p.sign(v.sign_bytes(CHAIN_ID))
        vs.add_vote(v, verify=False)
    return vs.make_commit()


def _wire(block) -> bytes:
    return bytes([2]) + pe.t_message(1, codec.encode_block(block), always=True)


def _block_id(block) -> BlockID:
    return BlockID(hash=block.hash(), part_set_header=block.make_part_set().header)


def _chain(kind: str, seed: int):
    """(genesis doc, honest blocks by height, the faulty copies by height,
    the producer's app hash after each height, the fault)."""
    rng = random.Random(f"bsync-ref/{seed}")
    privs = [Ed25519PrivKey.from_seed(hashlib.sha256(b"bsr%d/%d" % (seed, i)).digest())
             for i in range(N_VALS)]
    gdoc = GenesisDoc(chain_id=CHAIN_ID, genesis_time=Timestamp(0, 0),
                      validators=[GenesisValidator(p.pub_key(), 10) for p in privs])
    block_exec, _, state = _node(gdoc)
    vals = state.validators
    blocks, commits, app_hashes = {}, {}, {0: state.app_hash}
    last = empty_commit()
    for h in range(1, HEIGHTS + 2):
        txs = [b"acct%d=%d-%d" % (rng.randrange(6), h, j) for j in range(5)]
        proposer = vals.validators[h % N_VALS].address
        block = make_block(h, txs, last, state, proposer, Timestamp(1_700_000_000 + h, 0))
        bid = _block_id(block)
        state = block_exec.apply_block(state, bid, block)
        app_hashes[h] = state.app_hash
        times = [Timestamp(1_700_000_000 + h, 1 + rng.randrange(10**8)) for _ in privs]
        commits[h] = (_sign(privs, vals, h, bid, times), times)
        blocks[h] = block
        last = commits[h][0]
    faulty, fault = {}, None
    b = FAULT_AT
    if kind != "honest":
        bad = copy.deepcopy(blocks[b])
        index = {"prefix": rng.randrange(3), "past": 3, "body": None}[kind]
        if kind == "body":
            tx = bad.data.txs[2]
            bad.data.txs[2] = tx[:-1] + (b"0" if tx[-1:] != b"0" else b"1")
        else:
            cs = bad.last_commit.signatures[index]
            cs.signature = cs.signature[:32] + bytes([cs.signature[32] ^ 1]) + cs.signature[33:]
            if kind == "past":  # the header made over the altered LastCommit
                bad.header.last_commit_hash = bad.last_commit.hash()
        faulty[b] = bad
        if kind in ("past", "body"):  # the next commit signed over the bad copy
            nxt = copy.deepcopy(blocks[b + 1])
            nxt.last_commit = _sign(privs, vals, b, _block_id(bad), commits[b][1])
            nxt.header.last_block_id = _block_id(bad)
            nxt.header.last_commit_hash = nxt.last_commit.hash()
            faulty[b + 1] = nxt
        fault = (kind, index)
    return gdoc, blocks, faulty, app_hashes, fault


class _Peer:
    def __init__(self, joiner, peer_id, blocks, base, top):
        self.joiner, self.id, self.blocks = joiner, peer_id, blocks
        self.base, self.top = base, top

    def status(self) -> bytes:
        return bytes([5]) + pe.t_varint(1, self.top) + pe.t_varint(2, self.base)

    def try_send(self, chan_id, msg) -> bool:
        """Answered at once, as the joiner's receive routine would hand the
        peer's answer to the reactor."""
        r = self.joiner.reactor
        if msg[0] == 1:
            h = pe.to_int64(pe.fields_dict(msg[1:]).get(1, [0])[-1])
            r.receive(chan_id, self, _wire(self.blocks[h]))
        elif msg[0] == 4:
            r.receive(chan_id, self, self.status())
        return True


class _Joiner:
    """The reactor with its pool, fed by two honest helpers and, for a
    faulty copy, a peer of its own; a stopped helper reconnects."""

    def __init__(self, gdoc, blocks, faulty):
        from cometbft_tpu.blocksync.reactor import BLOCKSYNC_CHANNEL, BlocksyncReactor

        block_exec, block_store, state = _node(gdoc)

        class Choice(random.Random):
            def choice(self, seq):
                return next((p for p in seq if p.peer_id == "faulty"), None) or \
                    super().choice(seq)

        self.reactor = BlocksyncReactor(state, block_exec, block_store,
                                        rng=Choice(7))
        self.reactor.switch = self
        self.peers, self.stopped = {}, []
        top = max(blocks)
        for k in range(2):
            self.connect(_Peer(self, f"helper-{k}", blocks, 1, top))
        if faulty:
            self.connect(_Peer(self, "faulty", faulty, min(faulty), max(faulty)))
        self.chan = BLOCKSYNC_CHANNEL

    def connect(self, peer):
        self.peers[peer.id] = peer
        self.reactor.add_peer(peer)

    # the switch
    def get_peer(self, peer_id):
        return self.peers.get(peer_id)

    def broadcast(self, chan_id, msg):
        for p in list(self.peers.values()):
            p.try_send(chan_id, msg)

    def stop_peer_for_error(self, peer, err):
        self.stopped.append((peer.id, err))
        if self.peers.pop(peer.id, None) is not None:
            self.reactor.remove_peer(peer, err)
            if peer.id != "faulty":
                self.connect(peer)

    def tick(self) -> tuple:
        """Ticks until the frontier height is applied or rejected; an
        applied height with the block store read back."""
        r, h = self.reactor, self.reactor.pool.height
        self.stopped.clear()
        for _ in range(50):
            r.tick()
            if r.pool.height > h:
                bs = r.block_store
                block, seen = bs.load_block(h), bs.load_seen_commit(h)
                return ("applied", h, r.state.app_hash, bs.height(),
                        (block and block.hash(), seen and seen.hash()))
            if self.stopped:
                err = self.stopped[0][1]
                assert "faulty" in dict(self.stopped), self.stopped
                if isinstance(err, validation.InvalidSignatureError):
                    return ("rejected", h, "invalid_signature", err.index)
                if isinstance(err, InvalidBlockError):
                    return ("rejected", h, "invalid_block", None)
                assert isinstance(err, validation.CommitVerificationError), err
                return ("rejected", h, "invalid_commit", None)
        raise AssertionError(f"the frontier stayed at {h}")


def _expected(kind, fault, blocks, faulty, app_hashes):
    """The ticks by construction.  An applied height stores the honest
    block and, as its seen commit, the LastCommit of the copy of the next
    height the joiner held: the faulty peer's, where it serves that height
    and a rejection has not stopped it yet."""
    out = []
    for h in range(1, HEIGHTS):
        if kind == "prefix" and h == FAULT_AT - 1:
            out.append(("rejected", h, "invalid_signature", fault[1]))
        if h == FAULT_AT and kind in ("past", "body"):
            out.append(("rejected", h, "invalid_signature", 3) if kind == "past"
                       else ("rejected", h, "invalid_block", None))
        held = faulty[h + 1] if h + 1 == FAULT_AT and kind in ("past", "body") else blocks[h + 1]
        out.append(("applied", h, app_hashes[h], h,
                    (blocks[h].hash(), held.last_commit.hash())))
    return out


@pytest.mark.parametrize("kind", CLASSES)
def test_the_reactor_against_the_reference(backend, kind):
    from cometbft_tpu.verifysched import stats as sstats

    gdoc, blocks, faulty, app_hashes, fault = _chain(kind, seed=11)
    sigcache.reset_cache()  # a joiner's cache is cold: the producer's is not
    sstats.reset()
    joiner = _Joiner(gdoc, blocks, faulty)
    want = _expected(kind, fault, blocks, faulty, app_hashes)
    got = [joiner.tick() for _ in want]
    assert got == want
    assert joiner.reactor.block_store.height() == HEIGHTS - 1
    # the reference over the same copies, in the order they came
    served = {h: [_wire(b)] for h, b in blocks.items()}
    for h, b in faulty.items():
        served[h] = [_wire(b)] + served[h]
    genesis = reference.genesis(
        CHAIN_ID, [(v.pub_key.bytes(), v.voting_power)
                   for v in joiner.reactor.state.validators.validators],
        1, gdoc.consensus_params.hash())
    assert reference.replay(genesis, served, len(want), ref.verify_zip215) == want
    if backend == "served" and kind == "honest":
        # the windows rode the scheduler: a flush carried several heights
        assert 0 < sum(sstats.snapshot()["flushes"].values()) < HEIGHTS // 3


def test_benchmarks_copy_is_the_reference():
    """``benchmarks/bsync_ref.py`` is the reference, byte for byte."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cometbft_tpu", "blocksync", "reference.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(root, "benchmarks", "bsync_ref.py"), "rb") as f:
        assert f.read() == mine
