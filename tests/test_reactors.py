"""Multi-node integration over real TCP sockets: consensus gossip,
mempool gossip, evidence gossip (reference test model:
internal/consensus/reactor_test.go, mempool/reactor_test.go,
internal/evidence/reactor_test.go, node/node_test.go).
"""

import hashlib
import os
import time

import pytest

from cometbft_tpu.config.config import Config
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.node.node import Node
from cometbft_tpu.privval.file_pv import FilePV
from cometbft_tpu.types.basic import Timestamp
from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

CHAIN_ID = "reactor-test-chain"
N_VALS = 3


def _wait_for(cond, timeout=30.0, tick=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(tick)
    return False


def _make_node_home(tmp_path, i: int, gdoc: GenesisDoc, priv) -> Config:
    home = str(tmp_path / f"node{i}")
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)
    with open(os.path.join(home, "config", "genesis.json"), "w") as f:
        f.write(gdoc.to_json())
    pv = FilePV(
        priv,
        os.path.join(home, "config", "priv_validator_key.json"),
        os.path.join(home, "data", "priv_validator_state.json"),
    )
    pv.save()

    cfg = Config()
    cfg.base.home = home
    cfg.base.moniker = f"node{i}"
    cfg.base.db_backend = "memdb"
    cfg.rpc.laddr = ""  # no RPC in these tests
    cfg.p2p.laddr = "tcp://127.0.0.1:0"  # auto-assign port
    cfg.p2p.allow_duplicate_ip = True
    cfg.consensus.timeout_propose_ms = 2000
    cfg.consensus.timeout_propose_delta_ms = 500
    cfg.consensus.timeout_vote_ms = 1000
    cfg.consensus.timeout_vote_delta_ms = 500
    cfg.consensus.timeout_commit_ms = 100
    cfg.mempool.recheck = False
    return cfg


@pytest.fixture(scope="module")
def net(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("reactor-net")
    privs = [
        Ed25519PrivKey.from_seed(hashlib.sha256(b"reactval%d" % i).digest())
        for i in range(N_VALS)
    ]
    gdoc = GenesisDoc(
        chain_id=CHAIN_ID,
        genesis_time=Timestamp(0, 0),
        validators=[GenesisValidator(p.pub_key(), 10) for p in privs],
    )
    nodes = []
    try:
        # start node 0 first to learn its address
        cfg0 = _make_node_home(tmp_path, 0, gdoc, privs[0])
        n0 = Node(cfg0)
        n0.start()
        nodes.append(n0)
        addr0 = n0.switch.transport.listen_addr
        peer0 = f"{n0.node_key.node_id}@127.0.0.1:{addr0[1]}"

        for i in range(1, N_VALS):
            cfg = _make_node_home(tmp_path, i, gdoc, privs[i])
            cfg.p2p.persistent_peers = [peer0]
            n = Node(cfg)
            n.start()
            nodes.append(n)
        yield nodes
    finally:
        for n in nodes:
            try:
                n.stop()
            except Exception:  # noqa: BLE001
                pass


class TestConsensusGossip:
    def test_all_nodes_make_blocks(self, net):
        assert _wait_for(
            lambda: all(n.consensus.height >= 3 for n in net), timeout=60
        ), f"heights: {[n.consensus.height for n in net]}"

    def test_peers_connected(self, net):
        # node1 and node2 discover each other through PEX via node0
        counts = [len(n.switch.peers_list()) for n in net]
        assert counts[0] >= 2
        assert all(c >= 1 for c in counts)


class TestMempoolGossip:
    def test_tx_submitted_on_one_node_commits_everywhere(self, net):
        tx = b"gossip-key=gossip-value"
        net[1].mempool.check_tx(tx)

        def committed_on(n):
            h = n.block_store.height()
            for height in range(max(n.block_store.base(), 1), h + 1):
                block = n.block_store.load_block(height)
                if block is not None and tx in block.data.txs:
                    return True
            return False

        assert _wait_for(
            lambda: all(committed_on(n) for n in net), timeout=60
        ), "tx did not commit on all nodes"


class TestEvidenceGossip:
    def test_evidence_gossips_and_commits(self, net):
        from cometbft_tpu.types.basic import (
            PRECOMMIT_TYPE,
            BlockID,
            PartSetHeader,
        )
        from cometbft_tpu.types.evidence import DuplicateVoteEvidence
        from cometbft_tpu.types.vote import Vote

        # wait for some committed height so the evidence is verifiable
        assert _wait_for(lambda: net[0].consensus.height >= 2, timeout=60)

        byz_priv = Ed25519PrivKey.from_seed(
            hashlib.sha256(b"reactval0").digest()
        )
        addr = byz_priv.pub_key().address()
        state = net[1].consensus.state
        vals = net[1].state_store.load_validators(1)
        idx, val = vals.get_by_address(addr)
        meta = net[1].block_store.load_block_meta(1)

        def mkvote(tag: bytes) -> Vote:
            v = Vote(
                type_=PRECOMMIT_TYPE,
                height=1,
                round_=0,
                block_id=BlockID(
                    hash=hashlib.sha256(tag).digest(),
                    part_set_header=PartSetHeader(
                        1, hashlib.sha256(tag + b"p").digest()
                    ),
                ),
                timestamp=meta.header.time,
                validator_address=addr,
                validator_index=idx,
            )
            v.signature = byz_priv.sign(v.sign_bytes(CHAIN_ID))
            return v

        ev = DuplicateVoteEvidence.from_votes(
            mkvote(b"fork-a"),
            mkvote(b"fork-b"),
            meta.header.time,
            val.voting_power,
            vals.total_voting_power(),
        )
        net[1].evidence_pool.add_evidence(ev)

        # the evidence should gossip to other pools and land in a block
        def pool_has(n):
            return any(
                e.hash() == ev.hash() for e in n.evidence_pool.all_pending()
            ) or n.evidence_pool._is_committed(ev)

        assert _wait_for(lambda: all(pool_has(n) for n in net), timeout=30)

        def committed_in_block(n):
            for height in range(1, n.block_store.height() + 1):
                block = n.block_store.load_block(height)
                if block and any(e.hash() == ev.hash() for e in block.evidence):
                    return True
            return False

        assert _wait_for(
            lambda: all(committed_in_block(n) for n in net), timeout=60
        ), "evidence did not commit on all nodes"


class TestBlocksync:
    @pytest.mark.slow  # wall-clock blocksync on live threads
    def test_late_joiner_blocksyncs_to_head(self, net, tmp_path):
        """A fresh non-validator node joins after the chain has advanced and
        catches up via the blocksync pool (two-block verify pipeline)."""
        assert _wait_for(lambda: net[0].consensus.height >= 5, timeout=60)
        target = net[0].block_store.height()

        gdoc_json = open(
            os.path.join(net[0].config.base.home, "config", "genesis.json")
        ).read()
        from cometbft_tpu.types.genesis import GenesisDoc

        gdoc = GenesisDoc.from_json(gdoc_json)
        joiner_priv = Ed25519PrivKey.generate()  # NOT a validator
        cfg = _make_node_home(tmp_path, 99, gdoc, joiner_priv)
        addr0 = net[0].switch.transport.listen_addr
        cfg.p2p.persistent_peers = [
            f"{net[0].node_key.node_id}@127.0.0.1:{addr0[1]}"
        ]
        joiner = Node(cfg)
        joiner.start()
        try:
            assert joiner.blocksync_reactor.syncing  # started in sync mode
            assert _wait_for(
                lambda: joiner.block_store.height() >= target, timeout=60
            ), (
                f"joiner at {joiner.block_store.height()}, target {target}"
            )
            # after catchup it must have switched to consensus and follow live
            assert _wait_for(
                lambda: not joiner.blocksync_reactor.syncing, timeout=30
            )
            live_target = net[0].block_store.height() + 2
            assert _wait_for(
                lambda: joiner.block_store.height() >= live_target, timeout=60
            ), "joiner does not follow live consensus after blocksync"
        finally:
            joiner.stop()


class TestBlocksyncBodyValidation:
    """A malicious peer can pair a legitimately signed header with a
    tampered body — the commit only covers the header hash.  Blocksync must
    fully validate the block before applying (ADVICE r1 high; reference:
    internal/blocksync/reactor.go:546 ValidateBlock)."""

    def _mk_signed_block(self, state, privs, height, last_block_id, last_commit):
        from cometbft_tpu.state.execution import consensus_params_hash
        from cometbft_tpu.types.basic import (
            PRECOMMIT_TYPE,
            BlockID,
        )
        from cometbft_tpu.types.block import (
            Block,
            ConsensusVersion,
            Data,
            Header,
        )
        from cometbft_tpu.types.vote import Vote
        from cometbft_tpu.types.vote_set import VoteSet

        vals = state.validators
        header = Header(
            version=ConsensusVersion(11, state.version_app),
            chain_id=state.chain_id,
            height=height,
            time=Timestamp(1700000000 + height, 0),
            last_block_id=last_block_id,
            validators_hash=vals.hash(),
            next_validators_hash=state.next_validators.hash(),
            consensus_hash=consensus_params_hash(state.consensus_params),
            app_hash=state.app_hash,
            last_results_hash=state.last_results_hash,
            proposer_address=vals.get_proposer().address,
        )
        block = Block(
            header=header,
            data=Data(txs=[b"tx-%d" % height]),
            last_commit=last_commit,
        )
        ps = block.make_part_set()
        bid = BlockID(hash=block.hash(), part_set_header=ps.header)
        vs = VoteSet(state.chain_id, height, 0, PRECOMMIT_TYPE, vals)
        for p in privs:
            addr = p.pub_key().address()
            idx = vals.get_by_address(addr)[0]
            v = Vote(
                type_=PRECOMMIT_TYPE,
                height=height,
                round_=0,
                block_id=bid,
                timestamp=Timestamp(1700000000 + height, 1),
                validator_address=addr,
                validator_index=idx,
            )
            v.signature = p.sign(v.sign_bytes(state.chain_id))
            vs.add_vote(v)
        return block, bid, vs.make_commit()

    def test_tampered_body_banned_not_applied(self):
        from cometbft_tpu.blocksync.reactor import BlocksyncReactor
        from cometbft_tpu.state.execution import BlockExecutor
        from cometbft_tpu.state.state import state_from_genesis
        from cometbft_tpu.types.basic import BlockID
        from cometbft_tpu.types.block import empty_commit

        privs = [
            Ed25519PrivKey.from_seed(hashlib.sha256(b"bsv%d" % i).digest())
            for i in range(4)
        ]
        gdoc = GenesisDoc(
            chain_id="bs-body-chain",
            genesis_time=Timestamp(0, 0),
            validators=[GenesisValidator(p.pub_key(), 10) for p in privs],
        )
        state = state_from_genesis(gdoc)
        b1, bid1, c1 = self._mk_signed_block(
            state, privs, 1, BlockID(), empty_commit()
        )
        # block 2 only matters for its last_commit over block 1
        b2 = type(b1)(
            header=b1.header, data=b1.data, last_commit=c1, evidence=[]
        )

        # tamper block 1's body AFTER signing; wire-carried header hashes
        # stay those of the original body (fill_header_hashes fills only
        # empty fields, like a decode does)
        b1.data.txs = [b"forged-tx"]

        class FakePool:
            def __init__(self):
                self.redone = []

            def peek_two_blocks(self):
                return b1, b2, "peer1", "peer2", None

            def redo_request(self, h):
                self.redone.append(h)

        class ExplodingStore:
            def height(self):
                return 0

            def save_block(self, *a, **k):
                raise AssertionError("tampered block must not be saved")

        exec_ = BlockExecutor(None, None, None, None)
        r = BlocksyncReactor(state, exec_, ExplodingStore(), enabled=True)
        r.pool = FakePool()
        assert r._process_blocks() is True  # handled (rejected + redo)
        assert r.pool.redone == [1, 2]


class TestConsensusHandOff:
    """A node still in blocksync drops what a peer sends for the height it
    announced, and the peer marks it delivered: so it announces nothing
    until the hand-off (reference: reactor.go AddPeer)."""

    @staticmethod
    def _reactor(wait_sync):
        import threading
        from types import SimpleNamespace

        from cometbft_tpu.consensus.reactor import ConsensusReactor

        cs = SimpleNamespace(
            rs=SimpleNamespace(
                height=1, round_=0, step=1, start_time=time.time(),
                last_commit=None, votes=None,
            ),
            _mtx=threading.Lock(),
            _started=True,
            add_step_listener=lambda fn: None,
            add_vote_listener=lambda fn: None,
            update_to_state=lambda state: None,
        )
        return ConsensusReactor(cs, block_store=None, wait_sync=wait_sync)

    @staticmethod
    def _peer(sent):
        from types import SimpleNamespace

        return SimpleNamespace(
            id="peer0", is_running=False, set=lambda k, v: None,
            try_send=lambda chan, msg: sent.append(chan) or True,
        )

    def test_add_peer_is_silent_while_syncing_and_speaks_after(self):
        from cometbft_tpu.consensus.reactor import STATE_CHANNEL

        sent = []
        r = self._reactor(wait_sync=True)
        r.add_peer(self._peer(sent))
        assert sent == []
        r.switch_to_consensus(state=None)
        r.add_peer(self._peer(sent))
        assert sent == [STATE_CHANNEL]
