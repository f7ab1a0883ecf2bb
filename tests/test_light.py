"""Light client tests (reference test model: light/client_test.go,
light/verifier_test.go, light/detector_test.go)."""

import copy
import time

import pytest

from cometbft_tpu.cmd.main import main as cli_main
from cometbft_tpu.config import config as cfgmod
from cometbft_tpu.light import (
    SEQUENTIAL,
    SKIPPING,
    HTTPProvider,
    LightClient,
    LightStore,
    NodeProvider,
    TrustOptions,
)
from cometbft_tpu.light.client import ErrLightClientDivergence
from cometbft_tpu.light.provider import ErrLightBlockNotFound, Provider
from cometbft_tpu.light.verifier import LightClientError
from cometbft_tpu.node.node import Node
from cometbft_tpu.store.kv import MemKV

CHAIN_ID = "light-test-chain"


@pytest.fixture(scope="module")
def chain_node(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("light-chain")
    home = str(tmp_path / "node")
    assert cli_main(["--home", home, "init", "--chain-id", CHAIN_ID]) == 0
    cfg = cfgmod.load_config(home)
    cfg.base.home = home
    cfg.base.db_backend = "memdb"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.consensus.timeout_commit_ms = 30
    n = Node(cfg)
    n.start()
    deadline = time.monotonic() + 60
    while n.block_store.height() < 8 and time.monotonic() < deadline:
        time.sleep(0.05)
    assert n.block_store.height() >= 8
    yield n
    n.stop()


def _trust_options(provider, height=1):
    lb = provider.light_block(height)
    return TrustOptions(period_s=3600, height=height, hash=lb.hash())


class TestLightClient:
    def test_sequential_verification(self, chain_node):
        primary = NodeProvider(chain_node)
        client = LightClient(
            CHAIN_ID,
            _trust_options(primary),
            primary,
            [],
            LightStore(MemKV()),
            mode=SEQUENTIAL,
        )
        lb = client.verify_light_block_at_height(5)
        assert lb.height == 5
        # every intermediate header was verified, and the target stored
        # (reference: light/client.go verifySequential keeps the interim
        # headers in the detector's trace; updateTrustedLightBlock saves
        # the target), which the client now verifies forward from
        assert client.store.heights() == [1, 5]
        assert client.trusted_light_block() is lb

    def test_skipping_verification(self, chain_node):
        primary = NodeProvider(chain_node)
        client = LightClient(
            CHAIN_ID,
            _trust_options(primary),
            primary,
            [],
            LightStore(MemKV()),
            mode=SKIPPING,
        )
        target = chain_node.block_store.height() - 1
        lb = client.verify_light_block_at_height(target)
        assert lb.height == target
        # skipping: far fewer stored headers than heights covered
        assert len(client.store.heights()) < target

    def test_http_provider_roundtrip(self, chain_node):
        port = chain_node.rpc_server.bound_port
        primary = HTTPProvider(CHAIN_ID, f"http://127.0.0.1:{port}")
        client = LightClient(
            CHAIN_ID,
            _trust_options(primary),
            primary,
            [],
            LightStore(MemKV()),
        )
        updated = client.update()
        assert updated is not None and updated.height >= 5

    def test_bad_trust_hash_rejected(self, chain_node):
        primary = NodeProvider(chain_node)
        opts = TrustOptions(period_s=3600, height=1, hash=b"\x11" * 32)
        with pytest.raises(LightClientError):
            LightClient(CHAIN_ID, opts, primary, [], LightStore(MemKV()))

    def test_agreeing_witness_ok(self, chain_node):
        primary = NodeProvider(chain_node)
        witness = NodeProvider(chain_node)
        client = LightClient(
            CHAIN_ID,
            _trust_options(primary),
            primary,
            [witness],
            LightStore(MemKV()),
        )
        lb = client.verify_light_block_at_height(4)
        assert lb.height == 4

    def test_diverging_witness_detected(self, chain_node):
        class EvilWitness(Provider):
            """Returns the primary's block with a mutated app hash."""

            def __init__(self, inner):
                self.inner = inner

            def chain_id(self):
                return self.inner.chain_id()

            def light_block(self, height):
                lb = self.inner.light_block(height)
                forged = copy.deepcopy(lb)
                forged.signed_header.header.app_hash = b"\xde\xad" * 16
                return forged

            def report_evidence(self, ev):
                pass

        primary = NodeProvider(chain_node)
        client = LightClient(
            CHAIN_ID,
            _trust_options(primary),
            primary,
            [EvilWitness(NodeProvider(chain_node))],
            LightStore(MemKV()),
        )
        with pytest.raises(ErrLightClientDivergence):
            client.verify_light_block_at_height(3)
        # the disputed header must NOT have entered the trusted store
        assert client.store.light_block(3) is None

    def test_prune(self, chain_node):
        primary = NodeProvider(chain_node)
        client = LightClient(
            CHAIN_ID,
            _trust_options(primary),
            primary,
            [],
            LightStore(MemKV()),
            mode=SEQUENTIAL,
        )
        client.verify_light_block_at_height(6)
        client.prune(keep=2)
        assert client.store.size() == 2
