"""The light client's SEQUENTIAL sync (ISSUE 38) against the plain reference
(``cometbft_tpu/light/sequential_reference.py``, a loop of
``light/reference.verify_adjacent``: light/client.go:608 verifySequential),
seeded, at 8 and 16 validators, through the served path: the scheduler on,
the device stubbed as ``tests/test_light_reference.py`` stubs it.

A request is ``LightClient(mode=SEQUENTIAL).verify_light_block_at_height``
from the block the client holds; its verdict is that of the FIRST header
that is not accepted, with its height (``ErrVerificationFailed.to``).  Also
here: the client as upstream's (the trusted block in memory, the target the
only block saved), ``LightStore.light_block_before`` by bisection, the
spans, and ``cometbft light --sequential``."""

import copy
import dataclasses
import hashlib
import os
import re

import pytest

from cometbft_tpu.light import LightClient, LightStore, SEQUENTIAL, TrustOptions
from cometbft_tpu.light import reference, sequential_reference, verifier
from cometbft_tpu.light.provider import Provider
from cometbft_tpu.libs import tracing
from cometbft_tpu.store.kv import MemKV
from cometbft_tpu.types import validation
from cometbft_tpu.types.basic import BlockID, PartSetHeader, Timestamp
from cometbft_tpu.types.block import Commit, ConsensusVersion, Header
from cometbft_tpu.types.light import LightBlock, SignedHeader
from cometbft_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, CommitSig
from cometbft_tpu.verifysched import stats as sstats
from tests.test_light_reference import (  # noqa: F401 — the fixture
    BASE_NS,
    CHAIN,
    PERIOD_S,
    Universe,
    _flip,
    _h,
    device_stub,
    plain,
)

ROOT = 10  # the height the client is initialized at
SIZES = (8, 16)


def make_block(u: Universe, height: int, ids, next_ids, names=None) -> LightBlock:
    """A block of the set ``ids`` that announces ``next_ids``, signed by
    every member; ``names`` puts another root in ``validators_hash`` (and
    the commit signs that header: only the link is broken)."""
    vals = u.vals(ids)
    t_ns = BASE_NS + height * 10**9
    header = Header(
        ConsensusVersion(11, 1), CHAIN, height, Timestamp.from_ns(t_ns),
        BlockID(_h(b"last", height), PartSetHeader(1, _h(b"lp", height))),
        last_commit_hash=_h(b"lc", height), data_hash=_h(b"d", height),
        validators_hash=names or vals.hash(),
        next_validators_hash=u.vals(next_ids).hash(),
        consensus_hash=_h(b"c", 0), app_hash=_h(b"a", height),
        last_results_hash=_h(b"r", height),
        evidence_hash=hashlib.sha256(b"").digest(),
        proposer_address=vals.validators[0].address,
    )
    bid = BlockID(header.hash(), PartSetHeader(1, _h(b"p", height)))
    commit = Commit(height, 0, bid, [
        CommitSig(BLOCK_ID_FLAG_COMMIT, v.address,
                  Timestamp.from_ns(t_ns + 1 + u.rng.randrange(10**8)), b"")
        for v in vals.validators
    ])
    for i in range(len(commit.signatures)):
        u.sign(commit, i)
    return LightBlock(SignedHeader(header, commit), vals)


class Chain:
    """Heights ROOT .. ROOT + length, one validator replaced at each height
    of ``changes``; ``blocks`` what an honest primary serves."""

    def __init__(self, n: int, length: int, changes=(), seed: int = 0):
        self.u = Universe(1000 * seed + n, 2 * n)
        ids = self.u.rng.sample(range(2 * n), n)
        self.ids = {}
        for h in range(ROOT, ROOT + length + 2):
            if h in changes:
                ids = self.u.replace(ids, 1)
            self.ids[h] = ids
        self.blocks = {
            h: make_block(self.u, h, self.ids[h], self.ids[h + 1])
            for h in range(ROOT, ROOT + length + 1)
        }

    def flip(self, height: int, index: int) -> None:
        _flip(self.blocks[height].signed_header.commit, index)

    def break_link(self, height: int, link: str) -> None:
        """Break ONE hash link of the block at ``height``, every signature
        sound (the three of the cell's traffic)."""
        lb = self.blocks[height]
        if link == "header_hash":  # the header sent is not the one signed
            header = dataclasses.replace(
                lb.signed_header.header, app_hash=_h(b"another-app", height))
            lb.signed_header = SignedHeader(header, lb.signed_header.commit)
        elif link == "validators_hash":  # the header names another set
            self.blocks[height] = make_block(
                self.u, height, self.ids[height], self.ids[height + 1],
                names=_h(b"another-set", height))
        else:  # a set the block before did not announce, signing for itself
            self.blocks[height] = make_block(
                self.u, height, self.u.replace(self.ids[height], 1),
                self.ids[height + 1])


class Primary(Provider):
    def __init__(self, blocks):
        self.blocks = blocks
        self.asked = []

    def chain_id(self):
        return CHAIN

    def light_block(self, height):
        self.asked.append(height)
        return self.blocks[height]


def client_of(chain: Chain) -> LightClient:
    root = chain.blocks[ROOT]
    return LightClient(CHAIN, TrustOptions(PERIOD_S, ROOT, root.hash()),
                       Primary(chain.blocks), [], LightStore(MemKV()),
                       mode=SEQUENTIAL)


def now_for(chain: Chain, target: int, after: float = 5.0) -> float:
    return chain.blocks[target].signed_header.header.time.to_ns() / 1e9 + after


def verdict_of(err: Exception) -> tuple:
    if isinstance(err, validation.InvalidSignatureError):
        return ("invalid_signature", err.index)
    for cls, name in ((verifier.ErrOldHeaderExpired, "expired"),
                      (verifier.ErrInvalidHeader, "invalid_header"),
                      (validation.CommitVerificationError, "invalid_commit")):
        if isinstance(err, cls):
            return (name,)
    raise err


def program_sync(client: LightClient, target: int, now_s: float) -> tuple:
    try:
        client.verify_light_block_at_height(target, now_s)
    except verifier.ErrVerificationFailed as e:
        assert e.from_height == e.to - 1
        return verdict_of(e.reason), e.to
    return ("accepted",)


def reference_sync(chain: Chain, trusted: int, target: int, now_s: float) -> tuple:
    got = sequential_reference.verify_sequential(
        CHAIN, plain(chain.blocks[trusted]),
        [plain(chain.blocks[h]) for h in range(trusted + 1, target + 1)],
        PERIOD_S, now_s)
    if got == ("accepted",):
        return got
    verdict, height = got
    return (verdict if verdict[0] == "invalid_signature" else verdict[:1]), height


def _case(kind: str, n: int):
    """(chain, target, now, verdict wanted) of one request class: a run of
    11 headers from ROOT (a window of 8, then 3), one set change inside
    the first window."""
    chain = Chain(n, 12, changes=(ROOT + 3,), seed=len(kind))
    target, after = ROOT + 11, 5.0
    prefix = n * 2 // 3 + 1
    bad = ROOT + 4
    want = ("accepted",)
    if kind == "tampered":
        index = chain.u.rng.randrange(prefix)
        chain.flip(bad, index)
        want = (("invalid_signature", index), bad)
    elif kind.startswith("broken_"):
        chain.break_link(bad, kind[len("broken_"):])
        want = (("invalid_header",), bad)
    elif kind == "tampered_in_the_second_window":
        bad = ROOT + 10
        chain.flip(bad, 0)
        want = (("invalid_signature", 0), bad)
    elif kind == "address_mismatch":
        sigs = chain.blocks[bad].signed_header.commit.signatures
        sigs[1].validator_address = sigs[0].validator_address
        want = (("invalid_commit",), bad)
    elif kind == "expired_trusted_header":
        after = PERIOD_S + 1.0
        want = (("expired",), ROOT + 1)
    elif kind == "header_from_the_future":
        after = -3600.0
        want = (("invalid_header",), ROOT + 1)
    return chain, target, now_for(chain, target, after), want


CLASSES = (
    "honest", "tampered", "broken_header_hash", "broken_validators_hash",
    "broken_next_validators_hash", "tampered_in_the_second_window",
    "address_mismatch", "expired_trusted_header", "header_from_the_future",
)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("kind", CLASSES)
def test_program_equals_reference(device_stub, kind, n):
    chain, target, now_s, want = _case(kind, n)
    client = client_of(chain)
    got = program_sync(client, target, now_s)
    assert got == reference_sync(chain, ROOT, target, now_s) == want
    if got == ("accepted",):
        assert client.latest_trusted_block.height == target
    else:  # nothing of a failed request is trusted
        assert client.latest_trusted_block.height == ROOT
        assert client.store.heights() == [ROOT]


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("second", ["broken_header_hash", "tampered"])
def test_two_bad_headers_in_one_window_the_first_by_height_wins(device_stub, n, second):
    """A signature error at ROOT + 3, then a structural one (or another
    signature error) at ROOT + 5, inside one window: the first wins, as a
    loop of ``verify_adjacent`` gives it."""
    chain = Chain(n, 8, seed=7)
    chain.flip(ROOT + 3, 2)
    if second == "tampered":
        chain.flip(ROOT + 5, 0)
    else:
        chain.break_link(ROOT + 5, "header_hash")
    now_s = now_for(chain, ROOT + 8)
    want = (("invalid_signature", 2), ROOT + 3)
    assert program_sync(client_of(chain), ROOT + 8, now_s) == want
    assert reference_sync(chain, ROOT, ROOT + 8, now_s) == want


@pytest.mark.parametrize("n", SIZES)
def test_a_structural_error_before_a_signature_error(device_stub, n):
    chain = Chain(n, 8, seed=9)
    chain.break_link(ROOT + 2, "next_validators_hash")
    chain.flip(ROOT + 6, 1)
    now_s = now_for(chain, ROOT + 8)
    want = (("invalid_header",), ROOT + 2)
    assert program_sync(client_of(chain), ROOT + 8, now_s) == want
    assert reference_sync(chain, ROOT, ROOT + 8, now_s) == want


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("headers", [1, 2])
def test_one_and_two_headers(device_stub, n, headers):
    chain = Chain(n, headers, seed=headers)
    now_s = now_for(chain, ROOT + headers)
    assert program_sync(client_of(chain), ROOT + headers, now_s) == ("accepted",)
    chain.flip(ROOT + headers, 0)
    want = (("invalid_signature", 0), ROOT + headers)
    assert program_sync(client_of(chain), ROOT + headers, now_s) == want
    assert reference_sync(chain, ROOT, ROOT + headers, now_s) == want


def test_requests_go_forward_from_the_block_the_client_holds(device_stub):
    """Three requests, the second rejected: the client stays, verifies the
    next request forward from the same block, re-reads nothing from the
    store, saves only each accepted target; the headers the failed request
    verified are cache hits for the next."""
    chain = Chain(8, 20, changes=(ROOT + 5, ROOT + 13), seed=3)
    good = copy.deepcopy(chain.blocks[ROOT + 9])
    chain.flip(ROOT + 9, 1)
    client = client_of(chain)
    client.store.light_block_before = None  # not called on this path
    assert program_sync(client, ROOT + 6, now_for(chain, ROOT + 6)) == ("accepted",)
    assert program_sync(client, ROOT + 12, now_for(chain, ROOT + 12)) == (
        ("invalid_signature", 1), ROOT + 9)
    assert client.latest_trusted_block is chain.blocks[ROOT + 6]
    submitted = sstats.snapshot()["submitted"]["evidence_light"]
    chain.blocks[ROOT + 9] = good
    assert program_sync(client, ROOT + 14, now_for(chain, ROOT + 14)) == ("accepted",)
    # ROOT + 7 .. ROOT + 9 were verified by the failed request; of ROOT + 9
    # the one altered triple was not the honest one
    assert sstats.snapshot()["submitted"]["evidence_light"] - submitted == 1 + 5 * 6
    assert client.store.heights() == [ROOT, ROOT + 6, ROOT + 14]
    assert client.trusted_light_block() is chain.blocks[ROOT + 14]


def test_the_spans_of_a_request(device_stub):
    chain = Chain(8, 10, seed=5)
    client = client_of(chain)
    tracing.reset_tracer()
    assert program_sync(client, ROOT + 10, now_for(chain, ROOT + 10)) == ("accepted",)
    spans = tracing.get_tracer().tail(2000)
    by_id = {s["span"]: s for s in spans}

    def parent(s):
        return by_id.get(s.get("parent"), {}).get("stage")

    stages = [s["stage"] for s in spans]
    assert stages.count("light.sync") == 1
    assert stages.count("light.chain") == 2  # a window of 8, then 2
    assert stages.count("light.chain.prep") == 10
    assert stages.count("light.chain.wait") == 10
    assert stages.count("sched.segment") == 10
    sync = next(s for s in spans if s["stage"] == "light.sync")
    assert sync["attrs"] == {"to": ROOT + 10, "from": ROOT, "headers": 10}
    chains = [s for s in spans if s["stage"] == "light.chain"]
    assert [(c["attrs"]["headers"], c["attrs"]["h0"], c["attrs"]["sigs"])
            for c in chains] == [(8, ROOT + 1, 8 * 6), (2, ROOT + 9, 2 * 6)]
    parents = {
        "light.chain": "light.sync", "light.chain.prep": "light.chain",
        "light.chain.wait": "light.chain", "light.checks": "light.chain.prep",
        "valset.hash": "light.checks", "commit.sign_bytes": "light.chain.prep",
        "batch.keys": "light.chain.prep", "batch.lookup": "light.chain.prep",
        "sched.segment": "light.chain.prep", "sched.submit": "sched.segment",
        "sched.wait": "sched.segment", "sched.flush": "sched.segment",
        "batch.writeback": "light.chain", "light.store": "light.sync",
    }
    for s in spans:
        if s["stage"] in parents:
            assert parent(s) == parents[s["stage"]], s
    heights = [s["attrs"]["height"] for s in spans if s["stage"] == "light.chain.prep"]
    assert heights == list(range(ROOT + 1, ROOT + 11))
    stores = [s["attrs"]["op"] for s in spans if s["stage"] == "light.store"]
    assert stores == ["load", "save"]


# -- the store ------------------------------------------------------------------


class CountingKV(MemKV):
    def __init__(self):
        super().__init__()
        self.walks = 0

    def iterate(self, *a, **k):
        self.walks += 1
        return super().iterate(*a, **k)


def test_light_block_before_is_a_bisection_over_the_held_heights():
    chain = Chain(4, 3, seed=11)
    db = CountingKV()
    store = LightStore(db)
    for h in (ROOT + 2, ROOT, ROOT + 3):
        store.save_light_block(chain.blocks[h])
    walks = db.walks
    assert store.heights() == [ROOT, ROOT + 2, ROOT + 3]
    assert store.light_block_before(ROOT) is None
    for height, want in ((ROOT + 1, ROOT), (ROOT + 2, ROOT), (ROOT + 3, ROOT + 2),
                         (ROOT + 99, ROOT + 3)):
        assert store.light_block_before(height).height == want
    assert store.latest().height == ROOT + 3 and store.first().height == ROOT
    assert db.walks == walks  # no walk over the stored blocks
    # a second store over the same db reads the heights once, at its start
    again = LightStore(db)
    assert again.heights() == store.heights() and db.walks == walks + 1
    assert store.prune(2) == 1 and store.heights() == [ROOT + 2, ROOT + 3]
    assert store.light_block(ROOT) is None and store.size() == 2


# -- the reference and its copy -------------------------------------------------


def test_reference_imports_nothing_of_the_device_path():
    src = open(sequential_reference.__file__).read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", src, flags=re.M)
    assert sorted(set(imports)) == ["__future__", "cometbft_tpu.light.reference"]
    assert sequential_reference.verify_adjacent is reference.verify_adjacent


def test_benchmarks_copy_is_the_reference():
    """``benchmarks/light_seq_ref.py`` differs in its one import line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    copy = open(os.path.join(root, "benchmarks", "light_seq_ref.py")).read()
    mine = open(sequential_reference.__file__).read()
    assert copy == mine.replace(
        "from cometbft_tpu.light.reference import _ed, verify_adjacent",
        "from benchmarks.light_ref import _ed, verify_adjacent")
    assert copy != mine


# -- cometbft light --sequential --------------------------------------------------


@pytest.mark.parametrize("flag, mode", [(["--sequential"], "sequential"), ([], "skipping")])
def test_the_light_command_passes_the_mode(tmp_path, monkeypatch, flag, mode):
    import cometbft_tpu.light as light
    import cometbft_tpu.light.proxy as proxy
    import cometbft_tpu.store.kv as kv
    from cometbft_tpu.cmd import main as cmd

    seen = {}

    class Client:
        def __init__(self, *a, **k):
            seen.update(k)

        def trusted_light_block(self):
            return type("B", (), {"height": 1})()

    class Proxy:
        def __init__(self, *a, **k):
            pass

        def start(self):
            pass

        def stop(self):
            pass

    monkeypatch.setattr(light, "LightClient", Client)
    monkeypatch.setattr(light, "HTTPProvider", lambda *a, **k: None)
    monkeypatch.setattr(proxy, "LightProxy", Proxy)
    monkeypatch.setattr(kv, "SqliteKV", lambda *a, **k: MemKV())
    monkeypatch.setattr(cmd, "_run_until_signal", lambda cleanup: 0)
    argv = ["--home", str(tmp_path), "light", CHAIN, "--primary", "http://127.0.0.1:1",
            "--trust-height", "1", "--trust-hash", "ab" * 32] + flag
    assert cmd.main(argv) == 0
    assert seen["mode"] == mode
