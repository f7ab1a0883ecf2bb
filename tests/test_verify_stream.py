"""The fused verification stream: ``verify_segments`` bitwise equivalence +
dispatch accounting, blocksync's window on the served path (including
bad-block redo/ban), and the light client's sequential chain on the served
path.

Device-dispatch budget matters on the CPU-XLA CI host (~10 s per launch):
the equivalence test doubles as the fewer-dispatches smoke check, and the
integration tests either reuse the cache (zero extra dispatches) or
monkeypatch the device call with the host oracle."""

import hashlib
import time

import numpy as np
import pytest

from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.ops import dispatch_stats
from cometbft_tpu.ops import verify as ov
from cometbft_tpu.types import validation
from cometbft_tpu.types.basic import (
    PRECOMMIT_TYPE,
    BlockID,
    PartSetHeader,
    Timestamp,
)
from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
from cometbft_tpu.types.validator import Validator, ValidatorSet
from cometbft_tpu.types.vote import Vote
from cometbft_tpu.types.vote_set import VoteSet

CHAIN_ID = "stream-chain"


@pytest.fixture(autouse=True)
def fresh_cache():
    sigcache.reset_cache()
    yield
    sigcache.reset_cache()


def _triples(n, tag=b"vs", tamper=(), garble=()):
    """n (pub, msg, sig) triples; ``tamper`` flips a sig bit (crypto-invalid),
    ``garble`` truncates the sig (structurally invalid)."""
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = hashlib.sha256(tag + b"%d" % i).digest()
        pubs.append(ref.pubkey_from_seed(seed))
        msgs.append(tag + b"-msg-%d" % i)
        sigs.append(ref.sign(seed, msgs[-1]))
    for i in tamper:
        sigs[i] = sigs[i][:32] + bytes([sigs[i][32] ^ 1]) + sigs[i][33:]
    for i in garble:
        sigs[i] = sigs[i][:17]
    return pubs, msgs, sigs


class TestVerifySegments:
    def test_equivalence_and_dispatch_reduction(self):
        """verify_segments == per-segment verify_batch bitwise, on a
        randomized valid/invalid mix with invalid entries at segment
        boundaries and an empty segment — in ONE dispatch where the
        per-commit path takes K (the CI fewer-dispatches smoke check)."""
        rng = np.random.default_rng(0x5EED)
        work = [
            _triples(3, tag=b"segA"),
            ([], [], []),  # empty segment
            # invalids straddling the segment boundary: first and last
            _triples(5, tag=b"segB", tamper=(0, 4), garble=(2,)),
            _triples(2, tag=b"segC", tamper=(0, 1)),
            _triples(4, tag=b"segD", tamper=tuple(
                int(i) for i in rng.choice(4, size=2, replace=False)
            )),
        ]

        d0 = dispatch_stats.dispatch_count()
        fused = ov.verify_segments(work)
        fused_dispatches = dispatch_stats.dispatch_count() - d0

        d0 = dispatch_stats.dispatch_count()
        expected = [
            ov.verify_batch(p, m, s) if p else np.zeros(0, bool)
            for p, m, s in work
        ]
        percommit_dispatches = dispatch_stats.dispatch_count() - d0

        assert len(fused) == len(work)
        for got, want, (p, m, s) in zip(fused, expected, work):
            assert got.shape == want.shape
            assert (got == want).all()
            # and both agree with the host oracle
            oracle = [
                len(pub) == 32
                and len(sig) == 64
                and ref.verify_zip215(pub, msg, sig)
                for pub, msg, sig in zip(p, m, s)
            ]
            assert list(got) == oracle

        # the fused path must issue FEWER kernel dispatches: 1 vs one per
        # non-empty segment
        assert fused_dispatches == 1
        assert percommit_dispatches == 4
        assert fused_dispatches < percommit_dispatches
        snap = dispatch_stats.snapshot()
        assert snap["fused_batches"] >= 1
        assert snap["fused_segments"] >= len(work)

    def test_empty_work_and_all_empty_segments(self):
        d0 = dispatch_stats.dispatch_count()
        assert ov.verify_segments([]) == []
        out = ov.verify_segments([([], [], []), ([], [], [])])
        assert [o.shape for o in out] == [(0,), (0,)]
        assert dispatch_stats.dispatch_count() == d0  # no device work

    def test_overflow_falls_back_to_overlapped(self, monkeypatch):
        calls = []
        monkeypatch.setattr(
            ov,
            "verify_batches_overlapped",
            lambda work: calls.append(len(work)) or ["sentinel"] * len(work),
        )
        big = ov._BUCKETS[-1] // 2 + 1
        junk = ([b""] * big, [b""] * big, [b""] * big)  # structural-only
        out = ov.verify_segments([junk, junk])
        assert calls == [2]
        assert out == ["sentinel", "sentinel"]


# ---------------------------------------------------------------------------
# blocksync window prefetch
# ---------------------------------------------------------------------------


def _sign_commit(privs, vals, height, bid):
    vs = VoteSet(CHAIN_ID, height, 0, PRECOMMIT_TYPE, vals)
    for p in privs:
        addr = p.pub_key().address()
        idx = vals.get_by_address(addr)[0]
        v = Vote(
            type_=PRECOMMIT_TYPE,
            height=height,
            round_=0,
            block_id=bid,
            timestamp=Timestamp(1_700_000_000 + height, 1),
            validator_address=addr,
            validator_index=idx,
        )
        v.signature = p.sign(v.sign_bytes(CHAIN_ID))
        vs.add_vote(v, verify=False)  # keep gossip-time cache empty here
    return vs.make_commit()


def _make_chain(n_blocks, n_vals=4):
    """Blocks 1..n_blocks where block H+1 carries block H's commit as its
    LastCommit — the shape blocksync's two-block pipeline consumes."""
    from cometbft_tpu.state.execution import consensus_params_hash
    from cometbft_tpu.state.state import state_from_genesis
    from cometbft_tpu.types.block import (
        Block,
        ConsensusVersion,
        Data,
        Header,
        empty_commit,
    )

    privs = [
        Ed25519PrivKey.from_seed(hashlib.sha256(b"bsw%d" % i).digest())
        for i in range(n_vals)
    ]
    gdoc = GenesisDoc(
        chain_id=CHAIN_ID,
        genesis_time=Timestamp(0, 0),
        validators=[GenesisValidator(p.pub_key(), 10) for p in privs],
    )
    state = state_from_genesis(gdoc)
    vals = state.validators
    blocks, commits = [], {}
    last_commit, last_bid = empty_commit(), BlockID()
    for h in range(1, n_blocks + 1):
        header = Header(
            version=ConsensusVersion(11, state.version_app),
            chain_id=CHAIN_ID,
            height=h,
            time=Timestamp(1_700_000_000 + h, 0),
            last_block_id=last_bid,
            validators_hash=vals.hash(),
            next_validators_hash=state.next_validators.hash(),
            consensus_hash=consensus_params_hash(state.consensus_params),
            app_hash=state.app_hash,
            last_results_hash=state.last_results_hash,
            proposer_address=vals.get_proposer().address,
        )
        block = Block(
            header=header,
            data=Data(txs=[b"tx-%d" % h]),
            last_commit=last_commit,
        )
        ps = block.make_part_set()
        bid = BlockID(hash=block.hash(), part_set_header=ps.header)
        commit = _sign_commit(privs, vals, h, bid)
        blocks.append(block)
        commits[h] = commit
        last_commit, last_bid = commit, bid
    return state, privs, blocks, commits


class _StaticStore:
    def height(self):
        return 0

    def base(self):
        return 0


def _make_reactor(state, blocks, frontier=1):
    from cometbft_tpu.blocksync.pool import _Request
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor

    r = BlocksyncReactor(
        state, block_exec=None, block_store=_StaticStore(), enabled=False
    )
    now = time.monotonic()
    r.pool.height = frontier
    for block in blocks:
        h = block.header.height
        req = _Request(h, "peer-%d" % h, now)
        req.block = block
        r.pool.requests[h] = req
        r.pool.set_peer_range("peer-%d" % h, 1, len(blocks))
    return r


@pytest.fixture
def window(served, monkeypatch):
    """The served path (``served``, below) with the reactor's default
    window of 8 blocks: 7 commits a segment."""
    monkeypatch.setenv("COMETBFT_TPU_BLOCKSYNC_WINDOW", "8")
    yield served


def _flushes() -> int:
    from cometbft_tpu.verifysched import stats as sstats

    return sum(sstats.snapshot()["flushes"].values())


class TestBlocksyncFusedPrefetch:
    """Blocksync's window on the served path: a window of commits is ONE
    segment at bulk priority, queued without waiting; the frontier waits on
    it only where it has not landed."""

    def test_window_prefetch_then_zero_dispatch_verification(self, window):
        """One window is one flush; the authoritative light AND full commit
        verifications then resolve from cache, and a repeat tick (apply or
        redo) queues nothing again."""
        from cometbft_tpu.verifysched import stats as sstats

        state, privs, blocks, commits = _make_chain(5)
        r = _make_reactor(state, blocks)

        r._prefetch_window()
        r._settle(1, blocks[1].last_commit)
        assert window == [16]  # 4 commits of 4 signatures, one dispatch
        assert _flushes() == 1
        assert sstats.snapshot()["segments"]["bulk"] == 1

        # authoritative verification: zero further device work
        for h in range(1, 5):
            c = commits[h]
            validation.verify_commit_light(
                CHAIN_ID, state.validators, c.block_id, h, c
            )
        # apply-time FULL verification (validate_block's LastCommit check)
        validation.verify_commit(
            CHAIN_ID, state.validators, commits[2].block_id, 2, commits[2]
        )
        # memoized: another tick queues nothing
        r._prefetch_window()
        r._settle(1, blocks[1].last_commit)
        assert window == [16]
        assert sstats.snapshot()["segments"]["bulk"] == 1

    def test_bad_block_same_redo_ban_path_under_fused_prefetch(self, window):
        """A forged commit signature found through the window takes the
        identical redo/ban path: both provider requests dropped, both peers
        banned, loop reports handled."""
        state, privs, blocks, commits = _make_chain(5)
        # forge the commit for height 2 (carried inside block 3)
        c2 = blocks[2].last_commit
        cs = c2.signatures[1]
        cs.signature = cs.signature[:32] + bytes(
            [cs.signature[32] ^ 1]
        ) + cs.signature[33:]
        r = _make_reactor(state, blocks, frontier=2)

        handled = r._process_blocks()
        assert handled is True
        # exactly the window's dispatch (commits 2-4; block 2's own
        # LastCommit is for the genesis state's empty last set, which the
        # window leaves to the frontier); the authoritative rejection came
        # from the cached False verdict
        assert window == [12]
        assert 2 not in r.pool.requests and 3 not in r.pool.requests
        now = time.monotonic()
        assert r.pool.peers["peer-2"].banned_until > now
        assert r.pool.peers["peer-3"].banned_until > now

    def test_prefetch_disabled_paths(self, window, monkeypatch):
        state, privs, blocks, commits = _make_chain(5)

        # kill-switch: no cache -> no speculative work at all
        monkeypatch.setenv("COMETBFT_TPU_SIGCACHE", "0")
        r = _make_reactor(state, blocks)
        r._prefetch_window()
        assert window == [] and _flushes() == 0
        assert len(sigcache.get_cache()) == 0
        monkeypatch.delenv("COMETBFT_TPU_SIGCACHE")

        # window too small
        monkeypatch.setenv("COMETBFT_TPU_BLOCKSYNC_WINDOW", "1")
        r = _make_reactor(state, blocks)
        r._prefetch_window()
        assert window == [] and _flushes() == 0
        monkeypatch.setenv("COMETBFT_TPU_BLOCKSYNC_WINDOW", "8")

        # cpu backend: host library path has no dispatch floor to amortize
        monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "cpu")
        r = _make_reactor(state, blocks)
        r._prefetch_window()
        assert window == [] and _flushes() == 0

    def test_steady_state_window_leaves_in_one_flush(self, window):
        """As the frontier applies height after height, the next window is
        queued whole while the one before is still ahead of it: 19 heights
        take three flushes (7, 7 and the last 5 commits), not 19."""
        state, privs, blocks, commits = _make_chain(20)
        r = _make_reactor(state, blocks)
        for h in range(1, 20):
            r._prefetch_window()
            r._settle(h, blocks[h].last_commit)
            c = blocks[h].last_commit
            validation.verify_commit_light(
                CHAIN_ID, state.validators, c.block_id, h, c
            )
            r.pool.pop_request()
        assert window == [28, 28, 20]
        assert _flushes() == 3

    def test_scheduler_off_window_is_one_seam_call(self, window, monkeypatch):
        """With the scheduler switched off the window is one synchronous
        batch through the seam: one dispatch, nothing left to wait for."""
        monkeypatch.setenv("COMETBFT_TPU_VERIFY_SCHED", "0")
        state, privs, blocks, commits = _make_chain(5)
        r = _make_reactor(state, blocks)
        r._prefetch_window()
        assert window == [16] and _flushes() == 0
        r._settle(1, blocks[1].last_commit)
        for h in range(1, 5):
            c = commits[h]
            validation.verify_commit_light(
                CHAIN_ID, state.validators, c.block_id, h, c
            )
        assert window == [16]

    def test_pool_peek_window(self):
        state, privs, blocks, commits = _make_chain(4)
        r = _make_reactor(state, blocks)
        del r.pool.requests[3]  # gap stops the run
        window = r.pool.peek_window(8)
        assert [h for h, _, _, _ in window] == [1, 2]
        assert r.pool.peek_window(0) == [(1, blocks[0], "peer-1", None)]


# ---------------------------------------------------------------------------
# the light client's sequential chain, on the served path
# ---------------------------------------------------------------------------


def _make_light_chain(n_headers, n_vals=3):
    from cometbft_tpu.state.execution import consensus_params_hash
    from cometbft_tpu.types.block import ConsensusVersion, Header
    from cometbft_tpu.types.light import LightBlock, SignedHeader

    privs = [
        Ed25519PrivKey.from_seed(hashlib.sha256(b"lc%d" % i).digest())
        for i in range(n_vals)
    ]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    lbs = []
    for h in range(1, n_headers + 1):
        header = Header(
            version=ConsensusVersion(11, 1),
            chain_id=CHAIN_ID,
            height=h,
            time=Timestamp(1_700_000_000 + h, 0),
            last_block_id=BlockID(),
            validators_hash=vals.hash(),
            next_validators_hash=vals.hash(),
            proposer_address=vals.get_proposer().address,
        )
        bid = BlockID(
            hash=header.hash(),
            part_set_header=PartSetHeader(
                1, hashlib.sha256(b"ps%d" % h).digest()
            ),
        )
        commit = _sign_commit(privs, vals, h, bid)
        lbs.append(LightBlock(SignedHeader(header, commit), vals))
    return privs, vals, lbs


@pytest.fixture
def served(monkeypatch):
    """The served path with the device stubbed as the scheduler's tests do:
    a trusted ``tpu`` backend whose device runner is the host oracle,
    recording the lanes of every dispatch."""
    from cometbft_tpu import verifysched
    from cometbft_tpu.crypto import backend_health
    from cometbft_tpu.ops import sha256_tree, supervisor
    from cometbft_tpu.verifysched import stats as sstats

    dispatched = []

    def oracle(backend, pubs, msgs, sigs, lanes):
        dispatched.append(len(pubs))
        out = np.zeros(lanes, dtype=bool)
        out[: len(pubs)] = [
            ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)
        ]
        return out

    monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "tpu")
    monkeypatch.delenv("COMETBFT_TPU_VERIFY_SCHED", raising=False)
    supervisor.set_device_runner(oracle)
    sha256_tree.set_tree_runner(sha256_tree.host_tree_runner)
    for reset in (sstats.reset, dispatch_stats.reset, backend_health.reset,
                  verifysched.reset_scheduler):
        reset()
    yield dispatched
    verifysched.reset_scheduler()
    supervisor.clear_device_runner()
    sha256_tree.clear_tree_runner()
    backend_health.reset()
    sstats.reset()


class TestLightChainSync:
    """``verify_adjacent_chain`` on the served path (ISSUE 38): each
    header's misses one segment at light priority, judged in height
    order."""

    NOW = 1_700_000_500.0

    def test_chain_matches_sequential_and_uses_the_scheduler(self, served):
        import cometbft_tpu.light.verifier as lv
        from cometbft_tpu.verifysched import stats as sstats

        privs, vals, lbs = _make_light_chain(4)
        lv.verify_adjacent_chain(
            CHAIN_ID, lbs[0], lbs[1:], 10_000, self.NOW
        )
        # one train of three headers: a segment of three a header, at
        # light priority, every signature on the device
        ss = sstats.snapshot()
        assert ss["segments"] == {"consensus": 0, "evidence_light": 3, "bulk": 0}
        assert ss["submitted"]["evidence_light"] == 9
        assert sum(served) == 9
        # cache now holds the verdicts: a re-sync ships nothing
        served.clear()
        lv.verify_adjacent_chain(
            CHAIN_ID, lbs[0], lbs[1:], 10_000, self.NOW
        )
        assert served == []
        assert sstats.snapshot()["segments"]["evidence_light"] == 3

    def test_chain_failure_matches_sequential_error(self, served):
        import cometbft_tpu.light.verifier as lv

        privs, vals, lbs = _make_light_chain(4)
        # forge one signature on header 3
        cs = lbs[2].signed_header.commit.signatures[0]
        cs.signature = cs.signature[:32] + bytes(
            [cs.signature[32] ^ 1]
        ) + cs.signature[33:]

        # the verdict of a loop of verify_adjacent
        with pytest.raises(validation.CommitVerificationError) as seq_err:
            cur = lbs[0]
            for lb in lbs[1:]:
                lv.verify_adjacent(CHAIN_ID, cur, lb, 10_000, self.NOW)
                cur = lb

        sigcache.reset_cache()
        served.clear()
        with pytest.raises(lv.ErrVerificationFailed) as chain_err:
            lv.verify_adjacent_chain(
                CHAIN_ID, lbs[0], lbs[1:], 10_000, self.NOW
            )
        assert served  # the served path was exercised
        failed = chain_err.value
        assert (failed.from_height, failed.to) == (2, 3)
        assert type(failed.reason) is type(seq_err.value)
        assert str(failed.reason) == str(seq_err.value)

    def test_non_ed25519_sets_fall_back_sequential(self, served, monkeypatch):
        import cometbft_tpu.light.verifier as lv

        privs, vals, lbs = _make_light_chain(3)
        seen = []
        # masquerade the key type so the eligibility gate trips
        monkeypatch.setattr(
            lv, "verify_adjacent", lambda *a, **k: seen.append("seq")
        )
        monkeypatch.setattr(
            type(privs[0].pub_key()), "type_", "not-ed25519", raising=False
        )
        lv.verify_adjacent_chain(CHAIN_ID, lbs[0], lbs[1:], 10_000, self.NOW)
        assert seen == ["seq", "seq"]  # sequential per header, no device
        assert served == []
