"""Consensus-wide signature cache (crypto/sigcache) — bounds, kill-switch,
thread safety, and the gossip-then-commit loopback flow that motivates it
(docs/verify-stream.md)."""

import hashlib
import threading

import pytest

from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.crypto.keys import Ed25519PrivKey


@pytest.fixture(autouse=True)
def fresh_cache():
    sigcache.reset_cache()
    yield
    sigcache.reset_cache()


def _keypair(tag: bytes):
    priv = Ed25519PrivKey.from_seed(hashlib.sha256(tag).digest())
    return priv, priv.pub_key()


class TestSigCache:
    def test_put_get_roundtrip_and_stats(self):
        c = sigcache.SigCache(capacity=8)
        assert c.get(b"p", b"m", b"s") is None
        c.put(b"p", b"m", b"s", True)
        c.put(b"p", b"m2", b"s", False)
        assert c.get(b"p", b"m", b"s") is True
        assert c.get(b"p", b"m2", b"s") is False  # negative caching
        st = c.stats()
        assert st["hits"] == 2 and st["misses"] == 1 and st["size"] == 2
        assert 0 < st["hit_rate"] < 1

    def test_lru_bound_evicts_oldest(self):
        c = sigcache.SigCache(capacity=3)
        for i in range(4):
            c.put(b"p%d" % i, b"m", b"s", True)
        assert len(c) == 3
        assert c.get(b"p0", b"m", b"s") is None  # evicted
        assert c.get(b"p3", b"m", b"s") is True
        # access refreshes recency: p1 survives the next insert, p2 doesn't
        assert c.get(b"p1", b"m", b"s") is True
        c.put(b"p4", b"m", b"s", True)
        assert c.get(b"p2", b"m", b"s") is None
        assert c.get(b"p1", b"m", b"s") is True

    def test_key_is_unambiguous_across_field_boundaries(self):
        c = sigcache.SigCache()
        # same concatenation, different (pub, msg) split
        c.put(b"ab", b"c", b"s", True)
        assert c.get(b"a", b"bc", b"s") is None

    @pytest.mark.parametrize(
        "pub,msg,sig",
        [
            (b"\x01" * 32, b"vote sign bytes " * 8, b"\x02" * 64),
            (b"\x03" * 33, b"", b"\x04" * 64),  # secp256k1 sizes, empty msg
            (b"\x05" * 96, b"m" * 300, b"\x06" * 96),  # bls12_381 sizes
        ],
        ids=["ed25519", "secp256k1", "bls"],
    )
    def test_key_is_the_framed_digest(self, pub, msg, sig):
        """The key is SHA-256 over len(pub) | pub | len(msg) | msg | sig,
        lengths as 4 bytes little-endian, however it is computed."""
        h = hashlib.sha256()
        h.update(len(pub).to_bytes(4, "little"))
        h.update(pub)
        h.update(len(msg).to_bytes(4, "little"))
        h.update(msg)
        h.update(sig)
        assert sigcache._key(pub, msg, sig) == h.digest()

    def test_kill_switch_disables_lookup_and_insert(self, monkeypatch):
        c = sigcache.SigCache()
        c.put(b"p", b"m", b"s", True)
        monkeypatch.setenv("COMETBFT_TPU_SIGCACHE", "0")
        assert c.get(b"p", b"m", b"s") is None
        c.put(b"p2", b"m", b"s", True)
        monkeypatch.delenv("COMETBFT_TPU_SIGCACHE")
        assert c.get(b"p", b"m", b"s") is True  # old entry intact
        assert c.get(b"p2", b"m", b"s") is None  # disabled put dropped

    def test_thread_safety_hammer(self):
        c = sigcache.SigCache(capacity=64)
        errors = []

        def worker(t):
            try:
                for i in range(300):
                    c.put(b"p%d" % (i % 97), b"m%d" % t, b"s", i % 2 == 0)
                    c.get(b"p%d" % ((i + t) % 97), b"m%d" % t, b"s")
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(t,)) for t in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors
        assert len(c) <= 64

    def test_verify_with_cache_caches_both_verdicts(self):
        priv, pub = _keypair(b"vwc")
        msg = b"hello"
        sig = priv.sign(msg)
        bad = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        assert sigcache.verify_with_cache(pub, msg, sig) is True
        assert sigcache.verify_with_cache(pub, msg, bad) is False
        st = sigcache.get_cache().stats()
        assert st["misses"] == 2 and st["size"] == 2
        # second pass: pure hits
        assert sigcache.verify_with_cache(pub, msg, sig) is True
        assert sigcache.verify_with_cache(pub, msg, bad) is False
        st = sigcache.get_cache().stats()
        assert st["hits"] == 2


def _k(i: int) -> bytes:
    return hashlib.sha256(b"key-%d" % i).digest()


def _filled(capacity: int, entries) -> "sigcache.SigCache":
    c = sigcache.SigCache(capacity=capacity)
    for i, ok in entries:
        c._put(_k(i), ok)
    return c


def _state(c: "sigcache.SigCache"):
    """Everything the contract names: the entries in LRU order with their
    verdicts (so the evictions too), hits and misses."""
    st = c.stats()
    return list(c._entries.items()), st["hits"], st["misses"]


# (capacity, what the cache holds beforehand, the batch's key numbers)
_BATCH_CASES = {
    "empty": (8, [], [0, 1, 2, 3]),
    "part-filled": (8, [(0, True), (1, False), (5, True)], [1, 2, 0, 3, 5]),
    "at-capacity": (4, [(0, True), (1, False), (2, True), (3, True)],
                    [2, 7, 0, 8, 9]),
    "same-key-twice": (4, [(0, True), (1, True), (2, False)],
                       [1, 6, 1, 7, 6, 0]),
    "longer-than-capacity": (3, [(0, True)], [1, 2, 3, 0, 4, 1, 5]),
}


@pytest.mark.parametrize("case", sorted(_BATCH_CASES))
class TestBatchOperations:
    """The segment's two visits against a loop of ``_get`` / ``_put`` on
    the same keys: same verdicts, same counts, same LRU order, same
    evictions."""

    def test_get_many_is_a_loop_of_get(self, case):
        capacity, held, batch = _BATCH_CASES[case]
        keys = [_k(i) for i in batch]
        loop, many = _filled(capacity, held), _filled(capacity, held)
        want = [loop._get(k) for k in keys]
        assert many._get_many(keys) == want
        assert _state(many) == _state(loop)
        assert many.stats()["hits"] + many.stats()["misses"] == len(keys)

    def test_put_many_is_a_loop_of_put(self, case):
        capacity, held, batch = _BATCH_CASES[case]
        keys = [_k(i) for i in batch]
        # a key held twice gets two verdicts: the later one stays
        verdicts = [j % 3 != 0 for j in range(len(keys))]
        loop, many = _filled(capacity, held), _filled(capacity, held)
        before = loop.stats()["puts"]
        for k, ok in zip(keys, verdicts):
            loop._put(k, ok)
        many._put_many(keys, verdicts)
        assert _state(many) == _state(loop)
        assert len(many) <= capacity
        assert many.stats()["puts"] == loop.stats()["puts"] == before + len(keys)


class TestKeyHashedOnce:
    """``partition_misses`` hashes each possible triple once and hands the
    misses' keys on; ``writeback`` takes them back and hashes nothing."""

    @pytest.fixture
    def key_calls(self, monkeypatch):
        calls = []
        real = sigcache._key

        def counting(pub, msg, sig):
            calls.append((pub, msg, sig))
            return real(pub, msg, sig)

        monkeypatch.setattr(sigcache, "_key", counting)
        return calls

    @staticmethod
    def _triples(n, tag=b"once"):
        return (
            [hashlib.sha256(tag + b"p%d" % i).digest() for i in range(n)],
            [b"m%d" % i for i in range(n)],
            [hashlib.sha512(tag + b"s%d" % i).digest() for i in range(n)],
        )

    def test_partition_returns_the_keys_of_its_misses(self, key_calls):
        pubs, msgs, sigs = self._triples(5)
        cache = sigcache.get_cache()
        cache.put(pubs[1], msgs[1], sigs[1], True)
        cache.put(pubs[3], msgs[3], sigs[3], False)
        del key_calls[:]
        before = cache.stats()
        part = sigcache.partition_misses(pubs, msgs, sigs)
        assert part.bits == [None, True, None, False, None]
        assert part.miss == [0, 2, 4]
        assert part.keys == [
            sigcache._key(pubs[i], msgs[i], sigs[i]) for i in part.miss
        ]
        assert part.hashed == 5
        after = cache.stats()
        assert after["keys"] - before["keys"] == 5
        assert after["hits"] - before["hits"] == 2
        assert after["misses"] - before["misses"] == 3
        assert len(key_calls) == 5 + 3  # the look-up's, and this test's own

    def test_writeback_hashes_nothing_and_puts_once(self, key_calls):
        pubs, msgs, sigs = self._triples(6)
        part = sigcache.partition_misses(pubs, msgs, sigs)
        assert part.miss == list(range(6)) and len(part.keys) == 6
        assert len(key_calls) == 6
        sigcache.writeback(part, [True, False, True, True, False, True])
        assert len(key_calls) == 6  # not one more
        assert part.bits == [True, False, True, True, False, True]
        st = sigcache.get_cache().stats()
        assert st["keys"] == 6 and st["puts"] == 6 and st["size"] == 6
        # what was put is what a look-up by the triple finds
        again = sigcache.partition_misses(pubs, msgs, sigs)
        assert again.miss == [] and again.keys == []
        assert again.bits == part.bits

    def test_none_result_is_not_cached(self, key_calls):
        pubs, msgs, sigs = self._triples(3)
        part = sigcache.partition_misses(pubs, msgs, sigs)
        sigcache.writeback(part, [True, None, False])
        assert part.bits == [True, None, False]  # the hole stays a hole
        st = sigcache.get_cache().stats()
        assert st["puts"] == 2 and st["size"] == 2
        again = sigcache.partition_misses(pubs, msgs, sigs)
        assert again.miss == [1]  # still to be verified, under its key
        assert again.keys == [sigcache._key(pubs[1], msgs[1], sigs[1])]

    def test_wrong_lengths_are_false_and_get_no_key(self, key_calls):
        pubs, msgs, sigs = self._triples(4)
        pubs[1] = pubs[1][:31]
        sigs[2] = sigs[2] + b"\x00"
        part = sigcache.partition_misses(pubs, msgs, sigs)
        assert part.bits == [None, False, False, None]
        assert part.miss == [0, 3] and len(part.keys) == 2
        assert part.hashed == 2 and len(key_calls) == 2
        assert all(len(c[0]) == 32 and len(c[2]) == 64 for c in key_calls)
        # other key sizes filter by their own rule (secp256k1: 33 / 64)
        part = sigcache.partition_misses(
            [b"\x02" * 33, pubs[0]], msgs[:2], sigs[:1] + sigs[3:], (33,), (64,)
        )
        assert part.bits == [None, False] and part.miss == [0]

    def test_cache_off_makes_no_keys(self, key_calls, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_SIGCACHE", "0")
        pubs, msgs, sigs = self._triples(3)
        pubs[2] = b"short"
        part = sigcache.partition_misses(pubs, msgs, sigs)
        assert part.bits == [None, None, False] and part.miss == [0, 1]
        assert part.keys is None and part.hashed == 0
        sigcache.writeback(part, [True, False])
        assert part.bits == [True, False, False]
        assert not key_calls
        st = sigcache.get_cache().stats()
        assert st["keys"] == 0 and st["puts"] == 0 and st["size"] == 0

    def test_verify_with_cache_hashes_once(self, key_calls):
        priv, pub = _keypair(b"once-single")
        sig = priv.sign(b"m")
        assert sigcache.verify_with_cache(pub, b"m", sig) is True
        assert len(key_calls) == 1  # one key serves the look-up and the put
        assert sigcache.verify_with_cache(pub, b"m", sig) is True
        st = sigcache.get_cache().stats()
        assert (st["keys"], st["puts"], st["hits"]) == (2, 1, 1)


class TestSeamParts:
    """ISSUE 36: the seam's parts are spans of the CALL, never of a
    signature: one ``batch.keys``, one ``batch.lookup`` and one
    ``batch.writeback`` whatever the segment's length, nested under
    whatever span the caller has open, and none with the recorder off."""

    @staticmethod
    def _stages(tracer):
        return [s["stage"] for s in tracer.tail(100)]

    @pytest.mark.parametrize("n", [1, 50])
    def test_one_span_each_a_call(self, n):
        from cometbft_tpu.libs import tracing

        tracing.reset_tracer()
        tr = tracing.get_tracer()
        pubs, msgs, sigs = TestKeyHashedOnce._triples(n, b"parts")
        with tr.span("batch.verify") as seam:
            part = sigcache.partition_misses(pubs, msgs, sigs)
            sigcache.writeback(part, [True] * n)
        assert self._stages(tr) == [
            "batch.keys", "batch.lookup", "batch.writeback", "batch.verify",
        ]
        assert {s.get("parent") for s in tr.tail(100)[:3]} == {seam.span_id}
        # every look-up a hit: no write-back is made, and none is recorded
        tr.reset()
        part = sigcache.partition_misses(pubs, msgs, sigs)
        assert part.miss == [] and all(part.bits)
        assert self._stages(tr) == ["batch.keys", "batch.lookup"]
        tracing.reset_tracer()

    def test_cache_off_or_recorder_off_records_no_part(self, monkeypatch):
        from cometbft_tpu.libs import tracing

        tracing.reset_tracer()
        tr = tracing.get_tracer()
        pubs, msgs, sigs = TestKeyHashedOnce._triples(3, b"off")
        monkeypatch.setenv("COMETBFT_TPU_SIGCACHE", "0")
        part = sigcache.partition_misses(pubs, msgs, sigs)
        sigcache.writeback(part, [True] * 3)
        # nothing hashed, nothing looked up: only the write-back's one span
        assert self._stages(tr) == ["batch.writeback"]
        monkeypatch.delenv("COMETBFT_TPU_SIGCACHE")
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "0")
        tr.reset()
        part = sigcache.partition_misses(pubs, msgs, sigs)
        sigcache.writeback(part, [True] * 3)
        assert part.bits == [True] * 3 and self._stages(tr) == []
        tracing.reset_tracer()


class TestMetricsExposition:
    def test_callback_gauges_scrape_without_jax(self):
        """The verify-stream gauges read live counters at scrape time and a
        scrape must never raise (or initialize an accelerator backend)."""
        from cometbft_tpu.libs.metrics import NodeMetrics

        priv, pub = _keypair(b"metrics")
        sigcache.verify_with_cache(pub, b"m", priv.sign(b"m"))
        sigcache.verify_with_cache(pub, b"m", priv.sign(b"m"))
        page = NodeMetrics("testns").registry.expose()
        assert "testns_crypto_sigcache_hits 1" in page
        assert "testns_crypto_sigcache_misses 1" in page
        assert "testns_crypto_sigcache_hit_rate 0.5" in page
        assert "testns_crypto_verify_dispatches" in page
        assert "testns_crypto_verify_batch_occupancy" in page


class TestBatchVerifierIntegration:
    def _entries(self, n, tamper=()):
        privs = [_keypair(b"bv%d" % i)[0] for i in range(n)]
        pubs = [p.pub_key() for p in privs]
        msgs = [b"msg-%d" % i for i in range(n)]
        sigs = [p.sign(m) for p, m in zip(privs, msgs)]
        for i in tamper:
            sigs[i] = sigs[i][:32] + bytes([sigs[i][32] ^ 1]) + sigs[i][33:]
        return pubs, msgs, sigs

    def test_cpu_verifier_prefilters_hits(self):
        pubs, msgs, sigs = self._entries(4, tamper=(2,))
        bv = cbatch.CpuBatchVerifier()
        for p, m, s in zip(pubs, msgs, sigs):
            bv.add(p, m, s)
        ok, bits = bv.verify()
        assert not ok and bits == [True, True, False, True]
        # second verifier over the same entries: zero backend work
        bv2 = cbatch.CpuBatchVerifier()
        calls = []
        bv2._verify_pending = lambda *a: calls.append(a) or []
        for p, m, s in zip(pubs, msgs, sigs):
            bv2.add(p, m, s)
        ok2, bits2 = bv2.verify()
        assert (ok2, bits2) == (ok, bits)
        assert not calls  # everything resolved from cache

    def test_structural_garbage_never_reaches_backend(self):
        pubs, msgs, sigs = self._entries(3)
        bv = cbatch.CpuBatchVerifier()
        bv.add(pubs[0], msgs[0], sigs[0])
        bv.add(b"\x01" * 7, msgs[1], sigs[1])  # impossible pub length
        bv.add(pubs[2], msgs[2], b"short")  # impossible sig length
        shipped = []
        real = bv._verify_pending
        bv._verify_pending = lambda p, m, s: shipped.extend(p) or real(p, m, s)
        ok, bits = bv.verify()
        assert not ok and bits == [True, False, False]
        # only the structurally-plausible entry occupied backend work
        assert shipped == [pubs[0].bytes()]

    def test_kill_switch_restores_uncached_behavior(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_SIGCACHE", "0")
        pubs, msgs, sigs = self._entries(3, tamper=(1,))
        for _ in range(2):  # no memoization across passes
            bv = cbatch.CpuBatchVerifier()
            shipped = []
            real = bv._verify_pending
            bv._verify_pending = (
                lambda p, m, s: shipped.extend(p) or real(p, m, s)
            )
            for p, m, s in zip(pubs, msgs, sigs):
                bv.add(p, m, s)
            ok, bits = bv.verify()
            assert not ok and bits == [True, False, True]
            assert len(shipped) == 3  # every entry verified, every time
        assert len(sigcache.get_cache()) == 0


class TestLoopbackConsensusFlow:
    def test_gossip_verified_votes_make_commit_verification_free(self):
        """The motivating flow: precommits verified at gossip time
        (vote_set.add_vote -> Vote.verify) make the commit assembled from
        them verify with a 100% cache hit rate and zero backend work."""
        from cometbft_tpu.types import validation
        from cometbft_tpu.types.basic import (
            PRECOMMIT_TYPE,
            BlockID,
            PartSetHeader,
            Timestamp,
        )
        from cometbft_tpu.types.validator import Validator, ValidatorSet
        from cometbft_tpu.types.vote import Vote
        from cometbft_tpu.types.vote_set import VoteSet

        chain_id = "sigcache-loopback"
        privs = [_keypair(b"lb%d" % i)[0] for i in range(6)]
        vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
        bid = BlockID(
            hash=hashlib.sha256(b"blk").digest(),
            part_set_header=PartSetHeader(1, hashlib.sha256(b"psh").digest()),
        )
        vs = VoteSet(chain_id, 7, 0, PRECOMMIT_TYPE, vals)
        for p in privs:
            addr = p.pub_key().address()
            idx = vals.get_by_address(addr)[0]
            v = Vote(
                type_=PRECOMMIT_TYPE,
                height=7,
                round_=0,
                block_id=bid,
                timestamp=Timestamp(1_700_000_000, 0),
                validator_address=addr,
                validator_index=idx,
            )
            v.signature = p.sign(v.sign_bytes(chain_id))
            vs.add_vote(v)  # gossip-time verification populates the cache
        before = sigcache.get_cache().stats()
        assert before["size"] == 6 and before["hits"] == 0

        commit = vs.make_commit()
        shipped = []
        orig = cbatch.CpuBatchVerifier._verify_pending
        try:
            cbatch.CpuBatchVerifier._verify_pending = (
                lambda self, p, m, s: shipped.extend(p) or orig(self, p, m, s)
            )
            validation.verify_commit(
                chain_id, vals, bid, 7, commit, backend="cpu"
            )
        finally:
            cbatch.CpuBatchVerifier._verify_pending = orig
        after = sigcache.get_cache().stats()
        assert not shipped  # zero backend verifications at commit time
        assert after["hits"] - before["hits"] == 6
        assert after["hit_rate"] > 0
