"""Encrypted transport data plane (docs/transport-plane.md).

Covers the vectorized ChaCha20-Poly1305 frame plane (ops/chacha_aead +
p2p/transportplane), the batched X25519 handshake admission pool
(ops/x25519_ladder + p2p/handshake_pool), the SecretConnection frame
coalescing that rides on both, and the repo discipline lint.  The
device tiers run on the host runner seams here (jax-free, tier-1-safe);
the real-kernel differentials are slow-marked.
"""

import hashlib
import socket
import struct
import threading
import time

import pytest

from cometbft_tpu.crypto import aead_ref
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.ops import chacha_aead, x25519_ladder
from cometbft_tpu.p2p import handshake_pool, transportplane
from cometbft_tpu.p2p import transport_stats as tstats
from cometbft_tpu.p2p.secret_connection import (
    SecretConnection,
    SecretConnectionError,
)


@pytest.fixture(autouse=True)
def _clean_plane():
    from cometbft_tpu.crypto import backend_health

    def scrub():
        handshake_pool.reset_pool()
        chacha_aead.clear_aead_runner()
        x25519_ladder.clear_ladder_runner()
        tstats.reset()
        backend_health.reset()

    scrub()
    yield
    scrub()


def _key(tag: str) -> bytes:
    return hashlib.sha256(tag.encode()).digest()


def _payload(tag: str, size: int) -> bytes:
    block = hashlib.sha256(tag.encode()).digest()
    return (block * ((size + 31) // 32))[:size]


# -- RFC 7748 vectors through the batched ladder ------------------------------

_RFC7748_VECTORS = [
    (
        "a546e36bf0527c9d3b16154b82465edd62144c0ac1fc5a18506a2244ba449ac4",
        "e6db6867583030db3594c1a424b15f7c726624ec26b3353b10a903a6d0ab1c4c",
        "c3da55379de9c6908e94ea4df28d084f32eccf03491c71f754b4075577a28552",
    ),
    (
        "4b66e9d4d1b4673c5ad22691957d6af5c11b6421e0ea01d42ca4169e7918ba0d",
        "e5210f12786811d3f4b7959d0538ae2c31dbe7106fc03c3efc4cd549c715a493",
        "95cbde9476e8907d7aade45cb4b873f88b595a68799fa152e6f8f7647aac7957",
    ),
]

# RFC 7748 §6.1 Diffie-Hellman
_ALICE_PRIV = bytes.fromhex(
    "77076d0a7318a57d3c16c17251b26645df4c2f87ebc0992ab177fba51db92c2a"
)
_ALICE_PUB = bytes.fromhex(
    "8520f0098930a754748b7ddcb43ef75a0dbf3a0d26381af4eba4a98eaa9b4e6a"
)
_BOB_PRIV = bytes.fromhex(
    "5dab087e624a8a4b79e17f8b83800ee66f3bb1292618b6fd1c2f8b27ff88e0eb"
)
_BOB_PUB = bytes.fromhex(
    "de9edb7d7b7dc1b4d35b61c2ece435373f8343c85b78674dadfc7e146f882b4f"
)
_SHARED = bytes.fromhex(
    "4a5d9d5ba4ce2de1728e3bf480350f25e07e21c947d19e3376f09b3c1e161742"
)


class TestX25519Ladder:
    @pytest.mark.parametrize("scalar,u,want", _RFC7748_VECTORS)
    def test_rfc7748_vectors_host_paths(self, scalar, u, want):
        pair = (bytes.fromhex(scalar), bytes.fromhex(u))
        want = bytes.fromhex(want)
        assert x25519_ladder.host_exchange([pair]) == [want]
        # supervised batch (host tier on an untrusted backend)
        assert x25519_ladder.exchange_batch([pair]) == [want]
        # runner seam ("device" tier)
        x25519_ladder.set_ladder_runner(x25519_ladder.host_ladder_runner)
        assert x25519_ladder.exchange_batch([pair]) == [want]

    def test_rfc7748_dh_through_pool(self):
        x25519_ladder.set_ladder_runner(x25519_ladder.host_ladder_runner)
        assert handshake_pool.active()
        assert handshake_pool.public_key(_ALICE_PRIV) == _ALICE_PUB
        assert handshake_pool.public_key(_BOB_PRIV) == _BOB_PUB
        assert handshake_pool.exchange(_ALICE_PRIV, _BOB_PUB) == _SHARED
        assert handshake_pool.exchange(_BOB_PRIV, _ALICE_PUB) == _SHARED
        assert handshake_pool.sync_exchange(_ALICE_PRIV, _BOB_PUB) == _SHARED

    def test_batch_mixed_lanes_match_reference(self):
        pairs = [
            (_key("lad-scalar-%d" % i), aead_ref.x25519(
                _key("lad-peer-%d" % i), x25519_ladder.BASE_U))
            for i in range(13)
        ]
        want = [aead_ref.x25519(s, u) for s, u in pairs]
        assert x25519_ladder.exchange_batch(pairs) == want
        x25519_ladder.set_ladder_runner(x25519_ladder.host_ladder_runner)
        assert x25519_ladder.exchange_batch(pairs) == want

    def test_wrong_shape_runner_degrades_not_corrupts(self):
        from cometbft_tpu.crypto import backend_health

        pairs = [
            (_key("ws-scalar-%d" % i), _key("ws-u-%d" % i)) for i in range(4)
        ]
        want = x25519_ladder.host_exchange(pairs)

        def lane_dropper(ps):
            return x25519_ladder.host_ladder_runner(ps)[:-1]

        x25519_ladder.set_ladder_runner(lane_dropper)
        assert x25519_ladder.exchange_batch(pairs) == want
        br = backend_health.registry().breaker(x25519_ladder.BREAKER)
        assert br.stats()["failures_total"] >= 1


# -- AEAD plane ---------------------------------------------------------------

# RFC 8439 §2.8.2 key/nonce/plaintext (the full vector, with AAD, is
# anchored in tests/test_aead_ref.py; transport frames carry empty AAD)
_RFC8439_KEY = bytes(range(0x80, 0xA0))
_RFC8439_NONCE = bytes.fromhex("070000004041424344454647")
_RFC8439_PT = (
    b"Ladies and Gentlemen of the class of '99: If I could offer you "
    b"only one tip for the future, sunscreen would be it."
)


class TestChaChaAead:
    def test_rfc8439_inputs_empty_aad_parity(self):
        ref = aead_ref.ChaCha20Poly1305Ref(_RFC8439_KEY).encrypt(
            _RFC8439_NONCE, _RFC8439_PT, b""
        )
        frame = (_RFC8439_KEY, _RFC8439_NONCE, _RFC8439_PT)
        assert chacha_aead.seal_frames([frame]) == [ref]
        for pure in (False, True):
            (ct, tag), = chacha_aead._host_pass("seal", [frame], pure=pure)
            assert ct + tag == ref
        opened = chacha_aead.open_frames(
            [(_RFC8439_KEY, _RFC8439_NONCE, ref)]
        )
        assert opened == [_RFC8439_PT]

    def test_randomized_sizes_straddle_block_edges(self):
        sizes = [0, 1, 15, 16, 63, 64, 65, 127, 128, 129, 255, 500, 1021,
                 1024]
        frames = [
            (_key("sz-key-%d" % i), transportplane.nonce_bytes(i),
             _payload("sz-pt-%d" % i, n))
            for i, n in enumerate(sizes)
        ]
        want = [
            aead_ref.ChaCha20Poly1305Ref(k).encrypt(n, p, b"")
            for k, n, p in frames
        ]
        assert chacha_aead.seal_frames(frames) == want
        for pure in (False, True):
            outs = chacha_aead._host_pass("seal", frames, pure=pure)
            assert [ct + tag for ct, tag in outs] == want
        # runner seam ("device" tier) sees the same bytes
        chacha_aead.set_aead_runner(chacha_aead.host_aead_runner)
        assert chacha_aead.seal_frames(frames) == want
        chacha_aead.clear_aead_runner()
        sealed = [(k, n, s) for (k, n, _), s in zip(frames, want)]
        assert chacha_aead.open_frames(sealed) == [p for _, _, p in frames]

    def test_tampered_tag_and_wrong_key_reject(self):
        frames = [
            (_key("tk-key-%d" % i), transportplane.nonce_bytes(i),
             _payload("tk-pt-%d" % i, 100))
            for i in range(5)
        ]
        sealed = chacha_aead.seal_frames(frames)
        work = [(k, n, s) for (k, n, _), s in zip(frames, sealed)]
        # tamper the tag of frame 1, the ciphertext of frame 3
        work[1] = (work[1][0], work[1][1],
                   work[1][2][:-1] + bytes([work[1][2][-1] ^ 1]))
        work[3] = (work[3][0], work[3][1],
                   bytes([work[3][2][0] ^ 0x80]) + work[3][2][1:])
        opened = chacha_aead.open_frames(work)
        assert opened[1] is None and opened[3] is None
        for i in (0, 2, 4):
            assert opened[i] == frames[i][2]
        # wrong key: authentication must fail
        k2 = _key("tk-other-key")
        assert chacha_aead.open_frames(
            [(k2, work[0][1], work[0][2])]
        ) == [None]
        assert tstats.snapshot()["bad_tags"] >= 3

    @pytest.mark.parametrize("mode", ["raise", "hang", "wrong_shape"])
    def test_faulty_device_runner_degrades_not_corrupts(self, mode):
        from cometbft_tpu.crypto import backend_health

        frames = [
            (_key("fb-key-%d" % i), transportplane.nonce_bytes(i),
             _payload("fb-pt-%d" % i, 200))
            for i in range(6)
        ]
        want = chacha_aead.seal_frames(frames)

        def faulty(op, fs):
            if mode == "hang":
                time.sleep(0.05)
                raise TimeoutError("injected hang")
            if mode == "wrong_shape":
                return chacha_aead.host_aead_runner(op, fs)[:-1]
            raise RuntimeError("injected raise")

        chacha_aead.set_aead_runner(faulty)
        outs, tier = chacha_aead.aead_pass("seal", frames)
        assert tier == "numpy"
        assert [ct + tag for ct, tag in outs] == [
            s[:-16] + s[-16:] for s in want
        ]
        # the open VERDICT survives the same faults
        sealed = [(k, n, s) for (k, n, _), s in zip(frames, want)]
        assert chacha_aead.open_frames(sealed) == [p for _, _, p in frames]
        br = backend_health.registry().breaker(chacha_aead.BREAKER)
        assert br.stats()["failures_total"] >= 1
        assert tstats.snapshot()["device_fallbacks"] >= 1

    def test_device_reject_is_confirmed_on_reference(self):
        """A device tier that wrongly rejects a valid tag must not leak
        that verdict: the reject is re-verified on the pure reference
        and the valid plaintext served."""
        from cometbft_tpu.crypto import backend_health

        frames = [
            (_key("rc-key"), transportplane.nonce_bytes(7),
             _payload("rc-pt", 64))
        ]
        sealed = chacha_aead.seal_frames(frames)

        def tag_corruptor(op, fs):
            outs = chacha_aead.host_aead_runner(op, fs)
            return [(pt, bytes(16)) for pt, _ in outs]

        chacha_aead.set_aead_runner(tag_corruptor)
        opened = chacha_aead.open_frames(
            [(frames[0][0], frames[0][1], sealed[0])]
        )
        assert opened == [frames[0][2]]
        snap = tstats.snapshot()
        assert snap["reject_confirms"] >= 1
        assert snap["bad_tags"] == 0
        br = backend_health.registry().breaker(chacha_aead.BREAKER)
        assert br.stats()["failures_total"] >= 1

    def test_kill_switch_and_min_batch_routing(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_AEAD_MIN_BATCH", "8")
        assert not transportplane.batch_active(7)
        assert transportplane.batch_active(8)
        monkeypatch.setenv("COMETBFT_TPU_AEAD", "0")
        assert not transportplane.enabled()
        assert not transportplane.batch_active(100)


class TestTransportPlane:
    def test_prefix_delivery_stops_at_first_bad_tag(self):
        key = _key("plane-key")
        payloads = [_payload("plane-pt-%d" % i, 80) for i in range(8)]
        sealed = transportplane.seal_frames(key, 100, payloads)
        ref = [
            aead_ref.ChaCha20Poly1305Ref(key).encrypt(
                transportplane.nonce_bytes(100 + i), p, b""
            )
            for i, p in enumerate(payloads)
        ]
        assert sealed == ref
        tampered = list(sealed)
        tampered[3] = tampered[3][:-1] + bytes([tampered[3][-1] ^ 1])
        pts, bad = transportplane.open_frames(key, 100, tampered)
        assert bad == 3 and pts == payloads[:3]
        pts, bad = transportplane.open_frames(key, 100, sealed)
        assert bad is None and pts == payloads

    def test_a_stream_sealed_in_bursts_is_one_dispatch_a_burst(self):
        """A stream of frames sealed in bursts on the device runner seam:
        one dispatch a burst, not one a frame, every frame byte-identical
        to the serial reference at its nonce, and the whole stream opens
        back to its payloads burst by burst."""
        dispatches = []

        def counting(op, frames):
            dispatches.append((op, len(frames)))
            return chacha_aead.host_aead_runner(op, frames)

        key = _key("stream-key")
        payloads = [_payload("stream-pt-%d" % i, 300) for i in range(64)]
        chacha_aead.set_aead_runner(counting)
        sealed = []
        for start in range(0, 64, 16):
            sealed += transportplane.seal_frames(
                key, start, payloads[start : start + 16]
            )
        assert dispatches == [("seal", 16)] * 4
        cipher = aead_ref.ChaCha20Poly1305Ref(key)
        assert sealed == [
            cipher.encrypt(transportplane.nonce_bytes(i), p, b"")
            for i, p in enumerate(payloads)
        ]
        for start in range(0, 64, 16):
            pts, bad = transportplane.open_frames(
                key, start, sealed[start : start + 16]
            )
            assert bad is None and pts == payloads[start : start + 16]


# -- handshake admission pool -------------------------------------------------

class TestHandshakePool:
    def test_concurrent_dials_coalesce_into_one_dispatch(self):
        calls = []

        def counting(pairs):
            calls.append(len(pairs))
            return x25519_ladder.host_ladder_runner(pairs)

        x25519_ladder.set_ladder_runner(counting)
        pool = handshake_pool.HandshakePool(
            flush_us=50000.0, queue_cap=64, max_batch=64
        )
        pairs = [
            (_key("pool-scalar-%d" % i), aead_ref.x25519(
                _key("pool-peer-%d" % i), x25519_ladder.BASE_U))
            for i in range(12)
        ]
        try:
            pool.pause()
            futs = [pool.submit(s, p) for s, p in pairs]
            pool.resume()
            got = [f.result(timeout=30) for f in futs]
        finally:
            pool.close()
        assert got == [aead_ref.x25519(s, p) for s, p in pairs]
        assert calls == [12], calls
        snap = tstats.snapshot()
        assert sum(snap["hs_flushes"].values()) == 1
        assert snap["hs_flush_items"] == 12
        assert snap["hs_queue_depth"] == 0

    def test_queue_full_sheds_to_sync_never_drops(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_HANDSHAKE_QUEUE", "1")
        handshake_pool.reset_pool()
        x25519_ladder.set_ladder_runner(x25519_ladder.host_ladder_runner)
        pool = handshake_pool.get_pool()
        pool.pause()
        try:
            blocker = pool.submit(_key("shed-blocker"), _BOB_PUB)
            # the queue is at capacity: exchange() sheds to the sync dial
            # and still returns the right secret
            got = handshake_pool.exchange(_ALICE_PRIV, _BOB_PUB)
            assert got == _SHARED
            snap = tstats.snapshot()
            assert snap["hs_shed"] >= 1
            assert snap["handshakes"]["sync"] >= 1
        finally:
            pool.resume()
        assert blocker.result(timeout=30) == handshake_pool.sync_exchange(
            _key("shed-blocker"), _BOB_PUB
        )

    def test_ladder_fault_resolves_futures_on_host(self):
        def exploding(pairs):
            raise RuntimeError("injected ladder fault")

        x25519_ladder.set_ladder_runner(exploding)
        pool = handshake_pool.HandshakePool(
            flush_us=1000.0, queue_cap=8, max_batch=8
        )
        try:
            fut = pool.submit(_ALICE_PRIV, _BOB_PUB)
            assert fut.result(timeout=30) == _SHARED
        finally:
            pool.close()

    def test_kill_switch_goes_sync(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_HANDSHAKE", "0")
        assert not handshake_pool.enabled()
        assert not handshake_pool.active()
        assert handshake_pool.exchange(_ALICE_PRIV, _BOB_PUB) == _SHARED
        assert handshake_pool.public_key(_ALICE_PRIV) == _ALICE_PUB


# -- SecretConnection frame coalescing ----------------------------------------

def _make_secret_pair(tag="tp"):
    priv1 = Ed25519PrivKey.from_seed(_key(tag + "-sc1"))
    priv2 = Ed25519PrivKey.from_seed(_key(tag + "-sc2"))
    s1, s2 = socket.socketpair()
    out = {}

    def server():
        out["sc2"] = SecretConnection(s2, priv2)

    t = threading.Thread(target=server, daemon=True)
    t.start()
    sc1 = SecretConnection(s1, priv1)
    t.join(timeout=10)
    return sc1, out["sc2"]


class TestSecretConnectionBatching:
    def test_write_frames_batch_read_back_in_order(self):
        sc1, sc2 = _make_secret_pair("batch")
        datas = [_payload("fr-%d" % i, 40 + i) for i in range(10)]
        sc1.write_frames(datas)
        for d in datas:
            assert sc2.read_frame() == d
        snap = tstats.snapshot()
        assert snap["frames"]["batched"] >= 10

    def test_large_msg_roundtrip_with_reader_thread(self):
        sc1, sc2 = _make_secret_pair("large")
        big = _payload("large-msg", 300 * 1024)
        got = {}

        def reader():
            got["msg"] = sc2.read_msg()

        t = threading.Thread(target=reader, daemon=True)
        t.start()
        sc1.write_msg(big)
        t.join(timeout=30)
        assert got["msg"] == big

    def test_batched_read_delivers_prefix_then_sticky_error(self):
        sc1, sc2 = _make_secret_pair("tamper")
        sealed = [
            sc1._send.seal(b"one"),
            sc1._send.seal(b"two"),
            sc1._send.seal(b"bad-after-here"),
            sc1._send.seal(b"never-delivered"),
        ]
        sealed[2] = sealed[2][:-1] + bytes([sealed[2][-1] ^ 1])
        raw = b"".join(struct.pack(">I", len(s)) + s for s in sealed)
        sc1._sock.sendall(raw)
        assert sc2.read_frame() == b"one"
        assert sc2.read_frame() == b"two"
        with pytest.raises(SecretConnectionError):
            sc2.read_frame()
        # the error is sticky: the stream is dead past an auth failure
        with pytest.raises(SecretConnectionError):
            sc2.read_frame()

    def test_kill_switch_bitwise_parity(self, monkeypatch):
        sc1, _sc2 = _make_secret_pair("parity")
        datas = [_payload("parity-%d" % i, 64) for i in range(8)]
        nonce0 = sc1._send.nonce
        batched = sc1._send.seal_batch(datas)
        # rewind and re-seal serially with the plane off
        monkeypatch.setenv("COMETBFT_TPU_AEAD", "0")
        sc1._send.nonce = nonce0
        serial = [sc1._send.seal(d) for d in datas]
        assert batched == serial


# -- repo discipline ----------------------------------------------------------

def test_aead_callsites_lint_clean():
    import pathlib
    import sys

    repo = pathlib.Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(repo / "scripts"))
    try:
        import check_aead_callsites as lint

        assert lint.scan(repo) == []
    finally:
        sys.path.remove(str(repo / "scripts"))


# -- real kernels (slow lane) -------------------------------------------------

@pytest.mark.slow
class TestDeviceKernels:
    def test_chacha_device_pass_matches_reference(self):
        frames = [
            (_key("dev-key-%d" % i), transportplane.nonce_bytes(i),
             _payload("dev-pt-%d" % i, n))
            for i, n in enumerate((0, 1, 64, 100, 1024))
        ]
        want = [
            aead_ref.ChaCha20Poly1305Ref(k).encrypt(n, p, b"")
            for k, n, p in frames
        ]
        outs = chacha_aead.device_pass("seal", frames)
        assert [ct + tag for ct, tag in outs] == want
        opened = chacha_aead.device_pass(
            "open", [(k, n, s[:-16]) for (k, n, _), s in zip(frames, want)]
        )
        for (pt, tag), (_, _, p), s in zip(opened, frames, want):
            assert pt == p and tag == s[-16:]

    def test_x25519_device_exchange_matches_vectors(self):
        pairs = [
            (bytes.fromhex(s), bytes.fromhex(u))
            for s, u, _ in _RFC7748_VECTORS
        ] + [(_ALICE_PRIV, _BOB_PUB)]
        want = [bytes.fromhex(w) for _, _, w in _RFC7748_VECTORS] + [_SHARED]
        assert x25519_ladder.device_exchange(pairs) == want
