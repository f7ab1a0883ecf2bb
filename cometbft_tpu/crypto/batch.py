"""The pluggable batch-verification seam — where the TPU plugs in.

Reference: crypto/batch/batch.go:10-27 and crypto.BatchVerifier
(crypto/crypto.go:44-52).  ``create_batch_verifier(pub_key)`` hands back a
backend-selected verifier; everything above this seam (VoteSet, commit
verification, the light client) is backend-agnostic, exactly as in the
reference design.

Backends:
  * ``tpu``  — batched JAX kernel (cometbft_tpu.ops.verify): decompression,
    ladder and cofactored check on the accelerator; per-signature accept
    bits come back in one shot.
  * ``cpu``  — two-tier host verification (C-speed strict path + ZIP-215
    python fallback), used as oracle and when no accelerator is present.

Unlike the reference (which needs a second pass to attribute failures when a
random-linear-combination batch fails, types/validation.go:308-317), both
backends report per-signature validity directly.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

from cometbft_tpu.crypto import keys as ck

_DEFAULT_BACKEND: Optional[str] = None
_LOCK = threading.Lock()


def _tpu_self_check() -> bool:
    """Startup safety net: verify a known-good + known-bad signature pair on
    the accelerator before trusting it for consensus.  A kernel regression
    (round 2 shipped one) otherwise makes the node reject every valid commit
    on TPU hardware.  Returns True iff the backend is trustworthy."""
    try:
        from cometbft_tpu.crypto import ed25519_ref as ref
        from cometbft_tpu.ops import verify as _ops_verify

        seed = b"\x42" * 32
        pub = ref.pubkey_from_seed(seed)
        msg = b"cometbft-tpu backend self-check"
        sig = ref.sign(seed, msg)
        bad = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        bits = _ops_verify.verify_batch([pub, pub], [msg, msg], [sig, bad])
        ok = bool(bits[0]) and not bool(bits[1])
        if not ok:
            logging.getLogger("cometbft_tpu.crypto").error(
                "TPU crypto backend FAILED its known-answer self-check "
                "(valid=%s, tampered=%s) — falling back to the CPU verify "
                "path; consensus is safe but orders of magnitude slower",
                bool(bits[0]),
                bool(bits[1]),
            )
        return ok
    except Exception:
        logging.getLogger("cometbft_tpu.crypto").exception(
            "TPU crypto backend self-check raised — falling back to the "
            "CPU verify path"
        )
        return False


def default_backend() -> str:
    """'tpu' when an accelerator is visible to JAX *and* it passes a
    known-answer self-check, else 'cpu'.  Overridable via config
    (config.crypto.backend) or COMETBFT_TPU_CRYPTO_BACKEND."""
    global _DEFAULT_BACKEND
    env = os.environ.get("COMETBFT_TPU_CRYPTO_BACKEND")
    if env and env != "auto":
        return env
    with _LOCK:
        if _DEFAULT_BACKEND is None:
            try:
                import jax

                platform = jax.devices()[0].platform
            except Exception:
                # no usable JAX backend at all: say so, a node that was
                # meant to run on a chip must not land here in silence
                logging.getLogger("cometbft_tpu.crypto").exception(
                    "JAX backend initialisation failed — crypto backend "
                    "is 'cpu'"
                )
                platform = "cpu"
            if platform == "cpu":
                _DEFAULT_BACKEND = "cpu"
            elif _tpu_self_check():
                _DEFAULT_BACKEND = "tpu"
            else:
                # _tpu_self_check logged the cause (wrong verdicts or the
                # exception) at error
                logging.getLogger("cometbft_tpu.crypto").error(
                    "a %s device is visible but its verify path failed "
                    "the self-check — crypto backend is 'cpu'",
                    platform,
                )
                _DEFAULT_BACKEND = "cpu"
        return _DEFAULT_BACKEND


def set_default_backend(backend: Optional[str]) -> None:
    global _DEFAULT_BACKEND
    _DEFAULT_BACKEND = backend


class BatchVerifier:
    """Collects (pubkey, msg, sig) triples; verify() returns the overall
    result plus per-signature validity bits."""

    def add(self, pub_key, msg: bytes, sig: bytes) -> None:
        raise NotImplementedError

    def verify(self) -> tuple[bool, list[bool]]:
        raise NotImplementedError

    def __len__(self) -> int:
        raise NotImplementedError


class _CollectingVerifier(BatchVerifier):
    """Shared collection + the two pre-device filters every backend wants:

    1. signature-cache prefilter — verdicts already known (e.g. votes
       verified at gossip time) never reach the backend again; only cache
       MISSES are verified, and fresh verdicts are written back;
    2. structural short-circuit — entries whose pub/sig lengths make them
       impossible for the key type are resolved to False on the host, so a
       batch of garbage does not occupy real device lanes (or inflate the
       padding bucket).

    Subclasses implement ``_verify_pending(pubs, msgs, sigs)`` over the
    surviving entries.  ``COMETBFT_TPU_SIGCACHE=0`` turns filter 1 off,
    restoring uncached behavior exactly (filter 2 only resolves entries
    every backend already reports False for)."""

    PUB_SIZES: tuple = ()  # empty = no structural filter on that field
    SIG_SIZES: tuple = ()

    def __init__(self):
        self.pubs: list[bytes] = []
        self.msgs: list[bytes] = []
        self.sigs: list[bytes] = []
        # entries of the last verify() that needed no backend: cache hits and
        # structural rejects (the ``hits`` of the caller's batch.verify span)
        self.cache_hits = 0
        # cache keys the last verify() hashed (the span's ``keys``): one for
        # each structurally possible entry, none with the cache switched off
        self.keys_hashed = 0
        # the keys of the entries handed to ``_verify_pending``, aligned with
        # them (None with the cache off): a backend that queues them for the
        # scheduler sends the keys along, so its dedup hashes nothing
        self.pending_keys: Optional[list] = None

    def add(self, pub_key, msg: bytes, sig: bytes) -> None:
        data = pub_key.bytes() if hasattr(pub_key, "bytes") else bytes(pub_key)
        self.pubs.append(data)
        self.msgs.append(msg)
        self.sigs.append(sig)

    def __len__(self) -> int:
        return len(self.pubs)

    def _verify_pending(
        self, pubs: list[bytes], msgs: list[bytes], sigs: list[bytes]
    ) -> list[bool]:
        raise NotImplementedError

    def verify(self) -> tuple[bool, list[bool]]:
        if not self.pubs:
            return False, []
        from cometbft_tpu.crypto import sigcache

        part = sigcache.partition_misses(
            self.pubs, self.msgs, self.sigs, self.PUB_SIZES, self.SIG_SIZES
        )
        bits, pending = part.bits, part.miss
        self.cache_hits = len(self.pubs) - len(pending)
        self.keys_hashed = part.hashed
        if pending:
            # Attribution contract: ``_verify_pending`` returns DEFINITIVE
            # verdicts only.  An infrastructure failure must either raise
            # (propagates — nothing is cached, the caller sees an error,
            # not a False bit) or yield ``None`` for the affected entries
            # (skipped by writeback so a possibly-valid signature is never
            # negative-cached, then surfaced as a BackendError below).
            pubs, msgs, sigs = self.pubs, self.msgs, self.sigs
            if len(pending) < len(pubs):
                # every entry a miss (every fresh commit) goes on as it is
                pubs = [pubs[i] for i in pending]
                msgs = [msgs[i] for i in pending]
                sigs = [sigs[i] for i in pending]
            self.pending_keys = part.keys
            got = self._verify_pending(pubs, msgs, sigs)
            # the one put of each fresh verdict, whatever path answered it
            # (scheduler, shed tail, direct dispatch, host): under the key
            # the look-up hashed, before this call returns
            sigcache.writeback(part, got)
        if None in bits:
            from cometbft_tpu.crypto import backend_health

            raise backend_health.BackendError(
                "batch backend produced no definitive verdict for some "
                "entries (infrastructure failure, not a signature verdict)"
            )
        # every bit is a bool by now: a structural False, a cached verdict
        # or ``writeback``'s
        return all(bits) and len(bits) > 0, bits


class CpuBatchVerifier(_CollectingVerifier):
    PUB_SIZES = (32,)
    SIG_SIZES = (64,)

    def _verify_pending(self, pubs, msgs, sigs) -> list[bool]:
        return [
            ck.Ed25519PubKey(p).verify_signature(m, s)
            for p, m, s in zip(pubs, msgs, sigs)
        ]


class TpuBatchVerifier(_CollectingVerifier):
    PUB_SIZES = (32,)
    SIG_SIZES = (64,)

    def _verify_pending(self, pubs, msgs, sigs) -> list[bool]:
        from cometbft_tpu import verifysched

        if verifysched.scheduler_active():
            # the cache misses ride the process-wide continuous-batching
            # scheduler (at the caller's ambient priority class), so this
            # commit's segment coalesces with concurrent gossip/evidence/
            # light/catchup work into one fused dispatch — the scheduler
            # resolves only definitive supervised verdicts, matching this
            # method's attribution contract
            return verifysched.verify_segment_sync(
                pubs, msgs, sigs, keys=self.pending_keys
            )
        from cometbft_tpu.ops import verify as _ops_verify

        return [bool(b) for b in _ops_verify.verify_batch(pubs, msgs, sigs)]


_SECP_DEVICE_OK: Optional[bool] = None


def _secp_device_ok() -> bool:
    """Lazy gate for the TPU ECDSA path: a known-answer accept/reject pair
    must match the host library before consensus trusts the device ladder
    (same discipline as ``_tpu_self_check``).  COMETBFT_TPU_SECP_DEVICE=1/0
    forces."""
    global _SECP_DEVICE_OK
    env = os.environ.get("COMETBFT_TPU_SECP_DEVICE")
    if env == "1":
        return True
    if env == "0":
        return False
    with _LOCK:
        if _SECP_DEVICE_OK is None:
            try:
                from cometbft_tpu.crypto.secp256k1 import Secp256k1PrivKey
                from cometbft_tpu.ops import secp_verify as sv

                priv = Secp256k1PrivKey.from_secret(
                    b"cometbft-tpu secp self-check"
                )
                pub = priv.pub_key().bytes()
                msg = b"secp backend self-check"
                sig = priv.sign(msg)
                bad = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
                bits = sv.verify_batch([pub, pub], [msg, msg], [sig, bad])
                _SECP_DEVICE_OK = bool(bits[0]) and not bool(bits[1])
                if not _SECP_DEVICE_OK:
                    logging.getLogger("cometbft_tpu.crypto").error(
                        "TPU secp256k1 backend FAILED its known-answer "
                        "self-check - using sequential host verification"
                    )
            except Exception:
                _SECP_DEVICE_OK = False
        return _SECP_DEVICE_OK


class Secp256k1BatchVerifier(_CollectingVerifier):
    """Per-lane batched ECDSA on the device (ops/secp_verify) — a TPU-era
    extension past the reference, which verifies secp256k1 sequentially
    (crypto/secp256k1/secp256k1.go; BASELINE config #4 tracks this).
    Falls back to the host `cryptography` library when the device fails
    its self-check or ``backend='cpu'`` pins it off."""

    PUB_SIZES = (33,)
    SIG_SIZES = (64,)

    def __init__(self, backend: Optional[str] = None):
        super().__init__()
        self._backend = backend

    def _verify_pending(self, pubs, msgs, sigs) -> list[bool]:
        if self._backend != "cpu" and _secp_device_ok():
            # supervised: the breaker decides whether the device is
            # probed at all, the watchdog bounds a wedge, and a failure
            # demotes (metrics + backoff) instead of silently retrying
            # the dead device on every batch
            from cometbft_tpu.crypto import backend_health
            from cometbft_tpu.ops import supervisor

            def _device():
                from cometbft_tpu.ops import secp_verify as sv

                return [bool(b) for b in sv.verify_batch(pubs, msgs, sigs)]

            def _validate(bits):
                if len(bits) != len(pubs):
                    raise backend_health.BackendOutputError(
                        f"secp device returned {len(bits)} bits "
                        f"for {len(pubs)} inputs"
                    )

            bits = supervisor.supervised_device_call(
                "secp_device", _device, _validate,
                fallback_units=len(pubs),
            )
            if bits is not None:
                return bits
        from cometbft_tpu.crypto.secp256k1 import Secp256k1PubKey

        bits = []
        for p, m, s in zip(pubs, msgs, sigs):
            try:
                bits.append(Secp256k1PubKey(p).verify_signature(m, s))
            except ValueError:
                bits.append(False)
        return bits


_BLS_DEVICE_OK: Optional[bool] = None


def _bls_device_ok() -> bool:
    """Lazy gate for the TPU G1 path inside BLS batch verification: an
    accelerator must be visible AND a known-answer scalar-mul must match
    the host oracle before consensus trusts it (same discipline as
    ``_tpu_self_check``).  COMETBFT_TPU_BLS_DEVICE=1/0 forces."""
    global _BLS_DEVICE_OK
    env = os.environ.get("COMETBFT_TPU_BLS_DEVICE")
    if env == "1":
        return True
    if env == "0":
        return False
    with _LOCK:
        if _BLS_DEVICE_OK is None:
            try:
                import jax

                if jax.devices()[0].platform == "cpu":
                    # XLA-CPU runs the limb kernels orders of magnitude
                    # slower than host bigints — device path is TPU-only
                    _BLS_DEVICE_OK = False
                else:
                    from cometbft_tpu.crypto import bls12381 as bls
                    from cometbft_tpu.ops import bls_g1 as g1

                    gen = bls.E1.affine(bls.G1_GEN)
                    got = g1.batch_scalar_mul([gen], [0x1234], nbits=16)[0]
                    want = bls.E1.affine(
                        bls.E1.mul_scalar(bls.G1_GEN, 0x1234)
                    )
                    _BLS_DEVICE_OK = got == want
                    if not _BLS_DEVICE_OK:
                        logging.getLogger("cometbft_tpu.crypto").error(
                            "TPU BLS G1 backend FAILED its known-answer "
                            "self-check - using host arithmetic"
                        )
            except Exception:
                _BLS_DEVICE_OK = False
        return _BLS_DEVICE_OK


class BlsBatchVerifier(_CollectingVerifier):
    """Random-linear-combination batch verification for bls12_381.

    Check (basic scheme, per-vote distinct messages NOT required):

        e(G1, Σ rᵢ·Sᵢ)  ==  Π e(rᵢ·pkᵢ, H(mᵢ)),   rᵢ random 128-bit

    which costs n+1 Miller loops + ONE final exponentiation instead of the
    2n + n of sequential verifies.  The rᵢ·pkᵢ multi-scalar-mul runs on
    the TPU G1 kernel (ops/bls_g1) when the accelerator passes its
    self-check; G2 scalar work and the pairing product stay on the host
    (SURVEY §2.1.1 allows host pairing — one pair per batch after MSM).
    A failed combination falls back to per-signature verification for
    attribution, mirroring the reference's recheck pass
    (types/validation.go:308-317; key type crypto/bls12381/key_bls12381.go:
    160-188).

    ``backend='cpu'`` (the operator's accelerator kill-switch — config
    crypto.backend / COMETBFT_TPU_CRYPTO_BACKEND) pins the scalar-mul work
    to the host regardless of the device self-check."""

    PUB_SIZES = (96,)  # bls12381.PUB_KEY_SIZE (uncompressed G1)
    SIG_SIZES = (96,)  # bls12381.SIGNATURE_SIZE (compressed G2)

    def __init__(self, backend: Optional[str] = None):
        super().__init__()
        self._backend = backend

    def _verify_pending(self, pubs, msgs, sigs) -> list[bool]:
        import secrets

        from cometbft_tpu.crypto import bls12381 as bls

        n = len(pubs)
        lib = bls._nat()
        if lib is not None:
            return self._verify_native(lib, pubs, msgs, sigs)
        bits = [False] * n
        entries = []  # (index, pk_jac, h_jac, sig_jac)
        for i in range(n):
            pub, msg, sig = pubs[i], msgs[i], sigs[i]
            if len(pub) != bls.PUB_KEY_SIZE or len(sig) != bls.SIGNATURE_SIZE:
                continue
            pk = bls.g1_deserialize(pub)
            if pk is None or bls.E1.is_infinity(pk) or not bls._g1_subgroup(pk):
                continue
            s = bls.g2_uncompress(sig)
            if s is None or not bls._g2_subgroup(s):
                continue
            entries.append((i, pk, bls.hash_to_g2(msg), s))
        if not entries:
            return bits
        if len(entries) == 1:
            i, _, _, _ = entries[0]
            bits[i] = bls.verify(pubs[i], msgs[i], sigs[i])
            return bits

        rs = [secrets.randbits(128) | 1 for _ in entries]
        scaled = self._scaled_pubkeys(
            [e[1] for e in entries], rs, self._backend
        )
        agg = bls.E2.infinity()
        for (_, _, _, s), r in zip(entries, rs):
            agg = bls.E2.add_pts(agg, bls.E2.mul_scalar(s, r))
        pairs = [
            (bls.E1.neg_pt(rpk), h)
            for rpk, (_, _, h, _) in zip(scaled, entries)
        ]
        pairs.append((bls.G1_GEN, agg))
        if bls._pairing_product_is_one(pairs):
            for i, _, _, _ in entries:
                bits[i] = True
            return bits
        # attribution fallback: the combination failed, find the culprits
        return self._per_signature(pubs, msgs, sigs, [e[0] for e in entries], bits)

    @staticmethod
    def _per_signature(pubs, msgs, sigs, entries, bits) -> list[bool]:
        """Verify each structurally-valid entry on its own.  This is the
        refuge when a native batch op errors: such an error is an
        infrastructure failure, not evidence against any signature, so it
        must not surface as all-False bits (which would misattribute the
        failure to every signer in the batch)."""
        from cometbft_tpu.crypto import bls12381 as bls

        for i in entries:
            bits[i] = bls.verify(pubs[i], msgs[i], sigs[i])
        return bits

    def _verify_native(self, lib, pubs, msgs, sigs) -> list[bool]:
        """RLC batch verification with every host-side group/pairing op in
        the native library; the TPU G1 MSM still handles the rᵢ·pkᵢ
        multi-scalar-mul when the device passes its self-check.  Same
        check and attribution semantics as the pure-Python path.  Any
        native-op *error* (nonzero return) drops to ``_per_signature``."""
        import ctypes
        import secrets

        from cometbft_tpu.crypto import bls12381 as bls

        n = len(pubs)
        bits = [False] * n
        entries = []  # index of each structurally-valid (pub, msg, sig)
        for i in range(n):
            pub, sig = pubs[i], sigs[i]
            if len(pub) != bls.PUB_KEY_SIZE or len(sig) != bls.SIGNATURE_SIZE:
                continue
            if lib.bls_pubkey_validate(pub, len(pub)) != 1:
                continue
            if lib.bls_sig_validate(sig) != 1:
                continue
            entries.append(i)
        if not entries:
            return bits
        if len(entries) == 1:
            i = entries[0]
            bits[i] = bls.verify(pubs[i], msgs[i], sigs[i])
            return bits

        rs = [secrets.randbits(128) | 1 for _ in entries]
        r_bytes = [r.to_bytes(16, "big") for r in rs]

        # rᵢ·pkᵢ — TPU MSM when trusted, else native scalar mul.  With the
        # bls_g1 breaker open, skip straight to the native library: routing
        # through _scaled_pubkeys would land on the much slower pure-Python
        # host fallback, and the native path is the better degraded tier.
        use_device = self._backend != "cpu" and _bls_device_ok()
        if use_device:
            from cometbft_tpu.crypto import backend_health

            use_device = (
                backend_health.registry().breaker("bls_g1").state
                != backend_health.OPEN
            )
        g1_parts = []
        if use_device:
            pks = [bls.g1_deserialize(pubs[i]) for i in entries]
            for pt in self._scaled_pubkeys(pks, rs, self._backend):
                g1_parts.append(bls.g1_serialize(bls.E1.neg_pt(pt)))
        else:
            for i, rb in zip(entries, r_bytes):
                out = ctypes.create_string_buffer(96)
                if lib.bls_g1_scalar_mul(pubs[i], rb, 16, out) != 0:
                    return self._per_signature(pubs, msgs, sigs, entries, bits)
                g1_parts.append(bls.g1_negate_serialized(out.raw))

        # Σ rᵢ·Sᵢ and H(mᵢ), all native
        scaled_sigs = []
        hashes = []
        for i, rb in zip(entries, r_bytes):
            so = ctypes.create_string_buffer(96)
            if lib.bls_g2_scalar_mul_compressed(sigs[i], rb, 16, so) != 0:
                return self._per_signature(pubs, msgs, sigs, entries, bits)
            scaled_sigs.append(so.raw)
            ho = ctypes.create_string_buffer(96)
            msg = msgs[i]
            if lib.bls_hash_to_g2(msg, len(msg), ho) != 0:
                return self._per_signature(pubs, msgs, sigs, entries, bits)
            hashes.append(ho.raw)
        agg = ctypes.create_string_buffer(96)
        if lib.bls_aggregate_sigs(
            b"".join(scaled_sigs), len(scaled_sigs), agg
        ) != 0:
            return self._per_signature(pubs, msgs, sigs, entries, bits)

        from cometbft_tpu.crypto.bls12381 import G1_GEN, g1_serialize

        g1cat = b"".join(g1_parts) + g1_serialize(G1_GEN)
        g2cat = b"".join(hashes) + agg.raw
        if lib.bls_pairing_product_is_one_serialized(
            g1cat, g2cat, len(entries) + 1
        ) == 1:
            for i in entries:
                bits[i] = True
            return bits
        # attribution fallback: the combination failed, find the culprits
        return self._per_signature(pubs, msgs, sigs, entries, bits)

    @staticmethod
    def _scaled_pubkeys(pks, rs, backend: Optional[str] = None):
        """[rᵢ·pkᵢ] as jacobian host points; TPU kernel when trusted and
        not disabled by the backend kill-switch.  Supervised: the bls_g1
        breaker skips a dead device, the watchdog bounds a wedge, and a
        failure demotes to host arithmetic with the same metrics as the
        ed25519 chain (scalar-mul output feeds a pairing CHECK, so a host
        fallback changes cost, never verdicts)."""
        from cometbft_tpu.crypto import bls12381 as bls

        if backend != "cpu" and _bls_device_ok():
            from cometbft_tpu.crypto import backend_health
            from cometbft_tpu.ops import supervisor

            def _device():
                from cometbft_tpu.ops import bls_g1 as g1

                affs = [bls.E1.affine(pk) for pk in pks]
                out = g1.batch_scalar_mul(affs, rs, nbits=128)
                return [
                    bls.E1.infinity() if a is None else (a[0], a[1], 1)
                    for a in out
                ]

            def _validate(out):
                if len(out) != len(pks):
                    raise backend_health.BackendOutputError(
                        f"bls_g1 returned {len(out)} points for "
                        f"{len(pks)} inputs"
                    )

            out = supervisor.supervised_device_call(
                "bls_g1", _device, _validate, fallback_units=len(pks)
            )
            if out is not None:
                return out
        return [bls.E1.mul_scalar(pk, r) for pk, r in zip(pks, rs)]


def supports_batch_verifier(pub_key) -> bool:
    """Reference: crypto/batch/batch.go:21 — ed25519 there; bls12_381 joins
    via the aggregate path (key_bls12381.go:160-188); secp256k1 is the
    TPU-era extension (BASELINE config #4; no batch in the reference)."""
    return getattr(pub_key, "type_", None) in (
        ck.ED25519_KEY_TYPE,
        ck.BLS12381_KEY_TYPE,
        ck.SECP256K1_KEY_TYPE,
    )


def create_batch_verifier(pub_key, backend: Optional[str] = None) -> BatchVerifier:
    """Reference: crypto/batch/batch.go:10."""
    if not supports_batch_verifier(pub_key):
        raise ValueError(f"key type does not support batch verification: {pub_key}")
    key_type = getattr(pub_key, "type_", None)
    if key_type in (ck.BLS12381_KEY_TYPE, ck.SECP256K1_KEY_TYPE):
        env = os.environ.get("COMETBFT_TPU_CRYPTO_BACKEND")
        if (backend is None or backend == "auto") and env and env != "auto":
            backend = env
        if key_type == ck.SECP256K1_KEY_TYPE:
            return Secp256k1BatchVerifier(backend=backend)
        return BlsBatchVerifier(backend=backend)
    if backend is None or backend == "auto":
        backend = default_backend()
    if backend == "tpu":
        return TpuBatchVerifier()
    if backend == "cpu":
        return CpuBatchVerifier()
    raise ValueError(f"unknown crypto backend: {backend}")
