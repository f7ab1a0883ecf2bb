"""Correctness check of the COMPILED verify kernel on the live platform.

The CI parity test for the Pallas kernel runs in interpret mode on CPU
(tests/test_pallas.py); this script runs the same known-answer + tampered
vector suite through the actually-compiled kernel and prints its verdict
as one JSON line (exit 0 when every vector agrees, 3 otherwise).  It
writes no file: a record a CPU run could overwrite under a chip's name
would say nothing about the chip.

Vector semantics: ZIP-215 as the reference's ed25519 verify applies it
(crypto/ed25519/ed25519.go:170-222) — cofactored equation, non-canonical
A/R encodings accepted, s strictly < L.

    python scripts/chip_validate.py

selects the platform's kernel like production does, resolves the bucket
executable production launches (compile or AOT load) at the smallest
bucket that holds the vectors, and validates it.  ``_vectors`` is also
the known-answer set of ``chip_smoke.py`` and of the tests.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _vectors():
    """(pubs, msgs, sigs, expect, labels): valid signatures plus every
    tamper class the kernel must reject — and the ZIP-215 edge encodings it
    must accept."""
    from cometbft_tpu.crypto import ed25519_ref as ref

    pubs, msgs, sigs, expect, labels = [], [], [], [], []

    def add(pub, msg, sig, want, label):
        pubs.append(pub)
        msgs.append(msg)
        sigs.append(sig)
        expect.append(want)
        labels.append(label)

    base = []
    for i in range(8):
        seed = bytes([i + 1]) * 32
        pub = ref.pubkey_from_seed(seed)
        msg = b"chip-validate-%d" % i
        sig = ref.sign(seed, msg)
        base.append((seed, pub, msg, sig))
        add(pub, msg, sig, True, f"valid-{i}")

    _, pub, msg, sig = base[0]
    add(pub, msg, bytes([sig[0] ^ 1]) + sig[1:], False, "tampered-R")
    add(pub, msg + b"!", sig, False, "tampered-msg")
    add(pub, msg, sig[:32] + bytes([sig[32] ^ 1]) + sig[33:], False,
        "tampered-s")
    s_int = int.from_bytes(sig[32:], "little")
    add(pub, msg, sig[:32] + (s_int + ref.L).to_bytes(32, "little"), False,
        "non-canonical-s")
    _, pub2, msg2, sig2 = base[1]
    add(pub2, msg2, sig[:32] + sig2[32:], False, "swapped-halves")
    add(bytes([pub[0] ^ 1]) + pub[1:], msg, sig, False, "wrong-pub")

    # ZIP-215 edge: identity-key signature — A = non-canonical encoding of
    # the identity (y = P+1 ≡ 1, sign bit 0).  With A = identity the verify
    # equation collapses to [8](s·B − R) == 0, so R = s·B, s = 0 must
    # accept under ZIP-215 (cofactored, non-canonical encodings allowed).
    ident_pub = (ref.P + 1).to_bytes(32, "little")
    ident_sig = ident_pub + bytes(32)  # R = identity (non-canonical), s = 0
    add(ident_pub, b"zip215-identity", ident_sig, True, "zip215-identity-key")
    # same identity key, nonzero s: R must equal s·B — mismatch rejects
    add(ident_pub, b"zip215-identity", ident_pub + (1).to_bytes(32, "little"),
        False, "zip215-identity-bad-s")

    # structural rejects (wrong lengths) — prepare_batch masks these out
    add(pub[:31], msg, sig, False, "short-pub")
    add(pub, msg, sig[:63], False, "short-sig")

    # cross-check every expectation against the python oracle
    for p, m, s, want, label in zip(pubs, msgs, sigs, expect, labels):
        got = (
            ref.verify_zip215(p, m, s)
            if len(p) == 32 and len(s) == 64
            else False
        )
        assert got == want, f"oracle disagrees on {label}: {got} != {want}"
    return pubs, msgs, sigs, expect, labels


def main() -> int:
    from cometbft_tpu.libs import cachedir

    cachedir.enable()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cometbft_tpu.ops import verify as ov

    platform = jax.devices()[0].platform
    impl = "pallas" if ov._use_pallas() else "xla"
    pubs, msgs, sigs, expect, labels = _vectors()
    # the smallest bucket that holds the vector suite
    bucket = ov.bucket_size(len(pubs))
    call, info = ov.bucket_executable(impl, bucket)
    packed, n, structural, _ = ov.pack_batch(pubs, msgs, sigs, bucket)
    accept = np.asarray(call(jnp.asarray(packed)))
    got = list((accept & structural)[:n])
    failures = [
        {"label": lbl, "want": bool(w), "got": bool(g)}
        for lbl, w, g in zip(labels, expect, got)
        if bool(w) != bool(g)
    ]
    print(json.dumps({
        "ok": not failures, "n_vectors": n, "failures": failures,
        "impl": impl, "platform": platform, **info,
    }))
    return 3 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
