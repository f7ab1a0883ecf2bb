"""Correctness artifact for the COMPILED verify kernel on real hardware.

The CI parity test for the Pallas kernel runs in interpret mode on CPU
(tests/test_pallas.py); this script runs the same known-answer + tampered
vector suite through the actually-compiled kernel on the live platform and
writes a JSON verdict to CHIP_VALIDATE.json — a hardware-correctness record
independent of the throughput bench (VERDICT r3 #4).

Vector semantics: ZIP-215 as the reference's ed25519 verify applies it
(crypto/ed25519/ed25519.go:170-222) — cofactored equation, non-canonical
A/R encodings accepted, s strictly < L.

Usable two ways:
  * `validate_with(call, bucket)` — bench.py hands in its already-compiled
    one-chip executable; vectors are packed into that bucket's one buffer
    (no extra compile).
  * `python scripts/chip_validate.py` — standalone: selects the platform's
    kernel like production does, resolves the bucket executable production
    launches (compile or AOT load) at a small bucket, validates, writes the
    artifact.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ARTIFACT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "CHIP_VALIDATE.json",
)


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


def validate_with(call, bucket: int) -> dict:
    """Run the vector suite through ``call`` (a compiled one-chip executable
    taking the ONE packed buffer of ``bucket`` lanes).  Returns the verdict
    dict."""
    import numpy as np

    from cometbft_tpu.ops import verify as ov

    pubs, msgs, sigs, expect, labels = _vectors()
    packed, n, structural, _ = ov.pack_batch(pubs, msgs, sigs, bucket)
    assert structural.shape == (bucket,), (structural.shape, bucket)
    accept = np.asarray(call(packed))
    got = list((accept & structural)[:n])
    failures = [
        {"label": lbl, "want": bool(w), "got": bool(g)}
        for lbl, w, g in zip(labels, expect, got)
        if bool(w) != bool(g)
    ]
    return {
        "ok": not failures,
        "n_vectors": n,
        "failures": failures,
    }


def write_artifact(verdict: dict, impl: str, platform: str) -> None:
    """Append this run's verdict to CHIP_VALIDATE.json (keeping prior runs:
    the whole point of the artifact is the hardware-failure evidence).
    Top-level ``ok`` reflects the LATEST run per (impl, platform)."""
    rec = dict(verdict)
    rec.update(
        impl=impl,
        platform=platform,
        timestamp=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    )
    runs = []
    try:
        with open(ARTIFACT) as f:
            runs = json.load(f).get("runs", [])
    except (OSError, ValueError):
        pass
    runs.append(rec)
    runs = runs[-20:]  # bound growth across rounds
    latest = {}
    for r in runs:
        latest[(r.get("impl"), r.get("platform"))] = bool(r.get("ok"))
    doc = {"ok": all(latest.values()), "latest": rec, "runs": runs}
    with open(ARTIFACT, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


def main() -> int:
    from cometbft_tpu.libs import cachedir

    cachedir.enable()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cometbft_tpu.ops import verify as ov

    platform = jax.devices()[0].platform
    impl = "pallas" if ov._use_pallas() else "xla"
    # the smallest bucket that holds the vector suite
    bucket = ov.bucket_size(len(_vectors()[0]))
    call, info = ov.bucket_executable(impl, bucket)
    verdict = validate_with(
        lambda packed: np.asarray(call(jnp.asarray(packed))), bucket=bucket
    )
    write_artifact(verdict, impl=impl, platform=platform)
    print(json.dumps({**verdict, "impl": impl, "platform": platform, **info}))
    return 0 if verdict["ok"] else 3


if __name__ == "__main__":
    sys.exit(main())
