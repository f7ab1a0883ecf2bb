"""The 18 known-answer vectors: valid signatures, every tamper class the
verifier must reject, and the ZIP-215 edge encodings it must accept.

A copy of ``scripts/chip_validate._vectors`` as of PR 22, built on the
benchmark's own reference.  An entry sends them through the program's batch
verifier once in set-up, outside the window: the traffic holds honest
signatures and four tamper classes, and no edge encoding.
"""

from __future__ import annotations

from benchmarks import ed25519_ref as ref


def vectors():
    """(pubs, msgs, sigs, expect, labels)."""
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
    add(pub, msg, sig[:32] + bytes([sig[32] ^ 1]) + sig[33:], False, "tampered-s")
    s_int = int.from_bytes(sig[32:], "little")
    add(pub, msg, sig[:32] + (s_int + ref.L).to_bytes(32, "little"), False,
        "non-canonical-s")
    _, pub2, msg2, sig2 = base[1]
    add(pub2, msg2, sig[:32] + sig2[32:], False, "swapped-halves")
    add(bytes([pub[0] ^ 1]) + pub[1:], msg, sig, False, "wrong-pub")

    # ZIP-215 edge: A = a non-canonical encoding of the identity (y = p+1,
    # sign bit 0).  The equation collapses to [8](s*B - R) == 0, so R = the
    # identity and s = 0 must be accepted; a nonzero s must not.
    ident_pub = (ref.P + 1).to_bytes(32, "little")
    add(ident_pub, b"zip215-identity", ident_pub + bytes(32), True,
        "zip215-identity-key")
    add(ident_pub, b"zip215-identity", ident_pub + (1).to_bytes(32, "little"),
        False, "zip215-identity-bad-s")

    # structural rejects (wrong lengths)
    add(pub[:31], msg, sig, False, "short-pub")
    add(pub, msg, sig[:63], False, "short-sig")

    for p, m, s, want, label in zip(pubs, msgs, sigs, expect, labels):
        got = ref.verify_zip215(p, m, s) if len(p) == 32 and len(s) == 64 else False
        assert got == want, f"the reference disagrees on {label}: {got} != {want}"
    return pubs, msgs, sigs, expect, labels
