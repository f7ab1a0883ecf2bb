"""The one-chip executable's input: ONE packed buffer, split on the device
(``ops/verify.verify_packed``).  On the XLA tier on the CPU, through
``bucket_executable`` as a launch resolves it, its accept bits are bit for
bit those of the plain reference at the 128-lane bucket and one larger, on
the 18 known-answer vectors and a seeded mixed batch, and at 128 lanes those
of the five-input ``verify_core`` over the buffer's views.  Each of those
compiles an XLA-CPU verify, so they are warmcache-gated like the suite's
other verifies; the split itself is cheap and always runs."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import verify as ov
from scripts.chip_validate import _vectors


def _mixed(seed: int, n: int):
    """Signed triples with tampered signatures, non-canonical ``s``, keys
    that are no point, small-order keys and wrong lengths among them."""
    rng = random.Random(seed)
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        key = rng.randbytes(32)
        msg = rng.randbytes(rng.randrange(0, 200))
        pub, sig = ref.pubkey_from_seed(key), ref.sign(key, msg)
        kind = i % 8
        if kind == 1:  # tampered R
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        elif kind == 2:  # tampered s
            sig = sig[:40] + bytes([sig[40] ^ 4]) + sig[41:]
        elif kind == 3:  # s + L: not canonical
            s = int.from_bytes(sig[32:], "little") + ref.L
            sig = sig[:32] + s.to_bytes(32, "little")
        elif kind == 4:  # y = 2 is on no point
            pub = (2).to_bytes(32, "little")
        elif kind == 5:  # the identity, non-canonically: ZIP-215 accepts
            pub = (ref.P + 1).to_bytes(32, "little")
            sig = pub + bytes(32)
        elif kind == 6:  # a wrong length
            sig = sig[:63]
        pubs.append(pub)
        msgs.append(msg)
        sigs.append(sig)
    return pubs, msgs, sigs


def _oracle(pubs, msgs, sigs):
    return [
        len(p) == 32 and len(s) == 64 and ref.verify_zip215(p, m, s)
        for p, m, s in zip(pubs, msgs, sigs)
    ]


def _batch(b: int):
    """The 18 known-answer vectors, then mixed triples, three lanes short
    of the bucket."""
    kp, km, ks, expect, _ = _vectors()
    assert len(kp) == 18
    mp, mm, ms = _mixed(b, b - 18 - 3)
    return kp + mp, km + mm, ks + ms, expect


def _packed_call(b: int, packed):
    call, _ = ov.bucket_executable("xla", b)
    return np.asarray(call(jnp.asarray(packed)))


@pytest.mark.parametrize(
    "b",
    [
        pytest.param(128, marks=pytest.mark.warmcache("verify-xla-packed-128")),
        pytest.param(256, marks=pytest.mark.warmcache("verify-xla-packed-256")),
    ],
)
def test_the_packed_executable_equals_the_reference(b):
    pubs, msgs, sigs, expect = _batch(b)
    packed, n, structural, _ = ov.pack_batch(pubs, msgs, sigs, b)
    assert packed.shape == (ov.packed_rows(b), 32)
    got = _packed_call(b, packed)
    assert got.shape == (b,) and got.dtype == np.bool_
    assert not got[n:].any()
    want = _oracle(pubs, msgs, sigs)
    assert list(got[:n] & structural[:n]) == want
    assert list(want[:18]) == list(expect)
    assert 0 < sum(want) < n


@pytest.mark.warmcache("verify-xla-packed-128")
def test_the_packed_executable_equals_the_five_input_kernel():
    pubs, msgs, sigs, _ = _batch(128)
    packed = ov.pack_batch(pubs, msgs, sigs, 128)[0]
    five = jax.jit(ov.verify_core)(
        **{k: jnp.asarray(v) for k, v in ov.packed_views(packed).items()}
    )
    assert (_packed_call(128, packed) == np.asarray(five)).all()


@pytest.mark.parametrize("b", [128, 512, 8192])
def test_the_split_on_the_device_gives_the_five_arrays(b):
    pubs, msgs, sigs = _mixed(7, 21)
    packed = ov.pack_batch(pubs, msgs, sigs, b)[0]
    arrays = ov.packed_views(packed)
    *tables, ok = ov.split_packed(jnp.asarray(packed))
    for k, t in zip(ov.ARG_NAMES, tables):
        assert t.shape == (b, 32) and t.dtype == jnp.uint8
        assert np.array_equal(np.asarray(t), arrays[k]), k
    assert ok.shape == (b,)
    assert np.array_equal(np.asarray(ok) != 0, arrays["s_ok"])
