"""Pallas verify-kernel parity: interpret mode (CPU) vs the XLA path.

The compiled Mosaic kernel only runs on real TPU hardware; interpret mode
executes the same kernel logic op-for-op on CPU, so this is the CI-side
differential gate for ``ops.pallas_verify`` (on the chip, ``chip_smoke.py``
and the benchmark's known-answer check run the compiled kernel).

Interpret mode dispatches every ladder iteration eagerly (~10 min for one
batch), so the full-parity test is opt-in via COMETBFT_TPU_SLOW_TESTS=1;
the default suite still covers the kernel *body* logic because it is the
very same ``fe25519``/``ed25519_point`` functions the XLA path uses
(differentially tested in test_fe25519 / test_ed25519_jax).
"""

import os

import numpy as np
import jax.numpy as jnp
import pytest

from jax.experimental import pallas as pl

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import verify as ov

pytestmark = pytest.mark.skipif(
    not os.environ.get("COMETBFT_TPU_SLOW_TESTS"),
    reason="interpret-mode Pallas is minutes-slow; set "
    "COMETBFT_TPU_SLOW_TESTS=1 (on the chip, chip_smoke.py and the "
    "benchmark's known-answer check cover the compiled kernel)",
)


@pytest.fixture()
def interpret_pallas(monkeypatch):
    import cometbft_tpu.ops.pallas_verify as pv

    orig = pl.pallas_call

    def patched(*args, **kwargs):
        kwargs.setdefault("interpret", True)
        return orig(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", patched)
    pv._build.cache_clear()
    yield pv
    pv._build.cache_clear()


def test_pallas_matches_xla(interpret_pallas):
    pv = interpret_pallas
    pubs, msgs, sigs = [], [], []
    for i in range(16):
        seed = bytes([i]) * 32
        pubs.append(ref.pubkey_from_seed(seed))
        msgs.append(b"pallas %d" % i)
        sigs.append(ref.sign(seed, msgs[-1]))
    # tamper: bad R, bad message, non-canonical s, ZIP-215-valid identity key
    sigs[0] = bytes([sigs[0][0] ^ 1]) + sigs[0][1:]
    msgs[1] = msgs[1] + b"!"
    s = int.from_bytes(sigs[2][32:], "little")
    sigs[2] = sigs[2][:32] + (s + ref.L).to_bytes(32, "little")
    nc = (ref.P + 1).to_bytes(32, "little")
    pubs[3], sigs[3] = nc, nc + bytes(32)

    arrays, n, structural = ov.prepare_batch(pubs, msgs, sigs)
    dev = {k: jnp.asarray(v) for k, v in arrays.items()}
    got = np.asarray(
        pv.verify_core_pallas(
            dev["a_bytes"], dev["r_bytes"], dev["s_bytes"], dev["m_bytes"],
            dev["s_ok"], tile=128,
        )
    )
    want = np.asarray(ov.verify_core(**dev))
    assert (got == want).all()
    expect = [
        ref.verify_zip215(p, m, s) if len(s) == 64 and len(p) == 32 else False
        for p, m, s in zip(pubs, msgs, sigs)
    ]
    assert list(got[:n] & structural[:n]) == expect
