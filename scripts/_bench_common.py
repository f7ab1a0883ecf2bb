"""Shared helpers for the chip benchmark scripts: signature-batch fixture
generation and min-of-N wall timing (one definition, four users)."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cometbft_tpu.libs import cachedir  # noqa: E402

cachedir.enable()  # one cache root, named before jax is imported

import numpy as np


def make_sig_dev(n: int, distinct_cap: int = 1024):
    """n signed (pub, msg, sig) triples tiled from ``distinct_cap``
    distinct python-oracle signatures, prepared and put on device.
    Returns the device-array dict matching verify_core's kwargs."""
    import jax.numpy as jnp

    from cometbft_tpu.crypto import ed25519_ref as ref
    from cometbft_tpu.ops import verify as ov

    distinct = min(n, distinct_cap)
    pubs, msgs, sigs = [], [], []
    for i in range(distinct):
        seed = i.to_bytes(4, "little") * 8
        pubs.append(ref.pubkey_from_seed(seed))
        msgs.append(b"bench-%d" % i)
        sigs.append(ref.sign(seed, b"bench-%d" % i))
    reps = -(-n // distinct)
    arrays, _, _ = ov.prepare_batch(
        (pubs * reps)[:n], (msgs * reps)[:n], (sigs * reps)[:n]
    )
    return {k: jnp.asarray(v) for k, v in arrays.items()}


def timed(fn, args=(), kwargs=None, label="", reps=7, per_n=None):
    """min-of-``reps`` wall time, each call ended by the host fetch of its
    result."""
    kwargs = kwargs or {}
    np.asarray(fn(*args, **kwargs))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(fn(*args, **kwargs))
        ts.append(time.perf_counter() - t0)
    t = min(ts)
    if label:
        extra = f"   {per_n/t/1e3:8.1f} k/s" if per_n else ""
        print(f"{label:34s} {t*1e3:9.2f} ms{extra}")
    return t
