"""Bisect which part of the round-1 fe25519.mul costs 1.1ms/call."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cometbft_tpu.libs import cachedir  # noqa: E402

cachedir.enable()  # one cache root, named before jax is imported

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from cometbft_tpu.ops import fe25519 as fe

B, K = 8192, 64
print("device:", jax.devices()[0].platform)


def timeit(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def chain(body):
    @jax.jit
    def f(a, b):
        def step(c, _):
            return body(c, b), None

        c, _ = lax.scan(step, a, None, length=K)
        return c

    return f


NL, BITS, MASK = fe.NLIMBS, fe.BITS, fe.MASK
_COLSUM = jnp.asarray(fe._COLSUM.astype(np.int32))


def dotgen_only(a, b):
    outer = (a[:, None, :] * b[None, :, :]).reshape(NL * NL, B)
    cols = lax.dot_general(
        _COLSUM, outer, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return cols[:NL] & MASK


def dotgen_chain_carry(a, b):
    outer = (a[:, None, :] * b[None, :, :]).reshape(NL * NL, B)
    cols_arr = lax.dot_general(
        _COLSUM, outer, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    carry, cols = fe._carry_chain(cols_arr)
    hi = jnp.concatenate([cols[NL:], carry[None]], axis=0)
    return (cols[:NL] + fe.FOLD * hi) & MASK


def full_mul(a, b):
    return fe.mul(a, b)


def carry_only(a, b):
    return fe._carry(a + b * 7)


rng = np.random.default_rng(0)
a = jnp.asarray(rng.integers(0, MASK, size=(NL, B)).astype(np.int32))
b = jnp.asarray(rng.integers(0, MASK, size=(NL, B)).astype(np.int32))

for name, body in [
    ("dotgen-only", dotgen_only),
    ("dotgen+chain39", dotgen_chain_carry),
    ("full fe.mul", full_mul),
    ("fe._carry only", carry_only),
]:
    t = timeit(chain(body), a, b)
    print(f"{name:16s}: {t*1e3:8.3f} ms total, {t/K*1e6:8.2f} us/iter")


# --- hypothesis: 20 rows (2.5 sublane tiles) vs 24 rows (3 tiles) ---------
def scan_carry_rows(nrows):
    def body(c, b):
        def step(carry, row):
            row = row + carry
            cc = row >> BITS
            return cc, row - (cc << BITS)

        cout, rows = lax.scan(step, jnp.zeros_like(c[0]), c + b)
        return rows

    return body


for nrows in (8, 16, 20, 24, 32):
    aa = jnp.asarray(rng.integers(0, MASK, size=(nrows, B)).astype(np.int32))
    bb = jnp.asarray(rng.integers(0, MASK, size=(nrows, B)).astype(np.int32))
    t = timeit(chain(scan_carry_rows(nrows)), aa, bb)
    print(f"scan-carry rows={nrows:2d}: {t/K*1e6:9.2f} us/iter")
