"""Microbenchmark of field-mul implementation variants on the live chip.

Times k chained batched GF(2^255-19) multiplications per variant to pick
the design for the round-2 kernel rewrite.  Not part of the test suite.
"""

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

B = int(os.environ.get("B", "8192"))
K = int(os.environ.get("K", "64"))  # chained muls per timed call

P_INT = 2**255 - 19


def timeit(fn, *args, reps=5):
    out = fn(*args)
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
        ts.append(time.perf_counter() - t0)
    return min(ts)


# --- variant 1: current repo mul (13-bit x 20, dot_general + scans) -------
from cometbft_tpu.ops import fe25519 as fe_old


@jax.jit
def chain_old(a, b):
    def body(c, _):
        return fe_old.mul(c, b), None

    c, _ = lax.scan(body, a, None, length=K)
    return c


# --- variant 2: 11-bit x 24 limbs, unrolled columns + parallel carry ------
N2, W2 = 24, 11
M2 = (1 << W2) - 1
NCOL2 = 2 * N2 - 1
# 2^264 mod p fold for carry-out of limb 23: 2^264 = 2^9*2^255 = 512*19 = 9728
FOLD2 = 19 * (1 << (N2 * W2 - 255))


def mul2(a, b):
    # a, b: (24, B) int32, limbs <= ~2^13
    cols = [None] * NCOL2
    for i in range(N2):
        prod = a[i][None, :] * b  # (24, B)
        for j in range(N2):
            k = i + j
            cols[k] = prod[j] if cols[k] is None else cols[k] + prod[j]
    x = jnp.stack(cols)  # (47, B)
    # fold high columns 24..46 into 0..22  (weight 2^264 ≡ 9728)
    lo = x[:N2]
    hi = x[N2:]
    lo = lo.at[: NCOL2 - N2].add(FOLD2 * hi)
    # parallel carry: 4 steps
    for _ in range(4):
        c = lo >> W2
        lo = (lo & M2) + jnp.concatenate(
            [FOLD2 * c[-1:], c[:-1]], axis=0
        )
    return lo


@jax.jit
def chain2(a, b):
    def body(c, _):
        return mul2(c, b), None

    c, _ = lax.scan(body, a, None, length=K)
    return c


# --- variant 3: f32 8-bit x 32 limbs ------------------------------------
N3, W3 = 32, 8
M3 = (1 << W3) - 1
NCOL3 = 2 * N3 - 1
FOLD3 = float(19 * (1 << (N3 * W3 - 255)))  # 2^256 ≡ 38


def mul3(a, b):
    # a, b: (32, B) f32, limbs < 2^8 (plus small headroom)
    cols = [None] * NCOL3
    for i in range(N3):
        prod = a[i][None, :] * b
        for j in range(N3):
            k = i + j
            cols[k] = prod[j] if cols[k] is None else cols[k] + prod[j]
    x = jnp.stack(cols)  # (63, B) values < 2^21 exact
    lo = x[:N3]
    hi = x[N3:]
    lo = lo.at[: NCOL3 - N3].add(FOLD3 * hi)
    for _ in range(4):
        c = jnp.floor(lo * (1.0 / 256.0))
        lo = (lo - 256.0 * c) + jnp.concatenate(
            [FOLD3 * c[-1:], c[:-1]], axis=0
        )
    return lo


@jax.jit
def chain3(a, b):
    def body(c, _):
        return mul3(c, b), None

    c, _ = lax.scan(body, a, None, length=K)
    return c


# --- correctness spot check + timing --------------------------------------
def limbs(val, n, w):
    out = np.zeros((n,), np.int64)
    for i in range(n):
        out[i] = val & ((1 << w) - 1)
        val >>= w
    return out


def unlimbs(x, w):
    v = 0
    for i in reversed(range(x.shape[0])):
        v = (v << w) + int(x[i])
    return v % P_INT


rng = np.random.default_rng(0)
av = int(rng.integers(0, 2**63)) * 12345 % P_INT
bv = int(rng.integers(0, 2**63)) * 98765 % P_INT
# expected: av * bv^K mod p
exp = av
for _ in range(K):
    exp = exp * bv % P_INT

a1 = jnp.asarray(np.broadcast_to(limbs(av, 20, 13)[:, None], (20, B)).astype(np.int32))
b1 = jnp.asarray(np.broadcast_to(limbs(bv, 20, 13)[:, None], (20, B)).astype(np.int32))
a2 = jnp.asarray(np.broadcast_to(limbs(av, N2, W2)[:, None], (N2, B)).astype(np.int32))
b2 = jnp.asarray(np.broadcast_to(limbs(bv, N2, W2)[:, None], (N2, B)).astype(np.int32))
a3 = jnp.asarray(np.broadcast_to(limbs(av, N3, W3)[:, None], (N3, B)).astype(np.float32))
b3 = jnp.asarray(np.broadcast_to(limbs(bv, N3, W3)[:, None], (N3, B)).astype(np.float32))

r1 = unlimbs(np.asarray(chain_old(a1, b1))[:, 0], 13)
r2 = unlimbs(np.asarray(chain2(a2, b2))[:, 0].astype(np.int64), W2)
r3 = unlimbs(np.asarray(chain3(a3, b3))[:, 0].astype(np.int64), W3)
print("correct:", r1 == exp, r2 == exp, r3 == exp)

t1 = timeit(chain_old, a1, b1)
t2 = timeit(chain2, a2, b2)
t3 = timeit(chain3, a3, b3)
for name, t in [("old-13x20-dotgen", t1), ("int32-11x24", t2), ("f32-8x32", t3)]:
    per = t / K
    print(
        f"{name}: {t*1e3:.2f} ms for {K} muls @B={B} -> "
        f"{per*1e6:.1f} us/batched-mul, {per/B*1e9:.2f} ns/lane-mul"
    )
