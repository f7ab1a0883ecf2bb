"""Isolate per-iteration overhead: scan vs unrolled, trivial vs real body."""

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
K = int(os.environ.get("K", "64"))


def timeit(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


N2, W2 = 24, 11
M2 = (1 << W2) - 1


def trivial_body(c, b):
    return (c * b) & M2


def mul_nocarry(a, b):
    cols = [None] * (2 * N2 - 1)
    for i in range(N2):
        prod = a[i][None, :] * b
        for j in range(N2):
            k = i + j
            cols[k] = prod[j] if cols[k] is None else cols[k] + prod[j]
    lo = jnp.stack(cols[:N2])
    return lo & M2  # junk math, just timing the column work


def mul_carry(a, b):
    x = mul_nocarry(a, b)
    for _ in range(4):
        c = x >> W2
        x = (x & M2) + jnp.concatenate([c[-1:] * 38, c[:-1]], axis=0)
    return x


def make_chain(body, unroll):
    @jax.jit
    def f(a, b):
        if unroll:
            c = a
            for _ in range(K):
                c = body(c, b)
            return c

        def step(c, _):
            return body(c, b), None

        c, _ = lax.scan(step, a, None, length=K)
        return c

    return f


rng = np.random.default_rng(0)
a = jnp.asarray(rng.integers(0, M2, size=(N2, B)).astype(np.int32))
b = jnp.asarray(rng.integers(0, M2, size=(N2, B)).astype(np.int32))

for name, body in [
    ("trivial", trivial_body),
    ("mul-nocarry", mul_nocarry),
    ("mul-carry4", mul_carry),
]:
    for unroll in (False, True):
        t = timeit(make_chain(body, unroll), a, b)
        print(
            f"{name:12s} unroll={unroll}: {t*1e3:8.3f} ms total, "
            f"{t/K*1e6:8.2f} us/iter"
        )


# --- suspects: scatter (.at[].add) and small lax.scan carry chains --------
def mul_scatter(a, b):
    x = mul_nocarry(a, b) * 1  # (24,B) ints
    x = x.at[0].add(38 * (x[-1] >> W2))  # single scatter
    return x & M2


def mul_scan_carry(a, b):
    x = mul_nocarry(a, b)

    def step(carry, row):
        row = row + carry
        c = row >> W2
        return c, row - (c << W2)

    cout, rows = lax.scan(step, jnp.zeros_like(x[0]), x)
    return rows


def slice_concat_carry(a, b):
    x = mul_nocarry(a, b)
    for _ in range(4):
        c = x >> W2
        x = (x & M2) + jnp.concatenate([c[-1:] * 38, c[:-1]], axis=0)
    return x


for name, body in [
    ("mul+1scatter", mul_scatter),
    ("mul+scan24", mul_scan_carry),
    ("mul+4concat", slice_concat_carry),
]:
    t = timeit(make_chain(body, False), a, b)
    print(f"{name:14s}: {t*1e3:8.3f} ms total, {t/K*1e6:8.2f} us/iter")


def mul_slicescatter(a, b):
    # full 47-column version with at[slice].add fold (bench_fe_variants form)
    cols = [None] * (2 * N2 - 1)
    for i in range(N2):
        prod = a[i][None, :] * b
        for j in range(N2):
            k = i + j
            cols[k] = prod[j] if cols[k] is None else cols[k] + prod[j]
    x = jnp.stack(cols)
    lo, hi = x[:N2], x[N2:]
    lo = lo.at[: N2 - 1].add(38 * hi)
    return lo & M2


def mul_padfold(a, b):
    # same fold via pad+add instead of scatter
    cols = [None] * (2 * N2 - 1)
    for i in range(N2):
        prod = a[i][None, :] * b
        for j in range(N2):
            k = i + j
            cols[k] = prod[j] if cols[k] is None else cols[k] + prod[j]
    x = jnp.stack(cols)
    lo, hi = x[:N2], x[N2:]
    hipad = jnp.concatenate([38 * hi, jnp.zeros((1, hi.shape[1]), hi.dtype)], 0)
    return (lo + hipad) & M2


import jax.lax as jlax


def mul_dotgen_int32(a, b):
    # reproduce the old repo's (47,576)@(576,B) int32 dot_general shape
    outer = (a[:, None, :] * b[None, :, :]).reshape(N2 * N2, B)
    colsum = np.zeros((2 * N2 - 1, N2 * N2), np.float32)
    for i in range(N2):
        for j in range(N2):
            colsum[i + j, i * N2 + j] = 1.0
    cs = jnp.asarray(colsum.astype(np.int32))
    cols = jlax.dot_general(cs, outer, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    return cols[:N2] & M2


for name, body in [
    ("mul+sliceat", mul_slicescatter),
    ("mul+padfold", mul_padfold),
    ("mul+dotgen32", mul_dotgen_int32),
]:
    t = timeit(make_chain(body, False), a, b)
    print(f"{name:14s}: {t*1e3:8.3f} ms total, {t/K*1e6:8.2f} us/iter")
