"""Compare field-mul formulations on the live chip.

Variant A (current): skew-reshape outer product, axis-0 sum.
Variant B: shifted-row accumulation — 20 full-array FMAs, no reshape
  (also the formulation a Pallas kernel needs: Mosaic dislikes sublane
  reshapes).
Measured standalone: a chain of K muls over a (20, B) batch.
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

from cometbft_tpu.ops import fe25519 as fe

B = int(os.environ.get("B", "8192"))
K = int(os.environ.get("K", "200"))


def mul_rows(a: fe.F, b: fe.F) -> fe.F:
    """The library's own kernel-mode (shifted-row) multiplier — not a
    copy, so the benchmark always measures the code that ships."""
    with fe.kernel_mode(a.v.shape[1]):
        return fe.mul(a, b)


def chain(mulfn):
    def f(v):
        x = fe.F(v, fe.RED_LO, fe.RED_HI)
        y = x
        for _ in range(K):
            y = mulfn(y, x)
        return y.v
    return jax.jit(f)


def timed(f, v, label):
    np.asarray(f(v))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(f(v))
        ts.append(time.perf_counter() - t0)
    per_mul_ns = min(ts) / K / B * 1e9
    print(f"{label:12s} {min(ts)*1e3:8.2f} ms for {K} muls @ B={B}  ({per_mul_ns:6.1f} ns/mul/lane)")


rng = np.random.default_rng(0)
v = jnp.asarray(rng.integers(fe.RED_LO, fe.RED_HI + 1, size=(fe.NLIMBS, B)).astype(np.int32))

fa = chain(fe.mul)
fb = chain(mul_rows)
# correctness cross-check
ra, rb = np.asarray(fa(v)), np.asarray(fb(v))
ia = [fe.int_of_limbs(ra[:, i]) % fe.P_INT for i in range(4)]
ib = [fe.int_of_limbs(rb[:, i]) % fe.P_INT for i in range(4)]
assert ia == ib, f"mul variants DIVERGE: {ia} != {ib}"
print("variants agree: True")
timed(fa, v, "skew")
timed(fb, v, "rows")
timed(fa, v, "skew(2)")
timed(fb, v, "rows(2)")
