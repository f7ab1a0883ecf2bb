"""Per-op cost of the field layer on the live chip: mul vs square vs carry,
measured as long chains (amortizes the per-call dispatch cost).

Used to build the bottom-up cost model for the verify kernel: per-sig time
should be ~(#muls * t_mul + #squares * t_sq); a mismatch means the kernel
is bound by something other than VPU arithmetic (issue slots, VMEM, Mosaic
scheduling) and op-count optimizations won't pay."""

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
from _bench_common import timed as _timed

B = int(os.environ.get("B", "32768"))
K = int(os.environ.get("K", "400"))


def chain(op, kernel_mode):
    def f(v):
        x = fe.F(v, fe.RED_LO, fe.RED_HI)
        y = x
        if kernel_mode:
            with fe.kernel_mode(B):
                for _ in range(K):
                    y = op(y, x)
        else:
            for _ in range(K):
                y = op(y, x)
        return y.v

    return jax.jit(f)


def timed(f, v, label):
    t = _timed(f, args=(v,))
    print(f"{label:24s} {t*1e3:8.2f} ms  ({t / K / B * 1e9:6.2f} ns/op/lane)")
    return t


def main():
    print(f"platform={jax.devices()[0].platform} B={B} K={K}")
    rng = np.random.default_rng(0)
    v = jnp.asarray(
        rng.integers(fe.RED_LO, fe.RED_HI + 1, size=(fe.NLIMBS, B)).astype(
            np.int32
        )
    )
    sq = lambda y, x: fe.square(y)
    timed(chain(fe.mul, False), v, "mul (skew/XLA)")
    timed(chain(fe.mul, True), v, "mul (rows/kernel-mode)")
    timed(chain(sq, False), v, "square")
    timed(chain(lambda y, x: fe.red(fe.add(y, x)), False), v, "add+red")


if __name__ == "__main__":
    main()
