"""Per-op cost of field mul/square INSIDE a Pallas kernel (VMEM-resident,
like the real verify kernel) — the XLA chain bench is HBM-bound and
useless for sizing kernel work.

Grid tiles the batch; each kernel instance runs K ops on its (20, TILE)
block.  Cost model target: verify per-sig time ~= (#mul * t_mul +
#sq * t_sq + selects + freezes)."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cometbft_tpu.libs import cachedir  # noqa: E402

cachedir.enable()  # one cache root, named before jax is imported

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cometbft_tpu.ops import fe25519 as fe
from _bench_common import timed as _timed

B = int(os.environ.get("B", "32768"))
K = int(os.environ.get("K", "400"))
TILE = int(os.environ.get("TILE", "256"))


def make_chain(op):
    def kernel(x_ref, o_ref):
        with fe.kernel_mode(TILE):
            x = fe.F(x_ref[:], fe.RED_LO, fe.RED_HI)

            def body(_, y):
                return fe.red(op(y, x))

            y = jax.lax.fori_loop(0, K, body, x)
            o_ref[:] = y.v

    spec = pl.BlockSpec(
        (fe.NLIMBS, TILE), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    return jax.jit(
        pl.pallas_call(
            kernel,
            grid=(B // TILE,),
            in_specs=[spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((fe.NLIMBS, B), jnp.int32),
        )
    )


def timed(f, v, label):
    t = _timed(f, args=(v,))
    print(f"{label:20s} {t*1e3:8.2f} ms  ({t / K / B * 1e9:6.3f} ns/op/lane)")


def main():
    print(f"platform={jax.devices()[0].platform} B={B} K={K} TILE={TILE}")
    rng = np.random.default_rng(0)
    v = jnp.asarray(
        rng.integers(fe.RED_LO, fe.RED_HI + 1, size=(fe.NLIMBS, B)).astype(
            np.int32
        )
    )
    timed(make_chain(fe.mul), v, "mul (pallas)")
    timed(make_chain(lambda y, x: fe.square(y)), v, "square (pallas)")
    timed(make_chain(lambda y, x: fe.add(y, x)), v, "add+red (pallas)")


if __name__ == "__main__":
    main()
