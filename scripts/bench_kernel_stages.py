"""Stage-truncated Pallas verify kernels: where does the per-sig time go?

Builds kernels that stop after each pipeline stage (decompress A+R /
+table build / +ladder / full) and times them on the chip at one batch.
The deltas are the per-stage costs, all measured with identical dispatch
overhead."""

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

from cometbft_tpu.ops import fe25519 as fe, ed25519_point as ep
from _bench_common import make_sig_dev, timed

B = int(os.environ.get("BENCH_BATCH", "32768"))
TILE = 256


def make_stage_kernel(stage: str):
    def kernel(ya_ref, sa_ref, yr_ref, sr_ref, dig_s_ref, dig_m_ref,
               ok_ref, tbl_ref, out_ref):
        with fe.kernel_mode(TILE):
            ya = fe.F(ya_ref[:], 0, fe.MASK)
            yr = fe.F(yr_ref[:], 0, fe.MASK)
            ok_a, a = ep.decompress(ya, sa_ref[:][0])
            if stage == "decompressA":
                out_ref[:] = (ok_a & (a.x.v[0] != -1))[None, :].astype(jnp.int32)
                return
            ok_r, r = ep.decompress(yr, sr_ref[:][0])
            if stage == "decompressAR":
                out_ref[:] = (ok_a & ok_r)[None, :].astype(jnp.int32)
                return
            if stage == "table":
                tbl = ep.build_table_a(a)
                acc = sum(jnp.sum(c[-1][:1], axis=0) for c in tbl)
                out_ref[:] = (ok_a & ok_r & (acc != -1))[None, :].astype(jnp.int32)
                return

            def dig_get(i):
                return dig_s_ref[pl.ds(i, 1), :][0], dig_m_ref[pl.ds(i, 1), :][0]

            p = ep.double_base_scalar_mul(
                None, None, a, niels_tbl=tbl_ref[:], dig_get=dig_get,
                batch=TILE,
            )
            if stage == "ladder":
                out_ref[:] = (ok_a & ok_r & (p.x.v[0] != -1))[None, :].astype(jnp.int32)
                return
            q = ep.add(p, ep.negate(r))
            q = ep.double(ep.double(ep.double(q, need_t=False), need_t=False))
            accept = ok_a & ok_r & (ok_ref[:][0] != 0) & ep.is_identity(q)
            out_ref[:] = accept[None, :].astype(jnp.int32)

    def lane_spec(rows):
        return pl.BlockSpec((rows, TILE), lambda i: (0, i),
                            memory_space=pltpu.VMEM)

    call = pl.pallas_call(
        kernel,
        grid=(B // TILE,),
        in_specs=[
            lane_spec(fe.NLIMBS), lane_spec(1), lane_spec(fe.NLIMBS),
            lane_spec(1), lane_spec(64), lane_spec(64), lane_spec(1),
            pl.BlockSpec((3 * fe.NLIMBS, ep.WINDOW), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=lane_spec(1),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
    )

    @jax.jit
    def run(a_bytes, r_bytes, s_bytes, m_bytes, s_ok):
        ya, sa = fe.unpack255(a_bytes)
        yr, sr = fe.unpack255(r_bytes)
        dig_s = fe.signed_digits_msb_first(s_bytes)
        dig_m = fe.signed_digits_msb_first(m_bytes)
        return call(
            ya.v, sa[None, :].astype(jnp.int32), yr.v,
            sr[None, :].astype(jnp.int32), dig_s, dig_m,
            s_ok[None, :].astype(jnp.int32),
            jnp.asarray(ep._niels_base_table()),
        )

    return run


def main():
    dev = make_sig_dev(B)
    print(f"platform={jax.devices()[0].platform} B={B}")

    prev = 0.0
    for stage in ("decompressA", "decompressAR", "table", "ladder", "full"):
        t = timed(make_stage_kernel(stage), kwargs=dev)
        print(f"{stage:14s} {t*1e3:8.2f} ms   (delta {max(0, t-prev)*1e3:7.2f} ms)")
        prev = t


if __name__ == "__main__":
    main()
