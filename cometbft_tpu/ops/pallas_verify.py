"""Pallas TPU kernel for batched Ed25519 ZIP-215 verification.

The XLA path (``ops.verify.verify_core``) streams every intermediate of the
~3k field multiplications through HBM; this kernel tiles the signature batch
over the lane dimension and keeps the whole working set — decompressed
points, the 9-entry signed-digit per-lane table, and every ladder
intermediate — in VMEM for the full 64-position Straus walk.  The field/point layers are the
*same* traced functions as the XLA path (``ops.fe25519`` /
``ops.ed25519_point``): they are written reshape-free and 2-D-safe exactly
so one implementation serves both, and the differential oracle tests cover
the shared code.

Inputs are the unpacked limb/digit arrays (byte unpacking is trivial and
stays in XLA); output is the per-signature accept-bit vector.

Reference behavior: curve25519-voi batch verification as wrapped by
crypto/ed25519/ed25519.go:189-222 (SURVEY.md §3.4); the per-lane
independent-verification design is this framework's own (failure
attribution is free, unlike the reference's recheck pass,
types/validation.go:308-317).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cometbft_tpu.ops import fe25519 as fe
from cometbft_tpu.ops import ed25519_point as ep

# Lanes per grid step: ~1.3 MB of live field elements per step.  Which tile
# is fastest is not measured on the attached chip.
TILE = 256


def _kernel(ya_ref, sa_ref, yr_ref, sr_ref, dig_s_ref, dig_m_ref, ok_ref,
            tbl_ref, out_ref):
    with fe.kernel_mode(ya_ref.shape[1]):
        _kernel_body(
            ya_ref, sa_ref, yr_ref, sr_ref, dig_s_ref, dig_m_ref, ok_ref,
            tbl_ref, out_ref,
        )


def _kernel_body(ya_ref, sa_ref, yr_ref, sr_ref, dig_s_ref, dig_m_ref,
                 ok_ref, tbl_ref, out_ref):
    ya = fe.F(ya_ref[:], 0, fe.MASK)
    yr = fe.F(yr_ref[:], 0, fe.MASK)
    sa = sa_ref[:]  # (1, TILE)
    sr = sr_ref[:]
    # one double-width decompress for A and R: the sqrt chain is issued
    # once over (20, 2*TILE) — same flops, half the instructions
    from cometbft_tpu.ops.verify import _decompress_pair

    ok_a, a, ok_r, r = _decompress_pair(ya, sa[0], yr, sr[0])

    def dig_get(i):
        # dynamic *ref* loads — Mosaic lowers these (unlike dynamic_slice
        # on values), so the ladder can walk digit rows inside fori_loop
        return dig_s_ref[pl.ds(i, 1), :][0], dig_m_ref[pl.ds(i, 1), :][0]

    p = ep.double_base_scalar_mul(
        None,
        None,
        a,
        niels_tbl=tbl_ref[:],
        dig_get=dig_get,
        batch=ya.v.shape[1],
    )
    q = ep.add(p, ep.negate(r))
    q = ep.double(ep.double(ep.double(q, need_t=False), need_t=False))
    accept = ok_a & ok_r & (ok_ref[:][0] != 0) & ep.is_identity(q)
    out_ref[:] = accept[None, :].astype(jnp.int32)


@lru_cache(maxsize=8)
def _build(batch: int, tile: int):
    assert batch % tile == 0, (batch, tile)
    grid = (batch // tile,)

    def lane_spec(rows):
        return pl.BlockSpec(
            (rows, tile), lambda i: (0, i), memory_space=pltpu.VMEM
        )

    tbl_spec = pl.BlockSpec(
        (3 * fe.NLIMBS, ep.WINDOW), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            lane_spec(fe.NLIMBS),  # ya
            lane_spec(1),          # sign_a
            lane_spec(fe.NLIMBS),  # yr
            lane_spec(1),          # sign_r
            lane_spec(64),         # dig_s
            lane_spec(64),         # dig_m
            lane_spec(1),          # s_ok
            tbl_spec,              # niels base table (shared)
        ],
        out_specs=lane_spec(1),
        out_shape=jax.ShapeDtypeStruct((1, batch), jnp.int32),
        # the name a device trace prints for the kernel
        name="ed25519_verify",
    )


def verify_core_pallas(a_bytes, r_bytes, s_bytes, m_bytes, s_ok,
                       tile: int | None = None):
    """Drop-in replacement for ``ops.verify.verify_core`` on TPU.

    Same raw-byte signature; unpacking runs in XLA, the heavy pipeline in
    one Pallas kernel tiled over lanes.  Returns (B,) bool accept bits.
    ``tile`` defaults to the module's TILE (read at call time so tests and
    sweeps can adjust it).
    """
    batch = a_bytes.shape[0]
    tile = min(tile or TILE, batch)
    pad = (-batch) % tile
    if pad:
        # pad to a tile multiple with s_ok=0 lanes (rejected by
        # construction) — full lane occupancy for any batch size
        zeros2 = jnp.zeros((pad, 32), a_bytes.dtype)
        a_bytes = jnp.concatenate([a_bytes, zeros2])
        r_bytes = jnp.concatenate([r_bytes, zeros2])
        s_bytes = jnp.concatenate([s_bytes, zeros2])
        m_bytes = jnp.concatenate([m_bytes, zeros2])
        s_ok = jnp.concatenate([s_ok, jnp.zeros((pad,), s_ok.dtype)])
    with jax.named_scope("verify.unpack"):
        ya, sa = fe.unpack255(a_bytes)
        yr, sr = fe.unpack255(r_bytes)
        dig_s = fe.signed_digits_msb_first(s_bytes)
        dig_m = fe.signed_digits_msb_first(m_bytes)
        args = (
            ya.v,
            sa[None, :].astype(jnp.int32),
            yr.v,
            sr[None, :].astype(jnp.int32),
            dig_s,
            dig_m,
            s_ok[None, :].astype(jnp.int32),
            jnp.asarray(ep._niels_base_table()),
        )
    out = _build(batch + pad, tile)(*args)
    with jax.named_scope("verify.pack_bits"):
        return out[0, :batch] != 0
