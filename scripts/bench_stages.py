"""Per-stage profile of the verify kernel on the live chip.

Times decompress (2 sqrt chains), table build, the 64-position ladder, and
the final cofactor/identity check separately, to direct optimization work
(VERDICT r2 #4: profile per-stage first)."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cometbft_tpu.libs import cachedir  # noqa: E402

cachedir.enable()  # one cache root, named before jax is imported

import numpy as np
import jax
import jax.numpy as jnp

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import fe25519 as fe, ed25519_point as ep, verify as ov


def timed(fn, args, label, reps=5):
    out = jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        r = fn(*args)
        # end the timing on a device->host copy of one leaf
        leaf = jax.tree_util.tree_leaves(r)[0]
        np.asarray(leaf)
        ts.append(time.perf_counter() - t0)
    print(f"{label:28s} {min(ts)*1e3:9.2f} ms")
    return out


def main():
    n = int(os.environ.get("BENCH_BATCH", "8192"))
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = i.to_bytes(4, "little") * 8
        pubs.append(ref.pubkey_from_seed(seed))
        msgs.append(b"bench-%d" % i)
        sigs.append(ref.sign(seed, b"bench-%d" % i))
    arrays, _, _ = ov.prepare_batch(pubs, msgs, sigs)
    dev = {k: jnp.asarray(v) for k, v in arrays.items()}
    print(f"batch={dev['a_bytes'].shape[0]} platform={jax.devices()[0].platform}")

    @jax.jit
    def stage_unpack(a_bytes, r_bytes, s_bytes, m_bytes):
        ya, sa = fe.unpack255(a_bytes)
        yr, sr = fe.unpack255(r_bytes)
        return ya.v, sa, yr.v, sr, fe.signed_digits_msb_first(s_bytes), fe.signed_digits_msb_first(m_bytes)

    @jax.jit
    def stage_decompress(a_bytes):
        ya, sa = fe.unpack255(a_bytes)
        ok, p = ep.decompress(ya, sa)
        return ok, p.x.v, p.y.v, p.t.v

    @jax.jit
    def stage_table(a_bytes):
        ya, sa = fe.unpack255(a_bytes)
        _, a = ep.decompress(ya, sa)
        return ep.build_table_a(a)

    @jax.jit
    def stage_ladder(a_bytes, s_bytes, m_bytes):
        ya, sa = fe.unpack255(a_bytes)
        _, a = ep.decompress(ya, sa)
        p = ep.double_base_scalar_mul(
            fe.signed_digits_msb_first(s_bytes), fe.signed_digits_msb_first(m_bytes), a
        )
        return p.x.v, p.y.v, p.z.v

    @jax.jit
    def full(a_bytes, r_bytes, s_bytes, m_bytes, s_ok):
        return ov.verify_core(a_bytes, r_bytes, s_bytes, m_bytes, s_ok)

    timed(stage_unpack, (dev["a_bytes"], dev["r_bytes"], dev["s_bytes"], dev["m_bytes"]), "unpack+digits")
    timed(stage_decompress, (dev["a_bytes"],), "decompress A (1x sqrt)")
    timed(stage_table, (dev["a_bytes"],), "decompress+table16 A")
    timed(stage_ladder, (dev["a_bytes"], dev["s_bytes"], dev["m_bytes"]), "decompress+table+ladder")
    out = timed(full, tuple(dev[k] for k in ("a_bytes", "r_bytes", "s_bytes", "m_bytes", "s_ok")), "full verify_core")
    acc = np.asarray(out)
    print("accept:", int(acc.sum()), "/", n)


if __name__ == "__main__":
    main()
