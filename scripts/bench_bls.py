"""BLS12-381 benchmark: RLC batch verification + G1 MSM (BASELINE.json's
"BLS12-381 aggregate" tracked config).

Prints one JSON line per stage and a final summary line:
  {"metric": "bls_batch_verify", "value": sigs/s, ...}

Stages (each stands alone so a hang leaves the completed ones on stdout):
  * host RLC batch verify at n=16 and n=64 (the consensus seam path —
    crypto/batch.BlsBatchVerifier; pairings on the host oracle)
  * single-verify baseline (what the seam replaces: 2 pairings/signature)
  * G1 batch scalar-mul on the device (ops/bls_g1) vs host, when a
    non-CPU platform is up — the TPU piece of the RLC path

CPU smoke: JAX_PLATFORMS=cpu python scripts/bench_bls.py
(device stage reports platform=cpu and skips the kernel).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit(metric, value, unit, **extra):
    rec = {"metric": metric, "value": round(value, 2), "unit": unit}
    rec.update(extra)
    print(json.dumps(rec), flush=True)
    return rec


def _fixture(n):
    from cometbft_tpu.crypto.keys import Bls12381PrivKey

    privs = [Bls12381PrivKey.from_secret(b"bench-%d" % i) for i in range(n)]
    pubs = [p.pub_key().bytes() for p in privs]
    msgs = [b"bls bench %d" % i for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    return pubs, msgs, sigs


def main() -> None:
    from cometbft_tpu.libs import cachedir

    cachedir.enable()
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.crypto import bls12381 as bls

    results = {}
    # impl: native C++ pairing (the blst analog) or pure-Python oracle
    impl = "native" if bls._nat() is not None else "python"
    results["impl"] = impl

    # single-verify baseline
    pubs, msgs, sigs = _fixture(4)
    t0 = time.perf_counter()
    for p, m, s in zip(pubs, msgs, sigs):
        assert bls.verify(p, m, s)
    single_s = (time.perf_counter() - t0) / 4
    results["single_verify_ms"] = round(single_s * 1e3, 1)
    _emit("bls_single_verify", 1.0 / single_s, "verifies/s")

    # 100-sig aggregate verify (VERDICT r4 #4's named milestone; the
    # reference's blst path does this in single-digit ms — key_bls12381.go)
    n = 100
    pubs, msgs, sigs = _fixture(n)
    agg = bls.aggregate_signatures(sigs)
    assert agg is not None
    t0 = time.perf_counter()
    ok = bls.aggregate_verify(pubs, msgs, agg)
    agg_s = time.perf_counter() - t0
    assert ok
    results["aggregate100_ms"] = round(agg_s * 1e3, 1)
    _emit(
        "bls_aggregate_verify", n / agg_s, "verifies/s", batch=n,
        total_ms=round(agg_s * 1e3, 1), impl=impl,
    )

    # RLC batch verify through the consensus seam
    for n in (16, 64):
        pubs, msgs, sigs = _fixture(n)
        bv = cbatch.BlsBatchVerifier()
        for p, m, s in zip(pubs, msgs, sigs):
            bv.add(p, m, s)
        t0 = time.perf_counter()
        ok, bits = bv.verify()
        dt = time.perf_counter() - t0
        assert ok and all(bits)
        results[f"batch{n}_s"] = round(dt, 3)
        results[f"batch{n}_vps"] = round(n / dt, 2)
        _emit(
            "bls_batch_verify", n / dt, "verifies/s", batch=n,
            speedup_vs_single=round(single_s * n / dt, 2),
        )

    # device G1 batch scalar-mul (the TPU half of the RLC path)
    try:
        platform = jax.devices()[0].platform
    except Exception:
        platform = "unavailable"
    if platform != "cpu" and platform != "unavailable":
        import secrets

        from cometbft_tpu.ops import bls_g1 as g1

        n = int(os.environ.get("BENCH_BLS_MSM", "256"))
        gen = bls.E1.affine(bls.G1_GEN)
        pts = [gen] * n
        rs = [secrets.randbits(128) | 1 for _ in range(n)]
        t0 = time.perf_counter()
        out = g1.batch_scalar_mul(pts, rs, nbits=128)
        compile_and_run = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = g1.batch_scalar_mul(pts, rs, nbits=128)
        dev_s = time.perf_counter() - t0
        assert len(out) == n
        # host comparison on a small slice
        t0 = time.perf_counter()
        for r in rs[:8]:
            bls.E1.mul_scalar(bls.G1_GEN, r)
        host_s = (time.perf_counter() - t0) / 8 * n
        results["g1_mul_device_s"] = round(dev_s, 3)
        results["g1_mul_host_est_s"] = round(host_s, 3)
        _emit(
            "bls_g1_batch_scalar_mul", n / dev_s, "points/s", batch=n,
            platform=platform, compile_s=round(compile_and_run, 1),
            host_points_per_s=round(n / host_s, 2),
        )

    final = {
        "metric": "bls_batch_verify",
        "value": results.get("batch64_vps", 0.0),
        "unit": "verifies/s",
        "platform": platform,
    }
    final.update(results)
    print(json.dumps(final), flush=True)
    out_path = os.environ.get("BENCH_BLS_OUT")
    if out_path:
        with open(out_path, "w") as f:
            f.write(json.dumps(final) + "\n")


if __name__ == "__main__":
    main()
