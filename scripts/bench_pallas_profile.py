"""Profile the Pallas verify kernel: fixed dispatch overhead vs per-tile
compute, per-stage split (decompress / table / ladder), and TILE sweep.

Directs the round-3 perf push (VERDICT r2 #4): with ~70 ms of apparent
fixed overhead in bench.py's measurement, separating dispatch latency from
compute decides whether to attack the kernel or the host path.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cometbft_tpu.libs import cachedir  # noqa: E402

cachedir.enable()  # one cache root, named before jax is imported

import numpy as np
import jax

from cometbft_tpu.ops import pallas_verify as pv
from _bench_common import make_sig_dev as make_dev, timed as _timed


def timed(fn, dev, label, reps=7):
    return _timed(fn, kwargs=dev, label=label, reps=reps,
                  per_n=dev["a_bytes"].shape[0])


def main():
    print("platform:", jax.devices()[0].platform)

    kern = jax.jit(lambda **kw: pv.verify_core_pallas(**kw))

    # 1) batch sweep -> fixed overhead vs slope
    print("\n== batch sweep (TILE=256) ==")
    times = {}
    for n in (2048, 8192, 32768, 65536, 131072):
        dev = make_dev(n)
        times[n] = timed(kern, dev, f"pallas full n={n}")
    # least-squares fit t = F + c*n over the sweep
    ns = np.array(sorted(times))
    ts = np.array([times[n] for n in ns])
    A = np.vstack([np.ones_like(ns, float), ns]).T
    (F, c), *_ = np.linalg.lstsq(A, ts, rcond=None)
    print(f"fit: fixed={F*1e3:.1f} ms  per-sig={c*1e6:.3f} us  "
          f"asymptote={1/c/1e3:.1f} k/s")

    # 2) TILE sweep at n=32768
    print("\n== TILE sweep (n=32768) ==")
    dev = make_dev(32768)
    for tile in (128, 256, 512):
        f = jax.jit(lambda t=tile, **kw: pv.verify_core_pallas(tile=t, **kw))
        timed(f, dev, f"tile={tile}")


if __name__ == "__main__":
    main()
