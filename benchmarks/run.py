#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json`` on the attached TPU.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, once: set-up, warm-up, a window of ``--seconds``, the
plain reference, one JSON object as the last line of standard output.  There
is no CPU mode and no fallback: without a TPU, with another number of chips
than the cell asks for, or with a variable set that forces what a node
selects by itself, it prints no result and exits with code 1.
"""

import time

_STARTED = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def find_chips(chips: int) -> dict:
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if device["platform"] != "tpu":
        raise SystemExit(f"JAX found no TPU (platform {device['platform']!r})")
    if device["count"] != chips:
        raise SystemExit(
            f"{device['count']} chips visible, the cell asks for {chips}"
        )
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    pool = None
    try:
        from benchmarks import chain, harness, manifest, program

        forced = program.forced_variables()
        if forced:
            raise SystemExit(f"unset {forced}: nothing may be forced here")
        cell = manifest.Cell(manifest.load(), args.workload)
        pool = chain.SignPool()  # the workers sign while the chip is found
        signing = harness.start_signing(cell, args.seed, pool)
        program.enable_caches()
        device = find_chips(cell.chips)
        result = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), _STARTED, device,
            pool=pool, signing=signing, require_backend="tpu",
        )
    except SystemExit as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 1
    except BaseException:  # noqa: BLE001 — the one handler: it ends the run
        traceback.print_exc()
        return 1
    finally:
        if pool is not None:
            pool.close()
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
