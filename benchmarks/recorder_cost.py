#!/usr/bin/env python3
"""What the program's span recorder costs when it is on, and which stage
grows as a run goes on, by hand:

    python3 benchmarks/recorder_cost.py --workload <cell> --seed <n>
                                        --seconds <s> --recorder <0|1>

``run.py`` refuses ``COMETBFT_TPU_TRACE`` (nothing may be forced in a
measured run), so the run with the recorder off goes through
``harness.run_cell`` from here, as ``control.py`` goes around ``run.py`` for
its own purpose: ``--recorder 0`` sets ``COMETBFT_TPU_TRACE=0`` before the
program is imported, ``--recorder 1`` leaves the default (on).  Otherwise it
is ``run.py --trace 0``: same set-up, same window, same result line.  The
recorder's cost is this run's end-to-end metrics against ``run.py``'s on the
same seed.

The line also holds ``mean_request_ms`` (the window's requests, caller's
clock) and, with the recorder on, ``stage_ms`` (every stage's mean over the
window's whole seconds, as the nine span readers take it: the caller's five
stages have to add up to the request) and ``stage_ms_by_tenth``: for every
stage the mean duration of one span in each tenth of the window, from the
recorder's per-second totals (``Tracer.stage_seconds``), which is what says
WHICH stage grows when a run slows as it goes.
"""

import time

_STARTED = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def by_tenth(seconds: dict, lo: float, hi: float) -> dict:
    """``{stage: [mean ms in each tenth of [lo, hi)]}`` from
    ``{second: {stage: (count, seconds)}}``; None where a tenth has no span."""
    width = (hi - lo) / 10.0
    sums = {}
    for sec, bucket in seconds.items():
        k = int((sec + 0.5 - lo) // width) if width > 0 else -1
        if not 0 <= k < 10:
            continue
        for stage, (n, s) in bucket.items():
            row = sums.setdefault(stage, [[0, 0.0] for _ in range(10)])
            row[k][0] += n
            row[k][1] += s
    return {
        stage: [round(1e3 * s / n, 4) if n else None for n, s in row]
        for stage, row in sorted(sums.items())
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--recorder", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    from benchmarks import harness, spans

    result, window = spans.run_by_hand(
        args.workload, args.seed, args.seconds, False, _STARTED,
        recorder=bool(args.recorder),
    )
    result["recorder"] = args.recorder
    result["mean_request_ms"] = spans.mean_request_ms(window)
    if args.recorder:
        from cometbft_tpu.libs import tracing

        tracer = tracing.get_tracer()
        read = getattr(tracer, "stage_seconds", None)
        if read is not None:
            totals = spans.totals(SimpleNamespace(records=window.records)) or {}
            result["stage_ms"] = {
                k: 1e3 * s / n for k, (n, s) in sorted(totals.items()) if n
            }
            result["stage_ms_by_tenth"] = by_tenth(
                read(), window.start, window.end
            )
            result["recorder_snapshot"] = tracer.snapshot()["lifetime"]
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
