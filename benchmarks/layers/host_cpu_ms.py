"""CPU time the WHOLE process burnt a request of the cell: every thread, the
caller, the dispatcher, the completion thread, the watchdog's fresh workers
(where a launch's transfers and a fetch's pull run), the runtime's own and, in
a traced run, the profiler's (pseudo-stage ``host.cpu`` of the recorder's
per-second store: ``time.process_time``, one entry ``(1, difference)`` a
sampled second), over the cell's OWN requests that ended in the window's whole
seconds (``ctx.records``, not ``spans.counts``: ``verify.commit`` is not the
validator cell's request).  A request, not a second: in a closed loop a path
that waits less serves more requests a second and burns more CPU a second, so
a share of the second has no better direction; CPU a request has.  A run that
is slower at the SAME reading waited (for the CPU, the device, a lock); one
that is slower with it UP executed slower.  The one of the program's five host
counters that the chip's host keeps (a sandboxed kernel: no ``schedstat``, no
``cpu.stat``, ``getrusage`` all zeros)."""

import math

from benchmarks import spans

NAME, UNIT, BETTER = "host_cpu_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "host", "program_counter", "verify_p95_ms"


def read(ctx):
    t = spans.totals(ctx)
    if t is None or "host.cpu" not in t:
        return None
    sampled, cpu = t["host.cpu"]
    # the whole seconds ``spans.totals`` read, and the requests ended in them
    first, last = math.ceil(ctx.records[0].start), math.floor(ctx.records[-1].end)
    served = sum(1 for r in ctx.records if first <= r.end < last)
    if not sampled or not served:
        return None
    return 1e3 * (cpu / sampled) * (last - first) / served
