#!/usr/bin/env python3
"""The device's idle gaps by what the host was doing, by hand:

    python3 benchmarks/span_gaps.py --workload <cell> --seed <n> [--seconds 12]

One traced window through ``harness.run_cell`` as it stands.  The harness
reduces the profile with ``trace_reduce``, which keeps only the benchmark's
``request`` annotation from the host's plane, and deletes the file; this
command wraps ``trace_reduce.reduce_file`` from outside to read the same
file first, keeping the ``tpubft/<stage>`` annotations that the program's
recorder holds while a span is open (``cometbft_tpu/libs/tracing.py``), on
every host thread.  Then it cuts the idle time as ``trace_reduce`` does (its
``_union``, ``_gaps`` and ``_where``, the window from the first ``request``
to the last), cuts each gap again wherever a program span opens or closes
(a gap before a request's first device operation is one interval of 6 ms
that a dozen stages share), and keys each piece a second time: by the
INNERMOST program span open at the piece's middle on any thread, the one
opened last (a caller blocked in ``sched.wait`` is named only while no
other thread is inside a span), ``unnamed`` where none is open.

Prints one JSON object: ``idle_s`` (the sum of the gaps), ``harness_idle_s``
(the harness's window less busy, which it has to equal), ``by_where`` (the
harness's four keys, each split by span, seconds), ``unnamed_share_inside``
(of the idle time inside a request), ``mean_request_ms`` (the traced run's
requests over the whole window, caller's clock: what the caller's five span
metrics of the result line have to add up to) and the result line.  Folding
this into ``trace_reduce``, so that the ledger's ``idle_gaps`` carry these
names, is a ``benchmark`` PR's (``PERF.md`` §7).
"""

import time

_STARTED = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import trace_reduce  # noqa: E402

PREFIX = "tpubft/"
UNNAMED = "unnamed"


def read_planes(path: str):
    """(device operations of the first chip, ``request`` annotations, program
    spans of every host thread), each a list of (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    device, requests, spans = None, [], []
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            if device is None:
                device = [
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for line in plane.lines if line.name == trace_reduce.OPS_LINE
                    for e in line.events
                ]
        elif trace_reduce.HOST_PLANE.match(plane.name):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name == trace_reduce.REQUEST:
                        requests.append(
                            (name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        )
                    elif name.startswith(PREFIX):
                        spans.append(
                            (name[len(PREFIX):], int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                        )
    return device or [], requests, spans


def innermost_at(spans, times):
    """For each of ``times`` (sorted), the name of the span open then that
    was opened last, or ``unnamed``.  One sweep over starts and ends."""
    edges = sorted(
        [(a, 1, k) for k, (_, a, b) in enumerate(spans) if b > a]
        + [(b, 0, k) for k, (_, a, b) in enumerate(spans) if b > a]
    )  # at one instant, ends before starts
    out, open_now, at = [], {}, 0
    for t in times:
        while at < len(edges) and edges[at][0] <= t:
            _, opens, k = edges[at]
            if opens:
                open_now[k] = spans[k][1]
            else:
                open_now.pop(k, None)
            at += 1
        if open_now:
            k = max(open_now, key=open_now.get)
            out.append(spans[k][0])
        else:
            out.append(UNNAMED)
    return out


def gaps_by_span(device, requests, spans) -> dict:
    """``{"idle_s", "window_s", "by_where": {where: {span: seconds}}}``, cut
    exactly as ``trace_reduce.reduce_planes`` cuts its ``idle_gaps``."""
    lo = min(a for _, a, _ in requests)
    hi = max(b for _, _, b in requests)
    busy = trace_reduce._union(
        [(max(a, lo), min(b, hi)) for _, a, b in device if min(b, hi) > max(a, lo)]
    )
    starts = [a for a, _ in busy]
    reqs = []
    for _, a, b in sorted(requests, key=lambda r: r[1]):
        i = bisect.bisect_left(starts, a)
        if i and busy[i - 1][1] > a:
            i -= 1
        j = bisect.bisect_left(starts, b)
        reqs.append((a, b, busy[i][0], busy[j - 1][1]) if j > i
                    else (a, b, None, None))
    req_starts = [r[0] for r in reqs]
    cuts = sorted(
        {t for a, b, _, _ in reqs for t in (a, b)}
        | {t for _, a, b in spans for t in (a, b)}
    )
    pieces = []
    for a, b in trace_reduce._gaps(busy, lo, hi):
        inner = cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)]
        edges = [a, *inner, b]
        pieces.extend(zip(edges, edges[1:]))
    pieces.sort()
    names = innermost_at(spans, [(a + b) // 2 for a, b in pieces])
    by_where = {}
    for piece, name in zip(pieces, names):
        where = trace_reduce._where(piece, reqs, req_starts)
        row = by_where.setdefault(where, {})
        row[name] = row.get(name, 0.0) + (piece[1] - piece[0]) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum(b - a for a, b in pieces) / 1e9,
        "by_where": by_where,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    from benchmarks import spans

    kept = {}
    reduce_file = trace_reduce.reduce_file

    def keep_then_reduce(path):
        kept["planes"] = read_planes(path)
        return reduce_file(path)

    trace_reduce.reduce_file = keep_then_reduce
    try:
        result, window = spans.run_by_hand(
            args.workload, args.seed, args.seconds, True, _STARTED
        )
    finally:
        trace_reduce.reduce_file = reduce_file
    out = gaps_by_span(*kept["planes"])
    inside = {
        w: row for w, row in out["by_where"].items() if w.startswith("inside")
    }
    inside_s = sum(sum(row.values()) for row in inside.values())
    out["unnamed_share_inside"] = (
        sum(row.get(UNNAMED, 0.0) for row in inside.values()) / inside_s
        if inside_s else None
    )
    dev = result["device"]
    out["harness_idle_s"] = dev["window_s"] - dev["busy_s"]
    out["spans_in_trace"] = len(kept["planes"][2])
    out["mean_request_ms"] = spans.mean_request_ms(window)
    out["result"] = result
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
