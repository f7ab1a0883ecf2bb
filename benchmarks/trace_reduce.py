"""From the profiler's ``.xplane.pb`` to the few numbers the per-layer
readers take: device busy time, the traced window, device time by operation,
and the idle gaps by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a TPU trace of
this program looks like (looked at by hand, PR 25): one plane a chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event for every
operation that ran on the device (start and duration on the profiler's
clock; ``XLA Modules`` holds one an executable, ``Async XLA Ops`` the copies
in flight beside them); the host's plane ``/host:CPU`` holds one line a
thread, with the benchmark's own ``TraceAnnotation`` events (``request``)
among JAX's.  An operation's name is its whole HLO line, a kilobyte for the
kernel: ``short_name`` keeps the result's name and shape and marks a Pallas
kernel by its call target, ``_pallas_core.1 s32[1,8192] tpu_custom_call``.

The window is taken from the trace's own clock: from the start of the first
``request`` annotation to the end of the last.  Device events are clipped to
it.  Busy is the union of the device events' intervals, averaged over the
chips; idle is the window less busy.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = re.compile(r"^/host:")
REQUEST = "request"
KERNEL_MARK = "tpu_custom_call"
_HLO = re.compile(r"^%?([^\s=]+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def short_name(name: str) -> str:
    """``%op.1 = s32[1,8192]{...} custom-call(...)`` -> ``op.1 s32[1,8192]``,
    with `` tpu_custom_call`` after it for a Pallas kernel."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    out = m.group(1) + (" " + m.group(2) if m.group(2) else "")
    if 'custom_call_target="' + KERNEL_MARK in name:
        out += " " + KERNEL_MARK
    return out


class Trace(NamedTuple):
    window_s: float
    busy_s: float  # union of device-busy intervals, mean over chips
    chips: int
    op_seconds: dict  # device event name -> summed seconds, mean over chips
    op_counts: dict  # device event name -> events, all chips
    idle_gaps: dict  # what the host was doing -> summed idle seconds
    requests: int  # request annotations inside the window

    def seconds_of(self, patterns) -> "tuple[float, int]":
        """Summed device seconds and event count of the operations whose
        name matches any of ``patterns`` (regular expressions)."""
        rx = [re.compile(p) for p in patterns]
        total, count = 0.0, 0
        for name, s in self.op_seconds.items():
            if any(r.search(name) for r in rx):
                total += s
                count += self.op_counts[name]
        return total, count

    def top_ops(self, n: int = 10) -> "list[list]":
        ranked = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[name, s] for name, s in ranked[:n]]

    def top_gaps(self, n: int = 10) -> "list[list]":
        ranked = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])
        return [[name, s] for name, s in ranked[:n]]


def find_xplane(log_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals: "list[tuple[int, int]]") -> "list[tuple[int, int]]":
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _gaps(busy, lo: int, hi: int):
    at = lo
    for a, b in busy:
        if a > at:
            yield at, a
        at = max(at, b)
    if hi > at:
        yield at, hi


def _where(gap, requests, starts) -> str:
    """What the host was doing in an idle gap, as far as the benchmark's own
    annotations tell: by the request that holds the gap's middle, and
    whether the device had already worked for that request.  ``requests``
    do not overlap (one caller) and are sorted; ``starts`` are their starts."""
    mid = (gap[0] + gap[1]) // 2
    k = bisect.bisect_right(starts, mid) - 1
    if k < 0 or mid >= requests[k][1]:
        return "between requests"
    _, _, first_busy, last_busy = requests[k]
    if first_busy is None or mid < first_busy:
        return "inside a request, before its first device operation"
    if mid >= last_busy:
        return "inside a request, after its last device operation"
    return "inside a request, between its device operations"


def reduce_planes(planes) -> "Trace | None":
    """``planes``: (name, [(line name, [(event name, start_ns, duration_ns)])]).
    Returns None where there is nothing to read: no request annotation or no
    device plane."""
    device, host = [], []
    for name, lines in planes:
        if DEVICE_PLANE.match(name):
            for line_name, events in lines:
                if line_name == OPS_LINE:
                    device.append(events)
        elif HOST_PLANE.match(name):
            for _, events in lines:
                host.extend(e for e in events if e[0] == REQUEST)
    if not device or not host:
        return None
    lo = min(s for _, s, _ in host)
    hi = max(s + d for _, s, d in host)
    if hi <= lo:
        return None
    op_ns, op_n, busy_ns = {}, {}, 0
    unions = []
    for events in device:
        clipped = []
        for name, s, d in events:
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            clipped.append((a, b))
            op_ns[name] = op_ns.get(name, 0) + (b - a)
            op_n[name] = op_n.get(name, 0) + 1
        u = _union(clipped)
        unions.append(u)
        busy_ns += sum(b - a for a, b in u)
    chips = len(device)
    # idle gaps are read on the first chip: one chip today, and on a mesh
    # every chip runs the same program
    first = unions[0]
    requests = []
    starts = [a for a, _ in first]
    for _, s, d in sorted(host, key=lambda e: e[1]):
        i = bisect.bisect_left(starts, s)
        if i and first[i - 1][1] > s:
            i -= 1
        j = bisect.bisect_left(starts, s + d)
        requests.append(
            (s, s + d, first[i][0], first[j - 1][1]) if j > i
            else (s, s + d, None, None)
        )
    idle = {}
    req_starts = [r[0] for r in requests]
    cuts = sorted({t for a, b, _, _ in requests for t in (a, b)})
    for a, b in _gaps(first, lo, hi):
        # a gap that spans a request's boundary is cut there
        inner = cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)]
        edges = [a, *inner, b]
        for piece in zip(edges, edges[1:]):
            key = _where(piece, requests, req_starts)
            idle[key] = idle.get(key, 0.0) + (piece[1] - piece[0]) / 1e9
    return Trace(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_ns / chips / 1e9,
        chips=chips,
        op_seconds={k: v / chips / 1e9 for k, v in op_ns.items()},
        op_counts=op_n,
        idle_gaps=idle,
        requests=len(host),
    )


def read_planes(path: str):
    """Only what ``reduce_planes`` reads: the device planes' operations and
    the host's ``request`` annotations (a host plane holds JAX's own events
    by the hundred thousand)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            keep = lambda line, e: line.name == OPS_LINE  # noqa: E731
        elif HOST_PLANE.match(plane.name):
            keep = lambda line, e: e.name == REQUEST  # noqa: E731
        else:
            continue
        lines = []
        for line in plane.lines:
            events = [
                (short_name(e.name), int(e.start_ns), int(e.duration_ns))
                for e in line.events
                if keep(line, e)
            ]
            if events:
                lines.append((line.name, events))
        out.append((plane.name, lines))
    return out


def reduce_file(path: str) -> "Trace | None":
    return reduce_planes(read_planes(path))
