"""Loop ``closed``: ``clients`` callers (one today), each of which waits for
a verdict before it sends its next request: a light client or a joiner
verifying consecutive headers.  A slow system receives less load.

The window opens at the first send and closes when the request in flight at
``seconds`` is answered: rates are all the work over all of that time.  A
pool that is drained before ``seconds`` ends the window there.
"""

from __future__ import annotations

import contextlib
import time
from typing import NamedTuple


class Record(NamedTuple):
    key: object  # the request's ``key``
    start: float  # perf_counter
    end: float
    verdict: tuple
    signatures: int


class Window(NamedTuple):
    records: "list[Record]"
    start: float
    end: float
    drained: bool  # the pool ran out before the time did


def run(entry, state, chain, requests, seconds: float, traffic: dict,
        between=None, annotate=None) -> Window:
    """``requests`` are what ``entry.requests(chain)`` gave, sent in order.
    ``between(now)`` runs between requests (the traced run opens and closes
    the profiler there); ``annotate(name)`` wraps each request."""
    if int(traffic.get("clients", 1)) != 1:
        raise NotImplementedError("closed loop: one client only, so far")
    annotate = annotate or (lambda name: contextlib.nullcontext())
    records = []
    clock = time.perf_counter
    t0 = clock()
    deadline = t0 + seconds
    drained = True
    for req in requests:
        now = clock()
        if now >= deadline:
            drained = False
            break
        if between is not None:
            between(now)
        n = entry.signatures(chain, req)
        with annotate("request"):
            start = clock()
            verdict = entry.call(state, req)
            end = clock()
        records.append(Record(req.key, start, end, verdict, n))
    return Window(records, t0, clock(), drained)
