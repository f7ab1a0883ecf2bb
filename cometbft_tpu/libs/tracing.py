"""Verify-pipeline flight recorder: span tracing + anomaly forensics.

The paper's headline claim is a LATENCY claim (<5 ms for a 10k-validator
commit), but counters can only say that something was slow on average —
not WHICH dispatch, at WHAT shape, on WHICH supervisor tier.  This module
is the jax-free tracing half of the observability layer
(docs/observability.md): a thread-safe span tracer over a bounded
in-memory ring buffer (the *flight recorder*), threaded through the whole
verify journey — txingest admission → sigcache probe → verifysched
submit/queue-wait/flush → ``ops/verify`` bucket dispatch → supervisor tier
(watchdog fires, degradations, bisect quarantines) → verdict resolution —
plus the consensus/blocksync/light spans above it.

Span model: ``(trace_id, span_id, parent_id, stage, t_start, t_end,
attrs)``.  Trace propagation is ambient on one thread (a thread-local
stack): a span opened while another is live becomes its child and inherits
its trace id.  ACROSS threads the parent travels with the work: the
submitter takes ``current()`` once a segment, the scheduler keeps it on
the queued items, and the dispatcher and completion threads open their
spans with ``span(stage, parent=...)`` — so one commit verification is ONE
tree over the caller, dispatcher and fetch threads.  The clock is
injectable (``set_clock``) so the deterministic simulator traces on its
VirtualClock and two same-seed runs produce byte-identical span streams.

Stage taxonomy (dotted, coarse on the hot path — one span per batch or
per dispatch, never per signature):

  * ``txingest.flush`` / ``txingest.shed_sync``  — batched tx admission
  * ``verify.commit`` > ``commit.sign_bytes``, ``batch.verify`` (>
    ``batch.add``, ``batch.keys``, ``batch.lookup``, ``batch.writeback``:
    the seam's parts, one span each a call) > ``sched.segment`` >
    ``sched.submit``, ``sched.wait`` — the caller thread of one commit
    verification, public entry to verdict
  * ``sched.flush`` > ``sched.slot_wait``, ``sched.dispatch`` — the
    dispatcher thread; ``sched.fetch``, ``sched.landed``,
    ``sched.resolve`` — the completion thread, children of the flush;
    ``sched.shed_fallback``
  * the hand-offs between those threads, each a completed span with one
    stamp from either thread (``now`` / ``handoff``), recorded by the
    thread that takes the work up: ``sched.queue`` (callers →
    dispatcher, child of the flush it ends at), ``sched.handoff.fetch``
    (dispatcher → completion thread), ``sched.handoff.wake`` (completion
    thread → the caller back from ``result()``, child of its
    ``sched.wait``)
  * ``verify.pack`` (> ``verify.pack.glue``, ``verify.pack.native``) /
    ``verify.batch`` / ``verify.dispatch`` > ``verify.launch`` (>
    ``verify.launch.lookup``, ``verify.launch.put`` or ``mesh.put``,
    ``verify.launch.call``) / ``verify.fetch`` > ``verify.fetch.pull`` —
    bucket dispatch (the dispatch span carries bucket lanes + tier +
    dispatch seq: the triple an anomaly dump attributes a watchdog fire
    to); what the two watchdogged spans hold beyond their laps is the
    watchdog's trip, two wakes between live threads, and each says which
    ``worker`` took its call (``parked``, or ``fresh`` where a thread was
    started; ``mesh.shard`` too)
  * ``supervisor.host_fallback`` / ``supervisor.bisect``
  * ``consensus.vote`` / ``consensus.proposal`` / ``consensus.vote_ext``
    (per height-round)
  * ``blocksync.tick`` > ``blocksync.window``, ``blocksync.wait``,
    ``blocksync.validate``, ``blocksync.apply``; ``blocksync.receive`` —
    a joiner's frontier tick, its window queued, its wait, its checks and
    apply, and the decode of a block on the thread that receives
  * ``light.sync`` > ``light.store`` (``op`` load / save), ``light.chain``
    > ``light.chain.prep`` (> ``light.checks``, ``commit.sign_bytes``,
    ``sched.segment``), ``light.chain.wait`` — the light client's request,
    and the sequential client's window of headers on the served path
  * ``warmboot.shape`` / ``warmboot.run``        — warm-boot progress

Anomaly forensics: ``record_anomaly(kind, **attrs)`` counts every anomaly
(watchdog_fire, breaker_open, queue_shed, ingest_shed, quarantine,
exec_cache_stale) and — on the FIRST occurrence of each kind since the
last reset (``COMETBFT_TPU_TRACE_DUMP_ALL=1`` dumps every occurrence) —
writes the last ``COMETBFT_TPU_TRACE_DUMP_SPANS`` (256) spans as JSONL to
``COMETBFT_TPU_TRACE_DIR`` for postmortem.  The dump's first line names
the anomaly and its attributes; dump bytes are a pure function of the
span stream, so a sim scenario's dump replays byte-identically per seed.

Stage totals: the ring is the tail of WHOLE spans for an anomaly dump and
wraps in seconds under load.  Beside it every stage keeps a count and a
sum of durations by the whole second in which its spans ended (the last
``TOTALS_KEEP_S`` seconds, never dropping a span): ``stage_totals(t0, t1)``
reads them over an interval after the fact, and ``stage_summary`` and the
``/debug/verify_trace`` document read the same store.

Host pressure: when a span's end opens a new second of that store the
recorder takes one sample of what the machine did to the process (no
thread of its own, outside the ring lock, nothing with the recorder off or
on an injected clock) and stores the difference from the last sample under
pseudo-stages of the same store, ``[1, difference]`` in the second(s) that
passed: ``host.runq_wait`` (seconds the long-lived threads that opened a
span, the caller, the dispatcher and the completion thread, spent runnable
and not running; NOT the watchdog's workers, which park between calls but
open no span, so their ids are not known here), ``host.cpu`` (CPU seconds
of the WHOLE process, every thread, the workers too), ``host.throttled``
(the cgroup's ``cpu.stat``), ``host.switches`` and ``host.faults``
(``getrusage``, whole process: involuntary switches, minor faults).  A
source the host lacks is
left out, not zero.  ``stage_totals`` and ``stage_seconds`` carry
them with the stages; ``host_summary`` (the ``host`` of the
``/debug/verify_trace`` document) gives them apart, and ``stage_summary``
holds durations only.

Profiler bridge: while a ``with`` span is open AND a profiler is capturing
(``TraceMe.is_enabled()``: nothing is built otherwise) the tracer also holds a
``jax.profiler.TraceAnnotation`` named ``tpubft/<stage>``, so a device
trace taken over the same interval has every program span on the clock
of ``XLA Ops``.  The class is taken from ``sys.modules`` when jax is
already loaded; this module never imports it.  Work that runs on a
watchdog thread is timed there with ``lap`` (which enters the annotation)
and recorded by the calling thread.

Kill switch: ``COMETBFT_TPU_TRACE=0`` compiles spans down to no-ops (a
shared null context manager; one look-up in the environment's own mapping
per span site, one clock read per hand-off) — bench.py ``--obs`` pins the
disabled overhead at ≤1% of the sched bench.

Cross-node correlation (docs/observability.md "Cross-node tracing"): a
``TraceContext`` is the compact (trace_id, span_id, origin-node) triple a
gossip envelope carries so consensus-round spans on different nodes form
ONE causal tree per (height, round) — the proposer's ``consensus.round``
span is the root, every receiver's round span adopts its trace id, and a
commit's verify spans on node B link back to the proposal that originated
on node A through nothing but the shared trace id.  Event-driven stages
that outlive any ``with`` block (a consensus round spans many receive-loop
events) use the explicit ``begin``/``finish`` API; ``under`` temporarily
makes such an unfinished span the ambient parent so the verify pipeline
underneath it inherits the round's trace.  ``COMETBFT_TPU_TRACE_XNODE=0``
turns off context propagation (spans still record, per-node only).
``rounds_report`` merges the ring into per-(height, round) timelines —
tolerant of orphan parents (a crashed proposer's root span never records;
the group still renders with ``origin=None``) and of ring-bound drops.

Deliberately free of jax imports, like ``ops/dispatch_stats``: the
``/metrics`` scrape, the ``/debug/verify_trace`` RPC and the
``cometbft-tpu trace`` CLI all read this module, and none of them may be
the thing that initializes an accelerator backend.
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import sys
import threading
import time
from collections import deque
from typing import Callable, Optional

logger = logging.getLogger("cometbft_tpu.tracing")

DEFAULT_RING = 4096
DEFAULT_DUMP_SPANS = 256
# whole seconds of per-stage totals kept beside the ring
TOTALS_KEEP_S = 300
ANNOTATION_PREFIX = "tpubft/"
# anomaly kinds with a dump trigger (docs/observability.md).  Breaker
# opens are per-taxonomy-kind: the ed25519 device tiers share
# "breaker_open", while the single-tier secp256k1/BLS breakers get their
# own kinds — each kind's FIRST open dumps, so an ed25519 brownout can no
# longer eat the one dump a simultaneous secp_device failure deserved.
ANOMALY_KINDS = (
    "watchdog_fire",
    "breaker_open",
    "breaker_open_secp_device",
    "breaker_open_bls_g1",
    "queue_shed",
    "ingest_shed",
    "quarantine",
    "exec_cache_stale",
    # elastic mesh supervision (parallel/elastic, docs/backend-supervisor
    # "Fault isolation"): a shard abandoned past the watchdog, a device
    # removed from mesh membership (shard failure or proactive
    # probe-down), and a device re-admitted by a passing half-open probe.
    # Per-ordinal breaker opens additionally get their own
    # ``breaker_open_mesh_dev{N}`` kinds via backend_health.
    "shard_watchdog_fire",
    "mesh_shrink",
    "mesh_restore",
)


# ``os.environ.get`` encodes the key and decodes the value at every call
# (0.9 us, a fifth of a span, at some thirty span sites a request); the
# mapping underneath holds both encoded (``os.fsencode``), and a look-up
# there is a plain dict's.  Read from ``os.environ`` at every call, so a
# switch flipped while the process runs, or an environment replaced whole,
# still holds (``tests/test_tracing.py`` pins both).
_TRACE_KEY = os.fsencode("COMETBFT_TPU_TRACE")
_TRACE_OFF = os.fsencode("0")


def enabled() -> bool:
    """``COMETBFT_TPU_TRACE=0`` is the kill switch; default on.  One dict
    lookup — the only cost a disabled span site pays besides the null
    context manager."""
    return os.environ._data.get(_TRACE_KEY) != _TRACE_OFF


# -- durable sinks (libs/blackbox.py) -----------------------------------------
#
# The black-box journal subscribes here; with no sink installed (the
# default, and always under COMETBFT_TPU_BLACKBOX=0) every hook is a
# single None check — the RAM-only recorder is bit-for-bit unchanged.
#   span(sp)              — every COMPLETED span, as it lands in the ring
#   open(sp)              — every explicit begin() span (round anchors)
#   anomaly(kind, attrs, t) — EVERY anomaly occurrence (the RAM dump
#                           latch stays first-per-kind; the journal does not)
#   event(kind, attrs)    — low-rate journal-only events (breaker
#                           transitions, quorum arrivals, device probes)

_SINKS: dict = {"span": None, "open": None, "anomaly": None, "event": None}


def set_sink(kind: str, fn):
    """Install (or, with None, remove) a durable sink; returns the sink
    it replaced so callers can restore it.  Sink errors are swallowed at
    the call sites — forensics must never become a second failure."""
    prev = _SINKS[kind]
    _SINKS[kind] = fn
    return prev


def get_sink(kind: str):
    return _SINKS[kind]


def note_event(kind: str, **attrs) -> None:
    """Journal-only event: recorded by the black box when one is
    installed, invisible to the RAM ring.  For low-rate state transitions
    (breaker close, device probe flips, quorum arrivals on the in-flight
    round) whose loss at crash time would blind a postmortem."""
    fn = _SINKS["event"]
    if fn is None:
        return
    try:
        fn(kind, attrs)
    except Exception:  # noqa: BLE001
        pass


def trace_dir() -> Optional[str]:
    return os.environ.get("COMETBFT_TPU_TRACE_DIR") or None


def xnode_enabled() -> bool:
    """Whether gossip envelopes carry trace contexts
    (``COMETBFT_TPU_TRACE_XNODE=0`` disables propagation while keeping
    per-node spans).  Implies the recorder itself being on."""
    return (
        enabled()
        and os.environ.get("COMETBFT_TPU_TRACE_XNODE", "1") != "0"
    )


_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is loaded
_ANNOTATING = None  # its ``is_enabled``: whether any profiler is capturing


def _annotate(stage: str):
    """An ENTERED profiler annotation ``tpubft/<stage>`` on this thread, or
    None while jax is not loaded or no profiler is capturing (one call of
    ``TraceMe.is_enabled``, 0.1 us, where building, entering and leaving an
    annotation that nothing records costs 0.8).  Never imports jax: the
    forensic surfaces that read this module must not be what initializes a
    backend."""
    global _ANNOTATION, _ANNOTATING
    cls = _ANNOTATION
    if cls is None:
        jax = sys.modules.get("jax")
        cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
        if cls is None:
            return None
        _ANNOTATION = cls
        _ANNOTATING = getattr(cls, "is_enabled", None)
    if _ANNOTATING is not None and not _ANNOTATING():
        return None
    ann = cls(ANNOTATION_PREFIX + stage)
    ann.__enter__()
    return ann


# -- host pressure (what the machine did to the process) ----------------------
#
# One sample when the per-second store opens a new second, on whichever
# thread's span opened it: no thread of its own, nothing read while the
# recorder is off.  Each source is a cumulative reading; the difference from
# the last sample is stored under a pseudo-stage ``[1, difference]`` in the
# second(s) that passed, so ``stage_totals`` / ``stage_seconds`` carry it
# beside the spans: a slow second of ``verify.fetch`` reads next to what the
# kernel says of the same second.  A source the host lacks is left out, not
# zero: a sandboxed kernel (gVisor) keeps no ``schedstat``, no ``cpu.stat``
# and answers ``getrusage`` with zeros, and of the five only the process's
# CPU time is left there.

HOST_PREFIX = "host."
# threads whose run-queue wait is read (those that opened a span); a bound,
# not a census: a node's long-lived verify threads register in its first
# second
HOST_THREADS_MAX = 16


def _runq_wait_of(tid: int) -> float:
    """Seconds thread ``tid`` has spent runnable and not running: field 2 of
    its ``schedstat`` (nanoseconds; needs ``CONFIG_SCHED_INFO``)."""
    with open(f"/proc/self/task/{tid}/schedstat") as f:
        return int(f.read().split()[1]) / 1e9


class _PerThread:
    """A per-thread reading summed over the registered threads, cumulative
    over the DIFFERENCES of each thread's own readings: a thread counts from
    the sample after it registered (not for its whole life), and one whose
    reading fails (it has exited) counts no further."""

    def __init__(self, read_one: Callable[[int], float]):
        self.read_one = read_one
        self.last: dict = {}
        self.total = 0.0

    def __call__(self, tids) -> Optional[float]:
        seen = False
        for tid in list(tids):
            try:
                v = self.read_one(tid)
            except (OSError, ValueError, IndexError):
                self.last.pop(tid, None)
                continue
            seen = True
            if tid in self.last:
                self.total += v - self.last[tid]
            self.last[tid] = v
        return self.total if seen else None


def _cgroup_cpu_stat() -> "Optional[tuple[str, str, float]]":
    """``(path, key, seconds per unit)`` of the cgroup's ``cpu.stat`` that
    holds this process's throttled time: v2 (``throttled_usec``) under the
    unified mount, v1 (``throttled_time``, nanoseconds) under the ``cpu``
    controller's; None where neither is there."""
    rel = {}
    try:
        with open("/proc/self/cgroup") as f:
            for line in f:
                _, ctrls, path = line.rstrip("\n").split(":", 2)
                for c in ctrls.split(","):
                    rel[c] = path
    except (OSError, ValueError):
        return None
    tries = []
    if "" in rel:
        tries += [
            ("/sys/fs/cgroup" + rel[""], "throttled_usec", 1e-6),
            ("/sys/fs/cgroup/unified" + rel[""], "throttled_usec", 1e-6),
        ]
    if "cpu" in rel:
        tries += [
            (root + rel["cpu"], "throttled_time", 1e-9)
            for root in ("/sys/fs/cgroup/cpu", "/sys/fs/cgroup/cpu,cpuacct")
        ]
    for d, key, unit in tries:
        path = os.path.join(d, "cpu.stat")
        try:
            with open(path) as f:
                if any(ln.split()[:1] == [key] for ln in f):
                    return path, key, unit
        except OSError:
            continue
    return None


def _default_host_readers() -> dict:
    """``{pseudo-stage: reader(tids) -> cumulative value or None}``, only the
    sources THIS host has; built at the first sample, never at import."""
    readers: dict = {}
    try:
        _runq_wait_of(threading.get_native_id())
        readers[HOST_PREFIX + "runq_wait"] = _PerThread(_runq_wait_of)
    except Exception:  # noqa: BLE001 — not this kernel's
        pass
    # the WHOLE process's CPU clock: the watchdog's workers (where a
    # launch's transfers and a fetch's pull run) open no span, and the
    # runtime's own threads are none of the program's
    readers[HOST_PREFIX + "cpu"] = lambda tids: time.process_time()
    try:
        import resource

        def rusage(field):
            return lambda tids: float(
                getattr(resource.getrusage(resource.RUSAGE_SELF), field)
            )

        # a process that has run this far has faulted pages in: a kernel
        # that says none keeps no count, of switches either
        if resource.getrusage(resource.RUSAGE_SELF).ru_minflt > 0:
            readers[HOST_PREFIX + "switches"] = rusage("ru_nivcsw")
            readers[HOST_PREFIX + "faults"] = rusage("ru_minflt")
    except ImportError:  # not a POSIX host
        pass
    found = _cgroup_cpu_stat()
    if found is not None:
        path, key, unit = found

        def throttled(tids):
            try:
                with open(path) as f:
                    for ln in f:
                        k, _, v = ln.partition(" ")
                        if k == key:
                            return int(v) * unit
            except (OSError, ValueError):
                pass
            return None

        readers[HOST_PREFIX + "throttled"] = throttled
    return readers


class TraceContext:
    """The compact trace context a gossip envelope propagates: the
    sender's round-trace id, the span to parent under, and the origin
    node.  Encodes to a short ASCII token so any transport (sim fabric
    today, a p2p envelope field tomorrow) can carry it opaquely."""

    __slots__ = ("trace_id", "span_id", "origin")

    def __init__(self, trace_id: int, span_id: int, origin=None):
        self.trace_id = int(trace_id)
        self.span_id = int(span_id)
        self.origin = origin

    def encode(self) -> str:
        o = "" if self.origin is None else str(int(self.origin))
        return f"{self.trace_id:x}.{self.span_id:x}.{o}"

    @classmethod
    def decode(cls, token) -> "Optional[TraceContext]":
        """Tolerant decode: garbage, truncation or a foreign format yield
        None (a malformed context must never fail message handling)."""
        if isinstance(token, TraceContext):
            return token
        if not isinstance(token, str):
            return None
        parts = token.split(".")
        if len(parts) != 3:
            return None
        try:
            trace_id = int(parts[0], 16)
            span_id = int(parts[1], 16)
            origin = int(parts[2]) if parts[2] else None
        except ValueError:
            return None
        if trace_id <= 0 or span_id <= 0:
            return None
        return cls(trace_id, span_id, origin)

    def __repr__(self) -> str:  # debugging/trace logs
        return f"TraceContext({self.encode()!r})"

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, TraceContext)
            and self.trace_id == other.trace_id
            and self.span_id == other.span_id
            and self.origin == other.origin
        )


class Span:
    """One recorded stage interval.  ``attrs`` values must be
    JSON-serializable (dump files are byte-compared across sim runs)."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "stage", "t_start", "t_end",
        "attrs",
    )

    def __init__(self, trace_id, span_id, parent_id, stage, t_start, attrs):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.stage = stage
        self.t_start = t_start
        self.t_end = None
        self.attrs = attrs

    def set(self, **attrs) -> "Span":
        """Attach attributes mid-span (e.g. the outcome, known at exit)."""
        self.attrs.update(attrs)
        return self

    @property
    def duration(self) -> float:
        return (self.t_end or self.t_start) - self.t_start

    def to_dict(self) -> dict:
        # fixed rounding so float formatting can never vary a dump byte
        d = {
            "trace": self.trace_id,
            "span": self.span_id,
            "stage": self.stage,
            "t0": round(self.t_start, 9),
            "t1": round(self.t_end, 9) if self.t_end is not None else None,
            "dur_ms": (
                round((self.t_end - self.t_start) * 1e3, 6)
                if self.t_end is not None
                else None
            ),
        }
        if self.parent_id is not None:
            d["parent"] = self.parent_id
        if self.attrs:
            d["attrs"] = self.attrs
        return d


class _NullSpan:
    """The disabled-tracer span: a shared, allocation-free no-op that
    still satisfies the ``with tracer.span(...) as sp: sp.set(...)``
    calling convention."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self


_NULL_SPAN = _NullSpan()


class _SpanCtx:
    __slots__ = ("tracer", "sp", "ann")

    def __init__(self, tracer: "Tracer", sp: Span):
        self.tracer = tracer
        self.sp = sp
        self.ann = None

    def __enter__(self) -> Span:
        stack = self.tracer._stack()
        stack.append(self.sp)
        self.ann = _annotate(self.sp.stage)
        return self.sp

    def __exit__(self, etype, evalue, tb) -> bool:
        sp = self.sp
        sp.t_end = self.tracer._clock()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        if etype is not None:
            sp.attrs.setdefault("error", etype.__name__)
        stack = self.tracer._stack()
        if stack and stack[-1] is sp:
            stack.pop()
        elif sp in stack:  # mis-nested exit (exception unwound past us)
            stack.remove(sp)
        self.tracer._append(sp)
        return False


class Lap:
    """A stage timed where it runs and recorded later, by the thread that
    may write the ring: ``with tracing.lap(stage) as lp: ...`` reads the
    tracer's clock at both ends (and holds the profiler annotation, on
    THIS thread); ``lp.record(parent=..., **attrs)`` then lands it as a
    completed span.  Two users: a closure on a watchdog thread (an
    abandoned worker must never race a span into the ring, so the caller
    records after ``watchdog_call`` returns, and a lap that never closed
    records nothing), and the caller's submit and wait stages, which end
    while the dispatcher is writing (recording both after the wait keeps
    the ring's order a function of the work, not of thread timing)."""

    __slots__ = ("tracer", "stage", "t0", "t1", "ann")

    def __init__(self, tracer: "Optional[Tracer]", stage: str):
        self.tracer = tracer  # None: the recorder is off
        self.stage = stage
        self.t0 = self.t1 = self.ann = None

    def __enter__(self) -> "Lap":
        if self.tracer is not None:
            self.ann = _annotate(self.stage)
            self.t0 = self.tracer._clock()
        return self

    def __exit__(self, *exc) -> bool:
        if self.tracer is not None:
            self.t1 = self.tracer._clock()
            if self.ann is not None:
                self.ann.__exit__(None, None, None)
        return False

    def record(self, parent=None, **attrs) -> "Optional[Span]":
        if self.t1 is None:
            return None
        return self.tracer.record_span(
            self.stage, self.t0, self.t1, parent=parent, **attrs
        )


class _UnderCtx:
    """Pushes an unfinished explicit span as the ambient parent for the
    duration of a block; pops by identity so nested/rotated anchors can
    never unbalance the stack."""

    __slots__ = ("tracer", "sp")

    def __init__(self, tracer: "Tracer", sp: Span):
        self.tracer = tracer
        self.sp = sp

    def __enter__(self) -> Span:
        self.tracer._stack().append(self.sp)
        return self.sp

    def __exit__(self, *exc) -> bool:
        stack = self.tracer._stack()
        if stack and stack[-1] is self.sp:
            stack.pop()
        elif self.sp in stack:
            stack.remove(self.sp)
        return False

    def set(self, **attrs):  # parity with _NullSpan for disabled callers
        self.sp.set(**attrs)
        return self


class Tracer:
    """Bounded flight recorder; all methods are thread-safe.

    Spans land in the ring ON COMPLETION (the append is the caller
    thread's, so a worker abandoned by the dispatch watchdog never races
    a span into a deterministic sim's record)."""

    def __init__(
        self,
        ring_size: Optional[int] = None,
        clock: Optional[Callable[[], float]] = None,
    ):
        if ring_size is None:
            try:
                ring_size = int(
                    os.environ.get("COMETBFT_TPU_TRACE_RING", "")
                    or DEFAULT_RING
                )
            except ValueError:
                ring_size = DEFAULT_RING
        self._lock = threading.Lock()
        self._ring: "deque[Span]" = deque(maxlen=max(int(ring_size), 16))
        self._clock: Callable[[], float] = clock or time.perf_counter
        self._tls = threading.local()
        self._ids = itertools.count(1)  # ``next`` is atomic: no lock
        self._recorded = 0
        self._dropped = 0
        self._anomalies: dict = {}
        self._dumped_kinds: set = set()
        self._dump_seq = 0
        self._dumps: "list[str]" = []
        self._overhead_s = 0.0
        # second in which a span ended -> {stage: [count, seconds]}
        self._totals: dict = {}
        # host pressure: the readers (this host's, built at the first sample
        # on the default clock; or injected ones), the last sample
        # ``(second, {stage: cumulative})``, the native ids of the threads
        # that opened a span
        self._host_readers: Optional[dict] = None
        self._host_injected: Optional[dict] = None
        self._host_last: Optional[tuple] = None
        self._host_tids: set = set()
        self._host_lock = threading.Lock()
        # process-LIFETIME aggregates: reset() (sim per-run hygiene) does
        # not clear these, so the tier1-trace summary line still reports
        # the whole test run's span volume and recorder overhead
        self._life_recorded = 0
        self._life_dropped = 0
        self._life_anomalies = 0
        self._life_dumps = 0
        self._life_overhead_s = 0.0

    # -- span API ----------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
            # once a thread: its run-queue wait is read from here on
            if len(self._host_tids) < HOST_THREADS_MAX:
                self._host_tids.add(threading.get_native_id())
        return stack

    def span(self, stage: str, parent=None, **attrs):
        """Context manager recording one stage interval.  Nested spans
        (same thread) become children; the root span's id is the trace id.
        ``parent`` (a span or context taken on ANOTHER thread with
        ``current()``) overrides the ambient one: how a flush joins the
        trace of the request it serves.  Disabled tracer → the shared
        no-op span."""
        if not enabled():
            return _NULL_SPAN
        sid = next(self._ids)
        if parent is None or parent is _NULL_SPAN:
            stack = self._stack()
            parent = stack[-1] if stack else None
        sp = Span(
            trace_id=parent.trace_id if parent is not None else sid,
            span_id=sid,
            parent_id=parent.span_id if parent is not None else None,
            stage=stage,
            t_start=self._clock(),
            attrs=attrs,
        )
        return _SpanCtx(self, sp)

    def current_trace(self) -> Optional[int]:
        stack = getattr(self._tls, "stack", None)
        return stack[-1].trace_id if stack else None

    def current(self) -> Optional[Span]:
        """This thread's innermost open span: what work handed to another
        thread carries along as its ``parent``."""
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def lap(self, stage: str) -> Lap:
        return Lap(self if enabled() else None, stage)

    def start(self, stage: str, **attrs) -> Optional[Span]:
        """An unfinished span, child of this thread's innermost open one,
        and on no thread's stack: a stage the caller hands away and takes
        back later (a queued segment, ``verifysched.submit_segment_async``)
        opens here, ``under`` makes it the parent where it must be, and
        ``finish`` lands it.  The caller's own spans meanwhile are not its
        children.  Unlike ``begin`` it writes no open record: it is a stage
        of the hot path, not an anchor a crash has to leave behind."""
        if not enabled():
            return None
        stack = self._stack()
        parent = stack[-1] if stack else None
        sid = next(self._ids)
        return Span(
            parent.trace_id if parent is not None else sid,
            sid,
            parent.span_id if parent is not None else None,
            stage,
            self._clock(),
            attrs,
        )

    def time(self) -> float:
        """The tracer's clock (virtual in sim).  Event-driven callers use
        it for retroactive ``record_span`` timestamps so span times always
        share one time base with the rest of the ring."""
        return self._clock()

    # -- explicit span API (event-driven stages) ---------------------------
    #
    # A consensus round outlives any single receive-loop event, so no
    # ``with`` block can bracket it: ``begin`` allocates an UNFINISHED span
    # (id + start time), the state machine mutates/adopts it across events,
    # and ``finish`` stamps the end time and lands it in the ring — still
    # on completion, still from the owning thread.

    def begin(
        self,
        stage: str,
        parent: Optional[Span] = None,
        ctx: Optional[TraceContext] = None,
        **attrs,
    ) -> Optional[Span]:
        """Allocate an unfinished span.  ``parent`` (a local span) or
        ``ctx`` (a remote trace context) seed the trace; with neither the
        span is a trace root.  Returns None when tracing is disabled —
        every other explicit-API call accepts None as a no-op."""
        if not enabled():
            return None
        sid = next(self._ids)
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        elif ctx is not None:
            trace_id, parent_id = ctx.trace_id, ctx.span_id
            if ctx.origin is not None:
                attrs.setdefault("xnode", ctx.origin)
        else:
            trace_id, parent_id = sid, None
        sp = Span(trace_id, sid, parent_id, stage, self._clock(), attrs)
        sink = _SINKS["open"]
        if sink is not None:
            # the journal's OPEN record: an explicit span (a consensus
            # round anchor) exists from this moment, so a crash before
            # finish() still leaves the in-flight round reconstructable
            try:
                sink(sp)
            except Exception:  # noqa: BLE001
                pass
        return sp

    def finish(self, sp: Optional[Span], **attrs) -> None:
        """Stamp the end time and record an explicit span.  Idempotent on
        an already-finished span; None is a no-op."""
        if sp is None or sp.t_end is not None:
            return
        if attrs:
            sp.attrs.update(attrs)
        sp.t_end = self._clock()
        self._append(sp)

    def adopt(self, sp: Optional[Span], ctx: Optional[TraceContext]) -> bool:
        """Re-parent a still-rootless unfinished span under a remote
        context — how a receiver's ``consensus.round`` span joins the
        originating proposal's trace.  No-op (False) once the span has a
        parent or has finished: first adoption wins."""
        if (
            sp is None
            or ctx is None
            or sp.parent_id is not None
            or sp.t_end is not None
        ):
            return False
        sp.trace_id = ctx.trace_id
        sp.parent_id = ctx.span_id
        if ctx.origin is not None:
            sp.attrs.setdefault("xnode", ctx.origin)
        return True

    def record_span(
        self,
        stage: str,
        t_start: float,
        t_end: float,
        parent: Optional[Span] = None,
        **attrs,
    ) -> Optional[Span]:
        """Manufacture a COMPLETED span with explicit timestamps (taken
        from ``time()``) — retroactive step timing: the consensus state
        machine only knows a step's duration once the next step begins."""
        if not enabled():
            return None
        sid = next(self._ids)
        if parent is not None and parent is not _NULL_SPAN:
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            trace_id, parent_id = sid, None
        sp = Span(trace_id, sid, parent_id, stage, t_start, attrs)
        sp.t_end = t_end
        self._append(sp)
        return sp

    def under(self, sp: Optional[Span]):
        """Context manager making an UNFINISHED explicit span the ambient
        parent, so ``span()`` sites underneath (verify.commit, dispatches)
        inherit its trace — the linkage that lets a commit's verify spans
        resolve to the originating proposal.  ``under(None)`` is a shared
        no-op."""
        if sp is None or not enabled():
            return _NULL_SPAN
        return _UnderCtx(self, sp)

    def ctx_for(self, sp: Optional[Span], origin=None) -> Optional[TraceContext]:
        """A propagatable context pointing at an explicit span."""
        if sp is None:
            return None
        return TraceContext(sp.trace_id, sp.span_id, origin)

    def _append(self, sp: Span) -> None:
        t0 = time.perf_counter()
        opened = None
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
                self._life_dropped += 1
            self._ring.append(sp)
            self._recorded += 1
            self._life_recorded += 1
            sec = int(sp.t_end)
            bucket = self._totals.get(sec)
            if bucket is None:
                bucket = self._totals[sec] = {}
                opened = sec
                if len(self._totals) > TOTALS_KEEP_S:
                    for old in [
                        k for k in self._totals if k <= sec - TOTALS_KEEP_S
                    ]:
                        del self._totals[old]
            tot = bucket.get(sp.stage)
            if tot is None:
                bucket[sp.stage] = [1, sp.t_end - sp.t_start]
            else:
                tot[0] += 1
                tot[1] += sp.t_end - sp.t_start
            # exit-path cost only (the enter path is of the same order):
            # an approximate but honestly *measured* recorder overhead the
            # tier1-trace summary line reports as a share of wall time
            dt = time.perf_counter() - t0
            self._overhead_s += dt
            self._life_overhead_s += dt
        if opened is not None:
            self._sample_host(opened)
        sink = _SINKS["span"]
        if sink is not None:
            # outside the ring lock: the journal enqueue has its own lock
            # and never blocks on IO (bounded queue, counted drops)
            try:
                sink(sp)
            except Exception:  # noqa: BLE001
                pass

    # -- host pressure -----------------------------------------------------

    def set_host_readers(self, readers: Optional[dict]) -> None:
        """Swap the host sources (tests): ``{pseudo-stage: reader(tids) ->
        cumulative value or None}``; ``{}`` samples nothing, None restores
        this host's own.  Forgets the last sample."""
        with self._host_lock:
            self._host_injected = readers
            self._host_last = None

    def _sample_host(self, sec: int) -> None:
        """One sample as second ``sec`` opens; the difference from the last
        sample belongs to the seconds since that one opened and is stored
        there, ``[1, difference]`` a second (a gap of several seconds, a
        pause of the whole process, shares it out evenly).  Outside the ring
        lock but for the store itself; a second sampler at the same moment
        steps aside, and the next sample covers its seconds."""
        if not self._host_lock.acquire(blocking=False):
            return
        try:
            readers = self._host_injected
            if readers is None:
                if self._clock is not time.perf_counter:
                    # an injected (virtual) clock: its seconds are not the
                    # host's, and a sim's record stays a function of its seed
                    return
                readers = self._host_readers
                if readers is None:
                    readers = self._host_readers = _default_host_readers()
            now = {}
            for stage, read in readers.items():
                try:
                    v = read(self._host_tids)
                except Exception:  # noqa: BLE001 — a source, never a failure
                    v = None
                if v is not None:
                    now[stage] = v
            last, self._host_last = self._host_last, (sec, now)
            if last is None or sec <= last[0]:
                return
            sec0, before = last
            first = max(sec0, sec - TOTALS_KEEP_S + 1)
            with self._lock:
                for stage, v in now.items():
                    if stage not in before:
                        continue
                    share = (v - before[stage]) / (sec - sec0)
                    for s in range(first, sec):
                        self._totals.setdefault(s, {})[stage] = [1, share]
        finally:
            self._host_lock.release()

    def host_summary(self) -> dict:
        """``{source: {"seconds", "total", "last"}}`` over the kept seconds:
        the path's threads' run-queue wait, the process's CPU time and the
        cgroup's throttled time in seconds, the process's involuntary
        switches and minor faults as counts; ``last`` is the newest sampled second's.
        Only the sources this host has."""
        out: dict = {}
        with self._lock:
            for sec in sorted(self._totals):
                for stage, (n, v) in self._totals[sec].items():
                    if stage.startswith(HOST_PREFIX):
                        got = out.setdefault(
                            stage[len(HOST_PREFIX):],
                            {"seconds": 0, "total": 0.0, "last": 0.0},
                        )
                        got["seconds"] += n
                        got["total"] += v
                        got["last"] = v
        return {
            k: {"seconds": g["seconds"], "total": round(g["total"], 6),
                "last": round(g["last"], 6)}
            for k, g in sorted(out.items())
        }

    # -- anomaly forensics -------------------------------------------------

    def record_anomaly(self, kind: str, **attrs) -> Optional[str]:
        """Count an anomaly; dump the ring tail as JSONL on the first
        occurrence of ``kind`` since the last reset (all occurrences with
        ``COMETBFT_TPU_TRACE_DUMP_ALL=1``).  Returns the dump path, or
        None when no dump was written.  Never raises — forensics must not
        become a second failure."""
        dump_all = os.environ.get("COMETBFT_TPU_TRACE_DUMP_ALL") == "1"
        sink = _SINKS["anomaly"]
        if sink is not None:
            # the durable journal records EVERY occurrence (and fsyncs);
            # the RAM dump below stays latched first-per-kind
            try:
                sink(kind, attrs, self._clock())
            except Exception:  # noqa: BLE001
                pass
        with self._lock:
            self._anomalies[kind] = self._anomalies.get(kind, 0) + 1
            self._life_anomalies += 1
            want_dump = (
                enabled()
                and trace_dir() is not None
                and (dump_all or kind not in self._dumped_kinds)
            )
            if not want_dump:
                return None
            self._dumped_kinds.add(kind)
            self._dump_seq += 1
            seq = self._dump_seq
            tail = self._dump_tail_locked()
            now = self._clock()
        try:
            return self._write_dump(kind, seq, now, attrs, tail)
        except Exception as e:  # noqa: BLE001 — forensics is best-effort
            logger.warning("flight-recorder dump failed: %r", e)
            return None

    def _dump_tail_locked(self) -> "list[Span]":
        try:
            n = int(
                os.environ.get("COMETBFT_TPU_TRACE_DUMP_SPANS", "")
                or DEFAULT_DUMP_SPANS
            )
        except ValueError:
            n = DEFAULT_DUMP_SPANS
        ring = list(self._ring)
        return ring[-n:] if n > 0 else ring

    def _write_dump(self, kind, seq, now, attrs, tail) -> str:
        d = trace_dir()
        os.makedirs(d, exist_ok=True)
        name = f"trace-{seq:03d}-{kind}.jsonl"
        path = os.path.join(d, name)
        lines = [
            json.dumps(
                {
                    "anomaly": kind,
                    "seq": seq,
                    "t": round(now, 9),
                    "attrs": attrs,
                    "spans": len(tail),
                },
                sort_keys=True,
            )
        ]
        lines.extend(json.dumps(sp.to_dict(), sort_keys=True) for sp in tail)
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        with self._lock:
            self._dumps.append(name)
            del self._dumps[:-32]  # keep the last 32 names
            self._life_dumps += 1
        logger.warning(
            "flight recorder: anomaly %s -> dumped %d spans to %s",
            kind,
            len(tail),
            path,
        )
        return path

    # -- introspection -----------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "enabled": enabled(),
                "ring_size": self._ring.maxlen,
                "ring_len": len(self._ring),
                "spans_recorded": self._recorded,
                "spans_dropped": self._dropped,
                "anomalies": dict(self._anomalies),
                "anomalies_total": sum(self._anomalies.values()),
                "dumps": list(self._dumps),
                "dump_count": self._dump_seq,
                "overhead_seconds": self._overhead_s,
                "lifetime": {
                    "spans_recorded": self._life_recorded,
                    "spans_dropped": self._life_dropped,
                    "anomalies": self._life_anomalies,
                    "dumps": self._life_dumps,
                    "overhead_seconds": self._life_overhead_s,
                },
            }

    def tail(self, n: int = DEFAULT_DUMP_SPANS) -> "list[dict]":
        with self._lock:
            ring = list(self._ring)
        return [sp.to_dict() for sp in (ring[-n:] if n > 0 else ring)]

    def stage_totals(
        self, t0: Optional[float] = None, t1: Optional[float] = None
    ) -> dict:
        """``{stage: (count, seconds)}`` over the spans that ENDED in the
        whole seconds inside ``[t0, t1]`` on the tracer's clock (a second
        cut by either end is left out, so a mean over the same seconds
        loses nothing to the cut); every kept second where a bound is
        None.  Read after the fact: no snapshot is needed beforehand, and
        no span is dropped however often the ring has wrapped."""
        out: dict = {}
        with self._lock:
            for sec, bucket in self._totals.items():
                if (t0 is not None and sec < t0) or (
                    t1 is not None and sec + 1 > t1
                ):
                    continue
                for stage, (n, s) in bucket.items():
                    got = out.get(stage)
                    out[stage] = (
                        (n, s) if got is None else (got[0] + n, got[1] + s)
                    )
        return out

    def stage_seconds(self) -> dict:
        """The store itself, ``{second: {stage: (count, seconds)}}``: which
        stage grows as a run goes on."""
        with self._lock:
            return {
                sec: {k: (v[0], v[1]) for k, v in bucket.items()}
                for sec, bucket in sorted(self._totals.items())
            }

    def stage_summary(self) -> dict:
        """Per-stage count and total over the last ``TOTALS_KEEP_S``
        seconds (the store ``stage_totals`` reads: right however often the
        ring has wrapped), with p50 / p99 / max over the ``ring_count``
        spans of the stage still in the ring (the recent tail — what a
        regression hunt wants; None for a stage whose spans have all left
        the ring)."""
        with self._lock:
            ring = list(self._ring)
        by_stage: dict = {}
        for sp in ring:
            if sp.t_end is None:
                continue
            by_stage.setdefault(sp.stage, []).append(sp.t_end - sp.t_start)
        out = {}
        for stage, (count, seconds) in sorted(self.stage_totals().items()):
            if stage.startswith(HOST_PREFIX):
                continue  # not durations: ``host_summary``
            durs = sorted(by_stage.get(stage, ()))
            n = len(durs)
            out[stage] = {
                "count": count,
                "total_ms": round(seconds * 1e3, 3),
                "ring_count": n,
                "p50_ms": round(durs[n // 2] * 1e3, 3) if n else None,
                "p99_ms": round(durs[min(n - 1, (n * 99) // 100)] * 1e3, 3)
                if n
                else None,
                "max_ms": round(durs[-1] * 1e3, 3) if n else None,
            }
        return out

    def rounds_report(self, last_k: Optional[int] = None) -> dict:
        """Merged cross-node round timelines over the spans currently in
        the ring: one group per (height, round), carrying every node's
        ``consensus.round`` span (duration, committed flag, quorum-arrival
        times, per-step durations) plus the count of ``verify.commit``
        spans that link to the group's trace — the proof that a commit's
        verification attributes to the proposal that originated it.

        Orphan tolerance by construction: a group whose root span never
        recorded (crashed proposer, ring-bound drop) still renders, with
        ``origin=None``; a step or commit span whose parent fell off the
        ring still aggregates by its own (h, r)/trace attrs.  The report
        is a pure function of the span stream, so two same-seed sim runs
        serialize byte-identically (sort_keys JSON)."""
        with self._lock:
            ring = list(self._ring)
        groups: dict = {}  # (h, r) -> group dict
        step_agg: dict = {}  # step name -> [durations]
        quorum_agg: dict = {"prevote_ms": [], "precommit_ms": []}
        commit_traces: dict = {}  # trace_id -> verify.commit span count
        commits_total = 0
        commits_standalone = 0

        def group(h, r) -> dict:
            g = groups.get((h, r))
            if g is None:
                g = groups[(h, r)] = {
                    "h": h,
                    "r": r,
                    "trace": None,
                    "origin": None,
                    "nodes": {},
                    "traces": set(),
                }
            return g

        def node_entry(g, node) -> dict:
            e = g["nodes"].get(node)
            if e is None:
                e = g["nodes"][node] = {"node": node, "steps": {}}
            return e

        # a light client's request is a trace of its own (``light.sync``,
        # or ``light.verify`` called bare, is the root of its commit
        # passes), as standalone as a bare call
        light_traces = {
            sp.trace_id
            for sp in ring
            if sp.stage in ("light.sync", "light.verify") and sp.parent_id is None
        }
        for sp in ring:
            if sp.t_end is None:
                continue
            a = sp.attrs
            if sp.stage == "consensus.round":
                g = group(a.get("h"), a.get("r"))
                g["traces"].add(sp.trace_id)
                e = node_entry(g, a.get("node"))
                e["dur_ms"] = round(sp.duration * 1e3, 6)
                e["committed"] = bool(a.get("committed"))
                e["adopted"] = sp.parent_id is not None
                for k, agg in (
                    ("q_prevote_ms", "prevote_ms"),
                    ("q_precommit_ms", "precommit_ms"),
                ):
                    if k in a:
                        e[k] = a[k]
                        quorum_agg[agg].append(a[k])
                if sp.parent_id is None and a.get("proposer"):
                    # the trace root: the PROPOSER's round span.  A merely
                    # rootless span (a node that never adopted — partition,
                    # or propagation off) must not claim the round's origin
                    g["trace"] = sp.trace_id
                    g["origin"] = a.get("node")
            elif sp.stage == "consensus.step":
                g = group(a.get("h"), a.get("r"))
                e = node_entry(g, a.get("node"))
                dur = round(sp.duration * 1e3, 6)
                e["steps"][a.get("step", "?")] = dur
                step_agg.setdefault(a.get("step", "?"), []).append(
                    sp.duration
                )
            elif sp.stage == "verify.commit":
                if sp.parent_id is None or sp.trace_id in light_traces:
                    # a standalone verification (light client, statesync
                    # trust check, the sim's invariant checker): its own
                    # trace root by construction — not a linkage failure
                    commits_standalone += 1
                    continue
                commits_total += 1
                commit_traces[sp.trace_id] = (
                    commit_traces.get(sp.trace_id, 0) + 1
                )

        all_traces: set = set()
        rounds = []
        for (h, r) in sorted(
            groups, key=lambda k: (k[0] is None, k[0] or 0, k[1] or 0)
        ):
            g = groups[(h, r)]
            all_traces |= g["traces"]
            n_commits = sum(
                commit_traces.get(t, 0) for t in sorted(g["traces"])
            )
            if g["trace"] is None and len(g["traces"]) == 1:
                # orphan root: the trace id is still known from the
                # adopted members, only the proposer's span is missing
                g["trace"] = next(iter(g["traces"]))
            rounds.append(
                {
                    "h": h,
                    "r": r,
                    "trace": g["trace"],
                    "origin": g["origin"],
                    "commits": n_commits,
                    "nodes": [
                        g["nodes"][k]
                        for k in sorted(
                            g["nodes"], key=lambda n: (n is None, n)
                        )
                    ],
                }
            )

        def pctls(durs: list) -> dict:
            if not durs:
                return {"count": 0}
            durs = sorted(durs)
            n = len(durs)
            return {
                "count": n,
                "p50_ms": round(durs[n // 2] * 1e3, 6),
                "p99_ms": round(
                    durs[min(n - 1, (n * 99) // 100)] * 1e3, 6
                ),
                "max_ms": round(durs[-1] * 1e3, 6),
            }

        linked = sum(commit_traces.get(t, 0) for t in all_traces)
        return {
            "rounds_seen": len(rounds),
            "rounds": rounds[-last_k:] if last_k else rounds,
            "steps": {k: pctls(v) for k, v in sorted(step_agg.items())},
            "quorum": {
                # already in ms — scale back for the shared helper
                k: pctls([x / 1e3 for x in v])
                for k, v in sorted(quorum_agg.items())
            },
            "commits_linked": linked,
            "commits_unlinked": commits_total - linked,
            "commits_standalone": commits_standalone,
        }

    # -- lifecycle ---------------------------------------------------------

    def set_clock(self, clock: Optional[Callable[[], float]]) -> None:
        """Swap the time source (the sim pins its VirtualClock here so
        span times are virtual and deterministic); None restores
        ``time.perf_counter``."""
        self._clock = clock or time.perf_counter
        with self._host_lock:
            self._host_last = None  # no difference across two clocks

    def dump_state(self) -> dict:
        """Snapshot of the anomaly-dump latch (first-per-kind set, dump
        sequence, dump names).  Scenario setup hooks save this so their
        teardown can restore it — composed scenarios' setup/teardown must
        not leak dump-latch state into the run (or each other) any more
        than they leak env knobs."""
        with self._lock:
            return {
                "dumped_kinds": set(self._dumped_kinds),
                "dump_seq": self._dump_seq,
                "dumps": list(self._dumps),
            }

    def restore_dump_state(self, state: dict) -> None:
        with self._lock:
            self._dumped_kinds = set(state.get("dumped_kinds", ()))
            self._dump_seq = int(state.get("dump_seq", 0))
            self._dumps = list(state.get("dumps", ()))

    def reset(self) -> None:
        """Fresh recorder state: empty ring, zeroed counters/ids, dump
        latch cleared.  The sim calls this per scenario run so span ids
        (and therefore dump bytes) are a pure function of the seed."""
        with self._lock:
            self._ring.clear()
            self._ids = itertools.count(1)
            self._recorded = 0
            self._dropped = 0
            self._anomalies = {}
            self._dumped_kinds = set()
            self._dump_seq = 0
            self._dumps = []
            self._overhead_s = 0.0
            self._totals = {}
            self._host_last = None


_TRACER: Optional[Tracer] = None
_TRACER_LOCK = threading.Lock()


def get_tracer() -> Tracer:
    """The process-wide flight recorder (every pipeline stage writes to
    one ring — cross-stage attribution IS the feature)."""
    global _TRACER
    if _TRACER is None:
        with _TRACER_LOCK:
            if _TRACER is None:
                _TRACER = Tracer()
    return _TRACER


def reset_tracer() -> None:
    """Drop the process-wide tracer (tests/sim; re-reads the ring-size
    env on next use)."""
    global _TRACER
    with _TRACER_LOCK:
        _TRACER = None


# module-level conveniences — the spelling the pipeline call sites use
def span(stage: str, **attrs):
    return get_tracer().span(stage, **attrs)


def lap(stage: str) -> Lap:
    return get_tracer().lap(stage)


def current() -> Optional[Span]:
    return get_tracer().current() if enabled() else None


def now() -> float:
    """A stamp on the tracer's clock: the one thing a hand-off between two
    threads costs with the recorder off.  The thread that takes the work up
    turns it into a span with ``handoff``."""
    return get_tracer().time()


def handoff(stage: str, t_start: float, parent=None, **attrs):
    """The hand-off that began at ``t_start`` (``now()`` on the thread that
    gave the work away) ends here, on the thread that holds it: a completed
    span, recorded by the receiver."""
    if not enabled():
        return None
    tracer = get_tracer()
    return tracer.record_span(
        stage, t_start, tracer.time(), parent=parent, **attrs
    )


def mark(**attrs) -> None:
    """Set attributes on this thread's innermost open span, if there is one:
    how a layer below says what it found (a single-signature lookup that the
    signature cache answered marks ``hit`` on ``consensus.vote``)."""
    sp = current()
    if sp is not None:
        sp.set(**attrs)


def record_anomaly(kind: str, **attrs) -> Optional[str]:
    return get_tracer().record_anomaly(kind, **attrs)


def wall_seconds(sp, t0: float) -> float:
    """Wall seconds of the block a ``with`` span just closed over, for the
    histogram beside it: the span's own two readings where it is a real
    span on the default clock, so that span and histogram are fed from one
    pair; ``perf_counter() - t0`` for the no-op span or an injected
    (virtual) clock."""
    if (
        type(sp) is Span
        and sp.t_end is not None
        and get_tracer()._clock is time.perf_counter
    ):
        return sp.t_end - sp.t_start
    return time.perf_counter() - t0


def summary_line() -> str:
    """One parseable line for test logs (scripts/check_tier1_budget.py
    reads the span count and recorder overhead share from it).  Reports
    the process-LIFETIME aggregates: per-run ``reset()`` calls (the sim)
    must not hide the suite's true recorder traffic."""
    life = get_tracer().snapshot()["lifetime"]
    return (
        "tier1-trace: spans=%d dropped=%d anomalies=%d dumps=%d "
        "overhead_s=%.3f"
        % (
            life["spans_recorded"],
            life["spans_dropped"],
            life["anomalies"],
            life["dumps"],
            life["overhead_seconds"],
        )
    )


DEFAULT_ROUND_K = 8


def trace_document(
    max_spans: int = DEFAULT_DUMP_SPANS, rounds: int = DEFAULT_ROUND_K
) -> dict:
    """The one-call forensic snapshot behind the ``/debug/verify_trace``
    RPC and the ``cometbft-tpu trace`` CLI: ring tail + per-stage latency
    summary + pipeline health (breaker states, cache hit rates, scheduler
    queue, warm-boot progress) as a single JSON-serializable document.

    Every read is lazy and jax-free; a section that fails to import
    reports its error instead of sinking the document."""
    tracer = get_tracer()
    doc = {
        "tracing": tracer.snapshot(),
        "stages": tracer.stage_summary(),
        # what the machine did to the process in the same seconds
        "host": tracer.host_summary(),
        # last-K merged consensus-round timelines (cross-node when the
        # fabric propagates contexts); rounds <= 0 skips the section body
        "rounds": tracer.rounds_report(last_k=max(0, int(rounds)) or None)
        if rounds > 0
        else {},
        # max_spans <= 0 really means "health only, no span payload" —
        # tail()'s 0-means-all convention is for the dump path, not here
        "spans": tracer.tail(max_spans) if max_spans > 0 else [],
    }

    def section(name, fn):
        try:
            doc[name] = fn()
        except Exception as e:  # noqa: BLE001 — one bad section must not
            # sink the whole forensic document
            doc[name] = {"error": repr(e)}

    def _backend():
        from cometbft_tpu.crypto import backend_health

        return backend_health.snapshot()

    def _sigcache():
        from cometbft_tpu.crypto import sigcache

        return sigcache.get_cache().stats()

    def _dispatch():
        from cometbft_tpu.ops import dispatch_stats

        return dispatch_stats.snapshot()

    def _sched():
        from cometbft_tpu.verifysched import stats as sstats

        return sstats.snapshot()

    def _warmboot():
        from cometbft_tpu.ops import warm_stats

        return warm_stats.snapshot()

    def _ingest():
        from cometbft_tpu.txingest import stats as istats

        return istats.snapshot()

    def _device():
        from cometbft_tpu.ops import device_health

        return device_health.snapshot()

    def _blackbox():
        from cometbft_tpu.libs import blackbox

        return blackbox.journal_stats() or {"enabled": blackbox.enabled()}

    def _storage():
        from cometbft_tpu.libs import storage_stats

        return storage_stats.snapshot()

    def _proofserve():
        from cometbft_tpu.proofserve import stats as pstats

        return pstats.snapshot()

    def _blocksync():
        from cometbft_tpu.blocksync import stats as bstats

        return bstats.snapshot()

    section("backend", _backend)
    section("sigcache", _sigcache)
    section("dispatch", _dispatch)
    section("sched", _sched)
    section("warmboot", _warmboot)
    section("ingest", _ingest)
    section("device", _device)
    section("blackbox", _blackbox)
    section("storage", _storage)
    section("proofserve", _proofserve)
    section("blocksync", _blocksync)
    return doc
