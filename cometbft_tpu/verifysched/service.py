"""Continuous-batching asynchronous verification service.

The device kernel only earns its keep when batches are full, yet every
caller on the verify hot path historically assembled its *own* batch and
blocked on its *own* dispatch: gossip-time ``Vote.verify`` paid a
one-signature dispatch (or the host fallback), evidence checks verified
per vote, and concurrent submitters each ate the per-dispatch floor.  This
module is the missing shared engine — the continuous-batching scheduler
shape from inference serving applied to signature verification
(docs/verify-scheduler.md):

  * callers ``submit(pub, msg, sig, priority)`` and get back a Future (or
    bridge whole segments via ``verify_segment_sync``);
  * one dispatcher thread coalesces pending items ACROSS all submitters
    into a single ``ops/verify.verify_segments`` dispatch, flushing when
    the oldest item has waited ``COMETBFT_TPU_SCHED_FLUSH_US`` (~2000) or
    when a padding bucket fills (at which point the dispatch carries zero
    padding waste);
  * the sigcache is consulted before any queue slot or device lane is
    occupied, and duplicate in-flight triples (the same vote gossiped by
    two peers at once) collapse into one lane;
  * everything below the flush runs under the existing ``ops/supervisor``
    chain, so futures ALWAYS complete with definitive verdicts — an
    infrastructure failure degrades pallas -> xla -> host, never becomes a
    False accept bit (tests/test_verifysched.py pins this with
    ``FaultyBackend``).

Priority classes and admission control: ``consensus`` (vote/proposal/
extension checks) > ``evidence_light`` (evidence, light client) > ``bulk``
(blocksync, mempool).  The queue is bounded (``COMETBFT_TPU_SCHED_QUEUE``,
default 8192); overload sheds ONLY non-consensus classes — a shed caller
falls back to its own synchronous verify (it loses the batching win, never
the verdict) — while consensus submissions are always admitted: consensus
traffic is bounded by validator count x rounds, and blocking or dropping a
vote is a liveness hazard no queue bound justifies.

Activation: the scheduler takes the verify path only when
``COMETBFT_TPU_VERIFY_SCHED`` != 0 (default on) AND the accelerator batch
backend is trusted (``crypto.batch.default_backend() == "tpu"`` — the same
gate the fused stream uses).  Otherwise every wrapper here falls through
to the exact pre-scheduler code path, so the kill switch
``COMETBFT_TPU_VERIFY_SCHED=0`` restores prior behavior bit-for-bit.
``verify_now`` is the synchronous escape hatch for callers that cannot
tolerate queueing latency.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from typing import Optional, Sequence

from cometbft_tpu.crypto import sigcache
from cometbft_tpu.libs import tracing
from cometbft_tpu.verifysched import stats

logger = logging.getLogger("cometbft_tpu.verifysched")

# Priority classes (lower = more important).  EVIDENCE/LIGHT share a class,
# as do BLOCKSYNC/MEMPOOL — three queues cover the real urgency tiers.
PRIO_CONSENSUS = 0
PRIO_EVIDENCE = 1
PRIO_LIGHT = 1
PRIO_BLOCKSYNC = 2
PRIO_MEMPOOL = 2
N_CLASSES = 3

DEFAULT_FLUSH_US = 2000.0
DEFAULT_QUEUE_CAP = 8192
# largest fusable bucket (mirrors ops.verify._BUCKETS[-1]); a single drain
# never exceeds it — leftovers stay queued for the next flush
MAX_DRAIN = 32768

# ops.verify._BUCKETS mirror for the ``_bucket_target`` fallback path: if
# the ops import itself fails, the width-scaled target must STILL clamp to
# a real padding bucket — a non-bucket target would deliberately wait for
# a strictly worse-padded flush
_FALLBACK_BUCKETS = (32, 64, 128, 256, 512, 1024, 4096, 8192, 10240, 32768)


class QueueFullError(Exception):
    """Admission control rejected a non-consensus submission (backpressure).
    The caller verifies synchronously instead — shedding costs the batching
    win, never the verdict."""


def enabled() -> bool:
    return os.environ.get("COMETBFT_TPU_VERIFY_SCHED", "1") != "0"


def backend_trusted() -> bool:
    """True when the accelerator batch backend is the trusted ``tpu``
    seam — the gate the fused stream, blocksync prefetch, the scheduler
    AND the tx-ingest coalescer all share, so a CPU-backend node (whose
    host library path has no dispatch floor to amortize) keeps its
    synchronous behavior untouched.

    Deliberately NEVER calls ``cbatch.default_backend()``'s auto-probe:
    that would import jax and initialize a backend from gossip-time
    ``Vote.verify`` in processes that otherwise never touch the device
    (every CPU e2e node pays seconds of init on its first vote).  With the
    backend unconfigured and still unresolved, the gate stays closed; it
    opens the moment the batch seam's own first use resolves the backend
    to ``tpu``."""
    from cometbft_tpu.crypto import batch as cbatch

    env = os.environ.get("COMETBFT_TPU_CRYPTO_BACKEND")
    if env and env != "auto":
        return env == "tpu"
    return cbatch._DEFAULT_BACKEND == "tpu"


def scheduler_active() -> bool:
    """True when submissions should take the scheduler path: kill switch
    on AND the batch backend trusted (``backend_trusted``)."""
    return enabled() and backend_trusted()


def pipeline_enabled() -> bool:
    """In-flight pipelining (docs/verify-scheduler.md "In-flight
    pipeline"): the dispatcher ships flush i+1 while flush i is still on
    the device, and one completion thread resolves verdicts in drain
    order.  ``COMETBFT_TPU_SCHED_PIPELINE=0`` restores the single-flight
    dispatcher bit-for-bit."""
    return os.environ.get("COMETBFT_TPU_SCHED_PIPELINE", "1") != "0"


def inflight_target() -> int:
    """Bound on concurrently dispatched flushes: explicit
    ``COMETBFT_TPU_SCHED_INFLIGHT`` wins; the default is the LIVE elastic
    mesh width (each healthy lane carries its own dispatch, and the bound
    follows shrinks/restores automatically — ``healthy_width`` is
    jax-free) with a floor of 2 on a single chip, where the depth buys
    host-prep/device-compute overlap rather than lane parallelism."""
    env = os.environ.get("COMETBFT_TPU_SCHED_INFLIGHT")
    if env:
        try:
            return max(int(env), 1)
        except ValueError:
            pass
    try:
        from cometbft_tpu.parallel import elastic

        w = elastic.healthy_width()
    except Exception:  # noqa: BLE001 — mesh introspection is never
        # load-bearing for the flush loop
        w = 0
    return max(w, 2)


# -- per-thread priority class ----------------------------------------------

_TLS = threading.local()


def current_priority() -> int:
    """The ambient priority class for this thread.  Call sites that reach
    the scheduler through deep shared layers (the ``_CollectingVerifier``
    bridge under ``types/validation``) tag their work with
    ``priority_class`` instead of plumbing an argument through every
    signature-verification API.

    FAIL-CLOSED default: untagged work is BULK (sheddable).  The
    consensus class is shed-exempt and skips the queue bound, so handing
    it out implicitly would let any future untagged caller bypass
    admission control and starve every other class; the three consensus
    sites (vote, proposal, vote-extension) pass ``priority=`` explicitly."""
    return getattr(_TLS, "prio", PRIO_BLOCKSYNC)


@contextlib.contextmanager
def priority_class(priority: int):
    prev = getattr(_TLS, "prio", None)
    _TLS.prio = int(priority)
    try:
        yield
    finally:
        if prev is None:
            del _TLS.prio
        else:
            _TLS.prio = prev


# -- the scheduler -----------------------------------------------------------


class _Item:
    # t0 = submit time, t_drain = when the dispatcher drained it out of
    # the queue: submit->drain is QUEUE WAIT, drain->verdict is DEVICE
    # time — recorded as separate histograms so queue pressure and device
    # slowness are distinguishable regressions (docs/observability.md)
    # ctx = the submitter's innermost open span (None outside any): the
    # flush that serves the item joins its trace
    __slots__ = (
        "pub", "msg", "sig", "prio", "future", "t0", "t_drain", "ctx",
    )

    def __init__(self, pub, msg, sig, prio, future, t0, ctx=None):
        self.pub = pub
        self.msg = msg
        self.sig = sig
        self.prio = prio
        self.future = future
        self.t0 = t0
        self.t_drain = t0
        self.ctx = ctx


_AMBIENT = object()  # submit(ctx=...): "take this thread's current span"


class VerifyScheduler:
    """One dispatcher thread over three priority queues.  Thread-safe;
    lazily starts its thread on the first queued submission and drains
    everything (reason ``shutdown``) on ``close()`` — a future handed out
    is always eventually resolved."""

    def __init__(
        self,
        flush_us: Optional[float] = None,
        queue_cap: Optional[int] = None,
    ):
        if flush_us is None:
            try:
                flush_us = float(
                    os.environ.get("COMETBFT_TPU_SCHED_FLUSH_US", "")
                    or DEFAULT_FLUSH_US
                )
            except ValueError:
                flush_us = DEFAULT_FLUSH_US
        if queue_cap is None:
            try:
                queue_cap = int(
                    os.environ.get("COMETBFT_TPU_SCHED_QUEUE", "")
                    or DEFAULT_QUEUE_CAP
                )
            except ValueError:
                queue_cap = DEFAULT_QUEUE_CAP
        self.flush_s = max(flush_us, 0.0) / 1e6
        self.queue_cap = max(int(queue_cap), 1)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queues: "list[deque[_Item]]" = [
            deque() for _ in range(N_CLASSES)
        ]
        self._count = 0
        self._thread: Optional[threading.Thread] = None
        self._stopped = False
        self._paused = False
        self._full_target: Optional[int] = None
        # flush-interval histo — stamped at DISPATCH SUBMISSION on the
        # dispatcher thread (monotonic there), never from item drain
        # times: with K flushes in flight, drain-time deltas could go
        # negative or interleave
        self._last_flush_t: Optional[float] = None
        # in-flight pipeline state (pipeline_enabled()): FIFO of
        # dispatched-but-unfetched flushes, resolved in drain order by
        # one completion thread; _fcond has its OWN lock so a waiting
        # dispatcher never blocks submitters
        self._flock = threading.Lock()
        self._fcond = threading.Condition(self._flock)
        self._fetch_queue: "deque[tuple]" = deque()
        self._inflight = 0
        self._fetch_thread: Optional[threading.Thread] = None
        self._fetch_stop = False
        self._lane_rr = 0  # round-robin over healthy mesh ordinals

    # -- submission -------------------------------------------------------

    def submit(
        self,
        pub: bytes,
        msg: bytes,
        sig: bytes,
        priority: int = PRIO_CONSENSUS,
        precleared: bool = False,
        ctx=_AMBIENT,
    ) -> "Future[bool]":
        """Queue one (pub, msg, sig) check; returns a Future resolving to
        the definitive verdict.  A sigcache hit resolves immediately
        without occupying a queue slot (``precleared=True`` skips that
        lookup — for bridges that just partitioned the cache themselves).
        Raises ``QueueFullError`` for non-consensus classes when the queue
        is at capacity; consensus submissions are always admitted.
        ``ctx`` is the span the serving flush is traced under: this
        thread's current one unless ``submit_many`` already took it."""
        prio = min(max(int(priority), 0), N_CLASSES - 1)
        fut: "Future[bool]" = Future()
        if ctx is _AMBIENT:
            ctx = tracing.current()
        if not precleared:
            hit = sigcache.get_cache().get(pub, msg, sig)
            if hit is not None:
                stats.record_submit_hit(prio)
                fut.set_result(bool(hit))
                return fut
        try:
            with self._cond:
                if self._stopped:
                    raise RuntimeError("verify scheduler is stopped")
                if prio != PRIO_CONSENSUS and self._count >= self.queue_cap:
                    stats.record_shed(prio)
                    raise QueueFullError(
                        f"verify queue at capacity ({self.queue_cap}); "
                        f"shedding class {stats.CLASS_NAMES[prio]}"
                    )
                self._queues[prio].append(
                    _Item(pub, msg, sig, prio, fut, time.perf_counter(), ctx)
                )
                self._count += 1
                stats.record_submit(prio)
                if self._thread is None or not self._thread.is_alive():
                    # lazily started — and RESTARTED if it ever died (an
                    # exception escaping even the _execute fallback, e.g.
                    # MemoryError): without this, every queued future would
                    # hang forever and take consensus with it.  The new
                    # thread drains whatever the dead one left queued.
                    if self._thread is not None:
                        logger.error(
                            "verify dispatcher thread died; restarting "
                            "(%d items pending)",
                            self._count,
                        )
                    self._thread = threading.Thread(
                        target=self._run, name="verify-sched", daemon=True
                    )
                    self._thread.start()
                self._cond.notify_all()
        except QueueFullError:
            # flight-recorder anomaly (the FIRST shed dumps the ring;
            # later sheds are counted), recorded AFTER the cond is
            # released: the dump's file IO must never block other
            # submitters — least of all shed-exempt consensus votes —
            # behind the scheduler lock
            tracing.record_anomaly(
                "queue_shed",
                cls=stats.CLASS_NAMES[prio],
                queue_cap=self.queue_cap,
            )
            raise
        return fut

    def submit_many(
        self,
        pubs: Sequence[bytes],
        msgs: Sequence[bytes],
        sigs: Sequence[bytes],
        priority: int = PRIO_CONSENSUS,
        precleared: bool = False,
    ) -> "list[Optional[Future]]":
        """Submit a whole segment before waiting on any item, so the
        pieces can coalesce into one flush.  Entries the admission control
        sheds come back as ``None`` — the caller verifies those itself.
        A scheduler stopped mid-segment (teardown race) marks the rest
        ``None`` the same way: already-queued futures still resolve (close
        drains the queue), the remainder degrade to the caller's fallback.
        The submitter's trace context is taken once, not once a signature."""
        out: "list[Optional[Future]]" = []
        ctx = tracing.current()
        for p, m, s in zip(pubs, msgs, sigs):
            try:
                out.append(self.submit(p, m, s, priority, precleared, ctx))
            except QueueFullError:
                out.append(None)
            except RuntimeError:
                out.extend([None] * (len(msgs) - len(out)))
                break
        return out

    # -- test/bench hooks -------------------------------------------------

    def pause(self) -> None:
        """Hold flushing (test/bench hook: build a deterministic backlog)."""
        with self._cond:
            self._paused = True

    def resume(self) -> None:
        with self._cond:
            self._paused = False
            self._cond.notify_all()

    def pending(self) -> int:
        with self._lock:
            return self._count

    def close(self, timeout_s: float = 10.0) -> None:
        """Stop accepting work, drain the queue (reason ``shutdown``) and
        join the dispatcher.  Every outstanding future resolves.  A
        dispatcher wedged past the join timeout (a stuck device dispatch)
        is surfaced loudly: the caller may be about to restore global
        state (env knobs, device-runner seam, stats) that the straggling
        flush would then run — and record — under."""
        with self._cond:
            self._stopped = True
            self._paused = False
            self._cond.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout_s)
            if t.is_alive():
                logger.warning(
                    "verify scheduler dispatcher still alive %.1fs after "
                    "close() — a wedged flush will finish under whatever "
                    "global state exists when it unwedges",
                    timeout_s,
                )
        # the dispatcher is down (no new enqueues); now drain the
        # completion pool — it exits only once the in-flight FIFO is empty,
        # so every dispatched flush still resolves its futures
        with self._fcond:
            self._fetch_stop = True
            self._fcond.notify_all()
            ft = self._fetch_thread
        if ft is not None:
            ft.join(timeout_s)
            if ft.is_alive():
                logger.warning(
                    "verify scheduler completion thread still alive %.1fs "
                    "after close() — a wedged fetch will finish under "
                    "whatever global state exists when it unwedges",
                    timeout_s,
                )

    # -- dispatcher -------------------------------------------------------

    def _bucket_target(self) -> int:
        """Items that fill the smallest padding bucket for the active
        kernel: flushing there costs zero padding waste, so waiting any
        longer only adds latency.  The base bucket is computed once, off
        the submit path (the ops import pulls in jax); the LIVE elastic
        mesh width scales it per flush — a W-device mesh splits the batch
        W ways, so a full flush is W smallest buckets (one per shard),
        and the target follows shrinks and restores automatically
        (``parallel/elastic.healthy_width`` is jax-free)."""
        if self._full_target is None:
            try:
                from cometbft_tpu.ops import verify as ov

                self._full_target = ov.bucket_size(1, ov._min_bucket())
            except Exception:  # noqa: BLE001 — conservative fallback
                self._full_target = 128
        try:
            from cometbft_tpu.parallel import elastic

            w = elastic.healthy_width()
        except Exception:  # noqa: BLE001 — mesh introspection is never
            # load-bearing for the flush loop
            w = 0
        if w < 2:
            return self._full_target
        # round DOWN to a real padding bucket: the mesh path pads the
        # fused batch to a GLOBAL bucket before sharding, so a non-bucket
        # target (base×3 = 384 → bucket 512) would deliberately wait for
        # a strictly worse-padded flush; the largest bucket ≤ base×W
        # keeps the zero-waste property at lower latency
        scaled = self._full_target * w
        try:
            from cometbft_tpu.ops import verify as ov

            fits = [
                b for b in ov._BUCKETS if self._full_target <= b <= scaled
            ]
            return fits[-1] if fits else self._full_target
        except Exception:  # noqa: BLE001 — clamp against the static
            # bucket mirror: the raw scaled value may not be a bucket
            fits = [
                b
                for b in _FALLBACK_BUCKETS
                if self._full_target <= b <= scaled
            ]
            return fits[-1] if fits else self._full_target

    def _oldest_t0(self) -> Optional[float]:
        heads = [q[0].t0 for q in self._queues if q]
        return min(heads) if heads else None

    def _drain(self) -> "list[_Item]":
        out: "list[_Item]" = []
        now = time.perf_counter()
        for q in self._queues:  # consensus first
            while q and len(out) < MAX_DRAIN:
                it = q.popleft()
                it.t_drain = now
                out.append(it)
        self._count -= len(out)
        return out

    def _run(self) -> None:
        self._bucket_target()  # jax import happens here, unlocked
        # the dispatcher only exists when the trusted backend is active —
        # the exact population warm-boot serves: precompile the bucket x
        # tier matrix in the background so the first flush (and the first
        # post-demotion flush) meets a resident executable
        from cometbft_tpu.ops import warmboot

        warmboot.ensure_started()
        while True:
            # re-read once per flush cycle (not per wakeup: every submit
            # notifies the cond, and the live-width read walks breaker
            # locks) — the target still follows mesh shrinks/restores at
            # flush granularity
            full = self._bucket_target()
            with self._cond:
                while not self._stopped and (
                    self._count == 0 or self._paused
                ):
                    self._cond.wait()
                if self._stopped and self._count == 0:
                    return
                reason = "shutdown"
                if not self._stopped:
                    while True:
                        if self._stopped:
                            break
                        if self._paused:
                            break
                        if self._count >= full:
                            reason = "full"
                            break
                        oldest = self._oldest_t0()
                        if oldest is None:
                            break
                        remain = oldest + self.flush_s - time.perf_counter()
                        if remain <= 0:
                            reason = "deadline"
                            break
                        self._cond.wait(remain)
                    if self._paused and not self._stopped:
                        continue
                    if self._count == 0:
                        continue
                items = self._drain()
            if items:
                self._execute(items, reason)

    # -- flush ------------------------------------------------------------

    def _execute(self, items: "list[_Item]", reason: str) -> None:
        recorded = [False]
        try:
            if pipeline_enabled():
                # in-flight pipeline: dispatch without blocking on the
                # verdicts — enqueueing onto the completion FIFO is the
                # LAST step, so any exception reaching the fallback below
                # means these items were never handed off and the host
                # reference resolve covers all of them
                self._dispatch_flush(items, reason, recorded)
            else:
                self._execute_inner(items, reason, recorded)
        except BaseException as e:  # noqa: BLE001 — futures must ALWAYS
            # resolve: these items left the queue, so the submit-path
            # dispatcher restart can never recover them — an unresolved
            # future here is a permanent consensus hang in result()
            logger.exception(
                "verify flush failed unexpectedly; resolving %d items on "
                "the host reference",
                len(items),
            )
            from cometbft_tpu.crypto import ed25519_ref as ref

            # exactly-once flush accounting: if the inner pass failed
            # before recording, account the drained items here or
            # queue_depth stays inflated forever
            if not recorded[0]:
                stats.record_flush(
                    reason, items=len(items), misses=0, lanes=0
                )
            now = time.perf_counter()
            for it in items:
                if it.future.done():
                    continue
                try:
                    ok = len(it.pub) == 32 and len(it.sig) == 64 and bool(
                        ref.verify_zip215(it.pub, it.msg, it.sig)
                    )
                except Exception:  # noqa: BLE001 — malformed input
                    ok = False
                it.future.set_result(ok)
                stats.record_verdict(it.prio, now - it.t0)
            if not isinstance(e, Exception):
                raise  # SystemExit etc.: die, but only AFTER resolving
                # (the next submit detects the dead thread and restarts)

    @staticmethod
    def _flush_span(reason: str, items: "list[_Item]"):
        """``sched.flush``, saying where its items came from: the trace ids
        it serves (a flush may serve several requests), the first
        submitter's span as its parent, and how long the oldest item
        waited in the queue."""
        if not tracing.enabled():
            return tracing.span("sched.flush")
        ctxs = {it.ctx for it in items}
        ctxs.discard(None)
        return tracing.span(
            "sched.flush",
            parent=items[0].ctx,
            reason=reason,
            items=len(items),
            traces=sorted({c.trace_id for c in ctxs}),
            queue_wait_s=round(
                items[0].t_drain - min(it.t0 for it in items), 9
            ),
        )

    @staticmethod
    def _set_results(items, bits, now: float, lo: int, hi: int) -> None:
        for i in range(lo, hi):
            it = items[i]
            if it.future.done():
                continue
            it.future.set_result(bool(bits[i]))
            stats.record_verdict(
                it.prio,
                now - it.t0,
                queue_wait_s=it.t_drain - it.t0,
                device_s=now - it.t_drain,
            )

    def _resolve(self, items, bits, fsp, settle=None) -> None:
        """``sched.resolve``: ``settle()`` (the verdict map and the cache
        puts of a fetched flush), then ``set_result`` for every item.  The
        span lands BEFORE the last future resolves, so a deterministic
        sim's ring order cannot race its waiter; that one ``set_result``
        is the part of the stage the span does not hold."""
        n = len(items)
        last = max(n - 1, 0)
        with tracing.span("sched.resolve", parent=fsp, items=n):
            if settle is not None:
                settle()
            now = time.perf_counter()
            self._set_results(items, bits, now, 0, last)
        self._set_results(items, bits, now, last, n)

    def _execute_inner(
        self, items: "list[_Item]", reason: str, recorded: "list[bool]"
    ) -> None:
        n = len(items)
        pubs = [it.pub for it in items]
        msgs = [it.msg for it in items]
        sigs = [it.sig for it in items]

        # flush span (closed BEFORE futures resolve, like the stats below,
        # so a deterministic sim's ring order cannot race its waiters)
        with self._flush_span(reason, items) as fsp:
            # structural filter (garbage never occupies a device lane) +
            # in-flight dedup: concurrent gossip of the same vote collapses
            # into one lane, both futures share the verdict
            bits: "list[Optional[bool]]" = [None] * n
            uniq: "OrderedDict[bytes, list[int]]" = OrderedDict()
            for i in range(n):
                if len(pubs[i]) != 32 or len(sigs[i]) != 64:
                    bits[i] = False
                    continue
                k = sigcache._key(pubs[i], msgs[i], sigs[i])
                uniq.setdefault(k, []).append(i)
            firsts = [ixs[0] for ixs in uniq.values()]
            stats.record_dedup(sum(len(ixs) - 1 for ixs in uniq.values()))

            lanes = 0
            if firsts:
                from cometbft_tpu.ops import verify as ov

                # one segment per priority class present: verify_segments
                # fuses them into ONE dispatch (recording cross-class
                # fusion in ops/dispatch_stats), splits bits back per class
                by_class: "list[list[int]]" = [[] for _ in range(N_CLASSES)]
                for i in firsts:
                    by_class[items[i].prio].append(i)
                ordered = [i for cls in by_class for i in cls]
                work = [
                    (
                        [pubs[i] for i in cls],
                        [msgs[i] for i in cls],
                        [sigs[i] for i in cls],
                    )
                    for cls in by_class
                    if cls
                ]
                lanes = ov.bucket_size(len(ordered), ov._min_bucket())
                results = ov.verify_segments(work)
                self._settle(bits, uniq, ordered, results)
            fsp.set(misses=len(firsts), lanes=lanes)

        # record BEFORE resolving: set_result unblocks waiters, and a
        # caller reading stats right after its verdict (the sim's
        # end-of-run capture asserts queue_depth == 0) must not race the
        # dispatcher's bookkeeping; ``recorded`` keeps the _execute
        # fallback from double-counting if a resolve below raises
        interval = self._flush_interval()
        stats.record_flush(
            reason, items=n, misses=len(firsts), lanes=lanes,
            interval_s=interval,
        )
        recorded[0] = True
        self._resolve(items, bits, fsp)

    @staticmethod
    def _settle(bits, uniq, ordered, results) -> None:
        """Verdicts keyed by FIRST index of each dedup group (the hash was
        already paid once in the dedup loop) resolve every member of the
        group, and go to the cache.  Inlined rather than
        ``sigcache.writeback``: that would re-hash every entry, and the
        dedup loop already holds the keys.  Supervised verdicts are always
        definitive, so caching unconditionally is safe."""
        verdict_by_first = dict(
            zip(ordered, (bool(b) for seg in results for b in seg))
        )
        cache = sigcache.get_cache()
        cache_on = cache.enabled()
        for k, ixs in uniq.items():
            v = verdict_by_first[ixs[0]]
            for i in ixs:
                bits[i] = v
            if cache_on:
                cache._put(k, v)

    # -- in-flight pipeline (docs/verify-scheduler.md) --------------------

    def _flush_interval(self) -> Optional[float]:
        """Interval between consecutive flushes, stamped NOW on the
        dispatcher thread — monotonic there by construction, so the
        histogram cannot go negative or interleave however many flushes
        are in flight."""
        t = time.perf_counter()
        interval = (
            None if self._last_flush_t is None else t - self._last_flush_t
        )
        self._last_flush_t = t
        return interval

    def _dispatch_flush(
        self, items: "list[_Item]", reason: str, recorded: "list[bool]"
    ) -> None:
        """The pipelined front half of a flush: structural filter +
        dedup + ONE fused dispatch (``ops.verify.dispatch_segments``),
        then hand the in-flight handle to the completion thread and
        return to draining — up to ``inflight_target()`` flushes ride
        the device concurrently, round-robined across healthy mesh
        lanes.  Identical front-half semantics to ``_execute_inner``;
        only WHERE the fetch happens moves."""
        n = len(items)
        pubs = [it.pub for it in items]
        msgs = [it.msg for it in items]
        sigs = [it.sig for it in items]
        interval = self._flush_interval()

        with self._flush_span(reason, items) as fsp:
            bits: "list[Optional[bool]]" = [None] * n
            uniq: "OrderedDict[bytes, list[int]]" = OrderedDict()
            for i in range(n):
                if len(pubs[i]) != 32 or len(sigs[i]) != 64:
                    bits[i] = False
                    continue
                k = sigcache._key(pubs[i], msgs[i], sigs[i])
                uniq.setdefault(k, []).append(i)
            firsts = [ixs[0] for ixs in uniq.values()]
            stats.record_dedup(sum(len(ixs) - 1 for ixs in uniq.values()))

            lanes = 0
            handle = None
            ordered: "list[int]" = []
            if firsts:
                from cometbft_tpu.ops import verify as ov

                by_class: "list[list[int]]" = [[] for _ in range(N_CLASSES)]
                for i in firsts:
                    by_class[items[i].prio].append(i)
                ordered = [i for cls in by_class for i in cls]
                work = [
                    (
                        [pubs[i] for i in cls],
                        [msgs[i] for i in cls],
                        [sigs[i] for i in cls],
                    )
                    for cls in by_class
                    if cls
                ]
                lanes = ov.bucket_size(len(ordered), ov._min_bucket())
                self._ensure_fetch_thread()
                cap = max(inflight_target(), 1)
                # reserve an in-flight slot BEFORE dispatching — the cap
                # bounds concurrent dispatches, and the wait re-checks the
                # completion thread so a dead one is restarted rather
                # than waited on forever
                with self._fcond:
                    if self._inflight >= cap:
                        with tracing.span(
                            "sched.slot_wait", inflight=self._inflight, cap=cap
                        ):
                            while self._inflight >= cap:
                                if (
                                    self._fetch_thread is None
                                    or not self._fetch_thread.is_alive()
                                ):
                                    break
                                self._fcond.wait(0.1)
                    self._inflight += 1
                    stats.record_inflight(self._inflight)
                self._ensure_fetch_thread()
                lane = None
                try:
                    from cometbft_tpu.parallel import elastic

                    # the probe-ADMITTING membership walk, not the
                    # read-only healthy list: a half-open chip re-earns
                    # its lane via the one-bucket probe here, exactly as
                    # it would under a mesh-wide dispatch.  Below 2 lanes
                    # the mesh rule says single-chip: lane=None falls
                    # into the pallas→xla→host chain, which keeps THOSE
                    # breakers probed and re-promoted too.
                    ords = elastic.admit_ordinals()
                    if len(ords) >= 2:
                        lane = ords[self._lane_rr % len(ords)]
                        self._lane_rr += 1
                except Exception:  # noqa: BLE001 — lane pinning is an
                    # optimization, never load-bearing
                    lane = None
                try:
                    with tracing.span(
                        "sched.dispatch", reason=reason, items=n,
                        lanes=lanes,
                    ):
                        handle = ov.dispatch_segments(work, lane=lane)
                except BaseException:
                    with self._fcond:
                        self._inflight -= 1
                        stats.record_inflight(self._inflight)
                        self._fcond.notify_all()
                    raise
            fsp.set(misses=len(firsts), lanes=lanes)

        stats.record_flush(
            reason, items=n, misses=len(firsts), lanes=lanes,
            interval_s=interval,
        )
        recorded[0] = True
        if handle is None:
            # nothing device-bound (all garbage/empty): resolve inline
            self._resolve(items, bits, fsp)
            return
        with self._fcond:
            # the flush span rides along: the completion thread's spans
            # are its children
            self._fetch_queue.append((handle, items, bits, uniq, ordered, fsp))
            self._fcond.notify_all()

    def _ensure_fetch_thread(self) -> None:
        """Start — or RESTART, mirroring the dispatcher's own restart
        path — the completion thread.  A dead completion thread with
        flushes still queued would strand their futures forever."""
        with self._fcond:
            if self._fetch_thread is None or not self._fetch_thread.is_alive():
                if self._fetch_thread is not None:
                    logger.error(
                        "verify completion thread died; restarting "
                        "(%d flushes in flight)",
                        len(self._fetch_queue),
                    )
                self._fetch_thread = threading.Thread(
                    target=self._fetch_run,
                    name="verify-sched-fetch",
                    daemon=True,
                )
                self._fetch_thread.start()

    def _fetch_run(self) -> None:
        while True:
            with self._fcond:
                while not self._fetch_queue and not self._fetch_stop:
                    self._fcond.wait()
                if not self._fetch_queue:
                    return  # stop requested and FIFO drained
                pf = self._fetch_queue.popleft()
            try:
                self._resolve_flush(pf)
            finally:
                with self._fcond:
                    self._inflight = max(0, self._inflight - 1)
                    stats.record_inflight(self._inflight)
                    self._fcond.notify_all()

    def _resolve_flush(self, pf: tuple) -> None:
        """The completion half of one pipelined flush: fetch verdicts,
        write the sigcache back, resolve every future.  Runs on the
        completion thread in drain order; cannot leave a future
        unresolved — a fetch that somehow escapes the supervisor's
        degradation chain resolves the flush on the host reference."""
        handle, items, bits, uniq, ordered, fsp = pf
        results = None
        try:
            from cometbft_tpu.ops import verify as ov

            with tracing.span("sched.fetch", parent=fsp, items=len(items)):
                results = ov.fetch_segments(handle)
        except BaseException:  # noqa: BLE001 — swallow even SystemExit:
            # the completion thread must outlive one bad flush or every
            # queued flush behind it strands its futures
            logger.exception("pipelined flush fetch failed unexpectedly")

        def settle() -> None:
            try:
                if results is not None:
                    self._settle(bits, uniq, ordered, results)
            except BaseException:  # noqa: BLE001 — as above
                logger.exception("pipelined flush resolve failed unexpectedly")
            if None not in bits:
                return
            logger.error(
                "resolving the flush's unanswered items on the host reference"
            )
            from cometbft_tpu.crypto import ed25519_ref as ref

            for i, it in enumerate(items):
                if it.future.done() or bits[i] is not None:
                    continue
                try:
                    bits[i] = len(it.pub) == 32 and len(
                        it.sig
                    ) == 64 and bool(
                        ref.verify_zip215(it.pub, it.msg, it.sig)
                    )
                except Exception:  # noqa: BLE001 — malformed input
                    bits[i] = False

        self._resolve(items, bits, fsp, settle)


# -- process-wide instance ----------------------------------------------------

_SCHED: Optional[VerifyScheduler] = None
_SCHED_LOCK = threading.Lock()


def get_scheduler() -> VerifyScheduler:
    """The process-wide scheduler (consensus, evidence, light and blocksync
    all share one — that sharing IS the optimization)."""
    global _SCHED
    if _SCHED is None:
        with _SCHED_LOCK:
            if _SCHED is None:
                _SCHED = VerifyScheduler()
    return _SCHED


def reset_scheduler() -> None:
    """Drain + drop the process-wide scheduler (tests/sim; also re-reads
    the flush/queue env knobs on next use)."""
    global _SCHED
    with _SCHED_LOCK:
        sched, _SCHED = _SCHED, None
    if sched is not None:
        sched.close()


# -- call-site wrappers -------------------------------------------------------


def _ed25519_pub(pub_key) -> Optional[bytes]:
    from cometbft_tpu.crypto import keys as ck

    if getattr(pub_key, "type_", None) != ck.ED25519_KEY_TYPE:
        return None
    return pub_key.bytes() if hasattr(pub_key, "bytes") else bytes(pub_key)


def verify_now(pub_key, msg: bytes, sig: bytes) -> bool:
    """Synchronous escape hatch: cache-through single verification with no
    queueing — exactly the pre-scheduler path."""
    return sigcache.verify_with_cache(pub_key, msg, sig)


def _shed_fallback_verify(pub_key, msg: bytes, sig: bytes, prio: int) -> bool:
    """The synchronous verify a SHED caller runs: emits a span and a
    submit->verdict histogram sample so shed work stays in the latency
    record instead of vanishing from it (docs/observability.md)."""
    t0 = time.perf_counter()
    with tracing.span(
        "sched.shed_fallback", cls=stats.CLASS_NAMES[_clamp_prio(prio)]
    ):
        ok = verify_now(pub_key, msg, sig)
    stats.record_shed_fallback(prio, time.perf_counter() - t0)
    return ok


def _clamp_prio(priority: int) -> int:
    return min(max(int(priority), 0), N_CLASSES - 1)


def verify_cached(pub_key, msg: bytes, sig: bytes, priority=None) -> bool:
    """THE drop-in for ``sigcache.verify_with_cache`` on scheduler-wired
    call sites (gossip-time ``Vote.verify``, proposal and vote-extension
    checks, evidence).  Scheduler inactive, non-ed25519 key, or shed by
    admission control -> the synchronous path, verdict-identical."""
    prio = current_priority() if priority is None else priority
    if scheduler_active():
        pub = _ed25519_pub(pub_key)
        if pub is not None:
            try:
                return bool(
                    get_scheduler().submit(pub, msg, sig, prio).result()
                )
            except (QueueFullError, RuntimeError):
                # shed, or scheduler torn down under us (reset race):
                # synchronous fallback — spanned + histogram-sampled
                return _shed_fallback_verify(pub_key, msg, sig, prio)
    return verify_now(pub_key, msg, sig)


def verify_many_cached(
    pub_keys, msgs: Sequence[bytes], sigs: Sequence[bytes], priority=None
) -> "list[bool]":
    """Several independent checks submitted before waiting on any, so they
    ride one flush (evidence checks both duplicate-vote signatures this
    way).  Falls back per item on shed / inactive / non-ed25519."""
    prio = current_priority() if priority is None else priority
    out: "list[Optional[bool]]" = [None] * len(msgs)
    futs: "list[Optional[Future]]" = [None] * len(msgs)
    shed_ix: set = set()
    if scheduler_active():
        sched = get_scheduler()
        for i, (pk, m, s) in enumerate(zip(pub_keys, msgs, sigs)):
            pub = _ed25519_pub(pk)
            if pub is None:
                continue
            try:
                futs[i] = sched.submit(pub, m, s, prio)
            except (QueueFullError, RuntimeError):
                futs[i] = None  # shed or torn down: sync fallback below
                shed_ix.add(i)
    for i, (pk, m, s) in enumerate(zip(pub_keys, msgs, sigs)):
        if futs[i] is not None:
            out[i] = bool(futs[i].result())
        elif i in shed_ix:
            out[i] = _shed_fallback_verify(pk, m, s, prio)
        else:
            out[i] = verify_now(pk, m, s)
    return out


def verify_segment_sync(
    pubs: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    priority=None,
) -> "list[bool]":
    """The batch-verifier bridge: submit a pre-partitioned segment of raw
    ed25519 triples (the caller — ``_CollectingVerifier`` — already took
    its cache hits) and wait for all verdicts.  Entries shed by admission
    control are verified in one direct supervised dispatch instead, so the
    call never blocks on queue capacity."""
    prio = current_priority() if priority is None else priority
    with tracing.span("sched.segment", items=len(pubs)) as seg:
        # submit and wait end while the dispatcher writes its own spans:
        # timed here, recorded together after the wait (``tracing.Lap``)
        with tracing.lap("sched.submit") as submitted:
            futs = get_scheduler().submit_many(
                pubs, msgs, sigs, prio, precleared=True
            )
        shed = [i for i, f in enumerate(futs) if f is None]
        direct: dict = {}
        if shed:
            from cometbft_tpu.ops import verify as ov

            t0 = time.perf_counter()
            with tracing.span(
                "sched.shed_fallback",
                cls=stats.CLASS_NAMES[_clamp_prio(prio)],
                items=len(shed),
            ):
                got = ov.verify_batch(
                    [pubs[i] for i in shed],
                    [msgs[i] for i in shed],
                    [sigs[i] for i in shed],
                )
            dt = time.perf_counter() - t0
            for _ in shed:
                # every shed item experienced the whole direct dispatch —
                # that IS its submit->verdict latency, kept in the record
                stats.record_shed_fallback(prio, dt)
            direct = {i: bool(b) for i, b in zip(shed, got)}
        with tracing.lap("sched.wait") as waited:
            out = [
                direct[i] if f is None else bool(f.result())
                for i, f in enumerate(futs)
            ]
        submitted.record(parent=seg, items=len(futs), shed=len(shed))
        waited.record(parent=seg, futures=len(futs) - len(shed))
    return out
