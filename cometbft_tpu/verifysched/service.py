"""Continuous-batching asynchronous verification service.

The device kernel only earns its keep when batches are full, yet every
caller on the verify hot path historically assembled its *own* batch and
blocked on its *own* dispatch: gossip-time ``Vote.verify`` paid a
one-signature dispatch (or the host fallback), evidence checks verified
per vote, and concurrent submitters each ate the per-dispatch floor.  This
module is the missing shared engine — the continuous-batching scheduler
shape from inference serving applied to signature verification
(docs/verify-scheduler.md):

  * the queue's unit is the SEGMENT: n >= 1 signatures from one
    submitter, one queue entry, one lock acquisition and one Future that
    resolves to its verdicts by index.  ``verify_segment_sync`` bridges a
    batch verifier's whole segment that way; ``submit(pub, msg, sig,
    priority)`` is the n = 1 case of the same entry;
  * one dispatcher thread coalesces pending entries ACROSS all submitters
    into a single ``ops/verify.dispatch_segments`` dispatch.  The flush
    rule is work-conserving: flush when the queued signatures fill a
    padding bucket (``full``); else when no flush of this scheduler is in
    flight (``idle``: there is nothing to wait behind, a lone caller never
    sleeps); else, behind a flush in flight, when the oldest entry has
    waited ``COMETBFT_TPU_SCHED_FLUSH_US`` (~2000, ``deadline``) or the
    last flush out lands, whichever is first.  It never waits for
    verdicts: one completion thread fetches them (``fetch_segments``) in
    drain order, so flush i+1 is packed while flush i is on the device;
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
default 8192 signatures); overload sheds ONLY non-consensus classes — a
segment that would pass the bound is admitted up to it, and the shed caller
falls back to its own synchronous verify for the rest (it loses the batching
win, never the verdict) — while consensus submissions are always admitted
whole: consensus traffic is bounded by validator count x rounds, and
blocking or dropping a vote is a liveness hazard no queue bound justifies.

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
from collections import deque
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


def inflight_target() -> int:
    """Bound K on concurrently dispatched flushes: explicit
    ``COMETBFT_TPU_SCHED_INFLIGHT`` wins; the default is the LIVE elastic
    mesh width (the bound follows shrinks/restores automatically —
    ``healthy_width`` is jax-free) with a floor of 2 on a single chip.

    What K buys depends on what a flush holds.  A flush the mesh takes
    (``elastic.takes``) is ONE launch over EVERY healthy chip, so K such
    flushes do not run side by side: they queue on the same chips in
    order, and K is the depth of that queue, which buys what the floor of
    2 buys on one chip: the host prepares flush i+1 while the devices
    compute flush i.  Only flushes too small for the mesh are pinned one
    a lane, and their work runs at fetch on the one completion thread
    (``elastic._LaneHandle``).  With one caller a request is one segment
    and one flush, so never more than 1 is in flight whatever K is."""
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


class _Entry:
    # THE unit of the queue: a segment of n >= 1 signatures from one
    # submitter, answered by ONE future — its verdicts by index, or the
    # bit itself for ``submit``'s n = 1 entry (``scalar``)
    # t0 = submit time, t_drain = when the dispatcher drained it out of
    # the queue: submit->drain is QUEUE WAIT, drain->verdict is DEVICE
    # time — recorded as separate histograms so queue pressure and device
    # slowness are distinguishable regressions (docs/observability.md)
    # ctx = the submitter's innermost open span (None outside any): the
    # flush that serves the entry joins its trace.  q0 / q1 = the same two
    # moments as t0 / t_drain on the TRACER's clock (virtual in sim): the
    # ends of the ``sched.queue`` span the dispatcher records
    # keys = the triples' sigcache keys as the submitter's look-up hashed
    # them, riding along so that the dedup hashes nothing; None until
    # ``_plan`` keys an entry that brought none (then with None at the
    # structurally impossible indices).  puts = the scheduler stores this
    # entry's verdicts: whoever hashed a key puts its verdict, once — the
    # scheduler for what it keyed itself (``submit``'s votes, an entry that
    # brought no keys), the batch seam's ``writeback`` for a segment whose
    # keys it sent along
    __slots__ = (
        "pubs", "msgs", "sigs", "keys", "puts", "n", "prio", "future", "t0",
        "t_drain", "ctx", "scalar", "q0", "q1",
    )

    def __init__(
        self, pubs, msgs, sigs, keys, puts, prio, t0, ctx, scalar, q0
    ):
        self.pubs = pubs
        self.msgs = msgs
        self.sigs = sigs
        self.keys = keys
        self.puts = puts
        self.n = len(pubs)
        self.prio = prio
        self.future: Future = Future()
        self.t0 = t0
        self.t_drain = t0
        self.ctx = ctx
        self.scalar = scalar
        self.q0 = self.q1 = q0


def _ref_bit(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """One verdict on the host reference: what every failure path
    answers with, so a future resolves with a definitive bit whatever
    broke above it."""
    from cometbft_tpu.crypto import ed25519_ref as ref

    try:
        return len(pub) == 32 and len(sig) == 64 and bool(
            ref.verify_zip215(pub, msg, sig)
        )
    except Exception:  # noqa: BLE001 — malformed input
        return False


class VerifyScheduler:
    """One dispatcher thread over three priority queues of segments.
    Thread-safe; lazily starts its thread on the first queued submission
    and drains everything (reason ``shutdown``) on ``close()`` — a future
    handed out is always eventually resolved.  ``_count``, ``queue_cap``,
    the ``full`` target and ``MAX_DRAIN`` count SIGNATURES, whatever the
    entries that carry them."""

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
        self._queues: "list[deque[_Entry]]" = [
            deque() for _ in range(N_CLASSES)
        ]
        self._count = 0
        self._thread: Optional[threading.Thread] = None
        self._stopped = False
        self._paused = False
        self._full_target: Optional[int] = None
        # flush-interval histo — stamped at DISPATCH SUBMISSION on the
        # dispatcher thread (monotonic there), never from entry drain
        # times: with K flushes in flight, drain-time deltas could go
        # negative or interleave
        self._last_flush_t: Optional[float] = None
        # in-flight pipeline state (docs/verify-scheduler.md "In-flight
        # pipeline"): FIFO of dispatched-but-unfetched flushes, resolved
        # in drain order by one completion thread; _fcond has its OWN
        # lock so a waiting dispatcher never blocks submitters
        self._flock = threading.Lock()
        self._fcond = threading.Condition(self._flock)
        self._fetch_queue: "deque[tuple]" = deque()
        self._inflight = 0
        self._fetch_thread: Optional[threading.Thread] = None
        self._fetch_stop = False
        self._lane_rr = 0  # round-robin of SMALL flushes over mesh ordinals

    # -- submission -------------------------------------------------------

    def _enqueue(
        self, pubs, msgs, sigs, prio: int, scalar: bool = False,
        keys=None, puts: bool = True,
    ):
        """Queue a segment under ONE acquisition of the lock, with one
        notify; returns ``(futures, admitted)``: the futures of the entries
        that hold ``pubs[:admitted]``, in order (one, unless the segment is
        longer than ``MAX_DRAIN`` and was cut; ``keys`` are cut with the
        lists).  ``scalar``: the lists are independent checks, not a
        segment, and each becomes an n = 1 entry whose future resolves to
        its bit (``submit``, ``submit_many``): queued together, they leave
        together.  Admission is decided once:
        consensus is always admitted whole; any other class up to
        ``queue_cap`` signatures queued, and the rest is shed to the
        caller.  Raises ``RuntimeError`` once the scheduler is stopped."""
        n = len(pubs)
        ctx = tracing.current()
        futs: "list[Future]" = []
        with self._cond:
            if self._stopped:
                raise RuntimeError("verify scheduler is stopped")
            admitted = n
            if prio != PRIO_CONSENSUS:
                admitted = min(n, max(self.queue_cap - self._count, 0))
            t0 = time.perf_counter()
            q0 = tracing.now()
            step = 1 if scalar else MAX_DRAIN
            for lo in range(0, admitted, step):
                hi = min(lo + step, admitted)
                entry = _Entry(
                    pubs[lo:hi], msgs[lo:hi], sigs[lo:hi],
                    None if keys is None else keys[lo:hi], puts,
                    prio, t0, ctx, scalar, q0,
                )
                self._queues[prio].append(entry)
                stats.record_submit(prio, hi - lo)
                futs.append(entry.future)
            self._count += admitted
            if admitted < n:
                stats.record_shed(prio, n - admitted)
            if admitted:
                if self._thread is None or not self._thread.is_alive():
                    # lazily started — and RESTARTED if it ever died (an
                    # exception escaping even the _execute fallback, e.g.
                    # MemoryError): without this, every queued future would
                    # hang forever and take consensus with it.  The new
                    # thread drains whatever the dead one left queued.
                    if self._thread is not None:
                        logger.error(
                            "verify dispatcher thread died; restarting "
                            "(%d signatures pending)",
                            self._count,
                        )
                    self._thread = threading.Thread(
                        target=self._run, name="verify-sched", daemon=True
                    )
                    self._thread.start()
                self._cond.notify_all()
        if admitted < n:
            # flight-recorder anomaly (the FIRST shed dumps the ring;
            # later sheds are counted), recorded AFTER the cond is
            # released: the dump's file IO must never block other
            # submitters — least of all shed-exempt consensus votes —
            # behind the scheduler lock
            tracing.record_anomaly(
                "queue_shed",
                cls=stats.CLASS_NAMES[prio],
                queue_cap=self.queue_cap,
                items=n - admitted,
            )
        return futs, admitted

    def submit(
        self,
        pub: bytes,
        msg: bytes,
        sig: bytes,
        priority: int = PRIO_CONSENSUS,
    ) -> "Future[bool]":
        """Queue one (pub, msg, sig) check — the n = 1 segment — and
        return a Future resolving to its definitive verdict.  A sigcache
        hit resolves immediately without occupying a queue slot.  Raises
        ``QueueFullError`` for non-consensus classes when the queue is at
        capacity; consensus submissions are always admitted.  The key is
        hashed once, here, for the look-up; it rides the entry to the dedup
        and to the put, which is the scheduler's (``_settle``)."""
        prio = _clamp_prio(priority)
        cache = sigcache.get_cache()
        keys = None
        if cache.enabled():
            keys = cache.hash_keys((pub,), (msg,), (sig,))
            hit = cache._get(keys[0])
            if hit is not None:
                stats.record_submit_hit(prio)
                tracing.mark(hit=True)
                fut: "Future[bool]" = Future()
                fut.set_result(bool(hit))
                return fut
        futs, admitted = self._enqueue(
            [pub], [msg], [sig], prio, scalar=True, keys=keys
        )
        if not admitted:
            raise QueueFullError(
                f"verify queue at capacity ({self.queue_cap}); "
                f"shedding class {stats.CLASS_NAMES[prio]}"
            )
        return futs[0]

    def submit_many(
        self,
        pubs: Sequence[bytes],
        msgs: Sequence[bytes],
        sigs: Sequence[bytes],
        priority: int = PRIO_CONSENSUS,
    ) -> "list[Optional[Future[bool]]]":
        """``submit`` for the independent checks ONE caller makes before it
        waits on any (both signatures of a duplicate-vote evidence, an
        envelope batch): each triple is the entry ``submit`` would queue —
        its own key, put and scalar future — but all of them are queued
        under one acquisition of the lock, so they leave in one flush by
        construction: an idle dispatcher flushes what it finds the moment
        it is woken, and must find all of them.  One future a triple, in
        order, a cache hit's already resolved; ``None`` where admission
        control shed the triple (the tail, for a sheddable class at the
        cap).  Raises ``RuntimeError`` once the scheduler is stopped."""
        prio = _clamp_prio(priority)
        out: "list[Optional[Future[bool]]]" = [None] * len(pubs)
        cache = sigcache.get_cache()
        keys = hits = None
        if cache.enabled():
            keys = cache.hash_keys(pubs, msgs, sigs)
            hits = cache._get_many(keys)
        queued = []
        for i in range(len(pubs)):
            if hits is None or hits[i] is None:
                queued.append(i)
                continue
            stats.record_submit_hit(prio)
            out[i] = Future()
            out[i].set_result(bool(hits[i]))
        if queued:
            futs, _ = self._enqueue(
                [pubs[i] for i in queued],
                [msgs[i] for i in queued],
                [sigs[i] for i in queued],
                prio, scalar=True,
                keys=None if keys is None else [keys[i] for i in queued],
            )
            for i, fut in zip(queued, futs):
                out[i] = fut
        return out

    def submit_segment(
        self,
        pubs: Sequence[bytes],
        msgs: Sequence[bytes],
        sigs: Sequence[bytes],
        priority: int = PRIO_CONSENSUS,
        keys: Optional[Sequence[bytes]] = None,
    ) -> "tuple[list[Future], int]":
        """Queue a whole segment of cache MISSES (the batch seam has
        already taken its hits) as one entry with one future, which
        resolves to the segment's verdicts by index.  Returns ``(futures,
        admitted)`` as ``_enqueue`` does: the signatures from ``admitted``
        on were shed by admission control and the caller verifies those
        itself.  A scheduler stopped under the caller (teardown race)
        admits nothing, so the whole segment degrades to the caller's
        fallback the same way.

        ``keys``: the triples' sigcache keys (``sigcache.partition_misses``
        hashed them for its look-up), aligned with the lists.  A segment
        that brings them is deduplicated on them and its verdicts are NOT
        stored here: the caller's ``sigcache.writeback`` puts them, under
        the same keys.  One that brings none is keyed, and stored, by the
        scheduler."""
        try:
            return self._enqueue(
                pubs, msgs, sigs, _clamp_prio(priority),
                keys=keys, puts=keys is None,
            )
        except RuntimeError:
            return [], 0

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
        """Signatures that fill the smallest padding bucket for the active
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

    def _drain(self) -> "list[_Entry]":
        """Whole entries, consensus first, up to ``MAX_DRAIN`` signatures
        and always at least one entry (none is longer: ``_enqueue`` cuts);
        an entry that does not fit stays at the head of its queue, and no
        lower class overtakes it."""
        out: "list[_Entry]" = []
        n = 0
        now = time.perf_counter()
        q1 = tracing.now()
        for q in self._queues:  # consensus first
            while q and (not out or n + q[0].n <= MAX_DRAIN):
                e = q.popleft()
                e.t_drain = now
                e.q1 = q1
                out.append(e)
                n += e.n
            if q:
                break
        self._count -= n
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
                        if self._inflight == 0:
                            # nothing of ours is on the device, so nothing
                            # can land that more company should be awaited
                            # behind: holding the queue would only idle the
                            # chip.  Read without ``_flock``: a stale 1
                            # costs nothing, ``_landed`` notifies this cond
                            # after it has written the 0
                            reason = "idle"
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
                entries = self._drain()
            if entries:
                self._execute(entries, reason)

    # -- flush ------------------------------------------------------------

    def _execute(self, entries: "list[_Entry]", reason: str) -> None:
        recorded = [False]
        try:
            # dispatch without blocking on the verdicts — enqueueing onto
            # the completion FIFO is the LAST step, so any exception
            # reaching the fallback below means these entries were never
            # handed off and the host reference resolve covers all of them
            self._dispatch_flush(entries, reason, recorded)
        except BaseException as e:  # noqa: BLE001 — futures must ALWAYS
            # resolve: these entries left the queue, so the submit-path
            # dispatcher restart can never recover them — an unresolved
            # future here is a permanent consensus hang in result()
            n = sum(en.n for en in entries)
            logger.exception(
                "verify flush failed unexpectedly; resolving %d signatures "
                "on the host reference",
                n,
            )
            # exactly-once flush accounting: if the flush failed
            # before recording, account the drained signatures here or
            # queue_depth stays inflated forever
            if not recorded[0]:
                stats.record_flush(reason, items=n, misses=0, lanes=0)
            bits: "list[Optional[bool]]" = [None] * n
            self._answer_on_reference(entries, bits)
            now = time.perf_counter()
            for en, part in self._slices(entries, bits):
                self._finish(en, part, now)
            if not isinstance(e, Exception):
                raise  # SystemExit etc.: die, but only AFTER resolving
                # (the next submit detects the dead thread and restarts)

    @staticmethod
    def _answer_on_reference(entries, bits) -> None:
        """Fill the bits still ``None`` (flat over the entries) from the
        host reference; an entry whose future is already resolved is
        skipped."""
        i = 0
        for en in entries:
            if en.future.done():
                i += en.n
                continue
            for p, m, s in zip(en.pubs, en.msgs, en.sigs):
                if bits[i] is None:
                    bits[i] = _ref_bit(p, m, s)
                i += 1

    @staticmethod
    def _slices(entries, bits):
        """Each entry with its own part of the flat ``bits``."""
        lo = 0
        for en in entries:
            yield en, bits[lo:lo + en.n]
            lo += en.n

    @staticmethod
    def _queue_span(entries: "list[_Entry]", fsp) -> None:
        """``sched.queue``: the hand-off from the callers to the dispatcher,
        from the oldest entry's enqueue to the drain, as a child of the
        flush it ends at (a causal link: it lies BEFORE its parent)."""
        if tracing.enabled():
            tracing.get_tracer().record_span(
                "sched.queue",
                min(en.q0 for en in entries),
                entries[0].q1,
                parent=fsp,
            )

    @staticmethod
    def _key_entry(en: "_Entry") -> None:
        """Key an entry that brought no keys (the cache switched off at the
        seam, or a caller that made no look-up): the dedup needs them
        whatever the cache does.  The structurally impossible get None."""
        possible = [
            i
            for i in range(en.n)
            if len(en.pubs[i]) == 32 and len(en.sigs[i]) == 64
        ]
        hashed = sigcache.get_cache().hash_keys(
            [en.pubs[i] for i in possible],
            [en.msgs[i] for i in possible],
            [en.sigs[i] for i in possible],
        )
        en.keys = [None] * en.n
        for i, k in zip(possible, hashed):
            en.keys[i] = k

    @classmethod
    def _plan(cls, entries: "list[_Entry]"):
        """The front half of a flush, over the entries' lists laid end to
        end.  Structural filter: garbage never occupies a
        device lane.  In-flight dedup ACROSS the entries, on the keys they
        carry (an entry that brought none is keyed here): concurrent
        gossip of the same vote collapses into one lane, every index that
        holds it shares the verdict.  One work segment per priority class
        present: ``dispatch_segments`` fuses them into ONE dispatch
        (recording cross-class fusion in ops/dispatch_stats) and splits
        the bits back per class.  Returns ``(bits, dups, ordered, work,
        lanes)``: ``bits`` flat with the filtered indices already False,
        ``dups`` the ``(index, first index)`` of every index beyond its
        dedup group's first, ``ordered`` the first index of each group in
        ``work``'s order."""
        pubs: "list[bytes]" = []
        msgs: "list[bytes]" = []
        sigs: "list[bytes]" = []
        keys: "list[Optional[bytes]]" = []
        prios: "list[int]" = []
        for en in entries:
            if en.keys is None:
                cls._key_entry(en)
            pubs.extend(en.pubs)
            msgs.extend(en.msgs)
            sigs.extend(en.sigs)
            keys.extend(en.keys)
            prios.extend([en.prio] * en.n)
        n = len(pubs)
        bits: "list[Optional[bool]]" = [None] * n
        first: "dict[bytes, int]" = {}  # insertion-ordered, as the lanes are
        dups: "list[tuple[int, int]]" = []
        for i, k in enumerate(keys):
            if k is None or len(pubs[i]) != 32 or len(sigs[i]) != 64:
                bits[i] = False
                continue
            j = first.setdefault(k, i)
            if j != i:
                dups.append((i, j))
        stats.record_dedup(len(dups))
        ordered: "list[int]" = []
        work: "list[tuple]" = []
        lanes = 0
        if first:
            from cometbft_tpu.ops import verify as ov

            by_class: "list[list[int]]" = [[] for _ in range(N_CLASSES)]
            for i in first.values():
                by_class[prios[i]].append(i)
            ordered = [i for ixs in by_class for i in ixs]
            if any(len(ixs) == n for ixs in by_class):
                # every index its group's first, all of one class (a fresh
                # commit's segment alone in its flush): the lists as they are
                work = [(pubs, msgs, sigs)]
            else:
                work = [
                    (
                        [pubs[i] for i in ixs],
                        [msgs[i] for i in ixs],
                        [sigs[i] for i in ixs],
                    )
                    for ixs in by_class
                    if ixs
                ]
            lanes = ov.bucket_size(len(ordered), ov._min_bucket())
        return bits, dups, ordered, work, lanes

    @staticmethod
    def _finish(en: "_Entry", bits, now: float) -> None:
        """Resolve one entry: n signatures' worth of statistics in one
        weighted observation, then its one ``set_result`` — the waiter
        wakes to bookkeeping already done.  The stamp left on the future is
        on the tracer's clock: from it to the waiter back from ``result()``
        is the hand-off ``sched.handoff.wake`` (``_record_wait``)."""
        if en.future.done():
            return
        # where ``sched.handoff.wake`` begins: the waiter records it
        en.future.t_set = tracing.now()
        stats.record_verdict(
            en.prio,
            now - en.t0,
            len(bits),
            queue_wait_s=en.t_drain - en.t0,
            device_s=now - en.t_drain,
        )
        en.future.set_result(bits[0] if en.scalar else bits)

    def _resolve(self, entries, bits, fsp, settle=None) -> None:
        """``sched.resolve``: ``settle()`` (the verdict map and the cache
        puts of a fetched flush), then each entry's slice of ``bits`` to
        its future.  The span lands BEFORE the last future resolves, so a
        deterministic sim's ring order cannot race its waiter; that one
        ``_finish`` is the part of the stage the span does not hold."""
        with tracing.span("sched.resolve", parent=fsp, items=len(bits)):
            if settle is not None:
                settle()
            now = time.perf_counter()
            *head, last = self._slices(entries, bits)
            for en, part in head:
                self._finish(en, part, now)
        self._finish(*last, now)

    @staticmethod
    def _settle(entries, bits, dups, ordered, results) -> None:
        """The verdicts, in ``ordered``'s order, to the first index of each
        dedup group and from there to the group's other members; then the
        cache puts that are the scheduler's to make — one for each index of
        an entry it keyed itself (``_Entry.puts``: ``submit``'s single
        votes), under the key that entry carries, in one visit.  A segment
        that came with its keys is put by the batch seam's ``writeback``,
        on the caller's path, not here.  Supervised verdicts are always
        definitive, so caching unconditionally is safe."""
        for i, b in zip(ordered, (b for seg in results for b in seg)):
            bits[i] = bool(b)
        for i, j in dups:
            bits[i] = bits[j]
        cache = sigcache.get_cache()
        if not cache.enabled():
            return
        put_keys: "list[bytes]" = []
        put_bits: "list[bool]" = []
        lo = 0
        for en in entries:
            if en.puts:
                for k, v in zip(en.keys, bits[lo:lo + en.n]):
                    if k is not None:
                        put_keys.append(k)
                        put_bits.append(v)
            lo += en.n
        if put_keys:
            cache._put_many(put_keys, put_bits)

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
        self, entries: "list[_Entry]", reason: str, recorded: "list[bool]"
    ) -> None:
        """The front half of a flush: ``_plan`` (filter, dedup, per-class
        work) + ONE fused dispatch (``ops.verify.dispatch_segments``),
        then hand the in-flight handle to the completion thread and
        return to draining — up to ``inflight_target()`` flushes ride
        the device concurrently.  On a mesh, a flush the mesh takes is
        not pinned: the supervisor launches it over every healthy chip;
        only a smaller one is pinned at one lane, round-robin."""
        n = sum(en.n for en in entries)
        interval = self._flush_interval()

        # flush span (closed BEFORE futures resolve, like the stats below,
        # so a deterministic sim's ring order cannot race its waiters), a
        # child of the first entry's submitter: the flush is in THAT
        # request's tree (another request it serves finds it by the time
        # its ``sched.wait`` spans)
        with tracing.span(
            "sched.flush", parent=entries[0].ctx, reason=reason, items=n,
            segments=len(entries),
        ) as fsp:
            self._queue_span(entries, fsp)
            bits, dups, ordered, work, lanes = self._plan(entries)
            handle = None
            if work:
                from cometbft_tpu.ops import verify as ov

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

                    # a flush the mesh takes holds EVERY healthy chip:
                    # the supervisor launches it mesh-wide and walks the
                    # membership itself, so nothing is pinned.  A smaller
                    # one is pinned, by the probe-ADMITTING membership
                    # walk, not the read-only healthy list: a half-open
                    # chip re-earns its lane via the one-bucket probe
                    # here, exactly as it would under a mesh-wide
                    # dispatch.  Below 2 lanes the mesh rule says
                    # single-chip: lane=None falls into the
                    # pallas→xla→host chain, which keeps THOSE breakers
                    # probed and re-promoted too.
                    if not elastic.takes(len(ordered)):
                        ords = elastic.admit_ordinals()
                        if len(ords) >= 2:
                            lane = ords[self._lane_rr % len(ords)]
                            self._lane_rr += 1
                except Exception:  # noqa: BLE001 — lane pinning is an
                    # optimization, never load-bearing
                    lane = None
                try:
                    with tracing.span("sched.dispatch"):
                        handle = ov.dispatch_segments(work, lane=lane)
                except BaseException:
                    self._landed()
                    raise
            fsp.set(misses=len(ordered), lanes=lanes)

        # record BEFORE resolving: set_result unblocks waiters, and a
        # caller reading stats right after its verdict (the sim's
        # end-of-run capture asserts queue_depth == 0) must not race the
        # dispatcher's bookkeeping; ``recorded`` keeps the _execute
        # fallback from double-counting if a resolve below raises
        stats.record_flush(
            reason, items=n, misses=len(ordered), lanes=lanes,
            interval_s=interval,
        )
        recorded[0] = True
        if handle is None:
            # nothing device-bound (all garbage/empty): resolve inline
            self._resolve(entries, bits, fsp)
            return
        with self._fcond:
            # the flush span rides along (the completion thread's spans
            # are its children), and the stamp at which
            # ``sched.handoff.fetch`` begins
            self._fetch_queue.append(
                (handle, entries, bits, dups, ordered, fsp, tracing.now())
            )
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
            self._resolve_flush(pf)

    def _landed(self) -> None:
        """One flush is off the device: free its slot and, when it was the
        last one out, wake the dispatcher for what queued behind it (reason
        ``idle``).  Called BEFORE the flush's futures resolve, so a caller
        that is answered and submits again finds the count already down.
        The two locks are taken one after the other, never nested, and
        ``_lock`` only for the notify: no submitter waits behind
        ``_fcond``."""
        with self._fcond:
            self._inflight = left = max(0, self._inflight - 1)
            stats.record_inflight(left)
            self._fcond.notify_all()
        if left == 0:
            with self._cond:
                if self._count:
                    self._cond.notify_all()

    def _resolve_flush(self, pf: tuple) -> None:
        """The completion half of one flush: take it up (the hand-off
        ``sched.handoff.fetch`` ends here), fetch verdicts, give the slot
        back (``_landed``, under ``sched.landed``), settle them
        (``_settle``), resolve every future.  Runs on the completion thread
        in drain order; cannot leave a future unresolved — a fetch that
        somehow escapes the supervisor's degradation chain resolves the
        flush on the host reference."""
        handle, entries, bits, dups, ordered, fsp, handed = pf
        tracing.handoff("sched.handoff.fetch", handed, parent=fsp)
        results = None
        try:
            from cometbft_tpu.ops import verify as ov

            with tracing.span("sched.fetch", parent=fsp, items=len(bits)):
                results = ov.fetch_segments(handle)
        except BaseException:  # noqa: BLE001 — swallow even SystemExit:
            # the completion thread must outlive one bad flush or every
            # queued flush behind it strands its futures
            logger.exception("pipelined flush fetch failed unexpectedly")
        finally:
            with tracing.span("sched.landed", parent=fsp):
                self._landed()

        def settle() -> None:
            try:
                if results is not None:
                    self._settle(entries, bits, dups, ordered, results)
            except BaseException:  # noqa: BLE001 — as above
                logger.exception("pipelined flush resolve failed unexpectedly")
            if None not in bits:
                return
            logger.error(
                "resolving the flush's unanswered signatures on the host "
                "reference"
            )
            self._answer_on_reference(entries, bits)

        self._resolve(entries, bits, fsp, settle)


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


def _record_wait(waited: "tracing.Lap", parent, futs) -> None:
    """The caller's ``sched.wait`` lap and, inside it, the last hand-off of
    a request: ``sched.handoff.wake``, from the stamp the completion thread
    left on the (last) future before resolving it (``_finish``: the
    statistics, ``set_result`` and the wake-up lie after it) to the caller
    back from ``result()``.  A future answered before the wait began, or
    from the cache, has no hand-off to show."""
    wsp = waited.record(parent=parent, futures=len(futs))
    t_set = getattr(futs[-1], "t_set", None) if futs else None
    if wsp is not None and t_set is not None and t_set >= waited.t0:
        tracing.get_tracer().record_span(
            "sched.handoff.wake", t_set, waited.t1, parent=wsp
        )


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
                # timed here, recorded together after the wait, as in
                # ``verify_segment_sync``; the parent is the caller's open
                # span (``consensus.vote``)
                with tracing.lap("sched.submit") as submitted:
                    fut = get_scheduler().submit(pub, msg, sig, prio)
                with tracing.lap("sched.wait") as waited:
                    ok = bool(fut.result())
                parent = tracing.current()
                submitted.record(parent=parent, items=1, shed=0)
                _record_wait(waited, parent, [fut])
                return ok
            except (QueueFullError, RuntimeError):
                # shed, or scheduler torn down under us (reset race):
                # synchronous fallback — spanned + histogram-sampled
                return _shed_fallback_verify(pub_key, msg, sig, prio)
    return verify_now(pub_key, msg, sig)


def verify_many_cached(
    pub_keys, msgs: Sequence[bytes], sigs: Sequence[bytes], priority=None
) -> "list[bool]":
    """Several independent checks queued in ONE hand-off (``submit_many``)
    before waiting on any, so they ride one flush (evidence checks both
    duplicate-vote signatures this way).  Falls back per item on shed /
    inactive / non-ed25519."""
    prio = current_priority() if priority is None else priority
    out: "list[Optional[bool]]" = [None] * len(msgs)
    futs: "list[Optional[Future]]" = [None] * len(msgs)
    shed_ix: set = set()
    if scheduler_active():
        pubs = [_ed25519_pub(pk) for pk in pub_keys]
        ed = [i for i, pub in enumerate(pubs) if pub is not None]
        try:
            got = get_scheduler().submit_many(
                [pubs[i] for i in ed],
                [msgs[i] for i in ed],
                [sigs[i] for i in ed],
                prio,
            )
        except RuntimeError:  # torn down under us: sync fallback below
            got = [None] * len(ed)
        for i, fut in zip(ed, got):
            futs[i] = fut
            if fut is None:
                shed_ix.add(i)
    for i, (pk, m, s) in enumerate(zip(pub_keys, msgs, sigs)):
        if futs[i] is not None:
            out[i] = bool(futs[i].result())
        elif i in shed_ix:
            out[i] = _shed_fallback_verify(pk, m, s, prio)
        else:
            out[i] = verify_now(pk, m, s)
    return out


class PendingSegment:
    """A segment queued by ``submit_segment_async`` and not yet waited on:
    its ``sched.segment`` span (open, on no thread's stack), the submit's
    lap, the futures, and the shed tail's verdicts."""

    __slots__ = ("seg", "submitted", "futs", "direct", "n", "shed")

    def __init__(self, seg, submitted, futs, direct, n: int, shed: int):
        self.seg = seg
        self.submitted = submitted
        self.futs = futs
        self.direct = direct
        self.n = n
        self.shed = shed

    def done(self) -> bool:
        """Every verdict has landed: ``wait_segment`` will not block."""
        return all(f.done() for f in self.futs)


def submit_segment_async(
    pubs: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    priority=None,
    keys: Optional[Sequence[bytes]] = None,
) -> PendingSegment:
    """The first half of ``verify_segment_sync``: queue a pre-partitioned
    segment as ONE entry and return without waiting, so that the caller
    can do other work while it flies (the sequential light client prepares
    its next header).  The tail that admission control shed is verified
    here, in one direct supervised dispatch, so the call never blocks on
    queue capacity.  ``wait_segment`` takes the segment back; the
    ``sched.segment`` span runs from here to the end of that wait."""
    prio = current_priority() if priority is None else priority
    n = len(pubs)
    tracer = tracing.get_tracer()
    seg = tracer.start("sched.segment", items=n)
    with tracer.under(seg):
        # submit and wait end while the dispatcher writes its own spans:
        # timed here, recorded together after the wait (``tracing.Lap``)
        with tracing.lap("sched.submit") as submitted:
            futs, admitted = get_scheduler().submit_segment(
                pubs, msgs, sigs, prio, keys
            )
        direct: "list[bool]" = []
        if admitted < n:
            from cometbft_tpu.ops import verify as ov

            t0 = time.perf_counter()
            with tracing.span(
                "sched.shed_fallback",
                cls=stats.CLASS_NAMES[_clamp_prio(prio)],
                items=n - admitted,
            ):
                got = ov.verify_batch(
                    pubs[admitted:], msgs[admitted:], sigs[admitted:]
                )
            # every shed signature experienced the whole direct dispatch —
            # that IS its submit->verdict latency, kept in the record
            stats.record_shed_fallback(
                prio, time.perf_counter() - t0, n - admitted
            )
            direct = [bool(b) for b in got]
    return PendingSegment(seg, submitted, futs, direct, n, n - admitted)


def wait_segment(pending: PendingSegment) -> "list[bool]":
    """The second half: wait on the segment's futures and return its
    verdicts by index, the shed tail's last."""
    out: "list[bool]" = []
    with tracing.lap("sched.wait") as waited:
        for f in pending.futs:
            out.extend(f.result())
    out.extend(pending.direct)
    pending.submitted.record(
        parent=pending.seg, items=pending.n, shed=pending.shed
    )
    _record_wait(waited, pending.seg, pending.futs)
    tracing.get_tracer().finish(pending.seg)
    return out


def verify_segment_sync(
    pubs: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    priority=None,
    keys: Optional[Sequence[bytes]] = None,
) -> "list[bool]":
    """The batch-verifier bridge: submit a pre-partitioned segment of raw
    ed25519 triples (the caller — ``_CollectingVerifier`` — already took
    its cache hits) as ONE queue entry and wait on its one future.  ``keys``
    are the triples' sigcache keys where the caller's look-up hashed them
    (``submit_segment``): the caller then stores every verdict this
    returns, the shed tail's too, and the scheduler none."""
    return wait_segment(submit_segment_async(pubs, msgs, sigs, priority, keys))
