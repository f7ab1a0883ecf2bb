"""Continuous-batching async verification service (ISSUE 5,
cometbft_tpu/verifysched/ — docs/verify-scheduler.md).

Everything here runs on the supervisor's host-oracle device-runner seam
(the same one the sim uses): a real XLA-CPU dispatch costs ~1.7 s on the
throttled CI host, while every scheduler mechanism under test — queueing,
coalescing, dedup, admission control, priority classes, supervisor
integration, cache writeback — sits ABOVE that seam and runs unchanged.
One smoke test exercises a single real dispatch through the full stack.
"""

import functools
import contextlib
import hashlib
import sys
import threading
import time

import numpy as np
import pytest

from cometbft_tpu import verifysched
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.crypto.keys import Ed25519PrivKey, Ed25519PubKey
from cometbft_tpu.ops import dispatch_stats, supervisor
from cometbft_tpu.verifysched import stats as sstats
from cometbft_tpu.verifysched.service import VerifyScheduler


def _oracle_runner(backend, pubs, msgs, sigs, lanes):
    out = np.zeros(lanes, dtype=bool)
    out[: len(pubs)] = [
        ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)
    ]
    return out


@pytest.fixture
def sched_env(monkeypatch):
    """Scheduler-active environment: trusted tpu backend + host-oracle
    device runner; fresh scheduler/stats/caches; full teardown."""
    from cometbft_tpu.crypto import backend_health

    monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "tpu")
    monkeypatch.delenv("COMETBFT_TPU_VERIFY_SCHED", raising=False)
    supervisor.set_device_runner(_oracle_runner)
    sigcache.reset_cache()
    sstats.reset()
    dispatch_stats.reset()
    backend_health.reset()
    verifysched.reset_scheduler()
    yield
    verifysched.reset_scheduler()
    supervisor.clear_device_runner()
    supervisor.clear_fault_injector()
    backend_health.reset()
    sigcache.reset_cache()
    sstats.reset()


def _make_sigs(n, tag=b"vs", invalid_every=None):
    """n (pub, msg, sig) triples; every ``invalid_every``-th tampered."""
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = hashlib.sha256(b"%s-%d" % (tag, i)).digest()
        msg = b"%s-msg-%d" % (tag, i)
        sig = ref.sign(seed, msg)
        if invalid_every and i % invalid_every == 0:
            sig = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        pubs.append(ref.pubkey_from_seed(seed))
        msgs.append(msg)
        sigs.append(sig)
    return pubs, msgs, sigs


def _segment(sched, pubs, msgs, sigs, priority=verifysched.PRIO_CONSENSUS):
    """Queue one segment, admitted whole; its futures (one, unless the
    segment is longer than ``MAX_DRAIN``)."""
    futs, admitted = sched.submit_segment(pubs, msgs, sigs, priority)
    assert admitted == len(pubs)
    return futs


def _verdicts(futs, timeout=30):
    """The segments' verdicts by index, end to end."""
    return [b for f in futs for b in f.result(timeout=timeout)]


def _oracle(pubs, msgs, sigs):
    return [
        len(p) == 32
        and len(s) == 64
        and bool(ref.verify_zip215(p, m, s))
        for p, m, s in zip(pubs, msgs, sigs)
    ]


class _HeldDevice:
    """The device stand-in held on an ``Event``: what is dispatched stays
    in flight until ``release()``.  ``hold(sched)`` puts one flush (a lone
    vote, reason ``idle``) out and returns once it is in flight."""

    def __init__(self, runner=_oracle_runner):
        self.runner = runner
        self.gate = threading.Event()
        self.entered = threading.Event()
        supervisor.set_device_runner(self)

    def __call__(self, backend, pubs, msgs, sigs, lanes):
        self.entered.set()
        self.gate.wait(30)
        return self.runner(backend, pubs, msgs, sigs, lanes)

    def hold(self, sched):
        (pub,), (msg,), (sig,) = _make_sigs(1, b"held")
        fut = sched.submit(pub, msg, sig)
        assert self.entered.wait(30)
        assert sstats.snapshot()["inflight_depth"] == 1
        return fut

    def release(self):
        self.gate.set()


@contextlib.contextmanager
def _switching_every(seconds):
    """The interpreter hands the GIL on every ``seconds``: a thread that
    is woken runs at once, so a race a test is about is really run."""
    interval = sys.getswitchinterval()
    sys.setswitchinterval(seconds)
    try:
        yield
    finally:
        sys.setswitchinterval(interval)


def _flushes(**counts):
    """``stats.snapshot()["flushes"]`` with every reason not named at 0."""
    return {r: counts.get(r, 0) for r in sstats.FLUSH_REASONS}


# ----------------------------------------------------------------------
# core scheduler mechanics
# ----------------------------------------------------------------------


class TestSchedulerCore:
    def test_differential_random_mix(self, sched_env):
        """Scheduler verdicts bitwise-equal to the synchronous host path on
        a randomized valid/invalid mix including structural garbage."""
        pubs, msgs, sigs = _make_sigs(48, b"mix", invalid_every=3)
        # structural garbage: wrong pub/sig lengths must resolve False
        # without occupying a lane
        pubs[5], sigs[11] = b"\x01" * 31, b"\x02" * 63
        sched = verifysched.get_scheduler()
        sched.pause()
        futs = _segment(sched, pubs, msgs, sigs)
        sched.resume()
        assert _verdicts(futs) == _oracle(pubs, msgs, sigs)

    def test_concurrent_submitters_coalesce_fewer_dispatches(self, sched_env):
        """THE acceptance property: under 8 concurrent submitters the
        dispatch count per signature drops vs per-caller dispatch."""
        n_threads, per = 8, 16
        batches = [
            _make_sigs(per, b"thr-%d" % t, invalid_every=5)
            for t in range(n_threads)
        ]
        prios = [t % 3 for t in range(n_threads)]  # mixed priority classes

        # per-caller sync baseline: every submitter pays its own dispatch
        before = dispatch_stats.dispatch_count()
        from cometbft_tpu.ops import verify as ov

        want = [ov.verify_batch(*b).tolist() for b in batches]
        sync_dispatches = dispatch_stats.dispatch_count() - before
        assert sync_dispatches == n_threads

        sigcache.reset_cache()  # the baseline must not seed the scheduler run
        sched = verifysched.get_scheduler()
        sched.pause()  # deterministic coalescing: all 8 queue before a flush
        results = [None] * n_threads
        barrier = threading.Barrier(n_threads)

        def submitter(t):
            barrier.wait()
            futs = _segment(sched, *batches[t], priority=prios[t])
            results[t] = _verdicts(futs)

        threads = [
            threading.Thread(target=submitter, args=(t,))
            for t in range(n_threads)
        ]
        before = dispatch_stats.dispatch_count()
        for th in threads:
            th.start()
        while sched.pending() < n_threads * per:
            threading.Event().wait(0.002)  # poll without starving the GIL
        sched.resume()
        for th in threads:
            th.join(timeout=60)
        sched_dispatches = dispatch_stats.dispatch_count() - before

        assert results == want  # bitwise-equal to per-caller sync
        assert sched_dispatches < sync_dispatches, (
            sched_dispatches,
            sync_dispatches,
        )
        assert sched_dispatches <= 2  # 128 items: one fused dispatch (+margin)
        snap = sstats.snapshot()
        assert snap["flushes"]["full"] >= 1  # 128 >= the 32-lane bucket
        assert snap["verdicts_total"] == n_threads * per

    def test_in_flight_dedup_one_lane(self, sched_env):
        """The same triple submitted concurrently by several peers occupies
        ONE device lane; every future gets the shared verdict."""
        pubs, msgs, sigs = _make_sigs(1, b"dup")
        sched = verifysched.get_scheduler()
        sched.pause()
        futs = [sched.submit(pubs[0], msgs[0], sigs[0]) for _ in range(5)]
        sched.resume()
        assert all(f.result(timeout=30) is True for f in futs)
        snap = sstats.snapshot()
        assert snap["dedup_hits"] == 4
        assert snap["flush_misses"] == 1

    def test_submit_hit_resolves_without_queueing(self, sched_env):
        pubs, msgs, sigs = _make_sigs(1, b"hit")
        sigcache.get_cache().put(pubs[0], msgs[0], sigs[0], True)
        sched = verifysched.get_scheduler()
        sched.pause()  # a queued item could not resolve while paused
        fut = sched.submit(pubs[0], msgs[0], sigs[0])
        assert fut.done() and fut.result() is True
        assert sched.pending() == 0
        assert sstats.snapshot()["submit_hits"]["consensus"] == 1
        sched.resume()

    def test_handoff_stamps_ride_the_entry_and_the_future(self, sched_env):
        """ISSUE 36: the two ends of ``sched.queue`` are on the entries (the
        oldest entry's enqueue, the drain), the start of
        ``sched.handoff.wake`` on the future; a future answered from the
        cache was never handed off and has no stamp.  One ``sched.queue``
        a flush, however many entries it drained."""
        from cometbft_tpu.libs import tracing

        tracing.reset_tracer()
        tr = tracing.get_tracer()
        pubs, msgs, sigs = _make_sigs(3, b"stamp")
        sigcache.get_cache().put(pubs[2], msgs[2], sigs[2], True)
        sched = verifysched.get_scheduler()
        sched.pause()
        t_before = tr.time()
        first = sched.submit(pubs[0], msgs[0], sigs[0])
        time.sleep(0.02)
        second = sched.submit(pubs[1], msgs[1], sigs[1])
        hit = sched.submit(pubs[2], msgs[2], sigs[2])
        t_queued = tr.time()
        time.sleep(0.02)
        sched.resume()
        assert first.result(timeout=30) and second.result(timeout=30)
        assert hit.result() is True and not hasattr(hit, "t_set")
        by = {}
        for sp in tr.tail(100):
            by.setdefault(sp["stage"], []).append(sp)
        (queue,), (flush,) = by["sched.queue"], by["sched.flush"]
        assert flush["attrs"]["segments"] == 2
        assert queue["parent"] == flush["span"]
        # from the OLDEST entry's enqueue to the drain, which the pause held
        assert t_before <= queue["t0"] <= t_queued - 0.02
        assert queue["t1"] >= t_queued + 0.02 and queue["t1"] <= flush["t0"]
        assert queue["dur_ms"] >= 40.0
        resolve_end = by["sched.resolve"][0]["t1"]
        # the first entry is finished inside ``sched.resolve``, the last
        # one after it; both stamps are the tracer's clock
        assert flush["t1"] <= first.t_set <= resolve_end <= second.t_set
        assert second.t_set <= tr.time()
        tracing.reset_tracer()

    def test_flush_reasons_full_and_deadline(self, sched_env):
        # full: a long deadline that cannot be the trigger; the 32-lane
        # padding bucket fills first
        sched = VerifyScheduler(flush_us=5_000_000)
        try:
            pubs, msgs, sigs = _make_sigs(32, b"full")
            futs = _segment(sched, pubs, msgs, sigs)
            assert _verdicts(futs) == [True] * 32
            assert sstats.snapshot()["flushes"]["full"] >= 1
        finally:
            sched.close()
        # deadline: what decides behind a flush in flight (an idle
        # scheduler holds nothing): the held vote left ``idle``, the one
        # queued behind it leaves after its millisecond, into the free slot
        sstats.reset()
        dev = _HeldDevice()
        sched = VerifyScheduler(flush_us=1000)
        try:
            held = dev.hold(sched)
            pubs, msgs, sigs = _make_sigs(1, b"dl")
            fut = sched.submit(pubs[0], msgs[0], sigs[0])
            deadline = time.perf_counter() + 30
            while sstats.snapshot()["inflight_depth"] < 2:
                assert time.perf_counter() < deadline
                threading.Event().wait(0.002)
            assert sstats.snapshot()["flushes"] == _flushes(idle=1, deadline=1)
            dev.release()
            assert held.result(30) is True and fut.result(30) is True
        finally:
            dev.release()
            sched.close()

    @pytest.mark.parametrize("n", [1, 6], ids=["vote", "segment"])
    def test_dispatcher_restarts_after_death(self, sched_env, n):
        """A dispatcher killed by an escaping BaseException must not turn
        the scheduler into a future-black-hole: the drained entry resolves
        on the host fallback BEFORE the thread dies (a vote's bit, a
        segment's verdicts by index), and the next submit detects the dead
        thread and restarts it."""
        sched = VerifyScheduler(flush_us=500)

        def ask(pubs, msgs, sigs):
            if n == 1:
                return [sched.submit(pubs[0], msgs[0], sigs[0]).result(30)]
            return _verdicts(_segment(sched, pubs, msgs, sigs))

        try:
            pubs, msgs, sigs = _make_sigs(n, b"dead", invalid_every=5)
            orig_disp = sched._dispatch_flush

            def dying(entries, reason, recorded):
                raise SystemExit  # BaseException: kills the thread

            sched._dispatch_flush = dying
            # already-drained future still resolves (host fallback)...
            assert ask(pubs, msgs, sigs) == _oracle(pubs, msgs, sigs)
            t = sched._thread
            t.join(10)
            assert not t.is_alive()  # ...and THEN the thread died
            sched._dispatch_flush = orig_disp
            assert ask(*_make_sigs(n, b"alive")) == [True] * n
            assert sched._thread is not t  # a fresh dispatcher took over
            assert sstats.snapshot()["queue_depth"] == 0
        finally:
            sched.close()

    def test_close_drains_with_shutdown_reason(self, sched_env):
        sched = VerifyScheduler(flush_us=10_000_000)
        pubs, msgs, sigs = _make_sigs(3, b"shut")
        sched.pause()
        futs = _segment(sched, pubs, msgs, sigs)
        sched.close()  # overrides pause; every future must resolve
        assert _verdicts(futs) == [True] * 3
        assert sstats.snapshot()["flushes"]["shutdown"] >= 1
        with pytest.raises(RuntimeError):
            sched.submit(pubs[0], msgs[0], b"\x00" * 64)


# ----------------------------------------------------------------------
# the flush rule is work-conserving: hold the queue only behind a flush in
# flight (docs/verify-scheduler.md "The flush rule")
# ----------------------------------------------------------------------

LONG_US = 5_000_000  # a deadline no test waits out: its waits give up at 4 s


class TestIdleFlush:
    @pytest.mark.parametrize("n", [1, 117], ids=["vote", "commit-117"])
    def test_lone_caller_never_waits_out_the_deadline(self, sched_env, n):
        """Nothing in flight, one caller blocked on its own entry: nobody
        can join, so the entry leaves at once, reason ``idle``."""
        supervisor.set_device_runner(_lib_runner)
        pubs, msgs, sigs = _lib_sigs(n, b"lone")
        sched = VerifyScheduler(flush_us=LONG_US)
        sched._full_target = 128  # the chip's smallest bucket (32 on the CPU)
        try:
            if n == 1:
                got = [sched.submit(pubs[0], msgs[0], sigs[0]).result(4)]
            else:
                got = _verdicts(_segment(sched, pubs, msgs, sigs), 4)
            assert got == [True] * n
            snap = sstats.snapshot()
            assert snap["flushes"] == _flushes(idle=1)
            assert snap["flush_items"] == n
        finally:
            sched.close()

    def test_closed_loop_caller_finds_nothing_in_flight(
        self, sched_env, monkeypatch
    ):
        """The count of flushes in flight goes down BEFORE the futures
        resolve: a caller that is answered and submits its next vote at
        once never finds its own last flush still counted (it would wait
        behind nothing)."""
        supervisor.set_device_runner(_lib_runner)
        pubs, msgs, sigs = _lib_sigs(60, b"loop")
        sched = VerifyScheduler(flush_us=LONG_US)
        at_resolve = []
        real = VerifyScheduler._finish

        def finish(en, bits, now):
            at_resolve.append(sched._inflight)
            return real(en, bits, now)

        monkeypatch.setattr(VerifyScheduler, "_finish", staticmethod(finish))
        try:
            for p, m, s in zip(pubs, msgs, sigs):
                assert sched.submit(p, m, s).result(4) is True
        finally:
            sched.close()
        assert at_resolve == [0] * 60
        assert sstats.snapshot()["flushes"] == _flushes(idle=60)

    def test_concurrent_votes_behind_a_flush_leave_together(self, sched_env):
        """Coalescing under concurrency survives: while one flush is in
        flight, 8 senders' votes queue, and leave in ONE flush of 8 when it
        lands, not in eight flushes of one."""
        dev = _HeldDevice(_lib_runner)
        pubs, msgs, sigs = _lib_sigs(8, b"eight")
        sched = VerifyScheduler(flush_us=LONG_US)
        got = [None] * 8

        def sender(i):
            got[i] = sched.submit(pubs[i], msgs[i], sigs[i]).result(30)

        threads = [threading.Thread(target=sender, args=(i,)) for i in range(8)]
        try:
            held = dev.hold(sched)
            for th in threads:
                th.start()
            deadline = time.perf_counter() + 30
            while sched.pending() < 8:
                assert time.perf_counter() < deadline
                threading.Event().wait(0.002)
            # all eight are queued and none has left: one slot of the two
            # is free, but something is in flight
            assert sstats.snapshot()["flushes"] == _flushes(idle=1)
            dev.release()
            for th in threads:
                th.join(30)
            assert held.result(30) is True and got == [True] * 8
        finally:
            dev.release()
            sched.close()
        snap = sstats.snapshot()
        assert sum(snap["flushes"].values()) == 2  # the held one, then 8
        assert snap["flush_items"] == 9
        assert snap["segments"]["consensus"] == 9

    def test_late_vote_leaves_when_the_flush_in_flight_lands(self, sched_env):
        """The completion thread wakes the dispatcher when the last flush
        lands: a vote queued behind it leaves THEN (reason ``idle``), not
        at its deadline."""
        dev = _HeldDevice()
        (pub,), (msg,), (sig,) = _make_sigs(1, b"late")
        sched = VerifyScheduler(flush_us=LONG_US)
        try:
            held = dev.hold(sched)
            late = sched.submit(pub, msg, sig)
            threading.Event().wait(0.05)  # the dispatcher is asleep on it
            assert sched.pending() == 1 and not late.done()
            dev.release()
            assert held.result(4) is True and late.result(4) is True
        finally:
            dev.release()
            sched.close()
        assert sstats.snapshot()["flushes"] == _flushes(idle=2)

    def test_senders_in_closed_loops_lose_no_wakeup(self, sched_env):
        """More senders than cores, each in a closed loop, the interpreter
        switching threads every 10 us: every vote is answered long before
        the deadline (a lost wake-up would sit it out), every signature is
        flushed once, and the flushes' sizes follow the load."""
        supervisor.set_device_runner(_lib_runner)
        n_threads, per = 16, 12
        pubs, msgs, sigs = _lib_sigs(n_threads * per, b"stress")
        sched = VerifyScheduler(flush_us=60_000_000)
        sched._full_target = 128
        bad = []

        def sender(t):
            try:
                for i in range(t * per, (t + 1) * per):
                    if sched.submit(pubs[i], msgs[i], sigs[i]).result(20) is not True:
                        bad.append(i)
            except Exception as e:  # noqa: BLE001 — reported below
                bad.append(e)

        threads = [
            threading.Thread(target=sender, args=(t,)) for t in range(n_threads)
        ]
        try:
            with _switching_every(1e-5):
                for th in threads:
                    th.start()
                for th in threads:
                    th.join(60)
            assert not any(th.is_alive() for th in threads)
        finally:
            sched.close()
        assert bad == []
        snap = sstats.snapshot()
        assert snap["flush_items"] == n_threads * per
        assert snap["flushes"]["deadline"] == snap["flushes"]["shutdown"] == 0
        assert snap["queue_depth"] == 0 and snap["inflight_depth"] == 0
        # 16 senders behind one device: the flushes carried more than one
        assert sum(snap["flushes"].values()) < n_threads * per

    @pytest.mark.parametrize("n", [2, 40], ids=["evidence-2", "envelopes-40"])
    def test_verify_many_cached_is_one_hand_off(self, sched_env, n):
        """What one caller submits before it waits is queued under one
        acquisition of the lock, so it rides ONE flush by construction: an
        idle dispatcher (warm, woken by the first entry, the interpreter
        switching threads at once) cannot take the first and leave the
        rest."""
        supervisor.set_device_runner(_lib_runner)
        sched = verifysched.get_scheduler()
        sched._full_target = 128
        (pub,), (msg,), (sig,) = _lib_sigs(1, b"warm")
        assert sched.submit(pub, msg, sig).result(30) is True
        sstats.reset()
        hand_offs = []
        real = sched._enqueue
        sched._enqueue = lambda *a, **kw: hand_offs.append(a) or real(*a, **kw)
        pubs, msgs, sigs = _lib_sigs(n, b"many")
        sigs = _tampered(sigs, n - 1)
        with _switching_every(1e-6):
            got = verifysched.verify_many_cached(
                [Ed25519PubKey(p) for p in pubs], msgs, sigs,
                priority=verifysched.PRIO_EVIDENCE,
            )
        assert got == [i != n - 1 for i in range(n)]
        assert len(hand_offs) == 1
        snap = sstats.snapshot()
        assert snap["flushes"] == _flushes(idle=1)
        assert snap["flush_items"] == n
        assert snap["segments"]["evidence_light"] == n  # a future each
        assert _cached(pubs, msgs, sigs) == got  # and a put each

    def test_submit_many_takes_hits_and_sheds_the_tail(self, sched_env):
        """``submit_many``: a cached triple is answered without a queue
        slot, the misses are an n = 1 entry each, and what admission
        control sheds of a sheddable class comes back ``None``."""
        pubs, msgs, sigs = _make_sigs(6, b"sm")
        sigcache.get_cache().put(pubs[1], msgs[1], sigs[1], True)
        sched = VerifyScheduler(flush_us=1000, queue_cap=3)
        try:
            sched.pause()
            futs = sched.submit_many(
                pubs, msgs, sigs, verifysched.PRIO_BLOCKSYNC
            )
            assert futs[1].done() and futs[1].result() is True
            assert [f is None for f in futs] == [
                False, False, False, False, True, True,
            ]
            assert sched.pending() == 3
            assert [en.n for en in sched._queues[2]] == [1, 1, 1]
            sched.resume()
            assert [f.result(30) for f in futs[:4]] == [True] * 4
            snap = sstats.snapshot()
            assert snap["submit_hits"]["bulk"] == 1
            assert snap["shed"]["bulk"] == 2
            assert sum(snap["flushes"].values()) == 1
        finally:
            sched.close()


# ----------------------------------------------------------------------
# the queue's unit: one entry, one lock, one future a segment
# ----------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _lib_sigs(n, tag=b"seg"):
    """n valid triples signed by the host library over 8 keys (the
    reference signer takes 2.4 ms a signature; 1,500 are wanted).  Cached:
    copy before tampering."""
    keys = [
        Ed25519PrivKey.from_seed(hashlib.sha256(b"%s-key-%d" % (tag, i)).digest())
        for i in range(8)
    ]
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        k = keys[i % 8]
        msgs.append(b"%s-msg-%d" % (tag, i))
        pubs.append(k.pub_key().bytes())
        sigs.append(k.sign(msgs[-1]))
    return pubs, msgs, sigs


def _tampered(sigs, *at):
    out = list(sigs)
    for i in at:
        out[i] = out[i][:32] + bytes([out[i][32] ^ 1]) + out[i][33:]
    return out


def _lib_runner(backend, pubs, msgs, sigs, lanes):
    """Device stand-in on the host library (0.2 ms a signature against the
    reference's 4 ms): same verdicts on valid and bit-flipped signatures."""
    out = np.zeros(lanes, dtype=bool)
    out[: len(pubs)] = [
        Ed25519PubKey(p).verify_signature(m, s)
        for p, m, s in zip(pubs, msgs, sigs)
    ]
    return out


class TestSegmentUnit:
    @pytest.mark.parametrize("n", [1, 117, 1500])
    def test_segment_is_one_entry_one_future(self, sched_env, n):
        """A segment of n is ONE queue entry and ONE future, and every
        statistic still counts n signatures."""
        supervisor.set_device_runner(_lib_runner)
        pubs, msgs, sigs = _lib_sigs(n)
        sigs = _tampered(sigs, n // 2)
        sched = verifysched.get_scheduler()
        sched.pause()
        futs = _segment(sched, pubs, msgs, sigs)
        assert len(futs) == 1
        assert sum(len(q) for q in sched._queues) == 1
        assert sched.pending() == n
        snap = sstats.snapshot()
        assert snap["submitted"]["consensus"] == n
        assert snap["segments"]["consensus"] == 1
        assert snap["queue_depth"] == n
        sched.resume()
        assert futs[0].result(timeout=60) == [i != n // 2 for i in range(n)]
        snap = sstats.snapshot()
        assert snap["queue_depth"] == 0
        assert snap["verdicts"]["consensus"] == n
        for hist in ("queue_wait_hist", "device_hist", "latency_hist"):
            assert snap[hist]["consensus"]["count"] == n
        assert snap["flush_items"] == n
        assert sum(snap["flushes"].values()) == 1

    def test_paused_segment_flushes_once_full(self, sched_env):
        """One 1,500-signature segment is one flush, reason ``full`` (the
        deadline is out of reach), and ``sched.flush`` says it served one
        entry."""
        from cometbft_tpu.libs import tracing

        supervisor.set_device_runner(_lib_runner)
        tracing.get_tracer().reset()
        pubs, msgs, sigs = _lib_sigs(1500)
        sched = VerifyScheduler(flush_us=60_000_000)
        try:
            sched.pause()
            futs = _segment(sched, pubs, msgs, sigs)
            sched.resume()
            assert _verdicts(futs, 60) == [True] * 1500
        finally:
            sched.close()
        # ``full`` outranks ``idle``: nothing was in flight either
        assert sstats.snapshot()["flushes"] == {
            "deadline": 0, "full": 1, "idle": 0, "shutdown": 0,
        }
        (flush,) = [
            sp
            for sp in tracing.get_tracer().tail(100)
            if sp["stage"] == "sched.flush"
        ]
        assert flush["attrs"]["segments"] == 1
        assert flush["attrs"]["items"] == 1500
        assert flush["attrs"]["reason"] == "full"

    @pytest.mark.parametrize("at", [0, 58, 116], ids=["first", "middle", "last"])
    def test_per_index_bits(self, sched_env, at):
        """Every index gets its own bit: the one tampered signature is
        named wherever it sits."""
        supervisor.set_device_runner(_lib_runner)
        pubs, msgs, sigs = _lib_sigs(117)
        got = verifysched.verify_segment_sync(
            pubs, msgs, _tampered(sigs, at), verifysched.PRIO_CONSENSUS
        )
        assert got == [i != at for i in range(117)]

    def test_bulk_segment_admitted_up_to_cap(self, sched_env, monkeypatch):
        """A bulk segment that would pass ``queue_cap`` is admitted up to
        the cap, decided once, and the direct dispatch answers the rest
        (the tampered index lies in the shed tail)."""
        monkeypatch.setenv("COMETBFT_TPU_SCHED_QUEUE", "8")
        verifysched.reset_scheduler()
        pubs, msgs, sigs = _make_sigs(12, b"cap")
        sigs = _tampered(sigs, 10)
        got = verifysched.verify_segment_sync(
            pubs, msgs, sigs, verifysched.PRIO_BLOCKSYNC
        )
        assert got == [i != 10 for i in range(12)]
        snap = sstats.snapshot()
        assert snap["submitted"]["bulk"] == 8
        assert snap["segments"]["bulk"] == 1
        assert snap["shed"]["bulk"] == 4
        assert snap["shed_fallback"]["bulk"] == 4
        assert snap["latency_hist"]["bulk"]["count"] == 12
        assert snap["queue_depth"] == 0

    def test_consensus_segment_admitted_whole_past_cap(self, sched_env):
        sched = VerifyScheduler(flush_us=1000, queue_cap=8)
        try:
            sched.pause()
            pubs, msgs, sigs = _make_sigs(12, b"cap-cons")
            bulk, admitted = sched.submit_segment(
                pubs, msgs, sigs, verifysched.PRIO_BLOCKSYNC
            )
            assert admitted == 8 and len(bulk) == 1
            # the queue is at its cap: nothing more of a sheddable class...
            assert sched.submit_segment(
                pubs, msgs, sigs, verifysched.PRIO_LIGHT
            ) == ([], 0)
            # ...and consensus whole
            cons = _segment(sched, pubs, msgs, sigs)
            assert len(cons) == 1 and sched.pending() == 20
            sched.resume()
            assert _verdicts(bulk) == [True] * 8
            assert _verdicts(cons) == [True] * 12
            snap = sstats.snapshot()
            assert snap["shed"] == {
                "consensus": 0, "evidence_light": 12, "bulk": 4,
            }
            assert snap["queue_depth"] == 0
        finally:
            sched.close()

    def test_segment_longer_than_max_drain_resolves_whole(
        self, sched_env, monkeypatch
    ):
        """A segment longer than ``MAX_DRAIN`` is cut into entries of at
        most ``MAX_DRAIN`` when it is submitted, no flush carries more,
        and the caller still gets every index's bit in order."""
        from cometbft_tpu.verifysched import service

        monkeypatch.setattr(service, "MAX_DRAIN", 16)
        pubs, msgs, sigs = _make_sigs(50, b"cut", invalid_every=7)
        sched = verifysched.get_scheduler()
        sched.pause()
        futs = _segment(sched, pubs, msgs, sigs)
        assert [len(q) for q in sched._queues] == [4, 0, 0]
        sched.resume()
        assert [len(f.result(timeout=30)) for f in futs] == [16, 16, 16, 2]
        assert _verdicts(futs) == _oracle(pubs, msgs, sigs)
        snap = sstats.snapshot()
        assert snap["segments"]["consensus"] == 4
        assert snap["flush_items"] == 50
        assert sum(snap["flushes"].values()) == 4
        assert verifysched.verify_segment_sync(
            pubs, msgs, sigs, verifysched.PRIO_CONSENSUS
        ) == _oracle(pubs, msgs, sigs)

    def test_same_triple_in_two_segments_one_lane(self, sched_env):
        """In-flight dedup works ACROSS the entries of a flush: the same
        vote in two peers' segments is one lane, both get its verdict."""
        pubs, msgs, sigs = _make_sigs(7, b"dup-seg")
        sigs = _tampered(sigs, 3)
        sched = verifysched.get_scheduler()
        sched.pause()
        a = _segment(sched, pubs[:5], msgs[:5], sigs[:5])
        b = _segment(sched, pubs[3:], msgs[3:], sigs[3:])  # 3 and 4 again
        sched.resume()
        want = _oracle(pubs, msgs, sigs)
        assert _verdicts(a) == want[:5]
        assert _verdicts(b) == want[3:]
        snap = sstats.snapshot()
        assert snap["dedup_hits"] == 2
        assert snap["flush_misses"] == 7
        assert snap["flush_items"] == 9
        assert sum(snap["flushes"].values()) == 1

    def test_flush_that_raises_resolves_on_reference(self, sched_env):
        pubs, msgs, sigs = _make_sigs(9, b"boom", 4)
        sched = VerifyScheduler(flush_us=500)
        try:

            def boom(entries):
                raise ValueError("planted")

            sched._plan = boom
            futs = _segment(sched, pubs, msgs, sigs)
            assert _verdicts(futs) == _oracle(pubs, msgs, sigs)
            snap = sstats.snapshot()
            assert snap["queue_depth"] == 0
            assert snap["verdicts"]["consensus"] == 9
        finally:
            sched.close()

    def test_fetch_that_raises_resolves_on_reference(
        self, sched_env, monkeypatch
    ):
        """The completion thread answers what a lost fetch left open."""
        from cometbft_tpu.ops import verify as ov

        def lost(handle):
            raise OSError("planted")

        monkeypatch.setattr(ov, "fetch_segments", lost)
        pubs, msgs, sigs = _make_sigs(9, b"lost", invalid_every=4)
        pubs[2] = b"\x01" * 31  # filtered before the device: stays False
        sched = verifysched.get_scheduler()
        futs = _segment(sched, pubs, msgs, sigs)
        assert _verdicts(futs) == _oracle(pubs, msgs, sigs)
        assert sstats.snapshot()["inflight_depth"] == 0


# ----------------------------------------------------------------------
# one key a signature, one put a verdict (docs/verify-stream.md)
# ----------------------------------------------------------------------


def _bv_verify(pubs, msgs, sigs, priority=verifysched.PRIO_CONSENSUS):
    """The triples through ``TpuBatchVerifier``, as a commit's are."""
    from cometbft_tpu.crypto.batch import TpuBatchVerifier

    bv = TpuBatchVerifier()
    for p, m, s in zip(pubs, msgs, sigs):
        bv.add(Ed25519PubKey(p), m, s)
    with verifysched.priority_class(priority):
        return bv.verify()


def _cached(pubs, msgs, sigs):
    """What the cache holds for the triples, read past its counters."""
    entries = sigcache.get_cache()._entries
    return [
        entries.get(sigcache._key(p, m, s))
        for p, m, s in zip(pubs, msgs, sigs)
    ]


class TestKeysRideTheSegment:
    def test_fresh_commit_hashes_n_keys_and_makes_n_puts(self, sched_env):
        """A fresh commit of n signatures through the scheduler: n keys
        hashed (the seam's look-up; the dedup and the write-back hash
        none) and n verdicts stored (the seam's write-back; the scheduler
        stores none).  A second ``verify()`` of the same triples, made
        right after the first returns, is all hits and no flush: the put
        is on the caller's path (what the light client's second pass
        rests on)."""
        supervisor.set_device_runner(_lib_runner)
        n = 117
        pubs, msgs, sigs = _lib_sigs(n, b"fresh")
        sigs = _tampered(sigs, 40)
        want = [i != 40 for i in range(n)]
        assert _bv_verify(pubs, msgs, sigs) == (False, want)
        st = sigcache.get_cache().stats()
        assert (st["keys"], st["puts"]) == (n, n)
        assert (st["hits"], st["misses"], st["size"]) == (0, n, n)
        snap = sstats.snapshot()
        assert sum(snap["flushes"].values()) == 1
        assert snap["flush_misses"] == n
        assert _bv_verify(pubs, msgs, sigs) == (False, want)
        st = sigcache.get_cache().stats()
        assert (st["keys"], st["puts"]) == (2 * n, n)  # the look-up's keys
        assert (st["hits"], st["misses"]) == (n, n)
        snap = sstats.snapshot()
        assert sum(snap["flushes"].values()) == 1  # nothing was queued
        assert snap["submitted"]["consensus"] == n

    @pytest.mark.parametrize("keyed", ["carried", "none-cache-off"])
    def test_dedup_same_lanes_with_carried_keys_as_with_none(
        self, sched_env, monkeypatch, keyed
    ):
        """In-flight dedup across two entries ships the same lanes whether
        the entries carry the seam's keys or the scheduler keys them (the
        cache switched off, where the seam makes none)."""
        shipped = []

        def runner(backend, pubs, msgs, sigs, lanes):
            shipped.append((list(pubs), list(msgs), list(sigs)))
            return _oracle_runner(backend, pubs, msgs, sigs, lanes)

        supervisor.set_device_runner(runner)
        pubs, msgs, sigs = _make_sigs(7, b"dup-keys")
        sigs = _tampered(sigs, 3)
        if keyed == "none-cache-off":
            monkeypatch.setenv("COMETBFT_TPU_SIGCACHE", "0")
        a = sigcache.partition_misses(pubs[:5], msgs[:5], sigs[:5])
        b = sigcache.partition_misses(pubs[3:], msgs[3:], sigs[3:])
        assert (a.keys is None) == (b.keys is None) == (keyed != "carried")
        sched = verifysched.get_scheduler()
        sched.pause()
        fa, _ = sched.submit_segment(pubs[:5], msgs[:5], sigs[:5], keys=a.keys)
        fb, _ = sched.submit_segment(pubs[3:], msgs[3:], sigs[3:], keys=b.keys)
        sched.resume()
        want = _oracle(pubs, msgs, sigs)
        assert _verdicts(fa) == want[:5] and _verdicts(fb) == want[3:]
        # 3 and 4 came twice and went once: seven lanes, in order
        assert shipped == [(pubs, msgs, sigs)]
        snap = sstats.snapshot()
        assert snap["dedup_hits"] == 2 and snap["flush_misses"] == 7
        st = sigcache.get_cache().stats()
        # carried: the two look-ups' keys and no put (the seam's to make);
        # cache off: the scheduler's own keys for its dedup, and no put
        assert (st["keys"], st["puts"], st["size"]) == (9, 0, 0)

    @pytest.mark.parametrize(
        "path", ["longer-than-max-drain", "shed-tail", "scheduler-inactive"]
    )
    def test_every_verdict_cached_exactly_once(
        self, sched_env, monkeypatch, path
    ):
        """Whatever answered the seam's misses — several entries of one
        segment, the direct dispatch of a shed tail, the path without the
        scheduler — each verdict is stored once, under the look-up's key."""
        from cometbft_tpu.verifysched import service

        supervisor.set_device_runner(_lib_runner)
        n, prio = 50, verifysched.PRIO_CONSENSUS
        if path == "longer-than-max-drain":
            monkeypatch.setattr(service, "MAX_DRAIN", 16)
        elif path == "shed-tail":
            monkeypatch.setenv("COMETBFT_TPU_SCHED_QUEUE", "8")
            verifysched.reset_scheduler()
            prio = verifysched.PRIO_BLOCKSYNC
        else:
            monkeypatch.setenv("COMETBFT_TPU_VERIFY_SCHED", "0")
        pubs, msgs, sigs = _lib_sigs(n, b"once-%s" % path.encode())
        sigs = _tampered(sigs, 7, 44)
        want = [i not in (7, 44) for i in range(n)]
        assert _bv_verify(pubs, msgs, sigs, prio) == (False, want)
        st = sigcache.get_cache().stats()
        assert (st["keys"], st["puts"], st["size"]) == (n, n, n)
        assert _cached(pubs, msgs, sigs) == want
        snap = sstats.snapshot()
        if path == "longer-than-max-drain":
            assert snap["segments"]["consensus"] == 4
        elif path == "shed-tail":
            assert snap["shed"]["bulk"] == n - 8
        else:
            assert snap["submitted"]["consensus"] == 0

    def test_keys_are_cut_with_the_lists(self, sched_env, monkeypatch):
        from cometbft_tpu.verifysched import service

        monkeypatch.setattr(service, "MAX_DRAIN", 16)
        pubs, msgs, sigs = _make_sigs(40, b"cut-keys")
        part = sigcache.partition_misses(pubs, msgs, sigs)
        sched = verifysched.get_scheduler()
        sched.pause()
        futs, admitted = sched.submit_segment(pubs, msgs, sigs, keys=part.keys)
        assert admitted == 40
        entries = list(sched._queues[verifysched.PRIO_CONSENSUS])
        assert [en.n for en in entries] == [16, 16, 8]
        assert [k for en in entries for k in en.keys] == part.keys
        for en in entries:
            assert en.keys == [
                sigcache._key(*t) for t in zip(en.pubs, en.msgs, en.sigs)
            ]
            assert en.puts is False  # the seam's write-back stores these
        sched.resume()
        assert _verdicts(futs) == [True] * 40
        assert sigcache.get_cache().stats()["puts"] == 0

    def test_submit_carries_its_key_and_the_scheduler_puts(
        self, sched_env, monkeypatch
    ):
        """``submit``'s n = 1 entry: one key, hashed for the look-up, rides
        the entry to the dedup and to the put, which is the scheduler's."""
        calls = []
        real = sigcache._key
        monkeypatch.setattr(
            sigcache, "_key", lambda *t: calls.append(t) or real(*t)
        )
        (pub,), (msg,), (sig,) = _make_sigs(1, b"n1")
        sched = verifysched.get_scheduler()
        sched.pause()
        fut = sched.submit(pub, msg, sig)
        (entry,) = sched._queues[verifysched.PRIO_CONSENSUS]
        assert entry.keys == [real(pub, msg, sig)] and entry.puts is True
        assert entry.scalar is True
        sched.resume()
        assert fut.result(timeout=30) is True
        assert calls == [(pub, msg, sig)]  # once, whatever came after
        st = sigcache.get_cache().stats()
        assert (st["keys"], st["puts"], st["size"]) == (1, 1, 1)
        assert _cached([pub], [msg], [sig]) == [True]

    def test_keyless_segment_is_keyed_and_stored_by_the_scheduler(
        self, sched_env
    ):
        """A caller that made no look-up brings no keys: the scheduler
        hashes the structurally possible triples for its dedup and stores
        their verdicts itself, once each."""
        pubs, msgs, sigs = _make_sigs(6, b"keyless", invalid_every=4)
        pubs[2] = b"\x01" * 31  # no key, no lane, no put
        want = _oracle(pubs, msgs, sigs)
        assert verifysched.verify_segment_sync(
            pubs, msgs, sigs, verifysched.PRIO_CONSENSUS
        ) == want
        st = sigcache.get_cache().stats()
        assert (st["keys"], st["puts"], st["size"]) == (5, 5, 5)
        assert _cached(pubs, msgs, sigs) == want[:2] + [None] + want[3:]


# ----------------------------------------------------------------------
# admission control / backpressure
# ----------------------------------------------------------------------


class TestAdmissionControl:
    def test_overload_sheds_only_nonconsensus(self, sched_env):
        sched = VerifyScheduler(flush_us=1000, queue_cap=4)
        try:
            sched.pause()
            bp, bm, bs = _make_sigs(8, b"bulk")
            cp, cm, cs = _make_sigs(6, b"cons")
            admitted = []
            shed = 0
            for i in range(8):
                try:
                    admitted.append(
                        sched.submit(
                            bp[i], bm[i], bs[i], verifysched.PRIO_BLOCKSYNC
                        )
                    )
                except verifysched.QueueFullError:
                    shed += 1
            assert len(admitted) == 4 and shed == 4  # cap honored exactly
            with pytest.raises(verifysched.QueueFullError):
                sched.submit(bp[0], bm[0], bs[0], verifysched.PRIO_EVIDENCE)
            # consensus is EXEMPT: admitted past the cap, never shed,
            # never blocked
            cons = [
                sched.submit(cp[i], cm[i], cs[i], verifysched.PRIO_CONSENSUS)
                for i in range(6)
            ]
            assert sched.pending() == 10
            sched.resume()
            assert all(f.result(timeout=30) is True for f in admitted)
            assert all(f.result(timeout=30) is True for f in cons)
            snap = sstats.snapshot()
            assert snap["shed"]["bulk"] == 4
            assert snap["shed"]["evidence_light"] == 1
            assert snap["shed"]["consensus"] == 0
            assert snap["queue_depth"] == 0
        finally:
            sched.close()

    def test_shed_caller_falls_back_to_sync_verdict(self, sched_env, monkeypatch):
        """A shed costs the batching win, never the verdict: verify_cached
        at a sheddable priority still answers correctly."""
        monkeypatch.setenv("COMETBFT_TPU_SCHED_QUEUE", "1")
        verifysched.reset_scheduler()
        sched = verifysched.get_scheduler()
        sched.pause()
        bp, bm, bs = _make_sigs(2, b"sf")
        sched.submit(bp[0], bm[0], bs[0], verifysched.PRIO_BLOCKSYNC)  # fills
        ok = verifysched.verify_cached(
            Ed25519PubKey(bp[1]), bm[1], bs[1],
            priority=verifysched.PRIO_BLOCKSYNC,
        )
        assert ok is True  # shed -> synchronous host path
        assert sstats.snapshot()["shed"]["bulk"] == 1
        sched.resume()


# ----------------------------------------------------------------------
# kill switch / equivalence at the wired call sites
# ----------------------------------------------------------------------


def _signed_votes(n, chain_id, height=7, tamper=()):
    from cometbft_tpu.types.basic import (
        PRECOMMIT_TYPE,
        BlockID,
        PartSetHeader,
        Timestamp,
    )
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.types.vote import Vote

    privs = [
        Ed25519PrivKey.from_seed(hashlib.sha256(b"vsv%d" % i).digest())
        for i in range(n)
    ]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    bid = BlockID(
        hash=hashlib.sha256(b"vs-blk").digest(),
        part_set_header=PartSetHeader(1, hashlib.sha256(b"vs-psh").digest()),
    )
    votes = []
    for i, p in enumerate(privs):
        addr = p.pub_key().address()
        idx, _ = vals.get_by_address(addr)
        v = Vote(
            type_=PRECOMMIT_TYPE,
            height=height,
            round_=0,
            block_id=bid,
            timestamp=Timestamp(1_700_000_000, 0),
            validator_address=addr,
            validator_index=idx,
        )
        v.signature = p.sign(v.sign_bytes(chain_id))
        if i in tamper:
            v.signature = v.signature[:32] + bytes(
                [v.signature[32] ^ 1]
            ) + v.signature[33:]
        votes.append(v)
    return privs, vals, votes


class TestKillSwitchAndCallSites:
    def test_kill_switch_restores_sync_path(self, sched_env, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_VERIFY_SCHED", "0")
        assert not verifysched.scheduler_active()
        pubs, msgs, sigs = _make_sigs(4, b"ks", invalid_every=2)
        got = [
            verifysched.verify_cached(Ed25519PubKey(p), m, s)
            for p, m, s in zip(pubs, msgs, sigs)
        ]
        assert got == _oracle(pubs, msgs, sigs)
        # no scheduler was ever instantiated, nothing queued or flushed
        from cometbft_tpu.verifysched import service

        assert service._SCHED is None
        snap = sstats.snapshot()
        assert snap["verdicts_total"] == 0
        assert sum(snap["flushes"].values()) == 0
        # the synchronous path still populated the sigcache
        assert sigcache.get_cache().stats()["size"] == 4

    def test_inactive_without_trusted_accelerator(self, sched_env, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "cpu")
        assert not verifysched.scheduler_active()

    def test_vote_verify_parity_and_scheduling(self, sched_env, monkeypatch):
        """types/vote.Vote.verify: identical verdicts scheduler-on vs
        kill-switch, and scheduler-on traffic really rides the queue."""
        chain_id = "sched-vote-chain"
        privs, vals, votes = _signed_votes(6, chain_id, tamper=(2, 4))
        want = [i not in (2, 4) for i in range(6)]

        got_on = [
            v.verify(chain_id, vals.validators[v.validator_index].pub_key)
            for v in votes
        ]
        assert got_on == want
        snap = sstats.snapshot()
        assert snap["submitted"]["consensus"] == 6  # rode the scheduler
        assert snap["verdicts_total"] == 6

        sigcache.reset_cache()
        sstats.reset()
        monkeypatch.setenv("COMETBFT_TPU_VERIFY_SCHED", "0")
        got_off = [
            v.verify(chain_id, vals.validators[v.validator_index].pub_key)
            for v in votes
        ]
        assert got_off == got_on
        assert sstats.snapshot()["verdicts_total"] == 0  # pure sync path

    @pytest.mark.parametrize("tampered", (False, True))
    def test_votes_verdict_is_cached_before_vote_verify_returns(
        self, sched_env, monkeypatch, tampered
    ):
        """The LastCommit built from gossiped votes is all cache hits only
        if each vote's verdict, sound or not, is in ``crypto/sigcache``
        BEFORE the vote's future resolves: the put is the scheduler's
        (``_settle``), made on the fetch thread ahead of ``set_result``."""
        chain_id = "sched-vote-cache"
        _, vals, (vote,) = _signed_votes(1, chain_id, tamper=(0,) if tampered else ())
        pub = vals.validators[0].pub_key
        triple = ([pub.bytes()], [vote.sign_bytes(chain_id)], [vote.signature])
        at_resolve = []
        real = VerifyScheduler._finish

        def finish(en, bits, now):
            at_resolve.append(_cached(*triple))
            return real(en, bits, now)

        monkeypatch.setattr(VerifyScheduler, "_finish", staticmethod(finish))
        assert _cached(*triple) == [None]
        assert vote.verify(chain_id, pub) is (not tampered)
        assert at_resolve == [[not tampered]]  # stored when the future resolved
        assert _cached(*triple) == [not tampered]
        st = sigcache.get_cache().stats()
        assert (st["keys"], st["puts"]) == (1, 1)
        assert vote.verify(chain_id, pub) is (not tampered)  # the cache answers
        assert sstats.snapshot()["submitted"]["consensus"] == 1

    def test_evidence_duplicate_vote_seam_and_cache(self, sched_env):
        """evidence satellite: duplicate-vote checks go through the seam at
        evidence priority AND populate the sigcache (they were bare host
        verifies before)."""
        from cometbft_tpu.evidence.verify import (
            EvidenceInvalidError,
            verify_duplicate_vote,
        )
        from cometbft_tpu.types.basic import (
            PRECOMMIT_TYPE,
            BlockID,
            PartSetHeader,
            Timestamp,
        )
        from cometbft_tpu.types.evidence import DuplicateVoteEvidence
        from cometbft_tpu.types.validator import Validator, ValidatorSet
        from cometbft_tpu.types.vote import Vote

        chain_id = "sched-ev-chain"
        priv = Ed25519PrivKey.from_seed(hashlib.sha256(b"sev").digest())
        vals = ValidatorSet([Validator(priv.pub_key(), 10)])
        addr = priv.pub_key().address()

        def vote(tag):
            v = Vote(
                type_=PRECOMMIT_TYPE,
                height=3,
                round_=0,
                block_id=BlockID(
                    hash=hashlib.sha256(tag).digest(),
                    part_set_header=PartSetHeader(
                        1, hashlib.sha256(tag + b"p").digest()
                    ),
                ),
                timestamp=Timestamp(100, 0),
                validator_address=addr,
                validator_index=0,
            )
            v.signature = priv.sign(v.sign_bytes(chain_id))
            return v

        ev = DuplicateVoteEvidence.from_votes(
            vote(b"a"), vote(b"b"), Timestamp(100, 0), 10, 10
        )
        verify_duplicate_vote(ev, chain_id, vals)  # no raise
        snap = sstats.snapshot()
        assert snap["submitted"]["evidence_light"] == 2
        assert sigcache.get_cache().stats()["size"] == 2  # cache populated
        # second verification is pure cache — zero new scheduler traffic
        verify_duplicate_vote(ev, chain_id, vals)
        assert (
            sstats.snapshot()["submitted"]["evidence_light"] == 2
        )

        bad = DuplicateVoteEvidence.from_votes(
            vote(b"c"), vote(b"d"), Timestamp(100, 0), 10, 10
        )
        bad.vote_b.signature = b"\x00" * 64
        with pytest.raises(EvidenceInvalidError, match="vote B"):
            verify_duplicate_vote(bad, chain_id, vals)

    def test_batch_verifier_bridge_parity(self, sched_env):
        """The _CollectingVerifier bridge (the seam consensus apply,
        evidence light-attack, light client and blocksync all verify
        through): TpuBatchVerifier bits under the scheduler == the host
        CpuBatchVerifier bits, and the misses rode the ambient priority
        class."""
        from cometbft_tpu.crypto.batch import CpuBatchVerifier, TpuBatchVerifier

        pubs, msgs, sigs = _make_sigs(12, b"bv", invalid_every=4)
        want_bv = CpuBatchVerifier()
        got_bv = TpuBatchVerifier()
        for p, m, s in zip(pubs, msgs, sigs):
            want_bv.add(Ed25519PubKey(p), m, s)
            got_bv.add(Ed25519PubKey(p), m, s)
        want = want_bv.verify()
        sigcache.reset_cache()  # the cpu pass cached every verdict
        with verifysched.priority_class(verifysched.PRIO_LIGHT):
            got = got_bv.verify()
        assert got == want
        snap = sstats.snapshot()
        assert snap["submitted"]["evidence_light"] == 12
        assert snap["submitted"]["consensus"] == 0


# ----------------------------------------------------------------------
# supervisor integration: infra failures never become verdicts
# ----------------------------------------------------------------------


class TestSupervisorIntegration:
    @pytest.mark.parametrize("mode", ["raise", "wrong_shape"])
    def test_faulty_backend_definitive_verdicts(self, sched_env, mode):
        """An infrastructure failure inside a coalesced batch resolves per
        the supervisor chain: every future completes with the host-oracle
        verdict — valid signatures stay True (no False accept bits), the
        backend demotes, nothing raises into the submitters."""
        from cometbft_tpu.crypto import backend_health

        supervisor.set_fault_injector(supervisor.FaultyBackend(mode))
        pubs, msgs, sigs = _make_sigs(24, b"flt-%s" % mode.encode(), invalid_every=4)
        sched = verifysched.get_scheduler()
        sched.pause()
        futs = _segment(sched, pubs, msgs, sigs)
        sched.resume()
        assert _verdicts(futs, 60) == _oracle(pubs, msgs, sigs)
        snap = backend_health.snapshot()
        assert snap["demotions"] >= 1
        assert snap["fallback_signatures"] > 0  # resolved on the host tier

    def test_fault_does_not_negative_cache(self, sched_env):
        """After the fault clears, the same (valid) triples still verify
        True — the degraded flush cached only definitive verdicts."""
        supervisor.set_fault_injector(supervisor.FaultyBackend("raise"))
        pubs, msgs, sigs = _make_sigs(8, b"nnc")
        sched = verifysched.get_scheduler()
        futs = _segment(sched, pubs, msgs, sigs)
        assert _verdicts(futs, 60) == [True] * 8
        supervisor.clear_fault_injector()
        assert all(
            verifysched.verify_cached(Ed25519PubKey(p), m, s)
            for p, m, s in zip(pubs, msgs, sigs)
        )


# ----------------------------------------------------------------------
# in-flight pipeline (docs/verify-scheduler.md "In-flight pipeline")
# ----------------------------------------------------------------------


class TestInflightPipeline:
    WIDTH = 3

    @pytest.fixture
    def lane_mesh(self, sched_env, monkeypatch):
        """sched_env + a 3-ordinal virtual elastic mesh on the host-oracle
        mesh runner, so pipelined flushes round-robin across real lane
        handles (``elastic.dispatch_lane``/``fetch_lane``)."""
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.ops import device_health
        from cometbft_tpu.parallel import elastic

        monkeypatch.setenv("COMETBFT_TPU_BREAKER_THRESHOLD", "1")
        monkeypatch.setenv("COMETBFT_TPU_SCHED_INFLIGHT", str(self.WIDTH))
        backend_health.reset()
        device_health.reset()
        elastic.clear()
        elastic.configure(range(self.WIDTH))
        elastic.set_mesh_runner(elastic.host_oracle_runner)
        yield elastic
        elastic.clear_fault_injector()
        elastic.clear_mesh_runner()
        elastic.clear()
        device_health.reset()
        backend_health.reset()

    def test_differential_k_in_flight_vs_oracle(self, sched_env, monkeypatch):
        """K-in-flight verdicts over the mesh lanes bitwise-equal to the
        host oracle on a randomized valid/invalid mix including structural
        garbage, and nothing left in flight."""
        pubs, msgs, sigs = _make_sigs(96, b"pipe-mix", invalid_every=3)
        pubs[7], sigs[13] = b"\x01" * 30, b"\x02" * 60
        monkeypatch.setenv("COMETBFT_TPU_SCHED_INFLIGHT", "3")
        sched = VerifyScheduler(flush_us=500)
        try:
            piped = _verdicts(_segment(sched, pubs, msgs, sigs), 60)
        finally:
            sched.close()
        assert piped == _oracle(pubs, msgs, sigs)
        assert dispatch_stats.snapshot()["inflight_depth"] == 0

    def test_dispatch_overlap_inflight_high_water(
        self, sched_env, monkeypatch
    ):
        """With the completion pool parked on a gate, the dispatcher keeps
        shipping: the in-flight high-water mark proves two flushes
        genuinely overlapped instead of serializing."""
        monkeypatch.setenv("COMETBFT_TPU_SCHED_INFLIGHT", "2")
        gate = threading.Event()

        def slow_runner(backend, pubs, msgs, sigs, lanes):
            gate.wait(20)
            return _oracle_runner(backend, pubs, msgs, sigs, lanes)

        supervisor.set_device_runner(slow_runner)
        sched = VerifyScheduler(flush_us=500)
        try:
            a = _make_sigs(4, b"ovl-a")
            b = _make_sigs(4, b"ovl-b")
            futs = _segment(sched, *a)
            deadline = time.perf_counter() + 10
            # flush A dispatched, its fetch parked on the gate...
            while dispatch_stats.snapshot()["inflight_depth"] < 1:
                assert time.perf_counter() < deadline
                threading.Event().wait(0.005)
            # ...and flush B ships right behind it
            futs += _segment(sched, *b)
            while dispatch_stats.snapshot()["inflight_depth"] < 2:
                assert time.perf_counter() < deadline
                threading.Event().wait(0.005)
            gate.set()
            assert _verdicts(futs) == [True] * 8
        finally:
            gate.set()
            sched.close()
        snap = dispatch_stats.snapshot()
        assert snap["inflight_hwm"] >= 2
        assert snap["inflight_depth"] == 0  # every dispatch was fetched
        assert sstats.snapshot()["inflight_hwm"] >= 2

    def test_single_lane_fault_degrades_that_lane_only(self, lane_mesh):
        """FaultyDevice raise on ONE mesh lane mid-pipeline: the other
        lanes' flushes complete untouched, the guilty lane's breaker
        trips and the mesh shrinks by one, and every future still
        resolves with the oracle verdict."""
        from cometbft_tpu.crypto import backend_health

        elastic = lane_mesh
        elastic.set_fault_injector(
            elastic.FaultyDevice("raise", ordinals=(1,))
        )
        pubs, msgs, sigs = _make_sigs(18, b"lane-flt", invalid_every=5)
        sched = VerifyScheduler(flush_us=300)
        try:
            futs = []
            # one paused round per lane: three flushes round-robin over
            # the three ordinals, so exactly one rides the faulty lane
            for r in range(self.WIDTH):
                sched.pause()
                lo, hi = r * 6, (r + 1) * 6
                futs += _segment(
                    sched, pubs[lo:hi], msgs[lo:hi], sigs[lo:hi]
                )
                sched.resume()
                assert len(futs[-1].result(timeout=60)) == hi - lo
            got = _verdicts(futs, 60)
        finally:
            sched.close()
        assert got == _oracle(pubs, msgs, sigs)
        reg = backend_health.registry()
        assert reg.breaker("mesh_dev1").stats()["failures_total"] >= 1
        assert reg.breaker("mesh_dev0").stats()["failures_total"] == 0
        assert reg.breaker("mesh_dev2").stats()["failures_total"] == 0
        snap = dispatch_stats.snapshot()
        assert snap["mesh_shrinks"] == 1
        assert snap["lane_dispatches"].get("1", 0) >= 1  # it WAS routed

    def test_single_lane_hang_wedges_alone(self, lane_mesh, monkeypatch):
        """FaultyDevice hang on one lane: the shard watchdog abandons it
        (shard_watchdog_fire), the wedged lane alone degrades, and every
        future resolves — nobody waits on the hung fetch."""
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.libs import tracing

        monkeypatch.setenv("COMETBFT_TPU_DISPATCH_TIMEOUT_MS", "100")
        tracing.reset_tracer()
        elastic = lane_mesh
        elastic.set_fault_injector(
            elastic.FaultyDevice("hang", ordinals=(1,), hang_s=2.0)
        )
        pubs, msgs, sigs = _make_sigs(12, b"lane-hang", invalid_every=4)
        sched = VerifyScheduler(flush_us=300)
        try:
            futs = []
            for r in range(self.WIDTH):
                sched.pause()
                lo, hi = r * 4, (r + 1) * 4
                futs += _segment(
                    sched, pubs[lo:hi], msgs[lo:hi], sigs[lo:hi]
                )
                sched.resume()
                assert len(futs[-1].result(timeout=60)) == hi - lo
            got = _verdicts(futs, 60)
        finally:
            sched.close()
        assert got == _oracle(pubs, msgs, sigs)
        reg = backend_health.registry()
        assert reg.breaker("mesh_dev1").stats()["failures_total"] >= 1
        assert reg.breaker("mesh_dev0").stats()["failures_total"] == 0
        assert reg.breaker("mesh_dev2").stats()["failures_total"] == 0
        snap = tracing.get_tracer().snapshot()
        assert snap["anomalies"].get("shard_watchdog_fire", 0) >= 1

    @pytest.mark.parametrize("n,pinned", [(5, True), (6, False), (14, False)])
    def test_only_a_flush_the_mesh_does_not_take_is_pinned(
        self, lane_mesh, monkeypatch, n, pinned
    ):
        """ISSUE 34: a flush that reaches ``elastic.min_batch()`` holds
        every healthy chip, so the scheduler pins nothing (``lane=None``)
        and the supervisor routes it mesh-wide (here the whole ladder on
        the oracle runner seam, at fetch); a smaller one is pinned at one
        lane, round-robin, as before."""
        from cometbft_tpu.ops import verify as ov

        monkeypatch.setenv("COMETBFT_TPU_MESH_MIN_BATCH", "6")
        seen = []
        real = ov.dispatch_segments

        def spy(work, lane=None):
            h = real(work, lane=lane)
            seen.append((lane, h.sup.kind))
            return h

        monkeypatch.setattr(ov, "dispatch_segments", spy)
        pubs, msgs, sigs = _make_sigs(n, b"pin-%d" % n, invalid_every=4)
        sched = VerifyScheduler(flush_us=300)
        try:
            got = _verdicts(_segment(sched, pubs, msgs, sigs), 60)
            rr = sched._lane_rr
        finally:
            sched.close()
        assert got == _oracle(pubs, msgs, sigs)
        assert seen == [(0, "lane")] if pinned else seen == [(None, "mesh")]
        assert rr == (1 if pinned else 0)
        snap = dispatch_stats.snapshot()
        assert snap["inflight_depth"] == 0
        # a pinned flush is one shard, a mesh-wide one a shard a lane
        assert sum(h["count"] for h in snap["shard_hist"].values()) == (
            1 if pinned else self.WIDTH
        )

    def test_bucket_target_fallback_clamps_to_bucket(
        self, sched_env, monkeypatch
    ):
        """The _bucket_target exception fallback must return a REAL
        padding bucket, not the raw width-scaled value (32 x 3 = 96 is
        not a bucket; the largest bucket <= 96 is 64)."""
        import cometbft_tpu.ops as ops_pkg
        from cometbft_tpu.parallel import elastic

        sched = VerifyScheduler()
        sched._full_target = 32  # base bucket already resolved
        monkeypatch.setattr(elastic, "healthy_width", lambda: 3)
        monkeypatch.setattr(ops_pkg, "verify", None)  # ops seam broken
        assert sched._bucket_target() == 64
        sched.close()


# ----------------------------------------------------------------------
# metrics / tooling
# ----------------------------------------------------------------------


class TestMetricsAndTooling:
    def test_sched_metrics_exposition(self, sched_env):
        from cometbft_tpu.libs.metrics import NodeMetrics

        pubs, msgs, sigs = _make_sigs(3, b"met")
        sched = verifysched.get_scheduler()
        futs = _segment(sched, pubs, msgs, sigs)
        assert _verdicts(futs) == [True] * 3
        out = NodeMetrics().registry.expose()
        assert 'cometbft_sched_submitted{class="consensus"} 3' in out
        assert 'cometbft_sched_shed{class="consensus"} 0' in out
        assert "cometbft_sched_queue_depth 0" in out
        assert "cometbft_sched_verdicts 3" in out
        for reason in ("deadline", "full", "idle", "shutdown"):
            assert 'cometbft_sched_flushes{reason="%s"}' % reason in out
        # in-flight pipeline: everything resolved, so depth is back to 0
        # but the flush above rode the pipeline and left per-lane tallies
        assert "cometbft_sched_inflight_depth 0" in out
        assert "cometbft_sched_inflight_hwm 1" in out
        assert 'cometbft_crypto_lane_occupancy{lane="' in out

    def test_callsite_lint_clean(self):
        """The CI lint (tier-1-wired): no direct verify_batch/
        verify_segments call sites outside the sanctioned seams."""
        import pathlib
        sys.path.insert(
            0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts")
        )
        try:
            import check_verify_callsites as lint
        finally:
            sys.path.pop(0)
        root = pathlib.Path(__file__).resolve().parent.parent
        assert lint.scan(root) == []


# ----------------------------------------------------------------------
# real device smoke (one small dispatch through the full stack)
# ----------------------------------------------------------------------


@pytest.mark.warmcache("verify-xla-packed-32")
def test_real_dispatch_smoke(monkeypatch):
    """One real kernel dispatch end-to-end: submit -> flush ->
    verify_segments -> supervisor -> XLA -> futures.  Runs in tier-1 when
    the shared exec cache can serve the 32-lane bucket executable warm
    (ops/aot_cache — the load skips tracing AND compilation); rides the
    slow lane, which pays the compile once and warms the cache, otherwise
    (the tier-1 soft budget has no headroom for a cold kernel compile, and
    every layer below the oracle seam is already tier-1-covered by
    test_verify_stream/test_supervisor)."""
    from cometbft_tpu.crypto import backend_health

    monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "tpu")
    sigcache.reset_cache()
    sstats.reset()
    backend_health.reset()
    verifysched.reset_scheduler()
    try:
        pubs, msgs, sigs = _make_sigs(6, b"real", invalid_every=3)
        sched = verifysched.get_scheduler()
        sched.pause()
        futs = _segment(sched, pubs, msgs, sigs)
        sched.resume()
        assert _verdicts(futs, 300) == _oracle(pubs, msgs, sigs)
        assert sstats.snapshot()["flush_lanes"] == 32
    finally:
        verifysched.reset_scheduler()
        backend_health.reset()
        sigcache.reset_cache()
        sstats.reset()
