"""Backend supervisor (ISSUE 4): watchdog, circuit breaker, and the
verified degradation chain around the verify hot path.

The load-bearing guarantee, pinned by the differential tests: an
INFRASTRUCTURE failure (raise / hang past the watchdog / malformed output
/ flapping device) never changes an accept bit — under every fault mode
the supervised ``verify_batch`` is bitwise-equal to the pure-host
``ed25519_ref.verify_zip215`` oracle, and no exception escapes to the
caller.

Most tests install a host-backed device runner (the supervisor's
device-runner seam) so a "device dispatch" costs ~1 ms instead of the
~1.7 s a real XLA-CPU dispatch costs on this throttled host; everything
under test (watchdog, breaker, injector, bisection) sits above that seam.
Kernel-vs-oracle equivalence itself is tests/test_ed25519_jax.py's job.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from cometbft_tpu.crypto import backend_health as bh
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import dispatch_stats, supervisor


@pytest.fixture(autouse=True)
def _clean_supervisor_state():
    bh.reset()
    supervisor.clear_fault_injector()
    supervisor.clear_device_runner()
    yield
    bh.reset()
    supervisor.clear_fault_injector()
    supervisor.clear_device_runner()


class _CountingRunner:
    """Host-backed device runner that counts invocations."""

    def __init__(self):
        self.calls = 0

    def __call__(self, backend, pubs, msgs, sigs, lanes):
        self.calls += 1
        out = np.zeros(lanes, dtype=bool)
        out[: len(pubs)] = [
            ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)
        ]
        return out


def _mixed_batch(rng: np.random.Generator, n: int):
    """Randomized valid/invalid mix: tampered sigs, truncated sigs, wrong
    pub lengths, swapped messages — every failure class the structural
    filter and the kernel distinguish."""
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        pub = ref.pubkey_from_seed(seed)
        msg = b"msg-%d" % i
        sig = ref.sign(seed, msg)
        kind = int(rng.integers(0, 6))
        if kind == 1:  # tampered signature
            sig = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        elif kind == 2:  # truncated signature
            sig = sig[:40]
        elif kind == 3:  # wrong pub length
            pub = pub[:31]
        elif kind == 4:  # message swap
            msg = b"other-%d" % i
        pubs.append(pub)
        msgs.append(msg)
        sigs.append(sig)
    return pubs, msgs, sigs


def _oracle(pubs, msgs, sigs):
    return [ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]


class _SlowArray:
    """What a wedged device hands back: the copy to the host sleeps."""

    def __init__(self, bits, sleep_s=0.0):
        self.bits, self.sleep_s = bits, sleep_s

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.sleep_s)
        return self.bits


def _install_fake_chip(monkeypatch, batches, sleep_s=0.0):
    """The ``chip`` kind without a compile: the bucket's EXECUTABLE is
    replaced by a host stand-in that answers each lane it was handed with
    the oracle's bit for the triple packed there, as an unfetched "device
    array".  ``_launch`` itself (lookup, the one transfer of the packed
    buffer, the call, each under its lap), pack, both watchdog calls, the
    spans and the breaker handling above it run as on a chip.  Returns the
    launches."""
    known = {}
    for pubs, msgs, sigs in batches:
        for p, m, g in zip(pubs, msgs, sigs):
            if len(p) == 32 and len(g) == 64:
                known[(p, g[:32])] = ref.verify_zip215(p, m, g)
    launches = []

    from cometbft_tpu.ops import verify as ov

    def fake_executable(backend, lanes):
        def call(packed):
            arrays = ov.packed_views(np.asarray(packed))
            a, r = arrays["a_bytes"], arrays["r_bytes"]
            out = np.zeros(lanes, dtype=bool)
            for i in range(lanes):
                out[i] = known.get((a[i].tobytes(), r[i].tobytes()), False)
            launches.append((backend, lanes))
            return _SlowArray(out, sleep_s)

        return call, None

    monkeypatch.setattr(ov, "bucket_executable", fake_executable)
    return launches


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# -- circuit breaker state machine ------------------------------------------


class TestCircuitBreaker:
    def _mk(self, threshold=3, backoff=1.0, cap=8.0):
        clk = _FakeClock()
        br = bh.CircuitBreaker(
            "t", threshold=threshold, backoff_s=backoff,
            backoff_max_s=cap, clock=clk,
        )
        return br, clk

    def test_opens_after_threshold_consecutive_failures(self):
        br, _ = self._mk(threshold=3)
        for _ in range(2):
            br.record_failure(RuntimeError("x"))
            assert br.state == bh.CLOSED
            assert br.allow()
        br.record_failure(RuntimeError("x"))
        assert br.state == bh.OPEN
        assert not br.allow()

    def test_success_resets_consecutive_count(self):
        br, _ = self._mk(threshold=2)
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == bh.CLOSED  # never saw 2 consecutive

    def test_half_open_probe_after_backoff_then_close(self):
        br, clk = self._mk(threshold=1, backoff=1.0)
        br.record_failure()
        assert not br.allow()
        clk.advance(0.99)
        assert not br.allow()
        clk.advance(0.02)
        assert br.state == bh.HALF_OPEN
        assert br.allow()  # the probe
        assert not br.allow()  # only ONE probe per window
        br.record_success()
        assert br.state == bh.CLOSED
        assert br.stats()["repromotions"] == 1
        # re-promotion resets the backoff schedule
        assert br.stats()["backoff_s"] == 1.0

    def test_failed_probe_reopens_with_doubled_backoff(self):
        br, clk = self._mk(threshold=1, backoff=1.0, cap=3.0)
        br.record_failure()  # open; next window 2.0
        clk.advance(1.01)
        assert br.allow()
        br.record_failure()  # probe failed; open for 2.0, next window 3.0 (cap)
        assert not br.allow()
        clk.advance(1.5)
        assert not br.allow()  # 2.0 not yet elapsed
        clk.advance(0.6)
        assert br.allow()
        br.record_failure()  # open for 3.0 (capped), stays 3.0
        assert br.stats()["backoff_s"] == 3.0
        clk.advance(2.9)
        assert not br.allow()
        clk.advance(0.2)
        assert br.allow()
        br.record_success()
        assert br.state == bh.CLOSED

    def test_deterministic_under_fake_clock(self):
        def run():
            br, clk = self._mk(threshold=2, backoff=0.5, cap=4.0)
            log = []
            for step in range(40):
                if br.allow():
                    (br.record_failure if step % 3 else br.record_success)()
                log.append((br.state, round(br.stats()["backoff_s"], 3)))
                clk.advance(0.3)
            return log

        assert run() == run()


# -- watchdog ----------------------------------------------------------------


class TestWatchdog:
    def test_passthrough_value_and_exception(self):
        assert supervisor.watchdog_call(lambda: 42, timeout_s=5.0) == 42
        with pytest.raises(ValueError):
            supervisor.watchdog_call(
                lambda: (_ for _ in ()).throw(ValueError("boom")),
                timeout_s=5.0,
            )

    def test_timeout_fires_and_worker_recovers(self):
        release = threading.Event()

        def wedge():
            release.wait(5.0)
            return "late"

        t0 = time.monotonic()
        with pytest.raises(bh.DispatchTimeoutError):
            supervisor.watchdog_call(wedge, timeout_s=0.05, backend="xla")
        assert time.monotonic() - t0 < 2.0  # caller not blocked for 5 s
        assert bh.snapshot()["watchdog_fires"] == 1
        release.set()  # unwedge the abandoned worker
        # a fresh worker serves the next call
        assert supervisor.watchdog_call(lambda: "ok", timeout_s=1.0) == "ok"

    def test_zero_timeout_runs_inline(self):
        tid = supervisor.watchdog_call(
            lambda: threading.get_ident(), timeout_s=0
        )
        assert tid == threading.get_ident()

    # ISSUE 37: a worker that has finished a call parks and takes the next

    @pytest.fixture
    def wd(self, monkeypatch):
        """A watchdog of this test's own: nobody else's worker is parked."""
        wd = supervisor._Watchdog()
        monkeypatch.setattr(supervisor, "_WATCHDOG", wd)
        dispatch_stats.reset()
        return wd

    @staticmethod
    def _calls():
        return dispatch_stats.snapshot()["watchdog_calls"]

    @staticmethod
    def _dispatch_threads():
        return {
            t.ident for t in threading.enumerate()
            if t.name == "crypto-dispatch"
        }

    def test_two_calls_in_turn_run_on_the_same_thread(self, wd):
        from cometbft_tpu.libs import tracing

        tracing.get_tracer().reset()
        idents = []
        for _ in range(2):
            with tracing.span("verify.dispatch"):
                idents.append(
                    supervisor.watchdog_call(threading.get_ident, timeout_s=5.0)
                )
        assert idents[0] == idents[1] != threading.get_ident()
        marks = [sp["attrs"]["worker"] for sp in tracing.get_tracer().tail(2)]
        assert marks == ["fresh", "parked"]
        assert self._calls() == {"fresh": 1, "parked": 1}
        assert len(wd._parked) == 1

    @pytest.mark.parametrize("late", ["value", "exception"])
    def test_abandoned_worker_never_serves_again(self, wd, late):
        release = threading.Event()
        seen = {}

        def wedge():
            seen["ident"] = threading.get_ident()
            release.wait(5.0)
            if late == "exception":
                raise RuntimeError("late")
            return "late"

        t0 = time.monotonic()
        with pytest.raises(bh.DispatchTimeoutError):
            supervisor.watchdog_call(wedge, timeout_s=0.05, backend="xla")
        assert 0.05 <= time.monotonic() - t0 < 2.0  # at the deadline
        release.set()
        got, served = [], set()

        def fn(i):
            served.add(threading.get_ident())
            return i

        for i in range(20):
            got.append(
                supervisor.watchdog_call(lambda i=i: fn(i), timeout_s=5.0)
            )
        # the late value and the late exception reached nobody
        assert got == list(range(20))
        assert seen["ident"] not in served
        # it exited instead of parking
        for _ in range(200):
            if seen["ident"] not in self._dispatch_threads():
                break
            time.sleep(0.01)
        assert seen["ident"] not in self._dispatch_threads()
        assert len(wd._parked) == 1 and len(served) <= 2
        assert self._calls()["fresh"] == 2  # the wedged one's, then one more

    def test_concurrent_calls_never_queue(self, wd):
        """Eight calls held on a barrier, ONE worker parked: all eight are
        inside ``fn`` before any returns (a queue would break the barrier),
        each on a thread of its own under its own deadline."""
        supervisor.watchdog_call(lambda: None, timeout_s=5.0)
        assert len(wd._parked) == 1
        barrier = threading.Barrier(8, timeout=5.0)
        out, errs = [], []

        def fn():
            barrier.wait()
            return threading.get_ident()

        def caller():
            try:
                out.append(supervisor.watchdog_call(fn, timeout_s=5.0))
            except BaseException as e:  # noqa: BLE001 — the assert shows it
                errs.append(e)

        callers = [threading.Thread(target=caller) for _ in range(8)]
        for t in callers:
            t.start()
        for t in callers:
            t.join(10.0)
        assert errs == [] and len(set(out)) == 8
        assert self._calls() == {"fresh": 8, "parked": 1}

    def test_a_raising_fn_leaves_its_worker_reusable(self, wd):
        seen = []

        def boom():
            seen.append(threading.get_ident())
            raise KeyboardInterrupt("a BaseException too")

        with pytest.raises(KeyboardInterrupt):
            supervisor.watchdog_call(boom, timeout_s=5.0)
        assert supervisor.watchdog_call(
            threading.get_ident, timeout_s=5.0
        ) == seen[0]
        assert self._calls() == {"fresh": 1, "parked": 1}

    def test_parked_workers_are_bounded_and_an_idle_one_exits(
        self, wd, monkeypatch
    ):
        monkeypatch.setattr(supervisor, "_PARKED_MAX", 2)
        monkeypatch.setattr(supervisor, "_PARK_IDLE_S", 0.1)

        class Peak(list):
            peak = 0

            def append(self, w):
                super().append(w)
                self.peak = max(self.peak, len(self))

        wd._parked = Peak()
        before = self._dispatch_threads()
        barrier = threading.Barrier(5, timeout=5.0)
        callers = [
            threading.Thread(
                target=supervisor.watchdog_call,
                args=(barrier.wait,),
                kwargs={"timeout_s": 5.0},
            )
            for _ in range(5)
        ]
        for t in callers:
            t.start()
        for t in callers:
            t.join(10.0)
        # five workers came home at once: two parked, three exited
        assert self._calls() == {"fresh": 5, "parked": 0}
        assert wd._parked.peak == 2
        for _ in range(500):
            if not wd._parked and self._dispatch_threads() <= before:
                break
            time.sleep(0.01)
        assert wd._parked == [] and self._dispatch_threads() <= before
        # and the next call starts a thread again
        assert supervisor.watchdog_call(lambda: 8, timeout_s=5.0) == 8
        assert self._calls() == {"fresh": 6, "parked": 0}

    def test_many_callers_each_get_their_own_answer(self, wd):
        """Stress, time-bounded: more callers than cores hand jobs to and take
        workers from the one parked list at a short switch interval; a lost
        update there would cross two calls' answers or lose a call."""
        import sys

        callers_n, each = 24, 150
        wrong, errs = [], []

        def caller(k):
            try:
                for i in range(each):
                    got = supervisor.watchdog_call(
                        lambda k=k, i=i: (k, i), timeout_s=10.0
                    )
                    if got != (k, i):
                        wrong.append((k, i, got))
            except BaseException as e:  # noqa: BLE001 — the assert shows it
                errs.append(e)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            callers = [
                threading.Thread(target=caller, args=(k,))
                for k in range(callers_n)
            ]
            for t in callers:
                t.start()
            for t in callers:
                t.join(60.0)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in callers)
        assert errs == [] and wrong == []
        calls = self._calls()
        assert calls["fresh"] + calls["parked"] == callers_n * each
        assert calls["parked"] > calls["fresh"]
        assert len(wd._parked) <= supervisor._PARKED_MAX

    def test_zero_timeout_takes_no_worker(self, wd):
        assert supervisor.watchdog_call(lambda: 3, timeout_s=0) == 3
        assert supervisor.watchdog_call(lambda: 4, timeout_s=-1.0) == 4
        assert self._calls() == {"fresh": 0, "parked": 0}
        assert wd._parked == []

    def test_a_dispatch_marks_its_spans_and_is_parked_after_the_first(self, wd):
        """Through ``_launch_verify`` / ``_fetch_launched``: ``verify.dispatch``
        and ``verify.fetch`` say which worker served them, and of ten
        dispatches' twenty calls only the first two at most start a thread."""
        from cometbft_tpu.libs import tracing

        pubs, msgs, sigs = _mixed_batch(np.random.default_rng(37), 9)
        runner = _CountingRunner()
        supervisor.set_device_runner(runner)
        tracing.get_tracer().reset()
        for _ in range(10):
            h = supervisor.dispatch_verify(pubs, msgs, sigs)
            assert list(supervisor.fetch_verify(h)) == _oracle(pubs, msgs, sigs)
        assert runner.calls == 10
        marks = [
            sp["attrs"]["worker"] for sp in tracing.get_tracer().tail(0)
            if sp["stage"] in ("verify.dispatch", "verify.fetch")
        ]
        assert len(marks) == 20 and set(marks[2:]) == {"parked"}
        calls = self._calls()
        assert calls["fresh"] <= 2 and calls["fresh"] + calls["parked"] == 20
        assert tracing.trace_document(0, 0)["dispatch"]["watchdog_calls"] == calls


# -- differential: fault modes vs host oracle --------------------------------


class TestFaultDifferential:
    """For every injected fault mode the final accept bits are bitwise
    equal to the pure-host oracle and no exception reaches the caller —
    the acceptance criterion of ISSUE 4."""

    @pytest.mark.parametrize("entry", ["verify_batch", "dispatch_fetch"])
    @pytest.mark.parametrize("mode", ["raise", "hang", "wrong_shape", "flap"])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_verify_batch_bitwise_oracle(self, mode, seed, entry, monkeypatch):
        """Both entries of the one launch and the one fetch: the
        synchronous chain walk and the async primitive every cell takes."""
        from cometbft_tpu.ops import verify as ov

        if mode == "hang":
            monkeypatch.setenv("COMETBFT_TPU_DISPATCH_TIMEOUT_MS", "60")
        rng = np.random.default_rng(seed)
        pubs, msgs, sigs = _mixed_batch(rng, 12)
        runner = _CountingRunner()
        supervisor.set_device_runner(runner)
        shim = supervisor.FaultyBackend(
            mode, hang_s=0.25, fail_n=2, pass_n=1
        )
        supervisor.set_fault_injector(shim)
        for _ in range(4):  # several batches: breaker transitions included
            if entry == "verify_batch":
                got = ov.verify_batch(pubs, msgs, sigs)
            else:
                got = supervisor.fetch_verify(
                    supervisor.dispatch_verify(pubs, msgs, sigs)
                )
            assert list(got) == _oracle(pubs, msgs, sigs)
            assert dispatch_stats.snapshot()["inflight_depth"] == 0

    @pytest.mark.parametrize("mode", ["raise", "hang", "wrong_shape", "flap"])
    def test_chip_kind_bitwise_oracle(self, mode, monkeypatch):
        """The served road's own kind, ``chip`` (a launch at dispatch, a
        fetch later), under every fault mode, a wedge at launch included."""
        if mode == "hang":
            monkeypatch.setenv("COMETBFT_TPU_DISPATCH_TIMEOUT_MS", "60")
        pubs, msgs, sigs = _mixed_batch(np.random.default_rng(5), 12)
        _install_fake_chip(monkeypatch, [(pubs, msgs, sigs)])
        supervisor.set_fault_injector(
            supervisor.FaultyBackend(mode, hang_s=0.25, fail_n=2, pass_n=1)
        )
        kinds = set()
        for _ in range(4):
            h = supervisor.dispatch_verify(pubs, msgs, sigs)
            kinds.add(h.kind)
            assert list(supervisor.fetch_verify(h)) == _oracle(
                pubs, msgs, sigs
            )
            assert dispatch_stats.snapshot()["inflight_depth"] == 0
        assert kinds <= {"chip", "supervised"}
        assert ("chip" in kinds) == (mode in ("wrong_shape", "flap"))

    def test_chip_dispatch_is_one_pack_and_two_watchdog_calls(
        self, monkeypatch
    ):
        """What the benchmark reads of a dispatch on the served path: one
        ``verify.pack``, ``verify.dispatch`` > ``verify.launch`` under one
        deadline, ``verify.fetch`` under a second, one dispatch counted,
        ``dispatch_hist`` fed the fetch wait."""
        from cometbft_tpu.libs import tracing

        pubs, msgs, sigs = _mixed_batch(np.random.default_rng(6), 9)
        launches = _install_fake_chip(monkeypatch, [(pubs, msgs, sigs)], 0.02)
        deadlines = []
        real = supervisor._WATCHDOG.call
        monkeypatch.setattr(
            supervisor._WATCHDOG, "call",
            lambda fn, t: deadlines.append(t) or real(fn, t),
        )
        tracing.get_tracer().reset()
        dispatch_stats.reset()
        h = supervisor.dispatch_verify(pubs, msgs, sigs)
        assert (h.kind, h.error, len(deadlines)) == ("chip", None, 1)
        assert list(supervisor.fetch_verify(h)) == _oracle(pubs, msgs, sigs)
        assert len(deadlines) == 2 and launches == [("xla", 32)]
        spans = tracing.get_tracer().tail(20)
        # ISSUE 35: the pack says which path packed and how it splits
        from cometbft_tpu.ops import verify as ov

        path = "python" if ov._native_pack_into() is None else "native"
        halves = ["verify.pack.glue", "verify.pack.native"][: 1 + (path == "native")]
        # ISSUE 36: the launch's three parts and the fetch's copy, each timed
        # on its watchdog worker and recorded by the caller, in this order
        parts = [
            "verify.launch.lookup", "verify.launch.put", "verify.launch.call",
        ]
        assert [sp["stage"] for sp in spans] == halves + [
            "verify.pack", "verify.launch", *parts, "verify.dispatch",
            "verify.fetch.pull", "verify.fetch",
        ]
        by = {sp["stage"]: sp for sp in spans}
        assert by["verify.pack"]["attrs"]["path"] == path
        for half in halves:
            assert by[half]["parent"] == by["verify.pack"]["span"]
        assert by["verify.launch"]["parent"] == by["verify.dispatch"]["span"]
        launch = by["verify.launch"]
        for part in parts:
            assert by[part]["parent"] == launch["span"]
        # one after the other inside the launch, and they fill it
        edges = [launch["t0"]] + [
            t for part in parts for t in (by[part]["t0"], by[part]["t1"])
        ] + [launch["t1"]]
        assert edges == sorted(edges)
        assert sum(by[part]["dur_ms"] for part in parts) == pytest.approx(
            launch["dur_ms"], abs=1.0
        )
        pull = by["verify.fetch.pull"]
        assert pull["parent"] == by["verify.fetch"]["span"]
        assert pull["dur_ms"] >= 20.0  # the stand-in's copy sleeps in it
        assert by["verify.fetch"]["dur_ms"] >= pull["dur_ms"]
        assert by["verify.dispatch"]["attrs"]["pipelined"] is True
        snap = dispatch_stats.snapshot()
        assert (snap["dispatches"], snap["lanes_total"]) == (1, 32)
        assert snap["lanes_used"] == 9 and snap["inflight_depth"] == 0
        hist = snap["dispatch_hist"]["xla-32"]
        assert hist["count"] == 1 and hist["sum"] >= 0.02  # the fetch wait

    def test_a_chip_launch_is_one_transfer(self, monkeypatch):
        """The kind ``chip`` places ONE buffer: the packed one, whose views
        the five arrays are, handed to the executable as its one input;
        ``verify.launch.put`` says so (``transfers`` 1)."""
        import jax
        import jax.numpy as jnp

        from cometbft_tpu.libs import tracing
        from cometbft_tpu.ops import verify as ov

        pubs, msgs, sigs = _mixed_batch(np.random.default_rng(9), 9)
        _install_fake_chip(monkeypatch, [(pubs, msgs, sigs)])
        handed = []
        fake = ov.bucket_executable

        def recording(backend, lanes):
            call, info = fake(backend, lanes)
            return (lambda *a, **k: handed.append((a, k)) or call(*a, **k)), info

        monkeypatch.setattr(ov, "bucket_executable", recording)
        placed = []
        for mod, name in ((jnp, "asarray"), (jax, "device_put")):
            real = getattr(mod, name)
            monkeypatch.setattr(
                mod, name,
                lambda x, *a, _real=real, **k: placed.append(np.shape(x))
                or _real(x, *a, **k),
            )
        tracing.get_tracer().reset()
        h = supervisor.dispatch_verify(pubs, msgs, sigs)
        assert h.kind == "chip"
        assert placed == [(ov.packed_rows(32), 32)]
        ((args, kwargs),) = handed
        assert kwargs == {} and len(args) == 1
        assert args[0].shape == (ov.packed_rows(32), 32)
        assert args[0].dtype == np.uint8
        assert list(supervisor.fetch_verify(h)) == _oracle(pubs, msgs, sigs)
        (put,) = [
            sp for sp in tracing.get_tracer().tail(20)
            if sp["stage"] == "verify.launch.put"
        ]
        assert put["attrs"]["transfers"] == len(placed) == 1

    def test_abandoned_launch_worker_records_no_lap(self, monkeypatch):
        """ISSUE 36: the launch's laps are closed on the watchdog worker and
        recorded by the caller only after ``watchdog_call`` returned: a
        worker abandoned inside the executable's call leaves nothing, not
        even the two laps (lookup, put) it had already closed."""
        from cometbft_tpu.libs import tracing
        from cometbft_tpu.ops import verify as ov

        monkeypatch.setenv("COMETBFT_TPU_DISPATCH_TIMEOUT_MS", "40")
        released = threading.Event()

        def wedged_executable(backend, lanes):
            def call(packed):
                time.sleep(0.3)
                released.set()
                return np.zeros(lanes, dtype=bool)

            return call, None

        monkeypatch.setattr(ov, "bucket_executable", wedged_executable)
        pubs, msgs, sigs = _mixed_batch(np.random.default_rng(7), 5)
        tracing.get_tracer().reset()
        h = supervisor.dispatch_verify(pubs, msgs, sigs)
        assert h.kind == "supervised"
        assert isinstance(h.error, supervisor.DispatchTimeoutError)
        assert list(supervisor.fetch_verify(h)) == _oracle(pubs, msgs, sigs)
        assert released.wait(10)
        time.sleep(0.05)  # the worker has closed its last lap by now
        tr = tracing.get_tracer()
        recorded = {sp["stage"] for sp in tr.tail(100)} | set(tr.stage_totals())
        assert not {st for st in recorded if st.startswith("verify.launch")}
        assert "verify.dispatch" in recorded

    def test_verify_segments_under_fault(self):
        from cometbft_tpu.ops import verify as ov

        rng = np.random.default_rng(2)
        work = [_mixed_batch(rng, k) for k in (3, 5, 2)]
        supervisor.set_device_runner(_CountingRunner())
        supervisor.set_fault_injector(supervisor.FaultyBackend("raise"))
        outs = ov.verify_segments(work)
        assert [list(o) for o in outs] == [_oracle(*w) for w in work]

    def test_overlapped_under_fault_and_degraded(self):
        from cometbft_tpu.ops import verify as ov

        rng = np.random.default_rng(3)
        work = [_mixed_batch(rng, k) for k in (4, 3)]
        runner = _CountingRunner()
        supervisor.set_device_runner(runner)
        supervisor.set_fault_injector(supervisor.FaultyBackend("raise"))
        outs = ov.verify_batches_overlapped(work)
        assert [list(o) for o in outs] == [_oracle(*w) for w in work]
        # pre-open every device breaker: the window must resolve on host
        # with zero device calls
        for b in supervisor.device_chain():
            br = bh.registry().breaker(b)
            for _ in range(br.threshold):
                br.record_failure(RuntimeError("down"))
        calls = runner.calls
        outs = ov.verify_batches_overlapped(work)
        assert [list(o) for o in outs] == [_oracle(*w) for w in work]
        assert runner.calls == calls  # no device dispatch while open

    @pytest.mark.parametrize("wedged_at", ["launch", "fetch"])
    def test_overlapped_window_pays_one_deadline(self, wedged_at, monkeypatch):
        """The breaker opens at its third failure; a window on a stuck
        device stops waiting at its first."""
        from cometbft_tpu.ops import verify as ov

        monkeypatch.setenv("COMETBFT_TPU_DISPATCH_TIMEOUT_MS", "60")
        rng = np.random.default_rng(7)
        work = [_mixed_batch(rng, k) for k in (4, 3, 5, 2)]
        if wedged_at == "launch":
            supervisor.set_device_runner(_CountingRunner())
            supervisor.set_fault_injector(
                supervisor.FaultyBackend("hang", hang_s=0.25)
            )
        else:
            launches = _install_fake_chip(monkeypatch, work, sleep_s=0.25)
        outs = ov.verify_batches_overlapped(work)
        assert [list(o) for o in outs] == [_oracle(*w) for w in work]
        assert bh.snapshot()["watchdog_fires"] == 1
        assert dispatch_stats.snapshot()["inflight_depth"] == 0
        if wedged_at == "fetch":
            assert len(launches) == 4  # all in flight before the first fetch

    @pytest.mark.parametrize(
        "sizes", [(), (0, 0), (7,), (3, 5, 2), (30, 30, 10)],
        ids=["no-work", "empty", "one", "classes", "past-largest"],
    )
    def test_segments_sync_and_async_agree(self, sizes, monkeypatch):
        """``verify_segments`` and ``fetch_segments(dispatch_segments)``:
        one concatenate, one split, the oracle's bits by segment."""
        from cometbft_tpu.ops import verify as ov

        # the largest bucket cut to 64 lanes: 70 signatures overflow it
        monkeypatch.setattr(ov, "_BUCKETS", [32, 64])
        rng = np.random.default_rng(8)
        work = [_mixed_batch(rng, k) for k in sizes]
        supervisor.set_device_runner(_CountingRunner())
        want = [_oracle(*w) for w in work]
        sync = ov.verify_segments(work)
        h = ov.dispatch_segments(work)
        assert (h.sup is not None) == (0 < sum(sizes) <= 64)
        assert [list(o) for o in sync] == want
        assert [list(o) for o in ov.fetch_segments(h)] == want
        assert dispatch_stats.snapshot()["inflight_depth"] == 0

    def test_no_invalid_signature_error_from_infra(self, monkeypatch):
        """A commit whose signatures are all VALID must verify even while
        the device backend is down — the infra failure must not surface
        as InvalidSignatureError (misattribution) or any other error."""
        monkeypatch.setenv("COMETBFT_TPU_SIGCACHE", "0")
        from cometbft_tpu.crypto import batch as cbatch

        supervisor.set_device_runner(_CountingRunner())
        supervisor.set_fault_injector(supervisor.FaultyBackend("raise"))
        bv = cbatch.TpuBatchVerifier()
        for i in range(4):
            seed = bytes([i + 1]) * 32
            msg = b"commit-vote-%d" % i
            bv.add(ref.pubkey_from_seed(seed), msg, ref.sign(seed, msg))
        ok, bits = bv.verify()
        assert ok and all(bits)


# -- bisection / quarantine --------------------------------------------------


class TestBisectQuarantine:
    def _poison_setup(self, n=7):
        rng = np.random.default_rng(9)
        seeds = [bytes(rng.integers(0, 256, 32, dtype=np.uint8)) for _ in range(n)]
        pubs = [ref.pubkey_from_seed(s) for s in seeds]
        msgs = [b"m%d" % i for i in range(n)]
        sigs = [ref.sign(s, m) for s, m in zip(seeds, msgs)]
        poison = pubs[3]  # a VALID signature whose presence kills the kernel

        def inject(backend, p, m, s):
            if poison in p:
                raise RuntimeError("poisoned input kills kernel")
            return None

        return pubs, msgs, sigs, inject

    def test_single_poisoned_input_quarantined(self):
        from cometbft_tpu.ops import verify as ov

        pubs, msgs, sigs, inject = self._poison_setup()
        supervisor.set_device_runner(_CountingRunner())
        supervisor.set_fault_injector(inject)
        got = ov.verify_batch(pubs, msgs, sigs)
        # the poisoned input is VALID: quarantine verdicts it True via the
        # host oracle instead of blaming the signer for the crash
        assert list(got) == _oracle(pubs, msgs, sigs) == [True] * 7
        snap = bh.snapshot()
        assert snap["quarantined"] == 1
        assert snap["demotions"] == 0  # backend stayed in service
        assert snap["breakers"]["xla"]["state"] == bh.CLOSED

    def test_systematic_failure_demotes_without_quarantine(self):
        from cometbft_tpu.ops import verify as ov

        rng = np.random.default_rng(4)
        pubs, msgs, sigs = _mixed_batch(rng, 6)
        supervisor.set_device_runner(_CountingRunner())
        supervisor.set_fault_injector(supervisor.FaultyBackend("raise"))
        got = ov.verify_batch(pubs, msgs, sigs)
        assert list(got) == _oracle(pubs, msgs, sigs)
        snap = bh.snapshot()
        assert snap["quarantined"] == 0  # abandoned bisect is not a quarantine
        assert snap["demotions"] >= 1

    def test_bisect_kill_switch(self, monkeypatch):
        from cometbft_tpu.ops import verify as ov

        monkeypatch.setenv("COMETBFT_TPU_SUPERVISOR_BISECT", "0")
        pubs, msgs, sigs, inject = self._poison_setup()
        supervisor.set_device_runner(_CountingRunner())
        supervisor.set_fault_injector(inject)
        got = ov.verify_batch(pubs, msgs, sigs)
        assert list(got) == _oracle(pubs, msgs, sigs)
        snap = bh.snapshot()
        assert snap["quarantined"] == 0
        assert snap["demotions"] >= 1  # straight demotion instead


# -- breaker-driven demotion / re-promotion over the chain -------------------


class TestChainBreaker:
    def test_open_breaker_skips_device_then_repromotes(self, monkeypatch):
        from cometbft_tpu.ops import verify as ov

        monkeypatch.setenv("COMETBFT_TPU_BREAKER_THRESHOLD", "2")
        clk = _FakeClock()
        bh.registry().set_clock(clk)
        runner = _CountingRunner()
        supervisor.set_device_runner(runner)
        supervisor.set_fault_injector(supervisor.FaultyBackend("raise"))

        seed = b"\x05" * 32
        pub, msg = ref.pubkey_from_seed(seed), b"chain"
        sig = ref.sign(seed, msg)
        args = ([pub, pub], [msg, msg], [sig, sig])

        ov.verify_batch(*args)  # failure 1 (bisect counted separately)
        ov.verify_batch(*args)  # failure 2 -> open
        assert bh.snapshot()["breakers"]["xla"]["state"] == bh.OPEN
        calls = runner.calls
        assert list(ov.verify_batch(*args)) == [True, True]  # host tier
        assert runner.calls == calls  # device skipped while open

        supervisor.clear_fault_injector()
        clk.advance(1.05)  # past the initial backoff: half-open
        assert list(ov.verify_batch(*args)) == [True, True]  # probe passes
        snap = bh.snapshot()
        assert snap["breakers"]["xla"]["state"] == bh.CLOSED
        assert snap["repromotions"] == 1
        assert runner.calls > calls  # the probe reached the device


# -- secp256k1 / BLS fallback routing ----------------------------------------


class TestSecpBlsRouting:
    def test_secp_device_failure_trips_breaker(self, monkeypatch):
        from cometbft_tpu.crypto import batch as cbatch
        from cometbft_tpu.crypto.secp256k1 import Secp256k1PrivKey
        from cometbft_tpu.ops import secp_verify as sv

        monkeypatch.setenv("COMETBFT_TPU_SECP_DEVICE", "1")
        monkeypatch.setenv("COMETBFT_TPU_SIGCACHE", "0")
        monkeypatch.setenv("COMETBFT_TPU_BREAKER_THRESHOLD", "2")
        # fake clock: the pure-Python secp signing between batches can take
        # >1 s of real time under full-suite load, which would let the
        # breaker's backoff elapse and legitimately grant a half-open probe
        bh.registry().set_clock(_FakeClock())
        calls = {"n": 0}

        def boom(*a, **k):
            calls["n"] += 1
            raise RuntimeError("device died")

        monkeypatch.setattr(sv, "verify_batch", boom)

        privs = [
            Secp256k1PrivKey.from_secret(b"sup-secp-%d" % i) for i in range(2)
        ]
        msgs = [b"sm%d" % i for i in range(2)]

        def run_batch():
            bv = cbatch.Secp256k1BatchVerifier()
            for p, m in zip(privs, msgs):
                bv.add(p.pub_key(), m, p.sign(m))
            return bv.verify()

        ok, bits = run_batch()  # device raises -> host fallback verdicts
        assert ok and bits == [True, True]
        snap = bh.snapshot()
        assert snap["breakers"]["secp_device"]["failures_total"] == 1
        assert snap["demotions"] == 1

        run_batch()  # failure 2 -> breaker opens
        assert bh.snapshot()["breakers"]["secp_device"]["state"] == bh.OPEN
        n = calls["n"]
        ok, bits = run_batch()  # breaker open: device not even attempted
        assert ok and bits == [True, True]
        assert calls["n"] == n

    def test_bls_g1_failure_trips_breaker_host_result_identical(
        self, monkeypatch
    ):
        from cometbft_tpu.crypto import batch as cbatch
        from cometbft_tpu.crypto import bls12381 as bls
        from cometbft_tpu.ops import bls_g1 as g1

        monkeypatch.setenv("COMETBFT_TPU_BLS_DEVICE", "1")

        def boom(*a, **k):
            raise RuntimeError("g1 kernel died")

        monkeypatch.setattr(g1, "batch_scalar_mul", boom)
        pks = [bls.G1_GEN, bls.E1.mul_scalar(bls.G1_GEN, 7)]
        rs = [3, 11]
        got = cbatch.BlsBatchVerifier._scaled_pubkeys(pks, rs)
        want = [bls.E1.mul_scalar(pk, r) for pk, r in zip(pks, rs)]
        assert [bls.E1.affine(a) for a in got] == [
            bls.E1.affine(b) for b in want
        ]
        snap = bh.snapshot()
        assert snap["breakers"]["bls_g1"]["failures_total"] == 1
        assert snap["demotions"] == 1


# -- sigcache write-back audit -----------------------------------------------


class TestSigcacheAudit:
    def test_writeback_skips_non_definitive_verdicts(self, monkeypatch):
        from cometbft_tpu.crypto import sigcache

        sigcache.reset_cache()
        seed = b"\x09" * 32
        pub, msg = ref.pubkey_from_seed(seed), b"audit"
        sig = ref.sign(seed, msg)
        part = sigcache.partition_misses([pub], [msg], [sig])
        assert part.miss == [0]
        sigcache.writeback(part, [None])
        assert part.bits[0] is None  # hole stays a hole, not False
        assert sigcache.get_cache().get(pub, msg, sig) is None  # NOT cached
        sigcache.reset_cache()

    def test_infra_none_surfaces_as_backend_error_not_false_bit(self):
        from cometbft_tpu.crypto import batch as cbatch
        from cometbft_tpu.crypto import sigcache

        sigcache.reset_cache()
        seed = b"\x0a" * 32
        pub, msg = ref.pubkey_from_seed(seed), b"audit2"
        sig = ref.sign(seed, msg)  # VALID

        class _InfraVerifier(cbatch._CollectingVerifier):
            PUB_SIZES = (32,)
            SIG_SIZES = (64,)

            def _verify_pending(self, pubs, msgs, sigs):
                return [None] * len(pubs)  # "could not judge"

        bv = _InfraVerifier()
        bv.add(pub, msg, sig)
        with pytest.raises(bh.BackendError):
            bv.verify()
        # the valid signature was not negative-cached by the infra failure
        cpu = cbatch.CpuBatchVerifier()
        cpu.add(pub, msg, sig)
        ok, bits = cpu.verify()
        assert ok and bits == [True]
        sigcache.reset_cache()

    def test_verify_pending_raise_caches_nothing(self):
        from cometbft_tpu.crypto import batch as cbatch
        from cometbft_tpu.crypto import sigcache

        sigcache.reset_cache()
        seed = b"\x0b" * 32
        pub, msg = ref.pubkey_from_seed(seed), b"audit3"
        sig = ref.sign(seed, msg)

        class _RaisingVerifier(cbatch._CollectingVerifier):
            PUB_SIZES = (32,)
            SIG_SIZES = (64,)

            def _verify_pending(self, pubs, msgs, sigs):
                raise RuntimeError("backend exploded")

        bv = _RaisingVerifier()
        bv.add(pub, msg, sig)
        with pytest.raises(RuntimeError):
            bv.verify()
        assert len(sigcache.get_cache()) == 0
        sigcache.reset_cache()


# -- metrics exposition ------------------------------------------------------


class TestMetricsExposition:
    def test_breaker_metrics_exposed(self):
        from cometbft_tpu.libs.metrics import NodeMetrics

        br = bh.registry().breaker("xla")
        for _ in range(br.threshold):
            br.record_failure(RuntimeError("down"))
        bh.registry().record_demotion("xla")
        m = NodeMetrics(namespace="t_sup")
        page = m.registry.expose()
        assert 't_sup_crypto_backend_breaker_state{backend="xla"} 2' in page
        assert "t_sup_crypto_backend_demotions 1" in page
        assert "t_sup_crypto_backend_open_breakers 1" in page
        # scrape never initializes jax: the reads above went through
        # backend_health only (guaranteed by construction — backend_health
        # imports no jax; this line documents the contract)
