"""Verify-pipeline flight recorder (ISSUE 9, libs/tracing +
docs/observability.md): span model, anomaly forensics, deterministic
replay, histogram surfaces, jax isolation, and the /debug/verify_trace
document."""

import json
import subprocess
import sys
import threading

import numpy as np
import pytest

from cometbft_tpu import verifysched
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.libs import tracing
from cometbft_tpu.libs.histo import Histo
from cometbft_tpu.libs.metrics import NodeMetrics
from cometbft_tpu.ops import dispatch_stats, supervisor
from cometbft_tpu.verifysched import stats as sstats


@pytest.fixture(autouse=True)
def _fresh_tracer(monkeypatch):
    monkeypatch.delenv("COMETBFT_TPU_TRACE", raising=False)
    monkeypatch.delenv("COMETBFT_TPU_TRACE_DIR", raising=False)
    monkeypatch.delenv("COMETBFT_TPU_TRACE_DUMP_ALL", raising=False)
    tracing.reset_tracer()
    yield
    tracing.reset_tracer()


class TestSpans:
    def test_nesting_assigns_parent_and_trace(self):
        tr = tracing.get_tracer()
        with tr.span("verify.commit", height=3) as root:
            with tr.span("verify.dispatch", tier="xla") as child:
                pass
        spans = tr.tail(10)
        child_d = next(s for s in spans if s["stage"] == "verify.dispatch")
        root_d = next(s for s in spans if s["stage"] == "verify.commit")
        assert child_d["parent"] == root_d["span"]
        assert child_d["trace"] == root_d["trace"] == root_d["span"]
        assert root.trace_id == root.span_id
        assert child.parent_id == root.span_id

    def test_sibling_threads_get_separate_traces(self):
        tr = tracing.get_tracer()
        done = threading.Event()

        def other():
            with tr.span("sched.flush"):
                pass
            done.set()

        with tr.span("verify.commit"):
            t = threading.Thread(target=other)
            t.start()
            assert done.wait(5)
            t.join()
        spans = {s["stage"]: s for s in tr.tail(10)}
        # the other thread's span is a ROOT (ambient stack is per-thread)
        assert "parent" not in spans["sched.flush"]
        assert spans["sched.flush"]["trace"] != spans["verify.commit"]["trace"]

    def test_ring_bound_counts_drops(self):
        tr = tracing.Tracer(ring_size=16)
        for i in range(40):
            with tr.span("consensus.vote", i=i):
                pass
        s = tr.snapshot()
        assert s["ring_len"] == 16
        assert s["spans_recorded"] == 40
        assert s["spans_dropped"] == 24
        # the ring keeps the NEWEST spans
        assert tr.tail(16)[-1]["attrs"]["i"] == 39

    def test_error_annotated_on_exception(self):
        tr = tracing.get_tracer()
        with pytest.raises(ValueError):
            with tr.span("verify.batch"):
                raise ValueError("boom")
        sp = tr.tail(1)[0]
        assert sp["attrs"]["error"] == "ValueError"

    def test_injectable_clock_and_reset_determinism(self):
        """Same ops + same fake clock => identical span streams (the sim's
        byte-identical-dump property in miniature)."""

        def replay():
            t = [0.0]

            def clock():
                t[0] += 0.5
                return t[0]

            tr = tracing.get_tracer()
            tr.reset()
            tr.set_clock(clock)
            with tr.span("verify.commit", height=1):
                with tr.span("verify.dispatch", tier="xla", lanes=32):
                    pass
            out = [json.dumps(s, sort_keys=True) for s in tr.tail(10)]
            tr.set_clock(None)
            return out

        assert replay() == replay()

    def test_kill_switch_compiles_to_noop(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "0")
        tr = tracing.get_tracer()
        ctx = tr.span("verify.commit")
        # the shared null span: no allocation, no recording
        assert ctx is tracing._NULL_SPAN
        with ctx as sp:
            sp.set(anything=1)
        assert tr.snapshot()["spans_recorded"] == 0

    def test_the_switch_is_read_live_from_the_environment(self, monkeypatch):
        """``enabled()`` looks the switch up in the environment's own
        mapping: set, changed, deleted or the whole ``os.environ`` replaced
        while the process runs, the next span site sees it."""
        import os

        monkeypatch.delenv("COMETBFT_TPU_TRACE", raising=False)
        assert tracing.enabled()
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "0")
        assert not tracing.enabled()
        assert tracing.now() > 0 and tracing.handoff("sched.queue", 0.0) is None
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")
        assert tracing.enabled()
        os.environ["COMETBFT_TPU_TRACE"] = "0"
        assert not tracing.enabled()
        monkeypatch.delenv("COMETBFT_TPU_TRACE")
        assert tracing.enabled()
        other = type(os.environ)(
            {tracing._TRACE_KEY: tracing._TRACE_OFF},
            os.environ.encodekey, os.environ.decodekey,
            os.environ.encodevalue, os.environ.decodevalue,
        )
        monkeypatch.setattr(os, "environ", other)
        assert not tracing.enabled()

    def test_stage_summary_percentiles(self):
        tr = tracing.get_tracer()
        t = [0.0]
        tr.set_clock(lambda: t[0])
        for ms in (1, 2, 3, 100):
            with tr.span("verify.commit"):
                t[0] += ms / 1e3
        tr.set_clock(None)
        s = tr.stage_summary()["verify.commit"]
        assert s["count"] == 4
        assert s["max_ms"] == pytest.approx(100.0)
        assert s["p50_ms"] <= s["p99_ms"] <= s["max_ms"]


class TestAnomalies:
    def test_dump_written_and_parseable(self, tmp_path, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_TRACE_DIR", str(tmp_path))
        tr = tracing.get_tracer()
        with tr.span("verify.dispatch", tier="xla", lanes=32, dispatch=7):
            pass
        path = tr.record_anomaly(
            "watchdog_fire", tier="xla", lanes=32, dispatch=7
        )
        assert path is not None
        lines = [json.loads(l) for l in open(path)]
        head, spans = lines[0], lines[1:]
        # the header attributes the fire to a (bucket, tier, dispatch)
        assert head["anomaly"] == "watchdog_fire"
        assert head["attrs"] == {"tier": "xla", "lanes": 32, "dispatch": 7}
        assert spans and spans[-1]["stage"] == "verify.dispatch"
        assert spans[-1]["attrs"]["dispatch"] == 7

    def test_first_per_kind_dumps_rest_counted(self, tmp_path, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_TRACE_DIR", str(tmp_path))
        tr = tracing.get_tracer()
        p1 = tr.record_anomaly("queue_shed", cls="bulk")
        p2 = tr.record_anomaly("queue_shed", cls="bulk")
        p3 = tr.record_anomaly("breaker_open", backend="xla")
        assert p1 is not None and p2 is None and p3 is not None
        s = tr.snapshot()
        assert s["anomalies"] == {"queue_shed": 2, "breaker_open": 1}
        assert s["dump_count"] == 2
        # reset re-arms the per-kind dump latch
        tr.reset()
        assert tr.record_anomaly("queue_shed") is not None

    def test_dump_all_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("COMETBFT_TPU_TRACE_DUMP_ALL", "1")
        tr = tracing.get_tracer()
        assert tr.record_anomaly("queue_shed") is not None
        assert tr.record_anomaly("queue_shed") is not None
        assert tr.snapshot()["dump_count"] == 2

    def test_no_dir_counts_without_dump(self):
        tr = tracing.get_tracer()
        assert tr.record_anomaly("quarantine", tier="xla") is None
        assert tr.snapshot()["anomalies"] == {"quarantine": 1}

    def test_disabled_tracer_still_counts(self, tmp_path, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "0")
        monkeypatch.setenv("COMETBFT_TPU_TRACE_DIR", str(tmp_path))
        tr = tracing.get_tracer()
        assert tr.record_anomaly("watchdog_fire") is None  # no dump
        assert tr.snapshot()["anomalies"] == {"watchdog_fire": 1}


class TestJaxIsolation:
    def test_metrics_tracing_and_trace_doc_never_import_jax(self):
        """Importing libs/metrics + libs/tracing, rendering a full
        /metrics exposition AND building the /debug/verify_trace document
        must never initialize jax — the forensic surfaces have to work
        exactly when the accelerator is the thing that is sick.  (Extends
        the PR-2 lazy-import guarantee to the new endpoints.)"""
        code = (
            "import sys\n"
            "from cometbft_tpu.libs.metrics import NodeMetrics\n"
            "from cometbft_tpu.libs import tracing\n"
            "with tracing.span('verify.commit', height=1):\n"
            "    pass\n"
            "tracing.record_anomaly('queue_shed')\n"
            "out = NodeMetrics().registry.expose()\n"
            "assert 'cometbft_sched_latency_seconds_bucket' in out\n"
            "assert 'cometbft_trace_spans_total' in out\n"
            "assert 'cometbft_crypto_dispatch_seconds' in out\n"
            "import json\n"
            "doc = tracing.trace_document()\n"
            "json.dumps(doc)\n"
            "for section in ('backend', 'sigcache', 'dispatch', 'sched',\n"
            "                'warmboot', 'ingest'):\n"
            "    assert 'error' not in doc[section], (section, doc[section])\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "print('ISOLATED')\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "ISOLATED" in out.stdout


class TestHistograms:
    def test_histo_buckets_and_quantiles(self):
        h = Histo(bounds=(0.001, 0.01, 0.1))
        for v in (0.0005, 0.002, 0.002, 0.05, 5.0):
            h.observe(v)
        d = h.to_dict()
        assert d["counts"] == [1, 2, 1, 1]
        assert d["count"] == 5
        assert d["p50"] == 0.01
        assert d["p99"] == 0.1  # overflow reports the largest bound

    def test_sched_latency_histograms_render_on_metrics(self):
        sstats.reset()
        sstats.record_verdict(0, 0.002, queue_wait_s=0.0015, device_s=0.0005)
        sstats.record_verdict(2, 0.3, queue_wait_s=0.29, device_s=0.01)
        sstats.record_shed_fallback(2, 0.4)
        out = NodeMetrics().registry.expose()
        assert (
            'cometbft_sched_latency_seconds_bucket{class="consensus",le="0.0025"} 1'
            in out
        )
        assert 'cometbft_sched_queue_wait_seconds_bucket{class="bulk"' in out
        assert 'cometbft_sched_device_seconds_bucket{class="consensus"' in out
        assert 'cometbft_sched_shed_fallback{class="bulk"} 1' in out
        # shed fallback samples stay in the latency record
        snap = sstats.snapshot()
        assert snap["latency_hist"]["bulk"]["count"] == 2
        assert snap["shed_fallback"]["bulk"] == 1
        sstats.reset()

    def test_dispatch_time_histogram_per_tier_bucket(self):
        dispatch_stats.reset()
        dispatch_stats.record_dispatch(32, 4)
        dispatch_stats.record_dispatch(128, 100)
        dispatch_stats.record_dispatch_time("xla", 32, 0.004)
        dispatch_stats.record_dispatch_time("pallas", 128, 0.05)
        snap = dispatch_stats.snapshot()
        assert snap["buckets"] == {32: 1, 128: 1}
        assert snap["dispatch_hist"]["xla-32"]["count"] == 1
        assert snap["dispatch_hist"]["pallas-128"]["count"] == 1
        out = NodeMetrics().registry.expose()
        assert 'cometbft_crypto_dispatch_seconds_bucket{shape="xla-32"' in out
        assert 'cometbft_crypto_verify_commit_seconds_bucket' in out
        dispatch_stats.reset()


def _oracle_runner(backend, pubs, msgs, sigs, lanes):
    out = np.zeros(lanes, dtype=bool)
    out[: len(pubs)] = [
        ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)
    ]
    return out


@pytest.fixture
def sched_env(monkeypatch):
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


def _triple(i=0, tag=b"tr"):
    import hashlib

    seed = hashlib.sha256(b"%s-%d" % (tag, i)).digest()
    msg = b"%s-msg-%d" % (tag, i)
    return ref.pubkey_from_seed(seed), msg, ref.sign(seed, msg)


class TestSchedulerIntegration:
    def test_queue_wait_recorded_separately_from_device(self, sched_env):
        """The PR's verifysched latency bug-hunt: submit->verdict used to
        be one conflated number.  A paused dispatcher makes queue wait, a
        delayed device stand-in makes device time: each delay has to land
        in its own field, and the two add up to the latency.  Sleeps are
        lower bounds of what they delay, so nothing here compares two wall
        times of a loaded CPU with each other."""
        import time as _time

        def slow_runner(*a):
            _time.sleep(0.02)
            return _oracle_runner(*a)

        supervisor.set_device_runner(slow_runner)
        sched = verifysched.get_scheduler()
        sched.pause()
        pub, msg, sig = _triple(0)
        fut = sched.submit(pub, msg, sig, verifysched.PRIO_CONSENSUS)
        _time.sleep(0.05)  # real wall: queue wait accrues while paused
        sched.resume()
        assert fut.result(timeout=30) is True
        snap = sstats.snapshot()
        qw = snap["queue_wait_hist"]["consensus"]
        dv = snap["device_hist"]["consensus"]
        lat = snap["latency_hist"]["consensus"]
        assert qw["count"] == dv["count"] == lat["count"] == 1
        # submit, THEN drain, THEN verdict: the pause lands in queue wait,
        # the stand-in's delay in device time
        assert snap["queue_wait_seconds"]["consensus"] >= 0.05
        assert qw["sum"] >= 0.05
        assert dv["sum"] >= 0.02
        assert lat["sum"] == pytest.approx(qw["sum"] + dv["sum"], abs=1e-6)

    def test_flush_emits_span_and_interval(self, sched_env):
        tracing.get_tracer().reset()
        pub, msg, sig = _triple(1)
        assert verifysched.verify_segment_sync([pub], [msg], [sig]) == [True]
        pub2, msg2, sig2 = _triple(2)
        assert verifysched.verify_segment_sync(
            [pub2], [msg2], [sig2]
        ) == [True]
        spans = [
            s
            for s in tracing.get_tracer().tail(100)
            if s["stage"] == "sched.flush"
        ]
        assert len(spans) >= 2
        assert spans[0]["attrs"]["items"] >= 1
        assert "lanes" in spans[0]["attrs"]
        # second flush recorded an interval sample
        assert sstats.snapshot()["flush_interval_hist"]["count"] >= 1

    def test_shed_emits_anomaly_span_and_latency_sample(
        self, sched_env, tmp_path, monkeypatch
    ):
        """A shed must not vanish from the latency record: the fallback
        sync verify emits a span + a histogram sample, and the first shed
        dumps the flight recorder."""
        monkeypatch.setenv("COMETBFT_TPU_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("COMETBFT_TPU_SCHED_QUEUE", "1")
        verifysched.reset_scheduler()
        tracing.get_tracer().reset()
        sched = verifysched.get_scheduler()
        sched.pause()
        try:
            pubs, msgs, sigs = zip(*[_triple(i, b"shed") for i in range(4)])
            futs, admitted = sched.submit_segment(
                pubs, msgs, sigs, verifysched.PRIO_BLOCKSYNC
            )
            assert admitted == 1  # cap 1: the rest shed
        finally:
            sched.resume()
        for f in futs:
            assert f.result(timeout=30) == [True]
        # the scheduler-level wrappers run the fallback; drive one directly
        from cometbft_tpu.crypto.keys import Ed25519PubKey

        monkeypatch.setenv("COMETBFT_TPU_SCHED_QUEUE", "1")
        snap0 = sstats.snapshot()
        sched.pause()
        try:
            filler = _triple(99, b"fill")
            sched.submit(*filler, verifysched.PRIO_BLOCKSYNC)
            pub, msg, sig = _triple(100, b"fall")
            ok = verifysched.verify_cached(Ed25519PubKey(pub), msg, sig)
            assert ok is True
        finally:
            sched.resume()
        snap = sstats.snapshot()
        assert (
            snap["shed_fallback"]["bulk"]
            > snap0["shed_fallback"]["bulk"] - 1
        )
        assert snap["shed_fallback"]["bulk"] >= 1
        spans = [
            s
            for s in tracing.get_tracer().tail(200)
            if s["stage"] == "sched.shed_fallback"
        ]
        assert spans, "shed fallback must emit a span"
        anomalies = tracing.get_tracer().snapshot()["anomalies"]
        assert anomalies.get("queue_shed", 0) >= 1
        assert tracing.get_tracer().snapshot()["dump_count"] >= 1


class TestSupervisorSpans:
    def test_watchdog_fire_attributed_and_dumped(
        self, sched_env, tmp_path, monkeypatch
    ):
        """The acceptance property: a watchdog fire's anomaly dump
        attributes it to a specific (bucket, tier, dispatch)."""
        monkeypatch.setenv("COMETBFT_TPU_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("COMETBFT_TPU_DISPATCH_TIMEOUT_MS", "40")
        tracing.get_tracer().reset()
        supervisor.set_fault_injector(
            supervisor.FaultyBackend("hang", hang_s=0.2)
        )
        try:
            pubs, msgs, sigs = zip(*[_triple(i, b"wd") for i in range(3)])
            from cometbft_tpu.ops import verify as ov

            bits = ov.verify_batch(list(pubs), list(msgs), list(sigs))
            assert bits.all()  # host tier answered definitively
        finally:
            supervisor.clear_fault_injector()
        snap = tracing.get_tracer().snapshot()
        assert snap["anomalies"].get("watchdog_fire", 0) >= 1
        assert snap["dumps"], "watchdog fire must dump the ring"
        path = tmp_path / snap["dumps"][0]
        lines = [json.loads(l) for l in open(path)]
        head = lines[0]
        assert head["anomaly"] == "watchdog_fire"
        # specific (bucket, tier, dispatch) attribution
        assert head["attrs"]["tier"] == "xla"
        assert head["attrs"]["lanes"] >= 3
        assert head["attrs"]["dispatch"] >= 1
        # the failed dispatch span is the dump's most recent matching span
        failed = [
            s
            for s in lines[1:]
            if s["stage"] == "verify.dispatch"
            and s["attrs"].get("error") == "DispatchTimeoutError"
        ]
        assert failed
        assert failed[-1]["attrs"]["dispatch"] == head["attrs"]["dispatch"]
        # host fallback span exists and shares the verify.batch trace
        stages = {s["stage"] for s in tracing.get_tracer().tail(100)}
        assert "supervisor.host_fallback" in stages
        assert "verify.batch" in stages

    def test_breaker_open_anomaly(self, sched_env, tmp_path, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_TRACE_DIR", str(tmp_path))
        tracing.get_tracer().reset()
        supervisor.set_fault_injector(supervisor.FaultyBackend("raise"))
        monkeypatch.setenv("COMETBFT_TPU_SUPERVISOR_BISECT", "0")
        from cometbft_tpu.crypto import backend_health

        try:
            from cometbft_tpu.ops import verify as ov

            br = backend_health.registry().breaker("xla")
            for i in range(br.threshold):
                pub, msg, sig = _triple(i, b"open")
                ov.verify_batch([pub], [msg], [sig])
        finally:
            supervisor.clear_fault_injector()
        snap = tracing.get_tracer().snapshot()
        assert snap["anomalies"].get("breaker_open", 0) >= 1


def _commit(n=5, height=3):
    from tests.test_types import (
        CHAIN_ID, _block_id, _make_commit, _mk_validators,
    )

    privs, vals, _ = _mk_validators(n)
    bid = _block_id()
    commit = _make_commit(privs, vals, bid, height=height)
    # the vote set verified (and cached) every vote on its way in: the
    # request under test starts from a cold cache and an empty ring
    sigcache.reset_cache()
    tracing.get_tracer().reset()
    return CHAIN_ID, vals, bid, height, commit


def _by_stage(spans):
    out = {}
    for s in spans:
        out.setdefault(s["stage"], []).append(s)
    return out


class TestRequestTree:
    """ISSUE 26: one request is one tree across the caller, dispatcher and
    completion threads, through the device-runner seam."""

    def test_one_request_one_trace_with_the_tables_parents(self, sched_env):
        from cometbft_tpu.types.validation import verify_commit_light

        verify_commit_light(*_commit())
        by = _by_stage(tracing.get_tracer().tail(200))
        one = {k: v[0] for k, v in by.items() if len(v) == 1}
        parents = {
            "verify.commit": None,
            "commit.sign_bytes": "verify.commit",
            "batch.verify": "verify.commit",
            "sched.segment": "batch.verify",
            "sched.submit": "sched.segment",
            "sched.wait": "sched.segment",
            "sched.flush": "sched.segment",
            "sched.dispatch": "sched.flush",
            "sched.fetch": "sched.flush",
            "sched.resolve": "sched.flush",
            # the seam defers the whole attempt to the completion thread
            "verify.pack": "sched.fetch",
            "verify.dispatch": "sched.fetch",
            "verify.launch": "verify.dispatch",
            # ISSUE 36: the hand-offs between the three threads ...
            "sched.queue": "sched.flush",
            "sched.handoff.fetch": "sched.flush",
            "sched.landed": "sched.flush",
            "sched.handoff.wake": "sched.wait",
            # ... the copy inside the fetch's watchdog, the seam's parts
            "verify.fetch": "sched.fetch",
            "verify.fetch.pull": "verify.fetch",
            "batch.add": "batch.verify",
            "batch.keys": "batch.verify",
            "batch.lookup": "batch.verify",
            "batch.writeback": "batch.verify",
        }
        assert set(parents) <= set(one), sorted(by)
        root = one["verify.commit"]
        assert {one[k]["trace"] for k in parents} == {root["span"]}
        for stage, parent in parents.items():
            want = one[parent]["span"] if parent else None
            assert one[stage].get("parent") == want, stage
        # the flush's link to the request it serves is its parent (above);
        # the queue wait is a span of its own that ends where the flush
        # begins (the ``queue_wait_s`` attribute it replaced said as much)
        flush, queue = one["sched.flush"], one["sched.queue"]
        assert flush["attrs"]["segments"] == 1
        assert 0 <= queue["dur_ms"] and queue["t1"] <= flush["t0"]
        assert one["sched.submit"]["t0"] <= queue["t0"] <= queue["t1"]
        assert one["batch.verify"]["attrs"] == {"sigs": 4, "hits": 0, "keys": 4}
        assert one["verify.launch"]["attrs"]["lanes"] >= 4
        assert one["verify.pack"]["attrs"]["bytes"] > 0
        # the entry's basic checks are inside the request's span now
        assert one["verify.commit"]["attrs"]["entries"] == 4

    def test_the_entry_says_its_path_and_whether_it_built_the_sets_record(
        self, sched_env
    ):
        """ISSUE 31: ``path`` (``fast``: the one pass over a regular commit;
        ``loop``: anything else, and the look-up by address) and
        ``set_facts`` (``built`` by this call / ``kept`` from an earlier
        one) on both of the entry's spans."""
        from cometbft_tpu.types import validation as tv

        chain_id, vals, bid, height, commit = _commit()
        vals.validators = list(vals.validators)  # an assignment: no record
        said = []
        for call in (
            lambda: tv.verify_commit_light(chain_id, vals, bid, height, commit),
            lambda: tv.verify_commit(chain_id, vals, bid, height, commit),
            lambda: tv.verify_commit_light_trusting(chain_id, vals, commit),
        ):
            tracing.get_tracer().reset()
            call()
            (root,) = [
                s for s in tracing.get_tracer().tail(200)
                if s["stage"].startswith("verify.commit")
            ]
            a = root["attrs"]
            said.append((root["stage"], a["mode"], a["path"], a["set_facts"]))
        assert said == [
            ("verify.commit", "light", "fast", "built"),
            ("verify.commit", "full", "fast", "kept"),
            ("verify.commit.trusting", "trusting", "loop", "kept"),
        ]
        # an irregular commit is the loops' to judge, and says so
        commit.signatures[0].validator_address = bytes(20)
        tracing.get_tracer().reset()
        with pytest.raises(tv.CommitVerificationError, match="address mismatch"):
            tv.verify_commit_light(chain_id, vals, bid, height, commit)
        (root,) = tracing.get_tracer().tail(200)
        assert root["attrs"]["path"] == "loop"
        assert root["attrs"]["error"] == "CommitVerificationError"

    def test_flush_serving_two_callers_lists_both_traces(self, sched_env):
        tracing.get_tracer().reset()
        sched = verifysched.get_scheduler()
        sched.pause()
        roots, errs = {}, []

        def caller(i):
            try:
                with tracing.span("verify.commit", height=i) as sp:
                    roots[i] = sp.trace_id
                    pub, msg, sig = _triple(i, b"two")
                    assert verifysched.verify_segment_sync(
                        [pub], [msg], [sig]
                    ) == [True]
            except BaseException as e:  # noqa: BLE001 — relayed below
                errs.append(e)

        threads = [threading.Thread(target=caller, args=(i,)) for i in (1, 2)]
        for t in threads:
            t.start()
        import time as _time

        deadline = _time.monotonic() + 30
        while sched.pending() < 2 and _time.monotonic() < deadline:
            _time.sleep(0.005)
        assert sched.pending() == 2
        sched.resume()
        for t in threads:
            t.join(30)
            assert not t.is_alive()
        assert not errs, errs
        by = _by_stage(tracing.get_tracer().tail(200))
        (flush,) = by["sched.flush"]
        assert flush["attrs"]["items"] == 2
        assert flush["attrs"]["segments"] == 2
        assert len(set(roots.values())) == 2
        # the first item's submitter is the parent: the flush is in THAT
        # request's tree, and the other request finds it by the one
        # ``sched.queue`` span, which begins at the older of the two enqueues
        segments = {s["span"]: s["trace"] for s in by["sched.segment"]}
        assert flush["trace"] == segments[flush["parent"]]
        assert flush["trace"] in roots.values()
        (queue,) = by["sched.queue"]
        assert queue["parent"] == flush["span"]
        assert queue["t0"] <= min(s["t1"] for s in by["sched.submit"])
        # each caller records its own wake, under its own wait
        waits = {s["span"]: s["trace"] for s in by["sched.wait"]}
        assert sorted(waits[s["parent"]] for s in by["sched.handoff.wake"]) == (
            sorted(roots.values())
        )
        assert by["sched.resolve"][0]["parent"] == flush["span"]

    def test_caller_stages_sum_to_the_request(self, sched_env):
        """entry self + sign bytes + seam self + submit + wait is the
        request less the segment bridge's own lines: within 2%."""
        import time as _time

        from cometbft_tpu.types.validation import verify_commit_light

        def slow_runner(*a):
            _time.sleep(0.05)  # a request long beside the bridge's lines
            return _oracle_runner(*a)

        args = _commit()
        supervisor.set_device_runner(slow_runner)
        verify_commit_light(*args)
        dur = {
            s["stage"]: s["dur_ms"] for s in tracing.get_tracer().tail(200)
        }
        entry_self = (
            dur["verify.commit"] - dur["commit.sign_bytes"] - dur["batch.verify"]
        )
        seam_self = dur["batch.verify"] - dur["sched.segment"]
        stages = (
            entry_self + dur["commit.sign_bytes"] + seam_self
            + dur["sched.submit"] + dur["sched.wait"]
        )
        assert entry_self >= 0 and seam_self >= 0
        assert stages <= dur["verify.commit"]
        assert stages == pytest.approx(dur["verify.commit"], rel=0.02)

    def test_abandoned_watchdog_worker_writes_no_span(
        self, sched_env, monkeypatch
    ):
        import time as _time

        released = threading.Event()

        def wedged_runner(*a):
            _time.sleep(0.3)
            released.set()
            return _oracle_runner(*a)

        monkeypatch.setenv("COMETBFT_TPU_DISPATCH_TIMEOUT_MS", "40")
        supervisor.set_device_runner(wedged_runner)
        tracing.get_tracer().reset()
        from cometbft_tpu.ops import verify as ov

        pub, msg, sig = _triple(0, b"wedge")
        assert ov.verify_batch([pub], [msg], [sig]).all()  # the host answered
        assert released.wait(10)
        _time.sleep(0.05)  # the abandoned worker has closed its lap by now
        tr = tracing.get_tracer()
        by = _by_stage(tr.tail(200))
        assert "verify.launch" not in by
        assert "verify.launch" not in tr.stage_totals()
        assert by["verify.dispatch"][0]["attrs"]["error"] == (
            "DispatchTimeoutError"
        )


class TestHandoffs:
    """ISSUE 36: every hand-off between the path's threads is a completed
    span with one stamp from each of the two threads it joins, recorded by
    the receiving thread, in the request's tree."""

    @staticmethod
    def _threads_by_stage(monkeypatch):
        """Which thread lands each stage in the ring."""
        seen = []
        real = tracing.Tracer._append

        def noting(self, sp):
            seen.append((sp.stage, threading.current_thread().name))
            real(self, sp)

        monkeypatch.setattr(tracing.Tracer, "_append", noting)
        return seen

    def test_each_handoff_on_its_thread_in_the_works_order(
        self, sched_env, monkeypatch
    ):
        from cometbft_tpu.types.validation import verify_commit_light

        args = _commit()
        seen = self._threads_by_stage(monkeypatch)
        verify_commit_light(*args)
        me = threading.current_thread().name
        threads = dict(seen)
        assert len(threads) == len(seen)  # one span a stage in this request
        want = {
            # the receiver records: the dispatcher the queue, the completion
            # thread what the dispatcher handed it, the caller its wake
            "sched.queue": "verify-sched",
            "sched.flush": "verify-sched",
            "sched.handoff.fetch": "verify-sched-fetch",
            "verify.fetch.pull": "verify-sched-fetch",
            "sched.fetch": "verify-sched-fetch",
            "sched.landed": "verify-sched-fetch",
            "sched.resolve": "verify-sched-fetch",
            "sched.wait": me,
            "sched.handoff.wake": me,
            "batch.add": me, "batch.keys": me, "batch.lookup": me,
            "batch.writeback": me,
        }
        assert {k: threads.get(k) for k in want} == want
        order = [stage for stage, _ in seen]
        # a function of the work: the seam's first parts, the flush with its
        # queue first, the completion thread's stages, then the caller's
        chain = [
            "batch.add", "batch.keys", "batch.lookup",
            "sched.queue", "sched.dispatch", "sched.flush",
            "sched.handoff.fetch", "verify.fetch.pull", "verify.fetch",
            "sched.fetch", "sched.landed", "sched.resolve",
            "sched.submit", "sched.wait", "sched.handoff.wake",
            "sched.segment", "batch.writeback", "batch.verify",
            "verify.commit",
        ]
        assert [st for st in order if st in chain] == chain
        # the hand-offs lie where the table says: fetch between the flush
        # and the fetch, landed between fetch and resolve, the wake at the
        # end of the wait
        by = {s["stage"]: s for s in tracing.get_tracer().tail(200)}
        assert by["sched.flush"]["t1"] <= by["sched.handoff.fetch"]["t0"]
        assert by["sched.handoff.fetch"]["t1"] <= by["sched.fetch"]["t0"]
        assert by["sched.fetch"]["t1"] <= by["sched.landed"]["t0"]
        assert by["sched.landed"]["t1"] <= by["sched.resolve"]["t0"]
        assert by["sched.resolve"]["t1"] <= by["sched.handoff.wake"]["t0"]
        assert by["sched.handoff.wake"]["t1"] == by["sched.wait"]["t1"]

    def test_the_named_pieces_of_a_wait_add_up_to_the_wait(self, sched_env):
        """``wait_unseen_ms`` by hand: queue + flush + hand-off + fetch +
        landed + resolve + wake is the caller's wait.  On a clock that the
        device stand-in moves by 50 ms and every READING of it by a
        microsecond (so the result is the work's, not the machine's: a
        loaded test host cannot stretch a gap), what lies between two
        neighbours is the recorder's own few readings: under 1% of the
        wait."""
        t = [5000.0]

        def ticking():
            t[0] += 1e-6
            return t[0]

        def slow_runner(*a):
            t[0] += 0.05
            return _oracle_runner(*a)

        supervisor.set_device_runner(slow_runner)
        tr = tracing.get_tracer()
        tr.reset()
        tr.set_clock(ticking)
        try:
            pub, msg, sig = _triple(3, b"sum")
            with tracing.span("verify.commit"):
                assert verifysched.verify_segment_sync(
                    [pub], [msg], [sig]
                ) == [True]
        finally:
            tr.set_clock(None)
        dur = {s["stage"]: s["dur_ms"] for s in tr.tail(100)}
        pieces = sum(
            dur[st] for st in (
                "sched.queue", "sched.flush", "sched.handoff.fetch",
                "sched.fetch", "sched.landed", "sched.resolve",
                "sched.handoff.wake",
            )
        )
        assert dur["sched.wait"] > 50.0
        assert pieces == pytest.approx(dur["sched.wait"], rel=0.01)

    def test_a_votes_wake_hangs_under_its_wait(self, sched_env):
        from cometbft_tpu.crypto.keys import Ed25519PubKey

        tracing.get_tracer().reset()
        pub, msg, sig = _triple(4, b"vote")
        with tracing.span("consensus.vote") as vote:
            assert verifysched.verify_cached(Ed25519PubKey(pub), msg, sig)
            # answered from the cache the second time: no queue, no hand-off
            assert verifysched.verify_cached(Ed25519PubKey(pub), msg, sig)
        by = _by_stage(tracing.get_tracer().tail(100))
        assert len(by["sched.wait"]) == 2 and len(by["sched.handoff.wake"]) == 1
        (wake,), first_wait = by["sched.handoff.wake"], by["sched.wait"][0]
        assert wake["parent"] == first_wait["span"]
        assert first_wait["parent"] == vote.span_id
        assert wake["trace"] == vote.trace_id
        assert first_wait["t0"] <= wake["t0"] <= wake["t1"] == first_wait["t1"]

    def test_same_seed_runs_on_an_injected_clock_dump_the_same_bytes(
        self, sched_env, tmp_path, monkeypatch
    ):
        """The stamps are the TRACER's clock: on an injected one that only
        the device stand-in moves, two runs of the same requests give the
        same ring and the same anomaly dump, byte for byte."""
        from cometbft_tpu.types.validation import verify_commit_light

        args = _commit()

        def replay(sub):
            d = tmp_path / sub
            monkeypatch.setenv("COMETBFT_TPU_TRACE_DIR", str(d))
            verifysched.reset_scheduler()
            sigcache.reset_cache()
            dispatch_stats.reset()  # the dispatch ordinal is in the spans
            tr = tracing.get_tracer()
            tr.reset()
            t = [1000.0]

            def runner(*a):
                t[0] += 0.004
                return _oracle_runner(*a)

            supervisor.set_device_runner(runner)
            tr.set_clock(lambda: t[0])
            try:
                for _ in range(3):
                    sigcache.reset_cache()
                    verify_commit_light(*args)
                    t[0] += 0.5
                path = tracing.record_anomaly("queue_shed", cls="bulk")
                ring = [json.dumps(s, sort_keys=True) for s in tr.tail(0)]
            finally:
                tr.set_clock(None)
            assert "host.runq_wait" not in tr.stage_totals()
            return ring, open(path, "rb").read()

        first, second = replay("a"), replay("b")
        assert first == second
        stages = {json.loads(line)["stage"] for line in first[0]}
        assert {"sched.queue", "sched.handoff.fetch", "sched.landed",
                "sched.handoff.wake", "verify.fetch.pull"} <= stages

    def test_recorder_off_leaves_one_clock_read_a_handoff(
        self, sched_env, monkeypatch
    ):
        """``COMETBFT_TPU_TRACE=0``: no span, no host sample, no file of the
        host's read; what is left of this PR on the path is the stamp each
        hand-off begins with (the enqueue, the drain, the append to the
        completion thread's queue, the resolve)."""
        import builtins

        from cometbft_tpu.crypto.keys import Ed25519PubKey

        monkeypatch.setenv("COMETBFT_TPU_TRACE", "0")
        tr = tracing.get_tracer()
        reads = [0]

        def clock():
            reads[0] += 1
            return 5.0

        sampled = []
        tr.set_clock(clock)
        tr.set_host_readers({"host.switches": lambda tids: sampled.append(1)})
        opened = []
        real_open = builtins.open

        def noting_open(path, *a, **kw):
            if str(path).startswith(("/proc", "/sys")):
                opened.append(path)
            return real_open(path, *a, **kw)

        monkeypatch.setattr(builtins, "open", noting_open)
        try:
            pub, msg, sig = _triple(5, b"off")
            assert verifysched.verify_cached(Ed25519PubKey(pub), msg, sig)
            assert reads[0] == 4
            pub, msg, sig = _triple(6, b"off")
            assert verifysched.verify_segment_sync([pub], [msg], [sig]) == [True]
            assert reads[0] == 8
        finally:
            tr.set_clock(None)
        assert tr.snapshot()["spans_recorded"] == 0
        assert tr.stage_totals() == {} and tr.host_summary() == {}
        assert not sampled and not opened and not tr._host_tids


class TestHostPressure:
    """ISSUE 36: what the machine did to the process, by second, under
    pseudo-stages of the per-second store."""

    @staticmethod
    def _tracer(sources):
        """A tracer on a hand-moved clock whose host sources are the
        cumulative values in ``sources`` (a missing key: a source this host
        lacks)."""
        tr = tracing.Tracer(ring_size=64)
        t = [50.25]
        tr.set_clock(lambda: t[0])
        tr.set_host_readers(
            {k: (lambda tids, k=k: sources.get(k)) for k in (
                "host.runq_wait", "host.switches", "host.faults",
                "host.throttled",
            )}
        )

        def tick(to):
            t[0] = to
            with tr.span("verify.commit"):
                pass

        return tr, tick

    def test_differences_land_in_the_second_that_passed(self):
        src = {"host.runq_wait": 1.0, "host.switches": 10.0}
        tr, tick = self._tracer(src)
        tick(50.25)  # the first sample: a baseline, nothing stored
        assert not [k for b in tr.stage_seconds().values() for k in b
                    if k.startswith("host.")]
        src.update({"host.runq_wait": 1.125, "host.switches": 13.0})
        tick(50.75)  # the same second: no sample
        tick(51.5)  # second 51 opens: what passed belongs to second 50
        src.update({"host.runq_wait": 1.25, "host.switches": 13.0})
        tick(52.01)
        seconds = tr.stage_seconds()
        assert seconds[50]["host.runq_wait"] == (1, pytest.approx(0.125))
        assert seconds[50]["host.switches"] == (1, pytest.approx(3.0))
        assert seconds[51]["host.runq_wait"] == (1, pytest.approx(0.125))
        assert seconds[51]["host.switches"] == (1, pytest.approx(0.0))
        assert "host.switches" not in seconds[52]  # still open
        # a source this host lacks is left out, not zero
        assert "host.throttled" not in seconds[50]
        assert "host.faults" not in tr.stage_totals()
        # the interval reader carries them with the stages, no new code
        got = tr.stage_totals(50.0, 52.0)
        assert got["host.switches"] == (2, pytest.approx(3.0))
        assert got["verify.commit"][0] == 3

    def test_a_gap_of_several_seconds_is_shared_out(self):
        """A pause of the whole process: nothing ends for four seconds, and
        the next sample's difference is theirs in equal parts."""
        src = {"host.throttled": 0.0}
        tr, tick = self._tracer(src)
        tick(50.25)
        src["host.throttled"] = 2.0
        tick(54.5)
        seconds = tr.stage_seconds()
        assert [seconds[s]["host.throttled"] for s in (50, 51, 52, 53)] == [
            (1, pytest.approx(0.5))
        ] * 4
        assert "verify.commit" not in seconds[52]  # a second of its own
        assert tr.stage_totals(50.0, 54.0)["host.throttled"] == (
            4, pytest.approx(2.0)
        )

    def test_a_source_that_comes_and_goes_and_one_that_raises(self):
        def boom(tids):
            raise OSError("gone")

        src = {"host.faults": 5.0}
        tr, tick = self._tracer(src)
        tr.set_host_readers({
            "host.faults": lambda tids: src.get("host.faults"),
            "host.throttled": boom,
        })
        tick(50.25)
        del src["host.faults"]  # the file went away
        tick(51.25)
        src["host.faults"] = 9.0  # and came back: a new baseline
        tick(52.25)
        src["host.faults"] = 10.0
        tick(53.25)
        seconds = tr.stage_seconds()
        assert "host.faults" not in seconds[50]
        assert "host.faults" not in seconds[51]
        assert seconds[52]["host.faults"] == (1, pytest.approx(1.0))
        assert not [k for b in seconds.values() for k in b
                    if k == "host.throttled"]

    def test_summary_and_document_keep_hosts_counters_apart(self):
        src = {"host.runq_wait": 0.0, "host.switches": 0.0}
        tr, tick = self._tracer(src)
        tick(50.25)
        src.update({"host.runq_wait": 0.03, "host.switches": 7.0})
        tick(51.25)
        src.update({"host.runq_wait": 0.04, "host.switches": 7.0})
        tick(52.25)
        assert set(tr.stage_summary()) == {"verify.commit"}  # durations only
        assert tr.host_summary() == {
            "runq_wait": {"seconds": 2, "total": 0.04, "last": 0.01},
            "switches": {"seconds": 2, "total": 7.0, "last": 0.0},
        }
        tr.reset()
        assert tr.host_summary() == {}

    def test_trace_document_has_the_host_beside_the_stages(self):
        tr = tracing.get_tracer()
        src = {"host.throttled": 0.0}
        t = [10.5]
        tr.set_clock(lambda: t[0])
        tr.set_host_readers({"host.throttled": lambda tids: src["host.throttled"]})
        try:
            for to, v in ((10.5, 0.0), (11.5, 0.25)):
                t[0], src["host.throttled"] = to, v
                with tracing.span("verify.fetch"):
                    pass
            doc = tracing.trace_document(max_spans=4, rounds=0)
        finally:
            tr.set_clock(None)
        assert doc["host"] == {
            "throttled": {"seconds": 1, "total": 0.25, "last": 0.25}
        }
        assert "verify.fetch" in doc["stages"]
        assert "host.throttled" not in doc["stages"]
        json.dumps(doc)

    def test_an_injected_clock_reads_nothing_of_this_host(self, monkeypatch):
        """Virtual seconds are not the host's: with no readers injected a
        tracer on an injected clock samples nothing (a sim's record stays a
        function of its seed)."""
        monkeypatch.setattr(
            tracing, "_default_host_readers",
            lambda: pytest.fail("the host was read on an injected clock"),
        )
        tr = tracing.Tracer(ring_size=16)
        t = [0.0]
        tr.set_clock(lambda: t[0])
        for _ in range(3):
            with tr.span("consensus.vote"):
                t[0] += 0.75
        assert set(tr.stage_totals()) == {"consensus.vote"}

    def test_a_thread_counts_from_its_second_reading_and_leaves_when_gone(self):
        """The per-thread source (run-queue wait) is cumulative over each
        thread's OWN differences: a thread that registers late does not
        bring the wait of its whole life, and one whose reading fails (it
        has exited) counts no further."""
        clocks = {11: 5.0, 12: 100.0}

        def read_one(tid):
            if tid not in clocks:
                raise OSError("no such thread")
            return clocks[tid]

        tids = {11}
        per = tracing._PerThread(read_one)
        assert per(tids) == 0.0  # a baseline, not five seconds
        clocks[11] = 5.25
        tids.add(12)  # registers with a hundred seconds behind it
        assert per(tids) == pytest.approx(0.25)
        clocks.update({11: 5.5, 12: 100.5})
        assert per(tids) == pytest.approx(1.0)
        del clocks[11]  # exited
        clocks[12] = 101.0
        assert per(tids) == pytest.approx(1.5)
        del clocks[12]
        assert per(tids) is None  # nothing to read: left out, not zero

    @pytest.mark.skipif(
        not sys.platform.startswith("linux"), reason="the kernel's own counters"
    )
    def test_this_hosts_own_sources(self, monkeypatch):
        """The default readers on the real clock: each source this host has
        gives a number that does not fall; a kernel that answers
        ``getrusage`` with zeros (a sandbox) has its two counters left out,
        not zero; a real tracer takes the readers up by itself and
        registers the thread that opens a span."""
        import resource

        readers = tracing._default_host_readers()
        assert "host.cpu" in readers
        assert {"host.switches", "host.faults"} <= set(readers)
        tids = {threading.get_native_id()}
        first = {k: r(tids) for k, r in readers.items()}
        sum(i * i for i in range(300000))
        second = {k: r(tids) for k, r in readers.items()}
        assert all(v is not None and v >= 0 for v in first.values())
        assert all(second[k] >= first[k] for k in first)
        assert second["host.cpu"] > first["host.cpu"]
        # the WHOLE process's clock: a thread that never opened a span (the
        # watchdog's workers) is in it
        worker = threading.Thread(
            target=lambda: sum(i * i for i in range(600000))
        )
        worker.start()
        worker.join()
        third = readers["host.cpu"](tids)
        assert third - second["host.cpu"] > 0.5 * (
            second["host.cpu"] - first["host.cpu"]
        )
        zeros = type("ru", (), {"ru_minflt": 0, "ru_nivcsw": 0})()
        monkeypatch.setattr(resource, "getrusage", lambda who: zeros)
        stub = tracing._default_host_readers()
        assert "host.switches" not in stub and "host.faults" not in stub
        monkeypatch.undo()
        tr = tracing.Tracer(ring_size=16)
        with tr.span("verify.commit"):
            pass
        assert tr._host_readers and tr._host_tids == {threading.get_native_id()}


class TestStageTotals:
    def test_totals_over_an_interval_after_the_ring_wrapped(self):
        """No span is dropped from the totals however often the ring has
        wrapped, and an interval reads the whole seconds inside it."""
        tr = tracing.Tracer(ring_size=16)
        t = [100.25]
        tr.set_clock(lambda: t[0])
        ended = []  # (stage, t_end, duration)
        for i in range(80):  # the ring wraps five times
            stage = ("verify.commit", "sched.flush")[i % 2]
            with tr.span(stage):
                t[0] += 0.010 * (1 + i % 3)
            ended.append((stage, t[0], 0.010 * (1 + i % 3)))
            t[0] += 0.071
        assert tr.snapshot()["spans_dropped"] == 64
        lo, hi = 101.5, 105.75  # whole seconds inside: 102, 103, 104
        got = tr.stage_totals(lo, hi)
        for stage in ("verify.commit", "sched.flush"):
            durs = [d for s, e, d in ended if s == stage and 102 <= e < 105]
            assert durs
            assert got[stage][0] == len(durs)
            assert got[stage][1] == pytest.approx(sum(durs))
        # the summary reads the same store: every span, not the ring's 16
        summary = tr.stage_summary()
        assert summary["verify.commit"]["count"] == 40
        assert summary["verify.commit"]["ring_count"] == 8
        assert sum(n for n, _ in tr.stage_totals().values()) == 80
        seconds = tr.stage_seconds()
        assert sum(
            n for bucket in seconds.values() for n, _ in bucket.values()
        ) == 80

    def test_totals_keep_a_bounded_number_of_seconds(self):
        tr = tracing.Tracer(ring_size=16)
        t = [0.0]
        tr.set_clock(lambda: t[0])
        for _ in range(tracing.TOTALS_KEEP_S + 50):
            with tr.span("consensus.vote"):
                t[0] += 0.5
            t[0] += 0.5
        assert len(tr.stage_seconds()) == tracing.TOTALS_KEEP_S
        tr.reset()
        assert tr.stage_totals() == {}


class TestProfilerBridge:
    def test_open_span_lands_in_the_xplane(self, tmp_path):
        """A span open under ``jax.profiler.trace`` is in the ``.xplane.pb``
        as ``tpubft/<stage>``, and so is a lap; with the profiler closed
        the same calls write nothing."""
        import glob

        import jax
        from jax.profiler import ProfileData

        tr = tracing.get_tracer()
        with jax.profiler.trace(str(tmp_path)):
            with tr.span("verify.commit", height=1):
                with tr.lap("verify.launch") as lp:
                    jax.numpy.zeros(8).block_until_ready()
                lp.record(tier="xla")
        with tr.span("sched.flush"):
            pass
        (path,) = glob.glob(
            str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
        )
        names = {
            e.name
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines
            for e in line.events
            if e.name.startswith(tracing.ANNOTATION_PREFIX)
        }
        assert names == {"tpubft/verify.commit", "tpubft/verify.launch"}
        assert {s["stage"] for s in tr.tail(10)} >= {
            "verify.commit", "verify.launch", "sched.flush",
        }


class TestTraceDocument:
    def test_rpc_debug_verify_trace(self):
        from cometbft_tpu.rpc import core as rpccore

        assert rpccore.ROUTES["debug_verify_trace"] == "debug_verify_trace"
        assert rpccore.ROUTES["debug/verify_trace"] == "debug_verify_trace"

        class _Store:
            def height(self):
                return 7

        class _Node:
            block_store = _Store()

        env = rpccore.Environment(_Node())
        with tracing.span("verify.commit", height=7):
            pass
        doc = env.debug_verify_trace(spans=16)
        assert doc["node"]["latest_block_height"] == "7"
        assert doc["tracing"]["spans_recorded"] >= 1
        assert any(s["stage"] == "verify.commit" for s in doc["spans"])
        assert "breakers" in doc["backend"]
        json.dumps(doc)  # the whole thing is one JSON document

    def test_summary_line_parses_in_budget_gate(self):
        sys.path.insert(
            0, str(__import__("pathlib").Path(__file__).parent.parent)
        )
        from scripts import check_tier1_budget as gate

        with tracing.span("verify.commit"):
            pass
        line = tracing.summary_line()
        assert line.startswith("tier1-trace: spans=")
        lines, ok = gate.trace_share(line, wall=700.0)
        assert ok and lines and "flight recorder" in lines[0]
        # an absurd overhead fails the gate
        bad = (
            "tier1-trace: spans=10 dropped=0 anomalies=0 dumps=0 "
            "overhead_s=600.0"
        )
        lines, ok = gate.trace_share(bad, wall=700.0)
        assert not ok and "FAIL" in lines[0]


class TestCrossNode:
    """Cross-node trace correlation (ISSUE 11): TraceContext codec,
    explicit begin/finish/adopt/under span API, orphan tolerance and
    ring-bound behavior under cross-node fan-in."""

    def test_trace_context_roundtrip(self):
        ctx = tracing.TraceContext(0x2A, 0x2B, origin=3)
        dec = tracing.TraceContext.decode(ctx.encode())
        assert dec == ctx
        # origin-less contexts round-trip too (production p2p has no
        # small-integer node index)
        anon = tracing.TraceContext(7, 9)
        assert tracing.TraceContext.decode(anon.encode()) == anon
        # decode accepts an already-decoded context (idempotent)
        assert tracing.TraceContext.decode(ctx) is ctx

    def test_trace_context_garbage_tolerance(self):
        """A malformed context must decode to None, never raise — the
        gossip path treats it as absent (orphan-parent tolerance starts
        at the codec)."""
        bad = [
            None,
            b"2a.2b.3",          # wrong type
            123,
            "",                   # empty
            "2a.2b",              # truncated
            "2a.2b.3.4",          # too many fields
            "zz.2b.3",            # non-hex trace
            "2a.zz.3",            # non-hex span
            "2a.2b.x",            # non-int origin
            "0.2b.3",             # zero trace id
            "-1.2b.3",            # negative
        ]
        for token in bad:
            assert tracing.TraceContext.decode(token) is None, token

    def test_begin_finish_under_links_children(self):
        tr = tracing.get_tracer()
        anchor = tr.begin("consensus.round", h=5, r=0, node=1)
        assert anchor.parent_id is None and anchor.trace_id == anchor.span_id
        with tr.under(anchor):
            with tr.span("verify.commit", height=5):
                pass
        tr.finish(anchor, committed=True)
        spans = {s["stage"]: s for s in tr.tail(10)}
        assert spans["verify.commit"]["trace"] == anchor.trace_id
        assert spans["verify.commit"]["parent"] == anchor.span_id
        assert spans["consensus.round"]["attrs"]["committed"] is True
        # finish is idempotent: a second call must not double-record
        tr.finish(anchor)
        assert tr.snapshot()["spans_recorded"] == 2

    def test_adopt_reparents_rootless_only(self):
        tr = tracing.get_tracer()
        root = tr.begin("consensus.round", h=5, r=0, node=0)
        ctx = tr.ctx_for(root, origin=0)
        member = tr.begin("consensus.round", h=5, r=0, node=2)
        assert tr.adopt(member, ctx)
        assert member.trace_id == root.trace_id
        assert member.parent_id == root.span_id
        assert member.attrs["xnode"] == 0
        # first adoption wins: a second ctx cannot re-root the member
        other = tr.begin("consensus.round", h=5, r=1, node=3)
        assert not tr.adopt(member, tr.ctx_for(other, origin=3))
        assert member.trace_id == root.trace_id
        # a finished span never adopts
        tr.finish(root)
        late = tr.begin("consensus.round", h=6, r=0, node=1)
        tr.finish(late)
        assert not tr.adopt(late, ctx)

    def test_record_span_retroactive(self):
        """consensus.step timing: manufactured spans carry explicit
        timestamps and parent under the round anchor."""
        t = [0.0]

        def clock():
            t[0] += 1.0
            return t[0]

        tr = tracing.get_tracer()
        tr.set_clock(clock)
        try:
            anchor = tr.begin("consensus.round", h=9, r=0)
            tr.record_span(
                "consensus.step", 1.0, 3.5, parent=anchor,
                step="RoundStepPropose", h=9, r=0,
            )
            tr.finish(anchor)
        finally:
            tr.set_clock(None)
        step = next(
            s for s in tr.tail(10) if s["stage"] == "consensus.step"
        )
        assert step["dur_ms"] == 2500.0
        assert step["parent"] == anchor.span_id
        assert step["trace"] == anchor.trace_id

    def test_xnode_kill_switch(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_TRACE_XNODE", "0")
        assert not tracing.xnode_enabled()
        monkeypatch.delenv("COMETBFT_TPU_TRACE_XNODE", raising=False)
        assert tracing.xnode_enabled()
        # the recorder kill switch implies no propagation either
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "0")
        assert not tracing.xnode_enabled()
        # disabled begin/finish/adopt/under degrade to no-ops
        tr = tracing.get_tracer()
        assert tr.begin("consensus.round", h=1, r=0) is None
        tr.finish(None)
        assert not tr.adopt(None, tracing.TraceContext(1, 1))
        with tr.under(None):
            pass
        assert tr.snapshot()["spans_recorded"] == 0


def _mk_round(tr, h, r, proposer, members, commits_per_node=1,
              orphan_root=False):
    """Synthesize one cross-node round on the shared tracer: the proposer
    roots the trace, members adopt its context, each committing node runs
    a verify.commit under its anchor.  ``orphan_root=True`` models a
    crashed proposer: members adopt the context but the root span never
    records."""
    root = tr.begin("consensus.round", h=h, r=r, node=proposer)
    root.set(proposer=True)
    ctx = tr.ctx_for(root, origin=proposer)
    anchors = []
    for node in members:
        sp = tr.begin("consensus.round", h=h, r=r, node=node)
        tr.adopt(sp, ctx)
        anchors.append(sp)
    for sp in [root] + anchors:
        tr.record_span(
            "consensus.step", tr.time(), tr.time(), parent=sp,
            step="RoundStepPropose", h=h, r=r, node=sp.attrs["node"],
        )
        with tr.under(sp):
            for _ in range(commits_per_node):
                with tr.span("verify.commit", height=h, sigs=4):
                    pass
        sp.set(q_prevote_ms=1.5, q_precommit_ms=2.5)
    for sp in anchors:
        tr.finish(sp, committed=True)
    if not orphan_root:
        tr.finish(root, committed=True)
    return root


class TestRoundsReport:
    def test_merged_round_links_commits_to_proposal(self):
        tr = tracing.get_tracer()
        for h in (4, 5):
            _mk_round(tr, h, 0, proposer=0, members=[1, 2, 3])
        rep = tr.rounds_report()
        assert rep["rounds_seen"] == 2
        assert rep["commits_unlinked"] == 0
        assert rep["commits_linked"] == 2 * 4  # 4 nodes x 1 commit x 2 rounds
        g = rep["rounds"][0]
        assert g["h"] == 4 and g["origin"] == 0
        assert g["commits"] == 4
        assert [n["node"] for n in g["nodes"]] == [0, 1, 2, 3]
        assert all(
            n["adopted"] == (n["node"] != 0) for n in g["nodes"]
        )
        assert rep["steps"]["RoundStepPropose"]["count"] == 8
        assert rep["quorum"]["prevote_ms"]["p50_ms"] == 1.5

    def test_orphan_root_tolerated(self):
        """A crashed proposer's root span never records: the group still
        renders — origin unknown, trace recovered from the adopted
        members, commits still linked."""
        tr = tracing.get_tracer()
        _mk_round(tr, 7, 1, proposer=2, members=[0, 1], orphan_root=True)
        rep = tr.rounds_report()
        assert rep["rounds_seen"] == 1
        g = rep["rounds"][0]
        assert g["origin"] is None  # the root is missing...
        assert g["trace"] is not None  # ...but the trace id survived
        assert g["commits"] == 3  # root's commit spans linked by trace id
        assert rep["commits_unlinked"] == 0

    def test_ring_bound_under_cross_node_fan_in(self):
        """A fleet fanning into a small ring: old rounds fall off, drops
        are counted, and the report stays well-formed over the window
        that remains."""
        tr = tracing.Tracer(ring_size=64)
        for h in range(1, 21):  # 20 rounds x 8 nodes >> 64 ring slots
            _mk_round(tr, h, 0, proposer=h % 8,
                      members=[n for n in range(8) if n != h % 8])
        snap = tr.snapshot()
        assert snap["spans_dropped"] > 0
        rep = tr.rounds_report()
        json.dumps(rep, sort_keys=True)  # serializable, no cycles
        assert 0 < rep["rounds_seen"] <= 20
        last = rep["rounds"][-1]
        assert last["h"] == 20
        # the newest round survives complete: root present, all commits
        # linked within the window
        assert last["origin"] == 20 % 8
        assert last["commits"] == 8
        # rounds straddling the ring edge may be partial but never invent
        # linkage failures
        assert rep["commits_unlinked"] == 0
        # last_k trims the timeline but not the aggregates
        rep2 = tr.rounds_report(last_k=2)
        assert len(rep2["rounds"]) == 2
        assert rep2["rounds_seen"] == rep["rounds_seen"]

    def test_trace_document_rounds_section(self):
        tr = tracing.get_tracer()
        _mk_round(tr, 3, 0, proposer=1, members=[0, 2, 3])
        doc = tracing.trace_document(max_spans=8, rounds=4)
        assert doc["rounds"]["rounds_seen"] == 1
        assert doc["rounds"]["rounds"][0]["origin"] == 1
        json.dumps(doc)
        # rounds=0 skips the section body (health-only probes)
        doc0 = tracing.trace_document(max_spans=0, rounds=0)
        assert doc0["rounds"] == {}

    def test_rootless_non_proposer_never_claims_origin(self):
        """A node that never adopted (partitioned away, or propagation
        off) records a rootless round span too — it must NOT overwrite
        the round's origin/trace even when it lands after the real
        proposer's span in the ring."""
        tr = tracing.get_tracer()
        root = _mk_round(tr, 11, 0, proposer=3, members=[0, 1])
        # a partitioned node: same (h, r), rootless, NOT the proposer
        stray = tr.begin("consensus.round", h=11, r=0, node=5)
        tr.finish(stray, committed=False)
        rep = tr.rounds_report()
        g = rep["rounds"][0]
        assert g["origin"] == 3
        assert g["trace"] == root.trace_id
        # the stray still renders as a member, unadopted
        stray_entry = next(n for n in g["nodes"] if n["node"] == 5)
        assert stray_entry["adopted"] is False
        # with propagation off entirely (every node rootless, only the
        # proposer flagged), origin is still exactly the proposer
        tr.reset()
        for node in (0, 1, 2):
            sp = tr.begin("consensus.round", h=12, r=0, node=node)
            if node == 1:
                sp.set(proposer=True)
            tr.finish(sp, committed=True)
        g = tr.rounds_report()["rounds"][0]
        assert g["origin"] == 1
