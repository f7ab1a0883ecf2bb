"""Disk-fault supervisor tests (libs/diskguard, docs/storage-robustness.md):
policy enforcement, deterministic injection, retry/degrade discipline,
the kill switch, the durable-IO lint, and the /metrics + trace_document
surfaces."""

from __future__ import annotations

import errno
import os

import pytest

from cometbft_tpu.libs import diskguard as dg
from cometbft_tpu.libs import storage_stats, tracing


@pytest.fixture(autouse=True)
def _clean_guard(monkeypatch, tmp_path):
    """Fresh stats/plan per test; anomaly dumps land under tmp."""
    monkeypatch.setenv("COMETBFT_TPU_TRACE_DIR", str(tmp_path / "flight"))
    prev = dg.set_fault_plan(None)
    storage_stats.reset()
    tracing.reset_tracer()
    yield
    dg.set_fault_plan(prev)
    dg.set_sleeper(None)
    storage_stats.reset()
    tracing.reset_tracer()


def _anomalies() -> dict:
    return tracing.get_tracer().snapshot()["anomalies"]


class TestPolicyMap:
    def test_fail_stop_surfaces(self):
        for s in ("wal", "privval", "state"):
            assert dg.policy(s) == dg.FAIL_STOP

    def test_degradable_surfaces(self):
        for s in ("blackbox", "exec_cache", "indexer", "status"):
            assert dg.policy(s) == dg.DEGRADE

    def test_unknown_surface_defaults_to_degrade(self):
        # a new subsystem must opt IN to halting the node
        assert dg.policy("totally-new-surface") == dg.DEGRADE


class TestGuard:
    def test_success_records_op(self):
        out = dg.guard("wal", "append", lambda: 42, path="/x/wal")
        assert out == 42
        snap = storage_stats.snapshot()["surfaces"]["wal"]
        assert snap["writes"] == 1 and snap["fatals"] == 0

    def test_fsync_counts_separately(self):
        dg.guard("wal", "fsync", lambda: None)
        snap = storage_stats.snapshot()["surfaces"]["wal"]
        assert snap["fsyncs"] == 1 and snap["writes"] == 0

    def test_fail_stop_raises_storage_fatal(self):
        plan = dg.FaultPlan()
        plan.add(surface="wal", err=errno.ENOSPC)
        dg.set_fault_plan(plan)
        with pytest.raises(dg.StorageFatal) as ei:
            dg.guard("wal", "append", lambda: 1, path="/x/wal")
        assert ei.value.surface == "wal"
        assert ei.value.op == "append"
        assert ei.value.io_errno == errno.ENOSPC
        snap = storage_stats.snapshot()["totals"]
        assert snap["fatals"] == 1 and snap["fatal"]
        assert _anomalies().get("disk_fatal") == 1

    def test_fail_stop_never_retries(self):
        # even a TRANSIENT errno halts a fail-stop surface immediately:
        # consensus must not advance on a disk that is guessing
        plan = dg.FaultPlan()
        plan.add(surface="privval", err=errno.EIO, count=1)
        dg.set_fault_plan(plan)
        with pytest.raises(dg.StorageFatal):
            dg.guard("privval", "write", lambda: 1)
        assert storage_stats.snapshot()["totals"]["retries"] == 0

    def test_real_oserror_fail_stops_too(self):
        def boom():
            raise OSError(errno.EIO, "real disk error")

        with pytest.raises(dg.StorageFatal):
            dg.guard("state", "set", boom)

    def test_degrade_transient_retries_recover(self):
        sleeps = []
        dg.set_sleeper(sleeps.append)
        plan = dg.FaultPlan()
        plan.add(surface="blackbox", err=errno.EIO, count=2)
        dg.set_fault_plan(plan)
        out = dg.guard("blackbox", "write", lambda: "ok")
        assert out == "ok"
        snap = storage_stats.snapshot()["surfaces"]["blackbox"]
        assert snap["retries"] == 2 and snap["drops"] == 0
        # exponential backoff: second sleep is double the first
        assert len(sleeps) == 2 and sleeps[1] == 2 * sleeps[0]
        assert "disk_fault" not in _anomalies()

    def test_degrade_exhausted_budget_drops_and_reraises(self):
        dg.set_sleeper(lambda _s: None)
        plan = dg.FaultPlan()
        plan.add(surface="blackbox", err=errno.EIO)  # unbounded
        dg.set_fault_plan(plan)
        with pytest.raises(OSError) as ei:
            dg.guard("blackbox", "write", lambda: "ok")
        assert not isinstance(ei.value, dg.StorageFatal)
        snap = storage_stats.snapshot()["surfaces"]["blackbox"]
        assert snap["drops"] == 1 and snap["retries"] == dg.retries()
        assert _anomalies().get("disk_fault") == 1

    def test_degrade_enospc_not_transient(self):
        # a full disk does not heal in milliseconds: no retry tax
        plan = dg.FaultPlan()
        plan.add(surface="exec_cache", err=errno.ENOSPC)
        dg.set_fault_plan(plan)
        with pytest.raises(OSError):
            dg.guard("exec_cache", "store", lambda: 1)
        snap = storage_stats.snapshot()["surfaces"]["exec_cache"]
        assert snap["retries"] == 0 and snap["drops"] == 1

    def test_kill_switch_bypasses_everything(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_DISKGUARD", "0")
        plan = dg.FaultPlan()
        plan.add(surface="wal", err=errno.ENOSPC)
        dg.set_fault_plan(plan)
        assert dg.guard("wal", "append", lambda: "raw") == "raw"
        # no injection consumed, no stats recorded
        assert plan._rules[0].seen == 0
        assert storage_stats.snapshot()["surfaces"] == {}


class TestFaultRules:
    def test_count_window(self):
        plan = dg.FaultPlan()
        plan.add(surface="status", err=errno.EIO, begin=1, count=2)
        dg.set_fault_plan(plan)
        dg.set_sleeper(lambda _s: None)
        monkey_retries = os.environ.get("COMETBFT_TPU_DISKGUARD_RETRIES")
        os.environ["COMETBFT_TPU_DISKGUARD_RETRIES"] = "0"
        try:
            results = []
            for _ in range(4):
                try:
                    dg.guard("status", "write", lambda: "ok")
                    results.append(True)
                except OSError:
                    results.append(False)
            # ordinal 0 clean, 1-2 faulted, 3 clean
            assert results == [True, False, False, True]
        finally:
            if monkey_retries is None:
                os.environ.pop("COMETBFT_TPU_DISKGUARD_RETRIES", None)
            else:
                os.environ["COMETBFT_TPU_DISKGUARD_RETRIES"] = monkey_retries

    def test_path_and_op_filters(self):
        plan = dg.FaultPlan()
        rule = plan.add(
            surface="wal", op="fsync", path_substr="node1/", err=errno.EIO
        )
        dg.set_fault_plan(plan)
        # wrong path: clean; wrong op: clean; both right: fault
        dg.guard("wal", "fsync", lambda: 1, path="/root/node2/cs.wal")
        dg.guard("wal", "append", lambda: 1, path="/root/node1/cs.wal")
        with pytest.raises(dg.StorageFatal):
            dg.guard("wal", "fsync", lambda: 1, path="/root/node1/cs.wal")
        assert rule.seen == 1  # only the fully-matching op advanced it

    def test_latency_rule_slows_but_proceeds(self):
        waits = []
        dg.set_sleeper(waits.append)
        plan = dg.FaultPlan()
        plan.add(surface="status", kind=dg.KIND_LATENCY, latency_s=0.25)
        dg.set_fault_plan(plan)
        assert dg.guard("status", "write", lambda: "done") == "done"
        assert waits == [0.25]
        assert storage_stats.snapshot()["surfaces"]["status"]["writes"] == 1

    def test_torn_write_lands_prefix_then_fails(self, tmp_path):
        plan = dg.FaultPlan()
        plan.add(
            surface="wal", kind=dg.KIND_TORN, err=errno.EIO, torn_keep=5
        )
        dg.set_fault_plan(plan)
        p = tmp_path / "torn.bin"
        with open(p, "wb") as f:
            with pytest.raises(dg.StorageFatal):
                dg.file_write("wal", f, b"0123456789abcdef", path=str(p))
        assert p.read_bytes() == b"01234"  # the torn prefix really landed

    def test_torn_on_degradable_surface_never_retried(self, tmp_path):
        # a torn write models a CRASH: even with a transient errno on a
        # degradable surface it must not be retried — a retry would land
        # the full payload after the flushed prefix (mid-stream garbage
        # no real crash leaves), and with count>1 stack a second prefix
        dg.set_sleeper(lambda _s: None)
        plan = dg.FaultPlan()
        plan.add(
            surface="blackbox", kind=dg.KIND_TORN, err=errno.EIO,
            torn_keep=3, count=5,
        )
        dg.set_fault_plan(plan)
        p = tmp_path / "journal.bin"
        with open(p, "wb") as f:
            with pytest.raises(OSError) as ei:
                dg.file_write("blackbox", f, b"FRAMEFRAME", path=str(p))
        assert not isinstance(ei.value, dg.StorageFatal)
        assert p.read_bytes() == b"FRA"  # exactly one torn prefix
        snap = storage_stats.snapshot()["surfaces"]["blackbox"]
        assert snap["retries"] == 0
        assert snap["drops"] == 1


class TestAtomicWrite:
    def test_success_is_atomic_and_durable(self, tmp_path):
        p = tmp_path / "doc.json"
        dg.atomic_write("privval", str(p), b'{"h":1}')
        assert p.read_bytes() == b'{"h":1}'
        assert not [n for n in os.listdir(tmp_path) if n != "doc.json"]

    def test_replace_failure_keeps_old_file(self, tmp_path):
        p = tmp_path / "doc.json"
        dg.atomic_write("privval", str(p), b"old")
        plan = dg.FaultPlan()
        plan.add(surface="privval", op="replace", err=errno.EIO)
        dg.set_fault_plan(plan)
        with pytest.raises(dg.StorageFatal):
            dg.atomic_write("privval", str(p), b"new")
        # old content intact, no temp litter ("flight" is the fixture's
        # anomaly-dump dir)
        assert p.read_bytes() == b"old"
        assert sorted(
            n for n in os.listdir(tmp_path) if n != "flight"
        ) == ["doc.json"]


class TestDegradableSurfacesKeepAnswers:
    def test_faults_on_every_degradable_surface_change_no_answer(
        self, tmp_path, monkeypatch
    ):
        """ENOSPC on the executable cache and the status files, EIO on the
        black box, all at once: a verify gives the clean run's verdicts
        (the oracle's, two bad lanes included), an executable whose store
        failed still answers as the clean one, the status write and the
        journal degrade to counted drops, and nothing fail-stops."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from cometbft_tpu.crypto import ed25519_ref as ref
        from cometbft_tpu.libs import blackbox
        from cometbft_tpu.ops import aot_cache, supervisor
        from cometbft_tpu.ops import verify as ov
        from cometbft_tpu.parallel import elastic

        seeds = [bytes([i + 1]) * 32 for i in range(12)]
        pubs = [ref.pubkey_from_seed(s) for s in seeds]
        msgs = [b"disk-fault-%d" % i for i in range(12)]
        sigs = [ref.sign(s, m) for s, m in zip(seeds, msgs)]
        sigs[1] = sigs[1][:-1] + bytes([sigs[1][-1] ^ 1])
        sigs[10] = bytes(64)
        oracle = [ref.verify_zip215(*t) for t in zip(pubs, msgs, sigs)]
        monkeypatch.setenv("COMETBFT_TPU_EXEC_CACHE", str(tmp_path / "exec"))
        kernel = jax.jit(lambda x: x * 3 + 1)
        x = jnp.arange(8, dtype=jnp.int32)
        dg.set_sleeper(lambda _s: None)
        supervisor.set_device_runner(elastic.host_oracle_runner)
        try:
            clean = ov.verify_batch(pubs, msgs, sigs).tolist()
            plan = dg.FaultPlan()
            plan.add(surface="exec_cache", err=errno.ENOSPC)
            plan.add(surface="status", err=errno.ENOSPC)
            plan.add(surface="blackbox", err=errno.EIO)
            dg.set_fault_plan(plan)
            faulted = ov.verify_batch(pubs, msgs, sigs).tolist()
            aot_cache.reset_memo()
            call, info = aot_cache.load_or_compile(kernel, (x,), "t-disk")
            with pytest.raises(OSError) as ei:
                dg.atomic_write("status", str(tmp_path / "status.json"), b"{}")
            journal = blackbox.BlackboxJournal(
                str(tmp_path / "bbox"), threaded=True, queue_max=64
            )
            for i in range(16):
                journal.on_anomaly("disk_fault_test", {"i": i}, float(i))
            journal.close(clean=True)
        finally:
            supervisor.clear_device_runner()
            aot_cache.reset_memo()
        assert faulted == clean == oracle
        assert info["exec_cache_write"].startswith("unwritable:")
        assert np.asarray(call(x)).tolist() == np.asarray(kernel(x)).tolist()
        assert not isinstance(ei.value, dg.StorageFatal)
        jstats = journal.stats()
        assert jstats["dropped"] > 0 and jstats["closed"]
        totals = storage_stats.snapshot()["totals"]
        assert totals["drops"] >= 3 and totals["fatals"] == 0


class TestSqliteSurfaces:
    def test_state_surface_fail_stops(self, tmp_path):
        from cometbft_tpu.store.kv import SqliteKV

        kv = SqliteKV(str(tmp_path / "chain.db"), surface="state")
        kv.set(b"k", b"v")
        plan = dg.FaultPlan()
        plan.add(surface="state", err=errno.EIO)
        dg.set_fault_plan(plan)
        with pytest.raises(dg.StorageFatal):
            kv.set(b"k2", b"v2")
        dg.set_fault_plan(None)
        assert kv.get(b"k") == b"v"  # reads unguarded, store usable
        kv.close()

    def test_indexer_surface_degrades(self, tmp_path):
        from cometbft_tpu.store.kv import SqliteKV

        dg.set_sleeper(lambda _s: None)
        kv = SqliteKV(str(tmp_path / "tx_index.db"), surface="indexer")
        plan = dg.FaultPlan()
        plan.add(surface="indexer", err=errno.ENOSPC)
        dg.set_fault_plan(plan)
        with pytest.raises(OSError) as ei:
            kv.write_batch([(b"a", b"1")], [])
        assert not isinstance(ei.value, dg.StorageFatal)
        assert (
            storage_stats.snapshot()["surfaces"]["indexer"]["drops"] == 1
        )
        kv.close()

    def test_integrity_probe_ok(self, tmp_path):
        from cometbft_tpu.store.kv import SqliteKV

        kv = SqliteKV(str(tmp_path / "ok.db"))
        assert kv.integrity_probe()
        kv.close()

    def test_sqlite_lock_contention_retries_before_failstop(self, tmp_path):
        """'database is locked' is lock contention, not a durability
        failure — nothing was persisted, a retry is atomic and safe.  A
        fail-stop store must back off and retry it (another process's
        short-lived lock must not halt the validator), while real
        durability failures still fail-stop on the FIRST error, and
        contention outliving the budget still escalates."""
        import sqlite3

        from cometbft_tpu.store.kv import SqliteKV

        dg.set_sleeper(lambda _s: None)
        kv = SqliteKV(str(tmp_path / "chain.db"), surface="state")
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise sqlite3.OperationalError("database is locked")
            return "ok"

        assert kv._guard("set", flaky) == "ok"
        snap = storage_stats.snapshot()
        assert snap["surfaces"]["state"]["retries"] == 2
        assert not snap["totals"]["fatal"]

        def broken():
            raise sqlite3.DatabaseError("database disk image is malformed")

        with pytest.raises(dg.StorageFatal):
            kv._guard("set", broken)

        def always_locked():
            raise sqlite3.OperationalError("database is locked")

        with pytest.raises(dg.StorageFatal):
            kv._guard("set", always_locked)
        kv.close()

    def test_integrity_probe_only_after_unclean_shutdown(
        self, tmp_path, monkeypatch
    ):
        """quick_check is O(database size): it must NOT run on every
        open — only when a leftover sqlite ``-wal`` sidecar says the
        previous writer died unclean (a clean close checkpoints and
        unlinks it), or when the caller forces ``probe=True``."""
        from cometbft_tpu.store import kv as kvmod

        probed = []
        monkeypatch.setattr(
            kvmod.SqliteKV,
            "integrity_probe",
            lambda self: probed.append(self.path) or True,
        )
        p = str(tmp_path / "c.db")
        kv = kvmod.SqliteKV(p)  # fresh file: nothing to scrub
        kv.set(b"k", b"v")
        assert probed == []
        try:
            # crash image: a second opener while the first still holds
            # the db sees the un-checkpointed -wal sidecar -> probed
            assert os.path.getsize(p + "-wal") > 0
            kv2 = kvmod.SqliteKV(p)
            assert probed == [p]
            kv2.close()
        finally:
            kv.close()
        # clean close checkpointed and unlinked the sidecar -> skipped
        assert not os.path.exists(p + "-wal")
        probed.clear()
        kv3 = kvmod.SqliteKV(p)
        assert probed == []
        kv3.close()
        # explicit override in both directions
        kv4 = kvmod.SqliteKV(p, probe=True)
        assert probed == [p]
        kv4.close()


class TestObservability:
    def test_metrics_render_storage_series(self):
        from cometbft_tpu.libs.metrics import NodeMetrics

        dg.guard("wal", "append", lambda: 1)
        dg.guard("blackbox", "fsync", lambda: 1)
        text = NodeMetrics().registry.expose()
        assert 'cometbft_storage_writes_total{surface="wal"} 1' in text
        assert 'cometbft_storage_fsyncs_total{surface="blackbox"} 1' in text
        assert "cometbft_storage_fatal 0" in text

    def test_trace_document_storage_section(self):
        dg.guard("wal", "append", lambda: 1)
        doc = tracing.trace_document(max_spans=0, rounds=0)
        assert doc["storage"]["surfaces"]["wal"]["writes"] == 1
        assert doc["storage"]["totals"]["fatal"] is False


class TestDiskPolicyLint:
    def test_repo_is_clean(self):
        import pathlib
        import subprocess
        import sys

        root = pathlib.Path(__file__).resolve().parent.parent
        proc = subprocess.run(
            [sys.executable, str(root / "scripts" / "check_diskpolicy.py")],
            capture_output=True,
            text=True,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_new_direct_io_fails(self, tmp_path):
        import pathlib
        import sys

        sys.path.insert(0, str(
            pathlib.Path(__file__).resolve().parent.parent / "scripts"
        ))
        try:
            import check_diskpolicy as lint
        finally:
            sys.path.pop(0)
        pkg = tmp_path / "cometbft_tpu" / "newmod"
        pkg.mkdir(parents=True)
        (pkg / "writer.py").write_text(
            "import os\n"
            "def persist(path, data):\n"
            "    with open(path, 'wb') as f:\n"
            "        f.write(data)\n"
            "        os.fsync(f.fileno())\n"
            "    os.replace(path, path + '.pub')\n"
        )
        violations = lint.scan(tmp_path)
        assert any("writer.py" in v for v in violations)
        assert any("os.fsync" in v for v in violations)
        assert any("os.replace" in v for v in violations)

    def test_read_only_open_is_fine(self, tmp_path):
        import pathlib
        import sys

        sys.path.insert(0, str(
            pathlib.Path(__file__).resolve().parent.parent / "scripts"
        ))
        try:
            import check_diskpolicy as lint
        finally:
            sys.path.pop(0)
        pkg = tmp_path / "cometbft_tpu"
        pkg.mkdir(parents=True)
        (pkg / "reader.py").write_text(
            "def load(path):\n"
            "    with open(path) as f:\n"
            "        return f.read()\n"
            "def tweak(s):\n"
            "    return s.replace('a', 'b')\n"  # str.replace: not os.replace
        )
        assert lint.scan(tmp_path) == []
