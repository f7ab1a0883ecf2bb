"""ops/warmboot: the boot-time precompile pass over the bucket x backend
matrix (docs/warm-boot.md).

The executable seam (``ops.verify.bucket_executable``) is monkeypatched
throughout — these tests pin the MATRIX WALK, breaker integration and
threading, not the compiles themselves (test_aot_cache covers the cache;
bench.py --warmboot drives the real cold/warm boots)."""

import threading

import pytest

from cometbft_tpu.crypto import backend_health
from cometbft_tpu.ops import verify as ov
from cometbft_tpu.ops import warm_stats, warmboot


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    # pin the secp/BLS/merkle/transport extra matrices EMPTY for the
    # legacy ed25519-matrix tests: their run() calls would otherwise
    # really compile the ladder, G1, tree and AEAD kernels (~30s/shape
    # on this host).  TestExtraMatrix re-enables them against a
    # monkeypatched warm seam.
    monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_SECP_BUCKETS", "")
    monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BLS_BUCKETS", "")
    monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_MERKLE_BUCKETS", "")
    monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_TRANSPORT_BUCKETS", "")
    backend_health.reset()
    warmboot.reset()
    yield
    backend_health.reset()
    warmboot.reset()


class TestEnablement:
    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT", "0")
        assert not warmboot.enabled()
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT", "1")
        assert warmboot.enabled()

    def test_default_follows_trusted_backend(self, monkeypatch):
        monkeypatch.delenv("COMETBFT_TPU_WARMBOOT", raising=False)
        monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "tpu")
        assert warmboot.enabled()
        monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "cpu")
        assert not warmboot.enabled()


class TestMatrix:
    def test_every_bucket_per_tier_with_floors(self, monkeypatch):
        from cometbft_tpu.ops import supervisor

        monkeypatch.setattr(
            supervisor, "device_chain", lambda: ("pallas", "xla")
        )
        shapes = warmboot.warm_matrix()
        # xla warms every bucket; pallas only >= its Mosaic tile floor
        assert [b for t, b in shapes if t == "xla"] == list(ov._BUCKETS)
        assert [b for t, b in shapes if t == "pallas"] == [
            b for b in ov._BUCKETS if b >= ov._PALLAS_MIN_BUCKET
        ]
        # ascending: small commit shapes come online first
        xs = [b for _, b in shapes]
        assert xs == sorted(xs)

    def test_pruned_buckets_not_in_matrix(self):
        shapes = {b for _, b in warmboot.warm_matrix()}
        for pruned in ov._PRUNED_BUCKETS:
            assert pruned not in shapes

    def test_env_bound(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BUCKETS", "64,32")
        assert [b for _, b in warmboot.warm_matrix()] == [32, 64]
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BUCKETS", "garbage")
        assert warmboot.warm_matrix()  # unparsable -> full matrix


class TestRun:
    def test_warms_matrix_and_records(self, monkeypatch):
        calls = []

        def fake_exec(backend, bucket):
            calls.append((backend, bucket))
            return (lambda packed: None), {"exec_cache": "hit"}

        monkeypatch.setattr(ov, "bucket_executable", fake_exec)
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BUCKETS", "32,64")
        s0 = warm_stats.snapshot()
        report = warmboot.run()
        assert calls == [("xla", 32), ("xla", 64)]
        assert report["warmed"] == 2 and report["failures"] == 0
        assert set(report["statuses"].values()) == {"hit"}
        assert report["pruned"] == len(ov._PRUNED_BUCKETS)
        s1 = warm_stats.snapshot()
        assert s1["warm_runs"] == s0["warm_runs"] + 1
        assert s1["shapes_warmed"] == s0["shapes_warmed"] + 2
        assert s1["shapes_pruned"] > s0["shapes_pruned"]

    def test_compile_failure_demotes_via_breaker(self, monkeypatch):
        """A compile failure must surface through the EXISTING breaker
        machinery (demotion counter + recorded failure) and never wedge
        the pass — remaining shapes of that tier are skipped, the pass
        returns normally."""

        def fake_exec(backend, bucket):
            raise RuntimeError("compile exploded")

        monkeypatch.setattr(ov, "bucket_executable", fake_exec)
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BUCKETS", "32,64")
        d0 = backend_health.snapshot()["demotions"]
        report = warmboot.run()  # must not raise
        assert report["failures"] == 1
        assert report["statuses"]["xla-32"] == "error:RuntimeError"
        assert report["statuses"]["xla-64"] == "skipped:tier-demoted"
        assert backend_health.snapshot()["demotions"] == d0 + 1
        br = backend_health.registry().breaker("xla")
        assert br.stats()["consecutive_failures"] >= 1

    def test_compile_failure_is_loud_and_latched(self, monkeypatch, caplog):
        """bucket_executable does not absorb a compile failure into a
        quiet retry through plain jit: it logs the compiler's message at
        error, counts it, latches the shape and raises — and the latched
        shape raises again without a second compile."""
        from cometbft_tpu.ops import aot_cache

        calls = []

        def refuse(jitted, shapes, tag):
            calls.append(tag)
            raise RuntimeError("Mosaic failed to compile TPU kernel: forced")

        monkeypatch.setattr(aot_cache, "load_or_compile", refuse)
        ov.reset_executable_memo()
        c0 = warm_stats.snapshot()["compile_failures"]
        with caplog.at_level("ERROR", logger="cometbft_tpu.crypto"):
            with pytest.raises(ov.TierCompileError, match="Mosaic failed"):
                ov.bucket_executable("pallas", 128)
        assert any("Mosaic failed" in r.getMessage() for r in caplog.records)
        assert warm_stats.snapshot()["compile_failures"] == c0 + 1
        assert ("pallas", 128) in ov._AOT_BROKEN
        with pytest.raises(ov.TierCompileError):
            ov.bucket_executable("pallas", 128)
        assert calls == ["verify-pallas-packed-128"]
        ov.reset_executable_memo()
        assert not ov._AOT_BROKEN

    def test_open_breaker_skipped(self, monkeypatch):
        """Warming a dead device is probe traffic the breaker exists to
        prevent: an OPEN tier is skipped wholesale."""
        called = []
        monkeypatch.setattr(
            ov,
            "bucket_executable",
            lambda *a, **k: called.append(a)
            or ((lambda **kw: None), {"exec_cache": "hit"}),
        )
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BUCKETS", "32")
        br = backend_health.registry().breaker("xla")
        for _ in range(br.threshold):
            br.record_failure(RuntimeError("dead"))
        assert br.state == backend_health.OPEN
        report = warmboot.run()
        assert not called
        assert report["statuses"]["xla-32"] == "skipped:breaker-open"


class TestStart:
    def test_start_disabled_is_noop(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT", "0")
        assert warmboot.start() is None

    def test_start_background_and_idempotent(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT", "1")
        started = threading.Event()
        release = threading.Event()
        runs = []

        def fake_run():
            runs.append(1)
            started.set()
            release.wait(5)
            return {}

        monkeypatch.setattr(warmboot, "run", fake_run)
        t1 = warmboot.start()
        assert t1 is not None and started.wait(5)
        # second start while running: same thread, no second pass
        assert warmboot.start() is t1
        warmboot.ensure_started()  # never raises, never double-starts
        release.set()
        t1.join(5)
        assert not t1.is_alive()
        # a COMPLETED pass is never re-run: a late ensure_started (the
        # verifysched dispatcher, minutes after boot) must not re-walk
        # the matrix and double-count the warmboot metrics
        assert warmboot.start() is t1
        warmboot.ensure_started()
        assert len(runs) == 1
        # explicit reset (tests/new-process semantics) re-arms it
        warmboot.reset()
        release.set()
        t2 = warmboot.start()
        assert t2 is not None and t2 is not t1
        t2.join(5)
        assert len(runs) == 2


class TestExtraMatrix:
    """The secp ladder / BLS G1 families riding the warm pass (ROADMAP
    item 4 follow-up).  The warm seam (``warmboot._warm_extra``) is
    monkeypatched: these pin the matrix walk, breaker gating and status
    accounting, not the kernel compiles themselves."""

    def test_default_families_and_env_bounds(self, monkeypatch):
        monkeypatch.delenv("COMETBFT_TPU_WARMBOOT_SECP_BUCKETS", raising=False)
        monkeypatch.delenv("COMETBFT_TPU_WARMBOOT_BLS_BUCKETS", raising=False)
        monkeypatch.delenv(
            "COMETBFT_TPU_WARMBOOT_MERKLE_BUCKETS", raising=False
        )
        monkeypatch.delenv(
            "COMETBFT_TPU_WARMBOOT_TRANSPORT_BUCKETS", raising=False
        )
        shapes = warmboot.extra_matrix()
        assert [
            s for br, f, s in shapes if f == "secp-ladder"
        ] == sorted(warmboot.DEFAULT_SECP_BUCKETS)
        assert [
            s for br, f, s in shapes if f == "bls-g1"
        ] == sorted(warmboot.DEFAULT_BLS_BUCKETS)
        assert [
            s for br, f, s in shapes if f == "sha256-tree"
        ] == sorted(warmboot.DEFAULT_MERKLE_BUCKETS)
        assert {br for br, f, _ in shapes if f == "secp-ladder"} == {
            "secp_device"
        }
        assert {br for br, f, _ in shapes if f == "bls-g1"} == {"bls_g1"}
        assert {br for br, f, _ in shapes if f == "sha256-tree"} == {
            "merkle_device"
        }
        # one env var feeds BOTH transport families: the AEAD and ladder
        # kernels warm the same lane shapes, each behind its own breaker
        assert [
            s for br, f, s in shapes if f == "transport-aead"
        ] == sorted(warmboot.DEFAULT_TRANSPORT_BUCKETS)
        assert [
            s for br, f, s in shapes if f == "transport-x25519"
        ] == sorted(warmboot.DEFAULT_TRANSPORT_BUCKETS)
        assert {br for br, f, _ in shapes if f == "transport-aead"} == {
            "aead_device"
        }
        assert {br for br, f, _ in shapes if f == "transport-x25519"} == {
            "x25519_device"
        }
        # env override bounds each family; empty skips it entirely
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_SECP_BUCKETS", "4,2")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BLS_BUCKETS", "")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_MERKLE_BUCKETS", "8,32")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_TRANSPORT_BUCKETS", "16")
        shapes = warmboot.extra_matrix()
        assert [s for _, f, s in shapes if f == "secp-ladder"] == [2, 4]
        assert not [s for _, f, s in shapes if f == "bls-g1"]
        assert [s for _, f, s in shapes if f == "sha256-tree"] == [8, 32]
        assert [s for _, f, s in shapes if f == "transport-aead"] == [16]
        assert [s for _, f, s in shapes if f == "transport-x25519"] == [16]
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_TRANSPORT_BUCKETS", "")
        shapes = warmboot.extra_matrix()
        assert not [s for _, f, s in shapes if f.startswith("transport-")]

    def _fake_exec(self, calls):
        def fake(backend, bucket):
            calls.append((backend, bucket))
            return (lambda packed: None), {"exec_cache": "hit"}

        return fake

    def test_run_walks_extra_families(self, monkeypatch):
        warmed = []

        def fake_extra(family, lanes):
            warmed.append((family, lanes))
            return {f"{family}-{lanes}": {"exec_cache": "hit"}}

        monkeypatch.setattr(ov, "bucket_executable", self._fake_exec([]))
        monkeypatch.setattr(warmboot, "_warm_extra", fake_extra)
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BUCKETS", "32")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_SECP_BUCKETS", "1,2")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BLS_BUCKETS", "4")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_TRANSPORT_BUCKETS", "8")
        report = warmboot.run()
        assert ("secp-ladder", 1) in warmed
        assert ("secp-ladder", 2) in warmed
        assert ("bls-g1", 4) in warmed
        assert ("transport-aead", 8) in warmed
        assert ("transport-x25519", 8) in warmed
        assert report["statuses"]["secp-ladder-1"] == "hit"
        assert report["statuses"]["bls-g1-4"] == "hit"
        assert report["statuses"]["transport-aead-8"] == "hit"
        assert report["statuses"]["transport-x25519-8"] == "hit"
        # extra-family hits count toward the warmed total
        assert report["warmed"] >= 6

    def test_extra_compile_failure_demotes_family_breaker(self, monkeypatch):
        def fake_extra(family, lanes):
            raise RuntimeError("lowering exploded")

        monkeypatch.setattr(ov, "bucket_executable", self._fake_exec([]))
        monkeypatch.setattr(warmboot, "_warm_extra", fake_extra)
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BUCKETS", "32")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_SECP_BUCKETS", "1,2")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BLS_BUCKETS", "4")
        report = warmboot.run()  # must not raise
        # first secp shape failed -> family dead, second shape skipped
        assert report["statuses"]["secp-ladder-1"].startswith("error:")
        assert report["statuses"]["secp-ladder-2"] == "skipped:tier-demoted"
        # bls has its own breaker: also failed independently
        assert report["statuses"]["bls-g1-4"].startswith("error:")
        assert report["failures"] == 2
        reg = backend_health.registry()
        assert reg.breaker("secp_device").stats()["failures_total"] == 1
        assert reg.breaker("bls_g1").stats()["failures_total"] == 1

    def test_extra_open_breaker_skipped(self, monkeypatch):
        called = []

        def fake_extra(family, lanes):
            called.append((family, lanes))
            return {}

        monkeypatch.setattr(ov, "bucket_executable", self._fake_exec([]))
        monkeypatch.setattr(warmboot, "_warm_extra", fake_extra)
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BUCKETS", "32")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_SECP_BUCKETS", "2")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BLS_BUCKETS", "")
        br = backend_health.registry().breaker("secp_device")
        for _ in range(br.threshold):
            br.record_failure(RuntimeError("dead"))
        assert br.state == backend_health.OPEN
        report = warmboot.run()
        assert not called
        assert report["statuses"]["secp-ladder-2"] == "skipped:breaker-open"

    def test_warm_progress_is_span_visible(self, monkeypatch):
        from cometbft_tpu.libs import tracing

        tracing.get_tracer().reset()
        monkeypatch.setattr(ov, "bucket_executable", self._fake_exec([]))
        monkeypatch.setattr(
            warmboot,
            "_warm_extra",
            lambda f, s: {f"{f}-{s}": {"exec_cache": "hit"}},
        )
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BUCKETS", "32")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_SECP_BUCKETS", "2")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BLS_BUCKETS", "4")
        warmboot.run()
        stages = tracing.get_tracer().stage_summary()
        assert stages["warmboot.run"]["count"] == 1
        # ed25519 shapes (per tier) + secp + bls, all children of the run
        assert stages["warmboot.shape"]["count"] >= 3
        spans = tracing.get_tracer().tail(64)
        shape = [s for s in spans if s["stage"] == "warmboot.shape"]
        run = [s for s in spans if s["stage"] == "warmboot.run"]
        assert run and all(s.get("parent") == run[0]["span"] for s in shape)
        fams = {s["attrs"]["family"] for s in shape}
        assert {"ed25519", "secp-ladder", "bls-g1"} <= fams
        tracing.get_tracer().reset()
