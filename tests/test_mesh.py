"""Multi-chip sharding regression tests on the virtual 8-device CPU mesh.

These guard ``__graft_entry__.dryrun_multichip`` (an early dry run failed
because arrays were materialized on the default device before resharding) —
the full sharded verify must compile AND execute hermetically on whatever
mesh it is given.  They also pin the kernel-selection seam: the sharded
path must route through the SAME impl choice as the single-chip path
(VERDICT r3 #3 — the two flagship features were never composed).
"""

import os

import numpy as np
import jax
import pytest

import __graft_entry__ as graft
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import verify as ov
from cometbft_tpu.parallel import mesh as pmesh


class TestMeshVerify:
    @pytest.mark.slow  # ~60s of XLA compile on a 2-core CPU host
    def test_dryrun_multichip_8(self):
        # The exact function the driver invokes, on the full 8-device mesh.
        graft.dryrun_multichip(8)

    def test_verify_batch_sharded_mixed_validity(self):
        mesh = pmesh.make_mesh(jax.devices("cpu")[:8])
        pubs, msgs, sigs = [], [], []
        n = 19  # deliberately not a multiple of the mesh size
        for i in range(n):
            seed = bytes([i + 1]) * 32
            pubs.append(ref.pubkey_from_seed(seed))
            msgs.append(b"mesh-%d" % i)
            sigs.append(ref.sign(seed, msgs[-1]))
        # corrupt two signatures and one message
        sigs[3] = sigs[3][:-1] + bytes([sigs[3][-1] ^ 1])
        sigs[11] = bytes(64)
        msgs[17] = b"tampered"
        bits = pmesh.verify_batch_sharded(pubs, msgs, sigs, mesh=mesh)
        expected = np.ones(n, bool)
        expected[[3, 11, 17]] = False
        assert bits.shape == (n,)
        assert (bits == expected).all()

    def test_sharded_dispatch_emits_per_shard_spans(self):
        """ISSUE 11 per-shard visibility: the mesh dispatch records the
        verify.dispatch attribution triple extended with the mesh width,
        and the fetch emits one mesh.shard child span per device carrying
        (device ordinal, lanes-per-shard, tier) — feeding the
        cometbft_crypto_shard_dispatch_seconds{device=} histogram."""
        from cometbft_tpu.libs import tracing
        from cometbft_tpu.libs.metrics import NodeMetrics
        from cometbft_tpu.ops import dispatch_stats

        mesh = pmesh.make_mesh(jax.devices("cpu")[:8])
        n = 16
        pubs, msgs, sigs = [], [], []
        for i in range(n):
            seed = bytes([i + 1]) * 32
            pubs.append(ref.pubkey_from_seed(seed))
            msgs.append(b"shard-span-%d" % i)
            sigs.append(ref.sign(seed, msgs[-1]))
        tracing.reset_tracer()
        dispatch_stats.reset()
        try:
            bits = pmesh.verify_batch_sharded(pubs, msgs, sigs, mesh=mesh)
            assert bits.all()
            tr = tracing.get_tracer()
            spans = tr.tail(0)
            disp = [
                s for s in spans
                if s["stage"] == "verify.dispatch"
                and s["attrs"].get("mesh") == 8
            ]
            assert len(disp) == 1
            assert disp[0]["attrs"]["tier"] == "xla"
            assert disp[0]["attrs"]["lanes"] >= n
            shards = [s for s in spans if s["stage"] == "mesh.shard"]
            assert len(shards) == 8
            # children of the dispatch span, one per device ordinal, each
            # carrying the lanes-per-shard + tier + local accept count
            lanes = disp[0]["attrs"]["lanes"]
            for s in shards:
                assert s["parent"] == disp[0]["span"]
                assert s["attrs"]["lanes"] == lanes // 8
                assert s["attrs"]["tier"] == "xla"
                assert "ok" in s["attrs"]
            assert sorted(s["attrs"]["device"] for s in shards) == list(
                range(8)
            )
            assert sum(s["attrs"]["ok"] for s in shards) == n
            # the per-device histograms landed and render on /metrics
            snap = dispatch_stats.snapshot()
            assert sorted(snap["shard_hist"]) == [str(i) for i in range(8)]
            text = NodeMetrics().registry.expose()
            assert 'cometbft_crypto_shard_dispatch_seconds_bucket{device="0"' in text
        finally:
            tracing.reset_tracer()
            dispatch_stats.reset()

    @pytest.mark.warmcache("mesh-xla-8dev-128", "mesh-xla-8dev-128-donated")
    def test_donated_mesh_verdicts_bitwise_equal(self):
        """ROADMAP item 4's mesh leftover: the donated sharded executable
        must produce bitwise-identical verdicts to the plain one on a
        mixed-validity batch (donation only changes buffer aliasing, never
        lane results).  Compile-heavy (two 8-dev executables) — returns to
        tier-1 when the shared exec cache serves both warm."""
        mesh = pmesh.make_mesh(jax.devices("cpu")[:8])
        n = 19
        pubs, msgs, sigs = [], [], []
        for i in range(n):
            seed = bytes([i + 101]) * 32
            pubs.append(ref.pubkey_from_seed(seed))
            msgs.append(b"donate-%d" % i)
            sigs.append(ref.sign(seed, msgs[-1]))
        sigs[2] = sigs[2][:-1] + bytes([sigs[2][-1] ^ 1])
        msgs[13] = b"tampered"
        plain = pmesh.verify_batch_sharded(
            pubs, msgs, sigs, mesh=mesh, donated=False
        )
        donated = pmesh.verify_batch_sharded(
            pubs, msgs, sigs, mesh=mesh, donated=True
        )
        expected = np.ones(n, bool)
        expected[[2, 13]] = False
        assert (plain == expected).all()
        assert (donated == plain).all()


class TestKernelSelectionSeam:
    """The mesh path and the single-chip path share ``select_impl``."""

    def test_env_override_reaches_mesh(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_VERIFY_IMPL", "pallas")
        assert ov.select_impl(jax.devices("cpu")[:2]) == "pallas"
        monkeypatch.setenv("COMETBFT_TPU_VERIFY_IMPL", "xla")
        assert ov.select_impl(jax.devices("cpu")[:2]) == "xla"

    def test_cpu_mesh_defaults_to_xla(self, monkeypatch):
        monkeypatch.delenv("COMETBFT_TPU_VERIFY_IMPL", raising=False)
        assert ov.select_impl(jax.devices("cpu")[:2]) == "xla"
        # tpu-looking devices select pallas — same predicate verify_batch uses
        class FakeTpu:
            platform = "tpu"

        assert ov.select_impl([FakeTpu(), FakeTpu()]) == "pallas"
        assert ov.select_impl([FakeTpu(), jax.devices("cpu")[0]]) == "xla"

    def test_fn_cache_keyed_on_impl(self):
        mesh = pmesh.make_mesh(jax.devices("cpu")[:2])
        fn_xla = pmesh.sharded_verify_fn(mesh, impl="xla")
        assert pmesh.sharded_verify_fn(mesh, impl="xla") is fn_xla
        key_xla = ("xla", False) + tuple(
            (d.platform, d.id) for d in mesh.devices.flat
        )
        assert key_xla in pmesh._FN_CACHE
        # donated executables are distinct cache entries (input aliasing
        # changes the compiled artifact) with their own disk tag
        assert pmesh.sharded_verify_fn(mesh, impl="xla", donated=True) is not fn_xla
        assert pmesh.mesh_tag("xla", 8, 128) == "mesh-xla-8dev-128"
        assert (
            pmesh.mesh_tag("xla", 8, 128, donated=True)
            == "mesh-xla-8dev-128-donated"
        )


class TestMeshPallasComposition:
    """The real composition: a sharded verify whose per-shard body is the
    Pallas kernel.  VERDICT r4 #2: round 4's trace-time break (shard_map
    check_vma rejecting pallas_call) hid behind a slow-test gate — these
    now run UNGATED in the default suite.  The trace smoke catches
    trace-time breaks in seconds; the interpret execution (minutes, the
    suite's slowest test) proves numerics end-to-end."""

    def test_sharded_pallas_traces(self, monkeypatch):
        """Fast: the sharded Pallas verify must TRACE + LOWER on a CPU
        mesh (this is exactly where the r4 composition broke, in 2.4 s).
        No kernel execution — interpret-mode numerics are covered by
        test_sharded_pallas_interpret below.  (interpret=True is patched
        in because CPU lowering requires it; the shard_map×pallas_call
        abstract-eval this guards runs identically either way.)"""
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        import cometbft_tpu.ops.pallas_verify as pv

        orig = pl.pallas_call

        def patched(*args, **kwargs):
            kwargs.setdefault("interpret", True)
            return orig(*args, **kwargs)

        monkeypatch.setattr(pl, "pallas_call", patched)
        monkeypatch.setattr(pv, "TILE", 8)
        pv._build.cache_clear()
        pmesh._FN_CACHE.clear()
        try:
            mesh = pmesh.make_mesh(jax.devices("cpu")[:2])
            fn, _ = pmesh.sharded_verify_fn(mesh, impl="pallas")
            n = 16
            args = [
                jnp.zeros((n, 32), jnp.uint8),
                jnp.zeros((n, 32), jnp.uint8),
                jnp.zeros((n, 32), jnp.uint8),
                jnp.zeros((n, 32), jnp.uint8),
                jnp.zeros((n,), jnp.int32),
            ]
            lowered = fn.lower(*args)
            # the collective's spelling depends on the partitioner (shardy
            # lowers to all-reduce where older pipelines kept psum)
            text = lowered.as_text()
            assert any(
                op in text for op in ("psum", "all-reduce", "all_reduce")
            ), f"no cross-device collective in lowered text:\n{text[:2000]}"
        finally:
            pv._build.cache_clear()
            pmesh._FN_CACHE.clear()

    @pytest.mark.slow  # pallas interpret mode: ~90s of pure emulation
    def test_sharded_pallas_interpret(self, monkeypatch):
        from jax.experimental import pallas as pl

        import cometbft_tpu.ops.pallas_verify as pv

        orig = pl.pallas_call

        def patched(*args, **kwargs):
            kwargs.setdefault("interpret", True)
            return orig(*args, **kwargs)

        monkeypatch.setattr(pl, "pallas_call", patched)
        monkeypatch.setattr(pv, "TILE", 8)
        pv._build.cache_clear()
        pmesh._FN_CACHE.clear()
        try:
            mesh = pmesh.make_mesh(jax.devices("cpu")[:2])
            pubs, msgs, sigs = [], [], []
            n = 16
            for i in range(n):
                seed = bytes([i + 1]) * 32
                pubs.append(ref.pubkey_from_seed(seed))
                msgs.append(b"compose-%d" % i)
                sigs.append(ref.sign(seed, msgs[-1]))
            sigs[5] = bytes(64)
            msgs[9] = b"tampered"
            arrays, _, structural = ov.prepare_batch(pubs, msgs, sigs)
            arrays = pmesh.pad_to_mesh(arrays, mesh)
            fn, _ = pmesh.sharded_verify_fn(mesh, impl="pallas")
            accept, n_ok = fn(*pmesh.device_put_args(arrays, mesh))
            bits = (np.asarray(accept)[: len(structural)] & structural)[:n]
            expected = np.ones(n, bool)
            expected[[5, 9]] = False
            assert (bits == expected).all()
            assert int(n_ok) == n - 2
        finally:
            pv._build.cache_clear()
            pmesh._FN_CACHE.clear()


# ----------------------------------------------------------------------
# elastic mesh supervision (ISSUE 13: per-shard fault isolation)
# ----------------------------------------------------------------------


class TestElasticMesh:
    """The shrink ladder on the per-shard host-oracle runner seam: every
    injected fault mode at every ordinal must yield verdicts bitwise-equal
    to the host ZIP-215 oracle (infrastructure failures NEVER become wrong
    verdicts), shrinks must attribute to the right stable ordinal, and the
    breaker machinery must exclude/re-admit deterministically."""

    WIDTH = 4

    @pytest.fixture(autouse=True)
    def _elastic_mesh(self, monkeypatch):
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.libs import tracing
        from cometbft_tpu.ops import device_health, dispatch_stats
        from cometbft_tpu.parallel import elastic

        monkeypatch.setenv("COMETBFT_TPU_BREAKER_THRESHOLD", "1")
        monkeypatch.delenv("COMETBFT_TPU_MESH_SUPERVISOR", raising=False)
        backend_health.reset()
        device_health.reset()
        tracing.reset_tracer()
        dispatch_stats.reset()
        elastic.clear()
        elastic.configure(range(self.WIDTH))
        elastic.set_mesh_runner(self._oracle_runner)
        yield
        elastic.clear()
        device_health.reset()
        backend_health.reset()
        tracing.reset_tracer()
        dispatch_stats.reset()

    @staticmethod
    def _oracle_runner(ordinal, pubs, msgs, sigs, lanes):
        from cometbft_tpu.parallel import elastic

        return elastic.host_oracle_runner(ordinal, pubs, msgs, sigs, lanes)

    @staticmethod
    def _mixed_batch(seed: int, n: int):
        import random

        rng = random.Random(seed)
        pubs, msgs, sigs = [], [], []
        expected = np.zeros(n, dtype=bool)
        for i in range(n):
            s = bytes([(seed + i) % 255 + 1]) * 32
            pub = ref.pubkey_from_seed(s)
            msg = b"elastic-%d-%d" % (seed, i)
            sig = ref.sign(s, msg)
            roll = rng.random()
            if roll < 0.2:
                sig = sig[:-1] + bytes([sig[-1] ^ 1])  # forged
            elif roll < 0.3:
                sig = bytes(64)  # degenerate
            elif roll < 0.35:
                pub = pub[:16]  # structurally invalid
            pubs.append(pub)
            msgs.append(msg)
            sigs.append(sig)
            expected[i] = (
                len(pub) == 32
                and len(sig) == 64
                and ref.verify_zip215(pub, msg, sig)
            )
        return pubs, msgs, sigs, expected

    def test_fault_matrix_every_mode_every_ordinal(self, monkeypatch):
        """raise / wrong_shape / flap at EVERY ordinal: verdicts stay
        bitwise-equal to the host oracle, the failure attributes to the
        injected ordinal's breaker, and the mesh shrinks exactly once per
        dead chip (the open breaker excludes it thereafter)."""
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.parallel import elastic

        pubs, msgs, sigs, expected = self._mixed_batch(7, 23)
        for mode in ("raise", "wrong_shape", "flap"):
            for ordinal in range(self.WIDTH):
                backend_health.reset()
                elastic.set_fault_injector(
                    elastic.FaultyDevice(
                        mode, ordinals=(ordinal,), fail_n=2, pass_n=1
                    )
                )
                bits = elastic.verify_elastic(pubs, msgs, sigs)
                assert (bits == expected).all(), (mode, ordinal)
                st = backend_health.registry().breaker(
                    f"mesh_dev{ordinal}"
                ).stats()
                assert st["failures_total"] >= 1, (mode, ordinal, st)
                elastic.clear_fault_injector()

    @pytest.mark.parametrize("mode", ["raise", "wrong_shape", "flap"])
    @pytest.mark.parametrize("ordinal", range(4))
    def test_fault_matrix_through_the_served_path(
        self, monkeypatch, mode, ordinal
    ):
        """The same matrix with the scheduler on: a flush the mesh takes
        (``verify_segment_sync``) whose shard fails resolves every future
        with the oracle's verdicts at the next width, the failure on the
        injected ordinal's breaker."""
        from cometbft_tpu import verifysched
        from cometbft_tpu.crypto import backend_health, sigcache
        from cometbft_tpu.ops import dispatch_stats
        from cometbft_tpu.parallel import elastic
        from cometbft_tpu.verifysched import service

        monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "tpu")
        monkeypatch.setenv("COMETBFT_TPU_MESH_MIN_BATCH", "1")
        monkeypatch.delenv("COMETBFT_TPU_VERIFY_SCHED", raising=False)
        verifysched.reset_scheduler()
        sigcache.reset_cache()
        pubs, msgs, sigs, expected = self._mixed_batch(7, 23)
        elastic.set_fault_injector(
            elastic.FaultyDevice(mode, ordinals=(ordinal,), fail_n=2, pass_n=1)
        )
        try:
            got = service.verify_segment_sync(pubs, msgs, sigs)
        finally:
            verifysched.reset_scheduler()
            sigcache.reset_cache()
        assert got == [bool(b) for b in expected], (mode, ordinal)
        st = backend_health.registry().breaker(f"mesh_dev{ordinal}").stats()
        assert st["failures_total"] >= 1, (mode, ordinal, st)
        snap = dispatch_stats.snapshot()
        assert snap["mesh_shrinks"] == 1 and snap["inflight_depth"] == 0
        assert dispatch_stats.mesh_width() == self.WIDTH - 1

    def test_hang_mode_shard_watchdog_fires(self, monkeypatch):
        """A wedged shard: the shard watchdog abandons it, the anomaly
        taxonomy records shard_watchdog_fire with the ordinal, and the
        verdicts still match the oracle."""
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.libs import tracing
        from cometbft_tpu.parallel import elastic

        monkeypatch.setenv("COMETBFT_TPU_DISPATCH_TIMEOUT_MS", "60")
        pubs, msgs, sigs, expected = self._mixed_batch(11, 17)
        for ordinal in range(self.WIDTH):
            backend_health.reset()
            elastic.set_fault_injector(
                elastic.FaultyDevice("hang", ordinals=(ordinal,), hang_s=0.3)
            )
            bits = elastic.verify_elastic(pubs, msgs, sigs)
            assert (bits == expected).all(), ordinal
            elastic.clear_fault_injector()
        snap = tracing.get_tracer().snapshot()
        # the tracer survives the per-ordinal backend_health resets, so
        # it saw every ordinal's fire; the registry counter only keeps
        # the last iteration's
        assert snap["anomalies"].get("shard_watchdog_fire", 0) >= self.WIDTH
        assert backend_health.snapshot()["watchdog_fires"] >= 1

    def test_uneven_batch_with_dead_device(self):
        """Uneven shards (n not a multiple of the width) + a proactively
        dead device: membership drops to 3 BEFORE the dispatch (no shrink
        anomaly — the breaker was already open) and verdicts match."""
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.libs import tracing
        from cometbft_tpu.ops import dispatch_stats
        from cometbft_tpu.parallel import elastic

        backend_health.registry().breaker("mesh_dev3").trip("pre-dead")
        pubs, msgs, sigs, expected = self._mixed_batch(13, 19)
        bits = elastic.verify_elastic(pubs, msgs, sigs)
        assert (bits == expected).all()
        assert dispatch_stats.mesh_width() == self.WIDTH - 1
        spans = tracing.get_tracer().tail(0)
        shard_devs = sorted(
            s["attrs"]["device"] for s in spans if s["stage"] == "mesh.shard"
        )
        assert shard_devs == [0, 1, 2]  # stable ordinals, 3 excluded
        assert not any(
            s["stage"] == "verify.dispatch" and s["attrs"].get("error")
            for s in spans
        )

    def test_shrink_then_restore_round_trip(self, monkeypatch):
        """Kill ordinal 1, dispatch (shrink), heal it, advance the fake
        clock past the backoff: the next dispatch's membership probes the
        HALF_OPEN breaker with a one-bucket dispatch, re-admits the chip
        (mesh_restore), and the width returns to full — verdicts equal to
        the oracle at every step."""
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.libs import tracing
        from cometbft_tpu.ops import dispatch_stats
        from cometbft_tpu.parallel import elastic

        fake = [100.0]
        backend_health.reset()
        backend_health.registry().set_clock(lambda: fake[0])
        pubs, msgs, sigs, expected = self._mixed_batch(17, 21)

        elastic.set_fault_injector(
            elastic.FaultyDevice("raise", ordinals=(1,))
        )
        bits = elastic.verify_elastic(pubs, msgs, sigs)
        assert (bits == expected).all()
        assert dispatch_stats.mesh_width() == self.WIDTH - 1
        snap = dispatch_stats.snapshot()
        assert snap["mesh_shrinks"] == 1

        # still dead: the elapsed backoff costs one failed PROBE, never a
        # production batch, and the backoff doubles
        fake[0] += 5.0
        bits = elastic.verify_elastic(pubs, msgs, sigs)
        assert (bits == expected).all()
        assert dispatch_stats.mesh_width() == self.WIDTH - 1
        st = backend_health.registry().breaker("mesh_dev1").stats()
        assert st["probes"] >= 1

        # healed: the next backoff window's probe passes and re-admits
        elastic.clear_fault_injector()
        fake[0] += 10.0
        bits = elastic.verify_elastic(pubs, msgs, sigs)
        assert (bits == expected).all()
        snap = dispatch_stats.snapshot()
        assert snap["mesh_width"] == self.WIDTH
        assert snap["mesh_restores"] == 1
        st = backend_health.registry().breaker("mesh_dev1").stats()
        assert st["state"] == "closed"
        assert st["repromotions"] == 1
        anomalies = tracing.get_tracer().snapshot()["anomalies"]
        assert anomalies.get("mesh_shrink", 0) >= 1
        assert anomalies.get("mesh_restore", 0) == 1

    def test_a_chip_dead_for_good_costs_one_shrink_and_one_dispatch(self):
        """A chip that stays dead: the one dispatch that meets it is
        verified again at the next width and every later dispatch leaves
        it out, so three verifies cost the healthy dispatch count plus
        one, and one shrink — the oracle's verdicts throughout."""
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.ops import dispatch_stats
        from cometbft_tpu.parallel import elastic

        pubs, msgs, sigs, expected = self._mixed_batch(47, 24)

        def three_verifies() -> dict:
            dispatch_stats.reset()
            for _ in range(3):
                bits = elastic.verify_elastic(pubs, msgs, sigs)
                assert (bits == expected).all()
            return dispatch_stats.snapshot()

        healthy = three_verifies()
        backend_health.reset()
        elastic.clear()
        elastic.configure(range(self.WIDTH))
        elastic.set_mesh_runner(self._oracle_runner)
        elastic.set_fault_injector(elastic.FaultyDevice("raise", ordinals=(1,)))
        dead = three_verifies()
        assert healthy["mesh_shrinks"] == 0
        assert dead["mesh_shrinks"] == 1
        assert dead["mesh_width"] == self.WIDTH - 1
        assert dead["dispatches"] == healthy["dispatches"] + 1

    def test_a_pipelined_commit_keeps_its_depth_in_flight_on_every_lane(self):
        """``verify_pipelined`` cuts a commit into bucket-sized chunks,
        round-robin over the lanes with one in flight a lane: the bad
        lanes keep their indices, the in-flight high-water mark reaches
        the depth, and every lane takes a chunk."""
        from cometbft_tpu.ops import dispatch_stats

        distinct = self._mixed_batch(53, 16)
        n = 320  # five 64-lane chunks over four lanes
        pubs, msgs = (distinct[0] * 20)[:n], (distinct[1] * 20)[:n]
        sigs = list((distinct[2] * 20)[:n])
        expected = np.array((list(distinct[3]) * 20)[:n])
        for i in (0, n // 2, n - 1):
            sigs[i] = bytes(64)
            expected[i] = False
        dispatch_stats.reset()
        bits = ov.verify_pipelined(pubs, msgs, sigs)
        assert (bits == expected).all()
        snap = dispatch_stats.snapshot()
        lanes = snap["lane_dispatches"]
        chunks = sum(lanes.values())
        assert chunks == 5 and len(lanes) == self.WIDTH
        assert snap["inflight_hwm"] == self.WIDTH
        assert snap["inflight_depth"] == 0

    def test_probe_down_proactive_exclusion(self):
        """An ops/device_health down-probe for an ordinal removes it from
        membership BEFORE the next dispatch (breaker tripped, mesh_shrink
        anomaly with reason=probe-down) — no dispatch pays a failure."""
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.libs import tracing
        from cometbft_tpu.ops import device_health, dispatch_stats
        from cometbft_tpu.parallel import elastic

        changed = device_health.record_probe(
            False, source="chipwatch", ordinal=2
        )
        assert changed
        st = backend_health.registry().breaker("mesh_dev2").stats()
        assert st["state"] == "open"
        pubs, msgs, sigs, expected = self._mixed_batch(19, 9)
        bits = elastic.verify_elastic(pubs, msgs, sigs)
        assert (bits == expected).all()
        assert dispatch_stats.mesh_width() == self.WIDTH - 1
        anomalies = tracing.get_tracer().snapshot()["anomalies"]
        assert anomalies.get("mesh_shrink", 0) == 1
        # per-ordinal state surfaces in the forensic document
        assert device_health.snapshot()["ordinals"] == {"2": False}
        # a repeated identical probe is not a transition
        assert not device_health.record_probe(
            False, source="chipwatch", ordinal=2
        )

    def test_probe_down_before_configure_still_excludes(self):
        """A chip the watcher marked down BEFORE the mesh was configured
        (boot-time outage) must not join membership: configure() folds
        the recorded per-ordinal health state in."""
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.ops import device_health, dispatch_stats
        from cometbft_tpu.parallel import elastic

        elastic.clear()
        backend_health.reset()
        device_health.reset()
        device_health.record_probe(False, source="chipwatch", ordinal=1)
        elastic.configure(range(self.WIDTH))
        elastic.set_mesh_runner(self._oracle_runner)
        st = backend_health.registry().breaker("mesh_dev1").stats()
        assert st["state"] == "open", st
        pubs, msgs, sigs, expected = self._mixed_batch(43, 11)
        bits = elastic.verify_elastic(pubs, msgs, sigs)
        assert (bits == expected).all()
        assert dispatch_stats.mesh_width() == self.WIDTH - 1

    def test_all_ordinals_dead_falls_to_single_chip_chain(self):
        """Width < 2 is the bottom of the ladder: the batch resolves on
        the existing single-chip supervised chain (here the device-runner
        seam), still bitwise the oracle."""
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.ops import supervisor
        from cometbft_tpu.parallel import elastic

        for o in range(1, self.WIDTH):
            backend_health.registry().breaker(f"mesh_dev{o}").trip("dead")

        supervisor.set_device_runner(elastic.host_oracle_runner)
        try:
            pubs, msgs, sigs, expected = self._mixed_batch(23, 13)
            bits = elastic.verify_elastic(pubs, msgs, sigs)
            assert (bits == expected).all()
        finally:
            supervisor.clear_device_runner()

    def test_kill_switch_bitwise_parity(self, monkeypatch):
        """COMETBFT_TPU_MESH_SUPERVISOR=0: the supervised path must not
        touch the mesh at all — verdicts come from the single-chip chain
        bit-for-bit, and elastic reports inactive."""
        from cometbft_tpu.ops import supervisor
        from cometbft_tpu.parallel import elastic

        pubs, msgs, sigs, expected = self._mixed_batch(29, 15)

        supervisor.set_device_runner(elastic.host_oracle_runner)
        monkeypatch.setenv("COMETBFT_TPU_MESH_MIN_BATCH", "1")
        try:
            with_mesh = supervisor.verify_supervised(pubs, msgs, sigs)
            monkeypatch.setenv("COMETBFT_TPU_MESH_SUPERVISOR", "0")
            assert not elastic.active()
            without = supervisor.verify_supervised(pubs, msgs, sigs)
        finally:
            supervisor.clear_device_runner()
        assert (with_mesh == expected).all()
        assert (without == expected).all()
        assert (with_mesh == without).all()

    def test_min_batch_cutoff_keeps_small_batches_single_chip(
        self, monkeypatch
    ):
        """The production routing only meshes batches past
        COMETBFT_TPU_MESH_MIN_BATCH: a handful of gossip-vote signatures
        must not pay a cross-device dispatch — they stay on the
        single-chip chain (verdicts identical either way)."""
        from cometbft_tpu.libs import tracing
        from cometbft_tpu.ops import supervisor
        from cometbft_tpu.parallel import elastic

        monkeypatch.setenv("COMETBFT_TPU_MESH_MIN_BATCH", "16")
        supervisor.set_device_runner(elastic.host_oracle_runner)
        try:
            small = self._mixed_batch(37, 8)
            bits = supervisor.verify_supervised(*small[:3])
            assert (bits == small[3]).all()
            spans = tracing.get_tracer().tail(0)
            assert not any(s["stage"] == "mesh.shard" for s in spans)
            big = self._mixed_batch(41, 16)
            bits = supervisor.verify_supervised(*big[:3])
            assert (bits == big[3]).all()
            spans = tracing.get_tracer().tail(0)
            assert any(s["stage"] == "mesh.shard" for s in spans)
        finally:
            supervisor.clear_device_runner()

    def test_width_gauge_and_metrics_exposition(self):
        from cometbft_tpu.libs.metrics import NodeMetrics
        from cometbft_tpu.parallel import elastic

        pubs, msgs, sigs, expected = self._mixed_batch(31, 8)
        bits = elastic.verify_elastic(pubs, msgs, sigs)
        assert (bits == expected).all()
        text = NodeMetrics().registry.expose()
        assert "cometbft_crypto_mesh_width 4" in text
        assert "cometbft_crypto_mesh_shrinks" in text
        assert "cometbft_crypto_mesh_restores" in text

    def test_sched_bucket_target_follows_live_width(self):
        """The verifysched flush target scales with the live mesh width
        (a W-device mesh fills W smallest buckets per flush) and falls
        back to the single-chip target when the mesh is inactive."""
        from cometbft_tpu import verifysched
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.parallel import elastic

        from cometbft_tpu.ops import verify as ov

        sched = verifysched.VerifyScheduler()
        # width 4 (a power of two): base×4 is itself a bucket
        full = sched._bucket_target()
        base = ov.bucket_size(1, ov._min_bucket())
        assert full == base * self.WIDTH
        # width 3: base×3 is NOT a bucket — the target rounds DOWN to the
        # largest real bucket (the mesh path pads to a global bucket, so
        # waiting for a non-bucket count would flush worse-padded)
        backend_health.registry().breaker("mesh_dev0").trip("dead")
        want = max(b for b in ov._BUCKETS if base <= b <= base * 3)
        assert sched._bucket_target() == want
        elastic.clear()
        assert sched._bucket_target() == base

    def test_warmboot_mesh_shrink_matrix(self, monkeypatch):
        """COMETBFT_TPU_WARMBOOT_MESH_SHRINK=1 warms the (N, N-1)
        smallest-bucket mesh shapes through the monkeypatchable seam;
        off (default) or mesh-supervisor-off skips them entirely."""
        from cometbft_tpu.ops import warmboot

        warmed = []

        def fake_warm(width, lanes):
            warmed.append((width, lanes))
            return {f"mesh-xla-{width}dev-{lanes}": {"exec_cache": "hit"}}

        monkeypatch.setattr(warmboot, "_warm_mesh", fake_warm)
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BUCKETS", "32")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_SECP_BUCKETS", "")
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_BLS_BUCKETS", "")

        assert warmboot.mesh_shrink_matrix() == []  # default off
        monkeypatch.setenv("COMETBFT_TPU_WARMBOOT_MESH_SHRINK", "1")
        matrix = warmboot.mesh_shrink_matrix()
        assert [w for w, _ in matrix] == [self.WIDTH, self.WIDTH - 1]
        report = warmboot.run()
        assert warmed == matrix
        assert any(k.startswith("mesh-xla-4dev") for k in report["statuses"])
        assert any(k.startswith("mesh-xla-3dev") for k in report["statuses"])

        # kill switch: the mesh supervisor being off empties the matrix
        monkeypatch.setenv("COMETBFT_TPU_MESH_SUPERVISOR", "0")
        assert warmboot.mesh_shrink_matrix() == []


# ----------------------------------------------------------------------
# the served path over the mesh (ISSUE 34): scheduler on, a flush the mesh
# takes is ONE mesh-wide launch through the supervisor's one launch and
# one fetch.  On the real XLA-CPU device path unless a test says otherwise.
# ----------------------------------------------------------------------

WIDTHS = (2, 4, 8)
BUCKET = 32  # the XLA tier's smallest: every batch below pads to it


def _signed(tag: bytes, n: int):
    import hashlib

    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = hashlib.sha256(b"%s/%d" % (tag, i)).digest()
        msgs.append(b"%s-msg-%d" % (tag, i))
        pubs.append(ref.pubkey_from_seed(seed))
        sigs.append(ref.sign(seed, msgs[-1]))
    return pubs, msgs, sigs


def _forge(sigs, i):
    sigs[i] = sigs[i][:32] + bytes([sigs[i][32] ^ 1]) + sigs[i][33:]


def _oracle(pubs, msgs, sigs):
    return [
        len(p) == 32 and len(s) == 64 and bool(ref.verify_zip215(p, m, s))
        for p, m, s in zip(pubs, msgs, sigs)
    ]


def _spans(stage):
    from cometbft_tpu.libs import tracing

    return [
        s for s in tracing.get_tracer().tail(0) if s["stage"] == stage
    ]


@pytest.fixture(scope="module")
def commit_of_27():
    """(chain id, set, block id, height, commit) of 27 validators, whose
    light prefix is 19 signatures.  Module-scoped so that it is built
    before ``served`` turns the device backend on: the vote set verifies
    every vote on its way in."""
    from tests.test_types import (
        CHAIN_ID, _block_id, _make_commit, _mk_validators,
    )

    privs, vals, _ = _mk_validators(27)
    bid = _block_id()
    return CHAIN_ID, vals, bid, 34, _make_commit(privs, vals, bid, height=34)


@pytest.fixture
def served(monkeypatch):
    """A scheduler-active node whose elastic mesh is configured by the
    test: ``served(width)`` gives the mesh that many of the suite's CPU
    devices and lets it take any batch of 8 signatures or more."""
    from cometbft_tpu import verifysched
    from cometbft_tpu.crypto import backend_health, sigcache
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.ops import device_health, dispatch_stats
    from cometbft_tpu.parallel import elastic
    from cometbft_tpu.verifysched import stats as sstats

    def reset():
        verifysched.reset_scheduler()
        elastic.clear()
        backend_health.reset()
        device_health.reset()
        sigcache.reset_cache()
        sstats.reset()
        dispatch_stats.reset()
        tracing.reset_tracer()

    monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "tpu")
    monkeypatch.setenv("COMETBFT_TPU_MESH_MIN_BATCH", "8")
    monkeypatch.setenv("COMETBFT_TPU_BREAKER_THRESHOLD", "1")
    monkeypatch.delenv("COMETBFT_TPU_VERIFY_SCHED", raising=False)
    monkeypatch.delenv("COMETBFT_TPU_MESH_SUPERVISOR", raising=False)
    reset()

    def configure(width: int):
        pmesh.register_devices(jax.devices("cpu"))
        elastic.configure(range(width))
        return elastic

    yield configure
    reset()


def _bad_lanes(case: str, width: int, n: int) -> "list[int]":
    per = BUCKET // width
    return {
        "a_shards_first_lane": [per],
        "a_shards_last_lane": [per - 1],
        "the_last_real_lane_before_the_padding": [n - 1],
        "two_shards_at_once": [per - 1, per, n - 1],
    }[case]


class TestServedMesh:
    N = 27  # not a multiple of 2, 4 or 8; pads to 32 lanes

    @pytest.mark.parametrize("width", WIDTHS)
    @pytest.mark.parametrize("case", [
        "a_shards_first_lane", "a_shards_last_lane",
        "the_last_real_lane_before_the_padding", "two_shards_at_once",
    ])
    def test_a_bad_signature_keeps_its_index_whatever_shard_holds_it(
        self, served, width, case
    ):
        from cometbft_tpu.verifysched import service

        served(width)
        pubs, msgs, sigs = _signed(b"served/%s/%d" % (case.encode(), width), self.N)
        bad = _bad_lanes(case, width, self.N)
        for i in bad:
            _forge(sigs, i)
        got = service.verify_segment_sync(pubs, msgs, sigs)
        assert got == _oracle(pubs, msgs, sigs)
        assert [i for i, ok in enumerate(got) if not ok] == bad
        (disp,) = _spans("verify.dispatch")
        assert disp["attrs"]["mesh"] == width and disp["attrs"]["lanes"] == BUCKET
        assert disp["attrs"]["pipelined"] is True

    @pytest.mark.parametrize("width", WIDTHS)
    def test_a_seeded_mix_equals_the_reference_bit_for_bit(self, served, width):
        """Forged, degenerate and structurally invalid signatures at seeded
        places, n not a multiple of the width: through
        ``verify_segment_sync`` the verdicts are ``ed25519_ref``'s."""
        from cometbft_tpu.ops import dispatch_stats
        from cometbft_tpu.verifysched import service

        served(width)
        pubs, msgs, sigs, expected = TestElasticMesh._mixed_batch(40 + width, self.N)
        got = service.verify_segment_sync(pubs, msgs, sigs)
        assert got == [bool(b) for b in expected]
        assert 0 < sum(got) < self.N
        snap = dispatch_stats.snapshot()
        assert snap["mesh_dispatches"] == 1 and snap["mesh_shards"] == width
        shards = _spans("mesh.shard")
        assert sorted(s["attrs"]["device"] for s in shards) == list(range(width))
        (fetch,) = _spans("verify.fetch")
        assert all(s["parent"] == fetch["span"] for s in shards)
        (put,) = _spans("mesh.put")
        (launch,) = _spans("verify.launch")
        assert put["parent"] == launch["span"] and put["attrs"]["shards"] == width
        assert launch["attrs"]["mesh"] == width
        # ISSUE 36: the launch's parts on a mesh are the lookup, ``mesh.put``
        # in the one-chip transfer's place, and the call; the fetch has no
        # pull of its own (each shard's is inside its ``mesh.shard``)
        (lookup,), (called,) = (
            _spans("verify.launch.lookup"), _spans("verify.launch.call"),
        )
        assert lookup["parent"] == called["parent"] == launch["span"]
        assert lookup["t1"] <= put["t0"] <= put["t1"] <= called["t0"]
        assert not _spans("verify.launch.put") and not _spans("verify.fetch.pull")
        for stage in ("sched.queue", "sched.handoff.fetch", "sched.landed",
                      "sched.handoff.wake"):
            assert len(_spans(stage)) == 1, stage

    def test_the_mesh_launch_still_places_five_arrays(
        self, served, monkeypatch
    ):
        """The packed buffer is the one-chip launch's: mesh-wide, each of
        the five arrays is placed across the width, one placement call an
        array, and ``mesh.put`` counts them (``transfers`` 5)."""
        from cometbft_tpu.verifysched import service

        width = 4
        served(width)
        placed = []
        real = jax.device_put
        monkeypatch.setattr(
            jax, "device_put",
            lambda x, *a, **k: placed.append(np.shape(x)) or real(x, *a, **k),
        )
        pubs, msgs, sigs = _signed(b"served/five", self.N)
        assert all(service.verify_segment_sync(pubs, msgs, sigs))
        assert placed == [(BUCKET, 32)] * 4 + [(BUCKET,)]
        (put,) = _spans("mesh.put")
        assert put["attrs"]["transfers"] == len(placed) == 5
        assert put["attrs"]["shards"] == width

    def test_every_shard_pull_says_which_worker_served_it(self, served):
        """ISSUE 37: the launch and each shard's pull are watchdog calls in
        turn, so once the launch's worker has parked every pull finds it:
        five calls a dispatch, four of them at least on a parked worker."""
        from cometbft_tpu.ops import dispatch_stats
        from cometbft_tpu.verifysched import service

        served(4)
        pubs, msgs, sigs = _signed(b"served/worker", self.N)
        assert all(service.verify_segment_sync(pubs, msgs, sigs))
        (disp,) = _spans("verify.dispatch")
        assert disp["attrs"]["worker"] in ("fresh", "parked")
        shards = _spans("mesh.shard")
        assert [s["attrs"]["worker"] for s in shards] == ["parked"] * 4
        calls = dispatch_stats.snapshot()["watchdog_calls"]
        assert calls["parked"] >= 4 and calls["parked"] + calls["fresh"] == 5

    @pytest.mark.parametrize("width", WIDTHS)
    def test_the_parts_add_up(self, width):
        """The accept bits each shard gave, concatenated in ordinal order,
        are the one-device answer, and the psum count is their sum."""
        pmesh.register_devices(jax.devices("cpu"))
        m = pmesh.mesh_of(range(width))
        pubs, msgs, sigs = _signed(b"parts/%d" % width, self.N)
        for i in (0, BUCKET // width, self.N - 1):
            _forge(sigs, i)
        packed, n, structural, _ = ov.pack_batch(pubs, msgs, sigs, BUCKET)
        arrays = ov.packed_views(packed)
        assert arrays["s_ok"].shape[0] == BUCKET and n == self.N
        call, _ = pmesh.sharded_verify_call(m, BUCKET, "xla")
        accept, n_ok = call(*pmesh.device_put_args(arrays, m))
        parts = sorted(
            accept.addressable_shards,
            key=lambda s: pmesh.stable_ordinal(s.device),
        )
        assert len(parts) == width
        assert [s.index[0].start or 0 for s in parts] == [
            k * (BUCKET // width) for k in range(width)
        ]
        joined = np.concatenate([np.asarray(s.data) for s in parts])
        one = np.asarray(ov.bucket_executable("xla", BUCKET)[0](packed))
        assert (joined == one).all()
        assert int(n_ok) == int(joined.sum()) == sum(
            int(np.asarray(s.data).sum()) for s in parts
        )
        assert (joined[:n] & structural[:n]).tolist() == _oracle(pubs, msgs, sigs)

    @pytest.mark.parametrize("width", WIDTHS)
    def test_a_tampered_commit_names_the_same_index_mesh_on_and_off(
        self, commit_of_27, served, width
    ):
        import copy

        from cometbft_tpu.crypto import sigcache
        from cometbft_tpu.parallel import elastic
        from cometbft_tpu.types import validation

        chain_id, vals, bid, height, commit = commit_of_27
        commit = copy.deepcopy(commit)
        tampered = BUCKET // width  # the second shard's first lane
        sig = commit.signatures[tampered].signature
        commit.signatures[tampered].signature = (
            sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
        )
        named = []
        for mesh_on in (True, False):
            sigcache.reset_cache()
            served(width) if mesh_on else elastic.clear()
            with pytest.raises(validation.InvalidSignatureError) as err:
                validation.verify_commit_light(chain_id, vals, bid, height, commit)
            named.append(err.value.index)
        assert named == [tampered, tampered]
        widths = [s["attrs"].get("mesh") for s in _spans("verify.dispatch")]
        assert widths == [width, None]

    @pytest.mark.parametrize("width", WIDTHS)
    def test_the_mesh_takes_a_batch_from_min_batch_up(self, served, width):
        """One under ``min_batch()`` launches on one device, one at it
        launches mesh-wide: the ``verify.dispatch`` span says which."""
        from cometbft_tpu.ops import supervisor

        elastic = served(width)
        assert elastic.min_batch() == 8
        for n, want in ((7, None), (8, width)):
            pubs, msgs, sigs = _signed(b"rule/%d/%d" % (width, n), n)
            h = supervisor.dispatch_verify(pubs, msgs, sigs)
            assert h.kind == ("mesh" if want else "chip")
            assert supervisor.fetch_verify(h).all()
            assert _spans("verify.dispatch")[-1]["attrs"].get("mesh") == want
        assert len(_spans("mesh.shard")) == width

    @pytest.mark.parametrize("width", WIDTHS)
    def test_nothing_compiles_in_a_served_flush_after_the_warm_seam(
        self, served, width
    ):
        """What a node calls at start (``bucket_executable`` for every
        bucket its traffic can reach) leaves the mesh-wide executable of
        the host's width resident: a served flush compiles nothing."""
        from cometbft_tpu.ops import warm_stats
        from cometbft_tpu.verifysched import service

        served(width)
        ov.reset_executable_memo()  # a node's start: nothing resolved yet
        low = ov._min_bucket()
        reachable = [
            b for b in ov._BUCKETS if low <= b <= ov.bucket_size(self.N, low)
        ]
        assert reachable == [BUCKET]
        for lanes in reachable:
            _, info = ov.bucket_executable("xla", lanes)
            assert list(info["mesh"]) == [pmesh.mesh_tag("xla", width, lanes)]
        before = warm_stats.snapshot()
        for lanes in reachable:
            pubs, msgs, sigs = _signed(b"warm/%d/%d" % (width, lanes), lanes - 5)
            assert all(service.verify_segment_sync(pubs, msgs, sigs))
        after = warm_stats.snapshot()
        assert after["compiles"] == before["compiles"]
        assert after["exec_misses"] == before["exec_misses"]
        assert [s["attrs"]["mesh"] for s in _spans("verify.dispatch")] == [width]

    def test_a_bucket_the_mesh_does_not_take_warms_no_mesh_executable(
        self, served, monkeypatch
    ):
        monkeypatch.setenv("COMETBFT_TPU_MESH_MIN_BATCH", "64")
        served(2)
        ov.reset_executable_memo()
        _, info = ov.bucket_executable("xla", BUCKET)
        assert "mesh" not in info

    def test_a_lost_shard_at_fetch_is_verified_again_and_never_half_answered(
        self, served
    ):
        """Width 2, real devices: ordinal 1's shard fails at the fetch of a
        served flush.  Every future resolves with the reference's verdicts
        (the batch verified again, whole, on the one-chip chain: below two
        chips the ladder ends there), the failure is ordinal 1's alone."""
        from cometbft_tpu.crypto import backend_health
        from cometbft_tpu.ops import dispatch_stats
        from cometbft_tpu.verifysched import service

        elastic = served(2)
        elastic.set_fault_injector(elastic.FaultyDevice("raise", ordinals=(1,)))
        pubs, msgs, sigs = _signed(b"lost-shard", self.N)
        _forge(sigs, 2)
        _forge(sigs, 20)  # one bad signature in each shard
        got = service.verify_segment_sync(pubs, msgs, sigs)
        assert got == _oracle(pubs, msgs, sigs)
        reg = backend_health.registry()
        assert reg.breaker("mesh_dev1").stats()["failures_total"] == 1
        assert reg.breaker("mesh_dev0").stats()["failures_total"] == 0
        snap = dispatch_stats.snapshot()
        assert snap["mesh_shrinks"] == 1 and snap["mesh_dispatches"] == 1
        assert snap["inflight_depth"] == 0
        # the first launch over the mesh, the second on one device
        assert [s["attrs"].get("mesh") for s in _spans("verify.dispatch")] == [2, None]
        # the lost launch's signatures under the tier's name, and the
        # re-verification's under the chain's (both ``xla`` on this host)
        assert snap["lane_lanes_used"] == {"xla": 2 * self.N}
        assert snap["lane_dispatches"] == {"xla": 2}
        # the next flush finds ordinal 1's breaker open: one device
        pubs, msgs, sigs = _signed(b"after-the-shrink", self.N)
        assert all(service.verify_segment_sync(pubs, msgs, sigs))
        assert _spans("verify.dispatch")[-1]["attrs"].get("mesh") is None

    def test_a_clean_mesh_wide_flush_is_tallied_under_the_tiers_name(self, served):
        """``offtier_sigs_pct`` looks for the tier's name: a mesh-wide
        dispatch counts there, the chips' own tallies are the shard
        histograms."""
        from cometbft_tpu.ops import dispatch_stats
        from cometbft_tpu.verifysched import service

        served(4)
        pubs, msgs, sigs = _signed(b"tally", self.N)
        assert all(service.verify_segment_sync(pubs, msgs, sigs))
        snap = dispatch_stats.snapshot()
        assert snap["lane_lanes_used"] == {"xla": self.N}
        assert snap["lane_lanes_total"] == {"xla": BUCKET}
        assert sorted(snap["shard_hist"]) == ["0", "1", "2", "3"]
        assert (snap["mesh_dispatches"], snap["mesh_shards"]) == (1, 4)
        assert snap["dispatches"] == 1 and snap["lanes_used"] == self.N
