"""Deterministic simulation harness tests (cometbft_tpu/sim/).

Everything here runs on virtual time — no wall-clock sleeps, no threads —
so a 30-virtual-second partition scenario finishes in a few wall seconds
and a failure reproduces byte-identically from its seed.
"""

from __future__ import annotations

import copy
import dataclasses

import pytest

from cometbft_tpu.sim import SimCluster, run_scenario
from cometbft_tpu.sim.clock import SimTicker, VirtualClock
from cometbft_tpu.consensus.ticker import TimeoutInfo


# ----------------------------------------------------------------------
# virtual clock / ticker units
# ----------------------------------------------------------------------


class TestVirtualClock:
    def test_events_fire_in_time_order(self):
        clock = VirtualClock()
        fired = []
        clock.call_later(3.0, lambda: fired.append("c"))
        clock.call_later(1.0, lambda: fired.append("a"))
        clock.call_later(2.0, lambda: fired.append("b"))
        while clock.tick():
            pass
        assert fired == ["a", "b", "c"]
        assert clock.now() == 3.0

    def test_equal_times_fire_in_schedule_order(self):
        clock = VirtualClock()
        fired = []
        for tag in ("first", "second", "third"):
            clock.call_later(1.0, lambda t=tag: fired.append(t))
        while clock.tick():
            pass
        assert fired == ["first", "second", "third"]

    def test_cancel_is_honoured(self):
        clock = VirtualClock()
        fired = []
        timer = clock.call_later(1.0, lambda: fired.append("x"))
        clock.call_later(2.0, lambda: fired.append("y"))
        timer.cancel()
        while clock.tick():
            pass
        assert fired == ["y"]

    def test_past_schedules_clamp_to_now(self):
        clock = VirtualClock()
        clock.call_later(5.0, lambda: None)
        clock.tick()
        timer = clock.call_at(1.0, lambda: None)  # 1.0 is in the past
        assert timer.when == clock.now()


class TestSimTicker:
    def _mk(self):
        clock = VirtualClock()
        fired = []
        ticker = SimTicker(clock, fired.append)
        ticker.start()
        return clock, ticker, fired

    def test_fires_after_duration(self):
        clock, ticker, fired = self._mk()
        ticker.schedule_timeout(TimeoutInfo(1.5, 1, 0, 1))
        while clock.tick():
            pass
        assert [ti.height for ti in fired] == [1]
        assert clock.now() == 1.5

    def test_later_hrs_replaces_pending(self):
        clock, ticker, fired = self._mk()
        ticker.schedule_timeout(TimeoutInfo(5.0, 1, 0, 1))
        ticker.schedule_timeout(TimeoutInfo(1.0, 1, 1, 1))  # later round, sooner
        while clock.tick():
            pass
        assert [(ti.round_,) for ti in fired] == [(1,)]

    def test_stale_schedule_dropped(self):
        clock, ticker, fired = self._mk()
        ticker.schedule_timeout(TimeoutInfo(1.0, 2, 0, 1))
        ticker.schedule_timeout(TimeoutInfo(0.1, 1, 0, 1))  # earlier height: stale
        while clock.tick():
            pass
        assert [ti.height for ti in fired] == [2]

    def test_stop_suppresses_fire(self):
        clock, ticker, fired = self._mk()
        ticker.schedule_timeout(TimeoutInfo(1.0, 1, 0, 1))
        ticker.stop()
        while clock.tick():
            pass
        assert fired == []


# ----------------------------------------------------------------------
# determinism proof
# ----------------------------------------------------------------------


class TestDeterminism:
    def test_same_seed_identical_trace_and_hashes(self, tmp_path):
        """ISSUE acceptance: same (scenario, seed) twice ⇒ byte-identical
        event traces and identical commit hashes."""
        runs = []
        for sub in ("a", "b"):
            res = run_scenario(
                "baseline", 42, root=tmp_path / sub, keep_cluster=True
            )
            hashes = [
                res.cluster.commit_hash(h)
                for h in range(1, res.target_height + 1)
            ]
            runs.append((res.trace, hashes, res.events))
        assert runs[0][0] == runs[1][0], "event traces diverged"
        assert runs[0][1] == runs[1][1], "commit hashes diverged"
        assert runs[0][2] == runs[1][2]

    def test_different_seeds_diverge(self, tmp_path):
        """Distinct seeds must actually exercise distinct schedules (a
        constant trace would make the determinism check vacuous)."""
        r1 = run_scenario("baseline", 1, root=tmp_path / "s1")
        r2 = run_scenario("baseline", 2, root=tmp_path / "s2")
        assert r1.reached and r2.reached
        assert r1.trace != r2.trace


# ----------------------------------------------------------------------
# fault scenarios
# ----------------------------------------------------------------------


class TestScenarios:
    def test_minority_partition_heals_no_fork(self, tmp_path):
        """4 validators, cut off f=1, heal: the cluster keeps committing
        through the partition and the healed node catches up; the
        agreement invariant holds throughout (raise_on_violation)."""
        res = run_scenario(
            "partition-minority", 42, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        assert min(res.heights) >= res.target_height

    @pytest.mark.parametrize("seed", [42, 1337])
    def test_partition_leader_two_seeds(self, tmp_path, seed):
        """ISSUE acceptance: two different seeds on partition-leader both
        commit >= 5 heights on 4 validators with invariants passing."""
        res = run_scenario(
            "partition-leader", seed, root=tmp_path, raise_on_violation=True
        )
        assert res.reached and res.target_height >= 5
        assert res.commits_verified >= 4 * 5  # every node, every height
        assert not res.violations

    def test_crash_restart_rejoins(self, tmp_path):
        """Crashed node restarts from its stores (WAL + Handshaker replay)
        and rejoins; the wal-replay invariant validates the rebuild."""
        res = run_scenario(
            "crash-restart",
            42,
            root=tmp_path,
            raise_on_violation=True,
            keep_cluster=True,
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        assert any("restart node" in line for line in res.trace)
        # the restarted node holds the canonical chain
        cluster = res.cluster
        for h in range(1, res.target_height + 1):
            metas = {
                n.block_store.load_block_meta(h).block_id.hash
                for n in cluster.live_nodes()
            }
            assert len(metas) == 1, f"fork at height {h}"

    def test_n_vals_override_reaches_action_generators(self, tmp_path):
        """A --validators override must flow into the fault scripts: on a
        7-node cluster the minority partition is f=2 nodes [5, 6], not the
        default-sized scenario's single node [3]."""
        res = run_scenario(
            "partition-minority",
            3,
            root=tmp_path,
            n_vals=7,
            target_height=5,  # past the t=3.0 partition, so the script fires
            raise_on_violation=True,
        )
        assert res.n_vals == 7 and len(res.heights) == 7
        assert any("partition minority [5, 6]" in line for line in res.trace)

    def test_message_storm_commits(self, tmp_path):
        res = run_scenario("message-storm", 42, root=tmp_path,
                           raise_on_violation=True)
        assert res.reached
        assert res.cluster is None  # default: cluster not retained


# ----------------------------------------------------------------------
# invariant checkers catch real violations
# ----------------------------------------------------------------------


class TestInvariantDetection:
    def _committed_cluster(self, tmp_path):
        res = run_scenario("baseline", 42, root=tmp_path, keep_cluster=True)
        assert res.reached
        return res.cluster

    def test_forged_commit_signature_detected(self, tmp_path):
        """Flip a byte in a stored seen-commit signature: the validity
        invariant (production verify_commit path) must reject it."""
        cluster = self._committed_cluster(tmp_path)
        node = cluster.nodes[0]
        commit = node.block_store.load_seen_commit(2)
        forged = copy.deepcopy(commit)
        idx = next(
            i for i, cs in enumerate(forged.signatures) if cs.signature
        )
        sig = bytearray(forged.signatures[idx].signature)
        sig[0] ^= 0xFF
        forged.signatures[idx] = dataclasses.replace(
            forged.signatures[idx], signature=bytes(sig)
        )
        node.block_store.save_seen_commit(2, forged)

        cluster.raise_on_violation = False
        cluster.checker._checked[0] = 0  # force re-verification from genesis
        cluster.checker.on_event(cluster)
        assert any(
            v.invariant == "validity" for v in cluster.checker.violations
        ), cluster.checker.violations

    def test_fork_detected_as_agreement_violation(self, tmp_path):
        """Teach the checker a different canonical hash for a height: the
        next sweep must flag every node as forked."""
        cluster = self._committed_cluster(tmp_path)
        cluster.raise_on_violation = False
        cluster.checker.canonical[3] = b"\x00" * 32
        cluster.checker._checked = {}
        cluster.checker.on_event(cluster)
        agreements = [
            v for v in cluster.checker.violations if v.invariant == "agreement"
        ]
        assert len(agreements) == cluster.n_vals


# ----------------------------------------------------------------------
# backend fault scenarios (ISSUE 4: crypto-backend supervisor)
# ----------------------------------------------------------------------


class TestBackendFaultScenarios:
    """Mid-run accelerator loss must degrade, never stall or fork: zero
    invariant violations, monotone height progress on every node, and the
    breaker's demote/re-promote transitions visible in the run's backend
    stats (the same counters libs/metrics exposes)."""

    def _snapshot_globals(self):
        import os

        from cometbft_tpu.crypto import batch as cbatch

        return (
            os.environ.get("COMETBFT_TPU_CRYPTO_BACKEND"),
            os.environ.get("COMETBFT_TPU_SIGCACHE"),
            os.environ.get("COMETBFT_TPU_DISPATCH_TIMEOUT_MS"),
            cbatch._DEFAULT_BACKEND,
        )

    def test_backend_brownout_agreement_and_repromotion(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")  # dump asserts below
        before = self._snapshot_globals()
        # underscore alias accepted (the issue names it backend_brownout)
        res = run_scenario(
            "backend_brownout", 3, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        assert all(h >= res.target_height for h in res.heights)
        b = res.backend
        assert b["demotions"] >= 1, b
        assert b["breaker_opens"] >= 1, b
        assert b["repromotions"] >= 1, b  # restored after the brownout
        assert b["fallback_signatures"] > 0, b
        assert b["breakers"]["xla"] == "closed", b  # healthy again at end
        # anomaly taxonomy (ISSUE 11): the ed25519 brownout AND the
        # scripted secp/bls breaker failures each produce their OWN dump
        # kind, exactly one dump per kind (first-occurrence latch)
        anomalies = res.spans["anomalies"]
        assert anomalies.get("breaker_open", 0) >= 1, anomalies
        assert anomalies.get("breaker_open_secp_device", 0) == 1, anomalies
        assert anomalies.get("breaker_open_bls_g1", 0) == 1, anomalies
        dump_kinds = [
            d["file"].split("-", 2)[2] for d in res.spans["dumps"]
        ]
        for kind in (
            "breaker_open.jsonl",
            "breaker_open_secp_device.jsonl",
            "breaker_open_bls_g1.jsonl",
        ):
            assert dump_kinds.count(kind) == 1, res.spans["dumps"]
        # scenario teardown restored every piece of process-global state
        assert self._snapshot_globals() == before

    def test_backend_wedge_watchdog_and_progress(self, tmp_path, monkeypatch):
        """Watchdog/breaker behavior under a wedge, PLUS the ISSUE 9
        acceptance forensics on the SAME run (one scenario run, not two,
        for the tier-1 budget): the run yields a JSONL flight-recorder
        dump whose spans attribute the watchdog fire to a specific
        (bucket, tier, dispatch).  Byte-identical same-seed replay is the
        slow-lane test below."""
        import json as _json

        # the dump assertions REQUIRE the recorder: pin it on even if the
        # ambient environment exported the kill switch
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")
        res = run_scenario(
            "backend-wedge", 5, root=tmp_path, raise_on_violation=True,
            keep_cluster=True,
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        b = res.backend
        assert b["watchdog_fires"] >= 1, b
        assert b["demotions"] >= 1, b
        assert b["repromotions"] >= 1, b
        # flight-recorder forensics: dump produced + attribution
        # (keep_cluster preserves the run root, so the dump is readable)
        dump_files = {d["file"] for d in res.spans["dumps"]}
        assert any("watchdog_fire" in f for f in dump_files), res.spans
        assert res.spans["anomalies"].get("watchdog_fire", 0) >= 1
        wd = next(f for f in sorted(dump_files) if "watchdog_fire" in f)
        lines = [_json.loads(l) for l in open(tmp_path / "flight" / wd)]
        head = lines[0]
        assert head["attrs"]["tier"] == "xla"
        assert head["attrs"]["lanes"] >= 1  # the padding bucket
        assert head["attrs"]["dispatch"] >= 1  # the dispatch ordinal
        failed = [
            s for s in lines[1:]
            if s["stage"] == "verify.dispatch"
            and s["attrs"].get("error") == "DispatchTimeoutError"
        ]
        assert failed
        assert failed[-1]["attrs"]["dispatch"] == head["attrs"]["dispatch"]
        res.cluster.stop()

    @pytest.mark.slow
    def test_backend_wedge_dump_byte_identical(self, tmp_path, monkeypatch):
        """Same seed => byte-identical anomaly dumps (name, size, sha256):
        span times ride the VirtualClock and the recorder + dispatch
        ordinal reset per run, so the dump is a pure function of the
        seed.  (Slow lane: doubles a whole scenario run — the PR-1/PR-3
        determinism-double-run precedent; the single-run dump and its
        attribution stay tier-1 above.)"""
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")
        a = run_scenario("backend-wedge", 5, root=tmp_path / "a")
        b = run_scenario("backend-wedge", 5, root=tmp_path / "b")
        assert a.spans["dumps"], a.spans
        assert a.spans["dumps"] == b.spans["dumps"], (
            a.spans["dumps"],
            b.spans["dumps"],
        )
        # the merged CROSS-NODE round timeline replays byte-identically
        # too: span ids, virtual times, quorum stamps and trace linkage
        # are all pure functions of the seed (ISSUE 11)
        import json as _json

        ta = _json.dumps(a.spans["rounds"], sort_keys=True)
        tb = _json.dumps(b.spans["rounds"], sort_keys=True)
        assert ta == tb
        assert a.spans["rounds"]["commits_unlinked"] == 0

    def test_backend_flap_breaker_cycles(self, tmp_path):
        res = run_scenario(
            "backend-flap", 2, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        b = res.backend
        # flapping must produce repeated open->half-open->closed cycles,
        # with exponential backoff between probes (deterministic: the
        # breaker clock is the cluster's VirtualClock)
        assert b["breaker_opens"] >= 2, b
        assert b["repromotions"] >= 1, b

    def test_gossip_burst_sheds_only_bulk(self, tmp_path, monkeypatch):
        """Verify-scheduler overload (ISSUE 5): scripted bulk bursts blow
        past the scenario's 48-slot queue.  Admission control must shed
        only bulk-class items — consensus votes are exempt by design — and
        the cluster must agree and progress as if the overload never
        happened (a shed only costs the batching win, never a verdict)."""
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")  # dump asserts below
        before = self._snapshot_globals()
        res = run_scenario(
            "gossip-burst", 3, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        s = res.sched
        assert s["shed"]["bulk"] > 0, s
        assert s["shed"]["consensus"] == 0, s
        assert s["shed"]["evidence_light"] == 0, s
        assert s["submitted"]["consensus"] > 0, s  # votes rode the scheduler
        assert sum(s["flushes"].values()) > 0, s
        # all admitted futures resolved; nothing left hanging in the queue
        assert s["queue_depth"] == 0, s
        # queue-wait and device time recorded as SEPARATE distributions
        assert s["queue_wait_hist"]["consensus"]["count"] > 0, s
        assert s["device_hist"]["consensus"]["count"] > 0, s
        # the first shed dumped the flight recorder (anomaly forensics)
        assert res.spans["anomalies"].get("queue_shed", 0) > 0, res.spans
        assert any(
            "queue_shed" in d["file"] for d in res.spans["dumps"]
        ), res.spans
        assert res.spans["recorded"] > 0
        assert "sched.flush" in res.spans["stages"], res.spans["stages"]
        assert self._snapshot_globals() == before

    def test_pipeline_burst_overlaps_in_flight(self, tmp_path):
        """In-flight verify pipeline (docs/verify-scheduler.md): with the
        completion pool gated mid-burst, the dispatcher must ship a
        second fused flush while the first is still in flight — and every
        future still resolves with the definitive verdict, consensus
        untouched."""
        before = self._snapshot_globals()
        res = run_scenario(
            "pipeline-burst", 3, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        s = res.sched
        assert s["inflight_hwm"] >= 2, s  # two flushes genuinely overlapped
        assert s["inflight_depth"] == 0, s  # every dispatch was fetched
        assert s["shed"]["consensus"] == 0, s
        assert s["submitted"]["consensus"] > 0, s  # votes rode the scheduler
        assert s["queue_depth"] == 0, s  # nothing left hanging
        assert sum(s["flushes"].values()) > 0, s
        # the pipelined path keeps the flush span and adds the halves
        assert "sched.flush" in res.spans["stages"], res.spans["stages"]
        assert "sched.dispatch" in res.spans["stages"], res.spans["stages"]
        assert "sched.fetch" in res.spans["stages"], res.spans["stages"]
        burst_lines = [l for l in res.trace if "pipelined burst" in l]
        assert len(burst_lines) == 2, burst_lines
        assert self._snapshot_globals() == before

    def test_tx_flood_batched_admission(self, tmp_path):
        """Batched tx ingestion under flood (ISSUE 6, docs/tx-ingest.md):
        scripted bursts of valid/forged/malformed/oversize/duplicate
        signed-tx envelopes against a 32-slot ingest queue.  Overflow must
        shed to the per-tx sync path (a shed costs the batching win, never
        a verdict), consensus-class verify shed stays 0 while the flood
        runs, agreement holds, and every node sees identical admission
        counts (the trace is byte-compared per seed below)."""
        before = self._snapshot_globals()
        res = run_scenario(
            "tx-flood", 3, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        s = res.sched
        assert s["shed"]["consensus"] == 0, s
        assert s["submitted"]["consensus"] > 0, s  # votes rode the scheduler
        assert s["submitted"]["bulk"] > 0, s  # envelope sigs: bulk class
        ing = res.ingest
        assert ing["enqueued"] > 0, ing
        assert ing["shed_to_sync"] > 0, ing  # the 32-slot queue overflowed
        assert ing["admitted"] > 0, ing
        assert ing["app_batches"] > 0, ing
        assert ing["sig_prechecked"] > 0, ing
        assert ing["cache_hits"] > 0, ing  # duplicate bursts deduped
        assert ing["rejected"].get(str(102), 0) > 0, ing  # forged sigs
        assert ing["rejected"].get(str(101), 0) > 0, ing  # malformed
        assert ing["rejected"].get(str(103), 0) > 0, ing  # nonce replays
        assert ing["errors"].get("stale_nonce", 0) > 0, ing
        assert ing["errors"].get("too_large", 0) > 0, ing
        # admission is deterministic: every node logged identical counts
        # ("... tx-flood burst N nodeI: queued=... errors=...")
        flood_lines = [l for l in res.trace if "tx-flood burst" in l]
        assert len(flood_lines) >= res.n_vals
        per_burst: dict = {}
        for line in flood_lines:
            head, counts = line.rsplit(": ", 1)
            burst_no = head.split("burst ")[1].split()[0]
            per_burst.setdefault(burst_no, set()).add(counts)
        assert all(len(v) == 1 for v in per_burst.values()), per_burst
        assert self._snapshot_globals() == before

    def test_light_stampede_proof_plane(self, tmp_path, monkeypatch):
        """Light-client read stampede (ISSUE 16, docs/proof-serving.md):
        scripted bursts of thousands of tx/header/valset proof queries
        against a 512-slot proof queue while consensus runs.  The read
        plane must coalesce same-height queries into single tree builds,
        shed ONLY proof traffic (consensus-class verify shed stays 0 —
        a shed proof costs the coalescing win, never the response), and
        consensus hashing must ride the device tree seam throughout."""
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")  # dump asserts below
        before = self._snapshot_globals()
        res = run_scenario(
            "light-stampede", 3, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        # consensus untouched by the read flood
        assert res.sched["shed"]["consensus"] == 0, res.sched
        p = res.proofs
        assert p["queries_total"] > 0, p
        # every kind was queried and served
        for kind in ("tx", "header", "valset"):
            assert p["queries"][kind] > 0, p
        # coalescing: far fewer tree builds than admitted queries
        assert 0 < p["tree_builds_total"] < p["queries_total"] / 10, p
        assert p["queries_per_flush"] > 100, p
        # the bursts overflow the 512-slot queue: proof shed happened,
        # and the first shed dumped the flight recorder
        assert p["shed_total"] > 0, p
        assert res.spans["anomalies"].get("proof_shed", 0) > 0, res.spans
        assert any(
            "proof_shed" in d["file"] for d in res.spans["dumps"]
        ), res.spans
        # consensus hashing rode the device tree seam (host runner in
        # sim), never the untracked host path, with zero faults
        assert p["trees_device"] > 0, p
        assert p["trees_host"] == 0, p
        assert p["device_fallbacks"] == 0, p
        assert p["serial_fallbacks"] == 0, p
        # nothing left hanging: the teardown drained the server
        assert p["queue_depth"] == 0, p
        assert "merkle.tree" in res.spans["stages"], res.spans["stages"]
        assert "proof.flush" in res.spans["stages"], res.spans["stages"]
        assert self._snapshot_globals() == before

    @pytest.mark.slow
    def test_light_stampede_deterministic(self, tmp_path):
        """Same seed => byte-identical traces with the proof server in
        the loop: flush grouping is paused/resumed around each scripted
        burst so shed and build counts are a pure function of the seed
        even with the dispatcher thread running.  (Slow lane: doubles a
        whole scenario run — the PR-1/PR-3 precedent.)"""
        a = run_scenario("light-stampede", 17, root=tmp_path / "a")
        b = run_scenario("light-stampede", 17, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.heights == b.heights
        assert a.proofs == b.proofs

    @pytest.mark.slow
    def test_tx_flood_deterministic(self, tmp_path):
        """Same seed => byte-identical traces with batched admission in
        the tx path: flush grouping is wall-time-dependent, verdicts (and
        the logged per-burst admission counts) are not.  (Slow lane:
        doubles a whole scenario run — the PR-1/PR-3 precedent.)"""
        a = run_scenario("tx-flood", 17, root=tmp_path / "a")
        b = run_scenario("tx-flood", 17, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.heights == b.heights
        assert a.ingest == b.ingest

    @pytest.mark.slow
    def test_gossip_burst_deterministic(self, tmp_path, monkeypatch):
        """Same seed => byte-identical traces with the scheduler in the
        verify path: coalescing grouping is wall-time-dependent, but
        verdicts (and therefore every traced event, including the shed
        counts logged by the burst actions) are not.  (Slow lane: doubles
        a whole scenario run — the PR-1/PR-3 precedent for determinism
        double-runs; single-run scheduler behavior stays tier-1 above.)"""
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")  # dump asserts below
        a = run_scenario("gossip-burst", 17, root=tmp_path / "a")
        b = run_scenario("gossip-burst", 17, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.heights == b.heights
        assert a.sched["shed"] == b.sched["shed"]
        # the queue-shed anomaly dump replays byte-identically too: the
        # flight recorder rides the VirtualClock and resets per run, so
        # dump bytes are a pure function of the seed even with the
        # dispatcher thread in the loop (flush spans land while the
        # single-threaded sim blocks on its verdicts)
        assert a.spans["dumps"] == b.spans["dumps"], (
            a.spans["dumps"],
            b.spans["dumps"],
        )
        assert any("queue_shed" in d["file"] for d in a.spans["dumps"])

    @pytest.mark.slow
    def test_pipeline_burst_deterministic(self, tmp_path):
        """Same seed => byte-identical traces with the completion pool in
        the loop: each burst action blocks on every future before logging,
        so nothing in the trace can depend on dispatch/fetch interleaving.
        (Slow lane: doubles a whole scenario run — the PR-1/PR-3
        precedent.)"""
        a = run_scenario("pipeline-burst", 17, root=tmp_path / "a")
        b = run_scenario("pipeline-burst", 17, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.heights == b.heights
        assert a.sched["shed"] == b.sched["shed"]
        assert a.sched["verdicts"] == b.sched["verdicts"]

    @pytest.mark.slow
    def test_backend_brownout_deterministic(self, tmp_path):
        """Byte-identical replay with backend faults active (slow lane:
        baseline trace determinism is already tier-1-pinned by
        TestDeterminism; this doubles a whole scenario run)."""
        a = run_scenario("backend-brownout", 11, root=tmp_path / "a")
        b = run_scenario("backend-brownout", 11, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.backend == b.backend


# ----------------------------------------------------------------------
# soak (slow)
# ----------------------------------------------------------------------


@pytest.mark.slow
class TestSoak:
    @pytest.mark.parametrize("seed", range(5))
    def test_partition_minority_seed_sweep(self, tmp_path, seed):
        res = run_scenario(
            "partition-minority",
            seed,
            root=tmp_path,
            raise_on_violation=True,
        )
        assert res.reached, f"seed {seed}: heights {res.heights}"
        assert not res.violations

    def test_long_baseline_soak(self, tmp_path):
        res = run_scenario(
            "baseline",
            99,
            root=tmp_path,
            target_height=30,
            max_time=600.0,
            raise_on_violation=True,
        )
        assert res.reached
        assert not res.violations

    def test_backend_brownout_real_device(self, tmp_path, monkeypatch):
        """The tier-1 brownout runs on the supervisor's host-backed device
        runner (a real XLA-CPU dispatch costs ~1.7 s on this host); the
        slow lane proves the same scenario against the real kernel."""
        monkeypatch.setenv("COMETBFT_TPU_SIM_REAL_DEVICE", "1")
        res = run_scenario(
            "backend-brownout", 1, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        assert res.backend["demotions"] >= 1


# ----------------------------------------------------------------------
# fleet scale: validator rotation, churn, statesync joins (ISSUE 7)
# ----------------------------------------------------------------------


class TestFleetScale:
    def test_validator_rotation_invariants_track_the_set(self, tmp_path):
        """A standby is voted in and a genesis validator out; the checker
        replays the rotation itself (validator-set invariant) and verifies
        every commit against the height-correct set."""
        res = run_scenario(
            "validator-rotation", 3, root=tmp_path,
            raise_on_violation=True, keep_cluster=True,
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        assert res.rotations == 2  # one add, one removal
        sizes = {
            h: len(v) for h, v in res.cluster.checker.val_sets.items()
        }
        assert 5 in sizes.values()  # the spare joined the set
        assert sizes[max(sizes)] == 4  # and node0 left it again

    def test_fleet_churn_small_scale(self, tmp_path, monkeypatch):
        """ISSUE acceptance (tier-1 variant): rotation + churn — statesync
        join, graceful leave, crash-restart — at 8 validators on the
        host-path seam; the 100-validator variant runs in the slow lane.

        PLUS the ISSUE 11 acceptance on the SAME run (one scenario run,
        not two, for the tier-1 budget): the merged cross-node round
        timeline must link every commit's verify spans back to the
        originating proposal's trace id, with per-step p50/p99 rendered."""
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")  # timeline asserts
        # a vote is two spans since PR 32 (``voteset.add`` around
        # ``consensus.vote``): 5,149 spans where 4,096 held the run
        monkeypatch.setenv("COMETBFT_TPU_TRACE_RING", "8192")
        from cometbft_tpu.libs import tracing

        tracing.reset_tracer()
        res = run_scenario(
            "fleet-churn", 3, root=tmp_path, n_vals=8,
            raise_on_violation=True,
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        assert res.rotations >= 2
        assert any("statesync complete" in l for l in res.trace), (
            "the spare must have joined via statesync"
        )
        assert any("leave node7" in l for l in res.trace)
        assert res.heights[7] == -1  # the leaver stayed gone
        assert any("crash node1" in l for l in res.trace)
        # -- merged cross-node round timeline (ISSUE 11 acceptance) ------
        rep = res.spans["rounds"]
        assert res.spans["dropped"] == 0  # the whole run fits the ring
        assert rep["rounds_seen"] >= res.target_height
        # every consensus-path verify.commit links to a round trace; zero
        # broken linkage (standalone = checker/light verifies, separate)
        assert rep["commits_linked"] > 0
        assert rep["commits_unlinked"] == 0, rep
        # every round that carried commit verify-work has a resolved root
        # — the originating proposal's span — and the root is a proposer
        committed = [g for g in rep["rounds"] if g["commits"] > 0]
        assert committed
        for g in committed:
            assert g["trace"] is not None, g
            assert g["origin"] is not None, (
                "round (%s,%s) commits lack a root proposal" % (g["h"], g["r"])
            )
            # 8-validator cluster: the adopted members joined the
            # proposer's tree over the gossip fabric
            adopted = [n for n in g["nodes"] if n.get("adopted")]
            assert adopted, g
        # per-step latency percentiles render for the consensus steps
        for step in ("RoundStepPropose", "RoundStepPrevote",
                     "RoundStepPrecommit"):
            assert rep["steps"][step]["count"] > 0, rep["steps"]
            assert rep["steps"][step]["p99_ms"] >= 0.0
        # quorum-arrival times landed on the round anchors
        assert rep["quorum"]["prevote_ms"]["count"] > 0
        assert rep["quorum"]["precommit_ms"]["count"] > 0
        # and the soak-facing summary row carries the same shape
        row = res.summary()["spans"]["rounds"]
        assert row["seen"] == rep["rounds_seen"]
        assert row["commits_unlinked"] == 0
        assert "RoundStepPrevote" in row["steps"]

    def test_statesync_storm_joins_through_loss(self, tmp_path):
        """Two joiners statesync through 25%-lossy links while a serving
        peer crashes mid-run: backoff + peer rotation must still land both
        joins, with invariants green."""
        res = run_scenario(
            "statesync-storm", 3, root=tmp_path,
            raise_on_violation=True, keep_cluster=True,
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        joins = [l for l in res.trace if "statesync complete" in l]
        assert len(joins) == 2, joins
        # the storm actually dropped traffic (incl. chunk transfers)
        assert res.cluster.net.stats.dropped_rate > 0

    def test_dup_vote_flood_degrades_to_drops(self, tmp_path):
        """Evidence-pool hardening under flood: dedup before signature
        work, bound overflow -> counted drops, forgeries rejected, real
        evidence still committed through the verifysched evidence class,
        consensus never shed."""
        res = run_scenario(
            "dup-vote-flood", 3, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        evd = res.evidence
        assert evd["added"] > 0, evd
        assert evd["dedup"] > 0, evd
        assert evd["dropped"] > 0, evd  # the 8-entry bound engaged
        assert evd["rejected"] > 0, evd  # forged signatures
        assert evd["committed"] > 0, evd  # real evidence reached blocks
        s = res.sched
        assert s["submitted"]["evidence_light"] > 0, s
        assert s["shed"]["consensus"] == 0, s
        assert s["shed"]["evidence_light"] == 0, s

    def test_light_attack_verified_and_forgery_rejected(self, tmp_path):
        res = run_scenario(
            "light-attack", 3, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        evd = res.evidence
        assert evd["added"] > 0, evd  # the real lunatic attack verified
        assert evd["rejected"] > 0, evd  # the signature-broken one did not
        assert evd["committed"] > 0, evd
        s = res.sched
        assert s["submitted"]["evidence_light"] > 0, s
        assert s["shed"]["consensus"] == 0, s

    def test_combined_storm_composes_four_faults(self, tmp_path):
        """ISSUE acceptance: partition + backend brownout + gossip burst
        + a mesh blackout in ONE script (compose()) — agreement holds,
        consensus-class verify shed is 0, only bulk sheds, and the FULL
        ladder degrades: the mesh collapses below width 2 (3 shrinks), so
        the single-chip brownout underneath really fires (xla breaker
        opens, host fallback carries signatures), and every layer
        re-promotes after the storm."""
        res = run_scenario(
            "combined-storm", 3, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        s = res.sched
        assert s["shed"]["consensus"] == 0, s
        assert s["shed"]["evidence_light"] == 0, s
        assert s["shed"]["bulk"] > 0, s
        b = res.backend
        assert b["demotions"] >= 1, b
        assert b["repromotions"] >= 1, b
        # the mesh blackout really collapsed the mesh (one shrink per
        # dead ordinal) and every chip was probe-re-admitted after it
        assert b["mesh_shrinks"] >= 3, b
        assert b["mesh_restores"] >= 3, b
        assert b["mesh_width"] == 4, b
        # ... which means the single-chip chain REALLY ran under the
        # composed brownout: the xla breaker opened and the host tier
        # carried real signatures (the composed fault is not dead code)
        assert res.spans["anomalies"].get("breaker_open", 0) >= 1
        assert b["fallback_signatures"] > 0, b
        assert b["breakers"]["xla"] == "closed", b  # re-promoted
        assert res.spans["anomalies"].get("mesh_shrink", 0) >= 3
        assert res.spans["anomalies"].get("mesh_restore", 0) >= 3
        # the partition really happened too
        assert any("partition minority" in l for l in res.trace)

    @pytest.mark.slow
    def test_fleet_churn_deterministic(self, tmp_path, monkeypatch):
        """Same seed => byte-identical traces through statesync join,
        graceful leave, crash-restart AND rotation in one run — and the
        merged cross-node round timeline (ISSUE 11) replays byte-for-byte
        with them: trace contexts on the gossip fabric add no
        nondeterminism."""
        import json as _json

        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")
        a = run_scenario("fleet-churn", 17, root=tmp_path / "a")
        b = run_scenario("fleet-churn", 17, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.heights == b.heights
        assert a.rotations == b.rotations
        ta = _json.dumps(a.spans["rounds"], sort_keys=True)
        tb = _json.dumps(b.spans["rounds"], sort_keys=True)
        assert ta == tb
        assert a.spans["rounds"]["rounds_seen"] > 0

    @pytest.mark.slow
    def test_fleet_churn_100_validators(self, tmp_path):
        """ISSUE acceptance (nightly): the full 100-validator fleet with
        rotation + churn completes with invariants green and byte-identical
        traces across two same-seed runs."""
        a = run_scenario(
            "fleet-churn", 3, root=tmp_path / "a", n_vals=100,
            raise_on_violation=True,
        )
        assert a.reached, f"heights {sorted(set(a.heights))}"
        assert not a.violations
        assert a.rotations >= 2
        assert any("statesync complete" in l for l in a.trace)
        b = run_scenario("fleet-churn", 3, root=tmp_path / "b", n_vals=100)
        assert a.trace == b.trace, "100-validator trace diverged"

    @pytest.mark.slow
    def test_dup_vote_flood_deterministic(self, tmp_path):
        a = run_scenario("dup-vote-flood", 17, root=tmp_path / "a")
        b = run_scenario("dup-vote-flood", 17, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.evidence == b.evidence


# ----------------------------------------------------------------------
# validator-rotation edge cases (ISSUE 7 satellite)
# ----------------------------------------------------------------------


class TestRotationEdgeCases:
    def _churn_cluster(self, tmp_path, seed=7):
        from cometbft_tpu.sim.cluster import SimCluster

        return SimCluster(
            4, tmp_path, seed=seed, n_spares=1, raise_on_violation=True
        )

    def test_rotation_landing_with_crash_restart(self, tmp_path):
        """A validator crashes in the same window the set change lands and
        restarts across it: WAL + Handshaker replay must rebuild against
        the NEW set (the wal-replay + validator-set invariants check every
        replayed height)."""
        c = self._churn_cluster(tmp_path)
        c.start()
        c.clock.call_at(3.0, lambda: c.spawn_spare(4), label="spawn")
        c.clock.call_at(3.5, lambda: c.add_validator(4), label="rotate-in")
        # the update commits around h5-6; crash node1 right in that window
        c.clock.call_at(5.2, lambda: c.crash(1), label="crash")
        c.clock.call_at(9.0, lambda: c.restart(1), label="restart")
        assert c.run(until_height=12, max_time=120.0)
        assert not c.checker.violations
        assert c.checker.rotations_seen == 1
        # the restarted node reconverged on the post-rotation chain
        assert c.nodes[1].block_store.height() >= 12
        assert any("restart node1" in l for l in c.trace)
        c.stop()

    def test_proposer_rotation_across_set_change(self, tmp_path):
        """Proposer selection keeps rotating across a membership change:
        post-rotation heights are proposed by members of the NEW set
        (including, eventually, the joiner) and never by the removed
        validator."""
        c = self._churn_cluster(tmp_path)
        c.start()
        c.clock.call_at(1.0, lambda: c.spawn_spare(4), label="spawn")
        c.clock.call_at(2.0, lambda: c.add_validator(4), label="rotate-in")
        c.clock.call_at(4.0, lambda: c.remove_validator(0), label="rotate-out")
        assert c.run(until_height=13, max_time=180.0)
        assert not c.checker.violations

        removed_addr = c.privs[0].pub_key().address()
        spare_addr = c.privs[4].pub_key().address()
        # find the first height whose canonical set dropped node0
        out_height = min(
            h
            for h, vals in c.checker.val_sets.items()
            if vals.get_by_address(removed_addr) is None
        )
        proposers = []
        for h in range(out_height, 14):
            meta = c.nodes[1].block_store.load_block_meta(h)
            proposers.append(meta.header.proposer_address)
            assert meta.header.proposer_address != removed_addr, (
                f"removed validator proposed height {h}"
            )
            vals = c.checker.val_sets[h]
            assert vals.get_by_address(meta.header.proposer_address), (
                f"height {h} proposer not in that height's set"
            )
        assert len(set(proposers)) >= 3  # rotation actually rotates
        assert spare_addr in proposers  # the joiner got its turn
        c.stop()

    def test_verify_commit_needs_height_correct_set(self, tmp_path):
        """The checker verified post-rotation commits against the rotated
        set; the same commit must NOT verify against the genesis set —
        pinning the set (the pre-ISSUE-7 behavior) would be vacuous."""
        from cometbft_tpu.types import validation

        c = self._churn_cluster(tmp_path)
        c.start()
        c.clock.call_at(1.0, lambda: c.spawn_spare(4), label="spawn")
        c.clock.call_at(2.0, lambda: c.add_validator(4), label="rotate-in")
        assert c.run(until_height=10, max_time=120.0)
        assert not c.checker.violations
        genesis_vals = c.checker.val_sets[1]
        h = max(
            h for h, v in c.checker.val_sets.items()
            if h <= 10 and len(v) == 5
        )
        node = c.nodes[0]
        meta = node.block_store.load_block_meta(h)
        commit = node.block_store.load_seen_commit(h)
        with pytest.raises(validation.CommitVerificationError):
            validation.verify_commit(
                "sim-chain", genesis_vals, meta.block_id, h, commit,
                backend="cpu",
            )
        # while the height-correct set accepts it (what the checker did)
        validation.verify_commit(
            "sim-chain", c.checker.val_sets[h], meta.block_id, h, commit,
            backend="cpu",
        )
        c.stop()

    def test_header_forgery_detected_as_validator_set_violation(
        self, tmp_path
    ):
        """Tampering a stored header's validator hashes must trip the new
        validator-set invariant when re-checked."""
        import dataclasses

        res = run_scenario(
            "baseline", 42, root=tmp_path, keep_cluster=True
        )
        cluster = res.cluster
        node = cluster.nodes[0]
        meta = node.block_store.load_block_meta(3)
        forged_header = dataclasses.replace(
            meta.header, next_validators_hash=b"\x66" * 32
        )
        forged = dataclasses.replace(meta, header=forged_header)
        # store the forged meta through the block store's own codec
        from cometbft_tpu.store import block_store as bs_mod

        node.block_store._db.set(bs_mod._k_meta(3), forged.encode())
        cluster.raise_on_violation = False
        cluster.checker._checked[0] = 0
        cluster.checker.on_event(cluster)
        kinds = {v.invariant for v in cluster.checker.violations}
        assert "validator-set" in kinds, cluster.checker.violations


# ----------------------------------------------------------------------
# elastic mesh fault scenarios (ISSUE 13: per-shard fault isolation)
# ----------------------------------------------------------------------


class TestMeshFaultScenarios:
    """Chip-level faults on the 4-wide virtual mesh must cost a lane,
    never the fleet: the failed dispatch alone re-runs on the shrunken
    mesh, breakers exclude/re-admit deterministically on the virtual
    clock, verdicts never change, and the whole story lands on the
    observability rails (anomaly kinds, dumps, journal events)."""

    def test_chip_death_fleet_keeps_committing(self, tmp_path, monkeypatch):
        """ISSUE acceptance: a chip dies mid-dispatch at a scripted time;
        the fleet keeps committing, exactly one shrink re-runs the failed
        dispatch, the breaker attributes the death to the right ordinal,
        and the anomaly dump's header names that ordinal."""
        import json as _json

        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")  # dump asserts below
        res = run_scenario(
            "chip-death", 3, root=tmp_path, raise_on_violation=True,
            keep_cluster=True,
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        b = res.backend
        # the dead chip's dispatch failure + its failed re-admission
        # probes all attribute to mesh_dev2; the probe-marked ordinal 1
        # was excluded proactively and re-admitted by a passing probe
        assert b["breakers"]["mesh_dev2"] in ("open", "half-open"), b
        assert b["mesh_shrinks"] >= 2, b  # the death + the probe-down
        assert b["mesh_restores"] >= 1, b  # ordinal 1 came back
        assert b["mesh_width"] == 3, b  # only the corpse stays out
        anomalies = res.spans["anomalies"]
        assert anomalies.get("mesh_shrink", 0) >= 2, anomalies
        assert anomalies.get("mesh_restore", 0) >= 1, anomalies
        assert anomalies.get("breaker_open_mesh_dev2", 0) >= 1, anomalies
        assert anomalies.get("breaker_open_mesh_dev1", 0) == 1, anomalies
        # the mesh_shrink dump attributes the death to ordinal 2
        dump = next(
            d["file"] for d in res.spans["dumps"]
            if d["file"].endswith("mesh_shrink.jsonl")
        )
        lines = [
            _json.loads(l) for l in open(tmp_path / "flight" / dump)
        ]
        assert lines[0]["anomaly"] == "mesh_shrink"
        assert lines[0]["attrs"]["ordinal"] == 2
        assert lines[0]["attrs"]["width"] == 3
        # the failed shard span is in the dump, keyed by stable ordinal
        failed = [
            s for s in lines[1:]
            if s["stage"] == "mesh.shard" and s["attrs"].get("error")
        ]
        assert failed and failed[-1]["attrs"]["device"] == 2
        res.cluster.stop()

    def test_mesh_brownout_shrinks_and_restores(self, tmp_path, monkeypatch):
        """A flapping chip: the breaker must cycle open -> half-open ->
        closed on the virtual-clock backoff, with pass-phase probes
        re-admitting the chip, and the mesh must settle at full width."""
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")
        res = run_scenario(
            "mesh-brownout", 3, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        b = res.backend
        assert b["mesh_shrinks"] >= 1, b
        assert b["mesh_restores"] >= 1, b
        assert b["mesh_width"] == 4, b  # settled back at full width
        assert b["repromotions"] >= 1, b
        assert b["breakers"]["mesh_dev1"] == "closed", b
        anomalies = res.spans["anomalies"]
        assert anomalies.get("mesh_shrink", 0) >= 1, anomalies
        assert anomalies.get("mesh_restore", 0) >= 1, anomalies

    @pytest.mark.slow
    def test_chip_death_deterministic(self, tmp_path, monkeypatch):
        """Same seed => byte-identical traces AND anomaly dumps with the
        elastic mesh in the verify path: breaker backoff rides the
        virtual clock, flap/death counters are per-ordinal and seeded,
        so the whole degradation story is a pure function of the seed.
        (Slow lane: doubles a whole scenario run — PR-1/PR-3 precedent.)"""
        monkeypatch.setenv("COMETBFT_TPU_TRACE", "1")
        a = run_scenario("chip-death", 7, root=tmp_path / "a")
        b = run_scenario("chip-death", 7, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.heights == b.heights
        assert a.backend == b.backend
        assert a.spans["dumps"], a.spans
        assert a.spans["dumps"] == b.spans["dumps"]

    @pytest.mark.slow
    def test_mesh_brownout_deterministic(self, tmp_path):
        a = run_scenario("mesh-brownout", 11, root=tmp_path / "a")
        b = run_scenario("mesh-brownout", 11, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.backend == b.backend


# ----------------------------------------------------------------------
# byzantine voting (ISSUE 13 satellite; ROADMAP item 5 follow-up)
# ----------------------------------------------------------------------


class TestByzantineVoter:
    def test_equivocation_becomes_committed_evidence(self, tmp_path):
        """A LIVE validator double-signs prevotes/precommits through the
        production gossip path: honest nodes must detect the conflict in
        their vote sets, convert it to DuplicateVoteEvidence at finalize
        (the evidence pool's consensus buffer — no crafted evidence
        anywhere), COMMIT it, and hold agreement + validator-set
        invariants."""
        res = run_scenario(
            "byzantine-voter", 3, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"heights {res.heights}"
        assert not res.violations
        evd = res.evidence
        assert evd["added"] > 0, evd  # real equivocations pooled
        assert evd["committed"] > 0, evd  # and committed in blocks
        assert evd["rejected"] == 0, evd  # nothing forged in this path
        assert any("turns byzantine" in l for l in res.trace)
        assert any("honest again" in l for l in res.trace)

    def test_committed_evidence_names_the_byzantine_validator(
        self, tmp_path
    ):
        """The committed duplicate-vote evidence must attribute to the
        equivocating validator's address, with two votes at the same
        (height, round, type) and different block ids — the production
        evidence shape, end to end."""
        from cometbft_tpu.types.evidence import DuplicateVoteEvidence

        res = run_scenario(
            "byzantine-voter", 5, root=tmp_path, raise_on_violation=True,
            keep_cluster=True,
        )
        assert res.reached
        cluster = res.cluster
        byz_addr = cluster.privs[res.n_vals - 1].pub_key().address()
        found = []
        node = cluster.live_nodes()[0]
        for h in range(1, node.block_store.height() + 1):
            blk = node.block_store.load_block(h)
            if blk is None:
                continue
            for ev in blk.evidence:
                if isinstance(ev, DuplicateVoteEvidence):
                    found.append(ev)
        assert found, "no duplicate-vote evidence committed"
        for ev in found:
            assert ev.vote_a.validator_address == byz_addr
            assert ev.vote_b.validator_address == byz_addr
            assert ev.vote_a.height == ev.vote_b.height
            assert ev.vote_a.round_ == ev.vote_b.round_
            assert ev.vote_a.type_ == ev.vote_b.type_
            assert ev.vote_a.block_id.hash != ev.vote_b.block_id.hash
        cluster.stop()

    @pytest.mark.slow
    def test_byzantine_voter_deterministic(self, tmp_path):
        a = run_scenario("byzantine-voter", 17, root=tmp_path / "a")
        b = run_scenario("byzantine-voter", 17, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.heights == b.heights
        assert a.evidence == b.evidence


class TestDiskFaultScenarios:
    """Storage-plane robustness (docs/storage-robustness.md): fail-stop
    halts, degrade-with-retries, and torn-tail boot repair driven by the
    deterministic diskguard injector."""

    def test_disk_full_fail_stops_victim_survivors_agree(self, tmp_path):
        from cometbft_tpu.sim.scenarios import DISK_VICTIM

        res = run_scenario(
            "disk-full", 7, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"survivors stalled: {res.heights}"
        assert not res.violations
        # the victim fail-stopped: halted, zero participation after
        assert res.fail_stopped == [DISK_VICTIM]
        assert res.heights[DISK_VICTIM] == -1
        # survivors all reached the target (agreement checker green)
        for i, h in enumerate(res.heights):
            if i != DISK_VICTIM and i < res.n_vals:
                assert h >= res.target_height, res.heights
        totals = res.storage["totals"]
        assert totals["fatals"] == 1, totals           # one halted WAL
        assert totals["drops"] >= 1, totals            # blackbox degraded
        surfaces = res.storage["surfaces"]
        assert surfaces["wal"]["fatals"] == 1
        assert surfaces["blackbox"]["fatals"] == 0     # degrade, never halt
        # anomaly attribution: the fail-stop journaled disk_fatal
        anomalies = res.spans["anomalies"]
        assert anomalies.get("disk_fatal", 0) == 1, anomalies
        assert anomalies.get("disk_fault", 0) >= 1, anomalies
        # the halt is visible in the trace with surface/op attribution
        assert any("STORAGE FATAL" in line for line in res.trace)
        row = res.summary()
        assert row["storage"]["fail_stopped_nodes"] == [DISK_VICTIM]

    def test_disk_brownout_retries_recover_no_halt(self, tmp_path):
        res = run_scenario(
            "disk-brownout", 7, root=tmp_path, raise_on_violation=True
        )
        assert res.reached and not res.violations
        assert res.fail_stopped == []
        assert all(h >= res.target_height for h in res.heights)
        totals = res.storage["totals"]
        # three short bursts recovered via retries; the long burst
        # degraded to counted drops; nothing fail-stopped
        assert totals["retries"] >= 6, totals
        assert totals["drops"] >= 1, totals
        assert totals["fatals"] == 0, totals
        assert res.spans["anomalies"].get("disk_fault", 0) >= 1

    def test_torn_wal_restart_repairs_and_rejoins(self, tmp_path):
        from cometbft_tpu.sim.scenarios import DISK_VICTIM

        res = run_scenario(
            "torn-wal-restart", 7, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"victim never rejoined: {res.heights}"
        assert not res.violations
        assert res.fail_stopped == []
        # the victim is back at (or past) the target after the repair
        assert res.heights[DISK_VICTIM] >= res.target_height
        totals = res.storage["totals"]
        assert totals["repairs"] == 1, totals
        assert totals["repaired_bytes"] > 0, totals
        assert totals["fatals"] == 0, totals
        # the repair is logged with byte attribution and journaled into
        # the victim's fresh black box
        repair_lines = [l for l in res.trace if "wal_repair" in l]
        assert len(repair_lines) == 1, res.trace[-20:]
        assert "node%d" % DISK_VICTIM in repair_lines[0]
        # the victim's pre-crash journal decoded as an unclean shutdown
        assert res.postmortems, "no postmortem captured at restart"
        assert res.postmortems[0]["node"] == DISK_VICTIM
        assert res.postmortems[0]["report"]["unclean_shutdown"] is True

    def test_disk_full_same_seed_same_trace(self, tmp_path):
        """The disk-full run twice with one seed: byte-identical traces
        (the injector consumes the same rule windows on the same IO
        sequence), the victim halted at -1 in both, at least one fatal
        counted, the survivors at the target with no violation."""
        a = run_scenario("disk-full", 11, root=tmp_path / "a")
        b = run_scenario("disk-full", 11, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.reached and not a.violations
        assert a.fail_stopped
        assert all(a.heights[v] == -1 for v in a.fail_stopped)
        assert a.storage["totals"]["fatals"] >= 1

    @pytest.mark.slow
    def test_disk_scenarios_deterministic(self, tmp_path):
        import json as _json

        for name in ("disk-full", "disk-brownout", "torn-wal-restart"):
            a = run_scenario(name, 17, root=tmp_path / (name + "-a"))
            b = run_scenario(name, 17, root=tmp_path / (name + "-b"))
            assert a.trace == b.trace, name
            assert a.heights == b.heights, name
            assert _json.dumps(a.summary(), sort_keys=True) == _json.dumps(
                b.summary(), sort_keys=True
            ), name

    def test_diskguard_kill_switch_restores_behavior(
        self, tmp_path, monkeypatch
    ):
        """COMETBFT_TPU_DISKGUARD=0: the injector never fires (a hostile
        plan is a no-op), no storage stats are recorded, and the run is
        a plain baseline."""
        monkeypatch.setenv("COMETBFT_TPU_DISKGUARD", "0")
        res = run_scenario(
            "disk-full", 7, root=tmp_path, raise_on_violation=True
        )
        assert res.reached and not res.violations
        assert res.fail_stopped == []          # nobody halted
        assert all(h >= res.target_height for h in res.heights)
        assert res.storage == {}               # guard fully bypassed


class TestBlocksyncScenarios:
    """Deterministic blocksync under WAN-grade faults (blocksync-storm /
    wan-catchup): a late joiner catches 40+ heights through lossy
    bandwidth-shaped links while the adaptive pool bans, probes and
    re-admits misbehaving helpers."""

    def test_blocksync_storm_joiner_survives_faults(self, tmp_path):
        res = run_scenario(
            "blocksync-storm", 7, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"cluster stalled: {res.heights}"
        assert not res.violations
        # the joiner caught the full catchup span through the storm
        assert res.bsync.get("heights_synced", 0) >= 40, res.bsync
        # every leg of the fault envelope actually fired: timeouts on
        # dropped replies, a strike ban on the forger, the half-open
        # probe, and a re-admission after the probe answered
        assert res.bsync["timeouts"] >= 1, res.bsync
        assert res.bsync["bans"] >= 1, res.bsync
        assert res.bsync["probes"] >= 1, res.bsync
        assert res.bsync["probe_passes"] >= 1, res.bsync
        assert res.bsync["redos"] >= 1, res.bsync      # forged block redone
        # the crash-restart leg: the joiner died mid-catchup and resumed
        assert any("crashed mid-catchup" in line for line in res.trace)
        # ban -> probe -> re-admission is narrated in the shared trace
        assert any("blocksync peer banned" in line for line in res.trace)
        assert any("blocksync half-open probe" in line for line in res.trace)
        assert any(
            "probe passed, peer re-admitted" in line for line in res.trace
        )
        # the joiner's completion line carries the fused-prefetch budget
        done = [
            l for l in res.trace
            if "bsync node" in l and "complete h=" in l
        ]
        assert done, res.trace[-20:]
        assert "dispatches=" in done[-1]

    def test_wan_catchup_cross_region_through_partition(self, tmp_path):
        res = run_scenario(
            "wan-catchup", 7, root=tmp_path, raise_on_violation=True
        )
        assert res.reached, f"cluster stalled: {res.heights}"
        assert not res.violations
        # the joiner synced cross-region despite the mid-sync partition
        assert res.bsync.get("heights_synced", 0) >= 40, res.bsync
        assert any("complete h=" in line for line in res.trace)

    def test_blocksync_kill_switch_disables_adaptive(
        self, tmp_path, monkeypatch
    ):
        """COMETBFT_TPU_BSYNC_ADAPTIVE=0: fixed 15 s timeouts, flat bans,
        no half-open probes — and the catchup still completes.  (Seed 3:
        under flat 15 s timeouts some seeds leave the joiner mid-sync
        when the scenario window closes; seed 3 finishes inside it.)"""
        monkeypatch.setenv("COMETBFT_TPU_BSYNC_ADAPTIVE", "0")
        res = run_scenario(
            "blocksync-storm", 3, root=tmp_path, raise_on_violation=True
        )
        assert res.reached and not res.violations
        assert res.bsync.get("heights_synced", 0) >= 40, res.bsync
        assert res.bsync["probes"] == 0, res.bsync     # no half-open plane
        assert res.bsync["probe_passes"] == 0, res.bsync

    def test_blocksync_storm_same_seed_same_trace(self, tmp_path):
        """The blocksync storm twice with one seed: byte-identical traces
        and pool counters, the joiner done past 40 heights through a ban
        and a passed probe, and the fused prefetch under one dispatch a
        synced height (the count its completion line reports)."""
        import re

        a = run_scenario("blocksync-storm", 11, root=tmp_path / "a")
        b = run_scenario("blocksync-storm", 11, root=tmp_path / "b")
        assert a.trace == b.trace
        assert a.bsync == b.bsync
        assert a.reached and b.reached and not a.violations
        heights = a.bsync["heights_synced"]
        assert heights >= 40, a.bsync
        assert a.bsync["bans"] >= 1 and a.bsync["probe_passes"] >= 1
        done = [
            m for m in (
                re.search(r"bsync node\d+ complete h=\d+ dispatches=(\d+)", l)
                for l in a.trace
            ) if m
        ]
        assert done, a.trace[-20:]
        assert 1 <= int(done[-1].group(1)) < heights

    @pytest.mark.slow
    def test_blocksync_scenarios_deterministic(self, tmp_path):
        """Same seed, twice: byte-identical traces and pool counters.
        (Slow lane: doubles a whole scenario run — the PR-1/PR-3
        precedent.)"""
        for name in ("blocksync-storm", "wan-catchup"):
            a = run_scenario(name, 17, root=tmp_path / (name + "-a"))
            b = run_scenario(name, 17, root=tmp_path / (name + "-b"))
            assert a.trace == b.trace, name
            assert a.heights == b.heights, name
            assert a.bsync == b.bsync, (name, a.bsync, b.bsync)
