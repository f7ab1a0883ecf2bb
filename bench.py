"""Headline benchmark: Ed25519 batch-verify throughput on one chip.

Emits incremental one-line JSON results (smallest batch first) and ALWAYS
finishes with a final headline line:
  {"metric": ..., "value": N, "unit": "verifies/s", "vs_baseline": N/500000}
The driver keeps the tail of stdout, so every line printed here is a
complete, parseable record — whatever line happens to be last is an honest
summary of the best completed measurement.

One process for each chip: a parent that has touched JAX holds the chip,
so the orchestrator never imports jax and runs its children in turn —

  orchestrator ──┬── probe subprocess (bounded; 2 attempts)
                 └── tpu worker (streams a JSON line per stage; per-line
                      progress watchdog; killed on stall, partial results
                      kept)

With no TPU the headline prints no throughput and exits non-zero: a number
from a CPU run is never written under the device metric's name.

The tpu worker AOT-caches the compiled Pallas executable on disk
(ops/aot_cache.py) in addition to JAX's persistent compilation cache (one
cache root, libs/cachedir), so a warm second run skips the Mosaic compile.

Baseline (BASELINE.json): >=500k verifies/sec/chip on the commit-verify
hot path (SURVEY.md §3.4; reference seam crypto/ed25519/ed25519.go:189-222
+ types/validation.go:220-324).  Also reports the 10k-validator commit
latency target (<5 ms device portion).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

BASELINE_VERIFIES_PER_SEC = 500_000.0

# Stage batch sizes, smallest first: a stall mid-run still leaves the best
# completed number on stdout.  10240 is the 10k-validator commit shape.
TPU_BATCHES = (8192, 10240, 32768, 131072)

def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# --------------------------------------------------------------------------
# worker (runs in a subprocess; may hang — the orchestrator kills on stall)
# --------------------------------------------------------------------------


def _make_batch(n: int):
    """n (pub, msg, sig) triples: up to 1024 distinct python-oracle
    signatures, tiled to n.  The device work is data-independent per lane
    (branch-free ladder), so tiling does not flatter the throughput number;
    it keeps host-side signing (~4 ms/sig pure python) out of setup time."""
    from cometbft_tpu.crypto import ed25519_ref as ref

    distinct = min(n, 1024)
    pubs, msgs, sigs = [], [], []
    for i in range(distinct):
        seed = i.to_bytes(4, "little") * 8
        pub = ref.pubkey_from_seed(seed)
        msg = b"bench-%d" % i
        pubs.append(pub)
        msgs.append(msg)
        sigs.append(ref.sign(seed, msg))
    reps = -(-n // distinct)
    return (pubs * reps)[:n], (msgs * reps)[:n], (sigs * reps)[:n]


def _time_sign_bytes(n: int) -> float:
    """Seconds to build all n CanonicalVote sign-bytes of one synthetic
    commit (the native commit_sign_bytes path consensus verification
    uses; types/vote.go:151 + canonical.go:57 analog)."""
    import hashlib

    from cometbft_tpu.types.basic import (
        BLOCK_ID_FLAG_COMMIT, BlockID, PartSetHeader, Timestamp,
    )
    from cometbft_tpu.types.block import Commit
    from cometbft_tpu.types.vote import CommitSig

    bid = BlockID(
        hash=hashlib.sha256(b"bench-blk").digest(),
        part_set_header=PartSetHeader(2, hashlib.sha256(b"bench-psh").digest()),
    )
    sigs = [
        CommitSig(
            block_id_flag=BLOCK_ID_FLAG_COMMIT,
            validator_address=i.to_bytes(20, "little"),
            timestamp=Timestamp(1_700_000_000, i),
            signature=bytes(64),
        )
        for i in range(n)
    ]
    commit = Commit(height=1000, round_=0, block_id=bid, signatures=sigs)
    t0 = time.perf_counter()
    out = commit.all_vote_sign_bytes("bench-chain")
    dt = time.perf_counter() - t0
    assert len(out) == n
    return dt


def _make_catchup_window(n_heights: int, sigs_per_commit: int):
    """K consecutive synthetic commits: one (pubs, msgs, sigs) segment per
    height, distinct messages per height so nothing is accidentally cached
    or deduplicated across segments."""
    from cometbft_tpu.crypto import ed25519_ref as ref

    seeds = [i.to_bytes(4, "little") * 8 for i in range(sigs_per_commit)]
    pubs = [ref.pubkey_from_seed(s) for s in seeds]
    work = []
    for h in range(n_heights):
        msgs = [
            b"catchup-h%d-v%d" % (h, i) for i in range(sigs_per_commit)
        ]
        sigs = [ref.sign(s, m) for s, m in zip(seeds, msgs)]
        work.append((list(pubs), msgs, sigs))
    return work


def run_catchup(emit, n_heights=4, sigs_per_commit=21, reps=3) -> dict:
    """Multi-height catchup: K per-commit dispatches vs ONE fused
    verify_segments dispatch over the same K commits (the blocksync window
    prefetch's exact shape), plus the signature-cache hit rate of a
    loopback consensus round (gossip-verify votes, then re-verify the
    commit built from them).  Shapes stay tiny so the CPU XLA build of the
    kernel keeps this honest (and fast enough) on chipless hosts."""
    import numpy as np

    from cometbft_tpu.ops import dispatch_stats
    from cometbft_tpu.ops import verify as ov

    work = _make_catchup_window(n_heights, sigs_per_commit)
    total = n_heights * sigs_per_commit

    # warm: compile/load the bucket shapes both paths use
    ov.verify_batch(*work[0])
    ov.verify_segments(work)

    d0 = dispatch_stats.dispatch_count()
    seq_times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        outs = [
            ov.verify_batch(*w) for w in work
        ]
        seq_times.append(time.perf_counter() - t0)
        assert all(np.asarray(o).all() for o in outs)
    seq_disp = (dispatch_stats.dispatch_count() - d0) // reps
    seq_s = min(seq_times)

    d0 = dispatch_stats.dispatch_count()
    fused_times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        outs = ov.verify_segments(work)
        fused_times.append(time.perf_counter() - t0)
        assert all(np.asarray(o).all() for o in outs)
    fused_disp = (dispatch_stats.dispatch_count() - d0) // reps
    fused_s = min(fused_times)

    rec = {
        "metric": "catchup_fused_vs_percommit",
        "stage": "catchup",
        "heights": n_heights,
        "sigs_per_commit": sigs_per_commit,
        "percommit_sigs_per_s": round(total / seq_s, 1),
        "fused_sigs_per_s": round(total / fused_s, 1),
        "fused_speedup": round(seq_s / fused_s, 2),
        "percommit_dispatches": seq_disp,
        "fused_dispatches": fused_disp,
        "sigcache_hit_rate": _loopback_cache_hit_rate(),
    }
    emit(rec)
    return rec


def run_degraded(emit, n=128, reps=2) -> dict:
    """Degraded-mode throughput (docs/backend-supervisor.md): the SAME
    supervised ``verify_batch`` measured healthy (device tier) and with the
    device faulted (circuit breaker open -> host ed25519_ref tier), then a
    re-promotion probe after the fault clears.  Verdicts are asserted
    bitwise-identical across tiers — the chain is only interesting because
    degradation preserves them.  On chipless hosts the 'healthy' tier is
    the XLA-CPU kernel build, so the ratio, not the absolute number, is
    the story."""
    import numpy as np

    from cometbft_tpu.crypto import backend_health
    from cometbft_tpu.ops import supervisor
    from cometbft_tpu.ops import verify as ov

    pubs, msgs, sigs = _make_batch(n)
    backend_health.reset()
    supervisor.clear_fault_injector()
    # fake breaker clock: the degraded timing loop must not cross the real
    # open->half-open backoff mid-sample (a granted probe would re-dispatch
    # the faulted device inside a timed rep), and the recovery probe then
    # needs no wall-clock sleep — advance the clock past the backoff instead
    fake_now = [0.0]
    backend_health.registry().set_clock(lambda: fake_now[0])

    try:
        want = ov.verify_batch(pubs, msgs, sigs)
        t_healthy = []
        for _ in range(reps):
            t0 = time.perf_counter()
            got = ov.verify_batch(pubs, msgs, sigs)
            t_healthy.append(time.perf_counter() - t0)
            assert np.array_equal(got, want)

        # fault the device until the breaker opens, then measure the host tier
        supervisor.set_fault_injector(supervisor.FaultyBackend("raise"))
        first = supervisor.device_chain()[0]
        try:
            threshold = backend_health.registry().breaker(first).threshold
            for _ in range(threshold):
                got = ov.verify_batch(pubs, msgs, sigs)
                assert np.array_equal(got, want)  # degradation preserves verdicts
            t_degraded = []
            for _ in range(reps):
                t0 = time.perf_counter()
                got = ov.verify_batch(pubs, msgs, sigs)
                t_degraded.append(time.perf_counter() - t0)
                assert np.array_equal(got, want)
            snap = backend_health.snapshot()
        finally:
            supervisor.clear_fault_injector()

        # recovery: advance the clock past the backoff; one probe re-promotes
        repromoted = False
        try:
            fake_now[0] += backend_health.registry().breaker(first).backoff_max_s
            got = ov.verify_batch(pubs, msgs, sigs)
            assert np.array_equal(got, want)
            repromoted = backend_health.snapshot()["repromotions"] >= 1
        except AssertionError:
            raise  # re-promotion changed verdicts: never mask that
        except Exception:  # noqa: BLE001 — a missed probe is advisory
            pass
    finally:
        backend_health.registry().set_clock(time.monotonic)
        backend_health.reset()

    rec = {
        "metric": "degraded_mode_throughput",
        "stage": "degraded",
        "batch": n,
        "healthy_sigs_per_s": round(n / min(t_healthy), 1),
        "degraded_sigs_per_s": round(n / min(t_degraded), 1),
        "degradation_ratio": round(min(t_degraded) / min(t_healthy), 3),
        "demotions": snap["demotions"],
        "breaker_opens": sum(
            b["opens"] for b in snap["breakers"].values()
        ),
        "fallback_signatures": snap["fallback_signatures"],
        "repromoted": repromoted,
    }
    emit(rec)
    return rec


def run_sched(emit, submitters=8, per_submitter=64, flush_us=None) -> dict:
    """Continuous-batching scheduler stage (docs/verify-scheduler.md): N
    concurrent submitter threads, each verifying its own ``per_submitter``
    signatures, measured two ways —

      * per-caller sync: every thread calls ``ops.verify.verify_batch`` on
        its own batch (today's shape: one dispatch per caller);
      * scheduled: every thread submits its items to the shared
        ``verifysched`` service and waits its futures; the dispatcher
        coalesces across threads.

    Reports sigs/s, dispatches-per-1k-sigs and p50/p99 submit->verdict
    latency for both.  Verdicts are asserted identical.  Emitted as the
    BENCH_SCHED JSON line (stage="sched")."""
    import threading

    from cometbft_tpu import verifysched
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.crypto import sigcache
    from cometbft_tpu.ops import dispatch_stats
    from cometbft_tpu.ops import verify as ov

    def pctl(xs, q):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(q * len(xs)))]

    from cometbft_tpu.crypto import ed25519_ref as ref

    batches = []
    for t in range(submitters):
        # distinct messages per (submitter, index): nothing cached or
        # deduplicated across threads, so coalescing wins are real
        # batching wins.  Each message is signed exactly once.
        pubs, msgs, sigs = [], [], []
        for i in range(per_submitter):
            seed = (i % 1024).to_bytes(4, "little") * 8
            msg = b"sched-%d-bench-%d" % (t, i)
            pubs.append(ref.pubkey_from_seed(seed))
            msgs.append(msg)
            sigs.append(ref.sign(seed, msg))
        batches.append((pubs, msgs, sigs))
    total = submitters * per_submitter

    saved_backend = cbatch._DEFAULT_BACKEND
    cbatch.set_default_backend("tpu")
    sigcache.reset_cache()
    saved_flush = os.environ.get("COMETBFT_TPU_SCHED_FLUSH_US")
    if flush_us is not None:
        os.environ["COMETBFT_TPU_SCHED_FLUSH_US"] = str(flush_us)
    verifysched.reset_scheduler()
    verifysched.stats.reset()
    try:
        # warm BOTH kernel shapes outside the timed region: the per-caller
        # bucket (sync phase) and the larger coalesced bucket the
        # scheduler's flush dispatches — a cold compile inside the timed
        # flush would otherwise trip the dispatch watchdog and measure the
        # degraded host tier instead of the scheduler
        from cometbft_tpu.crypto import backend_health

        # watchdog OFF for the warmup: on a throttled CPU host a cold
        # XLA compile can exceed the 120 s deadline, and an abandoned
        # compile would poison both phases.  Warm EVERY bucket shape from
        # the smallest up through the full-coalesce size — flush timing is
        # race-dependent, so a flush may dispatch any intermediate bucket,
        # and a cold compile inside the timed region would corrupt the
        # numbers this stage exists to report.
        saved_wd = os.environ.get("COMETBFT_TPU_DISPATCH_TIMEOUT_MS")
        os.environ["COMETBFT_TPU_DISPATCH_TIMEOUT_MS"] = "0"
        try:
            allp = [p for b in batches for p in b[0]]
            allm = [m for b in batches for m in b[1]]
            alls = [s for b in batches for s in b[2]]
            min_b = ov._min_bucket()
            b = ov.bucket_size(1, min_b)
            while True:
                k = min(b, total)
                ov.verify_batch(allp[:k], allm[:k], alls[:k])
                if b >= total:
                    break
                b = ov.bucket_size(b + 1, min_b)
        finally:
            if saved_wd is None:
                os.environ.pop("COMETBFT_TPU_DISPATCH_TIMEOUT_MS", None)
            else:
                os.environ["COMETBFT_TPU_DISPATCH_TIMEOUT_MS"] = saved_wd
        backend_health.reset()  # warmup traffic must not skew the phases

        def run_phase(thread_fn):
            lats, errs = [[] for _ in range(submitters)], []
            barrier = threading.Barrier(submitters + 1)
            threads = [
                threading.Thread(
                    target=thread_fn, args=(t, barrier, lats[t], errs)
                )
                for t in range(submitters)
            ]
            for th in threads:
                th.start()
            d0 = dispatch_stats.dispatch_count()
            barrier.wait()
            t0 = time.perf_counter()
            for th in threads:
                th.join()
            wall = time.perf_counter() - t0
            if errs:
                raise errs[0]
            return wall, dispatch_stats.dispatch_count() - d0, [
                x for l in lats for x in l
            ]

        def sync_thread(t, barrier, lat, errs):
            try:
                barrier.wait()
                t0 = time.perf_counter()
                bits = ov.verify_batch(*batches[t])
                dt = time.perf_counter() - t0
                assert bits.all()
                # every signature in the caller's batch shares its
                # dispatch's latency — that IS the per-caller experience
                lat.extend([dt] * per_submitter)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        def sched_thread(t, barrier, lat, errs):
            try:
                sched = verifysched.get_scheduler()
                pubs, msgs, sigs = batches[t]
                barrier.wait()
                futs = []
                for p, m, s in zip(pubs, msgs, sigs):
                    futs.append(
                        (
                            time.perf_counter(),
                            sched.submit(p, m, s, verifysched.PRIO_CONSENSUS),
                        )
                    )
                # latency measured IN this thread after result() returns
                # (a done-callback fires on the dispatcher thread and can
                # race run_phase's read of `lat` after join); items behind
                # the first share its flush, so the skew is microseconds
                for t0, f in futs:
                    assert f.result(timeout=600) is True
                    lat.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        sync_wall, sync_disp, sync_lat = run_phase(sync_thread)
        sigcache.reset_cache()  # the sync phase must not feed the sched phase
        sched_wall, sched_disp, sched_lat = run_phase(sched_thread)
        snap = verifysched.stats.snapshot()
    finally:
        verifysched.reset_scheduler()
        cbatch.set_default_backend(saved_backend)
        sigcache.reset_cache()
        if flush_us is not None:
            if saved_flush is None:
                os.environ.pop("COMETBFT_TPU_SCHED_FLUSH_US", None)
            else:
                os.environ["COMETBFT_TPU_SCHED_FLUSH_US"] = saved_flush

    rec = {
        "metric": "sched_coalescing_throughput",
        "stage": "sched",
        "submitters": submitters,
        "sigs_per_submitter": per_submitter,
        "sync_sigs_per_s": round(total / sync_wall, 1),
        "sched_sigs_per_s": round(total / sched_wall, 1),
        "sched_speedup": round(sync_wall / sched_wall, 2),
        "sync_dispatches_per_1k": round(sync_disp * 1000 / total, 2),
        "sched_dispatches_per_1k": round(sched_disp * 1000 / total, 2),
        "sync_p50_ms": round(pctl(sync_lat, 0.50) * 1e3, 2),
        "sync_p99_ms": round(pctl(sync_lat, 0.99) * 1e3, 2),
        "sched_p50_ms": round(pctl(sched_lat, 0.50) * 1e3, 2),
        "sched_p99_ms": round(pctl(sched_lat, 0.99) * 1e3, 2),
        "sched_flushes": snap["flushes"],
        "sched_occupancy": round(snap["flush_occupancy"], 4),
        "shed_total": snap["shed_total"],
    }
    emit(rec)
    return rec


def run_txflood(emit, n_txs=384, batch=128, n_pertx=24) -> dict:
    """Batched tx-admission stage (docs/tx-ingest.md): a flood of signed-
    envelope txs into an envelope-aware mempool, measured two ways —

      * per-tx: ``mempool.check_tx`` per gossiped tx (today's shape: one
        app round trip and one coalesced-of-one verify dispatch each);
      * batched: the ingest coalescer drains the flood through
        ``check_tx_batch`` — one bulk-class signature pass and one
        ``check_txs`` app round trip per ``batch`` txs.

    Reports txs/s admitted, app round trips and verify dispatches per 1k
    txs, and consensus-class p99 submit->verdict latency idle vs during
    the flood (the flood must never shed or starve consensus).  Emitted
    as the BENCH_TXFLOOD JSON line (stage="txflood")."""
    import hashlib
    import threading

    from cometbft_tpu import verifysched
    from cometbft_tpu.abci import types as at
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config.config import MempoolConfig
    from cometbft_tpu.crypto import backend_health
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.crypto import ed25519_ref as ref
    from cometbft_tpu.crypto import keys as ck
    from cometbft_tpu.crypto import sigcache
    from cometbft_tpu.mempool.clist_mempool import CListMempool
    from cometbft_tpu.ops import dispatch_stats
    from cometbft_tpu.ops import verify as ov
    from cometbft_tpu.proxy.multi_app_conn import (
        AppConns,
        local_client_creator,
    )
    from cometbft_tpu.txingest import (
        IngestCoalescer,
        SigVerifyingApp,
        sign_tx,
    )
    from cometbft_tpu.txingest import stats as istats

    class _CountingConn:
        """Mempool-connection wrapper counting app round trips."""

        def __init__(self, inner):
            self.inner = inner
            self.round_trips = 0

        def check_tx(self, req):
            self.round_trips += 1
            return self.inner.check_tx(req)

        def check_txs(self, reqs):
            self.round_trips += 1
            return self.inner.check_txs(reqs)

    def _stack():
        conns = AppConns(
            local_client_creator(SigVerifyingApp(KVStoreApplication()))
        )
        conns.start()
        conn = _CountingConn(conns.mempool)
        return conn, CListMempool(
            MempoolConfig(recheck=False, size=100_000),
            conn,
            envelope_aware=True,
        )

    privs = [
        ck.Ed25519PrivKey.from_seed(
            hashlib.sha256(b"txflood%d" % i).digest()
        )
        for i in range(4)
    ]
    # distinct payloads everywhere: nothing deduplicates, so the batching
    # win is a round-trip/dispatch win, not a cache artifact
    def mk_txs(tag: str, n: int) -> "list[bytes]":
        return [
            sign_tx(privs[i % len(privs)], b"%s%d=%d" % (tag.encode(), i, i),
                    nonce=i)
            for i in range(n)
        ]

    pertx_txs = mk_txs("p", n_pertx)
    flood_txs = mk_txs("b", n_txs)
    # consensus-class probe items (distinct from everything above)
    probe_msgs = [b"consensus-probe-%d" % i for i in range(256)]
    probe_sigs = [privs[0].sign(m) for m in probe_msgs]
    probe_pub = privs[0].pub_key()

    saved_backend = cbatch._DEFAULT_BACKEND
    saved_ingest = os.environ.get("COMETBFT_TPU_TXINGEST")
    cbatch.set_default_backend("tpu")
    os.environ["COMETBFT_TPU_TXINGEST"] = "1"
    sigcache.reset_cache()
    verifysched.reset_scheduler()
    verifysched.stats.reset()
    istats.reset()
    try:
        # warm every bucket shape either phase can dispatch — per-tx fills
        # the smallest bucket, the coalesced flush any intermediate one (a
        # few consensus probe items may ride along) — with the watchdog
        # off so a cold compile can't open the breaker (run_sched pattern)
        saved_wd = os.environ.get("COMETBFT_TPU_DISPATCH_TIMEOUT_MS")
        os.environ["COMETBFT_TPU_DISPATCH_TIMEOUT_MS"] = "0"
        try:
            wp = [ref.pubkey_from_seed(b"\x31" * 32)] * (batch + 32)
            wm = [b"txflood-warm-%d" % i for i in range(batch + 32)]
            ws = [ref.sign(b"\x31" * 32, m) for m in wm]
            b = ov.bucket_size(1, ov._min_bucket())
            while True:
                k = min(b, len(wp))
                ov.verify_batch(wp[:k], wm[:k], ws[:k])
                if b >= len(wp):
                    break
                b = ov.bucket_size(b + 1, ov._min_bucket())
        finally:
            if saved_wd is None:
                os.environ.pop("COMETBFT_TPU_DISPATCH_TIMEOUT_MS", None)
            else:
                os.environ["COMETBFT_TPU_DISPATCH_TIMEOUT_MS"] = saved_wd
        backend_health.reset()
        sigcache.reset_cache()  # warmup verdicts must not feed the phases

        def pctl(xs, q):
            xs = sorted(xs)
            return xs[min(len(xs) - 1, int(q * len(xs)))]

        def consensus_probe(k0: int, n: int) -> "list[float]":
            lats = []
            for i in range(k0, k0 + n):
                t0 = time.perf_counter()
                ok = verifysched.verify_cached(
                    probe_pub, probe_msgs[i], probe_sigs[i],
                    priority=verifysched.PRIO_CONSENSUS,
                )
                lats.append(time.perf_counter() - t0)
                assert ok is True
            return lats

        # idle consensus latency: the comparison floor for "unharmed"
        idle_lat = consensus_probe(0, 16)

        # -- per-tx phase -------------------------------------------------
        conn_a, mp_a = _stack()
        d0 = dispatch_stats.dispatch_count()
        t0 = time.perf_counter()
        for tx in pertx_txs:
            res = mp_a.check_tx(tx)
            assert res.ok, res.log
        pertx_wall = time.perf_counter() - t0
        pertx_disp = dispatch_stats.dispatch_count() - d0
        pertx_rt = conn_a.round_trips
        assert mp_a.size() == n_pertx

        sigcache.reset_cache()  # phase A verdicts must not feed phase B

        # -- batched phase, consensus probes riding alongside --------------
        conn_b, mp_b = _stack()
        ing = IngestCoalescer(
            mp_b, batch_max=batch, queue_cap=n_txs, start_thread=False
        )
        flood_lat: "list[float]" = []
        stop = threading.Event()

        def prober():
            k = 16
            while not stop.is_set() and k < len(probe_msgs):
                flood_lat.extend(consensus_probe(k, 1))
                k += 1

        sshed0 = verifysched.stats.snapshot()["shed"]["consensus"]
        th = threading.Thread(target=prober)
        th.start()
        d0 = dispatch_stats.dispatch_count()
        t0 = time.perf_counter()
        try:
            for tx in flood_txs:
                queued = ing.submit(tx)
                assert queued is None  # queue sized to the flood: no shed
            ing.flush_now()
        finally:
            stop.set()
            th.join()
        flood_wall = time.perf_counter() - t0
        flood_disp = dispatch_stats.dispatch_count() - d0
        flood_rt = conn_b.round_trips
        assert mp_b.size() == n_txs
        if not flood_lat:  # flood outran the first probe (tiny configs)
            flood_lat = consensus_probe(16, 1)
        shed_consensus = (
            verifysched.stats.snapshot()["shed"]["consensus"] - sshed0
        )
        assert shed_consensus == 0, shed_consensus
        isnap = istats.snapshot()
    finally:
        verifysched.reset_scheduler()
        cbatch.set_default_backend(saved_backend)
        sigcache.reset_cache()
        istats.reset()
        if saved_ingest is None:
            os.environ.pop("COMETBFT_TPU_TXINGEST", None)
        else:
            os.environ["COMETBFT_TPU_TXINGEST"] = saved_ingest

    rec = {
        "metric": "txflood_admission_throughput",
        "stage": "txflood",
        "txs": n_txs,
        "batch": batch,
        "pertx_txs": n_pertx,
        "pertx_txs_per_s": round(n_pertx / pertx_wall, 1),
        "batched_txs_per_s": round(n_txs / flood_wall, 1),
        "pertx_round_trips_per_1k": round(pertx_rt * 1000 / n_pertx, 1),
        "batched_round_trips_per_1k": round(flood_rt * 1000 / n_txs, 1),
        "pertx_dispatches_per_1k": round(pertx_disp * 1000 / n_pertx, 1),
        "batched_dispatches_per_1k": round(flood_disp * 1000 / n_txs, 1),
        "round_trip_reduction": round(
            (pertx_rt / n_pertx) / max(flood_rt / n_txs, 1e-9), 1
        ),
        "dispatch_reduction": round(
            (pertx_disp / max(n_pertx, 1))
            / max(flood_disp / n_txs, 1e-9),
            1,
        ),
        "consensus_p50_idle_ms": round(pctl(idle_lat, 0.5) * 1e3, 2),
        "consensus_p99_idle_ms": round(pctl(idle_lat, 0.99) * 1e3, 2),
        "consensus_p50_flood_ms": round(pctl(flood_lat, 0.5) * 1e3, 2),
        "consensus_p99_flood_ms": round(pctl(flood_lat, 0.99) * 1e3, 2),
        "consensus_shed": shed_consensus,
        "flood_probe_samples": len(flood_lat),
        "sig_prechecked": isnap["sig_prechecked"],
        "ingest_occupancy": round(isnap["batch_occupancy"], 4),
    }
    emit(rec)
    return rec


def _warmboot_boot(cache_dir: str, jax_cache: str, buckets: str,
                   timeout_s: float) -> dict:
    """One cold-process boot against ``cache_dir``: spawn a fresh
    interpreter, warm the matrix, verify a commit, parse its JSON line."""
    env = dict(os.environ)
    # the one exception to the single cache root (libs/cachedir): this
    # stage measures a cold boot against a warm one, so both caches are
    # deliberately pointed at temporary directories made for the run
    env.update(
        COMETBFT_TPU_EXEC_CACHE=cache_dir,
        JAX_COMPILATION_CACHE_DIR=jax_cache,
        COMETBFT_TPU_WARMBOOT="1",
        COMETBFT_TPU_WARMBOOT_BUCKETS=buckets,
        # ed25519 matrix only: the secp/BLS/transport families would add
        # ~30s compiles per shape on this host and are not what this
        # stage times (their warm pass is covered by test_warmboot)
        COMETBFT_TPU_WARMBOOT_SECP_BUCKETS="",
        COMETBFT_TPU_WARMBOOT_BLS_BUCKETS="",
        COMETBFT_TPU_WARMBOOT_TRANSPORT_BUCKETS="",
        COMETBFT_TPU_SUPERVISOR="0",  # measure the pipeline, not the
        # watchdog: a >120s cold compile must not demote mid-measurement
        BENCH_T0=repr(time.time()),
    )
    # XLA-CPU's thunk runtime (jax 0.4.x default) serializes executables
    # it cannot reload in another process, so boot 2 would read every
    # entry as stale and recompile.  The legacy CPU runtime round-trips
    # (measured: 5s load vs 261s compile for the 32-lane bucket) at the
    # cost of a slower boot-1 compile — which only sharpens the cold/warm
    # contrast this stage measures.  Inert on TPU, where PJRT executable
    # serialization is native (docs/warm-boot.md).
    xla_flags = env.get("XLA_FLAGS", "")
    if "xla_cpu_use_thunk_runtime" not in xla_flags:
        env["XLA_FLAGS"] = (
            xla_flags + " --xla_cpu_use_thunk_runtime=false"
        ).strip()
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--warmboot-child"],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout_s,
        cwd=REPO,
    )
    for line in reversed(out.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(
        f"warmboot child emitted no JSON (rc={out.returncode}): "
        f"{out.stderr[-400:]}"
    )


def _warmboot_child() -> None:
    """Cold-process half of the warm-boot bench: verify one commit (the
    time-to-first-verified-commit clock starts at the parent's spawn
    timestamp), then warm the rest of the matrix, then report."""
    t_spawn = float(os.environ["BENCH_T0"])
    from cometbft_tpu.libs import cachedir

    cachedir.enable()  # the parent's temporary directories, via the env

    from cometbft_tpu.ops import verify as ov
    from cometbft_tpu.ops import warm_stats, warmboot

    n = 21  # a small-committee commit: the shape a booting node sees first
    pubs, msgs, sigs = _make_batch(n)
    st0 = warm_stats.snapshot()
    bits = ov.verify_batch(pubs, msgs, sigs)
    ttfvc_s = time.time() - t_spawn
    st1 = warm_stats.snapshot()
    first_src = (
        "hit" if st1["exec_hits"] > st0["exec_hits"]
        else "compiled" if st1["compiles"] > st0["compiles"]
        else "jit"
    )
    report = warmboot.run()
    statuses = dict(report["statuses"])
    # the commit bucket resolved during the verify above; report its true
    # source instead of the warm pass's in-process "memo" — keyed on the
    # impl that actually dispatched (pallas on TPU hosts) and its padding
    # floor, not hard-coded xla
    impl = ov.select_impl()
    floor = (
        ov._PALLAS_MIN_BUCKET if impl == "pallas" else ov._BUCKETS[0]
    )
    first_bucket = ov.bucket_size(n, floor)
    statuses[f"{impl}-{first_bucket}"] = first_src
    _emit(
        {
            "stage": "warmboot-child",
            "ttfvc_s": round(ttfvc_s, 2),
            "first_commit_exec": first_src,
            "statuses": statuses,
            "warm_pass_s": report["seconds"],
            "failures": report["failures"],
            "pruned": report["pruned"],
            "bits": [int(b) for b in bits],
            "stats": warm_stats.snapshot(),
        }
    )


def run_warmboot(emit, buckets: "str | None" = None) -> dict:
    """Warm-boot pipeline bench (docs/warm-boot.md): two cold processes
    against one empty exec+compile cache.  Boot 1 pays the full trace+XLA
    compile matrix; boot 2 must deserialize EVERY padding-bucket shape
    (``exec_cache: hit``, zero compiles) and reach its first verified
    commit >=5x faster.  Verdicts are asserted bitwise-equal across boots
    (the cached executable is the same computation)."""
    import tempfile

    import numpy as np

    buckets = buckets or os.environ.get("BENCH_WARMBOOT_BUCKETS", "32,64")
    work = tempfile.mkdtemp(prefix="bench_warmboot_")
    cache_dir = os.path.join(work, "exec")
    jax_cache = os.path.join(work, "jaxcache")  # cold: no persistent-cache
    # assist, so boot 1 is an honest fresh-machine boot
    timeout_s = float(os.environ.get("BENCH_WARMBOOT_TIMEOUT_S", "1500"))

    try:
        boot1 = _warmboot_boot(cache_dir, jax_cache, buckets, timeout_s)
        boot2 = _warmboot_boot(cache_dir, jax_cache, buckets, timeout_s)
    finally:
        import shutil

        shutil.rmtree(work, ignore_errors=True)  # two boots' exec +
        # jax-compile caches are tens-to-hundreds of MB per run

    all_hit = bool(boot2["statuses"]) and all(
        v == "hit" for v in boot2["statuses"].values()
    )
    verdicts_equal = boot1["bits"] == boot2["bits"]
    speedup = boot1["ttfvc_s"] / max(boot2["ttfvc_s"], 1e-9)
    # the ISSUE 8 acceptance gates — a regression (e.g. a per-process env
    # var leaking into the fingerprint) must FAIL the stage, not merely
    # flip a field in the JSON record
    assert verdicts_equal, "cached executable changed verdicts"
    assert all_hit, (
        f"second boot did not deserialize every shape: {boot2['statuses']}"
    )
    assert boot2["stats"]["compiles"] == 0, (
        f"second boot compiled {boot2['stats']['compiles']} kernels"
    )
    assert speedup >= 5.0, (
        f"warm boot only {speedup:.1f}x faster to first verified commit"
    )

    rec = {
        "metric": "warmboot_second_boot",
        "stage": "warmboot",
        "buckets": buckets,
        "boot1_ttfvc_s": boot1["ttfvc_s"],
        "boot2_ttfvc_s": boot2["ttfvc_s"],
        "ttfvc_speedup": round(speedup, 1),
        "boot1_statuses": boot1["statuses"],
        "boot2_statuses": boot2["statuses"],
        "second_boot_all_hit": all_hit,
        "second_boot_compiles": boot2["stats"]["compiles"],
        "verdicts_equal": verdicts_equal,
        "shapes_pruned": boot2["pruned"],
    }
    emit(rec)
    return rec


def run_obs(emit, n=128, reps=3) -> dict:
    """Observability overhead stage (docs/observability.md): pins the
    flight recorder's cost on the sched-bench workload shape — a
    supervised ``verify_batch`` of ``n`` signatures — run on the
    host-oracle device-runner seam, where per-op cost is deterministic
    and CPU-bound (a real device dispatch would bury any recorder cost
    in device wall time and prove nothing).

    Gates (asserted; emitted as BENCH_OBS stage="obs"):
      * tracer DISABLED (``COMETBFT_TPU_TRACE=0``): measured no-op span
        cost x spans-per-op <= 1% of the per-op wall time;
      * tracer ENABLED: measured record cost x spans-per-op <= 5%.

    The enabled measurement runs WITH the black-box journal installed
    (threaded mode, temp dir) — the durable sink is part of the default-on
    recorder now, so the 5% gate covers its enqueue cost too; journal
    volume/drops are reported alongside.

    The off->on wall delta is reported as advisory only — host noise on
    the throttled CI box swamps sub-5% effects, which is exactly why the
    gates multiply the MEASURED per-span cost by the MEASURED span count
    instead of differencing two noisy walls."""
    import numpy as np

    from cometbft_tpu.libs import tracing
    from cometbft_tpu.ops import supervisor
    from cometbft_tpu.ops import verify as ov

    pubs, msgs, sigs = _make_batch(n)

    def oracle(backend, ps, ms, ss, lanes):
        from cometbft_tpu.crypto import ed25519_ref as ref

        out = np.zeros(lanes, dtype=bool)
        out[: len(ps)] = [
            ref.verify_zip215(p, m, s) for p, m, s in zip(ps, ms, ss)
        ]
        return out

    knobs = (
        "COMETBFT_TPU_TRACE",
        "COMETBFT_TPU_TRACE_DIR",
        "COMETBFT_TPU_TRACE_XNODE",
        "COMETBFT_TPU_SIGCACHE",
        "COMETBFT_TPU_VERIFY_SCHED",
    )
    saved = {k: os.environ.get(k) for k in knobs}
    # every rep must do real verify work (no cache hits), with no dump IO
    # or scheduler queueing inside the timed region.  Cross-node context
    # propagation is pinned ON: the gates below re-baseline the recorder
    # with the PR-11 span taxonomy (round/step spans, ctx encode on the
    # gossip path) active, and must hold unchanged (disabled <=1%,
    # enabled <=5%).
    os.environ["COMETBFT_TPU_SIGCACHE"] = "0"
    os.environ["COMETBFT_TPU_VERIFY_SCHED"] = "0"
    os.environ["COMETBFT_TPU_TRACE_XNODE"] = "1"
    os.environ.pop("COMETBFT_TPU_TRACE_DIR", None)
    supervisor.set_device_runner(oracle)
    tracer = tracing.get_tracer()
    # the enabled baseline includes the durable journal: real production
    # shape (threaded writer, batched spans), scratch dir
    import shutil as _shutil
    import tempfile as _tempfile

    from cometbft_tpu.libs import blackbox

    bb_dir = _tempfile.mkdtemp(prefix="bench-obs-bb-")
    journal = blackbox.open_journal(bb_dir)
    journal_stats: dict = {}
    try:

        def measure() -> float:
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                bits = ov.verify_batch(pubs, msgs, sigs)
                best = min(best, time.perf_counter() - t0)
                assert bits.all()
            return best

        os.environ["COMETBFT_TPU_TRACE"] = "0"
        off1 = measure()
        os.environ["COMETBFT_TPU_TRACE"] = "1"
        tracer.reset()
        on = measure()
        spans_per_op = max(
            1, tracer.snapshot()["spans_recorded"] // reps
        )
        os.environ["COMETBFT_TPU_TRACE"] = "0"
        off = min(off1, measure())

        # per-span costs, measured directly at both switch positions
        # (the record loop pays the journal enqueue too — that's the
        # point: the 5% gate holds with the black box in the path)
        k = 20000
        t0 = time.perf_counter()
        for _ in range(k):
            with tracing.span("bench.noop"):
                pass
        noop_s = (time.perf_counter() - t0) / k
        os.environ["COMETBFT_TPU_TRACE"] = "1"
        t0 = time.perf_counter()
        for _ in range(k):
            with tracing.span("bench.record"):
                pass
        record_s = (time.perf_counter() - t0) / k
        tracer.reset()
        if journal is not None:
            journal_stats = journal.stats()
    finally:
        blackbox.close_journal(clean=False)
        _shutil.rmtree(bb_dir, ignore_errors=True)
        supervisor.clear_device_runner()
        for kname, v in saved.items():
            if v is None:
                os.environ.pop(kname, None)
            else:
                os.environ[kname] = v

    disabled_pct = 100.0 * noop_s * spans_per_op / off
    enabled_pct = 100.0 * record_s * spans_per_op / off
    rec = {
        "metric": "flight_recorder_overhead",
        "stage": "obs",
        "batch": n,
        "reps": reps,
        "per_op_ms": round(off * 1e3, 3),
        "per_op_traced_ms": round(on * 1e3, 3),
        "spans_per_op": spans_per_op,
        "noop_span_ns": round(noop_s * 1e9, 1),
        "record_span_ns": round(record_s * 1e9, 1),
        "disabled_overhead_pct": round(disabled_pct, 4),
        "enabled_overhead_pct": round(enabled_pct, 4),
        "wall_delta_pct_advisory": round(100.0 * (on - off) / off, 2),
        "gate_disabled_max_pct": 1.0,
        "gate_enabled_max_pct": 5.0,
        "journal_records": journal_stats.get("records", 0),
        "journal_bytes": journal_stats.get("bytes", 0),
        "journal_dropped": journal_stats.get("dropped", 0),
    }
    emit(rec)
    assert disabled_pct <= 1.0, (
        f"tracer-disabled overhead {disabled_pct:.3f}% exceeds the 1% gate"
    )
    assert enabled_pct <= 5.0, (
        f"tracer-enabled overhead {enabled_pct:.3f}% exceeds the 5% gate"
    )
    return rec


def run_meshfault(emit, n=256, reps=3, width=4) -> dict:
    """Elastic-mesh fault stage (docs/backend-supervisor.md "Fault
    isolation"): healthy full-width dispatch vs one-dead-chip dispatch
    on the per-shard host-oracle runner seam (``parallel/elastic``) —
    the same seam the chip-death sim scenario drives, so the numbers are
    deterministic and platform-independent.  Asserted hard:

      * verdicts bitwise-equal between the healthy mesh, the
        shrunken mesh, and the host ZIP-215 oracle;
      * exactly ONE shrink for a persistent dead chip (the failed
        dispatch alone re-runs; the open breaker excludes the corpse
        from every later dispatch — no per-dispatch retry tax);
      * dispatches-per-1k-sigs returns to the healthy rate once the
        breaker is open (trend-gated via ``dispatches_per_1k``).

    Walls (healthy vs first-fault dispatch latency) are advisory on the
    throttled host.  Emitted as stage="meshfault" and written to
    BENCH_MESHFAULT.json for the bench_trend gate."""
    import numpy as np

    from cometbft_tpu.crypto import backend_health
    from cometbft_tpu.crypto import ed25519_ref as ref
    from cometbft_tpu.ops import dispatch_stats
    from cometbft_tpu.parallel import elastic

    pubs, msgs, sigs = _make_batch(n)
    # two invalid lanes so shrink re-dispatch is exercised on a mixed
    # batch, not just the happy path
    sigs = list(sigs)
    sigs[1] = sigs[1][:-1] + bytes([sigs[1][-1] ^ 1])
    sigs[n - 2] = bytes(64)
    expected = np.array(
        [ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)],
        dtype=bool,
    )

    saved_thr = os.environ.get("COMETBFT_TPU_BREAKER_THRESHOLD")
    os.environ["COMETBFT_TPU_BREAKER_THRESHOLD"] = "1"
    try:
        def timed_run() -> "tuple[list[float], int]":
            dispatch_stats.reset()
            walls = []
            for _ in range(reps):
                t0 = time.perf_counter()
                bits = elastic.verify_elastic(pubs, msgs, sigs)
                walls.append(time.perf_counter() - t0)
                assert (bits == expected).all(), "verdicts diverged"
            return walls, dispatch_stats.snapshot()["dispatches"]

        # healthy: full width, one dispatch per verify
        backend_health.reset()
        elastic.clear()
        elastic.configure(range(width))
        elastic.set_mesh_runner(elastic.host_oracle_runner)
        healthy_walls, healthy_disp = timed_run()

        # one dead chip, persistent: first dispatch shrinks (re-dispatch),
        # every later dispatch runs at width-1 with no retry tax
        backend_health.reset()
        elastic.clear()
        elastic.configure(range(width))
        elastic.set_mesh_runner(elastic.host_oracle_runner)
        elastic.set_fault_injector(
            elastic.FaultyDevice("raise", ordinals=(1,))
        )
        dead_walls, dead_disp = timed_run()
        snap = dispatch_stats.snapshot()
        shrinks = snap["mesh_shrinks"]
        post_width = snap["mesh_width"]
    finally:
        elastic.clear()
        backend_health.reset()
        if saved_thr is None:
            os.environ.pop("COMETBFT_TPU_BREAKER_THRESHOLD", None)
        else:
            os.environ["COMETBFT_TPU_BREAKER_THRESHOLD"] = saved_thr

    total_sigs = reps * n
    rec = {
        "metric": "mesh_fault_isolation",
        "stage": "meshfault",
        "batch": n,
        "reps": reps,
        "width": width,
        "post_fault_width": post_width,
        "shrinks": shrinks,
        "dispatches_per_1k_sigs_healthy": round(
            1000.0 * healthy_disp / total_sigs, 3
        ),
        "dispatches_per_1k_sigs_dead": round(
            1000.0 * dead_disp / total_sigs, 3
        ),
        "healthy_dispatch_ms_p50": round(
            sorted(healthy_walls)[len(healthy_walls) // 2] * 1e3, 3
        ),
        "fault_dispatch_ms": round(dead_walls[0] * 1e3, 3),
        "post_fault_dispatch_ms_p50": round(
            sorted(dead_walls[1:])[(reps - 1) // 2] * 1e3, 3
        )
        if reps > 1
        else None,
    }
    emit(rec)
    # hard invariants (dispatch counts; walls stay advisory)
    assert shrinks == 1, f"expected exactly one shrink, got {shrinks}"
    assert post_width == width - 1, (width, post_width)
    assert dead_disp == healthy_disp + 1, (
        "dead-chip run must cost exactly one extra dispatch "
        f"(the single re-dispatch): {healthy_disp} -> {dead_disp}"
    )
    out = os.path.join(REPO, "BENCH_MESHFAULT.json")
    try:
        with open(out, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    return rec


def run_multichip(emit, n=10240, depth=None) -> dict:
    """Multi-lane in-flight pipeline stage (docs/verify-scheduler.md
    "In-flight pipeline"): the headline 10,240-signature commit shape
    chunked across the elastic mesh lanes with K chunk dispatches in
    flight (``ops.verify.verify_pipelined``), on the per-shard
    host-oracle runner seam so the dispatch counts are deterministic and
    platform-independent.  Asserted hard:

      * verdicts bitwise-equal to the host ZIP-215 oracle (three corrupt
        lanes attributed at their exact indices);
      * the pipeline genuinely overlaps: the in-flight high-water mark
        reaches the configured depth K, so ``inflight_occupancy`` is
        deterministically 1.0 (trend-gated via the ``*occupancy*``
        higher-is-better pattern);
      * every chunk lands on a lane (lane_dispatches covers the width).

    ``commit10k_ms`` walls stay advisory on the throttled host.  Emitted
    as stage="multichip" and written to BENCH_MULTICHIP.json for the
    bench_trend gate.  Skips cleanly (no record, no JSON) when jax
    reports < 2 devices — the gate stage forces an 8-device CPU mesh via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    import numpy as np

    try:
        import jax

        n_devs = len(jax.devices())
    except Exception:  # noqa: BLE001 — no backend at all: single chip
        n_devs = 1
    if n_devs < 2:
        print(
            "bench --multichip: skipped (1 jax device; force a virtual "
            "mesh with XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
        return {}
    width = min(n_devs, 8)

    from cometbft_tpu.crypto import backend_health
    from cometbft_tpu.crypto import ed25519_ref as ref
    from cometbft_tpu.ops import dispatch_stats
    from cometbft_tpu.ops import verify as ov
    from cometbft_tpu.parallel import elastic

    # commit-shaped batch: 64 distinct signed triples tiled to n (device
    # work is data-independent per lane), three corrupt lanes spread
    # head / middle / tail so attribution is exercised across chunks
    distinct = min(n, 64)
    pubs, msgs, sigs = [], [], []
    for i in range(distinct):
        seed = bytes([(i % 255) + 1]) * 32
        pub = ref.pubkey_from_seed(seed)
        msg = b"bench-multichip-%d" % i
        pubs.append(pub)
        msgs.append(msg)
        sigs.append(ref.sign(seed, msg))
    reps = -(-n // distinct)
    pubs = (pubs * reps)[:n]
    msgs = (msgs * reps)[:n]
    sigs = list((sigs * reps)[:n])
    bad = (0, n // 2, n - 1)
    for i in bad:
        sigs[i] = sigs[i][:-1] + bytes([sigs[i][-1] ^ 1])
    expected = np.ones(n, dtype=bool)
    expected[list(bad)] = False

    k = int(depth) if depth else max(width, 2)
    backend_health.reset()
    elastic.clear()
    elastic.configure(range(width))
    elastic.set_mesh_runner(elastic.host_oracle_runner)
    try:
        dispatch_stats.reset()
        t0 = time.perf_counter()
        bits = ov.verify_pipelined(pubs, msgs, sigs, inflight=k)
        wall = time.perf_counter() - t0
        assert (bits == expected).all(), "verdicts diverged from oracle"
        snap = dispatch_stats.snapshot()
    finally:
        elastic.clear()
        backend_health.reset()

    hwm = snap["inflight_hwm"]
    lane_disp = snap.get("lane_dispatches", {})
    chunks = sum(lane_disp.values())
    rec = {
        "metric": "multichip_pipeline",
        "stage": "multichip",
        "batch": n,
        "lanes": width,
        "inflight_depth": k,
        "inflight_hwm": hwm,
        "inflight_occupancy": round(hwm / float(k), 3),
        "chunks": chunks,
        "lanes_used": len(lane_disp),
        "commit10k_ms": round(wall * 1e3, 3),
        "sigs_per_s": round(n / wall, 1),
    }
    emit(rec)
    # hard invariants (occupancy + lane coverage; walls stay advisory)
    assert hwm == min(k, chunks), (
        f"pipeline under-filled: hwm {hwm}, depth {k}, chunks {chunks}"
    )
    assert chunks >= width, (chunks, width)
    assert len(lane_disp) == width, (
        f"round-robin missed lanes: {sorted(lane_disp)} of {width}"
    )
    out = os.path.join(REPO, "BENCH_MULTICHIP.json")
    try:
        with open(out, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    return rec


def run_proofserve(
    emit, n_queries=10000, n_heights=32, txs_per_block=64, sample=2000
) -> dict:
    """Coalesced proof-serving stage (docs/proof-serving.md).  A fake
    in-memory chain of ``n_heights`` blocks x ``txs_per_block`` txs is
    served two ways, both on the host tree-runner seam so the stage is
    jax-free, deterministic, and platform-independent:

      * **coalesced leg** — ``n_queries`` tx-proof queries through a
        ``ProofServer`` in paused bursts: each burst flushes as ONE
        dispatch group per height, and the LRU cache absorbs repeats,
        so tree builds stay near ``n_heights`` no matter how many
        queries arrive;
      * **serial leg** — a ``sample``-sized subset served the
        pre-plane way: one full ``merkle.proofs_from_byte_slices``
        tree build per query.

    Asserted hard: roots and proofs bitwise-equal between the two
    legs, and coalesced dispatches-per-1k-proofs strictly below
    serial (which is 1000 by construction).  Walls are advisory.
    Emitted as stage="proofserve" and written to BENCH_PROOFSERVE.json
    for the bench_trend gate."""
    from cometbft_tpu.crypto import merkle
    from cometbft_tpu.ops import sha256_tree
    from cometbft_tpu.proofserve import service as psvc
    from cometbft_tpu.proofserve import stats as pstats

    # deterministic fake chain: height h -> txs_per_block distinct txs
    chain = {
        h: [
            b"ps-tx-%d-%d-" % (h, i) + bytes([h & 0xFF, i & 0xFF]) * 8
            for i in range(txs_per_block)
        ]
        for h in range(1, n_heights + 1)
    }

    def tx_loader(height: int):
        return chain.get(height)

    heights = [1 + (i % n_heights) for i in range(n_queries)]
    burst = max(n_heights * 4, 512)

    sha256_tree.set_tree_runner(sha256_tree.host_tree_runner)
    server = psvc.ProofServer(
        tx_loader, lambda h: None, lambda h: None, queue_cap=burst
    )
    pstats.reset()
    responses: "dict[int, tuple]" = {}
    try:
        t0 = time.perf_counter()
        for start in range(0, n_queries, burst):
            hs = heights[start : start + burst]
            server.pause()
            futs = [server.submit("tx", h) for h in hs]
            server.resume()
            for h, f in zip(hs, futs):
                root, proofs = f.result(timeout=60)
                responses[h] = (root, proofs)
        coalesced_wall = time.perf_counter() - t0
        snap = pstats.snapshot()
    finally:
        server.close()
        sha256_tree.clear_tree_runner()

    builds = snap["tree_builds_total"]
    assert snap["shed_total"] == 0, snap
    assert len(responses) == n_heights

    # serial leg: one full tree build per query, bitwise-compared
    step = max(1, n_queries // sample)
    serial_n = 0
    t0 = time.perf_counter()
    for i in range(0, n_queries, step):
        h = heights[i]
        root, proofs = merkle.proofs_from_byte_slices(chain[h])
        serial_n += 1
        croot, cproofs = responses[h]
        assert root == croot, f"root diverged at height {h}"
        for p, cp in zip(proofs, cproofs):
            assert (
                p.total == cp.total
                and p.index == cp.index
                and p.leaf_hash == cp.leaf_hash
                and p.aunts == cp.aunts
            ), f"proof diverged at height {h} index {p.index}"
    serial_wall = time.perf_counter() - t0

    coalesced_per_1k = 1000.0 * builds / n_queries
    serial_per_1k = 1000.0  # one tree build per query, by construction
    rec = {
        "metric": "proofserve_coalescing",
        "stage": "proofserve",
        "queries": n_queries,
        "heights": n_heights,
        "txs_per_block": txs_per_block,
        "tree_builds": builds,
        "cache_hits": snap["cache_hits_total"],
        "queries_per_flush": snap["queries_per_flush"],
        "dispatches_per_1k_proofs_coalesced": round(coalesced_per_1k, 3),
        "dispatches_per_1k_proofs_serial": round(serial_per_1k, 3),
        "coalesced_wall_s": round(coalesced_wall, 3),
        "coalesced_proofs_per_s_advisory": round(
            n_queries / coalesced_wall, 1
        ),
        "serial_sample": serial_n,
        "serial_wall_s": round(serial_wall, 3),
        "serial_proofs_per_s_advisory": round(serial_n / serial_wall, 1),
    }
    emit(rec)
    assert coalesced_per_1k < serial_per_1k, (
        "coalesced proof serving must beat per-query serial serving: "
        f"{coalesced_per_1k} >= {serial_per_1k} dispatches/1k proofs"
    )
    out = os.path.join(REPO, "BENCH_PROOFSERVE.json")
    try:
        with open(out, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    return rec


def run_transport(emit, n_frames=2000, frame_bytes=1024, n_dials=1000) -> dict:
    """Encrypted transport data plane stage (docs/transport-plane.md).
    Two legs, both on the host runner seams so the stage is jax-free,
    deterministic, and platform-independent:

      * **AEAD leg** — ``n_frames`` fixed-size frames sealed through
        ``transportplane.seal_frames`` in write_msg-sized bursts (ONE
        counted dispatch per burst) vs the pre-plane way (one
        ``ChaCha20Poly1305Ref.encrypt`` per frame), then the whole
        stream re-opened through the plane;
      * **handshake leg** — ``n_dials`` concurrent dials through a
        paused/resumed ``HandshakePool`` (max_batch-sized ladder
        dispatches) vs one ``sync_exchange`` per dial.

    Asserted hard: ciphertexts||tags and shared secrets bitwise-equal
    between the legs, and coalesced dispatches-per-1k strictly below
    serial (which is 1000 by construction) on both legs.  Walls (MB/s,
    handshakes/s) are advisory.  Emitted as stage="transport" and
    written to BENCH_TRANSPORT.json for the bench_trend gate."""
    import hashlib

    from cometbft_tpu.crypto import aead_ref
    from cometbft_tpu.ops import chacha_aead, x25519_ladder
    from cometbft_tpu.p2p import handshake_pool, transportplane
    from cometbft_tpu.p2p import transport_stats as tpstats

    key = hashlib.sha256(b"bench-transport-key").digest()
    payloads = []
    for i in range(n_frames):
        block = hashlib.sha256(b"bench-transport-frame-%d" % i).digest()
        payloads.append((block * ((frame_bytes + 31) // 32))[:frame_bytes])

    # -- AEAD leg ---------------------------------------------------------
    aead_dispatches = 0

    def counting_aead_runner(op, frames):
        nonlocal aead_dispatches
        aead_dispatches += 1
        return chacha_aead.host_aead_runner(op, frames)

    burst = int(os.environ.get("BENCH_TRANSPORT_BURST", "64"))
    chacha_aead.set_aead_runner(counting_aead_runner)
    tpstats.reset()
    sealed_coalesced: "list[bytes]" = []
    try:
        t0 = time.perf_counter()
        for start in range(0, n_frames, burst):
            sealed_coalesced.extend(
                transportplane.seal_frames(
                    key, start, payloads[start : start + burst]
                )
            )
        coalesced_wall = time.perf_counter() - t0
        snap = tpstats.snapshot()
    finally:
        chacha_aead.clear_aead_runner()

    cipher = aead_ref.ChaCha20Poly1305Ref(key)
    t0 = time.perf_counter()
    sealed_serial = [
        cipher.encrypt(transportplane.nonce_bytes(i), payloads[i], b"")
        for i in range(n_frames)
    ]
    serial_wall = time.perf_counter() - t0
    assert sealed_serial == sealed_coalesced, (
        "coalesced AEAD diverged from the serial reference"
    )

    # full-stream re-open through the plane (numpy tier, uncounted):
    # every frame must authenticate and decrypt back to its payload
    for start in range(0, n_frames, burst):
        pts, bad = transportplane.open_frames(
            key, start, sealed_coalesced[start : start + burst]
        )
        assert bad is None and pts == payloads[start : start + burst], (
            f"plane open diverged in burst at {start}"
        )

    # -- handshake leg ----------------------------------------------------
    ladder_dispatches = 0

    def counting_ladder_runner(pairs):
        nonlocal ladder_dispatches
        ladder_dispatches += 1
        return x25519_ladder.host_ladder_runner(pairs)

    peer_pubs = [
        aead_ref.x25519(
            hashlib.sha256(b"bench-transport-peer-%d" % j).digest(),
            x25519_ladder.BASE_U,
        )
        for j in range(8)
    ]
    pairs = [
        (
            hashlib.sha256(b"bench-transport-dial-%d" % i).digest(),
            peer_pubs[i % len(peer_pubs)],
        )
        for i in range(n_dials)
    ]

    x25519_ladder.set_ladder_runner(counting_ladder_runner)
    pool = handshake_pool.HandshakePool(
        flush_us=2000.0, queue_cap=n_dials, max_batch=256
    )
    try:
        t0 = time.perf_counter()
        pool.pause()
        futs = [pool.submit(s, p) for s, p in pairs]
        pool.resume()
        pooled = [f.result(timeout=120) for f in futs]
        pool_wall = time.perf_counter() - t0
    finally:
        pool.close()
        x25519_ladder.clear_ladder_runner()

    t0 = time.perf_counter()
    serial_secrets = [handshake_pool.sync_exchange(s, p) for s, p in pairs]
    serial_hs_wall = time.perf_counter() - t0
    assert pooled == serial_secrets, (
        "pooled X25519 diverged from the serial reference"
    )

    mb = n_frames * frame_bytes / 1e6
    frames_per_1k = 1000.0 * aead_dispatches / n_frames
    dials_per_1k = 1000.0 * ladder_dispatches / n_dials
    rec = {
        "metric": "transport_plane",
        "stage": "transport",
        "frames": n_frames,
        "frame_bytes": frame_bytes,
        "aead_dispatches": aead_dispatches,
        "frames_per_batch": round(snap["frames_per_batch"], 2),
        "dispatches_per_1k_frames_coalesced": round(frames_per_1k, 3),
        "dispatches_per_1k_frames_serial": 1000.0,
        "coalesced_mb_per_s_advisory": round(mb / coalesced_wall, 2),
        "serial_mb_per_s_advisory": round(mb / serial_wall, 2),
        "dials": n_dials,
        "ladder_dispatches": ladder_dispatches,
        "dispatches_per_1k_dials_coalesced": round(dials_per_1k, 3),
        "dispatches_per_1k_dials_serial": 1000.0,
        "pooled_handshakes_per_s_advisory": round(n_dials / pool_wall, 1),
        "serial_handshakes_per_s_advisory": round(
            n_dials / serial_hs_wall, 1
        ),
    }
    emit(rec)
    assert frames_per_1k < 1000.0, (
        "coalesced AEAD must beat per-frame serial sealing: "
        f"{frames_per_1k} >= 1000 dispatches/1k frames"
    )
    assert dials_per_1k < 1000.0, (
        "pooled handshakes must beat per-dial serial exchange: "
        f"{dials_per_1k} >= 1000 dispatches/1k dials"
    )
    out = os.path.join(REPO, "BENCH_TRANSPORT.json")
    try:
        with open(out, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    return rec


def run_diskfault(emit, n=128, seed=11) -> dict:
    """Disk-fault supervisor stage (docs/storage-robustness.md).  Two
    legs, both deterministic and platform-independent:

      * **degrade leg** — verify verdicts with and without injected
        storage faults on every DEGRADABLE surface (exec_cache ENOSPC,
        blackbox EIO, status ENOSPC) must be BITWISE EQUAL: a disk fault
        on a degradable surface may cost an optimization or a forensic
        record, never a verdict.  The seam's counted-drop discipline is
        asserted (drops > 0, zero fatals, the blackbox writer survives).

      * **fail-stop leg** — the ``disk-full`` sim scenario, run TWICE
        with the same seed: the victim node must fail-stop (height -1,
        zero consensus participation after the halt), the survivors must
        reach the target with agreement green, and the two runs' traces
        must be byte-identical — the injector consumes the same rule
        windows on the same IO sequence every time.

    Emitted as stage="diskfault" and written to BENCH_DISKFAULT.json for
    the bench_trend gate (dispatch-free: its hard numbers are counters)."""
    import errno as _errno
    import tempfile as _tempfile

    import numpy as np

    from cometbft_tpu.crypto import ed25519_ref as ref
    from cometbft_tpu.libs import blackbox as bb
    from cometbft_tpu.libs import diskguard as dg
    from cometbft_tpu.libs import storage_stats
    from cometbft_tpu.ops import verify as ov
    from cometbft_tpu.sim.scenarios import run_scenario

    pubs, msgs, sigs = _make_batch(n)
    # two invalid lanes so equality is meaningful on a mixed batch
    sigs = list(sigs)
    sigs[1] = sigs[1][:-1] + bytes([sigs[1][-1] ^ 1])
    sigs[n - 2] = bytes(64)
    expected = np.array(
        [ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)],
        dtype=bool,
    )

    # -- degrade leg ---------------------------------------------------------
    storage_stats.reset()
    bits_clean = np.asarray(ov.verify_batch(pubs, msgs, sigs), dtype=bool)
    prev_plan = dg.set_fault_plan(dg.FaultPlan())
    dg.set_sleeper(lambda _s: None)
    tmpd = _tempfile.mkdtemp(prefix="bench-diskfault-")
    try:
        plan = dg.get_fault_plan()
        plan.add(surface="exec_cache", err=_errno.ENOSPC)
        plan.add(surface="status", err=_errno.ENOSPC)
        plan.add(surface="blackbox", err=_errno.EIO)
        # verdicts under active storage faults
        bits_fault = np.asarray(
            ov.verify_batch(pubs, msgs, sigs), dtype=bool
        )
        # the degradable seams really degrade: exec-cache publish fails
        # as a counted drop surfaced to the caller...
        try:
            dg.atomic_write(
                "exec_cache", os.path.join(tmpd, "entry.jexec"), b"payload"
            )
            exec_degraded = False
        except OSError:
            exec_degraded = True
        # ...and the blackbox writer thread survives EIO with counted
        # drops (forensics must never become a second failure)
        j = bb.BlackboxJournal(
            os.path.join(tmpd, "bbox"), threaded=True, queue_max=64
        )
        for i in range(16):
            j.on_anomaly("bench_fault", {"i": i}, float(i))
        j.close(clean=True)
        bb_stats = j.stats()
        snap = storage_stats.snapshot()["totals"]
    finally:
        dg.set_fault_plan(prev_plan)
        dg.set_sleeper(None)
        import shutil as _shutil

        _shutil.rmtree(tmpd, ignore_errors=True)

    # -- fail-stop leg -------------------------------------------------------
    res_a = run_scenario("disk-full", seed)
    res_b = run_scenario("disk-full", seed)
    victim_halted = (
        res_a.fail_stopped
        and all(res_a.heights[v] == -1 for v in res_a.fail_stopped)
    )
    sim_storage = (res_a.storage or {}).get("totals", {})

    rec = {
        "metric": "disk_fault_supervisor",
        "stage": "diskfault",
        "batch": n,
        "seed": seed,
        "verdicts_equal": bool((bits_clean == bits_fault).all()),
        "verdicts_match_oracle": bool((bits_clean == expected).all()),
        "degrade_drops": snap["drops"],
        "degrade_retries": snap["retries"],
        "degrade_fatals": snap["fatals"],
        "blackbox_dropped": bb_stats["dropped"],
        "sim_reached": bool(res_a.reached),
        "sim_violations": len(res_a.violations),
        "sim_fail_stopped": list(res_a.fail_stopped),
        "sim_fatals": sim_storage.get("fatals", 0),
        "sim_trace_identical": res_a.trace == res_b.trace,
        "survivor_height": max(res_a.heights),
    }
    emit(rec)
    # hard invariants — a disk fault must never change a verdict, and a
    # fail-stopped node must never participate after the halt
    assert rec["verdicts_equal"], "verdicts diverged under disk faults"
    assert rec["verdicts_match_oracle"], "verdicts diverged from oracle"
    assert exec_degraded, "exec_cache fault did not surface as OSError"
    assert snap["drops"] > 0, "no counted drops under injected faults"
    assert snap["fatals"] == 0, (
        "a degradable surface fault must never fail-stop"
    )
    assert bb_stats["dropped"] > 0 and bb_stats["closed"], (
        "blackbox writer did not degrade to counted drops"
    )
    assert rec["sim_reached"] and rec["sim_violations"] == 0, (
        res_a.violations or "survivors did not reach target"
    )
    assert victim_halted, (
        f"fail-stopped node still participating: {res_a.heights}"
    )
    assert rec["sim_fatals"] >= 1, "disk-full run recorded no fatal"
    assert rec["sim_trace_identical"], (
        "disk-full traces diverged between same-seed runs"
    )
    out = os.path.join(REPO, "BENCH_DISKFAULT.json")
    try:
        with open(out, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    return rec


def run_blocksync(emit, seed=11) -> dict:
    """Deterministic blocksync-under-faults stage (docs/sim-design.md
    "WAN-grade blocksync").  Three legs, all on the virtual clock and
    the host-oracle device seam (jax-free by construction):

      * **storm leg** — the ``blocksync-storm`` scenario run TWICE with
        the same seed: a late joiner catches 40+ heights through lossy
        links while one helper goes mute, another serves a forged block
        (ban -> half-open probe -> re-admission) and the joiner
        crash-restarts mid-catchup.  Both runs' traces must be
        byte-identical and the joiner must complete and promote.

      * **wan leg** — the ``wan-catchup`` scenario once: a joiner
        blocksyncs cross-region on the geo-cluster fabric while a
        5-of-7 majority keeps committing through a geo-partition.

      * **dispatch economics** — the fused-prefetch window must beat
        per-height dispatching: dispatches-per-1k-synced-heights
        strictly below 1000 (one dispatch per height is the serial
        floor), asserted hard via the completion lines in the trace.

    Emitted as stage="blocksync" and written to BENCH_BLOCKSYNC.json
    for the bench_trend gate (walls advisory, counters hard)."""
    import re as _re

    from cometbft_tpu.sim.scenarios import run_scenario

    t0 = time.perf_counter()
    res_a = run_scenario("blocksync-storm", seed)
    res_b = run_scenario("blocksync-storm", seed)
    storm_wall = time.perf_counter() - t0

    def _joiner_stats(res) -> dict:
        out: dict = {}
        for line in res.trace:
            m = _re.search(
                r"bsync node\d+ complete h=(\d+) dispatches=(\d+)", line
            )
            if m:
                out = {"height": int(m.group(1)), "dispatches": int(m.group(2))}
        return out

    storm_join = _joiner_stats(res_a)
    storm_bsync = res_a.bsync or {}
    heights = storm_bsync.get("heights_synced", 0)
    dispatches = storm_join.get("dispatches", 0)

    t1 = time.perf_counter()
    res_w = run_scenario("wan-catchup", seed)
    wan_wall = time.perf_counter() - t1
    wan_bsync = res_w.bsync or {}

    rec = {
        "metric": "blocksync_catchup",
        "stage": "blocksync",
        "seed": seed,
        "storm_reached": bool(res_a.reached and res_b.reached),
        "storm_violations": len(res_a.violations),
        "storm_trace_identical": res_a.trace == res_b.trace,
        "storm_joined": bool(storm_join),
        "storm_heights_synced": heights,
        "storm_requests": storm_bsync.get("requests", 0),
        "storm_timeouts": storm_bsync.get("timeouts", 0),
        "storm_bans": storm_bsync.get("bans", 0),
        "storm_probe_passes": storm_bsync.get("probe_passes", 0),
        "storm_redos": storm_bsync.get("redos", 0),
        "prefetch_dispatches": dispatches,
        "dispatches_per_1k_heights": (
            round(dispatches * 1000.0 / heights, 3) if heights else 0.0
        ),
        "catchup_heights_per_s_virtual": round(
            storm_bsync.get("heights_per_second", 0.0), 3
        ),
        "wan_reached": bool(res_w.reached),
        "wan_violations": len(res_w.violations),
        "wan_heights_synced": wan_bsync.get("heights_synced", 0),
        "storm_wall_s": round(storm_wall, 3),
        "wan_wall_s": round(wan_wall, 3),
    }
    emit(rec)
    # hard invariants — catchup under WAN-grade faults must complete,
    # replay byte-for-byte from the seed, and amortize verify dispatches
    assert rec["storm_reached"] and rec["storm_violations"] == 0, (
        res_a.violations or "storm did not reach target"
    )
    assert rec["storm_trace_identical"], (
        "blocksync-storm traces diverged between same-seed runs"
    )
    assert rec["storm_joined"], "joiner never completed blocksync"
    assert heights >= 40, f"joiner synced only {heights} heights"
    assert rec["storm_bans"] >= 1 and rec["storm_probe_passes"] >= 1, (
        "ban -> probe -> re-admission cycle never exercised"
    )
    assert dispatches >= 1, "fused-prefetch never dispatched"
    assert rec["dispatches_per_1k_heights"] < 1000.0, (
        "prefetch did not beat one-dispatch-per-height"
    )
    assert rec["wan_reached"] and rec["wan_violations"] == 0, (
        res_w.violations or "wan-catchup did not reach target"
    )
    assert rec["wan_heights_synced"] >= 40, (
        f"wan joiner synced only {rec['wan_heights_synced']} heights"
    )
    out = os.path.join(REPO, "BENCH_BLOCKSYNC.json")
    try:
        with open(out, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    return rec


def _loopback_cache_hit_rate() -> float:
    """Gossip-verify one round of precommits into a VoteSet, then re-verify
    the commit assembled from them (the apply-time LastCommit check) — the
    signature cache should absorb the second pass entirely.  Host-path
    only: this measures the cache, not the device."""
    from cometbft_tpu.crypto import sigcache
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.types import validation
    from cometbft_tpu.types.basic import (
        PRECOMMIT_TYPE, BlockID, PartSetHeader, Timestamp,
    )
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.types.vote import Vote
    from cometbft_tpu.types.vote_set import VoteSet
    import hashlib as _hashlib

    sigcache.reset_cache()
    chain_id = "bench-loopback"
    privs = [
        Ed25519PrivKey.from_seed(_hashlib.sha256(b"lb%d" % i).digest())
        for i in range(8)
    ]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    bid = BlockID(
        hash=_hashlib.sha256(b"lb-blk").digest(),
        part_set_header=PartSetHeader(1, _hashlib.sha256(b"lb-psh").digest()),
    )
    vs = VoteSet(chain_id, 5, 0, PRECOMMIT_TYPE, vals)
    for p in privs:
        addr = p.pub_key().address()
        idx = vals.get_by_address(addr)[0]
        v = Vote(
            type_=PRECOMMIT_TYPE,
            height=5,
            round_=0,
            block_id=bid,
            timestamp=Timestamp(1_700_000_000, 0),
            validator_address=addr,
            validator_index=idx,
        )
        v.signature = p.sign(v.sign_bytes(chain_id))
        vs.add_vote(v)  # gossip-time verification populates the cache
    commit = vs.make_commit()
    validation.verify_commit(
        chain_id, vals, bid, 5, commit, backend="cpu"
    )  # apply-time re-verification: all hits
    stats = sigcache.get_cache().stats()
    sigcache.reset_cache()
    return round(stats["hit_rate"], 4)


def _result_line(stage: str, vps: float, extra: dict) -> dict:
    out = {
        "metric": "ed25519_batch_verify_throughput",
        "value": round(vps, 1),
        "unit": "verifies/s",
        "vs_baseline": round(vps / BASELINE_VERIFIES_PER_SEC, 4),
        "stage": stage,
    }
    out.update(extra)
    return out


def worker() -> None:
    """Measure stages smallest-first, emitting a JSON line after each.

    Per-batch flow is compile -> validate (first batch only) -> measure ->
    emit, so a stall during a LATER compile still leaves every completed
    batch's number on stdout."""
    from cometbft_tpu.libs import cachedir

    cachedir.enable()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cometbft_tpu.ops import verify as ov

    platform = jax.devices()[0].platform
    impl = "pallas" if ov._use_pallas() else "xla"
    batches = TPU_BATCHES
    cap = os.environ.get("BENCH_BATCH")  # bound the sweep (legacy knob)
    if cap:
        cap_n = int(cap)
        batches = tuple(b for b in TPU_BATCHES if b <= cap_n) or (cap_n,)
        if cap_n not in batches:
            batches = tuple(sorted(set(batches) | {cap_n}))
    reps = int(os.environ.get("BENCH_REPS", "5"))

    def measure(call, packed, b: int) -> float:
        accept = np.asarray(call(packed))
        assert accept[:b].all(), f"batch {b} failed to verify"
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(call(packed))
            times.append(time.perf_counter() - t0)
        return min(times)

    stage_s = {}
    prep = {}
    for i, b in enumerate(batches):
        pubs, msgs, sigs = _make_batch(b)
        packed, _, structural, _ = ov.pack_batch(pubs, msgs, sigs)
        lanes = structural.shape[0]
        packed = jnp.asarray(packed)
        # heartbeat BEFORE the (possibly minutes-long) compile: the
        # orchestrator grants compile-sized stall budgets only while the
        # latest line is a compile-start marker
        _emit(
            _result_line(
                f"compile-{b}", 0.0,
                dict(impl=impl, platform=platform, partial=True, batch=b),
            )
        )
        call, info = ov.bucket_executable(impl, lanes)
        prep[b] = (pubs, msgs, sigs)
        if i == 0:
            # correctness of the COMPILED artifact before any timed run:
            # known-answer + tampered vectors padded into this batch shape
            from scripts import chip_validate

            verdict = chip_validate.validate_with(
                lambda p: np.asarray(call(jnp.asarray(p))), bucket=lanes
            )
            chip_validate.write_artifact(verdict, impl=impl, platform=platform)
            _emit(
                _result_line(
                    "chip_validate", 0.0,
                    dict(impl=impl, platform=platform, partial=True,
                         chip_validate_ok=verdict["ok"],
                         vectors=verdict["n_vectors"]),
                )
            )
            if not verdict["ok"]:
                # broken bits: a throughput number would be meaningless
                sys.exit(3)
        t = measure(call, packed, b)
        stage_s[b] = t
        _emit(
            _result_line(
                f"batch-{b}", b / t,
                dict(impl=impl, platform=platform, partial=True, batch=b,
                     kernel_s=round(t, 6), **info),
            )
        )

    # end-to-end at the commit shape (sign-bytes + host SHA-512/packing +
    # transfer + dispatch) — the number consensus actually sees, as a p50
    # over reps with a host/transfer/kernel breakdown (VERDICT r4 #3).
    # verify_batch goes through the jitted (not AOT) path, so a cold
    # cache can cost another Mosaic compile here: emit a compile
    # heartbeat so the orchestrator grants the compile-sized stall
    # budget (ADVICE r4).
    eb = 10240 if 10240 in prep else batches[-1]
    pubs, msgs, sigs = prep[eb]
    _emit(
        _result_line(
            f"compile-e2e-{eb}", 0.0,
            dict(impl=impl, platform=platform, partial=True, batch=eb),
        )
    )
    e2e_times = []
    for _ in range(max(reps, 3)):
        t0 = time.perf_counter()
        bits = ov.verify_batch(pubs, msgs, sigs)
        e2e_times.append(time.perf_counter() - t0)
        assert bits.all()
    e2e_times.sort()
    e2e_s = e2e_times[len(e2e_times) // 2]  # p50

    # breakdown: sign-bytes (native commit_sign_bytes on a synthetic
    # eb-sig commit), host pack (pack_batch), transfer (the one packed
    # buffer), kernel+fetch (AOT call on the resident buffer), dispatch
    # amortization.
    # The WHOLE breakdown is advisory — a failure here must never cost the
    # final headline line.
    breakdown = {}
    try:
        breakdown["signbytes_ms"] = round(_time_sign_bytes(eb) * 1e3, 2)
        t0 = time.perf_counter()
        packed_e, _, structural_e, _ = ov.pack_batch(pubs, msgs, sigs)
        breakdown["host_pack_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        t0 = time.perf_counter()
        packed_e = jnp.asarray(packed_e).block_until_ready()
        breakdown["transfer_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        call_e, _ = ov.bucket_executable(impl, structural_e.shape[0])
        kt = []
        for _ in range(max(reps, 3)):
            t0 = time.perf_counter()
            np.asarray(call_e(packed_e))
            kt.append(time.perf_counter() - t0)
        kt.sort()
        breakdown["kernel_fetch_p50_ms"] = round(kt[len(kt) // 2] * 1e3, 2)
        # dispatch amortization: 4 consecutive commits with async dispatch
        # + host/device overlap vs the serial e2e p50 (x4)
        t0 = time.perf_counter()
        outs = ov.verify_batches_overlapped([(pubs, msgs, sigs)] * 4)
        overlap_s = time.perf_counter() - t0
        assert all(bits.all() for bits in outs)
        breakdown["overlap4_per_commit_ms"] = round(overlap_s / 4 * 1e3, 2)
        breakdown["serial_per_commit_ms"] = round(e2e_s * 1e3, 2)
    except Exception as e:  # noqa: BLE001
        breakdown["error"] = repr(e)

    # light-client sync stage (BASELINE config #3): 1k-validator
    # sequential header sync through the same batch seam.  Small height
    # count: host-side python signing dominates setup, ~4s/1k-val height.
    if os.environ.get("BENCH_LIGHT", "1") != "0":
        _emit(
            _result_line(
                "compile-light", 0.0,
                dict(impl=impl, platform=platform, partial=True),
            )
        )
        try:
            from scripts import bench_light

            bench_light.run(
                lambda rec: _emit(dict(rec, stage="light", partial=True)),
                n_vals=int(os.environ.get("BENCH_LIGHT_VALS", "1000")),
                heights=int(os.environ.get("BENCH_LIGHT_HEIGHTS", "3")),
            )
        except Exception as e:  # noqa: BLE001 — never risk the headline
            _emit(
                _result_line(
                    "light-failed", 0.0, dict(partial=True, error=repr(e))
                )
            )

    # multi-height catchup (ISSUE 3): K fused commits vs K dispatches, the
    # blocksync window-prefetch shape, plus loopback cache hit rate
    if os.environ.get("BENCH_CATCHUP", "1") != "0":
        _emit(
            _result_line(
                "compile-catchup", 0.0,
                dict(impl=impl, platform=platform, partial=True),
            )
        )
        try:
            run_catchup(
                lambda rec: _emit(
                    dict(rec, impl=impl, platform=platform, partial=True)
                ),
                n_heights=int(os.environ.get("BENCH_CATCHUP_HEIGHTS", "4")),
                sigs_per_commit=int(
                    os.environ.get("BENCH_CATCHUP_SIGS", "21")
                ),
            )
        except Exception as e:  # noqa: BLE001 — never risk the headline
            _emit(
                _result_line(
                    "catchup-failed", 0.0, dict(partial=True, error=repr(e))
                )
            )

    # continuous-batching scheduler (ISSUE 5): N concurrent submitters,
    # scheduler-coalesced vs per-caller dispatch
    if os.environ.get("BENCH_SCHED", "1") != "0":
        _emit(
            _result_line(
                "compile-sched", 0.0,
                dict(impl=impl, platform=platform, partial=True),
            )
        )
        try:
            run_sched(
                lambda rec: _emit(
                    dict(rec, impl=impl, platform=platform, partial=True)
                ),
                submitters=int(os.environ.get("BENCH_SCHED_SUBMITTERS", "8")),
                per_submitter=int(os.environ.get("BENCH_SCHED_SIGS", "64")),
            )
        except Exception as e:  # noqa: BLE001 — never risk the headline
            _emit(
                _result_line(
                    "sched-failed", 0.0, dict(partial=True, error=repr(e))
                )
            )

    # batched tx admission (ISSUE 6): coalesced gossip-burst CheckTx vs
    # per-tx — round trips and verify dispatches per 1k txs
    if os.environ.get("BENCH_TXFLOOD", "1") != "0":
        _emit(
            _result_line(
                "compile-txflood", 0.0,
                dict(impl=impl, platform=platform, partial=True),
            )
        )
        try:
            run_txflood(
                lambda rec: _emit(
                    dict(rec, impl=impl, platform=platform, partial=True)
                ),
                n_txs=int(os.environ.get("BENCH_TXFLOOD_TXS", "384")),
                batch=int(os.environ.get("BENCH_TXFLOOD_BATCH", "128")),
                n_pertx=int(os.environ.get("BENCH_TXFLOOD_PERTX", "24")),
            )
        except Exception as e:  # noqa: BLE001 — never risk the headline
            _emit(
                _result_line(
                    "txflood-failed", 0.0, dict(partial=True, error=repr(e))
                )
            )

    # flight-recorder overhead gates (ISSUE 9): host-oracle seam, cheap
    # and platform-independent — the gate is a per-span cost budget
    if os.environ.get("BENCH_OBS", "1") != "0":
        try:
            run_obs(
                lambda rec: _emit(
                    dict(rec, impl=impl, platform=platform, partial=True)
                ),
                n=int(os.environ.get("BENCH_OBS_BATCH", "128")),
            )
        except Exception as e:  # noqa: BLE001 — never risk the headline
            _emit(
                _result_line(
                    "obs-failed", 0.0, dict(partial=True, error=repr(e))
                )
            )

    # final summary: headline = best throughput stage; device-time estimate
    # for the 10k commit from the slope between the two largest batches
    # (subtracts the fixed per-dispatch floor; BASELINE's <5 ms target is
    # the device-kernel portion).
    best_b = max(batches, key=lambda b: b / stage_s[b])
    vps = best_b / stage_s[best_b]
    extra = dict(
        impl=impl,
        platform=platform,
        batch=best_b,
        kernel_s=round(stage_s[best_b], 6),
        e2e_s=round(e2e_s, 6),
        e2e_vps=round(eb / e2e_s, 1),
        e2e_batch=eb,
        e2e_breakdown=breakdown,
    )
    if 10240 in stage_s:
        extra["commit10k_ms"] = round(stage_s[10240] * 1e3, 3)
        if eb == 10240:
            # measured (not estimated) end-to-end commit latency: sign
            # bytes + pack + transfer + kernel + fetch, p50 over reps
            extra["commit10k_e2e_p50_ms"] = round(
                e2e_s * 1e3 + breakdown.get("signbytes_ms", 0.0), 2
            )
    b1, b2 = (batches[-2], batches[-1]) if len(batches) >= 2 else (0, 0)
    if b2 > b1:
        slope = (stage_s[b2] - stage_s[b1]) / (b2 - b1)
        extra["commit10k_device_est_ms"] = round(max(slope, 0.0) * 10240 * 1e3, 3)
        extra["dispatch_floor_ms"] = round(
            max(stage_s[b1] - slope * b1, 0.0) * 1e3, 1
        )
    _emit(_result_line("final", vps, extra))


# --------------------------------------------------------------------------
# probe
# --------------------------------------------------------------------------


def probe() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.time()
    d = jax.devices()
    x = np.asarray(jnp.ones((256, 256)) @ jnp.ones((256, 256)))
    assert float(x[0, 0]) == 256.0
    _emit({"probe": "ok", "platform": d[0].platform,
           "init_s": round(time.time() - t0, 1)})


# --------------------------------------------------------------------------
# orchestrator
# --------------------------------------------------------------------------


class _Stream:
    """A worker subprocess whose stdout JSON lines are collected by a
    reader thread; the orchestrator polls for fresh lines with a stall
    watchdog and can kill the process at any time."""

    def __init__(self, env: dict):
        import tempfile

        fd, self.stderr_path = tempfile.mkstemp(
            prefix="bench-worker-", suffix=".err"
        )
        self._errf = os.fdopen(fd, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-u", os.path.abspath(__file__), "--worker"],
            stdout=subprocess.PIPE,
            stderr=self._errf,
            text=True,
            env=env,
            cwd=REPO,
        )
        self.killed = False
        self.lines: list = []
        self.last_line_t = time.monotonic()
        self._thread = threading.Thread(target=self._read, daemon=True)
        self._thread.start()

    def stderr_tail(self, max_chars: int = 400) -> str:
        try:
            self._errf.flush()
            with open(self.stderr_path) as f:
                return f.read()[-max_chars:]
        except OSError:
            return ""

    def _read(self):
        for line in self.proc.stdout:
            line = line.strip()
            if not line:
                continue
            try:
                self.lines.append(json.loads(line))
            except ValueError:
                continue
            self.last_line_t = time.monotonic()

    def results(self):
        return list(self.lines)

    def alive(self):
        return self.proc.poll() is None

    def kill(self):
        if self.alive():
            self.killed = True
            self.proc.kill()

    def cleanup(self):
        """Close the stderr handle and remove the temp file (call once the
        stream's records/stderr have been consumed)."""
        try:
            self._errf.close()
        except OSError:
            pass
        try:
            os.unlink(self.stderr_path)
        except OSError:
            pass


def _run_tpu_worker(env: dict, remaining) -> "_Stream":
    """Launch a tpu worker and stream its lines with a per-line progress
    watchdog: the first stages may include a minutes-long Mosaic compile;
    later stages must tick faster.  Returns the finished _Stream (records
    via .results(); crash/kill state via .proc.returncode / .killed)."""
    tpu = _Stream(env)
    n_seen = 0
    results: list = []
    while True:
        # generous budget before the first line and during any compile
        # (the worker emits a compile-<batch> heartbeat before each one —
        # cold caches mean EVERY batch shape can cost a Mosaic compile)
        in_compile = n_seen == 0 or str(
            results[-1].get("stage", "")
        ).startswith("compile-")
        stall_limit = 600.0 if in_compile else 270.0
        stall_limit = min(stall_limit, max(remaining() - 120.0, 60.0))
        if len(tpu.results()) > n_seen:
            for rec in tpu.results()[n_seen:]:
                _emit(rec)  # re-emit so the driver's tail has them
            n_seen = len(tpu.results())
            results = tpu.results()
            if results and results[-1].get("stage") == "final":
                break
            continue
        if not tpu.alive():
            tpu._thread.join(timeout=3.0)
            if len(tpu.results()) > n_seen:
                continue
            break
        if time.monotonic() - tpu.last_line_t > stall_limit:
            tpu.kill()
            _emit(
                _result_line(
                    "tpu-stalled", 0.0,
                    dict(partial=True,
                         after_stages=[r.get("stage") for r in results]),
                )
            )
            break
        if remaining() < 90.0:
            tpu.kill()
            break
        time.sleep(1.0)
    if not tpu.alive():
        tpu.proc.wait()  # populate returncode for crash detection
    return tpu


def orchestrate() -> None:
    budget = float(os.environ.get("BENCH_BUDGET_S", "1140"))
    t_start = time.monotonic()

    def remaining() -> float:
        return budget - (time.monotonic() - t_start)

    streams = []

    # Probe the chip (bounded, 2 attempts), then the worker: in turn, one
    # process on the chip at a time.
    probe_ok = False
    probe_info = {}
    for attempt in range(2):
        try:
            out = subprocess.run(
                [sys.executable, "-u", os.path.abspath(__file__), "--probe"],
                capture_output=True,
                text=True,
                timeout=min(100.0, max(remaining() - 600, 30.0)),
                env=dict(os.environ),
                cwd=REPO,
            )
            for line in out.stdout.splitlines():
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("probe") == "ok":
                    probe_ok = True
                    probe_info = rec
            if probe_ok:
                break
        except subprocess.TimeoutExpired:
            pass
        if attempt == 0:
            time.sleep(10)
    _emit({"stage": "probe", "probe_ok": probe_ok, **probe_info})

    tpu_results = []
    if probe_ok and probe_info.get("platform") == "tpu":
        stream = _run_tpu_worker(dict(os.environ), remaining)
        streams.append(stream)
        tpu_results = stream.results()
        crashed_early = (
            not stream.killed
            and stream.proc.returncode not in (0, None)
            and not any(
                r.get("stage", "").startswith("batch-") for r in tpu_results
            )
        )
        if crashed_early:
            _emit(
                _result_line(
                    "tpu-worker-crashed", 0.0,
                    dict(partial=True, rc=stream.proc.returncode,
                         stderr=stream.stderr_tail()),
                )
            )
    # Final line selection: the TPU final line, else the best TPU
    # partial; with neither there is no throughput to print.
    final = None
    for rec in tpu_results:
        if rec.get("stage") == "final":
            final = rec
    if final is None:
        timed = [r for r in tpu_results if r.get("stage", "").startswith("batch-")]
        if timed:
            best = max(timed, key=lambda r: r["value"])
            final = dict(best)
            final["stage"] = "final-partial"
            final["partial"] = True
    for s in streams:
        s.kill()
        s.cleanup()
    if final is None:
        # no TPU, or no stage completed on it: no throughput line at all —
        # a host number is never printed under the device metric's name
        _emit({
            "ok": False,
            "error": (
                "no stage completed within budget"
                if probe_info.get("platform") == "tpu"
                else "no TPU: platform "
                f"{probe_info.get('platform', 'unknown')!r}"
            ),
        })
        sys.exit(1)
    if final.get("stage") == "final":
        final.pop("partial", None)
    _emit(final)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--probe", action="store_true")
    ap.add_argument(
        "--catchup",
        action="store_true",
        help="run only the multi-height catchup comparison (fused "
        "verify_segments vs per-commit dispatches) on whatever platform "
        "JAX selects; BENCH_CATCHUP_HEIGHTS/_SIGS size the window",
    )
    ap.add_argument(
        "--degraded",
        action="store_true",
        help="run only the degraded-mode stage: supervised verify_batch "
        "healthy (device tier) vs device faulted (breaker open -> host "
        "ed25519_ref), plus the re-promotion probe; "
        "BENCH_DEGRADED_BATCH sizes the batch",
    )
    ap.add_argument(
        "--sched",
        action="store_true",
        help="run only the continuous-batching scheduler stage: N "
        "concurrent submitter threads coalesced by verifysched vs "
        "per-caller sync dispatch (sigs/s, dispatches/1k sigs, p50/p99 "
        "submit->verdict latency); BENCH_SCHED_SUBMITTERS / "
        "BENCH_SCHED_SIGS size the run",
    )
    ap.add_argument(
        "--txflood",
        action="store_true",
        help="run only the batched tx-admission stage: ingest-coalesced "
        "check_txs vs per-tx CheckTx (txs/s, app round trips and verify "
        "dispatches per 1k txs, consensus p99 latency idle vs flood); "
        "BENCH_TXFLOOD_TXS / _BATCH / _PERTX size the run",
    )
    ap.add_argument(
        "--obs",
        action="store_true",
        help="run only the flight-recorder overhead stage: measured "
        "per-span cost x spans-per-verify against the sched-bench "
        "workload on the host-oracle seam; gates tracer-disabled "
        "overhead <= 1%% and tracer-enabled <= 5%%; BENCH_OBS_BATCH "
        "sizes the batch",
    )
    ap.add_argument(
        "--meshfault",
        action="store_true",
        help="run only the elastic-mesh fault stage: healthy full-width "
        "dispatch vs one-dead-chip dispatch on the per-shard host-oracle "
        "runner seam — verdict equality, exactly one shrink, and "
        "dispatches-per-1k-sigs asserted hard, walls advisory; writes "
        "BENCH_MESHFAULT.json for the bench_trend gate; "
        "BENCH_MESHFAULT_BATCH / _WIDTH size the run",
    )
    ap.add_argument(
        "--multichip",
        action="store_true",
        help="run only the multi-lane in-flight pipeline stage: the "
        "10240-signature commit shape chunked across mesh lanes with K "
        "dispatches in flight (verify_pipelined) on the host-oracle "
        "shard runner — oracle-equal verdicts, full in-flight occupancy "
        "and lane coverage asserted hard, commit10k_ms advisory; writes "
        "BENCH_MULTICHIP.json for the bench_trend gate; skips when jax "
        "reports < 2 devices; BENCH_MULTICHIP_BATCH / _INFLIGHT size "
        "the run",
    )
    ap.add_argument(
        "--proofserve",
        action="store_true",
        help="run only the coalesced proof-serving stage: N tx-proof "
        "queries through the proofserve ProofServer (paused-burst "
        "flushes + LRU cache) vs per-query serial tree builds on the "
        "host tree-runner seam — roots/proofs bitwise-equal and "
        "dispatches-per-1k-proofs asserted hard, walls advisory; "
        "writes BENCH_PROOFSERVE.json for the bench_trend gate; "
        "BENCH_PROOFSERVE_QUERIES / _HEIGHTS / _TXS / _SAMPLE size "
        "the run",
    )
    ap.add_argument(
        "--transport",
        action="store_true",
        help="run only the encrypted-transport-plane stage: coalesced "
        "AEAD frame sealing (transportplane bursts, one counted "
        "dispatch per burst) vs per-frame ChaCha20Poly1305Ref, and "
        "pooled X25519 handshake admission vs per-dial sync exchange, "
        "both on the host runner seams — ciphertexts/secrets "
        "bitwise-equal and dispatches-per-1k asserted hard, MB/s and "
        "handshakes/s advisory; writes BENCH_TRANSPORT.json for the "
        "bench_trend gate; BENCH_TRANSPORT_FRAMES / _FRAME_B / _DIALS "
        "/ _BURST size the run",
    )
    ap.add_argument(
        "--diskfault",
        action="store_true",
        help="run only the disk-fault supervisor stage: verify verdicts "
        "with and without injected storage faults on the degradable "
        "surfaces must be bitwise-equal (counted drops, zero fatals), "
        "and the disk-full sim scenario must fail-stop its victim with "
        "zero consensus participation, byte-deterministically per seed; "
        "writes BENCH_DISKFAULT.json for the bench_trend gate; "
        "BENCH_DISKFAULT_BATCH / _SEED size the run",
    )
    ap.add_argument(
        "--blocksync",
        action="store_true",
        help="run only the blocksync-under-faults stage: the "
        "blocksync-storm sim scenario twice with one seed (traces must "
        "be byte-identical, the joiner must catch 40+ heights through "
        "loss/mute/forgery/crash-restart with ban -> probe -> "
        "re-admission exercised) plus one wan-catchup geo run; "
        "fused-prefetch dispatches-per-1k-heights asserted hard below "
        "the one-per-height floor; writes BENCH_BLOCKSYNC.json for the "
        "bench_trend gate; BENCH_BLOCKSYNC_SEED sizes the run",
    )
    ap.add_argument(
        "--warmboot",
        action="store_true",
        help="run only the warm-boot pipeline stage: two cold processes "
        "against one empty exec cache — first vs second boot "
        "time-to-first-verified-commit, per-shape exec_cache statuses "
        "(second boot must be all hits, zero compiles) and verdict "
        "differential; BENCH_WARMBOOT_BUCKETS bounds the matrix",
    )
    ap.add_argument(
        "--warmboot-child", action="store_true", help=argparse.SUPPRESS
    )
    args = ap.parse_args()
    # one cache root for this process and every child it starts; set
    # through the environment, so the orchestrator stays off jax
    from cometbft_tpu.libs import cachedir

    cachedir.enable()
    if not args.warmboot_child:
        # bench stages that activate the trusted backend (sched/txflood)
        # must not kick the background warm-boot compile matrix mid-
        # measurement; the warmboot stage drives it explicitly
        os.environ.setdefault("COMETBFT_TPU_WARMBOOT", "0")
    if args.warmboot_child:
        _warmboot_child()
        return
    if args.probe:
        probe()
    elif args.catchup:
        run_catchup(
            _emit,
            n_heights=int(os.environ.get("BENCH_CATCHUP_HEIGHTS", "4")),
            sigs_per_commit=int(os.environ.get("BENCH_CATCHUP_SIGS", "21")),
        )
    elif args.degraded:
        run_degraded(
            _emit, n=int(os.environ.get("BENCH_DEGRADED_BATCH", "128"))
        )
    elif args.sched:
        run_sched(
            _emit,
            submitters=int(os.environ.get("BENCH_SCHED_SUBMITTERS", "8")),
            per_submitter=int(os.environ.get("BENCH_SCHED_SIGS", "64")),
        )
    elif args.txflood:
        run_txflood(
            _emit,
            n_txs=int(os.environ.get("BENCH_TXFLOOD_TXS", "384")),
            batch=int(os.environ.get("BENCH_TXFLOOD_BATCH", "128")),
            n_pertx=int(os.environ.get("BENCH_TXFLOOD_PERTX", "24")),
        )
    elif args.obs:
        run_obs(_emit, n=int(os.environ.get("BENCH_OBS_BATCH", "128")))
    elif args.meshfault:
        # jax-free by construction (host-oracle shard runner): no
        # compilation cache plumbing needed
        run_meshfault(
            _emit,
            n=int(os.environ.get("BENCH_MESHFAULT_BATCH", "256")),
            width=int(os.environ.get("BENCH_MESHFAULT_WIDTH", "4")),
        )
    elif args.multichip:
        # the shard work runs on the host-oracle runner seam; jax is
        # probed only for the device count (skip on single-chip hosts)
        run_multichip(
            _emit,
            n=int(os.environ.get("BENCH_MULTICHIP_BATCH", "10240")),
            depth=int(os.environ.get("BENCH_MULTICHIP_INFLIGHT", "0"))
            or None,
        )
    elif args.proofserve:
        # jax-free by construction (host tree-runner seam): no
        # compilation cache plumbing needed
        run_proofserve(
            _emit,
            n_queries=int(os.environ.get("BENCH_PROOFSERVE_QUERIES", "10000")),
            n_heights=int(os.environ.get("BENCH_PROOFSERVE_HEIGHTS", "32")),
            txs_per_block=int(os.environ.get("BENCH_PROOFSERVE_TXS", "64")),
            sample=int(os.environ.get("BENCH_PROOFSERVE_SAMPLE", "2000")),
        )
    elif args.transport:
        # jax-free by construction (host AEAD/ladder runner seams): no
        # compilation cache plumbing needed
        run_transport(
            _emit,
            n_frames=int(os.environ.get("BENCH_TRANSPORT_FRAMES", "2000")),
            frame_bytes=int(os.environ.get("BENCH_TRANSPORT_FRAME_B", "1024")),
            n_dials=int(os.environ.get("BENCH_TRANSPORT_DIALS", "1000")),
        )
    elif args.diskfault:
        run_diskfault(
            _emit,
            n=int(os.environ.get("BENCH_DISKFAULT_BATCH", "128")),
            seed=int(os.environ.get("BENCH_DISKFAULT_SEED", "11")),
        )
    elif args.blocksync:
        # jax-free by construction (host-oracle device runner under the
        # sim scenarios): no compilation cache plumbing needed
        run_blocksync(
            _emit,
            seed=int(os.environ.get("BENCH_BLOCKSYNC_SEED", "11")),
        )
    elif args.warmboot:
        run_warmboot(_emit)
    elif args.worker:
        worker()
    else:
        orchestrate()


if __name__ == "__main__":
    main()
