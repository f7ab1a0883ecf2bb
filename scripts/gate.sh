#!/usr/bin/env bash
# Ship gate: run before every snapshot/commit of a milestone.
#
# Round 2 shipped with pytest, bench.py and the multichip dryrun all red —
# this 2-minute gate would have caught every one of them (VERDICT.md r2 #3).
#
#   1. full pytest suite (CPU, virtual 8-device mesh via tests/conftest.py)
#   2. bench.py: on a TPU exits 0 with the headline line; with no TPU it
#      must refuse — exit 1, last line "ok": false, no throughput
#   3. dryrun_multichip(8) on a forced 8-device CPU mesh
#
# NIGHTLY=1 additionally runs the slow lane: the -m slow pytest marks
# (real-kernel scenarios, determinism double-runs, 100-validator fleets)
# and the sim soak matrix (scenario x seed x scale with per-cell same-seed
# double runs — invariant violations OR trace divergence fail the gate).
set -euo pipefail
cd "$(dirname "$0")/.."

# One cache root (libs/cachedir): every gate stage — pytest (and the node
# subprocesses it spawns), bench, the multichip dry-run — keeps JAX's
# compile cache and the AOT executables (docs/warm-boot.md) under
# JAX_COMPILATION_CACHE_DIR, or .cache/ in the checkout when it is unset.

echo "== gate 1/13: verify/hash/aead call-site + disk-policy lints =="
python scripts/check_verify_callsites.py
# new direct merkle call sites must use the proofserve plane seam
python scripts/check_hash_callsites.py
# new direct AEAD/X25519 call sites must use the transport plane seam
python scripts/check_aead_callsites.py
# new direct open/fsync/replace call sites must use the diskguard seam
python scripts/check_diskpolicy.py

echo "== gate 2/13: pytest =="
rm -f /tmp/_gate_t1.log
python -m pytest tests/ -x -q --durations=40 2>&1 | tee /tmp/_gate_t1.log
python scripts/check_tier1_budget.py /tmp/_gate_t1.log

echo "== gate 3/13: bench.py (TPU: measures; no TPU: must refuse) =="
out=$(python bench.py) && rc=0 || rc=$?
echo "$out" | tail -n 2
if [ "$rc" -ne 0 ]; then
    echo "$out" | tail -n 1 | grep -q '"ok": false'
    ! echo "$out" | grep -q '"unit": "verifies/s"'
fi

echo "== gate 4/13: bench.py --meshfault (elastic mesh fault isolation) =="
# healthy vs one-dead-chip dispatch on the per-shard host-oracle seam:
# verdict equality, exactly one shrink, dispatch counts asserted hard;
# refreshes BENCH_MESHFAULT.json for the trend gate below
JAX_PLATFORMS=cpu python bench.py --meshfault

echo "== gate 5/13: disk-fault robustness (diskguard) =="
# the three storage scenarios (fail-stop halt / degrade-with-retries /
# torn-tail repair) with invariants raised to hard failures, then the
# bench stage: verdict equality under injected faults + same-seed trace
# determinism of the disk-full run; refreshes BENCH_DISKFAULT.json
JAX_PLATFORMS=cpu python -c "
from cometbft_tpu.sim.scenarios import run_scenario
for name in ('disk-full', 'disk-brownout', 'torn-wal-restart'):
    r = run_scenario(name, 3, raise_on_violation=True)
    assert r.reached, (name, r.heights)
    print('disk scenario %-16s ok heights=%s fail_stopped=%s' % (
        name, r.heights, r.fail_stopped))
"
JAX_PLATFORMS=cpu python bench.py --diskfault

echo "== gate 6/13: proof plane (light-stampede + bench.py --proofserve) =="
# thousands of light-client proof queries mid-consensus on the host
# tree-runner seam: zero consensus-class verify shed, commits reach the
# target, byte-deterministic per seed (invariants raised to hard
# failures); then the bench stage: coalesced proof serving must beat
# per-query serial on dispatches-per-1k-proofs with bitwise-identical
# roots/proofs; refreshes BENCH_PROOFSERVE.json for the trend gate
JAX_PLATFORMS=cpu python -c "
from cometbft_tpu.sim.scenarios import run_scenario
r = run_scenario('light-stampede', 3, raise_on_violation=True)
assert r.reached, r.heights
r2 = run_scenario('light-stampede', 3, raise_on_violation=True)
assert r.trace == r2.trace, 'light-stampede trace diverged between runs'
assert r.proofs == r2.proofs, (r.proofs, r2.proofs)
print('light-stampede ok heights=%s proofs=%s' % (r.heights, r.proofs))
"
JAX_PLATFORMS=cpu python bench.py --proofserve

echo "== gate 7/13: transport plane (dial-storm + bench.py --transport) =="
# hundreds of concurrent inbound dials mid-consensus on the host AEAD +
# ladder runner seams: handshake queue sheds only to the sync dial (zero
# consensus-class verify shed), frame batches authenticate with the
# tamper rejected at the exact serial position, byte-deterministic per
# seed including every transport counter (invariants raised to hard
# failures); then the bench stage: coalesced sealing/pooled admission
# must beat per-frame/per-dial serial on dispatches-per-1k with
# bitwise-identical ciphertexts and secrets; refreshes
# BENCH_TRANSPORT.json for the trend gate.  COMETBFT_TPU_WARMBOOT=0:
# the storm measures admission, not the background compile matrix
# (tests/test_warmboot.py covers the transport warm family).
COMETBFT_TPU_WARMBOOT=0 JAX_PLATFORMS=cpu python -c "
from cometbft_tpu.sim.scenarios import run_scenario
r = run_scenario('dial-storm', 3, raise_on_violation=True)
assert r.reached, r.heights
assert r.sched.get('shed', {}).get('consensus', 0) == 0, r.sched
r2 = run_scenario('dial-storm', 3, raise_on_violation=True)
assert r.trace == r2.trace, 'dial-storm trace diverged between runs'
assert r.transport == r2.transport, (r.transport, r2.transport)
print('dial-storm ok heights=%s transport=%s' % (r.heights, r.transport))
"
JAX_PLATFORMS=cpu python bench.py --transport

echo "== gate 8/13: bench.py --multichip (in-flight verify pipeline) =="
# the 10240-sig commit shape chunked over an 8-lane virtual mesh with K
# dispatches in flight on the host-oracle shard seam: oracle-equal
# verdicts, full in-flight occupancy and lane coverage asserted hard
# (skips itself when jax reports < 2 devices); refreshes
# BENCH_MULTICHIP.json for the trend gate below
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python bench.py --multichip

echo "== gate 9/13: blocksync catchup plane (storm + WAN + bench) =="
# a late joiner catches 40+ heights through lossy bandwidth-shaped links
# while helpers stall/forge (adaptive timeouts, strike bans, half-open
# probe re-admission, stall switch), and a geo-clustered joiner syncs
# cross-region through a mid-sync partition: both byte-deterministic per
# seed including every pool counter; then the bench stage asserts the
# ban->probe->re-admission cycle and the fused-prefetch dispatch budget;
# refreshes BENCH_BLOCKSYNC.json for the trend gate below
JAX_PLATFORMS=cpu python -c "
from cometbft_tpu.sim.scenarios import run_scenario
for name in ('blocksync-storm', 'wan-catchup'):
    r = run_scenario(name, 3, raise_on_violation=True)
    assert r.reached, (name, r.heights)
    assert r.bsync.get('heights_synced', 0) >= 40, (name, r.bsync)
    r2 = run_scenario(name, 3, raise_on_violation=True)
    assert r.trace == r2.trace, '%s trace diverged between runs' % name
    assert r.bsync == r2.bsync, (r.bsync, r2.bsync)
    print('%-16s ok heights=%s bsync=%s' % (name, r.heights, r.bsync))
"
JAX_PLATFORMS=cpu python bench.py --blocksync

echo "== gate 10/13: bench trend (BENCH_HISTORY.jsonl) =="
# re-ingests every BENCH_*.json + sim_soak trend JSON and fails on hard
# regressions (dispatch counts, cache/occupancy ratios) beyond the noise
# band; wall/throughput deltas stay advisory on this throttled host
python scripts/bench_trend.py --check

echo "== gate 11/13: SIGKILL forensics (black-box postmortem) =="
# crash a sim validator mid-round, decode its journal with the real
# `cometbft-tpu postmortem --json` subprocess, assert the reconstructed
# in-flight round + dispatch attribution, byte-deterministic per seed
JAX_PLATFORMS=cpu python scripts/check_postmortem.py

echo "== gate 12/13: dryrun_multichip(8) + elastic fault leg =="
# includes the chip-death leg: one ordinal killed mid-run, the batch
# must re-verify on the shrunken mesh with correct ordinal attribution
# (COMETBFT_TPU_DRYRUN_FAULT=0 skips the leg)
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "== gate 13/13: native sanitizers (TSAN+ASAN) =="
bash scripts/sanitize_native.sh

if [ "${NIGHTLY:-0}" = "1" ]; then
    echo "== nightly 1/2: slow-lane pytest =="
    python -m pytest tests/ -x -q -m slow

    echo "== nightly 2/2: sim soak matrix =="
    python scripts/sim_soak.py --matrix --seeds 2 --scales 8,25 \
        --out sim_soak_matrix.json
    # fold the fresh soak rows into the bench trend history so scenario
    # wall-time drift becomes a diffable column on the next gate run
    python scripts/bench_trend.py --check
fi

echo "gate: ALL GREEN"
