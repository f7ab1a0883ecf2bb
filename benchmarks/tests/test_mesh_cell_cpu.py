"""The four-chip cell (PR 34) without a chip: the manifest's new entries, the
chain its configuration gives, its files end to end on a four-device CPU
mesh at 8 validators, its five readers on made-up traces, and its control."""

import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from benchmarks import chain as chainlib
from benchmarks import manifest, mesh_control, roofline
from benchmarks import trace_reduce as tr
from benchmarks.loops.closed import Record
from cometbft_tpu.libs import tracing

CELL = "val10k-commit-stream-x4"
ONE_CHIP = "val10k-commit-stream"
NEW = {
    "shards_per_dispatch": ("count", "higher", "program_span", "supervisor", "sigs_per_s"),
    "mesh_put_ms": ("ms", "lower", "program_span", "executable", "verify_p50_ms"),
    "mesh_kernel_us_per_sig": ("us/sig", "lower", "device_trace", "kernel", "sigs_per_s"),
    "ed25519_verify_mesh_roofline": ("%", "higher", "device_trace", "kernel", "sigs_per_s"),
    "mesh_collective_us": ("us", "lower", "device_trace", "device", "verify_p50_ms"),
}
KERNEL = "ed25519_verify.1 s32[1,2048] tpu_custom_call"
REDUCE = ("psum.7 s32[]", "all-reduce.1 s32[]")  # the chip's trace prints the first
PEAKS = roofline.peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def pool():
    p = chainlib.SignPool(2)
    yield p
    p.close()


# -- the manifest --------------------------------------------------------------------


def test_the_new_entries_and_the_share_of_four_chip_cells():
    m = manifest.load()
    cells = m["workloads"]
    assert [w["name"] for w in cells][-1] == CELL and len(cells) == 5
    assert [w["name"] for w in cells if w["chips"] == 4] == [CELL]
    assert 1 <= max(1, len(cells) // 2)
    cell = manifest.Cell(m, CELL)
    assert cell.chips == 4 and cell.config_name == "val10k-ed25519-host4"
    assert cell.traffic["heights"] == {"val10k-ed25519-host4": 340}
    assert chainlib.pool_size(cell.traffic, cell.config_name, 10240) == 340
    assert cell.entry.NAME == "verify_commit_light" and cell.traffic["loop"] == "closed"
    listed = {x["name"]: x for x in m["per_layer"]}
    assert list(listed)[-5:] == list(NEW)
    for name, (unit, better, source, layer, moves) in NEW.items():
        x, r = listed[name], manifest.reader("layers", name)
        assert x["workloads"] == [CELL]
        assert (x["unit"], x["better"], x["source"], x["layer"], x["moves"]) == (
            unit, better, source, layer, moves)
        assert (r.NAME, r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES) == (
            name, unit, better, source, layer, moves)
    # what the new cell reports beside its own five: every reader that
    # lists no cells, and nothing that lists only others
    reported = {x["name"] for x in cell.per_layer()}
    assert set(NEW) <= reported
    assert {"dispatch_wall_ms", "lane_occupancy_pct", "offtier_sigs_pct",
            "compiles_in_window", "verify_mfu", "device_idle_pct"} <= reported
    assert "kernel_us_per_sig" not in reported and "launch_ms" not in reported


def test_the_configuration_is_the_one_chip_one_on_another_layout():
    m = manifest.load()
    one, four = manifest.Cell(m, ONE_CHIP), manifest.Cell(m, CELL)
    differ = {k for k in set(one.config) | set(four.config)
              if one.config.get(k) != four.config.get(k)}
    assert differ == {"name", "source", "what", "chips_layout", "guarantees", "assumed"}
    assert four.config["guarantees"][:5] == one.config["guarantees"]
    assert four.config["assumed"][:5] == one.config["assumed"]
    assert four.config["reduced"] == [] and "2,048 a chip" in four.config["chips_layout"]
    same = {k: v for k, v in four.traffic.items() if k != "heights"}
    assert same == {k: v for k, v in one.traffic.items() if k != "heights"}
    assert one.traffic["heights"]["val10k-ed25519"] == 340


def test_one_seed_gives_both_configurations_the_same_chain_bytes(pool):
    m = manifest.load()
    chains = []
    for name in (ONE_CHIP, CELL):
        cell = manifest.Cell(m, name)
        config = dict(cell.config, validators=8)
        traffic = dict(cell.traffic, heights=6, tamper_every=3, tamper_phase=1,
                       warmup_heights=1, warmup_tampered=1)
        chains.append(chainlib.build(config, traffic, cell.config_name, 2**31 + 5, pool))
    a, b = chains
    assert a.chain_id == b.chain_id == "bench-val10k"
    assert a.pubs == b.pubs and a.powers == b.powers
    assert len(a.pool) == len(b.pool) == 6
    for x, y in zip(a.pool + a.warm, b.pool + b.warm):
        assert (x.height, x.block_hash, x.parts_hash, x.times_ns, x.sigs, x.tamper) == (
            y.height, y.block_hash, y.parts_hash, y.times_ns, y.sigs, y.tamper)


# -- the cell's files, end to end, on a CPU mesh --------------------------------------

_RUN = """
import json, os, sys, time
from benchmarks import chain as chainlib, harness, manifest
from cometbft_tpu.libs import tracing
from cometbft_tpu.ops import dispatch_stats
cell = manifest.Cell(manifest.load(), %r)
cell.config = dict(cell.config, validators=8)
cell.traffic = dict(cell.traffic, heights=24, tamper_every=4, tamper_phase=2,
                    warmup_heights=1, warmup_tampered=1)
pool = chainlib.SignPool(2)
try:
    res = harness.run_cell(cell, 2**31 + 81, 30.0, False, time.perf_counter(),
                           {"platform": "cpu", "kind": "cpu", "count": 4}, pool=pool)
finally:
    pool.close()
snap = dispatch_stats.snapshot()
stages = tracing.get_tracer().stage_totals(0.0, time.perf_counter() + 1.0)
res["program"] = {
    "mesh_dispatches": snap["mesh_dispatches"], "mesh_shards": snap["mesh_shards"],
    "dispatches": snap["dispatches"], "lane_lanes_used": snap["lane_lanes_used"],
    "stages": {k: v[0] for k, v in stages.items()
               if k in ("verify.dispatch", "mesh.put", "mesh.shard", "verify.launch")},
}
print(json.dumps(res), flush=True)
# XLA:CPU with several forced host devices now and then dies in its own
# teardown at interpreter exit, after everything is done: leave without it
os._exit(0)
"""


def test_the_cells_files_run_end_to_end_on_a_four_device_cpu_mesh():
    """Scheduler on, the mesh enabled by the program's own probe (forced to
    take CPU devices), every request one mesh-wide launch of 6 signatures
    over four shards.  In a process of its own: the device count is fixed
    when jax starts.  A minute where the executables are not cached."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        COMETBFT_TPU_CRYPTO_BACKEND="tpu", COMETBFT_TPU_MESH="1",
        COMETBFT_TPU_MESH_MIN_BATCH="4", PYTHONPATH=manifest.ROOT,
    )
    run = subprocess.run(
        [sys.executable, "-c", _RUN % CELL], env=env, cwd=manifest.ROOT,
        capture_output=True, text=True, timeout=900,
    )
    assert run.returncode == 0, run.stderr[-3000:]
    res = json.loads(run.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 24
    assert all(c["value"] == 0 == c["limit"] for c in res["compared"].values())
    assert set(res["metrics"]) == {"verify_p50_ms", "verify_p95_ms", "sigs_per_s", "setup_s"}
    prog = res["program"]
    # 24 requests and 2 of warm-up, each one mesh-wide launch of four shards;
    # the known answers too (more than the test's min batch of 4)
    assert prog["mesh_dispatches"] == 27 and prog["mesh_shards"] == 4 * 27
    assert prog["stages"]["mesh.put"] == 27 and prog["stages"]["mesh.shard"] == 4 * 27
    # all under the tier's name: the requests' 6 signatures each, and the
    # known answers that are well-formed enough to reach the device
    assert list(prog["lane_lanes_used"]) == ["xla"]
    assert 26 * 6 < prog["lane_lanes_used"]["xla"] <= 26 * 6 + 18


# -- the five readers -----------------------------------------------------------------


def mesh_planes(chips=4, dispatches=3, kernel_ns=4_000_000, reduce_ns=20_000):
    """One request a dispatch, 60 ms apart; on every chip the kernel, then the
    all-reduce."""
    ops = []
    for k in range(dispatches):
        at = 10_000_000 + k * 60_000_000
        ops += [(KERNEL, at, kernel_ns), (REDUCE[k % 2], at + kernel_ns, reduce_ns)]
    host = [("request", k * 60_000_000, 50_000_000) for k in range(dispatches)]
    return [(f"/device:TPU:{c}", [("XLA Ops", list(ops))]) for c in range(chips)] + [
        ("/host:CPU", [("python3", host)])]


def ctx_of(trace, requests=3, sigs=6827, chips=4):
    records = [Record(k, float(k), k + 0.05, ("accepted",), sigs) for k in range(requests)]
    return SimpleNamespace(trace=trace, traced_records=records if trace else [],
                           records=records, peaks=PEAKS, chips=chips, counters={})


def test_the_device_readers_on_a_four_chip_trace():
    t = tr.reduce_planes(mesh_planes())
    assert t.chips == 4 and t.op_counts[KERNEL] == 12  # 4 kernel events a dispatch
    ctx = ctx_of(t)
    read = {n: manifest.reader("layers", n).read(ctx) for n in NEW if n.startswith(("mesh_k", "ed", "mesh_c"))}
    # 3 dispatches x 4 chips x 4 ms over 3 x 6,827 signatures: chip-microseconds
    assert read["mesh_kernel_us_per_sig"] == pytest.approx(4 * 4000.0 / 6827)
    assert read["mesh_collective_us"] == pytest.approx(20.0)
    least = 3 * 6827 * roofline.OPS_PER_SIG / (4 * PEAKS["int8_ops_per_s"])
    assert read["ed25519_verify_mesh_roofline"] == pytest.approx(100 * least / 0.012)
    assert 0 < read["ed25519_verify_mesh_roofline"] < 1
    # the same kernel time on one chip's peak would read four times the share
    one = roofline.share_pct(3 * 6827, 0.012, PEAKS, 1)
    assert one == pytest.approx(4 * read["ed25519_verify_mesh_roofline"])


def test_the_device_readers_read_nothing_where_there_is_nothing():
    for name in ("mesh_kernel_us_per_sig", "ed25519_verify_mesh_roofline", "mesh_collective_us"):
        assert manifest.reader("layers", name).read(ctx_of(None)) is None
    # a launch on one chip: a kernel and no collective.  None, never 0
    planes = mesh_planes(chips=1)
    planes[0] = (planes[0][0], [("XLA Ops", [e for e in planes[0][1][0][1] if e[0] == KERNEL])])
    ctx = ctx_of(tr.reduce_planes(planes), chips=1)
    assert manifest.reader("layers", "mesh_collective_us").read(ctx) is None
    assert manifest.reader("layers", "mesh_kernel_us_per_sig").read(ctx) == pytest.approx(4000.0 / 6827)
    # no kernel event at all: no share, not a share of 0
    planes = [(n, [(ln, [e for e in ev if e[0] != KERNEL]) for ln, ev in lines])
              for n, lines in mesh_planes()]
    ctx = ctx_of(tr.reduce_planes(planes))
    for name in ("mesh_kernel_us_per_sig", "ed25519_verify_mesh_roofline", "mesh_collective_us"):
        assert manifest.reader("layers", name).read(ctx) is None


def test_a_share_over_the_ceiling_raises():
    t = tr.reduce_planes(mesh_planes(kernel_ns=10))
    with pytest.raises(ValueError):
        manifest.reader("layers", "ed25519_verify_mesh_roofline").read(ctx_of(t))


@pytest.fixture
def tracer():
    tracing.reset_tracer()
    yield tracing.get_tracer()
    tracing.reset_tracer()


def test_the_span_readers(tracer):
    records = []
    for k in range(100):
        t = 3000.25 + k * 0.1
        tracer.record_span("verify.dispatch", t, t + 0.004)
        tracer.record_span("mesh.put", t + 0.001, t + 0.0025)
        # every tenth dispatch lost a chip and was verified again on three
        for c in range(3 if k % 10 == 0 else 4):
            tracer.record_span("mesh.shard", t + 0.01 + c * 0.001, t + 0.0105 + c * 0.001)
        records.append(Record(k, t, t + 0.06, ("accepted",), 6827))
    ctx = SimpleNamespace(records=records)
    assert manifest.reader("layers", "mesh_put_ms").read(ctx) == pytest.approx(1.5)
    assert manifest.reader("layers", "shards_per_dispatch").read(ctx) == pytest.approx(3.9)


def test_the_span_readers_read_nothing_from_a_program_without_the_spans(tracer):
    records = []
    for k in range(30):
        t = 4000.25 + k * 0.1
        tracer.record_span("verify.dispatch", t, t + 0.004)
        records.append(Record(k, t, t + 0.06, ("accepted",), 6827))
    ctx = SimpleNamespace(records=records)
    assert manifest.reader("layers", "mesh_put_ms").read(ctx) is None
    assert manifest.reader("layers", "shards_per_dispatch").read(ctx) is None
    assert manifest.reader("layers", "shards_per_dispatch").read(SimpleNamespace(records=[])) is None


# -- the control ------------------------------------------------------------------------


def small_cell():
    cell = manifest.Cell(manifest.load(), CELL)
    cell.config = dict(cell.config, validators=8)
    return cell


def test_exchanging_two_shards_moves_a_bit_one_shard():
    bits = [True] * 6827
    bits[100] = False
    got = mesh_control.exchange_shards(bits, 4)
    assert mesh_control.padded_lanes(6827, 4) == 8192
    assert [i for i, ok in enumerate(got) if not ok] == [100 + 2048]
    bits[100], bits[5000] = True, False  # in neither of the two shards
    assert mesh_control.exchange_shards(bits, 4) == bits


@pytest.mark.parametrize("seed", [1, 2**31 + 3, 99])
def test_two_shards_exchanged_comes_out_not_correct(pool, monkeypatch, seed):
    """8 validators, 6 signatures a request, cut over 2 chips of 3 lanes:
    every tampered index is named a shard (3) off."""
    monkeypatch.setattr(mesh_control, "BUCKETS", (6,))
    verdict = mesh_control.run_control(small_cell(), seed, 40, "shards_exchanged", pool, chips=2)
    assert verdict["correct"] is False
    assert verdict["compared"]["window_verdicts_unexpected"]["value"] == 10
    assert verdict["compared"]["sample_verdicts_wrong"]["value"] >= 1
    assert verdict["compared"]["reference_against_generator"]["value"] == 0
    for _, got, want in verdict["first_wrong"]:
        assert got[0] == want[0] == "invalid_signature" and abs(got[1] - want[1]) == 3


def test_nothing_exchanged_comes_out_correct(pool, monkeypatch):
    monkeypatch.setattr(mesh_control, "BUCKETS", (6,))
    verdict = mesh_control.run_control(small_cell(), 5, 40, "none", pool, chips=2)
    assert verdict["correct"] is True
