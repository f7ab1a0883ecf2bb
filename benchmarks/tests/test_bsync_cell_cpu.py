"""The joiner's blocksync cell without a chip, on the CPU: the generator is a
pure function of the seed whose encodings and hashes are the program's; every
tick has the verdict its fault says, and the plain reference agrees; through
the harness, with the device stubbed by the host oracle (the scheduler on,
the window on the served path), a sound program comes out correct and a
planted fault does not, in the checks or in the block store; each control of ``bsync_control.py`` is not correct
and ``none`` is; the six readers read their spans."""

import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import bsync_control, bsync_ref, bsyncchain, harness, manifest
from benchmarks import chain as chainlib
from benchmarks import ed25519_ref as ref
from benchmarks.loops.closed import Record

CELL = "rotating-blocksync"
SEED = 2**31 + 97
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
E2E = {"verify_p50_ms", "verify_p95_ms", "sigs_per_s", "setup_s"}
SIX = ["bsync_window_ms", "bsync_wait_ms", "bsync_validate_ms", "bsync_apply_ms",
       "bsync_receive_ms", "bsync_heights_per_flush"]


@pytest.fixture(scope="module")
def pool():
    p = chainlib.SignPool(3)
    yield p
    p.close()


def files(heights: int = 200):
    cell = manifest.Cell(manifest.load(), CELL)
    return cell, cell.config, dict(cell.traffic, sync_heights=heights)


def bsync_cell(monkeypatch, heights=200):
    cell, cell.config, cell.traffic = files(heights)
    monkeypatch.setattr(bsyncchain, "cell_files", lambda chain_id: (cell.config, cell.traffic))
    return cell


# -- the generator -----------------------------------------------------------------


def test_same_seed_same_bytes():
    _, config, traffic = files(80)
    one = bsyncchain.build(config, traffic, SEED)
    assert bsyncchain.fingerprint(one) == bsyncchain.fingerprint(
        bsyncchain.build(config, traffic, SEED))
    assert bsyncchain.fingerprint(one) != bsyncchain.fingerprint(
        bsyncchain.build(config, traffic, SEED + 1))
    bsyncchain.spot_check(one)


def test_the_faults_are_the_traffic_files():
    _, config, traffic = files(400)
    bc = bsyncchain.build(config, traffic, SEED)
    warm = {b: f[0] for b, f in bc.faults.items() if b <= traffic["sync_warmup_heights"]}
    assert sorted(warm.values()) == ["body", "past", "prefix"]
    pool = {b: f for b, f in bc.faults.items() if b > traffic["sync_warmup_heights"]}
    assert {b for b, f in pool.items() if f[0] != "body"} == {
        b for b in range(traffic["sync_warmup_heights"] + 2, bc.top) if b % 32 == 16}
    assert {b for b, f in pool.items() if f[0] == "body"} == {
        b for b in range(traffic["sync_warmup_heights"] + 2, bc.top) if b % 64 == 40}
    prefix = config["light_prefix_signatures"]
    for b, (kind, index, cls) in pool.items():
        if kind == "prefix":
            assert index < prefix and (b // 32) % 2 == 0
        elif kind == "past":
            assert prefix <= index < config["validators"] and (b // 32) % 2 == 1
        if kind != "body":
            assert cls == traffic["tamper_classes"][(b // 64) % 4]
    # the pool ticks: every height applied once, a rejection before each fault
    applied = [t.height for t in bc.pool if t.expected[0] == "applied"]
    assert applied == list(range(bc.pool[0].height, bc.top))
    assert sum(t.expected[0] == "rejected" for t in bc.pool) == len(pool)
    assert {t.signatures for t in bc.pool if t.expected[0] == "applied"
            and t.height - 1 not in bc.faults and t.height not in bc.faults
            and t.height + 1 not in bc.faults} == {10}


def test_every_tick_has_the_verdict_its_fault_says():
    from benchmarks.entries import blocksync_catchup as entry

    _, config, traffic = files(160)
    chain = SimpleNamespace(seed=5, chain_id=config["chain_id"])
    chain.bsync = bc = bsyncchain.build(config, traffic, 5)
    seen = set()
    for t in bc.warm + bc.pool:
        items = entry.reference_items(chain, t)
        bits = [ref.verify_zip215(*it) for it in items]
        assert entry.reference_verdict(chain, t, bits) == t.expected, t
        seen.add(t.expected[0] if t.expected[0] == "applied" else t.expected[2])
    assert seen == {"applied", "invalid_signature", "invalid_block"}
    # the whole run, a joiner over the copies in the order they come
    assert bsync_control.control_verdicts(bc, "none") == [t.expected for t in bc.warm + bc.pool]


def test_encodings_and_hashes_are_the_programs():
    from cometbft_tpu.types import codec
    from cometbft_tpu.types.params import (
        BlockParams, ConsensusParams, EvidenceParams, FeatureParams, ValidatorParams,
    )

    _, config, traffic = files(40)
    bc = bsyncchain.build(config, traffic, 9)
    for wire in bc.honest[1:8] + [w for _, w in bc.faulty.values()]:
        raw = bsync_ref.decode(wire).raw
        block = codec.decode_block(raw)
        assert codec.encode_block(block) == raw
        mine = bsync_ref.decode(wire)
        assert block.hash() == bsync_ref.header_hash(mine)
        total, parts = bsync_ref.part_set_header(raw)
        assert block.make_part_set().header.total == total
        assert block.make_part_set().header.hash == parts
        assert block.last_commit.hash() == bsync_ref.merkle_root(mine.last_commit.raw_sigs)
    p = bc.consensus
    params = ConsensusParams(
        block=BlockParams(p["block_max_bytes"], p["block_max_gas"]),
        evidence=EvidenceParams(p["evidence_max_age_num_blocks"],
                                p["evidence_max_age_duration_ns"], p["evidence_max_bytes"]),
        validator=ValidatorParams(tuple(p["pub_key_types"])),
        feature=FeatureParams(p["vote_extensions_enable_height"], p["pbts_enable_height"]))
    assert params.hash() == bc.consensus_hash == ConsensusParams().hash()


# -- through the harness, on the served path ----------------------------------------


def _oracle_runner(backend, pubs, msgs, sigs, lanes):
    out = np.zeros(lanes, dtype=bool)
    out[: len(pubs)] = [ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    return out


@pytest.fixture
def served(monkeypatch):
    """A trusted ``tpu`` backend whose device runner is the host oracle: the
    scheduler and the reactor's window run as on a chip."""
    from benchmarks import program
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.ops import sha256_tree, supervisor

    monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "tpu")
    monkeypatch.setattr(program, "warm_verify", lambda largest: {
        "backend": "tpu", "tier": "stub", "buckets": {}})
    supervisor.set_device_runner(_oracle_runner)
    sha256_tree.set_tree_runner(sha256_tree.host_tree_runner)
    cbatch.set_default_backend(None)
    yield
    cbatch.set_default_backend(None)
    supervisor.clear_device_runner()
    sha256_tree.clear_tree_runner()


def run(cell, pool):
    return harness.run_cell(cell, SEED, 60.0, False, time.perf_counter(), DEVICE, pool=pool)


def test_the_cell_is_correct_on_the_served_path(pool, served, monkeypatch):
    from cometbft_tpu.verifysched import stats as sstats

    cell = bsync_cell(monkeypatch)
    sstats.reset()
    res = run(cell, pool)
    assert res["correct"] is True and res["failed"] == 0
    bc = bsyncchain.build(cell.config, cell.traffic, SEED)
    assert res["attempted"] == len(bc.pool)
    assert set(res["metrics"]) == E2E
    assert all(c["value"] == 0 == c["limit"] for c in res["compared"].values())
    # the windows went through the scheduler at bulk priority, several
    # heights a flush
    ss = sstats.snapshot()
    assert 0 < sum(ss["flushes"].values()) < res["attempted"] / 3
    assert ss["segments"]["bulk"] > 0


def test_a_body_check_left_out_of_the_program(pool, served, monkeypatch):
    from cometbft_tpu.types.block import Block

    real = Block.validate_basic

    def without_data_hash(self):
        err = real(self)
        return None if err == "data hash mismatch" else err

    monkeypatch.setattr(Block, "validate_basic", without_data_hash)
    res = run(bsync_cell(monkeypatch), pool)
    assert res["correct"] is False
    assert res["compared"]["warmup_verdicts_wrong"]["value"] >= 1


@pytest.mark.parametrize("fault", ["save_dropped", "seen_commit_of_the_height_before"])
def test_a_store_fault_planted_in_the_program(pool, served, monkeypatch, fault):
    """What the joiner's block store holds is read back: a save left out, or
    a block stored with the wrong seen commit, is not correct."""
    from cometbft_tpu.store.block_store import BlockStore

    real = BlockStore.save_block

    def save_block(self, block, part_set, seen_commit, extended_commit=None):
        if fault == "seen_commit_of_the_height_before":
            real(self, block, part_set, block.last_commit, extended_commit)

    monkeypatch.setattr(BlockStore, "save_block", save_block)
    res = run(bsync_cell(monkeypatch, heights=60), pool)
    assert res["correct"] is False
    assert res["compared"]["warmup_verdicts_wrong"]["value"] >= 1
    assert res["compared"]["window_verdicts_unexpected"]["value"] >= 1
    assert res["compared"]["reference_against_generator"]["value"] == 0


# -- the controls --------------------------------------------------------------------


@pytest.mark.parametrize("control", ["no_body_check", "prefix_only_last_commit"])
@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_the_control_comes_out_not_correct(pool, seed, control):
    cell, cell.config, cell.traffic = files()
    verdict = bsync_control.run_control(cell, seed, 120, control, pool)
    assert verdict["correct"] is False
    assert verdict["compared"]["window_verdicts_unexpected"]["value"] >= 1
    assert verdict["compared"]["sample_verdicts_wrong"]["value"] >= 1
    assert verdict["compared"]["reference_against_generator"]["value"] == 0


def test_the_reference_in_the_programs_place_is_correct(pool):
    cell, cell.config, cell.traffic = files()
    assert bsync_control.run_control(cell, 5, 120, "none", pool)["correct"] is True


# -- the readers ---------------------------------------------------------------------


def test_the_six_readers_read_their_spans():
    from cometbft_tpu.libs import tracing

    tracing.reset_tracer()
    tracer = tracing.get_tracer()
    records = []
    for k in range(1400):  # a tick a height; a window and a flush every 7
        t = 1000.25 + 0.01 * k
        tracer.record_span("blocksync.tick", t, t + 0.005)
        tracer.record_span("blocksync.validate", t, t + 0.001)
        tracer.record_span("blocksync.apply", t + 0.001, t + 0.004)
        tracer.record_span("blocksync.receive", t, t + 0.0004)
        if k % 7 == 0:
            tracer.record_span("blocksync.window", t, t + 0.0007)
            tracer.record_span("blocksync.wait", t, t + 0.0014)
            tracer.record_span("sched.flush", t, t + 0.002)
        records.append(Record(k, t, t + 0.005, ("applied", k, b"", k, (b"", b"")), 10))
    ctx = SimpleNamespace(records=records)
    read = {name: manifest.reader("layers", name).read(ctx) for name in SIX}
    assert read == pytest.approx({
        "bsync_window_ms": 0.1, "bsync_wait_ms": 0.2, "bsync_validate_ms": 1.0,
        "bsync_apply_ms": 3.0, "bsync_receive_ms": 0.4, "bsync_heights_per_flush": 7.0},
        rel=0.01)  # whole seconds: a window and its flush may fall off the ends
    tracing.reset_tracer()
    # a program without the spans (the parent): nothing to read, no raise
    assert {manifest.reader("layers", name).read(SimpleNamespace(records=records))
            for name in SIX} == {None}


def test_the_manifest_lists_the_six_after_the_sequential_four():
    """Appended, as a program PR may only append: pinned by name."""
    m = manifest.load()
    cells = [w["name"] for w in m["workloads"]]
    assert cells.index(CELL) == cells.index("light1k-sequential") + 1
    cell = manifest.Cell(m, CELL)
    assert cell.chips == 1 and cell.config_name == "qa-rotating-val10"
    assert cell.entry.NAME == "blocksync_catchup" and cell.traffic["loop"] == "closed"
    assert cell.config["reduced"] == [] and cell.config["validators"] == 10
    names = [x["name"] for x in m["per_layer"]]
    at = names.index(SIX[0])
    assert at == names.index("seq_headers_per_flush") + 1
    assert names[at:at + 6] == SIX
    for x in m["per_layer"][at:at + 6]:
        assert x["workloads"] == [CELL] and x["source"] == "program_span"
    reported = {x["name"] for x in cell.per_layer()}
    assert {"flushes_per_request", "lane_occupancy_pct", "device_idle_pct", "verify_mfu",
            "dispatch_wall_ms", "compiles_in_window", "sigs_per_request"} <= reported
    assert "seq_prep_ms" not in reported and "kernel_us_per_sig" not in reported
