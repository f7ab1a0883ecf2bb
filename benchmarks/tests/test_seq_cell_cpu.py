"""The sequential light client's cell (ISSUE 38) without a chip, at 8 and 16
validators on the CPU: the generator is a pure function of the seed whose
hashes and sign-bytes are the program's; every request has the verdict its
class says; through the harness, with the device stubbed by the host oracle
as the program's tier-1 tests stub it (the scheduler on, the served path
taken), a sound program comes out correct and the planted faults do not;
each control of ``seq_control.py`` is not correct and ``none`` is; the four
readers read their spans."""

import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import chain as chainlib
from benchmarks import harness, light_ref, lightchain, manifest, seq_control, seqchain
from benchmarks import ed25519_ref as ref
from benchmarks.loops.closed import Record

CELL = "light1k-sequential"
SEED = 2**31 + 83
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
E2E = {"verify_p50_ms", "verify_p95_ms", "sigs_per_s", "setup_s"}


@pytest.fixture(scope="module")
def pool():
    p = chainlib.SignPool(3)
    yield p
    p.close()


def files(n: int = 16, headers: int = 400):
    """The cell's files at ``n`` validators and a short pool; a fault of
    each kind every 8 requests, so that a short pool holds all of them."""
    cell = manifest.Cell(manifest.load(), CELL)
    config = dict(cell.config, validators=n, validator_universe=2 * n)
    traffic = dict(cell.traffic, headers=headers, tamper_every=8, tamper_phase=3,
                   broken_link_every=8, broken_link_phase=6)
    return cell, config, traffic


def seq_cell(monkeypatch, n=16, headers=340):
    cell, cell.config, cell.traffic = files(n, headers)
    monkeypatch.setattr(seqchain, "cell_files", lambda chain_id: (cell.config, cell.traffic))
    return cell


# -- the generator -----------------------------------------------------------------


def test_same_seed_same_bytes_whatever_the_workers(pool):
    _, config, traffic = files(8, 120)
    one = seqchain.build(config, traffic, SEED)  # a pool of its own
    three = seqchain.build(config, traffic, SEED, pool)
    other = seqchain.build(config, traffic, SEED + 1, pool)
    assert seqchain.fingerprint(one) == seqchain.fingerprint(three)
    assert seqchain.fingerprint(one) != seqchain.fingerprint(other)
    seqchain.spot_check(one)


def test_the_cycle_of_lengths_and_the_faults_are_the_traffic_files():
    cell = manifest.Cell(manifest.load(), CELL)
    traffic = cell.traffic
    assert sorted(traffic["lengths"]) == traffic["lengths"] and len(traffic["lengths"]) == 15
    for seed in (1, SEED, 99):
        got = [seqchain.lengths(traffic, seed, k) for k in range(45)]
        for c in range(3):  # each cycle holds each length once
            assert sorted(got[15 * c:15 * c + 15]) == traffic["lengths"]
    kinds = [seqchain.kind_of(traffic, k) for k in range(192)]
    assert [k for k in range(192) if kinds[k] == "tampered"] == list(range(16, 192, 32))
    assert [(k, kinds[k]) for k in range(192) if kinds[k].startswith("broken_")] == [
        (40, "broken_header_hash"), (104, "broken_validators_hash"),
        (168, "broken_next_validators_hash")]
    reqs = seqchain.plan(cell.config, traffic, SEED)
    pool = [r for r in reqs if not isinstance(r.key, tuple)]
    assert 6000 - 64 < pool[-1].target - pool[0].trusted <= 6000
    # the client moves only on what it accepts, and a request walks 2..64
    newest = 1
    for r in reqs:
        assert r.trusted == newest and 2 <= r.target - r.trusted <= 64
        if r.expected == ("accepted",):
            newest = r.target
        else:
            assert r.trusted < r.bad <= r.target
    # a tampered request has a broken link after its bad header where it can
    for r in pool:
        if r.kind == "tampered" and r.bad < r.target:
            (later, link), = r.links.items()
            assert r.bad < later <= min(r.target, r.bad + 7)
    warm = [r for r in reqs if isinstance(r.key, tuple)]
    assert {r.kind for r in warm} == {"honest", "tampered", "broken_link"}
    assert {r.link for r in warm if r.kind == "broken_link"} == set(lightchain.LINKS)
    assert [r.tamper[1] for r in warm if r.tamper] == traffic["tamper_classes"]


def test_every_request_has_the_verdict_its_class_says(pool):
    from benchmarks.entries import light_sequential as entry

    _, config, traffic = files(8, 200)
    chain = SimpleNamespace(seed=5, chain_id=config["chain_id"])
    chain.seq = seq = seqchain.build(config, traffic, 5, pool)
    prefix = lightchain.light_prefix(8)
    kinds = {}
    for req in seq.warm + seq.pool:
        items = entry.reference_items(chain, req)
        bits = [ref.verify_zip215(*it) for it in items]
        assert entry.reference_verdict(chain, req, bits) == req.expected, req
        # the whole run against the plain reference agrees too
        assert seq_control.control_verdict(seq, req, "none") == req.expected, req
        assert len(items) == (prefix * len(req.checked) if req.kind != "broken_link" else 0)
        kinds.setdefault(req.kind, set()).add(req.expected[0])
    assert kinds == {"honest": {"accepted"}, "tampered": {"invalid_signature"},
                     "broken_link": {"invalid_header"}}
    # each triple counted once over the run: every honest header's prefix,
    # and one altered triple a tampered request
    walked = set()
    for r in seq.warm + seq.pool:
        walked.update(range(r.trusted + 1, (r.bad if r.bad else r.target + 1)))
        if r.tamper:
            walked.add(r.bad)
    tampered = sum(1 for r in seq.warm + seq.pool if r.tamper)
    assert sum(r.signatures for r in seq.warm + seq.pool) == prefix * len(walked) + tampered


@pytest.mark.parametrize("n", [8, 64])
def test_hashes_and_sign_bytes_are_the_programs(pool, n):
    from benchmarks.entries import light_sequential as entry

    _, config, traffic = files(n, 40)
    chain = SimpleNamespace(seed=77, chain_id=config["chain_id"])
    chain.seq = seq = seqchain.build(config, traffic, 77, pool)
    state = entry.State(chain)
    for req in seq.warm + seq.pool:
        target, _, served = state.requests[req.key]
        assert target == req.target and sorted(served) == list(
            range(req.trusted + 1, req.target + 1))
        for h, lb in served.items():
            b = seq.blocks[req.served(h)]
            assert lb.signed_header.header.hash() == light_ref.header_hash(b.header)
            assert lb.validator_set.hash() == light_ref.validators_hash(
                seq.light_block(req.served(h)).validators)
            link = req.links.get(h, "")
            assert (lb.validate_basic(seq.chain_id) is None) == (
                link not in ("header_hash", "validators_hash"))
            for i in (0, lightchain.light_prefix(n) - 1):
                assert lb.signed_header.commit.vote_sign_bytes(seq.chain_id, i) == \
                    light_ref.vote_sign_bytes(seq.chain_id, b.commit, i)


# -- through the harness, on the served path ----------------------------------------


def _oracle_runner(backend, pubs, msgs, sigs, lanes):
    out = np.zeros(lanes, dtype=bool)
    out[: len(pubs)] = [ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    return out


@pytest.fixture
def served(monkeypatch):
    """A trusted ``tpu`` backend whose device runner is the host oracle
    (``tests/test_light_reference.py``'s stub): the scheduler and
    ``verify_adjacent_chain``'s served path run as on a chip."""
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

    cell = seq_cell(monkeypatch)
    sstats.reset()
    res = run(cell, pool)
    assert res["correct"] is True and res["failed"] == 0
    planned = seqchain.plan(cell.config, cell.traffic, SEED)
    assert res["attempted"] == sum(1 for r in planned if not isinstance(r.key, tuple))
    assert set(res["metrics"]) == E2E
    assert all(c["value"] == 0 == c["limit"] for c in res["compared"].values())
    # the headers went through the scheduler at light priority
    assert sstats.snapshot()["segments"]["evidence_light"] > res["attempted"]


def test_the_next_validators_link_left_out_of_the_program(pool, served, monkeypatch):
    from cometbft_tpu.light import verifier

    def without_link(chain_id, trusted, new, period, now, drift):
        """``_check_adjacent_headers`` less its last compare."""
        if new.height != trusted.height + 1:
            raise verifier.ErrInvalidHeader("headers must be adjacent")
        if verifier.header_expired(trusted.signed_header.header.time, period, now):
            raise verifier.ErrOldHeaderExpired("trusted header expired")
        verifier._validate_new_block(chain_id, trusted, new, now, drift)

    monkeypatch.setattr(verifier, "_check_adjacent_headers", without_link)
    res = run(seq_cell(monkeypatch), pool)
    assert res["correct"] is False
    assert res["compared"]["window_verdicts_unexpected"]["value"] >= 1
    assert res["compared"]["warmup_verdicts_wrong"]["value"] >= 1


def test_a_later_structural_error_reported_first(pool, served, monkeypatch):
    """The parent's order: every header's checks before any verdict."""
    from cometbft_tpu.light import verifier

    real = verifier.verify_adjacent_chain

    def checks_first(chain_id, trusted, news, period, now, drift=10.0):
        current = trusted
        for lb in news:
            try:
                verifier._check_adjacent_headers(chain_id, current, lb, period, now, drift)
            except verifier.VerificationError as e:
                raise verifier.ErrVerificationFailed(current.height, lb.height, e)
            current = lb
        return real(chain_id, trusted, news, period, now, drift)

    monkeypatch.setattr(verifier, "verify_adjacent_chain", checks_first)
    res = run(seq_cell(monkeypatch), pool)
    assert res["correct"] is False
    assert res["compared"]["window_verdicts_unexpected"]["value"] >= 1
    assert res["compared"]["sample_verdicts_wrong"]["value"] >= 1


# -- the controls --------------------------------------------------------------------


@pytest.mark.parametrize("control", seq_control.CONTROLS)
@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_the_control_comes_out_not_correct(pool, seed, control):
    cell, cell.config, cell.traffic = files(8)
    verdict = seq_control.run_control(cell, seed, 24, control, pool)
    assert verdict["correct"] is False
    assert verdict["compared"]["window_verdicts_unexpected"]["value"] >= 1
    assert verdict["compared"]["sample_verdicts_wrong"]["value"] >= 1
    assert verdict["compared"]["reference_against_generator"]["value"] == 0


def test_the_reference_in_the_programs_place_is_correct(pool):
    cell, cell.config, cell.traffic = files(8)
    verdict = seq_control.run_control(cell, 5, 24, "none", pool)
    assert verdict["correct"] is True


# -- the readers ---------------------------------------------------------------------


def test_the_four_readers_read_their_spans():
    from cometbft_tpu.libs import tracing

    tracing.reset_tracer()
    tracer = tracing.get_tracer()
    records = []
    for k in range(100):  # a request: 4 headers, 2 flushes, a load and a save
        t = 1000.25 + 0.1 * k
        tracer.record_span("light.sync", t, t + 0.050)
        tracer.record_span("light.store", t, t + 0.001)
        tracer.record_span("light.store", t + 0.040, t + 0.049)
        for j in range(4):
            tracer.record_span("light.chain.prep", t, t + 0.006)
            tracer.record_span("light.chain.wait", t, t + 0.0005)
        for j in range(2):
            tracer.record_span("sched.flush", t, t + 0.002)
        records.append(Record(k, t, t + 0.050, ("accepted",), 667 * 4))
    ctx = SimpleNamespace(records=records)
    read = {name: manifest.reader("layers", name).read(ctx) for name in (
        "seq_prep_ms", "seq_wait_ms", "seq_store_ms", "seq_headers_per_flush")}
    assert read == pytest.approx({"seq_prep_ms": 6.0, "seq_wait_ms": 0.5,
                                  "seq_store_ms": 10.0, "seq_headers_per_flush": 2.0})
    tracing.reset_tracer()
    # a program without the spans (the parent): nothing to read, no raise
    assert {manifest.reader("layers", name).read(SimpleNamespace(records=records))
            for name in read} == {None}


def test_the_manifest_lists_the_four_after_the_thirteen():
    """Appended, as a program PR may only append: pinned by name, so that
    what later PRs append breaks nothing here."""
    m = manifest.load()
    cells = [w["name"] for w in m["workloads"]]
    assert cells.index(CELL) == cells.index("val10k-commit-stream-x4") + 1
    cell = manifest.Cell(m, CELL)
    assert cell.chips == 1 and cell.config_name == "light1k-ed25519-sequential"
    assert cell.entry.NAME == "light_sequential" and cell.traffic["loop"] == "closed"
    assert cell.config["reduced"] == [] and cell.config["validators"] == 1000
    names = [x["name"] for x in m["per_layer"]]
    at = names.index("seq_prep_ms")
    assert at == names.index("host_cpu_ms") + 1
    assert names[at:at + 4] == ["seq_prep_ms", "seq_wait_ms", "seq_store_ms",
                                "seq_headers_per_flush"]
    for x in m["per_layer"][at:at + 4]:
        assert x["workloads"] == [CELL]
    reported = {x["name"] for x in cell.per_layer()}
    assert {"flushes_per_request", "lane_occupancy_pct", "device_idle_pct",
            "dispatch_wall_ms", "compiles_in_window"} <= reported
    assert "light_checks_ms" not in reported and "kernel_us_per_sig" not in reported
