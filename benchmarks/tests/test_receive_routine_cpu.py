"""The validator's cell (PR 32) through the harness without a chip, at 8
validators on the CPU: the generator is a pure function of the seed, every
class appears with its verdict, a sound program comes out correct, and the
planted faults a receive routine's cell can have come out not correct (or
read ``lastcommit_hit_pct`` under 100)."""

import collections
import time
from types import SimpleNamespace

import pytest

from benchmarks import chain as chainlib
from benchmarks import harness, manifest, program, vote_control, votechain
from benchmarks.entries import consensus_votes

SEED = 2**31 + 83
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
E2E = {"verify_p50_ms", "verify_p95_ms", "sigs_per_s", "setup_s"}
CELL = "val175-receive-routine"


@pytest.fixture(scope="module")
def pool():
    p = chainlib.SignPool(2)
    yield p
    p.close()


def small_cell(monkeypatch, n=8, heights=12):
    """Six of eight vote for the block (60 of 80, the quorum is 54); the
    rules come round more often than at 175, at phases that never meet."""
    cell = manifest.Cell(manifest.load(), CELL)
    cell.config = dict(cell.config, validators=n)
    cell.traffic = dict(
        cell.traffic, heights=heights, absent_per_height=1, nil_per_height=1,
        duplicate_every=8, duplicate_phase=4, altered_every=16, altered_phase=8,
        forged_copy_every=32, forged_copy_phase=16, equivocation_every_heights=2,
        equivocation_phase=1, redelivery_after=[1, 6])
    monkeypatch.setattr(votechain, "cell_files",
                        lambda chain_id: (cell.config, cell.traffic))
    return cell


def run(cell, pool):
    return harness.run_cell(cell, SEED, 30.0, False, time.perf_counter(), DEVICE, pool=pool)


def build(cell, pool, seed=SEED):
    chain = chainlib.build(cell.config, cell.traffic, cell.config_name, seed, pool)
    return chain, votechain.build(chain, cell.traffic, pool)


def test_the_generator_is_a_pure_function_of_the_seed(pool, monkeypatch):
    cell = small_cell(monkeypatch)
    _, votes = build(cell, pool)
    assert votechain.fingerprint(votes) == votechain.fingerprint(build(cell, pool)[1])
    solo = chainlib.SignPool(1)
    try:
        assert votechain.fingerprint(votes) == votechain.fingerprint(build(cell, solo)[1])
    finally:
        solo.close()
    assert votechain.fingerprint(votes) != votechain.fingerprint(build(cell, pool, SEED + 1)[1])
    votechain.spot_check(votes)
    keys = [r.key for r in votes.pool + votes.warm]
    assert len(keys) == len(set(keys))
    assert all(votes.request(r.key) is r for r in votes.pool + votes.warm)


def test_every_class_appears_with_its_verdict_and_in_its_order(pool, monkeypatch):
    cell = small_cell(monkeypatch, heights=16)
    _, votes = build(cell, pool)
    kinds = collections.Counter(r.kind for r in votes.pool)
    assert kinds["last_commit"] == 16 and kinds["equivocation"] == 8
    sound = kinds["honest"] + kinds["equivocation"]
    assert sound == 16 * 14 + 8
    assert (kinds["duplicate"], kinds["altered"], kinds["forged_copy"]) == (
        sound // 8, sound // 16, sound // 32)
    assert {r.cls for r in votes.pool if r.kind == "altered"} == set(votechain.ALTERED_CLASSES)
    for kind, verdict, signatures in (
            ("duplicate", ("duplicate",), 0), ("altered", ("invalid_signature",), 1),
            ("forged_copy", ("nondeterministic_signature",), 0)):
        assert {(r.expected, r.signatures) for r in votes.pool if r.kind == kind} == {
            (verdict, signatures)}
    warm = collections.Counter((r.kind, r.cls) for r in votes.warm)
    assert all(warm[("altered", c)] >= 1 for c in votechain.ALTERED_CLASSES)
    assert all(warm[(k, "")] == 2 for k in ("duplicate", "forged_copy", "equivocation",
                                            "last_commit"))
    for h, of_height in votes.by_height.items():
        votes_of = of_height[:-1]
        assert of_height[-1].kind == "last_commit" and of_height[-1].signatures == 7
        assert [r.key for r in of_height] == [h * votechain.SLOTS + k
                                              for k in range(len(of_height))]
        honest = [r for r in votes_of if r.kind == "honest"]
        assert collections.Counter((r.type, r.block) for r in honest) == {
            (1, votechain.BLOCK): 6, (1, votechain.NIL): 1,
            (2, votechain.BLOCK): 6, (2, votechain.NIL): 1}
        # the majority appears at the sixth vote for the block, by type
        for type_ in (1, 2):
            for_block = [r for r in honest if r.type == type_ and r.block == votechain.BLOCK]
            assert [r.expected for r in for_block] == [("added", False)] * 5 + [("added", True)]
        for r in votes_of:
            mine = [q for q in votes_of
                    if (q.type, q.index) == (r.type, r.index) and q.key < r.key]
            if r.kind in ("duplicate", "forged_copy"):
                # the vote it copies is held by then
                assert any(q.kind == "honest" and q.block == r.block for q in mine)
            elif r.kind == "equivocation":
                assert r.block == votechain.OTHER and r.expected == ("conflicting", r.index)
                assert [q.kind for q in mine if q.block == votechain.BLOCK] == ["honest"]
    # where the altered rule hits an equivocator's second vote, its altered
    # copy arrives first, while the first vote is held
    framed = [r for r in votes.pool if r.kind == "altered" and r.block == votechain.OTHER]
    assert framed and all(r.expected == ("invalid_signature",) for r in framed)


def test_the_cell_is_correct_and_counts_what_a_request_needed(pool, monkeypatch):
    res = run(small_cell(monkeypatch), pool)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 12 * 15
    assert set(res["metrics"]) == E2E
    assert all(c["value"] == 0 == c["limit"] for c in res["compared"].values())


def test_a_duplicate_answered_added(pool, monkeypatch):
    """The set forgets what it holds: a second copy is verified (a cache hit)
    and added again."""
    from cometbft_tpu.types import vote_set

    monkeypatch.setattr(vote_set.VoteSet, "_get_vote", lambda self, idx, key: None)
    res = run(small_cell(monkeypatch), pool)
    assert res["correct"] is False
    assert res["compared"]["window_verdicts_unexpected"]["value"] >= 12
    assert res["compared"]["sample_verdicts_wrong"]["value"] >= 12
    assert res["compared"]["warmup_verdicts_wrong"]["value"] >= 4


def test_a_majority_one_vote_late(pool, monkeypatch):
    from cometbft_tpu.types import validator

    real = validator.ValidatorSet.total_voting_power
    monkeypatch.setattr(validator.ValidatorSet, "total_voting_power",
                        lambda self: real(self) + 10)  # the quorum is 61 of 60
    res = run(small_cell(monkeypatch), pool)
    assert res["correct"] is False
    # every height: the sixth vote of each type, and the LastCommit
    assert res["compared"]["window_verdicts_unexpected"]["value"] >= 12 * 2


def test_a_copy_under_another_signature_answered_duplicate(pool, monkeypatch):
    """Today's parent: a held vote for the same block id answers ``False``
    whatever the signature."""
    from cometbft_tpu.types import vote_set

    real = vote_set.VoteSet._add_vote

    def by_block_id(self, vote, verify):
        try:
            return real(self, vote, verify)
        except vote_set.NonDeterministicSignatureError:
            return False

    monkeypatch.setattr(vote_set.VoteSet, "_add_vote", by_block_id)
    res = run(small_cell(monkeypatch), pool)
    assert res["correct"] is False
    assert res["compared"]["window_verdicts_unexpected"]["value"] >= 3
    assert res["compared"]["sample_verdicts_wrong"]["value"] >= 3


def _counted_run(cell, pool, between=None):
    """A window by hand, for the readers that take counters: (ctx, records)."""
    from cometbft_tpu.crypto import sigcache

    sigcache.reset_cache()
    chain, votes = build(cell, pool)
    chain.votes = votes
    state = consensus_votes.State(chain)
    before = program.counters()
    window = cell.loop.run(consensus_votes, state, chain, votes.pool, 30.0, cell.traffic,
                           between=between)
    ctx = SimpleNamespace(records=window.records, chain=chain,
                          counters=program.delta(before, program.counters()))
    return ctx, window.records


def test_lastcommit_hit_pct_reads_100_and_a_signature_sent_on_reads_less(pool, monkeypatch):
    from cometbft_tpu.crypto import sigcache

    reader = manifest.reader("layers", "lastcommit_hit_pct")
    cell = small_cell(monkeypatch)
    ctx, records = _counted_run(cell, pool)
    assert [r.verdict for r in records if r.signatures == 7] == [("accepted",)] * 12
    assert reader.read(ctx) == 100.0
    # planted: one precommit's verdict is not in the cache when its LastCommit
    # is verified (here: the cache forgets everything once, mid-height)
    seen = []

    def forget_once(now):
        seen.append(now)
        if len(seen) == 25:
            sigcache.get_cache().clear()

    ctx, records = _counted_run(cell, pool, between=forget_once)
    assert all(r.verdict == ctx.chain.votes.request(r.key).expected
               for r in records)  # still correct: only slower
    assert reader.read(ctx) < 100.0
    assert manifest.reader("layers", "lastcommit_hit_pct").read(
        SimpleNamespace(records=records, chain=SimpleNamespace(), counters={})) is None


def test_sigs_per_flush_and_the_span_readers_return_nothing_without_their_source():
    ctx = SimpleNamespace(records=[], counters={"sched_flush_items": 0, "sched_flushes": 0})
    assert manifest.reader("layers", "sigs_per_flush").read(ctx) is None
    ctx.counters = {"sched_flush_items": 12, "sched_flushes": 12}
    assert manifest.reader("layers", "sigs_per_flush").read(ctx) == 1.0
    for name in ("vote_verify_ms", "voteset_self_ms", "lastcommit_ms"):
        assert manifest.reader("layers", name).read(SimpleNamespace(records=[])) is None


def test_the_span_readers_read_their_stages():
    from benchmarks.loops.closed import Record
    from cometbft_tpu.libs import tracing

    tracing.reset_tracer()
    tracer = tracing.get_tracer()
    records = []
    for k in range(100):
        t = 3000.25 + k * 0.1
        tracer.record_span("voteset.add", t, t + 0.0075)
        tracer.record_span("consensus.vote", t, t + 0.0070)
        tracer.record_span("voteset.add", t + 0.01, t + 0.0101)  # a copy: no verify
        tracer.record_span("verify.commit", t + 0.02, t + 0.0215)
        records.append(Record(k, t, t + 0.03, ("added", False), 1))
    ctx = SimpleNamespace(records=records)
    assert manifest.reader("layers", "vote_verify_ms").read(ctx) == pytest.approx(7.0)
    assert manifest.reader("layers", "voteset_self_ms").read(ctx) == pytest.approx(0.3)
    assert manifest.reader("layers", "lastcommit_ms").read(ctx) == pytest.approx(1.5)
    tracing.reset_tracer()


@pytest.mark.parametrize("control, correct", (
    ("duplicates_by_block_id", False), ("no_check_before_conflict", False), ("none", True)))
def test_the_controls_at_8_validators(pool, monkeypatch, control, correct):
    cell = small_cell(monkeypatch, heights=8)
    verdict = vote_control.run_control(cell, SEED, 8, control, pool)
    assert verdict["correct"] is correct
    if not correct:
        assert verdict["compared"]["window_verdicts_unexpected"]["value"] >= 1
        assert verdict["compared"]["sample_verdicts_wrong"]["value"] >= 1
        assert verdict["compared"]["reference_against_generator"]["value"] == 0
