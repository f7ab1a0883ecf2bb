"""The light client's cell (PR 28) through the harness without a chip, at 16
validators on the CPU: a sound program comes out correct, and the planted
faults a light client's cell can have come out not correct."""

import time

import pytest

from benchmarks import chain as chainlib
from benchmarks import harness, lightchain, manifest

SEED = 2**31 + 79
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
E2E = {"verify_p50_ms", "verify_p95_ms", "sigs_per_s", "setup_s"}


@pytest.fixture(scope="module")
def pool():
    p = chainlib.SignPool(2)
    yield p
    p.close()


def light_cell(monkeypatch, n=16, requests=70):
    cell = manifest.Cell(manifest.load(), "light1k-skipping-sync")
    cell.config = dict(cell.config, validators=n, validator_universe=2 * n,
                       churn_skipping=max(1, n // 10), too_far_kept=n * 3 // 10,
                       jump_heights=[2, 50])
    cell.traffic = dict(cell.traffic, requests=requests)
    monkeypatch.setattr(lightchain, "cell_files", lambda chain_id: (cell.config, cell.traffic))
    return cell


def run(cell, pool):
    return harness.run_cell(cell, SEED, 30.0, False, time.perf_counter(), DEVICE, pool=pool)


def test_the_light_cell_is_correct_and_counts_what_a_request_needed(pool, monkeypatch):
    res = run(light_cell(monkeypatch), pool)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 70
    assert set(res["metrics"]) == E2E
    assert all(c["value"] == 0 == c["limit"] for c in res["compared"].values())


def test_the_trusting_pass_left_out_of_the_program(pool, monkeypatch):
    from cometbft_tpu.types import validation

    monkeypatch.setattr(validation, "verify_commit_light_trusting", lambda *a, **k: None)
    res = run(light_cell(monkeypatch), pool)
    assert res["correct"] is False
    assert res["compared"]["window_verdicts_unexpected"]["value"] >= 3  # every too-far request
    assert res["compared"]["sample_verdicts_wrong"]["value"] >= 3


def test_the_link_checks_left_out_of_the_program(pool, monkeypatch):
    from cometbft_tpu.types.light import LightBlock

    monkeypatch.setattr(LightBlock, "validate_basic", lambda self, chain_id: None)
    res = run(light_cell(monkeypatch), pool)
    assert res["correct"] is False
    # request 40 (the commit signs another header); the warm-up's two as well
    assert res["compared"]["window_verdicts_unexpected"]["value"] == 1
    assert res["compared"]["sample_verdicts_wrong"]["value"] == 1
    assert res["compared"]["warmup_verdicts_wrong"]["value"] == 2
