"""The rest of a run without the look for a chip, at 8 validators on the CPU:
a sound program comes out correct; the same run with the timed path broken
underneath, or with a control in the program's place, comes out not correct.

Faults a commit-stream cell can have (one chip, no state carried from step to
step, so "state unchanged" and "exchange between chips left out" do not
apply): half of the batch left out; an answer altered where it is produced.
"""

import time

import pytest

from benchmarks import chain as chainlib
from benchmarks import control, harness, manifest

SEED = 2**31 + 77
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def pool():
    p = chainlib.SignPool(2)
    yield p
    p.close()


def small_cell():
    cell = manifest.Cell(manifest.load(), "val175-commit-stream")
    cell.config = dict(cell.config, validators=8)
    cell.traffic = dict(cell.traffic, heights=48, tamper_every=4, tamper_phase=2,
                        warmup_heights=1, warmup_tampered=1)
    return cell


def run(pool, seconds=30.0):
    return harness.run_cell(small_cell(), SEED, seconds, False,
                            time.perf_counter(), DEVICE, pool=pool)


def test_sound_run_is_correct_and_the_line_is_whole(pool, capsys):
    res = run(pool)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 48  # the pool was drained before the time was
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"verify_p50_ms", "verify_p95_ms", "sigs_per_s",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert all(c["limit"] == 0 and c["value"] == 0 for c in res["compared"].values())
    harness.emit(res)
    out, err = capsys.readouterr()
    assert out.strip().splitlines()[-1].startswith('{"correct": true')
    assert err.strip().splitlines()[-1] == "correct: True"
    assert "compared sample_verdicts_wrong: value 0 limit 0" in err


def test_half_of_the_batch_left_out(pool, monkeypatch):
    from cometbft_tpu.types import validation

    real = validation._collect_entries

    def half(*args, **kw):
        entries, tallied = real(*args, **kw)
        return entries[: len(entries) // 2], tallied

    monkeypatch.setattr(validation, "_collect_entries", half)
    res = run(pool)
    assert res["correct"] is False
    assert res["compared"]["window_verdicts_unexpected"]["value"] > 0
    assert res["compared"]["sample_verdicts_wrong"]["value"] > 0


def test_an_answer_altered_where_it_is_produced(pool, monkeypatch):
    from cometbft_tpu.types import validation

    real = validation._judge_entries

    def off_by_one(entries, bits):
        try:
            real(entries, bits)
        except validation.InvalidSignatureError as e:
            raise validation.InvalidSignatureError(e.index + 1)

    monkeypatch.setattr(validation, "_judge_entries", off_by_one)
    res = run(pool)
    assert res["correct"] is False and res["failed"] > 0


def test_a_verifier_that_accepts_everything(pool, monkeypatch):
    from cometbft_tpu.types import validation

    monkeypatch.setattr(
        validation.cbatch, "create_batch_verifier",
        lambda *a, **k: type("Yes", (), {
            "add": lambda self, *x: None,
            "verify": lambda self: (True, []),
        })(),
    )
    res = run(pool)
    assert res["correct"] is False


@pytest.mark.parametrize("which", control.CONTROLS)
@pytest.mark.parametrize("seed", [1, 2**31 + 3, 99])
def test_the_control_comes_out_not_correct(pool, which, seed):
    cell = small_cell()
    cell.traffic = dict(cell.traffic, tamper_every=32, tamper_phase=16)
    verdict = control.run_control(cell, seed, 40, which, pool)
    assert verdict["correct"] is False
    assert verdict["compared"]["sample_verdicts_wrong"]["value"] >= 1
    assert verdict["compared"]["reference_against_generator"]["value"] == 0


def test_the_reference_in_the_programs_place_is_correct(pool):
    """The same path as the controls with no guarantee broken."""
    cell = small_cell()
    verdict = control.run_control(cell, 5, 40, "none", pool)
    assert verdict["correct"] is True
