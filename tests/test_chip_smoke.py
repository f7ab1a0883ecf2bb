"""chip_smoke.py's own guarantees, as far as a CPU can show them: it
refuses to run without a TPU, and its health check fails — rather than
passes on the XLA tier — when the Pallas tier does not compile."""

import json

import numpy as np
import pytest

import chip_smoke
from cometbft_tpu.crypto import backend_health
from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import aot_cache, dispatch_stats, warm_stats
from cometbft_tpu.ops import verify as ov


def test_no_tpu_is_a_failure_before_any_verify(capsys, monkeypatch):
    for var in chip_smoke._MUST_BE_UNSET:
        monkeypatch.delenv(var, raising=False)
    d0 = dispatch_stats.dispatch_count()
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "no TPU" in last["error"]
    assert dispatch_stats.dispatch_count() == d0


def test_forced_selection_is_refused(capsys, monkeypatch):
    monkeypatch.setenv("COMETBFT_TPU_VERIFY_IMPL", "xla")
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False and "COMETBFT_TPU_VERIFY_IMPL" in last["error"]


@pytest.fixture()
def clean_counters():
    def reset():
        backend_health.reset()
        dispatch_stats.reset()
        warm_stats.reset()
        ov.reset_executable_memo()
        cbatch.set_default_backend(None)

    reset()
    yield
    reset()


def test_pallas_compile_failure_fails_the_tier_check(
    clean_counters, monkeypatch
):
    """The node's safety net works — the batch is re-verified on the XLA
    tier and the verdicts are right — and that is exactly what the smoke
    must not accept."""
    seed = b"\x07" * 32
    pub, msg = ref.pubkey_from_seed(seed), b"chip-smoke tier check"
    sig = ref.sign(seed, msg)
    bad = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]

    def load_or_compile(jitted, shapes, tag):
        if "pallas" in tag:
            raise RuntimeError("Mosaic failed to compile TPU kernel: forced")
        (packed,) = shapes  # the one-chip executable's one input
        lanes = packed.shape[0] * 32 // 129
        want = np.zeros(lanes, dtype=bool)
        want[0] = True  # stands in for the XLA tier's (right) verdicts
        return (lambda packed: want), {"exec_cache": "miss", "compile_s": 0.0}

    monkeypatch.setattr(aot_cache, "load_or_compile", load_or_compile)
    monkeypatch.setenv("COMETBFT_TPU_VERIFY_IMPL", "pallas")  # as on a TPU
    cbatch.set_default_backend("tpu")
    health = chip_smoke.Health("tpu", {"pallas"}, {128})

    bits = ov.verify_batch([pub, pub], [msg, msg], [sig, bad])
    assert list(bits) == [True, False]
    with pytest.raises(chip_smoke.SmokeFailure) as e:
        health.check()
    said = str(e.value)
    assert "tier 'xla'" in said
    assert "demotions=1" in said
    assert "compile_failures=1" in said
    assert "_AOT_BROKEN" in said and "Mosaic failed" in said
