"""The copied reference agrees with the program's on the 18 vectors."""

from benchmarks import ed25519_ref as mine
from benchmarks.vectors import vectors


def test_copied_reference_agrees_with_the_programs_on_the_vectors():
    from cometbft_tpu.crypto import ed25519_ref as theirs

    pubs, msgs, sigs, expect, labels = vectors()
    assert len(labels) == 18
    for p, m, s, want, label in zip(pubs, msgs, sigs, expect, labels):
        if len(p) != 32 or len(s) != 64:
            continue
        assert mine.verify_zip215(p, m, s) == theirs.verify_zip215(p, m, s) == want, label


def test_vectors_are_the_programs():
    from scripts.chip_validate import _vectors

    assert vectors() == _vectors()
