"""``libs/histo.Histo``: a weighted observation is n single ones."""

import pytest

from cometbft_tpu.libs.histo import LATENCY_BUCKETS_S, Histo


@pytest.mark.parametrize("n", [1, 7, 6827])
@pytest.mark.parametrize(
    "v",
    [0.003, 0.005, 50.0],
    ids=["inside-a-bucket", "on-a-bound", "overflow"],
)
def test_observe_n_equals_n_observations(v, n):
    one, many = Histo(), Histo()
    for h in (one, many):
        h.observe(0.0004)  # something already there, in another bucket
        h.observe(0.2, 3)
    one.observe(v, n)
    for _ in range(n):
        many.observe(v)
    assert one.n == many.n == n + 4
    assert one.counts == many.counts
    assert one.sum == pytest.approx(many.sum, rel=1e-9)
    for q in (0.01, 0.5, 0.99, 1.0):
        assert one.quantile(q) == many.quantile(q)
    got, want = one.to_dict(), many.to_dict()
    assert got.pop("sum") == pytest.approx(want.pop("sum"), rel=1e-9)
    assert got == want


def test_observe_lands_in_the_bucket_of_its_bound():
    h = Histo()
    h.observe(LATENCY_BUCKETS_S[3], 5)  # v <= bound: the bound's own bucket
    h.observe(LATENCY_BUCKETS_S[-1] * 2, 2)  # beyond the last: overflow
    assert h.counts[3] == 5 and h.counts[-1] == 2 and h.n == 7
    assert h.quantile(0.5) == LATENCY_BUCKETS_S[3]
    assert h.quantile(1.0) == LATENCY_BUCKETS_S[-1]
