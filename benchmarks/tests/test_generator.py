"""The generator is a pure function of the seed; tampered heights lie where
the traffic file says; its sign-bytes are the program's."""

import pytest

from benchmarks import canonical, chain as chainlib
from benchmarks import ed25519_ref as ref

CONFIG = {"validators": 9, "voting_power": 10, "chain_id": "bench-test"}
TRAFFIC = {
    "heights": 40, "warmup_heights": 2, "warmup_tampered": 1,
    "tamper_every": 8, "tamper_phase": 4, "time_jitter_ms": 500,
    "tamper_classes": ["noncanonical_s", "flip_s", "wrong_msg", "flip_r"],
}


@pytest.fixture(scope="module")
def pools():
    a, b = chainlib.SignPool(1), chainlib.SignPool(3)
    yield a, b
    a.close()
    b.close()


def _bytes(c):
    return (c.pubs, [(h.height, h.times_ns, h.sigs, h.tamper) for h in c.pool + c.warm])


def test_same_seed_same_bytes_whatever_the_workers(pools):
    one = chainlib.build(CONFIG, TRAFFIC, "x", 2**31 + 7, pools[0])
    three = chainlib.build(CONFIG, TRAFFIC, "x", 2**31 + 7, pools[1])
    assert _bytes(one) == _bytes(three)
    other = chainlib.build(CONFIG, TRAFFIC, "x", 2**31 + 8, pools[1])
    assert _bytes(one) != _bytes(other)


def test_tampered_heights_where_the_file_says(pools):
    c = chainlib.build(CONFIG, TRAFFIC, "x", 11, pools[1])
    assert len(c.pool) == 40 and len(c.warm) == 3
    prefix = c.light_prefix()
    assert prefix == 7  # 2/3 of 9 equal powers, and one more
    tampered = {h.height: h.tamper for h in c.pool if h.tamper}
    assert sorted(tampered) == [4, 12, 20, 28, 36]
    assert [tampered[h][1] for h in sorted(tampered)] == [
        "noncanonical_s", "flip_s", "wrong_msg", "flip_r", "noncanonical_s"]
    for h in c.pool:
        bits = [ref.verify_zip215(c.pubs[i], c.sign_bytes(h, i), h.sigs[i])
                for i in range(len(c.pubs))]
        bad = [i for i, ok in enumerate(bits) if not ok]
        assert bad == ([h.tamper[0]] if h.tamper else [])
        assert not h.tamper or h.tamper[0] < prefix
    assert [h.tamper is not None for h in c.warm] == [False, False, True]


def test_pool_size_by_config_or_by_signatures():
    t = {"heights": {"a": 5}, "pool_signatures": 1000}
    assert chainlib.pool_size(t, "a", 10) == 5
    assert chainlib.pool_size(t, "b", 10) == 100
    assert chainlib.pool_size({"heights": 7}, "b", 10) == 7


@pytest.mark.parametrize("ts_ns", [1_700_000_001_000_000_123, 1_700_000_002 * 10**9])
def test_sign_bytes_are_the_programs(ts_ns):
    from cometbft_tpu.types.basic import BlockID, PartSetHeader, Timestamp
    from cometbft_tpu.types.canonical import canonical_vote_sign_bytes

    bh, ph = bytes(range(32)), bytes(range(32, 64))
    mine = canonical.sign_bytes(
        canonical.vote_head(12345, 0, bh, 1, ph), ts_ns, canonical.vote_tail("c-1"))
    theirs = canonical_vote_sign_bytes(
        "c-1", 2, 12345, 0, BlockID(bh, PartSetHeader(1, ph)), Timestamp.from_ns(ts_ns))
    assert mine == theirs


def test_set_order_is_cometbfts():
    pubs = [bytes([i]) * 32 for i in range(6)]
    order = chainlib.set_order(pubs, [1, 5, 5, 1, 9, 1])
    assert order[0] == 4 and set(order[1:3]) == {1, 2}
