"""The signature cache in the sidecar (``sigcache_keys`` and the native LRU
behind ``crypto/sigcache``) against the Python it replaces: the keys byte
for byte ``_key``'s with both SHA-256 block functions, the store against
the ``OrderedDict`` store over seeded get / put sequences, the store under
threads, the ``COMETBFT_TPU_NO_NATIVE`` fallback, ``writeback``'s holes,
and the ``path`` each part of the seam reports."""

import ctypes
import hashlib
import random
import sys
import threading

import numpy as np
import pytest

from cometbft_tpu import native
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.libs import tracing

BLOCK_FNS = {"scalar": 0, "sha_ni": 1}  # the sidecar's ``ni`` argument


@pytest.fixture(scope="module")
def nlib():
    lib = native.lib()
    if lib is None or not hasattr(lib, "sigcache_new"):
        pytest.skip("native library unavailable")
    return lib


@pytest.fixture(autouse=True)
def fresh_cache():
    sigcache.reset_cache()
    yield
    sigcache.reset_cache()


def _triples(seed: int, n: int, pub_sizes, sig_sizes):
    rng = random.Random(seed)
    pubs = [rng.randbytes(rng.choice(pub_sizes)) for _ in range(n)]
    msgs = [rng.randbytes(rng.randint(0, 300)) for _ in range(n)]
    sigs = [rng.randbytes(rng.choice(sig_sizes)) for _ in range(n)]
    return pubs, msgs, sigs


def _native_keys(lib, pubs, msgs, sigs, ni, fixed):
    """The sidecar's keys with the block function named; ``fixed``: pass no
    pub / sig lengths (every one 32 / 64), else each one's."""
    n = len(pubs)
    lens = [
        np.array([len(x) for x in xs], np.int64) for xs in (pubs, msgs, sigs)
    ]
    out = ctypes.create_string_buffer(32 * n)
    rc = lib.sigcache_keys(
        b"".join(pubs), None if fixed else lens[0].ctypes.data, 32,
        b"".join(msgs), lens[1].ctypes.data,
        b"".join(sigs), None if fixed else lens[2].ctypes.data, 64,
        n, out, ni,
    )
    if rc == -2:
        pytest.skip("this CPU has no SHA extensions")
    assert rc == 0
    return [out.raw[32 * i:32 * i + 32] for i in range(n)]


# (pub sizes, sig sizes, whether the lengths go unlisted)
_SHAPES = {
    "ed25519": ((32,), (64,), True),
    "ed25519-listed": ((32,), (64,), False),
    "secp256k1": ((33,), (64,), False),
    "mixed-odd": ((32, 33), (64, 1, 63, 65, 97), False),
}


@pytest.mark.parametrize("shape", sorted(_SHAPES))
@pytest.mark.parametrize("block", sorted(BLOCK_FNS))
def test_native_keys_are_key(nlib, block, shape):
    """Every digest is ``_key``'s, messages 0-300 bytes (every padding
    edge), with each block function forced."""
    pub_sizes, sig_sizes, fixed = _SHAPES[shape]
    for seed, n in ((1, 1), (2, 7), (3, 301)):
        pubs, msgs, sigs = _triples(seed, n, pub_sizes, sig_sizes)
        want = [sigcache._key(*t) for t in zip(pubs, msgs, sigs)]
        got = _native_keys(nlib, pubs, msgs, sigs, BLOCK_FNS[block], fixed)
        assert got == want


@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_hash_keys_at_every_size_is_key(nlib, shape):
    """Through the cache, either side of ``NATIVE_KEYS_MIN``: the same keys
    whichever path hashed them, and the path says which."""
    pub_sizes, sig_sizes, _ = _SHAPES[shape]
    cache = sigcache.get_cache()
    cut = sigcache.NATIVE_KEYS_MIN
    for n in (0, 1, cut - 1, cut, 200):
        pubs, msgs, sigs = _triples(n, n, pub_sizes, sig_sizes)
        want = [sigcache._key(*t) for t in zip(pubs, msgs, sigs)]
        assert cache.hash_keys(pubs, msgs, sigs) == want
        digests, path = cache._digests(pubs, msgs, sigs)
        assert digests == b"".join(want)
        assert path == ("native" if n >= cut else "python")


def _run_sequence(store, seed: int, capacity: int):
    """Seeded batches of gets and puts over about three times the capacity
    in keys; what each call answered, and the counts, size and entries in
    order after each."""
    rng = random.Random(seed)
    keys = [
        hashlib.sha256(b"k%d" % i).digest() for i in range(3 * capacity + 2)
    ]
    seen = []
    for _ in range(300):
        batch = [rng.choice(keys) for _ in range(rng.randint(0, 9))]
        if rng.random() < 0.5:
            oks = bytes(rng.randint(0, 1) for _ in batch)
            seen.append(store.put_many(b"".join(batch), oks, len(batch)))
        else:
            seen.append(store.get_many(b"".join(batch), len(batch)))
        seen.append((store.counts(), len(store), store.items()))
    return seen


@pytest.mark.parametrize("capacity", [1, 7, 64])
@pytest.mark.parametrize("seed", [0, 1])
def test_native_store_is_the_dict_store(nlib, capacity, seed):
    """The same verdicts, hits, misses, puts, ``len`` and survivors in LRU
    order, call after call; and after ``clear``."""
    ours = sigcache._NativeStore(nlib, nlib.sigcache_new(capacity))
    oracle = sigcache._DictStore(capacity)
    assert _run_sequence(ours, seed, capacity) == _run_sequence(
        oracle, seed, capacity
    )
    ours.clear()
    assert ours.counts() == (0, 0, 0, 0) and ours.items() == []


def test_native_store_refuses_what_it_cannot_hold(nlib):
    assert not nlib.sigcache_new(0)
    assert not nlib.sigcache_new(1 << 29)
    store = sigcache._NativeStore(nlib, nlib.sigcache_new(4))
    with pytest.raises(ValueError):
        store.get_many(b"\x00" * 31, 1)  # a key is 32 bytes
    with pytest.raises(ValueError):
        store.put_many(b"\x00" * 64, b"\x01", 2)  # one verdict a key


def test_threads_lose_no_count_and_tear_no_verdict(nlib):
    """Six threads putting and getting at once on one store at capacity:
    every verdict read is the one its key was always given, and every get
    and put is counted."""
    cache = sigcache.SigCache(64)
    assert cache.store == "native"
    keys = [hashlib.sha256(b"t%d" % i).digest() for i in range(200)]
    verdict = {k: k[0] & 1 == 1 for k in keys}
    gets = [0] * 6
    puts = [0] * 6
    wrong = []

    def worker(t):
        rng = random.Random(t)
        for _ in range(400):
            batch = [rng.choice(keys) for _ in range(rng.randint(1, 24))]
            if rng.random() < 0.5:
                cache._put_many(batch, [verdict[k] for k in batch])
                puts[t] += len(batch)
            else:
                got = cache._get_many(batch)
                gets[t] += len(batch)
                wrong.extend(
                    k for k, v in zip(batch, got)
                    if v is not None and v != verdict[k]
                )

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(6)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert not wrong
    st = cache.stats()
    assert st["hits"] + st["misses"] == sum(gets)
    assert st["puts"] == sum(puts)
    assert st["size"] == len(cache) <= 64


def _seam_run(n: int):
    """Two passes of ``partition_misses`` / ``writeback`` over the same
    triples (the second finds the first's verdicts), a third after part
    of the cache was overwritten; everything each returned, and the stats
    without the store's name."""
    pubs, msgs, sigs = _triples(5, n, (32,), (64,))
    pubs[3] = pubs[3][:31]  # structurally impossible: no key
    out = []
    for verdicts in ([i % 3 != 0 for i in range(n - 1)], None):
        part = sigcache.partition_misses(pubs, msgs, sigs)
        if verdicts is not None:
            sigcache.writeback(part, verdicts)
        out.append((part.bits, part.miss, part.keys, part.hashed))
    cache = sigcache.get_cache()
    cache._put_many(cache.hash_keys(pubs[:5], msgs[:5], sigs[:5]), [True] * 5)
    out.append(sigcache.partition_misses(pubs, msgs, sigs).bits)
    st = cache.stats()
    out.append({k: v for k, v in st.items() if k != "store"})
    return out, st["store"]


@pytest.mark.parametrize("n", [6, 300])
def test_no_native_gives_the_python_store_with_the_same_answers(
    nlib, monkeypatch, n
):
    want, store = _seam_run(n)
    assert store == "native"
    sigcache.reset_cache()
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setenv("COMETBFT_TPU_NO_NATIVE", "1")
    got, store = _seam_run(n)
    assert store == "python" and sigcache.get_cache()._lib is None
    assert got == want


@pytest.mark.parametrize("store", ["native", "python"])
def test_writeback_stores_no_hole(nlib, monkeypatch, store):
    """A ``None`` verdict is stored nowhere, in either store, at either
    size of segment; the judged ones are, once each."""
    if store == "python":
        monkeypatch.setattr(sigcache, "_sidecar", lambda: None)
    for n in (3, 40):
        sigcache.reset_cache()
        pubs, msgs, sigs = _triples(n, n, (32,), (64,))
        part = sigcache.partition_misses(pubs, msgs, sigs)
        holes = set(range(0, n, 3))
        sigcache.writeback(
            part, [None if i in holes else i % 2 == 0 for i in range(n)]
        )
        cache = sigcache.get_cache()
        assert cache.store == store
        st = cache.stats()
        assert st["puts"] == st["size"] == n - len(holes)
        held = cache._entries
        for i, k in enumerate(part.keys):
            assert held.get(k) == (None if i in holes else i % 2 == 0)
        again = sigcache.partition_misses(pubs, msgs, sigs)
        assert again.miss == sorted(holes)


@pytest.mark.parametrize("n", [1, 50])
def test_each_part_of_the_seam_says_where_it_ran(nlib, n):
    """``path`` on ``batch.keys`` (native from ``NATIVE_KEYS_MIN`` up),
    ``batch.lookup`` and ``batch.writeback`` (the store's)."""
    tracing.reset_tracer()
    tr = tracing.get_tracer()
    pubs, msgs, sigs = _triples(9, n, (32,), (64,))
    part = sigcache.partition_misses(pubs, msgs, sigs)
    sigcache.writeback(part, [True] * n)
    paths = {s["stage"]: s["attrs"]["path"] for s in tr.tail(100)}
    keys = "native" if n >= sigcache.NATIVE_KEYS_MIN else "python"
    assert paths == {
        "batch.keys": keys,
        "batch.lookup": "native",
        "batch.writeback": "native",
    }
    assert sigcache.get_cache().stats()["store"] == "native"
    tracing.reset_tracer()
