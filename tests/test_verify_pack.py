"""ISSUE 35: the host pack as one native pass (``ops/verify.pack_batch`` →
the sidecar's ``ed25519_pack_into``).  Every case is differential: the wide
mod-L reduction against ``int`` arithmetic, the one-step-padded and the
four-lane SHA-512 against ``hashlib``, ``prepare_batch`` native against its
Python fallback byte for byte."""

import ctypes
import hashlib
import random

import numpy as np
import pytest

from cometbft_tpu import native
from cometbft_tpu.ops import verify as ov

L = 2**252 + 27742317777372353535851937790883648493
TOP = 2**512
ARRAYS = ("a_bytes", "r_bytes", "s_bytes", "m_bytes", "s_ok")


@pytest.fixture(scope="module")
def nlib():
    lib = native.lib()
    if lib is None or not hasattr(lib, "ed25519_pack_into"):
        pytest.skip("native library unavailable")
    return lib


# -- h mod L -----------------------------------------------------------------


def _mod_l(nlib, x: int) -> int:
    out = ctypes.create_string_buffer(32)
    nlib.ed25519_mod_l(x.to_bytes(64, "little"), out)
    return int.from_bytes(out.raw, "little")


EDGE_DIGESTS = {
    "zero": 0,
    "one": 1,
    "L-1": L - 1,
    "L": L,
    "L+1": L + 1,
    "2L-1": 2 * L - 1,
    "top_multiple_of_L": (TOP // L) * L,
    "top_multiple_of_L-1": (TOP // L) * L - 1,
    "top_multiple_of_L+1": (TOP // L) * L + 1,
    "next_to_top_multiple_of_L": (TOP // L - 1) * L,
    "2^512-1": TOP - 1,
    # the fold cuts at bit 252: hi and lo all ones, each alone and together
    "lo_all_ones": 2**252 - 1,
    "lo_zero_hi_one": 2**252,
    "hi_all_ones": TOP - 2**252,
    "hi_all_ones_lo_one": TOP - 2**252 + 1,
    # the second and third folds' own cuts (the products hi * c)
    "second_fold_all_ones": (2**133 - 1) << 252,
    "third_fold_all_ones": (2**6 - 1) << 504,
    "limb_edges": sum((2**64 - 1) << (128 * i) for i in range(4)),
}


@pytest.mark.parametrize("name", list(EDGE_DIGESTS))
def test_mod_l_edge_digest(nlib, name):
    x = EDGE_DIGESTS[name]
    assert _mod_l(nlib, x) == x % L


def test_mod_l_ten_thousand_random_digests(nlib):
    rng = random.Random(35)
    for _ in range(10_000):
        x = rng.getrandbits(rng.choice((512, 512, 512, 260, 253, 130)))
        assert _mod_l(nlib, x) == x % L, hex(x)


# -- SHA-512, scalar and four lanes -------------------------------------------


@pytest.mark.parametrize(
    "length", [0, 1, 111, 112, 113, 127, 128, 129, 239, 240, 255, 256, 257, 5000]
)
def test_sha512_one_step_padding_at_every_block_edge(nlib, length):
    msg = random.Random(length).randbytes(length)
    out = ctypes.create_string_buffer(64)
    nlib.sha512(msg, len(msg), out)
    assert out.raw == hashlib.sha512(msg).digest()


def _triples(rng, lengths, bad_s_every=7):
    pubs = [rng.randbytes(32) for _ in lengths]
    msgs = [rng.randbytes(n) for n in lengths]
    sigs = []
    for i in range(len(lengths)):
        s = rng.randrange(0, L)
        if bad_s_every and i % bad_s_every == 3:
            s = L + rng.randrange(0, 2**120)  # not canonical: s_ok clears
        sigs.append(rng.randbytes(32) + s.to_bytes(32, "little"))
    return pubs, msgs, sigs


def _pack_lanes(nlib, pubs, msgs, sigs, lanes, idx=None, rows=None):
    """The sidecar's pack with the block function named (0: its choice)."""
    n = len(pubs)
    rows = max(n, 1) if rows is None else rows
    bufs = (*np.zeros((4, rows, 32), np.uint8), np.zeros((rows,), bool))
    lens = np.fromiter(map(len, msgs), np.int64, n)
    idx_arr = None if idx is None else np.asarray(idx, np.int64)
    args = (
        b"".join(pubs), b"".join(sigs), b"".join(msgs),
        lens.ctypes.data if n else None, n,
        None if idx_arr is None else idx_arr.ctypes.data, rows,
        *(b.ctypes.data for b in bufs),
    )
    if lanes == 0:
        rc = nlib.ed25519_pack_into(*args)
    else:
        rc = nlib.ed25519_pack_into_lanes(*args, lanes)
    if rc == -2:
        pytest.skip("this CPU has no four-lane block function")
    return rc, bufs


def _oracle(pubs, msgs, sigs, rows=None, idx=None):
    """hashlib and int arithmetic, nothing of the code under test."""
    n = len(pubs)
    rows = max(n, 1) if rows is None else rows
    bufs = (*np.zeros((4, rows, 32), np.uint8), np.zeros((rows,), bool))
    a, r, s_out, m_out, ok = bufs
    for i in range(n):
        row = i if idx is None else idx[i]
        s = int.from_bytes(sigs[i][32:], "little")
        h = int.from_bytes(
            hashlib.sha512(sigs[i][:32] + pubs[i] + msgs[i]).digest(), "little"
        ) % L
        a[row] = np.frombuffer(pubs[i], np.uint8)
        r[row] = np.frombuffer(sigs[i][:32], np.uint8)
        ok[row] = s < L
        if s < L:
            s_out[row] = np.frombuffer(sigs[i][32:], np.uint8)
        m_out[row] = np.frombuffer(((L - h) % L).to_bytes(32, "little"), np.uint8)
    return bufs


def _same(got, want):
    for name, g, w in zip(ARRAYS, got, want):
        assert np.array_equal(g, w), name


LANES = pytest.mark.parametrize(
    "lanes", [1, 4, 0], ids=["scalar", "four_lane", "chosen"]
)


@LANES
def test_every_message_length_0_to_300(nlib, lanes):
    """Six signatures a length (a group of four and a tail of two), so the
    block-count boundaries at 47/48 and 175/176 bytes of message are
    crossed on the four-lane and on the scalar path."""
    rng = random.Random(300)
    for length in range(301):
        t = _triples(rng, [length] * 6)
        rc, got = _pack_lanes(nlib, *t, lanes)
        assert rc == 0
        _same(got, _oracle(*t))


GROUP_SHAPES = {
    "one_long_closes_a_group": [47, 47, 47, 48, 47, 47, 47, 47],
    "one_long_opens_a_group": [48, 47, 47, 47, 47],
    "alternating_one_and_two_blocks": [47, 48] * 6,
    "two_and_three_blocks": [175, 175, 176, 176, 175, 175, 175, 175, 176],
    "one_two_three_blocks": [0, 100, 200, 300, 0, 100, 200, 300],
    "a_group_then_tail_of_1": [122] * 5,
    "a_group_then_tail_of_2": [122] * 6,
    "a_group_then_tail_of_3": [122] * 7,
    "whole_blocks_lie_in_the_message": [64 + 128 * 3] * 4 + [64 + 128 * 2 + 1],
    "sign_bytes_of_a_chain": [110 + i % 16 for i in range(64)],
}


@LANES
@pytest.mark.parametrize("shape", list(GROUP_SHAPES))
def test_group_shapes(nlib, shape, lanes):
    t = _triples(random.Random(len(shape)), GROUP_SHAPES[shape])
    rc, got = _pack_lanes(nlib, *t, lanes)
    assert rc == 0
    _same(got, _oracle(*t))


@LANES
@pytest.mark.parametrize("n", [0, 1, 3, 4, 5, 117, 6827])
def test_batch_sizes(nlib, n, lanes):
    rng = random.Random(n)
    lengths = [rng.randrange(0, 301) for _ in range(n)]
    if n == 6827:  # the 10,240 commit's: sign-bytes, mostly one block count
        lengths = [rng.randrange(110, 126) for _ in range(n)]
        lengths[1000] = 40
        lengths[4001] = 250
    t = _triples(rng, lengths)
    rc, got = _pack_lanes(nlib, *t, lanes)
    assert rc == 0
    _same(got, _oracle(*t))


def test_legacy_symbol_runs_the_same_internals(nlib):
    t = _triples(random.Random(9), [122] * 9 + [10, 200])
    pubs, msgs, sigs = t
    n = len(pubs)
    off = [0]
    for m in msgs:
        off.append(off[-1] + len(m))
    s_out = ctypes.create_string_buffer(n * 32)
    m_out = ctypes.create_string_buffer(n * 32)
    ok_out = ctypes.create_string_buffer(n)
    rc = nlib.ed25519_pack(
        b"".join(pubs), b"".join(sigs), b"".join(msgs),
        (ctypes.c_int64 * (n + 1))(*off), n, s_out, m_out, ok_out,
    )
    assert rc == 0
    _, _, s_want, m_want, ok_want = _oracle(*t)
    assert s_out.raw == s_want.tobytes() and m_out.raw == m_want.tobytes()
    assert ok_out.raw == ok_want.astype(np.uint8).tobytes()


# -- rows: the index, the bounds ----------------------------------------------


def test_index_scatters_rows_and_leaves_the_rest_alone(nlib):
    t = _triples(random.Random(11), [122] * 9)
    idx = [17, 0, 3, 31, 8, 9, 10, 1, 30]
    rc, got = _pack_lanes(nlib, *t, 0, idx=idx, rows=32)
    assert rc == 0
    _same(got, _oracle(*t, rows=32, idx=idx))
    untouched = sorted(set(range(32)) - set(idx))
    for buf in got:
        assert not buf[untouched].any()


@pytest.mark.parametrize(
    "idx,rows",
    [([0, 1, 2, 8], 8), ([0, -1, 2, 3], 8), (None, 3), ([0, 1, 2, 2**40], 8)],
    ids=["row_at_rows", "negative_row", "more_signatures_than_rows", "far_row"],
)
def test_a_row_outside_the_tables_is_refused_before_any_write(nlib, idx, rows):
    t = _triples(random.Random(12), [122] * 4, bad_s_every=0)
    rc, got = _pack_lanes(nlib, *t, 0, idx=idx, rows=rows)
    assert rc == -1
    for buf in got:
        assert not buf.any()


# -- prepare_batch: native against the fallback --------------------------------


def _signed(n, tag=b"pack"):
    from cometbft_tpu.crypto import ed25519_ref as ref

    pubs, msgs, sigs = [], [], []
    for i in range(n):
        seed = hashlib.sha256(tag + b"%d" % i).digest()
        pubs.append(ref.pubkey_from_seed(seed))
        msgs.append(b"vote-%d-" % i + bytes(100 + i % 16))
        sigs.append(ref.sign(seed, msgs[-1]))
    return pubs, msgs, sigs


def _both_paths(monkeypatch, pubs, msgs, sigs, min_bucket=32):
    got = ov.pack_batch(pubs, msgs, sigs, min_bucket)
    with monkeypatch.context() as m:
        m.setattr(ov, "_native_pack_into", lambda: None)
        want = ov.pack_batch(pubs, msgs, sigs, min_bucket)
    assert want[3]["path"] == "python"
    return got, want


def _assert_byte_for_byte(got, want):
    assert got[1] == want[1]
    assert np.array_equal(got[2], want[2])
    assert got[0].shape == want[0].shape and got[0].dtype == want[0].dtype
    g, w = ov.packed_views(got[0]), ov.packed_views(want[0])
    assert g.keys() == w.keys() == set(ARRAYS)
    for k in ARRAYS:
        assert g[k].dtype == w[k].dtype, k
        assert g[k].shape == w[k].shape, k
        assert np.array_equal(g[k], w[k]), k


@pytest.mark.parametrize("n", [1, 3, 40, 128, 129])
def test_prepare_batch_straight_path(nlib, monkeypatch, n):
    """Every length right: the lists joined as they are, row i for
    signature i; n = 128 and 129 sit either side of a bucket's edge."""
    pubs, msgs, sigs = _signed(n)
    sigs[n // 2] = sigs[n // 2][:32] + (L + 5).to_bytes(32, "little")
    got, want = _both_paths(monkeypatch, pubs, msgs, sigs)
    assert got[3]["path"] == "native"
    _assert_byte_for_byte(got, want)
    arrays, m, structural = ov.packed_views(got[0]), *got[1:3]
    assert m == n and structural[:n].all() and not structural[n:].any()
    assert arrays["s_ok"].shape[0] == ov.bucket_size(n, 32)
    assert not arrays["s_ok"][n // 2] and not arrays["s_bytes"][n // 2].any()
    for k in ARRAYS:  # the padding beyond row n stays zero
        assert not arrays[k][n:].any(), k


def _broken(pubs, msgs, sigs, where, what):
    for i in where:
        if what in ("pub", "both"):
            pubs[i] = pubs[i][:31]
        if what in ("sig", "both"):
            sigs[i] = sigs[i] + b"\x00"
    return pubs, msgs, sigs


@pytest.mark.parametrize("what", ["pub", "sig", "both"])
@pytest.mark.parametrize(
    "where",
    [[0], [39], [0, 39], [7, 8, 20], list(range(40))],
    ids=["first", "last", "first_and_last", "inside", "all"],
)
def test_prepare_batch_index_path(nlib, monkeypatch, where, what):
    """A wrong-length entry occupies no lane and reads structural False;
    every other entry keeps its own row."""
    pubs, msgs, sigs = _broken(*_signed(40), where, what)
    got, want = _both_paths(monkeypatch, pubs, msgs, sigs)
    assert got[3]["path"] == "native"
    _assert_byte_for_byte(got, want)
    arrays, n, structural = ov.packed_views(got[0]), *got[1:3]
    assert n == 40
    assert [i for i in range(40) if not structural[i]] == where
    for k in ARRAYS:
        assert not arrays[k][where].any(), k


def test_prepare_batch_keeps_its_signature_and_return(nlib):
    pubs, msgs, sigs = _signed(5)
    arrays, n, structural = ov.prepare_batch(pubs, msgs, sigs)
    assert n == 5 and structural.shape == (128,) and structural.dtype == bool
    assert list(arrays) == list(ARRAYS)
    for k in ARRAYS[:4]:
        assert arrays[k].shape == (128, 32) and arrays[k].dtype == np.uint8
        assert arrays[k].flags.c_contiguous
    assert arrays["s_ok"].shape == (128,) and arrays["s_ok"].dtype == bool
    assert ov.prepare_batch(tuple(pubs), tuple(msgs), tuple(sigs))[1] == 5


def test_an_empty_batch_packs_nothing(nlib):
    arrays, n, structural = ov.prepare_batch([], [], [])
    assert n == 0 and not structural.any()
    assert all(not arrays[k].any() for k in ARRAYS)


def test_a_library_without_the_symbol_falls_back_without_raising(monkeypatch):
    """A stale prebuilt library (built before ``ed25519_pack_into``
    existed) still serves its other symbols; the pack goes to Python."""

    class Stale:
        def __getattr__(self, name):
            if name.startswith("ed25519_pack_into"):
                raise AttributeError(name)
            return lambda *a: 0

    monkeypatch.setattr(native, "lib", lambda: Stale())
    pubs, msgs, sigs = _signed(6)
    packed, n, structural, how = ov.pack_batch(pubs, msgs, sigs, 32)
    assert how["path"] == "python" and n == 6 and structural[:6].all()
    monkeypatch.setattr(native, "lib", lambda: None)  # no toolchain at all
    again = ov.pack_batch(pubs, msgs, sigs, 32)
    assert again[3]["path"] == "python"
    _assert_byte_for_byte((packed, n, structural), again)


def test_a_refused_call_is_packed_in_python(nlib, monkeypatch):
    pubs, msgs, sigs = _signed(6)
    monkeypatch.setattr(ov, "_native_pack_into", lambda: lambda *a: -1)
    got = ov.pack_batch(pubs, msgs, sigs, 32)
    monkeypatch.setattr(ov, "_native_pack_into", lambda: None)
    assert got[3]["path"] == "python"
    _assert_byte_for_byte(got, ov.pack_batch(pubs, msgs, sigs, 32))


# -- the packed layout: ONE buffer, the five arrays its views ------------------


@pytest.mark.parametrize("b", [128, 512, 8192])
def test_the_five_views_share_the_one_buffer_at_their_rows(nlib, b):
    """Rows 0 … 4b−1 are the tables a, r, s, m; the last b/32 rows are
    ``s_ok``, one byte a lane; every array is a view of the one buffer."""
    pubs, msgs, sigs = _signed(5)
    packed, n, structural, _ = ov.pack_batch(pubs, msgs, sigs, b)
    arrays = ov.packed_views(packed)
    assert packed.shape == (4 * b + b // 32, 32) == (ov.packed_rows(b), 32)
    assert packed.dtype == np.uint8 and packed.flags.c_contiguous
    assert packed.nbytes == 129 * b == sum(v.nbytes for v in arrays.values())
    base = packed.ctypes.data
    for row, k in zip((0, b, 2 * b, 3 * b, 4 * b), ARRAYS):
        assert np.shares_memory(arrays[k], packed), k
        assert arrays[k].ctypes.data - base == 32 * row, k
    assert arrays["s_ok"].shape == (b,) and arrays["s_ok"].dtype == bool
    tables = packed[: 4 * b].reshape(4, b, 32)
    for i, k in enumerate(ARRAYS[:4]):
        assert np.array_equal(tables[i], arrays[k]), k
    ok_bytes = packed[4 * b :].reshape(b)
    assert np.array_equal(ok_bytes, arrays["s_ok"].view(np.uint8))
    assert ok_bytes[:n].all() and not ok_bytes[n:].any()
    assert structural.shape == (b,)
    assert not np.shares_memory(structural, packed)  # never sent


@pytest.mark.parametrize(
    "broken",
    [(), ((0, 39), "pub"), ((7, 8, 20), "sig"), ((3,), "both")],
    ids=["all_right", "pub_first_and_last", "sig_inside", "both_one"],
)
def test_native_and_python_write_the_same_packed_buffer(
    nlib, monkeypatch, broken
):
    pubs, msgs, sigs = _signed(40, b"packed")
    sigs[11] = sigs[11][:32] + (L + 9).to_bytes(32, "little")
    if broken:
        pubs, msgs, sigs = _broken(pubs, msgs, sigs, list(broken[0]), broken[1])
    got, want = _both_paths(monkeypatch, pubs, msgs, sigs, 128)
    assert got[3]["path"] == "native"
    assert got[0].shape == want[0].shape == (ov.packed_rows(128), 32)
    assert got[0].tobytes() == want[0].tobytes()
    _assert_byte_for_byte(got, want)


@pytest.mark.parametrize("n", [1, 5, 117])
def test_an_odd_count_leaves_the_padding_lanes_zero(nlib, n):
    pubs, msgs, sigs = _signed(n, b"odd")
    packed, _, structural, _ = ov.pack_batch(pubs, msgs, sigs, 128)
    arrays = ov.packed_views(packed)
    b = arrays["s_ok"].shape[0]
    assert b == 128
    for k in ARRAYS[:4]:
        assert arrays[k][:n].any(axis=1).all(), k
        assert not arrays[k][n:].any(), k
    assert arrays["s_ok"][:n].all() and not arrays["s_ok"][n:].any()
    assert not packed[4 * b :].reshape(b)[n:].any()
    assert not structural[n:].any()


# -- the span: which path packed, and how the stage splits ----------------------


@pytest.mark.parametrize("path", ["native", "python"])
def test_the_span_names_the_path_and_holds_the_two_laps(
    nlib, monkeypatch, path
):
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.ops import supervisor

    if path == "python":
        monkeypatch.setattr(ov, "_native_pack_into", lambda: None)
    tracing.get_tracer().reset()
    pubs, msgs, sigs = _signed(9)
    supervisor._pack(pubs, msgs, sigs, 32)
    spans = tracing.get_tracer().tail(10)
    halves = ["verify.pack.glue"] + ["verify.pack.native"] * (path == "native")
    assert [sp["stage"] for sp in spans] == halves + ["verify.pack"]
    pack = spans[-1]
    assert pack["attrs"]["path"] == path
    assert pack["attrs"]["n"] == 9 and pack["attrs"]["lanes"] == 32
    for sp in spans[:-1]:
        assert sp["parent"] == pack["span"]
        assert pack["t0"] <= sp["t0"] <= sp["t1"] <= pack["t1"]


def test_the_pack_starts_no_thread(nlib):
    import threading

    before = threading.active_count()
    ov.prepare_batch(*_signed(200))
    assert threading.active_count() == before
