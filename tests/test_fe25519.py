"""Differential tests: JAX field arithmetic vs python big-int arithmetic.

These are the oracle tests for ``cometbft_tpu.ops.fe25519`` — every ring op,
the canonicalizer, and the sqrt chain are checked against python ints over
random and adversarial inputs (incl. limb values at the interval bounds, the
round-2 dropped-carry regression class).
"""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from cometbft_tpu.ops import fe25519 as fe

P = fe.P_INT
rng = random.Random(1234)


def _rand_vals(n, full=True):
    vals = [rng.randrange(2**260 if full else P) for _ in range(n)]
    # always include edge cases
    vals[:6] = [0, 1, P - 1, P, P + 1, 2**260 - 1][: min(6, n)]
    return vals


def _to_f(vals) -> fe.F:
    arr = np.stack([fe.limbs_of_int(v) for v in vals], axis=1)
    return fe.F(jnp.asarray(arr), 0, fe.MASK)


def _f_to_ints(f: fe.F):
    """Canonical ints mod p of each lane."""
    arr = np.asarray(fe.freeze(f))
    return [fe.int_of_limbs(arr[:, i]) for i in range(arr.shape[1])]


def test_limb_roundtrip():
    vals = _rand_vals(16)
    arr = np.stack([fe.limbs_of_int(v) for v in vals], axis=1)
    assert [fe.int_of_limbs(arr[:, i]) for i in range(len(vals))] == vals


def test_add_sub_mul():
    a_vals = _rand_vals(32)
    b_vals = list(reversed(_rand_vals(32)))
    a, b = _to_f(a_vals), _to_f(b_vals)
    for got, expect in [
        (fe.add(a, b), [(x + y) % P for x, y in zip(a_vals, b_vals)]),
        (fe.sub(a, b), [(x - y) % P for x, y in zip(a_vals, b_vals)]),
        (fe.mul(a, b), [(x * y) % P for x, y in zip(a_vals, b_vals)]),
        (fe.neg(a), [(-x) % P for x in a_vals]),
        (fe.mul_small(a, 2), [(2 * x) % P for x in a_vals]),
        (fe.square(a), [(x * x) % P for x in a_vals]),
    ]:
        assert _f_to_ints(got) == [e % P for e in expect]


def test_mul_adversarial_bounds():
    """Limbs at the signed interval bounds, esp. the top limb — the class of
    inputs that triggered the round-2 dropped-carry bug in _reduce_cols."""
    nrng = np.random.default_rng(99)
    for _ in range(40):
        a_limbs = nrng.integers(fe.RED_LO, fe.RED_HI + 1, size=fe.NLIMBS)
        b_limbs = nrng.integers(fe.RED_LO, fe.RED_HI + 1, size=fe.NLIMBS)
        # force the top-limb product large (this is what trips a carry out
        # of column 38 into the pad limb)
        a_limbs[fe.NLIMBS - 1] = fe.RED_HI
        b_limbs[fe.NLIMBS - 1] = fe.RED_LO
        a = fe.F(
            jnp.asarray(a_limbs[:, None].astype(np.int32)), fe.RED_LO, fe.RED_HI
        )
        b = fe.F(
            jnp.asarray(b_limbs[:, None].astype(np.int32)), fe.RED_LO, fe.RED_HI
        )
        want = (fe.int_of_limbs(a_limbs) * fe.int_of_limbs(b_limbs)) % P
        assert _f_to_ints(fe.mul(a, b)) == [want]


def test_kernel_mode_mul_agrees_with_the_default():
    """The Pallas kernel's shifted-row multiplier (``kernel_mode``) gives
    the default skew-reshape one's products, python's ints, with limbs at
    the interval bounds."""
    nrng = np.random.default_rng(7)
    limbs = nrng.integers(fe.RED_LO, fe.RED_HI + 1, size=(2, fe.NLIMBS, 32))
    limbs[0, fe.NLIMBS - 1, :8] = fe.RED_HI
    limbs[1, fe.NLIMBS - 1, :8] = fe.RED_LO
    a, b = (fe.F(jnp.asarray(x.astype(np.int32)), fe.RED_LO, fe.RED_HI)
            for x in limbs)
    want = [
        fe.int_of_limbs(limbs[0, :, i]) * fe.int_of_limbs(limbs[1, :, i]) % P
        for i in range(32)
    ]
    with fe.kernel_mode(32):
        rows = fe.mul(a, b)
    assert _f_to_ints(rows) == _f_to_ints(fe.mul(a, b)) == want


def test_mul_unreduced_operands():
    """mul must be correct when fed unreduced sums/differences (wide static
    bounds) — the ladder feeds it these constantly."""
    a_vals = _rand_vals(16)
    b_vals = list(reversed(_rand_vals(16)))
    a, b = _to_f(a_vals), _to_f(b_vals)
    h = fe.add(a, b)         # bound [0, 2*MASK]
    d = fe.sub(a, b)         # bound [-MASK, MASK]
    hh = fe.add(h, h)        # wider still
    got = fe.mul(hh, d)
    want = [
        (2 * (x + y) * (x - y)) % P for x, y in zip(a_vals, b_vals)
    ]
    assert _f_to_ints(got) == want


def test_carry_reaches_red_bounds():
    a = _to_f(_rand_vals(8))
    s = fe.add(fe.add(a, a), a)
    c = fe.carry(s)
    assert c.lo >= fe.RED_LO and c.hi <= fe.RED_HI
    assert _f_to_ints(c) == _f_to_ints(s)
    v = np.asarray(c.v)
    assert v.min() >= fe.RED_LO and v.max() <= fe.RED_HI


def test_freeze_canonical():
    vals = _rand_vals(32)
    out = _f_to_ints(_to_f(vals))
    assert out == [v % P for v in vals]
    # freeze of negative-limb values (post-sub) must also be canonical
    a, b = _to_f(vals), _to_f(list(reversed(vals)))
    d = fe.sub(a, b)
    assert _f_to_ints(d) == [
        (x - y) % P for x, y in zip(vals, reversed(vals))
    ]


def test_eq_and_is_zero():
    a = _to_f([0, P, 5, 2 * P, 7])
    b = _to_f([P, 0, 5, 0, 8])
    assert list(np.asarray(fe.eq(a, b))) == [True, True, True, True, False]
    assert list(np.asarray(fe.is_zero(a))) == [True, True, False, True, False]


def test_pow_and_sqrt_ratio():
    vals = _rand_vals(8, full=False)
    a = _to_f(vals)
    out = _f_to_ints(fe.pow_p58(a))
    assert out == [pow(v, (P - 5) // 8, P) for v in vals]

    # sqrt_ratio on known squares: u = t^2 * v for random t, v.
    ts = _rand_vals(8, full=False)
    vs = [rng.randrange(1, P) for _ in range(8)]
    us = [t * t % P * v % P for t, v in zip(ts, vs)]
    ok, x = fe.sqrt_ratio(_to_f(us), _to_f(vs))
    assert all(np.asarray(ok))
    for xi, u, v in zip(_f_to_ints(x), us, vs):
        assert (v * xi % P) * xi % P == u % P

    # non-squares must report not-ok: u/v = 2 is a non-residue for p=2^255-19.
    ok2, _ = fe.sqrt_ratio(_to_f([2] * 4), _to_f([1] * 4))
    assert not any(np.asarray(ok2))


def test_parity():
    vals = [0, 1, 2, P - 1, P, P + 1]
    out = np.asarray(fe.parity(_to_f(vals)))
    assert list(out) == [(v % P) & 1 for v in vals]


def test_unpack255_roundtrip():
    vals = [0, 1, P - 1, P + 3, 2**255 - 1, rng.randrange(2**255)]
    enc = np.stack(
        [np.frombuffer(int(v).to_bytes(32, "little"), np.uint8) for v in vals]
    )
    # set sign bits on half the lanes
    enc[1::2, 31] |= 0x80
    y, sign = fe.unpack255(jnp.asarray(enc))
    assert _f_to_ints(y) == [v % P for v in vals]
    assert list(np.asarray(sign)) == [0, 1, 0, 1, 0, 1]


def test_nibbles_msb_first():
    s = rng.randrange(2**252)
    enc = np.frombuffer(int(s).to_bytes(32, "little"), np.uint8)[None, :]
    digs = np.asarray(fe.nibbles_msb_first(jnp.asarray(enc)))[:, 0]
    rebuilt = 0
    for d in digs:
        rebuilt = rebuilt * 16 + int(d)
    assert rebuilt == s
