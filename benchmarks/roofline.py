"""The least work an Ed25519 ZIP-215 verification needs, and the least time
a chip could take for it.

The work is a function of the number of REAL signatures only.  It is the
textbook verification, counted here and nowhere read from the program: not
its kernel, its limb count, its window or its padded lanes.  So the share
reads the same work whatever implements it, and a kernel that pads, recomputes
or wastes lanes reads lower.

Field multiplications of one verification (GF(2^255-19); a squaring counts
as a multiplication; additions are not counted):

  * two ZIP-215 decompressions (A and R).  Each: y^2 and d*y^2 (2); the
    square-root candidate u*v^3*(u*v^7)^((p-5)/8): v^3 and v^7 (4), u*v^3 and
    u*v^7 (2), the power 2^252-3 by the usual chain (251 squarings, 11
    multiplications), the last product (1); the check v*x^2 (2) and the
    product by sqrt(-1) (1): 274.
  * one double-base scalar multiplication [s]B - [h]A with a 4-bit window
    (Straus): 252 doublings of 8 (extended coordinates, 4M+4S); 64 windows
    with one addition from A's table (9) and one mixed addition from B's
    constant table (8); A's table of 2A..15A, 14 additions of 9.
  * the comparison, cofactored: one addition of -R (9), three doublings (24);
    testing for the identity multiplies nothing.

One field multiplication is taken at the word size of the chip's only
published integer peak, int8: 32 limbs of 8 bits, schoolbook, 32*32 = 1,024
multiply-adds = 2,048 operations; the reduction mod p (a fold by 38) and the
carries are not counted.  SHA-512 of R|A|M (two blocks, some 10^4 word
operations) is under 1% of this and is left out; both leave the needed work
a little low, never high.

Bytes: a public key, a signature's two halves and the reduced hash in (4x32 B
and a mask byte), one accept bit out.
"""

from __future__ import annotations

import json
import os

DECOMPRESS_MULS = 2 + 4 + 2 + 251 + 11 + 1 + 2 + 1
SCALAR_MULS = 252 * 8 + 64 * (9 + 8) + 14 * 9
COMPARE_MULS = 9 + 3 * 8
FIELD_MULS_PER_SIG = 2 * DECOMPRESS_MULS + SCALAR_MULS + COMPARE_MULS

WORD_BITS = 8
LIMBS = 256 // WORD_BITS
OPS_PER_FIELD_MUL = 2 * LIMBS * LIMBS  # a multiply and an add each
OPS_PER_SIG = FIELD_MULS_PER_SIG * OPS_PER_FIELD_MUL
BYTES_PER_SIG = 4 * 32 + 1 + 1

CEILING_PCT = 105.0  # a share of a peak above this is a counting fault

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in benchmarks/peaks.json"
        )
    return table[device_kind]


def least_seconds(signatures: int, peak: dict, chips: int = 1) -> "tuple[float, str]":
    """The larger of operations over the peak rate and bytes over the peak
    bandwidth, and which of the two bounds it."""
    by_ops = signatures * OPS_PER_SIG / (peak["int8_ops_per_s"] * chips)
    by_bytes = signatures * BYTES_PER_SIG / (peak["hbm_bytes_per_s"] * chips)
    return (by_ops, "operations") if by_ops >= by_bytes else (by_bytes, "bytes")


def share_pct(signatures: int, seconds: float, peak: dict, chips: int = 1):
    """Share of the roofline, in percent; None where there is nothing to
    read.  Never clipped: a share over ``CEILING_PCT`` raises, because the
    operations are then counted too high or the time leaves out work."""
    if not signatures or not seconds or seconds <= 0:
        return None
    share = 100.0 * least_seconds(signatures, peak, chips)[0] / seconds
    if share > CEILING_PCT:
        raise ValueError(
            f"{share:.1f}% of the roofline: {signatures} signatures in "
            f"{seconds:.6f} s is faster than the chip's peak allows"
        )
    return share
