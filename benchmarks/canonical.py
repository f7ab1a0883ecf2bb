"""The benchmark's own CanonicalVote sign-bytes (reference:
``types/canonical.go`` CanonicalizeVote + protoio delimited framing).

Written from the protobuf layout, importing nothing of the program: the
generator signs THESE bytes, the program verifies against the bytes it builds
itself (``Commit.all_vote_sign_bytes``), so an encoder fault on either side
turns every signature invalid and ``correct`` false.

    CanonicalVote { 1: type (varint)  2: height (sfixed64)  3: round (sfixed64)
                    4: block_id { 1: hash  2: part_set_header { 1: total 2: hash } }
                    5: timestamp { 1: seconds  2: nanos }  6: chain_id }

proto3: zero-valued scalars are left out.
"""

from __future__ import annotations

PRECOMMIT_TYPE = 2


def uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _bytes_field(field: int, value: bytes) -> bytes:
    return bytes([(field << 3) | 2]) + uvarint(len(value)) + value


def _varint_field(field: int, value: int) -> bytes:
    return bytes([field << 3]) + uvarint(value) if value else b""


def _sfixed64_field(field: int, value: int) -> bytes:
    return bytes([(field << 3) | 1]) + value.to_bytes(8, "little") if value else b""


def vote_head(height: int, round_: int, block_hash: bytes, parts_total: int,
              parts_hash: bytes) -> bytes:
    """Fields 1-4: everything of a precommit that its validators share."""
    psh = _varint_field(1, parts_total) + _bytes_field(2, parts_hash)
    bid = _bytes_field(1, block_hash) + _bytes_field(2, psh)
    return (
        _varint_field(1, PRECOMMIT_TYPE)
        + _sfixed64_field(2, height)
        + _sfixed64_field(3, round_)
        + _bytes_field(4, bid)
    )


def vote_tail(chain_id: str) -> bytes:
    return _bytes_field(6, chain_id.encode())


def sign_bytes(head: bytes, ts_ns: int, tail: bytes) -> bytes:
    """One validator's sign-bytes: the shared head, its own timestamp, the
    shared tail, length-prefixed."""
    seconds, nanos = divmod(ts_ns, 1_000_000_000)
    ts = _varint_field(1, seconds) + _varint_field(2, nanos)
    body = head + _bytes_field(5, ts) + tail
    return uvarint(len(body)) + body
