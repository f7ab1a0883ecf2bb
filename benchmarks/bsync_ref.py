"""The plain reference of a joiner's blocksync frontier (reference:
internal/blocksync/reactor.go:520-585 poolRoutine, state/validation.go:17
validateBlock, types/validation.go:28,63, a kvstore app).

A joiner at height H holds the blocks H and H+1 as they came from its
peers, as wire bytes (a BlockResponse).  One tick checks H with H+1's
LastCommit (light: by index, stopping past 2/3 of the power), then H itself
against the state (its header's basic checks and hashes, its body against
its header, its header against the state, its own LastCommit in full
against the previous set, its proposer in the set), then applies H with a
plain kvstore.  A tick's verdict is ``("applied", H, app_hash, H, (block
hash, seen commit hash))``, the last three what the joiner's block store
holds after the tick (its height, H's block, and as its seen commit H + 1's
LastCommit as received), or ``("rejected", H, class, commit index or
None)``, the class one of ``invalid_signature`` (with the commit index of
the first wrong signature), ``invalid_commit`` (any other commit check) and
``invalid_block`` (any other check of the block).  After a rejection both
heights are fetched again, and nothing of the rejected tick is applied.

Straightforward Python: its own proto reader, hashes (RFC 6962 Merkle
roots, ``hashlib``) and kvstore.  No signature cache, no batching, no
scheduler, no device: it imports none of the program's modules, and the
caller gives it ``verify_sig`` (the ZIP-215 rule).  The tests
(``tests/test_blocksync_reference.py``) hold the program's reactor to it;
``benchmarks/bsync_ref.py`` is a byte-for-byte copy.

Departures from upstream, each on purpose, where the program departs and
the chains under test were made by it:

  * a block's data hash is the Merkle root of the transactions themselves
    (Go: of their SHA-256 hashes, types/tx.go Txs.Hash);
  * the consensus hash is the caller's (the program hashes more of the
    params than Go's HashedParams);
  * header fields are encoded as the program encodes them (chain id and
    height bare, as ``light_ref`` notes);
  * the kvstore's app hash is SHA-256 over the height (8 bytes, big
    endian) and every key and value in key order, each followed by a zero
    byte; a transaction is ``key=value`` (one ``=``, a non-empty key, both
    UTF-8) or fails with code 1 and changes nothing;
  * the block's time is not checked against the LastCommit's (the program
    does not check it).
"""

from __future__ import annotations

import functools
import hashlib
import struct
from typing import Callable, NamedTuple

FLAG_ABSENT, FLAG_COMMIT, FLAG_NIL = 1, 2, 3
PRECOMMIT = 2
BLOCK_PROTOCOL = 11
PART_SIZE = 65536
ADDRESS_LEN = 20
MSG_BLOCK_RESPONSE = 2

VerifySig = Callable[[bytes, bytes, bytes], bool]


# -- proto3 and Merkle, from their definitions -------------------------------------


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _varint(field: int, value: int) -> bytes:
    return bytes([field << 3]) + _uvarint(value) if value else b""


def _sfixed64(field: int, value: int) -> bytes:
    return bytes([(field << 3) | 1]) + value.to_bytes(8, "little") if value else b""


def _bytes(field: int, value: bytes) -> bytes:
    return bytes([(field << 3) | 2]) + _uvarint(len(value)) + value if value else b""


def _fields(buf: bytes) -> "dict[int, list]":
    """Every field of a message: varints as ints, length-delimited as bytes."""
    out: "dict[int, list]" = {}
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        field, wire = key >> 3, key & 7
        if wire == 0:
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            value = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire}")
        out.setdefault(field, []).append(value)
    return out


def _one(f: dict, field: int, default):
    return f[field][-1] if field in f else default


def merkle_root(items: "list[bytes]") -> bytes:
    """RFC 6962: leaves ``SHA-256(0x00 | item)``, inner nodes
    ``SHA-256(0x01 | left | right)``; built level by level, a level's odd
    last node carried up as it is, which is the same tree as the split at
    the largest power of two below the count."""
    if not items:
        return hashlib.sha256(b"").digest()
    sha = hashlib.sha256
    level = [sha(b"\x00" + x).digest() for x in items]
    while len(level) > 1:
        up = [sha(b"\x01" + level[i] + level[i + 1]).digest()
              for i in range(0, len(level) - 1, 2)]
        if len(level) % 2:
            up.append(level[-1])
        level = up
    return level[0]


def address(pub: bytes) -> bytes:
    return hashlib.sha256(pub).digest()[:ADDRESS_LEN]


# -- the block as received ----------------------------------------------------------


class BlockID(NamedTuple):
    hash: bytes
    parts_total: int
    parts_hash: bytes


class CommitSig(NamedTuple):
    flag: int
    address: bytes
    time: bytes  # the Timestamp message as encoded
    signature: bytes


class Commit(NamedTuple):
    height: int
    round: int
    block_id: BlockID
    sigs: "list[CommitSig]"
    raw_sigs: "list[bytes]"  # each CommitSig as encoded


class Block(NamedTuple):
    header: dict  # field number -> value, as received
    header_raw: "list[bytes]"  # the fourteen encodings the header hash reads
    txs: "list[bytes]"
    evidence: "list[bytes]"
    last_commit: Commit
    raw: bytes  # the Block message, what the part set is made of


def _block_id(buf: bytes) -> BlockID:
    f = _fields(buf)
    psh = _fields(_one(f, 2, b""))
    return BlockID(bytes(_one(f, 1, b"")), _one(psh, 1, 0), bytes(_one(psh, 2, b"")))


def _encode_block_id(b: BlockID) -> bytes:
    psh = _varint(1, b.parts_total) + _bytes(2, b.parts_hash)
    return _bytes(1, b.hash) + _bytes(2, psh)


def _commit(buf: bytes) -> Commit:
    f = _fields(buf)
    sigs, raw = [], []
    for s in f.get(4, []):
        g = _fields(s)
        sigs.append(CommitSig(_one(g, 1, 0), bytes(_one(g, 2, b"")),
                              bytes(_one(g, 3, b"")), bytes(_one(g, 4, b""))))
        raw.append(bytes(s))
    return Commit(_one(f, 1, 0), _one(f, 2, 0), _block_id(_one(f, 3, b"")), sigs, raw)


def decode(wire: bytes) -> Block:
    """A BlockResponse as sent: the kind byte, then field 1, the Block."""
    if not wire or wire[0] != MSG_BLOCK_RESPONSE:
        raise ValueError("not a BlockResponse")
    raw = bytes(_one(_fields(wire[1:]), 1, b""))
    f = _fields(raw)
    h = _fields(_one(f, 1, b""))
    header = {k: v[-1] for k, v in h.items()}
    height = header.get(3, 0)
    header_raw = [
        bytes(header.get(1, b"")), bytes(header.get(2, b"")), _uvarint(height),
        bytes(header.get(4, b"")), bytes(header.get(5, b"")),
    ] + [bytes(header.get(k, b"")) for k in range(6, 15)]
    data = _fields(_one(f, 2, b""))
    evidence = _fields(_one(f, 3, b""))
    return Block(header, header_raw, [bytes(t) for t in data.get(1, [])],
                 [bytes(e) for e in evidence.get(1, [])],
                 _commit(_one(f, 4, b"")), raw)


def header_hash(block: Block) -> bytes:
    return merkle_root(block.header_raw)


def part_set_header(raw: bytes) -> "tuple[int, bytes]":
    chunks = [raw[i:i + PART_SIZE] for i in range(0, len(raw), PART_SIZE)] or [b""]
    return len(chunks), merkle_root(chunks)


def validators_hash(validators: "list[tuple[bytes, int]]") -> bytes:
    """Root over SimpleValidator{PublicKey{ed25519 = 1}, voting_power = 2}."""
    return merkle_root(
        [_bytes(1, _bytes(1, pub)) + _varint(2, power) for pub, power in validators]
    )


def vote_sign_bytes(chain_id: str, commit: Commit, index: int) -> bytes:
    """The CanonicalVote a validator signed as its precommit, delimited."""
    cs = commit.sigs[index]
    body = (
        _varint(1, PRECOMMIT)
        + _sfixed64(2, commit.height)
        + _sfixed64(3, commit.round)
        + (_bytes(4, _encode_block_id(commit.block_id)) if cs.flag == FLAG_COMMIT else b"")
        + _bytes(5, cs.time)
        + _bytes(6, chain_id.encode())
    )
    return _uvarint(len(body)) + body


# -- the commit checks (types/validation.go) ----------------------------------------


def _commit_basic(commit: Commit) -> "str | None":
    """Commit.ValidateBasic."""
    if commit.height < 0 or commit.round < 0:
        return "negative height or round"
    if commit.height >= 1:
        if not commit.block_id.hash and not commit.block_id.parts_total:
            return "commit for a nil block"
        if not commit.sigs:
            return "no signatures"
    for cs in commit.sigs:
        if cs.flag not in (FLAG_ABSENT, FLAG_COMMIT, FLAG_NIL):
            return "unknown flag"
        if cs.flag == FLAG_ABSENT:
            if cs.address or cs.signature:
                return "absent signature with data"
        elif len(cs.address) != ADDRESS_LEN or not cs.signature or len(cs.signature) > 96:
            return "malformed signature"
    return None


def verify_commit(chain_id: str, validators, block_id: BlockID, height: int,
                  commit: Commit, verify_sig: VerifySig, light: bool) -> tuple:
    """VerifyCommitLight (``light``: by index, stop past 2/3 of the power)
    or VerifyCommit (every signature of the block): ``("ok",)``,
    ``("invalid_signature", index)`` or ``("invalid_commit", why)``."""
    bad = _commit_basic(commit)
    if bad:
        return ("invalid_commit", bad)
    if not validators:
        return ("invalid_commit", "empty validator set")
    if height != commit.height:
        return ("invalid_commit", "commit height")
    if block_id != commit.block_id:
        return ("invalid_commit", "commit is for another block id")
    if len(validators) != len(commit.sigs):
        return ("invalid_commit", "commit size differs from the set's")
    needed = sum(p for _, p in validators) * 2 // 3
    tallied, picked = 0, []
    for index, cs in enumerate(commit.sigs):
        if cs.flag == FLAG_ABSENT:
            continue
        pub, power = validators[index]
        if cs.address != address(pub):
            return ("invalid_commit", f"address mismatch at {index}")
        picked.append((index, pub))
        if cs.flag == FLAG_COMMIT:
            tallied += power
        if light and tallied > needed:
            break
    for index, pub in picked:
        if not verify_sig(pub, vote_sign_bytes(chain_id, commit, index),
                          commit.sigs[index].signature):
            return ("invalid_signature", index)
    if tallied <= needed:
        return ("invalid_commit", "not enough voting power signed")
    return ("ok",)


# -- the joiner ---------------------------------------------------------------------


class State(NamedTuple):
    chain_id: str
    height: int  # the last applied height
    last_block_id: BlockID
    validators: "list[tuple[bytes, int]]"  # (public key, power), set order
    app_version: int
    consensus_hash: bytes
    app_hash: bytes
    results_hash: bytes
    kv: dict  # key -> ``key 0x00 value 0x00``, what the app hash reads


def app_hash(height: int, kv: dict) -> bytes:
    return hashlib.sha256(
        struct.pack(">q", height) + b"".join(kv[k] for k in sorted(kv))
    ).digest()


def genesis(chain_id: str, validators, app_version: int,
            consensus_hash: bytes) -> State:
    """The joiner's state before height 1: InitChain of an empty kvstore."""
    return State(chain_id, 0, BlockID(b"", 0, b""), list(validators), app_version,
                 consensus_hash, app_hash(0, {}), b"", {})


@functools.lru_cache(maxsize=64)
def _results_root(results: "tuple[bytes, ...]") -> bytes:
    return merkle_root(list(results))


def _write(tx: bytes) -> "tuple[bytes, bytes] | None":
    """A transaction ``key=value`` (one ``=``, a non-empty key, both UTF-8)
    writes; any other fails with code 1 and changes nothing."""
    parts = tx.split(b"=")
    if len(parts) != 2 or not parts[0]:
        return None
    try:
        parts[0].decode()
        parts[1].decode()
    except UnicodeDecodeError:
        return None
    return parts[0], parts[1]


def apply(state: State, block, block_id: BlockID) -> State:
    """FinalizeBlock (a write uses 1 gas) and Commit of the kvstore, then
    the state update."""
    kv = dict(state.kv)
    results = []
    for tx in block.txs:
        write = _write(tx)
        if write is None:
            results.append(_varint(1, 1))
            continue
        key, value = write
        kv[key] = key + b"\x00" + value + b"\x00"
        results.append(_varint(6, 1))
    height = state.height + 1
    return state._replace(height=height, last_block_id=block_id,
                          app_hash=app_hash(height, kv),
                          results_hash=_results_root(tuple(results)), kv=kv)


def validate_block(state: State, block: Block, verify_sig: VerifySig) -> tuple:
    """state/validation.go:17 validateBlock: ``("ok",)`` or a rejection's
    (class, index or why)."""
    h = block.header
    chain_id = bytes(h.get(2, b"")).decode(errors="replace")
    proposer = bytes(h.get(14, b""))
    if not chain_id or len(chain_id) > 50 or (proposer and len(proposer) != ADDRESS_LEN):
        return ("invalid_block", "header basic checks")
    if _commit_basic(block.last_commit):
        return ("invalid_block", "last commit basic checks")
    if bytes(h.get(6, b"")) != merkle_root(block.last_commit.raw_sigs):
        return ("invalid_block", "last commit hash")
    if bytes(h.get(7, b"")) != merkle_root(block.txs):
        return ("invalid_block", "data hash")
    if bytes(h.get(13, b"")) != merkle_root([]) or block.evidence:
        return ("invalid_block", "evidence")
    version = _fields(bytes(h.get(1, b"")))
    if (_one(version, 1, 0), _one(version, 2, 0)) != (BLOCK_PROTOCOL, state.app_version):
        return ("invalid_block", "version")
    if chain_id != state.chain_id or h.get(3, 0) != state.height + 1:
        return ("invalid_block", "chain id or height")
    if _block_id(bytes(h.get(5, b""))) != state.last_block_id:
        return ("invalid_block", "last block id")
    if bytes(h.get(11, b"")) != state.app_hash:
        return ("invalid_block", "app hash")
    if bytes(h.get(12, b"")) != state.results_hash:
        return ("invalid_block", "last results hash")
    vhash = validators_hash(state.validators)
    if bytes(h.get(8, b"")) != vhash or bytes(h.get(9, b"")) != vhash:
        return ("invalid_block", "validators hash")
    if bytes(h.get(10, b"")) != state.consensus_hash:
        return ("invalid_block", "consensus hash")
    if state.height >= 1:
        if len(block.last_commit.sigs) != len(state.validators):
            return ("invalid_block", "last commit size")
        got = verify_commit(state.chain_id, state.validators, state.last_block_id,
                            state.height, block.last_commit, verify_sig, light=False)
        if got != ("ok",):
            return got
    elif block.last_commit.sigs:
        return ("invalid_block", "initial block with a last commit")
    if len(proposer) != ADDRESS_LEN or proposer not in {
            address(pub) for pub, _ in state.validators}:
        return ("invalid_block", "proposer not in the set")
    return ("ok",)


def check_tick(state: State, first: bytes, second: bytes, verify_sig: VerifySig):
    """One tick of the frontier at ``state.height + 1`` with the blocks
    ``first`` (that height) and ``second`` (the next) as received: the
    verdict, and the state after it (the same state after a rejection)."""
    height = state.height + 1
    a, b = decode(first), decode(second)
    total, parts = part_set_header(a.raw)
    block_id = BlockID(header_hash(a), total, parts)
    got = verify_commit(state.chain_id, state.validators, block_id, height,
                        b.last_commit, verify_sig, light=True)
    if got == ("ok",):
        got = validate_block(state, a, verify_sig)
    if got != ("ok",):
        index = got[1] if got[0] == "invalid_signature" else None
        return ("rejected", height, got[0], index), state
    new = apply(state, a, block_id)
    stored = (block_id.hash, merkle_root(b.last_commit.raw_sigs))
    return ("applied", height, new.app_hash, height, stored), new


def replay(state: State, served: "dict[int, list[bytes]]", ticks: int,
           verify_sig: VerifySig) -> "list[tuple]":
    """``ticks`` ticks of a joiner from ``state``, in Go's order.
    ``served[h]`` are the copies of height h in the order the joiner
    receives them (the last one again once they run out): a rejected tick
    fetches both of its heights again."""
    taken: "dict[int, int]" = {}

    def copy(h: int) -> bytes:
        got = served[h]
        return got[min(taken.get(h, 0), len(got) - 1)]

    out = []
    for _ in range(ticks):
        h = state.height + 1
        verdict, state = check_tick(state, copy(h), copy(h + 1), verify_sig)
        if verdict[0] == "rejected":
            taken[h] = taken.get(h, 0) + 1
            taken[h + 1] = taken.get(h + 1, 0) + 1
        out.append(verdict)
    return out
