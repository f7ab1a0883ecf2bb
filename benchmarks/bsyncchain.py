"""The joiner's chain: a chain of whole blocks (header, 40 transactions,
LastCommit) of a fixed validator set, as the BlockResponses a blocksync
helper sends, the faulty peers' copies, and the ticks a joiner makes over
it (internal/blocksync/reactor.go:520 poolRoutine), from ``--seed``.

A pure function of (configuration, traffic file, seed).  Nothing here
imports the program or jax: blocks are encoded as the program's codec
encodes them (the joiner builds its part set from what it decoded, so a
difference rejects every block), hashes and app hashes come from the plain
reference (``bsync_ref``), sign-bytes from ``canonical.py``, signatures
from the host library.  Block h carries commit h - 1, so the chain is
signed in height order, in one process.

A tick is one request: the joiner at frontier H checks H with H + 1's
LastCommit, then H itself, then applies it.  Faults, each served by a peer
of its own that serves nothing else (the honest copy comes from a helper
once that peer is gone):

    prefix     block B's LastCommit has one signature altered inside the
               2/3 prefix the light check reads: the tick at B - 1 is
               ("rejected", B - 1, "invalid_signature", index)
    past       the same past the prefix, B's header rehashed over it and
               B + 1's LastCommit signed over the new B: the light checks
               pass, the tick at B is ("rejected", B, "invalid_signature",
               index) from validate_block's full check of B's LastCommit
    body       one transaction of B altered after its header was made, and
               B + 1's LastCommit signed over the new part set: the tick at
               B is ("rejected", B, "invalid_block", None), the data hash

Tamper heights are ``B % tamper_every == tamper_phase``, prefix and past
alternating by ``B // tamper_every``, the classes cycled by ``B // (2 *
tamper_every)``; body heights ``B % body_every == body_phase``; the
warm-up heights place one of each kind where ``warmup_faults`` says.

An applied tick's verdict names what the joiner's block store holds after
it: the height, the block's hash and the hash of its seen commit (the
LastCommit of the copy of H + 1 the joiner held).

What a traffic file may say: ``sync_heights`` (the pool's heights),
``sync_warmup_heights``, ``warmup_faults``, ``txs_per_block``, ``tx_bytes``,
``tamper_every`` / ``tamper_phase`` / ``tamper_classes``,
``body_every`` / ``body_phase``, ``time_jitter_ms``.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import struct
from dataclasses import dataclass, field
from types import SimpleNamespace

from benchmarks import bsync_ref as bref
from benchmarks import canonical, chain as chainlib, manifest
from benchmarks import ed25519_ref as ref

ENTRY = "blocksync_catchup"
LOADTIME_KEY = b"a="  # test/loadtime/payload/payload.go keyPrefix
BASE_TIME_NS = 1_700_000_000 * 10**9


@dataclass
class Tick:
    key: object  # tick number; ("warm", k) before the pool
    height: int  # the frontier
    expected: tuple
    first: int  # copy of H the joiner holds: 0 honest, 1 the faulty peer's
    second: int  # copy of H + 1
    provider: str = ""  # the faulty peer a rejection must stop
    signatures: int = 0  # distinct triples its checks needed, new to the run


@dataclass
class BlockChain:
    seed: int
    chain_id: str
    pubs: "list[bytes]"  # set order
    powers: "list[int]"
    ids: "list[int]"  # validator identity at each place of the set order
    app_version: int
    consensus: dict
    consensus_hash: bytes
    honest: "list[bytes]" = field(default_factory=list)  # wire by height
    faulty: dict = field(default_factory=dict)  # height -> (peer, wire)
    faults: dict = field(default_factory=dict)  # B -> (kind, index, class)
    app_hashes: "list[bytes]" = field(default_factory=list)  # after h
    block_hashes: "list[bytes]" = field(default_factory=list)  # honest, by height
    commit_roots: dict = field(default_factory=dict)  # (h, copy) -> its LastCommit's hash
    warm: "list[Tick]" = field(default_factory=list)
    pool: "list[Tick]" = field(default_factory=list)

    @property
    def top(self) -> int:
        return len(self.honest) - 1

    def copy(self, height: int, which: int) -> bytes:
        return self.faulty[height][1] if which else self.honest[height]

    def genesis(self) -> bref.State:
        return bref.genesis(self.chain_id, list(zip(self.pubs, self.powers)),
                            self.app_version, self.consensus_hash)


def cell_files(chain_id: str) -> "tuple[dict, dict]":
    """The configuration whose chain id this is and the traffic file of the
    cell that runs it through ``blocksync_catchup`` (the harness hands an
    entry the chain only; ``seqchain.cell_files``' way)."""
    m = manifest.load()
    found = []
    for w in m["workloads"]:
        cfg = next(c for c in m["configs"] if c["name"] == w["config"])
        config = manifest._json(os.path.join(manifest.ROOT, cfg["file"]))
        traffic = manifest._json(
            os.path.join(manifest.HERE, "traffic", w["traffic"] + ".json"))
        if config.get("chain_id") == chain_id and traffic["entry"] == ENTRY:
            found.append((w["traffic"], config, traffic))
    if len({name for name, _, _ in found}) != 1:
        raise KeyError(f"{len(found)} {ENTRY} cells for chain id {chain_id!r}")
    return found[0][1:]


# -- encodings, as the program's codec writes them ---------------------------------


def _uvarint(n: int) -> bytes:
    return bytes([n]) if 0 <= n < 0x80 else canonical.uvarint(n & ((1 << 64) - 1))


def _varint(field_: int, value: int) -> bytes:
    return bytes([field_ << 3]) + _uvarint(value) if value else b""


def _bytes(field_: int, value: bytes) -> bytes:
    return bytes([(field_ << 3) | 2]) + _uvarint(len(value)) + value if value else b""


def _message(field_: int, body: bytes) -> bytes:
    """A message field written even when empty."""
    return bytes([(field_ << 3) | 2]) + _uvarint(len(body)) + body


def _timestamp(ns: int) -> bytes:
    seconds, nanos = divmod(ns, 1_000_000_000)
    return _varint(1, seconds) + _varint(2, nanos)


def _block_id(b: bref.BlockID) -> bytes:
    psh = _varint(1, b.parts_total) + _bytes(2, b.parts_hash)
    return _bytes(1, b.hash) + _bytes(2, psh)


def consensus_hash(p: dict) -> bytes:
    """The program's hash of the consensus params (the block, evidence,
    validator and feature params; Go's HashedParams holds block.max_bytes
    and block.max_gas only)."""
    body = b"".join([
        _varint(1, p["block_max_bytes"]), _varint(2, p["block_max_gas"]),
        _varint(3, p["evidence_max_age_num_blocks"]),
        _varint(4, p["evidence_max_age_duration_ns"]),
        _varint(5, p["evidence_max_bytes"]),
        b"".join(_bytes(6, t.encode()) for t in p["pub_key_types"]),
        _varint(7, p["vote_extensions_enable_height"]),
        _varint(8, p["pbts_enable_height"]),
    ])
    return hashlib.sha256(body).digest()


def _header_fields(h: dict) -> "list[bytes]":
    """The header's fields 1-14 as the program encodes them: message bodies
    for the version, the time and the last block id, the rest bare."""
    return [h[k] for k in range(1, 15)]


def _header(h: dict) -> bytes:
    f = _header_fields(h)
    return b"".join([
        _bytes(1, f[0]), _bytes(2, f[1]), _varint(3, h["height"]), _bytes(4, f[3]),
        _bytes(5, f[4]),
    ] + [_bytes(k + 1, f[k]) for k in range(5, 14)])


def _header_hash(h: dict) -> bytes:
    f = _header_fields(h)
    return bref.merkle_root(f[:2] + [bref._uvarint(h["height"])] + f[3:])


def _commit_sig(address: bytes, time_ns: int, sig: bytes) -> bytes:
    return (_varint(1, bref.FLAG_COMMIT) + _bytes(2, address)
            + _bytes(3, _timestamp(time_ns)) + _bytes(4, sig))


def _commit(height: int, block_id: bref.BlockID, sigs: "list[bytes]") -> bytes:
    return (_varint(1, height) + _message(3, _block_id(block_id))
            + b"".join(_message(4, s) for s in sigs))


def _block(header: dict, txs: "list[bytes]", commit: bytes) -> bytes:
    return (_message(1, _header(header)) + _message(2, b"".join(_message(1, t) for t in txs))
            + _message(3, b"") + _message(4, commit))


def _wire(block: bytes) -> bytes:
    return bytes([bref.MSG_BLOCK_RESPONSE]) + _message(1, block)


def _block_id_of(header: dict, block: bytes) -> bref.BlockID:
    total, parts = bref.part_set_header(block)
    return bref.BlockID(_header_hash(header), total, parts)


# -- the plan ---------------------------------------------------------------------


def fault_plan(traffic: dict, seed: int, first: int, top: int, n: int,
               prefix: int) -> "dict[int, tuple]":
    """{B: (kind, commit index, class)} for the heights first .. top - 1."""
    classes = traffic.get("tamper_classes", chainlib.TAMPER_CLASSES)
    every, phase = int(traffic["tamper_every"]), int(traffic["tamper_phase"])
    b_every, b_phase = int(traffic["body_every"]), int(traffic["body_phase"])
    out = {}
    for b in range(max(first, 2), top):
        rng = random.Random(f"tpu-bft-bench/{seed}/bsync-fault/{b}")
        if b % every == phase:
            if (b // every) % 2 == 0:
                out[b] = ("prefix", rng.randrange(prefix), classes[(b // (2 * every)) % len(classes)])
            else:
                out[b] = ("past", prefix + rng.randrange(n - prefix),
                          classes[(b // (2 * every)) % len(classes)])
        elif b % b_every == b_phase:
            out[b] = ("body", rng.randrange(int(traffic["txs_per_block"])), "")
    return out


def ticks(bc: BlockChain, heights: "range") -> "list[Tick]":
    """The ticks of the frontier over ``heights``, the generator's verdicts:
    a faulty copy is rejected once, then the honest one applied."""
    faults = bc.faults
    out = []
    for h in heights:
        here, nxt = faults.get(h, ("",))[0], faults.get(h + 1, ("",))[0]
        if nxt == "prefix":
            index = faults[h + 1][1]
            out.append(Tick(None, h, ("rejected", h, "invalid_signature", index), 0, 1,
                            provider=peer_id(h + 1)))
        if here == "past":
            out.append(Tick(None, h, ("rejected", h, "invalid_signature", faults[h][1]), 1, 1,
                            provider=peer_id(h)))
        elif here == "body":
            out.append(Tick(None, h, ("rejected", h, "invalid_block", None), 1, 1,
                            provider=peer_id(h)))
        # the faulty peer of a past or body fault at h + 1 serves h + 1 too
        second = int(nxt in ("past", "body"))
        stored = (bc.block_hashes[h], bc.commit_roots[(h + 1, second)])
        out.append(Tick(None, h, ("applied", h, bc.app_hashes[h], h, stored), 0, second))
    return out


def peer_id(height: int) -> str:
    return f"faulty-{height}"


def count_signatures(tks: "list[Tick]", faults: dict, n: int, prefix: int) -> None:
    """Each tick's distinct triples that no tick before it needed: the light
    check of H (the prefix of commit H, as H + 1's copy carries it) and the
    full check of H's own LastCommit (commit H - 1); an altered signature,
    and every signature of a commit signed again, is a triple of its own."""
    seen: set = set()

    def triples(c: int, which: int, indices) -> set:
        kind, index, _ = faults.get(c + 1, ("", -1, ""))
        resigned = which and faults.get(c, ("",))[0] in ("past", "body")
        return {(c, i, "resigned" if resigned else
                 "altered" if which and kind in ("prefix", "past") and i == index else "")
                for i in indices}

    for t in tks:
        h = t.height
        need = triples(h, t.second, range(prefix))
        if h > 1:
            need |= triples(h - 1, t.first, range(n))
        t.signatures = len(need - seen)
        seen |= need


# -- signing and building ------------------------------------------------------------


def _keys(seed: int, n: int):
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    keys = [Ed25519PrivateKey.from_private_bytes(chainlib.validator_seed(seed, i))
            for i in range(n)]
    raw = serialization.Encoding.Raw, serialization.PublicFormat.Raw
    return keys, [k.public_key().public_bytes(*raw) for k in keys]


def _txs(seed: int, height: int, count: int, size: int) -> "list[bytes]":
    """``count`` transactions of ``size`` bytes in loadtime's shape
    (test/loadtime/payload/payload.go NewBytes): the one key ``a`` and a
    hex payload, so that the kvstore holds one key whatever the height; the
    payloads hex from the seed."""
    room = size - len(LOADTIME_KEY)
    hexed = hashlib.shake_256(f"{seed}/{height}".encode()).hexdigest(count * room // 2 + count).encode()
    return [LOADTIME_KEY + hexed[j * room:(j + 1) * room] for j in range(count)]


def _altered(sig: bytes, cls: str, resign) -> bytes:
    if cls == "flip_s":
        return sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
    if cls == "flip_r":
        return bytes([sig[0] ^ 1]) + sig[1:]
    if cls == "noncanonical_s":
        s = int.from_bytes(sig[32:], "little") + ref.L
        return sig[:32] + s.to_bytes(32, "little")
    if cls == "wrong_msg":
        return resign()
    raise ValueError(f"unknown tamper class {cls!r}")


def build(config: dict, traffic: dict, seed: int) -> BlockChain:
    """The whole chain, its faulty copies and its ticks."""
    n = int(config["validators"])
    power = int(config.get("voting_power", 10))
    keys, pubs_by_id = _keys(seed, n)
    ids = chainlib.set_order(pubs_by_id, [power] * n)
    pubs = [pubs_by_id[i] for i in ids]
    signers = [keys[i] for i in ids]
    addresses = [bref.address(p) for p in pubs]
    params = config["consensus_params"]
    bc = BlockChain(seed, config["chain_id"], pubs, [power] * n, ids,
                    int(config["app_version"]), params, consensus_hash(params))
    prefix = chainlib.Chain(seed, bc.chain_id, pubs, bc.powers, ids).light_prefix()
    n_warm = int(traffic["sync_warmup_heights"])
    top = n_warm + int(traffic["sync_heights"]) + 1
    faults = fault_plan(traffic, seed, n_warm + 2, top, n, prefix)
    classes = traffic.get("tamper_classes", chainlib.TAMPER_CLASSES)
    for k, (b, kind) in enumerate(traffic.get("warmup_faults", [])):
        rng = random.Random(f"tpu-bft-bench/{seed}/bsync-fault/{b}")
        index = {"prefix": rng.randrange(prefix), "past": prefix + rng.randrange(n - prefix),
                 "body": rng.randrange(int(traffic["txs_per_block"]))}[kind]
        faults[int(b)] = (kind, index, classes[k % len(classes)] if kind != "body" else "")
    bc.faults = faults
    jitter = max(int(traffic.get("time_jitter_ms", 500)), 1) * 10**6
    tail = canonical.vote_tail(bc.chain_id)
    vset = bref.validators_hash(list(zip(pubs, bc.powers)))
    version = _varint(1, bref.BLOCK_PROTOCOL) + _varint(2, bc.app_version)
    count, size = int(traffic["txs_per_block"]), int(traffic["tx_bytes"])

    def sign(height: int, bid: bref.BlockID, times: "list[int]") -> "list[bytes]":
        head = canonical.vote_head(height, 0, bid.hash, bid.parts_total, bid.parts_hash)
        return [k.sign(canonical.sign_bytes(head, t, tail)) for k, t in zip(signers, times)]

    def vote_times(height: int) -> "list[int]":
        rng = random.Random(f"tpu-bft-bench/{seed}/bsync-time/{height}")
        base = BASE_TIME_NS + height * 10**9
        return [base + 1 + rng.randrange(jitter - 1) for _ in range(n)]

    def header(h: int, last_id, last_commit_sigs, txs, app: bytes, results: bytes) -> dict:
        return {
            1: version, 2: bc.chain_id.encode(), 3: b"", "height": h,
            4: _timestamp(BASE_TIME_NS + h * 10**9), 5: _block_id(last_id),
            6: bref.merkle_root(last_commit_sigs), 7: bref.merkle_root(txs),
            8: vset, 9: vset, 10: bc.consensus_hash, 11: app, 12: results,
            13: bref.merkle_root([]), 14: addresses[h % n],
        }

    state = bc.genesis()
    signed = {}  # height -> its commit's vote times
    bc.honest.append(b"")
    bc.app_hashes.append(state.app_hash)
    bc.block_hashes.append(b"")
    last_id, last_sigs, last_times = bref.BlockID(b"", 0, b""), [], []
    for h in range(1, top + 1):
        txs = _txs(seed, h, count, size)
        commit_sigs = [_commit_sig(addresses[i], t, s)
                       for i, (t, s) in enumerate(zip(last_times, last_sigs))]
        hd = header(h, last_id, commit_sigs, txs, state.app_hash, state.results_hash)
        raw = _block(hd, txs, _commit(h - 1, last_id, commit_sigs))
        bid = _block_id_of(hd, raw)
        bc.honest.append(_wire(raw))
        bc.block_hashes.append(bid.hash)
        bc.commit_roots[(h, 0)] = hd[6]
        state = bref.apply(state, SimpleNamespace(txs=txs), bid)
        bc.app_hashes.append(state.app_hash)
        times = vote_times(h)
        sigs = sign(h, bid, times)
        kind = faults.get(h, ("",))[0]
        if kind:
            _faulty(bc, h, faults[h], hd, txs, last_id, last_sigs, last_times,
                    addresses, signers, tail)
        last_id, last_sigs, last_times = bid, sigs, times
        signed[h] = times
    _finish_faulty(bc, faults, addresses, sign, signed)
    all_ticks = ticks(bc, range(1, top))
    count_signatures(all_ticks, faults, n, prefix)
    for t in all_ticks:
        warm = t.height <= n_warm
        t.key = ("warm", len(bc.warm)) if warm else len(bc.pool)
        (bc.warm if warm else bc.pool).append(t)
    return bc


def _faulty(bc, h, fault, hd, txs, last_id, last_sigs, last_times, addresses,
            signers, tail) -> None:
    """The faulty peer's copy of h (its copy of h + 1 waits for h + 1's
    honest block: ``_finish_faulty``)."""
    kind, index, cls = fault
    if kind in ("prefix", "past"):
        sigs = list(last_sigs)

        def resign():
            other = canonical.vote_head(h - 1, 0, hashlib.sha256(b"forged%d" % h).digest(),
                                        last_id.parts_total, last_id.parts_hash)
            return signers[index].sign(canonical.sign_bytes(other, last_times[index], tail))

        sigs[index] = _altered(sigs[index], cls, resign)
        commit_sigs = [_commit_sig(addresses[i], t, s)
                       for i, (t, s) in enumerate(zip(last_times, sigs))]
        new = dict(hd)
        if kind == "past":  # the header rehashed over the altered LastCommit
            new[6] = bref.merkle_root(commit_sigs)
        raw = _block(new, txs, _commit(h - 1, last_id, commit_sigs))
        bc.faulty[h] = (peer_id(h), _wire(raw), new, raw)
        bc.commit_roots[(h, 1)] = bref.merkle_root(commit_sigs)
    else:  # body: one transaction altered after the header was made
        forged = list(txs)
        tx = forged[index]
        forged[index] = tx[:-1] + (b"0" if tx[-1:] != b"0" else b"1")
        commit_sigs = [_commit_sig(addresses[i], t, s)
                       for i, (t, s) in enumerate(zip(last_times, last_sigs))]
        raw = _block(hd, forged, _commit(h - 1, last_id, commit_sigs))
        bc.faulty[h] = (peer_id(h), _wire(raw), hd, raw)
        bc.commit_roots[(h, 1)] = hd[6]


def _finish_faulty(bc, faults, addresses, sign, signed) -> None:
    """A past or body fault's copy of B + 1: the honest block with its
    LastCommit signed over the faulty B (the same vote times), rehashed."""
    for b, (kind, _, _) in faults.items():
        peer, wire, hd, raw = bc.faulty[b]
        bc.faulty[b] = (peer, wire)
        if kind == "prefix":
            continue
        bid = _block_id_of(hd, raw)
        times = signed[b]
        commit_sigs = [_commit_sig(addresses[i], t, s)
                       for i, (t, s) in enumerate(zip(times, sign(b, bid, times)))]
        honest = bref.decode(bc.honest[b + 1])
        new = {k: bytes(honest.header.get(k, b"")) for k in range(1, 15) if k != 3}
        new.update({3: b"", "height": b + 1, 5: _block_id(bid),
                    6: bref.merkle_root(commit_sigs)})
        bc.commit_roots[(b + 1, 1)] = new[6]
        bc.faulty[b + 1] = (peer, _wire(_block(new, honest.txs, _commit(b, bid, commit_sigs))))


def spot_check(bc: BlockChain, sample: int = 4) -> None:
    """Hold the host library to the plain reference: a few validators' keys
    and their signatures in the LastCommit of the first pool height."""
    rng = random.Random(f"tpu-bft-bench/{bc.seed}/bsync-spot")
    h = bc.pool[0].height + 1
    block = bref.decode(bc.honest[h])
    for index in rng.sample(range(len(bc.pubs)), min(sample, len(bc.pubs))):
        vseed = chainlib.validator_seed(bc.seed, bc.ids[index])
        if ref.pubkey_from_seed(vseed) != bc.pubs[index]:
            raise RuntimeError(f"host library's public key {index} differs")
        msg = bref.vote_sign_bytes(bc.chain_id, block.last_commit, index)
        if not ref.verify_zip215(bc.pubs[index], msg, block.last_commit.sigs[index].signature):
            raise RuntimeError(f"host library's signature {index} differs")


def fingerprint(bc: BlockChain) -> str:
    """One hash over everything generated: same seed, same bytes."""
    h = hashlib.sha256()
    for wire in bc.honest:
        h.update(hashlib.sha256(wire).digest())
    for b in sorted(bc.faulty):
        h.update(struct.pack(">q", b) + hashlib.sha256(bc.faulty[b][1]).digest())
    h.update(json.dumps([(str(t.key), t.height, t.expected[:2], t.first, t.second,
                          t.provider, t.signatures) for t in bc.warm + bc.pool]).encode())
    return h.hexdigest()
