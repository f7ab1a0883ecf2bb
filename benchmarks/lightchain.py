"""The light client's generator, beside ``chain.py``: a chain whose validator
set changes, as light blocks (header, commit, validator set) in plain bytes,
and the requests a skipping light client makes over it, from ``--seed``.

A pure function of (configuration, traffic file, seed), whatever the number
of workers.  Nothing here imports the program or jax.  Hashes come from the
benchmark's own encoders (``light_ref.header_hash`` / ``validators_hash``),
sign-bytes from ``canonical.py``; commits are signed with the host library in
``chain.SignPool``'s spawned workers.

The validators are a universe of ``validator_universe`` identities (keys from
``chain.validator_seed``), of which ``validators`` sit in the set at any
height; a step replaces some of them with validators from outside.  Every
request has a new block of its own (none is verified twice).  Request k's
trusted block is the newest block the client has accepted, which the
generator knows, because it knows every verdict:

    skip       h + jump (2..1,000 from the seed), ``churn_skipping`` replaced
    adjacent   h + 1, ``churn_adjacent`` replaced; the trusted block's
               ``next_validators_hash`` is this block's ``validators_hash``
    tampered   a skip with one signature altered, alternately inside the
               trusting pass's prefix and where only the light pass reads;
               the client does not move
    too_far    a block whose set keeps ``too_far_kept`` of the trusted set
               (under 1/3 of its power); the client does not move
    broken_link  a block every signature of which holds and ONE hash link of
               which does not (``LINKS``, cycled): the commit signs another
               header than the one sent; the header names another set than
               the one sent; or, on a step to h + 1, the set is not the one
               the trusted header announced.  A program that left that one
               check out would accept it; the client does not move

What a traffic file may say: ``requests`` (pool size), ``period`` and
``adjacent_at`` (one adjacent step a period), ``tamper_every`` /
``tamper_phase`` / ``tamper_classes``, ``too_far_every`` / ``too_far_phase``,
``broken_link_every`` / ``broken_link_phase``, ``warmup_kinds`` (requests of
their own before the window), ``time_jitter_ms``, ``now_after_s``.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import random
from dataclasses import dataclass, field
from typing import NamedTuple

from benchmarks import canonical, chain as chainlib, light_ref, manifest
from benchmarks import ed25519_ref as ref

BLOCK_TIME_NS = 10**9
LINKS = ("header_hash", "validators_hash", "next_validators_hash")
WARM_KINDS = ("skip", "adjacent", "too_far", "tampered_in", "tampered_out",
              "tampered_in", "tampered_out", "skip") + tuple("broken_" + l for l in LINKS)


def _h(*parts) -> bytes:
    return hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()


class Spec(NamedTuple):
    """What a worker needs to make one block."""
    key: object  # request number; "root" and ("warm", k) before the pool
    height: int
    ids: "tuple[int, ...]"  # validator identities in the set's order
    next_ids: "tuple[int, ...] | None"  # the set at height + 1, where it differs
    wrong: str = ""  # the one of ``LINKS`` this block breaks


class Block(NamedTuple):
    key: object
    ids: "tuple[int, ...]"
    header: light_ref.Header
    commit: light_ref.Commit


@dataclass
class Request:
    key: object
    kind: str  # skip | adjacent | tampered | too_far | broken_link
    trusted: object  # key of the trusted block
    now_s: float
    expected: tuple
    signatures: int  # distinct triples whose verdict the request needed
    tamper: "tuple[int, str, str] | None" = None  # (commit index, class, in | out)
    link: str = ""  # the broken one of ``LINKS``

    @property
    def new(self):
        """Key of the new block: every request has a block of its own."""
        return self.key


@dataclass
class Light:
    seed: int
    chain_id: str
    power: int
    trusting_period_s: float
    trust: "tuple[int, int]"
    pubs: "list[bytes]"  # by identity, the whole universe
    blocks: dict = field(default_factory=dict)
    pool: "list[Request]" = field(default_factory=list)
    warm: "list[Request]" = field(default_factory=list)

    def light_block(self, key) -> light_ref.LightBlock:
        b = self.blocks[key]
        return light_ref.LightBlock(
            b.header, b.commit, [(self.pubs[i], self.power) for i in b.ids]
        )


def cell_files(chain_id: str) -> "tuple[dict, dict]":
    """The configuration whose chain id this is and the traffic file of the
    cell that runs it through ``light_verify``.  The harness hands an entry
    the chain only, so the cell is found again by its chain id: where two
    cells would answer, this raises, and ``harness`` has to hand the entry
    its cell first (``PERF.md`` section 7).  The tests put small files here."""
    m = manifest.load()
    found = []
    for w in m["workloads"]:
        cfg = next(c for c in m["configs"] if c["name"] == w["config"])
        config = manifest._json(os.path.join(manifest.ROOT, cfg["file"]))
        traffic = manifest._json(
            os.path.join(manifest.HERE, "traffic", w["traffic"] + ".json")
        )
        if config.get("chain_id") == chain_id and traffic["entry"] == "light_verify":
            found.append((w["traffic"], config, traffic))
    if len({name for name, _, _ in found}) != 1:
        raise KeyError(
            f"{len(found)} light_verify cells for chain id {chain_id!r}: "
            f"{[name for name, _, _ in found]}"
        )
    return found[0][1:]


# -- the plan: kinds, heights and sets, with no signature --------------------------


def kind_of(traffic: dict, k: int) -> str:
    every = int(traffic.get("too_far_every", 0))
    if every and k % every == int(traffic.get("too_far_phase", 0)):
        return "too_far"
    every = int(traffic.get("tamper_every", 0))
    if every and k % every == int(traffic.get("tamper_phase", every // 2)):
        t = k // every
        return "tampered_in" if (t + t // 4) % 2 == 0 else "tampered_out"
    every = int(traffic.get("broken_link_every", 0))
    if every and k % every == int(traffic.get("broken_link_phase", 0)):
        return "broken_" + LINKS[k // every % len(LINKS)]
    if k % int(traffic.get("period", 8)) == int(traffic.get("adjacent_at", 6)):
        return "adjacent"
    return "skip"


def _replace(rng, ids, universe: int, out: int, order) -> "tuple[int, ...]":
    """``ids`` with ``out`` of them replaced from outside, in the set's order
    (equal powers: address ascending)."""
    leaving = set(rng.sample(sorted(ids), out))
    inside = set(ids)
    joining = rng.sample([i for i in range(universe) if i not in inside], out)
    return tuple(sorted([i for i in ids if i not in leaving] + joining,
                        key=order.__getitem__))


def plan(config: dict, traffic: dict, seed: int, addresses: "list[bytes]"):
    """(specs, requests without signatures): every block's height and set,
    every request's kind and trusted block."""
    n, universe = int(config["validators"]), int(config["validator_universe"])
    order = {i: a for i, a in enumerate(addresses)}
    rng = random.Random(f"tpu-bft-bench/{seed}/light-plan")
    ids = tuple(sorted(rng.sample(range(universe), n), key=order.__getitem__))
    lo, hi = int(config["jump_heights"][0]), int(config["jump_heights"][1])
    kinds = [(("warm", k), kd) for k, kd in
             enumerate(traffic.get("warmup_kinds", WARM_KINDS))]
    kinds += [(k, kind_of(traffic, k)) for k in range(int(traffic["requests"]))]
    specs = {"root": Spec("root", 1, ids, None)}
    requests = []
    trusted = "root"
    for key, kind in kinds:
        t = specs[trusted]
        if kind in ("adjacent", "broken_next_validators_hash"):
            new_ids = _replace(rng, t.ids, universe, int(config["churn_adjacent"]), order)
            if kind == "adjacent":  # the broken one is a set the trusted header never named
                specs[trusted] = t._replace(next_ids=new_ids)
            height = t.height + 1
        else:
            out = (n - int(config["too_far_kept"]) if kind == "too_far"
                   else int(config["churn_skipping"]))
            new_ids = _replace(rng, t.ids, universe, out, order)
            height = t.height + rng.randint(lo, hi)
        specs[key] = Spec(key, height, new_ids, None,
                          kind[len("broken_"):] if kind.startswith("broken_") else "")
        requests.append((key, kind, trusted))
        if kind in ("skip", "adjacent"):
            trusted = key
    return list(specs.values()), requests


# -- workers: the host library, the benchmark's encoders ----------------------------


def block_time_ns(height: int) -> int:
    return chainlib.BASE_TIME_NS + height * BLOCK_TIME_NS


def _header(seed: int, chain_id: str, spec: Spec, pubs, power: int):
    vh = light_ref.validators_hash([(pubs[i], power) for i in spec.ids])
    nvh = vh if spec.next_ids is None else light_ref.validators_hash(
        [(pubs[i], power) for i in spec.next_ids])
    if spec.wrong == "validators_hash":  # signed as it is: only the link is broken
        vh = _h(seed, "another-set", spec.key)
    rng = random.Random(f"tpu-bft-bench/{seed}/light-header/{spec.key}")
    return light_ref.Header(
        version_block=11, version_app=1, chain_id=chain_id, height=spec.height,
        time_ns=block_time_ns(spec.height),
        last_block_id=light_ref.BlockID(
            _h(seed, "last", spec.key), 1, _h(seed, "last-parts", spec.key)),
        last_commit_hash=_h(seed, "last-commit", spec.key),
        data_hash=_h(seed, "data", spec.key),
        validators_hash=vh, next_validators_hash=nvh,
        consensus_hash=_h(seed, "consensus"),
        app_hash=_h(seed, "app", spec.key),
        last_results_hash=_h(seed, "results", spec.key),
        evidence_hash=hashlib.sha256(b"").digest(),
        proposer_address=light_ref.address(pubs[rng.choice(spec.ids)]),
    )


def vote_times_ns(seed: int, spec: Spec, jitter_ms: int) -> "list[int]":
    """Each validator stamps its precommit with its own clock, after the
    block's time, never on a whole second."""
    rng = random.Random(f"tpu-bft-bench/{seed}/light-time/{spec.key}")
    base = block_time_ns(spec.height)
    span = max(jitter_ms, 1) * 10**6
    return [base + 1 + rng.randrange(span - 1) for _ in spec.ids]


@functools.lru_cache(maxsize=1)
def _keys(seed: int, universe: int):
    """(private keys, public keys) of the whole universe, by identity: once
    a process."""
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    keys = [
        Ed25519PrivateKey.from_private_bytes(chainlib.validator_seed(seed, i))
        for i in range(universe)
    ]
    raw = serialization.Encoding.Raw, serialization.PublicFormat.Raw
    return keys, [k.public_key().public_bytes(*raw) for k in keys]


def _sign_blocks(args):
    """[(key, header, the hash the commit signs, parts hash, times,
    signatures blob)] of ``specs``."""
    seed, chain_id, universe, power, jitter_ms, specs = args
    keys, pubs = _keys(seed, universe)
    tail = canonical.vote_tail(chain_id)
    out = []
    for spec in specs:
        header = _header(seed, chain_id, spec, pubs, power)
        parts = _h(seed, "parts", spec.key)
        signed = light_ref.header_hash(header)
        if spec.wrong == "header_hash":  # the header sent is not the one signed
            header = header._replace(app_hash=_h(seed, "another-app", spec.key))
        head = canonical.vote_head(spec.height, 0, signed, 1, parts)
        times = vote_times_ns(seed, spec, jitter_ms)
        sigs = b"".join(
            keys[i].sign(canonical.sign_bytes(head, t, tail))
            for i, t in zip(spec.ids, times)
        )
        out.append((spec.key, header, signed, parts, times, sigs))
    return out


# -- the chain ---------------------------------------------------------------------


def _tampered_sig(light: Light, block: Block, index: int, cls: str) -> bytes:
    sig = block.commit.sigs[index].signature
    if cls == "flip_s":
        return sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
    if cls == "flip_r":
        return bytes([sig[0] ^ 1]) + sig[1:]
    if cls == "noncanonical_s":
        s = int.from_bytes(sig[32:], "little") + ref.L
        return sig[:32] + s.to_bytes(32, "little")
    if cls == "wrong_msg":  # a sound signature of the same validator over another block
        c = block.commit
        other = canonical.sign_bytes(
            canonical.vote_head(c.height, 0, _h("forged", c.height), 1,
                                c.block_id.parts_hash),
            c.sigs[index].time_ns, canonical.vote_tail(light.chain_id))
        return ref.sign(chainlib.validator_seed(light.seed, block.ids[index]), other)
    raise ValueError(f"unknown tamper class {cls!r}")


def trusting_prefix(trusted_ids, new_ids, power: int, trust) -> "list[int]":
    """Commit indices whose signatures the trusting pass checks: the new
    commit's entries whose signer is in the trusted set, in order, until more
    than the trust level of the trusted power is tallied.  Empty where the
    whole commit does not carry that much (too far)."""
    inside = set(trusted_ids)
    needed = len(trusted_ids) * power * trust[0] // trust[1]
    tallied, picked = 0, []
    for index, i in enumerate(new_ids):
        if i in inside:
            picked.append(index)
            tallied += power
            if tallied > needed:
                return picked
    return []


def light_prefix(n: int) -> int:
    """How many signatures the light pass checks (equal powers): it stops
    past 2/3 of the power."""
    return n * 2 // 3 + 1


def _finish(light: Light, traffic: dict, raw_requests) -> None:
    """Tamper where the plan says, and give every request its verdict, its
    signatures and its ``now``."""
    classes = traffic.get("tamper_classes", chainlib.TAMPER_CLASSES)
    every = int(traffic.get("tamper_every", 1)) or 1
    after = float(traffic.get("now_after_s", 5.0))
    warm_tampered = 0
    for key, kind, trusted in raw_requests:
        new = light.blocks[key]
        n = len(new.ids)
        picked = trusting_prefix(light.blocks[trusted].ids, new.ids, light.power,
                                 light.trust)
        req = Request(key, kind, trusted,
                      new.header.time_ns / 1e9 + after, ("accepted",),
                      len(set(picked) | set(range(light_prefix(n)))))
        if kind == "too_far":
            if picked:
                raise RuntimeError("a too-far block that the trusted set can carry")
            req.expected, req.signatures = ("cant_be_trusted",), 0
        elif kind.startswith("broken_"):
            req.kind, req.link = "broken_link", kind[len("broken_"):]
            req.expected, req.signatures = ("invalid_header",), 0
            _hold_broken(light, req)
        elif kind.startswith("tampered"):
            rng = random.Random(f"tpu-bft-bench/{light.seed}/light-tamper/{key}")
            where = kind.split("_")[1]
            if where == "in":
                index = rng.choice(picked)
                req.signatures = len(picked)
            else:
                inside = set(picked)
                index = rng.choice(
                    [i for i in range(light_prefix(n)) if i not in inside])
            if isinstance(key, tuple):  # warm-up: every class in turn
                t, warm_tampered = warm_tampered, warm_tampered + 1
            else:
                t = key // every
            cls = classes[t % len(classes)]
            sigs = list(new.commit.sigs)
            sigs[index] = sigs[index]._replace(
                signature=_tampered_sig(light, new, index, cls))
            light.blocks[key] = new._replace(commit=new.commit._replace(sigs=sigs))
            req.kind = "tampered"
            req.expected, req.tamper = ("invalid_signature", index), (index, cls, where)
        (light.warm if isinstance(key, tuple) else light.pool).append(req)


def _hold_broken(light: Light, req: Request) -> None:
    """A broken-link block breaks the link it names and no other."""
    trusted, new = light.blocks[req.trusted], light.blocks[req.new]
    vh = light_ref.validators_hash([(light.pubs[i], light.power) for i in new.ids])
    broken = {
        "header_hash": new.commit.block_id.hash != light_ref.header_hash(new.header),
        "validators_hash": new.header.validators_hash != vh,
        "next_validators_hash": (
            new.header.height == trusted.header.height + 1
            and new.header.validators_hash != trusted.header.next_validators_hash),
    }
    if [link for link, is_broken in broken.items() if is_broken] != [req.link]:
        raise RuntimeError(f"request {req.key} breaks {broken}, not {req.link} alone")


def build(config: dict, traffic: dict, seed: int, pool=None) -> Light:
    """The whole chain.  ``pool`` is a ``chain.SignPool``; without one, one
    is started and closed here."""
    universe = int(config["validator_universe"])
    power = int(config.get("voting_power", 10))
    pubs = _keys(seed, universe)[1]
    specs, raw_requests = plan(
        config, traffic, seed, [light_ref.address(p) for p in pubs])
    own = pool is None
    pool = pool or chainlib.SignPool()
    try:
        step = max(1, -(-len(specs) // (8 * pool.workers)))
        tasks = [
            (seed, config["chain_id"], universe, power,
             int(traffic.get("time_jitter_ms", 500)), specs[k:k + step])
            for k in range(0, len(specs), step)
        ]
        made = [b for part in pool.map_async(_sign_blocks, tasks).get() for b in part]
    finally:
        if own:
            pool.close()
    num, den = (int(x) for x in str(config.get("trust_level", "1/3")).split("/"))
    light = Light(seed, config["chain_id"], power,
                  float(config["trusting_period_s"]), (num, den), pubs)
    by_key = {s.key: s for s in specs}
    for key, header, signed, parts, times, sigs in made:
        spec = by_key[key]
        commit = light_ref.Commit(
            spec.height, 0, light_ref.BlockID(signed, 1, parts),
            [light_ref.CommitSig(light_ref.FLAG_COMMIT, light_ref.address(pubs[i]), t,
                                 sigs[64 * k:64 * k + 64])
             for k, (i, t) in enumerate(zip(spec.ids, times))])
        light.blocks[key] = Block(key, spec.ids, header, commit)
    _finish(light, traffic, raw_requests)
    return light


def spot_check(light: Light, sample: int = 4) -> None:
    """Hold the host library to the plain reference on a seeded sample of
    the first pool block's signatures, over ``light_ref``'s own sign-bytes."""
    rng = random.Random(f"tpu-bft-bench/{light.seed}/light-spot")
    block = light.blocks[light.pool[0].new]
    tampered = light.pool[0].tamper
    for index in rng.sample(range(len(block.ids)), min(sample, len(block.ids))):
        if tampered and tampered[0] == index:
            continue
        vseed = chainlib.validator_seed(light.seed, block.ids[index])
        if ref.pubkey_from_seed(vseed) != light.pubs[block.ids[index]]:
            raise RuntimeError(f"host library's public key {index} differs")
        msg = light_ref.vote_sign_bytes(light.chain_id, block.commit, index)
        if ref.sign(vseed, msg) != block.commit.sigs[index].signature:
            raise RuntimeError(f"host library's signature {index} differs")


def fingerprint(light: Light) -> str:
    """One hash over everything generated: same seed, same bytes."""
    h = hashlib.sha256()
    for key in sorted(light.blocks, key=str):
        b = light.blocks[key]
        h.update(repr((b.key, b.ids, b.header, b.commit)).encode())
    h.update(json.dumps(
        [(str(r.key), r.kind, str(r.trusted), r.now_s, r.expected, r.signatures,
          r.tamper, r.link) for r in light.warm + light.pool]).encode())
    return h.hexdigest()
