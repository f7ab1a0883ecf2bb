"""The sequential light client's generator, beside ``lightchain.py`` (whose
keys, headers, vote times and altered signatures it uses): one chain of
light blocks whose set changes by one validator at one height in
``change_every``, and the requests a ``cometbft light --sequential`` client
makes over it (light/client.go:608 verifySequential), from ``--seed``.

A pure function of (configuration, traffic file, seed), whatever the number
of workers.  Nothing here imports the program or jax.  Hashes come from the
benchmark's own encoders (``light_ref``), sign-bytes from ``canonical.py``;
commits are signed with the host library in ``chain.SignPool``'s spawned
workers, and only the prefix that ``VerifyCommitLight`` reads: with equal
powers it stops past 2/3 of the power, at ``lightchain.light_prefix``.  The
rest of a commit is well-formed bytes that neither the program nor the
reference reads.

Request k asks for ``trusted + n_k``, where ``trusted`` is the newest block
the client has accepted (the generator knows every verdict) and ``n_k``
comes from a fixed cycle of ``lengths``, shuffled per cycle from the seed.
Every header in between is served honest, but for the one a fault names:

    honest        every header verified adjacently; the client moves
    tampered      one signature of one header's prefix altered (the classes
                  cycled); verdict ("invalid_signature", height, index).
                  Where the run goes on, a header at most
                  ``later_link_within`` after it has a hash link broken too
                  (the kinds cycled): the first bad header by height decides
    broken_link   one hash link of one header broken, every signature sound
                  (``lightchain.LINKS`` cycled: the commit signs another
                  header; the header names another set; the set is not the
                  one the header before announced); ("invalid_header",
                  height)

After a fault the client stays, and the next request walks honest headers
from the same block.  A request's ``signatures`` are the distinct triples
its verdict needed and no earlier request's had: the headers a failed
request verified are cache hits for the requests after it.

What a traffic file may say: ``headers`` (the pool), ``lengths``,
``tamper_every`` / ``tamper_phase`` / ``tamper_classes``,
``broken_link_every`` / ``broken_link_phase``, ``later_link_within``,
``warmup`` ([kind, length]
requests of their own before the window), ``reference_headers`` (headers the
reference replays of an accepted request), ``time_jitter_ms``,
``now_after_s``.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
from dataclasses import dataclass, field

from benchmarks import canonical, chain as chainlib, light_ref, lightchain, manifest
from benchmarks import ed25519_ref as ref

ENTRY = "light_sequential"


@dataclass
class Request:
    key: object  # request number; ("warm", k) before the pool
    kind: str  # honest | tampered | broken_link
    trusted: int  # the height of the block the client holds
    target: int
    now_s: float
    expected: tuple
    signatures: int = 0  # distinct triples the verdict needed, new to the run
    bad: int = 0  # the height of the first faulty header: the verdict's
    tamper: "tuple[int, str] | None" = None  # there: (commit index, class)
    links: dict = field(default_factory=dict)  # height -> a broken one of LINKS
    checked: "tuple[int, ...]" = ()  # what the reference replays: h-1 -> h

    @property
    def link(self) -> str:
        """The broken link that decides the verdict, if one does."""
        return self.links.get(self.bad, "")

    def served(self, height: int):
        """Key of the block the primary serves at ``height``: the honest
        one, or this request's own where it is faulty."""
        if height == self.bad or height in self.links:
            return ("variant", str(self.key), height)
        return height


def cell_files(chain_id: str) -> "tuple[dict, dict]":
    """The configuration whose chain id this is and the traffic file of the
    cell that runs it through ``light_sequential``: the harness hands an
    entry the chain only (``lightchain.cell_files``' way)."""
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


# -- the plan: sets, requests and faults, with no signature ------------------------


def kind_of(traffic: dict, k: int) -> str:
    every = int(traffic.get("tamper_every", 0))
    if every and k % every == int(traffic.get("tamper_phase", every // 2)):
        return "tampered"
    every = int(traffic.get("broken_link_every", 0))
    if every and k % every == int(traffic.get("broken_link_phase", 0)):
        return "broken_" + lightchain.LINKS[k // every % len(lightchain.LINKS)]
    return "honest"


def lengths(traffic: dict, seed: int, k: int) -> int:
    """Request k's length: the fixed cycle, shuffled per cycle from the
    seed, so that every run holds each length as often."""
    cycle = list(traffic["lengths"])
    random.Random(f"tpu-bft-bench/{seed}/seq-cycle/{k // len(cycle)}").shuffle(cycle)
    return int(cycle[k % len(cycle)])


def sets(config: dict, seed: int, top: int, addresses) -> dict:
    """{height: validator identities in the set's order} for 1 .. top: one
    validator replaced at each height ``h % change_every == change_phase``."""
    n, universe = int(config["validators"]), int(config["validator_universe"])
    order = dict(enumerate(addresses))
    rng = random.Random(f"tpu-bft-bench/{seed}/seq-sets")
    ids = tuple(sorted(rng.sample(range(universe), n), key=order.__getitem__))
    every, phase = int(config["change_every"]), int(config["change_phase"])
    out = {}
    for h in range(1, top + 1):
        if h > 1 and h % every == phase:
            ids = lightchain._replace(rng, ids, universe, int(config["churn_per_change"]),
                                      order)
        out[h] = ids
    return out


def plan(config: dict, traffic: dict, seed: int) -> "list[Request]":
    """Every request in the order it is sent, warm-up first, with its fault
    placed; ``signatures``, ``now_s`` and ``checked`` come later."""
    rng = random.Random(f"tpu-bft-bench/{seed}/seq-plan")
    classes = traffic.get("tamper_classes", chainlib.TAMPER_CLASSES)
    prefix = lightchain.light_prefix(int(config["validators"]))
    reach = int(traffic.get("later_link_within", 7))
    out, trusted, tampered = [], 1, 0

    def add(key, kind: str, n: int, number: int):
        nonlocal trusted
        req = Request(key, kind, trusted, trusted + n, 0.0, ("accepted",))
        if kind != "honest":
            place = rng.randint(1, n)
            req.bad = trusted + place
        if kind == "tampered":
            req.tamper = (rng.randrange(prefix), classes[number % len(classes)])
            req.expected = ("invalid_signature", req.bad, req.tamper[0])
            if place < n:  # and a broken link in a later header of its reach
                later = req.bad + rng.randint(1, min(n - place, reach))
                req.links[later] = lightchain.LINKS[number % len(lightchain.LINKS)]
        elif kind.startswith("broken_"):
            req.kind = "broken_link"
            req.links[req.bad] = kind[len("broken_"):]
            req.expected = ("invalid_header", req.bad)
        else:
            trusted = req.target
        out.append(req)

    for k, (kind, n) in enumerate(traffic.get("warmup", [])):
        add(("warm", k), kind, int(n), tampered)
        tampered += kind == "tampered"
    top = trusted + int(traffic["headers"])
    every = int(traffic.get("tamper_every", 1)) or 1
    k = 0
    while trusted + lengths(traffic, seed, k) <= top:
        add(k, kind_of(traffic, k), lengths(traffic, seed, k), k // every)
        k += 1
    return out


def count_signatures(requests: "list[Request]", prefix: int) -> None:
    """Each request's distinct new triples, in the order they are sent: the
    headers it verifies (up to and with the faulty one) less what earlier
    requests verified; a tampered header's altered triple is new, the rest
    of it is the honest header's; a broken link is refused before any of
    its signatures is read."""
    left: dict = {}  # height -> its prefix's indices not counted yet

    def honest(h: int, but: int = -1) -> int:
        todo = left.get(h)
        todo = set(range(prefix)) if todo is None else todo
        left[h] = {but} & todo
        return len(todo - {but})

    for req in requests:
        last = req.target + 1 if req.kind == "honest" else req.bad
        n = sum(honest(h) for h in range(req.trusted + 1, last))
        if req.kind == "tampered":
            n += honest(req.bad, but=req.tamper[0]) + 1
        req.signatures = n


# -- workers: the host library, the benchmark's encoders ----------------------------


def _unread(seed: int, key, index: int) -> bytes:
    """A signature nobody reads: 64 bytes of its own."""
    return hashlib.sha512(f"tpu-bft-bench/{seed}/unread/{key}/{index}".encode()).digest()


def _sign_headers(args):
    """[(key, header, the hash the commit signs, parts hash, times,
    signatures blob)] of ``specs``: the first ``prefix`` signed, the rest
    ``_unread``."""
    seed, chain_id, universe, power, jitter_ms, prefix, specs = args
    keys, pubs = lightchain._keys(seed, universe)
    tail = canonical.vote_tail(chain_id)
    out = []
    for spec in specs:
        header = lightchain._header(seed, chain_id, spec, pubs, power)
        parts = lightchain._h(seed, "parts", spec.key)
        signed = light_ref.header_hash(header)
        head = canonical.vote_head(spec.height, 0, signed, 1, parts)
        times = lightchain.vote_times_ns(seed, spec, jitter_ms)
        sigs = b"".join(
            keys[i].sign(canonical.sign_bytes(head, t, tail))
            for i, t in zip(spec.ids[:prefix], times)
        ) + b"".join(_unread(seed, spec.key, k) for k in range(prefix, len(spec.ids)))
        out.append((spec.key, header, signed, parts, times, sigs))
    return out


# -- the chain ---------------------------------------------------------------------


def _specs(requests, ids: dict, universe: int, order: dict, seed: int) -> list:
    """Every honest block, then every variant that needs signatures of its
    own (a set the header names otherwise, a set nobody announced)."""
    top = max(ids)
    specs = [
        lightchain.Spec(h, h, ids[h], None if ids.get(h + 1, ids[h]) == ids[h]
                        else ids[h + 1])
        for h in range(1, top)
    ]
    rng = random.Random(f"tpu-bft-bench/{seed}/seq-variants")
    for req in requests:
        for h, link in sorted(req.links.items()):
            nxt = None if ids[h + 1] == ids[h] else ids[h + 1]
            if link == "validators_hash":
                specs.append(lightchain.Spec(req.served(h), h, ids[h], nxt, link))
            elif link == "next_validators_hash":
                other = lightchain._replace(rng, ids[h], universe, 1, order)
                specs.append(lightchain.Spec(req.served(h), h, other, ids[h + 1]))
    return specs


def build(config: dict, traffic: dict, seed: int, pool=None) -> lightchain.Light:
    """The whole chain.  ``pool`` is a ``chain.SignPool``; without one, one
    is started and closed here."""
    universe = int(config["validator_universe"])
    power = int(config.get("voting_power", 10))
    pubs = lightchain._keys(seed, universe)[1]
    addresses = [light_ref.address(p) for p in pubs]
    prefix = lightchain.light_prefix(int(config["validators"]))
    requests = plan(config, traffic, seed)
    top = max(r.target for r in requests) + 2
    ids = sets(config, seed, top, addresses)
    specs = _specs(requests, ids, universe, dict(enumerate(addresses)), seed)
    own = pool is None
    pool = pool or chainlib.SignPool()
    try:
        step = max(1, -(-len(specs) // (8 * pool.workers)))
        tasks = [
            (seed, config["chain_id"], universe, power,
             int(traffic.get("time_jitter_ms", 500)), prefix, specs[k:k + step])
            for k in range(0, len(specs), step)
        ]
        made = [b for part in pool.map_async(_sign_headers, tasks).get() for b in part]
    finally:
        if own:
            pool.close()
    num, den = (int(x) for x in str(config.get("trust_level", "1/3")).split("/"))
    seq = lightchain.Light(seed, config["chain_id"], power,
                           float(config["trusting_period_s"]), (num, den), pubs)
    by_key = {s.key: s for s in specs}
    for key, header, signed, parts, times, sigs in made:
        spec = by_key[key]
        commit = light_ref.Commit(
            spec.height, 0, light_ref.BlockID(signed, 1, parts),
            [light_ref.CommitSig(light_ref.FLAG_COMMIT, addresses[i], t,
                                 sigs[64 * k:64 * k + 64])
             for k, (i, t) in enumerate(zip(spec.ids, times))])
        seq.blocks[key] = lightchain.Block(key, spec.ids, header, commit)
    _finish(seq, traffic, requests, prefix)
    return seq


def _finish(seq: lightchain.Light, traffic: dict, requests, prefix: int) -> None:
    """The variants that need no signing of their own (an altered signature;
    a header the commit did not sign), each request's ``now``, what the
    reference replays, and the signatures counted."""
    after = float(traffic.get("now_after_s", 5.0))
    replays = int(traffic.get("reference_headers", 2))
    for req in requests:
        if req.tamper:
            index, cls = req.tamper
            honest, key = seq.blocks[req.bad], req.served(req.bad)
            sigs = list(honest.commit.sigs)
            sigs[index] = sigs[index]._replace(
                signature=lightchain._tampered_sig(seq, honest, index, cls))
            seq.blocks[key] = honest._replace(
                key=key, commit=honest.commit._replace(sigs=sigs))
        for h, link in req.links.items():
            if link == "header_hash":  # the header sent is not the one signed
                honest, key = seq.blocks[h], req.served(h)
                seq.blocks[key] = honest._replace(
                    key=key, header=honest.header._replace(
                        app_hash=lightchain._h(seq.seed, "another-app", key)))
        req.now_s = seq.blocks[req.target].header.time_ns / 1e9 + after
        if req.bad:
            req.checked = (req.bad,)
        else:
            rng = random.Random(f"tpu-bft-bench/{seq.seed}/seq-checked/{req.key}")
            span = range(req.trusted + 1, req.target + 1)
            req.checked = tuple(sorted(rng.sample(span, min(replays, len(span)))))
        (seq.warm if isinstance(req.key, tuple) else seq.pool).append(req)
    count_signatures(seq.warm + seq.pool, prefix)


def spot_check(seq: lightchain.Light, sample: int = 4) -> None:
    """Hold the host library to the plain reference on a seeded sample of
    the first pool header's signed prefix, over ``light_ref``'s sign-bytes."""
    rng = random.Random(f"tpu-bft-bench/{seq.seed}/seq-spot")
    block = seq.blocks[seq.pool[0].trusted + 1]
    prefix = lightchain.light_prefix(len(block.ids))
    for index in rng.sample(range(prefix), min(sample, prefix)):
        vseed = chainlib.validator_seed(seq.seed, block.ids[index])
        if ref.pubkey_from_seed(vseed) != seq.pubs[block.ids[index]]:
            raise RuntimeError(f"host library's public key {index} differs")
        msg = light_ref.vote_sign_bytes(seq.chain_id, block.commit, index)
        if ref.sign(vseed, msg) != block.commit.sigs[index].signature:
            raise RuntimeError(f"host library's signature {index} differs")


def fingerprint(seq: lightchain.Light) -> str:
    """One hash over everything generated: same seed, same bytes."""
    h = hashlib.sha256()
    for key in sorted(seq.blocks, key=str):
        b = seq.blocks[key]
        h.update(repr((b.key, b.ids, b.header, b.commit)).encode())
    h.update(json.dumps(
        [(str(r.key), r.kind, r.trusted, r.target, r.now_s, r.expected, r.signatures,
          r.bad, r.tamper, sorted(r.links.items()), r.checked)
         for r in seq.warm + seq.pool]).encode())
    return h.hexdigest()
