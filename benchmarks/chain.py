"""The one general generator: a validator set and a pool of signed heights
from ``--seed``, as plain bytes.

A pure function of (configuration, traffic file, seed): the same three give
the same bytes whatever the number of workers.  Nothing here imports the
program or jax; an entry (``benchmarks/entries``) turns the bytes into the
program's objects.  The other nodes of the deployment exist only as this
pre-signed traffic.

What a traffic file may say (all optional but ``entry`` and ``loop``):

    heights            pool size: an int, or {config name: int}; a config that
                       is not listed gets ``pool_signatures // validators``
    pool_signatures    see above
    warmup_heights     honest heights verified before the window (own heights)
    warmup_tampered    tampered heights verified before the window
    tamper_every,      heights h with h % tamper_every == tamper_phase carry one
    tamper_phase       tampered signature inside the verified prefix
    tamper_classes     cycled in this order by h // tamper_every
    time_jitter_ms     spread of the validators' own vote timestamps

Signing runs in spawned workers that import this module and the host library
only (``SignPool``); the caller starts them before it imports jax, so that
they sign while the chip is found.
"""

from __future__ import annotations

import hashlib
import multiprocessing
import os
import random
from dataclasses import dataclass, field

from benchmarks import canonical
from benchmarks import ed25519_ref as ref

TAMPER_CLASSES = ("flip_s", "flip_r", "wrong_msg", "noncanonical_s")
BASE_TIME_NS = 1_700_000_000 * 10**9
ADDRESS_LEN = 20


# -- what every (seed, height, validator) maps to --------------------------------


def _h(*parts) -> bytes:
    return hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()


def validator_seed(seed: int, i: int) -> bytes:
    return _h("tpu-bft-bench", seed, "val", i)


def block_hash(seed: int, height: int) -> bytes:
    return _h("tpu-bft-bench", seed, "block", height)


def parts_hash(seed: int, height: int) -> bytes:
    return _h("tpu-bft-bench", seed, "parts", height)


def vote_times_ns(seed: int, height: int, n: int, jitter_ms: int) -> "list[int]":
    """Each validator stamps its precommit with its own clock: one time a
    validator (by identity, not by place in the set), never a whole second
    (proto3 leaves zero nanos out; that is legal and covered by the tests,
    but a real clock all but never reads it)."""
    rng = random.Random(f"tpu-bft-bench/{seed}/time/{height}")
    base = BASE_TIME_NS + height * 10**9
    span = max(jitter_ms, 1) * 10**6
    return [base + 1 + rng.randrange(span - 1) for _ in range(n)]


def height_head(seed: int, height: int) -> bytes:
    return canonical.vote_head(
        height, 0, block_hash(seed, height), 1, parts_hash(seed, height)
    )


# -- workers: host library only ----------------------------------------------------


def _sign_range(args):
    """Public keys of validators [lo, hi) and their signatures over each of
    ``heights``: (pubs blob, {height: sigs blob})."""
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    seed, chain_id, n, lo, hi, heights, jitter_ms = args
    keys = [
        Ed25519PrivateKey.from_private_bytes(validator_seed(seed, i))
        for i in range(lo, hi)
    ]
    raw = serialization.Encoding.Raw, serialization.PublicFormat.Raw
    pubs = b"".join(k.public_key().public_bytes(*raw) for k in keys)
    tail = canonical.vote_tail(chain_id)
    out = {}
    for h in heights:
        head = height_head(seed, h)
        times = vote_times_ns(seed, h, n, jitter_ms)[lo:hi]
        out[h] = b"".join(
            k.sign(canonical.sign_bytes(head, t, tail))
            for k, t in zip(keys, times)
        )
    return lo, hi, pubs, out


def _verify_items(items):
    """The plain reference over (pub, msg, sig) triples."""
    return [ref.verify_zip215(p, m, s) for p, m, s in items]


class SignPool:
    """Workers for signing (set-up) and for the plain reference (after the
    window).  Spawned, not forked: they start from a fresh interpreter and
    hold neither the program, nor jax, nor the chip."""

    def __init__(self, workers: "int | None" = None):
        if workers is None:
            workers = max(1, min(12, (os.cpu_count() or 2) - 1))
        self.workers = workers
        self._pool = multiprocessing.get_context("spawn").Pool(workers)

    def map_async(self, fn, tasks):
        return self._pool.map_async(fn, tasks, chunksize=1)

    def map_chunks(self, fn, items: list, chunk: int) -> list:
        """``fn`` over ``items`` in chunks, the results joined in order."""
        parts = [items[i:i + chunk] for i in range(0, len(items), chunk)]
        return [x for part in self._pool.map(fn, parts, chunksize=1) for x in part]

    def verify(self, items: list) -> "list[bool]":
        return self.map_chunks(_verify_items, items, 128)

    def close(self) -> None:
        self._pool.terminate()
        self._pool.join()


# -- the chain ---------------------------------------------------------------------


@dataclass
class Height:
    height: int
    block_hash: bytes
    parts_hash: bytes
    times_ns: "list[int]"  # in the set's order
    sigs: "list[bytes]"  # in the set's order
    tamper: "tuple[int, str] | None" = None  # (index in the set's order, class)

    @property
    def key(self) -> int:
        """What names this height as a request."""
        return self.height


@dataclass
class Chain:
    seed: int
    chain_id: str
    pubs: "list[bytes]"  # in the set's order
    powers: "list[int]"
    ids: "list[int]"  # validator identity at each place of the set's order
    pool: "list[Height]" = field(default_factory=list)
    warm: "list[Height]" = field(default_factory=list)

    def sign_bytes(self, hgt: Height, index: int) -> bytes:
        return canonical.sign_bytes(
            height_head(self.seed, hgt.height),
            hgt.times_ns[index],
            canonical.vote_tail(self.chain_id),
        )

    def light_prefix(self) -> int:
        """How many signatures ``VerifyCommitLight`` checks: it stops once
        the tallied power passes 2/3 of the total."""
        needed = sum(self.powers) * 2 // 3
        tallied = 0
        for k, p in enumerate(self.powers):
            tallied += p
            if tallied > needed:
                return k + 1
        return len(self.powers)


def pool_size(traffic: dict, config_name: str, validators: int) -> int:
    heights = traffic.get("heights")
    if isinstance(heights, dict):
        heights = heights.get(config_name)
    if heights is None:
        heights = max(1, int(traffic["pool_signatures"]) // validators)
    return int(heights)


def set_order(pubs: "list[bytes]", powers: "list[int]") -> "list[int]":
    """CometBFT's validator order: voting power descending, then address
    (the first 20 bytes of SHA-256 of the key) ascending."""
    addr = [hashlib.sha256(p).digest()[:ADDRESS_LEN] for p in pubs]
    return sorted(range(len(pubs)), key=lambda i: (-powers[i], addr[i]))


def tamper_plan(seed: int, traffic: dict, heights: "list[int]", prefix: int):
    """{height: (index, class)} for the pool: fixed phase, seeded index."""
    every = int(traffic.get("tamper_every", 0))
    if not every:
        return {}
    phase = int(traffic.get("tamper_phase", every // 2))
    classes = traffic.get("tamper_classes", TAMPER_CLASSES)
    plan = {}
    for h in heights:
        if h % every == phase:
            rng = random.Random(f"tpu-bft-bench/{seed}/tamper/{h}")
            plan[h] = (rng.randrange(prefix), classes[(h // every) % len(classes)])
    return plan


def _tampered(chain: Chain, hgt: Height, index: int, cls: str) -> bytes:
    sig = hgt.sigs[index]
    if cls == "flip_s":
        return sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
    if cls == "flip_r":
        return bytes([sig[0] ^ 1]) + sig[1:]
    if cls == "noncanonical_s":
        s = int.from_bytes(sig[32:], "little") + ref.L
        return sig[:32] + s.to_bytes(32, "little")
    if cls == "wrong_msg":
        # a sound signature of the same validator over another block
        other = canonical.sign_bytes(
            canonical.vote_head(hgt.height, 0, _h("forged", hgt.height), 1,
                                hgt.parts_hash),
            hgt.times_ns[index],
            canonical.vote_tail(chain.chain_id),
        )
        return ref.sign(validator_seed(chain.seed, chain.ids[index]), other)
    raise ValueError(f"unknown tamper class {cls!r}")


def submit(pool: SignPool, config: dict, traffic: dict, config_name: str,
           seed: int):
    """Start signing; returns the handle ``collect`` waits for."""
    n = int(config["validators"])
    jitter = int(traffic.get("time_jitter_ms", 500))
    n_pool = pool_size(traffic, config_name, n)
    n_warm = int(traffic.get("warmup_heights", 0)) + int(
        traffic.get("warmup_tampered", 0)
    )
    heights = list(range(1, n_pool + n_warm + 1))
    # validators in a few ranges (each worker derives only its keys), the
    # heights of a range in slices, so that the tasks are many and even
    ranges = max(1, min(pool.workers, n // 64))
    slices = max(1, min(len(heights), 4 * pool.workers // ranges))
    step_v = -(-n // ranges)
    step_h = -(-len(heights) // slices)
    tasks = [
        (seed, config["chain_id"], n, lo, min(lo + step_v, n),
         heights[k:k + step_h], jitter)
        for lo in range(0, n, step_v)
        for k in range(0, len(heights), step_h)
    ]
    return pool.map_async(_sign_range, tasks), n_pool, heights, jitter


def collect(handle, config: dict, traffic: dict, seed: int) -> Chain:
    """Wait for the workers, put the validators in the set's order, cut the
    signatures, tamper where the traffic file says."""
    pending, n_pool, heights, jitter = handle
    n = int(config["validators"])
    power = config.get("voting_power", 10)
    powers_by_id = power if isinstance(power, list) else [int(power)] * n
    pubs_by_id = [b""] * n
    blobs = {h: [None] * n for h in heights}
    for lo, hi, pubs, out in pending.get():
        for i in range(lo, hi):
            pubs_by_id[i] = pubs[(i - lo) * 32:(i - lo + 1) * 32]
        for h, blob in out.items():
            row = blobs[h]
            for i in range(lo, hi):
                row[i] = blob[(i - lo) * 64:(i - lo + 1) * 64]
    ids = set_order(pubs_by_id, powers_by_id)
    chain = Chain(
        seed=seed,
        chain_id=config["chain_id"],
        pubs=[pubs_by_id[i] for i in ids],
        powers=[powers_by_id[i] for i in ids],
        ids=ids,
    )
    prefix = chain.light_prefix()
    plan = tamper_plan(seed, traffic, heights[:n_pool], prefix)
    n_warm_ok = int(traffic.get("warmup_heights", 0))
    for k, h in enumerate(heights[n_pool + n_warm_ok:]):
        # warm-up rejections: every class once, then again from the first
        rng = random.Random(f"tpu-bft-bench/{seed}/tamper/{h}")
        plan[h] = (rng.randrange(prefix), TAMPER_CLASSES[k % len(TAMPER_CLASSES)])
    for h in heights:
        times = vote_times_ns(seed, h, n, jitter)
        row = blobs.pop(h)
        hgt = Height(
            height=h,
            block_hash=block_hash(seed, h),
            parts_hash=parts_hash(seed, h),
            times_ns=[times[i] for i in ids],
            sigs=[row[i] for i in ids],
        )
        if h in plan:
            index, cls = plan[h]
            hgt.sigs[index] = _tampered(chain, hgt, index, cls)
            hgt.tamper = (index, cls)
        (chain.pool if h <= n_pool else chain.warm).append(hgt)
    return chain


def build(config: dict, traffic: dict, config_name: str, seed: int,
          pool: SignPool) -> Chain:
    return collect(submit(pool, config, traffic, config_name, seed),
                   config, traffic, seed)


def spot_check(chain: Chain, sample: int = 4) -> None:
    """Hold the host library to the plain reference on a seeded sample:
    public key and signature of a few validators at the first height."""
    rng = random.Random(f"tpu-bft-bench/{chain.seed}/spot")
    hgt = chain.pool[0]
    for index in rng.sample(range(len(chain.pubs)), min(sample, len(chain.pubs))):
        if hgt.tamper and hgt.tamper[0] == index:
            continue
        vseed = validator_seed(chain.seed, chain.ids[index])
        if ref.pubkey_from_seed(vseed) != chain.pubs[index]:
            raise RuntimeError(f"host library's public key {index} differs")
        if ref.sign(vseed, chain.sign_bytes(hgt, index)) != hgt.sigs[index]:
            raise RuntimeError(f"host library's signature {index} differs")
