"""Consensus-wide signature verification cache.

A bounded, thread-safe LRU mapping SHA-256(pub ‖ msg ‖ sig) -> bool.  Every
vote signature is verified at gossip time (``vote_set.add_vote``); the same
signature is re-verified when the commit built from those votes is checked
at apply time (``state/execution.validate_block`` -> ``verify_commit``),
when blocksync re-checks a served commit, and when an extended commit is
validated.  Caching the verdict makes those re-verifications near-free and
lets the batch verifiers ship only cache MISSES to the device.

Key safety (docs/verify-stream.md):
  * the key digests the FULL (pub, msg, sig) triple with length framing, so
    two distinct triples can never alias short of a SHA-256 collision;
  * signature verification is a pure function of the triple — in particular
    an *invalid* triple is invalid forever, so negative caching is safe;
  * a wrong *prediction* (e.g. blocksync prefetching against a stale
    validator set) caches a verdict for a triple that is simply never
    queried — it can waste a slot, never corrupt an answer;
  * verdicts are implementation-independent, so it does not matter WHICH
    verifier produced a cached bit: every ed25519 path is ZIP-215 — the
    device kernel by construction, and the host single-sig path because
    ``Ed25519PubKey.verify_signature`` falls back to ``verify_zip215``
    whenever the strict library rejects (strict acceptance implies ZIP-215
    acceptance) — while the secp256k1/BLS device paths are gated by
    known-answer self-checks and differential-tested against their host
    oracles.  A node must never mix verifiers that genuinely disagree;
    that invariant predates this cache (batch vs single verification
    already selected per call site) and is what the self-checks enforce.

The key's life (docs/verify-stream.md "One key a signature"): a triple's key
is hashed ONCE a request, by whoever looks it up first, and travels with the
triple from there — ``partition_misses`` returns the keys of its misses,
``writeback`` takes them back and hashes nothing, and in between they ride the
scheduler's queue entry, whose in-flight dedup reads them.  Whoever hashed a
key stores its verdict, once: the batch seam's ``writeback`` for a segment,
the scheduler's ``_settle`` for the single votes it keyed itself.  The cache
is visited by the segment: one look-up pass and one put pass, each under one
acquisition of the lock (``_get_many`` / ``_put_many``), with the hits,
misses, LRU order and evictions that a loop of ``_get`` / ``_put`` gives.
``stats()`` counts both: ``keys`` hashed and ``puts`` stored — a fresh commit
of n signatures reads n and n.

Kill-switch: ``COMETBFT_TPU_SIGCACHE=0`` disables lookups AND inserts,
restoring the uncached behavior exactly.  ``COMETBFT_TPU_SIGCACHE_SIZE``
bounds the entry count (default 65536; ~48 B of digest+flag per entry plus
dict overhead keeps the default well under 10 MB).
"""

from __future__ import annotations

import hashlib
import os
import struct
import threading
from collections import OrderedDict
from typing import NamedTuple, Optional

from cometbft_tpu.libs import tracing

DEFAULT_CAPACITY = 65536


_U32 = struct.Struct("<I").pack


def _key(pub: bytes, msg: bytes, sig: bytes) -> bytes:
    # length framing: (pub, msg, sig) concatenations can otherwise alias
    # across entries with variable-length msgs.  One buffer, one call: the
    # digest is that of the five pieces fed in turn (tests pin the framing)
    return hashlib.sha256(
        b"".join((_U32(len(pub)), pub, _U32(len(msg)), msg, sig))
    ).digest()


class SigCache:
    """LRU over verification verdicts; all methods are thread-safe."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = max(int(capacity), 1)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[bytes, bool]" = OrderedDict()
        self._hits = 0
        self._misses = 0
        self._keys = 0  # keys hashed
        self._puts = 0  # verdicts stored

    @staticmethod
    def enabled() -> bool:
        return os.environ.get("COMETBFT_TPU_SIGCACHE", "1") != "0"

    def get(self, pub: bytes, msg: bytes, sig: bytes) -> Optional[bool]:
        """Cached verdict or None.  Disabled cache always misses (without
        counting: the stats then honestly read as all-miss-no-traffic)."""
        if not self.enabled():
            return None
        return self._get(self.hash_keys((pub,), (msg,), (sig,))[0])

    def hash_keys(self, pubs, msgs, sigs) -> "list[bytes]":
        """The triples' cache keys, counted in ``stats()["keys"]``: every
        key hashed for this cache comes through here, so the count says how
        often a request pays the SHA-256."""
        keys = [_key(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
        with self._lock:
            self._keys += len(keys)
        return keys

    def _get(self, k: bytes) -> Optional[bool]:
        """Lookup past the kill-switch check — batch callers
        (``partition_misses``) hoist ``enabled()`` to once per batch; a
        10k-signature commit must not pay an os.environ read per entry."""
        with self._lock:
            v = self._entries.get(k)
            if v is None:
                self._misses += 1
                return None
            self._entries.move_to_end(k)
            self._hits += 1
            return v

    def _get_many(self, keys) -> "list[Optional[bool]]":
        """``[self._get(k) for k in keys]`` under ONE acquisition of the
        lock: the same verdicts, counts and LRU order (a look-up evicts
        nothing, so reading all the keys before moving the hits to the end,
        in order, is what the loop does)."""
        entries = self._entries
        with self._lock:
            out = [entries.get(k) for k in keys]
            misses = out.count(None)
            if misses < len(out):
                for k, v in zip(keys, out):
                    if v is not None:
                        entries.move_to_end(k)
            self._hits += len(out) - misses
            self._misses += misses
        return out

    def put(self, pub: bytes, msg: bytes, sig: bytes, ok: bool) -> None:
        if not self.enabled():
            return
        self._put(self.hash_keys((pub,), (msg,), (sig,))[0], ok)

    def _put(self, k: bytes, ok: bool) -> None:
        self._put_many((k,), (ok,))

    def _put_many(self, keys, verdicts) -> None:
        """A loop of ``_put`` under ONE acquisition of the lock: inserted
        and moved to the end in order, then the oldest evicted down to the
        capacity — the survivors and their order are those of evicting
        after every insert (an LRU keeps the last ``capacity`` distinct
        keys by their latest touch, whenever it trims)."""
        entries = self._entries
        with self._lock:
            n = 0
            for k, ok in zip(keys, verdicts):
                entries[k] = bool(ok)
                entries.move_to_end(k)
                n += 1
            self._puts += n
            while len(entries) > self.capacity:
                entries.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._hits = 0
            self._misses = 0
            self._keys = 0
            self._puts = 0

    def stats(self) -> dict:
        with self._lock:
            hits, misses = self._hits, self._misses
            keys, puts = self._keys, self._puts
            size = len(self._entries)
        total = hits + misses
        return {
            "hits": hits,
            "misses": misses,
            "keys": keys,
            "puts": puts,
            "size": size,
            "capacity": self.capacity,
            "hit_rate": (hits / total) if total else 0.0,
        }


_CACHE: Optional[SigCache] = None
_CACHE_LOCK = threading.Lock()


def get_cache() -> SigCache:
    """The process-wide cache (consensus, blocksync, light client and the
    batch verifiers all share one — that sharing IS the optimization)."""
    global _CACHE
    if _CACHE is None:
        with _CACHE_LOCK:
            if _CACHE is None:
                cap = int(
                    os.environ.get(
                        "COMETBFT_TPU_SIGCACHE_SIZE", str(DEFAULT_CAPACITY)
                    )
                )
                _CACHE = SigCache(cap)
    return _CACHE


def reset_cache() -> None:
    """Drop the process-wide cache (tests; also re-reads the size env)."""
    global _CACHE
    with _CACHE_LOCK:
        _CACHE = None


class Partition(NamedTuple):
    """What ``partition_misses`` found; ``writeback`` takes it back whole."""

    bits: list  # verdict by index, ``None`` where ``miss`` says so
    miss: list  # the indices the caller has to verify
    keys: Optional[list]  # their cache keys, by ``miss``; None: cache off
    hashed: int  # keys hashed for the look-up (hits and misses)


def _all_sized(items, sizes: tuple) -> bool:
    """Every item of an allowed length (no ``sizes``: no rule), asked of the
    whole list at once: the answer is yes for every commit a node builds."""
    return not sizes or set(map(len, items)).issubset(sizes)


def partition_misses(
    pubs,
    msgs,
    sigs,
    pub_sizes: tuple = (32,),
    sig_sizes: tuple = (64,),
) -> Partition:
    """THE cache/structural prefilter, shared by every consumer (batch
    verifiers, blocksync window prefetch, light-client chain sync) so the
    size rules and get/put protocol cannot diverge.

    ``bits[i]`` is the resolved verdict — False for structurally impossible
    pub/sig lengths (they must never occupy backend lanes, and get no key),
    the cached verdict on a hit — or None for the entries listed in
    ``miss``, which the caller verifies and feeds to ``writeback``.  Each
    structurally possible triple is hashed once and all are looked up in
    one visit to the cache; the misses' ``keys`` go on with them (to the
    scheduler's dedup, then to ``writeback``), so nobody hashes them again.
    Empty ``pub_sizes``/``sig_sizes`` disable that structural filter."""
    cache = get_cache()
    n = len(pubs)
    bits: list = [None] * n
    cand = list(range(n))
    if not (_all_sized(pubs, pub_sizes) and _all_sized(sigs, sig_sizes)):
        cand = [
            i
            for i in cand
            if (not pub_sizes or len(pubs[i]) in pub_sizes)
            and (not sig_sizes or len(sigs[i]) in sig_sizes)
        ]
        bits = [False] * n
        for i in cand:
            bits[i] = None
        pubs = [pubs[i] for i in cand]
        msgs = [msgs[i] for i in cand]
        sigs = [sigs[i] for i in cand]
    if not cache.enabled():  # one env read per batch, not per sig
        return Partition(bits, cand, None, 0)
    # one span each a CALL (never a signature): the seam's parts by name
    with tracing.span("batch.keys"):
        keys = cache.hash_keys(pubs, msgs, sigs)
    with tracing.span("batch.lookup"):
        got = cache._get_many(keys)
        if got.count(None) == len(got):  # every fresh commit: nothing to sort
            return Partition(bits, cand, keys, len(keys))
        miss: list = []
        miss_keys: list = []
        for i, k, hit in zip(cand, keys, got):
            if hit is None:
                miss.append(i)
                miss_keys.append(k)
            else:
                bits[i] = hit
    return Partition(bits, miss, miss_keys, len(keys))


def writeback(part: Partition, results) -> None:
    """Resolve ``partition_misses``'s holes: record each fresh verdict in
    ``part.bits`` and, under the key the look-up hashed, in the cache
    (``results`` aligns with ``part.miss``) — the ONE put a fresh verdict
    gets, made on the caller's path before its verify returns, so the
    caller's next look-up finds it.

    Only DEFINITIVE verdicts are cached: a ``None`` result marks an entry
    the backend could not judge (an infrastructure failure — see
    docs/backend-supervisor.md).  Caching ``False`` for it would negative-
    cache a possibly-valid signature forever, so the hole is left in
    ``bits`` for the caller to surface as an error, never as a verdict."""
    bits, keys = part.bits, part.keys
    with tracing.span("batch.writeback"):
        got = [None if r is None else bool(r) for r in results]
        for i, r in zip(part.miss, got):
            if r is not None:
                bits[i] = r
        if keys is None:
            return
        if None in got:
            judged = [(k, r) for k, r in zip(keys, got) if r is not None]
            keys, got = [k for k, _ in judged], [r for _, r in judged]
        if got:
            get_cache()._put_many(keys, got)


def verify_with_cache(pub_key, msg: bytes, sig: bytes) -> bool:
    """Single-signature verification through the cache: the drop-in for
    ``pub_key.verify_signature(msg, sig)`` on consensus paths (vote,
    proposal, vote-extension checks)."""
    cache = get_cache()
    if not cache.enabled():
        return bool(pub_key.verify_signature(msg, sig))
    pub = pub_key.bytes() if hasattr(pub_key, "bytes") else bytes(pub_key)
    (k,) = cache.hash_keys((pub,), (msg,), (sig,))  # once, for get and put
    hit = cache._get(k)
    if hit is not None:
        tracing.mark(hit=True)
        return hit
    ok = bool(pub_key.verify_signature(msg, sig))
    cache._put(k, ok)
    return ok
