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
is visited by the segment: one look-up pass and one put pass, each ONE call
into the store, with the hits, misses, LRU order and evictions that a loop of
``_get`` / ``_put`` gives.  ``stats()`` counts both: ``keys`` hashed and
``puts`` stored — a fresh commit of n signatures reads n and n.

Where it runs: a segment of ``NATIVE_KEYS_MIN`` triples or more is hashed in
ONE call into the sidecar (``native/csrc``: SHA-256 with the SHA extensions
where the CPU has them), its n keys one n x 32-byte buffer;
fewer, and a single vote, take ``_key`` and hashlib.  The store is the
sidecar's LRU (an open-addressed index of digests over a fixed pool of
entries, a list over them oldest first, one mutex), handed that buffer as it
is.  Where the sidecar is absent (``COMETBFT_TPU_NO_NATIVE``, a failed build)
the keys are ``_key``'s and the store an ``OrderedDict``: the same keys, the
same answers; ``stats()["store"]`` says which.

Kill-switch: ``COMETBFT_TPU_SIGCACHE=0`` disables lookups AND inserts,
restoring the uncached behavior exactly.  ``COMETBFT_TPU_SIGCACHE_SIZE``
bounds the entry count (default 65536: 48 B an entry and 8 B an index slot,
two or more slots an entry, 4 MB in the sidecar).
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import struct
import threading
import weakref
from collections import OrderedDict
from typing import NamedTuple, Optional

import numpy as np

from cometbft_tpu.libs import tracing

DEFAULT_CAPACITY = 65536

# Below this many triples ``_digests`` hashes in Python, one ``_key`` a
# triple: there the sidecar's call and its glue (the joins, the lengths, the
# keys cut back out) cost more than hashlib.  Measured on a TPU v5e's host
# (AMD, SHA-NI; PERF.md §6), Python against the sidecar: 7.0 against 7.2 us
# at 6 triples, 9.0 against 7.7 at 8, 17.3 against 10.3 at 16.
NATIVE_KEYS_MIN = 8

_U32 = struct.Struct("<I").pack

_ABSENT = 2  # a store's answer where it holds no verdict
_VERDICT = (False, True, None)  # a store's answer byte as a verdict


def _key(pub: bytes, msg: bytes, sig: bytes) -> bytes:
    # length framing: (pub, msg, sig) concatenations can otherwise alias
    # across entries with variable-length msgs.  One buffer, one call: the
    # digest is that of the five pieces fed in turn (tests pin the framing)
    return hashlib.sha256(
        b"".join((_U32(len(pub)), pub, _U32(len(msg)), msg, sig))
    ).digest()


@functools.lru_cache(maxsize=8)
def _splitter(n: int) -> struct.Struct:
    return struct.Struct("32s" * n)


def _split(digests, n: int) -> "list[bytes]":
    """n keys cut out of their n x 32-byte buffer, in one call."""
    return list(_splitter(n).unpack_from(digests))


def _sidecar():
    """The native library where it has the cache's calls, else None."""
    from cometbft_tpu import native

    lib = native.lib()
    return lib if hasattr(lib, "sigcache_new") else None


def _lengths(items, fixed: Optional[int]):
    """``(None, fixed)`` where every item is ``fixed`` bytes long (known by
    the caller, or found here), else ``(lengths as int64, 0)``."""
    if fixed is None:
        sizes = set(map(len, items))
        if len(sizes) != 1:
            return np.fromiter(map(len, items), np.int64, len(items)), 0
        (fixed,) = sizes
    return None, fixed


def _address(lengths) -> Optional[int]:
    return None if lengths is None else lengths.ctypes.data


class _DictStore:
    """The LRU as an ``OrderedDict`` under a lock: the store where the
    sidecar is absent, and the tests' oracle for ``_NativeStore``, whose
    calls it answers alike (n keys as one n x 32-byte buffer, the verdicts
    as n bytes)."""

    kind = "python"

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._lock = threading.Lock()
        self._entries: "OrderedDict[bytes, bool]" = OrderedDict()
        self._hits = 0
        self._misses = 0
        self._puts = 0

    def get_many(self, digests, n: int) -> bytes:
        """Each key's verdict (0 / 1, ``_ABSENT``), every one found moved to
        the end in order (a look-up evicts nothing, so reading all the keys
        first is what a loop of single look-ups does)."""
        keys = _split(digests, n)
        entries = self._entries
        with self._lock:
            out = [entries.get(k, _ABSENT) for k in keys]
            misses = out.count(_ABSENT)
            if misses < n:
                for k, v in zip(keys, out):
                    if v is not _ABSENT:
                        entries.move_to_end(k)
            self._hits += n - misses
            self._misses += misses
        return bytes(out)

    def put_many(self, digests, oks: bytes, n: int) -> None:
        """Inserted and moved to the end in order, then the oldest evicted
        down to the capacity — the survivors and their order are those of
        evicting after every insert (an LRU keeps the last ``capacity``
        distinct keys by their latest touch, whenever it trims)."""
        keys = _split(digests, n)
        entries = self._entries
        with self._lock:
            for k, ok in zip(keys, oks):
                entries[k] = bool(ok)
                entries.move_to_end(k)
            self._puts += n
            while len(entries) > self.capacity:
                entries.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._hits = self._misses = self._puts = 0

    def counts(self) -> "tuple[int, int, int, int]":
        """Hits, misses, puts and size, read together."""
        with self._lock:
            return self._hits, self._misses, self._puts, len(self._entries)

    def items(self) -> "list[tuple[bytes, bool]]":
        """The entries oldest first."""
        with self._lock:
            return list(self._entries.items())


class _NativeStore:
    """The sidecar's LRU (``sigcache_new``): the same calls as
    ``_DictStore``, each one call under the store's own mutex (and the GIL,
    ``native.lib`` binds them so).  The handle is freed with the last
    reference, never at exit, where a daemon thread may still be inside a
    call."""

    kind = "native"

    def __init__(self, lib, handle: int):
        self._lib = lib
        self._h = handle
        weakref.finalize(self, lib.sigcache_free, handle).atexit = False

    def get_many(self, digests, n: int) -> bytes:
        if len(digests) != 32 * n:
            raise ValueError("sigcache keys are 32 bytes each")
        out = ctypes.create_string_buffer(n)
        self._lib.sigcache_get_many(self._h, digests, n, out)
        return out.raw

    def put_many(self, digests, oks: bytes, n: int) -> None:
        if len(digests) != 32 * n or len(oks) != n:
            raise ValueError("sigcache keys are 32 bytes, one verdict each")
        self._lib.sigcache_put_many(self._h, digests, oks, n)

    def __len__(self) -> int:
        return self._lib.sigcache_len(self._h)

    def clear(self) -> None:
        self._lib.sigcache_clear(self._h)

    def counts(self) -> "tuple[int, int, int, int]":
        out = (ctypes.c_int64 * 4)()
        self._lib.sigcache_counts(self._h, out)
        return tuple(out)

    def items(self) -> "list[tuple[bytes, bool]]":
        most = len(self)
        keys = ctypes.create_string_buffer(32 * most)
        oks = ctypes.create_string_buffer(most)
        k = self._lib.sigcache_items(self._h, keys, oks, most)
        return list(zip(_split(keys, k), map(bool, oks.raw[:k])))


def _new_store(capacity: int):
    """The sidecar's store where it can hold ``capacity``, else the dict."""
    lib = _sidecar()
    handle = lib.sigcache_new(capacity) if lib is not None else None
    if handle:
        return _NativeStore(lib, handle)
    return _DictStore(capacity)


class SigCache:
    """LRU over verification verdicts; all methods are thread-safe."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = max(int(capacity), 1)
        self._lib = _sidecar()
        self._store = _new_store(self.capacity)
        self._lock = threading.Lock()  # the ``keys`` count's
        self._keys = 0  # keys hashed

    @staticmethod
    def enabled() -> bool:
        return os.environ.get("COMETBFT_TPU_SIGCACHE", "1") != "0"

    @property
    def store(self) -> str:
        """``native`` (the sidecar's) or ``python`` (the ``OrderedDict``)."""
        return self._store.kind

    def get(self, pub: bytes, msg: bytes, sig: bytes) -> Optional[bool]:
        """Cached verdict or None.  Disabled cache always misses (without
        counting: the stats then honestly read as all-miss-no-traffic)."""
        if not self.enabled():
            return None
        return self._get(self.hash_keys((pub,), (msg,), (sig,))[0])

    def hash_keys(self, pubs, msgs, sigs) -> "list[bytes]":
        """The triples' cache keys, counted in ``stats()["keys"]``: every
        key hashed for this cache comes through here or ``_digests``, so the
        count says how often a request pays the SHA-256."""
        return _split(self._digests(pubs, msgs, sigs)[0], len(pubs))

    def _digests(
        self,
        pubs,
        msgs,
        sigs,
        pub_len: Optional[int] = None,
        sig_len: Optional[int] = None,
    ) -> "tuple[bytes, str]":
        """The triples' keys as ONE n x 32-byte buffer, and the path that
        hashed them: ``native`` (one sidecar call) from ``NATIVE_KEYS_MIN``
        triples up, else ``python``.  ``pub_len`` / ``sig_len``: every pub /
        sig is known to be that long (the caller's size rule said so)."""
        n = len(pubs)
        with self._lock:
            self._keys += n
        if n >= NATIVE_KEYS_MIN and self._lib is not None:
            pub_lens, pub_len = _lengths(pubs, pub_len)
            sig_lens, sig_len = _lengths(sigs, sig_len)
            msg_lens = np.fromiter(map(len, msgs), np.int64, n)
            out = ctypes.create_string_buffer(32 * n)
            if self._lib.sigcache_keys(
                b"".join(pubs), _address(pub_lens), pub_len,
                b"".join(msgs), msg_lens.ctypes.data,
                b"".join(sigs), _address(sig_lens), sig_len,
                n, out, -1,
            ) == 0:
                return out.raw, "native"
        keys = [_key(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
        return b"".join(keys), "python"

    def _get(self, k: bytes) -> Optional[bool]:
        """Lookup past the kill-switch check — batch callers
        (``partition_misses``) hoist ``enabled()`` to once per batch; a
        10k-signature commit must not pay an os.environ read per entry."""
        return _VERDICT[self._store.get_many(k, 1)[0]]

    def _get_many(self, keys) -> "list[Optional[bool]]":
        """``[self._get(k) for k in keys]`` in ONE visit to the store: the
        same verdicts, counts and LRU order."""
        found = self._store.get_many(b"".join(keys), len(keys))
        return [_VERDICT[c] for c in found]

    def put(self, pub: bytes, msg: bytes, sig: bytes, ok: bool) -> None:
        if not self.enabled():
            return
        self._put(self.hash_keys((pub,), (msg,), (sig,))[0], ok)

    def _put(self, k: bytes, ok: bool) -> None:
        self._put_many((k,), (ok,))

    def _put_many(self, keys, verdicts) -> None:
        """A loop of ``_put`` in ONE visit to the store: the survivors and
        their order are those of evicting after every insert."""
        self._store.put_many(
            b"".join(keys), bytes(map(bool, verdicts)), len(keys)
        )

    @property
    def _entries(self) -> "OrderedDict[bytes, bool]":
        """A copy of what the store holds, oldest first (tests, debugging)."""
        return OrderedDict(self._store.items())

    def __len__(self) -> int:
        return len(self._store)

    def clear(self) -> None:
        with self._lock:
            self._store.clear()
            self._keys = 0

    def stats(self) -> dict:
        with self._lock:
            keys = self._keys
        hits, misses, puts, size = self._store.counts()
        total = hits + misses
        return {
            "hits": hits,
            "misses": misses,
            "keys": keys,
            "puts": puts,
            "size": size,
            "capacity": self.capacity,
            "hit_rate": (hits / total) if total else 0.0,
            "store": self.store,
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
    digests: Optional[bytes] = None  # ``keys`` as one buffer, for the put


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
    # one span each a CALL (never a signature): the seam's parts by name,
    # each saying where it ran
    with tracing.span("batch.keys") as sp:
        digests, path = cache._digests(
            pubs, msgs, sigs, _one(pub_sizes), _one(sig_sizes)
        )
        sp.set(path=path)
    n = len(cand)
    with tracing.span("batch.lookup", path=cache.store):
        found = cache._store.get_many(digests, n)
        if found.count(_ABSENT) == n:  # every fresh commit: nothing to sort
            return Partition(bits, cand, _split(digests, n), n, digests)
        miss: list = []
        miss_keys: list = []
        for j, (i, c) in enumerate(zip(cand, found)):
            if c == _ABSENT:
                miss.append(i)
                miss_keys.append(digests[32 * j:32 * j + 32])
            else:
                bits[i] = c == 1
    return Partition(bits, miss, miss_keys, n, b"".join(miss_keys))


def _one(sizes: tuple) -> Optional[int]:
    """The one length a size rule allows, where it allows one."""
    return sizes[0] if len(sizes) == 1 else None


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
    cache = get_cache()
    with tracing.span("batch.writeback", path=cache.store):
        holes = None in results
        got = (
            [None if r is None else bool(r) for r in results]
            if holes
            else list(map(bool, results))
        )
        if len(got) == len(part.miss) == len(bits):
            bits[:] = got  # every entry a miss: ``miss`` is every index
        else:
            for i, r in zip(part.miss, got):
                if r is not None:
                    bits[i] = r
        if keys is None:
            return
        digests = part.digests
        if holes or len(got) != len(keys):
            judged = [(k, r) for k, r in zip(keys, got) if r is not None]
            digests = b"".join([k for k, _ in judged])
            got = [r for _, r in judged]
        if got:
            cache._store.put_many(digests, bytes(got), len(got))


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
