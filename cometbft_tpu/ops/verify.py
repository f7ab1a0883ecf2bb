"""Batched Ed25519 ZIP-215 verification: host preparation + JAX device kernel.

Pipeline per signature (pub, msg, sig=R||s):
  host:   h = SHA-512(R || pub || msg) mod L;  m = L - h;  s canonical check
          (the C++ sidecar does this batch-at-a-time; python fallback below)
  device: unpack bytes -> limbs/digits; ZIP-215 decompress A and R;
          radix-16 Straus ladder  s*B + m*A;  subtract R;  multiply by
          cofactor 8; accept iff identity.

Unlike the reference's CPU batch verify (random linear combination + one
giant multi-scalar-mul, curve25519-voi via crypto/ed25519/ed25519.go:189-222),
every signature here is verified *independently* in a SIMD lane: per-sig
accept bits come out for free — no recheck pass to attribute failures
(the reference needs one: types/validation.go:308-317).

The device inputs are RAW BYTES (32 B per element: pub, R, s, m) — limb
packing and digit extraction happen on device, keeping the host->device
transfer minimal and the host prep trivial.  On one chip they travel as ONE
packed u8 buffer, split on the device (``split_packed``, ``verify_packed``):
one transfer a launch.
"""

from __future__ import annotations

import contextlib
import ctypes
import hashlib
import logging
import os
import threading
import warnings
from collections import deque
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

# The mesh's donated input shards, which XLA cannot alias to the (much
# smaller) accept bitmap, produce a cosmetic compile-time warning; donation
# still lets the compiler reuse them as scratch.  Message-scoped so real
# warnings survive.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)

from cometbft_tpu.libs import tracing
from cometbft_tpu.ops import dispatch_stats
from cometbft_tpu.ops import fe25519 as fe
from cometbft_tpu.ops import ed25519_point as ep

L_INT = 2**252 + 27742317777372353535851937790883648493

# Batch buckets: pad to one of these sizes to bound recompilation.  The
# sub-128 buckets exist for the plain-XLA path only — a 4-validator commit
# costs a 32-lane kernel instead of a 128-lane one (the XLA-CPU build runs
# lanes ~linearly, so small-bucket dispatches are ~4-5x faster, which is
# what keeps the CPU test suite inside its budget).  Pallas keeps a
# 128-lane floor: the Mosaic lowering tiles on the 8x128 lane grid.
#
# The ladder is deliberately sparse above 1024 (2048 and 16384 were
# pruned): per-bucket dispatch histograms (dispatch_stats.snapshot()
# ["buckets"]) across tier-1, the sim scenarios and bench show nothing
# lands between the blocksync-window shapes (<=1024: votes, evidence
# pairs, <=100-validator commits, 8-commit prefetch windows) and the
# commit/bench shapes (>=4096: 10k-validator commits, bench sweeps).
# Every pruned shape is a compile the warm-boot matrix no longer pays per
# backend tier.
_BUCKETS = [32, 64, 128, 256, 512, 1024, 4096, 8192, 10240, 32768]
_PRUNED_BUCKETS = (2048, 16384)
_PALLAS_MIN_BUCKET = 128


def bucket_size(n: int, min_bucket: int = _PALLAS_MIN_BUCKET) -> int:
    for b in _BUCKETS:
        if b < min_bucket:
            continue
        if n <= b:
            return b
    return ((n + _BUCKETS[-1] - 1) // _BUCKETS[-1]) * _BUCKETS[-1]


def _min_bucket() -> int:
    return _PALLAS_MIN_BUCKET if _use_pallas() else _BUCKETS[0]


def verify_core(a_bytes, r_bytes, s_bytes, m_bytes, s_ok):
    """Unjitted kernel body — also the per-shard body for the mesh-sharded
    path (cometbft_tpu.parallel.mesh).

    a_bytes/r_bytes/s_bytes/m_bytes: (B, 32) uint8; s_ok: (B,) bool.
    Returns (B,) bool accept bits.
    """
    ya, sa = fe.unpack255(a_bytes)
    yr, sr = fe.unpack255(r_bytes)
    ok_a, a, ok_r, r = _decompress_pair(ya, sa, yr, sr)
    dig_s = fe.signed_digits_msb_first(s_bytes)
    dig_m = fe.signed_digits_msb_first(m_bytes)
    p = ep.double_base_scalar_mul(dig_s, dig_m, a)
    q = ep.add(p, ep.negate(r))
    # Cofactored equation: [8](s*B + m*A - R) == identity (ZIP-215).
    q = ep.double(ep.double(ep.double(q, need_t=False), need_t=False))
    return ok_a & ok_r & s_ok & ep.is_identity(q)


def _decompress_pair(ya, sa, yr, sr):
    """Decompress A and R as ONE double-width batch: the ~250-square
    sqrt chain is traced/issued once over (20, 2B) instead of twice over
    (20, B) — half the instruction count for the same flops, which is
    what matters when the kernel is issue-bound rather than ALU-bound.

    The signs are joined as (1, B) rows along the lane axis: Mosaic
    refuses a 1-D concatenate whose second operand starts past the first
    tile ("Input offsets outside of the first tile"), and accepts the
    2-D form (tests/test_tpu_compile.py compiles it for a described
    chip)."""
    t = ya.v.shape[1]
    y_all = fe.F(jnp.concatenate([ya.v, yr.v], axis=1), 0, fe.MASK)
    s_all = jnp.concatenate([sa[None, :], sr[None, :]], axis=1)[0]
    ctx = (
        fe.kernel_mode(2 * t)
        if fe._KERNEL_MODE[-1]
        else contextlib.nullcontext()
    )
    with ctx:
        ok_all, p_all = ep.decompress(y_all, s_all)
    half = lambda f, i: fe.F(f.v[:, i * t : (i + 1) * t], f.lo, f.hi)
    a = ep.PointBatch(*(half(c, 0) for c in p_all))
    r = ep.PointBatch(*(half(c, 1) for c in p_all))
    return ok_all[:t], a, ok_all[t:], r


ARG_NAMES = ("a_bytes", "r_bytes", "s_bytes", "m_bytes", "s_ok")


# -- the one-chip input: ONE packed buffer -----------------------------------
#
# A bucket of ``lanes`` lanes is ONE (4·lanes + lanes/32, 32) u8 array: rows
# 0 … 4·lanes−1 the four tables a, r, s, m (a lane's 32 bytes a row), the
# last lanes/32 rows ``s_ok``, one byte a lane.  129 bytes a lane, as the five
# arrays were, with no padding (every bucket is a multiple of 32).  The host
# packs into it in place (``pack_batch``) and the launch transfers it once.


def packed_rows(lanes: int) -> int:
    """Rows of the packed buffer of a ``lanes``-lane bucket."""
    return 4 * lanes + lanes // 32


def split_packed(packed) -> tuple:
    """The five inputs of ``verify_core`` as row slices of one packed
    buffer, numpy or jax alike: four (lanes, 32) tables and ``s_ok`` as
    (lanes,) u8.  On the host these are views of the buffer's memory; on a
    TPU each slice fuses into the byte unpacking that reads it (a reshape
    of the four tables into one (4, lanes, 32) array is a copy there)."""
    lanes = packed.shape[0] * 32 // 129
    tables = (packed[i * lanes : (i + 1) * lanes] for i in range(4))
    return (*tables, packed[4 * lanes :].reshape(lanes))


def packed_views(packed: np.ndarray) -> dict:
    """The five named arrays the rest of the code knows, as VIEWS into one
    host packed buffer (``s_ok`` read as bool)."""
    *tables, ok = split_packed(packed)
    return dict(zip(ARG_NAMES, (*tables, ok.view(bool))))


def _packed_front(core, name: str):
    """The one-chip executable of a tier: ONE packed input, split on the
    device, then the tier's unchanged five-input ``core``.
    (packed_rows(B), 32) u8 in, (B,) bool accept bits out."""

    def front(packed):
        *tables, ok = split_packed(packed)
        return core(*tables, ok != 0)

    front.__name__ = front.__qualname__ = name
    return jax.jit(front)


verify_packed = _packed_front(verify_core, "verify_packed")


def select_impl(devices=None) -> str:
    """Kernel selection — THE seam shared by the single-chip path
    (``verify_batch``) and the mesh-sharded path (``parallel.mesh``), so
    the flagship features always compose: Pallas on real TPU devices,
    plain-XLA everywhere else (CPU tests, virtual meshes).
    COMETBFT_TPU_VERIFY_IMPL=pallas|xla overrides."""
    import os

    env = os.environ.get("COMETBFT_TPU_VERIFY_IMPL")
    if env in ("pallas", "xla"):
        return env
    try:
        devs = list(devices) if devices is not None else jax.devices()
        if devs and all(d.platform == "tpu" for d in devs):
            return "pallas"
    except Exception:
        pass
    return "xla"


def _use_pallas() -> bool:
    return select_impl() == "pallas"


def _pallas_core(a_bytes, r_bytes, s_bytes, m_bytes, s_ok):
    from cometbft_tpu.ops import pallas_verify

    return pallas_verify.verify_core_pallas(
        a_bytes, r_bytes, s_bytes, m_bytes, s_ok
    )


verify_packed_pallas = _packed_front(_pallas_core, "verify_packed_pallas")


# -- AOT executable cache seam ----------------------------------------------
#
# Every bucketed verify dispatch obtains its executable here instead of
# calling the jitted kernels directly: on first use of an (impl, lanes)
# shape the executable is AOT-compiled (or deserialized from the on-disk
# cache, skipping tracing AND compilation) and memoized for the process.
# The memo plays the role jit's internal cache played — including its
# limitation that anything read at trace time only takes effect before a
# shape's first use.

_EXEC_LOCK = threading.Lock()
_EXEC_CACHE: dict = {}  # (impl, lanes) -> callable
# shape key -> the compiler's message, for every shape whose lowering or
# compile failed in this process: (impl, lanes) here, ("mesh", ...)
# for parallel/mesh's sharded executables.  A latched shape raises
# ``TierCompileError`` again at once: the supervisor demotes the tier
# through its breaker (the node keeps verifying one tier down), nothing
# retries the compile on every dispatch, and nothing retries it quietly
# through plain jit.  chip_smoke.py fails on a non-empty map.
_AOT_BROKEN: dict = {}


class TierCompileError(RuntimeError):
    """The selected verify tier does not compile for this device."""


def raise_if_broken(key) -> None:
    broken = _AOT_BROKEN.get(key)
    if broken is not None:
        raise TierCompileError(broken)


def compile_failed(key, what: str, e: BaseException) -> TierCompileError:
    """Make a compile failure loud — logged at error with the compiler's
    message, counted, latched under ``key`` — and hand back the error to
    raise from ``e``."""
    from cometbft_tpu.ops import warm_stats

    msg = f"{what} does not compile: {type(e).__name__}: {e}"
    _AOT_BROKEN[key] = msg
    warm_stats.record_compile_failure()
    logging.getLogger("cometbft_tpu.crypto").error(msg)
    return TierCompileError(msg)


def donation_enabled() -> bool:
    """Whether the MESH-WIDE executables donate their input shards: ON
    exactly for the Pallas/TPU production path.  The XLA-CPU CI path is OFF
    on purpose: donation changes the compiled artifact, so turning it on
    would force a fresh ~100s compile of every mesh shape the first time a
    host runs this code (measured on the CI host) for an aliasing win that
    only matters at device-HBM bandwidth.  The one-chip executable donates
    nothing: its one (packed_rows(B), 32) u8 input cannot alias its (B,)
    bool output."""
    return _use_pallas()


def bucket_tag(impl: str, lanes: int) -> str:
    """On-disk cache tag for one bucket's one-chip executable: the packed
    input's, so that no entry of the five-input form can be taken for it."""
    return f"verify-{impl}-packed-{lanes}"


def _bucket_jitted(impl: str):
    return verify_packed_pallas if impl == "pallas" else verify_packed


def _bucket_shapes(lanes: int) -> tuple:
    return (jax.ShapeDtypeStruct((packed_rows(lanes), 32), jnp.uint8),)


def bucket_executable(impl: str, lanes: int):
    """The one-chip executable for one padded bucket shape: (call, info).

    ``call(packed)`` runs it (async dispatch) on the bucket's ONE packed
    buffer (``pack_batch``'s first value, placed on the device).
    info["exec_cache"] records where it came from: ``memo`` (process
    cache), ``hit`` (deserialized from disk — no tracing, no compilation),
    ``miss``/``stale`` + ``compile_s`` (freshly built and persisted).

    A lowering or compile failure is LOUD: logged at error with the
    compiler's message, counted (``warm_stats`` ``compile_failures``),
    latched in ``_AOT_BROKEN`` and raised as ``TierCompileError`` — the
    supervised callers demote the tier through its breaker.

    On a host whose elastic mesh is active, a bucket's FIRST resolution
    also resolves what the served path launches for it there: the
    mesh-wide executable at the host's width, for every bucket the mesh
    takes (``info["mesh"]``; ``_resolve_mesh_wide``).  So whoever warms a
    bucket before serving (a node's start, ``chip_smoke.py``, the
    benchmark's set-up) leaves nothing to compile inside a request, under
    the watchdog's deadline."""
    key = (impl, lanes)
    raise_if_broken(key)
    with _EXEC_LOCK:
        memo = _EXEC_CACHE.get(key)
    if memo is not None:
        return memo, {"exec_cache": "memo"}
    from cometbft_tpu.ops import aot_cache

    wide = _resolve_mesh_wide(impl, lanes)
    try:
        call, info = aot_cache.load_or_compile(
            _bucket_jitted(impl), _bucket_shapes(lanes), bucket_tag(impl, lanes)
        )
    except Exception as e:  # noqa: BLE001 — whatever the compiler raised
        raise compile_failed(
            key, f"verify tier {bucket_tag(impl, lanes)}", e
        ) from e
    with _EXEC_LOCK:
        # two racing compilers: first writer wins, both results correct
        call = _EXEC_CACHE.setdefault(key, call)
    if wide:
        info = {**info, "mesh": wide}
    return call, info


def _resolve_mesh_wide(impl: str, lanes: int) -> "Optional[dict]":
    """The mesh-wide executable the served path launches for a batch of
    this bucket (``ops/supervisor._launch_mesh``), resolved through the
    executable cache: {tag: info}, or None where no mesh-wide launch can
    reach the bucket (one chip, a mesh of another tier, a bucket under
    ``elastic.min_batch()``).  Only the full width: a shrunken mesh's
    executables are ``ops/warmboot``'s (``COMETBFT_TPU_WARMBOOT_MESH_SHRINK``).
    A failure is loud and latched like any compile failure, and raised
    nowhere: the supervisor meets it again at the launch and takes the
    single-chip chain."""
    from cometbft_tpu.parallel import elastic

    _maybe_enable_mesh()
    if not elastic.active() or lanes < bucket_size(elastic.min_batch(), 1):
        return None
    from cometbft_tpu.parallel import mesh as pmesh

    ordinals = elastic.configured_ordinals()
    if pmesh.tier_of(ordinals) != impl:
        return None
    m = pmesh.mesh_of(ordinals)
    padded = lanes + (-lanes) % len(ordinals)
    tag = pmesh.mesh_tag(impl, len(ordinals), padded, donation_enabled())
    try:
        _, info = pmesh.sharded_verify_call(m, padded, impl)
    except TierCompileError as e:
        return {tag: {"error": str(e)}}
    return {tag: dict(info)}


def reset_executable_memo() -> None:
    """Drop the in-process executable memos — both this layer's and
    aot_cache's probe/memo/latch state (tests: force disk loads)."""
    with _EXEC_LOCK:
        _EXEC_CACHE.clear()
    _AOT_BROKEN.clear()
    from cometbft_tpu.ops import aot_cache

    aot_cache.reset_memo()


def prepare_batch(
    pubs: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    min_bucket: int = _PALLAS_MIN_BUCKET,
):
    """Host-side packing.  Returns (arrays, n, structural_ok): ``arrays``
    holds the padded uint8 device inputs and structural_ok marks
    length-valid entries.  ``min_bucket`` floors the padding bucket —
    callers that might run the Pallas kernel (or shard across a mesh) keep
    the conservative 128 default; the plain-XLA single-chip path passes
    the small-bucket floor.

    The per-signature SHA-512 + mod-L math runs in the C++ sidecar when
    available (cometbft_tpu/native — the host half of the verify pipeline),
    one call from the caller's lists into the padded buffers; the Python
    loop (``_pack_python``) is the fallback and the differential oracle for
    it.
    """
    packed, n, structural, _ = pack_batch(pubs, msgs, sigs, min_bucket)
    return packed_views(packed), n, structural


def pack_batch(pubs, msgs, sigs, min_bucket: int = _PALLAS_MIN_BUCKET):
    """``prepare_batch`` as the one-chip executable takes it, and how it
    went: (packed, n, structural, how).  ``packed`` is the ONE buffer
    (``packed_rows``; ``packed_views`` names its five arrays), ``how``
    ``{"path": "native" | "python", "laps": (glue, native)}``, the two
    halves of the stage timed where they ran (``tracing.lap``; the second
    never opened on the Python path) for whoever holds the stage's span to
    record under it (``ops/supervisor._pack``)."""
    n = len(pubs)
    b = bucket_size(max(n, 1), min_bucket)
    glue = tracing.lap("verify.pack.glue")
    call = tracing.lap("verify.pack.native")
    pack_into = _native_pack_into() if n else None
    with glue:
        # one allocation: the four tables and s_ok, written in place
        packed = np.zeros((packed_rows(b), 32), np.uint8)
        bufs = tuple(packed_views(packed).values())
        structural = np.zeros((b,), bool)
        if pack_into is None:
            _pack_python(pubs, msgs, sigs, bufs, structural)
        else:
            args, _alive = _native_args(pubs, msgs, sigs, bufs, structural)
    path = "python"
    if pack_into is not None:
        with call:
            rc = pack_into(*args)
        if rc == 0:
            path = "native"
        else:  # refused before anything was written: no row can be outside
            structural[:] = False
            _pack_python(pubs, msgs, sigs, bufs, structural)
    return packed, n, structural, {"path": path, "laps": (glue, call)}


def _native_pack_into():
    """The sidecar's in-place pack, or None: no toolchain, the library
    switched off, or a library built before the symbol existed."""
    from cometbft_tpu import native

    return getattr(native.lib(), "ed25519_pack_into", None)


def _native_args(pubs, msgs, sigs, bufs, structural) -> tuple:
    """The arguments of ``ed25519_pack_into`` for n >= 1 triples, and
    ``structural`` marked.  Where every key is 32 and every signature 64
    bytes long (asked of the whole lists at once; every commit and vote a
    node builds) the lists are joined as they are and signature i goes to
    row i; else the entries of a right length are, with their rows.  The
    arrays go as addresses: the second value holds what must outlive the
    call."""
    n = len(pubs)
    if (
        len(msgs) == n
        and len(sigs) == n
        and set(map(len, pubs)) == {32}
        and set(map(len, sigs)) == {64}
    ):
        k, rows = n, None
        structural[:n] = True
    else:
        ok = [i for i in range(n) if len(pubs[i]) == 32 and len(sigs[i]) == 64]
        k, rows = len(ok), np.asarray(ok, np.int64)
        structural[rows] = True
        pubs = [pubs[i] for i in ok]
        msgs = [msgs[i] for i in ok]
        sigs = [sigs[i] for i in ok]
    lens = np.fromiter(map(len, msgs), np.int64, k)
    args = (
        b"".join(pubs),
        b"".join(sigs),
        b"".join(msgs),
        _address(lens) if k else None,
        k,
        None if rows is None or not k else _address(rows),
        structural.shape[0],
        *map(_address, bufs),
    )
    return args, (lens, rows)


def _address(arr: np.ndarray) -> int:
    """Where a writable array's first byte lies (``arr.ctypes.data`` at a
    third of its price: the single vote pays this seven times)."""
    return ctypes.addressof(ctypes.c_char.from_buffer(arr))


def _pack_python(pubs, msgs, sigs, bufs, structural) -> None:
    """The pack a signature at a time: the fallback, and the oracle the
    sidecar is tested against."""
    pub_arr, r_arr, s_bytes, m_bytes, s_ok = bufs
    for i in range(len(pubs)):
        pub, msg, sig = pubs[i], msgs[i], sigs[i]
        if len(pub) != 32 or len(sig) != 64:
            continue
        structural[i] = True
        r_enc, s_enc = sig[:32], sig[32:]
        s = int.from_bytes(s_enc, "little")
        s_ok[i] = s < L_INT
        h = int.from_bytes(
            hashlib.sha512(r_enc + pub + msg).digest(), "little"
        ) % L_INT
        m = (L_INT - h) % L_INT
        pub_arr[i] = np.frombuffer(pub, np.uint8)
        r_arr[i] = np.frombuffer(r_enc, np.uint8)
        if s_ok[i]:
            s_bytes[i] = np.frombuffer(s_enc, np.uint8)
        m_bytes[i] = np.frombuffer(m.to_bytes(32, "little"), np.uint8)


_MESH_PROBED = [False]


def _maybe_enable_mesh() -> None:
    """One-time device probe deciding elastic-mesh activation
    (parallel/elastic): >= 2 devices AND either an all-TPU fleet (the
    production multi-chip host) or an explicit ``COMETBFT_TPU_MESH=1``
    (the CPU dry-run / bench harnesses with a forced virtual mesh).
    Single-chip hosts — and the CI suite's forced 8-device CPU mesh,
    which is virtual parallelism over two cores, not hardware — keep the
    exact pre-mesh supervised path.  ``COMETBFT_TPU_MESH=0`` vetoes
    auto-activation outright; scenarios/tests that configured the mesh
    explicitly are left untouched."""
    if _MESH_PROBED[0]:
        return
    _MESH_PROBED[0] = True
    from cometbft_tpu.parallel import elastic

    if not elastic.enabled() or elastic.configured():
        return
    force = os.environ.get("COMETBFT_TPU_MESH")
    if force == "0":
        return
    try:
        devs = jax.devices()
    except Exception:  # noqa: BLE001 — backend init failed: single-chip
        return
    if len(devs) < 2:
        return
    if force == "1" or all(d.platform == "tpu" for d in devs):
        from cometbft_tpu.parallel import mesh as pmesh

        ordinals = pmesh.register_devices(devs)
        elastic.configure(ordinals)


def reset_mesh_probe() -> None:
    """Forget the one-time activation probe (tests)."""
    _MESH_PROBED[0] = False


def verify_batch(
    pubs: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
) -> np.ndarray:
    """Verify a batch; returns (n,) bool numpy array of per-signature results.

    Supervised (ops/supervisor): the dispatch runs under a watchdog
    deadline and a device failure degrades down the verified chain
    pallas -> xla -> host instead of raising — accept bits are always
    definitive verdicts, never infrastructure errors in disguise.  On a
    multi-chip host the supervised path shards across the elastic device
    mesh first (``parallel/elastic`` — one sick chip loses a lane, not the
    fleet); ``_maybe_enable_mesh`` decides activation once per process."""
    from cometbft_tpu.ops import supervisor

    _maybe_enable_mesh()
    return supervisor.verify_supervised(pubs, msgs, sigs)


def verify_batches_overlapped(
    work: "Sequence[tuple[Sequence[bytes], Sequence[bytes], Sequence[bytes]]]",
) -> list:
    """Verify several (pubs, msgs, sigs) batches with host/device overlap:
    every batch is DISPATCHED (``supervisor.dispatch_verify``) before the
    first result is fetched, so the host prep (SHA-512 + packing) of batch
    i+1 runs while the device ladders batch i, and on backends that queue
    dispatches the kernels pipeline (VERDICT r4 #3 — amortizing the
    per-dispatch floor across consecutive commits).  Whether dispatches
    pipeline on the attached chip is not measured yet.

    Returns a list of (n,) bool arrays, one per input batch.

    Each dispatch and each fetch runs under the watchdog, a mid-window
    device failure re-runs the affected batch on the next tier down, and
    with every device breaker open the whole window resolves on the host —
    degraded, never aborted."""
    from cometbft_tpu.ops import supervisor

    # The breaker opens at its third failure, so by itself it would let a
    # window pay the watchdog deadline three times over for one stuck
    # device.  The window's own rule: once a dispatch has failed, nothing
    # more is dispatched; once a deadline has passed, nothing more is
    # waited for.  What is left re-verifies below the backend at fault.
    handles: list = []
    failed = None  # the handle whose dispatch failed
    for pubs, msgs, sigs in work:
        h = None
        if failed is None:
            h = supervisor.dispatch_verify(pubs, msgs, sigs)
            if h.error is not None:
                failed = h
        handles.append(h)
    wedged = failed is not None and isinstance(
        failed.error, supervisor.DispatchTimeoutError
    )
    out = []
    for (pubs, msgs, sigs), h in zip(work, handles):
        if h is None:
            out.append(
                supervisor.verify_supervised(
                    pubs, msgs, sigs, skip=failed.skip, mesh=False
                )
            )
            continue
        if wedged:
            h.give_up()
        out.append(supervisor.fetch_verify(h))
        wedged = wedged or isinstance(
            h.error, supervisor.DispatchTimeoutError
        )
    return out


def _fuse(work) -> tuple:
    """Several segments laid end to end, for ONE bucket-padded dispatch."""
    pubs: list = []
    msgs: list = []
    sigs: list = []
    for p, m, s in work:
        pubs.extend(p)
        msgs.extend(m)
        sigs.extend(s)
    if len(work) > 1:
        dispatch_stats.record_fused(len(work))
    return pubs, msgs, sigs


def _split(bits, sizes) -> "list[np.ndarray]":
    """The fused dispatch's accept bits, back out per segment."""
    out = []
    off = 0
    for n in sizes:
        out.append(bits[off : off + n])
        off += n
    return out


def verify_segments(
    work: "Sequence[tuple[Sequence[bytes], Sequence[bytes], Sequence[bytes]]]",
) -> "list[np.ndarray]":
    """Fused multi-segment verification: concatenate several (pubs, msgs,
    sigs) segments into ONE bucket-padded device batch and split the accept
    bits back out per segment, so K consecutive commits cost one dispatch
    instead of K (bench.py's ``dispatch_floor_ms`` is otherwise paid per
    height).  Bitwise-equal to calling ``verify_batch`` per segment: every
    lane is verified independently, so fusing cannot couple results across
    segments (tests/test_verify_stream.py pins this property).

    Falls back to ``verify_batches_overlapped`` when the concatenation
    would overflow the largest bucket — past that size there is no single
    dispatch to fuse into, and the overlapped pipeline is the next-best
    amortization.

    Returns a list of (n_i,) bool arrays, one per input segment."""
    sizes = [len(p) for p, _, _ in work]
    total = sum(sizes)
    if total == 0:
        return [np.zeros(0, dtype=bool) for _ in work]
    if total > _BUCKETS[-1]:
        return verify_batches_overlapped(work)
    return _split(verify_batch(*_fuse(work)), sizes)


# -- in-flight pipeline seam (docs/verify-scheduler.md) -----------------------
#
# The async half of ``verify_segments``: ``dispatch_segments`` ships one
# fused flush toward the device (or a pinned mesh lane) without blocking
# on its verdicts, and ``fetch_segments`` resolves them later — the
# verifysched completion pool keeps K of these in flight so host prep of
# flush i+1 overlaps device compute of flush i.  Bitwise-equal to
# ``verify_segments`` for any single handle (same fused concatenation,
# same supervised degradation chain at fetch time).


class _SegmentsHandle:
    """One fused multi-segment verify between dispatch and fetch; ``sup``
    is the supervisor's handle, None where nothing is in flight."""

    __slots__ = ("sizes", "work", "sup")

    def __init__(self, work, sizes):
        self.work = work
        self.sizes = sizes
        self.sup = None


def dispatch_segments(work, lane=None) -> _SegmentsHandle:
    """Async half of ``verify_segments``: returns a handle whose verdicts
    ``fetch_segments`` resolves later.  With no ``lane``, a fused batch
    the elastic mesh takes is ONE launch over every healthy chip; ``lane``
    pins a smaller one at one elastic-mesh ordinal (round-robined by the
    scheduler).
    Shapes with no single fused dispatch (empty, or overflowing the
    largest bucket) resolve synchronously at fetch time."""
    from cometbft_tpu.ops import supervisor

    work = [(list(p), list(m), list(s)) for p, m, s in work]
    sizes = [len(p) for p, _, _ in work]
    h = _SegmentsHandle(work, sizes)
    if 0 < sum(sizes) <= _BUCKETS[-1]:
        pubs, msgs, sigs = _fuse(work)
        _maybe_enable_mesh()
        h.sup = supervisor.dispatch_verify(pubs, msgs, sigs, lane=lane)
    return h


def fetch_segments(h: _SegmentsHandle) -> "list[np.ndarray]":
    """Resolve one in-flight fused dispatch: list of (n_i,) bool arrays,
    one per input segment.  Like ``verify_segments``, cannot raise for
    infrastructure reasons — the supervisor degrades a failed/wedged lane
    alone and re-verifies down the chain."""
    if h.sup is None:
        return verify_segments(h.work)
    from cometbft_tpu.ops import supervisor

    return _split(supervisor.fetch_verify(h.sup), h.sizes)


def verify_pipelined(
    pubs: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    inflight: "int | None" = None,
) -> np.ndarray:
    """Verify one LARGE batch by chunking it across mesh lanes with K
    chunk dispatches in flight — the headline 10240-sig commit shape runs
    through here (``__graft_entry__.dryrun_multichip``, ``bench.py
    --multichip``) instead of one monolithic full-shape dispatch.  Chunks
    round-robin over ``elastic.healthy_ordinals()`` when the mesh is
    active (each lane carries its own dispatch); on a single chip the
    depth floor of 2 still overlaps host prep with device compute.
    Bitwise-equal to ``verify_batch``: chunking splits lanes, never
    couples them.

    Sits BELOW verifysched deliberately: the scheduler's in-flight dedup
    would collapse repeated triples (the dry run tiles a small distinct
    set), and a commit this large is one caller's synchronous wait, not
    queued gossip."""
    from cometbft_tpu.ops import supervisor
    from cometbft_tpu.parallel import elastic

    pubs, msgs, sigs = list(pubs), list(msgs), list(sigs)
    n = len(pubs)
    if n == 0:
        return np.zeros(0, dtype=bool)
    _maybe_enable_mesh()
    ordinals = elastic.healthy_ordinals()
    width = max(len(ordinals), 1)
    depth = int(inflight) if inflight else max(width, 2)
    # chunk = the largest padding bucket each lane can fill when the
    # batch spreads evenly across the mesh — every chunk is then one
    # fully-occupied dispatch (floor: the smallest bucket)
    per_lane = (n + width - 1) // width
    fits = [b for b in _BUCKETS if b <= per_lane]
    chunk = fits[-1] if fits else _BUCKETS[0]
    out = np.zeros(n, dtype=bool)
    pending: "deque[tuple]" = deque()  # (handle, lo, hi)

    def _drain_one() -> None:
        handle, d_lo, d_hi = pending.popleft()
        out[d_lo:d_hi] = supervisor.fetch_verify(handle)

    seq = 0
    lo = 0
    while lo < n:
        hi = min(lo + chunk, n)
        while len(pending) >= depth:
            _drain_one()
        lane = ordinals[seq % width] if ordinals else None
        seq += 1
        pending.append(
            (
                supervisor.dispatch_verify(
                    pubs[lo:hi], msgs[lo:hi], sigs[lo:hi], lane=lane
                ),
                lo,
                hi,
            )
        )
        lo = hi
    while pending:
        _drain_one()
    return out
