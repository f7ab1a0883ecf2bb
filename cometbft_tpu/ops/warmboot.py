"""Warm-boot pass: precompile the padding-bucket × backend verify matrix.

A node that spends its first minute compiling is a node that misses rounds
(ISSUE 8; the committee-consensus measurements in PAPERS.md show commit-path
verification LATENCY decides consensus performance).  This module walks the
collapsed compile matrix — every padding bucket in ``ops.verify._BUCKETS``
for every tier of the supervisor degradation chain — through
``ops.verify.bucket_executable`` at node boot, in a background thread, so
the first real commit meets a resident executable instead of a tracer.
With the on-disk exec cache (``ops/aot_cache.py``) warm from a previous
boot, the whole pass is deserialization: zero tracing, zero compilation.

Supervisor-aware by design:

* each degradation tier is warmed independently (a demoted node re-promotes
  into warm executables, not into a compile);
* a tier whose breaker is OPEN is skipped (warming a dead device is probe
  traffic the breaker exists to prevent);
* a COMPILE failure is logged at error with the compiler's message,
  counted (``warm_failures``, and ``compile_failures`` for the verify
  tiers) and records a breaker failure for that tier; the pass moves on —
  boot is never wedged, and the tier demotes through the same machinery a
  dispatch failure would use.

Enablement: ``COMETBFT_TPU_WARMBOOT=1/0`` overrides; the default is ON
exactly when the trusted ``tpu`` batch backend is active (the gate the
fused stream / scheduler / tx-ingest share) — CPU-backend nodes and test
processes never burn minutes compiling shapes they dispatch in
milliseconds.  ``COMETBFT_TPU_WARMBOOT_BUCKETS`` (comma-separated) bounds
the matrix (bench and tests use it).

Counters land in ``ops/warm_stats`` (warm_runs / warm_seconds /
shapes_warmed / shapes_pruned / warm_failures) and surface as
``cometbft_crypto_warmboot_*`` metrics.  docs/warm-boot.md is the design
note.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Optional

logger = logging.getLogger("cometbft_tpu.crypto")

_LOCK = threading.Lock()
_THREAD: "list[Optional[threading.Thread]]" = [None]
_DONE = threading.Event()  # a pass COMPLETED in this process

# secp256k1 ladder / BLS G1 shapes warmed alongside the ed25519 buckets
# (ROADMAP item 4 follow-up: these used to compile on first use).  Sizes
# are batch lanes (padded to powers of two by their kernels); the defaults
# cover the envelope/evidence/aggregate traffic the verifiers actually
# see.  COMETBFT_TPU_WARMBOOT_SECP_BUCKETS / _BLS_BUCKETS override —
# an EMPTY value skips that family entirely.
DEFAULT_SECP_BUCKETS = (1, 2, 4, 8)
DEFAULT_BLS_BUCKETS = (2, 4, 8)
# sha256 tree kernel lane buckets (docs/proof-serving.md): 64 covers the
# common tx-count range; bigger buckets compile on first use
DEFAULT_MERKLE_BUCKETS = (64,)
DEFAULT_TRANSPORT_BUCKETS = (8,)


def enabled() -> bool:
    """Explicit ``COMETBFT_TPU_WARMBOOT`` wins; otherwise default on for
    the trusted tpu batch backend only.  jax-free (the whole point is
    deciding whether to pay device-backend init)."""
    env = os.environ.get("COMETBFT_TPU_WARMBOOT")
    if env is not None:
        return env != "0"
    from cometbft_tpu.verifysched import service

    return service.backend_trusted()


def _env_buckets() -> "Optional[list[int]]":
    raw = os.environ.get("COMETBFT_TPU_WARMBOOT_BUCKETS")
    if not raw:
        return None
    try:
        return sorted({int(x) for x in raw.split(",") if x.strip()})
    except ValueError:
        return None


def _env_sizes(name: str, default) -> "list[int]":
    """Like ``_env_buckets`` but for the secp/BLS families: unset ->
    the default matrix, an explicitly EMPTY value -> [] (skip family)."""
    raw = os.environ.get(name)
    if raw is None:
        return sorted(default)
    try:
        return sorted({int(x) for x in raw.split(",") if x.strip()})
    except ValueError:
        return sorted(default)


def extra_matrix() -> "list[tuple[str, str, int]]":
    """(breaker, family, lanes) shapes for the secp256k1 ladder and BLS
    G1 kernels.  Breaker names match the ones ``crypto/batch.py`` routes
    these device paths through, so a dead device is skipped and a compile
    failure demotes through the same machinery."""
    shapes = []
    for b in _env_sizes(
        "COMETBFT_TPU_WARMBOOT_SECP_BUCKETS", DEFAULT_SECP_BUCKETS
    ):
        shapes.append(("secp_device", "secp-ladder", b))
    for b in _env_sizes(
        "COMETBFT_TPU_WARMBOOT_BLS_BUCKETS", DEFAULT_BLS_BUCKETS
    ):
        shapes.append(("bls_g1", "bls-g1", b))
    for b in _env_sizes(
        "COMETBFT_TPU_WARMBOOT_MERKLE_BUCKETS", DEFAULT_MERKLE_BUCKETS
    ):
        shapes.append(("merkle_device", "sha256-tree", b))
    for b in _env_sizes(
        "COMETBFT_TPU_WARMBOOT_TRANSPORT_BUCKETS", DEFAULT_TRANSPORT_BUCKETS
    ):
        shapes.append(("aead_device", "transport-aead", b))
        shapes.append(("x25519_device", "transport-x25519", b))
    return shapes


def _warm_extra(family: str, lanes: int) -> "dict[str, dict]":
    """Resolve one secp/BLS shape's executables (no dispatch).  The seam
    tests monkeypatch — exactly like ``ov.bucket_executable`` for the
    ed25519 matrix.  Returns {exec-cache tag: info}."""
    if family == "secp-ladder":
        from cometbft_tpu.ops import secp_verify

        return {
            secp_verify.ladder_tag(lanes): secp_verify.warm_ladder(lanes)
        }
    if family == "sha256-tree":
        from cometbft_tpu.ops import sha256_tree

        return sha256_tree.warm_kernels(lanes)
    if family == "transport-aead":
        from cometbft_tpu.ops import chacha_aead

        return chacha_aead.warm_kernels(lanes)
    if family == "transport-x25519":
        from cometbft_tpu.ops import x25519_ladder

        return {
            x25519_ladder.ladder_tag(lanes): x25519_ladder.warm_ladder(lanes)
        }
    from cometbft_tpu.ops import bls_g1

    return bls_g1.warm_kernels(lanes)


def mesh_shrink_enabled() -> bool:
    """``COMETBFT_TPU_WARMBOOT_MESH_SHRINK=1`` opts the warm pass into
    precompiling the elastic mesh's shrink-ladder executables (default
    off: each mesh width is a full sharded compile, and single-chip
    hosts have no ladder to warm).  Implies nothing when the mesh
    supervisor is off or unconfigured."""
    return os.environ.get("COMETBFT_TPU_WARMBOOT_MESH_SHRINK", "0") == "1"


def mesh_shrink_matrix() -> "list[tuple[int, int]]":
    """(width, lanes) mesh shapes to warm: the full width AND the first
    shrink step (N-1) at the smallest padding bucket — the shape the
    first post-shrink dispatch needs mid-consensus.  Empty when the
    shrink warm-up is off, the mesh supervisor is off, or fewer than 2
    devices are configured."""
    if not mesh_shrink_enabled():
        return []
    from cometbft_tpu.parallel import elastic

    if not elastic.enabled() or not elastic.configured():
        return []
    n = elastic.total_width()
    if n < 2:
        return []
    from cometbft_tpu.ops import verify as ov

    lanes = ov.bucket_size(1, ov._min_bucket())
    return [(w, lanes) for w in (n, n - 1) if w >= 2]


def _warm_mesh(width: int, lanes: int) -> "dict[str, dict]":
    """Resolve one shrink-ladder mesh executable (no dispatch) — the
    monkeypatchable seam, exactly like ``_warm_extra``.  Returns
    {exec-cache tag: info}."""
    from cometbft_tpu.parallel import mesh as pmesh

    return pmesh.warm_shrink_shape(width, lanes)


def warm_matrix() -> "list[tuple[str, int]]":
    """(backend, bucket) shapes to warm, smallest buckets first so the
    commit-sized shapes (votes, small validator sets) come online before
    the 32k bench sweeps.  Honors each tier's padding floor (Pallas never
    dispatches sub-128 buckets) and the env bucket bound."""
    from cometbft_tpu.ops import supervisor
    from cometbft_tpu.ops import verify as ov

    buckets = _env_buckets() or list(ov._BUCKETS)
    shapes = []
    for b in sorted(buckets):
        for backend in supervisor.device_chain():
            floor = (
                ov._PALLAS_MIN_BUCKET
                if backend == "pallas"
                else ov._BUCKETS[0]
            )
            if b >= floor and b in ov._BUCKETS:
                shapes.append((backend, b))
    return shapes


def run() -> dict:
    """Synchronously warm the matrix; returns a report dict.

    ``statuses`` maps ``"backend-bucket"`` to the exec_cache outcome
    (``hit`` / ``miss``+compiled / ``memo`` / ``error:*`` / ``skipped:
    breaker-open``).  Never raises."""
    from cometbft_tpu.crypto import backend_health
    from cometbft_tpu.libs import tracing

    t0 = time.perf_counter()
    reg = backend_health.registry()
    statuses: dict = {}
    dead: set = set()
    # the with-block makes the root span exception-safe: a raise anywhere
    # in the walk must not leak it onto the thread-local stack (every
    # later span on this thread would mis-parent under it)
    with tracing.span("warmboot.run"):
        return _run_matrices(reg, statuses, dead, t0)


def _run_matrices(reg, statuses: dict, dead: set, t0: float) -> dict:
    """The matrix walk half of ``run()``, executed inside the root span."""
    from cometbft_tpu.crypto import backend_health
    from cometbft_tpu.libs import tracing
    from cometbft_tpu.ops import verify as ov
    from cometbft_tpu.ops import warm_stats

    warmed = failures = 0
    for backend, bucket in warm_matrix():
        key = f"{backend}-{bucket}"
        if backend in dead:
            statuses[key] = "skipped:tier-demoted"
            continue
        if reg.breaker(backend).state == backend_health.OPEN:
            statuses[key] = "skipped:breaker-open"
            continue
        try:
            # warm progress is span-visible: one span per shape, child of
            # the pass's root span (docs/observability.md)
            with tracing.span(
                "warmboot.shape", family="ed25519", tier=backend,
                lanes=bucket,
            ) as shape_sp:
                _, info = ov.bucket_executable(backend, bucket)
                shape_sp.set(
                    exec_cache=str(info.get("exec_cache", "compiled"))
                )
            # a miss/stale probe that then compiled reports "compiled" —
            # the per-shape statuses are what bench --warmboot asserts on
            status = (
                "compiled"
                if "compile_s" in info
                else str(info.get("exec_cache", "?"))
            )
            statuses[key] = status
            warmed += 1
        except Exception as e:  # noqa: BLE001 — a compile failure demotes
            # the tier via the breaker; boot itself never wedges
            failures += 1
            dead.add(backend)
            statuses.setdefault(key, f"error:{type(e).__name__}")
            reg.breaker(backend).record_failure(e)
            reg.record_demotion(backend)
            logger.error(
                "warm-boot: compiling %s failed (%r); tier demoted via "
                "breaker, continuing with the next tier",
                key,
                e,
            )
    # secp256k1 ladder + BLS G1 kernels (ROADMAP item 4 follow-up: they
    # used to compile on first use).  Same contract as the ed25519 loop:
    # OPEN breakers are skipped, a compile failure records a breaker
    # failure for that device family and moves on — boot never wedges.
    for breaker, family, lanes in extra_matrix():
        key = f"{family}-{lanes}"
        if breaker in dead:
            statuses[key] = "skipped:tier-demoted"
            continue
        if reg.breaker(breaker).state == backend_health.OPEN:
            statuses[key] = "skipped:breaker-open"
            continue
        try:
            with tracing.span(
                "warmboot.shape", family=family, tier=breaker, lanes=lanes
            ) as shape_sp:
                infos = _warm_extra(family, lanes)
                shape_sp.set(tags=len(infos))
            for tag, info in infos.items():
                status = (
                    "compiled"
                    if "compile_s" in info
                    else str(info.get("exec_cache", "?"))
                )
                statuses[tag] = status
                if not status.startswith(("unsupported", "no-roundtrip")):
                    warmed += 1
        except Exception as e:  # noqa: BLE001 — a compile failure demotes
            # the device family via its breaker; boot itself never wedges
            failures += 1
            dead.add(breaker)
            statuses.setdefault(key, f"error:{type(e).__name__}")
            reg.breaker(breaker).record_failure(e)
            reg.record_demotion(breaker)
            logger.warning(
                "warm-boot: compiling %s failed (%r); %s demoted via "
                "breaker, continuing",
                key,
                e,
                breaker,
            )
    # elastic-mesh shrink ladder (COMETBFT_TPU_WARMBOOT_MESH_SHRINK):
    # precompile the (N, N-1)-width sharded executables at the smallest
    # bucket so the first post-shrink dispatch meets a resident
    # executable instead of a cold compile mid-consensus.  Same contract
    # as every other family: a compile failure is counted and logged,
    # never wedges boot (no breaker here — no single tier represents the
    # whole mesh; a genuinely sick chip demotes through its own
    # mesh_dev* breaker at dispatch time).
    for width, lanes in mesh_shrink_matrix():
        key = f"mesh{width}-{lanes}"
        try:
            with tracing.span(
                "warmboot.shape", family="mesh", tier=f"mesh{width}",
                lanes=lanes,
            ) as shape_sp:
                infos = _warm_mesh(width, lanes)
                shape_sp.set(tags=len(infos))
            for tag, info in infos.items():
                status = (
                    "compiled"
                    if "compile_s" in info
                    else str(info.get("exec_cache", "?"))
                )
                statuses[tag] = status
                if not status.startswith("broken"):
                    warmed += 1
        except Exception as e:  # noqa: BLE001 — boot never wedges
            failures += 1
            statuses.setdefault(key, f"error:{type(e).__name__}")
            logger.warning(
                "warm-boot: mesh shrink shape %s failed (%r); continuing",
                key,
                e,
            )
    # shapes the collapsed matrix no longer pays, per warmed tier
    tiers = {b for b, _ in warm_matrix()} or {"xla"}
    pruned = len(ov._PRUNED_BUCKETS) * len(tiers)
    seconds = time.perf_counter() - t0
    warm_stats.record_warm_run(seconds, warmed, pruned, failures)
    report = {
        "statuses": statuses,
        "warmed": warmed,
        "failures": failures,
        "pruned": pruned,
        "seconds": round(seconds, 3),
    }
    logger.info(
        "warm-boot: %d shapes warm in %.1fs (%d failures, %d pruned)",
        warmed,
        seconds,
        failures,
        pruned,
    )
    return report


def start() -> "Optional[threading.Thread]":
    """Kick the warm-boot pass on a background daemon thread (node boot
    path).  No-op when disabled, already running, or already COMPLETED in
    this process — the matrix only needs warming once, and re-running it
    would double-count warm_runs/shapes metrics on every late
    ``ensure_started`` call site (the verifysched dispatcher).  Returns
    the thread (the finished one after completion)."""
    if not enabled():
        return None
    with _LOCK:
        t = _THREAD[0]
        if t is not None and (t.is_alive() or _DONE.is_set()):
            return t
        t = threading.Thread(target=_run_once, name="crypto-warmboot",
                             daemon=True)
        _THREAD[0] = t
        t.start()
        return t


def _run_once() -> None:
    try:
        run()
    finally:
        _DONE.set()


def ensure_started() -> None:
    """Idempotent ``start`` for lazy call sites (the verifysched
    dispatcher kicks it when the scheduler first activates)."""
    try:
        start()
    except Exception:  # noqa: BLE001 — warm-boot is never load-bearing
        pass


def reset() -> None:
    """Forget the started thread and the completion latch (tests)."""
    with _LOCK:
        _THREAD[0] = None
        _DONE.clear()
