"""Multi-chip sharding of the Ed25519 batch-verify kernel.

The reference's scale axis is validator-set size: a 10k-validator commit is
one batch of 10k independent signature checks (SURVEY.md §2.2 — the
"data-parallel crypto batching" axis; types/validation.go:220-324).  On TPU
that maps to sharding the signature batch across a 1-D device mesh: each chip
ladders its shard, the per-signature accept bits stay sharded (failure
attribution is local), and a single ``psum`` over the mesh produces the
global verdict — the only cross-chip traffic is one scalar per shard, riding
ICI.

This is the TPU-native analog of the reference spreading commit verification
across CPU cores; there the batch is a single random-linear-combination MSM
(curve25519-voi), here it is N independent lanes, so sharding is embarrassing
and the collective cost is O(1).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from cometbft_tpu.libs import tracing
from cometbft_tpu.ops import dispatch_stats
from cometbft_tpu.ops import fe25519 as fe
from cometbft_tpu.ops import verify as ov

SIG_AXIS = "sig"
# Packed batch arrays from ops.verify.prepare_batch: raw bytes, batch-major
# (B, 32) — limb unpacking happens per-shard on device.
ARG_ORDER = ov.ARG_NAMES


def make_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D mesh over all (or the given) devices; axis name ``sig``."""
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), (SIG_AXIS,))


# -- stable physical ordinals -------------------------------------------------
#
# Shard attribution must survive mesh reconfiguration: after a shrink, the
# surviving chips keep the ordinal they had in the FULL mesh — a
# ``mesh.shard`` span or ``cometbft_crypto_shard_dispatch_seconds{device=}``
# series must mean the same physical chip across every width, or a
# post-shrink outlier would masquerade as a different device.  Ordinals are
# assigned first-sight; the base registry seeds from ``jax.devices()`` in
# enumeration order, so on a normal host stable ordinal == device index.

_ORDINAL_BY_KEY: dict = {}  # (platform, id) -> stable ordinal
_DEVICE_BY_ORDINAL: dict = {}  # stable ordinal -> jax.Device


def _ensure_base_registry() -> None:
    if _ORDINAL_BY_KEY:
        return
    try:
        base = jax.devices()
    except Exception:  # noqa: BLE001 — backend init failed: first-sight
        return
    for d in base:
        key = (d.platform, d.id)
        if key not in _ORDINAL_BY_KEY:
            _ORDINAL_BY_KEY[key] = len(_ORDINAL_BY_KEY)
            _DEVICE_BY_ORDINAL[_ORDINAL_BY_KEY[key]] = d


def register_devices(devices) -> "list[int]":
    """Assign (or look up) stable physical ordinals for ``devices``;
    returns them in order."""
    _ensure_base_registry()
    out = []
    for d in devices:
        key = (d.platform, d.id)
        o = _ORDINAL_BY_KEY.get(key)
        if o is None:
            o = len(_ORDINAL_BY_KEY)
            _ORDINAL_BY_KEY[key] = o
            _DEVICE_BY_ORDINAL[o] = d
        out.append(o)
    return out


def stable_ordinal(device) -> int:
    """The device's stable physical ordinal, or -1 when it was never
    registered (sorts last in shard iteration)."""
    return _ORDINAL_BY_KEY.get((device.platform, device.id), -1)


def device_for_ordinal(ordinal: int):
    return _DEVICE_BY_ORDINAL.get(int(ordinal))


def mesh_of(ordinals: "Sequence[int]") -> Mesh:
    """The 1-D mesh over the devices with the given stable ordinals, in
    that order."""
    _ensure_base_registry()
    return make_mesh([_DEVICE_BY_ORDINAL[int(o)] for o in ordinals])


def tier_of(ordinals: "Sequence[int]") -> str:
    """The kernel tier a mesh over these ordinals runs (``select_impl``)."""
    return ov.select_impl(mesh_of(ordinals).devices.flat)


def _verify_shard(a_bytes, r_bytes, s_bytes, m_bytes, s_ok, *, impl: str):
    """Per-device body: verify the local shard through the SAME kernel the
    single-chip path selects (Pallas on TPU meshes, XLA elsewhere —
    ``ops.verify.select_impl``), contribute to the global accept count via
    one psum (the only collective)."""
    if impl == "pallas":
        from cometbft_tpu.ops import pallas_verify

        accept = pallas_verify.verify_core_pallas(
            a_bytes, r_bytes, s_bytes, m_bytes, s_ok
        )
    else:
        accept = ov.verify_core(a_bytes, r_bytes, s_bytes, m_bytes, s_ok)
    n_ok = jax.lax.psum(jnp.sum(accept.astype(jnp.int32)), SIG_AXIS)
    return accept, n_ok


_FN_CACHE: dict = {}


def sharded_verify_fn(
    mesh: Mesh, impl: Optional[str] = None, donated: bool = False
):
    """jit-compiled mesh-sharded verifier.  Inputs are the packed batch arrays
    from ``ops.verify.prepare_batch`` padded to a multiple of the mesh size;
    raw byte arrays are (B, 32) sharded on the batch (lane) axis, scalars
    (B,) sharded likewise.  ``impl`` overrides kernel selection (tests).

    ``donated=True`` donates all five input buffers (ROADMAP item 4's mesh
    leftover): the packed arrays are repacked per dispatch and placed fresh
    by ``device_put_args``, so no caller observes a donated shard's
    invalidation (docs/warm-boot.md) — XLA reuses the shards' HBM for the
    kernel's scratch instead of allocating alongside them."""
    impl = impl or ov.select_impl(mesh.devices.flat)
    key = (impl, bool(donated)) + tuple(
        (d.platform, d.id) for d in mesh.devices.flat
    )
    if key in _FN_CACHE:
        return _FN_CACHE[key]
    batch_first, vec = mesh_shardings(mesh)
    fn = shard_map(
        partial(_verify_shard, impl=impl),
        mesh=mesh,
        in_specs=(
            P(SIG_AXIS, None),  # a_bytes (B, 32)
            P(SIG_AXIS, None),  # r_bytes (B, 32)
            P(SIG_AXIS, None),  # s_bytes (B, 32)
            P(SIG_AXIS, None),  # m_bytes (B, 32)
            P(SIG_AXIS),        # s_ok (B,)
        ),
        out_specs=(P(SIG_AXIS), P()),
        # The per-shard body runs ~3k traced field ops whose literal
        # constants are unvarying; jax 0.9's vma tracker rejects mixing
        # them with varying operands ("Primitive mul requires varying
        # manual axes to match ... as a temporary workaround pass
        # check_vma=False").  The body is collective-free except for the
        # single psum, so the vma checker adds no safety here.
        check_vma=False,
    )
    jitted = jax.jit(
        fn, donate_argnums=tuple(range(5)) if donated else ()
    )
    out = (jitted, (batch_first, vec))
    _FN_CACHE[key] = out
    return out


_CALL_CACHE: dict = {}


def mesh_tag(impl: str, n_dev: int, lanes: int, donated: bool = False) -> str:
    """On-disk exec-cache tag for one (kernel, topology, bucket) mesh
    executable — what lets a restarted dry-run/bench process load the
    sharded executable instead of re-lowering per shard count.  Donation
    changes the compiled artifact (input aliasing), so donated executables
    get their own entry."""
    base = f"mesh-{impl}-{n_dev}dev-{lanes}"
    return base + "-donated" if donated else base


def sharded_verify_call(
    mesh: Mesh,
    lanes: int,
    impl: Optional[str] = None,
    donated: Optional[bool] = None,
):
    """AOT-cached mesh-sharded verify executable for a ``lanes``-lane
    padded batch: returns (call, info).  ``call(*device_put_args(...))``
    runs it.  The executable is resolved through ``ops.aot_cache`` —
    deserialized from disk when a previous process compiled this
    (impl, topology, lanes, donated) shape (the multichip dry-run's
    10240-sig commit no longer re-lowers on every invocation) — and
    memoized per process.  A lowering or compile failure is loud
    (``ops.verify.compile_failed``: logged at error, counted, latched) and
    raised: the elastic supervisor drops the batch to the single-chip
    chain, and nothing retries the compile quietly through plain jit.
    ``donated`` defaults to the mesh's donation policy
    (``ops.verify.donation_enabled`` — Pallas/TPU on, CPU CI off)."""
    impl = impl or ov.select_impl(mesh.devices.flat)
    if donated is None:
        donated = ov.donation_enabled()
    n_dev = mesh.devices.size
    key = (impl, lanes, bool(donated)) + tuple(
        (d.platform, d.id) for d in mesh.devices.flat
    )
    hit = _CALL_CACHE.get(key)
    if hit is not None:
        return hit, {"exec_cache": "memo"}
    jitted, _ = sharded_verify_fn(mesh, impl, donated=donated)
    ov.raise_if_broken(("mesh",) + key)
    from cometbft_tpu.ops import aot_cache

    batch_first, vec = mesh_shardings(mesh)
    byte = jax.ShapeDtypeStruct((lanes, 32), jnp.uint8, sharding=batch_first)
    specs = (
        byte,
        byte,
        byte,
        byte,
        jax.ShapeDtypeStruct((lanes,), jnp.bool_, sharding=vec),
    )
    try:
        call, info = aot_cache.load_or_compile(
            jitted, specs, mesh_tag(impl, n_dev, lanes, donated)
        )
    except Exception as e:  # noqa: BLE001 — whatever the compiler raised
        raise ov.compile_failed(
            ("mesh",) + key,
            f"mesh verify {mesh_tag(impl, n_dev, lanes, donated)}",
            e,
        ) from e
    _CALL_CACHE[key] = call
    return call, info


def mesh_shardings(mesh: Mesh) -> tuple:
    """(batch-major 2-D, vector) NamedShardings for the packed batch
    arrays.  Depends only on the mesh — split out of sharded_verify_fn so
    placement never constructs a jitted fn as a side effect (ADVICE r4)."""
    return (
        NamedSharding(mesh, P(SIG_AXIS, None)),
        NamedSharding(mesh, P(SIG_AXIS)),
    )


def device_put_args(arrays: dict, mesh: Mesh) -> list:
    """Place packed batch arrays onto the mesh in ``ARG_ORDER``.

    Hands numpy straight to ``jax.device_put`` with the mesh sharding: the
    arrays must never materialize on the default device first (which may not
    even be part of the mesh — MULTICHIP_r01 failed exactly this way).
    """
    batch_first, vec = mesh_shardings(mesh)
    return [
        jax.device_put(
            np.asarray(arrays[k]),
            batch_first if np.asarray(arrays[k]).ndim == 2 else vec,
        )
        for k in ARG_ORDER
    ]


def pad_to_mesh(arrays: dict, mesh: Mesh) -> dict:
    """Pad the batch axis (axis 0, batch-major layout) up to a multiple of
    the mesh size."""
    n_dev = mesh.devices.size
    b = arrays["s_ok"].shape[0]
    pad = (-b) % n_dev
    if pad == 0:
        return arrays
    out = {}
    for k, v in arrays.items():
        if v.ndim == 1:
            out[k] = np.concatenate([v, np.zeros((pad,), v.dtype)])
        else:
            out[k] = np.concatenate(
                [v, np.zeros((pad, v.shape[1]), v.dtype)], axis=0
            )
    return out


def fetch_sharded(
    accept,
    mesh: Mesh,
    impl: str,
    lanes: int,
    injector=None,
    watchdog: bool = False,
) -> np.ndarray:
    """Fetch the sharded accept bits shard-by-shard, one ``mesh.shard``
    child span per device carrying the (device ordinal, lanes-per-shard,
    tier) attribution plus the shard's local accept count — the per-lane
    visibility ROADMAP item 1 needs: a slow or sick chip shows up as ONE
    outlier shard-fetch latency (and its histogram on
    ``cometbft_crypto_shard_dispatch_seconds{device=}``), not as an opaque
    slow dispatch.  Falls back to a plain global fetch when the result is
    not shard-addressable (already-fetched arrays, single device).

    Spans and histogram series are keyed by STABLE physical ordinal
    (``register_devices``), so a post-shrink mesh never re-numbers the
    surviving chips; a device missing from the registry records -1 and
    sorts last.  A per-shard fetch-time exception (the chip died after
    the dispatch "succeeded" — the fetch is where an async XLA error
    actually surfaces) raises ``parallel.elastic.ShardFailure`` with the
    ordinal attached instead of crashing the caller; the elastic
    supervisor turns that into a shrink.  ``injector`` (per-ordinal fault
    seam) and ``watchdog`` (shard-level dispatch deadline) are used by
    the supervised path; the raw path leaves both off."""
    n_dev = int(mesh.devices.size)
    per = lanes // n_dev if n_dev else lanes
    shards = getattr(accept, "addressable_shards", None)
    if not shards or len(shards) != n_dev or per * n_dev != lanes:
        return np.asarray(accept)
    register_devices(mesh.devices.flat)
    out = np.zeros(lanes, dtype=bool)
    for sh in sorted(
        shards,
        key=lambda s: (stable_ordinal(s.device) < 0, stable_ordinal(s.device)),
    ):
        dev = stable_ordinal(sh.device)
        t0 = time.perf_counter()
        with tracing.span(
            "mesh.shard", device=dev, lanes=per, tier=impl
        ) as sp:

            def pull(sh=sh, dev=dev):
                transform = (
                    injector(dev, None, None, None)
                    if injector is not None
                    else None
                )
                data = np.asarray(sh.data)
                return transform(data) if transform is not None else data

            try:
                if watchdog:
                    from cometbft_tpu.ops import supervisor

                    data = supervisor.watchdog_call(
                        pull,
                        backend=f"mesh_dev{dev}",
                        note_anomaly=False,
                    )
                else:
                    data = pull()
                data = np.asarray(data)
                if data.shape != (per,) or data.dtype != np.bool_:
                    from cometbft_tpu.crypto.backend_health import (
                        BackendOutputError,
                    )

                    raise BackendOutputError(
                        f"mesh shard {dev} returned shape {data.shape} "
                        f"dtype {data.dtype}, want ({per},) bool"
                    )
            except Exception as e:  # noqa: BLE001 — a dead chip surfaces
                # HERE (fetch), after the async dispatch looked fine: a
                # typed, ordinal-attributed failure instead of a crash
                from cometbft_tpu.parallel import elastic

                raise elastic.ShardFailure(dev, e) from e
            sp.set(ok=int(data.sum()))
        start = sh.index[0].start or 0
        out[start : start + data.shape[0]] = data
        dispatch_stats.record_shard_time(
            impl, dev, per, time.perf_counter() - t0
        )
    return out


def verify_batch_sharded(
    pubs: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    mesh: Optional[Mesh] = None,
    donated: Optional[bool] = None,
) -> np.ndarray:
    """Mesh-sharded analogue of ``ops.verify.verify_batch``; returns (n,) bool.

    The dispatch records the same ``verify.dispatch`` attribution triple as
    the single-chip paths — (tier, lanes, dispatch ordinal) — extended with
    the mesh width, and the fetch emits per-device ``mesh.shard`` child
    spans (``fetch_sharded``)."""
    mesh = mesh or make_mesh()
    impl = ov.select_impl(mesh.devices.flat)
    arrays, n, structural = ov.prepare_batch(pubs, msgs, sigs)
    arrays = pad_to_mesh(arrays, mesh)
    lanes = arrays["s_ok"].shape[0]
    dispatch_stats.record_dispatch(lanes, n)
    seq = dispatch_stats.dispatch_count()
    t0 = time.perf_counter()
    with tracing.span(
        "verify.dispatch",
        tier=impl,
        lanes=lanes,
        n=n,
        dispatch=seq,
        mesh=int(mesh.devices.size),
    ):
        call, _ = sharded_verify_call(mesh, lanes, impl, donated=donated)
        accept, _ = call(*device_put_args(arrays, mesh))
        host = fetch_sharded(accept, mesh, impl, lanes)
    dispatch_stats.record_dispatch_time(impl, lanes, time.perf_counter() - t0)
    return (host[: len(structural)] & structural)[:n]


# -- elastic (supervised) device path ----------------------------------------
#
# The jax side of parallel/elastic.py: one mesh attempt over a CHOSEN set
# of stable ordinals, per-shard fault injection at fetch time, and the
# shard watchdog — everything that needs a real device in hand.  The
# shrink ladder, breakers and membership live in elastic.py (jax-free).


def dispatch_elastic(
    ordinals: "Sequence[int]",
    pubs: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
) -> np.ndarray:
    """One supervised mesh dispatch over the devices with the given
    stable ordinals: the supervisor's one launch and one fetch
    (``ops/supervisor._launch_verify`` / ``_fetch_launched``, the body the
    scheduler's mesh-wide flush runs too), one after the other.  Raises
    ``parallel.elastic.ShardFailure`` on any ordinal-attributable problem
    (injected fault, fetch-time error, malformed shard, shard watchdog
    fire) — the elastic supervisor shrinks and re-dispatches; any other
    exception means the mesh itself is broken (lowering, collective) and
    the caller falls to the single-chip chain."""
    from cometbft_tpu.ops import supervisor

    return supervisor._attempt(
        tier_of(ordinals), pubs, msgs, sigs,
        mesh=tuple(int(o) for o in ordinals), tally=True,
    )


def run_single_shard(
    ordinal: int,
    pubs: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    lanes: int,
) -> np.ndarray:
    """One shard's worth of verify work on ONE device — the re-admission
    probe's dispatch (parallel/elastic._probe_ordinal) when no mesh
    runner seam is installed.  Deliberately tiny: the smallest padding
    bucket on the probed device, no collective (a half-dead chip must not
    be able to wedge a healthy mesh's psum)."""
    device = _DEVICE_BY_ORDINAL.get(int(ordinal))
    if device is None:
        _ensure_base_registry()
        device = _DEVICE_BY_ORDINAL[int(ordinal)]
    impl = ov.select_impl([device])
    packed, n, structural, _ = ov.pack_batch(pubs, msgs, sigs)
    # the one-chip executable's jit (not the AOT cache): jit re-specializes
    # per committed device, so the probe really exercises the probed chip
    # instead of whatever device the cached executable was compiled for
    jitted = ov._bucket_jitted(impl)
    accept = np.asarray(jitted(jax.device_put(packed, device)))
    real = (accept[: len(structural)] & structural)[:n]
    out = np.zeros(int(lanes) if lanes else n, dtype=bool)
    out[: min(n, out.shape[0])] = real[: out.shape[0]]
    return out


def warm_shrink_shape(width: int, lanes: int) -> dict:
    """Precompile the sharded executable for a ``width``-device mesh at
    the given (pre-mesh-padding) lane count — the warm-boot shrink-ladder
    satellite (``COMETBFT_TPU_WARMBOOT_MESH_SHRINK``): the first
    post-shrink dispatch must meet a resident executable, not a cold
    compile mid-consensus.  Returns the exec-cache info dict."""
    m = mesh_of(range(int(width)))
    impl = ov.select_impl(m.devices.flat)
    padded = int(lanes) + (-int(lanes)) % int(width)
    _, info = sharded_verify_call(m, padded, impl)
    return {mesh_tag(impl, int(width), padded): dict(info)}
