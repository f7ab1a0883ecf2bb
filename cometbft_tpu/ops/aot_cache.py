"""Ahead-of-time executable cache for the device kernels.

The Mosaic compile of the Pallas verify kernel costs tens of seconds per
bucket, the XLA-CPU compile of the same trace about as much on the CI host,
and even a *warm* JAX persistent-compilation-cache boot still pays the full
Python tracing cost (~13s per shape here) — JAX's cache keys post-trace
artifacts.  This module adds a second, explicit
layer: after a successful compile the whole PJRT executable is pickled
(``jax.experimental.serialize_executable``) to disk, keyed by (source
fingerprint, jax version, platform, shape tag), and later runs load it
back without any tracing or compilation at all.  It is the backbone of
every verify dispatch (``ops/verify.py`` routes its bucketed executables
here) and of the warm-boot pass (``ops/warmboot.py``); docs/warm-boot.md
documents the key design and eviction policy.

Serialization support is a per-PJRT-plugin capability — every call degrades
gracefully (``info["exec_cache"]`` says what happened) so a plugin without
it only loses the optimization, never the run.

Reference analog: none — the reference's Go hot path (crypto/ed25519/
ed25519.go:189-222) has no compile step to amortize.  This is TPU-runtime
plumbing in service of SURVEY §3.4's bench story.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time

import jax

from cometbft_tpu.libs import cachedir
from cometbft_tpu.ops import warm_stats

# Payload format: bump whenever the pickled dict layout changes so old
# entries read as stale instead of half-deserializing.
_FORMAT = 3

# Env vars that change what XLA builds from the same trace (device
# topology, flag experiments, dtype width).  A tier-1 process running under
# --xla_force_host_platform_device_count=8 must not share executables with
# a single-device bench process.
_COMPILE_ENV_VARS = ("XLA_FLAGS", "JAX_ENABLE_X64", "LIBTPU_INIT_ARGS")

# Sources OUTSIDE ops/ that the verify traces close over:
# ops/ed25519_point.py imports the host reference for its precomputed
# base-table constants, so an ed25519_ref edit can change the traced
# computation without touching ops/.
_EXTRA_SOURCE_MODULES = ("cometbft_tpu.crypto.ed25519_ref",)

_EVICT_TTL_DAYS = 7.0

# Latched when a deserialization fails with the thunk-runtime signature
# ("Symbols not found"): this runtime cannot reload what it stores, so
# every further probe (a multi-MB pickle read + a doomed deserialize) and
# every further store (a multi-MB serialize + write no process can ever
# load) in this process is pure tax — skip both.  docs/warm-boot.md
# "Platform support".
_NO_ROUNDTRIP = [False]


def cache_dir() -> str:
    """``<cache root>/exec`` (``libs/cachedir``), read at call time so
    tests can redirect it per-process via COMETBFT_TPU_EXEC_CACHE."""
    return cachedir.exec_cache_dir()


def _source_files() -> "list[str]":
    """The compute-path sources: every ops/*.py plus the crypto modules the
    traces close over (tests monkeypatch this to drive invalidation)."""
    d = os.path.dirname(os.path.abspath(__file__))
    files = [
        os.path.join(d, fn) for fn in sorted(os.listdir(d))
        if fn.endswith(".py")
    ]
    import importlib

    for mod in _EXTRA_SOURCE_MODULES:
        try:
            m = importlib.import_module(mod)
            if getattr(m, "__file__", None):
                files.append(m.__file__)
        except Exception:  # noqa: BLE001 — a missing module hashes as absent
            pass
    return files


def _platform_version() -> str:
    """The backend's own build string (libtpu's on a TPU): an executable
    serialized by another build is refused at load, so it must key a miss
    instead."""
    return str(jax.devices()[0].client.platform_version)


def _fingerprint() -> str:
    """Hash of the compute-path sources + jax version + the backend's
    platform version + compile-affecting env vars: any kernel edit,
    toolchain or libtpu bump, or topology change invalidates cached
    executables."""
    h = hashlib.sha256()
    for path in _source_files():
        try:
            with open(path, "rb") as f:
                h.update(f.read())
        except OSError:
            h.update(b"<unreadable>")
    h.update(jax.__version__.encode())
    h.update(_platform_version().encode())
    for var in _COMPILE_ENV_VARS:
        h.update(f"{var}={os.environ.get(var, '')}".encode())
    return h.hexdigest()[:16]


def _platform() -> str:
    return jax.devices()[0].platform


def _path(tag: str, platform: str, fingerprint: str) -> str:
    return os.path.join(
        cache_dir(), f"{tag}-{platform}-{fingerprint}.jexec"
    )


def has(tag: str) -> bool:
    """True when a current-fingerprint entry for ``tag`` exists on disk.
    Existence is NOT loadability — see ``loadable``."""
    try:
        return os.path.exists(_path(tag, _platform(), _fingerprint()))
    except Exception:  # noqa: BLE001 — a probe must never raise
        return False


_PROBE_LOCK = threading.Lock()
_PROBE: dict = {}  # tag -> bool (deserialization probe results)


def loadable(tag: str) -> bool:
    """True when a current-fingerprint entry for ``tag`` exists on disk
    AND deserializes on this runtime.  The distinction matters: XLA-CPU's
    thunk runtime (the jax 0.4.x default) serializes executables it then
    cannot reload in another process ("Symbols not found"), so such
    entries read as ``stale`` and recompile.  The tier-1 conftest gates
    compile-heavy tests on THIS, not ``has`` — a test must only return to
    tier-1 when the warm load will actually happen.  The probe result is
    memoized per process, and a successful probe seeds the ``cached_call``
    memo so gating does not cost a second disk load."""
    if not has(tag):
        return False
    with _PROBE_LOCK:
        if tag in _PROBE:
            return _PROBE[tag]
    compiled, _ = load(tag)
    ok = compiled is not None
    if ok:
        with _MEMO_LOCK:
            _MEMO.setdefault(tag, compiled)
    with _PROBE_LOCK:
        _PROBE[tag] = ok
    return ok


def load(tag: str):
    """Load a cached executable for ``tag`` on the current platform.

    Returns (compiled, info) or (None, info).  Tolerant of corrupt or
    truncated entries: the payload is structure-checked (format version,
    key set, tag echo) before deserialization, so a bad pickle that
    happens to *unpickle cleanly* into the wrong shape still reads as
    ``stale`` instead of surprising the hot path at call time."""
    try:
        from jax.experimental import serialize_executable as se

        fingerprint = _fingerprint()
        path = _path(tag, _platform(), fingerprint)
    except Exception as e:  # noqa: BLE001 - degrade, never break the run
        warm_stats.record_unsupported()
        return None, {"exec_cache": f"unsupported:{type(e).__name__}"}
    if _NO_ROUNDTRIP[0]:
        warm_stats.record_miss()
        return None, {"exec_cache": "no-roundtrip"}
    if not os.path.exists(path):
        warm_stats.record_miss()
        return None, {"exec_cache": "miss"}
    try:
        t0 = time.perf_counter()
        with open(path, "rb") as f:
            payload = pickle.load(f)
        if (
            not isinstance(payload, dict)
            or payload.get("v") != _FORMAT
            or payload.get("tag") != tag
            or payload.get("fingerprint") != fingerprint
            or not isinstance(payload.get("serialized"), bytes)
            or "in_tree" not in payload
            or "out_tree" not in payload
            or not isinstance(payload.get("device_ids"), list)
        ):
            raise ValueError("malformed exec-cache payload")
        # load onto the devices it was compiled for: jax's default is
        # EVERY device of the backend, which turns a one-chip executable
        # into one that wants a shard per chip on a multi-chip host
        by_id = {d.id: d for d in jax.devices()}
        compiled = se.deserialize_and_load(
            payload["serialized"],
            payload["in_tree"],
            payload["out_tree"],
            execution_devices=[by_id[i] for i in payload["device_ids"]],
        )
        load_s = time.perf_counter() - t0
        warm_stats.record_hit(load_s)
        try:
            os.utime(path)  # a hit re-earns the entry's keep: the TTL
            # grace in evict_stale reads mtime, and a steady-state warm
            # config never writes — without this, another fingerprint's
            # writer would evict still-live entries after one TTL
        except OSError:
            pass
        return compiled, {
            "exec_cache": "hit",
            "exec_load_s": round(load_s, 3),
        }
    except Exception as e:  # noqa: BLE001 - any failure means recompile
        if "Symbols not found" in str(e):
            _NO_ROUNDTRIP[0] = True
        warm_stats.record_stale()
        # flight-recorder anomaly (docs/observability.md): a stale read
        # means the hot path is about to pay a recompile it expected to
        # skip — postmortems want the spans that led here
        from cometbft_tpu.libs import tracing

        tracing.record_anomaly(
            "exec_cache_stale", tag=tag, error=type(e).__name__
        )
        return None, {"exec_cache": f"stale:{type(e).__name__}"}


def store(tag: str, compiled) -> str:
    """Serialize ``compiled`` under ``tag``; returns a status string.

    Atomic and race-safe: the payload lands in a per-writer temp file
    (pid+thread suffix) and is renamed into place, so two processes
    storing the same tag concurrently both succeed and readers only ever
    see a complete file.  Each write also evicts stale-fingerprint entries
    so the cache dir stays bounded (see ``evict_stale``)."""
    if _NO_ROUNDTRIP[0]:
        return "skipped:no-roundtrip"
    try:
        from jax.experimental import serialize_executable as se

        platform = _platform()
        fingerprint = _fingerprint()
        serialized, in_tree, out_tree = se.serialize(compiled)
        payload = pickle.dumps(
            {
                "v": _FORMAT,
                "tag": tag,
                "fingerprint": fingerprint,
                "serialized": serialized,
                "in_tree": in_tree,
                "out_tree": out_tree,
                "device_ids": [
                    d.id
                    for d in compiled._executable.xla_executable.local_devices()
                ],
            }
        )
    except Exception as e:  # noqa: BLE001 - plugin may not support it
        warm_stats.record_unsupported()
        return f"unsupported:{type(e).__name__}"
    d = cache_dir()
    try:
        os.makedirs(d, exist_ok=True)
        path = _path(tag, platform, fingerprint)
        # diskguard seam (surface ``exec_cache``, degradable): injected or
        # real IO faults retry transients, then degrade to the
        # ``unwritable`` status below — the run only loses warm boots.
        # No fsync, as before: a torn entry is detected and recompiled.
        from cometbft_tpu.libs import diskguard as _dg

        _dg.atomic_write("exec_cache", path, payload, do_fsync=False)
    except OSError as e:
        return f"unwritable:{type(e).__name__}"
    warm_stats.record_write(len(payload))
    try:
        evict_stale()
    except Exception:  # noqa: BLE001 — eviction is best-effort
        pass
    return "written"


def evict_stale(ttl_days: float | None = None, now: float | None = None) -> int:
    """Delete ``.jexec`` entries whose filename does not carry the current
    fingerprint and whose mtime is older than the TTL
    (COMETBFT_TPU_EXEC_CACHE_TTL_DAYS, default 7) — dead weight from edited
    kernels and old toolchains.  The grace period keeps entries for OTHER
    live configurations (a different XLA_FLAGS topology, a flipped trace
    env var) from being evicted by whichever process writes last: every
    load hit refreshes the entry's mtime (``load``), so live entries
    re-earn their keep without ever being rewritten.
    Current-fingerprint entries are never evicted — they are the working
    set the warm boot exists to preserve.  Returns entries removed."""
    if ttl_days is None:
        try:
            ttl_days = float(
                os.environ.get("COMETBFT_TPU_EXEC_CACHE_TTL_DAYS", "")
                or _EVICT_TTL_DAYS
            )
        except ValueError:
            ttl_days = _EVICT_TTL_DAYS
    d = cache_dir()
    try:
        names = os.listdir(d)
    except OSError:
        return 0
    fingerprint = _fingerprint()
    cutoff = (time.time() if now is None else now) - ttl_days * 86400.0
    removed = 0
    for fn in names:
        full = os.path.join(d, fn)
        if fn.endswith(".tmp"):
            # abandoned writer temp (a killed process): always stale once
            # past the TTL window
            try:
                if os.path.getmtime(full) < cutoff:
                    os.remove(full)
                    removed += 1
            except OSError:
                pass
            continue
        if not fn.endswith(".jexec"):
            continue
        if fn.rsplit(".", 1)[0].endswith(fingerprint):
            continue
        try:
            if os.path.getmtime(full) < cutoff:
                os.remove(full)
                removed += 1
        except OSError:
            pass
    warm_stats.record_evicted(removed)
    return removed


def load_or_compile(jitted, kwargs, tag: str):
    """AOT-compile ``jitted`` for the shapes in ``kwargs`` (or load the
    cached executable).  Returns (call, info): ``call(**kwargs)`` runs the
    executable; info records cache behavior and compile time.

    ``kwargs`` may be a dict (keyword-lowered: ``jitted.lower(**kwargs)``,
    called back with keywords) or a tuple/list (positional: the mesh and
    secp/BLS kernels take positional pytree args).  Values may be concrete
    arrays or ``jax.ShapeDtypeStruct``s — AOT lowering needs shapes, not
    data.

    Consults the per-process tag memo first, so an executable a
    ``loadable`` probe (the tier-1 warmcache gate) already deserialized is
    reused instead of paying a second multi-MB disk load — regardless of
    whether the caller is ``cached_call`` or a higher-level seam like
    ``ops.verify.bucket_executable``."""
    with _MEMO_LOCK:
        memo = _MEMO.get(tag)
    if memo is not None:
        return memo, {"exec_cache": "memo"}
    compiled, info = load(tag)
    if compiled is None:
        t0 = time.perf_counter()
        if isinstance(kwargs, dict):
            lowered = jitted.lower(**kwargs)
        else:
            lowered = jitted.lower(*kwargs)
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        warm_stats.record_compile(compile_s)
        info["compile_s"] = round(compile_s, 1)
        info["exec_cache_write"] = store(tag, compiled)
    with _MEMO_LOCK:
        compiled = _MEMO.setdefault(tag, compiled)
    return compiled, info


_MEMO_LOCK = threading.Lock()
_MEMO: dict = {}


def cached_call(jitted, args: tuple, tag: str):
    """Run ``jitted(*args)`` through a per-process-memoized exec-cache
    executable — the one-line integration for positional device kernels
    (secp256k1 ladder, BLS G1 MSM/sum): first use per tag loads or
    AOT-compiles+persists; any failure degrades to the plain jitted call.
    The memo mirrors jit's internal cache, including its limitation that
    trace-affecting env flips only apply before a tag's first use."""
    with _MEMO_LOCK:
        call = _MEMO.get(tag)
    if call is None:
        try:
            call, _ = load_or_compile(jitted, args, tag)
        except Exception:  # noqa: BLE001 — never fail a dispatch over
            # cache plumbing; jit compiles lazily exactly as before
            call = jitted
        with _MEMO_LOCK:
            call = _MEMO.setdefault(tag, call)
    return call(*args)


def reset_memo() -> None:
    """Drop the in-process executable memo, the loadability-probe memo
    and the no-roundtrip latch (tests: force disk loads)."""
    with _MEMO_LOCK:
        _MEMO.clear()
    with _PROBE_LOCK:
        _PROBE.clear()
    _NO_ROUNDTRIP[0] = False
