"""Backend supervisor for the verify hot path: watchdog + degradation chain.

Every device dispatch in the commit-verification hot path routes through
this module (``ops/verify.verify_batch`` / ``verify_batches_overlapped`` /
``verify_segments`` — and, via ``watchdog_call``, the secp256k1 and BLS G1
device paths in ``crypto/batch.py``).  It guarantees one property above all
others: an INFRASTRUCTURE failure — a raised XLA/Pallas error, a dispatch
wedged past the watchdog deadline, a malformed result array — is NEVER
converted into a ``False`` accept bit.  On dispatch failure the affected
batch is re-verified on the next backend down the chain

    pallas  ->  xla  ->  host ed25519_ref (verify_zip215)

and the per-backend circuit breakers in ``crypto/backend_health`` decide
when subsequent batches stop probing a dead device (open), when to probe it
again (half-open, exponential backoff), and when to re-promote (probe
passes).  Every backend in the chain implements the same ZIP-215 accept
set, so degradation is verdict-preserving by construction: the host tier is
the differential oracle the device kernels are tested against
(tests/test_supervisor.py pins bitwise equality under every fault mode).

Watchdog: dispatches run on a worker thread with a deadline
(``COMETBFT_TPU_DISPATCH_TIMEOUT_MS``, default 120000; 0 disables) so a
wedged XLA call cannot block the consensus thread — the wedged worker is
abandoned (it exits when it unwedges, and never serves again) and another
serves later dispatches.  A worker whose call returned parks and takes
the next call; a thread is started only when none is parked, so no call
ever waits behind another (``_Watchdog``).

Bisection: a *single poisoned input* that reproducibly kills the kernel
(a lowering edge case, a driver-crashing encoding) would otherwise demote
the whole backend forever.  When a dispatch raises fast (not a timeout) and
the backend was healthy, the supervisor bisects the batch on the same
backend, quarantines the one input that keeps failing (host-verifying it),
and keeps the backend in service.  If more than one input "is poisoned"
the failure is systematic and the backend demotes normally.

Deterministic fault injection: ``set_fault_injector`` installs a hook
consulted inside every supervised dispatch; ``FaultyBackend`` is the
standard shim (modes: raise / hang / wrong_shape / flap) driven by
counters, and the sim scenarios ``backend_brownout`` / ``backend_wedge`` /
``backend_flap`` install it at virtual times (cometbft_tpu/sim/scenarios).

One launch, one fetch: the device dispatch is written once, as
``_launch_verify`` (pack, injector, executable, call: one watchdog deadline)
and ``_fetch_launched`` (the watchdogged copy back, validation, the accept
bits).  Over one chip it calls the bucket's executable; over an elastic mesh
(``h.mesh``, the ordinals) it pads the packed batch to the width, places one
shard a chip and calls the ONE sharded executable, and the fetch pulls shard
by shard (``parallel/mesh.fetch_sharded``).  ``dispatch_verify`` /
``fetch_verify`` are the async primitive over them, with the breaker and the
degradation around each half; ``verify_supervised`` walks the chain with
launch-then-fetch (``_attempt``) and bisects, and the mesh's shrink ladder
(``parallel/elastic.verify_elastic``) does the same over the mesh.  There is
no unsupervised path.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from typing import Callable, Optional, Sequence

import numpy as np

from cometbft_tpu.crypto import backend_health
from cometbft_tpu.crypto.backend_health import (
    BackendOutputError,
    DispatchTimeoutError,
)
from cometbft_tpu.libs import tracing
from cometbft_tpu.ops import dispatch_stats

logger = logging.getLogger("cometbft_tpu.crypto")

DEFAULT_TIMEOUT_MS = 120000.0
HOST_BACKEND = "host"


def dispatch_timeout_s() -> float:
    """Watchdog deadline in seconds; <= 0 disables (dispatch runs inline).
    The default is deliberately far above any legitimate compile+dispatch
    — it exists to catch a *wedge*, not a slow kernel."""
    try:
        ms = float(
            os.environ.get("COMETBFT_TPU_DISPATCH_TIMEOUT_MS", "")
            or DEFAULT_TIMEOUT_MS
        )
    except ValueError:
        ms = DEFAULT_TIMEOUT_MS
    return ms / 1000.0


def device_chain() -> tuple:
    """Device tiers to try, best first; the implicit final tier is the
    host reference implementation (``host_verify``)."""
    from cometbft_tpu.ops import verify as ov

    return ("pallas", "xla") if ov.select_impl() == "pallas" else ("xla",)


def active_backend() -> Optional[str]:
    """The device backend a new dispatch would currently target, or None
    when every device tier's breaker is open (fully degraded to host).
    Read-only: does NOT consume a half-open probe slot — speculative
    callers (blocksync prefetch, light chain sync) use this to skip fused
    device work while degraded."""
    reg = backend_health.registry()
    for b in device_chain():
        if reg.breaker(b).state != backend_health.OPEN:
            return b
    return None


# -- device runner seam ------------------------------------------------------

_DEVICE_RUNNER: Optional[Callable] = None


def set_device_runner(fn: Optional[Callable]) -> None:
    """Swap the device tier's execution for ``fn(backend, pubs, msgs,
    sigs, lanes) -> (lanes,) bool`` (padding lanes False).  The
    deterministic simulator installs a host-backed stand-in here: on the
    throttled CI host a real XLA dispatch costs ~1.7 s of wall time, which
    would make backend-fault scenarios unrunnable in tier-1, while every
    supervisor mechanism under test (watchdog, breaker, fault injector,
    bisection, attribution) sits ABOVE this seam and runs unchanged.
    ``COMETBFT_TPU_SIM_REAL_DEVICE=1`` makes the sim scenarios skip the
    stand-in and exercise the real kernel (slow lane).  ``None`` clears."""
    global _DEVICE_RUNNER
    _DEVICE_RUNNER = fn


def clear_device_runner() -> None:
    set_device_runner(None)


# -- fault injection ---------------------------------------------------------

_FAULT_INJECTOR: Optional[Callable] = None


def set_fault_injector(fn: Optional[Callable]) -> None:
    """Install ``fn(backend, pubs, msgs, sigs) -> Optional[transform]``,
    consulted inside every supervised device dispatch (on the watchdog
    worker, so a hanging injector exercises the real deadline path).  It
    may raise (simulated dispatch error), sleep (simulated wedge), or
    return a callable applied to the result array (simulated corruption,
    e.g. wrong shape).  ``None`` clears."""
    global _FAULT_INJECTOR
    _FAULT_INJECTOR = fn


def clear_fault_injector() -> None:
    set_fault_injector(None)


class FaultyBackend:
    """Deterministic fault shim for ``set_fault_injector``.

    Modes:
      * ``raise``       — every matching dispatch raises immediately;
      * ``hang``        — sleep ``hang_s`` then raise (a watchdog shorter
        than ``hang_s`` fires first; longer sees a plain raise);
      * ``wrong_shape`` — dispatch succeeds but the result loses a lane
        (the supervisor must treat this as infrastructure, not verdicts);
      * ``flap``        — bursty: ``fail_n`` failing dispatches, then
        ``pass_n`` clean ones, repeating (counter-based, deterministic).

    ``backends`` restricts which chain tiers are affected (the host tier
    is never injectable — it is the refuge).
    """

    def __init__(
        self,
        mode: str,
        backends: Sequence[str] = ("pallas", "xla"),
        hang_s: float = 30.0,
        fail_n: int = 4,
        pass_n: int = 2,
    ):
        assert mode in ("raise", "hang", "wrong_shape", "flap"), mode
        self.mode = mode
        self.backends = tuple(backends)
        self.hang_s = hang_s
        self.fail_n = fail_n
        self.pass_n = pass_n
        self.calls = 0
        self.faults = 0
        self._lock = threading.Lock()

    def __call__(self, backend, pubs, msgs, sigs):
        if backend not in self.backends:
            return None
        with self._lock:
            seq = self.calls
            self.calls += 1
            if self.mode == "flap":
                cycle = self.fail_n + self.pass_n
                if seq % cycle >= self.fail_n:
                    return None  # pass phase of the burst cycle
            self.faults += 1
        if self.mode == "hang":
            time.sleep(self.hang_s)
            raise RuntimeError("injected fault: backend wedge (unwedged)")
        if self.mode == "wrong_shape":
            return lambda out: out[:-1]
        raise RuntimeError(f"injected fault: {self.mode} on {backend}")


# -- watchdog ----------------------------------------------------------------


# the most workers kept parked (a worker that finds this many parked when
# its call returns exits instead), and how long a parked one waits for a
# call before it exits: a validator's votes come every few milliseconds
# and its heights every few seconds, so the served path's worker lives on
_PARKED_MAX = 8
_PARK_IDLE_S = 10.0


class _Job:
    """One call between its caller and its worker: the worker puts
    ``(val, err)`` into ``out`` unless the caller, its deadline past, has
    marked the job ``abandoned``."""

    __slots__ = ("fn", "out", "abandoned")

    def __init__(self, fn: Callable):
        self.fn = fn
        self.out: "queue.SimpleQueue" = queue.SimpleQueue()
        self.abandoned = False


class _Watchdog:
    """Dispatch threads with a deadline; a worker that has finished a call
    PARKS and takes the next one.

    ``call(fn, timeout_s)`` hands ``fn`` to the most recently parked
    worker (the warmest), or starts a daemon thread at once when none is
    parked, and waits up to the deadline, counted from its own entry.
    Starting a thread is what this avoids: priced at ~100 us when it was
    written, it costs 0.5 ms on the chip's host (a sandboxed kernel: 0.54
    ms of a launch, 0.48 of a fetch, twice a dispatch; PERF.md PR 36),
    where a wake between two live threads costs 0.07-0.10.

    NO call ever waits behind another: a worker parks only AFTER its
    ``fn`` has returned, so a parked worker is never inside a device call,
    and a call that finds none parked spawns and never queues.  That keeps
    what a shared worker QUEUE would lose: queueing behind another
    caller's healthy-but-slow dispatch would count against this caller's
    deadline and misattribute concurrency as a device wedge, demoting a
    healthy backend.  Concurrent dispatches run concurrently (jax
    execution is thread-safe), each on a worker of its own.

    On timeout the worker is ABANDONED and never reused: it finishes (or
    stays wedged) in the background, finds its job abandoned, discards
    value or exception and exits without parking.  Abandoned threads are
    bounded by the circuit breaker: after ``threshold`` timeouts the
    backend stops being dispatched until a half-open probe.  Parked ones
    are bounded by ``_PARKED_MAX`` and exit after ``_PARK_IDLE_S`` idle.

    Which of the two served a call is marked on the caller's open span
    (``worker`` = ``parked`` / ``fresh`` on ``verify.dispatch``,
    ``verify.fetch``, ``mesh.shard``) and counted in
    ``dispatch_stats["watchdog_calls"]``; the workers open no span.

    Known cosmetic limitation: if the PROCESS exits while an abandoned
    thread is still inside a wedged C++ (XLA) call, the runtime may abort
    at shutdown ("terminate called without an active exception") — the
    thread cannot be joined, which is the entire point of abandoning it.
    This only occurs on exit immediately after a real device wedge, a
    state where the operator is restarting the node anyway."""

    def __init__(self):
        self._lock = threading.Lock()
        # the parked workers' inboxes; LIFO: the last is the warmest
        self._parked: "list[queue.SimpleQueue]" = []

    def _serve(self, inbox: "queue.SimpleQueue") -> None:
        while True:
            try:
                job = inbox.get(timeout=_PARK_IDLE_S)
            except queue.Empty:
                with self._lock:
                    if inbox in self._parked:
                        self._parked.remove(inbox)
                        return
                # a call took us as the wait ran out: its job is coming
                job = inbox.get()
            val = err = None
            try:
                val = job.fn()
            except BaseException as e:  # noqa: BLE001 — relayed to caller
                err = e
            with self._lock:
                if job.abandoned:
                    return  # late value or exception reaches nobody
                park = len(self._parked) < _PARKED_MAX
                if park:
                    # BEFORE the caller wakes: its next call finds us
                    self._parked.append(inbox)
            job.out.put((val, err))
            if not park:
                return
            del job, val, err  # a parked worker holds nobody's arrays

    def call(self, fn: Callable, timeout_s: float):
        t0 = time.monotonic()
        job = _Job(fn)
        with self._lock:
            inbox = self._parked.pop() if self._parked else None
        how = "fresh" if inbox is None else "parked"
        if inbox is None:
            inbox = queue.SimpleQueue()
            threading.Thread(
                target=self._serve,
                args=(inbox,),
                name="crypto-dispatch",
                daemon=True,
            ).start()
        inbox.put(job)
        # while the worker runs
        dispatch_stats.record_watchdog_call(how)
        tracing.mark(worker=how)
        left = timeout_s - (time.monotonic() - t0)
        try:
            val, err = job.out.get(timeout=max(left, 0.0))
        except queue.Empty:
            with self._lock:
                job.abandoned = True
            raise DispatchTimeoutError(
                f"device dispatch exceeded {timeout_s:.3f}s watchdog deadline"
            ) from None
        if err is not None:
            raise err
        return val


_WATCHDOG = _Watchdog()


def watchdog_call(
    fn: Callable,
    timeout_s: Optional[float] = None,
    backend: str = "",
    note_anomaly: bool = True,
):
    """Run ``fn`` under the dispatch watchdog.  This is the seam the
    secp256k1/BLS device paths share: any device call a consensus thread
    must survive goes through here.  A fire lands in the flight recorder
    (``note_anomaly=False`` for callers that record their own with richer
    attribution, like ``_launch_verify``'s bucket/dispatch attrs)."""
    t = dispatch_timeout_s() if timeout_s is None else timeout_s
    if not t or t <= 0:
        return fn()
    try:
        return _WATCHDOG.call(fn, t)
    except DispatchTimeoutError:
        backend_health.registry().record_watchdog_fire(backend)
        if note_anomaly:
            tracing.record_anomaly("watchdog_fire", tier=backend)
        raise


def supervised_device_call(
    backend: str,
    fn: Callable,
    validate: Optional[Callable] = None,
    fallback_units: int = 0,
):
    """One breaker-gated, watchdogged device call — THE shared protocol for
    single-tier device paths (secp256k1 ECDSA, BLS G1 scalar-mul), so the
    allow/watchdog/validate/record sequence exists once instead of being
    hand-copied per key type.  Returns the call's result, or None when the
    breaker is open or the call failed (the caller then takes its host
    fallback; ``fallback_units`` signatures are recorded as degraded host
    work in that case).  ``validate(result)`` may raise
    ``BackendOutputError`` to classify a malformed result as infra."""
    reg = backend_health.registry()
    br = reg.breaker(backend)
    if br.allow():
        try:
            out = watchdog_call(fn, backend=backend)
            if validate is not None:
                validate(out)
            br.record_success()
            return out
        except Exception as e:  # noqa: BLE001 — any device error demotes
            br.record_failure(e)
            reg.record_demotion(backend)
            logger.warning(
                "crypto backend %s call failed (%r); host fallback "
                "(breaker recorded the failure)",
                backend,
                e,
            )
    if fallback_units:
        reg.record_fallback(fallback_units)
    return None


# -- supervised ed25519 verification ----------------------------------------


def _pack(pubs, msgs, sigs, min_b: int):
    """``pack_batch`` (host pack, SHA-512) under its span, which says
    which ``path`` packed (``native`` / ``python``) and holds the stage's
    two halves as children: ``verify.pack.glue`` (buffers, joins, lengths;
    the whole loop on the Python path) and ``verify.pack.native`` (the
    sidecar's call)."""
    from cometbft_tpu.ops import verify as ov

    with tracing.span("verify.pack", n=len(pubs)) as sp:
        packed, n, structural, how = ov.pack_batch(pubs, msgs, sigs, min_b)
        sp.set(
            lanes=structural.shape[0], bytes=packed.nbytes, path=how["path"]
        )
        for lp in how["laps"]:
            lp.record(parent=sp)
    return packed, n, structural


def _min_bucket(backend: str) -> int:
    from cometbft_tpu.ops import verify as ov

    return ov._PALLAS_MIN_BUCKET if backend == "pallas" else ov._BUCKETS[0]


def _nbytes(arrays: dict) -> int:
    return sum(v.nbytes for v in arrays.values())


def _launch(backend: str, lanes: int, packed: np.ndarray, laps):
    """Resolve the bucket's executable, transfer its ONE packed buffer,
    call: returns the UNFETCHED device array and the number of arrays
    placed.  Runs on the watchdog worker; ``laps`` time the three
    (``verify.launch.lookup`` / ``.put`` / ``.call``)."""
    import jax.numpy as jnp

    from cometbft_tpu.ops import verify as ov

    lookup, put, called = laps
    with lookup:
        call, _ = ov.bucket_executable(backend, lanes)
    with put:
        placed = [jnp.asarray(packed)]
    with called:
        return call(*placed), len(placed)


def _launch_mesh(backend: str, lanes: int, arrays: dict, mesh, laps):
    """The same over a mesh: resolve the sharded executable for this
    width, place one shard a chip (its lap is ``mesh.put``), call ONCE.
    Returns the UNFETCHED sharded accept bits (the ``psum`` of the
    per-shard counts stays on the devices) and the number of arrays
    placed.  Runs on the watchdog worker."""
    from cometbft_tpu.parallel import mesh as pmesh

    lookup, put, called = laps
    with lookup:
        call, _ = pmesh.sharded_verify_call(mesh, lanes, backend)
    with put:
        placed = pmesh.device_put_args(arrays, mesh)
    with called:
        return call(*placed)[0], len(placed)


def _validate_accept(accept, lanes: int) -> np.ndarray:
    """Wrong-shape/dtype output is an infrastructure failure (a kernel
    regression or memory corruption), never a verdict."""
    accept = np.asarray(accept)
    if accept.shape != (lanes,) or accept.dtype != np.bool_:
        raise BackendOutputError(
            f"backend returned shape {accept.shape} dtype {accept.dtype}, "
            f"want ({lanes},) bool"
        )
    return accept


class _InflightVerify:
    """One supervised verify between its launch and its fetch.

    Kinds:
      * ``mesh``       — ONE launch over the elastic mesh's admitted
        ordinals (``mesh``), already in the devices' queues: the fetch
        pulls shard by shard and is the first rung of the shrink ladder
        (``elastic.verify_elastic``).  With the mesh-runner seam installed
        (sim/tests) nothing is launched and the whole ladder runs at fetch;
      * ``lane``       — pinned at one healthy mesh ordinal, for a batch
        too small for the mesh to take (``elastic.dispatch_lane`` only
        records the routing; the shard's pack, transfer, kernel and copy
        back all run at fetch time on the completion pool, under the shard
        watchdog);
      * ``chip``       — a real async device dispatch already in the
        device queue (unfetched device array + injector transform);
      * ``deferred``   — the device-runner seam is installed (sim/tests):
        the whole ``_attempt`` runs at fetch time, so overlap — and the
        injector's raise/hang — happen on the completion pool;
      * ``supervised`` — fully degraded at dispatch time (or the dispatch
        itself failed): fetch walks ``verify_supervised`` with ``skip``.

    ``error`` says how the device part ended: the exception that
    ``dispatch_verify`` or ``fetch_verify`` answered by degrading, None
    while all is well.
    """

    __slots__ = (
        "kind", "pubs", "msgs", "sigs", "n", "lanes", "backend",
        "lane", "lane_handle", "dev", "transform", "structural", "skip",
        "error", "mesh",
    )

    def __init__(self, pubs, msgs, sigs, backend=None, mesh=()):
        self.kind = "supervised"
        self.pubs = pubs
        self.msgs = msgs
        self.sigs = sigs
        self.n = len(pubs)
        self.lanes = 0
        self.backend = backend
        self.lane = None
        self.lane_handle = None
        self.dev = None
        self.transform = None
        self.structural = None
        self.skip = ()
        self.error = None
        self.mesh = tuple(mesh)  # ordinals of a mesh-wide launch

    def give_up(self) -> None:
        """Leave what is in flight on the device where it is: the fetch
        re-verifies below ``backend`` and waits for nothing."""
        if self.kind in ("chip", "deferred"):
            self.kind = "supervised"
            self.skip = (self.backend,)
            self.dev = None


def _launch_verify(h: _InflightVerify, **attrs) -> None:
    """THE launch: pack, then injector, executable and call under ONE
    watchdog deadline; ``h`` leaves with the UNFETCHED device array.
    Raises ``DispatchTimeoutError`` or whatever the kernel raised.

    The dispatch SPAN is recorded on the CALLING thread around
    ``watchdog_call`` — never by the worker — so an abandoned (wedged)
    worker can't race a late span into a deterministic sim's flight
    record.  It carries the (tier, lanes) pair, and with ``attrs`` the
    dispatch ordinal, that an anomaly dump attributes a watchdog fire to."""
    backend, pubs, msgs, sigs = h.backend, h.pubs, h.msgs, h.sigs
    packed, n, h.structural = _pack(pubs, msgs, sigs, _min_bucket(backend))
    mesh = arrays = None
    lanes, size = h.structural.shape[0], packed.nbytes
    if h.mesh:
        from cometbft_tpu.ops import verify as ov
        from cometbft_tpu.parallel import mesh as pmesh

        mesh = pmesh.mesh_of(h.mesh)
        arrays = pmesh.pad_to_mesh(ov.packed_views(packed), mesh)
        lanes, size = arrays["s_ok"].shape[0], _nbytes(arrays)
        attrs["mesh"] = len(h.mesh)
    h.lanes = lanes
    # a mesh's faults are per ordinal and surface at the shards' fetch
    # (``elastic.set_fault_injector``); this one is the single chip's
    inj = _FAULT_INJECTOR if mesh is None else None
    runner = _DEVICE_RUNNER
    launched = tracing.lap("verify.launch")
    # the launch's three parts, timed where they run; the placement keeps
    # the name its reader knows on a mesh
    laps = (
        tracing.lap("verify.launch.lookup"),
        tracing.lap("verify.launch.put" if mesh is None else "mesh.put"),
        tracing.lap("verify.launch.call"),
    )

    def run():
        transform = inj(backend, pubs, msgs, sigs) if inj is not None else None
        dispatch_stats.record_dispatch(lanes, n)
        with launched:
            if mesh is not None:
                dispatch_stats.record_mesh_dispatch(len(h.mesh))
                dev, placed = _launch_mesh(backend, lanes, arrays, mesh, laps)
                return dev, None, placed
            if runner is not None:
                # device-runner seam (sim/tests): a synchronous stand-in,
                # whose fetch is then a no-op
                dev = runner(backend, pubs, msgs, sigs, lanes)
                return dev, transform, 0
            # executable resolution (exec-cache load or AOT compile)
            # runs INSIDE the watchdog worker: a wedged compile is
            # abandoned like a wedged dispatch, and the device-runner
            # seam above never pays a compile at all
            dev, placed = _launch(backend, lanes, packed, laps)
            return dev, transform, placed

    try:
        with tracing.span(
            "verify.dispatch", tier=backend, lanes=lanes, n=n, **attrs
        ) as dsp:
            h.dev, h.transform, placed = watchdog_call(
                run,
                backend=backend if mesh is None else "mesh",
                note_anomaly=False,
            )
            # timed inside the watchdog closure, recorded by the calling
            # thread once ``watchdog_call`` has returned: an abandoned
            # worker writes no span
            on_mesh = {} if mesh is None else {"mesh": len(h.mesh)}
            lsp = launched.record(
                parent=dsp, tier=backend, lanes=lanes, bytes=size, **on_mesh
            )
            lookup, put, called = laps
            lookup.record(parent=lsp)
            # ``transfers``: the arrays the placement handed the device
            if mesh is None:
                put.record(parent=lsp, transfers=placed)
            else:
                put.record(
                    parent=lsp, shards=len(h.mesh), bytes=size,
                    transfers=placed,
                )
            called.record(parent=lsp)
    except DispatchTimeoutError:
        # the failed span is already in the ring (the with-block closed),
        # so the dump this triggers shows it as its most recent entry
        tracing.record_anomaly(
            "watchdog_fire", tier=backend, lanes=lanes, n=n, **attrs
        )
        raise


def _fetch_launched(h: _InflightVerify) -> np.ndarray:
    """THE fetch of what ``_launch_verify`` left in ``h``: the watchdogged
    device-to-host copy, then (n,) accept bits.  Raises; never returns
    partial results.  ``dispatch_hist`` is fed the fetch wait."""

    pulled = tracing.lap("verify.fetch.pull")

    def fetch():
        with pulled:
            a = np.asarray(h.dev)
        return h.transform(a) if h.transform is not None else a

    t0 = time.perf_counter()
    with tracing.span(
        "verify.fetch", tier=h.backend, lanes=h.lanes, n=h.n
    ) as fsp:
        if h.mesh:
            # shard by shard, each pull under the shard watchdog with the
            # per-ordinal injector consulted: a sick chip raises
            # ``elastic.ShardFailure`` naming its ordinal
            from cometbft_tpu.parallel import elastic
            from cometbft_tpu.parallel import mesh as pmesh

            got = pmesh.fetch_sharded(
                h.dev, pmesh.mesh_of(h.mesh), h.backend, h.lanes,
                injector=elastic.fault_injector(), watchdog=True,
            )
        else:
            got = watchdog_call(fetch, backend=h.backend)
            # the copy itself, timed on the watchdog worker: the span less
            # this lap is what the watchdog's thread and two switches cost
            pulled.record(parent=fsp)
    dispatch_stats.record_dispatch_time(
        h.backend, h.lanes, tracing.wall_seconds(fsp, t0)
    )
    # the mesh pads past the bucket where the width does not divide it
    real = len(h.structural)
    return (_validate_accept(got, h.lanes)[:real] & h.structural)[: h.n]


def _attempt(
    backend: str, pubs, msgs, sigs, mesh: tuple = (), tally: bool = False
) -> np.ndarray:
    """One supervised dispatch on one device backend, launch then fetch:
    on one chip, or over the mesh of the ordinals ``mesh``
    (``parallel/mesh.dispatch_elastic``).  Raises ``DispatchTimeoutError``
    / ``BackendOutputError`` / ``elastic.ShardFailure`` / whatever the
    kernel raised; never returns partial results.  ``tally`` counts the
    batch's signatures under ``backend`` once it is answered
    (``lane_lanes_used``: who verified what)."""
    h = _InflightVerify(pubs, msgs, sigs, backend, mesh)
    # the ordinal this dispatch will record (single dispatch in flight per
    # attempt; concurrent attempts only skew the label, never the verdict)
    _launch_verify(h, dispatch=dispatch_stats.dispatch_count() + 1)
    bits = _fetch_launched(h)
    if tally:
        dispatch_stats.record_lane_dispatch(backend, h.lanes, h.n)
    return bits


def host_verify(pubs, msgs, sigs) -> np.ndarray:
    """The terminal tier: pure-host ZIP-215 reference verification —
    bitwise the accept set of the device kernels (it is their differential
    oracle), with no device to fail.  Orders of magnitude slower per
    signature; the breaker's half-open probes exist to leave it again."""
    from cometbft_tpu.crypto import ed25519_ref as ref

    n = len(pubs)
    if n:
        backend_health.registry().record_fallback(n)
    with tracing.span("supervisor.host_fallback", n=n):
        return np.fromiter(
            (ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)),
            dtype=bool,
            count=n,
        )


class _GiveUp(Exception):
    pass


def _bisect_quarantine(
    backend: str, pubs: list, msgs: list, sigs: list
) -> Optional[np.ndarray]:
    """Isolate a single poisoned input that reproducibly kills the kernel.

    Recursively re-dispatches halves on the SAME backend: halves that
    succeed keep their device verdicts; the subtree that keeps failing
    narrows to one index, which is quarantined (host-verified — its
    verdict may well be True: killing the kernel is not evidence against
    the signature).  Gives up (returns None -> normal demotion) on a
    second poisoned index (systematic failure), on a timeout (too slow to
    bisect a wedge), or past the dispatch budget."""
    from cometbft_tpu.crypto import ed25519_ref as ref

    n = len(pubs)
    reg = backend_health.registry()
    budget = [2 * max(1, n.bit_length()) + 8]
    quarantined = [0]

    def solve(lo: int, hi: int) -> list:
        if budget[0] <= 0:
            raise _GiveUp
        budget[0] -= 1
        try:
            return list(
                _attempt(backend, pubs[lo:hi], msgs[lo:hi], sigs[lo:hi])
            )
        except DispatchTimeoutError:
            raise _GiveUp
        except _GiveUp:
            raise
        except Exception:
            if hi - lo == 1:
                quarantined[0] += 1
                if quarantined[0] > 1:
                    raise _GiveUp
                return [bool(ref.verify_zip215(pubs[lo], msgs[lo], sigs[lo]))]
            mid = (lo + hi) // 2
            return solve(lo, mid) + solve(mid, hi)

    try:
        with tracing.span("supervisor.bisect", tier=backend, n=n) as sp:
            bits = np.asarray(solve(0, n), dtype=bool)
            sp.set(quarantined=quarantined[0])
    except _GiveUp:
        return None
    # record only on commit: an abandoned bisect (systematic failure) must
    # not masquerade as a quarantine in the metrics
    if quarantined[0]:
        reg.record_quarantine(backend)
        reg.record_fallback(1)
        tracing.record_anomaly("quarantine", tier=backend, n=n)
        logger.warning(
            "crypto backend %s: quarantined poisoned input "
            "(kills the kernel; host-verified instead)",
            backend,
        )
    return bits


def _bisect_enabled() -> bool:
    return os.environ.get("COMETBFT_TPU_SUPERVISOR_BISECT", "1") != "0"


def verify_supervised(
    pubs, msgs, sigs, skip: tuple = (), mesh: bool = True
) -> np.ndarray:
    """The supervised ed25519 batch verify: walk the degradation chain,
    return (n,) bool accept bits.  Cannot raise for infrastructure reasons
    — the host tier always answers.

    When the elastic mesh supervisor is active (``parallel/elastic`` —
    >= 2 configured devices, ``COMETBFT_TPU_MESH_SUPERVISOR`` != 0) the
    batch shards across the device mesh first; the single-chip chain
    below is the mesh's own floor (``mesh=False`` is how the elastic path
    re-enters here at width < 2 without recursing)."""
    pubs, msgs, sigs = list(pubs), list(msgs), list(sigs)
    if mesh and not skip:
        from cometbft_tpu.parallel import elastic

        if elastic.takes(len(pubs)):
            return elastic.verify_elastic(pubs, msgs, sigs)
    n = len(pubs)
    reg = backend_health.registry()
    with tracing.span("verify.batch", n=n) as vsp:
        for backend in device_chain():
            if backend in skip:
                continue
            br = reg.breaker(backend)
            if not br.allow():
                continue
            try:
                bits = _attempt(backend, pubs, msgs, sigs, tally=True)
            except Exception as e:  # noqa: BLE001 — any dispatch error
                # demotes
                if (
                    n >= 2
                    and _bisect_enabled()
                    and not isinstance(e, DispatchTimeoutError)
                    and br.stats()["consecutive_failures"] == 0
                ):
                    try:
                        solved = _bisect_quarantine(backend, pubs, msgs, sigs)
                    except Exception:  # noqa: BLE001 — bisect best-effort
                        solved = None
                    if solved is not None:
                        br.record_success()
                        vsp.set(tier=backend, bisected=True)
                        return solved
                br.record_failure(e)
                reg.record_demotion(backend)
                logger.warning(
                    "crypto backend %s dispatch failed (%r); retrying on "
                    "the next verify tier",
                    backend,
                    e,
                )
                continue
            br.record_success()
            vsp.set(tier=backend)
            return bits
        vsp.set(tier=HOST_BACKEND)
        return host_verify(pubs, msgs, sigs)


# -- in-flight dispatch/fetch seam (docs/verify-scheduler.md) -----------------
#
# The async half of ``verify_supervised``: ``dispatch_verify`` routes one
# batch over the whole mesh, toward one mesh lane or down the single-chip
# chain WITHOUT blocking on its verdict, and ``fetch_verify`` resolves it
# later (the verifysched completion pool, ``ops.verify.verify_pipelined``
# and ``verify_batches_overlapped``).  Every failure mode at fetch time
# degrades exactly like the synchronous path — a lost shard shrinks the
# mesh and the batch is verified again at the next width, a wedged or
# failed lane/backend is demoted alone and the batch re-verifies on the
# single-chip chain (host floor) — so accept bits stay definitive verdicts.


def _demote(h: _InflightVerify, e: BaseException, when: str) -> None:
    """A failed launch or fetch demotes its backend; the batch re-verifies
    below it at fetch, and ``h.error`` keeps how it ended."""
    reg = backend_health.registry()
    reg.breaker(h.backend).record_failure(e)
    reg.record_demotion(h.backend)
    logger.warning(
        "crypto backend %s pipelined %s failed (%r); batch re-verifies on "
        "the next tier",
        h.backend,
        when,
        e,
    )
    h.error = e
    h.skip = (h.backend,)


def _dispatch_mesh(h: _InflightVerify) -> bool:
    """The mesh-wide kind of ``dispatch_verify``: ONE launch over the
    ordinals the elastic mesh admits (a half-open chip earns its place by
    the one-bucket probe first).  False where fewer than two are admitted:
    the single-chip chain takes the batch.  A launch that fails has no
    ordinal to blame (lowering, placement, the one deadline): as in
    ``verify_elastic`` the batch then goes down the single-chip chain, at
    fetch."""
    from cometbft_tpu.parallel import elastic

    if elastic.mesh_runner() is not None:
        # mesh-runner seam (sim/tests): the stand-in runs synchronously,
        # so the whole ladder is deferred to the completion pool
        h.kind = "mesh"
        return True
    devs = elastic.admit_ordinals()
    if len(devs) < 2:
        return False
    from cometbft_tpu.parallel import mesh as pmesh

    h.mesh = tuple(devs)
    try:
        h.backend = pmesh.tier_of(devs)
        _launch_verify(h, pipelined=True)
    except Exception as e:  # noqa: BLE001 — the mesh itself failed
        logger.warning(
            "mesh-wide dispatch failed without shard attribution (%r); "
            "the batch goes down the single-chip chain",
            e,
        )
        h.error, h.mesh, h.backend = e, (), None
        return True
    h.kind = "mesh"
    # under the TIER's name: the chips' own tallies are the shard
    # histograms (``record_shard_time``)
    dispatch_stats.record_lane_dispatch(h.backend, h.lanes, h.n)
    return True


def dispatch_verify(pubs, msgs, sigs, lane=None) -> _InflightVerify:
    """Route one batch without blocking on its verdict.  With no ``lane``,
    a batch the elastic mesh takes (``elastic.takes``: the mesh is active
    and the batch reaches ``min_batch()``, the rule ``verify_supervised``
    applies) is ONE launch over every admitted chip.  ``lane`` (a mesh
    ordinal) pins a batch at that lane when the mesh is active and the
    lane is healthy: the scheduler pins what the mesh does not take.
    Otherwise the first breaker-allowed device backend takes it.  Pair
    every handle with exactly one ``fetch_verify`` — in-flight depth
    accounting (``dispatch_stats``) balances on fetch."""
    from cometbft_tpu.ops import verify as ov
    from cometbft_tpu.parallel import elastic

    pubs, msgs, sigs = list(pubs), list(msgs), list(sigs)
    h = _InflightVerify(pubs, msgs, sigs)
    n = h.n
    dispatch_stats.record_inflight_enter()
    try:
        if lane is None:
            if elastic.takes(n) and _dispatch_mesh(h):
                return h
        elif elastic.active() and int(lane) in elastic.healthy_ordinals():
            h.kind = "lane"
            h.lane = int(lane)
            h.lane_handle = elastic.dispatch_lane(h.lane, pubs, msgs, sigs)
            h.lanes = h.lane_handle.lanes
            dispatch_stats.record_lane_dispatch(str(h.lane), h.lanes, n)
            return h
        reg = backend_health.registry()
        for b in device_chain():
            if reg.breaker(b).allow():
                h.backend = b
                break
        if h.backend is None:
            # fully degraded: fetch walks the chain (host floor answers)
            dispatch_stats.record_lane_dispatch(HOST_BACKEND, max(n, 1), n)
            return h
        if _DEVICE_RUNNER is not None:
            # device-runner seam: the stand-in runs synchronously, so the
            # only way it can overlap is to defer it to the completion
            # pool entirely — which also puts the injector's raise/hang
            # where a real device fault would surface: at fetch
            h.kind = "deferred"
            h.lanes = ov.bucket_size(max(n, 1), _min_bucket(h.backend))
        else:
            try:
                _launch_verify(h, pipelined=True)
            except Exception as e:  # noqa: BLE001 — any dispatch error
                _demote(h, e, "dispatch")
                return h
            h.kind = "chip"
        dispatch_stats.record_lane_dispatch(h.backend, h.lanes, n)
        return h
    except BaseException:
        # a dispatch that never produced a handle must not leak depth
        dispatch_stats.record_inflight_exit()
        raise


def fetch_verify(h: _InflightVerify) -> np.ndarray:
    """Resolve one in-flight verify: (n,) bool accept bits.  Cannot raise
    for infrastructure reasons — every failure mode degrades the guilty
    lane/backend alone and re-verifies on the single-chip chain, whose
    floor is the host ZIP-215 oracle."""
    from cometbft_tpu.parallel import elastic

    try:
        if h.kind == "mesh":
            # the shrink ladder, its first rung the launch in flight: a
            # lost shard is verified again at the next width, below two
            # chips on the single-chip chain
            return elastic.verify_elastic(
                h.pubs, h.msgs, h.sigs, launched=h if h.mesh else None
            )
        if h.kind == "lane":
            try:
                return elastic.fetch_lane(h.lane_handle)
            except Exception as e:  # noqa: BLE001 — lane degrades alone
                if isinstance(e, elastic.ShardFailure):
                    ordinal, err = e.ordinal, e.err
                else:
                    ordinal, err = h.lane, e
                width = max(0, len(elastic.healthy_ordinals()) - 1)
                elastic.note_lane_failure(ordinal, err, width)
                return verify_supervised(h.pubs, h.msgs, h.sigs, mesh=False)
        if h.kind in ("chip", "deferred"):
            try:
                if h.kind == "chip":
                    bits = _fetch_launched(h)
                else:
                    bits = _attempt(h.backend, h.pubs, h.msgs, h.sigs)
            except Exception as e:  # noqa: BLE001 — any error demotes
                _demote(h, e, "fetch")
            else:
                backend_health.registry().breaker(h.backend).record_success()
                return bits
        return verify_supervised(
            h.pubs, h.msgs, h.sigs, skip=h.skip, mesh=False
        )
    finally:
        dispatch_stats.record_inflight_exit()
