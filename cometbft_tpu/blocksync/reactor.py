"""Blocksync reactor (reference: internal/blocksync/reactor.go).

Channel 0x40 (reference: reactor.go:20).  Serves stored blocks to
catching-up peers and drives the BlockPool: status exchange, parallel
block download, then the two-block verification pipeline —
``verify_commit_light`` on block H using block H+1's LastCommit routes
through the batch-verifier seam (the TPU path), making catchup the
biggest batch-verification consumer in the system (SURVEY.md §2.2).
Ahead of the frontier, a window of commits rides the verify scheduler as
one segment at ``PRIO_BLOCKSYNC`` while the heights before it are applied
(``_prefetch_window``).  On completion it hands off to consensus
(SwitchToConsensus).

Spans (``libs/tracing``): ``blocksync.tick`` (``height``, ``outcome``) >
``blocksync.window`` (``commits``, ``sigs``), ``blocksync.wait``
(``height``), ``blocksync.validate``, ``blocksync.apply``; on the thread
that receives, ``blocksync.receive`` (``height``, ``bytes``).
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from typing import Optional

from cometbft_tpu.blocksync.pool import BlockPool
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.libs import log as liblog
from cometbft_tpu.libs import protoenc as pe
from cometbft_tpu.libs import tracing
from cometbft_tpu.p2p.conn import ChannelDescriptor
from cometbft_tpu.p2p.reactor import Reactor
from cometbft_tpu.state.execution import InvalidBlockError
from cometbft_tpu.types import codec, validation
from cometbft_tpu.types.basic import BlockID

BLOCKSYNC_CHANNEL = 0x40

_MSG_BLOCK_REQUEST = 1
_MSG_BLOCK_RESPONSE = 2
_MSG_NO_BLOCK_RESPONSE = 3
_MSG_STATUS_REQUEST = 4
_MSG_STATUS_RESPONSE = 5

_STATUS_INTERVAL = 5.0
# Faster cadence for switch peers the pool has no range for yet: a lost
# StatusResponse otherwise blanks that peer's serving capacity for a full
# _STATUS_INTERVAL (painful when it is the fastest helper).
_STATUS_RETRY = 1.0
_SWITCH_TO_CONSENSUS_INTERVAL = 1.0
_POOL_TICK = 0.02

# Never heard from ANY peer for this long after starting: assume a solo
# chain / isolated node and run consensus (COMETBFT_TPU_BSYNC_SOLO_GRACE).
_SOLO_GRACE = 10.0

# Verification window: k blocks, whose k-1 commits leave in one segment
# (COMETBFT_TPU_BLOCKSYNC_WINDOW; <2 disables the prefetch).
_DEFAULT_WINDOW = 8


def _window_k() -> int:
    try:
        return int(os.environ.get("COMETBFT_TPU_BLOCKSYNC_WINDOW", str(_DEFAULT_WINDOW)))
    except ValueError:
        return _DEFAULT_WINDOW


def _solo_grace() -> float:
    try:
        return float(
            os.environ.get("COMETBFT_TPU_BSYNC_SOLO_GRACE", str(_SOLO_GRACE))
        )
    except ValueError:
        return _SOLO_GRACE


def _enc(kind: int, body: bytes = b"") -> bytes:
    return bytes([kind]) + body


class _Window:
    """The commits of one window, queued as ONE segment: the pending
    segment and, for each commit, its partition and its slice of the
    segment's verdicts."""

    __slots__ = ("pending", "parts", "settled")

    def __init__(self, pending, parts):
        self.pending = pending  # verifysched.PendingSegment
        self.parts = parts  # [(sigcache.Partition, start, end)]
        self.settled = False

    def settle(self) -> None:
        """Wait for the segment once and write every commit's verdicts
        back, so that the frontier's own checks find them in the cache."""
        from cometbft_tpu import verifysched

        self.settled = True
        bits = verifysched.wait_segment(self.pending)
        for part, a, b in self.parts:
            sigcache.writeback(part, bits[a:b])


class BlocksyncReactor(Reactor):
    """Reference: internal/blocksync/reactor.go Reactor."""

    def __init__(
        self,
        state,  # sm.State at boot
        block_exec,
        block_store,
        consensus_reactor=None,  # for SwitchToConsensus
        enabled: bool = True,
        logger=None,
        clock=None,  # injected seam (sim: virtual clock); wall monotonic default
        rng=None,  # injected seam (sim: seeded Random) for the pool's choices
    ):
        super().__init__("BlocksyncReactor")
        self.state = state
        self.block_exec = block_exec
        self.block_store = block_store
        self.consensus_reactor = consensus_reactor
        self.logger = logger or liblog.nop_logger()
        self.syncing = enabled
        self._clock = clock if clock is not None else time.monotonic
        self._solo_grace = _solo_grace()
        start = max(block_store.height() + 1, state.initial_height)
        self.pool = BlockPool(
            start, self._send_block_request, self.logger, clock=clock, rng=rng
        )
        self._thread: Optional[threading.Thread] = None
        self.synced_at: Optional[float] = None
        # tick() pacing state: -inf so the first tick broadcasts status and
        # runs the switch check immediately, as the wall-clock loop did
        self._last_status = float("-inf")
        self._last_status_retry = float("-inf")
        self._status_req_at: Optional[float] = None
        self._last_switch_check = float("-inf")
        # window memo: commit fingerprint -> (height, the window it rides,
        # or None once its verdicts are in the cache), so a commit is
        # queued once and apply/redo ticks never queue it again
        self._fused: dict[bytes, tuple[int, Optional[_Window]]] = {}

    def get_channels(self) -> list[ChannelDescriptor]:
        return [
            ChannelDescriptor(
                BLOCKSYNC_CHANNEL,
                priority=5,
                send_queue_capacity=1000,
                recv_message_capacity=64 * 1024 * 1024,
            )
        ]

    def on_start(self) -> None:
        if self.syncing:
            self._start_pool()

    def _start_pool(self) -> None:
        self._thread = threading.Thread(
            target=self._pool_routine, name="blocksync-pool", daemon=True
        )
        self._thread.start()

    def start_sync(self, state) -> None:
        """Hand-off from statesync (reference: bcReactor.SwitchToBlockSync,
        node/setup.go:587-601): resume block download from the snapshot
        height."""
        self.state = state
        self.pool.height = max(
            self.block_store.height() + 1, state.last_block_height + 1
        )
        self.pool._started_at = self._clock()
        self.syncing = True
        self._start_pool()

    # -- peer lifecycle ----------------------------------------------------

    def add_peer(self, peer) -> None:
        # announce our range + ask for theirs
        peer.try_send(BLOCKSYNC_CHANNEL, self._status_response())
        self._status_req_at = self._clock()
        peer.try_send(BLOCKSYNC_CHANNEL, _enc(_MSG_STATUS_REQUEST))

    def remove_peer(self, peer, reason) -> None:
        self.pool.remove_peer(peer.id)

    def _status_response(self) -> bytes:
        body = pe.t_varint(1, self.block_store.height()) + pe.t_varint(
            2, self.block_store.base()
        )
        return _enc(_MSG_STATUS_RESPONSE, body)

    def _send_block_request(self, peer_id: str, height: int) -> bool:
        sw = self.switch
        if sw is None:
            return False
        peer = sw.get_peer(peer_id)
        if peer is None:
            return False
        return peer.try_send(
            BLOCKSYNC_CHANNEL, _enc(_MSG_BLOCK_REQUEST, pe.t_varint(1, height))
        )

    # -- receive -----------------------------------------------------------

    def receive(self, chan_id: int, peer, msg_bytes: bytes) -> None:
        kind, body = msg_bytes[0], msg_bytes[1:]
        if kind == _MSG_BLOCK_REQUEST:
            f = pe.fields_dict(body)
            height = pe.to_int64(f.get(1, [0])[-1])
            block = self.block_store.load_block(height)
            if block is not None:
                body = pe.t_message(1, codec.encode_block(block), always=True)
                # attach the extended commit when stored (vote extensions):
                # a catching-up validator needs it to propose (reference:
                # BlockResponse.ext_commit).  Gated on the enable height:
                # no store read on the serve path for extension-less chains.
                ext_h = (
                    self.state.consensus_params.feature.vote_extensions_enable_height
                )
                ec = (
                    self.block_store.load_extended_commit(height)
                    if 0 < ext_h <= height
                    else None
                )
                if ec is not None:
                    body += pe.t_message(
                        2, codec.encode_extended_commit(ec), always=True
                    )
                peer.try_send(
                    BLOCKSYNC_CHANNEL, _enc(_MSG_BLOCK_RESPONSE, body)
                )
            else:
                peer.try_send(
                    BLOCKSYNC_CHANNEL,
                    _enc(_MSG_NO_BLOCK_RESPONSE, pe.t_varint(1, height)),
                )
        elif kind == _MSG_BLOCK_RESPONSE:
            with tracing.span("blocksync.receive", bytes=len(msg_bytes)) as sp:
                f = pe.fields_dict(body)
                block = codec.decode_block(f[1][-1])
                ec = (
                    codec.decode_extended_commit(f[2][-1]) if 2 in f else None
                )
                sp.set(height=block.header.height)
            self.pool.add_block(peer.id, block, ec)
        elif kind == _MSG_NO_BLOCK_RESPONSE:
            f = pe.fields_dict(body)
            self.pool.no_block(peer.id, pe.to_int64(f.get(1, [0])[-1]))
        elif kind == _MSG_STATUS_REQUEST:
            peer.try_send(BLOCKSYNC_CHANNEL, self._status_response())
        elif kind == _MSG_STATUS_RESPONSE:
            f = pe.fields_dict(body)
            height = pe.to_int64(f.get(1, [0])[-1])
            base = pe.to_int64(f.get(2, [0])[-1])
            # the status handshake doubles as the RTT bootstrap: without
            # it a new peer has no EWMA and falls back to the flat
            # 15 s REQUEST_TIMEOUT — one dropped first response would
            # wedge a frontier height for the full legacy timeout
            rtt = None
            if self._status_req_at is not None:
                rtt = self._clock() - self._status_req_at
            self.pool.set_peer_range(peer.id, base, height, rtt=rtt)

    # -- the sync loop (reference: reactor.go poolRoutine) -----------------

    def _check_ext_commit(
        self, block, block_id, ec, second_last_commit
    ) -> Optional[str]:
        return check_ext_commit(
            self.state.chain_id,
            self.state.validators,
            block,
            block_id,
            ec,
            second_last_commit,
        )


    def tick(self) -> bool:
        """One scheduler pass: periodic status broadcast, the
        switch-to-consensus check, window refill, then the verify/apply
        frontier.  Returns True when a block was processed (more frontier
        work may be immediately available).  The wall-clock thread loop
        wraps this; the deterministic sim drives it directly off the
        virtual clock (sim/blocksync.py)."""
        with tracing.span(
            "blocksync.tick", height=self.pool.height, outcome="waiting"
        ):
            return self._tick()

    def _tick(self) -> bool:
        now = self._clock()
        if now - self._last_status > _STATUS_INTERVAL:
            self._last_status = now
            if self.switch is not None:
                self._status_req_at = now
                self.switch.broadcast(
                    BLOCKSYNC_CHANNEL, _enc(_MSG_STATUS_REQUEST)
                )
        elif now - self._last_status_retry > _STATUS_RETRY:
            # re-ask only the peers whose range we still don't know: their
            # StatusResponse (or our request) was lost in transit
            self._last_status_retry = now
            sw_peers = getattr(self.switch, "peers", None)
            if sw_peers:
                with self.pool._lock:
                    known = set(self.pool.peers)
                for p in list(sw_peers.values()):
                    if p.id not in known:
                        self._status_req_at = now
                        p.try_send(
                            BLOCKSYNC_CHANNEL, _enc(_MSG_STATUS_REQUEST)
                        )
        if now - self._last_switch_check > _SWITCH_TO_CONSENSUS_INTERVAL:
            self._last_switch_check = now
            if self._maybe_switch_to_consensus():
                tracing.mark(outcome="switched")
                return False
        self.pool.make_next_requests()
        return self._process_blocks()

    def _pool_routine(self) -> None:
        while self.is_running and self.syncing:
            try:
                if not self.tick():
                    time.sleep(_POOL_TICK)
            except Exception as e:  # noqa: BLE001
                self.logger.error("blocksync pool error", err=repr(e))
                time.sleep(0.5)

    # -- the verification window ------------------------------------------

    @staticmethod
    def _commit_fingerprint(height: int, commit) -> bytes:
        """Cheap per-tick memo key: O(1) in validator count (hashing all
        10k signatures every 20 ms pool tick would be ~MBs of SHA-256 per
        tick).  A redo replaces the whole served commit, so height + block
        id + round + size + the first and last signatures distinguish every
        case that matters; a collision merely skips a SPECULATIVE window —
        the authoritative sequential verification is unaffected."""
        h = hashlib.sha256()
        h.update(height.to_bytes(8, "little", signed=True))
        h.update(commit.block_id.hash)
        h.update(commit.round_.to_bytes(4, "little", signed=True))
        h.update(len(commit.signatures).to_bytes(4, "little"))
        if commit.signatures:
            first, last = commit.signatures[0], commit.signatures[-1]
            h.update(bytes([first.block_id_flag]))
            h.update(first.signature)
            h.update(bytes([last.block_id_flag]))
            h.update(last.signature)
        return h.digest()

    def _prefetch_window(self) -> None:
        """Queue the next window of frontier commits on the verify
        scheduler, so that its verdicts land in the signature cache while
        the frontier applies the heights before it.

        A window is the k - 1 commits of k received blocks (k =
        ``_window_k()``), each prepared for the full check
        (``count_all``: the frontier's light check of H and
        ``validate_block``'s full check of H's LastCommit both read them)
        and partitioned against the cache; the misses of all of them go to
        the scheduler as ONE segment at ``PRIO_BLOCKSYNC``, in one hand-off,
        so they leave in one flush.  Nothing waits here: the frontier waits
        on the segment only if it has not landed when it gets there
        (``_settle``).  The reactor looks two windows ahead of the frontier
        and queues the next window as soon as it is whole, so that it flies
        while the heights before it are applied; a smaller one only where
        the frontier's own checks need a commit no window holds (at the
        start, after a redo).  The memo (``_fused``) keeps one commit from
        being queued twice.  With the scheduler switched off the window is
        one synchronous call through the batch seam, as every other
        caller's is.

        Safety: verdicts are keyed on the full (pub, msg, sig) triple.  A
        misprediction (validator set changed mid-window) caches triples the
        real verification never queries: the frontier then verifies its own.
        A window that fails is dropped, with the same effect.  Block
        *application* stays strictly sequential in ``_process_blocks``;
        a bad block still takes the same redo/ban path there."""
        k = _window_k()
        if k < 2 or not sigcache.SigCache.enabled():
            return
        if not validation.fused_verify_eligible([self.state.validators]):
            # no trusted accelerator, every device breaker open (catchup
            # then degrades to the authoritative per-commit host verify in
            # _process_blocks; the window resumes once a half-open probe
            # passes), or non-ed25519 validators: nothing to queue
            return
        frontier = self.pool.height
        self._fused = {
            fp: v for fp, v in self._fused.items() if v[0] >= frontier - 1
        }
        # the commits the next ticks read, as the blocks held carry them:
        # block H carries H - 1's (validate_block's full check), H + 1 H's
        todo = []  # (height, commit, fingerprint) not queued yet
        for h, block, _, _ in self.pool.peek_window(2 * (k - 1)):
            fp = self._commit_fingerprint(h - 1, block.last_commit)
            if h > 1 and fp not in self._fused:
                todo.append((h - 1, block.last_commit, fp))
        todo = todo[: k - 1]
        if not todo or (len(todo) < k - 1 and todo[0][0] > frontier):
            # a full window, or whatever the frontier's own ticks need now
            # (a start, a redo): no commit leaves alone while more can come
            return
        from cometbft_tpu import verifysched

        with tracing.span("blocksync.window") as sp:
            parts, pubs, msgs, sigs, keys = [], [], [], [], []
            for h, commit, fp in todo:
                # best-effort validator-set prediction past the frontier; a
                # miss is safe (see docstring)
                vals = (
                    self.state.last_validators if h < frontier
                    else self.state.validators if h == frontier
                    else self.state.next_validators
                )
                try:
                    p = validation.prepare_commit_light(
                        self.state.chain_id, vals, commit.block_id, h, commit,
                        count_all=True,
                    )
                except validation.CommitVerificationError:
                    continue  # malformed: the frontier's checks reject it
                part = sigcache.partition_misses(p.pubs, p.msgs, p.sigs)
                self._fused[fp] = (h, None)
                if not part.miss:
                    continue  # every verdict in the cache already
                parts.append((fp, h, part, len(pubs)))
                pubs += [p.pubs[j] for j in part.miss]
                msgs += [p.msgs[j] for j in part.miss]
                sigs += [p.sigs[j] for j in part.miss]
                keys += part.keys
            sp.set(commits=len(parts), sigs=len(pubs))
            if not parts:
                return
            if not verifysched.scheduler_active():
                from cometbft_tpu.crypto import batch as cbatch

                bv = cbatch.TpuBatchVerifier()
                for pub, msg, sig in zip(pubs, msgs, sigs):
                    bv.add(pub, msg, sig)
                bv.verify()  # the seam writes the verdicts back
                return
            pending = verifysched.submit_segment_async(
                pubs, msgs, sigs, verifysched.PRIO_BLOCKSYNC, keys
            )
        ends = [a for *_, a in parts[1:]] + [len(pubs)]
        win = _Window(
            pending, [(part, a, b) for (_, _, part, a), b in zip(parts, ends)]
        )
        for fp, h, _, _ in parts:
            self._fused[fp] = (h, win)

    def _settle(self, height: int, commit) -> None:
        """Before the frontier verifies ``commit``: if it rides a window
        whose verdicts are not in the cache yet, wait for that window.  A
        window that fails to settle leaves the frontier to verify its own."""
        got = self._fused.get(self._commit_fingerprint(height, commit))
        if got is None or got[1] is None or got[1].settled:
            return
        try:
            with tracing.span("blocksync.wait", height=height):
                got[1].settle()
        except Exception as e:  # noqa: BLE001 — the frontier verifies itself
            self.logger.error("blocksync window failed", err=repr(e))

    def _process_blocks(self) -> bool:
        """Verify + apply the frontier block using the NEXT block's
        LastCommit (reference: reactor.go:541)."""
        try:
            self._prefetch_window()
        except Exception as e:  # noqa: BLE001 — speculative only
            self.logger.error("blocksync window error", err=repr(e))
        first, second, first_peer, second_peer, first_ext = (
            self.pool.peek_two_blocks()
        )
        if first is None or second is None:
            return False
        first_parts = first.make_part_set()
        first_id = BlockID(hash=first.hash(), part_set_header=first_parts.header)
        from cometbft_tpu import verifysched

        # the two commits the checks below read, if a window holds them
        self._settle(first.header.height, second.last_commit)
        self._settle(first.header.height - 1, first.last_commit)
        try:
            # THE verification: batch Ed25519 through the pluggable seam,
            # tagged bulk-priority for the shared verify scheduler —
            # catchup signature batches must never delay (and are the
            # first to be shed behind) live consensus votes
            with verifysched.priority_class(verifysched.PRIO_BLOCKSYNC):
                validation.verify_commit_light(
                    self.state.chain_id,
                    self.state.validators,
                    first_id,
                    first.header.height,
                    second.last_commit,
                )
                # The commit only signs the header hash; the block body
                # arrived from an untrusted peer and keeps its wire-carried
                # hashes (fill_header_hashes fills empty fields only).
                # Fully validate body-vs-header and header-vs-state before
                # applying, exactly as the reference does
                # (internal/blocksync/reactor.go:546 ValidateBlock) —
                # otherwise a peer could pair the legitimately signed
                # header with tampered txs/last_commit/evidence.
                with tracing.span("blocksync.validate"):
                    self.block_exec.validate_block(self.state, first)
        except (validation.CommitVerificationError, InvalidBlockError) as e:
            tracing.mark(outcome="rejected")
            self.logger.error(
                "invalid block in blocksync",
                height=first.header.height,
                err=str(e),
            )
            # ban both providers and retry (reference: reactor.go bad-block path)
            self.pool.redo_request(first.header.height)
            self.pool.redo_request(first.header.height + 1)
            for pid in (first_peer, second_peer):
                if self.switch is not None and pid:
                    p = self.switch.get_peer(pid)
                    if p is not None:
                        self.switch.stop_peer_for_error(p, e)
            return True
        ext_enabled = self.state.consensus_params.feature.vote_extensions_enable_height
        need_ext = 0 < ext_enabled <= first.header.height
        if need_ext:
            err = self._check_ext_commit(
                first, first_id, first_ext, second.last_commit
            )
            if err is not None:
                tracing.mark(outcome="rejected")
                self.logger.error(
                    "bad extended commit in blocksync",
                    height=first.header.height,
                    err=err,
                )
                self.pool.redo_request(first.header.height)
                if self.switch is not None and first_peer:
                    p = self.switch.get_peer(first_peer)
                    if p is not None:
                        self.switch.stop_peer_for_error(p, ValueError(err))
                return True
        with tracing.span("blocksync.apply"):
            self.block_store.save_block(
                first,
                first_parts,
                second.last_commit,
                extended_commit=first_ext if need_ext else None,
            )
            self.state = self.block_exec.apply_verified_block(
                self.state, first_id, first
            )
        tracing.mark(outcome="applied")
        self.pool.pop_request()
        if self.block_store.height() % 100 == 0:
            self.logger.info(
                "blocksync progress",
                height=self.block_store.height(),
                target=self.pool.max_peer_height(),
            )
        return True

    def _maybe_switch_to_consensus(self) -> bool:
        """Reference: poolRoutine's switchToConsensusTicker."""
        if not self.pool.is_caught_up():
            # never heard from any peer after a grace period: we are alone
            # (solo chain / isolated) — run consensus.  A TEMPORARILY empty
            # peer set mid-sync must NOT trigger this: reconnect will refill
            if (
                not self.pool.ever_had_peers
                and self._clock() - self.pool._started_at > self._solo_grace
            ):
                return self._switch()
            return False
        return self._switch()

    def _switch(self) -> bool:
        self.syncing = False
        self.synced_at = self._clock()
        self.logger.info(
            "blocksync complete, switching to consensus",
            height=self.block_store.height(),
        )
        if self.consensus_reactor is not None:
            self.consensus_reactor.switch_to_consensus(self.state)
        return True



def check_ext_commit(
    chain_id, validators, block, block_id, ec, second_last_commit
) -> Optional[str]:
    """Validate a served extended commit.  The reference only checks
    structure (ExtendedCommit.EnsureExtensions; reactor.go:559 has a
    TODO about validating further) — we additionally verify +2/3 of
    the commit signatures (skipped when identical to the next block's
    already-verified LastCommit) AND every extension signature, both
    through the batch seam: extensions are NOT covered by the commit
    signatures, so a structural check alone would let one malicious
    peer serve real commit sigs with tampered extensions that later
    feed the app's ExtendedCommitInfo."""
    if ec is None:
        return "peer served no extended commit for an extension height"
    if ec.height != block.header.height:
        return f"extended commit height {ec.height} != block"
    if ec.block_id != block_id:
        return "extended commit is for a different block"
    for cs in ec.extended_signatures:
        if cs.for_block() and not cs.extension_signature:
            return "commit signature missing its extension signature"
    # bulk class for the whole check: ext-commit signature batches are
    # catchup traffic like the rest of blocksync — they must never ride
    # the shed-exempt consensus class ahead of live votes
    from cometbft_tpu import verifysched

    with verifysched.priority_class(verifysched.PRIO_BLOCKSYNC):
        return _check_ext_commit_sigs(
            chain_id, validators, block, block_id, ec, second_last_commit
        )


def _check_ext_commit_sigs(
    chain_id, validators, block, block_id, ec, second_last_commit
) -> Optional[str]:
    base = ec.to_commit()
    if base.signatures != second_last_commit.signatures:
        # usually identical to the (already verified) next block's
        # LastCommit; only a genuinely different signature set pays a
        # second +2/3 verification
        try:
            validation.verify_commit_light(
                chain_id,
                validators,
                block_id,
                block.header.height,
                base,
            )
        except Exception as e:  # noqa: BLE001
            return f"extended commit fails +2/3 verification: {e}"
    # Extension signatures are NOT covered by the commit signatures, so
    # verify them against the validator keys through the batch seam —
    # otherwise one malicious peer could serve real commit sigs with
    # tampered extensions, poisoning the app's future ExtendedCommitInfo.
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.types.canonical import (
        canonical_vote_extension_sign_bytes,
    )

    vals = validators.validators
    entries = []
    for i, cs in enumerate(ec.extended_signatures):
        if not cs.for_block():
            continue
        if i >= len(vals):
            return "extended commit has more signatures than validators"
        msg = canonical_vote_extension_sign_bytes(
            chain_id, ec.height, ec.round_, cs.extension
        )
        entries.append((vals[i].pub_key, msg, cs.extension_signature))
    # batch when every key supports it AND the key type is homogeneous
    # (same discipline as validation._should_batch — one batch verifier
    # handles one key type); per-signature fallback otherwise, so mixed or
    # secp256k1 validator sets must not stall blocksync
    if (
        len(entries) >= 2
        and len({getattr(pk, "type_", None) for pk, _, _ in entries}) == 1
        and all(cbatch.supports_batch_verifier(pk) for pk, _, _ in entries)
    ):
        bv = cbatch.create_batch_verifier(entries[0][0])
        for pk, msg, sig in entries:
            bv.add(pk, msg, sig)
        ok, _bits = bv.verify()
        if not ok:
            return "extension signature verification failed"
    else:
        for pk, msg, sig in entries:
            if not pk.verify_signature(msg, sig):
                return "extension signature verification failed"
    return None
