"""Light client verification core (reference: light/verifier.go).

``verify_adjacent`` (:91) checks a height+1 header against the trusted
header's next-validators hash; ``verify_non_adjacent`` (:30) checks an
arbitrary later header by requiring >1/3 (trust level) of the TRUSTED
validator set to have signed it, then +2/3 of its own set.  Both commit
checks route through the batch-verifier seam (the TPU path).
``verify_adjacent_chain`` is the sequential client's run of adjacent steps
(light/client.go:608): each header's misses queued at light priority while
the next header is prepared.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from fractions import Fraction
from typing import NamedTuple

from cometbft_tpu.libs import tracing
from cometbft_tpu.types import validation
from cometbft_tpu.types.basic import Timestamp
from cometbft_tpu.types.light import LightBlock

DEFAULT_TRUST_LEVEL = Fraction(1, 3)


class LightClientError(Exception):
    pass


class VerificationError(LightClientError):
    pass


class ErrOldHeaderExpired(VerificationError):
    pass


class ErrInvalidHeader(VerificationError):
    pass


class ErrVerificationFailed(VerificationError):
    """Header ``to`` failed against the trusted header ``from_height``;
    ``reason`` is the error of that one step (reference: light/errors.go
    ErrVerificationFailed, as verifySequential wraps it)."""

    def __init__(self, from_height: int, to: int, reason: Exception):
        super().__init__(
            f"verify from #{from_height} to #{to} failed: {reason}"
        )
        self.from_height = from_height
        self.to = to
        self.reason = reason


# what a step's verdict is raised as; anything else (a backend that gave no
# definitive verdict) is no verdict on the header and is not wrapped
_VERDICTS = (VerificationError, validation.CommitVerificationError)


@dataclass
class TrustOptions:
    """Reference: light/client.go TrustOptions."""

    period_s: int  # trusting period
    height: int
    hash: bytes

    def validate(self) -> None:
        if self.period_s <= 0:
            raise LightClientError("trusting period must be positive")
        if self.height <= 0:
            raise LightClientError("trust height must be positive")
        if len(self.hash) != 32:
            raise LightClientError("trust hash must be 32 bytes")


def header_expired(header_time: Timestamp, trusting_period_s: int, now: float) -> bool:
    """Reference: light/verifier.go HeaderExpired."""
    return header_time.to_ns() / 1e9 + trusting_period_s <= now


def _validate_new_block(
    chain_id: str,
    trusted: LightBlock,
    new: LightBlock,
    now: float,
    max_clock_drift_s: float,
) -> None:
    err = new.validate_basic(chain_id)
    if err:
        raise ErrInvalidHeader(err)
    if new.height <= trusted.height:
        raise ErrInvalidHeader(
            f"new height {new.height} <= trusted {trusted.height}"
        )
    if new.signed_header.header.time.to_ns() <= trusted.signed_header.header.time.to_ns():
        raise ErrInvalidHeader("new header time is not after trusted header time")
    if new.signed_header.header.time.to_ns() / 1e9 > now + max_clock_drift_s:
        raise ErrInvalidHeader("new header is from the future")


def verify_adjacent(
    chain_id: str,
    trusted: LightBlock,
    new: LightBlock,
    trusting_period_s: int,
    now: float,
    max_clock_drift_s: float = 10.0,
) -> None:
    """Reference: light/verifier.go:91 VerifyAdjacent.

    The commit check runs at light priority through the shared verify
    scheduler (via the batch-verifier seam): a syncing light client's
    signature batches coalesce with other callers' work without ever
    delaying consensus votes (docs/verify-scheduler.md)."""
    from cometbft_tpu import verifysched

    with _verify_span(trusted, new, True):
        with tracing.span("light.checks"):
            _check_adjacent_headers(
                chain_id, trusted, new, trusting_period_s, now, max_clock_drift_s
            )
        with verifysched.priority_class(verifysched.PRIO_LIGHT):
            validation.verify_commit_light(
                chain_id,
                new.validator_set,
                new.signed_header.commit.block_id,
                new.height,
                new.signed_header.commit,
            )


def _verify_span(trusted: LightBlock, new: LightBlock, adjacent: bool):
    """``light.verify`` around one step of the verifier, with ``light.checks``
    (the header, time and hash-link checks: a header hash and the new set's
    Merkle root) and the commit passes as its children."""
    return tracing.span(
        "light.verify",
        adjacent=adjacent,
        height=new.height,
        trusted_height=trusted.height,
    )


def _check_adjacent_headers(
    chain_id: str,
    trusted: LightBlock,
    new: LightBlock,
    trusting_period_s: int,
    now: float,
    max_clock_drift_s: float,
) -> None:
    """Every check ``verify_adjacent`` performs EXCEPT the commit signature
    verification — the host half, ONE copy shared by the sequential path
    (``verify_adjacent`` calls this) and the pipelined chain path."""
    if new.height != trusted.height + 1:
        raise ErrInvalidHeader("headers must be adjacent")
    if header_expired(trusted.signed_header.header.time, trusting_period_s, now):
        raise ErrOldHeaderExpired("trusted header expired")
    _validate_new_block(chain_id, trusted, new, now, max_clock_drift_s)
    if (
        new.signed_header.header.validators_hash
        != trusted.signed_header.header.next_validators_hash
    ):
        raise ErrInvalidHeader(
            "new validators hash does not match trusted next_validators_hash"
        )


def verify_adjacent_chain(
    chain_id: str,
    trusted: LightBlock,
    news: "list[LightBlock]",
    trusting_period_s: int,
    now: float,
    max_clock_drift_s: float = 10.0,
) -> None:
    """Verify a consecutive run of headers (trusted+1, trusted+2, ...) as a
    loop of ``verify_adjacent`` does (reference: light/client.go:608
    verifySequential), on the served path: each header's host pass (its
    checks and set root, sign-bytes, cache look-up) ends by queueing its
    cache misses as ONE segment at ``PRIO_LIGHT``, and the caller goes on
    to the next header's host pass while that segment flies.  Verdicts are
    taken back in height order: each header's as soon as it has landed, the
    rest at the end.  What leaves the queue together is the scheduler's
    rule; nothing here calls ``ops`` or the supervisor.

    A rejection is that of the FIRST bad header by height, with that
    header's own class: ``ErrVerificationFailed`` whose ``reason`` is what
    ``verify_adjacent`` raises for it.  A header that fails its host pass
    is held until every header before it has been judged.  Nothing of a
    failed run is trusted, and the segments queued past the bad header are
    not waited for.

    With no trusted device, the scheduler off, or a set that is not
    uniformly ed25519, the plain loop of ``verify_adjacent`` serves, with
    the same verdicts."""
    from cometbft_tpu import verifysched

    if not news:
        return
    if not (
        validation.fused_verify_eligible(lb.validator_set for lb in news)
        and verifysched.scheduler_active()
    ):
        current = trusted
        for lb in news:
            try:
                verify_adjacent(
                    chain_id, current, lb, trusting_period_s, now,
                    max_clock_drift_s,
                )
            except _VERDICTS as e:
                raise ErrVerificationFailed(current.height, lb.height, e) from e
            current = lb
        return

    from cometbft_tpu.crypto import sigcache

    queued: "deque[_Queued]" = deque()
    current, sent = trusted, 0
    with tracing.span(
        "light.chain", headers=len(news), h0=news[0].height
    ) as chain_span:
        try:
            for lb in news:
                try:
                    with tracing.span("light.chain.prep", height=lb.height):
                        with tracing.span("light.checks"):
                            _check_adjacent_headers(
                                chain_id, current, lb, trusting_period_s, now,
                                max_clock_drift_s,
                            )
                        p = validation.prepare_commit_light(
                            chain_id,
                            lb.validator_set,
                            lb.signed_header.commit.block_id,
                            lb.height,
                            lb.signed_header.commit,
                        )
                        part = sigcache.partition_misses(p.pubs, p.msgs, p.sigs)
                        pending = None
                        if part.miss:
                            pending = verifysched.submit_segment_async(
                                [p.pubs[j] for j in part.miss],
                                [p.msgs[j] for j in part.miss],
                                [p.sigs[j] for j in part.miss],
                                verifysched.PRIO_LIGHT,
                                part.keys,
                            )
                            sent += len(part.miss)
                except _VERDICTS as e:
                    _judge(queued, block=True)  # an earlier header's comes first
                    raise ErrVerificationFailed(current.height, lb.height, e) from e
                queued.append(_Queued(current.height, lb.height, p, part, pending))
                _judge(queued, block=False)
                current = lb
            _judge(queued, block=True)
        finally:
            chain_span.set(sigs=sent)


class _Queued(NamedTuple):
    """One header of ``verify_adjacent_chain`` between its host pass and its
    judgement."""

    trusted_height: int
    height: int
    prepared: "validation.PreparedCommit"
    part: object  # its ``sigcache.Partition``
    pending: object  # its ``verifysched.PendingSegment``; None: all hits


def _judge(queued: "deque[_Queued]", block: bool) -> None:
    """Judge the queued headers in height order, writing each one's fresh
    verdicts back first; without ``block``, stop at the first whose segment
    has not landed."""
    from cometbft_tpu import verifysched
    from cometbft_tpu.crypto import sigcache

    while queued:
        q = queued[0]
        if q.pending is not None:
            if not block and not q.pending.done():
                return
            with tracing.span("light.chain.wait", height=q.height):
                got = verifysched.wait_segment(q.pending)
            sigcache.writeback(q.part, got)
        queued.popleft()
        if None in q.part.bits:
            from cometbft_tpu.crypto import backend_health

            raise backend_health.BackendError(
                "the scheduler gave no definitive verdict for some entries "
                "(infrastructure failure, not a signature verdict)"
            )
        try:
            validation.finish_commit_light(q.prepared, q.part.bits)
        except _VERDICTS as e:
            raise ErrVerificationFailed(q.trusted_height, q.height, e) from e


def verify_non_adjacent(
    chain_id: str,
    trusted: LightBlock,
    new: LightBlock,
    trusting_period_s: int,
    now: float,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
    max_clock_drift_s: float = 10.0,
) -> None:
    """Reference: light/verifier.go:30 VerifyNonAdjacent."""
    if new.height == trusted.height + 1:
        return verify_adjacent(
            chain_id, trusted, new, trusting_period_s, now, max_clock_drift_s
        )
    from cometbft_tpu import verifysched

    with _verify_span(trusted, new, False):
        with tracing.span("light.checks"):
            if header_expired(
                trusted.signed_header.header.time, trusting_period_s, now
            ):
                raise ErrOldHeaderExpired("trusted header expired")
            _validate_new_block(chain_id, trusted, new, now, max_clock_drift_s)
        # >trust_level of the TRUSTED set signed the new header
        try:
            with verifysched.priority_class(verifysched.PRIO_LIGHT):
                validation.verify_commit_light_trusting(
                    chain_id,
                    trusted.validator_set,
                    new.signed_header.commit,
                    trust_level=trust_level,
                )
        except validation.NotEnoughPowerError as e:
            raise ErrNewValSetCantBeTrusted(str(e)) from e
        # and +2/3 of the NEW set signed it
        with verifysched.priority_class(verifysched.PRIO_LIGHT):
            validation.verify_commit_light(
                chain_id,
                new.validator_set,
                new.signed_header.commit.block_id,
                new.height,
                new.signed_header.commit,
            )


class ErrNewValSetCantBeTrusted(VerificationError):
    """Not enough trusted power signed: bisect (reference:
    ErrNewValSetCantBeTrusted)."""


def verify(
    chain_id: str,
    trusted: LightBlock,
    new: LightBlock,
    trusting_period_s: int,
    now: float,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
) -> None:
    """Reference: light/verifier.go:128 Verify."""
    if new.height == trusted.height + 1:
        verify_adjacent(chain_id, trusted, new, trusting_period_s, now)
    else:
        verify_non_adjacent(
            chain_id, trusted, new, trusting_period_s, now, trust_level
        )
