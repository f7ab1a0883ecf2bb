"""Light client verification core (reference: light/verifier.go).

``verify_adjacent`` (:91) checks a height+1 header against the trusted
header's next-validators hash; ``verify_non_adjacent`` (:30) checks an
arbitrary later header by requiring >1/3 (trust level) of the TRUSTED
validator set to have signed it, then +2/3 of its own set.  Both commit
checks route through the batch-verifier seam (the TPU path).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from fractions import Fraction

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
    """Verify a consecutive run of headers (trusted+1, trusted+2, ...) with
    host/device overlap: every header's host work (adjacency + validator-
    hash link + sign-bytes construction) runs up front, then all commit
    batches are dispatched through ``ops.verify.verify_batches_overlapped``
    — header i+1's host prep overlaps header i's in-flight dispatch, and on
    backends that queue dispatches the kernels pipeline.  Judgement stays
    strictly in order, so the raised error class matches what sequential
    ``verify_adjacent`` raises for that header (when several headers are
    independently bad, the chain may surface a later header's *structural*
    error before an earlier header's *signature* error — either way the
    sync aborts and nothing is trusted).

    Falls back to the plain sequential loop when the accelerator batch
    backend is off or a validator set is not uniformly ed25519."""
    from cometbft_tpu.crypto import sigcache
    from cometbft_tpu.types import validation

    if not news:
        return

    def _sequential() -> None:
        current = trusted
        for lb in news:
            verify_adjacent(
                chain_id, current, lb, trusting_period_s, now, max_clock_drift_s
            )
            current = lb

    # shared eligibility gate (types/validation.fused_verify_eligible):
    # trusted accelerator + live device tier (with every breaker open the
    # sequential path host-verifies per header — same verdicts, no fused
    # batches to build) + uniformly-ed25519 validator sets
    if len(news) < 2 or not validation.fused_verify_eligible(
        lb.validator_set for lb in news
    ):
        return _sequential()

    # host pass: adjacency checks + entry collection for every header
    prepared = []
    current = trusted
    for lb in news:
        _check_adjacent_headers(
            chain_id, current, lb, trusting_period_s, now, max_clock_drift_s
        )
        prepared.append(
            validation.prepare_commit_light(
                chain_id,
                lb.validator_set,
                lb.signed_header.commit.block_id,
                lb.height,
                lb.signed_header.commit,
            )
        )
        current = lb

    # device pass: ship only cache misses, one overlapped batch per header
    per_header = [  # (prepared, its sigcache.Partition: bits with None holes)
        (p, sigcache.partition_misses(p.pubs, p.msgs, p.sigs))
        for p in prepared
    ]
    from cometbft_tpu.ops import verify as ov

    work = [
        (
            [p.pubs[j] for j in part.miss],
            [p.msgs[j] for j in part.miss],
            [p.sigs[j] for j in part.miss],
        )
        for p, part in per_header
        if part.miss
    ]
    from cometbft_tpu.libs import tracing

    with tracing.span(
        "light.chain",
        headers=len(news),
        h0=news[0].height,
        sigs=sum(len(part.miss) for _, part in per_header),
    ):
        fresh = iter(ov.verify_batches_overlapped(work) if work else [])

    # judge strictly in order
    for p, part in per_header:
        if part.miss:
            sigcache.writeback(part, next(fresh))
        validation.finish_commit_light(p, part.bits)


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
