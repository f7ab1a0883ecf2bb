"""Commit verification — the north-star hot path
(reference: types/validation.go:28,63,129,220-333).

``verify_commit`` / ``verify_commit_light`` / ``verify_commit_light_trusting``
route every signature through the pluggable batch-verifier seam
(cometbft_tpu.crypto.batch).  On the TPU backend a 10k-validator commit is
one kernel launch; per-signature accept bits make failure attribution free
(the reference needs a second pass: types/validation.go:308-317).
"""

from __future__ import annotations

import contextlib
import time
from bisect import bisect_right
from dataclasses import dataclass, field
from fractions import Fraction
from itertools import accumulate, compress
from operator import attrgetter, itemgetter, mul
from typing import Optional

from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.libs import tracing
from cometbft_tpu.ops import dispatch_stats
from cometbft_tpu.types.basic import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
)
from cometbft_tpu.types.block import Commit
from cometbft_tpu.types.validator import ValidatorSet


class CommitVerificationError(Exception):
    pass


class InvalidSignatureError(CommitVerificationError):
    def __init__(self, index: int):
        super().__init__(f"wrong signature at index {index}")
        self.index = index


class NotEnoughPowerError(CommitVerificationError):
    def __init__(self, got: int, needed: int):
        super().__init__(f"insufficient voting power: got {got}, needed > {needed}")
        self.got = got
        self.needed = needed


_FLAG = attrgetter("block_id_flag")
_ADDRESS = attrgetter("validator_address")
_SIGNATURE = attrgetter("signature")
_FLAGS = bytes((BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL))
# ``bytes.translate`` tables.  By flag: whether the entry is there and
# whether it is for the block; by a signature's size: none, 1 to 96 bytes,
# longer.
_PRESENT = bytes(f != BLOCK_ID_FLAG_ABSENT for f in range(256))
_FOR_BLOCK = bytes(f == BLOCK_ID_FLAG_COMMIT for f in range(256))
_SIGNATURE_SIZE = bytes((n > 0) + (n > 96) for n in range(256))


def _scan_regular(vals: ValidatorSet, commit: Commit) -> Optional[bytes]:
    """One read of each signature's flag, address and signature size, in
    passes that take no Python step a signature, against the set's
    addresses.  It decides the REGULAR commit only: every signature passes
    what ``Commit.validate_basic`` asks of it, the commit is as long as
    the set, and each entry that is there names the validator at its index.
    Such a commit gives its flags, one byte a signature.  Anything else
    (ABSENT and NIL entries are regular) gives None, and the loops judge
    that commit as they always have, error by error."""
    sigs = commit.signatures
    if not vals or not sigs or len(sigs) != len(vals):
        return None
    try:
        flags = bytes(map(_FLAG, sigs))
        signature_sizes = bytes(map(len, map(_SIGNATURE, sigs)))
    except (TypeError, ValueError):  # a flag or a size past a byte
        return None
    present = flags.translate(_PRESENT)
    if (
        flags.translate(None, _FLAGS)
        or signature_sizes.translate(_SIGNATURE_SIZE) != present
    ):
        return None
    want = vals.facts().addresses
    if BLOCK_ID_FLAG_ABSENT in flags:
        # an absent entry has no address: b"" where its validator's would be
        want = list(map(mul, want, present))
    # one comparison reads each address once, its size with it
    return flags if list(map(_ADDRESS, sigs)) == want else None


def _verify_basic(
    vals: ValidatorSet,
    commit: Commit,
    height: int,
    block_id: BlockID,
    sp=tracing._NULL_SPAN,
) -> Optional[bytes]:
    """The checks before any signature, in the reference's order.  Returns
    the flags of a regular commit (``_scan_regular``), for
    ``_collect_entries``; the signatures of such a commit have passed
    ``validate_basic``'s loop."""
    if commit is None:
        raise CommitVerificationError("nil commit")
    flags = _scan_regular(vals, commit)
    sp.set(path="loop" if flags is None else "fast")
    err = commit.validate_basic() if flags is None else commit.validate_basic_head()
    if err:
        raise CommitVerificationError(err)
    if vals is None or len(vals) == 0:
        raise CommitVerificationError("empty validator set")
    if height != commit.height:
        raise CommitVerificationError(
            f"commit height {commit.height} != expected {height}"
        )
    if commit.block_id != block_id:
        raise CommitVerificationError("commit is for a different block id")
    if len(vals) != commit.size():
        raise CommitVerificationError(
            f"commit size {commit.size()} != validator set size {len(vals)}"
        )
    return flags


def _should_batch(vals: ValidatorSet, signatures: int) -> bool:
    """Reference: types/validation.go:15 shouldBatchVerify — >=2 signatures
    (``signatures`` is the count to be verified) and a batch-capable
    HOMOGENEOUS key type (a batch verifier handles one key type; a mixed
    ed25519/bls set must fall back to per-signature), which the set keeps."""
    return signatures >= 2 and vals.facts().batch_capable


def _collect_entries(
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    count_all: bool,
    lookup_by_address: bool,
    flags: Optional[bytes] = None,
):
    """The entry-selection half of ``_verify_commit``: which (idx, val, cs)
    triples get their signatures checked.  Shared with the pipelined
    consumers (blocksync prefetch, light-client chain sync) so speculative
    verification covers EXACTLY the entries the authoritative pass will
    query.  Returns (entries, tallied) — tallied is only meaningful for
    count_all=False, where collection stops at the power threshold.
    ``flags`` are ``_verify_basic``'s, of a regular commit by index: the
    same entries then come from them and the set's running power."""
    if flags is not None and not lookup_by_address:
        return _entries_by_flags(
            vals, commit, voting_power_needed, count_all, flags
        )
    entries = []  # (commit_idx, validator, commit_sig)
    tallied = 0
    seen_addrs: set[bytes] = set()  # trusting mode: count each validator once
    for idx, cs in enumerate(commit.signatures):
        if cs.absent():
            continue
        if lookup_by_address:
            found = vals.get_by_address(cs.validator_address)
            if found is None:
                continue
            val = found[1]
            if val.address in seen_addrs:
                raise CommitVerificationError(
                    f"duplicate validator {val.address.hex()} in commit"
                )
            seen_addrs.add(val.address)
        else:
            val = vals.get_by_index(idx)
            if val is None:
                continue
            if cs.validator_address and val.address != cs.validator_address:
                raise CommitVerificationError(
                    f"validator address mismatch at index {idx}"
                )
        entries.append((idx, val, cs))
        if not count_all:
            if cs.for_block():
                tallied += val.voting_power
            if tallied > voting_power_needed:
                break
    return entries, tallied


def _entries_by_flags(
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    count_all: bool,
    flags: bytes,
):
    """``_collect_entries``' by-index loop on a regular commit: the stop is
    found by bisection in the running power, which is the set's own unless
    an ABSENT or NIL entry, which carries none to the block, is among the
    signatures; the triples are zipped."""
    stop = len(flags)  # one past the last signature read
    tallied = 0
    if not count_all:
        facts = vals.facts()
        running = facts.cum_power
        if flags.count(BLOCK_ID_FLAG_COMMIT) != stop:
            for_block = flags.translate(_FOR_BLOCK)
            running = list(accumulate(map(mul, facts.powers, for_block)))
        # the first entry that carries the tally past the threshold ends it
        stop = min(bisect_right(running, voting_power_needed) + 1, stop)
        tallied = running[stop - 1]
    triples = zip(range(stop), vals.validators, commit.signatures)
    if flags.find(BLOCK_ID_FLAG_ABSENT, 0, stop) >= 0:
        triples = compress(triples, flags.translate(_PRESENT))
    return list(triples), tallied


def _judge_entries(entries, bits) -> None:
    """Turn per-entry accept bits into the verdict ``_verify_commit``
    reports: first failed entry names the culprit index."""
    for (idx, _, _), bit in zip(entries, bits):
        if not bit:
            raise InvalidSignatureError(idx)


def _tally(entries, tallied: int, count_all: bool, voting_power_needed: int):
    if count_all:
        tallied = sum(
            val.voting_power for _, val, cs in entries if cs.for_block()
        )
    if tallied <= voting_power_needed:
        raise NotEnoughPowerError(tallied, voting_power_needed)


@contextlib.contextmanager
def _commit_span(commit: Commit, mode: str, vals: ValidatorSet):
    """``verify.commit`` around a WHOLE public call, basic checks included;
    the verify-latency histogram is fed from the span's own readings (a
    call that raises records the span, with its error, and no sample).
    ``mode`` is ``full`` / ``light`` / ``trusting``; the trusting pass is a
    stage of its own, ``verify.commit.trusting``, so that the two passes a
    skipping light client makes over one commit are told apart in the
    recorder's totals.  ``set_facts`` says whether this call built the
    set's record (``ValidatorSet.facts``) or found it kept."""
    had = vals is not None and vals.has_facts()
    t0 = time.perf_counter()
    with tracing.span(
        "verify.commit.trusting" if mode == "trusting" else "verify.commit",
        height=getattr(commit, "height", None),
        sigs=len(getattr(commit, "signatures", None) or ()),
        count_all=mode == "full",
        mode=mode,
    ) as sp:
        try:
            yield sp
        finally:
            if vals is not None and vals.has_facts():
                sp.set(set_facts="kept" if had else "built")
    dispatch_stats.record_verify_latency(tracing.wall_seconds(sp, t0))


def _sign_bytes(chain_id: str, commit: Commit, entries) -> list:
    """One native call builds every sign-bytes (10k-commit hot path);
    python per-index fallback inside."""
    with tracing.span("commit.sign_bytes", sigs=len(entries)):
        return commit.all_vote_sign_bytes(
            chain_id, list(map(itemgetter(0), entries))
        )


def _verify_commit(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    count_all: bool,
    lookup_by_address: bool,
    backend: Optional[str] = None,
    sp=tracing._NULL_SPAN,
    flags: Optional[bytes] = None,
) -> None:
    """Shared engine for all three public variants; ``sp`` is the caller's
    ``verify.commit`` span, ``flags`` what ``_verify_basic`` returned.

    count_all=True  -> verify every non-absent signature (consensus safety).
    count_all=False -> stop as soon as tallied power exceeds the threshold
                       (light-client fast path; remaining sigs unverified).
    lookup_by_address -> trusting mode: commit indexes may not match the
                       validator set; match signatures by address.
    """
    entries, tallied = _collect_entries(
        vals, commit, voting_power_needed, count_all, lookup_by_address, flags
    )
    # collection stops at the entry that carries the tally past the threshold
    stopped = not count_all and tallied > voting_power_needed
    scanned = entries[-1][0] + 1 if stopped else len(commit.signatures)
    sp.set(entries=len(entries), scanned=scanned, skipped=scanned - len(entries))

    # Verify the collected signatures (batch seam).  The batch verifiers
    # pre-filter through the consensus-wide signature cache, so a commit
    # whose votes were verified at gossip time ships zero device work.
    if entries:
        # the entries are signatures of the commit that are not absent
        if _should_batch(vals, len(entries)):
            bv = cbatch.create_batch_verifier(entries[0][1].pub_key, backend)
            sign_bytes = _sign_bytes(chain_id, commit, entries)
            with tracing.span("batch.verify", sigs=len(entries)) as bsp:
                with tracing.span("batch.add"):
                    for (idx, val, cs), sb in zip(entries, sign_bytes):
                        bv.add(val.pub_key, sb, cs.signature)
                ok, bits = bv.verify()
                bsp.set(
                    hits=getattr(bv, "cache_hits", 0),
                    keys=getattr(bv, "keys_hashed", 0),
                )
            if not ok:
                _judge_entries(entries, bits)
                raise CommitVerificationError("batch verification failed")
        else:
            for idx, val, cs in entries:
                if not sigcache.verify_with_cache(
                    val.pub_key,
                    commit.vote_sign_bytes(chain_id, idx),
                    cs.signature,
                ):
                    raise InvalidSignatureError(idx)

    # Tally voting power for the committed block.
    _tally(entries, tallied, count_all, voting_power_needed)


@dataclass
class PreparedCommit:
    """The host half of a light commit verification, split out so pipelined
    consumers (light-client chain sync, blocksync window prefetch) can
    dispatch many commits' signature batches before judging any of them.
    ``pubs``/``msgs``/``sigs`` align 1:1 with ``entries``."""

    chain_id: str
    vals: ValidatorSet
    commit: Commit
    voting_power_needed: int
    tallied: int
    count_all: bool = False
    entries: list = field(default_factory=list)
    pubs: list = field(default_factory=list)
    msgs: list = field(default_factory=list)
    sigs: list = field(default_factory=list)


def prepare_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    count_all: bool = False,
) -> PreparedCommit:
    """Phase 1 of ``verify_commit_light``: basic checks + entry collection +
    sign-bytes construction.  Raises exactly what ``verify_commit_light``
    would raise for a malformed commit; does NOT touch any signature.

    ``count_all=True`` collects every non-absent entry (the superset the
    full ``verify_commit`` queries) — blocksync prefetches with this so
    BOTH the light frontier check and apply-time ``validate_block``'s full
    re-verification resolve from cache."""
    flags = _verify_basic(vals, commit, height, block_id)
    needed = vals.total_voting_power() * 2 // 3
    entries, tallied = _collect_entries(
        vals, commit, needed, count_all, False, flags
    )
    msgs = _sign_bytes(chain_id, commit, entries)
    return PreparedCommit(
        chain_id=chain_id,
        vals=vals,
        commit=commit,
        voting_power_needed=needed,
        tallied=tallied,
        count_all=count_all,
        entries=entries,
        pubs=[val.pub_key.bytes() for _, val, _ in entries],
        msgs=list(msgs),
        sigs=[cs.signature for _, _, cs in entries],
    )


def fused_verify_eligible(validator_sets=()) -> bool:
    """THE eligibility gate for speculative fused verification, shared by
    the blocksync window prefetch and the light-client chain sync so the
    clauses cannot diverge: a trusted accelerator backend must be selected
    (a CPU-backend node's host library path has no dispatch floor to
    amortize), the supervisor must have a live device tier (with every
    breaker open, catchup degrades to per-commit host verify instead of
    speculating — see docs/backend-supervisor.md), and every supplied
    validator set must be uniformly ed25519 (the fused kernel's key type)."""
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.crypto import keys as ck
    from cometbft_tpu.ops import supervisor

    if cbatch.default_backend() != "tpu":
        return False
    if supervisor.active_backend() is None:
        return False
    for vals in validator_sets:
        if not all(
            getattr(v.pub_key, "type_", None) == ck.ED25519_KEY_TYPE
            for v in vals.validators
        ):
            return False
    return True


def finish_commit_light(prepared: PreparedCommit, bits) -> None:
    """Phase 2: judge the accept bits (aligned with ``prepared.entries``)
    and tally power — same errors, same order, as the ``_verify_commit``
    mode ``prepared`` was collected under."""
    _judge_entries(prepared.entries, bits)
    _tally(
        prepared.entries,
        prepared.tallied,
        prepared.count_all,
        prepared.voting_power_needed,
    )


def verify_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    backend: Optional[str] = None,
) -> None:
    """Full verification: every signature checked, +2/3 power required
    (reference: types/validation.go:28)."""
    with _commit_span(commit, "full", vals) as sp:
        flags = _verify_basic(vals, commit, height, block_id, sp)
        needed = vals.total_voting_power() * 2 // 3
        _verify_commit(
            chain_id, vals, commit, needed, True, False, backend, sp, flags
        )


def verify_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    backend: Optional[str] = None,
) -> None:
    """Light verification: stop at +2/3 (reference: types/validation.go:63)."""
    with _commit_span(commit, "light", vals) as sp:
        flags = _verify_basic(vals, commit, height, block_id, sp)
        needed = vals.total_voting_power() * 2 // 3
        _verify_commit(
            chain_id, vals, commit, needed, False, False, backend, sp, flags
        )


def verify_commit_light_trusting(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    trust_level: Fraction = Fraction(1, 3),
    backend: Optional[str] = None,
) -> None:
    """Trusting-period verification against a possibly different validator
    set; needs > trust_level of this set's power
    (reference: types/validation.go:129)."""
    with _commit_span(commit, "trusting", vals) as sp:
        sp.set(path="loop")  # by address: a look-up a signature
        if commit is None or not commit.signatures:
            raise CommitVerificationError("nil or empty commit")
        if trust_level.numerator * 3 < trust_level.denominator:  # < 1/3
            raise CommitVerificationError("trust level must be >= 1/3")
        total = vals.total_voting_power()
        needed = total * trust_level.numerator // trust_level.denominator
        _verify_commit(chain_id, vals, commit, needed, False, True, backend, sp)
