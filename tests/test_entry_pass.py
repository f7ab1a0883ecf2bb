"""The commit entry's one pass (ISSUE 31, ``types/validation``): a regular
commit is read once against the facts its validator set keeps, anything else
goes to the loops.  The oracle below is a plain copy of the three functions
the pass replaced (``_verify_basic`` with ``Commit.validate_basic``,
``_should_batch``, ``_collect_entries``) as they stood before it, with a
look-up by address that scans the set; the public calls must select the same
entries (by index and by object), tally the same power and raise the same
error, in every mode, and say on their span which path decided."""

import hashlib
import random
import zlib
from dataclasses import replace

import pytest

from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.crypto.keys import Ed25519PubKey
from cometbft_tpu.libs import tracing
from cometbft_tpu.types import validation
from cometbft_tpu.types.basic import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    PartSetHeader,
    Timestamp,
)
from cometbft_tpu.types.block import Commit
from cometbft_tpu.types.validation import (
    CommitVerificationError,
    NotEnoughPowerError,
)
from cometbft_tpu.types.validator import Validator, ValidatorSet
from cometbft_tpu.types.vote import CommitSig

CHAIN_ID = "entry-chain"
HEIGHT = 9
MODES = ("full", "light", "trusting")
SIZES = (4, 31, 300)


# -- the oracle: the entry as it stood before the pass ------------------------


def _old_validate_basic(commit):
    if commit.height < 0:
        return "negative height"
    if commit.round_ < 0:
        return "negative round"
    if commit.height >= 1:
        if commit.block_id.is_zero():
            return "commit cannot be for nil block"
        if not commit.signatures:
            return "no signatures in commit"
    for cs in commit.signatures:
        if cs.block_id_flag not in (
            BLOCK_ID_FLAG_ABSENT,
            BLOCK_ID_FLAG_COMMIT,
            BLOCK_ID_FLAG_NIL,
        ):
            return "invalid block id flag"
        if cs.block_id_flag == BLOCK_ID_FLAG_ABSENT:
            if cs.validator_address or cs.signature:
                return "absent signature with data"
        else:
            if len(cs.validator_address) != 20:
                return "invalid validator address"
            if not cs.signature or len(cs.signature) > 96:
                return "invalid signature size"
    return None


def _old_verify_basic(vals, commit, height, block_id):
    if commit is None:
        raise CommitVerificationError("nil commit")
    err = _old_validate_basic(commit)
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


def _old_should_batch(vals, commit):
    non_absent = sum(0 if cs.absent() else 1 for cs in commit.signatures)
    if non_absent < 2:
        return False
    types = {getattr(v.pub_key, "type_", None) for v in vals.validators}
    if len(types) != 1:
        return False
    return all(cbatch.supports_batch_verifier(v.pub_key) for v in vals.validators)


def _scan_by_address(vals, address):
    for i, v in enumerate(vals.validators):
        if v.address == address:
            return i, v
    return None


def _old_collect_entries(vals, commit, voting_power_needed, count_all, by_address):
    entries = []
    tallied = 0
    seen_addrs = set()
    for idx, cs in enumerate(commit.signatures):
        if cs.absent():
            continue
        if by_address:
            found = _scan_by_address(vals, cs.validator_address)
            if found is None:
                continue
            val = found[1]
            if val.address in seen_addrs:
                raise CommitVerificationError(
                    f"duplicate validator {val.address.hex()} in commit"
                )
            seen_addrs.add(val.address)
        else:
            val = vals.validators[idx] if 0 <= idx < len(vals.validators) else None
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


def _old_entry(mode, vals, commit, height, block_id):
    """(entries, tallied, batched, error) of the entry as it stood."""
    entries, tallied, batched = None, None, False
    try:
        total = sum(v.voting_power for v in vals.validators)
        if mode == "trusting":
            if commit is None or not commit.signatures:
                raise CommitVerificationError("nil or empty commit")
            needed = total // 3
        else:
            _old_verify_basic(vals, commit, height, block_id)
            needed = total * 2 // 3
        count_all = mode == "full"
        entries, tallied = _old_collect_entries(
            vals, commit, needed, count_all, mode == "trusting"
        )
        batched = bool(entries) and _old_should_batch(vals, commit) and (
            len(entries) >= 2
        )
        if count_all:
            tallied_for_block = sum(
                v.voting_power for _, v, cs in entries if cs.for_block()
            )
        else:
            tallied_for_block = tallied
        if tallied_for_block <= needed:
            raise NotEnoughPowerError(tallied_for_block, needed)
    except CommitVerificationError as e:
        return entries, tallied, batched, (type(e), str(e))
    return entries, tallied, batched, None


# -- seeded sets and commits ---------------------------------------------------


def _block_id(tag=b"block"):
    return BlockID(
        hash=hashlib.sha256(tag).digest(),
        part_set_header=PartSetHeader(total=1, hash=hashlib.sha256(b"parts").digest()),
    )


def _key(tag, i):
    return Ed25519PubKey(hashlib.sha256(b"%s-%d" % (tag, i)).digest())


def _set(n, rng, tag=b"entry"):
    powers = [rng.randrange(1, 1000) for _ in range(n)]
    if n >= 8:
        powers[rng.randrange(n)] = 0  # a validator without power is a member too
    return ValidatorSet([Validator(_key(tag, i), p) for i, p in enumerate(powers)])


def _commit(vals, rng, block_id):
    sigs = [
        CommitSig(
            BLOCK_ID_FLAG_COMMIT,
            bytes(v.address),  # a decoder's own object, not the set's
            Timestamp(1_700_000_000 + i, rng.randrange(10**9)),
            rng.randbytes(64),
        )
        for i, v in enumerate(vals.validators)
    ]
    return Commit(height=HEIGHT, round_=1, block_id=block_id, signatures=sigs)


def _trusted_of(vals, rng):
    """A trusted set that shares nine members in ten with ``vals``, under
    powers of its own."""
    n = len(vals)
    out = max(1, n // 10)
    gone = set(rng.sample(range(n), out))
    members = [
        Validator(v.pub_key, rng.randrange(1, 1000))
        for i, v in enumerate(vals.validators)
        if i not in gone
    ]
    members += [Validator(_key(b"older", i), rng.randrange(1, 1000)) for i in range(out)]
    return ValidatorSet(members)


class _World:
    """One case's inputs; a mutation edits it in place."""

    def __init__(self, n, seed):
        self.rng = random.Random(seed)
        self.block_id = _block_id()
        self.vals = _set(n, self.rng)
        self.commit = _commit(self.vals, self.rng, self.block_id)
        self.trusted = _trusted_of(self.vals, self.rng)
        self.height = HEIGHT
        self.n = n

    @property
    def sigs(self):
        return self.commit.signatures

    def threshold(self):
        """Index of the entry that carries an all-COMMIT light pass over 2/3."""
        total = self.vals.total_voting_power()
        entries, _ = _old_collect_entries(
            self.vals, self.commit, total * 2 // 3, False, False
        )
        return entries[-1][0]

    def absent(self, i):
        self.sigs[i] = CommitSig.absent_sig()

    def nil(self, i):
        self.sigs[i] = replace(self.sigs[i], block_id_flag=BLOCK_ID_FLAG_NIL)


def _edit(i, **fields):
    def mutate(w):
        at = i if i >= 0 else w.n + i
        at = min(at, w.n - 1)
        w.sigs[at] = replace(w.sigs[at], **fields)

    return mutate


def _scatter(w):
    for i in range(w.n):
        roll = w.rng.random()
        if roll < 0.2:
            w.absent(i)
        elif roll < 0.3:
            w.nil(i)


def _never_enough(w):
    for i in range(0, w.n, 2):
        w.absent(i)
    for i in range(1, w.n, 4):
        w.nil(i)


def _swap_sizes(w):
    # a 19- and a 21-byte address that join to the same 40 bytes
    a, b = w.sigs[0].validator_address, w.sigs[1].validator_address
    w.sigs[0] = replace(w.sigs[0], validator_address=a[:19])
    w.sigs[1] = replace(w.sigs[1], validator_address=a[19:] + b)


def _duplicate_signer(w):
    w.sigs[1] = replace(w.sigs[0])


def _longer(w):
    w.sigs.append(replace(w.sigs[-1]))


def _shorter(w):
    w.sigs.pop()


def _set_attr(name, value):
    def mutate(w):
        setattr(w, name, value)

    return mutate


def _commit_attr(name, value):
    def mutate(w):
        setattr(w.commit, name, value)

    return mutate


def _both(*mutations):
    def mutate(w):
        for m in mutations:
            m(w)

    return mutate


# name -> (mutation, the path the by-index modes must take)
CASES = {
    "all_commit": (lambda w: None, "fast"),
    "absent_first": (lambda w: w.absent(0), "fast"),
    "absent_middle": (lambda w: w.absent(w.n // 2), "fast"),
    "absent_at_threshold": (lambda w: w.absent(w.threshold()), "fast"),
    "absent_last": (lambda w: w.absent(w.n - 1), "fast"),
    "nil_first": (lambda w: w.nil(0), "fast"),
    "nil_middle": (lambda w: w.nil(w.n // 2), "fast"),
    "nil_at_threshold": (lambda w: w.nil(w.threshold()), "fast"),
    "nil_then_absent_at_threshold": (
        lambda w: (w.nil(max(w.threshold() - 1, 0)), w.absent(w.threshold())),
        "fast",
    ),
    "scattered_absent_and_nil": (_scatter, "fast"),
    "never_enough_power": (_never_enough, "fast"),
    "all_absent_but_one": (
        lambda w: [w.absent(i) for i in range(1, w.n)],
        "fast",
    ),
    "tampered_signature": (_edit(0, signature=bytes(64)), "fast"),
    "bls_sized_signature": (_edit(1, signature=bytes(96)), "fast"),
    "wrong_address_first": (_edit(0, validator_address=bytes(20)), "loop"),
    "wrong_address_last": (_edit(-1, validator_address=b"\x01" * 20), "loop"),
    "address_19_bytes": (_edit(2, validator_address=bytes(19)), "loop"),
    "address_sizes_swapped": (_swap_sizes, "loop"),
    "signature_empty": (_edit(1, signature=b""), "loop"),
    "signature_97_bytes": (_edit(-1, signature=bytes(97)), "loop"),
    "signature_300_bytes": (_edit(0, signature=bytes(300)), "loop"),
    "absent_with_address": (
        _edit(1, block_id_flag=BLOCK_ID_FLAG_ABSENT, signature=b""),
        "loop",
    ),
    "absent_with_signature": (
        _edit(1, block_id_flag=BLOCK_ID_FLAG_ABSENT, validator_address=b""),
        "loop",
    ),
    "flag_4": (_edit(2, block_id_flag=4), "loop"),
    "flag_0": (_edit(0, block_id_flag=0), "loop"),
    "flag_past_a_byte": (_edit(-1, block_id_flag=300), "loop"),
    "commit_longer": (_longer, "loop"),
    "commit_shorter": (_shorter, "loop"),
    "duplicate_signer": (_duplicate_signer, "loop"),
    # the head's checks keep their place around the signatures'
    "other_height": (_set_attr("height", HEIGHT + 1), "fast"),
    "other_block": (_set_attr("block_id", _block_id(b"other")), "fast"),
    "negative_round": (_commit_attr("round_", -1), "fast"),
    "nil_block": (
        _both(_commit_attr("block_id", BlockID()), _set_attr("block_id", BlockID())),
        "fast",
    ),
    "flag_4_and_other_height": (
        _both(_edit(2, block_id_flag=4), _set_attr("height", HEIGHT + 1)),
        "loop",
    ),
    "wrong_address_and_other_block": (
        _both(
            _edit(0, validator_address=bytes(20)),
            _set_attr("block_id", _block_id(b"other")),
        ),
        "loop",
    ),
}


class _AcceptAll:
    def __init__(self):
        self.added = 0

    def add(self, *triple):
        self.added += 1

    def verify(self):
        return True, [True] * self.added


@pytest.fixture
def entry_spy(monkeypatch):
    """The public calls with every signature accepted; what the entry
    selected, and whether it went to a batch verifier, is written down."""
    seen = {"collected": None, "batched": False}
    real = validation._collect_entries

    def collect(*args, **kw):
        seen["collected"] = real(*args, **kw)
        return seen["collected"]

    def batch_verifier(*args, **kw):
        seen["batched"] = True
        return _AcceptAll()

    monkeypatch.setattr(validation, "_collect_entries", collect)
    monkeypatch.setattr(validation.cbatch, "create_batch_verifier", batch_verifier)
    monkeypatch.setattr(sigcache, "verify_with_cache", lambda *a, **k: True)
    tracing.reset_tracer()
    yield seen
    tracing.reset_tracer()


def _call(mode, w):
    if mode == "trusting":
        return validation.verify_commit_light_trusting(CHAIN_ID, w.trusted, w.commit)
    fn = validation.verify_commit if mode == "full" else validation.verify_commit_light
    return fn(CHAIN_ID, w.vals, w.block_id, w.height, w.commit)


def _ids(entries):
    return [(idx, id(val), id(cs)) for idx, val, cs in entries]


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_pass_selects_what_the_loops_selected(case, mode, n, entry_spy):
    mutate, by_index_path = CASES[case]
    w = _World(n, seed=zlib.crc32(case.encode()) + n)
    mutate(w)
    vals = w.trusted if mode == "trusting" else w.vals
    want_entries, want_tallied, want_batched, want_error = _old_entry(
        mode, vals, w.commit, w.height, w.block_id
    )

    error = None
    try:
        _call(mode, w)
    except CommitVerificationError as e:
        error = (type(e), str(e))
    assert error == want_error

    if want_entries is None:
        assert entry_spy["collected"] is None
    else:
        entries, tallied = entry_spy["collected"]
        assert _ids(entries) == _ids(want_entries)
        assert tallied == want_tallied
        assert entry_spy["batched"] == want_batched

    stage = "verify.commit.trusting" if mode == "trusting" else "verify.commit"
    (span,) = [s for s in tracing.get_tracer().tail(50) if s["stage"] == stage]
    assert span["attrs"]["path"] == ("loop" if mode == "trusting" else by_index_path)
    if want_entries is not None:
        assert span["attrs"]["entries"] == len(want_entries)
        assert span["attrs"]["set_facts"] == "built"
    else:  # refused before the set was asked anything: no record, no word
        assert span["attrs"].get("set_facts", "built") == "built"


@pytest.mark.parametrize("count_all", (False, True))
@pytest.mark.parametrize("case", ("all_commit", "scattered_absent_and_nil", "flag_4"))
def test_prepare_commit_light_shares_the_pass(case, count_all):
    """The pipelined consumers (blocksync's window, the light chain sync)
    collect through the same two functions."""
    w = _World(60, seed=7)
    CASES[case][0](w)
    want_entries, want_tallied, _, want_error = _old_entry(
        "full" if count_all else "light", w.vals, w.commit, w.height, w.block_id
    )
    if want_error and want_error[0] is not NotEnoughPowerError:
        with pytest.raises(CommitVerificationError) as e:
            validation.prepare_commit_light(
                CHAIN_ID, w.vals, w.block_id, w.height, w.commit, count_all
            )
        assert str(e.value) == want_error[1]
        return
    prepared = validation.prepare_commit_light(
        CHAIN_ID, w.vals, w.block_id, w.height, w.commit, count_all
    )
    assert _ids(prepared.entries) == _ids(want_entries)
    assert prepared.tallied == want_tallied
    assert prepared.msgs == [
        w.commit.vote_sign_bytes(CHAIN_ID, idx) for idx, _, _ in want_entries
    ]
    assert prepared.sigs == [cs.signature for _, _, cs in want_entries]


def test_an_empty_set_and_a_nil_commit_raise_as_before():
    w = _World(4, seed=1)
    empty = ValidatorSet([])
    for vals in (None, empty):
        with pytest.raises(CommitVerificationError, match="empty validator set"):
            validation.verify_commit_light(
                CHAIN_ID, vals, w.block_id, w.height, w.commit
            )
    with pytest.raises(CommitVerificationError, match="nil commit"):
        validation.verify_commit(CHAIN_ID, w.vals, w.block_id, w.height, None)
