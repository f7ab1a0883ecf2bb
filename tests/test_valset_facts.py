"""The record a validator set keeps of itself (ISSUE 31,
``ValidatorSet.facts``): built once at first use, never at a miss, dropped
wherever membership, order, keys or powers change, by the assignment itself."""

import base64
import hashlib

import pytest

from cometbft_tpu.crypto.keys import Bls12381PrivKey, Ed25519PubKey
from cometbft_tpu.types import codec
from cometbft_tpu.types import validator as validator_mod
from cometbft_tpu.types.validator import Validator, ValidatorSet


def _key(i, tag=b"facts"):
    return Ed25519PubKey(hashlib.sha256(b"%s-%d" % (tag, i)).digest())


def _set(n=12):
    return ValidatorSet([Validator(_key(i), 3 + i) for i in range(n)])


@pytest.fixture
def builds(monkeypatch):
    """How often the record was built, counted at the one builder."""
    calls = []
    real = validator_mod._build_facts

    def counting(validators):
        calls.append(len(validators))
        return real(validators)

    monkeypatch.setattr(validator_mod, "_build_facts", counting)
    return calls


def test_not_built_by_the_constructor_and_once_over_hits_and_misses(builds):
    vals = _set()
    assert not vals.has_facts() and builds == []
    for _ in range(3):
        for i, v in enumerate(vals.validators):
            assert vals.get_by_address(v.address) == (i, v)
        for i in range(200):
            assert vals.get_by_address(_key(i, b"stranger").address()) is None
            assert not vals.has_address(bytes(20))
    assert builds == [12]
    assert vals.has_facts()


def test_the_record_holds_the_sets_order_power_and_key_type():
    vals = _set(9)
    facts = vals.facts()
    assert facts.addresses == [v.address for v in vals.validators]
    assert facts.index == {v.address: i for i, v in enumerate(vals.validators)}
    powers = [v.voting_power for v in vals.validators]
    assert facts.cum_power == [sum(powers[: i + 1]) for i in range(9)]
    assert facts.cum_power[-1] == vals.total_voting_power()
    assert facts.batch_capable is True
    assert ValidatorSet([]).facts().batch_capable is False


@pytest.mark.parametrize(
    "keys,capable",
    [
        ("ed25519", True),
        ("bls", True),
        ("mixed", False),
    ],
)
def test_batch_capable_is_one_key_type_that_a_batch_verifier_takes(keys, capable):
    ed = [Validator(_key(i), 5) for i in range(3)]
    bls = [
        Validator(Bls12381PrivKey.from_secret(b"facts-%d" % i).pub_key(), 5)
        for i in range(2)
    ]
    members = {"ed25519": ed, "bls": bls, "mixed": ed + bls}[keys]
    assert ValidatorSet(members).facts().batch_capable is capable


def test_update_with_change_set_drops_the_record(builds):
    vals = _set()
    gone, stays = vals.validators[0], vals.validators[1]
    before = vals.facts()
    joiner = Validator(_key(99), 40)
    vals.update_with_change_set(
        [Validator(gone.pub_key, 0), Validator(stays.pub_key, 77), joiner]
    )
    assert vals.get_by_address(gone.address) is None
    i, v = vals.get_by_address(joiner.address)
    assert vals.validators[i] is v and v.voting_power == 40
    after = vals.facts()
    assert after is not before
    assert after.cum_power[-1] == vals.total_voting_power()
    assert after.addresses == [v.address for v in vals.validators]
    assert after.cum_power == [
        sum(v.voting_power for v in vals.validators[: k + 1])
        for k in range(len(vals))
    ]


def test_assigning_validators_drops_the_record():
    vals = _set()
    other = _set(5)
    vals.facts()
    vals.validators = [v.copy() for v in other.validators]
    assert not vals.has_facts()
    assert vals.get_by_address(other.validators[4].address)[0] == 4
    assert len(vals.facts().cum_power) == 5


def test_a_copy_never_sees_the_originals_later_change(builds):
    vals = _set()
    vals.facts()
    twin = vals.copy()
    assert twin.facts() is vals.facts()  # same members, order, keys, powers
    assert builds == [12]
    first = vals.validators[0]
    vals.update_with_change_set([Validator(first.pub_key, 0)])
    assert vals.get_by_address(first.address) is None
    i, v = twin.get_by_address(first.address)
    assert (i, v) == (0, twin.validators[0]) and v is not first
    assert len(twin.facts().cum_power) == 12 and len(vals.facts().cum_power) == 11
    # and the other way round
    last = twin.validators[-1]
    twin.update_with_change_set([Validator(last.pub_key, last.voting_power + 1)])
    assert twin.facts().cum_power[-1] == vals.facts().cum_power[-1] + first.voting_power + 1


def test_proposer_priorities_keep_the_record(builds):
    vals = _set()
    facts = vals.facts()
    vals.increment_proposer_priority(5)
    rotated = vals.copy_increment_proposer_priority(3)
    assert vals.facts() is facts and rotated.facts() is facts
    assert builds == [12]


def _rpc_items(vals):
    return [
        {
            "pub_key": {
                "type": "tendermint/PubKeyEd25519",
                "value": base64.b64encode(v.pub_key.bytes()).decode(),
            },
            "voting_power": str(v.voting_power),
            "proposer_priority": str(v.proposer_priority),
        }
        for v in vals.validators
    ]


def _decoded(vals):
    return codec.decode_validator_set(codec.encode_validator_set(vals))


def _state_round_trip(vals):
    from cometbft_tpu.state.state import State

    return State._vals_from_json(State._vals_to_json(vals))


def _from_provider(vals):
    from cometbft_tpu.light.provider import _parse_validators

    return _parse_validators(_rpc_items(vals))


@pytest.mark.parametrize(
    "rebuild", [_decoded, _state_round_trip, _from_provider, ValidatorSet.copy]
)
def test_a_set_filled_without_the_constructor_answers_at_once(rebuild):
    vals = _set()
    vals.facts()
    made = rebuild(vals)
    for i, v in enumerate(vals.validators):
        j, w = made.get_by_address(v.address)
        assert j == i and w is made.validators[i] and w is not v
    assert made.get_by_address(bytes(20)) is None
    assert made.facts().cum_power == vals.facts().cum_power
    # and a change after that is seen
    made.update_with_change_set([Validator(vals.validators[3].pub_key, 0)])
    assert made.get_by_address(vals.validators[3].address) is None
    assert vals.get_by_address(vals.validators[3].address) is not None
