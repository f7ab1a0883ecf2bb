"""The validator set's Merkle root in one native call
(``proofserve/plane.valset_root`` -> the sidecar's ``valset_root_ed25519``)
against the Python oracle, ``Validator.simple_encode`` +
``merkle.hash_from_byte_slices``: every size, the edge powers, both SHA-256
block functions; and the sets that take the Python path, with the
``valset.hash`` span's ``path`` saying which path served."""

import ctypes
import functools
import hashlib
import random
from array import array

import pytest

from cometbft_tpu import native
from cometbft_tpu.crypto import merkle
from cometbft_tpu.crypto.keys import Bls12381PubKey, Ed25519PubKey
from cometbft_tpu.crypto.secp256k1 import Secp256k1PubKey
from cometbft_tpu.libs import tracing
from cometbft_tpu.proofserve import plane
from cometbft_tpu.types.light import LightBlock
from cometbft_tpu.types.validator import (
    MAX_TOTAL_VOTING_POWER,
    Validator,
    ValidatorSet,
)

SIZES = (0, 1, 2, 3, 4, 5, 7, 8, 9, 100, 175, 1000, 1001, 10240)
BLOCK_FNS = {"scalar": 0, "sha_ni": 1}  # the sidecar's ``ni`` argument
POWERS = (0, 1, 127, 128, 2**31, MAX_TOTAL_VOTING_POWER, -1, -(2**63), 2**63 - 1)


@pytest.fixture(scope="module")
def nlib():
    lib = native.lib()
    if lib is None or not hasattr(lib, "valset_root_ed25519"):
        pytest.skip("native library unavailable")
    return lib


def _key(i: int, tag: bytes = b"vr") -> Ed25519PubKey:
    return Ed25519PubKey(hashlib.sha256(tag + b"-%d" % i).digest())


@functools.lru_cache(maxsize=None)
def _set(n: int) -> ValidatorSet:
    return ValidatorSet([Validator(_key(i), 10 + i % 7) for i in range(n)])


def oracle(validators) -> bytes:
    return merkle.hash_from_byte_slices([v.simple_encode() for v in validators])


def native_root(lib, validators, ni: int) -> bytes:
    """The sidecar's root with the block function named."""
    blob = b"".join(v.pub_key.bytes() for v in validators)
    powers = array("q", [v.voting_power for v in validators])
    out = ctypes.create_string_buffer(32)
    rc = lib.valset_root_ed25519_ni(
        blob, powers.buffer_info()[0], len(validators), out, ni
    )
    if rc == -2:
        pytest.skip("this CPU has no SHA extensions")
    assert rc == 0
    return out.raw


@pytest.mark.parametrize("block", sorted(BLOCK_FNS))
def test_sha256_is_hashlib_at_every_length(nlib, block):
    rng = random.Random(7)
    for n in range(201):
        msg = rng.randbytes(n)
        out = ctypes.create_string_buffer(32)
        rc = nlib.sha256_ni(msg, n, out, BLOCK_FNS[block])
        if rc == -2:
            pytest.skip("this CPU has no SHA extensions")
        assert rc == 0 and out.raw == hashlib.sha256(msg).digest(), n


@pytest.mark.parametrize("block", sorted(BLOCK_FNS))
@pytest.mark.parametrize("n", SIZES)
def test_native_root_is_the_oracle_at_every_size(nlib, n, block):
    vals = _set(n).validators
    assert native_root(nlib, vals, BLOCK_FNS[block]) == oracle(vals)


@pytest.mark.parametrize("n", SIZES)
def test_the_set_hashes_natively_and_as_the_oracle(nlib, n):
    vs = _set(n)
    assert vs.hash_with_path() == (oracle(vs.validators), "native")
    assert vs.hash() == oracle(vs.validators)


@pytest.mark.parametrize("block", sorted(BLOCK_FNS))
@pytest.mark.parametrize("power", POWERS)
def test_edge_powers(nlib, power, block):
    """Zero is left out of the leaf, a negative power is the 10-byte two's
    complement (``pe.t_varint``), every other one its uvarint."""
    vals = [Validator(_key(i, b"pw"), power) for i in range(5)]
    if power < 0:
        assert len(vals[0].simple_encode()) == 4 + 32 + 1 + 10
    if power == 0:
        assert len(vals[0].simple_encode()) == 4 + 32
    assert native_root(nlib, vals, BLOCK_FNS[block]) == oracle(vals)


@pytest.mark.parametrize("block", sorted(BLOCK_FNS))
def test_random_powers_across_int64(nlib, block):
    rng = random.Random(11)
    vals = [
        Validator(_key(i, b"rp"), rng.randrange(-(2**63), 2**63) >> rng.randrange(64))
        for i in range(333)
    ]
    assert native_root(nlib, vals, BLOCK_FNS[block]) == oracle(vals)


def test_a_set_after_update_with_change_set(nlib):
    vs = ValidatorSet([Validator(_key(i, b"up"), 10) for i in range(100)])
    before = vs.hash()
    vs.update_with_change_set([
        Validator(_key(3, b"up"), 0),  # removed
        Validator(_key(5, b"up"), 77),  # re-weighted
        Validator(_key(0, b"joiner"), 5),  # joins
    ])
    assert len(vs) == 100
    root, path = vs.hash_with_path()
    assert (root, path) == (oracle(vs.validators), "native") and root != before


def test_nothing_is_kept_between_calls(nlib):
    """The root is computed from the set as it stands on every call."""
    vs = ValidatorSet([Validator(_key(i, b"kp"), 10) for i in range(50)])
    first = vs.hash()
    vs.validators[7].voting_power = 11  # in place: no list assigned
    assert vs.hash() == oracle(vs.validators) != first
    vs.validators[7].voting_power = 10
    assert vs.hash() == first


def _other_keys(kind: str) -> list:
    secp = [Secp256k1PubKey(b"\x02" + _key(i, b"s").data) for i in range(6)]
    bls = [Bls12381PubKey(_key(i, b"b").data * 3) for i in range(6)]
    ed = [_key(i, b"e") for i in range(6)]
    return {"secp256k1": secp, "bls12_381": bls, "mixed": ed[:5] + secp[:1]}[kind]


@pytest.mark.parametrize("kind", ["secp256k1", "bls12_381", "mixed"])
def test_sets_of_other_keys_take_the_python_path(nlib, kind):
    vs = ValidatorSet([Validator(k, 10) for k in _other_keys(kind)])
    assert vs.hash_with_path() == (oracle(vs.validators), "python")


def test_a_power_outside_int64_takes_the_python_path(nlib):
    vals = [Validator(_key(i, b"big"), 2**63) for i in range(3)]
    assert plane.valset_root(vals) == (oracle(vals), "python")


def test_no_native_takes_the_python_path(nlib, monkeypatch):
    vs = _set(175)
    monkeypatch.setenv("COMETBFT_TPU_NO_NATIVE", "1")
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_lib", None)
    assert vs.hash_with_path() == (oracle(vs.validators), "python")


def test_a_library_without_the_symbol_takes_the_python_path(nlib, monkeypatch):
    vs = _set(9)
    monkeypatch.setattr(native, "lib", lambda: object())
    assert vs.hash_with_path() == (oracle(vs.validators), "python")


def test_the_kill_switch_takes_the_python_path(nlib, monkeypatch):
    vs = _set(100)
    monkeypatch.setenv("COMETBFT_TPU_PROOFSERVE", "0")
    assert vs.hash_with_path() == (oracle(vs.validators), "python")


def test_a_tree_for_the_device_tier_keeps_the_python_leaves(nlib, monkeypatch):
    """At or above ``min_batch()`` the leaves go to ``tree_hash`` (and so to
    the device tier); below it the native root serves."""
    monkeypatch.setenv("COMETBFT_TPU_MERKLE_MIN_BATCH", "8")
    trees = []

    def tree_hash(items):
        trees.append(list(items))
        return merkle.hash_from_byte_slices(trees[-1])

    monkeypatch.setattr(plane, "tree_hash", tree_hash)
    big, small = _set(9), _set(7)
    assert big.hash_with_path() == (oracle(big.validators), "python")
    assert trees == [[v.simple_encode() for v in big.validators]]
    assert small.hash_with_path() == (oracle(small.validators), "native")
    assert len(trees) == 1


@pytest.mark.parametrize("path, env", [
    ("native", {}),
    ("python", {"COMETBFT_TPU_PROOFSERVE": "0"}),
])
def test_the_span_says_which_path_served(nlib, monkeypatch, path, env):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    vs = _set(100)
    tracing.reset_tracer()
    assert LightBlock(None, vs)._validators_hash() == oracle(vs.validators)
    spans = [s for s in tracing.get_tracer().tail(8) if s["stage"] == "valset.hash"]
    assert [s.get("attrs") for s in spans] == [
        {"leaves": 100, "tier": "host", "path": path}
    ]
