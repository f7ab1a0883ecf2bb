"""The light client's generator is a pure function of the seed; its header
and set hashes and sign-bytes are the program's; every request has the verdict
its class says, and a broken-link request breaks the link it names alone; each
control (the trusting pass left out, the link checks left out) is not correct."""

import os

import pytest

from benchmarks import chain as chainlib
from benchmarks import light_control, light_ref, lightchain, manifest


def files(n: int, requests: int = 70):
    config = dict(
        manifest._json(os.path.join(
            manifest.HERE, "configs", "light1k-ed25519.json")),
        validators=n, validator_universe=2 * n, churn_skipping=max(1, n // 10),
        too_far_kept=n * 3 // 10, jump_heights=[2, 50])
    traffic = dict(
        manifest._json(os.path.join(
            manifest.HERE, "traffic", "light-skipping.json")),
        requests=requests)
    return config, traffic


@pytest.fixture(scope="module")
def pools():
    a, b = chainlib.SignPool(1), chainlib.SignPool(3)
    yield a, b
    a.close()
    b.close()


def test_same_seed_same_bytes_whatever_the_workers(pools):
    config, traffic = files(8, 40)
    one = lightchain.build(config, traffic, 2**31 + 7, pools[0])
    three = lightchain.build(config, traffic, 2**31 + 7, pools[1])
    other = lightchain.build(config, traffic, 2**31 + 8, pools[1])
    assert lightchain.fingerprint(one) == lightchain.fingerprint(three)
    assert lightchain.fingerprint(one) != lightchain.fingerprint(other)
    lightchain.spot_check(one)


def test_the_step_pattern_is_the_traffic_files():
    _, traffic = files(8)
    kinds = [lightchain.kind_of(traffic, k) for k in range(64)]
    assert kinds[0] == "too_far" and kinds[32] == "too_far"
    assert {kinds[16], kinds[48]} == {"tampered_in", "tampered_out"}
    assert [k for k in range(64) if kinds[k] == "adjacent"] == list(range(6, 64, 8))
    assert kinds[40] == "broken_header_hash" and kinds.count("skip") == 64 - 8 - 4 - 1
    assert [lightchain.kind_of(traffic, k) for k in (104, 168, 232)] == [
        "broken_validators_hash", "broken_next_validators_hash", "broken_header_hash"]


@pytest.mark.parametrize("n", [8, 64])
def test_hashes_and_sign_bytes_are_the_programs(pools, n):
    from benchmarks.entries import light_verify

    config, traffic = files(n, 12)
    chain = type("Chain", (), {})()
    chain.seed, chain.chain_id = 77, config["chain_id"]
    chain.light = light = lightchain.build(config, traffic, 77, pools[1])
    state = light_verify.State(chain)
    seen_adjacent = False
    for req in light.warm + light.pool:
        trusted, new, _ = state.requests[req.key]
        b = light.blocks[req.new]
        assert new.signed_header.header.hash() == light_ref.header_hash(b.header)
        assert new.validator_set.hash() == light_ref.validators_hash(
            light.light_block(req.new).validators)
        # the program sees the links as the generator left them
        assert (new.signed_header.header.hash() == b.commit.block_id.hash) == \
            (req.link != "header_hash")
        assert (new.validator_set.hash() == b.header.validators_hash) == \
            (req.link != "validators_hash")
        assert (new.validate_basic(light.chain_id) is None) == \
            (req.link not in ("header_hash", "validators_hash"))
        for i in (0, n - 1):
            assert new.signed_header.commit.vote_sign_bytes(light.chain_id, i) == \
                light_ref.vote_sign_bytes(light.chain_id, b.commit, i)
        if req.kind == "adjacent":
            seen_adjacent = True
            t = light.blocks[req.trusted].header
            assert t.next_validators_hash == b.header.validators_hash != t.validators_hash
            assert b.header.height == t.height + 1
    assert seen_adjacent


def test_every_request_has_the_verdict_its_class_says(pools):
    from benchmarks.entries import light_verify

    config, traffic = files(8, 70)
    chain = type("Chain", (), {})()
    chain.seed, chain.chain_id = 5, config["chain_id"]
    chain.light = light = lightchain.build(config, traffic, 5, pools[1])
    kinds = {}
    for req in light.warm + light.pool:
        items = light_verify.reference_items(chain, req)
        bits = [light_ref._ed.verify_zip215(*it) for it in items]
        assert light_verify.reference_verdict(chain, req, bits) == req.expected, req
        assert len(items) == req.signatures or req.kind == "tampered"
        kinds.setdefault(req.kind, set()).add(req.expected[0])
        if req.tamper:
            index, cls, where = req.tamper
            picked = lightchain.trusting_prefix(
                light.blocks[req.trusted].ids, light.blocks[req.new].ids,
                light.power, light.trust)
            assert (index in picked) == (where == "in")
            assert index < lightchain.light_prefix(8)
    assert kinds == {"skip": {"accepted"}, "adjacent": {"accepted"},
                     "tampered": {"invalid_signature"}, "too_far": {"cant_be_trusted"},
                     "broken_link": {"invalid_header"}}
    # a broken-link request asks for no signature, and the reference names the link
    broken = [r for r in light.warm + light.pool if r.kind == "broken_link"]
    assert [r.link for r in broken] == list(lightchain.LINKS) + ["header_hash"]
    texts = {
        "header_hash": "commit signs a different header",
        "validators_hash": "validator set does not match validators_hash",
        "next_validators_hash": "validators_hash is not the trusted next_validators_hash",
    }
    for req in broken:
        got = light_ref.verify(
            light.chain_id, light.light_block(req.trusted), light.light_block(req.new),
            light.trusting_period_s, req.now_s, *light.trust)
        assert got == ("invalid_header", texts[req.link]) and req.signatures == 0
    classes = [r.tamper[1:] for r in light.warm if r.tamper]
    assert classes == [("noncanonical_s", "in"), ("flip_s", "out"),
                       ("wrong_msg", "in"), ("flip_r", "out")]
    # the client moves only on what it accepts
    newest = "root"
    for req in light.warm + light.pool:
        assert req.trusted == newest
        if req.expected == ("accepted",):
            newest = req.new


@pytest.mark.parametrize("control, requests, caught", [
    ("no_trusting_pass", 40, 2),  # the too-far requests 0 and 32
    ("no_link_checks", 170, 3),  # the broken links of 40, 104 and 168, one of each
])
@pytest.mark.parametrize("seed", [1, 2**31 + 3, 99])
def test_the_control_comes_out_not_correct(pools, seed, control, requests, caught):
    cell = manifest.Cell(manifest.load(), light_control.WORKLOAD)
    cell.config, cell.traffic = files(8, requests)
    verdict = light_control.run_control(cell, seed, requests, control, pools[1])
    assert verdict["correct"] is False
    assert verdict["compared"]["window_verdicts_unexpected"]["value"] == caught
    assert verdict["compared"]["sample_verdicts_wrong"]["value"] == caught
    assert verdict["compared"]["reference_against_generator"]["value"] == 0
    assert {tuple(v) for _, v in verdict["first_unexpected"]} == {("accepted",)}


def test_the_reference_in_the_programs_place_is_correct(pools):
    cell = manifest.Cell(manifest.load(), light_control.WORKLOAD)
    cell.config, cell.traffic = files(8, 40)
    verdict = light_control.run_control(cell, 5, 40, "none", pools[1])
    assert verdict["correct"] is True
