"""Batched Merkle/hash plane: differential + service tests.

The plane (``cometbft_tpu/proofserve/`` + ``ops/sha256_tree.py``,
docs/proof-serving.md) may only ever change WHERE a tree is hashed,
never WHAT it hashes to — every test here pins some face of that
contract against the serial reference ``crypto/merkle.py`` (the
reference model's RFC 6962 tree, itself pinned by test_types.py
golden vectors):

  * host oracle (``host_levels``/``proofs_from_levels``) ≡ merkle on
    empty/single/odd counts, SHA block-boundary leaf sizes, duplicates;
  * device kernel (``device_levels``) ≡ host oracle, bit for bit;
  * supervised degradation: a device fault costs a breaker failure and
    a host recompute, never a wrong (or missing) root;
  * plane gating: kill switch and min-batch restore the serial path
    bit-for-bit;
  * proof server: coalescing, LRU cache, backpressure shed, and the
    ``prove_tx`` serial fallback.
"""

import hashlib

import pytest

from cometbft_tpu.crypto import backend_health, merkle
from cometbft_tpu.ops import sha256_tree
from cometbft_tpu.proofserve import plane
from cometbft_tpu.proofserve import service as psvc
from cometbft_tpu.proofserve import stats as pstats
from cometbft_tpu.proofserve.service import ProofServer, QueueFullError

# SHA-256 block-edge leaf sizes: around the one-block padding limit
# (54 is the largest leaf whose 0x00-prefixed padded message is one
# block), the 64-byte block size itself, and the two-block limit.
_EDGE_LENS = (0, 1, 31, 32, 54, 55, 56, 63, 64, 65, 118, 119, 120)


def _leaves(n: int, lens=_EDGE_LENS) -> "list[bytes]":
    out = []
    for i in range(n):
        ln = lens[i % len(lens)]
        out.append((hashlib.sha256(b"leaf-%d" % i).digest() * 8)[:ln])
    return out


@pytest.fixture(autouse=True)
def _clean_plane(monkeypatch):
    """Every test starts with a pristine plane: no runner, closed
    singleton server, zeroed counters, healthy breaker."""
    pstats.reset()
    backend_health.reset()
    yield
    psvc.reset_server()
    sha256_tree.clear_tree_runner()
    pstats.reset()
    backend_health.reset()


# -- host oracle differential -------------------------------------------------


def test_host_levels_matches_merkle_roots_and_proofs():
    for n in (1, 2, 3, 4, 5, 7, 8, 9, 13, 16, 17, 33):
        items = _leaves(n)
        levels = sha256_tree.host_levels(items)
        root = levels[-1][0]
        assert root == merkle.hash_from_byte_slices(items), n
        ref_root, ref_proofs = merkle.proofs_from_byte_slices(items)
        proofs = sha256_tree.proofs_from_levels(levels)
        assert root == ref_root
        for p, rp in zip(proofs, ref_proofs):
            assert (p.total, p.index, p.leaf_hash, p.aunts) == (
                rp.total,
                rp.index,
                rp.leaf_hash,
                rp.aunts,
            ), (n, p.index)
            assert p.verify(root, items[p.index])


def test_empty_and_single_leaf():
    assert plane.tree_hash([]) == merkle.hash_from_byte_slices([])
    assert plane.tree_hash([]) == sha256_tree.EMPTY_HASH
    root, proofs = plane.tree_proofs([])
    assert root == sha256_tree.EMPTY_HASH and proofs == []
    one = [b"only"]
    assert sha256_tree.host_levels(one)[-1][0] == (
        merkle.hash_from_byte_slices(one)
    )


def test_host_oracle_duplicate_leaves():
    # duplicate leaves must keep distinct proofs (index disambiguates)
    items = [b"same"] * 7 + [b""] * 3
    levels = sha256_tree.host_levels(items)
    root = levels[-1][0]
    assert root == merkle.hash_from_byte_slices(items)
    for p in sha256_tree.proofs_from_levels(levels):
        assert p.verify(root, items[p.index])


# -- device kernel differential ----------------------------------------------


@pytest.mark.warmcache("sha256leaf-8x1", "sha256layer-8")
def test_device_kernel_differential_one_block():
    # n <= 8 and leaf <= 54B pin the (8 lanes, 1 block) bucket
    lens = (0, 1, 31, 32, 53, 54)
    for n in (1, 2, 3, 5, 7, 8):
        items = _leaves(n, lens)
        assert sha256_tree.device_levels(items) == (
            sha256_tree.host_levels(items)
        ), n


@pytest.mark.warmcache(
    "sha256leaf-8x1", "sha256leaf-8x2", "sha256layer-8"
)
def test_device_kernel_differential_multiblock():
    # 55..118-byte leaves need two SHA blocks: the scan's carry masking
    # is what this pins (shorter lanes must ignore the extra block)
    for n in (1, 4, 6, 8):
        items = _leaves(n, (55, 56, 63, 64, 65, 118))
        assert sha256_tree.device_levels(items) == (
            sha256_tree.host_levels(items)
        ), n
        # mixed 1-block + 2-block lanes in one dispatch
        mixed = _leaves(n, (0, 54, 55, 118))
        assert sha256_tree.device_levels(mixed) == (
            sha256_tree.host_levels(mixed)
        ), n


def test_oversize_leaf_set_rejected():
    assert sha256_tree._bucket_shape([b"x"] * 9) == (16, 1)
    big = b"x" * (sha256_tree._MAX_BLOCKS * 64)
    assert sha256_tree._bucket_shape([big]) is None


# -- supervised degradation ---------------------------------------------------


def test_runner_seam_counts_as_device():
    sha256_tree.set_tree_runner(sha256_tree.host_tree_runner)
    items = _leaves(40)
    levels = sha256_tree.tree_levels(items)
    assert levels[-1][0] == merkle.hash_from_byte_slices(items)
    snap = pstats.snapshot()
    assert snap["trees_device"] == 1 and snap["trees_host"] == 0


def test_device_fault_degrades_to_host_never_wrong():
    calls = []

    def bad_runner(items):
        calls.append(len(items))
        raise RuntimeError("injected device fault")

    sha256_tree.set_tree_runner(bad_runner)
    items = _leaves(40)
    levels = sha256_tree.tree_levels(items)
    # the fault cost a fallback, not a root
    assert levels[-1][0] == merkle.hash_from_byte_slices(items)
    assert calls == [40]
    snap = pstats.snapshot()
    assert snap["device_fallbacks"] == 1
    assert snap["trees_host"] == 1 and snap["trees_device"] == 0
    health = backend_health.registry().snapshot()
    assert health["breakers"]["merkle_device"]["failures_total"] >= 1


def test_open_breaker_skips_device_path():
    calls = []

    def bad_runner(items):
        calls.append(len(items))
        raise RuntimeError("still dead")

    sha256_tree.set_tree_runner(bad_runner)
    breaker = backend_health.registry().breaker(sha256_tree.BREAKER)
    items = _leaves(33)
    for _ in range(32):
        assert sha256_tree.tree_levels(items)[-1][0] == (
            merkle.hash_from_byte_slices(items)
        )
        if not breaker.allow():
            break
    assert not breaker.allow(), "breaker never opened"
    before = len(calls)
    assert sha256_tree.tree_levels(items)[-1][0] == (
        merkle.hash_from_byte_slices(items)
    )
    assert len(calls) == before, "open breaker must not touch the device"


# -- plane gating -------------------------------------------------------------


def test_kill_switch_restores_serial_path(monkeypatch):
    sha256_tree.set_tree_runner(sha256_tree.host_tree_runner)
    monkeypatch.setenv("COMETBFT_TPU_MERKLE_MIN_BATCH", "4")
    items = _leaves(40)
    monkeypatch.setenv("COMETBFT_TPU_PROOFSERVE", "0")
    assert not plane.enabled()
    root = plane.tree_hash(items)
    proot, proofs = plane.tree_proofs(items)
    assert root == merkle.hash_from_byte_slices(items)
    assert (proot, [p.aunts for p in proofs]) == (
        merkle.proofs_from_byte_slices(items)[0],
        [p.aunts for p in merkle.proofs_from_byte_slices(items)[1]],
    )
    assert pstats.snapshot()["trees_device"] == 0, "kill switch leaked"
    monkeypatch.setenv("COMETBFT_TPU_PROOFSERVE", "1")
    assert plane.tree_hash(items) == root, "paths diverged"
    assert pstats.snapshot()["trees_device"] == 1


def test_min_batch_gate(monkeypatch):
    sha256_tree.set_tree_runner(sha256_tree.host_tree_runner)
    monkeypatch.setenv("COMETBFT_TPU_MERKLE_MIN_BATCH", "16")
    small, big = _leaves(15), _leaves(16)
    assert plane.tree_hash(small) == merkle.hash_from_byte_slices(small)
    assert pstats.snapshot()["trees_device"] == 0
    assert plane.tree_hash(big) == merkle.hash_from_byte_slices(big)
    assert pstats.snapshot()["trees_device"] == 1


# -- proof server -------------------------------------------------------------


def _chain(n_heights=4, txs=40):
    return {
        h: [b"tx-%d-%d" % (h, i) for i in range(txs)]
        for h in range(1, n_heights + 1)
    }


def test_server_coalesces_same_height_queries(monkeypatch):
    monkeypatch.setenv("COMETBFT_TPU_MERKLE_MIN_BATCH", "8")
    chain = _chain()
    server = ProofServer(chain.get, lambda h: None, lambda h: None)
    try:
        server.pause()
        futs = [server.submit("tx", 2) for _ in range(3)]
        server.resume()
        results = [f.result(timeout=10) for f in futs]
        ref = merkle.proofs_from_byte_slices(chain[2])
        for root, proofs in results:
            assert root == ref[0]
            assert [p.aunts for p in proofs] == [
                p.aunts for p in ref[1]
            ]
        snap = pstats.snapshot()
        assert snap["tree_builds_total"] == 1, "queries not coalesced"
        assert snap["queries"]["tx"] == 3
    finally:
        server.close()


def test_bursts_over_many_heights_equal_the_serial_proofs():
    """Queries over many heights in paused bursts, on the device tree
    seam: one tree build a height however many queries ask for it, none
    shed, and every height's root and proofs (total, index, leaf hash,
    aunts) equal to one serial ``proofs_from_byte_slices`` build."""
    sha256_tree.set_tree_runner(sha256_tree.host_tree_runner)
    chain = _chain(n_heights=8, txs=16)
    heights = [1 + i % 8 for i in range(96)]
    server = ProofServer(
        chain.get, lambda h: None, lambda h: None, queue_cap=32
    )
    served = {}
    try:
        for start in range(0, len(heights), 32):
            burst = heights[start : start + 32]
            server.pause()
            futs = [server.submit("tx", h) for h in burst]
            server.resume()
            for h, f in zip(burst, futs):
                served[h] = f.result(timeout=10)
    finally:
        server.close()
    snap = pstats.snapshot()
    assert snap["shed_total"] == 0
    assert snap["tree_builds_total"] == len(served) == 8
    for h, (root, proofs) in served.items():
        ref_root, ref_proofs = merkle.proofs_from_byte_slices(chain[h])
        assert root == ref_root, h
        assert [(p.total, p.index, p.leaf_hash, p.aunts) for p in proofs] == [
            (p.total, p.index, p.leaf_hash, p.aunts) for p in ref_proofs
        ], h


def test_server_cache_hit_and_miss_accounting():
    chain = _chain()
    server = ProofServer(chain.get, lambda h: None, lambda h: None)
    try:
        first = server.submit("tx", 1).result(timeout=10)
        assert server.cached("tx", 1)
        fut = server.submit("tx", 1)
        assert fut.done(), "LRU hit must resolve without queueing"
        assert fut.result(timeout=0) == first
        snap = pstats.snapshot()
        assert snap["cache_hits"]["tx"] == 1
        assert snap["tree_builds_total"] == 1
        # a missing height is NOT cached (the block may appear later)
        assert server.submit("tx", 999).result(timeout=10) is None
        assert not server.cached("tx", 999)
    finally:
        server.close()


def test_server_sheds_at_capacity():
    chain = _chain()
    server = ProofServer(
        chain.get, lambda h: None, lambda h: None, queue_cap=2
    )
    try:
        server.pause()
        f1 = server.submit("tx", 1)
        f2 = server.submit("tx", 2)
        with pytest.raises(QueueFullError):
            server.submit("tx", 3)
        assert pstats.snapshot()["shed"]["tx"] == 1
        server.resume()
        assert f1.result(timeout=10) is not None
        assert f2.result(timeout=10) is not None
    finally:
        server.close()


def test_header_and_valset_kinds_use_their_hashers():
    hdr = {2: b"\x11" * 32}
    vs = {2: b"\x22" * 32}
    server = ProofServer(lambda h: None, hdr.get, vs.get)
    try:
        assert server.submit("header", 2).result(timeout=10) == hdr[2]
        assert server.submit("valset", 2).result(timeout=10) == vs[2]
        assert server.submit("header", 3).result(timeout=10) is None
    finally:
        server.close()


def test_prove_tx_coalesced_and_serial_paths(monkeypatch):
    chain = _chain()
    ref_root, ref_proofs = merkle.proofs_from_byte_slices(chain[3])

    # no server configured: serial path serves the identical proof
    assert not psvc.server_active()
    got = psvc.prove_tx(chain.get, 3, 5)
    assert got is not None
    root, proof = got
    assert root == ref_root and proof.aunts == ref_proofs[5].aunts
    assert proof.verify(root, chain[3][5])

    # through the coalescer: byte-identical response
    psvc.configure(chain.get, lambda h: None, lambda h: None)
    assert psvc.server_active()
    root2, proof2 = psvc.prove_tx(chain.get, 3, 5)
    assert (root2, proof2.aunts) == (root, proof.aunts)

    # missing height / out-of-range index
    assert psvc.prove_tx(chain.get, 99, 0) is None
    assert psvc.prove_tx(chain.get, 3, len(chain[3])) is None

    # kill switch: server stays configured but is bypassed
    monkeypatch.setenv("COMETBFT_TPU_PROOFSERVE", "0")
    assert not psvc.server_active()
    root3, proof3 = psvc.prove_tx(chain.get, 3, 5)
    assert (root3, proof3.aunts) == (root, proof.aunts)


def test_queue_drains_on_reset():
    chain = _chain()
    psvc.configure(chain.get, lambda h: None, lambda h: None)
    fut = psvc.get_server().submit("tx", 1)
    psvc.reset_server()
    assert psvc.get_server() is None
    assert fut.result(timeout=10) is not None, "close() must drain"
    assert pstats.queue_depth() == 0


# -- repo discipline ----------------------------------------------------------


def test_hash_callsites_lint_clean():
    import pathlib
    import sys

    repo = pathlib.Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(repo / "scripts"))
    try:
        import check_hash_callsites as lint

        assert lint.scan(repo) == []
    finally:
        sys.path.remove(str(repo / "scripts"))


def test_type_layer_stays_jax_free():
    """The plane's producer-side routing (types/, state/) must not pull
    jax into a process that never activates the device path — node
    subprocesses on the serial path boot without it."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import cometbft_tpu.types.block\n"
        "import cometbft_tpu.types.validator\n"
        "import cometbft_tpu.types.part_set\n"
        "import cometbft_tpu.types.evidence\n"
        "import cometbft_tpu.state.execution\n"
        "import cometbft_tpu.proofserve\n"
        "from cometbft_tpu.proofserve import plane\n"
        "plane.tree_hash([b'a', b'b'])\n"
        "assert 'jax' not in sys.modules, 'jax leaked into import'\n"
    )
    subprocess.run(
        [sys.executable, "-c", code], check=True, timeout=120
    )
