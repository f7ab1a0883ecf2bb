"""The hashing front door: route Merkle work to the device tree kernel
or the serial host reference, bit-identically.

Every producer-side hashing call site (``types/block.py`` header/data/
commit hashes, ``types/validator.py`` valset hash, ``state/execution.py``
results hash, ``types/part_set.py`` part proofs) goes through
``tree_hash``/``tree_proofs`` instead of calling ``crypto/merkle.py``
directly — ``scripts/check_hash_callsites.py`` pins that.  The plane then
decides the path:

  * kill switch off (``COMETBFT_TPU_PROOFSERVE=0``) → the exact serial
    reference, restoring pre-plane behavior bit for bit;
  * trees of fewer than ``COMETBFT_TPU_MERKLE_MIN_BATCH`` leaves → the
    reference as well.  The default, ``DEFAULT_MIN_BATCH``, lies above the
    tree kernel's whole ladder, because on the chip the device tree was
    slower than the host tree at every size it takes (the readings are
    beside the constant): a node hashes on the host unless its operator
    lowers the variable.  The reference IS the correctness oracle, so
    there is nothing to gate;
  * everything else → ``ops/sha256_tree.tree_root``/``tree_proofs``,
    which itself supervises device→host degradation behind the
    ``merkle_device`` breaker.

The validator set's root has a front door of its own, ``valset_root``:
under the same gate, a host-tier set of ed25519 keys is one native call
from keys and powers to the root (leaves encoded in C++), and every other
set is ``tree_hash`` over ``simple_encode``.

jax-free at import: the sha256_tree import happens only past the size
gate, and that module imports jax lazily in turn.
"""

from __future__ import annotations

import ctypes
import os
from array import array

from cometbft_tpu.crypto import merkle

# No crossover inside the tree kernel's ladder (8 to 16,384 lanes) on a TPU
# v5e (by hand, PR 28, medians of five, device tree against
# ``merkle.hash_from_byte_slices``, ms): 32 leaves 7.27 / 0.06; 128 10.1 / 0.20;
# 512 13.3 / 0.80; 1,000 16.2 / 1.55; 4,096 31.7 / 6.29; 16,384 85.0 / 26.3.
# One leaf pass and one pass a level, each fetched to the host, cost about
# 7 ms before the first leaf and 4.8 us a leaf after it; the host tree costs
# 1.6 us a leaf.  So the default sends every tree to the host; ROADMAP.md D4
# decides what becomes of the device plane.
DEFAULT_MIN_BATCH = 32768


def enabled() -> bool:
    """Master kill switch for the whole Merkle/hash plane (the proof
    server consults it too): ``COMETBFT_TPU_PROOFSERVE=0`` restores the
    serial host path everywhere, bit for bit."""
    return os.environ.get("COMETBFT_TPU_PROOFSERVE", "1") != "0"


def min_batch() -> int:
    try:
        return int(
            os.environ.get("COMETBFT_TPU_MERKLE_MIN_BATCH", "")
            or DEFAULT_MIN_BATCH
        )
    except ValueError:
        return DEFAULT_MIN_BATCH


def _on_reference(leaves: int) -> bool:
    """The one gate of the plane: switched off, or a tree too small."""
    return not enabled() or leaves < min_batch()


def tier_for(leaves: int) -> str:
    """Where a tree of ``leaves`` goes: ``device`` or ``host`` (what a span
    around a hashing call site says; a device pass that degrades says so in
    its own ``merkle.tree`` span)."""
    if _on_reference(leaves):
        return "host"
    from cometbft_tpu.ops import sha256_tree

    return "device" if sha256_tree.device_active() else "host"


def tree_hash(items) -> bytes:
    """Merkle root of ``items`` — bit-identical to
    ``merkle.hash_from_byte_slices`` on every path."""
    items = list(items)
    if _on_reference(len(items)):
        return merkle.hash_from_byte_slices(items)
    from cometbft_tpu.ops import sha256_tree

    return sha256_tree.tree_root(items)


def valset_root(validators) -> "tuple[bytes, str]":
    """The Merkle root of SimpleValidator encodings in set order
    (reference: types/validator_set.go Hash) and the path that computed
    it, ``native`` or ``python``.  On the host tier a set whose keys are
    all ed25519 is ONE sidecar call: every leaf encoded and the tree
    reduced in C++, SHA-NI where the CPU has it, the GIL released for the
    call (ctypes does).  Every other set, the kill switch, a tree for the
    device tier and a process without the library take ``simple_encode``
    and ``tree_hash``, the oracle.  Computed from the set as it stands on
    every call: nothing is kept."""
    if enabled() and len(validators) < min_batch():
        root = _native_valset_root(validators)
        if root is not None:
            return root, "native"
    return tree_hash([v.simple_encode() for v in validators]), "python"


def _native_valset_root(validators) -> "bytes | None":
    """The sidecar's root, or None where it cannot serve the set: no
    library or no such symbol, a key of another type, a power outside
    int64."""
    from cometbft_tpu import native
    from cometbft_tpu.crypto.keys import Ed25519PubKey

    fn = getattr(native.lib(), "valset_root_ed25519", None)
    if fn is None:
        return None
    keys = [v.pub_key for v in validators]
    if any(type(k) is not Ed25519PubKey for k in keys):
        return None
    blob = b"".join([k.data for k in keys])
    if len(blob) != 32 * len(keys):
        return None
    try:
        powers = array("q", [v.voting_power for v in validators])
    except OverflowError:
        return None
    out = ctypes.create_string_buffer(32)
    if fn(blob, powers.buffer_info()[0], len(keys), out) != 0:
        return None
    return out.raw


def tree_proofs(items):
    """(root, [Proof]) for ``items`` — bit-identical to
    ``merkle.proofs_from_byte_slices`` on every path."""
    items = list(items)
    if _on_reference(len(items)):
        return merkle.proofs_from_byte_slices(items)
    from cometbft_tpu.ops import sha256_tree

    return sha256_tree.tree_proofs(items)
