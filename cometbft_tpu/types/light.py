"""SignedHeader and LightBlock (reference: types/light.go).

A ``SignedHeader`` is a header plus the commit that signed it; a
``LightBlock`` adds the validator set that produced the commit.  These are
the units the light client verifies and the payload of light-client-attack
evidence.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from cometbft_tpu.types.block import Commit, Header
from cometbft_tpu.types.validator import ValidatorSet


@dataclass
class SignedHeader:
    """Reference: types/light.go SignedHeader."""

    header: Header
    commit: Commit

    def hash(self) -> bytes:
        return self.header.hash()

    @property
    def height(self) -> int:
        return self.header.height

    def validate_basic(self, chain_id: str) -> Optional[str]:
        if self.header is None:
            return "missing header"
        if self.commit is None:
            return "missing commit"
        err = self.header.validate_basic()
        if err:
            return err
        err = self.commit.validate_basic()
        if err:
            return err
        if self.header.chain_id != chain_id:
            return f"header chain id {self.header.chain_id!r} != {chain_id!r}"
        if self.commit.height != self.header.height:
            return (
                f"commit height {self.commit.height} != header height "
                f"{self.header.height}"
            )
        if self.commit.block_id.hash != self.header.hash():
            return "commit signs a different header"
        return None


@dataclass
class LightBlock:
    """Reference: types/light.go LightBlock."""

    signed_header: SignedHeader
    validator_set: ValidatorSet

    @property
    def height(self) -> int:
        return self.signed_header.height

    def hash(self) -> bytes:
        return self.signed_header.hash()

    def validate_basic(self, chain_id: str) -> Optional[str]:
        if self.signed_header is None:
            return "missing signed header"
        if self.validator_set is None:
            return "missing validator set"
        err = self.signed_header.validate_basic(chain_id)
        if err:
            return err
        if self.signed_header.header.validators_hash != self._validators_hash():
            return "validator set does not match header validators_hash"
        return None

    def _validators_hash(self) -> bytes:
        """The set's Merkle root under a ``valset.hash`` span: the one hash
        of a light client's request that grows with the set.  The span is
        here and not in ``ValidatorSet.hash``, which a node calls several
        times a block.  ``path`` says what computed it: ``native`` (one
        sidecar call) or ``python``."""
        from cometbft_tpu.libs import tracing
        from cometbft_tpu.proofserve import plane

        n = len(self.validator_set)
        with tracing.span(
            "valset.hash", leaves=n, tier=plane.tier_for(n)
        ) as sp:
            root, path = self.validator_set.hash_with_path()
            sp.set(path=path)
            return root
