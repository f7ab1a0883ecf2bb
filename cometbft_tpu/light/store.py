"""Trusted light-block store (reference: light/store/db/db.go)."""

from __future__ import annotations

import struct
import threading
from bisect import bisect_left
from typing import Optional

from cometbft_tpu.types import codec
from cometbft_tpu.types.light import LightBlock

_PREFIX = b"lb/"


def _key(height: int) -> bytes:
    return _PREFIX + struct.pack(">q", height)


class LightStore:
    """Persists verified light blocks, ordered by height.  The stored
    heights are also kept in memory, sorted (read from the db once, at
    construction), so that finding a height is a bisection, not a walk over
    every stored block."""

    def __init__(self, db):
        self._db = db
        self._lock = threading.Lock()
        self._heights = [
            struct.unpack(">q", k[len(_PREFIX) :])[0]
            for k, _raw in db.iterate(_PREFIX, _PREFIX + b"\xff")
        ]

    def save_light_block(self, lb: LightBlock) -> None:
        raw = codec.encode_light_block(lb)
        with self._lock:
            self._db.set(_key(lb.height), raw)
            i = bisect_left(self._heights, lb.height)
            if i == len(self._heights) or self._heights[i] != lb.height:
                self._heights.insert(i, lb.height)

    def light_block(self, height: int) -> Optional[LightBlock]:
        raw = self._db.get(_key(height))
        return codec.decode_light_block(raw) if raw else None

    def latest(self) -> Optional[LightBlock]:
        with self._lock:
            top = self._heights[-1] if self._heights else None
        return self.light_block(top) if top is not None else None

    def first(self) -> Optional[LightBlock]:
        with self._lock:
            low = self._heights[0] if self._heights else None
        return self.light_block(low) if low is not None else None

    def heights(self) -> list[int]:
        with self._lock:
            return list(self._heights)

    def light_block_before(self, height: int) -> Optional[LightBlock]:
        """Latest stored block strictly below ``height`` (reference:
        db.go LightBlockBefore)."""
        with self._lock:
            i = bisect_left(self._heights, height)
            best = self._heights[i - 1] if i else None
        return self.light_block(best) if best is not None else None

    def prune(self, keep: int) -> int:
        """Keep only the newest ``keep`` blocks (reference: db.go Prune)."""
        with self._lock:
            cut = len(self._heights) - keep if keep > 0 else len(self._heights)
            to_delete = self._heights[: max(cut, 0)]
            del self._heights[: len(to_delete)]
            for h in to_delete:
                self._db.delete(_key(h))
        return len(to_delete)

    def size(self) -> int:
        with self._lock:
            return len(self._heights)
