"""Key -> lane of a device relation, kept on the host.

The maintained device copy of a table (``storage/engine.py``) clears the
lane of every key a commit deleted or superseded and writes new versions
into dead pad lanes; this is where it finds a key's lane.  Built once
from the relation itself (the key columns of its live lanes, so a lane
is right by construction), then kept current by the deltas: a sorted,
immutable base of the baseline's keys whose lanes are struck out in
place, and a small overlay of the keys written since.

A composite key is packed into one int64 through each column's rank
among the baseline's distinct values (mixed radix), so a lookup is one
vectorised ``searchsorted`` whatever the key's types.
"""

from __future__ import annotations

from itertools import repeat

import numpy as np


class KeyIndex:
    def __init__(self, uniques: list, codes: np.ndarray, lanes: np.ndarray):
        self._uniques = uniques      # per key column: sorted distinct values
        self._codes = codes          # packed keys of the baseline, sorted
        self._lanes = lanes          # their lanes; -1 once struck out
        self._overlay: dict = {}     # key tuple -> lane, written since

    @classmethod
    def from_relation(cls, rel, key_cols) -> "KeyIndex":
        mask = np.asarray(rel.mask_or_true())
        lanes = np.nonzero(mask)[0].astype(np.int64)
        cols = []
        for k in key_cols:
            col = rel.columns[k]
            data = np.asarray(col.data)[lanes]
            if col.sdict is not None:
                data = col.sdict.values[data]
            cols.append(data)
        uniques = [np.unique(c) for c in cols]
        codes = cls._pack(uniques, [np.searchsorted(u, c)
                                    for u, c in zip(uniques, cols)])
        if len(codes) > 1 and not (codes[1:] > codes[:-1]).all():
            order = np.argsort(codes, kind="stable")
            codes, lanes = codes[order], lanes[order]
        return cls(uniques, codes, lanes)

    @staticmethod
    def _pack(uniques, ranks) -> np.ndarray:
        space = 1
        for u in uniques:
            space *= max(len(u), 1)
        if space >= 2 ** 62:
            raise OverflowError("key space too large to pack")
        out = np.zeros(len(ranks[0]) if ranks else 0, dtype=np.int64)
        for u, r in zip(uniques, ranks):
            out = out * max(len(u), 1) + r
        return out

    def _base_slots(self, keys: list) -> np.ndarray:
        """Position of each key in the base, -1 where it is not there."""
        n = len(keys)
        ok = np.ones(n, dtype=bool)
        ranks = []
        for u, part in zip(self._uniques, zip(*keys)):
            col = np.array(part, dtype=object if u.dtype == object else None)
            r = np.minimum(np.searchsorted(u, col), max(len(u) - 1, 0))
            ok &= (u[r] == col) if len(u) else False
            ranks.append(r)
        codes = self._pack(self._uniques, ranks)
        slot = np.minimum(np.searchsorted(self._codes, codes),
                          max(len(self._codes) - 1, 0))
        ok &= (self._codes[slot] == codes) if len(self._codes) else False
        return np.where(ok, slot, -1)

    def take(self, keys: list) -> np.ndarray:
        """The lanes that hold ``keys`` now (-1: none), struck out of the
        index: whoever asks is about to clear them."""
        out = np.fromiter(map(self._overlay.pop, keys, repeat(-1)),
                          dtype=np.int64, count=len(keys))
        rest = np.nonzero(out < 0)[0]
        if len(rest) and len(self._codes):
            slots = self._base_slots([keys[i] for i in rest])
            hit = slots >= 0
            out[rest[hit]] = self._lanes[slots[hit]]
            self._lanes[slots[hit]] = -1
        return out

    def put(self, keys: list, first_lane: int):
        """``keys`` now live in consecutive lanes from ``first_lane``."""
        self._overlay.update(zip(keys, range(first_lane,
                                             first_lane + len(keys))))
