"""Partitioned tables (range, hash, key): multiple tablets per table.

Reference analog: partitioned tables mapping to multiple tablets hosted
by log streams (src/storage/tablet + the partition routing the DAS layer
performs).  A PartitionedTablet keeps the single-tablet interface the
rest of the engine uses (write/commit/abort/freeze/compact/snapshot) and
routes internally:

- writes route by the partition key's range, or by the hash of the key
  columns (``share/keyhash.py``: the function the PX exchanges use, so a
  partition is also a shard; ≙ PKEY slice routing)
- snapshot reads concatenate per-partition arrays (scans parallelize
  naturally — each partition is an independent granule source)
- freeze/compaction iterate partitions (≙ per-tablet merge DAGs)

Bounds are upper-exclusive split points: bounds [10, 20] makes partitions
(-inf,10), [10,20), [20,+inf).  A hash or key partitioning has
``hash_cols`` and a partition count instead, and no ``part_col``: nothing
prunes it by range.
"""

from __future__ import annotations

import bisect
import threading

import numpy as np

from oceanbase_tpu.share import keyhash
from oceanbase_tpu.storage.encoding import CodedStrings
from oceanbase_tpu.storage.tablet import Tablet


class PartitionedTablet:
    def __init__(self, tablet_id: int, columns, types, key_cols,
                 part_col: str | None = None, bounds: list | None = None,
                 hash_cols: list | None = None, nparts: int = 0):
        """Range: ``part_col`` and ``bounds``.  Hash / key: ``hash_cols``
        and ``nparts``."""
        for c in hash_cols or [part_col]:
            if c not in columns:
                raise ValueError(
                    f"partition column {c!r} is not a table column")
        bounds = list(bounds or [])
        if any(bounds[i] >= bounds[i + 1] for i in range(len(bounds) - 1)):
            raise ValueError("partition bounds must be strictly increasing")
        self.part_col = part_col
        self.bounds = bounds
        self.hash_cols = list(hash_cols or [])
        self.columns = list(columns)
        self.types = dict(types)
        self.key_cols = list(key_cols)
        self.partitions = [
            Tablet(tablet_id * 1000 + i, columns, types, key_cols)
            for i in range(nparts if self.hash_cols else len(bounds) + 1)
        ]
        # one segment-id space across partitions (filenames stay unique;
        # add_segment bumps it past recovered ids — see SegIdAlloc)
        from oceanbase_tpu.storage.tablet import SegIdAlloc

        shared = SegIdAlloc(1)
        for p in self.partitions:
            p._next_seg = shared
            p.logs_commits = False
        self._lock = threading.RLock()

    # ------------------------------------------------------------------
    @property
    def data_version(self) -> int:
        return sum(p.data_version for p in self.partitions)

    @property
    def active(self):
        # callers needing ALL memtables must use memtables(); this exists
        # only for interface compatibility with single-tablet code paths
        return self.partitions[0].active

    def memtables(self):
        """Every memtable across partitions, newest-first per partition."""
        out = []
        for p in self.partitions:
            out.append(p.active)
            out.extend(p.frozen[::-1])
        return out

    def max_commit_version(self) -> int:
        return max((p.max_commit_version() for p in self.partitions),
                   default=0)

    @property
    def frozen(self):
        out = []
        for p in self.partitions:
            out.extend(p.frozen)
        return out

    @property
    def segments(self):
        out = []
        for p in self.partitions:
            out.extend(p.segments)
        return out

    @property
    def part_cols(self) -> list:
        """The columns a row's partition follows from."""
        return self.hash_cols or [self.part_col]

    def _hash_route(self, vals) -> Tablet:
        """One row's key values (a NULL hashes as 0) -> its partition."""
        datas = [np.array([0 if v is None else int(v)], dtype=np.int64)
                 for v in vals]
        return self.partitions[int(
            keyhash.partition_of(datas, len(self.partitions))[0])]

    def _route(self, values: dict) -> Tablet:
        if self.hash_cols:
            return self._hash_route([values.get(c) for c in self.hash_cols])
        v = values.get(self.part_col)
        if v is None:
            return self.partitions[0]  # NULLs live in the first partition
        return self.partitions[bisect.bisect_right(self.bounds, v)]

    def _route_key(self, key: tuple) -> Tablet | None:
        """Route by key when the partition columns are part of the key."""
        if not all(c in self.key_cols for c in self.part_cols):
            return None
        vals = [key[self.key_cols.index(c)] for c in self.part_cols]
        if self.hash_cols:
            return self._hash_route(vals)
        return self.partitions[bisect.bisect_right(self.bounds, vals[0])]

    # ------------------------------------------------------------------
    def make_key(self, values: dict) -> tuple:
        return self._route(values).make_key(values)

    def next_rowid(self, n: int) -> int:
        return self.partitions[0].next_rowid(n)

    def write(self, key: tuple, op: str, values: dict, tx_id: int,
              stmt_seq: int = 0, snapshot=None):
        t = self._route_key(key) or self._route(values)
        return t.write(key, op, values, tx_id, stmt_seq, snapshot)

    def commit(self, tx_id: int, commit_version: int, keys):
        for p in self.partitions:
            p.commit(tx_id, commit_version, keys)

    def abort(self, tx_id: int, keys, min_stmt_seq: int = 0):
        for p in self.partitions:
            p.abort(tx_id, keys, min_stmt_seq)

    # ------------------------------------------------------------------
    def freeze(self):
        for p in self.partitions:
            p.freeze()

    def mini_compact(self, snapshot: int):
        """-> list[(part_idx, Segment)] of newly produced segments."""
        out = []
        for i, p in enumerate(self.partitions):
            s = p.mini_compact(snapshot)
            if s is not None:
                out.append((i, s))
        return out or None

    def minor_compact(self):
        out = []
        for i, p in enumerate(self.partitions):
            s = p.minor_compact()
            if s is not None:
                out.append((i, s))
        return out or None

    def major_compact(self):
        out = []
        for i, p in enumerate(self.partitions):
            s = p.major_compact()
            if s is not None:
                out.append((i, s))
        return out or None

    # ------------------------------------------------------------------
    def snapshot_arrays(self, snapshot: int, tx_id: int = 0, prune=None):
        return self.snapshot_arrays_counted(snapshot, tx_id, prune)[:2]

    def snapshot_arrays_counted(self, snapshot: int, tx_id: int = 0,
                                prune=None):
        """-> (arrays, valids, rows of each partition read): the rows of
        partition ``i`` are one contiguous run of the arrays, in partition
        order (a device copy per partition slices them there)."""
        live = self.partitions
        if prune and self.part_col in prune:
            lo, hi = prune[self.part_col]
            first = 0 if lo is None else \
                bisect.bisect_right(self.bounds, lo)
            last = len(self.partitions) - 1 if hi is None else \
                bisect.bisect_right(self.bounds, hi)
            live = self.partitions[first:last + 1]
        # chunk-level pruning below the partition router is only sound on
        # key columns (see Tablet.snapshot_arrays); partition-level routing
        # on part_col is sound regardless because a row's partition is
        # derived from the very value being ranged on
        sub = ({k: v for k, v in prune.items()
                if k in self.partitions[0].key_cols} or None) if prune \
            else None
        parts = [p.snapshot_arrays(snapshot, tx_id, prune=sub)
                 for p in live]
        arrays: dict = {}
        valids: dict = {}
        for c in self.columns:
            chunks = [a[c] for a, _v in parts if c in a]
            if any(x.dtype == object for x in chunks):
                chunks = [x.astype(object) for x in chunks]
            arrays[c] = np.concatenate(chunks) if chunks else \
                np.zeros(0, dtype=self.types[c].np_dtype)
            vs = [v.get(c) for _a, v in parts]
            if any(x is not None for x in vs):
                valids[c] = np.concatenate(
                    [x if x is not None
                     else np.ones(len(a[c]), dtype=bool)
                     for (a, v), x in zip(parts, vs)])
            else:
                valids[c] = None
        counts = [len(next(iter(a.values()))) if a else 0 for a, _v in parts]
        return arrays, valids, counts

    def row_count_estimate(self) -> int:
        return sum(p.row_count_estimate() for p in self.partitions)

    # -- segment management hooks ----------------------------------------
    def add_segment(self, seg, part_idx=None):
        self.partitions[part_idx or 0].add_segment(seg)

    def remove_segments(self, ids):
        for p in self.partitions:
            p.remove_segments(ids)

    def segment_locations(self):
        out = []
        for i, p in enumerate(self.partitions):
            out.extend((s, i) for s in p.segments)
        return out

    def split_arrays_by_partition(self, arrays: dict, valids=None):
        """Bulk-load routing: -> [(part_idx, {col -> rows}, selector)] per
        partition that gets rows (``valids``: a NULL hash key is 0)."""
        if self.hash_cols:
            datas = []
            for c in self.hash_cols:
                d = np.asarray(arrays[c]).astype(np.int64)
                v = (valids or {}).get(c)
                datas.append(d if v is None else np.where(v, d, 0))
            idx = keyhash.partition_of(datas, len(self.partitions))
        else:
            col = arrays[self.part_col]
            if isinstance(col, CodedStrings):   # a load's factorised column
                col = col.strings()
            idx = np.searchsorted(np.asarray(self.bounds), col,
                                  side="right")
        out = []
        for i in range(len(self.partitions)):
            sel = idx == i
            if sel.any():
                out.append((i, {k: v[sel] for k, v in arrays.items()}, sel))
        return out

    def route_partition_index(self, values: dict) -> int:
        """Which partition a row with these values lives in (DML uses it
        to detect partition-moving updates)."""
        return self.partitions.index(self._route(values))
