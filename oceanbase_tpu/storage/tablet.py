"""Tablet: one partition's LSM — memtables + leveled segments.

Reference analog: ObTablet (src/storage/tablet) owning memtables and an
SSTable table-store; freeze/mini/minor/major compaction driven by the
tenant scheduler (src/storage/compaction/ob_tenant_tablet_scheduler.h:140).

Read path: ``snapshot_arrays`` fuses base segments (oldest..newest,
newest-wins by primary key) with the visible memtable overlay — the TPU
build's version of ObMultipleScanMerge fusing memtable + SSTables
(src/storage/access/ob_multiple_merge.cpp:507), done column-wise on host
metadata before the device upload instead of row-at-a-time.
"""

from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass, field
from operator import itemgetter

import numpy as np

from oceanbase_tpu.datatypes import SqlType
from oceanbase_tpu.storage.memtable import MemTable, Version
from oceanbase_tpu.storage.segment import Segment, merge_segments


class SegIdAlloc:
    """Monotonic segment-id allocator that can be bumped past ids seen
    on recovery/repair installs: a restarted tablet must never mint an
    id that collides with a persisted segment file (the fresh segment
    would silently overwrite the old one on disk)."""

    def __init__(self, start: int = 1):
        self.n = start

    def __next__(self) -> int:
        v = self.n
        self.n += 1
        return v

    def bump_past(self, seg_id: int):
        self.n = max(self.n, int(seg_id) + 1)


#: keys the commit log holds before it drops its oldest entries (a device
#: copy further behind than the log reaches is rebuilt)
COMMIT_LOG_KEYS = 4_000_000


@dataclass
class TabletDelta:
    """What was committed to a tablet since a mark: the newest committed
    version of every key touched, as ``delta_since`` found it."""

    keys: list                    # every key touched (deleted or written)
    row_keys: list                # the keys whose newest version is live
    arrays: dict                  # column -> values of those rows
    valids: dict                  # column -> validity, None = all valid
    mark: tuple                   # the mark a copy holds once applied
    segment_keys: int = 0         # keys that were looked up in L0 segments


class Tablet:
    def __init__(self, tablet_id: int, columns: list[str],
                 types: dict[str, SqlType], key_cols: list[str]):
        self.tablet_id = tablet_id
        self.columns = list(columns)
        self.types = dict(types)
        self.key_cols = list(key_cols)
        self.active = MemTable(0)
        self.frozen: list[MemTable] = []
        self.segments: list[Segment] = []   # oldest first
        self._next_mt = itertools.count(1)
        self._next_seg = SegIdAlloc(1)
        self._lock = threading.RLock()
        self._auto_key = itertools.count()  # rowid for keyless tables
        self.data_version = 0               # bumps on any visible change
        # what delta_since answers from: every commit applied here, in
        # the order applied, as (sequence, commit version, keys, the
        # version each key got | None once a flush moved them to L0)
        # (a PartitionedTablet turns it off for its partitions: their
        # copies are rebuilt, and every partition is told every key)
        self.logs_commits = True
        self._commit_log: list[tuple] = []
        self._commit_seq = 0
        self._log_keys = 0
        self._log_floor = (0, 0)    # newest (sequence, version) dropped
        # bumps whenever segments are rewritten or the schema changes:
        # a copy built before then cannot be brought up by a delta
        self.baseline_epoch = 0

    # ------------------------------------------------------------------
    def make_key(self, values: dict) -> tuple:
        if self.key_cols == ["__rowid__"] and "__rowid__" not in values:
            values["__rowid__"] = self.next_rowid(1)
        return tuple(values[k] for k in self.key_cols)

    def next_rowid(self, n: int) -> int:
        """Allocate n consecutive hidden rowids (restart-safe: seeded from
        the max persisted rowid on first use)."""
        with self._lock:
            if not hasattr(self, "_rowid_base"):
                base = 0
                for seg in self.segments:
                    chunks = seg.columns.get("__rowid__")
                    if chunks:
                        for ec in chunks:
                            if ec.zone.vmax is not None:
                                base = max(base, int(ec.zone.vmax) + 1)
                # rows replayed from the WAL live only in memtables
                if self.key_cols == ["__rowid__"]:
                    for mt in [self.active] + self.frozen:
                        for key in mt._rows:
                            base = max(base, int(key[0]) + 1)
                self._rowid_base = base
            out = self._rowid_base
            self._rowid_base += n
            return out

    def write(self, key: tuple, op: str, values: dict, tx_id: int,
              stmt_seq: int = 0, snapshot: int | None = None):
        with self._lock:
            # invariant: stored values always carry their key columns
            # (callers that copied the dict before make_key would
            # otherwise persist NULL rowids that dedup collapses)
            if any(values.get(kc) is None for kc in self.key_cols):
                values = dict(values)
                for kc, kv in zip(self.key_cols, key):
                    if values.get(kc) is None:
                        values[kc] = kv
            # SI conflict checks look at frozen memtables too: the key's
            # newest version may have been frozen mid-transaction
            if snapshot is not None:
                for mt in self.frozen:
                    head = mt._rows.get(key)
                    if head is not None and head.commit_version > snapshot:
                        from oceanbase_tpu.tx.errors import WriteConflict

                        raise WriteConflict(
                            f"key {key} modified after snapshot {snapshot}")
            v = self.active.write(key, op, values, tx_id, stmt_seq,
                                  snapshot=snapshot)
            return v

    def commit(self, tx_id: int, commit_version: int, keys):
        with self._lock:
            self.active.commit(tx_id, commit_version, keys)
            for mt in self.frozen:
                mt.commit(tx_id, commit_version, keys)
            self.data_version += 1
            if self.logs_commits:
                self._log_commit(tx_id, commit_version, tuple(keys))

    def _log_commit(self, tx_id: int, commit_version: int, keys: tuple):
        """The commit joins the log with the version it gave each key: the
        head of the key's chain in the newest memtable that holds one, when
        that is this transaction's (``None`` where its statement was rolled
        back, so that ``delta_since`` looks the key up).  The memtables hold
        those versions anyway; ``mini_compact`` lets go of them here."""
        with self._lock:
            tables = [mt._rows for mt in self.memtables()]
            versions = []
            for key in keys:
                head = None
                for rows in tables:
                    head = rows.get(key)
                    if head is not None:
                        break
                versions.append(
                    head if head is not None and head.tx_id == tx_id
                    and head.commit_version == commit_version else None)
            self._commit_seq += 1
            self._commit_log.append((self._commit_seq, commit_version, keys,
                                     tuple(versions)))
            self._log_keys += len(keys)
            while self._log_keys > COMMIT_LOG_KEYS and \
                    len(self._commit_log) > 1:
                seq, version, dropped, _ = self._commit_log.pop(0)
                self._log_keys -= len(dropped)
                self._log_floor = (seq, max(self._log_floor[1], version))

    def rebase(self):
        """The baseline was rewritten (compaction above L0, a segment
        installed or removed, a schema change, the memtables reset): a
        visible change no delta describes."""
        self.data_version += 1
        self.baseline_epoch += 1

    # ------------------------------------------------------------------
    # committed deltas (the device copy's maintenance reads these)
    # ------------------------------------------------------------------
    def delta_mark(self) -> tuple:
        """Where a copy built from a snapshot read that STARTS now stands:
        (baseline epoch, commit-log sequence).  Taken before the read, so
        a commit that lands during it is listed again, never lost."""
        with self._lock:
            return (self.baseline_epoch, self._commit_seq)

    def delta_since(self, mark: tuple, after: int, upto: int):
        """The newest committed version <= ``upto`` of every key whose
        commit was applied after ``mark`` or carries a version above
        ``after`` -> ``TabletDelta``; ``None`` where that cannot be
        answered exactly (the baseline was rewritten, or the log no
        longer reaches back to the mark): the caller rebuilds.

        One pass over the commit log: each entry carries the version its
        commit gave every key, and a key's commits lie in the log in the
        order of its version chain, so folding the entries in order leaves
        the newest.  Only a key whose newest entry cannot say (its
        statement was rolled back, or a flush moved the versions to L0) is
        looked up: in the memtables, then in the L0 segments that hold
        versions as new as the commits asked for."""
        epoch, seq = mark
        with self._lock:
            if epoch != self.baseline_epoch or seq < self._log_floor[0] \
                    or after < self._log_floor[1]:
                return None
            newest: dict = {}
            oldest = upto
            for s, version, ks, versions in self._commit_log:
                if (s > seq or version > after) and version <= upto:
                    oldest = min(oldest, version)
                    newest.update(zip(ks, versions) if versions is not None
                                  else dict.fromkeys(ks))
            new_mark = (self.baseline_epoch, self._commit_seq)
            tables = self.memtables()
            missing = []
            for key in [k for k, v in newest.items() if v is None]:
                for mt in tables:
                    v = mt.visible_version(key, upto)
                    if v is not None:
                        newest[key] = v
                        break
                else:
                    missing.append(key)
            if missing:
                for key, (deleted, values) in self._segment_versions(
                        missing, oldest, upto).items():
                    newest[key] = Version(
                        0, 0, "delete" if deleted else "insert", values)
        # a key with no committed version at all was written and rolled
        # back by its statement: nothing changed for it
        found = {k: v for k, v in newest.items() if v is not None}
        touched = list(found)
        row_keys = [k for k, v in found.items() if v.op != "delete"]
        rows = [found[k].values for k in row_keys]
        arrays, valids = _values_to_arrays(rows, self.columns, self.types)
        return TabletDelta(touched, row_keys, arrays, valids, new_mark,
                           len(missing))

    def _segment_versions(self, keys: list, oldest: int, upto: int) -> dict:
        """{key: (deleted, values)} of the newest version <= ``upto`` of
        each of ``keys`` in the L0 segments a mini-compaction wrote since
        ``oldest`` (their rows carry ``__version__``)."""
        want = set(keys)
        first = np.array([k[0] for k in keys])
        best: dict = {}
        for seg in self.segments:
            if seg.level != 0 or seg.max_version < oldest \
                    or seg.min_version > upto:
                continue
            a, v = seg.decode()
            vers = a.get("__version__")
            sel = np.isin(a[self.key_cols[0]], first)
            if vers is not None:
                sel &= vers <= upto
            for i in np.nonzero(sel)[0]:
                key = tuple(_item(a[k][i]) for k in self.key_cols)
                if key not in want:
                    continue
                ver = int(vers[i]) if vers is not None else seg.max_version
                if key in best and best[key][0] > ver:
                    continue
                values = {c: (None if v.get(c) is not None and not v[c][i]
                              else _item(a[c][i]))
                          for c in self.columns if c in a}
                best[key] = (ver, bool(a["__deleted__"][i])
                             if "__deleted__" in a else False, values)
        return {k: (deleted, values)
                for k, (_ver, deleted, values) in best.items()}

    def abort(self, tx_id: int, keys, min_stmt_seq: int = 0):
        with self._lock:
            self.active.abort(tx_id, keys, min_stmt_seq)
            for mt in self.frozen:
                mt.abort(tx_id, keys, min_stmt_seq)

    # ------------------------------------------------------------------
    # compaction (≙ mini/minor/major merge DAGs)
    # ------------------------------------------------------------------
    def freeze(self):
        with self._lock:
            if len(self.active) == 0:
                return None
            mt = self.active.freeze()
            self.frozen.append(mt)
            self.active = MemTable(next(self._next_mt))
            return mt

    def mini_compact(self, snapshot: int):
        """Frozen memtables -> one L0 segment.

        Versions the flush snapshot cannot capture (uncommitted, or
        committed after the snapshot) are CARRIED OVER into the active
        memtable instead of being dropped — a frozen memtable may hold a
        live transaction's writes (≙ the reference's freeze waiting on
        active tx handover; we migrate instead of waiting)."""
        with self._lock:
            if not self.frozen:
                return None
            parts = []
            leftovers: list[dict] = []
            for mt in self.frozen:
                arrays, valids = mt.to_arrays(self.columns, self.types,
                                              snapshot)
                parts.append((arrays, valids, mt))
                leftovers.append(mt.leftover_versions(snapshot))
            merged_arrays, merged_valids = _stack_parts(parts, self.columns,
                                                        self.types)
            from oceanbase_tpu.storage.segment import sort_rows_by_keys

            merged_arrays, merged_valids = sort_rows_by_keys(
                merged_arrays, merged_valids, self.key_cols)
            seg = Segment.build(
                next(self._next_seg), 0, merged_arrays,
                {**self.types, "__deleted__": SqlType.bool_(),
                 "__version__": SqlType.int_()},
                merged_valids,
                min_version=min((mt.min_version for _, _, mt in parts
                                 if mt.max_version > 0), default=snapshot),
                max_version=max((mt.max_version for _, _, mt in parts),
                                default=snapshot),
            )
            self.segments.append(seg)
            self.frozen = []
            for lo in leftovers:
                self._graft_versions(lo)
            # the flushed versions live in the segment now: the log keeps
            # none of them alive (delta_since looks those keys up)
            self._commit_log = [
                (s, version, ks, None if version <= snapshot else versions)
                for s, version, ks, versions in self._commit_log]
            self.data_version += 1
            return seg

    def _graft_versions(self, chains: dict):
        """Attach carried-over version chains under the active memtable's
        chains (active versions are strictly newer)."""
        for key, head in chains.items():
            cur = self.active._rows.get(key)
            if cur is None:
                self.active.adopt(key, head)
            else:
                tail = cur
                while tail.prev is not None:
                    tail = tail.prev
                tail.prev = head

    def minor_compact(self):
        """All L0 segments -> one L1 (≙ minor merge).  Tombstones are
        RETAINED: the rows they shadow may live in lower levels outside
        this merge."""
        with self._lock:
            l0 = [s for s in self.segments if s.level == 0]
            if len(l0) < 2:
                return None
            keep = [s for s in self.segments if s.level != 0]
            merged = merge_segments(next(self._next_seg), 1, l0,
                                    self.key_cols, drop_tombstones=False)
            # place after existing L1/L2 so order stays oldest-first
            self.segments = keep + [merged]
            self.rebase()
            return merged

    def major_compact(self):
        """Everything -> one L2 baseline (≙ daily major merge); the merge
        covers every level, so tombstones fall out here."""
        with self._lock:
            if not self.segments:
                return None
            merged = merge_segments(next(self._next_seg), 2, self.segments,
                                    self.key_cols, drop_tombstones=True)
            self.segments = [merged]
            self.rebase()
            return merged

    # ------------------------------------------------------------------
    # snapshot read
    # ------------------------------------------------------------------
    def snapshot_arrays(self, snapshot: int, tx_id: int = 0, prune=None):
        """-> (arrays, valids) visible at ``snapshot`` (plus own tx).

        ``prune``: optional {key_col: (lo, hi)} inclusive ranges used for
        zone-map chunk pruning (≙ blockscan skipping via index blocks).
        SOUNDNESS: pruning columns MUST be key columns — every version of
        a key (including tombstones) carries identical key-column values,
        so a chunk mask derived from key ranges either keeps every version
        of a key or drops every version; newest-wins dedup stays correct
        for all surviving keys.  Pruning on a non-key column could split a
        version chain and resurrect stale rows."""
        if prune:
            assert set(prune) <= set(self.key_cols), \
                "zone-map pruning is only sound on key columns"
        with self._lock:
            seg_parts = []
            for seg in self.segments:
                if seg.min_version > snapshot:
                    continue  # wholly invisible at this snapshot
                if prune:
                    cm = np.ones(seg.n_chunks, dtype=bool)
                    for pc, (lo, hi) in prune.items():
                        cm &= seg.prune_chunks(pc, lo, hi)
                    if not cm.any():
                        continue
                    a, v = seg.decode(chunk_mask=None if cm.all() else cm)
                else:
                    a, v = seg.decode()
                if seg.max_version > snapshot and "__version__" in a:
                    vis = a["__version__"] <= snapshot
                    a = {k: arr[vis] for k, arr in a.items()}
                    v = {k: (vv[vis] if vv is not None else None)
                         for k, vv in v.items()}
                seg_parts.append((a, v, None))
            mt_parts = []
            # the same ranges prune the memtables: sound for the reason
            # above, and a DELETE by key then decodes the rows it may
            # touch, not every row written since the last freeze
            within = self.key_ranges(prune)
            for mt in self.frozen + [self.active]:
                rows = mt.snapshot_rows(snapshot, tx_id, within)
                if rows:
                    a, v = _rows_to_arrays(rows, self.columns, self.types)
                    mt_parts.append((a, v, None))
        parts = seg_parts + mt_parts
        if not parts:
            return ({c: np.zeros(0, dtype=object if self.types[c].is_string
                                 else self.types[c].np_dtype)
                     for c in self.columns},
                    {c: None for c in self.columns})
        arrays, valids = _stack_parts(parts, self.columns, self.types)
        n = len(next(iter(arrays.values())))
        keep = np.ones(n, dtype=bool)
        if self.key_cols and n:
            # newest last -> wins: of the rows with one key, the last
            keep = _last_of_each_key([arrays[k] for k in self.key_cols])
        if "__deleted__" in arrays:
            keep &= ~arrays["__deleted__"].astype(bool)
        out_a = {c: arrays[c][keep] for c in self.columns}
        out_v = {c: (valids[c][keep] if valids.get(c) is not None else None)
                 for c in self.columns}
        return out_a, out_v

    def key_ranges(self, ranges) -> list:
        """{key column: (lo, hi)} -> ``MemTable.keys_within``'s form."""
        return [(self.key_cols.index(c), lo, hi)
                for c, (lo, hi) in (ranges or {}).items()
                if c in self.key_cols]

    def row_count_estimate(self) -> int:
        return sum(s.n_rows for s in self.segments) + len(self.active) + \
            sum(len(m) for m in self.frozen)

    def memtables(self):
        """Active + frozen memtables, newest-first (interface shared with
        PartitionedTablet for point-lookup/streaming paths)."""
        return [self.active] + self.frozen[::-1]

    # -- segment management hooks (shared with PartitionedTablet) --------
    def add_segment(self, seg, part_idx=None):
        # segment list + data_version guard reads through THIS tablet's
        # lock; callers under the engine lock still must not bypass it
        with self._lock:
            self.segments.append(seg)
            self._next_seg.bump_past(seg.segment_id)
            self.rebase()

    def remove_segments(self, ids):
        ids = set(ids)
        with self._lock:
            self.segments = [s for s in self.segments
                             if s.segment_id not in ids]
            self.rebase()

    def segment_locations(self):
        """-> [(Segment, partition_idx|None)] for manifest checkpoints."""
        return [(s, None) for s in self.segments]

    def max_commit_version(self) -> int:
        """Largest commit version any row in this tablet carries; a read
        at snapshot >= this sees the same data as a latest-commit read."""
        v = max((s.max_version for s in self.segments), default=0)
        for mt in [self.active] + self.frozen:
            v = max(v, mt.max_version)
        return v


def _last_of_each_key(key_arrays: list) -> np.ndarray:
    """keep[i]: no later row has row i's key.  One stable sort by the key
    columns (rows of one key stay in their order, so the last of each run
    is the newest), no per-row work."""
    n = len(key_arrays[0])
    order = np.lexsort(key_arrays[::-1])
    same_as_next = np.ones(n - 1, dtype=bool)
    for a in key_arrays:
        s = a[order]
        same_as_next &= s[1:] == s[:-1]
    keep = np.ones(n, dtype=bool)
    keep[order[:-1][same_as_next]] = False
    return keep


def _item(x):
    return x.item() if hasattr(x, "item") else x


def _has_null(vals) -> bool:
    try:
        return None in vals     # identity first, at C speed
    except ValueError:          # an array-valued cell compares elementwise
        return any(x is None for x in vals)


def _values_to_arrays(rows: list, columns, types):
    """[{column: python value | None}] -> (arrays, valids), a column at a
    time; ``valids[c]`` is None where no row is NULL there."""
    columns = list(columns)
    cell = itemgetter(*columns) if len(columns) > 1 else \
        (lambda r, c=columns[0]: (r[c],))
    try:
        # one C-level pass a row, transposed once
        cells = list(zip(*map(cell, rows)))
    except KeyError:
        # a row lacks a column (a delete's, or one written before an ALTER
        # TABLE ADD COLUMN): NULL there
        cells = [tuple(r.get(c) for r in rows) for c in columns]
    arrays, valids = {}, {}
    for c, vals in zip(columns, cells or [()] * len(columns)):
        valids[c] = None
        if _has_null(vals):
            fill = "" if types[c].is_string else 0
            valids[c] = np.fromiter((x is not None for x in vals),
                                    dtype=bool, count=len(vals))
            vals = [fill if x is None else x for x in vals]
        arrays[c] = np.array(vals, dtype=object) if types[c].is_string \
            else np.asarray(vals, dtype=types[c].np_dtype)
    return arrays, valids


def _rows_to_arrays(rows: dict, columns, types):
    """{key: Version} -> (arrays with ``__deleted__``, valids), in key
    order; every column gets a validity array."""
    versions = [v for _key, v in sorted(rows.items())]
    out, valids = _values_to_arrays([v.values for v in versions], columns,
                                    types)
    n = len(versions)
    valids = {c: np.ones(n, dtype=bool) if v is None else v
              for c, v in valids.items()}
    out["__deleted__"] = np.fromiter((v.op == "delete" for v in versions),
                                     dtype=bool, count=n)
    return out, valids


def _stack_parts(parts, columns, types):
    """Stack (arrays, valids, _) parts preserving the hidden __deleted__
    tombstone and __version__ commit-version columns.

    A part MISSING a real column (segments written before an ALTER TABLE
    ADD COLUMN) contributes NULLs for it — schema evolution without
    rewriting old segments."""
    cols = list(columns) + ["__deleted__", "__version__"]
    arrays = {}
    valids = {}
    for c in cols:
        arrs = []
        missing = []  # parallel flags: part lacked this column entirely
        for a, v, _ in parts:
            if c in a:
                arrs.append(a[c])
                missing.append(False)
            else:
                n = len(next(iter(a.values())))
                if c == "__deleted__":
                    arrs.append(np.zeros(n, dtype=bool))
                elif c == "__version__":
                    arrs.append(np.zeros(n, dtype=np.int64))
                else:
                    arrs.append(
                        np.array([""] * n, dtype=object)
                        if types[c].is_string
                        else np.zeros(n, dtype=types[c].np_dtype))
                missing.append(True)
        if any(x.dtype == object for x in arrs):
            arrs = [x.astype(object) for x in arrs]
        arrays[c] = np.concatenate(arrs) if arrs else np.zeros(0)
        if c not in ("__deleted__", "__version__"):
            vparts = []
            has = any(v.get(c) is not None for _, v, _ in parts) or \
                any(m for m in missing)
            if has:
                for (a, v, _), m, arr in zip(parts, missing, arrs):
                    n = len(arr)
                    if m:
                        vparts.append(np.zeros(n, dtype=bool))  # NULLs
                    else:
                        vv = v.get(c)
                        vparts.append(vv if vv is not None
                                      else np.ones(n, dtype=bool))
                valids[c] = np.concatenate(vparts)
            else:
                valids[c] = None
    return arrays, valids
