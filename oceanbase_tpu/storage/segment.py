"""Immutable column segments (SSTable analog).

Reference analog: ObSSTable macro/micro blocks + column store CG files
(src/storage/blocksstable, src/storage/column_store).  A segment is the
unit the LSM produces at freeze/compaction time: per-column encoded chunks
with zone maps, optionally persisted as one .npz file, decoded column-wise
straight into the device upload path.

Layout: rows are chunked (CHUNK_ROWS ≙ micro block); each (column, chunk)
is independently encoded and zone-mapped so scans can skip chunks from
pushdown ranges (≙ blockscan + index-block skipping,
src/storage/access/ob_multiple_scan_merge.cpp:209).
"""

from __future__ import annotations

import io
import os
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from oceanbase_tpu.datatypes import SqlType
from oceanbase_tpu.storage.encoding import (
    CodedStrings,
    EncodedColumn,
    decode_column,
    encode_coded,
    encode_column,
)

CHUNK_ROWS = 65536


@dataclass
class Segment:
    """Immutable sorted-run of rows for one tablet."""

    segment_id: int
    level: int                      # 0 = mini (L0), 1 = minor, 2 = major
    n_rows: int
    columns: dict                   # name -> list[EncodedColumn] per chunk
    types: dict                     # name -> SqlType
    # commit-version range covered (MVCC): rows in this segment are visible
    # to snapshots >= max_version
    min_version: int = 0
    max_version: int = 0
    #: what readers derive from the (immutable) chunks once and keep: a
    #: string column's merged dictionary, whether the keys are unique
    cache: dict = field(default_factory=dict, repr=False, compare=False)

    @property
    def n_chunks(self) -> int:
        any_col = next(iter(self.columns.values()))
        return len(any_col)

    def nbytes(self) -> int:
        return sum(ec.nbytes() for chunks in self.columns.values()
                   for ec in chunks)

    # ------------------------------------------------------------------
    @staticmethod
    def build(segment_id: int, level: int, arrays: dict, types: dict,
              valids: dict | None = None, min_version=0, max_version=0,
              chunk_rows: int = CHUNK_ROWS) -> "Segment":
        n = len(next(iter(arrays.values()))) if arrays else 0
        cols: dict[str, list[EncodedColumn]] = {}
        for name, arr in arrays.items():
            valid = (valids or {}).get(name)
            coded = isinstance(arr, CodedStrings)
            chunks = []
            for s in range(0, max(n, 1), chunk_rows):
                e = min(s + chunk_rows, n)
                v = valid[s:e] if valid is not None else None
                chunks.append(encode_coded(arr[s:e], v) if coded
                              else encode_column(np.asarray(arr[s:e]), v))
            cols[name] = chunks
        return Segment(segment_id, level, n, cols, dict(types),
                       min_version, max_version)

    def decode(self, names=None, chunk_mask=None):
        """-> (arrays, valids) decoded host columns, optionally skipping
        chunks (zone-map pruning)."""
        names = names if names is not None else list(self.columns)
        arrays, valids = {}, {}
        for name in names:
            chunks = self.columns[name]
            parts, vparts = [], []
            has_valid = any(c.valid is not None for c in chunks)
            for i, ec in enumerate(chunks):
                if chunk_mask is not None and not chunk_mask[i]:
                    continue
                parts.append(decode_column(ec))
                if has_valid:
                    vparts.append(ec.valid if ec.valid is not None
                                  else np.ones(ec.n, dtype=bool))
            if not parts:
                dt = self.types[name].np_dtype
                arrays[name] = np.zeros(0, dtype=object
                                        if self.types[name].is_string else dt)
                valids[name] = None
                continue
            arrays[name] = np.concatenate(parts)
            valids[name] = np.concatenate(vparts) if has_valid else None
        return arrays, valids

    def prune_chunks(self, col: str, lo, hi) -> np.ndarray:
        """Zone-map chunk pruning for a range predicate on ``col``
        (≙ index-block skip, the blockscan fast path)."""
        chunks = self.columns.get(col)
        if chunks is None:
            return np.ones(self.n_chunks, dtype=bool)
        return np.array([ec.zone.may_match_range(lo, hi) for ec in chunks])

    # ------------------------------------------------------------------
    # persistence (≙ macro-block file + manifest entry)
    # ------------------------------------------------------------------
    # Integrity layout: every (column, chunk) entry carries a crc64 over
    # its encoded buffers + validity (≙ micro-block checksum), and the
    # footer carries a whole-segment digest over the meta json — which
    # transitively covers every chunk crc (≙ macro-block checksum).
    # ``load`` verifies both and raises CorruptionError instead of
    # decoding poisoned rows.
    def save(self, path: str):
        from oceanbase_tpu.storage.integrity import chunk_crc

        payload = {}
        meta = {
            "segment_id": self.segment_id, "level": self.level,
            "n_rows": self.n_rows, "min_version": self.min_version,
            "max_version": self.max_version,
            "cols": {}, "types": {},
        }
        for name, t in self.types.items():
            meta["types"][name] = [t.kind.value, t.precision, t.scale]
        for name, chunks in self.columns.items():
            meta["cols"][name] = []
            for i, ec in enumerate(chunks):
                centry = {"encoding": ec.encoding, "n": ec.n,
                          "keys": list(ec.payload),
                          "crc": chunk_crc(ec.payload, ec.valid,
                                           ec.encoding, ec.n),
                          "zone": [None if ec.zone.vmin is None else
                                   _scalar(ec.zone.vmin),
                                   None if ec.zone.vmax is None else
                                   _scalar(ec.zone.vmax),
                                   ec.zone.null_count, ec.zone.row_count]}
                for k, v in ec.payload.items():
                    payload[f"{name}/{i}/{k}"] = np.asarray(v)
                if ec.valid is not None:
                    payload[f"{name}/{i}/__valid__"] = ec.valid
                    centry["has_valid"] = True
                meta["cols"][name].append(centry)
        import json

        from oceanbase_tpu.native import crc64

        meta_json = json.dumps(meta).encode()
        payload["__meta__"] = np.frombuffer(meta_json, dtype=np.uint8)
        payload["__digest__"] = np.array([crc64(meta_json)],
                                         dtype=np.uint64)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            # stored, not deflated: the chunks are already encoded to a
            # few bits a value, and deflating a direct load's baseline
            # was a third of the load on one core
            np.savez(f, **payload)
            # fsync BEFORE the rename: without it a crash can publish
            # the name with the bytes still in the page cache — a torn
            # current-generation segment behind an "atomic" replace
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)  # atomic publish (≙ macro block seal)

    @staticmethod
    def load(path: str, verify: bool = True) -> "Segment":
        import json

        from oceanbase_tpu.datatypes import TypeKind
        from oceanbase_tpu.native import crc64
        from oceanbase_tpu.storage.encoding import ZoneMap
        from oceanbase_tpu.storage.integrity import (
            CorruptionError,
            chunk_crc,
        )

        try:
            with np.load(path, allow_pickle=True) as z:
                meta_json = bytes(z["__meta__"])
                meta = json.loads(meta_json.decode())
                if verify and "__digest__" in z.files:
                    if int(z["__digest__"][0]) != crc64(meta_json):
                        raise CorruptionError(
                            f"segment footer digest mismatch: {path}",
                            kind="segment", path=path)
                types = {n: SqlType(TypeKind(k), p, s)
                         for n, (k, p, s) in meta["types"].items()}
                cols = {}
                for name, centries in meta["cols"].items():
                    chunks = []
                    for i, ce in enumerate(centries):
                        payload = {k: z[f"{name}/{i}/{k}"]
                                   for k in ce["keys"]}
                        valid = None
                        if ce.get("has_valid"):
                            valid = z[f"{name}/{i}/__valid__"]
                        if verify and "crc" in ce and \
                                chunk_crc(payload, valid, ce["encoding"],
                                          ce["n"]) != ce["crc"]:
                            raise CorruptionError(
                                f"segment chunk crc mismatch: {path} "
                                f"column {name!r} chunk {i}",
                                kind="segment", path=path)
                        zn = ce["zone"]
                        chunks.append(EncodedColumn(
                            ce["encoding"], payload, valid,
                            ZoneMap(zn[0], zn[1], zn[2], zn[3]), ce["n"]))
                    cols[name] = chunks
        except CorruptionError:
            raise
        except Exception as e:
            # a flipped bit in the compressed container surfaces as a
            # zip/zlib/json/key error long before any crc check runs —
            # normalize to the ONE typed error read paths handle
            raise CorruptionError(
                f"segment unreadable: {path} ({e})",
                kind="segment", path=path) from e
        return Segment(meta["segment_id"], meta["level"], meta["n_rows"],
                       cols, types, meta["min_version"], meta["max_version"])


def sort_rows_by_keys(arrays: dict, valids: dict, key_cols: list[str]):
    """STABLY sort row arrays by the key columns (oldest-first order of
    equal keys is preserved, so position-based newest-wins dedup in
    ``snapshot_arrays`` stays correct).

    Key-sorted segments are the TPU build's primary index: each chunk's
    zone map on the key columns becomes a tight range, so point/range
    lookups decode only the chunks that can contain the key
    (≙ the index-block row scanner seeking macro/micro blocks,
    src/storage/blocksstable/index_block/ob_index_block_row_scanner.h)."""
    present = [k for k in key_cols if k in arrays]
    if not present:
        return arrays, valids
    n = len(next(iter(arrays.values()))) if arrays else 0
    if n <= 1:
        return arrays, valids
    sort_keys = []
    for k in reversed(present):  # lexsort: last key is primary
        a = arrays[k]
        sort_keys.append(a.codes if isinstance(a, CodedStrings) else
                         a.astype("U") if a.dtype == object else a)
    if _in_key_order(sort_keys[::-1]):
        # a stable sort of rows that arrive in key order moves none
        return arrays, valids
    order = np.lexsort(sort_keys)
    out_a = {c: a[order] for c, a in arrays.items()}
    out_v = {c: (v[order] if v is not None else None)
             for c, v in valids.items()}
    return out_a, out_v


def _in_key_order(keys: list) -> bool:
    """Whether the rows are in non-descending order of ``keys`` (first
    key primary): a few passes, where the sort is n log n."""
    tied = None         # rows whose earlier keys equal their successor's
    for a in keys:
        lo, hi = a[:-1], a[1:]
        down = lo > hi
        if (down if tied is None else down & tied).any():
            return False
        same = lo == hi
        tied = same if tied is None else tied & same
        if not tied.any():
            return True
    return True


def _scalar(v):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, (np.str_, str)):
        return str(v)
    if isinstance(v, (np.bool_,)):
        return bool(v)
    return v


def merge_segments(segment_id: int, level: int, segments: list,
                   key_cols: list[str], drop_tombstones: bool) -> Segment:
    """Compaction merge: stack rows, newest version of each key wins
    (≙ ObPartitionMerger major/minor merge,
    src/storage/compaction/ob_partition_merger.h:140).

    Segments must be given oldest-first; key_cols empty -> append-only
    merge (no dedup).  ``drop_tombstones`` must be True only when the merge
    covers EVERY level (major merge) — otherwise a tombstone may shadow a
    base row in a lower level outside the merge set and must be retained.

    The column set is the UNION across inputs: segments built from bulk
    load lack the __deleted__/__version__ bookkeeping columns that
    memtable flushes carry; missing columns fill with defaults
    (not-deleted, version = segment max_version).
    """
    if not segments:
        raise ValueError("nothing to merge")
    types: dict = {}
    for seg in segments:
        for n, t in seg.types.items():
            types.setdefault(n, t)
    all_arrays = []
    all_valids = []
    for seg in segments:
        a, v = seg.decode()
        n_rows = len(next(iter(a.values()))) if a else 0
        for n, t in types.items():
            if n not in a:
                if n == "__deleted__":
                    a[n] = np.zeros(n_rows, dtype=bool)
                elif n == "__version__":
                    a[n] = np.full(n_rows, seg.max_version, dtype=np.int64)
                else:
                    a[n] = (np.array([""] * n_rows, dtype=object)
                            if t.is_string else
                            np.zeros(n_rows, dtype=t.np_dtype))
                    v[n] = np.zeros(n_rows, dtype=bool)  # NULL-filled
        all_arrays.append(a)
        all_valids.append(v)
    names = list(types)
    stacked = {}
    stacked_valid = {}
    for n in names:
        parts = [a[n] for a in all_arrays]
        if any(p.dtype == object for p in parts):
            parts = [p.astype(object) for p in parts]
        stacked[n] = np.concatenate(parts)
        if any(v.get(n) is not None for v in all_valids):
            stacked_valid[n] = np.concatenate(
                [v[n] if v.get(n) is not None
                 else np.ones(len(a[n]), bool)
                 for v, a in zip(all_valids, all_arrays)])
    total = len(next(iter(stacked.values()))) if names else 0

    keep = np.ones(total, dtype=bool)
    if key_cols and total:
        # newest wins: iterate from the end (newest segment last)
        key_arrays = [stacked[k] for k in key_cols]
        seen: set = set()
        order = np.arange(total - 1, -1, -1)
        for idx in order:
            key = tuple(a[idx] for a in key_arrays)
            if key in seen:
                keep[idx] = False
            else:
                seen.add(key)
    if "__deleted__" in stacked and drop_tombstones:
        keep &= ~stacked["__deleted__"].astype(bool)
        del stacked["__deleted__"]
        stacked_valid.pop("__deleted__", None)
        types.pop("__deleted__", None)

    out_arrays = {n: stacked[n][keep] for n in stacked}
    out_valids = {n: v[keep] for n, v in stacked_valid.items()}
    out_arrays, out_valids = sort_rows_by_keys(out_arrays, out_valids,
                                               key_cols)
    return Segment.build(
        segment_id, level, out_arrays, types, out_valids,
        min_version=min(s.min_version for s in segments),
        max_version=max(s.max_version for s in segments),
    )
