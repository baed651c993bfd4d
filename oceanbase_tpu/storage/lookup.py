"""Index-aware point and range lookups over the tablet LSM.

Reference analog: the DAS iterator stack walking index-block B+-trees to
seek micro blocks (src/sql/das/iter/ob_das_iter.h,
src/storage/blocksstable/index_block/ob_index_block_row_scanner.h).  The
TPU build's segments are key-sorted with per-chunk zone maps on the key
columns (see storage/segment.py::sort_rows_by_keys), so a lookup prunes
to the few chunks whose zone ranges cover the key and decodes only those
— a point ``get`` touches O(chunks-holding-key) rows, not the whole
segment.

All work here is host-side numpy: point/small-range operations are
latency-bound, and a device dispatch costs orders of magnitude more than
decoding one 64k-row chunk on the host.
"""

from __future__ import annotations

import numpy as np


def _base_tablets(tablet, key=None):
    """Resolve the physical tablets a key could live in."""
    parts = getattr(tablet, "partitions", None)
    if parts is None:
        return [tablet]
    if key is not None:
        t = tablet._route_key(key)
        if t is not None:
            return [t]
    return list(parts)


def _chunk_mask(seg, ranges: dict):
    """AND of per-column zone-map prunes; None -> nothing survives."""
    cm = np.ones(seg.n_chunks, dtype=bool)
    for col, (lo, hi) in ranges.items():
        cm &= seg.prune_chunks(col, lo, hi)
    if not cm.any():
        return None
    return cm


def estimate_in_ranges(tablet, ranges: dict) -> tuple[int, int]:
    """(upper bound on rows, segment chunks) a pruned scan would decode
    (zone-map metadata only — no decode).  Feeds the access-path cost
    decision; the chunks are ``dml.candidates``' tag."""
    total = chunks = 0
    for t in _base_tablets(tablet):
        sub = {k: v for k, v in ranges.items() if k in t.key_cols}
        for seg in t.segments:
            if not sub:
                total += seg.n_rows
                chunks += seg.n_chunks
                continue
            cm = _chunk_mask(seg, sub)
            if cm is None:
                continue
            any_col = next(iter(seg.columns.values()))
            kept = np.nonzero(cm)[0]
            total += sum(any_col[i].n for i in kept)
            chunks += len(kept)
        within = t.key_ranges(sub)
        total += sum(len(m.keys_within(within)) if within else len(m)
                     for m in [t.active] + t.frozen)
    return total, chunks


_INF = 2**62


def _tablet_newest(t, key: tuple, snapshot: int, tx_id: int):
    """Newest visible version of ``key`` in one physical tablet ->
    (commit_version, row-values | None-if-tombstone, found)."""
    for mt in [t.active] + t.frozen[::-1]:
        v = mt.visible_version(key, snapshot, tx_id)
        if v is not None:
            # own uncommitted writes (commit_version 0) are newest of all
            ver = v.commit_version or _INF
            row = None if v.op == "delete" else dict(v.values)
            return ver, row, True
    ranges = {kc: (kv, kv) for kc, kv in zip(t.key_cols, key)
              if kv is not None}
    best = None
    best_ver = -1
    found = False
    for seg in t.segments[::-1]:
        if seg.min_version > snapshot:
            continue
        cm = _chunk_mask(seg, ranges) if ranges else \
            np.ones(seg.n_chunks, dtype=bool)
        if cm is None:
            continue
        arrays, valids = seg.decode(chunk_mask=None if cm.all() else cm)
        n = len(next(iter(arrays.values()))) if arrays else 0
        if n == 0:
            continue
        sel = np.ones(n, dtype=bool)
        for kc, kv in zip(t.key_cols, key):
            col = arrays[kc]
            vd = valids.get(kc)
            if kv is None:
                sel &= (~vd if vd is not None
                        else np.zeros(n, dtype=bool))
            else:
                sel &= col == kv
                if vd is not None:
                    sel &= vd
        if "__version__" in arrays:
            sel &= arrays["__version__"] <= snapshot
        idx = np.nonzero(sel)[0]
        if len(idx) == 0:
            continue
        vers = arrays.get("__version__")
        i = idx[-1] if vers is None else idx[np.argmax(vers[idx])]
        ver = int(vers[i]) if vers is not None else seg.max_version
        if ver > best_ver:
            best_ver = ver
            found = True
            if arrays.get("__deleted__") is not None and \
                    arrays["__deleted__"][i]:
                best = None
            else:
                row = {}
                for c in t.columns:
                    if c not in arrays:
                        continue
                    vd = valids.get(c)
                    row[c] = (None if vd is not None and not vd[i]
                              else arrays[c][i].item()
                              if hasattr(arrays[c][i], "item")
                              else arrays[c][i])
                best = row
    return best_ver, best, found


def point_lookup(tablet, key: tuple, snapshot: int, tx_id: int = 0):
    """Newest visible row for ``key`` -> values dict | None (absent or
    deleted).

    Memtables are probed newest-first (their versions are strictly newer
    than flushed segments for the same key); segments are probed with
    zone-map pruning on every key column, decoding only surviving chunks.
    When the key cannot be routed to one partition, EVERY candidate
    partition is consulted and the newest version wins — a
    partition-moving update leaves a tombstone in the old partition and a
    live row (same commit version) in the new one, and the live row must
    win the tie."""
    best_ver = -1
    best = None
    for t in _base_tablets(tablet, key):
        ver, row, found = _tablet_newest(t, key, snapshot, tx_id)
        if not found:
            continue
        if ver > best_ver or (ver == best_ver and row is not None):
            best_ver = ver
            best = row
    return best


def range_rows(tablet, ranges: dict, snapshot: int, tx_id: int = 0,
               columns=None):
    """All live rows whose key columns fall in ``ranges`` (inclusive) ->
    (arrays, valids).  Built on the pruned snapshot read, then exactly
    filtered — the result is snapshot-consistent, not a superset."""
    sub = {k: v for k, v in ranges.items()
           if k in tablet.key_cols or k == getattr(tablet, "part_col", None)}
    arrays, valids = tablet.snapshot_arrays(snapshot, tx_id, prune=sub)
    n = len(next(iter(arrays.values()))) if arrays else 0
    if n == 0:
        return arrays, valids
    sel = np.ones(n, dtype=bool)
    for col, (lo, hi) in ranges.items():
        a = arrays[col]
        vd = valids.get(col)
        if vd is not None:
            sel &= vd
        if lo is not None:
            sel &= a >= lo
        if hi is not None:
            sel &= a <= hi
    names = columns if columns is not None else list(arrays)
    return ({c: arrays[c][sel] for c in names},
            {c: (valids[c][sel] if valids.get(c) is not None else None)
             for c in names})
