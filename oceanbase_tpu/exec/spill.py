"""Spill-partitioned join: joins whose inputs exceed the device budget.

Reference analog: the unified hash-partitioning spill infrastructure
(ob_hp_infras_vec_op.h; recursive partition dump in
ob_hash_join_vec_op.h:413 build_hash_table_for_recursive).  The TPU
version: hash-partition BOTH sides on the join key on the host (numpy),
then run each co-partition pair through the device join — each pair fits
the device budget, partitions stream through one compiled program when
sizes are padded to a uniform capacity.

This composes with granule streaming: scan-side granules fill host
partitions, then partitions join pairwise (out-of-HBM joins, SURVEY §7
hard part (d)).
"""

from __future__ import annotations

import numpy as np

from oceanbase_tpu.exec import diag, ops
from oceanbase_tpu.expr import ir
from oceanbase_tpu.share.keyhash import mix64 as _mix64_np  # the one mixer
from oceanbase_tpu.vector import Relation, from_numpy, to_numpy


def _partition_of(arrays: dict, keys: list[str], n_parts: int) -> np.ndarray:
    h = np.zeros(len(next(iter(arrays.values()))), dtype=np.uint64)
    for k in keys:
        kv = arrays[k]
        if kv.dtype == object or kv.dtype.kind in "US":
            kv = np.array([hash(x) & 0xFFFFFFFFFFFFFFFF for x in kv],
                          dtype=np.uint64)
        h = _mix64_np(h ^ _mix64_np(kv.astype(np.int64).view(np.uint64)
                                    if kv.dtype.kind in "iu"
                                    else kv.astype(np.uint64)))
    return (h % np.uint64(n_parts)).astype(np.int64)


def partitioned_join(
    left: dict, right: dict, left_keys: list[str], right_keys: list[str],
    how: str = "inner", n_partitions: int = 8,
    left_types: dict | None = None, right_types: dict | None = None,
    out_capacity_per_part: int | None = None,
):
    """Join two host-resident column sets partition-by-partition.

    left/right: {col -> numpy array} (column names must be disjoint,
    as in the planner's join contract).  Returns (arrays, valids):
    {col -> numpy array} plus {col -> bool array} for columns carrying
    NULLs (left-join unmatched sides).  Keys hash-copartition, so every
    match lands in the same pair; per-pair capacity overflow grows the
    budget and redoes the pair (≙ recursive partition dump).
    """
    lp = _partition_of(left, left_keys, n_partitions)
    rp = _partition_of(right, right_keys, n_partitions)
    lkeys_e = [ir.col(k) for k in left_keys]
    rkeys_e = [ir.col(k) for k in right_keys]

    out_parts: list[dict] = []
    for p in range(n_partitions):
        lsel = lp == p
        rsel = rp == p
        la, ra = bool(lsel.any()), bool(rsel.any())
        if not la or (how == "inner" and not ra):
            continue
        lrel = from_numpy({k: v[lsel] for k, v in left.items()},
                          types=left_types)
        rrel = (from_numpy({k: v[rsel] for k, v in right.items()},
                           types=right_types)
                if ra else _empty_like(right, right_types))
        cap = out_capacity_per_part or max(int(lsel.sum()) * 2, 1024)
        for _attempt in range(4):
            with diag.collect() as entries:
                j = ops.join(lrel, rrel, lkeys_e, rkeys_e, how=how,
                             out_capacity=cap)
                dropped = sum(int(v) for _name, v, _cap in entries)
            if dropped == 0:
                break
            cap *= 4  # ≙ recursive re-partition: grow and redo this pair
        else:
            raise diag.CapacityOverflow(
                f"spill partition {p} still overflows at capacity {cap}")
        out_parts.append(to_numpy(j))

    if not out_parts:
        return {}, {}
    cols = [c for c in out_parts[0] if not c.startswith("__valid__")]
    arrays = {c: np.concatenate([pt[c] for pt in out_parts if c in pt])
              for c in cols}
    valids = {}
    for c in cols:
        vkey = "__valid__" + c
        if any(vkey in pt for pt in out_parts):
            valids[c] = np.concatenate(
                [pt.get(vkey, np.ones(len(pt[c]), dtype=bool))
                 for pt in out_parts])
    return arrays, valids


def partitioned_join_spilled(
    left_chunks, right_chunks, left_keys: list[str],
    right_keys: list[str], store, how: str = "inner",
    n_partitions: int = 16, left_types: dict | None = None,
    right_types: dict | None = None, budget_rows: int = 1 << 22,
    _salt: int = 0, _depth: int = 0,
):
    """Disk-tier join: inputs arrive as (arrays, valids) chunk streams,
    hash-partition to temp-file runs, then join co-partition pairs one
    pair at a time — peak host memory is one pair, everything else lives
    on disk (≙ the recursive partition dump of
    ob_hash_join_vec_op.h:413 over src/storage/tmp_file/).

    A partition pair that still exceeds ``budget_rows`` recursively
    re-partitions with a different hash salt (up to 3 levels).  Yields
    (arrays, valids) output batches."""
    lruns = [store.new_run() for _ in range(n_partitions)]
    rruns = [store.new_run() for _ in range(n_partitions)]

    def scatter(chunks, keys, runs):
        for arrays, valids in chunks:
            n = len(next(iter(arrays.values()))) if arrays else 0
            if n == 0:
                continue
            part = _partition_of_salted(arrays, keys, n_partitions, _salt)
            for p in range(n_partitions):
                sel = part == p
                if not sel.any():
                    continue
                store.append_chunk(
                    runs[p], {k: v[sel] for k, v in arrays.items()},
                    {k: (v[sel] if v is not None else None)
                     for k, v in (valids or {}).items()})

    scatter(left_chunks, left_keys, lruns)
    scatter(right_chunks, right_keys, rruns)

    for p in range(n_partitions):
        lrows = store.run(lruns[p]).n_rows
        rrows = store.run(rruns[p]).n_rows
        if lrows == 0:
            store.close_run(lruns[p])
            store.close_run(rruns[p])
            continue
        if max(lrows, rrows) > budget_rows and _depth < 3:
            # recursive re-partition of this pair with a fresh salt
            yield from partitioned_join_spilled(
                store.read_chunks(lruns[p]), store.read_chunks(rruns[p]),
                left_keys, right_keys, store, how=how,
                n_partitions=n_partitions, left_types=left_types,
                right_types=right_types, budget_rows=budget_rows,
                _salt=_salt + 1, _depth=_depth + 1)
            store.close_run(lruns[p])
            store.close_run(rruns[p])
            continue
        if how == "inner" and rrows == 0:
            store.close_run(lruns[p])
            store.close_run(rruns[p])
            continue
        la, lv = _load_run(store, lruns[p])
        if rrows:
            ra, rv = _load_run(store, rruns[p])
        else:
            # outer/anti with an empty build side: typed empty columns
            ra = {c: (np.zeros(0, dtype=object) if t.is_string
                      else np.zeros(0, dtype=t.np_dtype))
                  for c, t in (right_types or {}).items()}
            rv = {}
        store.close_run(lruns[p])
        store.close_run(rruns[p])
        arrays, valids = partitioned_join(
            la, ra, left_keys, right_keys, how=how,
            n_partitions=1, left_types=left_types,
            right_types=right_types)
        if arrays:
            yield arrays, valids


def _partition_of_salted(arrays, keys, n_parts, salt):
    if salt == 0:
        return _partition_of(arrays, keys, n_parts)
    h = np.zeros(len(next(iter(arrays.values()))), dtype=np.uint64)
    for k in keys:
        kv = arrays[k]
        if kv.dtype == object or kv.dtype.kind in "US":
            kv = np.array([hash(x) & 0xFFFFFFFFFFFFFFFF for x in kv],
                          dtype=np.uint64)
        h = _mix64_np(h ^ _mix64_np(
            kv.astype(np.int64).view(np.uint64) if kv.dtype.kind in "iu"
            else kv.astype(np.uint64)))
    h = _mix64_np(h ^ np.uint64(
        (0x9E3779B97F4A7C15 * (salt + 1)) & 0xFFFFFFFFFFFFFFFF))
    return (h % np.uint64(n_parts)).astype(np.int64)


def _load_run(store, run_id):
    parts_a, parts_v = [], []
    for arrays, valids in store.read_chunks(run_id):
        parts_a.append(arrays)
        parts_v.append(valids)
    if not parts_a:
        return {}, {}
    cols = list(parts_a[0])
    out_a = {}
    out_v = {}
    for c in cols:
        chunks = [p[c] for p in parts_a]
        if any(x.dtype == object for x in chunks):
            chunks = [x.astype(object) for x in chunks]
        out_a[c] = np.concatenate(chunks)
        if any(v.get(c) is not None for v in parts_v):
            out_v[c] = np.concatenate(
                [v[c] if v.get(c) is not None
                 else np.ones(len(a[c]), dtype=bool)
                 for v, a in zip(parts_v, parts_a)])
    return out_a, out_v


def _empty_like(arrays: dict, types):
    one = {}
    valids = {}
    for k, v in arrays.items():
        if v.dtype == object or v.dtype.kind in "US":
            one[k] = np.array([""], dtype=object)
        else:
            one[k] = np.zeros(1, dtype=v.dtype)
        valids[k] = np.array([False])
    import jax.numpy as jnp

    rel = from_numpy(one, types=types, valids=valids)
    return Relation(columns=rel.columns, mask=jnp.zeros(1, dtype=jnp.bool_))
