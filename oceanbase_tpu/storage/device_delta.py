"""A table's device relation kept current by committed deltas.

Reference analog: OceanBase's column store read as a baseline merged with
the row-format increments (``WITH COLUMN GROUP``): the baseline here is
the device relation ``StorageCatalog._device_copy`` built, an increment
what ``Tablet.delta_since`` reads from the commit log and the memtables,
and the merge happens once, on the device, when the next statement reads
the table:

- the lane of every key a commit deleted or superseded goes dead in the
  row mask (the key's lane comes from a host index, ``KeyIndex``);
- every new version is written into the dead pad lanes after the live
  high-water mark, validity alike;
- a string column keeps its SORTED dictionary: values it lacks are merged
  in and the codes at or after each insertion point move up, by compares
  against the insertion points (no gather), ``NEW_CODES`` of them a pass.

Capacity, dtypes and pytree structure stay the baseline's, so a plan
compiled for the baseline runs on.  JAX arrays are immutable: a statement
that holds the old relation keeps its snapshot, and no buffer is donated.
Every delta goes through in chunks of ``DELTA_LANES`` rows, so one program
a table layout serves every delta size and compiles with the first commit.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from oceanbase_tpu.storage.keyindex import KeyIndex
from oceanbase_tpu.vector.column import Column, Relation

#: rows (and cleared lanes) a chunk of a delta carries
DELTA_LANES = 8192
#: dictionary insertion points one pass over a string column's codes takes
NEW_CODES = 256

_INT32_MAX = np.iinfo(np.int32).max


@dataclass(slots=True)
class DeviceCopy:
    """What the relation cache holds for a table: the relation, what it is
    current for, and what the next delta needs."""

    version: int            # tablet.data_version it is current for
    rel: Relation
    tablet: object          # the tablet object (TRUNCATE swaps it)
    snapshot: int           # every commit <= this is in ``rel``
    mark: tuple | None      # Tablet.delta_mark() it stands at
    high: int               # lanes [high, capacity) never held a row
    live: int               # live rows
    index: KeyIndex | None = None   # built by the first delta


class PadExhausted(Exception):
    """The delta's rows do not fit the dead pad lanes that are left."""


@jax.jit
def _apply_chunk(cols, mask, clear, first, n, rows):
    """One chunk: ``clear`` lanes go dead, then rows ``[0, n)`` of ``rows``
    land in lanes ``[first, first + n)``.  ``cols`` / ``rows``: name ->
    (data, validity | None); lanes at or past the capacity are dropped."""
    cap = mask.shape[0]
    at = jnp.arange(clear.shape[0], dtype=jnp.int32)
    lanes = jnp.where(at < n, first + at, cap + at)
    hints = dict(mode="drop", indices_are_sorted=True, unique_indices=True)
    mask = mask.at[clear].set(False, **hints).at[lanes].set(True, **hints)
    out = {}
    for name, (data, valid) in cols.items():
        vals, vvalid = rows[name]
        data = data.at[lanes].set(vals, **hints)
        if valid is not None:
            valid = valid.at[lanes].set(vvalid, **hints)
        out[name] = (data, valid)
    return out, mask


@jax.jit
def _shift_codes(codes, at):
    """Codes of a dictionary that gained values before positions ``at``
    (ascending, padded with INT32_MAX): each moves up by the insertion
    points at or below it."""
    return codes + jnp.sum(codes[:, None] >= at[None, :], axis=1,
                           dtype=codes.dtype)


def _grown(col: Column, values, valid):
    """``col`` with the strings of ``values`` in its dictionary -> (data,
    dictionary, the rows' codes)."""
    strings = values if valid is None else values[valid]
    sdict, at = col.sdict.merged(strings)
    data = col.data
    if at is not None:
        # highest insertion points first: a code a pass moved up stays
        # above every lower point, so each pass compares as the old code
        for hi in range(len(at), 0, -NEW_CODES):
            part = at[max(hi - NEW_CODES, 0):hi]
            padded = np.full(NEW_CODES, _INT32_MAX, dtype=np.int32)
            padded[:len(part)] = part
            data = _shift_codes(data, padded)
    codes = sdict.codes_of(np.where(valid, values, sdict.values[0])
                           if valid is not None else values)
    return data, sdict, codes


def delta_bytes(rel: Relation, n_rows: int, n_cleared: int) -> int:
    """The delta's own bytes: rows written x the lane width of every
    column with its validity and the row mask, plus lanes cleared x the
    mask's width."""
    lane = 1                                    # the row mask, a bool
    for c in rel.columns.values():
        lane += c.data.dtype.itemsize * int(np.prod(c.data.shape[1:]))
        if c.valid is not None:
            lane += c.valid.dtype.itemsize
    return n_rows * lane + n_cleared


def apply_delta(copy: DeviceCopy, delta, key_cols) -> tuple:
    """``copy.rel`` with ``delta`` merged in -> (relation, high, live,
    rows written, lanes cleared).  Raises ``PadExhausted`` before anything
    changed when the rows do not fit."""
    rel = copy.rel
    cap = rel.capacity
    n = len(delta.row_keys)
    if copy.high + n > cap:
        raise PadExhausted
    if copy.index is None:
        copy.index = KeyIndex.from_relation(rel, key_cols)
    old = copy.index.take(delta.keys)
    old = np.sort(old[old >= 0]).astype(np.int32)
    copy.index.put(delta.row_keys, copy.high)

    cols, sdicts, rows = {}, {}, {}
    for name, col in rel.columns.items():
        vals = delta.arrays[name]
        vvalid = delta.valids.get(name)
        data, valid = col.data, col.valid
        if col.sdict is not None:
            data, sdicts[name], vals = _grown(col, vals, vvalid)
        if vvalid is not None and valid is None:
            # the column's first NULL: it gets a validity array, as a
            # rebuilt copy would (plans that read it compile again)
            valid = jnp.ones(cap, dtype=jnp.bool_)
        cols[name] = (data, valid)
        rows[name] = (np.asarray(vals).astype(data.dtype).reshape(
            (n,) + tuple(data.shape[1:])),
                      None if valid is None else
                      np.ones(n, dtype=bool) if vvalid is None else vvalid)

    mask = rel.mask_or_true()
    for lo in range(0, max(n, len(old)), DELTA_LANES):
        m = max(min(n - lo, DELTA_LANES), 0)
        clear = cap + np.arange(DELTA_LANES, dtype=np.int32)
        part = old[lo:lo + DELTA_LANES]
        clear[:len(part)] = part
        chunk = {}
        for name, (vals, vvalid) in rows.items():
            pv = np.zeros((DELTA_LANES,) + vals.shape[1:], dtype=vals.dtype)
            pv[:m] = vals[lo:lo + m]
            pvalid = None
            if vvalid is not None:
                pvalid = np.zeros(DELTA_LANES, dtype=bool)
                pvalid[:m] = vvalid[lo:lo + m]
            chunk[name] = (pv, pvalid)
        cols, mask = _apply_chunk(cols, mask, clear,
                                  np.int32(copy.high + lo), np.int32(m),
                                  chunk)
    out = Relation(
        columns={name: Column(data=cols[name][0], valid=cols[name][1],
                              dtype=col.dtype,
                              sdict=sdicts.get(name, col.sdict))
                 for name, col in rel.columns.items()},
        mask=mask)
    jax.block_until_ready(mask)
    return out, copy.high + n, copy.live + n - len(old), n, len(old)
