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

How a chunk is written.  New rows always land in the run of neighbouring
lanes ``[first, first + n)`` after the high-water mark, so each column (its
validity and the mask's new lanes alike) takes them as ONE contiguous window
of ``W = min(DELTA_LANES, capacity)`` lanes: the old window is read
(``dynamic_slice``), the chunk's rows are rolled to their offset inside it,
the lanes outside ``[first, first + n)`` keep their old values (``where``),
and the window goes back with one ``dynamic_update_slice``: the column is
copied once and ``W`` lanes are updated in place in the copy (0.6 us of
device time for a 64-bit column of 8,388,608 lanes, beside the copy), where
a scatter of the same rows rewrote its whole operand inside the scatter's
fusion (0.57-1.1 ms for that column; PERF.md section 6, PR 41).
``dynamic_update_slice`` CLAMPS a start whose window would pass the end,
which would shift the rows down onto live lanes, so the program computes
``start = min(first, capacity - W)`` itself and places the rows at
``first - start``.  ``W`` follows from the relation's shape: a table under a
chunk's size takes the same program with a smaller window.  The lanes that
go dead lie anywhere in the table and stay one scatter over the mask alone.

Capacity, dtypes and pytree structure stay the baseline's, so a plan
compiled for the baseline runs on.  Nothing is donated: JAX arrays are
immutable and a statement may still hold the old relation, which keeps its
snapshot whole (whether no one holds it is not this module's to know).
Every delta goes through in chunks of ``W`` rows, so one program a table
layout serves every delta size and compiles with the first commit.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from oceanbase_tpu.storage.keyindex import KeyIndex
from oceanbase_tpu.vector.column import Column, Relation, factorize_strings

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
    """One chunk: ``clear`` lanes go dead (lanes at or past the capacity
    are dropped), then rows ``[0, n)`` of ``rows`` land in lanes ``[first,
    first + n)``, one window a column.  ``cols`` / ``rows``: name -> (data,
    validity | None); ``rows`` and ``clear`` are ``W`` long."""
    cap, w = mask.shape[0], clear.shape[0]
    start = jnp.minimum(first, cap - w)     # the clamp, made here
    off = first - start
    at = jnp.arange(w, dtype=jnp.int32)
    new = (at >= off) & (at < off + n)

    def write(data, vals):
        old = lax.dynamic_slice_in_dim(data, start, w)
        placed = jnp.roll(vals, off, axis=0)
        here = new.reshape((w,) + (1,) * (data.ndim - 1))
        return lax.dynamic_update_slice_in_dim(
            data, jnp.where(here, placed, old), start, axis=0)

    mask = mask.at[clear].set(False, mode="drop", indices_are_sorted=True,
                              unique_indices=True)
    mask = write(mask, jnp.ones(w, dtype=mask.dtype))
    out = {}
    for name, (data, valid) in cols.items():
        vals, vvalid = rows[name]
        out[name] = (write(data, vals),
                     None if valid is None else write(valid, vvalid))
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
    dictionary, the rows' codes).  The rows' strings take one hash pass
    (``factorize_strings``); only the distinct ones are merged and looked
    up in the dictionary."""
    local, distinct = factorize_strings(
        np.asarray(values if valid is None else values[valid], dtype=object))
    sdict, at = col.sdict.merged(distinct)
    data = col.data
    if at is not None:
        # highest insertion points first: a code a pass moved up stays
        # above every lower point, so each pass compares as the old code
        for hi in range(len(at), 0, -NEW_CODES):
            part = at[max(hi - NEW_CODES, 0):hi]
            padded = np.full(NEW_CODES, _INT32_MAX, dtype=np.int32)
            padded[:len(part)] = part
            data = _shift_codes(data, padded)
    codes = sdict.codes_of(distinct)[local]
    if valid is not None:       # a NULL row's code is never read
        codes, rows = np.zeros(len(values), dtype=np.int32), codes
        codes[valid] = rows
    return data, sdict, codes


def _window(capacity: int) -> int:
    """Lanes a chunk's window spans: static, from the relation's shape."""
    return min(DELTA_LANES, capacity)


def delta_chunks(capacity: int, n_rows: int, n_cleared: int) -> int:
    """Chunks (``_apply_chunk`` programs run) a delta of that size takes."""
    return -(-max(n_rows, n_cleared) // _window(capacity))


def new_codes(old: Relation, new: Relation) -> int:
    """Dictionary insertion points an apply brought, summed over the string
    columns: each ``NEW_CODES`` of a column cost one pass over its codes."""
    return sum(c.sdict.size - old.columns[name].sdict.size
               for name, c in new.columns.items() if c.sdict is not None)


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
    w = _window(cap)
    for lo in range(0, max(n, len(old)), w):
        m = max(min(n - lo, w), 0)
        clear = cap + np.arange(w, dtype=np.int32)
        part = old[lo:lo + w]
        clear[:len(part)] = part
        chunk = {}
        for name, (vals, vvalid) in rows.items():
            pv = np.zeros((w,) + vals.shape[1:], dtype=vals.dtype)
            pv[:m] = vals[lo:lo + m]
            pvalid = None
            if vvalid is not None:
                pvalid = np.zeros(w, dtype=bool)
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
