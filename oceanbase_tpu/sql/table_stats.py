"""ANALYZE's column statistics, computed where the column lies.

Reference analog: DBMS_STATS gather (src/share/stat/ob_opt_column_stat.h,
ob_basic_stats_estimator): row count, NDV, an equi-height histogram for a
numeric column, a most-common-values list for a string column.

A table's device copy is resident when ANALYZE runs, and a column crosses
to the host at 0.3 GB/s (PERF.md section 6, PR 34), so the statistics are
programs over the resident columns and only their answers cross: one sort
a column, then

- numeric (integers, dates, decimals, bools): the distinct count from the
  sorted lanes' changes, and the sorted values at the 2 x 65 positions
  ``np.percentile``'s linear method reads, interpolated on the host in
  its own arithmetic (``percentile_edges``): the edges are bit-equal to
  ``np.percentile`` over the fetched column;
- string (dictionary codes): the rows of each code (ranks of the code
  boundaries in the sorted codes), from which the host takes NDV and the
  top-k as it always did.

Dead and NULL lanes sort behind every live value.  Floats and vectors
keep the host path (NaN ordering, 2-D payloads): ``on_device`` says
which a column takes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIST_BUCKETS = 64
MCV_K = 16          # most-common-values kept per string column
#: values kept of a string column's row-weighted sample: a pattern that
#: 5 % of the rows match is then estimated to 3 % of itself (one sigma), so
#: that the budgets derived from it do not change from one load to the next
SAMPLE_K = 16384
#: the code-count program is compiled per dictionary size rounded up to a
#: power of two, never under this (small dictionaries share one program)
_MIN_SLOTS = 1024


def _ok(mask, valid, capacity):
    ok = jnp.ones(capacity, dtype=jnp.bool_) if mask is None else mask
    return ok if valid is None else ok & valid


@jax.jit
def _live_counts(valids: dict, mask):
    """{column: its live non-NULL rows}, and the live rows."""
    cap = mask.shape[0]
    return ({name: jnp.sum(_ok(mask, v, cap), dtype=jnp.int64)
             for name, v in valids.items()},
            jnp.sum(mask, dtype=jnp.int64))


def _sorted_live(data, mask, valid):
    """The live non-NULL values first, ascending; the rest behind them."""
    ok = _ok(mask, valid, data.shape[0])
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.int32)
    top = jnp.iinfo(data.dtype).max
    # values alone are sorted, so stability buys nothing, and the TPU
    # compiler takes half as long over an unstable sort (35 s against 70
    # for int64 at 67,108,864 lanes, compiled for a described v5e)
    return jnp.sort(jnp.where(ok, data, top), stable=False)


@jax.jit
def _numeric_program(data, mask, valid, n, lo, hi):
    """-> (distinct values among the ``n`` live ones, the sorted values
    at positions ``lo`` and at ``hi``)."""
    s = _sorted_live(data, mask, valid)
    at = jnp.arange(1, s.shape[0], dtype=jnp.int64)
    ndv = jnp.sum((s[1:] != s[:-1]) & (at < n), dtype=jnp.int64) \
        + (n > 0)
    return ndv, s[lo], s[hi]


@functools.partial(jax.jit, static_argnames=("slots",))
def _code_count_program(codes, mask, valid, slots: int):
    """-> int32[slots]: the live non-NULL rows of each code in
    ``[0, slots)``."""
    ok = _ok(mask, valid, codes.shape[0]) & (codes >= 0)
    s = jnp.sort(jnp.where(ok, codes, jnp.iinfo(jnp.int32).max),
                 stable=False)
    bounds = jnp.searchsorted(
        s, jnp.arange(slots + 1, dtype=jnp.int32), side="left")
    return (bounds[1:] - bounds[:-1]).astype(jnp.int32)


def percentile_positions(n: int, buckets: int = HIST_BUCKETS):
    """What ``np.percentile(a, linspace(0, 100, buckets + 1))`` reads of
    a sorted ``a`` of ``n`` values, linear method -> (previous indexes,
    next indexes, gamma), in NumPy's own arithmetic
    (``numpy.lib._function_base_impl._quantile``)."""
    q = np.true_divide(np.linspace(0, 100, buckets + 1), 100)
    virtual = n * q + (1 + q * (1 - 1 - 1)) - 1
    previous = np.floor(virtual).astype(np.intp)
    nxt = previous + 1
    above = virtual >= n - 1
    previous[above] = -1
    nxt[above] = -1
    below = virtual < 0
    previous[below] = 0
    nxt[below] = 0
    gamma = np.asanyarray(virtual - previous)
    return previous % n, nxt % n, gamma


def percentile_edges(previous: np.ndarray, nxt: np.ndarray,
                     gamma: np.ndarray) -> np.ndarray:
    """NumPy's ``_lerp`` between the values read at the two positions."""
    diff = np.subtract(nxt, previous)
    out = np.asanyarray(np.add(previous, diff * gamma))
    np.subtract(nxt, diff * (1 - gamma), out=out, where=gamma >= 0.5,
                casting="unsafe", dtype=type(out.dtype))
    return out


def on_device(col) -> bool:
    """Whether ``col``'s statistics are computed by a device program."""
    return col.data.ndim == 1 and col.data.dtype.kind in "iub"


def live_counts(rel) -> tuple[dict, int]:
    """-> ({column: live non-NULL rows}, live rows) in one program."""
    counts, n = _live_counts({name: c.valid
                              for name, c in rel.columns.items()},
                             rel.mask_or_true())
    counts, n = jax.device_get((counts, n))
    return {k: int(v) for k, v in counts.items()}, int(n)


def numeric_stats(col, mask, n_valid: int):
    """-> (NDV, histogram edges | None) of an integer-kind column with
    ``n_valid`` live non-NULL rows."""
    if n_valid == 0:
        return 1, None
    lo, hi, gamma = percentile_positions(n_valid)
    ndv, at_lo, at_hi = jax.device_get(_numeric_program(
        col.data, mask, col.valid, np.int64(n_valid), lo, hi))
    edges = None
    if n_valid >= HIST_BUCKETS and col.data.dtype.kind in "iu":
        edges = percentile_edges(at_lo, at_hi, gamma)
    return int(ndv), edges


def code_counts(col, mask) -> np.ndarray:
    """Live non-NULL rows of every code of a dictionary column."""
    size = col.sdict.size
    slots = max(_MIN_SLOTS, 1 << max(size - 1, 0).bit_length())
    return np.asarray(jax.device_get(_code_count_program(
        col.data, mask, col.valid, slots)))[:size].astype(np.int64)


def column_stats(col, mask, n: int, n_valid: int):
    """One column's statistics -> (NDV, detail | None): for a dictionary
    column ``(values, frequencies, sample)``: its ``MCV_K`` most frequent
    strings and the strings at ``SAMPLE_K`` evenly spaced ranks of its
    rows; for any other ``(edges, null fraction)`` of an equi-height
    histogram when it has ``HIST_BUCKETS`` live values or more."""
    if col.sdict is not None:
        counts = code_counts(col, mask)
        codes = np.flatnonzero(counts)
        counts = counts[codes]
        if not len(codes):
            return 1, None
        # top-k by measured frequency: string-equality selectivity reads
        # this instead of the 0.1 guess
        order = np.argsort(counts)[::-1][:MCV_K]
        total = max(int(counts.sum()), 1)
        # the rows in code order, read at evenly spaced ranks: a code is
        # drawn in proportion to its rows (LIKE selectivity reads this)
        ranks = (np.arange(SAMPLE_K) + 0.5) * total / SAMPLE_K
        drawn = codes[np.searchsorted(np.cumsum(counts), ranks,
                                      side="right").clip(0, len(codes) - 1)]
        return len(codes), (
            [str(col.sdict.values[int(codes[i])]) for i in order],
            [float(counts[i]) / total for i in order],
            tuple(str(col.sdict.values[int(c)]) for c in drawn))
    if on_device(col):
        ndv, edges = numeric_stats(col, mask, n_valid)
    else:
        # floats (NaN ordering) and vectors: the column crosses
        live = np.asarray(mask)
        data = np.asarray(col.data)[live]
        if col.valid is not None:
            data = data[np.asarray(col.valid)[live]]
        ndv = int(len(np.unique(data))) if len(data) else 1
        edges = None
        if len(data) >= HIST_BUCKETS and data.dtype.kind in "iuf":
            edges = np.percentile(data, np.linspace(0, 100,
                                                    HIST_BUCKETS + 1))
    if edges is None:
        return ndv, None
    null_frac = 0.0 if col.valid is None else 1.0 - (n_valid / max(n, 1))
    return ndv, (edges, float(null_frac))
