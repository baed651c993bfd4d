"""Column and Relation: the device-resident batch formats.

Reference analogs:
- ``Column``   ≙ ObIVector + null bitmap (src/share/vector/ob_i_vector.h:472,
  src/share/vector/ob_bitmap_null_vector_base.h) — but as a dense SoA jax
  array plus a validity array, registered as a pytree so whole relations can
  flow through jit/shard_map.
- ``Relation`` ≙ ObBatchRows (src/sql/engine/ob_batch_rows.h:19-67): a set of
  column vectors plus a skip bitmap.  We keep the *mask* convention
  (True = live row) instead of the reference's skip (True = dead row).

Design rule (SURVEY §7 hard part (b)): operators carry the mask instead of
compacting, exactly like the reference keeps skip bitmaps; compaction happens
only where an operator genuinely needs dense rows (sorts, exchanges).

Strings are dictionary codes (int32) with the dictionary on the host
(``StringDict``), order-preserving so comparisons work on codes — the TPU
re-imagination of VEC_DISCRETE + cs_encoding dict encoding
(src/storage/blocksstable/cs_encoding/ob_dict_column_decoder_simd.cpp).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from oceanbase_tpu.datatypes import SqlType, TypeKind
from oceanbase_tpu.server import metrics as qmetrics

# ---------------------------------------------------------------------------
# capacity bucket ladder (the static-shape policy)
# ---------------------------------------------------------------------------

DEFAULT_BUCKET_FLOOR = 64
DEFAULT_BUCKET_GROWTH = 2.0


def bucket_capacity(n: int, floor: int = DEFAULT_BUCKET_FLOOR,
                    growth: float = DEFAULT_BUCKET_GROWTH) -> int:
    """Smallest ladder capacity >= ``n``.

    The ladder is geometric: ``floor, floor*g, floor*g^2, ...`` — so a
    relation growing row-by-row passes through O(log n) distinct
    capacities instead of O(n).  Every consumer of padded relations
    (aggregates, joins, sorts) is mask-aware, which makes the dead pad
    lanes invisible; what the ladder buys is XLA executable reuse:
    ``jax.jit`` retraces per input *shape*, so two snapshots inside one
    bucket share a compiled plan.
    """
    cap = max(int(floor), 1)
    n = max(int(n), 1)
    g = max(float(growth), 1.125)  # guard against a degenerate ladder
    while cap < n:
        cap = max(cap + 1, int(math.ceil(cap * g)))
    return cap


@dataclass(frozen=True, eq=False)  # content hash via digest (see below)
class StringDict:
    """Order-preserving dictionary for one string column.

    ``values`` is a sorted numpy array of unique python strings; a column
    stores int32 codes indexing it.  Code -1 is reserved for NULL payloads
    (the validity array is authoritative; -1 just keeps gathers in range
    after clamping).

    Equality/hash are CONTENT-based (a lazily cached digest of the sorted
    values): two materializations of the same table produce distinct dict
    objects with identical encodings, and jit keys compiled executables on
    pytree aux data via ``__eq__`` — identity semantics would force a
    retrace per materialization even when nothing changed.  Trace-time
    host translations bake in ``values``, so equal content implies
    identical traced behavior.
    """

    values: np.ndarray  # dtype=object or <U*, sorted ascending

    def __post_init__(self):
        assert self.values.ndim == 1

    def _content_digest(self) -> int:
        d = self.__dict__.get("_digest")
        if d is None:
            import hashlib

            a = self.values
            u = a.astype("U") if a.dtype == object else np.ascontiguousarray(a)
            h = hashlib.blake2b(digest_size=8)
            h.update(str(u.dtype).encode())
            h.update(u.tobytes())
            d = int.from_bytes(h.digest(), "little")
            object.__setattr__(self, "_digest", d)
        return d

    def __hash__(self):
        return self._content_digest()

    def __eq__(self, other):
        if self is other:
            return True
        if not isinstance(other, StringDict):
            return NotImplemented
        return (self.values.shape == other.values.shape
                and self._content_digest() == other._content_digest())

    @property
    def size(self) -> int:
        return int(self.values.shape[0])

    def code_of(self, s: str) -> int:
        """Exact code of ``s`` or -1 if absent."""
        i = int(np.searchsorted(self.values, s))
        if i < self.size and self.values[i] == s:
            return i
        return -1

    def lower_bound(self, s: str) -> int:
        return int(np.searchsorted(self.values, s, side="left"))

    def upper_bound(self, s: str) -> int:
        return int(np.searchsorted(self.values, s, side="right"))

    def lut(self, fn) -> np.ndarray:
        """Evaluate a host predicate/transform over every dict value.

        This is how LIKE / SUBSTRING / arbitrary string functions run in the
        TPU build: O(|dict|) host work producing a lookup table, then a
        device gather ``lut[codes]`` — never per-row string work on device.
        """
        return np.array([fn(v) for v in self.values])

    def merged(self, strings: np.ndarray):
        """This dictionary with the values of ``strings`` it lacks merged
        in, still sorted -> (dictionary, positions): ``positions`` are the
        places in THIS dictionary before which the new values went, in
        ascending order, so a code ``c`` of this dictionary becomes
        ``c + count(positions <= c)`` in the new one.  ``(self, None)``
        when nothing is new.  The new dictionary's digest is chained from
        this one's and what was merged, not hashed over all the values."""
        new = np.unique(np.asarray(strings, dtype=object))
        at = np.searchsorted(self.values, new)
        have = at < self.size
        have[have] = self.values[at[have]] == new[have]
        new, at = new[~have], at[~have]
        if len(new) == 0:
            return self, None
        out = StringDict(np.insert(self.values.astype(object), at, new))
        import hashlib

        h = hashlib.blake2b(digest_size=8)
        h.update(self._content_digest().to_bytes(8, "little"))
        h.update(at.astype(np.int64).tobytes())
        h.update("\0".join(new.tolist()).encode())
        object.__setattr__(out, "_digest",
                           int.from_bytes(h.digest(), "little"))
        return out, at.astype(np.int32)

    def codes_of(self, strings: np.ndarray) -> np.ndarray:
        """int32 codes of values this dictionary holds."""
        return np.searchsorted(self.values, np.asarray(
            strings, dtype=object)).astype(np.int32)

    @staticmethod
    def encode(strings: np.ndarray) -> tuple[np.ndarray, "StringDict"]:
        """Encode raw strings -> (int32 codes, dict)."""
        codes, values = factorize_strings(np.asarray(strings))
        return codes, StringDict(values)


def factorize_strings(strings: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """-> (int32 codes, the distinct strings sorted ascending), with
    ``values[codes]`` the input.  An array of Python strings takes ONE
    hash pass by value (``pandas.factorize``: 60-70 ns a row, whether the
    rows share their string objects or not) and a sort of the distinct
    strings alone, where ``np.unique`` sorts every row's string (1.3 us a
    row).  pandas is a requirement (``requirements.txt``): there is no
    second way.  A fixed-width array is NumPy's own to sort."""
    if strings.dtype != object or strings.ndim != 1:
        values, codes = np.unique(strings, return_inverse=True)
        return codes.astype(np.int32).reshape(-1), values
    from pandas import factorize

    first_seen, distinct = factorize(strings)
    if len(first_seen) and first_seen.min() < 0:
        # as ``np.unique``'s sort says it; a NULL is the validity's to say
        raise TypeError("a NULL among the strings of a column")
    distinct = np.asarray(distinct, dtype=object)
    order = np.argsort(distinct, kind="stable")
    rank = np.empty(len(order), dtype=np.int32)
    rank[order] = np.arange(len(order), dtype=np.int32)
    return rank[first_seen], distinct[order]


@jax.tree_util.register_pytree_node_class
@dataclass
class Column:
    """One column vector: dense data + optional validity, plus static metadata.

    ``data``  — jax array, shape [n]
    ``valid`` — optional bool jax array, shape [n]; None means all-valid
    ``dtype`` — SqlType (static/aux)
    ``sdict`` — StringDict for string columns (static/aux, host-side)
    """

    data: Any
    valid: Optional[Any] = None
    dtype: SqlType = field(default_factory=SqlType.int_)
    sdict: Optional[StringDict] = None

    # -- pytree protocol (dtype/sdict are static aux data) ---------------
    def tree_flatten(self):
        return (self.data, self.valid), (self.dtype, self.sdict)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, valid = children
        dtype, sdict = aux
        return cls(data=data, valid=valid, dtype=dtype, sdict=sdict)

    # --------------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def valid_or_true(self):
        if self.valid is None:
            return jnp.ones(self.data.shape[0], dtype=jnp.bool_)
        return self.valid

    def with_data(self, data, valid="__keep__") -> "Column":
        v = self.valid if valid == "__keep__" else valid
        return Column(data=data, valid=v, dtype=self.dtype, sdict=self.sdict)

    def gather(self, idx) -> "Column":
        """Row gather (used by sorts/joins); clamps are caller's concern."""
        data = jnp.take(self.data, idx, axis=0, mode="clip")
        valid = None
        if self.valid is not None:
            valid = jnp.take(self.valid, idx, axis=0, mode="clip")
        return self.with_data(data, valid)

    def pad_to(self, capacity: int) -> "Column":
        """Extend to ``capacity`` rows with dead lanes (zero payload —
        in-range code 0 for dictionary-encoded strings — and invalid
        when a validity array exists).  Liveness is the Relation mask's
        concern; the StringDict is shared unchanged."""
        n = self.data.shape[0]
        if capacity <= n:
            return self
        pad = capacity - n
        zeros = jnp.zeros((pad,) + self.data.shape[1:],
                          dtype=self.data.dtype)
        data = jnp.concatenate([self.data, zeros])
        valid = None
        if self.valid is not None:
            valid = jnp.concatenate(
                [self.valid, jnp.zeros(pad, dtype=jnp.bool_)])
        return Column(data=data, valid=valid, dtype=self.dtype,
                      sdict=self.sdict)


@jax.tree_util.register_pytree_node_class
@dataclass
class Relation:
    """A batch of rows: named columns + live-row mask (≙ ObBatchRows).

    ``mask`` is None when every row in [0, capacity) is live
    (≙ all_rows_active_ fast path, src/sql/engine/ob_batch_rows.h:61).
    All columns share one capacity; the live row count is ``mask.sum()``
    (a device scalar — never forced to host inside a compiled plan).
    """

    columns: dict[str, Column]
    mask: Optional[Any] = None

    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        return ((tuple(self.columns[n] for n in names), self.mask), names)

    @classmethod
    def tree_unflatten(cls, names, children):
        cols, mask = children
        return cls(columns=dict(zip(names, cols)), mask=mask)

    # --------------------------------------------------------------------
    @property
    def capacity(self) -> int:
        for c in self.columns.values():
            return c.capacity
        return 0

    def mask_or_true(self):
        if self.mask is None:
            return jnp.ones(self.capacity, dtype=jnp.bool_)
        return self.mask

    def count(self):
        """Live row count as a device scalar."""
        if self.mask is None:
            return jnp.asarray(self.capacity, dtype=jnp.int64)
        return jnp.sum(self.mask.astype(jnp.int64))

    def column(self, name: str) -> Column:
        return self.columns[name]

    def with_mask(self, mask) -> "Relation":
        return Relation(columns=self.columns, mask=mask)

    def select(self, names) -> "Relation":
        return Relation(
            columns={n: self.columns[n] for n in names}, mask=self.mask
        )

    def gather(self, idx, mask=None) -> "Relation":
        return Relation(
            columns={n: c.gather(idx) for n, c in self.columns.items()},
            mask=mask,
        )

    def pad_to(self, capacity: int) -> "Relation":
        """Pad every column to ``capacity`` with the extra lanes dead in
        the mask.  The mask is ALWAYS materialized (even when no padding
        is needed): mask=None and mask=array are different pytree
        structures, and a relation that flips between them as its live
        count crosses a bucket boundary would retrace compiled plans the
        bucket ladder exists to preserve."""
        n = self.capacity
        if capacity < n:
            raise ValueError(
                f"pad_to({capacity}) below current capacity {n}")
        mask = self.mask_or_true()
        if capacity > n:
            mask = jnp.concatenate(
                [mask, jnp.zeros(capacity - n, dtype=jnp.bool_)])
        return Relation(
            columns={nm: c.pad_to(capacity)
                     for nm, c in self.columns.items()},
            mask=mask,
        )


# ---------------------------------------------------------------------------
# Host <-> device conversion
# ---------------------------------------------------------------------------


@dataclass
class HostColumn:
    """One column encoded on the host as the device will hold it: what
    ``from_numpy`` copies over, and what a direct load builds its segment
    and its device copy from (one encode for both)."""

    data: np.ndarray            # dtype.np_dtype values / int32 codes
    valid: Optional[np.ndarray]
    dtype: SqlType
    sdict: Optional[StringDict] = None


def encode_host(
    arrays: dict[str, np.ndarray],
    types: dict[str, SqlType] | None = None,
    valids: dict[str, np.ndarray] | None = None,
) -> dict[str, HostColumn]:
    """Host numpy columns -> ``HostColumn``s: types settled, string
    (object/str-dtype) columns dictionary-encoded.  Touches no device."""
    cols: dict[str, HostColumn] = {}
    for name, arr in arrays.items():
        arr = np.asarray(arr)
        sdict = None
        want = types.get(name) if types else None
        vec_t = want is not None and want.kind == TypeKind.VECTOR
        valid = None
        if valids and name in valids and valids[name] is not None:
            valid = np.asarray(valids[name]).astype(np.bool_)
        if arr.dtype == object and len(arr) and \
                isinstance(arr.reshape(-1)[0], (list, np.ndarray)) and \
                arr.ndim == 1:
            # object array of per-row embeddings -> [n, d] float32
            arr = np.stack([np.asarray(v, dtype=np.float32)
                            for v in arr])
            vec_t = True
        if arr.ndim == 2 or vec_t:
            if arr.ndim == 1:
                # a VECTOR-typed column seeded from a flat placeholder
                # (empty-table seeds): shape it [n, dim]
                dim = want.precision if want is not None else 0
                arr = np.zeros((len(arr), dim), dtype=np.float32)
            data = arr.astype(np.float32)
            cols[name] = HostColumn(data, valid,
                                    SqlType.vector(data.shape[1]))
            continue
        if arr.dtype.kind in ("U", "S", "O"):
            data, sdict = StringDict.encode(arr)
            dtype = SqlType.string()
        else:
            data = arr
            if types and name in types:
                dtype = types[name]
                data = arr.astype(dtype.np_dtype, copy=False)
            else:
                if arr.dtype.kind == "f":
                    dtype = SqlType.double()
                    data = arr.astype(np.float64, copy=False)
                elif arr.dtype.kind == "b":
                    dtype = SqlType.bool_()
                else:
                    dtype = SqlType.int_()
                    data = arr.astype(np.int64, copy=False)
        if types and name in types and types[name].is_string:
            dtype = types[name]
        cols[name] = HostColumn(data, valid, dtype, sdict)
    return cols


def relation_from_host(cols: dict[str, HostColumn],
                       capacity: int | None = None,
                       device=None) -> Relation:
    """Copy ``HostColumn``s to the device, a column at a time.  With a
    ``capacity`` the relation comes padded to it as ``Relation.pad_to``
    pads (zero payload, invalid, dead in a mask that is always there),
    the padding done on the host: the device never holds a column
    twice."""
    def put(a: np.ndarray):
        if capacity is None or capacity <= len(a):
            return jax.device_put(a, device)
        pad = np.zeros((capacity - len(a),) + a.shape[1:], a.dtype)
        # waited for, so that the host holds one padded column at a time
        return jax.device_put(np.concatenate([a, pad]),
                              device).block_until_ready()

    out: dict[str, Column] = {}
    n = 0
    for name, c in cols.items():
        n = len(c.data)
        out[name] = Column(data=put(c.data),
                           valid=None if c.valid is None else put(c.valid),
                           dtype=c.dtype, sdict=c.sdict)
    mask = None
    if capacity is not None:
        if capacity < n:
            raise ValueError(f"capacity {capacity} below the {n} rows")
        mask = put(np.ones(n, dtype=np.bool_))
    return Relation(columns=out, mask=mask)


def from_numpy(
    arrays: dict[str, np.ndarray],
    types: dict[str, SqlType] | None = None,
    valids: dict[str, np.ndarray] | None = None,
    device=None,
) -> Relation:
    """Build a device Relation from host numpy columns.

    String (object/str-dtype) columns are dictionary-encoded here.
    """
    return relation_from_host(encode_host(arrays, types, valids),
                              device=device)


def empty_relation(types: dict[str, "SqlType"]) -> Relation:
    """One all-dead row typed after ``types`` (static shapes need
    capacity >= 1): the canonical empty-table seed shared by CREATE
    TABLE, transient registration, and type-only plan traces."""
    arrays, valids = {}, {}
    for name, t in types.items():
        if t.is_string:
            arrays[name] = np.array([""], dtype=object)
        elif t.kind == TypeKind.VECTOR:
            arrays[name] = np.zeros((1, t.precision or 1),
                                    dtype=np.float32)
        else:
            arrays[name] = np.zeros(1, dtype=t.np_dtype)
        valids[name] = np.array([False])
    rel = from_numpy(arrays, types=types, valids=valids)
    return Relation(columns=rel.columns,
                    mask=jnp.zeros(1, dtype=jnp.bool_))


# ---------------------------------------------------------------------------
# the result boundary: device relation -> host columns
# ---------------------------------------------------------------------------

qmetrics.declare("sql.result_fetches", "counter",
                 "relations brought to the host by to_numpy (labels: kind "
                 "= packed | dense | columns)")
qmetrics.declare("sql.result_fetch_bytes", "counter",
                 "bytes those fetches moved from the device to the host")

# Shape rules of the fetch, from what a TPU v5e and its host measured
# (PR 34; no knob sets them): a transfer that is waited for alone takes
# 0.42 ms however small, one of k requested together 0.07 ms more; a
# program, however small, is done 0.75 ms after its call; bytes cross at
# 0.3 GB/s one array at a time (1 GB/s requested together).
#
# A relation of at most this many lanes crosses as it lies, dead lanes
# and all, every copy requested at once: counting its live rows first is
# a program and a transfer (1.19 ms), more than its dead lanes cost.
_AS_IT_LIES_MAX_LANES = 4096
# Above it the live rows are counted, and brought to the front of their
# count's bucket when that bucket is at most this share of the capacity
# (``1 / n``).  The densify is a prefix sum over the capacity, a binary
# search of ``bucket`` lanes in it and ``bucket`` gathered elements a leaf
# (524,288 lanes of four columns to 64: 1.0 ms, to 16,384: 4.5, to 65,536:
# 14.4, 0.2 us a bucket lane; the same lanes crossing whole: 0.1 us each,
# 51 ms).  A denser relation crosses as it lies.
_DENSIFY_MAX_SHARE = 8

_RAW = SqlType.int_()  # the fetch programs see arrays, not SQL types


def _leaves(rel: Relation, names) -> list:
    """``[mask, data, valid, data, valid, ...]`` in the order of ``names``
    (``None`` where a relation has no mask or a column no validity)."""
    out = [rel.mask]
    for n in names:
        c = rel.columns[n]
        out += [c.data, c.valid]
    return out


def _plane_rows(leaves) -> list:
    """Where each leaf of a packed relation travels: ``(plane, row)``,
    ``None`` for an absent leaf.  Booleans and integers ride widened in
    ONE int64 plane (exact both ways; a 64-bit bit-cast does not lower on
    the TPU, which holds int64 as pairs of 32-bit words); every other
    element type has a plane of its own (named by the type), so no value
    passes through a float or a narrower type; a 2-D leaf (a VECTOR
    column's rows) is its own plane.  The traced pack and the host's split both read this, so
    they cannot disagree."""
    rows: dict = {}
    where = []
    for i, x in enumerate(leaves):
        if x is None:
            where.append(None)
            continue
        dt = np.dtype(x.dtype)
        if x.ndim > 1:
            plane = f"leaf{i}"
        elif dt.kind in "bi" or (dt.kind == "u" and dt.itemsize < 8):
            plane = "int64"
        else:
            plane = dt.name
        row = rows.get(plane, 0)
        rows[plane] = row + 1
        where.append((plane, row))
    return where


#: lanes a row of a two-level scan (``prefix_sum``, ``exec/ops.py``'s)
SCAN_ROW = 1024


def prefix_sum(x):
    """Traced: the inclusive prefix sum of ``x`` (wrapping, for an integer
    dtype), in two levels, within rows of ``SCAN_ROW`` lanes and over the
    rows' totals: the TPU compiler takes 0.2 s over it at 524,288 int32
    lanes where one flat ``cumsum`` costs it 7.7 s (compiled for a
    described v5e in the sandbox, PR 34; 21 s of a warm-up on the chip)."""
    n = x.shape[0]
    rows = jnp.pad(x, (0, -n % SCAN_ROW)).reshape(-1, SCAN_ROW)
    within = jnp.cumsum(rows, axis=1)
    totals = within[:, -1]
    before = jnp.cumsum(totals) - totals
    return (within + before[:, None]).reshape(-1)[:n]


def _live_through(mask):
    """Traced: for every lane, the live lanes up to and including it."""
    return prefix_sum(mask.astype(jnp.int32))


def _pack_body(bucket, tables):
    """Traced: the first ``bucket`` live rows brought to the front, in
    lane order, as planes (``_plane_rows``): a prefix sum of the mask, a
    binary search of ``1..bucket`` in it and one gather a leaf (no
    scatter, no sort over the capacity's lanes)."""
    rel = tables["r"]
    leaves = _leaves(rel, sorted(rel.columns))
    seen = _live_through(leaves[0])
    lanes = jnp.searchsorted(
        seen, jnp.arange(1, bucket + 1, dtype=jnp.int32))
    leaves = [jnp.arange(bucket, dtype=jnp.int32) < seen[-1]] + [
        None if x is None else jnp.take(x, lanes, axis=0, mode="clip")
        for x in leaves[1:]]
    planes: dict = {}
    for x, at in zip(leaves, _plane_rows(leaves)):
        if at is not None:
            planes.setdefault(at[0], []).append(x)
    return {plane: xs[0] if xs[0].ndim > 1
            else jnp.stack([x.astype(plane) for x in xs])
            for plane, xs in planes.items()}


def _count_body(tables):
    """Traced: the live rows of a mask."""
    return jnp.sum(tables["r"].mask, dtype=jnp.int32)


def _on_device(body, args, leaves):
    """Run a fetch program over the leaves, compiled once per input
    signature and placement like any plan program (``executable_for``):
    it has a ``gv$plan_cache`` row and its compiles count.  The program
    sees positional names and no SQL types or dictionaries, so every
    relation of the same shapes shares it."""
    # exec sits above vector: imported where it is called
    from oceanbase_tpu.exec.plan import Program, executable_for

    name = f"result.{body.__name__.strip('_')}{args}"
    placed = tuple(x.sharding for x in leaves if x is not None)
    exe = executable_for(Program(body, args, (name, placed), name))
    cols = {f"c{i:04d}": Column(leaves[j], leaves[j + 1], _RAW)
            for i, j in enumerate(range(1, len(leaves), 2))}
    (out, _lanes, _total, _mon), *_ = exe.call(
        {"r": Relation(columns=cols, mask=leaves[0])})
    exe.stats.executions += 1
    return out


def prefetch(rel: Relation) -> None:
    """Ask for the host copies of a small relation's arrays, and wait for
    nothing: called between a program's dispatch and the wait for it, the
    copies follow the computation and ``to_numpy`` finds them there."""
    if rel.capacity <= _AS_IT_LIES_MAX_LANES:
        for x in _leaves(rel, rel.columns):
            if isinstance(x, jax.Array):
                x.copy_to_host_async()


def to_numpy(rel: Relation, limit: int | None = None,
             tags: dict | None = None) -> dict[str, np.ndarray]:
    """Materialize live rows back to host (decoding string dictionaries).

    This is the result-set boundary (≙ result drivers serializing rows to
    MySQL packets, src/observer/mysql/ob_sync_plan_driver.cpp).  Dynamic
    shape begins ON the device, at the bucket of the live count, not on
    the host at the capacity, and every transfer is requested before the
    first is read.  One algorithm; the regime follows from what the
    relation shows (``sql.result_fetches{kind}``):

    - ``dense``: at most ``_AS_IT_LIES_MAX_LANES`` lanes, or more lanes
      most of which are live (or no mask): the arrays cross as they lie,
      with no copy on the device.
    - ``packed``: more lanes, few of them live: one round trip reads the
      live count, a small cached program brings the live rows to the front
      of the count's bucket (``bucket_capacity``) and stacks mask, data
      and validity into one plane an element type (``_plane_rows``), and
      that bucket crosses: transfers grow with neither the columns nor the
      capacity.
    - ``columns``: a leaf that already lies on the host: nothing to fetch.

    ``tags`` (the ``materialize`` span's) takes ``kind``, ``rows``,
    ``capacity``, ``bytes`` and ``transfers``.
    """
    names = list(rel.columns)
    if not names:
        return {}
    capacity = rel.capacity
    leaves = _leaves(rel, names)
    fetched = []
    kind = "dense"
    if not all(isinstance(x, jax.Array) for x in leaves if x is not None):
        kind = "columns"
    elif capacity > _AS_IT_LIES_MAX_LANES and rel.mask is not None:
        fetched.append(jax.device_get(
            _on_device(_count_body, (), [rel.mask])))
        live = int(fetched[0])
        bucket = bucket_capacity(live if limit is None
                                 else min(live, limit))
        if bucket * _DENSIFY_MAX_SHARE <= capacity:
            kind = "packed"
    if kind == "packed":
        planes = jax.device_get(_on_device(_pack_body, (bucket,), leaves))
        fetched += planes.values()
        host = [None if at is None
                else planes[at[0]] if x.ndim > 1
                else planes[at[0]][at[1]].astype(x.dtype, copy=False)
                for x, at in zip(leaves, _plane_rows(leaves))]
    else:
        host = jax.device_get(leaves)
        fetched += [h for x, h in zip(leaves, host)
                    if isinstance(x, jax.Array)]
    nbytes = sum(int(x.nbytes) for x in fetched)

    mask = host[0]
    idx = np.nonzero(mask)[0] if mask is not None \
        else np.arange(host[1].shape[0])
    if limit is not None:
        idx = idx[:limit]
    out: dict[str, np.ndarray] = {}
    for i, name in enumerate(names):
        col = rel.columns[name]
        data = host[1 + 2 * i][idx]
        v = host[2 + 2 * i]
        if v is not None:
            v = v[idx]
        if col.dtype.kind == TypeKind.VECTOR:
            # embeddings come back as an object array of float32 rows
            out[name] = np.array([data[i] for i in range(len(data))],
                                 dtype=object)
            if v is not None:
                out.setdefault("__valid__" + name, v)
            continue
        if col.sdict is not None:
            codes = np.clip(data, 0, col.sdict.size - 1)
            data = col.sdict.values[codes]
        if v is not None:
            data = np.where(v, data, None) if data.dtype == object else data
            out[name] = data
            out.setdefault("__valid__" + name, v)
        else:
            out[name] = data
    qmetrics.inc("sql.result_fetches", kind=kind)
    qmetrics.inc("sql.result_fetch_bytes", nbytes)
    if tags is not None:
        tags.update(kind=kind, rows=len(idx), capacity=capacity,
                    bytes=nbytes, transfers=len(fetched))
    return out
