"""Column encodings + zone maps for immutable segments.

Reference analog: the cs_encoding suite (src/storage/blocksstable/
cs_encoding — dict/RLE/delta/bit-packed decoders with SIMD) and
index-block zone maps (src/storage/blocksstable/index_block).

Encodings (chosen per column chunk by a simple cost rule, ≙ the
reference's encoding selector):
- PLAIN     raw numpy array
- DICT      small-cardinality values -> uint{8,16,32} codes (the global
            string dictionary already lives at the table level; this is a
            second, per-segment code compression)
- RLE       run-length (values + run lengths), good for sorted/clustered
- DELTA     monotonic-ish int sequences -> base + small deltas (bit-width
            reduced)
- SDICT     a string chunk as the distinct strings it holds (sorted) and
            uint{8,16,32} codes into them: what a load that has already
            factorised the column (``CodedStrings``) writes, a gather to
            decode

Decode happens column-at-a-time into dense arrays — on TPU the decode is a
gather (DICT), repeat (RLE) or cumsum (DELTA), all vectorizable; round 1
decodes on host into the device upload path, the jnp decode kernels slot
in behind the same Segment.decode() interface.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np


@dataclass
class ZoneMap:
    """Per-chunk min/max/null-count (≙ index-block aggregate row)."""

    vmin: object
    vmax: object
    null_count: int
    row_count: int

    def may_match_range(self, lo, hi) -> bool:
        """Can any value in [lo, hi] exist in this chunk?"""
        if self.null_count == self.row_count:
            return False
        if lo is not None and self.vmax is not None and self.vmax < lo:
            return False
        if hi is not None and self.vmin is not None and self.vmin > hi:
            return False
        return True


@dataclass
class EncodedColumn:
    encoding: str                  # plain | dict | rle | delta
    payload: dict                  # encoding-specific numpy arrays
    valid: Optional[np.ndarray]    # bool validity or None
    zone: ZoneMap
    n: int

    def nbytes(self) -> int:
        total = 0
        for v in self.payload.values():
            if isinstance(v, np.ndarray):
                total += v.nbytes
        if self.valid is not None:
            total += self.valid.nbytes
        return total


def _zone(arr: np.ndarray, valid) -> ZoneMap:
    n = len(arr)
    nulls = 0 if valid is None else int((~valid).sum())
    live = arr[valid] if valid is not None else arr
    if n == 0 or nulls == n or len(live) == 0:
        return ZoneMap(None, None, nulls, n)
    if arr.dtype == object or arr.dtype.kind in "US":
        # numpy 2.x has no min/max ufunc loop for strings
        vals = live.tolist()
        return ZoneMap(min(vals), max(vals), nulls, n)
    return ZoneMap(live.min(), live.max(), nulls, n)


@dataclass
class CodedStrings:
    """A string column as a load factorised it (``vector.column.
    factorize_strings``): int32 ``codes`` into ``values``, the sorted
    distinct strings.  Slicing and fancy indexing give the same column
    over fewer rows, so the key sort and the partition split treat it as
    they treat an array; the codes order as the strings do."""

    codes: np.ndarray
    values: np.ndarray            # object, sorted ascending

    def __len__(self) -> int:
        return len(self.codes)

    def __getitem__(self, sel) -> "CodedStrings":
        return CodedStrings(self.codes[sel], self.values)

    def strings(self) -> np.ndarray:
        """The column as an object array of its strings."""
        return self.values[self.codes]


def encode_coded(col: CodedStrings, valid) -> EncodedColumn:
    """One chunk of a factorised string column -> an ``sdict`` chunk."""
    codes, n = col.codes, len(col.codes)
    nulls = 0 if valid is None else int((~valid).sum())
    live = codes if valid is None else codes[valid]
    if len(live) == 0:
        return EncodedColumn("plain", {"data": col.strings()}, valid,
                             ZoneMap(None, None, nulls, n), n)
    size = len(col.values)
    if size <= 4 * n:
        # a flag per dictionary entry: no sort of the chunk's codes
        present = np.zeros(size, dtype=bool)
        present[codes] = True
        used = np.flatnonzero(present)
        local = (np.cumsum(present, dtype=np.int64) - 1)[codes]
    else:
        used, local = np.unique(codes, return_inverse=True)
    zone = ZoneMap(str(col.values[live.min()]), str(col.values[live.max()]),
                   nulls, n)
    return EncodedColumn(
        "sdict", {"values": col.values[used],
                  "codes": local.astype(_best_uint(len(used)))},
        valid, zone, n)


def _best_uint(maxval: int) -> np.dtype:
    if maxval < 256:
        return np.dtype(np.uint8)
    if maxval < 65536:
        return np.dtype(np.uint16)
    return np.dtype(np.uint32)


def encode_column(arr: np.ndarray, valid: np.ndarray | None) -> EncodedColumn:
    """Pick an encoding by measured size (≙ encoding selector cost rule)."""
    n = len(arr)
    zone = _zone(arr, valid)
    if n == 0 or arr.dtype == object or arr.ndim > 1:
        # object strings and [n,d] vector embeddings store plain
        return EncodedColumn("plain", {"data": arr}, valid, zone, n)

    candidates: list[tuple[int, str, dict]] = [
        (arr.nbytes, "plain", {"data": arr})
    ]

    # RLE
    if n > 1:
        change = np.empty(n, dtype=bool)
        change[0] = True
        np.not_equal(arr[1:], arr[:-1], out=change[1:])
        n_runs = int(change.sum())
        if n_runs * (arr.itemsize + 4) < arr.nbytes // 2:
            starts = np.nonzero(change)[0]
            lengths = np.diff(np.append(starts, n)).astype(np.uint32)
            candidates.append(
                (n_runs * (arr.itemsize + 4), "rle",
                 {"values": arr[starts], "lengths": lengths})
            )

    # DICT (per-segment)
    if arr.dtype.kind in "iu":
        uniq = np.unique(arr)
        if len(uniq) <= max(2, n // 4) and len(uniq) < 2**32:
            codes = np.searchsorted(uniq, arr).astype(_best_uint(len(uniq)))
            sz = uniq.nbytes + codes.nbytes
            candidates.append((sz, "dict", {"values": uniq, "codes": codes}))

    # DELTA (ints with small spread of consecutive differences)
    if arr.dtype.kind in "iu" and n > 1:
        d = np.diff(arr.astype(np.int64))
        if len(d) and d.min() >= np.iinfo(np.int32).min // 2 and \
                d.max() <= np.iinfo(np.int32).max // 2:
            spread = int(d.max() - d.min()) if len(d) else 0
            dt = (np.int8 if spread < 127 and abs(d).max() < 127 else
                  np.int16 if spread < 32000 and abs(d).max() < 32000 else
                  np.int32)
            deltas = d.astype(dt)
            sz = 8 + deltas.nbytes
            candidates.append(
                (sz, "delta", {"base": np.int64(arr[0]), "deltas": deltas})
            )

    # VARINT (delta+zigzag+LEB128 via the native codec): byte-granular,
    # often the smallest for int64 key/date columns
    if arr.dtype.kind in "iu" and arr.itemsize == 8 and n > 0:
        from oceanbase_tpu.native import delta_varint_encode

        buf = np.frombuffer(delta_varint_encode(arr), dtype=np.uint8)
        candidates.append((buf.nbytes, "varint", {"buf": buf}))

    sz, enc, payload = min(candidates, key=lambda c: c[0])
    return EncodedColumn(enc, payload, valid, zone, n)


def decode_column(ec: EncodedColumn, out_dtype=None) -> np.ndarray:
    if ec.encoding == "plain":
        data = ec.payload["data"]
    elif ec.encoding == "rle":
        data = np.repeat(ec.payload["values"], ec.payload["lengths"])
    elif ec.encoding == "dict":
        data = ec.payload["values"][ec.payload["codes"]]
    elif ec.encoding == "sdict":
        data = ec.payload["values"][ec.payload["codes"]]
    elif ec.encoding == "delta":
        base = ec.payload["base"]
        deltas = ec.payload["deltas"].astype(np.int64)
        data = np.concatenate([[0], np.cumsum(deltas)]) + base
    elif ec.encoding == "varint":
        from oceanbase_tpu.native import delta_varint_decode

        data = delta_varint_decode(ec.payload["buf"].tobytes(), ec.n)
    else:  # pragma: no cover
        raise ValueError(ec.encoding)
    if out_dtype is not None and data.dtype != out_dtype:
        data = data.astype(out_dtype)
    return data


def decode_column_into(ec: EncodedColumn, out: np.ndarray, lut=None):
    """Decode one chunk into ``out`` (``ec.n`` elements of the reader's
    buffer): a granule's chunks land in one array a column, with no
    concatenation after.  ``lut``: for an ``sdict`` chunk, the table from
    its own codes to the codes the reader wants (the strings are not
    touched)."""
    if ec.encoding == "sdict":
        if lut is None:
            raise ValueError("a string chunk decodes into codes of a "
                             "dictionary: pass its table")
        np.take(lut, ec.payload["codes"], out=out)
    elif ec.encoding == "dict" and \
            ec.payload["values"].dtype == out.dtype:
        np.take(ec.payload["values"], ec.payload["codes"], out=out)
    else:
        out[:] = decode_column(ec)
