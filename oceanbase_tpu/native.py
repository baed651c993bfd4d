"""ctypes bridge to the native host-runtime kernels (native/).

Builds lazily with make on first import if the shared library is missing;
every entry point has a pure-numpy fallback so the framework works without
a toolchain (≙ the reference's portable fallbacks next to SIMD paths).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "native")
_SO = os.path.join(_NATIVE_DIR, "libobtpu_native.so")

_lib = None
_lib_lock = threading.Lock()
_build_attempted = False


_MASK64 = (1 << 64) - 1


def _load():
    global _lib, _build_attempted
    if _lib is not None:  # lock-free fast path (hot on the WAL append path)
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_SO) and not _build_attempted:
            _build_attempted = True
            # several processes may find the library missing at once:
            # each builds under its own name, and the rename is atomic
            tmp = f"{_SO}.{os.getpid()}.tmp"
            try:
                subprocess.run(
                    ["make", "-C", _NATIVE_DIR,
                     f"TARGET={os.path.basename(tmp)}"],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, _SO)
            except Exception:
                if os.path.exists(tmp):
                    os.unlink(tmp)
                return None
        if not os.path.exists(_SO):
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        lib.obtpu_crc64.restype = ctypes.c_uint64
        lib.obtpu_crc64.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                    ctypes.c_uint64]
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
        lib.obtpu_delta_varint_encode.restype = ctypes.c_uint64
        lib.obtpu_delta_varint_encode.argtypes = [
            i64p, ctypes.c_uint64, u8p, ctypes.c_uint64]
        lib.obtpu_delta_varint_decode.restype = ctypes.c_uint64
        lib.obtpu_delta_varint_decode.argtypes = [
            u8p, ctypes.c_uint64, i64p, ctypes.c_uint64]
        lib.obtpu_rle_runs_i64.restype = ctypes.c_uint64
        lib.obtpu_rle_runs_i64.argtypes = [
            i64p, ctypes.c_uint64, u64p, ctypes.c_uint64]
        u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
        bytep = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        lib.obtpu_csv_tokenize.restype = ctypes.c_uint64
        lib.obtpu_csv_tokenize.argtypes = [
            bytep, ctypes.c_uint64, ctypes.c_uint8, ctypes.c_uint64,
            u64p, u32p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.obtpu_parse_int64_fields.restype = ctypes.c_uint64
        lib.obtpu_parse_int64_fields.argtypes = [
            bytep, u64p, u32p, ctypes.c_uint64, ctypes.c_int64, i64p,
            bytep]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# crc64 (log/segment integrity)
# ---------------------------------------------------------------------------

_PY_TABLE = None


def _py_crc64_table():
    global _PY_TABLE
    if _PY_TABLE is None:
        poly = np.uint64(0xC96C5795D7870F42)
        table = np.zeros(256, dtype=np.uint64)
        for i in range(256):
            crc = np.uint64(i)
            for _ in range(8):
                crc = (crc >> np.uint64(1)) ^ (
                    poly if crc & np.uint64(1) else np.uint64(0))
            table[i] = crc
        _PY_TABLE = table
    return _PY_TABLE


def crc64(data: bytes, seed: int = 0) -> int:
    lib = _load()
    if lib is not None:
        return int(lib.obtpu_crc64(data, len(data), seed))
    # numpy fallback (byte-at-a-time through the table)
    table = _py_crc64_table()
    crc = np.uint64(~seed & 0xFFFFFFFFFFFFFFFF)
    for b in data:
        crc = table[int((crc ^ np.uint64(b)) & np.uint64(0xFF))] ^ \
            (crc >> np.uint64(8))
    return int(~crc & 0xFFFFFFFFFFFFFFFF)


# ---------------------------------------------------------------------------
# delta + zigzag + varint codec (segment persistence)
# ---------------------------------------------------------------------------


def delta_varint_encode(values: np.ndarray) -> bytes:
    values = np.ascontiguousarray(values, dtype=np.int64)
    lib = _load()
    if lib is not None:
        out = np.empty(len(values) * 10 + 16, dtype=np.uint8)
        n = int(lib.obtpu_delta_varint_encode(values, len(values), out,
                                              len(out)))
        if n:
            return out[:n].tobytes()
    # python fallback: deltas in wrapping 64-bit arithmetic (matches the
    # native codec for full-range values like MAX-MIN)
    out_b = bytearray()
    prev = 0
    for v in values.tolist():
        d = (v - prev) & _MASK64
        if d >= 1 << 63:
            d -= 1 << 64  # back to signed
        u = ((d << 1) ^ (d >> 63)) & _MASK64
        prev = v
        while True:
            b = u & 0x7F
            u >>= 7
            out_b.append(b | (0x80 if u else 0))
            if not u:
                break
    return bytes(out_b)


def delta_varint_decode(buf: bytes, n: int) -> np.ndarray:
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    lib = _load()
    if lib is not None:
        arr = np.frombuffer(buf, dtype=np.uint8)
        out = np.empty(n, dtype=np.int64)
        used = int(lib.obtpu_delta_varint_decode(
            np.ascontiguousarray(arr), len(arr), out, n))
        if used == 0:
            raise ValueError("corrupt varint payload (native decode failed)")
        return out
    out_l = np.empty(n, dtype=np.int64)
    pos = 0
    prev = 0
    try:
        for i in range(n):
            u = 0
            shift = 0
            while True:
                b = buf[pos]
                pos += 1
                u |= (b & 0x7F) << shift
                if not (b & 0x80):
                    break
                shift += 7
                if shift > 63:
                    raise ValueError("corrupt varint payload")
            d = (u >> 1) ^ -(u & 1)
            prev = (prev + d) & _MASK64
            if prev >= 1 << 63:
                prev -= 1 << 64
            out_l[i] = prev
    except IndexError:
        raise ValueError("corrupt varint payload (truncated)") from None
    return out_l


# ---------------------------------------------------------------------------
# CSV tokenizer + field parsers (direct-load fast path; python csv module
# remains the fallback and the oracle for quoting semantics)
# ---------------------------------------------------------------------------


def csv_tokenize(data: bytes, n_cols: int, delimiter: str = ","):
    """-> (buf, offsets[n_rows*n_cols], lengths, n_rows) or None when the
    native library is unavailable or the file is ragged (caller falls
    back to the python csv module)."""
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, dtype=np.uint8)
    # upper bound on rows: every row ends with \n or a lone \r (counting
    # \r\n twice only over-allocates)
    approx_rows = data.count(b"\n") + data.count(b"\r") + 2
    offsets = np.empty(approx_rows * n_cols, dtype=np.uint64)
    lengths = np.empty(approx_rows * n_cols, dtype=np.uint32)
    err = ctypes.c_uint64(0)
    n_rows = int(lib.obtpu_csv_tokenize(
        np.ascontiguousarray(buf), len(buf), ord(delimiter), n_cols,
        offsets, lengths, approx_rows, ctypes.byref(err)))
    if n_rows == 0 and err.value:
        return None
    return buf, offsets[:n_rows * n_cols], lengths[:n_rows * n_cols], n_rows


def parse_int64_fields(buf: np.ndarray, offsets, lengths,
                       scale: int = 0):
    """Batch-parse tokenized fields into scaled int64 + validity."""
    lib = _load()
    n = len(offsets)
    out = np.empty(n, dtype=np.int64)
    valid = np.empty(n, dtype=np.uint8)
    if lib is None:
        for i in range(n):
            ln = int(lengths[i]) & 0x7FFFFFFF
            s = bytes(buf[int(offsets[i]):int(offsets[i]) + ln]).decode()
            try:
                if scale:
                    from decimal import Decimal

                    out[i] = int(Decimal(s).scaleb(scale))
                else:
                    out[i] = int(s)
                valid[i] = 1
            except Exception:  # noqa: BLE001
                out[i] = 0
                valid[i] = 0
        return out, valid.astype(bool)
    lib.obtpu_parse_int64_fields(
        np.ascontiguousarray(buf), np.ascontiguousarray(offsets),
        np.ascontiguousarray(lengths), n, 10 ** scale, out, valid)
    return out, valid.astype(bool)


def field_strings(buf, offsets, lengths) -> np.ndarray:
    """Materialize tokenized fields as python strings (unescaping the rare
    quoted-quote fields flagged in the length high bit).  ``buf`` may be
    the original bytes object (no copy) or a uint8 array."""
    out = np.empty(len(offsets), dtype=object)
    data = buf if isinstance(buf, (bytes, bytearray)) else buf.tobytes()
    for i in range(len(offsets)):
        ln = int(lengths[i])
        esc = bool(ln & 0x80000000)
        ln &= 0x7FFFFFFF
        o = int(offsets[i])
        s = data[o:o + ln].decode(errors="replace")
        out[i] = s.replace('""', '"') if esc else s
    return out


def rle_run_starts(values: np.ndarray) -> np.ndarray:
    values = np.ascontiguousarray(values, dtype=np.int64)
    lib = _load()
    if lib is not None:
        starts = np.empty(len(values), dtype=np.uint64)
        n = int(lib.obtpu_rle_runs_i64(values, len(values), starts,
                                       len(starts)))
        return starts[:n].astype(np.int64)
    if len(values) == 0:
        return np.zeros(0, dtype=np.int64)
    change = np.empty(len(values), dtype=bool)
    change[0] = True
    np.not_equal(values[1:], values[:-1], out=change[1:])
    return np.nonzero(change)[0]
