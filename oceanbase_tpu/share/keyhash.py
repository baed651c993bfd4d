"""The one hash that places a row by its key: in storage and on the mesh.

A hash-partitioned table's rows are routed to their partitions on the host
(``storage/partition.py``: load and DML), and a PX exchange sends rows to
the chips inside a compiled program (``px/exchange.py::_hash_dest``).  A
repartition TO a declared layout (upstream's PKEY distribution) is only
right when both ask this module: every function here takes NumPy and JAX
arrays alike and does the same arithmetic on either, so partition ``i`` of
a table and shard ``i`` of an exchange hold the rows of the same keys.

Keys are storage-domain integers (ints, dates as days, decimals as scaled
ints, bools); a NULL key is placed as 0 by the storage router (≙ MySQL's
``PARTITION BY KEY``: NULL hashes as 0).
"""

from __future__ import annotations

import numpy as np

from oceanbase_tpu.datatypes import TypeKind

#: column kinds whose stored value is an integer the hash can take
HASHABLE_KINDS = (TypeKind.INT, TypeKind.DATE, TypeKind.DATETIME,
                  TypeKind.DECIMAL, TypeKind.BOOL)

M1 = np.uint64(0xBF58476D1CE4E5B9)
M2 = np.uint64(0x94D049BB133111EB)


def mix64(x):
    """splitmix64's finalizer over a uint64 array (wraps modulo 2**64)."""
    x = x.astype(np.uint64)
    x = (x ^ (x >> 30)) * M1
    x = (x ^ (x >> 27)) * M2
    return x ^ (x >> 31)


def combine(datas, xp=np, raw_single: bool = True):
    """Key columns -> one int64 per row.  A single integer-like column is
    its raw value (exact: equal keys, equal values, and nothing else);
    several columns, or a float column, fold into a 64-bit mix."""
    if len(datas) == 1 and raw_single:
        return datas[0].astype(np.int64)
    h = xp.zeros(datas[0].shape[0], dtype=np.uint64)
    for d in datas:
        if xp.issubdtype(d.dtype, xp.floating):
            k = d.astype(np.float64).view(np.int64)
        else:
            k = d.astype(np.int64)
        h = mix64(h ^ mix64(k.astype(np.uint64)))
    return h.astype(np.int64)


def dest_of(key, n: int):
    """Combined int64 key -> the partition (or shard) in ``[0, n)``."""
    return (mix64(key.astype(np.uint64)) % np.uint64(n)).astype(np.int32)


def partition_of(datas, n: int, xp=np):
    """Key columns -> partition of every row: ``dest_of(combine(...))``."""
    return dest_of(combine(datas, xp), n)
