"""Catalog: schemas, tables, statistics.

Reference analog: the schema service (src/share/schema,
ObMultiVersionSchemaService src/share/schema/ob_multi_version_schema_service.h:151)
plus optimizer statistics (src/share/stat).  Round-1 scope: an in-memory
catalog versioned by a monotonically increasing schema version; the storage
engine (storage/) persists and reloads it.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from oceanbase_tpu.datatypes import SqlType, TypeKind
from oceanbase_tpu.vector import Relation, from_numpy


@dataclass
class ColumnDef:
    name: str
    dtype: SqlType
    nullable: bool = True


@dataclass
class IndexDef:
    """A secondary index (≙ index-table schema, ObTableSchema with
    INDEX_TYPE_NORMAL/UNIQUE — src/share/schema/ob_table_schema.h).

    Stored as its own index TABLE whose key is (index columns + primary
    key columns) — the index-table model OceanBase uses, riding the same
    tablet/WAL/MVCC machinery as any table.  ``storage_table`` names it.
    """

    name: str
    table: str
    columns: list[str]
    unique: bool
    storage_table: str


@dataclass
class TableDef:
    name: str
    columns: list[ColumnDef]
    primary_key: list[str] = field(default_factory=list)
    # optimizer stats (≙ src/share/stat basic table stats)
    row_count: int = 0
    ndv: dict[str, int] = field(default_factory=dict)
    # equi-height histograms from ANALYZE: col -> (edges ndarray in the
    # STORAGE value domain, null_fraction) — ≙ ObOptColumnStat histogram
    # (src/share/stat/ob_opt_column_stat.h)
    histograms: dict = field(default_factory=dict)
    # most-common-values lists from ANALYZE for dict-encoded string
    # columns: col -> (values list, frequency-fraction list) — string
    # equality selectivity reads the measured frequency instead of a
    # guess (≙ ObOptColumnStat top-k frequency histogram)
    mcv: dict = field(default_factory=dict)
    # a row-weighted sample of each dict-encoded string column's values
    # from ANALYZE: col -> tuple of strings at evenly spaced ranks of
    # the rows; LIKE selectivity is the share of it that matches
    samples: dict = field(default_factory=dict)
    # range partitioning: (column, [upper-exclusive split points]) or None
    partition: tuple | None = None
    # hash / key partitioning: (method, [columns], partitions) or None.
    # A row lives in partition ``storage.partition.hash_partition_of(its
    # key values) ``; partition i's device copy lives on device i
    hash_partition: tuple | None = None
    # the tablegroup the table was created in (≙ __all_tablegroup): its
    # tables share method, partition count and key types, so equal keys
    # lie in equal partitions
    tablegroup: str | None = None
    # WITH COLUMN GROUP (all columns | each column [, ...]) as the DDL
    # declared it (≙ OceanBase 4.3's column-group clause).  Both groups
    # always exist here (the host LSM is the row store, the device
    # relation the column store), so this records the schema author's
    # declaration and selects no path
    column_groups: list | None = None
    auto_increment_cols: list = field(default_factory=list)
    indexes: list = field(default_factory=list)  # list[IndexDef]
    # vector/fulltext indexes: name -> {"kind", "column", "metric"...}
    # (runtime structures — IVF buckets, posting lists — rebuild lazily
    # per data_version; ≙ INDEX_TYPE_VEC_* / INDEX_TYPE_FTS_* schemas)
    aux_indexes: dict = field(default_factory=dict)

    def column(self, name: str) -> ColumnDef:
        for c in self.columns:
            if c.name == name:
                return c
        raise KeyError(name)

    def has_column(self, name: str) -> bool:
        return any(c.name == name for c in self.columns)

    @property
    def column_names(self) -> list[str]:
        return [c.name for c in self.columns]

    @property
    def partition_columns(self) -> list[str]:
        """The columns a row's partition follows from (range, hash or
        key); empty for an unpartitioned table."""
        if self.partition is not None:
            return [self.partition[0]]
        return list(self.hash_partition[1]) if self.hash_partition else []


def sampled_ndv(arr, n: int, sample: int = 8192) -> int:
    """NDV estimate from a fixed-seed sample (load-time default stats;
    ANALYZE refines with the exact count).  A saturating sample (few
    distinct values) means a low-cardinality domain — report the sample
    distinct count, not a scaled guess: nationkey-style columns must not
    look like high-cardinality keys to the join-order cost model."""
    import numpy as _np

    if n == 0:
        return 1
    if n <= sample:
        return max(1, int(len(_np.unique(arr[:n]))))
    idx = _np.random.default_rng(0).choice(n, sample, replace=False)
    d = int(len(_np.unique(arr[idx])))
    if d <= sample // 2:
        return max(d, 1)
    return max(1, min(n, int(d * (n / sample))))


class Catalog:
    """Named tables -> (definition, device-resident data).

    Thread-safe; schema_version bumps on DDL (≙ schema refresh protocol)."""

    def __init__(self):
        self._lock = threading.RLock()
        self._defs: dict[str, TableDef] = {}
        self._data: dict[str, Relation] = {}
        # transient tables: materialized virtual (gv$/v$) relations,
        # refreshed per statement (≙ virtual table iterators)
        self._transients: dict[str, tuple] = {}
        # external (lake) tables: name -> {"tdef", "location", "format",
        # "delimiter", "skip", "cache": (mtime, Relation)|None}
        # (≙ src/share/external_table — files scanned at query time)
        self._externals: dict[str, dict] = {}
        # views: name -> {"sql": body text, "cols": [alias...]|[]}
        # (≙ __all_view view_definition; expanded at bind time)
        self._views: dict[str, dict] = {}
        self.schema_version = 1

    # -- views ------------------------------------------------------------
    def create_view(self, name: str, sql: str, cols=None,
                    or_replace: bool = False):
        with self._lock:
            if self.has_table(name) or name in self._externals:
                raise ValueError(f"table {name} already exists")
            if name in self._views and not or_replace:
                raise ValueError(f"view {name} already exists")
            self._views[name] = {"sql": sql, "cols": list(cols or [])}
            self.schema_version += 1

    def drop_view(self, name: str) -> bool:
        with self._lock:
            if self._views.pop(name, None) is None:
                return False
            self.schema_version += 1
            return True

    def view_def(self, name: str):
        with self._lock:
            return self._views.get(name)

    def view_names(self) -> list[str]:
        with self._lock:
            return sorted(self._views)

    def drop_transient(self, name: str):
        with self._lock:
            self._transients.pop(name, None)

    # -- external tables --------------------------------------------------
    def register_external(self, tdef: TableDef, location: str,
                          fmt: str = "csv", delimiter: str = ",",
                          skip_lines: int = 0,
                          if_not_exists: bool = False):
        with self._lock:
            if tdef.name in self._externals:
                if if_not_exists:
                    return
                raise ValueError(f"external table {tdef.name} exists")
            # collision checks inside ONE locked section (no
            # check-then-act window against concurrent DDL); has_table()
            # stays virtual — StorageCatalog covers WAL-applied engine
            # tables the base maps don't know about
            if self.has_table(tdef.name):
                raise ValueError(f"table {tdef.name} already exists")
            if self.view_def(tdef.name) is not None:
                raise ValueError(f"view {tdef.name} already exists")
            self._externals[tdef.name] = {
                "tdef": tdef, "location": location, "format": fmt,
                "delimiter": delimiter, "skip": skip_lines,
                "cache": None}
            self.schema_version += 1

    def drop_external(self, name: str) -> bool:
        with self._lock:
            if self._externals.pop(name, None) is not None:
                self.schema_version += 1
                return True
            return False

    def _external_lookup(self, name: str):
        return self._externals.get(name)

    def _external_data(self, name: str) -> Relation:
        import os as _os

        from oceanbase_tpu.share.external import read_external

        e = self._externals.get(name)
        if e is None:  # dropped concurrently: the normal missing-table path
            raise KeyError(f"unknown table {name}")
        try:
            mtime = _os.path.getmtime(e["location"])
        except OSError:
            mtime = None
        with self._lock:
            hit = e["cache"]
            if hit is not None and hit[0] == mtime:
                return hit[1]
        arrays, valids, types = read_external(
            e["location"], e["format"], e["tdef"], e["delimiter"],
            e["skip"])
        rel = from_numpy(arrays, types=types, valids=valids or None)
        with self._lock:
            e["cache"] = (mtime, rel)
            e["tdef"].row_count = rel.capacity
        return rel

    def register_transient(self, name: str, arrays, types=None,
                           valids=None):
        from oceanbase_tpu.vector import empty_relation, from_numpy

        n = len(next(iter(arrays.values()))) if arrays else 0
        if n == 0:
            # static shapes need capacity >= 1: one all-dead row
            def infer(v):
                kind = np.asarray(v).dtype.kind
                if kind in "OUS":
                    return SqlType.string()
                if kind == "f":
                    return SqlType.double()
                if kind == "b":
                    return SqlType.bool_()
                return SqlType.int_()

            col_types = {k: (types or {}).get(k) or infer(v)
                         for k, v in arrays.items()}
            rel = empty_relation(col_types)
            row_count = 0
        else:
            rel = from_numpy(arrays, types=types, valids=valids or None)
            row_count = rel.capacity
        cols = [ColumnDef(c, rel.columns[c].dtype) for c in arrays]
        tdef = TableDef(name, cols, row_count=max(row_count, 1))
        with self._lock:
            # symmetric to register_external: a transient must not
            # shadow a view (re-registering an existing transient is the
            # normal per-statement gv$ refresh and stays allowed)
            if self.view_def(name) is not None:
                raise ValueError(f"view {name} already exists")
            self._transients[name] = (tdef, rel)

    # -- DDL -------------------------------------------------------------
    def create_table(self, tdef: TableDef, if_not_exists: bool = False):
        with self._lock:
            # view-collision check INSIDE the locked section: a
            # concurrent CREATE VIEW between check and insert must not
            # leave a table shadowing a view (create_view holds the same
            # lock, so check+insert is atomic against it)
            if self.view_def(tdef.name) is not None:
                raise ValueError(f"view {tdef.name} already exists")
            if tdef.name in self._defs or tdef.name in self._externals:
                if if_not_exists:
                    return
                raise ValueError(f"table {tdef.name} already exists")
            self._defs[tdef.name] = tdef
            self.schema_version += 1

    def drop_table(self, name: str, if_exists: bool = False):
        with self._lock:
            if name not in self._defs:
                if if_exists:
                    return
                raise KeyError(name)
            del self._defs[name]
            self._data.pop(name, None)
            self.schema_version += 1

    # -- data ------------------------------------------------------------
    def load_numpy(self, name: str, arrays: dict[str, np.ndarray],
                   types: dict[str, SqlType] | None = None,
                   primary_key: list[str] | None = None,
                   valids: dict[str, np.ndarray] | None = None):
        """Bulk-load host arrays as a table (≙ direct load path,
        src/storage/direct_load)."""
        rel = from_numpy(arrays, types=types, valids=valids)
        n = rel.capacity
        cols = []
        ndv = {}
        for cname in arrays:
            col = rel.columns[cname]
            cols.append(ColumnDef(cname, col.dtype, nullable=col.valid is not None))
            if col.sdict is not None:
                ndv[cname] = col.sdict.size
            elif col.dtype.kind == TypeKind.VECTOR:
                ndv[cname] = n
            else:
                ndv[cname] = sampled_ndv(np.asarray(arrays[cname]), n)
        with self._lock:
            self._defs[name] = TableDef(
                name, cols, primary_key=primary_key or [], row_count=n, ndv=ndv
            )
            self._data[name] = rel
            self.schema_version += 1

    def set_data(self, name: str, rel: Relation):
        with self._lock:
            self._data[name] = rel
            d = self._defs.get(name)
            if d is not None:
                d.row_count = rel.capacity

    # -- lookup ----------------------------------------------------------
    def table_def(self, name: str) -> TableDef:
        with self._lock:
            t = self._transients.get(name)
            if t is not None:
                return t[0]
            e = self._externals.get(name)
            if e is not None:
                return e["tdef"]
            if name not in self._defs:
                raise KeyError(f"unknown table {name}")
            return self._defs[name]

    def scan_lanes(self, name: str) -> int:
        """The lanes a scan of ``name`` presents to a plan program (its
        relation's static capacity), from the definition alone: where
        the planner reads how many lanes a filter chain over the table
        arrives on.  Loaded relations are not padded here, so it is the
        loaded row count; the storage catalog pads to its bucket ladder."""
        return max(int(self.table_def(name).row_count), 1)

    def table_data(self, name: str) -> Relation:
        with self._lock:
            t = self._transients.get(name)
            if t is not None:
                return t[1]
        if name in self._externals:
            return self._external_data(name)
        with self._lock:
            if name not in self._data:
                raise KeyError(f"table {name} has no data")
            return self._data[name]

    def has_table(self, name: str) -> bool:
        with self._lock:
            return name in self._defs or name in self._transients or \
                name in self._externals

    def tables(self) -> list[str]:
        with self._lock:
            # index storage tables are internal (reachable by name, but
            # hidden from SHOW TABLES / information_schema enumeration)
            return sorted([n for n in self._defs
                           if not n.startswith("__idx__")]
                          + list(self._externals))
