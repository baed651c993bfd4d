"""The system under test, and the only file of the benchmark that imports it.

Everything the harness does to the program goes through ``System``: boot a
``Database`` in a fresh directory, settings as SQL statements, direct load
and ``ANALYZE``, ``Session.execute`` with the rows fetched to the host, and
reads of the program's own counters (``gv$sql_audit``, ``gv$plan_cache``,
``gv$sysstat``, ``gv$cost_units``, ``show trace``) through its SQL surface.
"""

from __future__ import annotations

import json
import os
import shutil

# the program first: its import places the persistent compile cache
# (JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache) and
# turns on 64-bit integers before anything compiles
import oceanbase_tpu  # noqa: F401
import jax

from oceanbase_tpu.datatypes import SqlType
from oceanbase_tpu.server import Database

_AUDIT_COLUMNS = ("sql", "elapsed_s", "bind_s", "lower_s", "xla_compile_s",
                  "dispatch_s", "host_s", "device_s", "queue_s", "error")


def device_info() -> dict:
    """The devices as JAX reports them."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": str(devs[0].device_kind),
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where the backend
    reports none)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def compile_cache_dir() -> str:
    return str(jax.config.jax_compilation_cache_dir)


def _sql_type(t: tuple) -> SqlType:
    if t[0] == "decimal":
        return SqlType.decimal(t[1], t[2])
    if t[0] == "date":
        return SqlType.date()
    raise ValueError(f"unknown column type {t!r}")


class Answer:
    """One statement's result on the host: ``rows`` as the client sees
    them, ``arrays`` the raw result columns (decimals as scaled ints)."""

    __slots__ = ("names", "rows", "arrays")

    def __init__(self, names, rows, arrays):
        self.names = names
        self.rows = rows
        self.arrays = arrays


class System:
    def __init__(self, root: str):
        shutil.rmtree(root, ignore_errors=True)
        os.makedirs(os.path.dirname(root), exist_ok=True)
        self.root = root
        self.db = Database(root)
        self.session = self.db.session()

    def close(self):
        try:
            self.session.close()
        finally:
            self.db.close()

    # -- set-up ---------------------------------------------------------
    def apply(self, statements: list[str]):
        for sql in statements:
            self.session.execute(sql)

    def load_table(self, name: str, arrays: dict, types: dict,
                   primary_key: list[str]):
        self.session.catalog.load_numpy(
            name, arrays,
            types={c: _sql_type(t) for c, t in types.items() if c in arrays},
            primary_key=primary_key)

    def analyze(self, name: str):
        self.session.execute(f"analyze table {name}")

    def relation_layout(self, table: str) -> dict:
        """How the device holds a loaded table: bucket capacity and the
        element sizes of its columns, validity masks and row mask."""
        rel = self.session.catalog.table_data(table)
        return {
            "capacity": int(rel.capacity),
            "mask_itemsize": 0 if rel.mask is None
            else int(rel.mask.dtype.itemsize),
            "columns": {
                name: {"itemsize": int(col.data.dtype.itemsize),
                       "valid_itemsize": 0 if col.valid is None
                       else int(col.valid.dtype.itemsize)}
                for name, col in rel.columns.items()}}

    # -- the served path --------------------------------------------------
    def execute(self, sql: str):
        """``Session.execute``: returns when the result is on the host as
        columns."""
        return self.session.execute(sql)

    @staticmethod
    def fetch(result) -> Answer:
        """The rows as a client reads them."""
        return Answer(list(result.names), result.rows(), result.arrays)

    # -- the program's own counters, through SQL --------------------------
    def _dicts(self, sql: str) -> list[dict]:
        r = self.session.execute(sql)
        return [dict(zip(r.names, row)) for row in r.rows()]

    def audit_rows(self) -> list[dict]:
        """``gv$sql_audit`` in ring order (oldest first)."""
        return self._dicts(
            f"select {', '.join(_AUDIT_COLUMNS)} from gv$sql_audit")

    def plan_cache(self) -> dict:
        """plan_hash -> {"xla_trace_count", "plan_text"} of every cached
        plan that reads no virtual table (the harness's own reads of the
        ``gv$`` tables are plans too)."""
        return {r["plan_hash"]: {"xla_trace_count": int(r["xla_trace_count"]),
                                 "plan_text": r["plan_text"]}
                for r in self._dicts("select plan_hash, plan_text, "
                                     "xla_trace_count from gv$plan_cache")
                if "gv$" not in r["plan_text"]}

    def monitored_plans(self) -> list[dict]:
        """``gv$sql_plan_monitor``'s executions (one dict each, oldest
        first): the record timestamp, the capacity-insensitive digest of
        the plan and the path it ran on.  Serial and PX plans alike."""
        seen, out = set(), []
        for r in self._dicts("select ts, logical_hash, path "
                             "from gv$sql_plan_monitor"):
            if r["ts"] not in seen:
                seen.add(r["ts"])
                out.append(r)
        return sorted(out, key=lambda r: r["ts"])

    def counters(self) -> dict:
        """``gv$sysstat`` counters by name; one never bumped is absent."""
        return {r["stat_name"]: float(r["value"]) for r in self._dicts(
            "select stat_name, value from gv$sysstat "
            "where stat_type = 'counter'")}

    def cost_constants(self) -> dict:
        """The boot calibration's constants (the CBO prices plans with
        them)."""
        return {r["name"]: float(r["value"]) for r in self._dicts(
            "select name, value from gv$cost_units where kind = 'constant'")}

    def last_path(self) -> str:
        """The execution path the session's last statement recorded."""
        for row in self.session.execute("show trace").rows():
            if row[0].strip() == "execute":
                tags = json.loads(row[4])
                return ("dtl" if tags.get("dtl") else
                        "px" if tags.get("px") else "serial")
        return "unrecorded"
