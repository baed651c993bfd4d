"""SQLite as the plain reference: the same statements on the same data.

Copied from ``oceanbase_tpu/bench/oracle.py``: loads generated tables into
an in-memory SQLite database and translates the MySQL-dialect text into
SQLite's (date literals and arithmetic, EXTRACT, SUBSTRING).  ``types``
maps a column to ``("decimal", precision, scale)`` or ``("date",)``.
"""

from __future__ import annotations

import re
import sqlite3

import numpy as np

_EPOCH = np.datetime64("1970-01-01", "D")


def _py_columns(cols: dict, types: dict) -> list[list]:
    """The columns as SQLite takes them: decimals as doubles, dates as
    ISO text."""
    pycols = []
    for c, arr in cols.items():
        t = types.get(c)
        if t is not None and t[0] == "decimal":
            pycols.append([v / (10 ** t[2]) for v in arr.tolist()])
        elif t is not None and t[0] == "date":
            pycols.append((_EPOCH + np.asarray(arr).astype(
                "timedelta64[D]")).astype(str).tolist())
        elif arr.dtype == object or arr.dtype.kind in "US":
            pycols.append([str(v) for v in arr])
        else:
            pycols.append(arr.tolist())
    return pycols


def insert_rows(conn: sqlite3.Connection, name: str, cols: dict,
                types: dict):
    ph = ",".join("?" * len(cols))
    conn.executemany(f"insert into {name} ({', '.join(cols)}) values ({ph})",
                     list(zip(*_py_columns(cols, types))))


def delete_rows(conn: sqlite3.Connection, name: str, column: str, values):
    conn.executemany(f"delete from {name} where {column} = ?",
                     [(v,) for v in np.asarray(values).tolist()])


def load_sqlite(tables: dict, types: dict) -> sqlite3.Connection:
    conn = sqlite3.connect(":memory:")
    for name, cols in tables.items():
        conn.execute(f"create table {name} ({', '.join(cols)})")
        insert_rows(conn, name, cols, types)
    # index every *key column (PKs and FKs) so correlated subqueries and
    # joins in the ORACLE don't go quadratic at SF>=0.1 — the oracle's
    # job is to be correct AND fast enough to produce SF1 evidence
    for name, cols in tables.items():
        for c in cols:
            if c.endswith("key"):
                conn.execute(
                    f"create index idx_{name}_{c} on {name} ({c})")
    conn.execute("analyze")
    conn.commit()
    return conn


_DATE_RE = re.compile(r"date\s+'([0-9-]+)'", re.I)
_INTERVAL_RE = re.compile(
    r"'([0-9-]+)'\s*([+-])\s*interval\s+'(\d+)'\s+(year|month|day)", re.I)
_EXTRACT_RE = re.compile(r"extract\s*\(\s*year\s+from\s+([a-z0-9_.]+)\s*\)", re.I)
_SUBSTR_RE = re.compile(
    r"substring\s*\(\s*([a-z0-9_.]+)\s+from\s+(\d+)\s+for\s+(\d+)\s*\)", re.I)


def to_sqlite_sql(sql: str) -> str:
    s = _DATE_RE.sub(r"'\1'", sql)
    # fold '<date>' +/- interval 'n' unit  -> literal date
    while True:
        m = _INTERVAL_RE.search(s)
        if not m:
            break
        base, sign, n, unit = m.groups()
        d = np.datetime64(base, "D")
        k = int(n) if sign == "+" else -int(n)
        if unit.lower() == "day":
            d2 = d + np.timedelta64(k, "D")
        elif unit.lower() == "month":
            mm = d.astype("datetime64[M]") + np.timedelta64(k, "M")
            day = (d - d.astype("datetime64[M]")).astype(int)
            d2 = mm.astype("datetime64[D]") + np.timedelta64(int(day), "D")
        else:
            yy = d.astype("datetime64[Y]") + np.timedelta64(k, "Y")
            rest = d - d.astype("datetime64[Y]").astype("datetime64[D]")
            d2 = yy.astype("datetime64[D]") + rest
        s = s[: m.start()] + f"'{d2}'" + s[m.end():]
    s = _EXTRACT_RE.sub(r"cast(strftime('%Y', \1) as integer)", s)
    s = _SUBSTR_RE.sub(r"substr(\1, \2, \3)", s)
    return s


def run_oracle(conn: sqlite3.Connection, sql: str) -> list[tuple]:
    cur = conn.execute(to_sqlite_sql(sql))
    return [tuple(r) for r in cur.fetchall()]
