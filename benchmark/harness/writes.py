"""Write statements: what a transaction sends, as SQL text.

A statement file with ``writes`` (the tables it changes) names ``rows``,
a function of the configuration's dataset module:
``rows(scale, seed, k, batch) -> [binding, ...]``, the k-th set as a pure
function of its arguments, cut into one binding per transaction; a
binding is ``{name: {column: array}}``.  ``transaction`` lists what the
client sends between ``begin`` and ``commit``:

- ``{"insert": <table>, "rows": <name>}``: one multi-row
  ``insert into <table> (<columns>) values (...), (...)`` of the binding's
  row set ``<name>``
- ``{"delete": <table>, "where": <column>, "rows": <name>, "column": <c>}``:
  ``delete from <table> where <column> in (...)`` with the values of
  column ``<c>`` of the row set ``<name>``

The reference (``reference.py``) applies the same operations to its own
tables from the same bindings; it never sees this text.
"""

from __future__ import annotations

import numpy as np

_EPOCH = np.datetime64("1970-01-01", "D")


def set_key(template: str, k: int) -> str:
    """The key of a ``"sequence"`` template's k-th execution."""
    return f"{template}|k={k}"


def bindings(statement: dict, dataset, scale: float, seed: int, k: int):
    return getattr(dataset, statement["rows"])(
        scale, seed, k, int(statement["batch"]))


def _literals(values, type_: tuple | None) -> list[str]:
    """One column's values as SQL literals.  Decimals are scaled integers
    and dates days since 1970-01-01, as the dataset module makes them."""
    if type_ is not None and type_[0] == "decimal":
        scale = 10 ** type_[2]
        return [f"{'-' if v < 0 else ''}{abs(v) // scale}."
                f"{abs(v) % scale:0{type_[2]}d}" for v in values.tolist()]
    if type_ is not None and type_[0] == "date":
        days = (_EPOCH + np.asarray(values).astype("timedelta64[D]"))
        return [f"date '{d}'" for d in days.astype(str).tolist()]
    if values.dtype == object or values.dtype.kind in "US":
        return ["'" + str(v).replace("'", "''") + "'" for v in values]
    return [str(v) for v in values.tolist()]


def render(op: dict, binding: dict, types: dict) -> str | None:
    """The SQL of one operation of a transaction; None where the binding
    gives it no rows."""
    rows = binding[op["rows"]]
    if "insert" in op:
        cols = list(rows)
        if len(rows[cols[0]]) == 0:
            return None
        lits = [_literals(rows[c], types.get(c)) for c in cols]
        return (f"insert into {op['insert']} ({', '.join(cols)}) values "
                + ", ".join("(" + ", ".join(r) + ")" for r in zip(*lits)))
    values = rows[op["column"]]
    if len(values) == 0:
        return None
    return (f"delete from {op['delete']} where {op['where']} in ("
            + ", ".join(_literals(values, types.get(op["column"]))) + ")")


def transactions(statement: dict, dataset, types: dict, scale: float,
                 seed: int, k: int) -> list[list[str]]:
    """The k-th set as the SQL of its transactions, ``begin`` and
    ``commit`` left to the client."""
    out = []
    for binding in bindings(statement, dataset, scale, seed, k):
        sqls = [render(op, binding, types) for op in statement["transaction"]]
        out.append([s for s in sqls if s is not None])
    return out
