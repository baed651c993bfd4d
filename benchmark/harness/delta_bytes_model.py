"""The least bytes a committed delta must move into a resident relation.

Computed from the layout of the loaded relation as the device holds it
(``record["layouts"]``: element size of each column, a validity mask where
the column has one, the row mask) and from what the traffic committed, as
the dataset module's own row sets give it; never from a count of the
program's.  A row written moves one lane of every column with its validity
and its bit of the row mask; a lane cleared moves its bit of the row mask.
It is a floor, whatever implements the apply (scatter, copy, rebuild): a
share of it says how far the apply is from being bound by bytes.
"""

from __future__ import annotations


def lane_bytes(layout: dict) -> int:
    """Bytes of ONE lane over all columns of a relation, validity masks
    and the row mask included."""
    return layout["mask_itemsize"] + sum(
        c["itemsize"] + c["valid_itemsize"]
        for c in layout["columns"].values())


def delta_bytes(layout: dict, rows_written: int, lanes_cleared: int) -> int:
    return rows_written * lane_bytes(layout) \
        + lanes_cleared * max(layout["mask_itemsize"], 1)


def committed_between(statements: list, at: int) -> list:
    """The writes of ``statements`` (a run's records in the order sent)
    between the read at index ``at`` and the read before it."""
    out = []
    for rec in reversed(statements[:at]):
        if "k" not in rec:
            break
        out.append(rec)
    return out[::-1]


def table_delta(writes: list, statement_files: dict, dataset, scale: float,
                seed: int, table: str) -> tuple[int, int]:
    """-> (rows written into ``table``, lanes of it cleared at least) by
    the acknowledged transactions of ``writes``.  An insert writes its
    rows.  A delete by another column than the table's own row set gives
    (an order's lineitems by the order's key) clears one lane a value at
    least: the row sets name the keys, not the rows they matched."""
    rows = cleared = 0
    for rec in writes:
        st = statement_files[rec["template"]]
        sets = getattr(dataset, st["rows"])(scale, seed, rec["k"],
                                            int(st["batch"]))
        for binding, ack in zip(sets, rec["acks"]):
            if not ack:
                continue
            for op in st["transaction"]:
                values = binding[op["rows"]]
                if op.get("insert") == table:
                    rows += len(next(iter(values.values())))
                elif op.get("delete") == table:
                    cleared += len(values[op["column"]])
    return rows, cleared
