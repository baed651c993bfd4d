"""The least bytes a statement must move: ONE pass over the columns it reads.

Computed from the layout of the loaded relations as the device holds them
(bucket capacity, element size of each column, a validity mask where the
column has one) plus the relation's row mask.  It is a floor and is named
for what it is: a join or a sort needs more than one pass, so a share of
this floor is a ceiling on the share of the real roofline, and is honest
for a single streaming scan.
"""

from __future__ import annotations


def one_pass_bytes(reads: dict, layouts: dict) -> int:
    """``reads``: table -> columns (a statement file's ``reads``);
    ``layouts``: table -> {"capacity", "mask_itemsize", "columns":
    {name: {"itemsize", "valid_itemsize"}}} as read from the loaded
    relations."""
    total = 0
    for table, cols in reads.items():
        lay = layouts[table]
        per_lane = lay["mask_itemsize"]
        for c in cols:
            col = lay["columns"][c]
            per_lane += col["itemsize"] + col["valid_itemsize"]
        total += per_lane * lay["capacity"]
    return total


def least_seconds(reads: dict, layouts: dict, hbm_bytes_per_s: float) -> float:
    return one_pass_bytes(reads, layouts) / hbm_bytes_per_s
