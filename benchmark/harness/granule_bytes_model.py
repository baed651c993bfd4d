"""The least bytes the chunk programs of a streamed statement must read.

A statement priced over the work area streams its large table granule by
granule through one chunk program: each execution reads one granule of the
streamed table (the granule's lanes times the widths of the columns the
statement reads of it, with the row mask) and, where the statement joins,
the columns it reads of each resident table, whole.  Computed from the
statement file's ``reads`` and the loaded relations' layout
(``record["layouts"]``: element sizes as the device holds them; a granule's
columns have the same), never from a count of the program's.  A table with
more lanes than a granule streams; one that fits a granule is resident.
It is a floor: a probe of a build side needs more than one pass.
"""

from __future__ import annotations

import re

_LANES = re.compile(r"^granule\(lanes=(\d+)\)")


def granule_lanes(plan_texts) -> int | None:
    """The lanes of a granule, from the ``gv$plan_cache`` rows of the chunk
    programs (``granule(lanes=N) <plan>``); ``None`` where the program has
    no such row, or rows of more than one shape."""
    found = {int(m.group(1)) for m in map(_LANES.match, plan_texts) if m}
    return found.pop() if len(found) == 1 else None


def lane_bytes(layout: dict, columns) -> int:
    """Bytes of ONE lane over ``columns`` of a relation, validity masks
    and the row mask included."""
    return max(layout["mask_itemsize"], 1) + sum(
        layout["columns"][c]["itemsize"]
        + layout["columns"][c]["valid_itemsize"] for c in columns)


def program_bytes(reads: dict, layouts: dict, lanes: int) -> int:
    """Bytes ONE execution of the statement's chunk program reads at
    least: a granule of each table that streams, each resident table
    whole."""
    total = 0
    for table, columns in reads.items():
        lay = layouts[table]
        total += lane_bytes(lay, columns) * (
            lanes if lay["capacity"] > lanes else lay["capacity"])
    return total


def least_seconds(reads: dict, layouts: dict, lanes: int, executions: int,
                  hbm_bytes_per_s: float) -> float:
    return executions * program_bytes(reads, layouts, lanes) / hbm_bytes_per_s
