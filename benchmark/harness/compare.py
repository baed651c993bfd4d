"""The comparison that decides ``correct``: the system's rows against the
reference's.  Copied from ``oceanbase_tpu/bench/oracle.py::rows_match``.

Integers and strings compare exactly; a value that is a float on either
side compares to ``rtol`` (decimals reach SQLite as doubles, and so do
``avg`` results).  Statements with an exact reference are held to it bit
for bit by their reference module, beside this."""

from __future__ import annotations

RTOL = 1e-6


def rows_match(got: list[tuple], want: list[tuple], ordered: bool,
               rtol: float = RTOL) -> tuple[bool, str, float]:
    """-> (equal, why not, the widest relative gap between two floats that
    were compared)."""
    gap = 0.0
    if len(got) != len(want):
        return False, f"row count {len(got)} != {len(want)}", gap

    def key(row):
        return tuple((x is None, round(x, 6) if isinstance(x, float) else x)
                     for x in row)

    g = got if ordered else sorted(got, key=key)
    w = want if ordered else sorted(want, key=key)
    for i, (gr, wr) in enumerate(zip(g, w)):
        if len(gr) != len(wr):
            return False, f"row {i} arity mismatch", gap
        for j, (a, b) in enumerate(zip(gr, wr)):
            if a is None or b is None:
                if a is not b:
                    return False, f"row {i} col {j}: {a!r} != {b!r}", gap
                continue
            if isinstance(a, float) or isinstance(b, float):
                fa, fb = float(a), float(b)
                gap = max(gap, abs(fa - fb) / max(1.0, abs(fa), abs(fb)))
                if gap > rtol:
                    return False, f"row {i} col {j}: {fa} != {fb}", gap
                continue
            if a != b:
                return False, f"row {i} col {j}: {a!r} != {b!r}", gap
    return True, "", gap
