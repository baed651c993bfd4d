"""The one traffic generator: a mix file's parameters -> the statements of a run.

A mix names statement templates and, for each, a parameter rule:
``"validation"`` (the one set the statement file gives as its validation
values), ``{"pool": N}`` (N distinct sets drawn from ``--seed`` inside
the ranges of the statement file) or ``"sequence"`` (a write statement:
every execution takes the next set of its ``rows`` function, and no set
is used twice in a run).  Statements are sent round-robin over the
templates by one client that waits for each answer; a template with
``"every": N`` takes N statements in a row at each of its turns
(default 1), cycling its own parameter sets.

Parameter kinds a statement file may use:

- ``int``: ``min``, ``max`` (inclusive)
- ``decimal``: ``min``, ``max``, ``step`` as strings, exact decimal steps
- ``date``: ``year_min``/``year_max`` and optional ``month_*``/``day_*``
  ranges (default 1), rendered ``YYYY-MM-DD``
- ``choice``: ``values``

and ``derived`` values: ``{"from": <parameter>, "add": <decimal>}``.
"""

from __future__ import annotations

import math
import re
import zlib
from decimal import Decimal

import numpy as np

_PLACEHOLDER = re.compile(r"\{([A-Za-z0-9_]+)\}")


def _count(spec: dict) -> int:
    kind = spec["kind"]
    if kind == "int":
        return int(spec["max"]) - int(spec["min"]) + 1
    if kind == "decimal":
        return int((Decimal(spec["max"]) - Decimal(spec["min"]))
                   / Decimal(spec["step"])) + 1
    if kind == "date":
        return ((spec["year_max"] - spec["year_min"] + 1)
                * (spec.get("month_max", 1) - spec.get("month_min", 1) + 1)
                * (spec.get("day_max", 1) - spec.get("day_min", 1) + 1))
    if kind == "choice":
        return len(spec["values"])
    raise ValueError(f"unknown parameter kind {kind!r}")


def _nth(spec: dict, i: int) -> str:
    """The i-th value of a parameter's range, as the text the SQL takes."""
    kind = spec["kind"]
    if kind == "int":
        return str(int(spec["min"]) + i)
    if kind == "decimal":
        return str(Decimal(spec["min"]) + i * Decimal(spec["step"]))
    if kind == "date":
        nd = spec.get("day_max", 1) - spec.get("day_min", 1) + 1
        nm = spec.get("month_max", 1) - spec.get("month_min", 1) + 1
        day = spec.get("day_min", 1) + i % nd
        month = spec.get("month_min", 1) + (i // nd) % nm
        year = spec["year_min"] + i // (nd * nm)
        return f"{year:04d}-{month:02d}-{day:02d}"
    return str(spec["values"][i])


def in_range(spec: dict, value: str) -> bool:
    return any(_nth(spec, i) == str(value) for i in range(_count(spec)))


def with_derived(statement: dict, params: dict) -> dict:
    out = dict(params)
    for name, rule in statement.get("derived", {}).items():
        out[name] = str(Decimal(out[rule["from"]]) + Decimal(rule["add"]))
    return out


def validation_params(statement: dict) -> dict:
    return {k: str(p["validation"])
            for k, p in statement["parameters"].items()}


def draw_pool(statement_name: str, statement: dict, n: int,
              seed: int) -> list[dict]:
    """``n`` distinct parameter sets, a pure function of the seed and the
    statement's name and ranges."""
    specs = statement["parameters"]
    names = sorted(specs)
    space = 1
    for k in names:
        space *= _count(specs[k])
    if n > space:
        raise ValueError(f"{statement_name}: a pool of {n} from {space} "
                         "possible parameter sets")
    rng = np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, zlib.crc32(statement_name.encode())])
    picks = rng.choice(space, size=n, replace=False)
    pool = []
    for flat in (int(x) for x in picks):
        params = {}
        for k in names:
            c = _count(specs[k])
            params[k] = _nth(specs[k], flat % c)
            flat //= c
        pool.append(params)
    return pool


def render(statement: dict, params: dict) -> str:
    values = with_derived(statement, params)

    def sub(m):
        if m.group(1) not in values:
            raise KeyError(f"no value for placeholder {m.group(0)}")
        return values[m.group(1)]

    return " ".join(_PLACEHOLDER.sub(sub, statement["sql"]).split())


class Item:
    """One (template, parameter set) of a run, with its SQL text.  A
    ``"sequence"`` template's item has no ``sql``: it is a slot, and
    whoever executes it binds the next set number (``writes.set_key``)."""

    __slots__ = ("template", "params", "sql", "key")

    def __init__(self, template: str, params: dict, sql: str | None):
        self.template = template
        self.params = params
        self.sql = sql
        self.key = template + "|" + ",".join(
            f"{k}={params[k]}" for k in sorted(params))


def schedule(traffic: dict, statements: dict, seed: int) -> list[Item]:
    """One round of the mix, in the order the client cycles through it: as
    many turns as it takes every template to come back to its first
    parameter set, each template sending ``every`` statements a turn."""
    per_template = []
    for t in traffic["templates"]:
        name = t["statement"]
        st = statements[name]
        rule = t.get("params", "validation")
        if rule == "sequence":
            items = [Item(name, {}, None)]
        else:
            if rule == "validation":
                sets = [validation_params(st)]
            elif isinstance(rule, dict) and "pool" in rule:
                sets = draw_pool(name, st, int(rule["pool"]), seed)
            else:
                raise ValueError(f"unknown parameter rule {rule!r}")
            items = [Item(name, p, render(st, p)) for p in sets]
        every = int(t.get("every", 1))
        if every < 1:
            raise ValueError(f"{name}: every must be 1 or more")
        per_template.append((items, every))
    turns = math.lcm(*(len(items) // math.gcd(len(items), every)
                       for items, every in per_template))
    return [items[(turn * every + j) % len(items)]
            for turn in range(turns)
            for items, every in per_template for j in range(every)]
