"""Compile/evaluate expression IR over device relations.

``eval_expr(expr, rel)`` runs under jax tracing and returns a Column; the
whole expression DAG fuses into the enclosing operator's XLA computation.
This replaces the reference's three eval ABIs + frame layout
(src/sql/engine/expr/ob_expr.h:953-963, :1030-1075): XLA does buffer
placement, common-subexpression reuse and elementwise fusion that the
reference implements by hand (eval flags, frames, SIMD .ipp kernels).

Null semantics: every sub-expression yields (data, valid).  Three-valued
logic is implemented exactly for AND/OR/NOT (known-true/known-false lanes),
matching MySQL semantics the reference encodes per-expr.

String semantics: string columns are order-preserving dictionary codes; all
string predicates/functions lower to host work over the dictionary plus a
device gather/compare (see vector/column.py StringDict).
"""

from __future__ import annotations

import contextlib
import contextvars
import re

import jax.numpy as jnp
import numpy as np

from oceanbase_tpu.datatypes import (
    SqlType,
    TypeKind,
    add_result,
    common_numeric,
    date_to_days,
    div_result,
    mul_result,
)
from oceanbase_tpu.expr import ir
from oceanbase_tpu.vector.column import Column, Relation, StringDict

_POW10 = [10**i for i in range(38)]


def _all_valid(n):
    return jnp.ones(n, dtype=jnp.bool_)


# ---------------------------------------------------------------------------
# literal -> (host scalar, SqlType)
# ---------------------------------------------------------------------------

def literal_value(e: ir.Literal):
    v, t = e.value, e.dtype
    if t is None:
        if v is None:
            t = SqlType.null()
        elif isinstance(v, bool):
            t = SqlType.bool_()
        elif isinstance(v, int):
            t = SqlType.int_()
        elif isinstance(v, float):
            t = SqlType.double()
        elif isinstance(v, str):
            t = SqlType.string()
        else:
            raise TypeError(f"unsupported literal {v!r}")
    if t.kind == TypeKind.DATE and isinstance(v, str):
        v = date_to_days(v)
    if t.kind == TypeKind.DECIMAL and isinstance(v, str):
        # exact decimal parse: '0.06' with scale from text; trailing zeros
        # stripped so '0.0001000000' costs scale 4, not 10 (keeps products
        # inside int64 range)
        neg = v.startswith("-")
        body = v.lstrip("+-")
        if "." in body:
            ip, fp = body.split(".")
        else:
            ip, fp = body, ""
        fp = fp.rstrip("0")
        scale = len(fp)
        iv = int(ip or "0") * _POW10[scale] + int(fp or "0")
        v = -iv if neg else iv
        t = SqlType.decimal(t.precision or 15, scale)
    return v, t


def _lit_column(e: ir.Literal, n: int) -> Column:
    v, t = literal_value(e)
    if v is None:
        data = jnp.zeros(n, dtype=jnp.int64)
        return Column(data=data, valid=jnp.zeros(n, dtype=jnp.bool_), dtype=t)
    if t.kind == TypeKind.STRING:
        # a bare string literal column: single-value dictionary
        sd = StringDict(np.array([v]))
        return Column(
            data=jnp.zeros(n, dtype=jnp.int32), valid=None, dtype=t, sdict=sd
        )
    data = jnp.full(n, v, dtype=jnp.dtype(t.np_dtype))
    return Column(data=data, valid=None, dtype=t)


# ---------------------------------------------------------------------------
# numeric alignment helpers
# ---------------------------------------------------------------------------

def _to_float(c: Column, kind=TypeKind.DOUBLE) -> Column:
    dt = jnp.float64 if kind == TypeKind.DOUBLE else jnp.float32
    if c.dtype.kind == TypeKind.DECIMAL:
        data = c.data.astype(dt) / _POW10[c.dtype.scale]
    else:
        data = c.data.astype(dt)
    return Column(data=data, valid=c.valid, dtype=SqlType(kind))


def _align_pair(a: Column, b: Column) -> tuple:
    """Align two numeric/date columns to a common physical representation.

    Returns (a_data, b_data, common SqlType)."""
    ta, tb = a.dtype, b.dtype
    # date/datetime compare & arith against ints happens raw
    if ta.kind in (TypeKind.DATE, TypeKind.DATETIME) or tb.kind in (
        TypeKind.DATE,
        TypeKind.DATETIME,
    ):
        ct = ta if ta.kind in (TypeKind.DATE, TypeKind.DATETIME) else tb
        return a.data.astype(jnp.int64), b.data.astype(jnp.int64), ct
    if ta.kind == TypeKind.BOOL and tb.kind == TypeKind.BOOL:
        return a.data, b.data, ta
    ct = common_numeric(ta, tb)
    if ct.kind in (TypeKind.FLOAT, TypeKind.DOUBLE):
        return _to_float(a, ct.kind).data, _to_float(b, ct.kind).data, ct
    if ct.kind == TypeKind.DECIMAL:
        s = max(ta.scale, tb.scale)
        da = a.data.astype(jnp.int64) * _POW10[s - ta.scale]
        db = b.data.astype(jnp.int64) * _POW10[s - tb.scale]
        return da, db, SqlType(TypeKind.DECIMAL, max(ta.precision, tb.precision), s)
    return a.data.astype(jnp.int64), b.data.astype(jnp.int64), ct


def _merge_valid(a: Column, b: Column):
    if a.valid is None:
        return b.valid
    if b.valid is None:
        return a.valid
    return a.valid & b.valid


# ---------------------------------------------------------------------------
# string predicate lowering
# ---------------------------------------------------------------------------

def _string_cmp(op: str, c: Column, s: str, n: int) -> Column:
    """Compare a dict-encoded column against a string literal on codes."""
    sd = c.sdict
    assert sd is not None, "string compare on non-dict column"
    if op in ("=", "!="):
        code = sd.code_of(s)
        if code < 0:
            val = jnp.zeros(n, dtype=jnp.bool_) if op == "=" else jnp.ones(n, jnp.bool_)
        else:
            val = (c.data == code) if op == "=" else (c.data != code)
        return Column(data=val, valid=c.valid, dtype=SqlType.bool_())
    # order-preserving dict: translate to a code boundary
    lb = sd.lower_bound(s)
    exists = sd.code_of(s) >= 0
    if op == "<":
        val = c.data < lb
    elif op == "<=":
        val = c.data < (lb + 1 if exists else lb)
    elif op == ">":
        val = c.data >= (lb + 1 if exists else lb)
    elif op == ">=":
        val = c.data >= lb
    else:  # pragma: no cover
        raise ValueError(op)
    return Column(data=val, valid=c.valid, dtype=SqlType.bool_())


US_PER_DAY = 86_400_000_000


def _temporal_literal(s: str, kind: TypeKind) -> int:
    """'1994-01-01[ hh:mm:ss]' -> days (DATE) or microseconds (DATETIME)."""
    date_part = s.split(" ")[0]
    days = date_to_days(date_part)
    if kind == TypeKind.DATE:
        return days
    us = days * US_PER_DAY
    if " " in s:
        hms = s.split(" ", 1)[1].split(":")
        parts = [float(x) for x in hms] + [0.0] * (3 - len(hms))
        us += int((parts[0] * 3600 + parts[1] * 60 + parts[2]) * 1_000_000)
    return us


# ---------------------------------------------------------------------------
# LIKE over a large dictionary: the lookup table is an INPUT of the program.
# As a constant it put the dictionary's every value into the HLO, so a table
# loaded from other data (a high-cardinality string column such as TPC-H's
# p_name) was another program to the persistent compile cache: minutes of
# compile for a statement whose plan had not changed.
# ---------------------------------------------------------------------------

#: a dictionary of at least this many values hands its tables over as inputs
LUT_INPUT_MIN = 4096
#: the pseudo table of a program's inputs that holds them, a column each
LUTS_TABLE = "__dictionary_luts__"

_luts: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "ob_tpu_dictionary_luts", default=None)
_LUT_CACHE: dict = {}       # (name, where) -> the placed table, newest last
_LUT_CACHE_MAX = 32


def lut_name(sdict: StringDict, pattern: str) -> str:
    """The column of ``LUTS_TABLE`` that holds ``pattern`` over ``sdict``."""
    return f"{sdict._content_digest():016x}|{pattern}"


def like_lut(sdict: StringDict, pattern: str) -> np.ndarray:
    """bool[|dict|]: which values of ``sdict`` match ``pattern``."""
    rx = re.compile(like_to_regex(pattern))
    return sdict.lut(lambda s: rx.match(s) is not None)


def dictionary_luts(patterns, tables: dict, mesh=None) -> Relation:
    """What a program whose plan holds LIKE ``patterns`` takes as
    ``LUTS_TABLE``: for every dictionary of ``LUT_INPUT_MIN`` values or
    more among ``tables``' columns and every pattern, the pattern's table
    over it, padded with False to the dictionary's bucket (a shape that a
    reload of other data keeps) and placed once (on ``mesh``, whole on
    every device, for a shard program).  Which pattern meets which column
    is the trace's business: a table nobody reads is an unused input."""
    from oceanbase_tpu.vector.column import bucket_capacity

    cols = {}
    big = {c.sdict for rel in tables.values() for c in rel.columns.values()
           if c.sdict is not None and c.sdict.size >= LUT_INPUT_MIN}
    for sdict in big:
        for pattern in patterns:
            name = lut_name(sdict, pattern)
            placed = _LUT_CACHE.pop((name, mesh), None)
            if placed is None:
                lut = np.zeros(bucket_capacity(sdict.size), dtype=bool)
                lut[:sdict.size] = like_lut(sdict, pattern)
                if mesh is None:
                    placed = jnp.asarray(lut)
                else:
                    import jax
                    from jax.sharding import NamedSharding, PartitionSpec

                    placed = jax.device_put(
                        lut, NamedSharding(mesh, PartitionSpec()))
            _LUT_CACHE[name, mesh] = placed
            while len(_LUT_CACHE) > _LUT_CACHE_MAX:
                _LUT_CACHE.pop(next(iter(_LUT_CACHE)))
            cols[name] = Column(placed, None, SqlType.bool_(), None)
    return Relation(columns=cols, mask=None)


@contextlib.contextmanager
def provided_luts(luts: Relation | None):
    """While a program is traced: the tables its inputs brought."""
    tok = _luts.set(None if luts is None else
                    {n: c.data for n, c in luts.columns.items()})
    try:
        yield
    finally:
        _luts.reset(tok)


def like_patterns(x, out: set | None = None) -> set:
    """Every LIKE pattern anywhere inside a plan (its nodes, their
    expressions and aggregate specs are dataclasses)."""
    import dataclasses

    out = set() if out is None else out
    if isinstance(x, ir.Like):
        out.add(x.pattern)
    if isinstance(x, dict):
        x = list(x.values())
    if isinstance(x, (list, tuple, set, frozenset)):
        for v in x:
            like_patterns(v, out)
    elif dataclasses.is_dataclass(x) and not isinstance(x, (type, SqlType)):
        for f in dataclasses.fields(x):
            like_patterns(getattr(x, f.name), out)
    return out


def like_to_regex(pattern: str) -> str:
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return "^" + "".join(out) + "$"


# ---------------------------------------------------------------------------
# 3-valued logic lanes
# ---------------------------------------------------------------------------

def _tf(c: Column):
    v = c.valid_or_true()
    d = c.data
    if d.dtype != jnp.bool_:
        # SQL truthiness of a numeric predicate (MATCH score, 0/1 ints)
        d = d != 0
    return d & v, (~d) & v


# ---------------------------------------------------------------------------
# date decomposition (Hinnant civil-from-days, branch-free for XLA)
# ---------------------------------------------------------------------------

def civil_from_days(z):
    z = z.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(
        doe - doe // 1460 + doe // 36524 - doe // 146096, 365
    )
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


# ---------------------------------------------------------------------------
# main evaluator
# ---------------------------------------------------------------------------

def _in_operand(vals: list) -> list:
    """An IN list's values at a ladder length (the last one repeated): the
    membership test is a program per (lanes, list length), and a DELETE or
    UPDATE ... WHERE key IN (...) evaluates it eagerly, statement after
    statement with lists of any length."""
    from oceanbase_tpu.vector import bucket_capacity

    return vals + [vals[-1]] * (bucket_capacity(len(vals)) - len(vals))


def eval_expr(e: ir.Expr, rel: Relation) -> Column:
    n = rel.capacity

    if isinstance(e, ir.ColumnRef):
        return rel.columns[e.name]

    if isinstance(e, ir.Literal):
        return _lit_column(e, n)

    if isinstance(e, ir.Cmp):
        return _eval_cmp(e, rel, n)

    if isinstance(e, ir.Arith):
        return _eval_arith(e, rel, n)

    if isinstance(e, ir.Logic):
        cols = [eval_expr(a, rel) for a in e.args]
        t, f = _tf(cols[0])
        for c in cols[1:]:
            t2, f2 = _tf(c)
            if e.op == "and":
                t, f = t & t2, f | f2
            else:
                t, f = t | t2, f & f2
        return Column(data=t, valid=t | f, dtype=SqlType.bool_())

    if isinstance(e, ir.Not):
        c = eval_expr(e.arg, rel)
        return Column(data=~c.data, valid=c.valid, dtype=SqlType.bool_())

    if isinstance(e, ir.IsNull):
        c = eval_expr(e.arg, rel)
        isnull = (
            jnp.zeros(n, dtype=jnp.bool_) if c.valid is None else ~c.valid
        )
        return Column(
            data=(~isnull if e.negated else isnull), valid=None,
            dtype=SqlType.bool_(),
        )

    if isinstance(e, ir.InList):
        c = eval_expr(e.arg, rel)
        if c.dtype.is_string and c.sdict is not None:
            codes = [c.sdict.code_of(_as_str(v)) for v in e.values]
            codes = [cd for cd in codes if cd >= 0]
            if not codes:
                val = jnp.zeros(n, dtype=jnp.bool_)
            else:
                val = jnp.isin(c.data, jnp.asarray(_in_operand(codes),
                                                   dtype=c.data.dtype))
        else:
            vals = []
            for v in e.values:
                lv, lt = literal_value(v if isinstance(v, ir.Literal) else ir.Literal(v))
                if c.dtype.kind == TypeKind.DECIMAL and lt.kind in (
                    TypeKind.DECIMAL, TypeKind.INT,
                ):
                    ls = lt.scale if lt.kind == TypeKind.DECIMAL else 0
                    if ls <= c.dtype.scale:
                        lv = lv * _POW10[c.dtype.scale - ls]
                    else:
                        # literal more precise than the column: exact match
                        # only possible when the extra digits are zero
                        q, r = divmod(lv, _POW10[ls - c.dtype.scale])
                        if r != 0:
                            continue  # can never equal a column value
                        lv = q
                elif c.dtype.kind in (TypeKind.DATE, TypeKind.DATETIME) and \
                        isinstance(lv, str):
                    lv = _temporal_literal(lv, c.dtype.kind)
                vals.append(lv)
            if not vals:
                val = jnp.zeros(n, dtype=jnp.bool_)
            else:
                val = jnp.isin(c.data, jnp.asarray(_in_operand(vals)))
        if e.negated:
            val = ~val
        return Column(data=val, valid=c.valid, dtype=SqlType.bool_())

    if isinstance(e, ir.Like):
        c = eval_expr(e.arg, rel)
        assert c.sdict is not None, "LIKE requires a dict-encoded column"
        luts, name = _luts.get() or {}, lut_name(c.sdict, e.pattern)
        # a large dictionary's table is an input; a small one's a constant
        lut = luts[name] if name in luts \
            else jnp.asarray(like_lut(c.sdict, e.pattern))
        val = lut[jnp.clip(c.data, 0, lut.shape[0] - 1)]
        if e.negated:
            val = ~val
        return Column(data=val, valid=c.valid, dtype=SqlType.bool_())

    if isinstance(e, ir.Case):
        return _eval_case(e, rel, n)

    if isinstance(e, ir.Cast):
        c = eval_expr(e.arg, rel)
        return cast_column(c, e.dtype)

    if isinstance(e, ir.FuncCall):
        return _eval_func(e, rel, n)

    raise NotImplementedError(f"eval of {type(e).__name__}")


def _as_str(v):
    if isinstance(v, ir.Literal):
        return v.value
    return v


def _eval_cmp(e: ir.Cmp, rel: Relation, n: int) -> Column:
    # string-vs-literal fast path on dictionary codes
    lc_is_str_lit = isinstance(e.left, ir.Literal) and isinstance(e.left.value, str)
    rc_is_str_lit = isinstance(e.right, ir.Literal) and isinstance(e.right.value, str)
    if rc_is_str_lit:
        lcol = eval_expr(e.left, rel)
        if lcol.dtype.is_string:
            return _string_cmp(e.op, lcol, e.right.value, n)
        if lcol.dtype.kind in (TypeKind.DATE, TypeKind.DATETIME):
            rv = _temporal_literal(e.right.value, lcol.dtype.kind)
            return _cmp_data(e.op, lcol.data.astype(jnp.int64),
                             jnp.full(n, rv, jnp.int64), lcol.valid)
    if lc_is_str_lit:
        flipped = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "=", "!=": "!="}
        return _eval_cmp(ir.Cmp(flipped[e.op], e.right, e.left), rel, n)

    a = eval_expr(e.left, rel)
    b = eval_expr(e.right, rel)
    if a.dtype.is_string and b.dtype.is_string:
        return _string_col_cmp(e.op, a, b)
    da, db, _ = _align_pair(a, b)
    return _cmp_data(e.op, da, db, _merge_valid(a, b))


def _cmp_data(op, da, db, valid) -> Column:
    fns = {
        "=": jnp.equal, "!=": jnp.not_equal, "<": jnp.less,
        "<=": jnp.less_equal, ">": jnp.greater, ">=": jnp.greater_equal,
    }
    return Column(data=fns[op](da, db), valid=valid, dtype=SqlType.bool_())


def _string_col_cmp(op, a: Column, b: Column) -> Column:
    if a.sdict is b.sdict:
        return _cmp_data(op, a.data, b.data, _merge_valid(a, b))
    # translate a's codes into b's dictionary space (host, O(|dict|))
    assert a.sdict is not None and b.sdict is not None
    pos = np.searchsorted(b.sdict.values, a.sdict.values).astype(np.int64)
    exact = np.zeros(a.sdict.size, dtype=bool)
    inb = pos < b.sdict.size
    exact[inb] = b.sdict.values[pos[inb]] == a.sdict.values[inb]
    posm = jnp.asarray(pos)[jnp.clip(a.data, 0, a.sdict.size - 1)]
    exm = jnp.asarray(exact)[jnp.clip(a.data, 0, a.sdict.size - 1)]
    valid = _merge_valid(a, b)
    if op == "=":
        return Column(data=exm & (posm == b.data), valid=valid, dtype=SqlType.bool_())
    if op == "!=":
        return Column(data=~(exm & (posm == b.data)), valid=valid, dtype=SqlType.bool_())
    # order comparisons: a < b  <=>  rank(a in b-space) < code_b, with ties
    # broken by exact membership
    raise NotImplementedError("ordered compare across dictionaries")


def _eval_arith(e: ir.Arith, rel: Relation, n: int) -> Column:
    a = eval_expr(e.left, rel)
    b = eval_expr(e.right, rel)
    valid = _merge_valid(a, b)
    ta, tb = a.dtype, b.dtype

    # temporal arithmetic: DATE ± days, DATETIME ± days, DATE - DATE;
    # "INT + DATE" commutes, "INT - DATE" is a type error
    temporal = (TypeKind.DATE, TypeKind.DATETIME)
    if tb.kind in temporal and ta.kind == TypeKind.INT:
        if e.op == "+":
            a, b, ta, tb = b, a, tb, ta
        else:
            raise TypeError(f"cannot apply {e.op!r} to INT and {tb.kind.name}")
    if ta.kind in temporal and tb.kind == TypeKind.INT and e.op in "+-":
        d = a.data.astype(jnp.int64)
        o = b.data.astype(jnp.int64)
        if ta.kind == TypeKind.DATETIME:
            o = o * US_PER_DAY
        data = d + o if e.op == "+" else d - o
        if ta.kind == TypeKind.DATE:
            data = data.astype(jnp.int32)
        return Column(data=data, valid=valid, dtype=ta)
    if ta.kind in temporal and tb.kind in temporal and e.op == "-":
        da = a.data.astype(jnp.int64)
        db = b.data.astype(jnp.int64)
        if ta.kind == TypeKind.DATETIME or tb.kind == TypeKind.DATETIME:
            if ta.kind == TypeKind.DATE:
                da = da * US_PER_DAY
            if tb.kind == TypeKind.DATE:
                db = db * US_PER_DAY
        data = da - db
        return Column(data=data, valid=valid, dtype=SqlType.int_())
    if ta.kind in temporal or tb.kind in temporal:
        raise TypeError(
            f"unsupported arithmetic {ta.kind.name} {e.op} {tb.kind.name}"
        )

    if e.op == "/":
        ct = div_result(ta, tb)
        fa, fb = _to_float(a, ct.kind), _to_float(b, ct.kind)
        zero = fb.data == 0
        data = jnp.where(zero, jnp.nan, fa.data / jnp.where(zero, 1.0, fb.data))
        v = valid if valid is not None else _all_valid(n)
        return Column(data=data, valid=v & ~zero, dtype=ct)

    if e.op == "*":
        ct = mul_result(ta, tb)
        if ct.kind == TypeKind.DECIMAL and ct.scale > 10:
            # combined fixed-point scale would overflow int64 on large
            # aggregates: fall back to double (MySQL keeps DECIMAL(65,30)
            # via wide ints; exact wide-decimal kernels are a later round)
            fa, fb = _to_float(a, TypeKind.DOUBLE), _to_float(b, TypeKind.DOUBLE)
            return Column(data=fa.data * fb.data, valid=valid,
                          dtype=SqlType.double())
        if ct.kind == TypeKind.DECIMAL:
            data = a.data.astype(jnp.int64) * b.data.astype(jnp.int64)
            return Column(data=data, valid=valid, dtype=ct)
        da, db, c2 = _align_pair(a, b)
        return Column(data=da * db, valid=valid, dtype=c2)

    da, db, ct = _align_pair(a, b)
    if e.op == "+":
        data = da + db
    elif e.op == "-":
        data = da - db
    elif e.op == "%":
        # MySQL MOD: truncated division — result carries the dividend's sign
        zero = db == 0
        safe = jnp.where(zero, 1, db)
        data = jnp.sign(da) * jnp.remainder(jnp.abs(da), jnp.abs(safe))
        data = jnp.where(zero, 0, data)
        v = valid if valid is not None else _all_valid(n)
        return Column(data=data, valid=v & ~zero, dtype=ct)
    else:  # pragma: no cover
        raise ValueError(e.op)
    return Column(data=data, valid=valid, dtype=add_result(ta, tb))


def _unify_branches(branches: list) -> tuple[list, SqlType, "StringDict | None"]:
    """Unify CASE/COALESCE branch columns to one physical representation.

    Numerics go through common_numeric; strings are re-encoded into a
    merged (union) order-preserving dictionary; date/bool/etc require
    matching kinds.  NULLTYPE branches adopt the result type.
    """
    kinds = {b.dtype.kind for b in branches if b.dtype.kind != TypeKind.NULLTYPE}
    if not kinds:
        return branches, SqlType.null(), None
    if kinds <= {TypeKind.INT, TypeKind.DECIMAL, TypeKind.FLOAT, TypeKind.DOUBLE,
                 TypeKind.BOOL}:
        if kinds == {TypeKind.BOOL}:
            rt = SqlType.bool_()
        else:
            rt = SqlType.int_()  # BOOL branches widen to INT when mixed
            for b in branches:
                if b.dtype.kind not in (TypeKind.NULLTYPE, TypeKind.BOOL):
                    rt = common_numeric(rt, b.dtype)
        return [cast_column(b, rt) for b in branches], rt, None
    if kinds == {TypeKind.STRING}:
        dicts = [b.sdict for b in branches if b.sdict is not None]
        if all(d is dicts[0] for d in dicts):
            merged = dicts[0]
            out = branches
        else:
            allvals = np.unique(np.concatenate([d.values for d in dicts]))
            merged = StringDict(allvals)
            out = []
            for b in branches:
                if b.sdict is None:
                    out.append(b)
                    continue
                remap = np.searchsorted(allvals, b.sdict.values).astype(np.int32)
                codes = jnp.asarray(remap)[jnp.clip(b.data, 0, b.sdict.size - 1)]
                out.append(Column(codes, b.valid, SqlType.string(), merged))
        return out, SqlType.string(), merged
    if len(kinds) == 1:
        rt = next(b.dtype for b in branches if b.dtype.kind != TypeKind.NULLTYPE)
        return branches, rt, None
    raise TypeError(f"CASE branches mix incompatible types: {kinds}")


def _eval_case(e: ir.Case, rel: Relation, n: int) -> Column:
    conds = []
    vals = []
    for c, v in e.whens:
        conds.append(eval_expr(c, rel))
        vals.append(eval_expr(v, rel))
    else_c = eval_expr(e.else_, rel) if e.else_ is not None else None

    branches = vals + ([else_c] if else_c is not None else [])
    branches, rt, sdict = _unify_branches(branches)

    if else_c is not None:
        data = branches[-1].data
        valid = branches[-1].valid_or_true()
    else:
        data = jnp.zeros(n, dtype=branches[0].data.dtype)
        valid = jnp.zeros(n, dtype=jnp.bool_)
    taken = jnp.zeros(n, dtype=jnp.bool_)
    for cond, val in zip(conds, branches[: len(vals)]):
        t, _ = _tf(cond)
        sel = t & ~taken
        data = jnp.where(sel, val.data, data)
        valid = jnp.where(sel, val.valid_or_true(), valid)
        taken = taken | t
    return Column(data=data, valid=valid, dtype=rt, sdict=sdict)


def cast_column(c: Column, t: SqlType) -> Column:
    if c.dtype.kind == t.kind and c.dtype.scale == t.scale:
        return c
    if t.kind in (TypeKind.FLOAT, TypeKind.DOUBLE):
        return _to_float(c, t.kind)
    if t.kind == TypeKind.DECIMAL:
        if c.dtype.kind == TypeKind.DECIMAL:
            if t.scale >= c.dtype.scale:
                data = c.data * _POW10[t.scale - c.dtype.scale]
            else:
                data = _div_round(c.data, _POW10[c.dtype.scale - t.scale])
            return Column(data=data, valid=c.valid, dtype=t)
        if c.dtype.kind == TypeKind.INT or c.dtype.kind == TypeKind.BOOL:
            data = c.data.astype(jnp.int64) * _POW10[t.scale]
            return Column(data=data, valid=c.valid, dtype=t)
        if c.dtype.kind in (TypeKind.FLOAT, TypeKind.DOUBLE):
            data = jnp.round(c.data * _POW10[t.scale]).astype(jnp.int64)
            return Column(data=data, valid=c.valid, dtype=t)
    if t.kind == TypeKind.INT:
        if c.dtype.kind == TypeKind.DECIMAL:
            data = _div_round(c.data, _POW10[c.dtype.scale])
        else:
            data = c.data.astype(jnp.int64)
        return Column(data=data, valid=c.valid, dtype=t)
    if t.kind == TypeKind.NULLTYPE or c.dtype.kind == TypeKind.NULLTYPE:
        return Column(data=c.data, valid=c.valid, dtype=t if t.kind != TypeKind.NULLTYPE else c.dtype)
    if t.kind == TypeKind.BOOL:
        return Column(data=c.data != 0, valid=c.valid, dtype=t)
    raise NotImplementedError(f"cast {c.dtype} -> {t}")


def _div_round(x, d: int):
    """Round-half-away-from-zero integer division (MySQL decimal rounding)."""
    half = d // 2
    return jnp.where(x >= 0, (x + half) // d, -((-x + half) // d))


def days_from_civil(y, m, d):
    """Inverse of civil_from_days (Hinnant, floor-division form)."""
    y = y - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = m + jnp.where(m > 2, -3, 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _days_in_month(y, m):
    leap = ((y % 4 == 0) & (y % 100 != 0)) | (y % 400 == 0)
    lengths = jnp.asarray([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31])
    base = lengths[jnp.clip(m - 1, 0, 11)]
    return jnp.where((m == 2) & leap, 29, base)


# ---------------------------------------------------------------------------
# UDF registry (≙ PL/SQL + LLVM JIT, src/pl + src/objit): user functions
# written against jax.numpy trace straight into the plan's XLA program —
# tracing IS the JIT.
# ---------------------------------------------------------------------------

_UDFS: dict[str, tuple] = {}


def register_udf(name: str, fn, result_type: "SqlType | None" = None):
    """Register fn(*jnp_arrays) -> jnp_array as a SQL scalar function.

    The function must be traceable (jax.numpy ops, no data-dependent
    python control flow); NULL handling: result is NULL where any input
    is NULL (strict functions)."""
    _UDFS[name.lower()] = (fn, result_type)


def unregister_udf(name: str):
    _UDFS.pop(name.lower(), None)


def parse_vector_text(s: str) -> np.ndarray:
    """'[0.1, 0.2, ...]' -> float32 ndarray (the vector literal format)."""
    body = s.strip()
    if body.startswith("[") and body.endswith("]"):
        body = body[1:-1]
    if not body.strip():
        return np.zeros(0, dtype=np.float32)
    return np.asarray([float(x) for x in body.split(",")],
                      dtype=np.float32)


def _eval_func(e: ir.FuncCall, rel: Relation, n: int) -> Column:
    name = e.name.lower()
    if name in _UDFS:
        fn, rt = _UDFS[name]
        cols = [eval_expr(a, rel) for a in e.args]
        data = fn(*[c.data for c in cols])
        valid = None
        for c in cols:
            valid = c.valid if valid is None else (
                valid if c.valid is None else (valid & c.valid))
        if rt is None:
            if jnp.issubdtype(data.dtype, jnp.floating):
                rt = SqlType.double()
            elif data.dtype == jnp.bool_:
                rt = SqlType.bool_()
            else:
                rt = SqlType.int_()
        return Column(jnp.asarray(data), valid, rt)
    if name == "match_against":
        # MATCH(col) AGAINST('terms'): token containment evaluated in
        # the DICTIONARY domain — one host pass over distinct values
        # builds the score LUT, then a device gather maps codes to
        # scores.  ≙ the FTS inverted index consulted per term
        # (src/storage/fts): the dictionary IS the term-space here.
        import re as _re

        c = eval_expr(e.args[0], rel)
        terms = e.args[1].value if isinstance(e.args[1], ir.Literal) \
            else ""
        qtoks = [t for t in _re.split(r"\W+", str(terms).lower()) if t]
        if c.sdict is None or not qtoks:
            return Column(jnp.zeros(n, jnp.float64), c.valid,
                          SqlType.double())

        def score(text):
            toks = set(_re.split(r"\W+", str(text).lower()))
            return float(sum(1.0 for t in qtoks if t in toks))

        lut = jnp.asarray(c.sdict.lut(score).astype(np.float64))
        data = jnp.take(lut, jnp.clip(c.data, 0, c.sdict.size - 1))
        if c.valid is not None:
            data = jnp.where(c.valid, data, 0.0)
        return Column(data, c.valid, SqlType.double())
    if name in ("l2_distance", "inner_product", "negative_inner_product",
                "cosine_distance"):
        # vector distance over a VECTOR column and a '[...]' literal /
        # second vector column (≙ the vector distance exprs feeding
        # src/share/vector_index); [n,d] x [d] -> [n] double
        def _vec_arg(x):
            if isinstance(x, ir.Literal) and isinstance(x.value, str):
                v = parse_vector_text(x.value)
                return Column(jnp.asarray(v), None,
                              SqlType.vector(len(v)))
            return eval_expr(x, rel)

        a = _vec_arg(e.args[0])
        b = _vec_arg(e.args[1])
        va, vb = a.data, b.data
        if va.ndim == 1 and vb.ndim == 2:
            a, b = b, a
            va, vb = vb, va
        va = va.astype(jnp.float32)
        vb = vb.astype(jnp.float32)
        if name == "l2_distance":
            diff = va - (vb if vb.ndim == 2 else vb[None, :])
            out = jnp.sqrt(jnp.sum(diff * diff, axis=-1)
                           .astype(jnp.float64))
        elif name == "cosine_distance":
            num = jnp.sum(va * (vb if vb.ndim == 2 else vb[None, :]),
                          axis=-1)
            na = jnp.sqrt(jnp.sum(va * va, axis=-1))
            nb = jnp.sqrt(jnp.sum(vb * vb, axis=-1))
            out = (1.0 - num / jnp.maximum(na * nb, 1e-12)) \
                .astype(jnp.float64)
        else:
            out = jnp.sum(va * (vb if vb.ndim == 2 else vb[None, :]),
                          axis=-1).astype(jnp.float64)
            if name == "negative_inner_product":
                out = -out
        return Column(out, _merge_valid(a, b), SqlType.double())
    if name in ("extract_year", "year", "extract_month", "month",
                "extract_day", "day", "quarter", "dayofyear", "dayofweek",
                "weekday"):
        c = eval_expr(e.args[0], rel)
        y, m, d = civil_from_days(c.data)
        if name in ("extract_year", "year"):
            out = y
        elif name in ("extract_month", "month"):
            out = m
        elif name in ("extract_day", "day"):
            out = d
        elif name == "quarter":
            out = (m + 2) // 3
        elif name == "dayofyear":
            out = c.data.astype(jnp.int64) - days_from_civil(
                y, jnp.ones_like(m), jnp.ones_like(d)) + 1
        elif name == "dayofweek":   # MySQL: 1 = Sunday
            out = jnp.remainder(c.data.astype(jnp.int64) + 4, 7) + 1
        else:                       # weekday: 0 = Monday
            out = jnp.remainder(c.data.astype(jnp.int64) + 3, 7)
        return Column(data=out, valid=c.valid, dtype=SqlType.int_())
    if name == "add_months":
        c = eval_expr(e.args[0], rel)
        k = eval_expr(e.args[1], rel)
        y, m, d = civil_from_days(c.data)
        total = y * 12 + (m - 1) + k.data.astype(jnp.int64)
        y2 = jnp.floor_divide(total, 12)
        m2 = total - y2 * 12 + 1
        d2 = jnp.minimum(d, _days_in_month(y2, m2))
        out = days_from_civil(y2, m2, d2).astype(jnp.int32)
        return Column(data=out, valid=_merge_valid(c, k), dtype=c.dtype)
    if name == "datediff":
        a = eval_expr(e.args[0], rel)
        b = eval_expr(e.args[1], rel)
        data = a.data.astype(jnp.int64) - b.data.astype(jnp.int64)
        return Column(data=data, valid=_merge_valid(a, b),
                      dtype=SqlType.int_())
    if name == "abs":
        c = eval_expr(e.args[0], rel)
        return c.with_data(jnp.abs(c.data))
    if name == "sign":
        c = eval_expr(e.args[0], rel)
        return Column(jnp.sign(c.data).astype(jnp.int64), c.valid,
                      SqlType.int_())
    if name in ("ceil", "ceiling", "floor"):
        c = eval_expr(e.args[0], rel)
        if c.dtype.kind == TypeKind.DECIMAL:
            s = _POW10[c.dtype.scale]
            if name == "floor":
                data = jnp.floor_divide(c.data, s)
            else:
                data = -jnp.floor_divide(-c.data, s)
            return Column(data, c.valid, SqlType.int_())
        if c.dtype.kind == TypeKind.INT:
            return c
        f = jnp.floor if name == "floor" else jnp.ceil
        return Column(f(c.data).astype(jnp.int64), c.valid, SqlType.int_())
    if name in ("round", "truncate"):
        c = eval_expr(e.args[0], rel)
        nd = 0
        if len(e.args) > 1:
            nd = e.args[1].value if isinstance(e.args[1], ir.Literal) else 0
        if c.dtype.kind == TypeKind.DECIMAL:
            target = SqlType(TypeKind.DECIMAL, c.dtype.precision,
                             max(nd, 0))
            if name == "round":
                return cast_column(c, target)
            if nd >= c.dtype.scale:
                return c
            d = _POW10[c.dtype.scale - max(nd, 0)]
            data = jnp.where(c.data >= 0, c.data // d, -((-c.data) // d))
            return Column(data, c.valid, target)
        if c.dtype.kind == TypeKind.INT:
            return c
        scale = 10.0 ** nd
        if name == "round":
            data = jnp.round(c.data * scale) / scale
        else:
            data = jnp.trunc(c.data * scale) / scale
        return Column(data, c.valid, c.dtype)
    if name in ("sqrt", "exp", "ln", "log2", "log10", "sin", "cos", "tan"):
        c = _to_float(eval_expr(e.args[0], rel), TypeKind.DOUBLE)
        fns = {"sqrt": jnp.sqrt, "exp": jnp.exp, "ln": jnp.log,
               "log2": jnp.log2, "log10": jnp.log10, "sin": jnp.sin,
               "cos": jnp.cos, "tan": jnp.tan}
        data = fns[name](c.data)
        bad = jnp.isnan(data) | jnp.isinf(data)
        v = c.valid_or_true() & ~bad
        return Column(data, v, SqlType.double())
    if name in ("power", "pow"):
        a = _to_float(eval_expr(e.args[0], rel), TypeKind.DOUBLE)
        b = _to_float(eval_expr(e.args[1], rel), TypeKind.DOUBLE)
        data = jnp.power(a.data, b.data)
        return Column(data, _merge_valid(a, b), SqlType.double())
    if name == "mod":
        return _eval_arith(ir.Arith("%", e.args[0], e.args[1]), rel, n)
    if name in ("greatest", "least"):
        cols = [eval_expr(a, rel) for a in e.args]
        cols, rt, sdict = _unify_branches(cols)
        opf = jnp.maximum if name == "greatest" else jnp.minimum
        data = cols[0].data
        valid = cols[0].valid
        for c in cols[1:]:
            data = opf(data, c.data)
            valid = _merge_valid(Column(data, valid, rt),
                                 c)
        return Column(data, valid, rt, sdict=sdict)
    if name == "ifnull":
        return _eval_func(ir.FuncCall("coalesce", e.args), rel, n)
    if name == "nullif":
        a = eval_expr(e.args[0], rel)
        eq = _eval_cmp(ir.Cmp("=", e.args[0], e.args[1]), rel, n)
        t, _f = _tf(eq)
        v = a.valid_or_true() & ~t
        return Column(a.data, v, a.dtype, a.sdict)
    if name in ("length", "char_length", "character_length"):
        c = eval_expr(e.args[0], rel)
        assert c.sdict is not None, f"{name} requires a string column"
        lut = jnp.asarray(c.sdict.lut(len).astype("int64"))
        data = lut[jnp.clip(c.data, 0, c.sdict.size - 1)]
        return Column(data, c.valid, SqlType.int_())
    if name in ("trim", "ltrim", "rtrim", "reverse"):
        fns = {"trim": str.strip, "ltrim": str.lstrip,
               "rtrim": str.rstrip, "reverse": lambda s: s[::-1]}
        return _dict_transform(e.args[0], rel, fns[name])
    if name == "replace":
        old = e.args[1].value
        new = e.args[2].value
        return _dict_transform(e.args[0], rel,
                               lambda s: s.replace(old, new))
    if name in ("left", "right"):
        k = e.args[1].value
        if name == "left":
            return _dict_transform(e.args[0], rel, lambda s: s[:k])
        return _dict_transform(e.args[0], rel,
                               lambda s: s[-k:] if k else "")
    if name == "concat":
        return _eval_concat(e, rel, n)
    if name == "coalesce":
        cols = [eval_expr(a, rel) for a in e.args]
        cols, rt, sdict = _unify_branches(cols)
        data = cols[-1].data
        valid = cols[-1].valid_or_true()
        for c in reversed(cols[:-1]):
            v = c.valid_or_true()
            data = jnp.where(v, c.data, data)
            valid = v | valid
        if any(c.valid is None for c in cols):
            valid = None    # a NOT NULL branch: the result is NOT NULL
        return Column(data=data, valid=valid, dtype=rt, sdict=sdict)
    if name in ("substring", "substr", "upper", "lower"):
        return _dict_string_func(name, e, rel)
    if name in ("lcase", "ucase"):
        return _dict_transform(e.args[0], rel,
                               str.lower if name == "lcase" else str.upper)
    if name == "if":
        from oceanbase_tpu.expr.compile import eval_predicate as _ep

        t = _ep(e.args[0], rel)
        a = eval_expr(e.args[1], rel)
        b = eval_expr(e.args[2], rel)
        (a, b), rt, sdict = _unify_branches([a, b])
        data = jnp.where(t, a.data, b.data)
        valid = jnp.where(t, a.valid_or_true(), b.valid_or_true())
        return Column(data, valid, rt, sdict)
    if name == "isnull":
        c = eval_expr(e.args[0], rel)
        v = c.valid
        data = jnp.zeros(n, jnp.bool_) if v is None else ~v
        return Column(data, None, SqlType.bool_())
    if name in ("atan", "asin", "acos", "sinh", "cosh", "tanh", "cot",
                "degrees", "radians"):
        c = eval_expr(e.args[0], rel)
        x = c.data.astype(jnp.float64)
        out = {"atan": jnp.arctan, "asin": jnp.arcsin,
               "acos": jnp.arccos, "sinh": jnp.sinh, "cosh": jnp.cosh,
               "tanh": jnp.tanh,
               "cot": lambda v: 1.0 / jnp.tan(v),
               "degrees": jnp.degrees, "radians": jnp.radians}[name](x)
        return Column(out, c.valid, SqlType.double())
    if name == "atan2":
        a = eval_expr(e.args[0], rel)
        b = eval_expr(e.args[1], rel)
        out = jnp.arctan2(a.data.astype(jnp.float64),
                          b.data.astype(jnp.float64))
        return Column(out, _merge_valid(a, b), SqlType.double())
    if name == "pi":
        return Column(jnp.full(n, np.pi, jnp.float64), None,
                      SqlType.double())
    if name == "log":
        # log(x) = ln; log(base, x) = ln(x)/ln(base) (MySQL)
        if len(e.args) == 1:
            c = eval_expr(e.args[0], rel)
            return Column(jnp.log(c.data.astype(jnp.float64)), c.valid,
                          SqlType.double())
        b = eval_expr(e.args[0], rel)
        c = eval_expr(e.args[1], rel)
        out = jnp.log(c.data.astype(jnp.float64)) / \
            jnp.log(b.data.astype(jnp.float64))
        return Column(out, _merge_valid(b, c), SqlType.double())
    if name == "repeat" and len(e.args) == 2 and \
            isinstance(e.args[1], ir.Literal):
        k = int(e.args[1].value)
        return _dict_transform(e.args[0], rel, lambda s: s * max(k, 0))
    if name in ("lpad", "rpad"):
        k = int(e.args[1].value)
        pad = str(e.args[2].value) if len(e.args) > 2 else " "

        def _pad(s, k=k, pad=pad, left=(name == "lpad")):
            if len(s) >= k:
                return s[:k]
            fill = (pad * k)[: k - len(s)]
            return fill + s if left else s + fill

        return _dict_transform(e.args[0], rel, _pad)
    if name in ("instr", "locate", "position"):
        # instr(str, sub) / locate(sub, str): 1-based, 0 = not found
        if len(e.args) > 2:
            raise NotImplementedError(
                f"{name} with a start position is not supported")
        if name == "instr":
            col_a, sub_a = e.args[0], e.args[1]
        else:
            col_a, sub_a = e.args[1], e.args[0]
        sub = str(sub_a.value) if isinstance(sub_a, ir.Literal) else None
        if sub is None:
            raise NotImplementedError(f"{name} needs a literal needle")
        c = eval_expr(col_a, rel)
        assert c.sdict is not None, f"{name} requires a string column"
        lut = jnp.asarray(
            c.sdict.lut(lambda s: s.find(sub) + 1).astype("int64"))
        data = jnp.take(lut, jnp.clip(c.data, 0, c.sdict.size - 1))
        return Column(data, c.valid, SqlType.int_())
    if name == "ascii":
        c = eval_expr(e.args[0], rel)
        assert c.sdict is not None, "ascii requires a string column"
        lut = jnp.asarray(
            c.sdict.lut(lambda s: ord(s[0]) if s else 0).astype("int64"))
        data = jnp.take(lut, jnp.clip(c.data, 0, c.sdict.size - 1))
        return Column(data, c.valid, SqlType.int_())
    if name == "substring_index" and isinstance(e.args[1], ir.Literal) \
            and isinstance(e.args[2], ir.Literal):
        delim = str(e.args[1].value)
        cnt = int(e.args[2].value)

        def _si(s, d=delim, k=cnt):
            parts = s.split(d)
            return d.join(parts[:k]) if k >= 0 else d.join(parts[k:])

        return _dict_transform(e.args[0], rel, _si)
    if name == "concat_ws":
        sep = str(e.args[0].value) if isinstance(e.args[0], ir.Literal) \
            else None
        if sep is None:
            raise NotImplementedError("concat_ws needs a literal sep")
        if len(e.args) < 2:
            raise NotImplementedError("concat_ws needs value arguments")
        # MySQL semantics: NULL values are SKIPPED (with their
        # separator), unlike CONCAT's null propagation — fold with CASE
        out = e.args[1]
        for a in e.args[2:]:
            out = ir.Case(whens=[
                (ir.FuncCall("isnull", [out]), a),
                (ir.FuncCall("isnull", [a]), out),
            ], else_=ir.FuncCall("concat", [out, ir.Literal(sep), a]))
        out = ir.FuncCall("coalesce", [out, ir.Literal("")])
        return eval_expr(out, rel)
    if name in ("md5", "sha1", "hex"):
        import hashlib as _hl

        fns = {"md5": lambda s: _hl.md5(s.encode()).hexdigest(),
               "sha1": lambda s: _hl.sha1(s.encode()).hexdigest(),
               "hex": lambda s: s.encode().hex().upper()}
        return _dict_transform(e.args[0], rel, fns[name])
    if name in ("dayname", "monthname"):
        c = eval_expr(e.args[0], rel)
        if name == "dayname":
            names = np.array(["Monday", "Tuesday", "Wednesday",
                              "Thursday", "Friday", "Saturday",
                              "Sunday"], dtype=object)
            codes = jnp.remainder(c.data.astype(jnp.int64) + 3, 7)
        else:
            names = np.array(["January", "February", "March", "April",
                              "May", "June", "July", "August",
                              "September", "October", "November",
                              "December"], dtype=object)
            _y, m, _d = civil_from_days(c.data)
            codes = (m - 1).astype(jnp.int64)
        # StringDict values must be sorted (searchsorted code lookups)
        order = np.argsort(names.astype(str))
        remap = jnp.asarray(np.argsort(order).astype(np.int32))
        return Column(jnp.take(remap, codes).astype(jnp.int32), c.valid,
                      SqlType.string(), StringDict(names[order]))
    if name == "last_day":
        c = eval_expr(e.args[0], rel)
        y, m, d = civil_from_days(c.data)
        out = days_from_civil(y, m, _days_in_month(y, m)) \
            .astype(jnp.int32)
        return Column(out, c.valid, c.dtype)
    raise NotImplementedError(f"function {name}")


def _dict_transform(arg: ir.Expr, rel: Relation, fn) -> Column:
    """Apply a host string function through the dictionary (LUT + remap)."""
    c = eval_expr(arg, rel)
    assert c.sdict is not None, "string function requires dict column"
    mapped = c.sdict.lut(fn)
    new_values, inv = np.unique(mapped.astype(object), return_inverse=True)
    remap = jnp.asarray(inv.astype(np.int32))
    codes = remap[jnp.clip(c.data, 0, c.sdict.size - 1)]
    return Column(codes, c.valid, SqlType.string(), StringDict(new_values))


_CONCAT_DICT_LIMIT = 1 << 20


def _eval_concat(e: ir.FuncCall, rel: Relation, n: int) -> Column:
    """CONCAT over dict columns/literals.  Column x column concatenation
    materializes the code-pair product dictionary, guarded by a size cap
    (beyond it the planner should pre-aggregate — r2)."""
    cols = [eval_expr(a, rel) for a in e.args]
    out = cols[0]
    for c in cols[1:]:
        if out.sdict is None or c.sdict is None:
            raise NotImplementedError("concat requires string operands")
        if out.sdict.size * c.sdict.size > _CONCAT_DICT_LIMIT:
            raise NotImplementedError(
                "concat dictionary product too large (round-1 limit)")
        pairs = np.char.add(
            np.repeat(out.sdict.values.astype(str), c.sdict.size),
            np.tile(c.sdict.values.astype(str), out.sdict.size),
        ).astype(object)
        new_values, inv = np.unique(pairs, return_inverse=True)
        remap = jnp.asarray(inv.astype(np.int32)).reshape(
            out.sdict.size, c.sdict.size)
        codes = remap[jnp.clip(out.data, 0, out.sdict.size - 1),
                      jnp.clip(c.data, 0, c.sdict.size - 1)]
        out = Column(codes, _merge_valid(out, c), SqlType.string(),
                     StringDict(new_values))
    return out


def _dict_string_func(name: str, e: ir.FuncCall, rel: Relation) -> Column:
    """String functions as dictionary transforms (host) + device remap."""
    c = eval_expr(e.args[0], rel)
    assert c.sdict is not None, f"{name} requires dict-encoded column"
    if name in ("substring", "substr"):
        start = e.args[1].value if isinstance(e.args[1], ir.Literal) else e.args[1]
        length = None
        if len(e.args) > 2:
            length = e.args[2].value if isinstance(e.args[2], ir.Literal) else e.args[2]
        s0 = start - 1

        def f(s):
            return s[s0: s0 + length] if length is not None else s[s0:]
    elif name == "upper":
        def f(s):
            return s.upper()
    else:
        def f(s):
            return s.lower()
    mapped = c.sdict.lut(f)
    new_values, inv = np.unique(mapped, return_inverse=True)
    remap = jnp.asarray(inv.astype(np.int32))
    codes = remap[jnp.clip(c.data, 0, c.sdict.size - 1)]
    return Column(data=codes, valid=c.valid, dtype=SqlType.string(),
                  sdict=StringDict(new_values))


def eval_predicate(e: ir.Expr, rel: Relation):
    """Evaluate a WHERE predicate to a live-row bool mask (NULL -> False),
    combined with the relation's existing mask — the TPU analog of
    ObOperator filter_rows + skip accounting
    (src/sql/engine/ob_operator.cpp:1466-1560)."""
    c = eval_expr(e, rel)
    t, _ = _tf(c)
    return t & rel.mask_or_true()
