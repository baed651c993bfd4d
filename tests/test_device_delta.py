"""The device column copy under commits (storage/device_delta.py,
storage/engine.py::table_data, Tablet.delta_since): a cached relation is
brought up to the newest commit by the committed delta, equals a rebuilt one
and a plain row model, keeps capacity and input signature, and falls back to
a rebuild in the counted cases only.  Also DELETE / UPDATE by key compiling
once, ``WITH COLUMN GROUP``, and the spans and counters of the mechanism."""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from oceanbase_tpu.server import Database
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.sql.parser import ParseError
from oceanbase_tpu.storage import device_delta
from oceanbase_tpu.storage.keyindex import KeyIndex
from oceanbase_tpu.vector import to_numpy

DDL = ("create table t (k bigint not null, j bigint not null, "
       "v decimal(15,2), s varchar(20), d date, primary key (k, j))")
COLS = ("k", "j", "v", "s", "d")


# -- the plain reference: a dict of rows, independent of storage/ ------------

class RowModel:
    """key -> row, as the acknowledged statements leave it."""

    def __init__(self):
        self.rows: dict = {}

    def insert(self, row: dict):
        self.rows[row["k"], row["j"]] = dict(row)

    def update(self, key, **changes):
        if key in self.rows:
            self.rows[key].update(changes)

    def delete_k(self, ks):
        self.rows = {key: r for key, r in self.rows.items()
                     if key[0] not in ks}

    def columns(self) -> dict:
        keys = sorted(self.rows)
        return {c: [self.rows[k][c] for k in keys] for c in COLS}


def _lit(x):
    if x is None:
        return "null"
    if isinstance(x, str):
        return "'" + x + "'"
    return str(x)


def _row_sql(r: dict) -> str:
    v = "null" if r["v"] is None else f"{r['v'] // 100}.{r['v'] % 100:02d}"
    d = "null" if r["d"] is None else f"date '{np.datetime64(r['d'], 'D')}'"
    return f"({r['k']}, {r['j']}, {v}, {_lit(r['s'])}, {d})"


def _columns_of(rel) -> dict:
    """A relation's live rows sorted by key: column -> list (None a NULL;
    decimals as scaled ints, dates as days)."""
    raw = to_numpy(rel)
    order = np.lexsort((raw["j"], raw["k"]))
    out = {}
    for c in COLS:
        vals = raw[c][order]
        valid = raw.get("__valid__" + c)
        valid = None if valid is None else valid[order]
        out[c] = [None if valid is not None and not valid[i]
                  else (x.item() if hasattr(x, "item") else x)
                  for i, x in enumerate(vals)]
    return out


def _rebuilt(s, table="t"):
    """The relation a rebuild from the store gives now (beside the cache)."""
    cat = s.catalog
    ts = cat.engine.tables[table]
    return cat._device_copy(ts, cat.snapshot_fn(), 0)[0]


def _count(name, **labels):
    return qmetrics.counter_value(name, **labels)


@pytest.fixture()
def db(tmp_path):
    d = Database(str(tmp_path / "db"))
    yield d
    d.close()


def _seeded(s, model, n=40):
    rows = [{"k": i, "j": i % 3, "v": i * 100 + 50, "s": f"s{i % 5}",
             "d": 9000 + i} for i in range(n)]
    s.execute(DDL)
    s.execute("insert into t values " + ", ".join(map(_row_sql, rows)))
    for r in rows:
        model.insert(r)
    s.execute("select count(*) from t")     # the baseline copy


# -- a maintained copy equals a rebuilt one and the row model ------------------

@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_maintained_copy_equals_rebuild_and_row_model(db, seed):
    rng = np.random.default_rng(seed)
    s = db.session()
    model = RowModel()
    _seeded(s, model)
    applies0 = _count("storage.delta_applies")
    next_k = 1000
    for _round in range(6):
        explicit = bool(rng.integers(0, 2))
        abort = explicit and bool(rng.integers(0, 4) == 0)
        shadow = RowModel()
        shadow.rows = {k: dict(r) for k, r in model.rows.items()}
        if explicit:
            s.execute("begin")
        for _op in range(int(rng.integers(1, 5))):
            kind = rng.choice(["insert", "update", "delete", "reinsert"])
            live = sorted(shadow.rows)
            if kind == "insert" or not live:
                rows = []
                for _ in range(int(rng.integers(1, 6))):
                    rows.append({
                        "k": next_k, "j": int(rng.integers(0, 3)),
                        "v": None if rng.integers(0, 5) == 0
                        else int(rng.integers(0, 10 ** 6)),
                        "s": None if rng.integers(0, 5) == 0
                        else f"new{int(rng.integers(0, 50))}",
                        "d": None if rng.integers(0, 6) == 0
                        else int(rng.integers(8000, 12000))})
                    next_k += 1
                s.execute("insert into t values "
                          + ", ".join(map(_row_sql, rows)))
                for r in rows:
                    shadow.insert(r)
            elif kind == "update":
                key = live[int(rng.integers(0, len(live)))]
                v = int(rng.integers(0, 10 ** 6))
                text = f"upd{int(rng.integers(0, 30))}"
                s.execute(f"update t set v = {v // 100}.{v % 100:02d}, "
                          f"s = '{text}' where k = {key[0]} and "
                          f"j = {key[1]}")
                shadow.update(key, v=v, s=text)
            elif kind == "delete":
                ks = {live[int(i)][0] for i in
                      rng.integers(0, len(live), int(rng.integers(1, 4)))}
                s.execute("delete from t where k in ("
                          + ", ".join(map(str, sorted(ks))) + ")")
                shadow.delete_k(ks)
            else:   # a key deleted and inserted again
                key = live[int(rng.integers(0, len(live)))]
                row = dict(shadow.rows[key], s="again",
                           v=int(rng.integers(0, 10 ** 6)))
                s.execute(f"delete from t where k in ({key[0]})")
                shadow.delete_k({key[0]})
                s.execute("insert into t values " + _row_sql(row))
                shadow.insert(row)
        if explicit:
            s.execute("rollback" if abort else "commit")
        if not abort:
            model = shadow
        got = _columns_of(s.catalog.table_data("t"))
        assert got == model.columns()
        assert got == _columns_of(_rebuilt(s))
    assert _count("storage.delta_applies") > applies0
    s.close()


def _rows(first_k, n, **over):
    return [dict({"k": first_k + i, "j": i % 3, "v": i * 7 + 1,
                  "s": f"e{i % 4}", "d": 9500 + i}, **over)
            for i in range(n)]


#: the window's edges: (DELTA_LANES or None for the module's own, rows the
#: baseline holds (its capacity is 64), steps); a step inserts rows or
#: deletes by ``k``, and is one commit that the next read applies
WINDOW_EDGES = {
    # first = capacity - W: the window needs no clamp and ends the table
    "last_row_on_the_last_lane": (8, 56, [("insert", _rows(2000, 8))]),
    # first within W of the end: the start is clamped by the program, the
    # lanes below ``first`` (rows 56-58, then 56-61) keep their values
    "start_within_a_window_of_the_end": (
        8, 59, [("insert", _rows(2000, 3)), ("insert", _rows(2100, 2))]),
    # the relation is shorter than a chunk: W is its capacity
    "capacity_under_a_chunk": (
        None, 10, [("insert", _rows(2000, 5)), ("delete", [2001, 3, 4]),
                   ("insert", _rows(2100, 3, s=None))]),
    "more_rows_than_a_chunk": (8, 30, [("insert", _rows(2000, 21))]),
    "clears_alone": (8, 40, [("delete", list(range(0, 19)))]),
    "rows_and_clears_of_different_chunk_counts": (
        8, 40, [("mixed", _rows(2000, 3), list(range(5, 25)))]),
}


@pytest.mark.parametrize("edge", sorted(WINDOW_EDGES))
def test_window_edges_equal_rebuild_and_row_model(db, monkeypatch, edge):
    lanes, seeded, steps = WINDOW_EDGES[edge]
    if lanes is not None:
        monkeypatch.setattr(device_delta, "DELTA_LANES", lanes)
    s = db.session()
    model = RowModel()
    _seeded(s, model, n=seeded)
    assert s.catalog.table_data("t").capacity == 64
    for kind, *what in steps:
        if kind == "mixed":
            s.execute("begin")
        if kind in ("insert", "mixed"):
            s.execute("insert into t values "
                      + ", ".join(map(_row_sql, what[0])))
            for r in what[0]:
                model.insert(r)
        if kind in ("delete", "mixed"):
            s.execute("delete from t where k in ("
                      + ", ".join(map(str, what[-1])) + ")")
            model.delete_k(set(what[-1]))
        if kind == "mixed":
            s.execute("commit")
        before = s.catalog._cache.get("t")
        applies = _count("storage.delta_applies")
        builds = _count("storage.device_copy_builds")
        rel = s.catalog.table_data("t")
        assert _count("storage.delta_applies") == applies + 1
        assert _count("storage.device_copy_builds") == builds
        assert rel.capacity == 64
        got = _columns_of(rel)
        assert got == model.columns()
        assert got == _columns_of(_rebuilt(s))
        # the lanes the delta did not name are the old relation's, value
        # for value: nothing was shifted onto them
        kept = np.asarray(before.rel.mask_or_true()) \
            & np.asarray(rel.mask_or_true())
        for c in ("k", "j", "v", "d"):
            old, new = (np.asarray(r.columns[c].data)
                        for r in (before.rel, rel))
            assert (old[kept] == new[kept]).all(), c
    s.close()


def test_a_columns_first_null_lands_inside_the_window(db):
    s = db.session()
    s.execute(DDL)
    n = 20
    s.catalog.load_numpy(
        "t", {"k": np.arange(n), "j": np.arange(n) % 3,
              "v": np.arange(n) * 100, "d": 9000 + np.arange(n),
              "s": np.array([f"s{i % 5}" for i in range(n)], dtype=object)},
        primary_key=["k", "j"])
    model = RowModel()
    for i in range(n):
        model.insert({"k": i, "j": i % 3, "v": i * 100, "s": f"s{i % 5}",
                      "d": 9000 + i})
    before = s.catalog.table_data("t")
    assert before.columns["v"].valid is None    # loaded with no NULL
    rows = _rows(2000, 3, v=None) + _rows(2100, 2, d=None)
    s.execute("insert into t values " + ", ".join(map(_row_sql, rows)))
    for r in rows:
        model.insert(r)
    applies = _count("storage.delta_applies")
    after = s.catalog.table_data("t")
    assert _count("storage.delta_applies") == applies + 1
    assert after.columns["v"].valid is not None
    assert _columns_of(after) == model.columns()
    assert _columns_of(after) == _columns_of(_rebuilt(s))
    assert _columns_of(before)["k"] == list(range(n))   # its snapshot
    s.close()


def test_nulls_and_new_strings_keep_a_sorted_dictionary(db):
    s = db.session()
    model = RowModel()
    _seeded(s, model, n=10)
    before = s.catalog.table_data("t")
    assert before.columns["v"].valid is not None    # nullable by DDL
    rows = [{"k": 100, "j": 0, "v": None, "s": None, "d": None},
            {"k": 101, "j": 1, "v": 7, "s": "aaa-first", "d": 1},
            {"k": 102, "j": 2, "v": 8, "s": "zzz-last", "d": 2},
            {"k": 103, "j": 0, "v": 9, "s": "s2x-middle", "d": 3}]
    s.execute("insert into t values " + ", ".join(map(_row_sql, rows)))
    for r in rows:
        model.insert(r)
    after = s.catalog.table_data("t")
    sd = after.columns["s"].sdict
    assert list(sd.values) == sorted(sd.values)
    assert {"aaa-first", "zzz-last", "s2x-middle"} <= set(sd.values)
    assert _columns_of(after) == model.columns()
    # codes still order as the strings do: a range predicate on them
    got = s.execute("select k from t where s >= 's2' and s < 's3' "
                    "order by k").rows()
    want = sorted(k for (k, _j), r in model.rows.items()
                  if r["s"] is not None and "s2" <= r["s"] < "s3")
    assert [r[0] for r in got] == want
    # the statement that held the old relation keeps its snapshot
    assert _columns_of(before)["k"] == list(range(10))
    s.close()


def test_aborted_and_own_uncommitted_writes_do_not_reach_the_copy(db):
    s, other = db.session(), db.session()
    model = RowModel()
    _seeded(s, model, n=8)
    shared = s.catalog.table_data("t")
    s.execute("begin")
    s.execute("insert into t values (500, 0, 1.00, 'mine', null)")
    # the writer sees its own row; the shared copy and another session
    # do not
    assert s.execute("select count(*) from t").rows() == [(9,)]
    assert other.execute("select count(*) from t").rows() == [(8,)]
    assert s.catalog.table_data("t") is shared
    s.execute("rollback")
    assert s.catalog.table_data("t") is shared
    assert _columns_of(s.catalog.table_data("t")) == model.columns()
    s.close()
    other.close()


def test_table_data_at_behind_the_newest_commit_is_not_the_shared_copy(db):
    s = db.session()
    model = RowModel()
    _seeded(s, model, n=8)
    old_snapshot = s._txsvc.gts.current()
    s.execute("insert into t values (600, 0, 2.00, 'later', null)")
    newest = s.catalog.table_data("t")
    behind = s.catalog.table_data_at("t", old_snapshot)
    assert behind is not newest
    assert _columns_of(behind) == model.columns()          # without 600
    assert 600 in _columns_of(newest)["k"]
    assert s.catalog.table_data("t") is newest             # still cached
    s.close()


def test_freeze_and_mini_compaction_between_baseline_and_read(db):
    s = db.session()
    model = RowModel()
    _seeded(s, model, n=12)
    builds = _count("storage.device_copy_builds")
    row = {"k": 700, "j": 1, "v": 123, "s": "flushed", "d": 5}
    s.execute("insert into t values " + _row_sql(row))
    model.insert(row)
    s.execute("delete from t where k in (3, 4)")
    model.delete_k({3, 4})
    s.execute("alter system minor freeze")      # memtables -> an L0 segment
    ts = s.catalog.engine.tables["t"]
    assert len(ts.tablet.active) == 0 and ts.tablet.segments
    assert _columns_of(s.catalog.table_data("t")) == model.columns()
    assert _count("storage.device_copy_builds") == builds  # applied
    # a compaction above L0 rewrites the baseline: counted, still right
    s.execute("insert into t values (701, 1, 1.00, 'x', null)")
    model.insert({"k": 701, "j": 1, "v": 100, "s": "x", "d": None})
    s.execute("alter system major freeze")
    before = _count("storage.device_copy_fallbacks",
                    reason="delta_unavailable")
    assert _columns_of(s.catalog.table_data("t")) == model.columns()
    assert _count("storage.device_copy_fallbacks",
                  reason="delta_unavailable") == before + 1
    s.close()


def test_pad_lanes_exhausted_rebuilds_into_the_next_bucket(db):
    s = db.session()
    model = RowModel()
    _seeded(s, model, n=60)                     # bucket 64: four pad lanes
    assert s.catalog.table_data("t").capacity == 64
    rows = [{"k": 800 + i, "j": 0, "v": i, "s": "p", "d": None}
            for i in range(3)]
    s.execute("insert into t values " + ", ".join(map(_row_sql, rows)))
    for r in rows:
        model.insert(r)
    applies = _count("storage.delta_applies")
    assert s.catalog.table_data("t").capacity == 64
    assert _count("storage.delta_applies") >= applies
    before = _count("storage.device_copy_fallbacks", reason="pad_exhausted")
    more = [{"k": 900 + i, "j": 0, "v": i, "s": "q", "d": None}
            for i in range(5)]
    s.execute("insert into t values " + ", ".join(map(_row_sql, more)))
    for r in more:
        model.insert(r)
    rel = s.catalog.table_data("t")
    assert rel.capacity == 128
    assert _count("storage.device_copy_fallbacks",
                  reason="pad_exhausted") == before + 1
    assert _columns_of(rel) == model.columns()
    s.close()


def test_a_partitioned_table_falls_back_and_is_still_right(db):
    s = db.session()
    s.execute("create table p (k bigint not null, v bigint, "
              "primary key (k)) partition by hash (k) partitions 4")
    s.execute("insert into p values " + ", ".join(
        f"({i}, {i * 2})" for i in range(30)))
    assert s.execute("select count(*), sum(v) from p").rows() == \
        [(30, 870)]
    before = _count("storage.device_copy_fallbacks", reason="partitioned")
    applies = _count("storage.delta_applies")
    s.execute("delete from p where k in (1, 2)")
    s.execute("insert into p values (100, 5)")
    assert s.execute("select count(*), sum(v) from p").rows() == \
        [(29, 870 - 6 + 5)]
    assert _count("storage.device_copy_fallbacks",
                  reason="partitioned") > before
    assert _count("storage.delta_applies") == applies
    s.close()


def test_truncate_and_first_read_count_as_no_entry(db):
    s = db.session()
    before = _count("storage.device_copy_fallbacks", reason="no_entry")
    model = RowModel()
    _seeded(s, model, n=5)
    assert _count("storage.device_copy_fallbacks",
                  reason="no_entry") == before + 1
    s.execute("truncate table t")
    assert s.execute("select count(*) from t").rows() == [(0,)]
    s.execute("insert into t values (1, 1, 1.00, 'a', null)")
    assert s.execute("select count(*) from t").rows() == [(1,)]
    s.close()


# -- no plan compiles after a commit --------------------------------------------

def test_an_apply_keeps_capacity_and_signature_and_compiles_no_plan(db):
    from oceanbase_tpu.exec.plan import _input_signature

    s = db.session()
    model = RowModel()
    _seeded(s, model, n=30)
    q = "select sum(v) from t where j < 2 and d >= date '1994-01-01'"

    def numeric(rel):
        return {"t": rel.select(["k", "j", "v", "d"])}

    s.execute(q)
    for i in range(3):
        before = s.catalog.table_data("t")
        s.execute("begin")
        s.execute(f"insert into t values ({2000 + i}, 1, 3.00, "
                  f"'never-seen-{i}', date '1995-05-0{i + 1}')")
        s.execute(f"delete from t where k in ({i})")
        s.execute("commit")
        compiles = _count("plan.compiles")
        applies = _count("storage.delta_applies")
        s.execute(q)
        after = s.catalog.table_data("t")
        assert _count("storage.delta_applies") == applies + 1
        assert _count("plan.compiles") == compiles
        assert after.capacity == before.capacity
        assert _input_signature(numeric(after)) == \
            _input_signature(numeric(before))
        assert jax.tree_util.tree_structure(after.select(["k", "v"])) == \
            jax.tree_util.tree_structure(before.select(["k", "v"]))
    s.close()


def _backend_compiles():
    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _sec, **_kw: events.append(name)
        if name.endswith("backend_compile_duration") else None)
    return events


def test_second_delete_and_update_by_key_compile_nothing(db):
    s = db.session()
    rng = np.random.default_rng(3)
    n_lines = rng.integers(1, 8, 90000)
    ok = np.repeat(np.arange(1, 90001), n_lines)
    n = len(ok)
    s.execute("create table li (ok bigint not null, ln bigint not null, "
              "q bigint not null, c varchar(8) not null, "
              "primary key (ok, ln))")
    s.catalog.load_numpy(
        "li", {"ok": ok,
               "ln": np.arange(n) - np.repeat(np.cumsum(n_lines) - n_lines,
                                              n_lines) + 1,
               "q": rng.integers(1, 50, n),
               "c": np.array([f"c{i % 9}" for i in range(n)], dtype=object)},
        primary_key=["ok", "ln"])
    s.execute("analyze table li")
    events = _backend_compiles()
    builds = _count("storage.device_copy_builds")
    seen = []
    for rnd in range(3):
        keys = ", ".join(str(k) for k in range(rnd * 40 + 1, rnd * 40 + 38))
        e0 = len(events)
        s.execute("begin")
        s.execute(f"delete from li where ok in ({keys})")
        s.execute("commit")
        e1 = len(events)
        s.execute(f"update li set q = q + 1 where ok in "
                  f"({70000 + rnd}, {70003 + rnd})")
        seen.append((e1 - e0, len(events) - e1))
        s.execute("select count(*), sum(q) from li")
    # the candidate rows differ in count every time (1-7 lines an order):
    # one bucket, one set of programs
    assert seen[1] == (0, 0) and seen[2] == (0, 0), seen
    # and no statement rebuilt the table: the deletes and updates read
    # candidates, the reads applied deltas
    assert _count("storage.device_copy_builds") == builds
    s.close()


# -- WITH COLUMN GROUP ----------------------------------------------------------

def test_with_column_group_parses_prints_and_survives_a_reopen(tmp_path):
    root = str(tmp_path / "db")
    db = Database(root)
    s = db.session()
    s.execute("create table h (a bigint not null, b varchar(9), "
              "primary key (a)) with column group (all columns, each column)")
    s.execute("create table e (a bigint primary key) "
              "with column group (each column)")
    text = s.execute("show create table h").rows()[0][1]
    assert text.endswith("WITH COLUMN GROUP (all columns, each column)")
    assert s.catalog.table_def("h").column_groups == \
        ["all columns", "each column"]
    # a bulk load keeps the DDL's definition
    s.catalog.load_numpy("h", {"a": np.arange(5),
                               "b": np.array(list("vwxyz"), dtype=object)},
                         primary_key=["a"])
    assert s.catalog.table_def("h").column_groups == \
        ["all columns", "each column"]
    assert s.execute("select count(*) from h").rows() == [(5,)]
    s.execute("insert into h values (9, 'new')")    # the same path as any
    assert s.execute("select count(*) from h").rows() == [(6,)]
    s.close()
    db.close()
    db = Database(root)
    s = db.session()
    assert s.execute("show create table h").rows()[0][1] == text
    assert s.execute("show create table e").rows()[0][1].endswith(
        "WITH COLUMN GROUP (each column)")
    db.engine.checkpoint()
    s.close()
    db.close()
    db = Database(root)     # from the manifest this time
    assert db.session().execute("show create table h").rows()[0][1] == text
    db.close()


@pytest.mark.parametrize("clause", [
    "with column group (some columns)",
    "with column group (all columns, all columns)",
    "with column group ()",
    "with column (all columns)",
    "with column group (each columns)"])
def test_a_wrong_column_group_specification_raises(db, clause):
    with pytest.raises(ParseError):
        db.session().execute(
            f"create table w (a bigint primary key) {clause}")


# -- spans, counters, the audit column --------------------------------------------

def test_spans_counters_and_the_audit_column(db):
    s = db.session()
    model = RowModel()
    _seeded(s, model, n=20)
    s.execute("insert into t values (300, 0, 1.00, 'n1', null), "
              "(301, 1, 2.00, 'n2', null)")
    s.execute("delete from t where k in (5)")
    c0 = {name: _count(name) for name in
          ("storage.delta_applies", "storage.delta_apply_ns")}
    ins = _count("storage.delta_rows", op="insert")
    dele = _count("storage.delta_rows", op="delete")
    s.execute("select sum(v) from t")
    assert _count("storage.delta_applies") == c0["storage.delta_applies"] + 1
    assert _count("storage.delta_apply_ns") > c0["storage.delta_apply_ns"]
    assert _count("storage.delta_rows", op="insert") == ins + 2
    assert _count("storage.delta_rows", op="delete") == dele + 1
    rows = s.execute("show trace").rows()
    names = [r[0].strip() for r in rows]
    at = names.index("storage.delta_apply")
    assert names[at + 1] == "storage.delta_read"
    assert "tables" in names[:at]
    tags = json.loads(rows[at][4])
    assert tags["table"] == "t" and tags["rows_inserted"] == 2
    assert tags["lanes_cleared"] == 1 and tags["bytes"] > 0
    audit = s.execute("select sql, delta_apply_s, device_copy_s from "
                      "gv$sql_audit").rows()
    mine = [r for r in audit if r[0].startswith("select sum(v) from t")]
    assert mine[-1][1] > 0 and mine[-1][2] == 0
    stat = dict(s.execute("select stat_name, value from gv$sysstat where "
                          "stat_type = 'counter'").rows())
    assert stat["storage.delta_applies"] >= 1
    assert "storage.delta_rows{op=insert}" in stat
    assert "storage.device_copy_fallbacks{reason=no_entry}" in stat
    model = {r[0]: r[1] for r in s.execute(
        "select phase, seconds from gv$time_model").rows()}
    assert model["delta_apply_s"] > 0
    s.close()


# -- the delta source and the key index on their own -----------------------------

def test_delta_since_says_no_when_the_log_no_longer_reaches(db, monkeypatch):
    from oceanbase_tpu.storage import tablet as tablet_mod

    monkeypatch.setattr(tablet_mod, "COMMIT_LOG_KEYS", 4)
    s = db.session()
    model = RowModel()
    _seeded(s, model, n=6)
    tab = s.catalog.engine.tables["t"].tablet
    mark = tab.delta_mark()
    snap = s._txsvc.gts.current()
    for i in range(4):
        s.execute(f"insert into t values ({40 + i}, 0, 1.00, 'a', null), "
                  f"({50 + i}, 0, 1.00, 'b', null), "
                  f"({60 + i}, 0, 1.00, 'c', null)")
    assert tab.delta_since(mark, snap, s._txsvc.gts.current()) is None
    newest = tab.delta_mark()
    d = tab.delta_since(newest, s._txsvc.gts.current(),
                        s._txsvc.gts.current())
    assert d is not None and d.keys == []
    # the copy behind the log is rebuilt, and right
    before = _count("storage.device_copy_fallbacks",
                    reason="delta_unavailable")
    assert s.execute("select count(*) from t").rows() == [(18,)]
    assert _count("storage.device_copy_fallbacks",
                  reason="delta_unavailable") == before + 1
    s.close()


def test_delta_since_lists_the_newest_version_of_each_key(db):
    s = db.session()
    model = RowModel()
    _seeded(s, model, n=6)
    tab = s.catalog.engine.tables["t"].tablet
    mark, a = tab.delta_mark(), s._txsvc.gts.current()
    s.execute("insert into t values (70, 0, 1.00, 'first', null)")
    s.execute("update t set s = 'second' where k = 70 and j = 0")
    s.execute("delete from t where k in (1)")
    s.execute("begin")
    s.execute("insert into t values (71, 0, 1.00, 'open', null)")
    d = tab.delta_since(mark, a, s._txsvc.gts.current())
    s.execute("rollback")
    assert sorted(d.keys) == [(1, 1), (70, 0)]
    assert d.row_keys == [(70, 0)]
    assert list(d.arrays["s"]) == ["second"]
    assert d.valids["d"] is not None and not d.valids["d"][0]
    s.close()


def _delta_by_lookup(tab, mark, after, upto):
    """The plain reference of ``delta_since``: the loop it replaced, which
    asks every memtable for every key the log names, then the L0 segments
    -> (keys touched, keys live, their rows)."""
    _epoch, seq = mark
    keys, oldest = {}, upto
    for s, version, ks, *_ in tab._commit_log:
        if (s > seq or version > after) and version <= upto:
            oldest = min(oldest, version)
            keys.update(dict.fromkeys(ks))
    found, missing = {}, []
    for key in keys:
        for mt in [tab.active] + tab.frozen[::-1]:
            v = mt.visible_version(key, upto)
            if v is not None:
                found[key] = (v.op == "delete", v.values)
                break
        else:
            missing.append(key)
    if missing:
        found.update(tab._segment_versions(missing, oldest, upto))
    touched = [k for k in keys if k in found]
    live = [k for k in touched if not found[k][0]]
    return touched, live, [found[k][1] for k in live]


def _twice(s, tab):
    s.execute("insert into t values (70, 0, 1.00, 'first', null)")
    s.execute("update t set s = 'second', v = 2.50 where k = 70 and j = 0")
    s.execute("update t set d = date '1995-01-01' where k = 2 and j = 2")


def _inserted_then_deleted(s, tab):
    s.execute("insert into t values (71, 0, 1.00, 'gone', null), "
              "(72, 1, 1.00, 'stays', null)")
    s.execute("delete from t where k in (71, 3)")


def _frozen(s, tab):
    s.execute("insert into t values (73, 0, 1.00, 'cold', null)")
    s.execute("delete from t where k in (4)")
    assert tab.freeze() is not None and tab.frozen      # no compaction
    s.execute("insert into t values (74, 0, 1.00, 'warm', null)")


def _moved_to_l0(s, tab):
    s.execute("insert into t values (75, 0, 1.00, 'flushed', null)")
    s.execute("delete from t where k in (5)")
    s.execute("alter system minor freeze")
    assert not tab.frozen and tab.segments
    s.execute("insert into t values (76, 0, 1.00, 'after', null)")


def _rolled_back_statement(s, tab):
    from oceanbase_tpu.tx.errors import DuplicateKey

    s.execute("begin")
    s.execute("insert into t values (77, 0, 1.00, 'kept', null)")
    with pytest.raises(DuplicateKey):   # 78 is written, then taken back
        s.execute("insert into t values (78, 0, 1.00, 'no', null), "
                  "(77, 0, 1.00, 'twice', null)")
    s.execute("update t set s = 'mine' where k = 1 and j = 1")
    s.execute("commit")


#: what is committed between the mark and the read -> (commits, does the
#: log answer every key alone)
DELTA_HISTORIES = {
    "written_twice_in_two_commits": (_twice, True),
    "inserted_then_deleted": (_inserted_then_deleted, True),
    "committed_then_frozen": (_frozen, True),
    "moved_to_l0_by_a_mini_compaction": (_moved_to_l0, False),
    "written_and_rolled_back_by_its_statement": (_rolled_back_statement,
                                                 None),
}


@pytest.mark.parametrize("below_newest", [False, True],
                         ids=["upto_newest", "upto_below_newest"])
@pytest.mark.parametrize("history", sorted(DELTA_HISTORIES))
def test_delta_since_from_the_log_equals_the_per_key_lookup(
        db, history, below_newest):
    commits, log_answers = DELTA_HISTORIES[history]
    s = db.session()
    model = RowModel()
    _seeded(s, model, n=8)
    tab = s.catalog.engine.tables["t"].tablet
    mark, after = tab.delta_mark(), s._txsvc.gts.current()
    commits(s, tab)
    upto = s._txsvc.gts.current()
    if below_newest:    # one more commit on a key of the delta, above upto
        s.execute("insert into t values (99, 0, 1.00, 'later', null)")
        s.execute("update t set s = 'later' where k = 2 and j = 2")
        s.execute("delete from t where k in (72, 74, 76, 77)")
    want_keys, want_live, want_rows = _delta_by_lookup(tab, mark, after,
                                                       upto)
    d = tab.delta_since(mark, after, upto)
    assert d.keys == want_keys and want_keys
    assert d.row_keys == want_live
    assert (99, 0) not in d.keys
    for c in COLS:
        valid = d.valids[c]
        got = [None if valid is not None and not valid[i] else x
               for i, x in enumerate(d.arrays[c].tolist())]
        assert got == [r[c] for r in want_rows], c
    if log_answers is not None:
        assert (d.segment_keys == 0) == log_answers
    # and the maintained copy it feeds equals a rebuild
    assert _columns_of(s.catalog.table_data("t")) == \
        _columns_of(_rebuilt(s))
    s.close()


@pytest.mark.parametrize("strings", [False, True])
def test_key_index_finds_takes_and_puts(strings):
    import jax.numpy as jnp

    from oceanbase_tpu.vector import from_numpy

    rng = np.random.default_rng(11)
    a = rng.permutation(200)[:120]
    b = rng.integers(0, 4, 120)
    first = np.array([f"k{x:03d}" for x in a], dtype=object) if strings \
        else a
    rel = from_numpy({"a": first, "b": b}).pad_to(128)
    rel = rel.with_mask(rel.mask & (jnp.arange(128) != 7))
    index = KeyIndex.from_relation(rel, ["a", "b"])

    def key(i):
        return (first[i] if strings else int(a[i]), int(b[i]))

    got = index.take([key(3), key(7), key(50), ("nope" if strings else -5,
                                                0)])
    assert list(got) == [3, -1, 50, -1]
    assert list(index.take([key(3)])) == [-1]           # struck out
    index.put([key(3), ("new" if strings else 999, 1)], 120)
    assert list(index.take([("new" if strings else 999, 1), key(3)])) == \
        [121, 120]


def test_apply_in_chunks_meets_every_size_with_one_program(db, monkeypatch):
    monkeypatch.setattr(device_delta, "DELTA_LANES", 8)
    s = db.session()
    model = RowModel()
    _seeded(s, model, n=30)
    rows = [{"k": 3000 + i, "j": i % 3, "v": i, "s": f"w{i}", "d": i}
            for i in range(21)]                 # three chunks of eight
    s.execute("insert into t values " + ", ".join(map(_row_sql, rows)))
    for r in rows:
        model.insert(r)
    s.execute("delete from t where k in (" + ", ".join(
        str(k) for k in range(0, 19)) + ")")    # 19 lanes: three chunks
    model.delete_k(set(range(0, 19)))
    assert _columns_of(s.catalog.table_data("t")) == model.columns()
    s.close()


def test_a_chunk_is_written_by_windows_and_one_scatter_over_the_mask():
    """The mechanism, with no chip: in the program ``_apply_chunk`` lowers
    to, no scatter takes a column's data or validity (they go through
    ``dynamic_update_slice``); the one scatter left clears the mask."""
    import re

    import jax.numpy as jnp

    cap, w = 4096, 256
    cols = {"a": (jnp.zeros(cap, jnp.int64), None),
            "b": (jnp.zeros(cap, jnp.int32), jnp.ones(cap, jnp.bool_))}
    rows = {"a": (np.zeros(w, np.int64), None),
            "b": (np.zeros(w, np.int32), np.ones(w, bool))}
    lowered = device_delta._apply_chunk.lower(
        cols, jnp.ones(cap, jnp.bool_), np.zeros(w, np.int32),
        np.int32(0), np.int32(0), rows)
    hlo = lowered.compiler_ir(dialect="hlo").as_hlo_text()
    scatters = re.findall(r"= (\w+)\[([\d,]*)\][^=]* scatter\(", hlo)
    assert scatters == [("pred", str(cap))], scatters
    # data of a, data and validity of b, the mask's new lanes
    updates = re.findall(r"= (\w+)\[([\d,]*)\][^=]* "
                         r"dynamic-update-slice\(", hlo)
    assert sorted(u for u in updates if u[1] == str(cap)) == sorted(
        [("s64", str(cap)), ("s32", str(cap)), ("pred", str(cap)),
         ("pred", str(cap))]), updates
    assert "gather(" not in hlo


def test_a_second_apply_of_another_size_compiles_nothing(db):
    s = db.session()
    model = RowModel()
    _seeded(s, model, n=30)
    s.execute("insert into t values " + ", ".join(map(_row_sql, _rows(
        2000, 2, s="new-a"))))
    s.catalog.table_data("t")               # the layout's program compiles
    events = _backend_compiles()
    s.execute("insert into t values " + ", ".join(map(_row_sql, _rows(
        2100, 7, s="new-b"))))
    s.execute("delete from t where k in (1, 2, 3, 2000)")
    e0 = len(events)
    applies = _count("storage.delta_applies")
    rel = s.catalog.table_data("t")
    assert _count("storage.delta_applies") == applies + 1
    assert len(events) == e0, events[e0:]
    assert int(np.asarray(rel.mask_or_true()).sum()) == 30 + 2 + 7 - 4
    s.close()


def test_memtable_keys_by_leading_part_follow_writes_and_aborts():
    from oceanbase_tpu.storage.memtable import MemTable

    mt = MemTable()
    for k in range(50):
        for j in range(k % 3 + 1):
            mt.write((k, j), "insert", {"k": k, "j": j}, tx_id=1)
    mt.commit(1, 5, list(mt._rows))
    mt.write((7, 9), "insert", {"k": 7, "j": 9}, tx_id=2)
    mt.write((60, 0), "insert", {"k": 60, "j": 0}, tx_id=2)
    mt.abort(2, [(7, 9), (60, 0)])

    def walk(within):
        return sorted(k for k in mt._rows
                      if all((lo is None or k[i] >= lo)
                             and (hi is None or k[i] <= hi)
                             for i, lo, hi in within))

    for within in ([(0, 5, 9)], [(0, 7, 7), (1, 1, None)], [(0, 40, 70)],
                   [(0, None, 3)], [(1, 2, 2)], [(0, 0, 10 ** 9)], []):
        assert sorted(mt.keys_within(within)) == walk(within), within
    assert 60 not in mt._by_first and (7, 9) not in mt._by_first[7]
