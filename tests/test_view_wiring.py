"""View DDL wired through the SQL surface + catalog DDL race hardening.

Satellites of the DTL pushdown PR: CREATE/DROP VIEW dispatch in
sql/session.py, views in SHOW TABLES / DESCRIBE / SHOW CREATE, the loud
WITH RECURSIVE rejection, and the catalog's locked collision checks.
"""

from __future__ import annotations

import numpy as np
import pytest

from oceanbase_tpu.catalog import Catalog, ColumnDef, TableDef
from oceanbase_tpu.datatypes import SqlType


@pytest.fixture()
def session(new_session):
    s = new_session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("insert into t values (1, 10), (2, 20), (3, 30)")
    return s


def test_create_select_show_drop_view_end_to_end(session):
    s = session
    s.execute("create view big (kk, vv) as select k, v from t "
              "where v >= 20")
    assert s.execute("select kk, vv from big order by kk").rows() == \
        [(2, 20), (3, 30)]
    # views show up in metadata
    names = [r[0] for r in s.execute("show tables").rows()
             if not r[0].startswith(("gv$", "v$", "information_schema."))]
    assert names == ["big", "t"]
    desc = s.execute("describe big").rows()
    assert [(f, t) for f, t, _n, _k in desc] == \
        [("kk", "INT"), ("vv", "INT")]
    create = s.execute("show create table big").rows()[0][1]
    assert create.startswith("CREATE VIEW big (kk, vv) AS")
    # OR REPLACE swaps the body; plain re-create errors
    with pytest.raises(ValueError, match="exists"):
        s.execute("create view big as select k from t")
    s.execute("create or replace view big as select k from t where k = 1")
    assert s.execute("select * from big").rows() == [(1,)]
    # drop removes it from metadata and binding
    s.execute("drop view big")
    assert [r[0] for r in s.execute("show tables").rows()
            if not r[0].startswith(("gv$", "v$", "information_schema."))] \
        == ["t"]
    with pytest.raises(KeyError):
        s.execute("drop view big")
    s.execute("drop view if exists big")  # no error
    with pytest.raises(KeyError):
        s.execute("select * from big")


def test_view_name_collisions(session):
    s = session
    s.execute("create view v1 as select k from t")
    # a table must not shadow a view, in either creation order
    with pytest.raises(ValueError, match="view v1"):
        s.execute("create table v1 (x int)")
    with pytest.raises(ValueError, match="already exists"):
        s.execute("create view t as select 1")


def test_self_referencing_cte_message(session):
    # a plain CTE referencing itself gets a direct, non-contradicting
    # error instead of pretending a materializer exists
    with pytest.raises(Exception, match="WITH RECURSIVE is not"):
        session.execute(
            "with r (x) as (select x from r) select * from r")


def test_view_over_virtual_table_refreshes(tmp_path):
    """A view body referencing a gv$ table must re-materialize the
    virtual relation per statement, not serve the snapshot captured by
    whichever query touched it first."""
    from oceanbase_tpu.server.database import Database

    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create view audit_v as select sql from gv$sql_audit")
    r1 = s.execute("select count(*) from audit_v").rows()[0][0]
    r2 = s.execute("select count(*) from audit_v").rows()[0][0]
    assert r2 > r1  # the audit ring grew between statements


def _tdef(name):
    return TableDef(name, [ColumnDef("x", SqlType.int_())])


def test_catalog_collision_checks_are_locked():
    cat = Catalog()
    cat.create_view("v", "select 1")
    # create_table checks views inside the locked section
    with pytest.raises(ValueError, match="view v"):
        cat.create_table(_tdef("v"))
    # register_external refuses views and base tables atomically
    with pytest.raises(ValueError, match="view v"):
        cat.register_external(_tdef("v"), "/nowhere.csv")
    cat.create_table(_tdef("t"))
    with pytest.raises(ValueError, match="already exists"):
        cat.register_external(_tdef("t"), "/nowhere.csv")
    # register_transient refuses to shadow a view ...
    with pytest.raises(ValueError, match="view v"):
        cat.register_transient("v", {"x": np.arange(3)})
    # ... but re-registering an existing transient (per-statement gv$
    # refresh) stays allowed
    cat.register_transient("gv$x", {"x": np.arange(3)})
    cat.register_transient("gv$x", {"x": np.arange(4)})


def test_concurrent_view_vs_table_create_never_coexist():
    """Race a CREATE VIEW against a CREATE TABLE of the same name: with
    the check inside the lock, exactly one side wins."""
    import threading

    for trial in range(20):
        cat = Catalog()
        errs = []
        barrier = threading.Barrier(2)

        def mk_table():
            barrier.wait()
            try:
                cat.create_table(_tdef("x"))
            except ValueError as e:
                errs.append(e)

        def mk_view():
            barrier.wait()
            try:
                cat.create_view("x", "select 1")
            except ValueError as e:
                errs.append(e)

        ts = [threading.Thread(target=mk_table),
              threading.Thread(target=mk_view)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        is_table = cat.has_table("x")
        is_view = cat.view_def("x") is not None
        assert is_table != is_view, (trial, is_table, is_view)
        assert len(errs) == 1
