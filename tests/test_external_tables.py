"""External (lake) tables: CSV + Parquet scanned at query time, and
Arrow interop (VERDICT r3 missing #12).

≙ src/share/external_table + src/sql/engine/connector +
src/sql/engine/basic/ob_arrow_basic.h.
"""

import numpy as np
import pytest

from oceanbase_tpu.server import Database


def _write_csv(path, rows):
    with open(path, "w") as fh:
        for r in rows:
            fh.write(",".join(str(x) for x in r) + "\n")


def test_external_csv_table(tmp_path, new_session):
    p = tmp_path / "sales.csv"
    _write_csv(p, [(1, "north", "2024-01-05", "10.50"),
                   (2, "south", "2024-02-11", "3.25"),
                   (3, "north", "2024-03-02", "7.00")])
    s = new_session()
    s.execute(f"create external table sales ("
              f"id int, region varchar(16), d date, amt decimal(10,2)) "
              f"location '{p}'")
    r = s.execute("select region, sum(amt), count(*) from sales "
                  "group by region order by region")
    assert r.rows() == [("north", 17.5, 2), ("south", 3.25, 1)]
    r = s.execute("select id from sales where d >= date '2024-02-01' "
                  "order by id")
    assert [x[0] for x in r.rows()] == [2, 3]
    # joins against regular tables work
    s.catalog.load_numpy("dim", {"region": np.array(
        ["north", "south"], dtype=object),
        "mgr": np.array(["ann", "bob"], dtype=object)})
    r = s.execute("select mgr, count(*) from sales join dim using "
                  "(region) group by mgr order by mgr")
    assert r.rows() == [("ann", 2), ("bob", 1)]
    # DROP removes it
    s.execute("drop table sales")
    assert not s.catalog.has_table("sales")


def test_external_csv_reflects_file_changes(tmp_path, new_session):
    p = tmp_path / "t.csv"
    _write_csv(p, [(1, 10)])
    s = new_session()
    s.execute(f"create external table t (k int, v int) location '{p}'")
    assert s.execute("select count(*) from t").rows()[0][0] == 1
    import os
    import time

    _write_csv(p, [(1, 10), (2, 20), (3, 30)])
    os.utime(p, (time.time() + 5, time.time() + 5))
    assert s.execute("select count(*) from t").rows()[0][0] == 3


def test_external_parquet_table(tmp_path, new_session):
    pa = pytest.importorskip("pyarrow")
    import pyarrow.parquet as pq

    p = str(tmp_path / "d.parquet")
    table = pa.table({
        "k": pa.array([1, 2, 3]),
        "name": pa.array(["a", "b", None]),
        "score": pa.array([1.5, 2.5, 3.5])})
    pq.write_table(table, p)
    s = new_session()
    s.execute(f"create external table d ("
              f"k int, name varchar(8), score double) location '{p}'")
    r = s.execute("select k, name, score from d order by k")
    assert r.rows() == [(1, "a", 1.5), (2, "b", 2.5), (3, None, 3.5)]
    # external tables work in a Database (engine catalog) too
    db = Database(str(tmp_path / "db"))
    sdb = db.session()
    sdb.execute(f"create external table d2 (k int, name varchar(8), "
                f"score double) location '{p}'")
    assert sdb.execute("select sum(score) from d2").rows()[0][0] == 7.5
    db.close()


def test_arrow_interop_roundtrip(tmp_path, new_session):
    pa = pytest.importorskip("pyarrow")
    from oceanbase_tpu.share.external import (
        arrow_to_arrays, result_to_arrow)

    s = new_session()
    t = pa.table({"k": pa.array([1, 2]),
                  "s": pa.array(["x", "y"])})
    arrays, valids, types = arrow_to_arrays(t)
    s.catalog.load_numpy("a", arrays, types=types,
                         valids=valids or None)
    res = s.execute("select k, upper(s) as u from a order by k")
    out = result_to_arrow(res)
    assert out.column("k").to_pylist() == [1, 2]
    assert out.column("u").to_pylist() == ["X", "Y"]


def test_external_table_persists_with_database(tmp_path):
    p = tmp_path / "e.csv"
    _write_csv(p, [(1, 5), (2, 6)])
    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute(f"create external table e (k int, v int) location '{p}'")
    assert s.execute("select sum(v) from e").rows()[0][0] == 11
    # shadowing a base table is rejected
    s.execute("create table base (k int primary key)")
    with pytest.raises(ValueError):
        s.execute(f"create external table base (k int) location '{p}'")
    db.close()
    db2 = Database(str(tmp_path / "db"))
    s2 = db2.session()
    assert s2.execute("select count(*) from e").rows()[0][0] == 2
    s2.execute("drop table e")
    db2.close()
    db3 = Database(str(tmp_path / "db"))
    assert not db3.session().catalog.has_table("e")
    db3.close()
