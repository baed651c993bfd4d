"""SQL end-to-end basics: DDL, DML, simple queries (≙ mysqltest smoke)."""

import numpy as np
import pytest


@pytest.fixture()
def sess(new_session):
    return new_session()


def test_create_insert_select(sess):
    sess.execute("create table t (a int primary key, b varchar(20), "
                 "c decimal(10,2), d date)")
    sess.execute("insert into t values (1, 'x', 1.50, '2020-01-05'), "
                 "(2, 'y', 2.25, '2021-06-01'), (3, null, 0.75, '2020-01-05')")
    r = sess.execute("select a, b, c from t where c > 1.00 order by a")
    assert r.rows() == [(1, "x", 1.5), (2, "y", 2.25)]

    r = sess.execute("select count(*), sum(c) from t")
    assert r.rows() == [(3, 4.5)]

    r = sess.execute("select b, count(*) as n from t group by b order by n desc, b")
    rows = r.rows()
    assert len(rows) == 3  # 'x', 'y', NULL are distinct groups

    r = sess.execute("select a from t where b is null")
    assert r.rows() == [(3,)]


def test_update_delete(sess):
    sess.execute("create table u (k int, v int)")
    sess.execute("insert into u values (1, 10), (2, 20), (3, 30)")
    r = sess.execute("update u set v = v + 5 where k >= 2")
    assert r.rowcount == 2
    r = sess.execute("select sum(v) from u")
    assert r.rows() == [(70,)]
    r = sess.execute("delete from u where k = 1")
    assert r.rowcount == 1
    assert sess.execute("select count(*) from u").rows() == [(2,)]


def test_joins_sql(sess):
    sess.execute("create table dept (id int primary key, dname varchar(10))")
    sess.execute("create table emp (eid int, did int, sal int)")
    sess.execute("insert into dept values (1, 'eng'), (2, 'ops')")
    sess.execute("insert into emp values (1, 1, 100), (2, 1, 200), (3, 2, 50), (4, 9, 10)")
    r = sess.execute("select dname, sum(sal) as total from emp, dept "
                     "where did = id group by dname order by total desc")
    assert r.rows() == [("eng", 300), ("ops", 50)]
    # left join keeps unmatched emp
    r = sess.execute("select eid, dname from emp left join dept on did = id "
                     "order by eid")
    rows = r.rows()
    assert rows[3] == (4, None)


def test_subqueries_sql(sess):
    sess.execute("create table t1 (a int, b int)")
    sess.execute("insert into t1 values (1, 10), (2, 20), (3, 30)")
    sess.execute("create table t2 (x int)")
    sess.execute("insert into t2 values (2), (3), (5)")
    r = sess.execute("select a from t1 where a in (select x from t2) order by a")
    assert r.rows() == [(2,), (3,)]
    r = sess.execute("select a from t1 where not exists "
                     "(select * from t2 where x = a) order by a")
    assert r.rows() == [(1,)]
    r = sess.execute("select a from t1 where b > (select avg(b) from t1) order by a")
    assert r.rows() == [(3,)]


def test_setops_sql(sess):
    sess.execute("create table s1 (v int)")
    sess.execute("insert into s1 values (1), (2), (2), (3)")
    sess.execute("create table s2 (v int)")
    sess.execute("insert into s2 values (2), (4)")
    r = sess.execute("select v from s1 union select v from s2 order by v")
    assert r.rows() == [(1,), (2,), (3,), (4,)]
    r = sess.execute("select v from s1 union all select v from s2 order by v")
    assert len(r.rows()) == 6
    r = sess.execute("select v from s1 intersect select v from s2")
    assert r.rows() == [(2,)]
    r = sess.execute("select v from s1 except select v from s2 order by v")
    assert r.rows() == [(1,), (3,)]


def test_explain_show_describe(sess):
    sess.execute("create table e (a int, b varchar(5))")
    r = sess.execute("explain select a from e where b = 'x'")
    assert "TableScan" in r.plan_text
    assert "Filter" in r.plan_text
    names = sess.execute("show tables").arrays["table_name"]
    assert "e" in list(names)
    d = sess.execute("describe e")
    assert d.rowcount == 2


def test_params(sess):
    sess.execute("create table p (a int, b int)")
    sess.execute("insert into p values (1, 2), (3, 4)")
    r = sess.execute("select b from p where a = ?", params=[3])
    assert r.rows() == [(4,)]


def test_distinct_and_case(sess):
    sess.execute("create table dc (g varchar(2), v int)")
    sess.execute("insert into dc values ('a', 1), ('a', 2), ('b', 3)")
    r = sess.execute("select distinct g from dc order by g")
    assert r.rows() == [("a",), ("b",)]
    r = sess.execute(
        "select g, sum(case when v > 1 then v else 0 end) as s "
        "from dc group by g order by g")
    assert r.rows() == [("a", 2), ("b", 3)]
