"""Regression tests for SQL-frontend code-review findings."""

import pytest

from oceanbase_tpu.sql.binder import BindError


@pytest.fixture()
def sess(new_session):
    s = new_session()
    s.execute("create table a (x int, v int)")
    s.execute("insert into a values (1, 10), (2, 20)")
    s.execute("create table b (x int, z int)")
    s.execute("insert into b values (1, 100), (1, 101), (3, 300)")
    s.execute("create table c (x int, w int)")
    s.execute("insert into c values (2, 7)")
    return s


def test_left_join_with_inner_join_side(sess):
    # the a-b inner join predicate must apply (not degrade to cross join),
    # and the LEFT join must keep unmatched rows
    r = sess.execute(
        "select a.x, b.z, c.w from a join b on a.x = b.x "
        "left join c on a.x = c.x order by b.z")
    assert r.rows() == [(1, 100, None), (1, 101, None)]


def test_left_join_same_column_names(sess):
    # 'x' exists on both sides: ownership must track colids, not names
    r = sess.execute(
        "select a.x, c.w from a left join c on a.x = c.x order by a.x")
    assert r.rows() == [(1, None), (2, 7)]


def test_paren_union_limit(sess):
    r = sess.execute("(select x from a order by x limit 1) "
                     "union all select x from b order by x")
    # limit applies to the left branch only: 1 + 3 rows
    assert [t[0] for t in r.rows()] == [1, 1, 1, 3]


def test_union_trailing_limit(sess):
    r = sess.execute("select x from a union all select x from b "
                     "order by x limit 2")
    assert len(r.rows()) == 2


def test_order_by_aggregate_expr(sess):
    r = sess.execute("select x from b group by x order by count(*) desc, x")
    assert [t[0] for t in r.rows()] == [1, 3]
    r = sess.execute("select x, sum(z) as s from b group by x "
                     "order by sum(z) desc")
    assert r.rows() == [(3, 300), (1, 201)]


def test_order_by_base_column_not_selected(sess):
    r = sess.execute("select v from a order by x desc")
    assert r.rows() == [(20,), (10,)]


def test_order_by_ordinal_bounds(sess):
    with pytest.raises(BindError):
        sess.execute("select x from a order by 3")
    with pytest.raises(BindError):
        sess.execute("select x from a order by 0")


def test_storage_package_imports():
    import oceanbase_tpu.storage  # noqa: F401
