"""Stored procedures: CREATE/DROP PROCEDURE, CALL, DECLARE/SET/IF/WHILE
(≙ src/pl — here an interpreted statement list over the shared
expression engine; traced UDFs remain the JIT analog).
"""

import pytest

from oceanbase_tpu.server import Database


def test_procedure_control_flow(tmp_path):
    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("""
create procedure fill(in n int)
begin
  declare i int default 0;
  while i < n do
    insert into t values (i, i * i);
    set i = i + 1;
  end while;
end""")
    s.execute("call fill(5)")
    assert s.execute("select count(*), sum(v) from t").rows()[0] == \
        (5, 0 + 1 + 4 + 9 + 16)
    # IF / ELSEIF / ELSE
    s.execute("""
create procedure judge(in x int)
begin
  if x > 10 then
    select 'big';
  elseif x > 5 then
    select 'mid';
  else
    select 'small';
  end if;
end""")
    assert s.execute("call judge(20)").rows() == [("big",)]
    assert s.execute("call judge(7)").rows() == [("mid",)]
    assert s.execute("call judge(1)").rows() == [("small",)]
    db.close()


def test_procedure_params_in_queries(tmp_path):
    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table acc (id int primary key, bal int)")
    s.execute("insert into acc values (1, 100), (2, 50)")
    s.execute("""
create procedure transfer(in src int, in dst int, in amt int)
begin
  update acc set bal = bal - amt where id = src;
  update acc set bal = bal + amt where id = dst;
  select bal from acc where id = dst;
end""")
    r = s.execute("call transfer(1, 2, 30)")
    assert r.rows() == [(80,)]
    assert s.execute("select bal from acc order by id").rows() == \
        [(70,), (80,)]
    db.close()


def test_procedure_persists_across_restart(tmp_path):
    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (k int primary key)")
    s.execute("create procedure p1(in k int) begin "
              "insert into t values (k); end")
    db.close()
    db2 = Database(str(tmp_path / "db"))
    s2 = db2.session()
    s2.execute("call p1(7)")
    assert s2.execute("select k from t").rows() == [(7,)]
    s2.execute("drop procedure p1")
    with pytest.raises(KeyError):
        s2.execute("call p1(8)")
    db2.close()


def test_procedure_in_memory_session(new_session):
    s = new_session()
    import numpy as np

    s.catalog.load_numpy("t", {"k": np.arange(4),
                               "v": np.array([1, 2, 3, 4])},
                         primary_key=["k"])
    s.execute("create procedure q(in lo int) begin "
              "select sum(v) from t where k >= lo; end")
    assert s.execute("call q(2)").rows() == [(7,)]
