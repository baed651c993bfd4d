"""UDFs (trace-time JIT) + LOAD DATA INFILE tests."""

import numpy as np
import pytest

from oceanbase_tpu.expr.compile import register_udf, unregister_udf
from oceanbase_tpu.server import Database


def test_udf_traced_into_plan(rng, new_session):
    import jax.numpy as jnp

    register_udf("sigmoid_cents",
                 lambda x: 1.0 / (1.0 + jnp.exp(-x.astype(jnp.float64) / 100)))
    try:
        s = new_session()
        s.catalog.load_numpy("t", {"v": np.array([0, 100, -100])})
        r = s.execute("select v, sigmoid_cents(v) as p from t order by v")
        rows = r.rows()
        assert rows[1][1] == pytest.approx(0.5)
        assert rows[2][1] == pytest.approx(1 / (1 + np.exp(-1)))
        # strict NULL semantics
        s.catalog.load_numpy("n", {"v": np.array([5, 7])},
                             valids={"v": np.array([True, False])})
        r = s.execute("select sigmoid_cents(v) as p from n order by v")
        assert r.rows()[1][0] is None or r.rows()[0][0] is None
    finally:
        unregister_udf("sigmoid_cents")
    # unregistered again -> clean error
    with pytest.raises(Exception):
        s.execute("select sigmoid_cents(1)")


def test_load_data_infile(tmp_path):
    csv_path = tmp_path / "in.csv"
    csv_path.write_text(
        "k,v,name,d\n"
        "1,10.50,ann,2020-01-01\n"
        "2,20.25,bob,2021-06-15\n"
        "3,,carol,2022-12-31\n"
    )
    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (k int primary key, v decimal(10,2), "
              "name varchar(20), d date)")
    r = s.execute(f"load data infile '{csv_path}' into table t "
                  f"fields terminated by ',' ignore 1 lines")
    assert r.rowcount == 3
    rows = s.execute("select k, v, name, d from t order by k").rows()
    assert rows[0] == (1, 10.5, "ann", "2020-01-01")
    assert rows[2][1] is None  # empty field -> NULL
    # direct load produced a baseline segment, not memtable rows
    assert db.engine.tables["t"].tablet.segments
    db.close()
