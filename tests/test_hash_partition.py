"""Hash / key partitioned tables and tablegroups as schema, their partitions
resident one a device, and PX plans that derive every exchange from the
declared layout (≙ upstream's TPC-H DDL: ``PARTITION BY KEY ... PARTITIONS
n`` in tablegroups; ObShardingInfo / ObPwjComparer).

Runs on four of the eight virtual CPU devices ``conftest.py`` forces.  The
references are independent of the program: SQLite for answers, and a plain
NumPy function of the key, written here, for where a row lies.
"""

import jax
import numpy as np
import pytest

from oceanbase_tpu.bench.oracle import load_sqlite, rows_match, run_oracle
from oceanbase_tpu.bench.tpch import TPCH_PRIMARY_KEYS, gen_tpch
from oceanbase_tpu.bench.tpch_queries import QUERIES
from oceanbase_tpu.exec.plan import executable_for
from oceanbase_tpu.px import planner as px_planner
from oceanbase_tpu.px.exchange import default_mesh
from oceanbase_tpu.server import Database
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.sql.parser import ParseError, parse_sql

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 devices")

NPARTS = 4
TABLES = ("lineitem", "orders", "customer", "part")
KEYS = {"lineitem": "l_orderkey", "orders": "o_orderkey",
        "customer": "c_custkey", "part": "p_partkey"}
GROUP = {"lineitem": "tg_orders", "orders": "tg_orders"}


def plain_partition_of(key: np.ndarray, nparts: int) -> np.ndarray:
    """splitmix64's finalizer of the key, modulo the partition count:
    written out here, with Python integers, so that it shares nothing with
    the program's ``share/keyhash.py``."""
    out = np.empty(len(key), dtype=np.int64)
    m = (1 << 64) - 1
    for i, k in enumerate(key.tolist()):
        x = k & m
        x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & m
        x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & m
        out[i] = (x ^ (x >> 31)) % nparts
    return out


def _ddl(table: str, arrays: dict, types: dict) -> str:
    cols = []
    for c, a in arrays.items():
        t = types.get(c)
        sql_type = ("varchar(200)" if a.dtype == object else "bigint") \
            if t is None else str(t)
        cols.append(f"{c} {sql_type} not null")
    group = f" tablegroup = {GROUP[table]}" if table in GROUP else ""
    return (f"create table {table} ({', '.join(cols)}, primary key "
            f"({', '.join(TPCH_PRIMARY_KEYS[table])})){group} partition by "
            f"key ({KEYS[table]}) partitions {NPARTS}")


@pytest.fixture(scope="module")
def data():
    tables, types = gen_tpch(sf=0.01, seed=29)
    return {t: tables[t] for t in TABLES}, types


def _load(session, tables, types):
    for name, arrays in tables.items():
        session.catalog.load_numpy(
            name, arrays,
            types={k: v for k, v in types.items() if k in arrays},
            primary_key=TPCH_PRIMARY_KEYS[name])


@pytest.fixture(scope="module")
def partitioned(data, tmp_path_factory):
    tables, types = data
    db = Database(str(tmp_path_factory.mktemp("part4")))
    s = db.session()
    s.execute("create tablegroup tg_orders")
    for name, arrays in tables.items():
        s.execute(_ddl(name, arrays, types))
    _load(s, tables, types)
    yield s
    db.close()


@pytest.fixture(scope="module")
def unpartitioned(data, tmp_path_factory):
    tables, types = data
    db = Database(str(tmp_path_factory.mktemp("plain")))
    s = db.session()
    _load(s, tables, types)
    yield s
    db.close()


@pytest.fixture(scope="module")
def sqlite(data):
    return load_sqlite(*data)


def _run_px(s, sql):
    s.execute("set px_dop = 4")
    try:
        rows = s.execute(sql).rows()
        assert s._last_px
        return rows
    finally:
        s.execute("set px_dop = 1")


def _joins() -> dict:
    return {d: qmetrics.counter_value("px.joins", dist=d)
            for d in ("partition_wise", "broadcast", "pkey", "hash")}


def _lanes() -> dict:
    return {k: qmetrics.counter_value("px.exchange_lanes", kind=k)
            for k in ("broadcast", "pkey", "hash")}


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


# -- (a) where the rows lie ---------------------------------------------------

@pytest.mark.parametrize("table", TABLES)
def test_every_row_lies_in_the_partition_its_key_hashes_to(
        partitioned, data, table):
    tablet = partitioned.db.engine.tables[table].tablet
    assert len(tablet.partitions) == NPARTS
    key = KEYS[table]
    seen = []
    for i, part in enumerate(tablet.partitions):
        arrays, _valids = part.snapshot_arrays(2 ** 62)
        keys = np.asarray(arrays[key])
        assert (plain_partition_of(keys, NPARTS) == i).all()
        seen.append(np.stack([np.asarray(arrays[c]) for c in
                              TPCH_PRIMARY_KEYS[table]], axis=1))
    # exactly one partition each: as many rows as were loaded, none twice
    rows = np.concatenate(seen)
    assert len(rows) == len(data[0][table][key])
    assert len(np.unique(rows, axis=0)) == len(rows)


@pytest.mark.parametrize("table", TABLES)
def test_partition_i_is_resident_on_device_i(partitioned, table):
    rel = partitioned.catalog.table_data(table)
    layout = rel.partitions
    assert layout.key_cols == (KEYS[table],)
    sharded = layout.sharded(default_mesh(NPARTS), "px")
    assert sharded is layout.sharded(default_mesh(NPARTS), "px")  # kept
    cap = layout.capacity
    assert sharded.capacity == NPARTS * cap
    for col in sharded.columns.values():
        shards = sorted(col.data.addressable_shards,
                        key=lambda sh: sh.index[0].start)
        assert [sh.device for sh in shards] == jax.devices()[:NPARTS]
    keys = sharded.columns[KEYS[table]].data
    mask = sharded.mask
    for i in range(NPARTS):
        live = np.asarray(mask.addressable_shards[i].data)
        local = np.asarray(keys.addressable_shards[i].data)[live]
        assert mask.addressable_shards[i].device == jax.devices()[i]
        assert len(local) == layout.rows[i]
        assert (plain_partition_of(local, NPARTS) == i).all()
    assert sum(layout.rows) == int(np.asarray(rel.mask).sum())


def test_location_view_lists_every_partition_on_its_device(partitioned):
    _run_px(partitioned, "select count(*) from lineitem, orders "
                         "where l_orderkey = o_orderkey")
    rows = partitioned.execute(
        "select table_name, tablegroup, partition_id, method, partition_key,"
        " rows, device, capacity from gv$table_locations "
        "where table_name = 'lineitem' order by partition_id").rows()
    assert [r[2] for r in rows] == list(range(NPARTS))
    assert {r[1] for r in rows} == {"tg_orders"}
    assert {(r[3], r[4]) for r in rows} == {("key", "l_orderkey")}
    assert [r[6] for r in rows] == [str(d) for d in jax.devices()[:NPARTS]]
    total = partitioned.execute("select count(*) from lineitem").rows()[0][0]
    assert sum(r[5] for r in rows) == total
    assert len({r[7] for r in rows}) == 1 and rows[0][7] >= max(
        r[5] for r in rows)


def test_host_and_device_hash_are_one_function():
    import jax.numpy as jnp

    from oceanbase_tpu.datatypes import SqlType
    from oceanbase_tpu.expr import ir
    from oceanbase_tpu.px.exchange import _hash_dest
    from oceanbase_tpu.share import keyhash
    from oceanbase_tpu.vector import from_numpy

    r = np.random.default_rng(3)
    a = r.integers(-2 ** 62, 2 ** 62, 4096)
    b = r.integers(0, 1000, 4096)
    rel = from_numpy({"a": a, "b": b},
                     types={"a": SqlType.int_(), "b": SqlType.int_()})
    one = np.asarray(_hash_dest(rel, [ir.col("a")], NPARTS))
    assert (one == keyhash.partition_of([a], NPARTS)).all()
    assert (one == plain_partition_of(a, NPARTS)).all()
    two = np.asarray(_hash_dest(rel, [ir.col("a"), ir.col("b")], NPARTS))
    assert (two == keyhash.partition_of([a, b], NPARTS)).all()
    assert (np.asarray(keyhash.partition_of(
        [jnp.asarray(a), jnp.asarray(b)], NPARTS, jnp)) == two).all()


# -- (b) answers ------------------------------------------------------------------

STATEMENTS = {
    "q1": (QUERIES[1], True),
    "q3": (QUERIES[3], True),
    "q6": (QUERIES[6], True),
    "q14": (QUERIES[14], True),
    # a join on a key neither table is partitioned by on both sides
    "orders_customer": (
        "select c_mktsegment, count(*) as n, sum(o_totalprice) as total "
        "from orders, customer where o_custkey = c_custkey "
        "group by c_mktsegment order by c_mktsegment", True),
}


@pytest.mark.parametrize("layout", ["partitioned", "unpartitioned"])
@pytest.mark.parametrize("name", sorted(STATEMENTS))
def test_px_answers_equal_sqlite(request, sqlite, layout, name):
    s = request.getfixturevalue(layout)
    sql, ordered = STATEMENTS[name]
    want = run_oracle(sqlite, sql)
    got = _run_px(s, sql)
    ok, why = rows_match(got, want, ordered=ordered)
    assert ok, f"{name} ({layout}, px): {why}"
    serial = s.execute(sql).rows()
    assert not s._last_px
    ok, why = rows_match(serial, want, ordered=ordered)
    assert ok, f"{name} ({layout}, serial): {why}"


def test_a_dop_other_than_the_partition_count_shards_per_statement(
        partitioned, sqlite):
    sql = STATEMENTS["q3"][0]
    before = qmetrics.counter_value("px.partition_builds")
    partitioned.execute("set px_dop = 2")
    try:
        got = partitioned.execute(sql).rows()
        assert partitioned._last_px
    finally:
        partitioned.execute("set px_dop = 1")
    ok, why = rows_match(got, run_oracle(sqlite, sql), ordered=True)
    assert ok, why
    assert qmetrics.counter_value("px.partition_builds") == before


# -- (c) the plan, read from the counters --------------------------------------------

PWJ = ("select count(*), sum(l_quantity) from lineitem, orders "
       "where l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'")
MOVE = ("select count(*), sum(o_totalprice) from orders, customer "
        "where o_custkey = c_custkey and c_mktsegment = 'BUILDING'")


def test_a_join_on_the_partition_key_of_both_sides_has_no_exchange(
        partitioned, sqlite):
    _run_px(partitioned, PWJ)        # traced, compiled
    joins, lanes = _joins(), _lanes()
    got = _run_px(partitioned, PWJ)
    assert _delta(_joins(), joins) == {"partition_wise": 1}
    assert _delta(_lanes(), lanes) == {}
    assert rows_match(got, run_oracle(sqlite, PWJ), ordered=True)[0]


def test_a_join_on_another_key_has_one_exchange(partitioned, sqlite):
    _run_px(partitioned, MOVE)
    joins, lanes = _joins(), _lanes()
    got = _run_px(partitioned, MOVE)
    assert _delta(_joins(), joins) == {"broadcast": 1}   # a small build side
    moved = _delta(_lanes(), lanes)
    assert list(moved) == ["broadcast"] and moved["broadcast"] > 0
    assert rows_match(got, run_oracle(sqlite, MOVE), ordered=True)[0]


def test_a_large_side_moves_to_the_others_partitions(partitioned, sqlite,
                                                     monkeypatch):
    # nothing is small enough to broadcast: customer lies by the join key
    # already, so orders alone moves, to customer's partitions (PKEY)
    monkeypatch.setattr(px_planner, "BROADCAST_THRESHOLD_BYTES", 0)
    executable_for.cache_clear()
    try:
        _run_px(partitioned, MOVE)
        joins, lanes = _joins(), _lanes()
        got = _run_px(partitioned, MOVE)
        assert _delta(_joins(), joins) == {"pkey": 1}
        assert list(_delta(_lanes(), lanes)) == ["pkey"]
        assert rows_match(got, run_oracle(sqlite, MOVE), ordered=True)[0]
        # Q3: orders moves to customer's partitions, so the join with
        # lineitem finds orders' rows elsewhere and moves lineitem too
        got = _run_px(partitioned, QUERIES[3])
        assert rows_match(got, run_oracle(sqlite, QUERIES[3]),
                          ordered=True)[0]
    finally:
        executable_for.cache_clear()


def test_q3_takes_one_partition_wise_join_and_one_exchange(partitioned):
    _run_px(partitioned, QUERIES[3])
    joins = _joins()
    shard_spans_before = qmetrics.counter_value("px.partition_builds")
    _run_px(partitioned, QUERIES[3])
    assert _delta(_joins(), joins) == {"partition_wise": 1, "broadcast": 1}
    # a table that does not change keeps its partitions' copies
    assert qmetrics.counter_value("px.partition_builds") == \
        shard_spans_before


def test_unpartitioned_tables_keep_the_per_statement_path(unpartitioned):
    before = qmetrics.counter_value("px.partition_builds")
    _run_px(unpartitioned, PWJ)
    joins = _joins()
    _run_px(unpartitioned, PWJ)
    # choose_affinity co-shards both scans by the join key, per statement
    assert _delta(_joins(), joins) == {"partition_wise": 1}
    assert qmetrics.counter_value("px.partition_builds") == before
    assert not hasattr(unpartitioned.catalog.table_data("orders"),
                       "partitions")


# -- (d) DML --------------------------------------------------------------------------

@pytest.fixture()
def small(tmp_path):
    db = Database(str(tmp_path))
    s = db.session()
    s.execute("create table t (k bigint, g bigint, v decimal(15,2), "
              "primary key (k)) partition by hash (k) partitions 4")
    s.execute("insert into t values " + ", ".join(
        f"({k}, {k % 3}, {k}.25)" for k in range(40)))
    yield s
    db.close()


def _keys_by_partition(s, table="t", key="k"):
    tablet = s.db.engine.tables[table].tablet
    return [sorted(np.asarray(
        p.snapshot_arrays(2 ** 62)[0][key]).tolist())
        for p in tablet.partitions]


def test_inserted_rows_are_routed_by_the_hash_of_their_key(small):
    parts = _keys_by_partition(small)
    assert sorted(sum(parts, [])) == list(range(40))
    for i, keys in enumerate(parts):
        assert (plain_partition_of(np.array(keys, dtype=np.int64), 4)
                == i).all()
    assert small.execute("select count(*), sum(k), sum(v) from t").rows() \
        == [(40, 780, 790.0)]


def test_an_update_of_the_key_moves_the_row(small):
    small.execute("update t set k = k + 1000 where k < 10")
    parts = _keys_by_partition(small)
    assert sorted(sum(parts, [])) == list(range(10, 40)) + list(
        range(1000, 1010))
    for i, keys in enumerate(parts):
        assert (plain_partition_of(np.array(keys, dtype=np.int64), 4)
                == i).all()
    assert small.execute("select v from t where k = 1003").rows() == \
        [(3.25,)]
    small.execute("update t set v = v + 1 where k = 1003")
    assert small.execute("select v from t where k = 1003").rows() == \
        [(4.25,)]


def test_deletes_and_px_reads_after_dml(small):
    small.execute("delete from t where g = 0")
    want = [(k, float(k) + 0.25) for k in range(40) if k % 3]
    assert small.execute("select k, v from t order by k").rows() == want
    builds = qmetrics.counter_value("px.partition_builds")
    small.execute("set px_dop = 4")
    got = small.execute("select g, count(*), sum(v) from t group by g "
                        "order by g").rows()
    assert small._last_px
    assert qmetrics.counter_value("px.partition_builds") == builds + 4
    assert got == [(g, sum(1 for k, _ in want if k % 3 == g),
                    sum(v for k, v in want if k % 3 == g)) for g in (1, 2)]
    # a commit makes the copies stale: the next PX statement rebuilds them
    small.execute("insert into t values (500, 1, 1.00)")
    got = small.execute("select count(*) from t where g = 1").rows()
    assert got == [(14,)] and small._last_px
    assert qmetrics.counter_value("px.partition_builds") == builds + 8


def test_a_transaction_sees_its_own_rows_in_their_partitions(small):
    small.execute("begin")
    small.execute("insert into t values (700, 1, 7.00), (701, 2, 7.50)")
    assert small.execute("select count(*) from t where k >= 700").rows() \
        == [(2,)]
    small.execute("rollback")
    assert small.execute("select count(*) from t where k >= 700").rows() \
        == [(0,)]


# -- (e) DDL ----------------------------------------------------------------------------

def test_show_create_table_round_trips(partitioned):
    text = partitioned.execute("show create table lineitem").rows()[0][1]
    assert "TABLEGROUP = tg_orders" in text
    assert f"PARTITION BY KEY (l_orderkey) PARTITIONS {NPARTS}" in text
    partitioned.execute(text.replace("CREATE TABLE lineitem",
                                     "CREATE TABLE lineitem_again"))
    again = partitioned.execute(
        "show create table lineitem_again").rows()[0][1]
    assert again == text.replace("CREATE TABLE lineitem",
                                 "CREATE TABLE lineitem_again")
    partitioned.execute("drop table lineitem_again")


def test_the_layout_survives_a_reopen(tmp_path):
    db = Database(str(tmp_path))
    s = db.session()
    s.execute("create tablegroup g1")
    s.execute("create table a (k bigint primary key, v bigint) "
              "tablegroup = g1 partition by key (k) partitions 4")
    s.execute("insert into a values (1, 10), (2, 20), (3, 30), (4, 40)")
    before = _keys_by_partition(s, "a")
    text = s.execute("show create table a").rows()[0][1]
    db.close()
    db = Database(str(tmp_path))
    s = db.session()
    assert s.execute("show create table a").rows()[0][1] == text
    assert _keys_by_partition(s, "a") == before
    s.execute("insert into a values (5, 50)")
    assert s.execute("select sum(v) from a").rows() == [(150,)]
    with pytest.raises(ValueError, match="exists"):
        s.execute("create tablegroup g1")
    s.execute("create tablegroup if not exists g1")
    with pytest.raises(ValueError, match="not empty"):
        s.execute("drop tablegroup g1")
    s.execute("drop table a")
    s.execute("drop tablegroup g1")
    s.execute("drop tablegroup if exists g1")
    db.close()


def test_the_partition_column_cannot_be_dropped(tmp_path):
    db = Database(str(tmp_path))
    s = db.session()
    s.execute("create table h (a bigint, b bigint) partition by hash (a) "
              "partitions 2")
    with pytest.raises(ValueError, match="partition column"):
        s.execute("alter table h drop column a")
    s.execute("alter table h drop column b")
    s.execute("insert into h values (1), (2), (3)")
    assert s.execute("select sum(a) from h").rows() == [(6,)]
    db.close()


@pytest.mark.parametrize("sql, message", [
    ("create table x (a bigint, b bigint) partition by hash (a, b) "
     "partitions 4", "one column"),
    ("create table x (a bigint) partition by key (a)", "PARTITIONS"),
    ("create table x (a bigint) partition by key (a) partitions 0",
     "at least 1"),
    ("create table x (a bigint) partition by list (a) partitions 2",
     "RANGE, HASH or KEY"),
])
def test_the_parser_refuses(sql, message):
    with pytest.raises(ParseError, match=message):
        parse_sql(sql)


def test_the_parser_takes_the_options_in_either_order():
    a = parse_sql("create table x (a bigint) tablegroup = g partition by "
                  "key (a) partitions 8")
    b = parse_sql("create table x (a bigint) partition by key (a) "
                  "partitions 8 tablegroup g")
    assert a.hash_partition == b.hash_partition == ("key", ["a"], 8)
    assert a.tablegroup == b.tablegroup == "g"
    assert parse_sql("create table x (a bigint) partition by hash (a) "
                     "partitions 2").hash_partition == ("hash", ["a"], 2)


@pytest.mark.parametrize("sql, message", [
    ("create table x (a varchar(8)) partition by key (a) partitions 4",
     "can be hashed"),
    ("create table x (a bigint) partition by key (b) partitions 4",
     "not a table column"),
    ("create table x (a bigint, b bigint, primary key (a)) partition by "
     "key (b) partitions 4", "PRIMARY KEY must include"),
    ("create table x (a bigint) tablegroup = nowhere partition by key (a) "
     "partitions 4", "unknown tablegroup"),
    ("create table x (a bigint) tablegroup = g2 partition by key (a) "
     "partitions 8", "not partitioned as"),
])
def test_the_engine_refuses(tmp_path, sql, message):
    db = Database(str(tmp_path))
    s = db.session()
    s.execute("create tablegroup g2")
    s.execute("create table first (k bigint) tablegroup = g2 partition by "
              "key (k) partitions 4")
    with pytest.raises(ValueError, match=message):
        s.execute(sql)
    assert not s.catalog.has_table("x")
    db.close()
