"""The refresh sets of the TPC-H dataset module and the SQL a write
statement's transactions are rendered to."""

import os

import numpy as np
import pytest

from benchmark.harness import spec, writes

DS = spec.load_module("datasets", "tpch")
SCALE = 0.01
N = 15          # SF x 1500 orders a set
LOADED = 15000  # the population at this scale


def _statement(name):
    return spec.read_json(
        os.path.join(spec.BENCH_DIR, "statements", name + ".json"))


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 11])
def test_a_set_is_a_pure_function_of_scale_seed_and_k(seed):
    a, b = DS.refresh(SCALE, seed, 3), DS.refresh(SCALE, seed, 3)
    for x, y in zip(a[:2], b[:2]):
        assert list(x) == list(y)
        assert all((x[c] == y[c]).all() for c in x)
    assert (a[2] == b[2]).all()
    other = DS.refresh(SCALE, seed + 1, 3)
    assert (other[2] == a[2]).all()     # the old keys do not hang on the seed
    assert not (other[0]["o_custkey"] == a[0]["o_custkey"]).all()


def test_new_keys_lie_off_the_population_and_old_keys_on_it():
    new, old = set(), set()
    for k in range(40):
        orders, lineitem, old_keys = DS.refresh(SCALE, 5, k)
        assert len(orders["o_orderkey"]) == len(old_keys) == N
        assert orders["o_orderkey"].min() > LOADED
        assert 1 <= old_keys.min() and old_keys.max() <= LOADED
        assert (np.diff(old_keys) > 0).all()        # ascending
        assert set(lineitem["l_orderkey"]) == set(orders["o_orderkey"])
        per_order = np.bincount(lineitem["l_orderkey"]
                                - orders["o_orderkey"].min())
        assert per_order.min() >= 1 and per_order.max() <= 7
        assert not new & set(orders["o_orderkey"].tolist())  # sets disjoint
        assert not old & set(old_keys.tolist())
        new |= set(orders["o_orderkey"].tolist())
        old |= set(old_keys.tolist())
    assert old == set(range(1, 40 * N + 1))     # the k-th N keys, in order


def test_a_set_beyond_the_population_is_refused():
    DS.refresh(SCALE, 5, LOADED // N - 1)
    with pytest.raises(ValueError):
        DS.refresh(SCALE, 5, LOADED // N)


def test_new_rows_have_the_loaded_tables_columns_and_distributions():
    tables, types = DS.generate(SCALE, 5)
    orders, lineitem, _ = DS.refresh(SCALE, 5, 0)
    assert list(orders) == list(tables["orders"])
    assert list(lineitem) == list(tables["lineitem"])
    for new, loaded in ((orders, tables["orders"]),
                        (lineitem, tables["lineitem"])):
        for c in new:
            assert new[c].dtype == loaded[c].dtype, c
    assert set(lineitem["l_discount"]) <= set(range(11))
    assert set(lineitem["l_shipmode"]) <= set(DS.SHIPMODES)
    # an order's total is what its lineitems charge
    charged = (lineitem["l_extendedprice"] * (100 - lineitem["l_discount"])
               // 100 * (100 + lineitem["l_tax"]) // 100)
    for key, total in zip(orders["o_orderkey"], orders["o_totalprice"]):
        assert charged[lineitem["l_orderkey"] == key].sum() == total


def test_an_order_and_its_lineitems_share_a_transaction():
    st = _statement("tpch_rf1")
    sets = writes.bindings(dict(st, batch=4), DS, SCALE, 5, 2)
    assert len(sets) == 4       # 15 orders, 4 a transaction
    seen = []
    for b in sets:
        keys = set(b["orders"]["o_orderkey"].tolist())
        assert set(b["lineitem"]["l_orderkey"].tolist()) == keys
        seen += sorted(keys)
    assert seen == DS.refresh(SCALE, 5, 2)[0]["o_orderkey"].tolist()
    old = writes.bindings(dict(_statement("tpch_rf2"), batch=4), DS,
                          SCALE, 5, 2)
    assert np.concatenate([b["orders"]["o_orderkey"] for b in old]).tolist() \
        == DS.refresh(SCALE, 5, 2)[2].tolist()


def test_transactions_render_to_the_sql_the_client_sends():
    _, types = DS.generate(SCALE, 5)
    rf1 = writes.transactions(dict(_statement("tpch_rf1"), batch=2), DS,
                              types, SCALE, 5, 0)
    assert len(rf1) == 8 and all(len(tx) == 2 for tx in rf1)
    first, second = rf1[0]
    assert first.startswith(
        "insert into orders (o_orderkey, o_custkey, o_orderstatus, "
        "o_totalprice, o_orderdate, ") and first.count("), (") == 1
    assert second.startswith("insert into lineitem (l_orderkey, ")
    orders = DS.refresh(SCALE, 5, 0)[0]
    cents = int(orders["o_totalprice"][0])
    assert f", {cents // 100}.{cents % 100:02d}, date '" in first
    rf2 = writes.transactions(dict(_statement("tpch_rf2"), batch=100), DS,
                              types, SCALE, 5, 1)
    keys = ", ".join(str(k) for k in range(16, 31))
    assert rf2 == [[f"delete from lineitem where l_orderkey in ({keys})",
                    f"delete from orders where o_orderkey in ({keys})"]]


def test_literals_by_type():
    lit = writes._literals
    assert lit(np.array([5, -5, 12345, -100]), ("decimal", 15, 2)) == \
        ["0.05", "-0.05", "123.45", "-1.00"]
    assert lit(np.array([0, 9131], dtype=np.int32), ("date",)) == \
        ["date '1970-01-01'", "date '1995-01-01'"]
    assert lit(np.array(["it's", "x"], dtype=object), None) == \
        ["'it''s'", "'x'"]
    assert lit(np.array([7, -2]), None) == ["7", "-2"]


def test_an_operation_with_no_rows_sends_nothing():
    op = {"delete": "t", "where": "a", "rows": "r", "column": "a"}
    assert writes.render(op, {"r": {"a": np.array([], dtype=np.int64)}},
                         {}) is None
    op = {"insert": "t", "rows": "r"}
    assert writes.render(op, {"r": {"a": np.array([], dtype=np.int64)}},
                         {}) is None
