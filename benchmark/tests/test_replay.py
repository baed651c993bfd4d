"""The reference of a mix that writes: the acknowledged transactions are
applied in the order they were sent, every read is answered at its
position, and every replay starts from the seed's data.  The reference's
own code, run in this process (it needs no JAX and no system)."""

import os

import numpy as np
import pytest

from benchmark.harness import reference, spec, traffic, writes

SCALE, SEED = 0.01, 5


def _statement(name):
    return spec.read_json(
        os.path.join(spec.BENCH_DIR, "statements", name + ".json"))


@pytest.fixture(scope="module")
def ref():
    """Q6 (exact reference) and Q1 (SQLite and exact) over the two refresh
    statements, as ``runner.Run.start_reference`` would build the job."""
    items = []
    for name in ("tpch_q6", "tpch_q1"):
        st = _statement(name)
        params = traffic.validation_params(st)
        items.append({"key": name, "sql": traffic.render(st, params),
                      "params": params,
                      "sqlite": bool(st["reference"].get("sqlite")),
                      "exact": st["reference"].get("exact")})
    reads = {"lineitem": list(dict.fromkeys(
        _statement("tpch_q6")["reads"]["lineitem"]
        + _statement("tpch_q1")["reads"]["lineitem"]))}
    return reference._Reference({
        "bench_dir": spec.BENCH_DIR, "dataset": "tpch", "scale": SCALE,
        "seed": SEED, "reads": reads, "items": items,
        "writes": {n: _statement(n) for n in ("tpch_rf1", "tpch_rf2")},
        "read_back": ["orders", "lineitem"]})


def _write(at, template, k, acks=None):
    n = len(writes.bindings(_statement(template),
                            spec.load_module("datasets", "tpch"),
                            SCALE, SEED, k))
    return {"at": at, "template": template, "k": k,
            "acks": [True] * n if acks is None else acks}


def _read(at, key="tpch_q1"):
    return {"at": at, "key": key}


def test_with_no_write_every_read_gets_the_seeds_answer(ref):
    out = ref.replay([_read(0), _read(1, "tpch_q6"), _read(2)])
    assert out["sqlite"][0] == out["sqlite"][2]
    assert out["exact"][0] == out["exact"][2]
    assert out["seconds"]["answered"] == 2      # one answer a key
    tables, _ = spec.load_module("datasets", "tpch").generate(SCALE, SEED)
    assert out["read_back"]["orders"][0] == len(tables["orders"]["o_orderkey"])
    assert out["read_back"]["lineitem"] == [
        len(tables["lineitem"]["l_orderkey"]),
        int(tables["lineitem"]["l_orderkey"].sum()),
        int(tables["lineitem"]["l_quantity"].sum())]


def test_a_read_is_answered_at_its_position(ref):
    out = ref.replay([_read(0), _write(1, "tpch_rf1", 0), _read(2),
                      _write(3, "tpch_rf2", 0), _read(4), _read(5)])
    counts = [sum(g["count_order"] for g in out["exact"][at].values())
              for at in (0, 2, 4)]
    orders, lineitem, old = spec.load_module(
        "datasets", "tpch").refresh(SCALE, SEED, 0)
    cutoff = 10471 - 90     # 1998-12-01 less the validation DELTA, in days
    added = int((lineitem["l_shipdate"] <= cutoff).sum())
    assert added > 0 and counts[1] == counts[0] + added
    assert counts[2] < counts[1]                # the old sales went
    assert out["exact"][5] == out["exact"][4]   # no write between: shared
    assert out["seconds"]["answered"] == 3
    # SQLite took the same writes: its counts are the exact reference's
    for at in (0, 2, 4):
        assert sum(row[-1] for row in out["sqlite"][at]) == \
            sum(g["count_order"] for g in out["exact"][at].values())
    # the read-back is the population less one set plus one set
    assert out["read_back"]["orders"][0] == 15000
    assert out["read_back"]["orders"][1] == \
        sum(range(1, 15001)) - int(old.sum()) + int(orders["o_orderkey"].sum())


def test_a_transaction_that_raised_is_not_applied(ref):
    none = ref.replay([_write(0, "tpch_rf1", 0, acks=[False]), _read(1)])
    base = ref.replay([_read(1)])
    assert none["exact"][1] == base["exact"][1]
    assert none["read_back"] == base["read_back"]
    # a set cut short: only the transactions before the raise count
    st = dict(_statement("tpch_rf1"), batch=5)
    ref.job["writes"]["tpch_rf1"] = st
    try:
        part = ref.replay([{"at": 0, "template": "tpch_rf1", "k": 0,
                            "acks": [True, False]}])
    finally:
        ref.job["writes"]["tpch_rf1"] = _statement("tpch_rf1")
    assert part["read_back"]["orders"][0] == \
        base["read_back"]["orders"][0] + 5


def test_every_replay_starts_from_the_seeds_data(ref):
    log = [_write(0, "tpch_rf1", 1), _write(1, "tpch_rf2", 1), _read(2)]
    a, b = ref.replay(log), ref.replay(log)
    assert a["sqlite"] == b["sqlite"] and a["exact"] == b["exact"]
    assert a["read_back"] == b["read_back"]


def test_writes_between_reads_are_applied_in_groups():
    base = {"t": {"k": np.arange(10), "v": np.arange(10) * 2}}
    arrays = reference._Arrays(base, {"t": ["k", "v"]})
    arrays.insert("t", {"k": np.array([10, 11]), "v": np.array([1, 1]),
                        "unread": np.array([0, 0])})
    arrays.insert("t", {"k": np.array([12]), "v": np.array([1])})
    arrays.delete("t", "k", [0, 10])
    arrays.delete("t", "k", [1])
    arrays.insert("t", {"k": np.array([0]), "v": np.array([5])})
    got = arrays.table("t")
    assert got["k"].tolist() == [2, 3, 4, 5, 6, 7, 8, 9, 11, 12, 0]
    assert got["v"].tolist()[-3:] == [1, 1, 5]
    assert arrays.pending["t"] == []
    arrays.reset()
    assert arrays.table("t")["k"].tolist() == list(range(10))
    assert base["t"]["k"].tolist() == list(range(10))   # the seed's data
