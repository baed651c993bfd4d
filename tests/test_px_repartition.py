"""Joins that repartition over the mesh, under the DDL of the benchmark's
``tpch_sf10_part4`` configuration at SF 0.01 on four virtual devices.

TPC-H Q9 and Q14 through ``Session.execute`` at ``px_dop = 4`` are held to
plain NumPy references (``bench/numpy_ref.py``: bit-equal int64 sums) and
to SQLite, with the planner's own choice of distribution and with PKEY and
HASH-HASH forced in turn; the exchanges' counters, an exchange's overflow
and re-plan, the replicated ``nation`` and ``parallel_servers_target`` are
read from ``gv$sysstat`` / ``show trace``.
"""

import json
import os

import numpy as np
import pytest

from oceanbase_tpu.bench import numpy_ref
from oceanbase_tpu.bench.oracle import load_sqlite, rows_match, run_oracle
from oceanbase_tpu.bench.tpch import TPCH_PRIMARY_KEYS, gen_tpch
from oceanbase_tpu.px import planner
from oceanbase_tpu.server import Database
from oceanbase_tpu.server import metrics as qmetrics

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
TABLES = ("part", "supplier", "lineitem", "partsupp", "orders", "nation")
D0 = int((np.datetime64("1995-09-01") - np.datetime64("1970-01-01"))
         .astype(np.int64))
D1 = int((np.datetime64("1995-10-01") - np.datetime64("1970-01-01"))
         .astype(np.int64))


def _json(*path):
    with open(os.path.join(BENCH, *path), encoding="utf-8") as f:
        return json.load(f)


Q9 = _json("statements", "tpch_q9_sf10.json")["sql"].replace("{COLOR}",
                                                              "green")
Q14 = _json("statements", "tpch_q14_sf10.json")["sql"].replace(
    "{DATE}", "1995-09-01")


@pytest.fixture(scope="module")
def data():
    return gen_tpch(sf=0.01)


@pytest.fixture(scope="module")
def cluster(data, tmp_path_factory):
    """The six tables under the configuration's own DDL and settings."""
    tables, types = data
    db = Database(str(tmp_path_factory.mktemp("part4") / "db"))
    s = db.session()
    cfg = _json("configs", "tpch_sf10_part4.json")
    for sql in cfg["system_settings"]:
        s.execute(sql)
    for name in TABLES:
        s.catalog.load_numpy(
            name, tables[name],
            types={c: t for c, t in types.items() if c in tables[name]},
            primary_key=TPCH_PRIMARY_KEYS[name])
        s.execute(f"analyze table {name}")
    for sql in cfg["session_settings"]:
        s.execute(sql)
    yield db, s
    db.close()


@pytest.fixture(scope="module")
def sqlite(data):
    tables, types = data
    return load_sqlite({t: tables[t] for t in TABLES}, types)


def _counters(prefix: str) -> dict:
    return {k: v for k, v in qmetrics.counters().items()
            if k.startswith(prefix)} if hasattr(qmetrics, "counters") \
        else {}


def _sysstat(s, like: str) -> dict:
    r = s.execute("select stat_name, value from gv$sysstat "
                  f"where stat_name like '{like}'")
    return {n: float(v) for n, v in r.rows()}


def _grew(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def _q9_rows(result):
    a = result.arrays
    return [(str(n), int(y), int(v)) for n, y, v in zip(
        a["nation"], a["o_year"], a["sum_profit"])]


def _force(monkeypatch, dist: str):
    """Every join that is not partition-wise takes ``dist``: nothing is
    small enough to broadcast, and for HASH-HASH no side is taken to lie
    by its join keys."""
    monkeypatch.setattr(planner, "BROADCAST_THRESHOLD_BYTES", 0)
    if dist == "hash":
        monkeypatch.setattr(planner, "_key_positions",
                            lambda alts, keys: [])


@pytest.mark.parametrize("dist", ["chosen", "pkey", "hash"])
def test_q9_px_bit_equal_to_numpy_and_sqlite(dist, cluster, data, sqlite,
                                             monkeypatch):
    _db, s = cluster
    if dist != "chosen":
        _force(monkeypatch, dist)
        s.plan_cache.clear()
    before = _sysstat(s, "px.%")
    got = s.execute(Q9)
    assert s._last_px
    assert _q9_rows(got) == numpy_ref.numpy_q9(data[0])
    ok, why = rows_match(got.rows(), run_oracle(sqlite, Q9), ordered=True)
    assert ok, why
    grew = _grew(before, _sysstat(s, "px.%"))
    joins = {k: v for k, v in grew.items() if k.startswith("px.joins")}
    overflows = sum(v for k, v in grew.items()
                    if k.startswith("px.exchange_overflows"))
    # five joins an attempt; an overflow re-plans once
    assert sum(joins.values()) == 5 * (1 + overflows), joins
    if dist == "chosen":
        # at this scale every build side but orders, which shares
        # lineitem's tablegroup, is small enough to broadcast
        assert joins.get("px.joins{dist=broadcast}", 0) >= 3, joins
    elif dist == "pkey":
        assert overflows == 0
        # a side that lies by its join key stays; where neither does
        # (nation against supplier's s_nationkey) both move
        assert joins["px.joins{dist=pkey}"] >= 2, joins
        assert "px.joins{dist=broadcast}" not in joins
        assert grew["px.exchange_lanes{kind=pkey}"] > 0
        assert grew["px.exchange_rows{kind=pkey}"] > 0
    else:
        # orders' rows already lie by the hash of o_orderkey, so a forced
        # HASH-HASH sends a whole shard to one destination: that exchange
        # overflows, is named, and the re-plan with its budget raised
        # answers as exactly
        assert set(joins) == {"px.joins{dist=hash}"}, joins
        assert overflows == grew.get("px.exchange_overflows{kind=hash}", 0)
        assert grew["px.exchange_lanes{kind=hash}"] > 0
        assert grew["px.exchange_rows{kind=hash}"] > 0
        assert grew["px.exchange_bytes{kind=hash}"] \
            > grew["px.exchange_rows{kind=hash}"]


@pytest.mark.parametrize("dist", ["chosen", "pkey", "hash"])
def test_q14_px_equal_to_numpy(dist, cluster, data, monkeypatch):
    _db, s = cluster
    if dist != "chosen":
        _force(monkeypatch, dist)
        s.plan_cache.clear()
    before = _sysstat(s, "px.%")
    got = s.execute(Q14)
    assert s._last_px
    promo, total = numpy_ref.numpy_q14(data[0], D0, D1)
    value = float(got.arrays["promo_revenue"][0])
    assert value == pytest.approx(100.0 * promo / total, rel=1e-12)
    grew = _grew(before, _sysstat(s, "px.%"))
    if dist == "chosen":
        assert grew.get("px.joins{dist=broadcast}") == 1
        return
    assert grew.get(f"px.joins{{dist={dist}}}") == 1, grew
    assert grew[f"px.exchange_lanes{{kind={dist}}}"] > 0
    li = data[0]["lineitem"]
    month = int(((li["l_shipdate"] >= D0) & (li["l_shipdate"] < D1)).sum())
    if dist == "pkey":
        # the month's lineitems move to part's partitions, each once
        assert grew["px.exchange_rows{kind=pkey}"] == month
        # what the join and the sums above it read and no more (the
        # filter's l_shipdate stays behind): l_partkey, l_extendedprice,
        # l_discount and the row mask's byte
        assert grew["px.exchange_bytes{kind=pkey}"] == month * 25
    else:
        # both sides move (the bloom filter drops no lineitem whose part
        # exists, and every part exists); the hybrid join's few hot build
        # rows reach every shard
        moved = month + len(data[0]["part"]["p_partkey"])
        assert moved <= grew["px.exchange_rows{kind=hash}"] <= moved + 64


def test_exchange_overflow_replans_that_budget_and_keeps_marks(
        cluster, data, monkeypatch):
    """A PKEY budget of 16 lanes a destination overflows: the statement
    re-plans ONCE with that exchange's budget raised, answers correctly,
    counts the overflow by kind, and its ``build_unique`` join still emits
    on its probe's lanes."""
    _db, s = cluster
    _force(monkeypatch, "pkey")
    monkeypatch.setattr(planner, "_snap_budget", lambda n: 16)
    s.plan_cache.clear()
    s._px_budgets.clear()
    before = {**_sysstat(s, "px.%"), **_sysstat(s, "plan.%")}
    got = s.execute(Q14)
    spans = [r[0].strip() for r in s.execute("show trace").rows()]
    after = {**_sysstat(s, "px.%"), **_sysstat(s, "plan.%")}
    grew = _grew(before, after)
    promo, total = numpy_ref.numpy_q14(data[0], D0, D1)
    assert float(got.arrays["promo_revenue"][0]) \
        == pytest.approx(100.0 * promo / total, rel=1e-12)
    assert s._last_px
    assert grew["px.exchange_overflows{kind=pkey}"] == 1
    assert grew["plan.capacity_retries"] == 1
    # both attempts' programs kept the mark: two joins on probe lanes,
    # none expanded
    assert grew["plan.join_emits{kind=probe_lanes}"] == 2
    assert "plan.join_emits{kind=expanded}" not in grew
    (raised,) = s._px_budgets.values()
    assert list(raised) == ["px_exchange.pkey.0"] and raised[
        "px_exchange.pkey.0"] >= 4
    assert "px.replan" in spans
    # the next execution starts from the budget that cleared it
    before = after
    s.execute(Q14)
    grew = _grew(before, {**_sysstat(s, "px.%"), **_sysstat(s, "plan.%")})
    assert "plan.capacity_retries" not in grew
    assert "px.exchange_overflows{kind=pkey}" not in grew


def test_nation_is_replicated_once_per_data_version(cluster):
    _db, s = cluster
    s.execute(Q9)
    rel = s.catalog.table_data("nation")
    copy = planner.replicated_on(rel, rel._px_replica[0])
    s.execute(Q9)
    assert planner.replicated_on(rel, rel._px_replica[0]) is copy
    shards = {}
    for name, _el, _self, _dev, tags in (
            r[:5] for r in s.execute("show trace").rows()):
        if name.strip() == "px.shard":
            tags = json.loads(tags)
            shards[tags["table"]] = tags
    assert shards["nation"]["by"] == "replicated"
    # no table crosses the host on the way to the mesh
    assert all("bytes" not in t for t in shards.values()), shards
    assert {t["by"] for n, t in shards.items() if n != "nation"} \
        == {"partition"}


@pytest.mark.parametrize("target,admitted", [(128, True), (4, True),
                                             (2, False)])
def test_parallel_servers_target_sizes_px_admission(target, admitted,
                                                    cluster):
    db, s = cluster
    quota = db.tenant("sys").px_admission
    try:
        s.execute(f"set global parallel_servers_target = {target}")
        assert quota.limit == target
        before = qmetrics.counter_value("admission.px_downgrades",
                                        tenant="sys")
        s.execute(Q14)
        assert s._last_px is admitted
        assert (qmetrics.counter_value("admission.px_downgrades",
                                       tenant="sys") - before) \
            == (0 if admitted else 1)
    finally:
        s.execute("set global parallel_servers_target = 128")
    assert quota.limit == 128 and quota._held == 0


def test_unset_target_leaves_px_workers_per_tenant(tmp_path):
    db = Database(str(tmp_path / "db"))
    try:
        assert db.tenant("sys").px_admission.limit == 64
    finally:
        db.close()


def test_like_selectivity_reads_the_analyzed_sample(cluster, data):
    """ANALYZE keeps a row-weighted sample of a string column; the
    binder's LIKE selectivity is the share of it that matches (5 % of
    p_name holds 'green'), not the fixed tenth."""
    _db, s = cluster
    names = data[0]["part"]["p_name"].astype("U")
    share = float((np.char.find(names, "green") >= 0).mean())
    td = s.catalog.table_def("part")
    sample = td.samples["p_name"]
    assert len(sample) == 16384
    got = sum("green" in v for v in sample) / len(sample)
    assert abs(got - share) < 0.03
    text = s.execute(
        "explain select count(*) from part where p_name like '%green%'"
    ).plan_text
    assert "Filter" in text


def _load(tmp_path, seed):
    tables, types = gen_tpch(sf=0.01, seed=seed)
    db = Database(str(tmp_path / f"db{seed}"))
    s = db.session()
    cfg = _json("configs", "tpch_sf10_part4.json")
    for sql in cfg["system_settings"]:
        s.execute(sql)
    for name in TABLES:
        s.catalog.load_numpy(
            name, tables[name],
            types={c: t for c, t in types.items() if c in tables[name]},
            primary_key=TPCH_PRIMARY_KEYS[name])
        s.execute(f"analyze table {name}")
    s.execute("set px_dop = 4")
    return db, s, tables


def test_another_load_is_the_same_shard_program(tmp_path, monkeypatch):
    """Q9 over two loads of other data (another seed: other part names,
    other row counts) lowers to the SAME shard program, so the second
    finds the first one's compile in the persistent cache: ``p_name``'s
    LIKE table is an input of the program, padded to the dictionary's
    bucket, not a constant in it, and the budgets come from estimates
    rounded to powers of two."""
    import hashlib

    from oceanbase_tpu.exec import plan as qplan
    from oceanbase_tpu.expr import compile as xcompile

    monkeypatch.setattr(xcompile, "LUT_INPUT_MIN", 0)
    compile_ = qplan._PlanExecutable._compile
    programs = []

    def spy(self, tables, sig):
        if self.program.shard is not None:
            assert xcompile.LUTS_TABLE in tables
            (lut,) = [c.data for c in
                      tables[xcompile.LUTS_TABLE].columns.values()
                      if c.data.shape[0] >= 2048]
            programs.append((hashlib.sha1(self._run.lower(
                tables).as_text().encode()).hexdigest(), lut.shape,
                int(lut.sum())))
        return compile_(self, tables, sig)

    monkeypatch.setattr(qplan._PlanExecutable, "_compile", spy)
    answers = []
    for seed in (7, 8):
        db, s, tables = _load(tmp_path, seed)
        try:
            assert _q9_rows(s.execute(Q9)) == numpy_ref.numpy_q9(tables)
            assert s._last_px
            answers.append(numpy_ref.numpy_q9(tables))
        finally:
            db.close()
    assert answers[0] != answers[1]
    (text_a, shape_a, green_a), (text_b, shape_b, green_b) = programs
    assert text_a == text_b
    assert shape_a == shape_b == (2048,)
    assert 0 < green_a != green_b > 0     # other names match in each load
