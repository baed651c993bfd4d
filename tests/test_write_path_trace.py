"""The write path on the statement's one clock (PR 40): DML, commit, the
replicated log and its fsyncs as spans under the statement's root
(``show trace``), as phases of ``gv$sql_audit`` / ``gv$time_model``
(``dml_s``, ``tx_commit_s``, ``log_sync_s``, ``freeze_s``) and as counters
of ``gv$sysstat`` (``tx.*``, ``palf.*``, ``sql.parse_bytes``), with no span
and no metric call a row."""

from __future__ import annotations

import json

import numpy as np
import pytest

from oceanbase_tpu.server import Database
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.server import trace as qtrace

#: rows of the bulk-loaded table: five chunks of 65,536, so that one
#: chunk is under the candidate path's quarter of the table
N_BIG = 270_000

WRITE_PHASES = ("dml_s", "tx_commit_s", "log_sync_s", "freeze_s")
PHASE_COLUMNS = ("queue_s", "device_s", "xla_compile_s") + tuple(
    p for p in qtrace.PHASES if p != "compile_s")


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    d = Database(str(tmp_path_factory.mktemp("writes") / "db"))
    # an append that finds its leader's lease lapsed (400 ms) runs an
    # election first: five more persists under it.  Not in these tests.
    for p in d.wal.proposers.values():
        p.lease_ms = 3_600_000
    d.wal.proposers[d.wal.leader_id].refresh_lease()
    s = d.session()
    s.execute("create table o (k int primary key, v decimal(10,2), "
              "c varchar(20))")
    s.execute("create table l (k int, n int, q int, primary key (k, n))")
    s.execute("create table ix (k int primary key, a int, b int)")
    s.execute("create index ix_a on ix (a)")
    rng = np.random.default_rng(40)
    s.catalog.load_numpy(
        "big", {"k": np.arange(N_BIG), "v": rng.integers(0, 50, N_BIG)},
        primary_key=["k"])
    # the first commit of a process imports what later ones find loaded
    s.execute("insert into o values (-1, 0.00, 'warm')")
    yield d
    d.close()


_next_key = [0]


def _keys(n: int) -> range:
    k = _next_key[0]
    _next_key[0] += n
    return range(k, k + n)


def _insert_o(keys) -> str:
    return "insert into o values " + ", ".join(
        f"({k}, {k}.25, 'c{k}')" for k in keys)


def _insert_l(keys) -> str:
    return "insert into l values " + ", ".join(
        f"({k}, {n}, {k + n})" for k in keys for n in range(3))


def _trace(sess) -> list[tuple[int, str, dict]]:
    """``show trace`` as (depth, name, tags)."""
    out = []
    for row in sess.execute("show trace").rows():
        name = row[0]
        depth = (len(name) - len(name.lstrip())) // 2
        out.append((depth, name.strip(), json.loads(row[4] or "{}")))
    return out


def _children(tree, parent: str) -> list[tuple[str, dict]]:
    """Names and tags of the spans directly under the first ``parent``."""
    at = next(i for i, (_d, n, _t) in enumerate(tree) if n == parent)
    depth = tree[at][0]
    out = []
    for d, n, t in tree[at + 1:]:
        if d <= depth:
            break
        if d == depth + 1:
            out.append((n, t))
    return out


def _counters(*names_and_labels) -> list[int]:
    return [qmetrics.counter_value(n, **lbl) for n, lbl in names_and_labels]


LOG = (("tx.commits", {"path": "two_phase"}), ("palf.fsyncs", {}),
       ("palf.acks", {}), ("palf.appends", {}),
       ("tx.rows_written", {"op": "insert"}), ("tx.rollbacks", {}),
       ("tx.commits", {}))


def test_show_trace_after_a_commit_holds_the_log_tree(db):
    s = db.session()
    keys = _keys(4)
    s.execute("begin")
    s.execute(_insert_o(keys))
    s.execute(_insert_l(keys))
    s.execute("commit")
    tree = _trace(s)
    assert [n for d, n, _t in tree if d == 1] == \
        ["parse", "admission", "virtuals", "tx.commit"]
    commit = dict(tree[[n for _d, n, _t in tree].index("tx.commit")][2])
    assert commit["path"] == "two_phase" and commit["participants"] == 2
    assert commit["rows"] == 4 + 12 and commit["redo_bytes"] > 0
    under_commit = _children(tree, "tx.commit")
    assert [n for n, _t in under_commit] == \
        ["tx.log_encode", "palf.append", "tx.apply"]
    encode, append, apply = (t for _n, t in under_commit)
    # 16 redo records, two prepares, one commit record
    assert encode["records"] == 19 and encode["bytes"] == commit["redo_bytes"]
    assert (append["entries"], append["acks"], append["quorum"],
            append["replicas"]) == (19, 3, 2, 3)
    assert "elected" not in append
    assert apply == {"tables": 2, "keys": 16}
    under_append = _children(tree, "palf.append")
    persists = [t for n, t in under_append if n == "palf.persist"]
    assert len(persists) == 3
    assert sorted(t["replica"] for t in persists) == [1, 2, 3]
    assert sorted(t["role"] for t in persists) == \
        ["follower", "follower", "leader"]
    # every replica wrote the same encoded batch: payloads + 28 B a header
    assert {t["bytes"] for t in persists} == {append["bytes"] + 19 * 28}
    assert all(t["fsync_ns"] > 0 for t in persists)
    applies = [t for n, t in under_append if n == "palf.apply"]
    assert [t["entries"] for t in applies] == [19, 19, 19]


@pytest.mark.parametrize("n_tx", [1, 5])
def test_counters_after_n_two_table_transactions(db, n_tx):
    s = db.session()
    before = _counters(*LOG)
    fsync_ns = qmetrics.counter_value("palf.fsync_ns")
    log_bytes = qmetrics.counter_value("palf.append_bytes")
    redo = qmetrics.counter_value("tx.redo_bytes")
    sent = 0
    for _ in range(n_tx):
        keys = _keys(7)
        s.execute("begin")
        s.execute(_insert_o(keys))
        s.execute(_insert_l(keys))
        s.execute("commit")
        sent += 7 + 21
    got = [a - b for a, b in zip(_counters(*LOG), before)]
    assert got == [n_tx, 3 * n_tx, 3 * n_tx, n_tx, sent, 0, n_tx]
    assert qmetrics.counter_value("palf.fsync_ns") > fsync_ns
    # three replicas wrote every record, each under a 28-byte header
    redo = qmetrics.counter_value("tx.redo_bytes") - redo
    assert qmetrics.counter_value("palf.append_bytes") - log_bytes == \
        3 * (redo + 28 * (sent + 3 * n_tx))


def test_the_operators_query_finds_the_series(db):
    s = db.session()
    s.execute(_insert_o(_keys(1)))
    r = s.execute("select stat_name, value from gv$sysstat where "
                  "stat_name like 'tx.%' or stat_name like 'palf.%'")
    names = {row[0] for row in r.rows()}
    assert {"tx.commits{path=one_phase}", "tx.rows_written{op=insert}",
            "tx.redo_bytes", "palf.fsyncs", "palf.fsync_ns", "palf.acks",
            "palf.appends", "palf.append_bytes"} <= names
    declared = qmetrics.declared()
    assert "palf.entries_appended" not in declared
    assert "palf.entries_applied" not in declared


@pytest.mark.parametrize("autocommit", [False, True])
def test_a_500_row_insert_opens_as_many_spans_as_a_5_row_one(db, autocommit):
    s = db.session()
    counts, writes = [], []
    for n in (5, 500):
        if not autocommit:
            s.execute("begin")
        s.execute(_insert_o(_keys(n)))
        tree = _trace(s)
        if not autocommit:
            s.execute("rollback")
        counts.append([name for _d, name, _t in tree])
        writes.append(next(t for _d, name, t in tree if name == "dml.write"))
    assert counts[0] == counts[1]
    assert ("tx.commit" in counts[0]) == autocommit
    assert [w["rows"] for w in writes] == [5, 500]
    for w in writes:
        parts = [w[k] for k in ("admit_ns", "index_ns", "memtable_ns",
                                "redo_ns")]
        assert all(isinstance(x, int) and x > 0 for x in parts)
    # the parts are what the rows cost: a hundred times the rows, more time
    assert sum(writes[1][k] for k in ("memtable_ns", "redo_ns")) > \
        sum(writes[0][k] for k in ("memtable_ns", "redo_ns"))


def test_a_pdml_statement_sums_its_workers_into_one_span(db):
    s = db.session()
    s.execute("alter system set pdml_min_rows = 64")
    try:
        before = qmetrics.counter_value("tx.rows_written", op="insert")
        s.execute("begin")
        s.execute(_insert_o(_keys(5)))
        serial = [name for _d, name, _t in _trace(s)]
        s.execute(_insert_o(_keys(256)))
        tree = _trace(s)
        s.execute("commit")
    finally:
        s.execute("alter system set pdml_min_rows = 8192")
    assert [name for _d, name, _t in tree] == serial
    write = next(t for _d, name, t in tree if name == "dml.write")
    assert write["rows"] == 256 and write["pdml_workers"] >= 2
    assert write["memtable_ns"] > 0 and write["admit_ns"] > 0
    assert qmetrics.counter_value("tx.rows_written", op="insert") \
        - before == 261


def test_index_entries_are_counted_apart(db):
    s = db.session()
    base = qmetrics.counter_value("tx.rows_written", op="insert")
    index = qmetrics.counter_value("tx.rows_written", op="index")
    s.execute("insert into ix values (1, 10, 0), (2, 20, 0), (3, 30, 0)")
    write = next(t for _d, name, t in _trace(s) if name == "dml.write")
    assert (write["rows"], write["index_rows"]) == (3, 3)
    assert write["index_ns"] > 0
    assert qmetrics.counter_value("tx.rows_written", op="insert") \
        - base == 3
    assert qmetrics.counter_value("tx.rows_written", op="index") \
        - index == 3
    # a changed indexed column: the old entry's tombstone and the new entry
    s.execute("update ix set a = 11 where k = 1")
    write = next(t for _d, name, t in _trace(s) if name == "dml.write")
    assert (write["kind"], write["rows"], write["index_rows"]) == \
        ("update", 1, 2)


def test_delete_in_on_the_candidate_path_and_on_the_full_table(db):
    s = db.session()
    s.execute("delete from big where k in (5, 6, 7)")
    tree = _trace(s)
    match = next(t for _d, name, t in tree if name == "dml.match")
    assert match == {"table": "big", "rows": 3, "full_table": 0}
    leaves = _children(tree, "dml.match")
    assert [n for n, _t in leaves] == \
        ["dml.bind", "dml.candidates", "dml.predicate", "materialize",
         "dml.rows"]
    cand = dict(leaves)["dml.candidates"]
    assert cand["path"] == "primary" and cand["chunks"] >= 1
    assert 3 <= cand["rows"] <= 65_536
    assert dict(leaves)["dml.rows"] == {"rows": 3}
    write = next(t for _d, name, t in tree if name == "dml.write")
    assert (write["kind"], write["rows"]) == ("delete", 3)

    # no range on a key column: the whole table at the snapshot
    s.execute("delete from big where v = 49")
    tree = _trace(s)
    match = next(t for _d, name, t in tree if name == "dml.match")
    assert match["full_table"] == 1 and match["rows"] >= 1
    assert dict(_children(tree, "dml.match"))["dml.candidates"]["path"] \
        == "none"


def _audit(sess, prefix: str) -> dict:
    r = sess.execute("select * from gv$sql_audit")
    i = r.names.index("sql")
    rows = [dict(zip(r.names, row)) for row in r.rows()
            if row[i].startswith(prefix)]
    return rows[-1]


def test_audit_phases_of_an_insert_and_its_commit_sum_to_elapsed(db):
    s = db.session()
    shares = []
    for _attempt in range(3):       # a busy machine may stall one
        s.execute("begin")
        s.execute(_insert_o(_keys(500)))
        s.execute("commit")
        ins, com = _audit(s, "insert into o values"), _audit(s, "commit")
        for row in (ins, com):
            owned = sum(row[c] for c in PHASE_COLUMNS)
            assert owned + row["other_s"] == \
                pytest.approx(row["elapsed_s"], rel=1e-6)
        assert ins["dml_s"] > 0 and ins["parse_s"] > 0
        assert ins["tx_commit_s"] == ins["log_sync_s"] == 0
        assert com["tx_commit_s"] > 0 and com["log_sync_s"] > 0
        assert com["dml_s"] == com["freeze_s"] == 0
        shares.append(max(ins["other_s"] / ins["elapsed_s"],
                          com["other_s"] / com["elapsed_s"]))
        if shares[-1] < 0.10:
            break
    assert min(shares) < 0.10, shares
    model = {r[1]: r[2] for r in s.execute(
        "select tenant, phase, seconds from gv$time_model").rows()}
    assert all(p in model for p in WRITE_PHASES)
    assert model["dml_s"] > 0 and model["log_sync_s"] > 0


def test_a_rolled_back_transaction_counts_and_syncs_nothing(db):
    s = db.session()
    before = _counters(*LOG)
    s.execute("begin")
    s.execute(_insert_o(_keys(9)))
    s.execute("rollback")
    got = [a - b for a, b in zip(_counters(*LOG), before)]
    # rows were written (and taken back); nothing reached the log
    assert got == [0, 0, 0, 0, 9, 1, 0]
    assert s.execute("select count(*) from o where c = 'c%d'"
                     % (_next_key[0] - 1)).rows()[0][0] == 0


@pytest.mark.parametrize("statement, path", [
    ("insert", "one_phase"), ("lock", "empty"), ("xa", "xa")])
def test_every_commit_path_is_counted_under_its_name(db, statement, path):
    s = db.session()
    before = qmetrics.counter_value("tx.commits", path=path)
    if statement == "insert":
        s.execute(_insert_o(_keys(1)))
    elif statement == "lock":
        s.execute("lock tables o read")
        s.execute("unlock tables")
    else:
        xid = f"w{_next_key[0]}"
        s.execute(f"xa start '{xid}'")
        s.execute(_insert_o(_keys(2)))
        s.execute(f"xa end '{xid}'")
        s.execute(f"xa prepare '{xid}'")
        prepare = next(t for _d, n, t in _trace(s) if n == "tx.commit")
        assert prepare["path"] == "xa_prepare" and prepare["rows"] == 2
        s.execute(f"xa commit '{xid}'")
    assert qmetrics.counter_value("tx.commits", path=path) - before == 1
    commit = next(t for _d, n, t in _trace(s) if n == "tx.commit")
    assert commit["path"] == path


def test_a_freeze_in_the_foreground_is_a_span_and_a_phase(db):
    s = db.session()
    s.execute("create table fz (k int primary key, v int)")
    s.execute("alter system set memstore_limit_rows = 8")
    try:
        s.execute("insert into fz values " + ", ".join(
            f"({k}, {k})" for k in range(12)))
        tree = _trace(s)
    finally:
        s.execute("alter system set memstore_limit_rows = 1000000")
    freeze = next(t for _d, n, t in tree if n == "storage.freeze")
    assert freeze == {"table": "fz", "rows": 12, "l0_segments": 1,
                      "compacted": 0}
    assert _audit(s, "insert into fz values")["freeze_s"] > 0


def test_parse_carries_its_bytes(db):
    s = db.session()
    before = qmetrics.counter_value("sql.parse_bytes")
    sql = _insert_o(_keys(3))
    s.execute(sql)
    parse = next(t for _d, n, t in _trace(s) if n == "parse")
    assert parse == {"bytes": len(sql)}
    # ``show trace`` itself is a statement of ten bytes
    assert qmetrics.counter_value("sql.parse_bytes") - before == \
        len(sql) + len("show trace")


def test_a_read_opens_the_spans_it_always_did(db):
    """Nothing was added to a read's path: no write span, no new phase."""
    s = db.session()
    s.execute("select count(*) from o").rows()
    names = {n for _d, n, _t in _trace(s)}
    assert not any(n.startswith(("dml.", "tx.", "palf.", "storage.freeze"))
                   for n in names)
    row = _audit(s, "select count(*) from o")
    assert all(row[p] == 0 for p in WRITE_PHASES)
