"""Secondary indexes: DDL, transactional maintenance, uniqueness,
recovery, and the pruned point-lookup path.

Reference behaviors mirrored: index tables keyed by (index cols + pk)
maintained in the same transaction as the base row (src/storage DML
index-write path), MySQL unique-index NULL semantics, index survival
across restart (schema + backfilled segments persisted)."""

import numpy as np
import pytest

from oceanbase_tpu.server.database import Database
from oceanbase_tpu.tx.errors import DuplicateKey


def _mk(tmp_path, name="db"):
    return Database(str(tmp_path / name))


def test_create_index_backfill_and_lookup(tmp_path):
    db = _mk(tmp_path)
    s = db.session()
    s.execute("create table t (k int primary key, v int, w int)")
    for i in range(50):
        s.execute(f"insert into t values ({i}, {i % 7}, {i * 10})")
    s.execute("create index iv on t (v)")
    store = db.engine.tables["__idx__t__iv"]
    assert store.tablet.key_cols == ["v", "k"]
    # backfilled entries match the base table
    rows = s.execute("select k from t where v = 3 order by k").rows()
    assert [r[0] for r in rows] == [3, 10, 17, 24, 31, 38, 45]
    assert store.tablet.row_count_estimate() == 50
    db.close()


def test_index_maintained_by_dml(tmp_path):
    db = _mk(tmp_path)
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("create index iv on t (v)")
    s.execute("insert into t values (1, 10), (2, 20), (3, 10)")
    s.execute("update t set v = 99 where k = 2")
    s.execute("delete from t where k = 3")
    snap = db.tenant().tx.gts.current()
    store = db.engine.tables["__idx__t__iv"].tablet
    arrays, _ = store.snapshot_arrays(snap)
    live = sorted(zip(arrays["v"].tolist(), arrays["k"].tolist()))
    assert live == [(10, 1), (99, 2)]
    db.close()


def test_unique_index_rejects_duplicates(tmp_path):
    db = _mk(tmp_path)
    s = db.session()
    s.execute("create table t (k int primary key, email varchar(64))")
    s.execute("insert into t values (1, 'a@x'), (2, 'b@x')")
    s.execute("create unique index ue on t (email)")
    with pytest.raises(DuplicateKey):
        s.execute("insert into t values (3, 'a@x')")
    # NULLs never conflict (MySQL semantics)
    s.execute("insert into t values (4, null)")
    s.execute("insert into t values (5, null)")
    # updating into a conflict also rejected
    with pytest.raises(DuplicateKey):
        s.execute("update t set email = 'b@x' where k = 1")
    # the failed statements left no partial state
    assert s.execute("select count(*) from t").rows()[0][0] == 4
    db.close()


def test_create_unique_index_on_duplicate_data_fails(tmp_path):
    db = _mk(tmp_path)
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("insert into t values (1, 5), (2, 5)")
    with pytest.raises(DuplicateKey):
        s.execute("create unique index uv on t (v)")
    # failed creation leaves no index behind
    assert db.engine.tables["t"].tdef.indexes == [] or \
        all(ix.name != "uv" for ix in db.engine.tables["t"].tdef.indexes)
    db.close()


def test_index_survives_restart(tmp_path):
    root = str(tmp_path / "db")
    db = Database(root)
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("create index iv on t (v)")
    s.execute("insert into t values (1, 10), (2, 20)")
    db.checkpoint()
    s.execute("insert into t values (3, 30)")  # WAL-only at crash
    db.close()
    db2 = Database(root)
    td = db2.engine.tables["t"].tdef
    assert [ix.name for ix in td.indexes] == ["iv"]
    s2 = db2.session()
    s2.execute("insert into t values (4, 20)")
    snap = db2.tenant().tx.gts.current()
    store = db2.engine.tables["__idx__t__iv"].tablet
    arrays, _ = store.snapshot_arrays(snap)
    live = sorted(zip(arrays["v"].tolist(), arrays["k"].tolist()))
    assert live == [(10, 1), (20, 2), (20, 4), (30, 3)]
    db2.close()


def test_drop_index_and_guards(tmp_path):
    db = _mk(tmp_path)
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("create index iv on t (v)")
    with pytest.raises(ValueError):
        s.execute("alter table t drop column v")
    s.execute("drop index iv on t")
    assert "__idx__t__iv" not in db.engine.tables
    s.execute("alter table t drop column v")  # now allowed
    s.execute("drop index if exists iv on t")  # no error
    db.close()


def test_inline_index_specs_and_show_create(tmp_path):
    db = _mk(tmp_path)
    s = db.session()
    s.execute("create table t (k int primary key, v int, e varchar(10), "
              "index iv (v), unique key ue (e))")
    td = db.engine.tables["t"].tdef
    assert sorted(ix.name for ix in td.indexes) == ["iv", "ue"]
    text = s.execute("show create table t").rows()[0][1]
    assert "KEY iv (v)" in text and "UNIQUE KEY ue (e)" in text
    # SHOW TABLES hides index storage tables (virtual views do list)
    names = [r[0] for r in s.execute("show tables").rows()
             if r[0] not in db.virtual_tables.names()]
    assert names == ["t"]
    with pytest.raises(DuplicateKey):
        s.execute("insert into t values (1, 1, 'x'), (2, 2, 'x')")
    db.close()


def test_truncate_clears_indexes(tmp_path):
    db = _mk(tmp_path)
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("create unique index uv on t (v)")
    s.execute("insert into t values (1, 10)")
    s.execute("truncate table t")
    # the old entry must not block re-insertion of the same value
    s.execute("insert into t values (2, 10)")
    snap = db.tenant().tx.gts.current()
    store = db.engine.tables["__idx__t__uv"].tablet
    arrays, _ = store.snapshot_arrays(snap)
    assert sorted(zip(arrays["v"].tolist(), arrays["k"].tolist())) == \
        [(10, 2)]
    db.close()


def test_bulk_load_maintains_index(tmp_path):
    db = _mk(tmp_path)
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("create index iv on t (v)")
    db.engine.bulk_load("t", {"k": np.arange(100, dtype=np.int64),
                              "v": np.arange(100, dtype=np.int64) % 5},
                        version=db.tenant().tx.gts.current())
    db.tenant().catalog.invalidate("t")
    rows = s.execute("select count(*) from t where v = 2").rows()
    assert rows[0][0] == 20
    store = db.engine.tables["__idx__t__iv"].tablet
    assert store.row_count_estimate() == 100
    db.close()


def test_point_lookup_prunes_chunks(tmp_path):
    """Key-sorted segments + zone maps: a point get decodes only the
    chunks that can hold the key, not the whole segment."""
    from oceanbase_tpu.kv import KvTable
    from oceanbase_tpu.storage import segment as seg_mod

    db = _mk(tmp_path)
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    n = 50_000
    db.engine.bulk_load("t", {"k": np.arange(n, dtype=np.int64),
                              "v": np.arange(n, dtype=np.int64)},
                        version=db.tenant().tx.gts.current())
    # shrink chunks so one segment has many (bulk_load above used the
    # default 64k chunk; rebuild with small chunks to exercise pruning)
    tab = db.engine.tables["t"].tablet
    old = tab.segments[-1]
    a, v = old.decode()
    small = seg_mod.Segment.build(
        old.segment_id, old.level, a, old.types,
        {k: x for k, x in v.items() if x is not None},
        min_version=old.min_version, max_version=old.max_version,
        chunk_rows=4096)
    tab.segments[-1] = small
    calls = {"n": 0}
    orig = seg_mod.decode_column

    def counting(ec, out_dtype=None):
        calls["n"] += 1
        return orig(ec, out_dtype)

    seg_mod.decode_column = counting
    try:
        kv = KvTable(db.tenant(), "t")
        row = kv.get((12345,))
    finally:
        seg_mod.decode_column = orig
    assert row["v"] == 12345
    # one chunk x (2 cols + bookkeeping) decodes, not ~13 chunks' worth
    n_chunks = small.n_chunks
    assert n_chunks >= 12
    assert calls["n"] <= 6, f"decoded {calls['n']} chunks-worth"
    db.close()


def test_create_index_waits_for_inflight_tx(tmp_path):
    """Review finding: writes of a transaction live at CREATE INDEX time
    predate maintenance; the build must drain it before backfilling."""
    import threading
    import time as _t

    db = _mk(tmp_path)
    s1 = db.session()
    s2 = db.session()
    s1.execute("create table t (k int primary key, v int)")
    s1.execute("begin")
    s1.execute("insert into t values (1, 10)")

    done = {}

    def build():
        done["t0"] = _t.time()
        s2.execute("create index iv on t (v)")
        done["t1"] = _t.time()

    th = threading.Thread(target=build)
    th.start()
    _t.sleep(0.3)
    assert "t1" not in done  # still draining
    s1.execute("commit")
    th.join(timeout=10)
    assert "t1" in done
    # the drained transaction's row made it into the index
    rows = s1.execute("select k from t where v = 10").rows()
    assert rows == [(1,)]
    snap = db.tenant().tx.gts.current()
    store = db.engine.tables["__idx__t__iv"].tablet
    arrays, _ = store.snapshot_arrays(snap)
    assert sorted(zip(arrays["v"].tolist(), arrays["k"].tolist())) == \
        [(10, 1)]
    db.close()


def test_bulk_load_unique_checks_existing_rows(tmp_path):
    """Review finding: LOAD DATA must enforce unique indexes against
    already-committed rows, not only batch-locally."""
    db = _mk(tmp_path)
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("create unique index uv on t (v)")
    s.execute("insert into t values (1, 5)")
    with pytest.raises(Exception):
        db.engine.bulk_load(
            "t", {"k": np.array([2], dtype=np.int64),
                  "v": np.array([5], dtype=np.int64)},
            version=db.tenant().tx.gts.current())
    # re-loading the SAME row (same pk) is fine
    db.engine.bulk_load(
        "t", {"k": np.array([1], dtype=np.int64),
              "v": np.array([5], dtype=np.int64)},
        version=db.tenant().tx.gts.current())
    db.close()
