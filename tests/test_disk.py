"""Disk-pressure plane: write-error fault injection, crash-safe
unwind, per-surface budgets, and read-only degradation
(net/faults.py disk errno rules, server/diskmgr.py, the durable
writers in palf/log.py / storage/engine.py / storage/tmpfile.py /
server/backup.py).

≙ the reference's errsim disk-error suites (ENOSPC/EIO injection in
the log engine and sstable writers) plus the log-disk guard tests:
``log_disk_utilization_threshold`` crossing → checkpoint + recycle
reclaim → tenant read-only → auto-exit.  Every fault is seeded and
one-shot; every faulted surface is followed by a restart/reopen that
must land on the unfaulted oracle state (no torn artifacts).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from oceanbase_tpu.catalog import ColumnDef, TableDef
from oceanbase_tpu.net.faults import FaultPlane
from oceanbase_tpu.palf.log import PalfReplica
from oceanbase_tpu.server import Database
from oceanbase_tpu.server.diskmgr import (
    DiskFull,
    DiskIOError,
    DiskManager,
    SpillBudgetExceeded,
    TenantReadOnly,
)
from oceanbase_tpu.datatypes import SqlType
from oceanbase_tpu.storage.engine import StorageEngine
from oceanbase_tpu.storage.tmpfile import TempFileStore

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _tdef(name="t"):
    return TableDef(name, [ColumnDef("k", SqlType.int_()),
                           ColumnDef("v", SqlType.int_())],
                    primary_key=["k"])


def _du(paths):
    total = 0
    for root in paths:
        if os.path.isfile(root):
            total += os.path.getsize(root)
            continue
        for dirpath, _dirs, files in os.walk(root):
            for f in files:
                try:
                    total += os.path.getsize(os.path.join(dirpath, f))
                except OSError:
                    pass
    return total


def _leader(tmp_path, n_entries=0):
    r = PalfReplica(0, log_dir=str(tmp_path / "wal"))
    r.role = "leader"
    r.current_term = 1
    if n_entries:
        r.leader_append([f"e{i}".encode() for i in range(n_entries)])
    return r


def _cfg(**kw):
    cfg = {"log_disk_limit_bytes": 0, "data_disk_limit_bytes": 0,
           "temporary_file_max_disk_size": 0,
           "log_disk_utilization_threshold": 80}
    cfg.update(kw)
    return cfg


# ---------------------------------------------------------------------------
# FaultPlane: the disk errno family
# ---------------------------------------------------------------------------


def test_disk_errno_rules_validate_and_scope():
    fp = FaultPlane(seed=0)
    # errno actions live on the disk plane only
    with pytest.raises(ValueError):
        fp.inject("send", "enospc")
    with pytest.raises(ValueError):
        fp.disk("enospc", kind="nonsense")
    # kind scoping: a wal rule never fires for segment writes
    fp.disk("enospc", kind="wal")
    assert fp.check_write("segment", "/x") is None
    with pytest.raises(OSError) as ei:
        fp.check_write("wal", "/x")
    import errno as _errno

    assert ei.value.errno == _errno.ENOSPC
    # one-shot by default: the budget is spent
    assert fp.check_write("wal", "/x") is None


def test_disk_partial_rule_is_seeded_and_bounded():
    fp = FaultPlane(seed=7)
    fp.disk("partial", kind="wal", seed=7)
    cut = None
    with pytest.raises(OSError):
        # the writer persists cut bytes then raises; without nbytes the
        # plane degrades to a plain ENOSPC raise
        fp.check_write("wal", "/x")
    fp2 = FaultPlane(seed=7)
    fp2.disk("partial", kind="wal", seed=7)
    cut = fp2.check_write("wal", "/x", nbytes=1000)
    assert cut is not None and 1 <= cut < 1000
    fp3 = FaultPlane(seed=7)
    fp3.disk("partial", kind="wal", seed=7)
    assert fp3.check_write("wal", "/x", nbytes=1000) == cut  # seeded


# ---------------------------------------------------------------------------
# WAL (palf/log.py::_persist): typed errors + crash-safe unwind
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("action,exc_type", [
    ("enospc", DiskFull), ("eio", DiskIOError)])
def test_wal_errno_fault_typed_and_unwound(tmp_path, action, exc_type):
    r = _leader(tmp_path, n_entries=4)
    pre_size = os.path.getsize(r._log_path())
    pre_last = r.last_lsn()
    fp = FaultPlane(seed=1)
    fp.disk(action, kind="wal")
    r.faults = fp
    with pytest.raises(exc_type):
        r.leader_append([b"doomed"])
    # memory did not run ahead of the failed durable append
    assert r.last_lsn() == pre_last
    assert os.path.getsize(r._log_path()) == pre_size
    # the one-shot budget is spent: the next append goes through
    r.leader_append([b"after"])
    r.close()
    r2 = PalfReplica(0, log_dir=str(tmp_path / "wal"))
    assert r2.last_lsn() == pre_last + 1
    assert r2.entries[-1].payload == b"after"
    r2.close()


def test_wal_partial_write_truncates_back_no_torn_entry(tmp_path):
    r = _leader(tmp_path, n_entries=3)
    pre_size = os.path.getsize(r._log_path())
    oracle = [(e.term, e.lsn, e.payload) for e in r.entries]
    fp = FaultPlane(seed=5)
    fp.disk("partial", kind="wal", seed=5)
    r.faults = fp
    with pytest.raises(DiskFull):
        r.leader_append([b"x" * 512, b"y" * 512])
    # the torn half-batch was physically truncated back
    assert os.path.getsize(r._log_path()) == pre_size
    assert r.last_lsn() == 3
    r.close()
    # restart lands on the unfaulted oracle, and keeps working
    r2 = PalfReplica(0, log_dir=str(tmp_path / "wal"))
    assert [(e.term, e.lsn, e.payload) for e in r2.entries] == oracle
    r2.role, r2.current_term = "leader", 1
    r2.leader_append([b"clean"])
    assert r2.last_lsn() == 4
    r2.close()


# ---------------------------------------------------------------------------
# slog / manifest / segment (storage/engine.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("action,exc_type", [
    ("enospc", DiskFull), ("eio", DiskIOError)])
def test_slog_fault_typed_and_restart_clean(tmp_path, action, exc_type):
    root = str(tmp_path / "db")
    eng = StorageEngine(root)
    eng.create_table(_tdef("t1"))
    fp = FaultPlane(seed=2)
    fp.disk(action, kind="slog")
    eng.faults = fp
    with pytest.raises(exc_type):
        eng.create_table(_tdef("t2"))
    # the slog carries no torn record: reopen replays cleanly and sees
    # only the durable table
    eng2 = StorageEngine(root)
    assert "t1" in eng2.tables and "t2" not in eng2.tables
    eng2.create_table(_tdef("t2"))
    eng3 = StorageEngine(root)
    assert set(eng3.tables) >= {"t1", "t2"}


def test_manifest_fault_keeps_previous_generation(tmp_path):
    root = str(tmp_path / "db")
    eng = StorageEngine(root)
    eng.create_table(_tdef())
    eng.bulk_load("t", {"k": np.arange(50), "v": np.arange(50) * 2})
    eng.checkpoint()  # generation 1
    eng.create_table(_tdef("u"))
    fp = FaultPlane(seed=3)
    fp.disk("enospc", kind="manifest")
    eng.faults = fp
    with pytest.raises(DiskFull):
        eng.checkpoint()
    # no torn tmp left behind; the previous generation is intact and
    # the slog (NOT truncated by the failed checkpoint) still carries u
    assert not os.path.exists(eng._manifest_path() + ".tmp")
    eng2 = StorageEngine(root)
    assert set(eng2.tables) >= {"t", "u"}
    a, _ = eng2.tables["t"].tablet.snapshot_arrays(snapshot=10)
    assert len(a["k"]) == 50
    # the budget is spent: the retry checkpoint publishes atomically
    eng.checkpoint()
    eng3 = StorageEngine(root)
    assert set(eng3.tables) >= {"t", "u"}


@pytest.mark.parametrize("action,exc_type", [
    ("enospc", DiskFull), ("eio", DiskIOError)])
def test_segment_fault_no_torn_file(tmp_path, action, exc_type):
    root = str(tmp_path / "db")
    eng = StorageEngine(root)
    eng.create_table(_tdef())
    eng.bulk_load("t", {"k": np.arange(100), "v": np.arange(100)})
    eng.checkpoint()
    ts = eng.tables["t"]
    ts.tablet.write((500,), "insert", {"k": 500, "v": 1}, tx_id=1)
    ts.tablet.commit(1, 5, [(500,)])
    fp = FaultPlane(seed=4)
    fp.disk(action, kind="segment")
    eng.faults = fp
    with pytest.raises(exc_type):
        eng.freeze_and_flush("t", snapshot=10)
    segdir = os.path.join(root, "segments")
    assert not [f for f in os.listdir(segdir) if f.endswith(".tmp")]
    # the durable prefix reopens oracle-identical
    eng2 = StorageEngine(root)
    a, _ = eng2.tables["t"].tablet.snapshot_arrays(snapshot=10)
    assert len(a["k"]) == 100


def test_segment_fault_pending_retry_persists(tmp_path):
    """A failed segment save parks the seg (memory keeps serving it)
    and the NEXT flush/checkpoint re-persists — the manifest never
    references a file that does not exist."""
    root = str(tmp_path / "db")
    eng = StorageEngine(root)
    eng.create_table(_tdef())
    ts = eng.tables["t"]
    ts.tablet.write((1,), "insert", {"k": 1, "v": 10}, tx_id=1)
    ts.tablet.commit(1, 5, [(1,)])
    fp = FaultPlane(seed=11)
    fp.disk("enospc", kind="segment")
    eng.faults = fp
    with pytest.raises(DiskFull):
        eng.freeze_and_flush("t", snapshot=10)
    assert eng._pending_segs  # parked, not lost
    # the live engine still serves the row (memory is authoritative)
    a, _ = ts.tablet.snapshot_arrays(snapshot=10)
    assert list(a["k"]) == [1]
    # checkpoint drains the pending persist first, then publishes a
    # manifest that references only on-disk files
    eng.checkpoint()
    assert not eng._pending_segs
    eng2 = StorageEngine(root)
    a, _ = eng2.tables["t"].tablet.snapshot_arrays(snapshot=10)
    assert list(a["k"]) == [1]


# ---------------------------------------------------------------------------
# spill (storage/tmpfile.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("action,exc_type", [
    ("enospc", DiskFull), ("eio", DiskIOError)])
def test_spill_fault_typed_no_residue(tmp_path, action, exc_type):
    fp = FaultPlane(seed=6)
    fp.disk(action, kind="spill")
    with TempFileStore(str(tmp_path / "spill"), faults=fp) as store:
        rid = store.new_run()
        arrays = {"x": np.arange(64, dtype=np.int64)}
        with pytest.raises(exc_type):
            store.append_chunk(rid, arrays)
        # no chunk (or tmp) published for the failed append
        assert store.run(rid).n_chunks == 0
        assert not os.listdir(store._chunk_dir(rid))
        # budget spent: spilling continues
        store.append_chunk(rid, arrays)
        (got, _), = list(store.read_chunks(rid))
        np.testing.assert_array_equal(got["x"], arrays["x"])


def test_spill_budget_kills_statement_only(tmp_path):
    dm = DiskManager(_cfg(temporary_file_max_disk_size=1), paths={},
                     poll_interval_s=0.0)
    big = {"x": np.random.default_rng(0).integers(0, 1 << 30, 4096)}
    with TempFileStore(str(tmp_path / "spill"), budget=dm,
                       label="stmt-1") as store:
        rid = store.new_run()
        with pytest.raises(SpillBudgetExceeded):
            store.append_chunk(rid, big)
        # the rejected chunk left no file AND no phantom accounting
        assert not os.listdir(store._chunk_dir(rid))
        assert dm.usage("spill") == 0
        assert dm.spill_rejections == 1
    # the durable surface was never involved
    assert not dm.read_only
    dm.admit_write()  # writes still admitted


def test_spill_accounting_admit_release_and_stats(tmp_path):
    dm = DiskManager(_cfg(temporary_file_max_disk_size=1 << 20), paths={})
    arrays = {"x": np.arange(256, dtype=np.int64)}
    with TempFileStore(str(tmp_path / "s"), budget=dm,
                       label="select heavy") as store:
        rid = store.new_run()
        store.append_chunk(rid, arrays)
        used = dm.usage("spill")
        assert used > 0
        rows = dm.stats(tenant="sys")
        stmt = [r for r in rows if r["surface"] == "spill_stmt"]
        assert stmt and stmt[0]["detail"] == "select heavy"
        assert stmt[0]["used_bytes"] == used
        store.close_run(rid)
        assert dm.usage("spill") == 0


# ---------------------------------------------------------------------------
# backup (server/backup.py)
# ---------------------------------------------------------------------------


def test_backup_enospc_typed_and_retry_restores(tmp_path):
    from oceanbase_tpu.server.backup import full_backup, restore_chain

    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i * 3})" for i in range(500)))
    fp = FaultPlane(seed=8)
    fp.disk("enospc", kind="backup")
    db.faults = fp
    dest = str(tmp_path / "b0")
    with pytest.raises(DiskFull):
        full_backup(db, dest)
    assert not os.path.exists(dest)  # no half backup left behind
    full = full_backup(db, dest)  # budget spent: retry succeeds
    db.close()
    target = str(tmp_path / "restored")
    restore_chain(full, target)
    db2 = Database(target)
    got = db2.session().execute("select count(*), sum(v) from t").rows()
    assert got[0] == (500, sum(i * 3 for i in range(500)))
    db2.close()


def test_wal_archive_eio_typed(tmp_path):
    from oceanbase_tpu.server.backup import archive_wal

    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (k int primary key)")
    s.execute("insert into t values (1), (2)")
    fp = FaultPlane(seed=9)
    fp.disk("eio", kind="backup")
    db.faults = fp
    with pytest.raises(DiskIOError):
        archive_wal(db, str(tmp_path / "arch"))
    archive_wal(db, str(tmp_path / "arch"))  # budget spent
    db.close()


# ---------------------------------------------------------------------------
# DiskManager: budgets, reclaim, read-only enter/auto-exit
# ---------------------------------------------------------------------------


def test_diskmgr_readonly_enter_and_autoexit(tmp_path):
    d = str(tmp_path / "log")
    os.makedirs(d)
    f = os.path.join(d, "wal.log")
    with open(f, "wb") as fh:
        fh.write(b"x" * 1000)
    events = []
    cfg = _cfg(log_disk_limit_bytes=500)
    dm = DiskManager(cfg, paths={"log": [d]},
                     reclaim_cb=lambda: events.append("reclaim"),
                     on_readonly=lambda s: events.append(f"ro:{s}"),
                     on_exit_readonly=lambda: events.append("exit"),
                     poll_interval_s=0.0, reclaim_backoff_s=0.0)
    dm.poll(force=True)
    # reclaim was tried first; it freed nothing, so read-only followed
    assert events[:2] == ["reclaim", "ro:log"]
    assert dm.read_only and dm.state("log") == "readonly"
    with pytest.raises(TenantReadOnly):
        dm.admit_write()
    assert dm.write_rejections == 1
    # space frees up -> the next poll auto-exits
    with open(f, "wb") as fh:
        fh.write(b"x" * 100)
    dm.poll(force=True)
    assert not dm.read_only and "exit" in events
    dm.admit_write()


def test_diskmgr_reclaim_avoids_readonly(tmp_path):
    d = str(tmp_path / "log")
    os.makedirs(d)
    f = os.path.join(d, "wal.log")
    with open(f, "wb") as fh:
        fh.write(b"x" * 900)

    def reclaim():  # the aggressive checkpoint + WAL recycle analog
        with open(f, "wb") as fh:
            fh.write(b"x" * 100)

    dm = DiskManager(_cfg(log_disk_limit_bytes=1000),
                     paths={"log": [d]}, reclaim_cb=reclaim,
                     poll_interval_s=0.0, reclaim_backoff_s=0.0)
    dm.poll(force=True)
    assert dm.reclaims == 1
    assert not dm.read_only
    dm.admit_write()


def test_diskmgr_data_surface_readonly(tmp_path):
    d = str(tmp_path / "data")
    os.makedirs(d)
    with open(os.path.join(d, "seg.npz"), "wb") as fh:
        fh.write(b"x" * 400)
    cfg = _cfg(data_disk_limit_bytes=300)
    dm = DiskManager(cfg, paths={"data": [d]}, poll_interval_s=0.0)
    dm.poll(force=True)
    assert dm.read_only and dm.readonly_surface == "data"
    cfg["data_disk_limit_bytes"] = 10_000
    dm.poll(force=True)
    assert not dm.read_only


# ---------------------------------------------------------------------------
# tenant-level degradation (server/tenant.py wiring + gv$disk)
# ---------------------------------------------------------------------------


def test_tenant_log_budget_readonly_reads_serve_then_autoexit(tmp_path):
    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i})" for i in range(200)))
    dm = db.tenant("sys").diskmgr
    s.execute("alter system set log_disk_limit_bytes = 10")
    dm.poll(force=True)
    # reclaim (checkpoint + recycle) ran first but 10 bytes is
    # unreachable -> read-only
    assert dm.reclaims >= 1 and dm.read_only
    with pytest.raises(TenantReadOnly):
        s.execute("insert into t values (9001, 1)")
    # reads keep serving in read-only (writes shed, not the tenant)
    assert s.execute("select count(*) from t").rows()[0][0] == 200
    rows = s.execute(
        "select surface, state from gv$disk"
        " where surface = 'log'").rows()
    assert rows == [("log", "readonly")]
    # the reclaim actually shrank the wal (recycle dropped the prefix)
    assert _du(dm.paths["log"]) < 10_000
    s.execute("alter system set log_disk_limit_bytes = 0")
    dm.poll(force=True)
    assert not dm.read_only and dm.readonly_exits >= 1
    s.execute("insert into t values (9001, 1)")
    assert s.execute("select count(*) from t").rows()[0][0] == 201
    db.close()
    # restart after the whole episode is oracle-identical
    db2 = Database(str(tmp_path / "db"))
    assert db2.session().execute(
        "select count(*) from t").rows()[0][0] == 201
    db2.close()


def test_gv_disk_matches_du_within_5pct(tmp_path):
    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {i})" for i in range(500)))
    db.checkpoint()
    s.execute("alter system set log_disk_limit_bytes = 1073741824")
    s.execute("alter system set data_disk_limit_bytes = 1073741824")
    dm = db.tenant("sys").diskmgr
    rows = s.execute(
        "select surface, used_bytes, limit_bytes, state from gv$disk"
        " order by surface").rows()
    by_surface = {r[0]: r for r in rows}
    for surface in ("log", "data"):
        du = _du(dm.paths[surface])
        used = by_surface[surface][1]
        assert abs(used - du) <= max(1, du) * 0.05, (surface, used, du)
        assert by_surface[surface][3] == "ok"
    assert by_surface["log"][2] == 1 << 30
    db.close()


def test_statement_spill_budget_via_sql(tmp_path):
    """Spill exhaustion kills ONLY the statement: the session keeps
    working and the durable surface never degrades."""
    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, {(i * 7919) % 100000})" for i in range(3000)))
    s.execute("alter system set sql_work_area_rows = 100")
    s.execute("alter system set temporary_file_max_disk_size = 1")
    with pytest.raises(SpillBudgetExceeded):
        s.execute("select k, v from t order by v, k")
    dm = db.tenant("sys").diskmgr
    assert not dm.read_only
    assert dm.usage("spill") == 0  # failed statement left no residue
    # the session and durable surface keep working
    s.execute("insert into t values (9001, 1)")
    assert s.execute("select count(*) from t").rows()[0][0] == 3001
    # with a sane budget the same statement completes spilled
    s.execute("alter system set temporary_file_max_disk_size = 1073741824")
    got = s.execute("select k, v from t order by v, k").rows()
    assert len(got) == 3001
    assert got == sorted(got, key=lambda r: (r[1], r[0]))
    db.close()


# ---------------------------------------------------------------------------
# WAL recycle + restart identity (reclaim correctness)
# ---------------------------------------------------------------------------


def test_wal_recycle_restart_identical_and_smaller(tmp_path):
    r = _leader(tmp_path)
    r.leader_append([f"p{i}".encode() for i in range(40)])
    r.advance_commit(40)
    assert r.applied_lsn == 40
    before = os.path.getsize(r._log_path())
    freed = r.recycle(25)
    assert freed > 0
    after = os.path.getsize(r._log_path())
    assert after < before
    assert r.base_lsn == 25 and r.last_lsn() == 40
    oracle = [(e.term, e.lsn, e.payload) for e in r.entries]
    r.close()
    r2 = PalfReplica(0, log_dir=str(tmp_path / "wal"))
    assert (r2.base_lsn, r2.base_term) == (25, 1)
    assert r2.committed_lsn == 25 and r2.applied_lsn == 25
    assert [(e.term, e.lsn, e.payload) for e in r2.entries] == oracle
    # recycled history is unservable (rebuild plane); the suffix serves
    assert r2.entries_from(10) is None
    got = r2.entries_from(25)
    assert [e.lsn for e in got] == list(range(26, 41))
    r2.close()
