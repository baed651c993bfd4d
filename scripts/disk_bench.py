#!/usr/bin/env python
"""Disk-pressure bench: the disk plane's standing contract.

Three halves, one dtl_bench-style JSON line:

1. **Overhead** — a write+read workload timed with disk budgets OFF
   (all limits 0: the plane costs one monotonic read per write) vs ON
   (1 GiB limits: the interval-gated poll walks the surfaces while the
   workload runs).  Contract: <= 2% elapsed overhead.

2. **Seeded ENOSPC per surface** — one-shot errno injection on every
   durable surface (wal, slog, manifest, segment, spill, backup)
   through the REAL entry points (SQL insert/DDL, checkpoint, spilled
   query, full backup).  Contract per surface: the failure lands as the
   typed plane error (DiskFull — never a bare OSError), the retry
   succeeds once the budget is spent, and the restarted instance is
   oracle-identical (no torn artifacts).

3. **Inject -> degrade -> recover** — an unreachable log budget drops
   the tenant to read-only (after the reclaim round: aggressive
   checkpoint + WAL recycle); writes fail fast typed, reads keep
   serving, and lifting the budget auto-exits.  gv$disk used_bytes must
   track du within 5% throughout.

    python scripts/disk_bench.py            # BENCH_ROWS=4000 default
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


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


def _count(s):
    return s.execute("select count(*), sum(v) from t").rows()[0]


def workload_block(s, keys, n_writes=40):
    """One timed block: n_writes rows through the admitted write path
    (the choke point the budgets gate).  Reads are NOT timed here —
    they bypass the gate by design, and their XLA recompiles at bucket
    boundaries would drown a 2% write-side signal in compile noise."""
    base = keys[0]
    vals = ", ".join(f"({base + i}, {(base + i) % 997})"
                     for i in range(n_writes))
    s.execute(f"insert into t values {vals}")
    keys[0] = base + n_writes


def _set_limits(s, lim):
    for knob in ("log_disk_limit_bytes", "data_disk_limit_bytes",
                 "temporary_file_max_disk_size"):
        s.execute(f"alter system set {knob} = {lim}")


def bench_overhead(s, keys, blocks=24):
    """Alternating off/on blocks; the verdict compares MEDIAN block
    times (a memtable flush or GC spike must not decide the gate)."""
    import statistics

    off, on = [], []
    for b in range(blocks):
        order = (False, True) if b % 2 == 0 else (True, False)
        for mode in order:
            _set_limits(s, (1 << 30) if mode else 0)
            t0 = time.monotonic()
            for _ in range(4):
                workload_block(s, keys)
            (on if mode else off).append(time.monotonic() - t0)
    _set_limits(s, 0)
    med_off = statistics.median(off)
    med_on = statistics.median(on)
    overhead = (med_on - med_off) / med_off if med_off else 0.0
    return {"off_s": round(sum(off), 3), "on_s": round(sum(on), 3),
            "median_off_s": round(med_off, 4),
            "median_on_s": round(med_on, 4),
            "overhead_pct": round(overhead * 100, 2),
            "pass": overhead <= 0.02}


def bench_surfaces(db, s, keys, tmp):
    """One-shot seeded ENOSPC per durable surface, through the real
    entry points; each must surface typed and recover on retry."""
    from oceanbase_tpu.net.faults import FaultPlane
    from oceanbase_tpu.server.backup import full_backup
    from oceanbase_tpu.server.diskmgr import DiskFull

    tenant = db.tenant("sys")
    local = tenant.wal.replicas[tenant.wal.leader_id]
    results = []

    def trial(surface, arm, fire, recover):
        plane = FaultPlane(seed=1000 + len(results))
        plane.disk("enospc", kind=surface)
        arm(plane)
        t0 = time.monotonic()
        typed = retried = False
        err = ""
        try:
            fire()
        except DiskFull:
            typed = True
        except Exception as exc:  # wrong type = torn contract
            err = f"{type(exc).__name__}: {exc}"
        if typed:
            try:
                recover()
                retried = True
            except Exception as exc:
                err = f"retry failed: {type(exc).__name__}: {exc}"
        arm(None)
        results.append({
            "surface": surface, "typed_error": typed,
            "recovered": retried, "error": err,
            "round_trip_s": round(time.monotonic() - t0, 3),
            "pass": typed and retried})

    def _ins():
        k = keys[0]
        keys[0] += 1
        s.execute(f"insert into t values ({k}, {k % 997})")

    def _arm_wal(p):
        local.faults = p

    def _arm_eng(p):
        tenant.engine.faults = p

    def _arm_db(p):
        db.faults = p

    trial("wal", _arm_wal, _ins, _ins)
    trial("slog", _arm_eng,
          lambda: s.execute("create table slog_probe (k int primary key)"),
          lambda: s.execute("create table slog_probe (k int primary key)"))
    _ins()  # memtable data so the next checkpoint flushes a segment
    trial("segment", _arm_eng, db.checkpoint, db.checkpoint)
    trial("manifest", _arm_eng, db.checkpoint, db.checkpoint)
    s.execute("alter system set sql_work_area_rows = 100")
    spill_q = "select k, v from t order by v, k"
    trial("spill", _arm_db,
          lambda: s.execute(spill_q), lambda: s.execute(spill_q))
    s.execute("alter system set sql_work_area_rows = 1000000")
    bdir = os.path.join(tmp, "backup")

    def _backup():
        shutil.rmtree(bdir, ignore_errors=True)
        full_backup(db, bdir)

    trial("backup", _arm_db, _backup, _backup)
    return {"surfaces": results,
            "pass": all(r["pass"] for r in results)}


def bench_degrade(db, s):
    """Inject (unreachable log budget) -> degrade (read-only, reads
    serve) -> recover (auto-exit), with gv$disk tracking du <= 5%."""
    from oceanbase_tpu.server.diskmgr import TenantReadOnly

    dm = db.tenant("sys").diskmgr
    out = {}
    t0 = time.monotonic()
    s.execute("alter system set log_disk_limit_bytes = 10")
    dm.poll(force=True)
    out["reclaims"] = dm.reclaims
    out["entered_readonly"] = dm.read_only
    rejected = False
    try:
        s.execute("insert into t values (99999991, 1)")
    except TenantReadOnly:
        rejected = True
    out["write_rejected_typed"] = rejected
    pre = _count(s)
    out["reads_serve_in_readonly"] = pre[0] > 0
    rows = s.execute("select surface, used_bytes, state from gv$disk"
                     " where surface = 'log'").rows()
    du = _du(dm.paths["log"])
    out["gv_disk_state"] = rows[0][2] if rows else ""
    out["gv_vs_du_pct"] = round(
        abs(rows[0][1] - du) / max(1, du) * 100, 2) if rows else 100.0
    s.execute("alter system set log_disk_limit_bytes = 0")
    dm.poll(force=True)
    out["exited_readonly"] = not dm.read_only
    recovered = False
    try:
        s.execute("insert into t values (99999991, 1)")
        recovered = True
    except Exception:
        pass
    out["writes_resume"] = recovered
    out["round_trip_s"] = round(time.monotonic() - t0, 3)
    out["pass"] = bool(
        out["entered_readonly"] and out["write_rejected_typed"]
        and out["reads_serve_in_readonly"] and out["exited_readonly"]
        and out["writes_resume"] and out["gv_disk_state"] == "readonly"
        and out["gv_vs_du_pct"] <= 5.0 and out["reclaims"] >= 1)
    return out


def main():
    from oceanbase_tpu.server import Database

    n_rows = int(os.environ.get("BENCH_ROWS", "4000"))
    tmp = tempfile.mkdtemp(prefix="diskbench_")
    out = {"metric": "disk_bench", "rows": n_rows}
    db = None
    try:
        db = Database(os.path.join(tmp, "db"))
        s = db.session()
        s.execute("create table t (k int primary key, v int)")
        for lo in range(0, n_rows, 1000):
            hi = min(lo + 1000, n_rows)
            s.execute("insert into t values " + ", ".join(
                f"({i}, {i % 997})" for i in range(lo, hi)))
        keys = [n_rows]
        workload_block(s, keys)  # warmup (plan cache, jit)

        out["overhead"] = bench_overhead(s, keys)
        out["surfaces"] = bench_surfaces(db, s, keys, tmp)
        out["degrade"] = bench_degrade(db, s)

        # gv$disk vs du with budgets armed, steady state
        s.execute("alter system set log_disk_limit_bytes = 1073741824")
        s.execute("alter system set data_disk_limit_bytes = 1073741824")
        dm = db.tenant("sys").diskmgr
        rows = s.execute("select surface, used_bytes from gv$disk").rows()
        by = {r[0]: r[1] for r in rows}
        acct = {}
        for surface in ("log", "data"):
            du = _du(dm.paths[surface])
            pct = abs(by[surface] - du) / max(1, du) * 100
            acct[surface] = {"gv_bytes": by[surface], "du_bytes": du,
                             "delta_pct": round(pct, 2)}
        acct["pass"] = all(a["delta_pct"] <= 5.0
                           for a in acct.values() if isinstance(a, dict))
        out["accounting"] = acct

        # restart after the whole gauntlet is oracle-identical
        expect = _count(s)
        db.close()
        db = Database(os.path.join(tmp, "db"))
        got = _count(db.session())
        out["restart"] = {"expect": list(expect), "got": list(got),
                          "pass": got == expect}

        out["pass"] = bool(out["overhead"]["pass"]
                           and out["surfaces"]["pass"]
                           and out["degrade"]["pass"]
                           and out["accounting"]["pass"]
                           and out["restart"]["pass"])
        from oceanbase_tpu.server import metrics as qmetrics

        out["sysstat"] = {k: v for k, v in
                          sorted(qmetrics.sysstat_dict().items())
                          if k.startswith("disk.")}
        print(json.dumps(out))
        if not out["pass"]:
            sys.exit(1)
    finally:
        if db is not None:
            try:
                db.close()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
