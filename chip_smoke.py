"""chip_smoke.py — the served SQL path on one TPU chip, every answer checked.

Default (one chip): device -> boot (``Database``) -> load (TPC-H through
``catalog.load_numpy`` + ``ANALYZE``) -> six queries through
``Session.execute``, each twice, each compared with references that share
no code with the engine (SQLite, and exact NumPy integers for Q1/Q6) ->
a committed write read back before and after a reopen -> a summary.
``--px`` (four chips) runs Q1/Q3/Q6 with ``px_dop = 4`` against the same
three run serially, and nothing else.

Every phase prints one JSON line as it ends.  The LAST line is
``{"ok": ..., "device": {"platform", "kind", "count"}}`` and the exit
code is 0 only when ``ok`` is true, which takes a TPU: on any other
platform the script is a rehearsal (give ``--sf``) that ends ``ok:
false``.  One process touches JAX; the reference runs in a child that is
pinned to the CPU and is stopped before the script exits.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import multiprocessing
import os
import re
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(HERE, ".smoke_scratch")

#: Q1, Q6 scan-aggregate; Q14, Q19 lineitem-part joins (CASE + SUM, an OR
#: of IN-lists over dictionary strings); Q3 3-way join, top-n; Q5 6-way
#: join, group-by on a string.  Chosen by what the v5e compiler takes
#: over their plan programs at SF1 shapes (0.7-240 s each, 400 s in all,
#: CHANGES.md PR 22): Q10's and Q2's take longer than the whole run may.
SMOKE_QUERIES = (1, 6, 14, 19, 3, 5)
PX_QUERIES = (1, 3, 6)
#: order-by / top-n statements whose row order is part of the answer
ORDERED = {1, 3, 5}
Q6_DATES = ("1994-01-01", "1995-01-01")
Q1_CUTOFF = "1998-09-02"
#: whole-run budget the reference child is waited for (the driver's
#: limit is 1200 s, compilation included)
REFERENCE_WAIT_S = 900.0
#: the default work area (4,194,304 rows) sends every statement that reads
#: all of SF1's lineitem through the disk spill tier; a deployment on a
#: 16 GB chip sizes it so that SF1 stays resident, and so does the smoke
WORK_AREA_ROWS = 1 << 24


def emit(rec: dict):
    print(json.dumps(rec, default=str), flush=True)


# ---------------------------------------------------------------------------
# the rows the write phase inserts, in the generator's own representation
# (decimals as scaled integers, dates as day numbers)
# ---------------------------------------------------------------------------


def write_rows(order_key: int):
    from oceanbase_tpu.datatypes import date_to_days as D

    order = {
        "o_orderkey": order_key, "o_custkey": 1, "o_orderstatus": "O",
        "o_totalprice": 300060, "o_orderdate": D("1994-06-15"),
        "o_orderpriority": "1-URGENT", "o_clerk": "Clerk#000000001",
        "o_shippriority": 0, "o_comment": "chip smoke order"}
    lines = [{
        "l_orderkey": order_key, "l_partkey": 1, "l_suppkey": 1,
        "l_linenumber": ln, "l_quantity": 1000,
        "l_extendedprice": 100000 + 10 * ln, "l_discount": 6, "l_tax": 2,
        "l_returnflag": "N", "l_linestatus": "O",
        "l_shipdate": D("1994-06-15"), "l_commitdate": D("1994-06-20"),
        "l_receiptdate": D("1994-06-25"), "l_shipinstruct": "NONE",
        "l_shipmode": "MAIL", "l_comment": f"chip smoke line {ln}"}
        for ln in (1, 2, 3)]
    return order, lines


def readback_sql(order_key: int) -> dict:
    # no ORDER BY: a sort over lineitem's whole capacity is minutes in the
    # TPU compiler, and three rows compare as well unordered
    return {
        "lineitem": (
            "select l_orderkey, l_linenumber, l_quantity, l_extendedprice, "
            "l_discount, l_tax, l_returnflag, l_shipdate from lineitem "
            f"where l_orderkey = {order_key}"),
        "orders": (
            "select o_orderkey, o_custkey, o_orderstatus, o_totalprice, "
            "o_orderdate, o_shippriority from orders "
            f"where o_orderkey = {order_key}"),
    }


def _typed(col: str, v, types: dict, sql: bool):
    """One generator-representation value as a SQL literal (engine) or a
    SQLite parameter (reference), by the column's generated type."""
    from oceanbase_tpu.datatypes import TypeKind, days_to_date

    t = types.get(col)
    if t is not None and t.kind == TypeKind.DECIMAL:
        x = v / (10 ** t.scale)
        return f"{x:.{t.scale}f}" if sql else x
    if t is not None and t.kind == TypeKind.DATE:
        d = days_to_date(int(v))
        return f"date '{d}'" if sql else d
    if isinstance(v, str):
        return f"'{v}'" if sql else v
    return str(v) if sql else v


def insert_sql(table: str, row: dict, types: dict) -> str:
    vals = ", ".join(_typed(c, v, types, sql=True) for c, v in row.items())
    return f"insert into {table} ({', '.join(row)}) values ({vals})"


# ---------------------------------------------------------------------------
# the reference: a child process, pinned to the CPU, SQLite + NumPy
# ---------------------------------------------------------------------------


def _reference_worker(sf: float, seed: int, out):
    os.environ["JAX_PLATFORMS"] = "cpu"  # the child never needs the chip
    try:
        out.put(_reference_answers(sf, seed))
    except BaseException as e:  # noqa: BLE001 — reported by the parent
        out.put({"error": f"{type(e).__name__}: {e}",
                 "traceback": traceback.format_exc()})


def _reference_answers(sf: float, seed: int) -> dict:
    import numpy as np

    from oceanbase_tpu.bench.numpy_ref import numpy_q1, numpy_q6
    from oceanbase_tpu.bench.oracle import load_sqlite, run_oracle
    from oceanbase_tpu.bench.tpch import gen_tpch
    from oceanbase_tpu.bench.tpch_queries import QUERIES
    from oceanbase_tpu.datatypes import date_to_days

    t0 = time.monotonic()
    tables, types = gen_tpch(sf=sf, seed=seed)
    gen_s = time.monotonic() - t0
    order_key = int(tables["orders"]["o_orderkey"].max()) + 1
    order, lines = write_rows(order_key)
    rb = readback_sql(order_key)

    li = tables["lineitem"]
    d0, d1 = (date_to_days(d) for d in Q6_DATES)
    ans = {"order_key": order_key,
           "q1_exact": numpy_q1(li, date_to_days(Q1_CUTOFF)),
           "q6_exact": numpy_q6(li, d0, d1)}
    li2 = {c: np.concatenate([li[c], np.array([r[c] for r in lines],
                                               dtype=li[c].dtype)])
           for c in ("l_shipdate", "l_discount", "l_quantity",
                     "l_extendedprice")}
    ans["q6_exact_after_writes"] = numpy_q6(li2, d0, d1)

    # SQLite gets the columns the statements read, of the tables they read
    text = " ".join([QUERIES[q] for q in SMOKE_QUERIES] + list(rb.values()))
    words = set(re.findall(r"[a-z_0-9]+", text.lower()))
    used = {t: {c: a for c, a in cols.items() if c in words}
            for t, cols in tables.items() if t in words}
    t0 = time.monotonic()
    conn = load_sqlite(used, types)
    load_s = time.monotonic() - t0
    t0 = time.monotonic()
    ans["sqlite"] = {q: run_oracle(conn, QUERIES[q]) for q in SMOKE_QUERIES}
    for table, rows in (("orders", [order]), ("lineitem", lines)):
        for row in rows:
            row = {c: v for c, v in row.items() if c in used[table]}
            conn.execute(
                f"insert into {table} ({', '.join(row)}) values "
                f"({', '.join('?' * len(row))})",
                [_typed(c, v, types, sql=False) for c, v in row.items()])
    ans["sqlite_q6_after_writes"] = run_oracle(conn, QUERIES[6])
    ans["sqlite_readback"] = {t: run_oracle(conn, q) for t, q in rb.items()}
    ans["seconds"] = {"gen": round(gen_s, 1), "sqlite_load": round(load_s, 1),
                      "sqlite_queries": round(time.monotonic() - t0, 1)}
    return ans


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self, args):
        self.args = args
        self.ok = True
        self.device = {"platform": "none", "kind": "", "count": 0}
        self.db = None
        self.session = None
        self.types = None            # column -> SqlType, from the generator
        self.reference = None        # the child's answers, once joined
        self._ref_proc = self._ref_queue = None
        self.cache_events = {"hits": 0, "misses": 0}
        self.t_start = time.monotonic()

    # -- plumbing -------------------------------------------------------
    def phase(self, name: str, fn) -> bool:
        t0 = time.monotonic()
        rec = {"phase": name}
        try:
            rec.update(fn() or {})
            rec["ok"] = not rec.get("failed")
        except Exception as e:  # noqa: BLE001 — recorded, makes ok false
            traceback.print_exc()
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {e}"[:600]
        rec["seconds"] = round(time.monotonic() - t0, 3)
        self.ok = self.ok and rec["ok"]
        emit(rec)
        return rec["ok"]

    def start_reference(self):
        ctx = multiprocessing.get_context("spawn")
        self._ref_queue = ctx.Queue()
        self._ref_proc = ctx.Process(
            target=_reference_worker,
            args=(self.args.sf, self.args.seed, self._ref_queue),
            daemon=True)
        self._ref_proc.start()

    def join_reference(self) -> dict:
        if self.reference is None:
            import queue

            t0 = time.monotonic()
            while self.reference is None:
                try:
                    self.reference = self._ref_queue.get(timeout=5.0)
                except queue.Empty:
                    if not self._ref_proc.is_alive():
                        self.reference = {"error": "reference child died"}
                    elif time.monotonic() - self.t_start > REFERENCE_WAIT_S:
                        self.reference = {"error": "reference timed out"}
            self.reference["waited_s"] = round(time.monotonic() - t0, 1)
            emit({"phase": "reference",
                  "ok": "error" not in self.reference,
                  "waited_s": self.reference["waited_s"],
                  **{k: self.reference[k] for k in ("seconds", "error")
                     if k in self.reference}})
        if "error" in self.reference:
            raise RuntimeError(f"reference: {self.reference['error']}")
        return self.reference

    def stop_reference(self):
        if self._ref_proc is not None and self._ref_proc.is_alive():
            self._ref_proc.terminate()
            self._ref_proc.join(10)
            if self._ref_proc.is_alive():
                self._ref_proc.kill()

    def need_db(self):
        if self.session is None:
            raise RuntimeError("no open database (an earlier phase failed)")
        return self.session

    # -- phases ---------------------------------------------------------
    def p_device(self):
        import jax

        jax.monitoring.register_event_listener(self._on_jax_event)
        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": str(devs[0].device_kind),
                       "count": len(devs)}
        want = 4 if self.args.px else 1
        failed = []
        if self.device["platform"] != "tpu":
            failed.append("platform is not tpu")
        if self.args.px and len(devs) != want:
            failed.append(f"--px needs {want} devices")
        return {**self.device, "failed": failed}

    def _on_jax_event(self, name: str, **_kw):
        if name.endswith("/cache_hits"):
            self.cache_events["hits"] += 1
        elif name.endswith("/cache_misses"):
            self.cache_events["misses"] += 1

    def p_boot(self, fresh: bool = True):
        from oceanbase_tpu.server import Database

        root = os.path.join(SCRATCH, "px" if self.args.px else "db")
        if fresh:
            shutil.rmtree(root, ignore_errors=True)
            os.makedirs(SCRATCH, exist_ok=True)
        self.db = Database(root)
        self.session = self.db.session()
        self.session.execute(
            f"alter system set sql_work_area_rows = {WORK_AREA_ROWS}")
        # the server swallows a failed calibration probe so that it can
        # boot; the smoke does not
        units = self.db.cost_units
        failed = []
        if units is None:
            failed.append("cost_units is None: the calibration probe failed")
        else:
            if units.backend != self.device["platform"]:
                failed.append(f"calibrated on {units.backend}")
            failed += [f"probe {m['kernel']}: {m['error']}"
                       for m in units.measurements if "error" in m]
        rec = {"root": os.path.relpath(root, HERE), "failed": failed}
        if units is not None:
            rec.update(backend=units.backend, probe_s=units.probe_s,
                       launch_overhead_s=units.launch_overhead_s,
                       peak_bytes_s=units.peak_bytes_s,
                       eff_bytes_s=units.eff_bytes_s,
                       peak_flops_s=units.peak_flops_s)
        return rec

    def p_load(self):
        from oceanbase_tpu.bench.tpch import TPCH_PRIMARY_KEYS, gen_tpch

        s = self.need_db()
        t0 = time.monotonic()
        tables, self.types = gen_tpch(sf=self.args.sf, seed=self.args.seed)
        gen_s = time.monotonic() - t0
        rows, load_s, analyze_s = {}, {}, {}
        for name, arrays in tables.items():
            t0 = time.monotonic()
            s.catalog.load_numpy(
                name, arrays,
                types={k: v for k, v in self.types.items() if k in arrays},
                primary_key=TPCH_PRIMARY_KEYS[name])
            load_s[name] = round(time.monotonic() - t0, 3)
            rows[name] = len(next(iter(arrays.values())))
        for name in tables:
            t0 = time.monotonic()
            s.execute(f"analyze table {name}")
            analyze_s[name] = round(time.monotonic() - t0, 3)
        return {"sf": self.args.sf, "seed": self.args.seed, "rows": rows,
                "gen_s": round(gen_s, 3), "load_s": load_s,
                "analyze_s": analyze_s}

    def _plan_traces(self) -> dict:
        """plan_hash -> xla_trace_count of every cached plan that reads
        no virtual table (the smoke's own gv$ reads compile too)."""
        r = self.session.execute(
            "select plan_hash, plan_text, xla_trace_count "
            "from gv$plan_cache")
        return {h: int(n) for h, text, n in r.rows() if "gv$" not in text}

    def _audit_rows(self, sql: str, n: int) -> list[dict]:
        r = self.session.execute(
            "select sql, elapsed_s, compile_s, bind_s, lower_s, "
            "xla_compile_s, dispatch_s, host_s, device_s, error "
            "from gv$sql_audit")
        mine = [dict(zip(r.names, row)) for row in r.rows()
                if row[0] and sql.startswith(row[0][:100])]
        out = []
        for a in mine[-n:]:
            a.pop("sql")
            out.append({k: round(v, 6) if isinstance(v, float) else v
                        for k, v in a.items()})
        return out

    def _timed(self, sql: str):
        from oceanbase_tpu.server import metrics as qmetrics

        c0 = dict(self.cache_events)
        r0 = qmetrics.counter_value("plan.capacity_retries")
        t0 = time.monotonic()
        res = self.session.execute(sql)
        dt = time.monotonic() - t0
        return res, {
            "s": round(dt, 4),
            "capacity_retries": int(
                qmetrics.counter_value("plan.capacity_retries") - r0),
            "cache_hits": self.cache_events["hits"] - c0["hits"],
            "cache_misses": self.cache_events["misses"] - c0["misses"]}

    def _run_twice(self, qnum: int):
        """-> (warm result, record): cold run, warm run, the audit's
        split of both, and the proof that the warm run compiled nothing."""
        from oceanbase_tpu.bench.tpch_queries import QUERIES

        sql = QUERIES[qnum]
        _cold_res, cold = self._timed(sql)
        traces = self._plan_traces()
        res, warm = self._timed(sql)
        failed = []
        if self._plan_traces() != traces:
            failed.append("the warm run compiled (xla_trace_count moved)")
        audit = self._audit_rows(sql, 2)
        for a, run in zip(audit, (cold, warm)):
            run.update(a)
        if len(audit) != 2 or audit[1]["xla_compile_s"] != 0 \
                or audit[1]["lower_s"] != 0:
            failed.append("the warm run's audit row shows a compile")
        return res, {"query": f"q{qnum}", "rows": res.rowcount,
                     "cold": cold, "warm": warm, "failed": failed}

    def _check_exact(self, qnum: int, res, ref: dict, key: str) -> list:
        """Exact scaled-integer comparison of Q1/Q6 with NumPy."""
        failed = []
        if qnum == 6:
            got = int(res.arrays["revenue"][0])
            if got != ref[key]:
                failed.append(f"q6 exact: {got} != {ref[key]}")
        elif qnum == 1:
            a = res.arrays
            want = ref["q1_exact"]
            cols = next(iter(want.values())).keys()
            got = {(str(a["l_returnflag"][i]), str(a["l_linestatus"][i])):
                   {c: int(a[c][i]) for c in cols}
                   for i in range(res.rowcount)}
            if got != want:
                failed.append(f"q1 exact: {got} != {want}")
        return failed

    def p_queries(self):
        from oceanbase_tpu.bench.oracle import rows_match

        self.need_db()
        n_ok = 0
        t0 = time.monotonic()
        results = {}
        for qnum in SMOKE_QUERIES:
            def one(qnum=qnum):
                res, rec = self._run_twice(qnum)
                results[qnum] = res
                return rec
            self.phase(f"query.q{qnum}", one)
        run_s = time.monotonic() - t0
        ref = self.join_reference()
        failed = []
        for qnum in SMOKE_QUERIES:
            if qnum not in results:
                failed.append(f"q{qnum}: did not run")
                continue
            res = results[qnum]
            ok, why = rows_match(res.rows(), ref["sqlite"][qnum],
                                 ordered=qnum in ORDERED)
            bad = ([] if ok else [f"q{qnum} vs sqlite: {why}"]) \
                + self._check_exact(qnum, res, ref, "q6_exact")
            failed += bad
            n_ok += not bad
        return {"queries": len(SMOKE_QUERIES), "equal_to_reference": n_ok,
                "run_s": round(run_s, 3), "rtol_doubles": 1e-6,
                "failed": failed}

    def _q6_and_readback(self, ref: dict, tag: str) -> tuple[dict, list]:
        from oceanbase_tpu.bench.oracle import rows_match
        from oceanbase_tpu.bench.tpch_queries import QUERIES

        res, rec = self._timed(QUERIES[6])
        failed = self._check_exact(6, res, ref, "q6_exact_after_writes")
        ok, why = rows_match(res.rows(), ref["sqlite_q6_after_writes"],
                             ordered=True)
        if not ok:
            failed.append(f"q6 vs sqlite: {why}")
        for table, sql in readback_sql(ref["order_key"]).items():
            got = self.session.execute(sql).rows()
            ok, why = rows_match(got, ref["sqlite_readback"][table],
                                 ordered=False)
            if not ok or not got:
                failed.append(f"{table} read back: {why or 'no rows'}")
        return {f"q6_{tag}": rec}, [f"{tag}: {f}" for f in failed]

    def p_writes(self):
        s = self.need_db()
        ref = self.join_reference()
        order, lines = write_rows(ref["order_key"])
        t0 = time.monotonic()
        s.execute("begin")
        s.execute(insert_sql("orders", order, self.types))
        for row in lines:
            s.execute(insert_sql("lineitem", row, self.types))
        s.execute("commit")
        rec = {"inserted": 1 + len(lines),
               "tx_s": round(time.monotonic() - t0, 3)}
        r, failed = self._q6_and_readback(ref, "after_commit")
        rec.update(r)
        # durability as far as a run can show it: close, reopen, read
        t0 = time.monotonic()
        s.close()
        self.db.close()
        self.db = self.session = None
        rec["close_s"] = round(time.monotonic() - t0, 3)
        t0 = time.monotonic()
        failed += self.p_boot(fresh=False)["failed"]
        rec["reopen_s"] = round(time.monotonic() - t0, 3)
        r, f2 = self._q6_and_readback(ref, "after_reopen")
        rec.update(r)
        return {**rec, "failed": failed + f2}

    def p_summary(self):
        import jax

        from oceanbase_tpu.native import native_available

        stats = jax.devices()[0].memory_stats() or {}
        cache_dir = jax.config.jax_compilation_cache_dir
        n_files = len(os.listdir(cache_dir)) \
            if cache_dir and os.path.isdir(cache_dir) else 0
        return {"peak_bytes_in_use": stats.get("peak_bytes_in_use",
                                               "not reported"),
                "bytes_limit": stats.get("bytes_limit", "not reported"),
                "compile_cache_dir": cache_dir,
                "compile_cache_files": n_files,
                "compile_cache_hits": self.cache_events["hits"],
                "compile_cache_misses": self.cache_events["misses"],
                "native_available": bool(native_available()),
                "total_s": round(time.monotonic() - self.t_start, 1)}

    # -- --px -----------------------------------------------------------
    def p_px(self):
        import jax

        from oceanbase_tpu.bench.oracle import rows_match
        from oceanbase_tpu.bench.tpch_queries import QUERIES
        from oceanbase_tpu.px.exchange import default_mesh, shard_relation

        s = self.need_db()
        failed = []
        serial = {}
        s.execute("set px_dop = 1")
        for qnum in PX_QUERIES:
            def one(qnum=qnum):
                serial[qnum], rec = self._run_twice(qnum)
                return {**rec, "path": "serial"}
            self.phase(f"serial.q{qnum}", one)
        s.execute("set px_dop = 4")
        for qnum in PX_QUERIES:
            def one(qnum=qnum):
                res, cold = self._timed(QUERIES[qnum])
                path_cold = self._recorded_path()
                res, warm = self._timed(QUERIES[qnum])
                path = self._recorded_path()
                bad = []
                if path_cold != "px" or path != "px":
                    bad.append(f"recorded path {path_cold}/{path}, not px")
                if qnum not in serial:
                    bad.append("no serial answer to compare with")
                else:
                    ok, why = rows_match(res.rows(), serial[qnum].rows(),
                                         ordered=qnum in ORDERED)
                    if not ok:
                        bad.append(f"px != serial: {why}")
                return {"query": f"q{qnum}", "path": path,
                        "rows": res.rowcount, "cold": cold, "warm": warm,
                        "equal_to_serial": not bad, "failed": bad}
            if not self.phase(f"px.q{qnum}", one):
                failed.append(f"q{qnum}")
        # what each device holds of a sharded lineitem, and in all
        mesh = default_mesh(4)
        sharded = shard_relation(s.catalog.table_data("lineitem"), mesh)
        held = {str(d.id): 0 for d in mesh.devices.flat}
        arrays = [sharded.mask] + [
            a for col in sharded.columns.values()
            for a in (col.data, col.valid) if a is not None]
        for a in arrays:
            for sh in a.addressable_shards:
                held[str(sh.device.id)] += int(sh.data.nbytes)
        in_use = {str(d.id): (d.memory_stats() or {}).get(
            "bytes_in_use", "not reported") for d in jax.devices()}
        if min(held.values()) == 0 or \
                max(held.values()) > 2 * min(held.values()):
            failed.append(f"lineitem shards are uneven: {held}")
        return {"lineitem_shard_bytes_per_device": held,
                "bytes_in_use_per_device": in_use, "failed": failed}

    def _recorded_path(self) -> str:
        """The execution path the last statement's trace recorded."""
        for op, _node, _ts, _ms, tags in \
                self.session.execute("show trace").rows():
            if op.strip() == "execute":
                t = json.loads(tags)
                return ("dtl" if t.get("dtl") else
                        "px" if t.get("px") else "serial")
        return "unrecorded"

    # -- driver ---------------------------------------------------------
    def run(self):
        if not self.phase("device", self.p_device) \
                and not self.args.sf_given:
            # not the devices asked for, and no rehearsal size given
            return
        if not self.args.px:
            self.start_reference()
        self.phase("boot", self.p_boot)
        self.phase("load", self.p_load)
        if self.args.px:
            self.phase("px", self.p_px)
        else:
            self.phase("queries", self.p_queries)
            self.phase("writes", self.p_writes)
        self.phase("summary", self.p_summary)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=None,
                    help="TPC-H scale factor (default 1; off the TPU a "
                    "rehearsal runs only when this is given)")
    ap.add_argument("--seed", type=int, default=19920101)
    ap.add_argument("--px", action="store_true",
                    help="four chips: Q1/Q3/Q6 at px_dop=4 against the "
                    "same three serially, and no other phase")
    args = ap.parse_args()
    args.sf_given = args.sf is not None
    if args.sf is None:
        args.sf = 1.0

    smoke = Smoke(args)
    try:
        smoke.run()
    except BaseException as e:  # noqa: BLE001 — the last line still prints
        traceback.print_exc()
        smoke.ok = False
        emit({"phase": "aborted", "ok": False,
              "error": f"{type(e).__name__}: {e}"[:600]})
    finally:
        smoke.stop_reference()
        with contextlib.suppress(Exception):
            if smoke.db is not None:
                smoke.db.close()
    ok = smoke.ok and smoke.device["platform"] == "tpu"
    print(json.dumps({"ok": ok, "device": smoke.device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
