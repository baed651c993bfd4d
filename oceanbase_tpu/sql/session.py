"""Session: the SQL entry point (parse -> bind -> optimize -> execute).

Reference analog: ObSQLSessionInfo + ObSql::stmt_query + ObResultSet
(src/sql/session, src/sql/ob_sql.cpp:152, src/sql/ob_result_set.cpp:147).
Includes the plan-cache probe (fingerprinted physical plans + XLA
compilation cache underneath, ≙ ObPlanCache::get_plan) and the
capacity-retry loop: a CapacityOverflow from the static-shape engine
re-plans with 4x budgets (the TPU analog of spill-on-overflow).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp
import numpy as np

from oceanbase_tpu.catalog import ColumnDef, TableDef
from oceanbase_tpu.datatypes import SqlType, TypeKind, days_to_date
from oceanbase_tpu.exec.diag import CapacityOverflow
from oceanbase_tpu.exec import plan as qplan
from oceanbase_tpu.exec.plan import execute_plan
from oceanbase_tpu.expr import ir
from oceanbase_tpu.expr.compile import literal_value
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.server import trace as qtrace
from oceanbase_tpu.sql import ast
from oceanbase_tpu.sql.binder import Binder
from oceanbase_tpu.sql.optimizer import after_overflow, scale_capacities
from oceanbase_tpu.sql.parser import parse_sql
from oceanbase_tpu.tx.service import WriteStats
from oceanbase_tpu.vector import Relation, from_numpy, to_numpy

# serving-plane statement accounting (host side, statement boundary —
# the latency distribution the p50/p99 serving arc is gated on)
qmetrics.declare("sql.statements", "counter",
                 "statements executed (labels: tenant, ok)")
qmetrics.declare("sql.statement_s", "histogram",
                 "end-to-end statement latency", unit="s")
qmetrics.declare("sql.parse_bytes", "counter",
                 "SQL text handed to the parser (beside gv$time_model's "
                 "parse_s: seconds per KB of text)", unit="bytes")
qmetrics.declare("sql.work_area_bytes", "gauge",
                 "the work-area budget in force, in bytes: "
                 "ob_sql_work_area_percentage of the device's memory; "
                 "absent while sql_work_area_rows is not 0 and decides",
                 unit="bytes")
qmetrics.declare("sql.work_area_decisions", "counter",
                 "statements the work-area budget kept on the device "
                 "{kind=resident} or sent through the disk spill tier "
                 "{kind=spill}, one per plan execution priced")
qmetrics.declare("storage.analyze_ns", "counter",
                 "time ANALYZE TABLE spent gathering statistics (the "
                 "span ``analyze``)", unit="ns")
qmetrics.declare("plan_cache.hits", "counter",
                 "session plan-cache hits")
qmetrics.declare("plan_cache.misses", "counter",
                 "session plan-cache misses (bind + optimize paid)")
qmetrics.declare("plan_cache.evictions", "counter",
                 "session plan-cache LRU evictions")

_POW10 = [10**i for i in range(38)]


@dataclass
class Result:
    """A materialized result set (the MySQL-packet boundary analog)."""

    names: list
    arrays: dict            # name -> numpy array (decoded strings)
    valids: dict            # name -> bool array or None
    dtypes: dict            # name -> SqlType
    rowcount: int = 0
    plan_text: Optional[str] = None

    def rows(self) -> list[tuple]:
        out = []
        n = len(next(iter(self.arrays.values()))) if self.names else 0
        for i in range(n):
            row = []
            for name in self.names:
                v = self.valids.get(name)
                if v is not None and not v[i]:
                    row.append(None)
                    continue
                x = self.arrays[name][i]
                t = self.dtypes.get(name)
                if t is not None and t.kind == TypeKind.DECIMAL:
                    row.append(float(x) / _POW10[t.scale])
                elif t is not None and t.kind == TypeKind.DATE:
                    row.append(days_to_date(int(x)))
                elif isinstance(x, (np.floating,)):
                    row.append(float(x))
                elif isinstance(x, (np.integer,)):
                    row.append(int(x))
                elif isinstance(x, np.str_):
                    row.append(str(x))
                else:
                    row.append(x)
            out.append(tuple(row))
        return out


class Session:
    """One client session (≙ ObSQLSessionInfo): session vars + execute()."""

    MAX_CAPACITY_RETRIES = 3

    def __init__(self, tenant, db):
        self.tenant = tenant  # server.Tenant: catalog, engine, tx, config
        self.db = db  # its host (server.database.Host)
        self.catalog = tenant.catalog
        self.session_id = next(db._session_ids)
        self.variables: dict[str, object] = {
            "autocommit": 1, "max_capacity_retry": self.MAX_CAPACITY_RETRIES,
        }
        from collections import OrderedDict

        # LRU plan cache: most-recently-used last; byte-accounted against
        # plan_cache_mem_limit (≙ ObPlanCache memory-bounded eviction)
        self.plan_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        # logical plan hash -> {PX exchange overflow lane: the factor its
        # budget was raised by}: a statement that overflowed an exchange
        # starts its next execution at the budget that cleared it
        self._px_budgets: dict[str, dict] = {}
        #: capacity factor a streamed plan's granule programs cleared at,
        #: by logical plan (the spill tier's half of the retry ladder)
        self._spill_factors: dict[str, int] = {}
        self._plan_cache_bytes: dict[tuple, int] = {}
        self._plan_cache_total = 0
        self._last_spill = None  # SpillStats of the last spilled query
        self._tx = None  # active explicit transaction (BEGIN ... COMMIT)
        self._last_trace_id = ""  # SHOW TRACE target (last kept trace)
        self._last_compile_s = 0.0
        self._ash_state = {"active": False, "sql": "", "state": "idle",
                           "trace_id": ""}
        db.ash.register(self.session_id, self._ash_state)

    def close(self):
        """Release session resources (ASH slot, open transaction,
        admission eviction flag)."""
        if self._tx is not None:
            self._txsvc.rollback(self._tx)
            self._tx = None
        self.db.ash.unregister(self.session_id)
        self.db.admission.forget_session(self.session_id)

    # the tenant's module stack
    @property
    def _txsvc(self):
        return self.tenant.tx

    @property
    def _engine(self):
        return self.tenant.engine

    # ------------------------------------------------------------------
    # statement shapes that pay admission (queries + DML + anything
    # that executes a plan); admin/control statements — SET, SHOW,
    # KILL, ALTER SYSTEM, transaction verbs — bypass so the operator
    # can still steer a saturated server
    _ADMITTED_STMTS = (ast.SelectStmt, ast.InsertStmt, ast.UpdateStmt,
                       ast.DeleteStmt, ast.CallStmt, ast.LoadDataStmt)

    def _needs_admission(self, stmt) -> bool:
        if isinstance(stmt, ast.ProfileStmt):
            return self._needs_admission(stmt.stmt)  # PROFILE runs it
        if isinstance(stmt, self._ADMITTED_STMTS):
            return True
        if isinstance(stmt, ast.ExplainStmt) and \
                getattr(stmt, "analyze", False):
            return True  # EXPLAIN ANALYZE executes the plan
        if isinstance(stmt, ast.CreateTableStmt) and \
                getattr(stmt, "as_select", None) is not None:
            return True  # CTAS executes its SELECT
        return False

    def _stmt_timeout_s(self) -> float | None:
        """Effective per-statement deadline: the session variable wins
        (SET query_timeout_s = 0.5 works sub-second), then the tenant's
        config overlay (SET GLOBAL writes there — reading db.config
        directly would silently ignore it), else the cluster default.
        ``ob_query_timeout`` (upstream's name, microseconds) is the same
        variable at both scopes (``server/config.py::ALIASES``)."""
        v = self.variables.get("query_timeout_s")
        if v is None:
            v = self.tenant.config["query_timeout_s"]
        try:
            v = float(v)
        except (TypeError, ValueError):
            return None
        return v if v > 0 else None

    def execute(self, sql: str, params: list | None = None) -> Result:
        """Parse + execute one statement, with request auditing, ASH
        state, and a full-link trace root span (≙ obmp_query process +
        sql_audit recording + ObTrace begin/end).

        Overload plane: query/DML statements check a per-tenant
        admission slot out BEFORE binding (typed ServerBusy when the
        bounded queue is full) and run under a StmtCtx whose deadline
        and KILL flag the result-boundary checkpoints observe."""
        from oceanbase_tpu.server import admission as qadmission

        start = time.time()        # wall ts for the audit record
        t0 = time.monotonic()      # duration source (step-proof)
        err = ""
        out = None
        # host/device split accumulator: statement-scoped, so audit and
        # plan-monitor rows attribute exactly this statement's work
        times = qplan.reset_exec_times()
        qtrace.begin_statement()
        self._last_compile_s = 0.0
        self._stmt_is_show_trace = False  # set by _show_trace()
        tctx = qtrace.start_trace(self.db)
        admission = self.db.admission
        ctx: qadmission.StmtCtx | None = None
        # every host phase below is a span whose self time is a column
        # of this statement's audit row (trace.PHASE_OF); what lies
        # between them is ``other_s``
        try:
            # a session evicted by plain KILL <id> takes no more
            # statements (typed; the client reconnects)
            admission.check_session(self.session_id)
            with qtrace.activate(tctx):
                timeout_s = self._stmt_timeout_s()
                with qtrace.span("statement", sql=sql[:200],
                                 session=self.session_id,
                                 ob_query_timeout=round(
                                     (timeout_s or 0) * 1_000_000)):
                    with qtrace.span("parse", bytes=len(sql)):
                        stmt = parse_sql(sql)
                    with qtrace.span("admission"):
                        # the statement's own bookkeeping lives here,
                        # so that it has an owner on the timeline
                        self._ash_state.update(
                            active=True, sql=sql, state="executing",
                            trace_id=tctx.trace_id
                            if tctx is not None else "")
                        if self._needs_admission(stmt):
                            ctx = qadmission.StmtCtx(
                                session_id=self.session_id,
                                tenant=self.tenant.name,
                                sql=sql, timeout_s=timeout_s,
                                controller=admission,
                                ash_state=self._ash_state)
                            self._ash_state["state"] = "queued"
                            try:
                                admission.acquire(ctx)
                            finally:
                                if self._ash_state.get("state") == \
                                        "queued":
                                    self._ash_state["state"] = "executing"
                            if ctx.queue_s > 0:
                                # queued time is a first-class wait: a
                                # span in the statement tree +
                                # gv$sql_audit's queue_s column (emitted
                                # only when the statement actually
                                # waited; the admission span's self time
                                # gives it up)
                                qtrace.add_span("admission.wait",
                                                ctx.queue_s,
                                                tenant=ctx.tenant)
                    with qadmission.activate(ctx):
                        with qtrace.span("virtuals"):
                            self._materialize_virtuals(stmt)
                        out = self.execute_stmt(stmt, params)
                        return out
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
            raise
        finally:
            elapsed = time.monotonic() - t0
            # the root span has closed: what follows is on the
            # profiler's timeline (ob:statement.close) and in close_s,
            # outside elapsed_s
            with qtrace.span("statement.close") as csp:
                if ctx is not None:
                    admission.release(ctx)
                self._ash_state.update(active=False, state="idle",
                                       trace_id="")
                tname = self.tenant.name
                qmetrics.inc("sql.statements", tenant=tname,
                             ok=0 if err else 1)
                qmetrics.observe("sql.statement_s", elapsed, tenant=tname)
                qmetrics.inc("sql.parse_bytes", len(sql))
                trace_id = ""
                if tctx is not None:
                    kept = qtrace.finish_trace(self.db, tctx, elapsed,
                                               error=err)
                    if kept:
                        trace_id = tctx.trace_id
                        # SHOW TRACE reads the LAST statement's tree — a
                        # SHOW TRACE must not clobber what it displays
                        if not self._stmt_is_show_trace:
                            self._last_trace_id = trace_id
                    elif not self._stmt_is_show_trace:
                        # sampled away: SHOW TRACE must come up empty,
                        # not silently attribute an OLDER statement's
                        # tree
                        self._last_trace_id = ""
                from oceanbase_tpu.server.monitor import AuditRecord

                queue_s = ctx.queue_s if ctx is not None else 0.0
                other_s = elapsed - queue_s - times.phase_sum()
                # the row keeps the statement's accumulator itself,
                # so close_s (booked when this span closes) shows
                self.db.audit.record(AuditRecord(
                    sql=sql, session_id=self.session_id,
                    tenant=tname,
                    start_ts=start, elapsed_s=elapsed,
                    rows=out.rowcount if out is not None else 0,
                    error=err,
                    compile_s=self._last_compile_s,
                    trace_id=trace_id,
                    queue_s=queue_s,
                    host_s=times.host_s, device_s=times.device_s,
                    bind_s=times.bind_s,
                    sidecar_build_s=times.sidecar_build_s,
                    lower_s=times.lower_s,
                    xla_compile_s=times.compile_s,
                    dispatch_s=times.dispatch_s,
                    merge_s=times.merge_s,
                    other_s=other_s, times=times,
                ))
                self.db.time_model.observe(
                    tname, times, elapsed_s=elapsed, queue_s=queue_s,
                    other_s=other_s, close_s=csp.so_far_s())

    def _materialize_virtuals(self, stmt):
        """Refresh any referenced gv$/v$ virtual tables as transient
        catalog relations (≙ virtual table iterators serving the query).
        Covers every statement shape that can reference a table: SELECT
        (FROM, CTEs, set ops, expression subqueries), EXPLAIN,
        INSERT ... SELECT, UPDATE/DELETE WHERE subqueries."""
        vt = self.db.virtual_tables
        # the walk is made of methods, not of closures that name each
        # other: those are reference cycles, made anew by every
        # statement, that only the collector can free
        seen_views: set = set()
        if isinstance(stmt, ast.ProfileStmt):
            stmt = stmt.stmt
        if isinstance(stmt, ast.ExplainStmt):
            stmt = stmt.stmt
        if isinstance(stmt, ast.SelectStmt):
            self._virtuals_in_select(stmt, vt, seen_views)
        elif isinstance(stmt, ast.InsertStmt) and stmt.select is not None:
            self._virtuals_in_select(stmt.select, vt, seen_views)
        elif isinstance(stmt, (ast.UpdateStmt, ast.DeleteStmt)):
            self._virtuals_in_expr(stmt.where, vt, seen_views)
        elif isinstance(stmt, ast.DescribeStmt):
            # DESCRIBE on a gv$ table or on a view whose body reads one
            # must materialize it before the binder expands the name
            self._refresh_virtual(stmt.table, vt, seen_views)

    def _refresh_virtual(self, name, vt, seen_views):
        arrays = vt.provide(name)
        if arrays is not None:
            self.catalog.register_transient(name, arrays)
            return
        # a view body may reference gv$/v$ tables too — walk it so
        # they refresh per statement like direct references
        vdef = self.catalog.view_def(name)
        if vdef is None or name in seen_views:
            return
        seen_views.add(name)
        try:
            body = parse_sql(vdef["sql"])
        except Exception:
            return
        if isinstance(body, ast.SelectStmt):
            self._virtuals_in_select(body, vt, seen_views)

    def _virtuals_in_expr(self, e, vt, seen_views):
        if e is None or not isinstance(e, ir.Expr):
            return
        if isinstance(e, ast.Subquery) and e.select is not None:
            self._virtuals_in_select(e.select, vt, seen_views)
        for c in e.children():
            self._virtuals_in_expr(c, vt, seen_views)

    def _virtuals_in_from(self, items, vt, seen_views):
        for t in items:
            if isinstance(t, ast.TableRef):
                self._refresh_virtual(t.name, vt, seen_views)
            elif isinstance(t, ast.JoinRef):
                self._virtuals_in_from([t.left, t.right], vt, seen_views)
                if isinstance(t.on, ir.Expr):
                    self._virtuals_in_expr(t.on, vt, seen_views)
            elif isinstance(t, ast.SubqueryRef):
                self._virtuals_in_select(t.select, vt, seen_views)

    def _virtuals_in_select(self, s, vt, seen_views):
        self._virtuals_in_from(s.from_, vt, seen_views)
        for e, _ in s.items:
            self._virtuals_in_expr(e, vt, seen_views)
        self._virtuals_in_expr(s.where, vt, seen_views)
        self._virtuals_in_expr(s.having, vt, seen_views)
        for _, sub in s.ctes:
            self._virtuals_in_select(sub, vt, seen_views)
        for _, _, rhs in s.setops:
            self._virtuals_in_select(rhs, vt, seen_views)

    def execute_stmt(self, stmt, params=None) -> Result:
        if isinstance(stmt, ast.SelectStmt):
            return self._execute_select(stmt, params)
        if isinstance(stmt, ast.ExplainStmt):
            return self._explain(stmt.stmt, params,
                                 analyze=getattr(stmt, "analyze", False))
        if isinstance(stmt, ast.ProfileStmt):
            return self._profile(stmt, params)
        if isinstance(stmt, ast.CreateTableStmt):
            return self._create_table(stmt)
        if isinstance(stmt, ast.DropTableStmt):
            if self.catalog.drop_external(stmt.name):
                return _ok()
            self.catalog.drop_table(stmt.name, if_exists=stmt.if_exists)
            return _ok()
        if isinstance(stmt, ast.TablegroupStmt):
            if stmt.op == "create":
                self._engine.create_tablegroup(stmt.name, stmt.flag)
            else:
                self._engine.drop_tablegroup(stmt.name, stmt.flag)
            self.catalog.schema_version += 1
            return _ok()
        if isinstance(stmt, ast.CreateViewStmt):
            self.catalog.create_view(stmt.name, stmt.sql_text,
                                     cols=stmt.columns,
                                     or_replace=stmt.or_replace)
            return _ok()
        if isinstance(stmt, ast.DropViewStmt):
            if not self.catalog.drop_view(stmt.name) and \
                    not stmt.if_exists:
                raise KeyError(f"unknown view {stmt.name}")
            return _ok()
        if isinstance(stmt, ast.CreateExternalTableStmt):
            td = TableDef(stmt.name,
                          [ColumnDef(c.name, c.dtype, c.nullable)
                           for c in stmt.columns])
            self.catalog.register_external(
                td, stmt.location, fmt=stmt.format,
                delimiter=stmt.delimiter, skip_lines=stmt.skip_lines,
                if_not_exists=stmt.if_not_exists)
            return _ok()
        if isinstance(stmt, ast.CreateIndexStmt):
            return self._create_index(stmt)
        if isinstance(stmt, ast.DropIndexStmt):
            return self._drop_index(stmt)
        if isinstance(stmt, ast.InsertStmt):
            return self._insert(stmt, params)
        if isinstance(stmt, ast.UpdateStmt):
            return self._update(stmt, params)
        if isinstance(stmt, ast.DeleteStmt):
            return self._delete(stmt, params)
        if isinstance(stmt, ast.ShowTablesStmt):
            # virtual gv$ tables are part of the schema surface: every
            # diagnostic view must be discoverable, not folklore
            names = sorted(set(self.catalog.tables())
                           | set(self.catalog.view_names())
                           | set(self.db.virtual_tables.names()))
            return Result(["table_name"],
                          {"table_name": np.array(names, dtype=object)},
                          {}, {"table_name": SqlType.string()},
                          rowcount=len(names))
        if isinstance(stmt, ast.DescribeStmt):
            if self.catalog.view_def(stmt.table) is not None:
                return self._describe_view(stmt.table)
            td = self.catalog.table_def(stmt.table)
            return Result(
                ["field", "type", "null", "key"],
                {"field": np.array([c.name for c in td.columns], dtype=object),
                 "type": np.array([str(c.dtype) for c in td.columns], dtype=object),
                 "null": np.array(["YES" if c.nullable else "NO"
                                   for c in td.columns], dtype=object),
                 "key": np.array(["PRI" if c.name in td.primary_key else ""
                                  for c in td.columns], dtype=object)},
                {}, {}, rowcount=len(td.columns))
        if isinstance(stmt, ast.AnalyzeWorkloadStmt):
            return self._analyze_workload(stmt)
        if isinstance(stmt, ast.AnalyzeStmt):
            return self._analyze(stmt)
        if isinstance(stmt, ast.KillStmt):
            return self._kill(stmt)
        if isinstance(stmt, ast.TxStmt):
            return self._tx_control(stmt.op)
        if isinstance(stmt, ast.SavepointStmt):
            return self._savepoint(stmt)
        if isinstance(stmt, ast.XaStmt):
            return self._xa(stmt)
        if isinstance(stmt, ast.ProcedureStmt):
            return self._procedure_ddl(stmt)
        if isinstance(stmt, ast.CallStmt):
            return self._call_procedure(stmt, params)
        if isinstance(stmt, ast.SetVarStmt):
            return self._set_var(stmt)
        if isinstance(stmt, ast.AlterSystemStmt):
            return self._alter_system(stmt)
        if isinstance(stmt, ast.AlterTableStmt):
            if stmt.action == "add_column":
                c = stmt.column
                self._engine.alter_table(stmt.table, "add_column",
                                         (c.name, c.dtype, c.nullable))
            else:
                self._engine.alter_table(stmt.table, "drop_column",
                                         stmt.column)
            self.catalog.invalidate(stmt.table)
            self.catalog.schema_version += 1
            return _ok()
        if isinstance(stmt, ast.TenantStmt):
            if stmt.op == "create":
                self.db.create_tenant(stmt.name)
            else:
                self.db.drop_tenant(stmt.name)
            return _ok()
        if isinstance(stmt, ast.UserStmt):
            if stmt.op == "create":
                self.db.create_user(stmt.name, stmt.password)
            elif stmt.op == "drop":
                self.db.drop_user(stmt.name)
            else:
                self.db.set_password(stmt.name, stmt.password)
            return _ok()
        if isinstance(stmt, ast.LoadDataStmt):
            return self._load_data(stmt)
        if isinstance(stmt, ast.TruncateStmt):
            return self._truncate(stmt)
        if isinstance(stmt, ast.ShowCreateStmt):
            vdef = self.catalog.view_def(stmt.table)
            if vdef is not None:
                cols = (" (" + ", ".join(vdef["cols"]) + ")"
                        if vdef.get("cols") else "")
                text = (f"CREATE VIEW {stmt.table}{cols} AS "
                        f"{vdef['sql']}")
                return Result(
                    ["view", "create_view"],
                    {"view": np.array([stmt.table], dtype=object),
                     "create_view": np.array([text], dtype=object)},
                    {}, {}, rowcount=1)
            td = self.catalog.table_def(stmt.table)
            parts = []
            for c in td.columns:
                bits = [c.name, str(c.dtype)]
                if not c.nullable:
                    bits.append("NOT NULL")
                if c.name in getattr(td, "auto_increment_cols", []):
                    bits.append("AUTO_INCREMENT")
                parts.append("  " + " ".join(bits))
            if td.primary_key:
                parts.append("  PRIMARY KEY (" +
                             ", ".join(td.primary_key) + ")")
            for ix in getattr(td, "indexes", []):
                kw = "UNIQUE KEY" if ix.unique else "KEY"
                parts.append(f"  {kw} {ix.name} (" +
                             ", ".join(ix.columns) + ")")
            text = (f"CREATE TABLE {td.name} (\n" + ",\n".join(parts) +
                    "\n)")
            if td.tablegroup:
                text += f" TABLEGROUP = {td.tablegroup}"
            if td.hash_partition:
                method, pcols, nparts = td.hash_partition
                text += (f" PARTITION BY {method.upper()} ("
                         + ", ".join(pcols) + f") PARTITIONS {nparts}")
            if td.partition:
                pcol, bounds = td.partition
                ps = [f"PARTITION p{i} VALUES LESS THAN ({b})"
                      for i, b in enumerate(bounds)]
                ps.append(f"PARTITION p{len(bounds)} VALUES LESS THAN "
                          f"MAXVALUE")
                text += (f" PARTITION BY RANGE ({pcol}) (" +
                         ", ".join(ps) + ")")
            if td.column_groups:
                text += (" WITH COLUMN GROUP ("
                         + ", ".join(td.column_groups) + ")")
            return Result(
                ["table", "create_table"],
                {"table": np.array([td.name], dtype=object),
                 "create_table": np.array([text], dtype=object)},
                {}, {}, rowcount=1)
        if isinstance(stmt, ast.SequenceStmt):
            seqs = self.tenant.sequences
            if stmt.op == "create":
                seqs.create(stmt.name, stmt.start, stmt.increment, stmt.cache)
            else:
                seqs.drop(stmt.name)
            return _ok()
        if isinstance(stmt, ast.LockTableStmt):
            return self._lock_table(stmt)
        if isinstance(stmt, ast.ShowStmt):
            if stmt.what == "index":
                td = self.catalog.table_def(stmt.table)
                names, cols, uniq, kinds = [], [], [], []
                if td.primary_key:
                    names.append("PRIMARY")
                    cols.append(",".join(td.primary_key))
                    uniq.append(1)
                    kinds.append("primary")
                for ix in td.indexes:
                    names.append(ix.name)
                    cols.append(",".join(ix.columns))
                    uniq.append(1 if ix.unique else 0)
                    kinds.append("unique" if ix.unique else "normal")
                for nm, spec in td.aux_indexes.items():
                    names.append(nm)
                    cols.append(spec["column"])
                    uniq.append(0)
                    kinds.append(spec["kind"])
                return Result(
                    ["key_name", "columns", "unique", "index_type"],
                    {"key_name": np.array(names, dtype=object),
                     "columns": np.array(cols, dtype=object),
                     "unique": np.array(uniq, dtype=np.int64),
                     "index_type": np.array(kinds, dtype=object)},
                    {}, {}, rowcount=len(names))
            if stmt.what == "trace":
                return self._show_trace()
            if stmt.what == "workload_report":
                return self._show_workload_report()
            if stmt.what == "metrics":
                return self._show_metrics()
            if stmt.what == "profile":
                return self._show_profile()
            if stmt.what == "processlist":
                # admission-plane states surface MySQL-style: QUEUED
                # (waiting for a slot), RUNNING, KILLED (flagged, still
                # unwinding), IDLE
                disp = {"executing": "RUNNING", "queued": "QUEUED",
                        "killed": "KILLED", "idle": "IDLE"}
                rows = []
                for sid, st in self.db.ash.sessions().items():
                    raw = st.get("state", "idle")
                    rows.append((sid, disp.get(raw, raw.upper()),
                                 st.get("sql", "")[:120]))
                rows.sort()
                return Result(
                    ["id", "state", "info"],
                    {"id": np.array([r[0] for r in rows], np.int64),
                     "state": np.array([r[1] for r in rows],
                                       dtype=object),
                     "info": np.array([r[2] for r in rows],
                                      dtype=object)},
                    {}, {}, rowcount=len(rows))
            if stmt.what == "variables":
                from oceanbase_tpu.server.config import ALIASES, aliased

                shown = dict(self.variables)
                # a variable set under either of its names shows under both
                shown.update({a: aliased(a, shown[t])
                              for a, (t, _) in ALIASES.items()
                              if t in shown})
                names = sorted(shown)
                return Result(
                    ["variable_name", "value"],
                    {"variable_name": np.array(names, dtype=object),
                     "value": np.array([str(shown[n]) for n in names],
                                       dtype=object)},
                    {}, {}, rowcount=len(names))
            snap = self.tenant.config.snapshot()
            return Result(
                ["name", "value"],
                {"name": np.array(list(snap), dtype=object),
                 "value": np.array([str(v) for v in snap.values()],
                                   dtype=object)},
                {}, {}, rowcount=len(snap))
        raise NotImplementedError(type(stmt).__name__)

    def _kill(self, stmt: ast.KillStmt) -> Result:
        """KILL [QUERY] <session_id>: flag the target's running (or
        queued) statement; the victim unwinds with typed QueryKilled at
        its next host-side checkpoint (operator close / spill chunk /
        DTL slice join / retry ladder) — and in-flight remote DTL
        fragments are cancelled over the idempotent dtl.cancel verb."""
        # existence first (MySQL: ER_NO_SUCH_THREAD): plain KILL must
        # not plant eviction flags for ids that were never sessions
        if stmt.session_id not in self.db.ash.sessions():
            raise KeyError(f"unknown session id {stmt.session_id}")
        # KILL QUERY cancels the in-flight statement (rowcount 0 on an
        # idle session); plain KILL also evicts the session itself
        found = self.db.admission.kill(stmt.session_id,
                                       query_only=(stmt.kind == "query"))
        return _ok(rowcount=1 if found else 0)

    def _set_var(self, stmt: ast.SetVarStmt) -> Result:
        if stmt.scope == "global":
            self.tenant.config.set(stmt.name, stmt.value)
        else:
            from oceanbase_tpu.server.config import canonical

            name, value = canonical(stmt.name, stmt.value)
            self.variables[name] = value
        return _ok()

    def _alter_system(self, stmt: ast.AlterSystemStmt) -> Result:
        if stmt.action == "set":
            self.db.config.set(stmt.name, stmt.value)
            return _ok()
        if stmt.action == "calibrate":
            # re-run the roofline probe suite on the live backend
            # (full ladder) and persist the refreshed machine constants
            if not bool(self.db.config["enable_calibration"]):
                raise ValueError(
                    "enable_calibration is off (ALTER SYSTEM SET "
                    "enable_calibration = true first)")
            from oceanbase_tpu.server import calibrate as qcalibrate

            units = qcalibrate.ensure_units(self.db.root, preset="full",
                                            force=True)
            self.db.cost_units = units
            names = ["backend", "peak_gflops", "peak_gbps",
                     "eff_gbps", "launch_overhead_us",
                     "rpc_s_per_byte", "probe_s"]
            vals = [units.backend,
                    f"{units.peak_flops_s / 1e9:.3f}",
                    f"{units.peak_bytes_s / 1e9:.3f}",
                    f"{units.eff_bytes_s / 1e9:.3f}",
                    f"{units.launch_overhead_s * 1e6:.2f}",
                    f"{units.rpc_s_per_byte:.3e}",
                    f"{units.probe_s:.3f}"]
            return Result(
                ["constant", "value"],
                {"constant": np.array(names, dtype=object),
                 "value": np.array(vals, dtype=object)},
                {}, {}, rowcount=len(names))
        eng = self._engine
        # flush at the horizon, not gts-now: versions newer than a live
        # transaction's snapshot must stay in the memtables or its
        # write-conflict check goes blind (lost update)
        snap = self._txsvc.flush_snapshot()
        for name in list(eng.tables):
            eng.freeze_and_flush(name, snapshot=snap)
            if stmt.action == "major_freeze":
                eng.major_compact(name)
        return _ok()

    def _load_data(self, stmt: ast.LoadDataStmt) -> Result:
        """LOAD DATA INFILE: CSV -> direct-load baseline segment
        (≙ src/storage/direct_load bypassing the memtable).  The hot path
        tokenizes + parses numerics in the native library; the python csv
        module is the fallback (and the quoting-semantics oracle)."""
        td = self.catalog.table_def(stmt.table)
        fast = self._load_data_native(stmt, td)
        if fast is not None:
            arrays, valids, n = fast
            return self._finish_load(stmt, td, arrays, valids, n)
        import csv

        cols = [[] for _ in td.columns]
        with open(stmt.path, newline="") as f:
            reader = csv.reader(f, delimiter=stmt.delimiter)
            for i, row in enumerate(reader):
                if i < stmt.skip_lines:
                    continue
                if len(row) != len(td.columns):
                    raise ValueError(
                        f"row {i + 1}: {len(row)} fields, expected "
                        f"{len(td.columns)}")
                for j, cell in enumerate(row):
                    cols[j].append(cell)
        n = len(cols[0]) if cols else 0
        arrays, valids = {}, {}
        for cdef, raw in zip(td.columns, cols):
            vals = []
            valid = np.ones(n, dtype=bool)
            for i, cell in enumerate(raw):
                if cell == "" or cell.upper() == "\\N":
                    valid[i] = False
                    vals.append("" if cdef.dtype.is_string else 0)
                    continue
                if cdef.dtype.is_string:
                    vals.append(cell)
                elif cdef.dtype.kind == TypeKind.DECIMAL:
                    v, t = literal_value(ir.Literal(cell, SqlType.decimal()))
                    vals.append(_rescale(v, t.scale, cdef.dtype.scale))
                elif cdef.dtype.kind == TypeKind.DATE:
                    from oceanbase_tpu.datatypes import date_to_days

                    vals.append(date_to_days(cell))
                elif cdef.dtype.kind in (TypeKind.FLOAT, TypeKind.DOUBLE):
                    vals.append(float(cell))
                else:
                    vals.append(int(cell))
            arrays[cdef.name] = (np.array(vals, dtype=object)
                                 if cdef.dtype.is_string
                                 else np.asarray(vals,
                                                 dtype=cdef.dtype.np_dtype))
            if not valid.all():
                valids[cdef.name] = valid
        return self._finish_load(stmt, td, arrays, valids, n)

    def _load_data_native(self, stmt, td):
        """Native CSV fast path -> (arrays, valids, n) or None to fall
        back (no native lib / ragged file / exotic types)."""
        from oceanbase_tpu import native
        from oceanbase_tpu.datatypes import DATE_EPOCH

        with open(stmt.path, "rb") as f:
            data = f.read()
        n_cols = len(td.columns)
        tok = native.csv_tokenize(data, n_cols, stmt.delimiter)
        if tok is None:
            return None
        buf, offsets, lengths, n_rows = tok
        if n_rows <= stmt.skip_lines:
            return {}, {}, 0
        start = stmt.skip_lines * n_cols
        offsets = offsets[start:]
        lengths = lengths[start:]
        n = n_rows - stmt.skip_lines
        arrays, valids = {}, {}

        def _check_numeric(valid, offs, lens, colname):
            # python-oracle semantics: garbage (non-empty, non-\N)
            # numeric cells ABORT the load instead of nulling silently
            empty = (lens & 0x7FFFFFFF) == 0
            suspicious = ~valid & ~empty
            if suspicious.any():
                idx = np.nonzero(suspicious)[0]
                cells = native.field_strings(
                    data, np.ascontiguousarray(offs[idx]),
                    np.ascontiguousarray(lens[idx]))
                for row_i, cell in zip(idx, cells):
                    if cell.upper() != "\\N":
                        raise ValueError(
                            f"row {int(row_i) + 1 + stmt.skip_lines}: "
                            f"invalid value {cell!r} for column "
                            f"{colname!r}")
            return valid

        for j, cdef in enumerate(td.columns):
            offs = np.ascontiguousarray(offsets[j::n_cols])
            lens = np.ascontiguousarray(lengths[j::n_cols])
            k = cdef.dtype.kind
            if k == TypeKind.INT:
                out, valid = native.parse_int64_fields(buf, offs, lens, 0)
                valid = _check_numeric(valid, offs, lens, cdef.name)
                arrays[cdef.name] = out
            elif k == TypeKind.DECIMAL:
                out, valid = native.parse_int64_fields(
                    buf, offs, lens, cdef.dtype.scale)
                valid = _check_numeric(valid, offs, lens, cdef.name)
                arrays[cdef.name] = out
            elif k == TypeKind.DATE:
                strs = native.field_strings(data, offs, lens)
                valid = np.array([s != "" and s.upper() != "\\N"
                                  for s in strs])
                days = np.zeros(n, dtype=np.int32)
                if valid.any():
                    d64 = np.array(
                        [s if v else "1970-01-01"
                         for s, v in zip(strs, valid)],
                        dtype="datetime64[D]")
                    days = (d64 - DATE_EPOCH).astype(np.int32)
                arrays[cdef.name] = days
            elif k in (TypeKind.FLOAT, TypeKind.DOUBLE):
                strs = native.field_strings(data, offs, lens)
                valid = np.array([s != "" and s.upper() != "\\N"
                                  for s in strs])
                vals = np.zeros(n, dtype=cdef.dtype.np_dtype)
                for i, (sv, v) in enumerate(zip(strs, valid)):
                    if v:
                        try:
                            vals[i] = float(sv)
                        except ValueError:
                            raise ValueError(
                                f"row {i + 1 + stmt.skip_lines}: invalid "
                                f"value {sv!r} for column "
                                f"{cdef.name!r}") from None
                arrays[cdef.name] = vals
            elif cdef.dtype.is_string:
                strs = native.field_strings(data, offs, lens)
                valid = np.array([s != "" and s != "\\N" for s in strs])
                arrays[cdef.name] = strs
            else:
                return None  # exotic type: python fallback handles it
            if not valid.all():
                valids[cdef.name] = valid
        return arrays, valids, n

    def _finish_load(self, stmt, td, arrays, valids, n) -> Result:
        if n:
            self._engine.bulk_load(stmt.table, arrays, valids or None,
                                   version=self._txsvc.gts.get_ts())
        self.catalog.invalidate(stmt.table)
        td.row_count = self._engine.tables[stmt.table] \
            .tablet.row_count_estimate()
        return _ok(rowcount=n)

    def _truncate(self, stmt: ast.TruncateStmt) -> Result:
        """TRUNCATE TABLE: DDL semantics — implicit commit of the open
        transaction (MySQL), exclusive table lock so live transactions'
        redo lands BEFORE the WAL barrier, fresh tablet, counters reset."""
        td = self.catalog.table_def(stmt.table)  # existence check
        if self._tx is not None:
            self._txsvc.commit(self._tx)  # DDL implies COMMIT
            self._tx = None
        tx = self._txsvc.begin()
        try:
            # blocks until every live writer of the table finishes,
            # so their (group-committed) redo precedes the barrier
            self.tenant.locks.acquire(stmt.table, "X", tx.tx_id,
                                      timeout=30.0)
            lsn = self._txsvc._log_batch(
                [{"op": "truncate", "table": stmt.table}])
            self._engine.truncate_table(stmt.table, wal_lsn=lsn)
            # MySQL: TRUNCATE resets AUTO_INCREMENT
            for cname in getattr(td, "auto_increment_cols", []):
                seq = f"__ai_{stmt.table}_{cname}"
                self.tenant.sequences.drop(seq)
                self.tenant.sequences.create(seq, start=1)
        finally:
            self._txsvc.commit(tx)  # releases the lock
        self.catalog.invalidate(stmt.table)
        return _ok()

    def _lock_table(self, stmt: ast.LockTableStmt) -> Result:
        """LOCK TABLES t READ|WRITE / UNLOCK TABLES (≙ tablelock as a tx
        operation; MySQL-flavored syntax)."""
        if stmt.unlock:
            if self._tx is not None:
                self.tenant.locks.release_all(self._tx.tx_id)
                if not self._tx.participants:
                    # lock-only implicit tx: end it so later autocommit
                    # DML doesn't silently ride (and lose) it
                    self._txsvc.commit(self._tx)
                    self._tx = None
            return _ok()
        if self._tx is None:
            self._tx = self._txsvc.begin()  # implicit tx holds the lock
        self.tenant.locks.acquire(stmt.table, stmt.mode, self._tx.tx_id)
        return _ok()

    def _maybe_freeze(self, table: str):
        """Memstore-pressure freeze: active memtable beyond the configured
        row budget flushes to L0 (≙ freeze trigger + write throttling)."""
        ts = self._engine.tables.get(table)
        if ts is None:
            return
        limit = int(self.tenant.config["memstore_limit_rows"])
        if len(ts.tablet.active) >= limit:
            # the foreground statement pays for the flush (and, at the
            # trigger, a minor compaction): a span of its own
            with qtrace.span("storage.freeze", table=table,
                             rows=len(ts.tablet.active)) as sp:
                # horizon-clamped: see _alter_system major_freeze
                self._engine.freeze_and_flush(
                    table, snapshot=self._txsvc.flush_snapshot())
                l0 = sum(1 for s in ts.tablet.segments if s.level == 0)
                compact = \
                    l0 >= int(self.tenant.config["minor_compact_trigger"])
                sp.tags.update(l0_segments=l0, compacted=int(compact))
                if compact:
                    self._engine.minor_compact(table)

    def _analyze_workload(self, stmt: ast.AnalyzeWorkloadStmt) -> Result:
        """ANALYZE WORKLOAD REPORT [FROM <id> TO <id>]: build (and
        remember) the delta report between two workload snapshots.
        Without ids, a fresh cluster-merged snapshot is taken as the TO
        side and the previous one is the FROM side, so the statement
        works with the background thread off.  The structured rows come
        back directly (the same shape gv$workload_report serves);
        SHOW WORKLOAD REPORT renders the text tree."""
        rep = self.db.workload.build_report(stmt.from_id, stmt.to_id)
        rows = rep["rows"]
        return Result(
            ["section", "item", "value", "detail"],
            {"section": np.array([r["section"] for r in rows],
                                 dtype=object),
             "item": np.array([r["item"] for r in rows], dtype=object),
             "value": np.array([r["value"] for r in rows], np.float64),
             "detail": np.array([r["detail"] for r in rows],
                                dtype=object)},
            {}, {"section": SqlType.string(), "item": SqlType.string(),
                 "detail": SqlType.string()}, rowcount=len(rows))

    def _show_workload_report(self) -> Result:
        """SHOW WORKLOAD REPORT: the last ANALYZE WORKLOAD REPORT's
        indented text tree, one row per line (SHOW TRACE's style)."""
        rep = self.db.workload.last_report
        lines = rep["text"].split("\n") if rep else []
        return Result(
            ["report"],
            {"report": np.array(lines, dtype=object)},
            {}, {"report": SqlType.string()}, rowcount=len(lines))

    def _analyze(self, stmt: ast.AnalyzeStmt) -> Result:
        """Refresh optimizer stats for a table: row count, NDV,
        equi-height histograms for non-string columns, and
        most-common-values frequency lists for dict-encoded string
        columns (≙ DBMS_STATS gather, src/share/stat/
        ob_opt_column_stat.h top-k frequency histogram).  The columns
        stay on the device: programs over them answer (sql/table_stats
        .py), and only counts, edges and per-code rows cross."""
        from oceanbase_tpu.sql import table_stats as ts

        td = self.catalog.table_def(stmt.table)
        with qtrace.span("analyze", table=stmt.table) as sp:
            rel = self.catalog.table_data(stmt.table)
            valid_rows, n = ts.live_counts(rel)
            sp.tags["rows"] = n
            td.row_count = n
            mask = rel.mask_or_true()
            for c in td.columns:
                col = rel.columns.get(c.name)
                if col is None:
                    continue
                with qtrace.span("analyze.column", column=c.name,
                                 on_device=int(ts.on_device(col))):
                    ndv, detail = ts.column_stats(
                        col, mask, n, valid_rows[c.name])
                td.ndv[c.name] = ndv
                # a dictionary column's frequency list, any other's
                # histogram; one that no longer qualifies drops its
                # stale one (it must not keep feeding selectivity)
                store = td.mcv if col.sdict is not None else td.histograms
                if detail is None:
                    store.pop(c.name, None)
                    td.samples.pop(c.name, None)
                elif col.sdict is not None:
                    store[c.name] = detail[:2]
                    td.samples[c.name] = detail[2]
                else:
                    store[c.name] = detail
        qmetrics.inc("storage.analyze_ns", int(sp.elapsed_s * 1e9))
        return _ok()

    def _describe_view(self, name: str) -> Result:
        """DESCRIBE on a view: expand the body through the binder and
        derive output names/types by running the plan over EMPTY typed
        relations — a metadata command must not scan the view's base
        tables.  Nullability/keys are not defined for a derived
        relation."""
        from oceanbase_tpu.exec.plan import referenced_tables
        from oceanbase_tpu.vector import empty_relation

        def typed(t):
            td = self.catalog.table_def(t)
            return empty_relation({c.name: c.dtype for c in td.columns})

        plan, outputs, _est = self._plan_select(
            parse_sql(f"select * from {name}"), None)
        dtables = {t: typed(t) for t in referenced_tables(plan)
                   if self.catalog.has_table(t)}
        self._prepare_index_probes(plan, dtables)
        rel = execute_plan(plan, dtables, check_overflow=False)
        names, types = [], []
        for cid, oname in outputs:
            out_name, k = oname, 2
            while out_name in names:
                out_name = f"{oname}_{k}"
                k += 1
            names.append(out_name)
            t = rel.columns[cid].dtype
            types.append(str(t) if t is not None else "")
        return Result(
            ["field", "type", "null", "key"],
            {"field": np.array(names, dtype=object),
             "type": np.array(types, dtype=object),
             "null": np.array(["YES"] * len(names), dtype=object),
             "key": np.array([""] * len(names), dtype=object)},
            {}, {}, rowcount=len(names))

    def _show_metrics(self) -> Result:
        """SHOW METRICS: the cluster-merged scrape rendered as
        Prometheus text exposition, one line per row (the same dump
        ``metrics.scrape(format="prom")`` serves over the wire)."""
        lines = qmetrics.prom_text(
            self.db.virtual_tables.scrape_cluster()).splitlines()
        return Result(
            ["metric"],
            {"metric": np.array(lines, dtype=object)},
            {}, {"metric": SqlType.string()}, rowcount=len(lines))

    def _profile(self, stmt: ast.ProfileStmt, params=None) -> Result:
        """PROFILE <statement>: execute it under a jax.profiler device
        trace; parsed per-kernel rows land in gv$device_profile keyed
        by this statement's trace_id (SHOW PROFILE shows them).  The
        statement's own result (and errors) pass through unchanged;
        backends without a profiler degrade to a note."""
        from oceanbase_tpu.server import profiler as qprofiler

        store = self.db.device_profiles
        if store is None or not bool(self.db.config["enable_profiling"]):
            # no store / knob off: run the statement, skip the capture
            return self.execute_stmt(stmt.stmt, params)
        tctx = qtrace.current()
        if tctx is not None:
            trace_id = tctx.trace_id
        else:
            # query tracing off: mint a standalone capture id so the
            # gv$device_profile rows stay joinable (to each other and
            # to SHOW PROFILE), just not to gv$trace/gv$sql_audit
            import uuid

            trace_id = uuid.uuid4().hex[:16]
        sql = self._ash_state.get("sql", "")
        out, rows, note = qprofiler.profile_statement(
            lambda: self.execute_stmt(stmt.stmt, params))
        store.record(qprofiler.make_profile(trace_id, sql, rows, note))
        self._last_profile_trace_id = trace_id
        return out

    def _show_profile(self) -> Result:
        """SHOW PROFILE: this session's most recent PROFILE capture as
        per-kernel rows (total/avg time, share of device time)."""
        store = self.db.device_profiles
        tid = getattr(self, "_last_profile_trace_id", "")
        prof = store.get(tid) if (store is not None and tid) else None
        rows = prof.rows if prof is not None else []
        note = prof.note if prof is not None else \
            "no PROFILE captured in this session"
        if not rows and note:
            rows = [{"device": "", "kernel": f"({note})", "kind": "note",
                     "occurrences": 0, "total_s": 0.0, "avg_s": 0.0,
                     "pct": 0.0}]
        return Result(
            ["device", "kernel", "kind", "occurrences", "total_ms",
             "avg_us", "pct_device"],
            {"device": np.array([r["device"] for r in rows],
                                dtype=object),
             "kernel": np.array([r["kernel"] for r in rows],
                                dtype=object),
             "kind": np.array([r["kind"] for r in rows], dtype=object),
             "occurrences": np.array([r["occurrences"] for r in rows],
                                     np.int64),
             "total_ms": np.array([r["total_s"] * 1e3 for r in rows],
                                  np.float64),
             "avg_us": np.array([r["avg_s"] * 1e6 for r in rows],
                                np.float64),
             "pct_device": np.array([r["pct"] for r in rows],
                                    np.float64)},
            {}, {"device": SqlType.string(), "kernel": SqlType.string(),
                 "kind": SqlType.string()}, rowcount=len(rows))

    def _show_trace(self) -> Result:
        """SHOW TRACE: the last kept statement trace rendered as an
        indented span tree (≙ SHOW TRACE over the flt span store).
        Remote spans (node != coordinator) sit under the rpc span that
        carried them.  Empty when the last statement's trace was sampled
        away — raise trace_sample_rate (slow statements always keep)."""
        import json as _json

        self._stmt_is_show_trace = True  # don't clobber _last_trace_id

        cols = ["operation", "node", "start_ts", "elapsed_ms", "tags"]

        def result(rows):
            return Result(
                cols,
                {"operation": np.array([r[0] for r in rows], dtype=object),
                 "node": np.array([r[1] for r in rows], np.int64),
                 "start_ts": np.array([r[2] for r in rows], np.float64),
                 "elapsed_ms": np.array([r[3] for r in rows], np.float64),
                 "tags": np.array([r[4] for r in rows], dtype=object)},
                {}, {"operation": SqlType.string(),
                     "tags": SqlType.string()}, rowcount=len(rows))

        tid = self._last_trace_id
        spans = self.db.trace_registry.trace(tid) if tid else []
        if not spans:
            return result([])
        by_parent: dict[int, list] = {}
        ids = {s.span_id for s in spans}
        for s in spans:
            # a span whose parent was not captured here (e.g. pruned by
            # ring wraparound) renders as a root
            key = s.parent_id if s.parent_id in ids else 0
            by_parent.setdefault(key, []).append(s)
        for kids in by_parent.values():
            kids.sort(key=lambda s: (s.start_ts, s.span_id))
        rows: list = []
        seen: set = set()

        def walk(s, depth):
            if s.span_id in seen:
                return  # defensive: a malformed remote parent loop
            seen.add(s.span_id)
            rows.append((("  " * depth) + s.name, s.node, s.start_ts,
                         s.elapsed_s * 1000.0,
                         _json.dumps(s.tags, sort_keys=True, default=str)
                         if s.tags else ""))
            for c in by_parent.get(s.span_id, ()):
                walk(c, depth + 1)

        for root in by_parent.get(0, ()):
            walk(root, 0)
        return result(rows)

    # ------------------------------------------------------------------
    def _cost_model(self):
        """CBO pricing context for this statement: THIS database's
        measured gv$cost_units roofline (process fallback inside
        CostModel when absent) with gv$time_calibration per-operator
        corrections folded in — corrections are clamped and require a
        few observations, so one wild early sample cannot poison every
        later plan choice."""
        from oceanbase_tpu.sql.optimizer import CostModel

        corrections: dict = {}
        tc = self.db.time_calibration
        if tc is not None:
            for r in tc.rows():
                if r["count"] >= 3 and r["correction"] > 0.0:
                    corrections[r["op"]] = min(
                        max(float(r["correction"]), 0.25), 8.0)
        return CostModel(units=self.db.cost_units, corrections=corrections)

    def _plan_select(self, stmt: ast.SelectStmt, params):
        binder = Binder(self.catalog, params=params or [],
                        sequences=self.tenant.sequences,
                        sysvars=self.variables)
        binder.cost_model = self._cost_model()
        out = binder.bind_select(stmt)
        self._last_cbo_choices = list(binder.cbo_choices)
        return out

    def _plan_select_cached(self, sql_key: str, stmt, params):
        """Plan-cache probe (≙ ObPlanCache::get_plan): bound plans keyed by
        statement text + schema version; parameter values bind as literals
        so parameterized statements share one entry only when identical.
        Plans that folded volatile or data-dependent values at bind time
        (nextval, eagerly-executed scalar subqueries) never cache."""
        key = (sql_key, tuple(params or []), self.catalog.schema_version)
        hit = self.plan_cache.get(key)
        if hit is not None:
            self.plan_cache.move_to_end(key)  # LRU touch
            qmetrics.inc("plan_cache.hits")
            # the gv$plan_choice row was recorded at the original bind;
            # a cache hit only re-executes the already-chosen plan
            self._last_cbo_choices = []
            return hit
        qmetrics.inc("plan_cache.misses")
        binder = Binder(self.catalog, params=params or [],
                        sequences=self.tenant.sequences,
                        sysvars=self.variables)
        binder.cost_model = self._cost_model()
        out = binder.bind_select(stmt)
        self._last_cbo_choices = list(binder.cbo_choices)
        if not binder.folded_volatile:
            self._plan_cache_put(key, out)
        return out

    # session plan-cache sizing: entries are python plan trees whose
    # live-object footprint far exceeds their repr — the fingerprint
    # length tracks node count (~100 chars/node), and each dataclass
    # node with its expr objects costs on the order of 1KB, so charge
    # ~10 bytes of estimate per fingerprint char plus a fixed overhead
    _PLAN_ENTRY_OVERHEAD = 2048
    _PLAN_BYTES_PER_CHAR = 10
    _PLAN_CACHE_MAX_ENTRIES = 4096  # backstop against tiny-entry floods

    def _plan_cache_put(self, key, out):
        """Insert with real LRU eviction (oldest first) honoring
        ``plan_cache_mem_limit`` (and an entry-count backstop)."""
        try:
            fp = out[0].fingerprint()
        except Exception:
            fp = ""
        nbytes = self._PLAN_ENTRY_OVERHEAD + \
            self._PLAN_BYTES_PER_CHAR * (len(str(key[0])) + len(fp))
        limit = int(self.db.config["plan_cache_mem_limit"])
        if nbytes > limit:
            return  # a single over-budget plan is not cacheable
        old = self._plan_cache_bytes.pop(key, None)
        if old is not None:
            self._plan_cache_total -= old
            self.plan_cache.pop(key, None)
        self.plan_cache[key] = out
        self._plan_cache_bytes[key] = nbytes
        self._plan_cache_total += nbytes
        while self.plan_cache and (
                self._plan_cache_total > limit
                or len(self.plan_cache) > self._PLAN_CACHE_MAX_ENTRIES):
            k, _ = self.plan_cache.popitem(last=False)
            self._plan_cache_total -= self._plan_cache_bytes.pop(k, 0)
            qmetrics.inc("plan_cache.evictions")

    def _table_snapshot(self, name: str):
        """Read a table at the right snapshot: an active transaction sees
        its own writes plus its begin-snapshot; otherwise latest committed
        (cached device relation)."""
        if self._tx is not None:
            return self.catalog.table_data_at(
                name, self._tx.snapshot, self._tx.tx_id)
        return self.catalog.table_data(name)

    def _execute_select(self, stmt: ast.SelectStmt, params) -> Result:
        from oceanbase_tpu.exec.plan import referenced_tables

        use_cache = (bool(self.db.config["enable_plan_cache"])
                     and self._ash_state.get("sql"))
        tb0 = time.monotonic()
        with qtrace.span("compile", cached=int(bool(use_cache))):
            if use_cache:
                plan, outputs, _est = self._plan_select_cached(
                    self._ash_state["sql"], stmt, params)
            else:
                plan, outputs, _est = self._plan_select(stmt, params)
        # the bind window (parse → logical plan → CBO) is the first
        # host phase of the statement's time model: the compile span's
        # self time is bind_s (trace.PHASE_OF)
        self._last_compile_s = time.monotonic() - tb0
        from oceanbase_tpu.exec.plan import logical_hash as _lhash_of
        from oceanbase_tpu.sql.optimizer import apply_feedback

        monitor = None
        mon_collect = True
        with qtrace.span("plan.prepare"):
            # cardinality feedback (gv$plan_feedback): a logical plan
            # whose operators were observed bigger than their static
            # budgets starts at the observed capacity bucket instead of
            # re-riding the CapacityOverflow retry ladder (≙ plan
            # evolution consulting measured stats).  Keyed by the
            # capacity-insensitive hash so the corrected plan keeps
            # matching its own history.
            lhash = _lhash_of(plan)
            if self.db.plan_choice is not None \
                    and getattr(self, "_last_cbo_choices", None):
                # bind-time CBO beliefs land in gv$plan_choice; the
                # measured device seconds fold in below once the plan
                # has run
                self.db.plan_choice.record(lhash, self._last_cbo_choices)
            feedback_on = bool(self.db.config["enable_plan_feedback"])
            if feedback_on:
                corr = self.db.plan_feedback.corrections(lhash)
                if corr:
                    qmetrics.inc("plan.feedback_hits")
                    plan, n_fixed = apply_feedback(plan, corr)
                    if n_fixed:
                        qmetrics.inc("plan.feedback_corrections", n_fixed)
            # estimate-driven spill route (≙ the SQL memory manager
            # deciding spill from work-area estimates BEFORE execution):
            # over-budget inputs never materialize whole on device
            big = self._spill_candidates(plan)
            if self.db.config["enable_sql_plan_monitor"]:
                # sampled ledger collection: every execution runs the
                # SAME monitored executable (the variant is part of the
                # compile key — alternating it would double the plan's
                # XLA trace count and break the shape-bucket
                # amortization invariant); unsampled executions merely
                # skip the host transfer and the ledger record
                monitor = []
                mon_collect = self.db.plan_monitor.should_record(
                    lhash,
                    int(self.db.config["plan_monitor_sample_every"]))
        if big:
            res = self._try_spilled(plan, outputs, big)
            if res is not None:
                return res
        tables: dict | None = None  # device relations, built lazily

        def local_tables():
            # deferred until a non-pushdown path needs them: DTL reads
            # tablet snapshots on the data nodes itself, so a pushed-down
            # query must not pay the full host->device materialization
            nonlocal tables
            if tables is None:
                with qtrace.span("tables"):
                    tables = {t: self._table_snapshot(t)
                              for t in referenced_tables(plan)
                              if self.catalog.has_table(t)}
                    self._try_ann_prefilter(plan, tables)
                    self._last_access_paths = self._index_prefilter(
                        plan, tables)
                    self._prepare_index_probes(plan, tables)
            return tables

        self._last_access_paths = {}
        dop = self._px_dop()
        factor = 1
        # PX exchange budgets this statement's earlier overflows raised
        # ({overflow lane: factor}, kept by logical plan for the session)
        px_budgets = dict(self._px_budgets.get(lhash, ()))
        from oceanbase_tpu.exec.plan import (
            compile_flag,
            reset_compile_flag,
        )

        reset_compile_flag()
        t0 = time.monotonic()  # plan-monitor total_s (step-proof delta)
        self._last_px = False  # did the last query run through PX?
        self._last_dtl = False  # did it push down over the DTL exchange?
        self._last_px_downgrade = False  # px admission denied -> serial
        # cross-node compute pushdown (px/dtl.py): ship the partial plan
        # to the cluster's data nodes instead of scanning everything on
        # this node; an open transaction keeps the own-writes read path
        dtl = self.db.dtl if self._tx is None else None
        from oceanbase_tpu.server import admission as qadmission

        with qtrace.span("execute") as xsp:
            for attempt in range(
                    int(self.variables["max_capacity_retry"]) + 1):
                # retry-ladder checkpoint: a killed/expired statement
                # must not re-plan and re-execute with bigger budgets
                qadmission.checkpoint()
                try:
                    p = plan if factor == 1 \
                        else scale_capacities(plan, factor)
                    rel = None
                    if dtl is not None:
                        try:
                            rel = dtl.try_execute(p, monitor=monitor,
                                                  collect=mon_collect)
                        except CapacityOverflow:
                            raise  # remote overflow: re-plan with 4x
                        except Exception:
                            rel = None  # exchange surprise -> serial
                        self._last_dtl = rel is not None
                    if rel is None and dop > 1:
                        # a re-plan after an overflow has a span of its
                        # own: a new shard program is compiled inside it
                        with qtrace.span("px.replan", attempt=attempt,
                                         raised=len(px_budgets)) \
                                if attempt else contextlib.nullcontext():
                            rel = self._try_px(
                                p, local_tables(), dop, factor=factor,
                                monitor=monitor if mon_collect else None,
                                budgets=px_budgets)
                        self._last_px = rel is not None
                    if rel is None:
                        rel = execute_plan(p, local_tables(),
                                           monitor_out=monitor,
                                           monitor_collect=mon_collect)
                    break
                except CapacityOverflow as ovf:
                    if attempt >= \
                            int(self.variables["max_capacity_retry"]):
                        # backstop: re-plan retries exhausted -> disk
                        # spill tier, largest input as the stream
                        big = self._spill_candidates(
                            plan, force_largest=True)
                        res = (self._try_spilled(plan, outputs, big)
                               if big else None)
                        if res is not None:
                            return res
                        raise
                    qmetrics.inc("plan.capacity_retries")
                    # the overflow report carries (lane, static cap,
                    # rows dropped): with feedback on, jump straight to
                    # a clearing budget instead of riding the blind 4x
                    # ladder; a build side that repeated its key gives
                    # up its joins' marks, not its budgets
                    from oceanbase_tpu.px.planner import EXCHANGE_LANE
                    from oceanbase_tpu.sql.optimizer import (
                        after_overflow,
                        overflow_jump_factor,
                    )

                    drops = getattr(ovf, "drops", None) or []
                    if drops and all(d[0].startswith(EXCHANGE_LANE)
                                     for d in drops):
                        # only exchange budgets overflowed, and each says
                        # which: those alone grow; the plan, its other
                        # budgets and its build_unique marks stay
                        for d in drops:
                            px_budgets[d[0]] = px_budgets.get(d[0], 1) \
                                * overflow_jump_factor([d])
                        self._px_budgets[lhash] = px_budgets
                    else:
                        plan, step = after_overflow(plan, drops,
                                                    jump=feedback_on)
                        factor *= step
                    if monitor is not None:
                        monitor.clear()
            xsp.tags.update(attempts=attempt + 1, factor=factor,
                            dtl=int(self._last_dtl),
                            px=int(self._last_px))
            if self._last_px_downgrade:
                # the satellite: a px_admission denial is visible on
                # the statement's trace span, not silently serial
                xsp.tags["px_downgrade"] = 1
        exec_elapsed = time.monotonic() - t0
        with qtrace.span("plan.record"):
            if attempt > 0 and use_cache:
                # evolve the cached plan: a plan bound against a smaller
                # table keeps overflowing its stale capacity budgets
                # (and one whose build side repeats its key keeps
                # finding that out), which would replay the whole
                # (device-executing) retry ladder on EVERY later
                # execution — cache the successfully re-planned one in
                # its place so the next run starts where this one ended
                key = (self._ash_state["sql"], tuple(params or []),
                       self.catalog.schema_version)
                if key in self.plan_cache:
                    self._plan_cache_put(key, (p, outputs, _est))
            path = ("dtl" if self._last_dtl
                    else "px" if self._last_px else "serial")
            if monitor is not None and mon_collect:
                # roofline prediction vs the measured device half of
                # this statement (server/calibrate.py): the TIME q-error
                # beside the cardinality one, aggregated per
                # root-operator type into gv$time_calibration for the
                # CBO arc
                times, pred_s, time_q = self._roofline(plan)
                self.db.plan_monitor.record(
                    plan.fingerprint()[:64]
                    if hasattr(plan, "fingerprint")
                    else "", monitor, exec_elapsed,
                    logical_hash=lhash, retries=attempt, path=path,
                    host_s=times.host_s, device_s=times.device_s,
                    pred_s=pred_s, time_q=time_q)
                if self.db.plan_choice is not None:
                    # validate the CHOICE, not just the plan: measured
                    # device seconds against the bind-time prediction
                    self.db.plan_choice.observe(lhash, times.device_s)
                if feedback_on and monitor and path == "serial":
                    # teach the feedback store from the serial ledger
                    # only: PX/DTL rows are positioned against rewritten
                    # plans, so their postorder would not line up with
                    # future binds
                    self.db.plan_feedback.observe(lhash, monitor)
            base = self.db.plan_history.baseline_s(lhash)
            if base > 0.0 and exec_elapsed > base * qtrace.SLOW_FACTOR:
                # far over its own baseline (a stall, not a slow
                # plan): the tree goes to the slow ring, which fast
                # statements cannot evict
                tctx = qtrace.current()
                if tctx is not None:
                    tctx.slow = True
            if attempt == 0 and not compile_flag() and \
                    self.db.plan_history.record(
                        lhash, exec_elapsed,
                        float(self.db.config["plan_regress_threshold"])):
                # plan-regression watchdog: latency baselines per
                # logical hash, independent of the plan-monitor knob
                # (a regression must be visible even when per-op
                # collection is off).  Samples that paid an XLA
                # compile or a CapacityOverflow retry replay are
                # excluded — they measure one-time plan work, not
                # the plan's steady-state latency, and would inflate
                # the frozen baseline (blinding the watchdog) or
                # spike the EWMA into a false regressed flag
                qmetrics.inc("plan.regressions")
        with qtrace.span("materialize") as msp:
            return self._materialize(rel, outputs, msp.tags)

    # -- ANN top-k access path (vector index) ---------------------------
    _ANN_FETCH_FACTOR = 4

    def _try_ann_prefilter(self, plan, tables):
        """ORDER BY <distance>(vcol, '[...]') [ASC] LIMIT k over a
        single vector-indexed scan: replace the scanned relation with
        the index's top candidates, so the unchanged plan re-sorts a
        handful of rows instead of the whole table (≙ the vector-index
        access path lowering ORDER BY distance APPROXIMATE LIMIT k onto
        the ANN index; exact for small tables, IVF recall above).

        The substitution is APPROXIMATE by design for IVF (matching the
        reference's approximate vector search semantics); small tables
        search exactly, making the result identical to the full sort."""
        from oceanbase_tpu.exec import plan as pp
        from oceanbase_tpu.expr import ir as _ir

        if not isinstance(plan, pp.Limit):
            return
        node = plan.child
        k = plan.k + (plan.offset or 0)
        if not isinstance(node, pp.Sort) or len(node.keys) != 1 or \
                not (node.ascending[0] if node.ascending else True):
            return
        key = node.keys[0]
        if not isinstance(key, _ir.ColumnRef):
            return
        # resolve the sort column through Project/Compact to the scan
        expr, cur = None, node.child
        while True:
            if isinstance(cur, pp.Project):
                if expr is None:
                    expr = cur.outputs.get(key.name)
                    if expr is None:
                        return
                else:
                    # nested projects would need substitution; keep the
                    # simple shape
                    return
                cur = cur.child
            elif isinstance(cur, pp.Compact):
                cur = cur.child
            else:
                break
        if not isinstance(cur, pp.TableScan) or expr is None:
            return
        if not isinstance(expr, _ir.FuncCall) or expr.name.lower() not in \
                ("l2_distance", "cosine_distance"):
            return
        args = expr.args
        colref = next((a for a in args if isinstance(a, _ir.ColumnRef)),
                      None)
        lit = next((a for a in args if isinstance(a, _ir.Literal)
                    and isinstance(a.value, str)), None)
        if colref is None or lit is None:
            return
        inv = {cid: base for base, cid in (cur.rename or {}).items()}
        base_col = inv.get(colref.name, colref.name)
        td = self.catalog.table_def(cur.table)
        metric = {"l2_distance": "l2",
                  "cosine_distance": "cosine"}[expr.name.lower()]
        vix = next((v for v in td.aux_indexes.values()
                    if v["kind"] == "vector" and v["column"] == base_col
                    and v["metric"] == metric), None)
        if vix is None:
            return
        rel = tables.get(cur.table)
        if rel is None:
            return
        import numpy as _np

        n_live = (rel.capacity if rel.mask is None
                  else int(_np.asarray(rel.mask).sum()))
        if n_live <= max(k * self._ANN_FETCH_FACTOR, 64):
            return
        from oceanbase_tpu.expr.compile import parse_vector_text

        q = parse_vector_text(lit.value)[None, :]
        idx = self._ann_runtime(cur.table, base_col, metric, rel)
        fetch = min(max(k * self._ANN_FETCH_FACTOR, 64), n_live)
        if idx is None:
            return
        import numpy as _np

        if hasattr(idx, "search"):
            _s, ids = idx.search(q, fetch)
        else:
            from oceanbase_tpu.share.vector_index import exact_search

            _s, ids = exact_search(q, idx, fetch, metric=metric)
        rows = _np.asarray(ids)[0]
        rows = rows[rows >= 0]
        if len(rows) == 0:
            return
        take = jnp.asarray(_np.sort(rows))
        mask = None
        if rel.mask is not None:
            mask = jnp.take(rel.mask, take)
        tables[cur.table] = rel.gather(take, mask)

    def _ann_runtime(self, table: str, col: str, metric: str, rel):
        """Lazily (re)built ANN structure for (table, col): IVF-Flat
        above IVF_MIN_ROWS, the raw vector matrix (exact matmul search)
        below.  Keyed by data_version so DML invalidates."""
        import numpy as _np

        from oceanbase_tpu.share.vector_index import IvfFlatIndex

        cache = getattr(self.catalog, "_ann_cache", None)
        if cache is None:
            cache = self.catalog._ann_cache = {}
        ts = self._engine.tables.get(table)
        if ts is not None:
            ver = ts.tablet.data_version
        else:
            # no tablet (external, transient): set_data replaces the
            # Relation object, so its identity is the data version
            ver = id(rel)
        key = (table, col, metric)
        hit = cache.get(key)
        if hit is not None and hit[0] == ver:
            return hit[1]
        colv = rel.columns.get(col)
        if colv is None or _np.asarray(colv.data).ndim != 2:
            return None
        vecs = _np.asarray(colv.data)
        if rel.mask is not None:
            m = _np.asarray(rel.mask)
            n_live = int(m.sum())
            if not bool(m[:n_live].all()):
                # interior dead rows would need an id remap; skip
                # (bucket padding is a dead SUFFIX, which slices clean)
                return None
            vecs = vecs[:n_live]
        # IVF (approximate recall) ONLY when the index opted in with
        # WITH (approximate = true) — index DDL must never silently
        # change the answers of an unchanged exact query
        td = self.catalog.table_def(table)
        approx = any(v["kind"] == "vector" and v["column"] == col
                     and v.get("options", {}).get("approximate")
                     for v in td.aux_indexes.values())
        idx = IvfFlatIndex(vecs, metric=metric) \
            if approx and len(vecs) >= 4096 else jnp.asarray(vecs)
        # the cache entry holds the source Relation too: identity-keyed
        # versions (tables without a tablet) must keep the object alive or a
        # recycled id would serve a stale index
        cache[key] = (ver, idx, rel)
        return idx

    def _prepare_index_probes(self, plan, tables):
        """Inject the sorted index sidecars every IndexProbe in the plan
        reads (exec/plan.py::prepare_index_probes does the work; the
        cache lives on the catalog keyed by source-relation identity)."""
        from oceanbase_tpu.exec.plan import prepare_index_probes

        prepare_index_probes(self.catalog, plan, tables)

    def _index_prefilter(self, plan, tables) -> dict:
        """Candidate-superset access paths (sql/access_path.py): replace
        a filtered table's device relation with a small host-pruned
        candidate set.  The plan re-applies its full filter, so the
        substitution never changes results — only how few rows reach the
        device.  -> {table: AccessChoice} for EXPLAIN."""
        if not tables:
            return {}
        if not bool(self.variables.get("enable_index_access", 1)):
            return {}
        from oceanbase_tpu.sql import access_path as ap

        try:
            by_table = ap.scan_filter_ranges(plan, self._engine)
        except Exception:
            return {}
        choices: dict = {}
        for t, ranges in by_table.items():
            if t not in tables or t not in self._engine.tables:
                continue
            choice = ap.choose_path(self._engine, t, ranges)
            if choice is None:
                continue
            if self._tx is not None:
                snap, txid = self._tx.snapshot, self._tx.tx_id
            else:
                snap, txid = self._txsvc.gts.current(), 0
            try:
                arrays, valids = ap.materialize_candidates(
                    self._engine, choice, snap, txid)
            except Exception:
                continue  # any surprise -> keep the full-table path
            tables[t] = self._candidate_relation(
                self._engine.tables[t], arrays, valids)
            choices[t] = choice
        return choices

    @staticmethod
    def _candidate_relation(ts, arrays, valids):
        """Host candidate arrays -> device Relation padded onto the shared
        capacity-bucket ladder (bounds jit-cache entries) with a live-row
        mask."""
        from oceanbase_tpu.vector import bucket_capacity

        n = len(next(iter(arrays.values()))) if arrays else 0
        # padded on the HOST: a device-side pad is one small program per
        # (row count, pad) pair, i.e. per statement
        pad = bucket_capacity(n) - n

        def padded(a, fill):
            a = np.asarray(a)
            tail = np.full((pad,) + a.shape[1:], fill, dtype=a.dtype)
            return np.concatenate([a, tail])

        rel = from_numpy(
            {c: padded(a, "" if np.asarray(a).dtype.kind in "OUS" else 0)
             for c, a in arrays.items()},
            types={c.name: c.dtype for c in ts.tdef.columns},
            valids={k: padded(v, False) for k, v in valids.items()
                    if v is not None})
        return Relation(columns=rel.columns,
                        mask=jnp.asarray(np.arange(n + pad) < n))

    def _px_dop(self) -> int:
        """Effective degree of parallelism.  A session px_dop wins over the
        config default; setting it to 0/1 EXPLICITLY forces serial
        execution (≙ the /*+ no_parallel */ hint)."""
        if "px_dop" in self.variables:
            dop = int(self.variables["px_dop"] or 0)
        else:
            dop = int(self.db.config["px_default_dop"])
        if dop <= 1:
            return 1
        import jax

        return min(dop, len(jax.devices()))

    def _try_px(self, plan, tables, dop, factor=1, monitor=None,
                budgets=None):
        """Attempt distributed execution; None -> fall back to single-node
        (unsupported plan shape, ≙ the optimizer declining a PX plan)."""
        from oceanbase_tpu.px.planner import (
            NotDistributable,
            execute_plan_distributed,
        )

        if not self.tenant.px_admission.acquire(n=dop):
            # admission denied: run serial (≙ px downgrade) — but
            # VISIBLY: counted, span-tagged, shown by EXPLAIN
            # ANALYZE (the silent downgrade was unobservable)
            qmetrics.inc("admission.px_downgrades",
                         tenant=self.tenant.name)
            self._last_px_downgrade = True
            return None
        # estimates over ANALYZEd tables may bound a shard's budgets; a
        # guess may not
        analyzed = all(
            td.histograms or td.mcv for td in (
                self.catalog.table_def(t) for t in tables
                if self.catalog.has_table(t)))
        try:
            rel = execute_plan_distributed(plan, tables, dop=dop,
                                           budget_factor=factor,
                                           exchange_budgets=budgets,
                                           trust_estimates=analyzed)
        except (NotDistributable, NotImplementedError):
            return None
        finally:
            self.tenant.px_admission.release(n=dop)
        if monitor is not None:
            from oceanbase_tpu.exec.plan import q_error as _qe

            est = getattr(plan, "est_rows", None)
            with qtrace.span("plan.monitor"):
                act = int(rel.count())
            monitor.append({"op": f"PxExecute(dop={dop})",
                            "pos": len(monitor), "est": est,
                            "rows": act, "q_error": _qe(est, act),
                            "elapsed_s": 0.0})
        return rel

    def _materialize(self, rel: Relation, outputs, tags=None) -> Result:
        raw = to_numpy(rel, tags=tags)
        names, arrays, valids, dtypes = [], {}, {}, {}
        for cid, name in outputs:
            col = rel.columns[cid]
            # disambiguate duplicate output names
            out_name = name
            k = 2
            while out_name in arrays:
                out_name = f"{name}_{k}"
                k += 1
            names.append(out_name)
            arrays[out_name] = raw[cid]
            valids[out_name] = raw.get("__valid__" + cid)
            dtypes[out_name] = col.dtype
        n = len(next(iter(arrays.values()))) if names else 0
        return Result(names, arrays, valids, dtypes, rowcount=n)

    # ------------------------------------------------------------------
    # disk spill tier (≙ SQL memory manager + spillable operators)
    # ------------------------------------------------------------------
    def _spill_candidates(self, plan, force_largest: bool = False) -> set:
        """Tables whose estimated rows REACHING the plan exceed the
        work-area budget (``_work_area``).  The estimate is
        post-access-path (≙ deciding spill from per-operator work-area
        estimates, not base-table size): a table whose filter conjuncts
        admit a selective primary/secondary path keeps the in-memory
        index fast-path even when the raw table is over budget.  With
        force_largest (the CapacityOverflow backstop) the largest table
        qualifies even under budget — the plan overflowed regardless, so
        stream it."""
        if not bool(self.db.config["enable_sql_spill"]):
            return set()
        from oceanbase_tpu.exec.plan import referenced_tables
        from oceanbase_tpu.sql import access_path as ap
        from oceanbase_tpu.storage.lookup import estimate_in_ranges

        refs = list(referenced_tables(plan))
        if self._tx is not None:
            # spill streams read committed state at a snapshot; a table
            # this tx has written must come from the own-writes read
            # path, so stay in-memory when any referenced table is dirty
            if any(t in self._tx.participants for t in refs):
                return set()
        fits = self._work_area(plan)
        try:
            ranges_by_table = ap.scan_filter_ranges(plan, self._engine)
        except Exception:
            ranges_by_table = {}
        est = {}
        for t in refs:
            ts = self._engine.tables.get(t)
            if ts is None:
                # no tablet (external / transient relation): spill can
                # still stream it chunk-wise to bound intermediates
                if self.catalog.has_table(t):
                    try:
                        rel = self.catalog.table_data(t)
                    except KeyError:
                        continue
                    # live rows, not pow2-padded capacity — padding alone
                    # must not route a fitting query to the disk tier
                    if rel.mask is None:
                        est[t] = rel.capacity
                    else:
                        est[t] = int(np.asarray(rel.mask).sum())
                continue
            rngs = ranges_by_table.get(t) or {}
            choice = ap.choose_path(self._engine, t, rngs) if rngs \
                else None
            if choice is not None:
                est[t] = choice.est_rows
            else:
                est[t] = estimate_in_ranges(ts.tablet, rngs)[0]
        # a table at a time, as the spill tier streams: the rows reaching
        # the plan against the rows of it the work area holds
        big = {t for t, e in est.items() if e > fits(t)}
        if not force_largest and any(t in self._engine.tables for t in refs):
            # a statement over stored tables was priced; one that reads
            # only what the session just materialised (gv$ tables, a
            # transient relation) had nothing to keep resident or stream
            qmetrics.inc("sql.work_area_decisions",
                         kind="spill" if big else "resident")
        if not big and force_largest and est:
            big = {max(est, key=est.get)}
        return big

    def _work_area(self, plan):
        """-> fits(table): the rows of ``table`` the work area holds.  The
        budget is ``ob_sql_work_area_percentage`` of the device's memory,
        in BYTES, and a row is priced at the widths of the columns
        ``plan`` reaches (validity and row mask included); while
        ``sql_work_area_rows`` is not 0 it is the budget instead, that
        many rows whatever their width."""
        from oceanbase_tpu.exec.plan import scan_columns
        from oceanbase_tpu.server.config import work_area_bytes

        # the tenant's overlay (SET GLOBAL) over the cluster's (ALTER SYSTEM)
        cfg = self.tenant.config
        rows = int(cfg["sql_work_area_rows"])
        if rows:
            return lambda table: rows
        nbytes = work_area_bytes(cfg)
        qmetrics.set_gauge("sql.work_area_bytes", nbytes)
        reach = scan_columns(plan)

        def fits(table: str) -> int:
            cols = self.catalog.table_def(table).columns
            renames = reach[1].get(table) if reach is not None else None
            if renames is not None:
                cols = [c for c in cols
                        if any(r.get(c.name, c.name) in reach[0]
                               for r in renames)] or cols[:1]
            row_bytes = 1 + sum(
                c.dtype.np_dtype.itemsize * max(
                    c.dtype.precision if c.dtype.kind == TypeKind.VECTOR
                    else 1, 1) + bool(c.nullable) for c in cols)
            return nbytes // row_bytes

        return fits

    def _try_spilled(self, plan, outputs, big: set):
        """Execute through exec/spill_exec (granule streams, device
        merge, temp-file runs).  -> Result, or None when the plan shape
        cannot stream: the caller then runs the resident plan, which the
        budget refused, so the fall-back is counted by its reason
        (``spill.fallbacks{reason}``) and tagged on the statement's
        trace."""
        import os
        import uuid

        from oceanbase_tpu.exec import spill_exec
        from oceanbase_tpu.exec.granule import segment_chunk_provider
        from oceanbase_tpu.exec.plan import referenced_tables
        from oceanbase_tpu.px.planner import NotDistributable
        from oceanbase_tpu.server.config import work_area_bytes

        # ONE read point for every table in the query (big streams and
        # small device relations alike) — a commit landing mid-query must
        # not split the snapshot across joined tables.  Inside an explicit
        # transaction the read point is the tx begin-snapshot
        # (_spill_candidates already excluded tables the tx wrote).
        snap = (self._tx.snapshot if self._tx is not None
                else self._txsvc.gts.current())
        providers, types_by_table, device_tables = {}, {}, {}
        for t in referenced_tables(plan):
            ts = self._engine.tables.get(t)
            if t in big and ts is not None:
                providers[t] = segment_chunk_provider(ts.tablet, snap)
                types_by_table[t] = {c.name: c.dtype
                                     for c in ts.tdef.columns}
            elif t in big and self.catalog.has_table(t):
                providers[t] = self._catalog_provider(t)
                types_by_table[t] = {
                    c.name: c.dtype
                    for c in self.catalog.table_def(t).columns}
            elif ts is not None:
                device_tables[t] = self.catalog.table_data_at(t, snap)
            elif self.catalog.has_table(t):
                device_tables[t] = self._table_snapshot(t)
        if not providers:
            return None
        # device-resident (non-streamed) subtrees may carry IndexProbe
        # nodes; their sorted sidecars ride in the device-table dict
        self._prepare_index_probes(plan, device_tables)
        sdir = os.path.join(self.db.root or "/tmp/obtpu", "tmpfile",
                            f"q{uuid.uuid4().hex[:10]}")
        cfg = self.tenant.config
        t0 = time.time()       # record timestamp (wall)
        m0 = time.monotonic()  # elapsed source (step-proof)
        # a granule program's static budgets overflow as a resident
        # plan's do: the same ladder, and the factor that cleared is
        # where this plan's next execution starts
        from oceanbase_tpu.exec.plan import logical_hash

        lhash = logical_hash(plan)
        factor = self._spill_factors.get(lhash, 1)
        retries = int(self.variables["max_capacity_retry"])
        try:
            for attempt in range(retries + 1):
                try:
                    out = spill_exec.execute_spilled(
                        plan if factor == 1
                        else scale_capacities(plan, factor),
                        providers, sdir,
                        # its sorts and joins count rows: the fewest the
                        # work area holds of a streamed table
                        max(min(map(self._work_area(plan), big)), 1),
                        device_tables, types_by_table, big,
                        disk_budget=self.tenant.diskmgr,
                        faults=self.db.faults,
                        label=(self._ash_state.get("sql", "")[:80]
                               or f"session {self.session_id}"),
                        budget_bytes=None
                        if int(cfg["sql_work_area_rows"])
                        else work_area_bytes(cfg))
                    break
                except CapacityOverflow as ovf:
                    if attempt >= retries:
                        raise
                    qmetrics.inc("plan.capacity_retries")
                    # the stream stopped at the first granule that
                    # dropped rows and says how many: grow by that, as
                    # the resident ladder does
                    plan, step = after_overflow(
                        plan, ovf.drops,
                        jump=bool(self.db.config["enable_plan_feedback"]))
                    factor *= step
                    self._spill_factors[lhash] = factor
        except (NotDistributable, NotImplementedError) as e:
            # unsupported shape OR a non-splittable aggregate
            # (count_distinct): the resident engine answers
            reason = str(e)[:60] or type(e).__name__
            qmetrics.inc("spill.fallbacks", reason=reason)
            with qtrace.span("spill.fallback", reason=reason):
                pass
            return None
        stats = out.stats
        self._last_spill = stats
        # a statement finished on the device leaves as any plan's result
        # does; the host half hands its columns over
        with qtrace.span("materialize") as msp:
            result = self._materialize(out.relation, outputs, msp.tags) \
                if out.relation is not None else self._materialize_host(
                    out.arrays, out.valids, out.dtypes, outputs)
        elapsed = time.monotonic() - m0
        try:
            plan_hash = plan.fingerprint()[:64]
        except Exception:
            plan_hash = ""
        self.db.workarea_history.append({
            "ts": t0, "sql": self._ash_state.get("sql", ""),
            "plan_hash": plan_hash,
            "kind": stats.kind, "runs": stats.runs,
            "bytes": stats.bytes, "spilled_rows": stats.spilled_rows,
            "batches": stats.batches, "elapsed_s": elapsed})
        self.db.wait_events.add("spill io", elapsed)
        if self.db.config["enable_sql_plan_monitor"]:
            # the spill tier streams batches, so only the ROOT operator's
            # output cardinality is observable whole — still enough for
            # a q-error ledger row (plus the spill cost) on this path
            from oceanbase_tpu.exec.plan import logical_hash as _lh
            from oceanbase_tpu.exec.plan import monitored_postorder
            from oceanbase_tpu.exec.plan import q_error as _qe

            n_out = result.rowcount
            # the row must describe the operator that OWNS its postorder
            # position: a pass-through root (Sort/Project) emits no
            # monitor lane, so name/est come from the last MONITORED
            # node — keeping (logical_hash, op_pos) joins consistent
            # with the serial path's ledger rows
            mon_nodes = monitored_postorder(plan)
            row_node = mon_nodes[-1] if mon_nodes else plan
            root_est = getattr(row_node, "est_rows", None)
            op_rows = [{"op": type(row_node).__name__,
                        "pos": max(len(mon_nodes) - 1, 0),
                        "est": root_est, "rows": n_out,
                        "q_error": _qe(root_est, n_out),
                        "elapsed_s": elapsed,
                        "spill_bytes": stats.bytes}]
            # the spill tier's plans are the heaviest ones: the time
            # ledger must cover them too (device_s from the chunk
            # programs execute_plan drove; pred covers the same work)
            times, pred_s, time_q = self._roofline(plan)
            self.db.plan_monitor.record(
                plan_hash, op_rows, elapsed, logical_hash=_lh(plan),
                spill_bytes=stats.bytes, path="spill",
                host_s=times.host_s, device_s=times.device_s,
                pred_s=pred_s, time_q=time_q)
        return result

    def _catalog_provider(self, name: str):
        """Chunk provider over a relation without a tablet (external /
        transient): decode to host once, stream in slices so plan
        intermediates stay inside the work-area budget."""
        from oceanbase_tpu.exec.granule import numpy_chunk_provider
        from oceanbase_tpu.vector import to_numpy

        raw = to_numpy(self.catalog.table_data(name))
        arrays = {k: v for k, v in raw.items()
                  if not k.startswith("__valid__")}
        valids = {k[len("__valid__"):]: v for k, v in raw.items()
                  if k.startswith("__valid__")}
        return numpy_chunk_provider(arrays, valids)

    def _materialize_host(self, arrays, valids, dtypes, outputs) -> Result:
        """Result from host columns (the spill path's output boundary —
        same shape contract as _materialize, minus the device hop)."""
        names, out_a, out_v, out_t = [], {}, {}, {}
        n = len(next(iter(arrays.values()))) if arrays else 0
        for cid, name in outputs:
            out_name = name
            k = 2
            while out_name in out_a:
                out_name = f"{name}_{k}"
                k += 1
            names.append(out_name)
            a = arrays.get(cid)
            if a is None:
                if n == 0:
                    # legitimately empty spilled result: no batches
                    # survived, so no columns materialized at all
                    a = np.zeros(0, dtype=np.int64)
                else:
                    # a dropped output column with rows present is a
                    # planner/spill bug — surface it (the in-memory
                    # _materialize would KeyError here too)
                    raise KeyError(
                        f"spill result missing output column {cid} "
                        f"({name})")
            out_a[out_name] = a
            out_v[out_name] = valids.get(cid)
            t = dtypes.get(cid)
            if t is None:
                if a.dtype == object or a.dtype.kind in "US":
                    t = SqlType.string()
                elif a.dtype.kind == "f":
                    t = SqlType.double()
                elif a.dtype.kind == "b":
                    t = SqlType.bool_()
                else:
                    t = SqlType.int_()
            out_t[out_name] = t
        return Result(names, out_a, out_v, out_t, rowcount=n)

    def _roofline(self, plan):
        """Roofline prediction for THIS statement's accumulated device
        work -> (ExecTimes, pred_s, time_q); records the pair into the
        per-operator-type calibration table.  Degrades to zeros when
        the split is off or THIS database is uncalibrated (the
        per-Database units, not the process cache: a database booted
        with enable_calibration=false must predict nothing, matching
        what its gv$cost_units/gv$backend report)."""
        from oceanbase_tpu.exec import plan as qplan
        from oceanbase_tpu.server import calibrate as qcalibrate

        times = qplan.exec_times()
        pred_s = time_q = 0.0
        units = self.db.cost_units
        if units is not None and times.device_s > 0.0 and \
                times.calls > 0:
            pred_s = qcalibrate.predict_seconds(
                units, times.flops, times.bytes, times.calls)
            time_q = qcalibrate.time_q_error(pred_s, times.device_s)
            if self.db.time_calibration is not None:
                self.db.time_calibration.observe(
                    type(plan).__name__, pred_s, times.device_s,
                    host_s=times.host_s)
        return times, pred_s, time_q

    def _explain(self, stmt, params, analyze: bool = False) -> Result:
        if not isinstance(stmt, ast.SelectStmt):
            raise NotImplementedError("EXPLAIN supports SELECT")
        # planning for EXPLAIN must not consume sequence values
        binder = Binder(self.catalog, params=params or [],
                        sequences=_PeekSequences(self.tenant.sequences),
                        sysvars=self.variables)
        plan, outputs, est = binder.bind_select(stmt)
        row_counts = None
        spill_line = ""
        if analyze:
            from oceanbase_tpu.exec.plan import referenced_tables

            # over-budget inputs run through the spill tier (running the
            # in-memory path here would hit the very overflow the route
            # exists to avoid); the spill counters annotate the plan
            big = self._spill_candidates(plan)
            res = self._try_spilled(plan, outputs, big) if big else None
            if res is not None:
                s = self._last_spill
                spill_line = (f"\nspill: kind={s.kind} runs={s.runs} "
                              f"bytes={s.bytes} "
                              f"spilled_rows={s.spilled_rows} "
                              f"batches={s.batches}")
            else:
                tables = {t: self._table_snapshot(t)
                          for t in referenced_tables(plan)
                          if self.catalog.has_table(t)}
                self._prepare_index_probes(plan, tables)
                # ANALYZE always collects per-operator rows: the user
                # asked for actuals, so the enable_sql_plan_monitor knob
                # does not gate this statement's own collection
                monitor: list = []
                factor = 1
                an0 = time.monotonic()  # ledger total_s (step-proof)
                for attempt in range(
                        int(self.variables["max_capacity_retry"]) + 1):
                    # the same retry ladder as execution: EXPLAIN
                    # ANALYZE must survive the misestimates it exists
                    # to expose (a CapacityOverflow IS the finding)
                    try:
                        p = plan if factor == 1 \
                            else scale_capacities(plan, factor)
                        execute_plan(p, tables, monitor_out=monitor)
                        break
                    except CapacityOverflow as ovf:
                        if attempt >= int(
                                self.variables["max_capacity_retry"]):
                            raise
                        from oceanbase_tpu.sql.optimizer import (
                            after_overflow,
                        )

                        plan, step = after_overflow(
                            plan, getattr(ovf, "drops", None) or [])
                        factor *= step
                        monitor.clear()
                # monitor entries arrive in the executor's postorder
                # (pass-through ops emit no lane); map them back to
                # their nodes for annotation
                from oceanbase_tpu.exec.plan import monitored_postorder

                row_counts = dict(zip(
                    (id(n) for n in monitored_postorder(plan)), monitor))
                # the time q-error beside the cardinality one: roofline
                # prediction vs this statement's measured device half
                times, pred_s, time_q = self._roofline(plan)
                if times.device_s > 0.0:
                    # the worst host phase names the blame the time
                    # model assigns (gv$time_model aggregates the same
                    # decomposition per tenant)
                    wname, wsec = times.worst_phase()
                    spill_line += (
                        f"\nroofline: [pred={pred_s:.3e}s "
                        f"dev={times.device_s:.3e}s "
                        f"host={times.host_s:.3e}s "
                        + (f"tq={time_q:.2f}" if time_q > 0.0
                           else "tq=uncalibrated")
                        + f" worst_phase={wname}:{wsec:.3e}s]")
                from oceanbase_tpu.exec.plan import logical_hash as _lh

                self.db.plan_monitor.record(
                    plan.fingerprint()[:64], monitor,
                    time.monotonic() - an0,
                    logical_hash=_lh(plan), retries=attempt,
                    path="serial",
                    host_s=times.host_s, device_s=times.device_s,
                    pred_s=pred_s, time_q=time_q)
        text = format_plan(plan, row_counts=row_counts) + spill_line
        if analyze and self._px_dop() > 1:
            # surface the px_admission verdict the statement would get
            # RIGHT NOW: a denied probe means concurrent PX statements
            # hold the tenant quota and this plan runs serial
            if self.tenant.px_admission.acquire(n=self._px_dop()):
                self.tenant.px_admission.release(n=self._px_dop())
            else:
                text += ("\npx: admission denied "
                         f"(dop={self._px_dop()} downgraded to serial; "
                         "see admission.px_downgrades)")
        if row_counts:
            worst = max(row_counts.values(),
                        key=lambda r: r.get("q_error", 0.0))
            if worst.get("q_error", 0.0) > 0.0:
                text += (f"\nworst misestimate: {worst['op']} "
                         f"est={worst['est']} act={worst['rows']} "
                         f"q={worst['q_error']:.2f}")
        # access-path annotations (≙ the 'Outputs & filters ... access'
        # section of the reference's EXPLAIN)
        from oceanbase_tpu.sql import access_path as ap

        try:
            by_table = ap.scan_filter_ranges(plan, self._engine)
            for t in sorted(by_table):
                if t not in self._engine.tables:
                    continue
                choice = ap.choose_path(self._engine, t, by_table[t])
                if choice is None:
                    continue
                via = ("PRIMARY" if choice.kind == "primary"
                       else f"INDEX {choice.index_name}")
                text += (f"\naccess: {t} via {via} "
                         f"(~{choice.est_rows} rows, "
                         f"cols {sorted(choice.prune)})")
        except Exception:
            pass
        lines = np.array(text.splitlines(), dtype=object)
        return Result(["plan"], {"plan": lines}, {},
                      {"plan": SqlType.string()}, rowcount=len(lines),
                      plan_text=text)

    # ------------------------------------------------------------------
    # DDL / DML (storage-engine integration deepens in storage/ + tx/)
    # ------------------------------------------------------------------
    def _create_table(self, stmt: ast.CreateTableStmt) -> Result:
        if getattr(stmt, "as_select", None) is not None:
            return self._create_table_as(stmt)
        cols = [ColumnDef(c.name, c.dtype, c.nullable) for c in stmt.columns]
        auto_cols = [c.name for c in stmt.columns
                     if getattr(c, "auto_increment", False)]
        tdef = TableDef(stmt.name, cols, primary_key=stmt.primary_key,
                        partition=getattr(stmt, "partition", None),
                        hash_partition=stmt.hash_partition,
                        tablegroup=stmt.tablegroup,
                        column_groups=stmt.column_groups,
                        auto_increment_cols=auto_cols)
        existed = stmt.if_not_exists and self.catalog.has_table(stmt.name)
        self.catalog.create_table(tdef, if_not_exists=stmt.if_not_exists)
        if existed:
            return _ok()  # IF NOT EXISTS no-op: skip index/sequence setup
        # inline INDEX/UNIQUE KEY specs become secondary indexes (the
        # table is brand-new: nothing to backfill or drain)
        for i, (iname, icols, iuniq) in enumerate(
                getattr(stmt, "indexes", [])):
            self._engine.create_index(
                stmt.name, iname or f"idx_{stmt.name}_{i}", icols,
                unique=iuniq)
        # AUTO_INCREMENT backs onto a hidden persisted sequence (≙ table
        # auto-inc service riding the sequence allocator); the column list
        # itself persists with the table definition
        for cname in auto_cols:
            seq = f"__ai_{stmt.name}_{cname}"
            try:
                self.tenant.sequences.create(seq, start=1)
            except ValueError:
                pass  # already exists (IF NOT EXISTS re-run)
        return _ok()  # the engine serves empty snapshots itself

    def _create_index(self, stmt: ast.CreateIndexStmt) -> Result:
        """CREATE [UNIQUE] INDEX: engine-side index table + backfill
        (≙ ObDDLService index build); the plan cache invalidates via the
        schema-version bump so access paths re-resolve."""
        td = self.catalog.table_def(stmt.table)
        if stmt.kind in ("vector", "fulltext"):
            # metadata only; the IVF buckets / posting lists build
            # lazily per data_version (≙ vector/FTS index DDL,
            # src/share/vector_index + src/storage/fts)
            if stmt.name in td.aux_indexes:
                if stmt.if_not_exists:
                    return _ok()
                raise ValueError(f"index {stmt.name} exists")
            if len(stmt.columns) != 1:
                raise ValueError(f"{stmt.kind} index takes one column")
            col = td.column(stmt.columns[0])  # existence check
            if stmt.kind == "vector" and col.dtype.kind != TypeKind.VECTOR:
                raise ValueError("vector index needs a VECTOR column")
            if stmt.kind == "fulltext" and not col.dtype.is_string:
                raise ValueError("fulltext index needs a string column")
            spec = {"kind": stmt.kind, "column": stmt.columns[0],
                    "metric": str(stmt.options.get("metric", "l2")),
                    "options": dict(stmt.options)}
            td.aux_indexes[stmt.name] = spec
            if stmt.table in self._engine.tables:
                # persist through the slog (+ the multi-node DDL stream)
                self._engine._log_meta({"op": "aux_index",
                                        "table": stmt.table,
                                        "name": stmt.name, "spec": spec})
            self.catalog.schema_version += 1
            return _ok()
        if any(ix.name == stmt.name for ix in td.indexes):
            if stmt.if_not_exists:
                return _ok()
            raise ValueError(f"index {stmt.name} exists on {stmt.table}")
        if self._tx is not None and stmt.table in self._tx.participants:
            raise RuntimeError(
                "CREATE INDEX on a table already written by the open "
                "transaction is not supported (commit first)")
        self._engine.create_index(
            stmt.table, stmt.name, stmt.columns, unique=stmt.unique,
            drain=self._tx_drain_fence())
        self.catalog.invalidate(stmt.table)
        self.catalog.schema_version += 1
        return _ok()

    def _tx_drain_fence(self, timeout_s: float = 10.0):
        """-> callable waiting out transactions live NOW (their earlier
        writes predate index maintenance); the online-DDL write fence
        (≙ ObDDLService waiting on the schema-version tx barrier)."""
        svc = self._txsvc
        own_tx = self._tx.tx_id if self._tx is not None else None

        def drain():
            # capture the live set HERE — engine.create_index calls the
            # fence AFTER installing the IndexDef, so every transaction
            # whose writes could have escaped maintenance is in this set
            # (a tx beginning between fence construction and IndexDef
            # install would otherwise be neither maintained nor drained)
            with svc._lock:
                live_before = set(svc._live)
            # the session's own open transaction cannot be waited on —
            # it must not have written the table yet, or index creation
            # inside it would deadlock; mirror MySQL's implicit-commit
            # by refusing instead of hanging
            live_before.discard(own_tx)
            # monotonic, not wall clock: an NTP step backwards would
            # extend the online-DDL fence indefinitely, a step forward
            # would expire it spuriously mid-drain
            deadline = time.monotonic() + timeout_s
            while True:
                with svc._lock:
                    if not (live_before & set(svc._live)):
                        return
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        "CREATE INDEX timed out waiting for in-flight "
                        "transactions to finish")
                time.sleep(0.01)
        return drain

    def _drop_index(self, stmt: ast.DropIndexStmt) -> Result:
        td = self.catalog.table_def(stmt.table)
        if stmt.name in td.aux_indexes:
            td.aux_indexes.pop(stmt.name, None)
            cache = getattr(self.catalog, "_ann_cache", None)
            if cache is not None:
                for k in [k for k in cache if k[0] == stmt.table]:
                    cache.pop(k, None)
            if stmt.table in self._engine.tables:
                self._engine._log_meta({"op": "drop_aux_index",
                                        "table": stmt.table,
                                        "name": stmt.name})
            self.catalog.schema_version += 1
            return _ok()
        try:
            self._engine.drop_index(stmt.table, stmt.name)
        except KeyError:
            if not stmt.if_exists:
                raise
        cache = getattr(self.catalog, "_probe_cache", None)
        if cache is not None:
            cache.pop((stmt.table, stmt.name), None)
        self.catalog.invalidate(stmt.table)
        self.catalog.schema_version += 1
        return _ok()

    # ------------------------------------------------------------------
    # transactional DML (storage/tx plane)
    # ------------------------------------------------------------------
    def _savepoint(self, stmt: ast.SavepointStmt) -> Result:
        """SAVEPOINT name / ROLLBACK TO name / RELEASE name: a savepoint
        records the tx's statement counter + per-table write counts;
        rollback-to aborts every write with a later statement seq
        (statement-granular undo, ≙ savepoint rollback over
        ObPartTransCtx's stmt-scoped callbacks)."""
        if self._tx is None:
            raise RuntimeError("no active transaction for SAVEPOINT")
        tx = self._tx
        if not hasattr(tx, "savepoints"):
            tx.savepoints = {}
        if stmt.op == "create":
            tx.savepoints[stmt.name] = (
                tx.stmt_seq,
                {t: len(p.keys) for t, p in tx.participants.items()})
            return _ok()
        sp = tx.savepoints.get(stmt.name)
        if sp is None:
            raise KeyError(f"savepoint {stmt.name} does not exist")
        if stmt.op == "release":
            del tx.savepoints[stmt.name]
            return _ok()
        # rollback to: undo everything written after the savepoint
        sp_seq, counts = sp
        stmt_writes = {}
        for t, p in tx.participants.items():
            new = p.keys[counts.get(t, 0):]
            if new:
                stmt_writes[t] = new
        self._txsvc.rollback_statement(tx, sp_seq + 1, stmt_writes)
        for t, p in tx.participants.items():
            del p.keys[counts.get(t, 0):]
        # savepoints created after this one are destroyed (MySQL)
        tx.savepoints = {n: v for n, v in tx.savepoints.items()
                         if v[0] <= sp_seq}
        return _ok()

    # ------------------------------------------------------------------
    # XA transactions (externally-coordinated 2PC; ≙ ObXAService)
    # ------------------------------------------------------------------
    def _xa_store(self) -> dict:
        # the store lives on the TENANT's TransService: xids, tx ids,
        # WALs, and lock tables are all tenant-scoped — a db-global
        # store would let another tenant's service commit this tx
        return self._txsvc.xa_transactions

    def _xa(self, stmt: ast.XaStmt) -> Result:
        store = self._xa_store()
        if stmt.op == "start":
            if self._tx is not None:
                raise RuntimeError("a transaction is already active")
            if stmt.xid in store:
                raise ValueError(f"XA xid {stmt.xid!r} exists")
            self._tx = self._txsvc.begin()
            self._tx.xid = stmt.xid
            store[stmt.xid] = self._tx
            return _ok()
        if stmt.op == "recover":
            # the service's locked view (live-prepared AND crash-
            # recovered branches — durable XA)
            xids = self._txsvc.recoverable_xids()
            return Result(["xid"],
                          {"xid": np.array(xids, dtype=object)}, {},
                          {"xid": SqlType.string()}, rowcount=len(xids))
        tx = store.get(stmt.xid)
        if tx is None:
            raise KeyError(f"unknown XA xid {stmt.xid!r}")
        if stmt.op == "end":
            # detach from this session; the xid keeps the tx reachable
            if self._tx is tx:
                self._tx = None
            return _ok()
        if stmt.op == "prepare":
            self._txsvc.xa_prepare(tx)
            if self._tx is tx:
                # a PREPARE-state tx takes no more statements; keeping it
                # attached would wedge every later DML in this session
                self._tx = None
            return _ok()
        if self._tx is tx:
            self._tx = None
        from oceanbase_tpu.tx.service import TxState

        if stmt.op == "commit":
            if tx.state == TxState.ACTIVE:  # XA ... ONE PHASE path
                self._txsvc.commit(tx)
            else:
                self._txsvc.xa_commit_prepared(tx)
        else:
            self._txsvc.xa_rollback_prepared(tx)
        store.pop(stmt.xid, None)
        return _ok()

    # ------------------------------------------------------------------
    # stored procedures (interpreted PL subset; ≙ src/pl — DECLARE/SET/
    # IF/WHILE over the shared expression engine, SQL via the session)
    # ------------------------------------------------------------------
    def _proc_store(self) -> dict:
        if self.db.procedures is None:
            self.db.procedures = {}
            self._load_procs()
        return self.db.procedures

    def _procs_path(self):
        import os

        return (os.path.join(self.db.root, "procedures.json")
                if self.db.root else None)

    def _load_procs(self):
        import json
        import os

        p = self._procs_path()
        if p and os.path.exists(p):
            with open(p) as fh:
                for name, src in json.load(fh).items():
                    stmt = parse_sql(src)
                    stmt.source = src
                    self.db.procedures[name] = stmt

    def _persist_procs(self):
        import json
        import os

        p = self._procs_path()
        if not p:
            return
        store = self._proc_store()
        tmp = p + ".tmp"
        with open(tmp, "w") as fh:
            json.dump({n: s.source for n, s in store.items()}, fh)
        os.replace(tmp, p)

    def _procedure_ddl(self, stmt: ast.ProcedureStmt) -> Result:
        store = self._proc_store()
        if stmt.op == "drop":
            if store.pop(stmt.name, None) is None:
                raise KeyError(f"unknown procedure {stmt.name}")
        else:
            if stmt.name in store:
                raise ValueError(f"procedure {stmt.name} exists")
            if not stmt.source:
                raise ValueError(
                    "procedure definition lost its source text")
            store[stmt.name] = stmt
        self._persist_procs()
        return _ok()

    def _call_procedure(self, stmt: ast.CallStmt, params) -> Result:
        from oceanbase_tpu.expr.compile import literal_value

        proc = self._proc_store().get(stmt.name)
        if proc is None:
            raise KeyError(f"unknown procedure {stmt.name}")
        if len(stmt.args) != len(proc.params):
            raise ValueError(
                f"{stmt.name} expects {len(proc.params)} arguments")
        env: dict = {}
        for (pname, ptype), arg in zip(proc.params, stmt.args):
            v, t = literal_value(_as_literal(arg, params, None))
            env[pname] = _coerce_value(v, t, ptype)
        out = [None]
        self._pl_exec(proc.body, env, out, depth=0)
        return out[0] if out[0] is not None else _ok()

    _PL_MAX_ITERS = 100_000

    def _pl_eval(self, expr, env: dict):
        """Evaluate a PL expression over the variable environment via
        the shared expression engine (a 1-row relation of vars)."""
        from oceanbase_tpu.expr.compile import eval_expr
        from oceanbase_tpu.vector import from_numpy, to_numpy

        arrays = {}
        valids = {}
        for k, v in env.items():
            if v is None:
                arrays[k] = np.zeros(1, np.int64)
                valids[k] = np.zeros(1, bool)
            elif isinstance(v, str):
                arrays[k] = np.array([v], dtype=object)
            elif isinstance(v, float):
                arrays[k] = np.array([v], np.float64)
            else:
                arrays[k] = np.array([int(v)], np.int64)
        arrays.setdefault("__one__", np.ones(1, np.int64))
        rel = from_numpy(arrays, valids=valids or None)
        c = eval_expr(expr, rel)
        raw = to_numpy(type(rel)(columns={"r": c}, mask=rel.mask))
        x = raw["r"][0]
        vmask = raw.get("__valid__r")
        if vmask is not None and not vmask[0]:
            return None
        return x.item() if hasattr(x, "item") else x

    def _pl_subst(self, node, env: dict):
        """Deep-substitute PL variables (bare ColumnRefs matching env
        names) with literals inside a statement AST."""
        import copy

        def sub_expr(e):
            if isinstance(e, ir.ColumnRef) and e.name in env:
                return ir.Literal(env[e.name])
            if isinstance(e, ir.Expr):
                e2 = copy.copy(e)
                for f, v in vars(e).items():
                    setattr(e2, f, sub_any(v))
                return e2
            return e

        def sub_any(v):
            if isinstance(v, ir.Expr):
                return sub_expr(v)
            if isinstance(v, list):
                return [sub_any(x) for x in v]
            if isinstance(v, tuple):
                return tuple(sub_any(x) for x in v)
            if hasattr(v, "__dataclass_fields__"):
                v2 = copy.copy(v)
                for f in v.__dataclass_fields__:
                    setattr(v2, f, sub_any(getattr(v, f)))
                return v2
            return v

        return sub_any(node)

    def _pl_exec(self, body: list, env: dict, out: list, depth: int):
        if depth > 64:
            raise RecursionError("PL nesting too deep")
        for item in body:
            if isinstance(item, ast.PlDeclare):
                env[item.name] = (self._pl_eval(item.default, env)
                                  if item.default is not None else None)
            elif isinstance(item, ast.PlSet):
                env[item.name] = self._pl_eval(item.expr, env)
            elif isinstance(item, ast.PlIf):
                done = False
                for cond, blk in item.branches:
                    if bool(self._pl_eval(cond, env)):
                        self._pl_exec(blk, env, out, depth + 1)
                        done = True
                        break
                if not done and item.else_:
                    self._pl_exec(item.else_, env, out, depth + 1)
            elif isinstance(item, ast.PlWhile):
                iters = 0
                while bool(self._pl_eval(item.cond, env)):
                    self._pl_exec(item.body, env, out, depth + 1)
                    iters += 1
                    if iters > self._PL_MAX_ITERS:
                        raise RuntimeError("PL WHILE iteration limit")
            else:
                # body statements must NOT hit the plan cache under the
                # CALL statement's text (its key would collide across
                # different/iterating SELECTs) — blank the audit text
                saved = self._ash_state.get("sql", "")
                self._ash_state["sql"] = ""
                try:
                    res = self.execute_stmt(self._pl_subst(item, env),
                                            None)
                finally:
                    self._ash_state["sql"] = saved
                if res is not None and res.names:
                    out[0] = res

    def _run_in_tx(self, fn, tx_hint=None):
        """Run fn(tx) in the active explicit transaction (with
        statement-level rollback on failure) or an autocommit one
        (≙ implicit transactions around single statements).  ``tx_hint``
        supplies a pre-begun autocommit transaction so the statement's
        reads and writes share one snapshot."""
        if self._tx is not None:
            tx = self._tx
            tx.stmt_seq += 1
            seq = tx.stmt_seq
            writes_before = {t: len(p.keys)
                             for t, p in tx.participants.items()}
            try:
                return fn(tx)
            except Exception:
                stmt_writes = {}
                for t, p in tx.participants.items():
                    new = p.keys[writes_before.get(t, 0):]
                    if new:
                        stmt_writes[t] = new
                self._txsvc.rollback_statement(tx, seq, stmt_writes)
                raise
        tx = tx_hint if tx_hint is not None else self._txsvc.begin()
        try:
            out = fn(tx)
        except Exception:
            self._txsvc.rollback(tx)
            raise
        try:
            self._txsvc.commit(tx)
        except Exception:
            # a failed commit aborts the transaction (locks released)
            self._txsvc.rollback(tx)
            raise
        return out

    def _stmt_tx(self):
        """-> (tx-for-this-statement, hint): the explicit tx if one is
        open, else a fresh autocommit tx whose snapshot the statement's
        reads must use (pass hint on to _run_in_tx)."""
        if self._tx is not None:
            return self._tx, None
        tx = self._txsvc.begin()
        return tx, tx

    def _insert(self, stmt: ast.InsertStmt, params) -> Result:
        td = self.catalog.table_def(stmt.table)
        cols = stmt.columns or td.column_names
        rows_values: list[dict] = []
        sub = self._execute_select(stmt.select, params) \
            if stmt.rows is None else None
        # text (or a sub-select's arrays) to typed rows: one span for the
        # statement, whatever its rows
        with qtrace.span("dml.bind", table=stmt.table) as bsp:
            self._insert_rows(stmt, params, td, cols, sub, rows_values)
            bsp.tags["rows"] = len(rows_values)
        tablet = self._engine.tables[stmt.table].tablet
        replace = getattr(stmt, "replace", False)
        from oceanbase_tpu.kv import KvTable

        kv = KvTable(self.tenant, stmt.table) if replace else None

        def op(tx):
            with qtrace.span("dml.write", table=stmt.table,
                             kind="replace" if replace else "insert") as sp:
                self._insert_write(tx, stmt.table, tablet, rows_values,
                                   replace, kv, sp.tags)

        self._run_in_tx(op)
        # keep the binder's est_rows current: a plan bound while the
        # table looked empty would budget capacities for one row and
        # ride the CapacityOverflow retry ladder on every execution
        td.row_count = tablet.row_count_estimate()
        self._maybe_freeze(stmt.table)
        return _ok(rowcount=len(rows_values))

    def _insert_rows(self, stmt, params, td, cols, sub, rows_values: list):
        """``dml.bind``'s loop: literal evaluation, coercion to the
        column's storage value, defaults, auto-increment."""
        if sub is None:
            seqs = self.tenant.sequences
            for row in stmt.rows:
                if len(row) != len(cols):
                    raise ValueError("INSERT arity mismatch")
                values: dict = {}
                for c, e in zip(cols, row):
                    v, t = literal_value(_as_literal(e, params, seqs))
                    cdef = td.column(c)
                    values[c] = _coerce_value(v, t, cdef.dtype)
                for c in td.columns:
                    values.setdefault(c.name, None)
                self._fill_auto_increment(td, values)
                rows_values.append(values)
            return
        for i in range(sub.rowcount):
            values = {}
            for c, sn in zip(cols, sub.names):
                x = sub.arrays[sn][i]
                vd = sub.valids.get(sn)
                if vd is not None and not vd[i]:
                    values[c] = None
                else:
                    values[c] = x.item() if hasattr(x, "item") else x
            for c in td.columns:
                values.setdefault(c.name, None)
            self._fill_auto_increment(td, values)
            rows_values.append(values)

    def _insert_write(self, tx, table, tablet, rows_values, replace, kv,
                      tags):
        """``dml.write``'s loop for INSERT / REPLACE."""
        if not replace and self._pdml_eligible(len(rows_values)):
            keyed = [(tablet.make_key(v), v) for v in rows_values]
            if len({k for k, _ in keyed}) == len(keyed):
                # distinct keys: the write phase is order-free, fan
                # it out (intra-statement dup keys need serial
                # first-wins ordering)
                self._pdml_write(tx, table, tablet, keyed, "insert", tags)
                return
        stats = WriteStats()
        try:
            for values in rows_values:
                key = tablet.make_key(values)
                kind = "insert"
                if replace:
                    # REPLACE INTO: newest version wins over an existing
                    # row (≙ REPLACE as delete+insert, here one update);
                    # own-tx writes (incl. earlier rows of this statement)
                    # count as existing
                    existing = kv.get(key, snapshot=tx.snapshot,
                                      tx_id=tx.tx_id)
                    kind = "update" if existing is not None else "insert"
                self._txsvc.write(tx, table, tablet, key, kind, values,
                                  stats)
        finally:
            stats.book(tags)

    # ------------------------------------------------------------------
    # parallel DML (≙ src/sql/engine/pdml: partition-aware parallel
    # insert/update/delete DFOs under ONE transaction)
    # ------------------------------------------------------------------
    def _pdml_eligible(self, n_rows: int) -> bool:
        return (int(self.db.config["pdml_dop"]) > 1
                and n_rows >= int(self.db.config["pdml_min_rows"]))

    def _pdml_write(self, tx, table: str, tablet, keyed: list,
                    kind: str, tags: dict):
        """Fan the write phase of one statement out over tenant workers;
        each keeps its own ``WriteStats`` (no span on a worker's thread),
        summed into ``tags`` (the ``dml.write`` span's) when all are in.

        keyed: [(key, values)].  Rows group by target partition so each
        worker owns whole partitions (no cross-worker tablet contention;
        ≙ the PDML repartition by PKEY, ob_sub_trans_ctrl.h); an
        unpartitioned tablet falls back to round-robin chunks (its
        memtable writes serialize on the tablet lock, but index
        maintenance and redo encoding still parallelize)."""
        dop = int(self.db.config["pdml_dop"])
        groups: dict[int, list] = {}
        if hasattr(tablet, "route_partition_index"):
            for key, values in keyed:
                groups.setdefault(
                    tablet.route_partition_index(values), []).append(
                        (key, values))
        else:
            for i, kv_ in enumerate(keyed):
                groups.setdefault(i % dop, []).append(kv_)

        def worker(batch):
            stats = WriteStats()
            try:
                for key, values in batch:
                    self._txsvc.write(tx, table, tablet, key, kind, values,
                                      stats)
            finally:
                done.append(stats)

        done: list = []
        futures = [self.tenant.submit(worker, batch)
                   for batch in groups.values()]
        errs = []
        for f in futures:
            try:
                f.result()
            except Exception as e:  # noqa: BLE001 — surface first error
                errs.append(e)
        total = WriteStats()
        for stats in done:
            total.add(stats)
        total.book(tags)
        tags["pdml_workers"] = len(futures)
        if errs:
            raise errs[0]

    def _fill_auto_increment(self, td, values: dict):
        for cname in getattr(td, "auto_increment_cols", []):
            seq = f"__ai_{td.name}_{cname}"
            if seq not in self.tenant.sequences._defs:
                self.tenant.sequences.create(seq, start=1)
            if values.get(cname) is None:
                values[cname] = self.tenant.sequences.nextval(seq)
            else:
                # explicit value advances the counter (MySQL semantics)
                try:
                    self.tenant.sequences.advance_past(seq,
                                                       int(values[cname]))
                except (TypeError, ValueError):
                    pass

    def _matching_rows(self, table: str, where, params, tx):
        """-> (rel, mask, tablet, binder, scope, full_table): relation at
        the statement tx's snapshot + WHERE mask (reads and writes share
        one snapshot so the SI write-conflict check is sound);
        ``full_table``: no candidate path was taken.

        Point/range WHERE clauses on the primary key or an index take the
        candidate-superset access path — an OLTP UPDATE/DELETE touches a
        few pruned chunks, not a whole-table materialization."""
        from oceanbase_tpu.expr.compile import eval_predicate
        from oceanbase_tpu.sql.binder import Binder, Scope

        ts = self._engine.tables[table]
        tablet = ts.tablet
        with qtrace.span("dml.bind", table=table):
            binder = Binder(self.catalog, params=params or [])
            scope = Scope()
            for cname in tablet.columns:
                scope.add(cname, cname, alias=table)
            pred = binder.bind_expr(where, scope) \
                if where is not None else None
        rel = None
        if pred is not None and \
                bool(self.variables.get("enable_index_access", 1)):
            from oceanbase_tpu.sql import access_path as ap

            # the chunk decode on the host, or the decision against it
            with qtrace.span("dml.candidates", path="none") as csp:
                try:
                    ranges = ap.ranges_of_pred(pred, tablet.types)
                    choice = ap.choose_path(self._engine, table, ranges)
                    if choice is not None:
                        arrays, valids = ap.materialize_candidates(
                            self._engine, choice, tx.snapshot, tx.tx_id)
                        rel = self._candidate_relation(ts, arrays, valids)
                        csp.tags.update(
                            path=choice.kind, chunks=choice.chunks,
                            rows=len(next(iter(arrays.values())))
                            if arrays else 0)
                except Exception:
                    rel = None  # any surprise -> full-table path
        full_table = rel is None
        if full_table:
            # its own spans: ``storage.device_copy`` / ``delta_apply``
            rel = self.catalog.table_data_at(table, tx.snapshot, tx.tx_id)
        with qtrace.span("dml.predicate"):
            mask = eval_predicate(pred, rel) if pred is not None \
                else rel.mask_or_true()
        return rel, mask, tablet, binder, scope, full_table

    @staticmethod
    def _matched_rows(tablet, matched: dict, n: int) -> list:
        """``dml.rows``'s loop: the matched host arrays as one dict of
        python values a row."""
        out = []
        for i in range(n):
            values = {}
            for c in tablet.columns:
                if c in matched:
                    x = matched[c][i]
                    vd = matched.get("__valid__" + c)
                    values[c] = (None if vd is not None and not vd[i]
                                 else (x.item() if hasattr(x, "item")
                                       else x))
            out.append(values)
        return out

    def _update(self, stmt: ast.UpdateStmt, params) -> Result:
        td = self.catalog.table_def(stmt.table)
        tx, tx_hint = self._stmt_tx()
        try:
            return self._update_body(stmt, params, td, tx, tx_hint)
        except Exception:
            if tx_hint is not None and tx_hint.state.value == "active":
                self._txsvc.rollback(tx_hint)
            raise

    def _update_body(self, stmt, params, td, tx, tx_hint) -> Result:
        from oceanbase_tpu.expr.compile import cast_column, eval_expr
        import numpy as _np

        # everything before the first write: a parent span, its leaves
        # ``dml.bind``, ``dml.candidates``, ``dml.predicate``,
        # ``dml.assign``, ``materialize`` and ``dml.rows``
        with qtrace.span("dml.match", table=stmt.table) as msp:
            rel, mask, tablet, binder, scope, full_table = \
                self._matching_rows(stmt.table, stmt.where, params, tx)
            # evaluate assignments over the snapshot, then pull matched rows
            with qtrace.span("dml.assign", columns=len(stmt.assignments)):
                new_cols = {}
                for cname, e in stmt.assignments:
                    b = binder.bind_expr(e, scope)
                    c = eval_expr(b, rel)
                    new_cols[cname] = cast_column(c, td.column(cname).dtype)
                new_host = {}
                midx = _np.nonzero(_np.asarray(mask))[0]
                for cname, c in new_cols.items():
                    vals = _np.asarray(c.data)[midx]
                    if c.sdict is not None:
                        vals = c.sdict.values[
                            _np.clip(vals, 0, c.sdict.size - 1)]
                    vv = (_np.asarray(c.valid)[midx] if c.valid is not None
                          else _np.ones(len(midx), dtype=bool))
                    new_host[cname] = (vals, vv)
            with qtrace.span("materialize") as fsp:
                matched = to_numpy(rel.with_mask(mask), tags=fsp.tags)
            n_upd = len(next(iter(matched.values()))) if matched else 0
            with qtrace.span("dml.rows", rows=n_upd):
                keyed = []
                for i, old_values in enumerate(
                        self._matched_rows(tablet, matched, n_upd)):
                    values = dict(old_values)
                    for cname, (vals, vv) in new_host.items():
                        x = vals[i]
                        values[cname] = (None if not vv[i]
                                         else (x.item() if hasattr(x, "item")
                                               else x))
                    keyed.append((old_values, values))
            msp.tags.update(rows=n_upd, full_table=int(full_table))

        key_changed = any(c in tablet.key_cols for c, _ in stmt.assignments)
        # an update that moves a row across range partitions must also be
        # delete+insert (the versions live in different tablets)
        part_cols = getattr(tablet, "part_cols", ())
        part_changed = any(c in part_cols for c, _ in stmt.assignments)

        def op(tx):
            with qtrace.span("dml.write", table=stmt.table,
                             kind="update") as sp:
                self._update_write(tx, stmt.table, tablet, keyed,
                                   key_changed, part_changed, sp.tags)

        self._run_in_tx(op, tx_hint=tx_hint)
        self._maybe_freeze(stmt.table)
        return _ok(rowcount=n_upd)

    def _update_write(self, tx, table, tablet, keyed, key_changed,
                      part_changed, tags):
        """``dml.write``'s loop for UPDATE."""
        if not key_changed and not part_changed and \
                self._pdml_eligible(len(keyed)):
            # plain (no PK/partition move) bulk update: per-row
            # target keys are distinct, the write phase fans out
            self._pdml_write(
                tx, table, tablet,
                [(tuple(v[k] for k in tablet.key_cols), v)
                 for _o, v in keyed], "update", tags)
            return
        stats = WriteStats()
        try:
            for old_values, values in keyed:
                new_key = tuple(values[k] for k in tablet.key_cols)
                moved = False
                if part_changed:
                    moved = tablet.route_partition_index(old_values) != \
                        tablet.route_partition_index(values)
                if key_changed or moved:
                    old_key = tuple(old_values[k] for k in tablet.key_cols)
                    if old_key != new_key or moved:
                        # PK/partition move = delete old row + insert new
                        self._txsvc.write(tx, table, tablet, old_key,
                                          "delete", old_values, stats)
                        self._txsvc.write(tx, table, tablet, new_key,
                                          "insert", values, stats)
                        continue
                self._txsvc.write(tx, table, tablet, new_key, "update",
                                  values, stats)
        finally:
            stats.book(tags)

    def _delete(self, stmt: ast.DeleteStmt, params) -> Result:
        tx, tx_hint = self._stmt_tx()
        try:
            return self._delete_body(stmt, params, tx, tx_hint)
        except Exception:
            if tx_hint is not None and tx_hint.state.value == "active":
                self._txsvc.rollback(tx_hint)
            raise

    def _delete_body(self, stmt, params, tx, tx_hint) -> Result:
        with qtrace.span("dml.match", table=stmt.table) as msp:
            rel, mask, tablet, _b, _s, full_table = self._matching_rows(
                stmt.table, stmt.where, params, tx)
            with qtrace.span("materialize") as fsp:
                matched = to_numpy(rel.with_mask(mask), tags=fsp.tags)
            n_del = len(next(iter(matched.values()))) if matched else 0
            with qtrace.span("dml.rows", rows=n_del):
                keyed = [(tuple(values[k] for k in tablet.key_cols), values)
                         for values in self._matched_rows(tablet, matched,
                                                          n_del)]
            msp.tags.update(rows=n_del, full_table=int(full_table))

        def op(tx):
            with qtrace.span("dml.write", table=stmt.table,
                             kind="delete") as sp:
                if self._pdml_eligible(n_del):
                    self._pdml_write(tx, stmt.table, tablet, keyed,
                                     "delete", sp.tags)
                    return
                stats = WriteStats()
                try:
                    for key, values in keyed:
                        self._txsvc.write(tx, stmt.table, tablet, key,
                                          "delete", values, stats)
                finally:
                    stats.book(sp.tags)

        self._run_in_tx(op, tx_hint=tx_hint)
        self._maybe_freeze(stmt.table)
        return _ok(rowcount=n_del)

    def _create_table_as(self, stmt: ast.CreateTableStmt) -> Result:
        """CREATE TABLE AS SELECT: schema inferred from the result set,
        rows direct-loaded (≙ CTAS via the direct-load path)."""
        res = self._execute_select(stmt.as_select, None)
        cols = [ColumnDef(name, res.dtypes.get(name, SqlType.int_()))
                for name in res.names]
        tdef = TableDef(stmt.name, cols)
        self.catalog.create_table(tdef, if_not_exists=stmt.if_not_exists)
        arrays, valids = {}, {}
        for name in res.names:
            arr = res.arrays[name]
            t = res.dtypes.get(name)
            if t is not None and t.is_string:
                # NULL lanes carry None payloads; validity is authoritative
                arrays[name] = np.array(
                    [x if x is not None else "" for x in arr], dtype=object)
            else:
                arrays[name] = arr
            v = res.valids.get(name)
            if v is not None and not v.all():
                valids[name] = v
        if res.rowcount:
            self._engine.bulk_load(stmt.name, arrays, valids or None,
                                   version=self._txsvc.gts.get_ts())
        self.catalog.invalidate(stmt.name)
        tdef.row_count = res.rowcount
        return _ok(rowcount=res.rowcount)

    def _tx_control(self, op: str) -> Result:
        if self._tx is not None and getattr(self._tx, "xid", None):
            # an XA branch only ends through XA verbs (≙ XAER_RMFAIL):
            # committing it here would strand the xid in the store
            raise RuntimeError(
                f"transaction is an XA branch "
                f"({self._tx.xid!r}); use XA END/PREPARE/COMMIT")
        if op == "begin":
            if self._tx is not None:
                self._txsvc.commit(self._tx)  # implicit commit (MySQL)
            self._tx = self._txsvc.begin()
        elif op == "commit":
            if self._tx is not None:
                self._txsvc.commit(self._tx)
                self._tx = None
        elif op == "rollback":
            if self._tx is not None:
                self._txsvc.rollback(self._tx)
                self._tx = None
        return _ok()


def _as_literal(e, params, sequences=None) -> ir.Literal:
    if isinstance(e, ir.Literal):
        return e
    if isinstance(e, ast.Param):
        return ir.Literal(params[e.index])
    if isinstance(e, ir.FuncCall) and e.name == "nextval" and \
            sequences is not None:
        return ir.Literal(sequences.nextval(e.args[0].value))
    if isinstance(e, ir.Arith) and isinstance(e.left, ir.Literal) and \
            isinstance(e.right, ir.Literal):
        lv, _ = literal_value(e.left)
        rv, _ = literal_value(e.right)
        return ir.Literal({"+": lv + rv, "-": lv - rv, "*": lv * rv}
                          [e.op])
    raise ValueError("INSERT VALUES must be literals")


def _coerce_value(v, t, target: SqlType):
    """Coerce a parsed literal (value, type) to a column's storage value."""
    if v is None:
        return None
    if target.kind == TypeKind.DECIMAL:
        if t.kind == TypeKind.DECIMAL:
            return _rescale(v, t.scale, target.scale)
        if isinstance(v, int):
            return v * _POW10[target.scale]
        if isinstance(v, float):
            return round(v * _POW10[target.scale])
    if target.kind == TypeKind.DATE and isinstance(v, str):
        from oceanbase_tpu.datatypes import date_to_days

        return date_to_days(v)
    if target.kind == TypeKind.BOOL:
        return bool(v)
    if target.kind == TypeKind.VECTOR and isinstance(v, str):
        from oceanbase_tpu.expr.compile import parse_vector_text

        vec = parse_vector_text(v)
        if len(vec) != target.precision:
            raise ValueError(
                f"vector literal has dim {len(vec)}, column wants "
                f"{target.precision}")
        return [float(x) for x in vec]
    return v


class _PeekSequences:
    """Sequence view that never advances (EXPLAIN planning)."""

    def __init__(self, seqs):
        self._seqs = seqs

    def nextval(self, name: str) -> int:
        return self._seqs.peek(name)


def _rescale(v: int, from_scale: int, to_scale: int) -> int:
    if to_scale >= from_scale:
        return v * _POW10[to_scale - from_scale]
    d = _POW10[from_scale - to_scale]
    half = d // 2
    return (v + half) // d if v >= 0 else -((-v + half) // d)


def _ok(rowcount: int = 0) -> Result:
    return Result([], {}, {}, {}, rowcount=rowcount)


def format_plan(node, indent: int = 0, row_counts: dict | None = None) -> str:
    """EXPLAIN [ANALYZE] output (≙ src/sql/printer plan text; ANALYZE adds
    the estimate-vs-actual ledger per operator — ``[est=… act=… q=…]``
    from the plan-monitor lanes, the worst misestimate flagged)."""
    from oceanbase_tpu.exec import plan as pp

    pad = "  " * indent
    name = type(node).__name__
    attrs = []
    for k, v in vars(node).items():
        if k == "est_rows" or k.startswith("_"):
            continue  # ledger annotation / memoized metadata
        if k == "build_unique":
            # the join's emit kind, readable without a trace; an
            # unmarked join prints as it always did
            if v:
                attrs.insert(0, "unique build, on probe lanes")
            continue
        if k == "below_join":
            # an outer join's aggregation planned under the join
            if v:
                attrs.insert(0, "below join")
            continue
        if isinstance(v, pp.PlanNode) or k in ("child", "left", "right",
                                               "inputs"):
            continue
        s = repr(v)
        if len(s) > 60:
            s = s[:57] + "..."
        attrs.append(f"{k}={s}")
    line = f"{pad}{name}({', '.join(attrs)})"
    if row_counts is not None and id(node) in row_counts:
        r = row_counts[id(node)]
        est = r["est"] if r.get("est") is not None else "?"
        line += (f"  [est={est} act={r['rows']} "
                 f"q={r.get('q_error', 0.0):.2f}]")
    kids = list(node.children())
    return "\n".join([line] + [format_plan(c, indent + 1, row_counts)
                               for c in kids])
