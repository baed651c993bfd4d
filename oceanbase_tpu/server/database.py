"""Database: the server instance — tenants, config, observability.

Reference analog: ObServer::init/start (src/observer/ob_server.cpp:228)
booting config, network frame, multi-tenant env, storage meta replay and
log replay — collapsed to the in-process instance:

- cluster Config (persisted) + per-tenant overlays
- tenants, each owning the full module stack (see server/tenant.py);
  tenant 'sys' always exists (≙ the sys tenant)
- observability singletons: SQL audit ring, plan monitor, ASH sampler,
  wait events, virtual tables (gv$/v$ served through SQL)

``Host`` is what a session is served by: every plane a session,
``VirtualTables``, ``WorkloadRepository`` or ``server/trace.py`` reads,
built in one place.  ``Database`` is the single-process host;
``net/node.py::NodeDatabase`` is one node's.

``Database.session(tenant=...)`` hands out SQL sessions
(≙ MySQL frontend connections landing in a tenant's queue).
"""

from __future__ import annotations

import itertools
import os
from typing import Optional

from oceanbase_tpu.server.config import Config
from oceanbase_tpu.server.monitor import (
    AshSampler,
    PlanChoiceLedger,
    PlanFeedback,
    PlanHistory,
    PlanMonitor,
    SqlAudit,
    TimeCalibration,
    TimeModel,
    WaitEvents,
)
from oceanbase_tpu.server.tenant import Tenant
from oceanbase_tpu.server.trace import TraceRegistry
from oceanbase_tpu.server.virtual_tables import VirtualTables


class Host:
    """The planes under a session (≙ what ObServer hands every tenant
    worker).  A plane one kind of host does not run is declared here as
    ``None`` and set by the host that runs it, so a reader tests the
    attribute, never its existence."""

    def __init__(self, config: Config, root: str | None, node_id: int,
                 tenants: dict):
        self.config = config
        self.root = root
        self.node_id = node_id  # stamps trace spans / gv$trace
        self.tenants = tenants
        self._session_ids = itertools.count(1)

        # observability (cluster-wide)
        self.audit = SqlAudit(int(config["sql_audit_queue_size"]))
        self.plan_monitor = PlanMonitor()
        # plan-quality plane: cardinality feedback + regression watchdog
        # (gv$plan_feedback / gv$plan_history; sql/session.py wires them
        # into bind + the CapacityOverflow retry ladder)
        self.plan_feedback = PlanFeedback(
            int(config["plan_feedback_entries"]))
        self.plan_history = PlanHistory(
            int(config["plan_history_entries"]))
        # per-tenant time-model accounting (gv$time_model): every
        # statement folds its host-phase split + device/queue/wall here
        self.time_model = TimeModel()
        # full-link trace ring (gv$trace / SHOW TRACE; server/trace.py)
        self.trace_registry = TraceRegistry(
            int(config["trace_ring_spans"]))
        # JAX's compile events and the collector's pauses, booked to the
        # statement that paid them (one listener for the process)
        from oceanbase_tpu.server.trace import install_runtime_hooks

        install_runtime_hooks()
        # sessions register their state slots in Session.__init__; the
        # host decides when the sampler thread runs
        self.ash = AshSampler(
            interval_s=int(config["ash_sample_interval_ms"]) / 1000.0)
        self.wait_events = WaitEvents()
        # per-query spill records (feeds v$sql_workarea,
        # ≙ the SQL memory manager's work-area profiles)
        self.workarea_history: list[dict] = []
        # overload plane: statement admission + fair queuing + KILL
        # (server/admission.py); per-tenant WRR weights read live from
        # each tenant's config overlay
        from oceanbase_tpu.server.admission import AdmissionController

        self.admission = AdmissionController(
            config, weight_of=self._tenant_weight)
        self.virtual_tables = VirtualTables(self)
        # workload diagnostics repository (server/workload.py):
        # persistent snapshots + ANALYZE WORKLOAD REPORT; the host
        # starts its snapshot thread
        from oceanbase_tpu.server.workload import WorkloadRepository

        self.workload = WorkloadRepository(self, root)
        # stored procedures, loaded by the first session that asks
        self.procedures: dict | None = None

        # a Database's alone: the CBO self-validation ledger, roofline
        # constants and accounting, PROFILE captures, the job scheduler
        self.plan_choice = None
        self.cost_units = None
        self.time_calibration = None
        self.device_profiles = None
        self.jobs = None
        # a node's alone (net/node.py): its NodeServer, the disk-fault
        # plane durable writers consult (None = no injection), the DTL
        # exchange and its counters, the failure detector, the scrub state
        self.node = None
        self.faults = None
        self.dtl = None
        self.dtl_metrics = None
        self.health = None
        self.scrub = None

    def _tenant_weight(self, name: str) -> int:
        t = self.tenants.get(name)
        cfg = t.config if t is not None else self.config
        return int(cfg["admission_tenant_weight"])

    # -- sys-tenant convenience (single-tenant callers) ------------------
    @property
    def engine(self):
        return self.tenants["sys"].engine

    @property
    def tx(self):
        return self.tenants["sys"].tx

    @property
    def catalog(self):
        return self.tenants["sys"].catalog


class Database(Host):
    def __init__(self, root: str | None = None, wal_replicas: int = 3,
                 start_ash: bool = False):
        cfg_path = os.path.join(root, "config.json") if root else None
        if root:
            os.makedirs(root, exist_ok=True)
        super().__init__(Config(persist_path=cfg_path), root, node_id=0,
                         tenants={})

        # metrics plane on/off rides the config (ALTER SYSTEM SET
        # enable_metrics; scripts/metrics_bench.py prices the toggle)
        from oceanbase_tpu.server import metrics as qmetrics

        qmetrics.set_enabled(bool(self.config["enable_metrics"]))
        self.config.watch(
            lambda k, v: qmetrics.set_enabled(bool(v))
            if k == "enable_metrics" else None)

        # host/device time split (exec/plan.py): process-global like the
        # metrics flag; scripts/profile_bench.py prices the toggle
        from oceanbase_tpu.exec import plan as qplan

        qplan.set_time_split(bool(self.config["enable_profiling"]))
        self.config.watch(
            lambda k, v: qplan.set_time_split(bool(v))
            if k == "enable_profiling" else None)

        # roofline calibration (server/calibrate.py): adopt persisted
        # machine constants or run the first-boot probe (cached
        # process-wide — the constants describe the backend, not this
        # instance); a corrupt cost_units.json is quarantined and
        # re-probed, never served (PR 9 contract)
        from oceanbase_tpu.server import calibrate as qcalibrate

        if bool(self.config["enable_calibration"]):
            try:
                self.cost_units = qcalibrate.ensure_units(root)
            except Exception:  # noqa: BLE001 — calibration is
                # observability: a probe failure degrades predictions
                # to zeros, never boot
                pass

        # CBO self-validation ledger: bind-time predicted seconds vs the
        # runner-up and the measured device seconds (gv$plan_choice)
        self.plan_choice = PlanChoiceLedger(
            int(self.config["plan_history_entries"]))
        # roofline accounting per operator type + PROFILE capture store
        # (gv$time_calibration / gv$device_profile)
        from oceanbase_tpu.server.profiler import DeviceProfileStore

        self.time_calibration = TimeCalibration()
        self.device_profiles = DeviceProfileStore()
        if start_ash and self.config["enable_ash"]:
            self.ash.start()
        # The workload snapshot thread starts with the knob (or later,
        # when ALTER SYSTEM turns it on — the watcher below); the loop
        # re-reads both knobs every round, so turning it OFF needs no
        # restart.
        if bool(self.config["enable_workload_repo"]):
            self.workload.start()
        self.config.watch(
            lambda k, v: self.workload.start()
            if k == "enable_workload_repo" and bool(v) else None)
        # DBMS job scheduler (≙ dbms_job/dbms_scheduler); built-ins
        # register at boot, the thread starts on demand or when enabled
        from oceanbase_tpu.server.jobs import JobScheduler

        self.jobs = JobScheduler(self)
        self.jobs.register_builtins(
            stats_interval_s=float(
                self.config["stats_gather_interval_s"]),
            compact_interval_s=float(
                self.config["auto_compact_interval_s"]))
        if bool(self.config["enable_dbms_jobs"]):
            self.jobs.start()

        # user store: mysql_native_password hashes (≙ __all_user);
        # root starts passwordless like a fresh deployment
        from oceanbase_tpu.server.mysql_protocol import mysql_native_hash

        self.users: dict[str, bytes] = {"root": mysql_native_hash("")}
        self._users_path = (os.path.join(root, "users.json")
                            if root else None)
        if self._users_path and os.path.exists(self._users_path):
            import json as _json

            with open(self._users_path) as fh:
                self.users = {u: bytes.fromhex(h)
                              for u, h in _json.load(fh).items()}

        # boot tenants: 'sys' plus any persisted tenant directories
        self.create_tenant("sys", wal_replicas=wal_replicas, _boot=True)
        if root:
            tdir = os.path.join(root, "tenants")
            if os.path.isdir(tdir):
                for name in sorted(os.listdir(tdir)):
                    if name != "sys" and name not in self.tenants and \
                            os.path.isdir(os.path.join(tdir, name)):
                        self.create_tenant(name, wal_replicas=wal_replicas,
                                           _boot=True)

        # one boot log line naming the RESOLVED backend; gv$backend
        # serves the same info through SQL
        import logging

        from oceanbase_tpu.server.backend_info import backend_summary

        logging.getLogger("oceanbase_tpu.server").info(
            "boot backend: %s", backend_summary(self.cost_units))

    # ------------------------------------------------------------------
    def create_tenant(self, name: str, wal_replicas: int = 3,
                      _boot: bool = False) -> Tenant:
        if name in self.tenants:
            if _boot:
                return self.tenants[name]
            raise ValueError(f"tenant {name} exists")
        troot = (os.path.join(self.root, "tenants", name)
                 if self.root else None)
        if troot:
            os.makedirs(troot, exist_ok=True)
        t = Tenant(name, troot, self.config, wal_replicas=wal_replicas)
        self.tenants[name] = t
        return t

    def drop_tenant(self, name: str):
        if name == "sys":
            raise ValueError("cannot drop sys tenant")
        t = self.tenants.pop(name, None)
        if t is not None:
            t.close()
        if self.root:
            import shutil

            troot = os.path.join(self.root, "tenants", name)
            if os.path.isdir(troot):
                shutil.rmtree(troot, ignore_errors=True)

    def tenant(self, name: str = "sys") -> Tenant:
        return self.tenants[name]

    @property
    def tls_context(self):
        """Lazily built server TLS context (self-signed credentials
        persisted under <root>/tls; None for in-memory databases)."""
        if self.root is None:
            return None
        ctx = getattr(self, "_tls_ctx", None)
        if ctx is None:
            from oceanbase_tpu.server.tls import server_context

            ctx = self._tls_ctx = server_context(self.root)
        return ctx

    # -- users (mysql_native_password credentials) -----------------------
    def create_user(self, name: str, password: str):
        from oceanbase_tpu.server.mysql_protocol import mysql_native_hash

        self.users[name] = mysql_native_hash(password)
        self._persist_users()

    def drop_user(self, name: str):
        if name == "root":
            raise ValueError("cannot drop root")
        self.users.pop(name, None)
        self._persist_users()

    def set_password(self, name: str, password: str):
        if name not in self.users:
            raise KeyError(f"unknown user {name}")
        self.create_user(name, password)

    def _persist_users(self):
        if not self._users_path:
            return
        import json as _json

        tmp = self._users_path + ".tmp"
        with open(tmp, "w") as fh:
            _json.dump({u: h.hex() for u, h in self.users.items()}, fh)
        os.replace(tmp, self._users_path)

    @property
    def wal(self):
        return self.tenants["sys"].wal

    # ------------------------------------------------------------------
    def session(self, tenant: str = "sys"):
        from oceanbase_tpu.sql.session import Session

        return Session(self.tenants[tenant], self)

    def checkpoint(self, tenant: str | None = None):
        for name, t in self.tenants.items():
            if tenant is None or name == tenant:
                t.checkpoint()

    def backup(self, dest_root: str):
        """Physical backup: checkpoint everything, then copy the data tree
        (≙ data backup, src/storage/backup).  Restore = Database(dest)."""
        if self.root is None:
            raise ValueError("in-memory database cannot be backed up")
        import shutil

        self.checkpoint()
        os.makedirs(os.path.dirname(dest_root) or ".", exist_ok=True)
        shutil.copytree(self.root, dest_root, dirs_exist_ok=False)

    def close(self):
        self.ash.stop()
        self.jobs.stop()
        self.workload.stop()
        for t in self.tenants.values():
            t.close()
