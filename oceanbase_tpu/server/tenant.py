"""Multi-tenancy: isolated resource units per tenant.

Reference analog: the omt layer (ObMultiTenant,
src/observer/omt/ob_multi_tenant.h:71) — per-tenant resource units (CPU
via worker counts, memory budgets), request queues/workers
(ObThWorker, src/observer/omt/ob_th_worker.cpp:345) and the MTL module
registry (src/share/rc/ob_tenant_base.h:615).

Each tenant here owns the full module stack: storage engine (own data
directory), WAL (own PALF group), transaction service, catalog, config
overlay, a bounded worker pool (the CPU quota) and a PX admission
semaphore (≙ ObPxAdmission per-tenant target)."""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from oceanbase_tpu.palf.cluster import PalfCluster
from oceanbase_tpu.server.config import Config, kv_cache_limit
from oceanbase_tpu.storage.engine import StorageCatalog, StorageEngine
from oceanbase_tpu.tx.service import TransService


class PxQuota:
    """The PX workers a tenant's statements may hold at once (≙ the px
    target monitor behind ``parallel_servers_target``): a statement asks
    for its degree of parallelism in workers and runs serially when they
    are not there.  ``resize`` takes effect for the next statement; what
    is held is given back against the new limit."""

    def __init__(self, limit: int):
        self.limit = int(limit)
        self._held = 0
        self._lock = threading.Lock()

    def acquire(self, blocking: bool = False, n: int = 1) -> bool:
        # admission never waits: a statement without workers is downgraded
        del blocking
        with self._lock:
            if self._held + n > self.limit:
                return False
            self._held += n
            return True

    def release(self, n: int = 1):
        with self._lock:
            self._held = max(self._held - n, 0)

    def resize(self, limit: int):
        with self._lock:
            self.limit = int(limit)


class Tenant:
    def __init__(self, name: str, root: str | None, cluster_config: Config,
                 wal_replicas: int = 3, wal=None, recovery=None,
                 corrupt_policy: str = "raise"):
        """``wal``: inject an external log handle (a NetPalf group whose
        replicas live in other OS processes, palf/netcluster.py) instead
        of the in-process PalfCluster — the multi-node path.
        ``recovery``: a shared RecoveryState (the node process passes its
        own so rebuild + boot events land in one gv$recovery log).
        ``corrupt_policy``: what boot does with a checksum-failing
        segment — "raise" (no repair source) or "quarantine" (cluster
        node; the scrub plane refetches from a peer)."""
        import time as _time

        from oceanbase_tpu.server import trace as qtrace
        from oceanbase_tpu.storage.recovery import RecoveryState

        self.name = name
        self.config = Config(parent=cluster_config)
        self.recovery = recovery if recovery is not None \
            else RecoveryState()
        # serializes checkpoint() across its three callers (the node's
        # periodic loop, rebuild.fetch_meta handlers, admin sessions):
        # interleaved checkpoints could persist a REGRESSED replay point
        self._ckpt_lock = threading.Lock()
        data_dir = os.path.join(root, "data") if root else None
        wal_dir = os.path.join(root, "wal") if root else None
        if wal_dir:
            os.makedirs(wal_dir, exist_ok=True)
        self.engine = StorageEngine(data_dir,
                                    corrupt_policy=corrupt_policy)
        if wal is not None:
            self.wal = wal
            local = wal.replica  # NetPalf: this process's replica
        else:
            self.wal = PalfCluster(wal_replicas, log_root=wal_dir)
            self.wal.elect()
            local = self.wal.replicas[self.wal.leader_id]
        self.tx = TransService(wal=self.wal)
        self.tx.engine = self.engine  # secondary-index maintenance

        # restart tier: replay the palf WAL tail from the persisted
        # replay point (the periodic checkpoint keeps it O(tail), not
        # O(history)) through the service's PERSISTENT replay buffers,
        # so a commit record arriving later via catch-up still finds
        # redo the boot replay buffered
        start = self.engine.meta.get("wal_lsn", 0)
        m0 = _time.monotonic()
        stats: dict = {}
        if local.committed_lsn > start:
            with qtrace.span("recovery.replay", tenant=name,
                             start_lsn=start, end_lsn=local.committed_lsn):
                max_ts = self.tx.apply_replay(
                    local.entries_between(start, local.committed_lsn),
                    stats=stats)
            self.tx.gts.advance_to(max_ts)
        if stats.get("entries") or start or local.last_lsn():
            # a networked replica restores its log but cannot know the
            # commit point without quorum: its apply happens through
            # catch-up (leader push / election noop) from ``start``
            deferred = local.last_lsn() - max(local.committed_lsn, start)
            self.recovery.record(
                "boot_replay", tenant=name, wal_start_lsn=start,
                wal_end_lsn=local.committed_lsn,
                entries=stats.get("entries", 0),
                prepared=stats.get("prepared", 0),
                elapsed_s=_time.monotonic() - m0,
                note=f"commits={stats.get('commits', 0)}"
                     + (f" deferred_to_catchup={deferred}"
                        if deferred > 0 else ""))
        # durable XA: branches prepared before the crash reconstruct
        # into PREPARE state (XA RECOVER reports them; XA COMMIT applies
        # their WAL-buffered redo) — closes the round-5 LIMITATION
        with qtrace.span("recovery.restore_prepared", tenant=name) as sp:
            restored = self.tx.restore_prepared()
            sp.tags["branches"] = len(restored)
        if restored:
            self.recovery.record(
                "restore_prepared", tenant=name, prepared=len(restored),
                xids=",".join(sorted(tx.xid for tx in restored
                                     if tx.xid)))
        # incremental apply (multi-node) resumes where boot replay ended:
        # entries at/below the checkpoint replay-point are already in the
        # engine (segments/slog), later committed ones were just replayed
        local.applied_lsn = max(local.applied_lsn, start,
                                local.committed_lsn)
        self.tx.gts.advance_to(self.engine.meta.get("gts", 0))
        # bulk_load (CTAS / LOAD DATA / direct load) stamps segments with
        # GTS values that reach neither the WAL nor (pre-checkpoint) the
        # persisted meta — seed GTS past every persisted segment version
        # so the boot snapshot sees them
        self.tx.gts.advance_to(max(
            (s.max_version for ts in self.engine.tables.values()
             for s, _ in ts.tablet.segment_locations()), default=0))

        self.catalog = StorageCatalog(self.engine,
                                      snapshot_fn=self.tx.gts.current,
                                      config=self.config)
        self.catalog._cache.resize(kv_cache_limit(self.config))

        # satellites: sequences, table locks, KV/CDC front-ends
        from oceanbase_tpu.share.sequence import SequenceManager
        from oceanbase_tpu.tx.tablelock import LockTable

        self.sequences = SequenceManager(self.engine)
        self.locks = LockTable()
        self.tx.lock_table = self.locks
        self.tx.lock_wait_timeout_s = float(
            self.config["lock_wait_timeout_s"])

        def _on_cfg(k, v):
            if k == "lock_wait_timeout_s":
                self.tx.lock_wait_timeout_s = float(v)
            elif k == "kv_cache_limit_bytes":
                self.catalog._cache.resize(kv_cache_limit(self.config))
            elif k in ("enable_shape_buckets", "shape_bucket_growth",
                       "shape_bucket_floor"):
                # cached relations were padded under the old policy;
                # drop them so the next read re-materializes
                self.catalog._cache.invalidate()
            elif k in ("parallel_servers_target", "px_workers_per_tenant"):
                self.px_admission.resize(self._px_workers())

        # hot-reload from the tenant overlay AND the cluster config
        self.config.watch(_on_cfg)
        cluster_config.watch(_on_cfg)

        # CPU quota = bounded worker pool (≙ tenant unit min/max cpu)
        self._pool = ThreadPoolExecutor(
            max_workers=int(self.config["tenant_cpu_quota"]),
            thread_name_prefix=f"tnt-{name}")
        # PX admission quota (≙ px target monitor)
        self.px_admission = PxQuota(self._px_workers())
        self.memory_used = 0

        # memstore write backpressure (≙ writing throttling): byte
        # accounting + ramp/hard-limit at the TransService.write choke
        # point; pressure kicks a horizon-clamped freeze/flush of the
        # fattest table, and the engine's flush listener re-bases the
        # accounting when any flush (throttle-kicked, row-threshold or
        # checkpoint) clears memtable rows
        from oceanbase_tpu.server.admission import MemstoreThrottle

        self.throttle = MemstoreThrottle(self.config,
                                         flush_cb=self._pressure_flush)
        self.tx.throttle = self.throttle
        self.engine.flush_listener = self.throttle.on_flush

        # disk-pressure plane: per-surface byte budgets (log/data/spill)
        # with read-only degradation; the log surface reclaims
        # (aggressive checkpoint + WAL recycle) before it degrades.  The
        # spill surface is accounted incrementally by TempFileStore, so
        # it needs no walk paths.
        from oceanbase_tpu.server.diskmgr import DiskManager

        self.diskmgr = DiskManager(
            self.config,
            paths={"log": [wal_dir] if wal_dir else [],
                   "data": [data_dir] if data_dir else []},
            reclaim_cb=self.reclaim_log_disk)
        self.tx.diskmgr = self.diskmgr

    def _px_workers(self) -> int:
        """``parallel_servers_target`` when set, else the older
        ``px_workers_per_tenant``."""
        return int(self.config["parallel_servers_target"]) \
            or int(self.config["px_workers_per_tenant"])

    def _pressure_flush(self, table: str):
        """Memstore-pressure flush: freeze + flush ``table`` at the
        PR-6 flush horizon (never past a live writer's snapshot) so
        throttled writers unblock without losing conflict checks."""
        try:
            self.engine.freeze_and_flush(
                table, snapshot=self.tx.flush_snapshot())
        except KeyError:
            self.throttle.drop_table(table)  # dropped mid-pressure

    def reclaim_log_disk(self):
        """Log-disk pressure reclaim: checkpoint aggressively, then
        recycle the WAL prefix below the persisted replay point — the
        checkpoint made those entries' effects durable in segments, so
        boot replay never needs them again."""
        self.checkpoint()
        if hasattr(self.wal, "recycle"):
            self.wal.recycle(int(self.engine.meta.get("wal_lsn", 0)))

    def kv(self, table: str):
        """OBKV-style table API handle (≙ src/libtable client)."""
        from oceanbase_tpu.kv import KvTable

        return KvTable(self, table)

    def cdc(self):
        """Change-data-capture pump over this tenant's WAL (≙ libobcdc)."""
        from oceanbase_tpu.cdc import CdcPump

        return CdcPump(self)

    def submit(self, fn, *args, **kwargs):
        """Queue work onto this tenant's workers (≙ tenant request queue)."""
        return self._pool.submit(fn, *args, **kwargs)

    def checkpoint(self):
        with self._ckpt_lock:
            self._checkpoint_locked()

    def _checkpoint_locked(self):
        import time as _time

        from oceanbase_tpu.server import trace as qtrace

        # capture the replay point BEFORE the flush snapshot: commit()
        # assigns the version before appending to the WAL, so every
        # commit at or below this LSN has version <= snap and is covered
        # by the flushed segments (a commit landing between the two reads
        # has LSN > wal_lsn and is replayed on recovery)
        m0 = _time.monotonic()
        # the flush horizon clamps BOTH halves to the oldest active
        # transaction: versions a live writer's conflict check still
        # needs stay in the memtables, and the replay point only covers
        # commits the clamped flush snapshot captured
        snap, wal_lsn = self.tx.flush_horizon()
        # a follower may have committed-but-not-yet-applied entries:
        # those are not in its memtables, so the flush below would not
        # cover them — the replay point must not skip them
        local = getattr(self.wal, "replica", None)
        if local is not None:
            wal_lsn = min(wal_lsn, local.applied_lsn)
        # group commit keeps ordinary live transactions out of the WAL,
        # but a prepared XA branch's redo lives ONLY there until its
        # commit/abort — never advance past its prepare batch
        clamp = self.tx.min_prepared_lsn()
        if clamp is not None:
            wal_lsn = min(wal_lsn, clamp)
        # monotonic: a long-lived tx can clamp this checkpoint's horizon
        # BELOW a previous one; commits under the old replay point are
        # already durable in segments, so never regress it
        wal_lsn = max(wal_lsn, int(self.engine.meta.get("wal_lsn", 0)))
        with qtrace.span("recovery.checkpoint", tenant=self.name,
                         wal_lsn=wal_lsn):
            for name in list(self.engine.tables):
                self.engine.freeze_and_flush(name, snapshot=snap)
            self.engine.meta["wal_lsn"] = wal_lsn
            self.engine.meta["gts"] = self.tx.gts.current()
            self.engine.checkpoint()
        self.recovery.record(
            "checkpoint", tenant=self.name, wal_end_lsn=wal_lsn,
            elapsed_s=_time.monotonic() - m0,
            note=f"clamped={clamp is not None}")

    def close(self):
        self._pool.shutdown(wait=False)
        self.wal.close()
