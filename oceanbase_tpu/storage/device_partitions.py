"""A hash-partitioned table on the mesh: one device copy per partition.

Reference analog: a partition's leader replica living on one observer
(``DBA_OB_TABLE_LOCATIONS``), so that a PX plan reads every partition where
it lies and the optimizer derives its exchanges from the table's
partitioning (``ObShardingInfo``).  Here partition ``i`` of a table lives on
device ``i``: the copies share one ladder capacity, so together they ARE one
array sharded over the mesh, and a ``shard_map`` program takes them with no
movement at all.

The copies are cut on the device from the table's whole relation
(``StorageCatalog._device_copy``: the partitions' snapshots in partition
order, one string dictionary for all of them), which serial plans read; both
live and die with that relation, i.e. with the table's ``data_version``.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.server import trace as qtrace
from oceanbase_tpu.vector.column import Relation

qmetrics.declare("px.partition_builds", "counter",
                 "device copies of one partition of a hash-partitioned "
                 "table placed on its device (once per partition and data "
                 "version: none while a table does not change)")
qmetrics.declare("px.partition_build_ns", "counter",
                 "time spent cutting partitions out of a table's relation "
                 "and copying them to their devices", unit="ns")


@functools.partial(jax.jit, static_argnames=("capacity", "padded"))
def _cut_lanes(x, offset, rows, capacity: int, padded: bool):
    """Lanes ``[offset, offset + rows)`` of one array in ``capacity``
    lanes, the rest zeroed.  ``offset`` and ``rows`` are traced: one
    program per (array shape, capacity), whatever the row counts of a
    load.  ``padded``: ``offset + capacity`` may pass the last lane, so
    the array is read with ``capacity`` zero lanes behind it."""
    if padded:
        pad = jnp.zeros((capacity,) + x.shape[1:], dtype=x.dtype)
        x = jnp.concatenate([x, pad])
    piece = jax.lax.dynamic_slice_in_dim(x, offset, capacity)
    keep = (jnp.arange(capacity) < rows).reshape(
        (capacity,) + (1,) * (x.ndim - 1))
    return jnp.where(keep, piece, jnp.zeros_like(piece))


def _cut(whole: Relation, offset: int, rows: int, capacity: int) -> Relation:
    """Lanes ``[offset, offset + rows)`` of ``whole`` as a relation of
    ``capacity`` lanes, the rest dead and zeroed: a program a column, so
    that what a cut keeps beside its output is one column's (the TPU
    holds a 64-bit column as two 32-bit halves, and ONE program over
    ``lineitem``'s SF10 relation split every such column at once: 4.3 GB
    of temporaries beside the 6.4 GB it read, compiled for a described
    v5e), and none at all where the slice cannot pass the whole's last
    lane (every partition of a large table)."""
    padded = offset + capacity > whole.capacity

    def cut(x):
        return _cut_lanes(x, offset, rows, capacity=capacity, padded=padded)

    cols = {n: c.with_data(cut(c.data),
                           None if c.valid is None else cut(c.valid))
            for n, c in whole.columns.items()}
    return Relation(columns=cols, mask=jnp.arange(capacity) < rows)


class DevicePartitions:
    """Where a hash-partitioned table's rows lie on the devices."""

    def __init__(self, table: str, key_cols, tablegroup, rows,
                 whole: Relation, capacity: int):
        self.table = table
        self.key_cols = tuple(key_cols)
        self.tablegroup = tablegroup
        self.rows = [int(n) for n in rows]      # live rows per partition
        self.capacity = int(capacity)           # lanes per partition
        # the whole relation's arrays, not the object the catalog caches
        # (which points here): no reference cycle keeps device memory
        self._whole = Relation(columns=whole.columns, mask=whole.mask)
        self._sharded: Relation | None = None
        self._devices: tuple = ()
        self._lock = threading.Lock()

    @property
    def nparts(self) -> int:
        return len(self.rows)

    def devices(self) -> list:
        """Device of each partition's copy; empty until it is built."""
        return [str(d) for d in self._devices]

    def sharded(self, mesh, axis: str) -> Relation:
        """The table as one relation sharded over ``mesh``: shard ``i`` is
        partition ``i`` on ``mesh``'s device ``i``.  Built on first use,
        then kept for as long as the whole relation is."""
        devs = tuple(mesh.devices.flat)
        if len(devs) != self.nparts:
            raise ValueError(f"{self.table}: {self.nparts} partitions do "
                             f"not lie on a mesh of {len(devs)}")
        with self._lock:
            if self._sharded is None or self._devices != devs:
                self._sharded = self._build(devs, NamedSharding(mesh,
                                                                P(axis)))
                self._devices = devs
            return self._sharded

    def _build(self, devs, sharding) -> Relation:
        from oceanbase_tpu.share.kvcache import relation_bytes

        pieces = []
        offsets = np.concatenate([[0], np.cumsum(self.rows)])
        for i, dev in enumerate(devs):
            with qtrace.span("px.partition_build", table=self.table,
                             partition=i, rows=self.rows[i],
                             device=str(dev)) as sp:
                piece = jax.device_put(
                    _cut(self._whole, int(offsets[i]), self.rows[i],
                         self.capacity), dev)
                jax.block_until_ready(piece)
                sp.tags["bytes"] = relation_bytes(piece)
            qmetrics.inc("px.partition_builds")
            qmetrics.inc("px.partition_build_ns", int(sp.elapsed_s * 1e9))
            pieces.append(piece)

        def join(*shards):
            shape = (len(shards) * self.capacity,) + shards[0].shape[1:]
            return jax.make_array_from_single_device_arrays(
                shape, sharding, list(shards))

        return jax.tree_util.tree_map(join, *pieces)
