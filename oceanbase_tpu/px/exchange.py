"""PX exchange: repartition/broadcast between plan fragments via collectives.

Reference analog: ObPxTransmitOp slice calc + DTL send
(src/sql/engine/px/exchange/ob_px_transmit_op.cpp:576,
src/sql/engine/px/ob_slice_calc.h:73) and ObPxReceiveOp channel polling
(src/sql/engine/px/exchange/ob_px_receive_op.h:83).

On TPU the transmit/receive pair collapses into one collective:

    HASH / PKEY   -> bucket the rows by hash(keys) % ndev, pack into a
                     [ndev, cap] send buffer, jax.lax.all_to_all over ICI
    BROADCAST     -> jax.lax.all_gather
    datahub       -> jax.lax.psum

Everything here runs *inside* shard_map over the mesh axis — the per-shard
view is the PX worker (SQC task analog).  Capacities are static: the
planner budgets cap_per_dest; overflow rows are counted into a diagnostics
lane rather than silently dropped (≙ DTL flow-control backpressure made
compile-time).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from oceanbase_tpu.datatypes import SqlType
from oceanbase_tpu.exec import diag
from oceanbase_tpu.exec.ops import _combined_key
from oceanbase_tpu.expr import ir
from oceanbase_tpu.expr.compile import eval_expr
from oceanbase_tpu.share import keyhash
from oceanbase_tpu.vector.column import Column, Relation

PX_AXIS = "px"


def default_mesh(n_devices: int | None = None, axis: str = PX_AXIS):
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return jax.sharding.Mesh(np.array(devs), (axis,))


# ---------------------------------------------------------------------------
# host-side sharding of whole tables onto the mesh (granule assignment)
# ---------------------------------------------------------------------------


def shard_relation(rel: Relation, mesh, axis: str = PX_AXIS) -> Relation:
    """Row-shard a device relation across the mesh (block distribution).

    ≙ granule->worker assignment (ObGranulePump::fetch_granule_task,
    src/sql/engine/px/ob_granule_pump.cpp:361) made static: contiguous row
    ranges per chip.  Pads capacity to a multiple of the mesh size; the pad
    rows are masked dead.
    """
    ndev = mesh.devices.size
    n = rel.capacity
    cap = ((n + ndev - 1) // ndev) * ndev
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(axis)
    )
    mask = np.ones(n, dtype=bool) if rel.mask is None else np.asarray(rel.mask)
    pad_mask = np.zeros(cap, dtype=bool)
    pad_mask[:n] = mask

    cols = {}
    for name, c in rel.columns.items():
        d = np.asarray(c.data)
        pad = np.zeros((cap - n,) + d.shape[1:], dtype=d.dtype)
        d2 = jax.device_put(np.concatenate([d, pad]), sharding)
        v2 = None
        if c.valid is not None:
            v = np.asarray(c.valid)
            v2 = jax.device_put(
                np.concatenate([v, np.zeros(cap - n, dtype=bool)]), sharding
            )
        cols[name] = Column(d2, v2, c.dtype, c.sdict)
    return Relation(columns=cols, mask=jax.device_put(pad_mask, sharding))


def shard_relation_by_hash(rel: Relation, key_cols: Sequence[str], mesh,
                           axis: str = PX_AXIS) -> Relation:
    """Hash-shard a device relation by key columns: rows with equal keys
    land on the same chip, so a join between two relations sharded on
    their join keys needs NO exchange (partition-wise join / PKEY
    distribution, ≙ ob_pwj_comparer.h matching + PKEY slice routing).

    The destinations are ``share/keyhash.py``'s, the function the
    in-program exchanges and the storage router use; key columns must be
    non-string (dict codes are relation-local).  NULL-key rows hash on 0
    — they never match an equi-join, any placement works."""
    ndev = mesh.devices.size
    datas = []
    for c in key_cols:
        col = rel.columns[c]
        d = np.asarray(col.data).astype(np.int64)
        if col.valid is not None:
            d = np.where(np.asarray(col.valid), d, 0)
        datas.append(d)
    dest = keyhash.partition_of(datas, ndev).astype(np.int64)
    n = rel.capacity
    mask = np.ones(n, dtype=bool) if rel.mask is None \
        else np.asarray(rel.mask)
    dest = np.where(mask, dest, ndev)  # dead rows fill the shortest shard
    order = np.argsort(dest, kind="stable")
    counts = np.bincount(dest[order], minlength=ndev + 1)[:ndev]
    cap = int(max(counts.max(initial=0), 1))
    cap = ((cap + 7) // 8) * 8  # mild alignment
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(axis))

    # slot assignment: row j of bucket b -> b*cap + j; dead rows pad
    pos = np.arange(n)
    sd = dest[order]
    in_bucket = pos - np.concatenate(
        [[0], np.cumsum(np.bincount(sd, minlength=ndev + 1))])[sd]
    live_rows = sd < ndev
    slot_of_sorted = np.where(live_rows, sd * cap + in_bucket, -1)

    out_mask = np.zeros(ndev * cap, dtype=bool)
    taken = slot_of_sorted[live_rows]
    out_mask[taken] = mask[order][live_rows]
    cols = {}
    for name, c in rel.columns.items():
        d = np.asarray(c.data)
        buf = np.zeros((ndev * cap,) + d.shape[1:], dtype=d.dtype)
        buf[taken] = d[order][live_rows]
        v2 = None
        if c.valid is not None:
            v = np.asarray(c.valid)
            vbuf = np.zeros(ndev * cap, dtype=bool)
            vbuf[taken] = v[order][live_rows]
            v2 = jax.device_put(vbuf, sharding)
        cols[name] = Column(jax.device_put(buf, sharding), v2, c.dtype,
                            c.sdict)
    return Relation(columns=cols, mask=jax.device_put(out_mask, sharding))


def unshard_relation(rel: Relation) -> Relation:
    """Gather a sharded relation back to one addressable array set."""
    cols = {
        n: Column(jnp.asarray(c.data), None if c.valid is None else
                  jnp.asarray(c.valid), c.dtype, c.sdict)
        for n, c in rel.columns.items()
    }
    m = None if rel.mask is None else jnp.asarray(rel.mask)
    return Relation(columns=cols, mask=m)


# ---------------------------------------------------------------------------
# in-SPMD exchanges (call inside shard_map)
# ---------------------------------------------------------------------------


def _hash_dest(rel: Relation, keys: Sequence[ir.Expr], ndev: int):
    cols = [eval_expr(e, rel) for e in keys]
    k, _ = _combined_key(cols)
    return keyhash.dest_of(k, ndev)


def row_bytes(rel: Relation) -> int:
    """Bytes of one row of ``rel`` as an exchange moves it: every
    column's element, a byte where it has a validity mask, and the row
    mask's byte."""
    mask_bytes = 1 if rel.mask is None else rel.mask.dtype.itemsize
    return mask_bytes + sum(
        c.data.dtype.itemsize * int(np.prod(c.data.shape[1:], dtype=int))
        + (c.valid is not None) for c in rel.columns.values())


def _count_received(kind: str | None, recv: Relation):
    """The live rows this shard received over an exchange of ``kind``
    (``px.exchange_rows``; ``diag.count_rows``)."""
    if kind is not None:
        diag.count_rows(kind, row_bytes(recv),
                        jnp.sum(recv.mask_or_true(), dtype=jnp.int64))


def exchange_by_dest(
    rel: Relation,
    dest,
    ndev: int,
    cap_per_dest: int,
    axis_name: str = PX_AXIS,
    kind: str | None = None,
) -> tuple[Relation, jnp.ndarray]:
    """Ship each local row to the shard named by ``dest`` (dead rows must
    carry dest == ndev, the drop sentinel).  The generic transmit half of
    every slice strategy — HASH, RANGE, PKEY all reduce to a dest vector
    (≙ ObSliceIdxCalc::get_slice_indexes + DTL send, as one all_to_all).

    Returns (received relation with capacity ndev*cap_per_dest, local
    overflow count)."""
    n = rel.capacity
    # rows by destination: one sort of (dest, lane); equal destinations
    # may come in any order.  Destination d's rows are then the sorted
    # lanes [bounds[d], bounds[d + 1]): the send buffer's slot (d, j) is
    # FILLED FROM sorted lane bounds[d] + j, a gather of ndev x cap lanes
    # a column (a scatter of n lanes a column costs the TPU ten times as
    # much an element), and nothing counts with a scatter-add
    s_dest, order = jax.lax.sort(
        (dest.astype(jnp.int32), jnp.arange(n, dtype=jnp.int32)),
        num_keys=1, is_stable=False)
    bounds = jnp.searchsorted(
        s_dest, jnp.arange(ndev + 1, dtype=jnp.int32), side="left")
    counts = bounds[1:] - bounds[:-1]
    overflow = jnp.sum(jnp.maximum(counts - cap_per_dest, 0))
    j = jnp.arange(cap_per_dest, dtype=jnp.int32)
    sent_mask = j[None, :] < counts[:, None]            # [ndev, cap]
    src = jnp.take(order, jnp.clip(bounds[:-1, None] + j[None, :],
                                   0, n - 1))           # source lanes

    def gather(x):
        keep = sent_mask.reshape(sent_mask.shape + (1,) * (x.ndim - 1))
        return jnp.where(keep, jnp.take(x, src, axis=0),
                         jnp.zeros((), x.dtype))

    recv_cols = {}
    # exchange the [ndev, cap] buffers; only live rows have dest < ndev,
    # so a slot that is sent is a live row
    ex_mask = _a2a(sent_mask, axis_name)
    for name, c in rel.columns.items():
        rd = _a2a(gather(c.data), axis_name)
        rv = None
        if c.valid is not None:
            rv = _a2a(gather(c.valid), axis_name).reshape(-1)
        recv_cols[name] = Column(
            rd.reshape((ndev * cap_per_dest,) + rd.shape[2:]), rv, c.dtype, c.sdict
        )
    out = Relation(columns=recv_cols, mask=ex_mask.reshape(-1))
    _count_received(kind, out)
    return out, overflow


def all_to_all_repartition(
    rel: Relation,
    keys: Sequence[ir.Expr],
    ndev: int,
    cap_per_dest: int,
    axis_name: str = PX_AXIS,
    kind: str | None = None,
) -> tuple[Relation, jnp.ndarray]:
    """HASH-repartition the local shard across the mesh axis.

    Rows with the same key hash land on the same chip.
    ≙ ObSliceIdxCalc hash slice + DTL send/recv, as one all_to_all.
    """
    m = rel.mask_or_true()
    dest = jnp.where(m, _hash_dest(rel, keys, ndev), ndev)  # dead -> sentinel
    return exchange_by_dest(rel, dest, ndev, cap_per_dest, axis_name, kind)


def _a2a(x, axis_name):
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=False)


def broadcast_gather(rel: Relation, axis_name: str = PX_AXIS,
                     kind: str | None = None) -> Relation:
    """BROADCAST distribution: every chip receives every shard's rows
    (≙ ObSliceIdxCalc BROADCAST + bc2host; on TPU it's one all_gather)."""
    cols = {}
    for name, c in rel.columns.items():
        d = jax.lax.all_gather(c.data, axis_name, axis=0, tiled=True)
        v = None
        if c.valid is not None:
            v = jax.lax.all_gather(c.valid, axis_name, axis=0, tiled=True)
        cols[name] = Column(d, v, c.dtype, c.sdict)
    m = jax.lax.all_gather(rel.mask_or_true(), axis_name, axis=0, tiled=True)
    out = Relation(columns=cols, mask=m)
    _count_received(kind, out)
    return out


def datahub_psum(x, axis_name: str = PX_AXIS):
    """Coordinator-mediated aggregation (≙ PX datahub,
    src/sql/engine/px/datahub/components/) — semantically an allreduce."""
    return jax.lax.psum(x, axis_name)
