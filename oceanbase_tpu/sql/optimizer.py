"""Cost-based join optimizer: plans priced in predicted SECONDS.

Reference analog: the CBO join-order enumeration (src/sql/optimizer —
ObJoinOrder with DP/IDP enumeration, ob_join_order_enum_idp.cpp) and the
cost model (ObOptEstCost).  Three layers replace the old left-deep,
cardinality-only DP:

1. **Cost model in seconds** (``CostModel``): every candidate operator
   is priced as ``predict_seconds(gv$cost_units, flops, bytes)`` —
   the calibrated roofline from server/calibrate.py — scaled by the
   per-operator-type correction factor ``gv$time_calibration`` has
   measured (dev_s_sum / pred_s_sum).  Without a calibration probe the
   model falls back to conservative CPU constants, so ranking still
   reflects the real asymmetries (a build-side sort is n·log n, a
   probe is a searchsorted, an index probe skips the sort entirely).

2. **Bushy DP / IDP enumeration** (``_dp_bushy`` / ``_idp_tree``):
   subset DP over the equi-join graph up to DP_MAX_RELS relations
   (TPC-H tops out at 8), bushy trees allowed; beyond that, IDP(k) —
   greedy seed order, then windowed DP re-optimization collapsing each
   window's best tree into a composite vertex (≙ the reference's
   iterative dynamic programming).  Join output estimates are NDV-based
   with the PK-side rule applied as an UPPER BOUND, not a shortcut: a
   filtered unique side keeps its filter selectivity (the old
   ``return est`` ignored it — TPC-H Q17's 16M-row capacity cliff).

3. **Access paths worth choosing between**: per join the model prices
   (a) hash join probe→build, (b) hash join build→probe (orientation —
   the build side pays the argsort), and (c) an index nested-loop
   probe (exec/plan.py::IndexProbe) over a secondary index of the
   build-side base table, when one exists on the join key.  Semi/anti
   subquery edges (binder ``qb.semi_edges``) are PLACED by cost: on the
   home fragment (filter early) or above the join tree (probe the
   reduced intermediate) — TPC-H Q21's equality-expansion shrinks by
   the full join selectivity in the latter spot.

Static capacities (the TPU twist): every join gets an out_capacity
budget derived from the cardinality estimate; underestimates surface as
CapacityOverflow at runtime and the session retries with a larger
budget (≙ the reference spilling to disk where we re-plan).  Capacities
clamp at CAP_MAX: the overflow routes to the disk-spill tier instead of
an int32 crash.  ``gv$plan_feedback`` corrections re-seed both the
budgets and the estimate ledger at bind time (``apply_feedback``), so a
misestimate observed once does not compound into the next plan.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from oceanbase_tpu.exec import plan as pp
from oceanbase_tpu.expr import ir

DP_MAX_RELS = 10
CAP_MAX = 1 << 28  # rows; beyond this the spill tier is the answer

# index nested-loop: only exact int-like single-column keys keep the
# searchsorted probe collision-free (string/multi-key would need the
# verification expansion a plain hash join already pays)
_INL_MIN_SHRINK = 4  # probe side must be this much under the base rows


def _pow2(n: int) -> int:
    p = 1
    while p < max(1, n):
        p <<= 1
    return min(p, CAP_MAX)


def _bucket(rows: int, slack: float) -> int:
    """The static budget for ``rows`` estimated or observed rows: the
    power of two over rows x slack (+16 so that tiny estimates keep room)."""
    return _pow2(int(min(rows, CAP_MAX) * slack) + 16)


# ---------------------------------------------------------------------------
# cost model: operators priced in predicted seconds
# ---------------------------------------------------------------------------


def _default_units():
    """Conservative single-core CPU constants used before any ALTER
    SYSTEM CALIBRATE has populated gv$cost_units: the absolute seconds
    are rough, but the RATIOS (sort vs probe vs gather) are what plan
    ranking consumes."""
    from oceanbase_tpu.server.calibrate import CostUnits

    return CostUnits(backend="uncalibrated", peak_flops_s=2.0e9,
                     peak_bytes_s=8.0e9, eff_bytes_s=4.0e9,
                     launch_overhead_s=20e-6)


def _log2(n: int) -> int:
    return max(int(n), 2).bit_length()


class CostModel:
    """Prices candidate plan operators in predicted seconds.

    ``units`` defaults to the process gv$cost_units payload
    (server/calibrate.py::get_cost_units — populated by ALTER SYSTEM
    CALIBRATE) or the uncalibrated fallback constants.  ``corrections``
    maps operator-type name -> measured correction factor from
    gv$time_calibration (dev_s_sum / pred_s_sum), so operator families
    the roofline consistently misprices are re-anchored to measurement.
    """

    def __init__(self, units=None, corrections: dict | None = None):
        if units is None:
            from oceanbase_tpu.server import calibrate as qcal

            units = qcal.get_cost_units() or _default_units()
        self.units = units
        self.corrections = dict(corrections or {})

    def seconds(self, op: str, flops: float, nbytes: float,
                calls: int = 1) -> float:
        from oceanbase_tpu.server.calibrate import predict_seconds

        s = predict_seconds(self.units, flops, nbytes, calls)
        return s * float(self.corrections.get(op, 1.0))

    # -- operator shapes (flops/bytes mirror exec/ops.py's kernels) ----
    def hash_join_s(self, probe: int, build: int, out: int,
                    ncols: int = 4) -> float:
        """Sort-based equi-join: the build side pays an argsort
        (n log n), the probe two searchsorteds (m log n), the output an
        expansion gather per column."""
        lb = _log2(build)
        flops = 4.0 * build * lb + 2.0 * probe * lb + 2.0 * out
        nbytes = 8.0 * (2.0 * build * lb / 4 + 2.0 * probe
                        + out * max(ncols, 2))
        return self.seconds("HashJoin", flops, nbytes)

    def index_probe_s(self, probe: int, idx_rows: int, expand: int,
                      ncols: int = 4) -> float:
        """Index nested-loop: searchsorted into the PRE-SORTED index
        sidecar (no build sort), then one gather per output column at
        the matched base positions."""
        flops = 2.0 * probe * _log2(idx_rows) + 2.0 * expand
        nbytes = 8.0 * (2.0 * probe + expand * max(ncols, 2))
        return self.seconds("IndexProbe", flops, nbytes)

    def semi_s(self, probe: int, build: int, expand: int) -> float:
        """Semi/anti join; ``expand`` is the equality-expansion lane
        count (1:1 with probe for the exact-key fast path)."""
        lb = _log2(build)
        flops = 4.0 * build * lb + 2.0 * probe * lb + 4.0 * expand
        nbytes = 8.0 * (2.0 * build + 2.0 * probe + 3.0 * expand)
        return self.seconds("SemiJoinResidual" if expand > probe
                            else "HashJoin", flops, nbytes)


def default_cost_model() -> CostModel:
    return CostModel()


# ---------------------------------------------------------------------------
# cardinality estimation
# ---------------------------------------------------------------------------


def _join_out_est(lest: int, lndv: dict, rest: int, rndv: dict,
                  lunique, runique, keys) -> int:
    """|L ⋈ R| estimate: the classic |L|·|R| / max(ndv(k)) with NDV
    from ANALYZE stats (≙ ObOptEstCost join selectivity).  A unique
    (PK) key side makes the probe side an UPPER BOUND — it must not
    override the NDV estimate, which already carries the unique side's
    filter selectivity (a 200-row filtered `part` joined to 6M
    `lineitem` rows yields ~6k rows, not 6M — the old PK shortcut
    returned the probe side whole and its capacity rode the plan)."""
    if not keys:
        return min(max(lest, 1) * max(rest, 1), 1 << 62)
    ndvs = []
    for lk, rk in keys:
        if isinstance(lk, ir.ColumnRef) and lk.name in lndv:
            ndvs.append(lndv[lk.name])
        if isinstance(rk, ir.ColumnRef) and rk.name in rndv:
            ndvs.append(rndv[rk.name])
    lkey_cols = {k.name for k, _ in keys if isinstance(k, ir.ColumnRef)}
    rkey_cols = {k.name for _, k in keys if isinstance(k, ir.ColumnRef)}
    r_hit = _holds_key(rkey_cols, runique)
    unique_hit = r_hit or _holds_key(lkey_cols, lunique)
    if ndvs:
        out = max(1, lest * max(rest, 1) // max(ndvs))
    elif unique_hit:
        out = max(lest, rest)
    else:
        return max(lest * 2, rest)
    if unique_hit:
        # each probe row matches at most one build row (and vice versa
        # on a both-unique join): cap at the smaller preserved side
        bound = lest if r_hit else rest
        return max(1, min(out, bound))
    # keep headroom: non-unique estimates are approximate
    return max(out, lest // 2, rest // 2)


def _holds_key(key_cols: set, unique) -> bool:
    """Do a join's key columns on one side hold a key of that side: a
    unique column, or every column of a composite primary key?"""
    return any(u in key_cols if isinstance(u, str)
               else set(u) <= key_cols for u in unique)


def _edge_keys(edges, left_members, right_members):
    """All equi-join key pairs between two member sets, left-oriented."""
    keys = []
    for i in left_members:
        for j in right_members:
            for le, re_ in edges[i].get(j, []):
                keys.append((le, re_))
    return keys


# ---------------------------------------------------------------------------
# enumeration: bushy DP + IDP windowing + greedy fallback
# ---------------------------------------------------------------------------


@dataclass
class _Item:
    """One enumeration vertex: a base fragment or a collapsed subtree."""

    tree: object            # frag index, or ("join", litem, ritem, swap)
    members: frozenset      # frag indices covered
    est: int
    ndv: dict
    unique: frozenset
    ncols: int
    cost_s: float = 0.0


def _frag_item(i, f) -> _Item:
    return _Item(tree=i, members=frozenset((i,)), est=max(f.est_rows, 1),
                 ndv=dict(f.ndv), unique=frozenset(f.unique_cols),
                 ncols=max(len(f.colids), 1))


def _join_items(li: _Item, ri: _Item, edges, model: CostModel) -> _Item | None:
    keys = _edge_keys(edges, li.members, ri.members)
    if not keys:
        return None
    out = _join_out_est(li.est, li.ndv, ri.est, ri.ndv,
                        li.unique, ri.unique, keys)
    ncols = li.ncols + ri.ncols
    # orientation: the build side pays the argsort — price both
    fwd = model.hash_join_s(li.est, ri.est, out, ncols)
    rev = model.hash_join_s(ri.est, li.est, out, ncols)
    swap = rev < fwd
    jc = rev if swap else fwd
    ndv = dict(li.ndv)
    ndv.update(ri.ndv)
    return _Item(tree=("join", li, ri, swap),
                 members=li.members | ri.members,
                 est=max(out, 1), ndv=ndv,
                 unique=li.unique | ri.unique, ncols=ncols,
                 cost_s=li.cost_s + ri.cost_s + jc)


def _dp_bushy(items: list, edges, model: CostModel):
    """Subset DP over ``items`` (bushy trees, connected splits only).
    -> (best _Item, runner_up_cost_s, states) or None when the join
    graph is disconnected (cross joins route to the greedy path)."""
    n = len(items)
    full = (1 << n) - 1
    dp: dict[int, _Item] = {1 << i: items[i] for i in range(n)}
    root_second = None
    for mask in range(1, full + 1):
        if mask & (mask - 1) == 0 or mask in dp:
            continue
        best = None
        second = None
        sub = (mask - 1) & mask
        while sub:
            rest = mask ^ sub
            if sub < rest:  # each split seen once; orientation is priced
                li, ri = dp.get(sub), dp.get(rest)
                if li is not None and ri is not None:
                    cand = _join_items(li, ri, edges, model)
                    if cand is not None:
                        if best is None or cand.cost_s < best.cost_s:
                            second = best.cost_s if best else second
                            best = cand
                        elif second is None or cand.cost_s < second:
                            second = cand.cost_s
            sub = (sub - 1) & mask
        if best is not None:
            dp[mask] = best
            if mask == full:
                root_second = second
    hit = dp.get(full)
    if hit is None:
        return None
    return hit, root_second, len(dp)


def _greedy_item(items: list, edges, model: CostModel) -> _Item:
    """Greedy fallback (cross joins / over-wide graphs): start at the
    largest item, repeatedly fold in the edged candidate with the
    cheapest resulting join; cross join only when nothing connects."""
    remaining = list(items)
    cur = max(remaining, key=lambda it: it.est)
    remaining.remove(cur)
    while remaining:
        best, best_item = None, None
        for it in remaining:
            cand = _join_items(cur, it, edges, model)
            if cand is not None and (best is None
                                     or cand.cost_s < best.cost_s):
                best, best_item = cand, it
        if best is None:
            # cross join: smallest first bounds the product
            it = min(remaining, key=lambda x: x.est)
            out = min(cur.est * max(it.est, 1), 1 << 62)
            ndv = dict(cur.ndv)
            ndv.update(it.ndv)
            best = _Item(tree=("join", cur, it, False),
                         members=cur.members | it.members,
                         est=max(out, 1), ndv=ndv,
                         unique=cur.unique | it.unique,
                         ncols=cur.ncols + it.ncols,
                         cost_s=cur.cost_s + it.cost_s
                         + model.hash_join_s(cur.est, it.est, out,
                                             cur.ncols + it.ncols))
            best_item = it
        cur = best
        remaining.remove(best_item)
    return cur


def _idp_tree(items: list, edges, model: CostModel, k: int = DP_MAX_RELS):
    """IDP(k): order items greedily, then repeatedly run the bushy DP
    over a k-wide window and collapse its best tree into one composite
    vertex (≙ ob_join_order_enum_idp.cpp's iterative DP past the full
    enumeration width)."""
    seed = _greedy_item(items, edges, model)

    def order_of(it: _Item, acc):
        if isinstance(it.tree, tuple):
            _tag, li, ri, _swap = it.tree
            order_of(li, acc)
            order_of(ri, acc)
        else:
            acc.append(it)
        return acc

    ordered = order_of(seed, [])
    work = list(ordered)
    states = 0
    while len(work) > 1:
        window = work[: max(k, 2)]
        rest = work[max(k, 2):]
        hit = _dp_bushy(window, edges, model)
        if hit is None:
            collapsed = _greedy_item(window, edges, model)
        else:
            collapsed, _sec, st = hit
            states += st
        work = [collapsed] + rest
    return work[0], None, states


# ---------------------------------------------------------------------------
# plan construction (access-path choice per join)
# ---------------------------------------------------------------------------


def _frag_scan_chain(plan):
    """Filter*/Compact* chain over a TableScan -> (scan, [filter preds])
    or None.  The preds re-apply above an index probe, so only plain
    chains qualify (a Project would re-derive columns)."""
    preds = []
    node = plan
    while isinstance(node, (pp.Filter, pp.Compact)):
        if isinstance(node, pp.Filter):
            preds.append(node.pred)
        node = node.child
    if isinstance(node, pp.TableScan):
        return node, preds
    return None


# a join input is compacted when its estimate's bucket is at most this
# share of the lanes it arrives on: the compaction sorts those lanes
# once (4 ns a lane), every gather and sort of the join then runs over
# the bucket
_COMPACT_LANE_SHARE = 8


def compact_join_input(f, catalog, capacity_factor: float = 1.5):
    """``compacted`` for a fragment of the join graph.  -> the fragment,
    compacted or as it was."""
    plan = compacted(f.plan, f.est_rows, catalog, capacity_factor)
    return f if plan is f.plan else _clone_fragment(f, plan, f.est_rows)


def _masks(plan) -> bool:
    """Does ``plan`` end, under any projections, in an operator that
    leaves dead lanes behind: a filter, or a semi / anti join (which only
    mask their probe)?"""
    while isinstance(plan, pp.Project):
        plan = plan.child
    return isinstance(plan, (pp.Filter, pp.SemiJoinResidual)) or (
        isinstance(plan, pp.HashJoin) and plan.how in ("semi", "anti"))


def compacted(plan, est_rows: int, catalog, capacity_factor: float = 1.5):
    """A join pays by the LANES of its inputs (the build side sorts them,
    the probe ranks and expands them), the cost model prices their
    estimated ROWS.  Where an input ends in an operator that masks (a
    filter chain over a scan; since PR 44 also a filter over a derived
    table such as a group-by's HAVING, and a semi / anti join's output)
    and is estimated to leave at most 1/8 of the lanes it arrives on
    (``_static_lanes``; unknown = as it is), densify it to the estimate's
    bucket before the join, so that both agree.  ``strict``: a row that
    does not fit is reported on the ``compact_overflow`` lane and the
    session re-plans with scaled budgets, as for a join's out_capacity.
    -> the plan, compacted or as it was."""
    if not _masks(plan):
        return plan
    lanes = _static_lanes(plan, catalog)
    bucket = _bucket(est_rows, capacity_factor)
    if lanes is None or bucket * _COMPACT_LANE_SHARE > lanes:
        return plan
    return pp.Compact(plan, capacity=bucket, strict=True, est_rows=est_rows)


def _base_column(scan: pp.TableScan, col: ir.ColumnRef) -> str:
    """The table's own name of a scan's output column."""
    inv = {cid: base for base, cid in (scan.rename or {}).items()}
    return inv.get(col.name, col.name)


def _static_lanes(plan, catalog) -> int | None:
    """The lanes ``plan``'s output arrives on, where the plan alone says
    so: a ``Compact``'s capacity, a scan's ``catalog.scan_lanes`` (filters
    and semi / anti joins only mask, projections keep the lanes), a
    join's output lanes, a group-by's (its capacity, or its input's lanes
    if fewer; a group-by over dictionary codes emits fewer still).  None:
    unknown."""
    node = plan
    while True:
        if isinstance(node, (pp.Filter, pp.Project)):
            node = node.child
        elif isinstance(node, pp.SemiJoinResidual) or (
                isinstance(node, pp.HashJoin)
                and node.how in ("semi", "anti")):
            node = node.left
        else:
            break
    if isinstance(node, pp.Compact):
        return node.capacity
    if isinstance(node, pp.TableScan):
        try:
            return catalog.scan_lanes(node.table)
        except KeyError:    # a relation the session injects, not a table
            return None
    if isinstance(node, pp.HashJoin) and node.how in ("inner", "left"):
        if node.build_unique:
            return _static_lanes(node.left, catalog)
        return node.out_capacity
    if isinstance(node, pp.GroupBy) and node.out_capacity is not None:
        under = _static_lanes(node.child, catalog)
        return node.out_capacity if under is None \
            else min(node.out_capacity, under)
    return None


def unique_build(probe, build, build_keys, out_capacity, catalog) -> bool:
    """Does a join of ``probe`` against ``build`` emit on its probe's
    lanes (``HashJoin.build_unique``)?  Yes when the build side is
    PROVABLY unique on the join key — a Filter*/Compact* chain over a
    scan, one key pair, its column on that side the table's single-column
    primary key — and the probe's static lanes are at most the
    ``out_capacity`` the join would expand into: the output then has no
    more lanes than today's, and none of the expansion's work.
    ``Fragment.unique_cols`` is not used: after a join it is the union of
    both sides' keys, good for estimates, not sound as a guarantee."""
    if len(build_keys) != 1 or not isinstance(build_keys[0], ir.ColumnRef):
        return False
    chain = _frag_scan_chain(build)
    if chain is None:
        return False
    scan = chain[0]
    try:
        pk = list(catalog.table_def(scan.table).primary_key or [])
    except KeyError:    # a relation the session injects, not a table
        return False
    if pk != [_base_column(scan, build_keys[0])]:
        return False
    lanes = _static_lanes(probe, catalog)
    return lanes is not None and out_capacity is not None \
        and lanes <= out_capacity


def without_unique_builds(node: pp.PlanNode) -> pp.PlanNode:
    """The plan with every ``HashJoin.build_unique`` mark off, each join
    as it is oriented: what a statement re-plans to when a build side
    repeated its key (``join_build_dup``)."""
    import dataclasses

    updates = {f: without_unique_builds(getattr(node, f))
               for f in ("child", "left", "right") if hasattr(node, f)}
    if hasattr(node, "inputs"):
        updates["inputs"] = [without_unique_builds(c) for c in node.inputs]
    if isinstance(node, pp.HashJoin):
        updates["build_unique"] = False
    return dataclasses.replace(node, **updates) if updates else node


def _index_for(catalog, table: str, base_col: str):
    """Leading-column secondary index on ``table.base_col`` -> index
    name, or None.  Only int-like columns qualify (the searchsorted
    probe must be collision-free without a verification expansion)."""
    try:
        td = catalog.table_def(table)
    except Exception:  # noqa: BLE001 — catalog-only relations
        return None
    if td is None:
        return None
    try:
        kind = td.column(base_col).dtype.kind
    except Exception:  # noqa: BLE001 — unknown column
        return None
    from oceanbase_tpu.datatypes import TypeKind

    if kind not in (TypeKind.INT, TypeKind.DATE, TypeKind.DATETIME):
        return None  # raw int64 comparison must be collision-free
    for ix in getattr(td, "indexes", None) or []:
        cols = list(getattr(ix, "columns", []) or [])
        if cols and cols[0] == base_col:
            return ix.name
    return None


def _inl_candidate(ri: _Item, frags, keys, catalog):
    """Is the build side a single scan-chain fragment with a secondary
    index on the (single) join key?  -> (frag, scan, preds, base_col,
    index_name) or None."""
    if len(ri.members) != 1 or len(keys) != 1:
        return None
    (idx,) = ri.members
    f = frags[idx]
    chain = _frag_scan_chain(f.plan)
    if chain is None:
        return None
    scan, preds = chain
    rk = keys[0][1]
    if not isinstance(rk, ir.ColumnRef):
        return None
    base_col = _base_column(scan, rk)
    iname = _index_for(catalog, scan.table, base_col)
    if iname is None:
        return None
    return f, scan, preds, base_col, iname


def _build_plan(item: _Item, frags, edges, model: CostModel, catalog,
                capacity_factor: float, stats: dict):
    """Recursively construct the physical plan for an enumeration item,
    choosing the access path per join (hash fwd/rev vs index probe)."""
    if not isinstance(item.tree, tuple):
        return frags[item.tree].plan
    _tag, li, ri, swap = item.tree
    lplan = _build_plan(li, frags, edges, model, catalog,
                        capacity_factor, stats)
    rplan = _build_plan(ri, frags, edges, model, catalog,
                        capacity_factor, stats)
    keys = _edge_keys(edges, li.members, ri.members)
    out_est = item.est
    cap = _bucket(out_est, capacity_factor)
    ncols = item.ncols
    hash_s = min(model.hash_join_s(li.est, ri.est, out_est, ncols),
                 model.hash_join_s(ri.est, li.est, out_est, ncols))

    # index nested-loop probe: build side is an indexed base table and
    # the probe side is far under it — skip the scan-side sort wholly
    for probe_i, build_i, probe_p, oriented in (
            (li, ri, lplan, keys),
            (ri, li, rplan, [(r, l) for l, r in keys])):
        cand = _inl_candidate(build_i, frags, oriented, catalog)
        if cand is None:
            continue
        f, scan, preds, base_col, iname = cand
        base_rows = max(int(getattr(
            catalog.table_def(scan.table), "row_count", 0) or 0),
            f.est_rows, 1)
        if probe_i.est * _INL_MIN_SHRINK > base_rows:
            continue
        key_ndv = max(f.ndv.get(oriented[0][1].name, base_rows), 1)
        exp_est = max(1, probe_i.est * base_rows // key_ndv)
        inl_s = model.index_probe_s(probe_i.est, base_rows, exp_est,
                                    ncols)
        if inl_s >= hash_s:
            continue
        stats["index_probes"] = stats.get("index_probes", 0) + 1
        # enumeration priced this join as a hash join; the probe is
        # cheaper by (hash_s - inl_s).  Accumulate so the ledger's
        # pred_s reflects the plan actually emitted, and the all-hash
        # variant of the same order becomes the runner-up.
        stats["probe_saving_s"] = (stats.get("probe_saving_s", 0.0)
                                   + (hash_s - inl_s))
        icap = _bucket(exp_est, capacity_factor)
        node = pp.IndexProbe(
            probe_p, table=scan.table, index=iname,
            key=oriented[0][0], columns=scan.columns,
            rename=scan.rename, out_capacity=icap, est_rows=exp_est)
        # re-apply the chain's filter conjuncts above the probe
        for pred in reversed(preds):
            node = pp.Filter(node, pred, est_rows=max(1, out_est))
        return node
    fwd = (lplan, rplan, [k[0] for k in keys], [k[1] for k in keys])
    rev = (rplan, lplan, fwd[3], fwd[2])
    priced, other = (rev, fwd) if swap else (fwd, rev)
    # a side that holds the key once builds, whichever side the cost
    # model would have sorted
    marked = next((o for o in (priced, other)
                   if unique_build(o[0], o[1], o[3], cap, catalog)), None)
    probe, build, pkeys, bkeys = marked or priced
    return pp.HashJoin(probe, build, pkeys, bkeys, how="inner",
                       out_capacity=cap, est_rows=max(1, out_est),
                       build_unique=marked is not None)


# ---------------------------------------------------------------------------
# semi/anti edge placement
# ---------------------------------------------------------------------------


def _semi_expansion(probe_est: int, build_est: int, key_ndv: int) -> int:
    """Equality-expansion lane estimate for a residual semi join."""
    return max(probe_est,
               probe_est * max(build_est, 1) // max(key_ndv, 1))


def _semi_key_ndv(e, ndv: dict, probe_est: int) -> int:
    ndvs = [ndv[lk.name] for lk in e.lhs
            if isinstance(lk, ir.ColumnRef) and lk.name in ndv]
    return max(ndvs) if ndvs else max(probe_est, 1)


def _attach_semi(plan, probe_est: int, e, key_ndv: int, catalog):
    """Wrap ``plan`` with the semi/anti edge; -> (plan, est).  A
    semi-join keeps half its probe, and no more probe rows than its build
    side's rows can match (each build row's key meets ``probe_est /
    key_ndv`` of them); its build side is compacted as any join input
    is (``compacted``)."""
    exp = _semi_expansion(probe_est, e.build_est, key_ndv)
    cap = _pow2(int(min(exp, CAP_MAX) * 2) + 16)
    if e.anti:
        est = max(1, probe_est // 3)
    else:
        est = max(1, min(probe_est // 2, e.build_est * max(
            probe_est // max(key_ndv, 1), 1)))
    build = compacted(e.plan, e.build_est, catalog)
    if e.residual:
        node = pp.SemiJoinResidual(plan, build, list(e.lhs),
                                   list(e.rkeys), list(e.residual),
                                   anti=e.anti, out_capacity=cap,
                                   est_rows=est)
    else:
        node = pp.HashJoin(plan, build, list(e.lhs), list(e.rkeys),
                           how="anti" if e.anti else "semi",
                           out_capacity=cap, est_rows=est)
    return node, est


def _semi_cost(model: CostModel, probe_est: int, e, key_ndv: int) -> float:
    exp = _semi_expansion(probe_est, e.build_est, key_ndv)
    if not e.residual:
        exp = probe_est  # exact-key fast path stays mask-only
    return model.semi_s(probe_est, e.build_est, exp)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def build_join_tree(qb, catalog, capacity_factor: float = 1.5,
                    cost: CostModel | None = None):
    """qb: QueryBlock with fragments + join_edges (+ semi_edges).
    -> (plan, est_rows, colid->fragment map).  Side effect: sets
    ``qb.cbo_choice`` with the chosen plan's predicted seconds, the
    runner-up's, and the enumeration breadth (the gv$plan_choice
    ledger's bind-time half)."""
    frags = list(qb.fragments)
    if not frags:
        raise ValueError("empty FROM")
    model = cost or default_cost_model()
    semi_edges = list(getattr(qb, "semi_edges", None) or [])
    n = len(frags)
    colid_frag = {}
    for i, f in enumerate(frags):
        for c in f.colids:
            colid_frag[c] = i

    if n > 1 or semi_edges:
        # before any join is priced: the enumeration then prices rows
        # that the program's lanes match
        frags = [compact_join_input(f, catalog, capacity_factor)
                 for f in frags]
    stats: dict = {}
    if n == 1:
        f = frags[0]
        plan, est = f.plan, max(f.est_rows, 1)
        for e in semi_edges:
            key_ndv = _semi_key_ndv(e, f.ndv, est)
            plan, est = _attach_semi(plan, est, e, key_ndv, catalog)
        qb.cbo_choice = {"pred_s": 0.0, "runner_up_s": 0.0,
                         "enumerated": 1, "method": "single",
                         "n_rels": 1, "index_probes": 0}
        return plan, est, {c: 0 for c in f.colids}

    # adjacency: edges[i][j] = list[(lexpr on i, rexpr on j)]
    edges: dict[int, dict[int, list]] = {i: {} for i in range(n)}
    for fi, fj, le, re_ in qb.join_edges:
        edges[fi].setdefault(fj, []).append((le, re_))
        edges[fj].setdefault(fi, []).append((re_, le))

    # -- semi/anti placement: home fragment vs above the join tree ----
    # a quick estimate-only greedy pass prices the "above the tree"
    # probe side; each edge then takes the cheaper spot (TPC-H Q21's
    # equality expansion shrinks by the join selectivity at the top)
    top_semis = []
    if semi_edges:
        pre = _greedy_item([_frag_item(i, f) for i, f in
                            enumerate(frags)], edges, model)
        for e in semi_edges:
            f = frags[e.home]
            key_ndv = _semi_key_ndv(e, f.ndv, f.est_rows)
            at_frag = _semi_cost(model, max(f.est_rows, 1), e, key_ndv)
            at_top = _semi_cost(model, pre.est, e, key_ndv)
            if at_top < at_frag:
                top_semis.append(e)
            else:
                new_plan, new_est = _attach_semi(
                    f.plan, max(f.est_rows, 1), e, key_ndv, catalog)
                # what the semi-join leaves is a join input as any other
                frags[e.home] = compact_join_input(
                    _clone_fragment(f, new_plan, new_est), catalog,
                    capacity_factor)

    items = [_frag_item(i, f) for i, f in enumerate(frags)]
    method = "greedy"
    runner_up = None
    enumerated = n
    best = None
    if n <= DP_MAX_RELS:
        hit = _dp_bushy(items, edges, model)
        if hit is not None:
            best, runner_up, enumerated = hit
            method = "dp"
    else:
        best, runner_up, enumerated = _idp_tree(items, edges, model)
        method = "idp"
    if best is None:
        best = _greedy_item(items, edges, model)
    plan = _build_plan(best, frags, edges, model, catalog,
                       capacity_factor, stats)
    est = best.est
    tree_ndv = best.ndv

    for e in top_semis:
        key_ndv = _semi_key_ndv(e, tree_ndv, est)
        plan, est = _attach_semi(plan, est, e, key_ndv, catalog)

    saving = stats.get("probe_saving_s", 0.0)
    pred_s = max(best.cost_s - saving, 0.0)
    # runner-up: the cheaper of the second-best join ORDER and (when an
    # index probe won an access-path contest) the all-hash variant of
    # the chosen order — both are real plans the optimizer rejected
    alts = [c for c in (runner_up,) if c]
    if saving > 0.0:
        alts.append(best.cost_s)
    qb.cbo_choice = {
        "pred_s": round(pred_s, 9),
        "runner_up_s": round(min(alts), 9) if alts else 0.0,
        "enumerated": int(enumerated), "method": method,
        "n_rels": n, "index_probes": int(stats.get("index_probes", 0))}
    return plan, est, colid_frag


def _clone_fragment(f, plan, est):
    import dataclasses

    return dataclasses.replace(f, plan=plan, est_rows=max(1, est))


# ---------------------------------------------------------------------------
# capacity evolution (retry ladder + feedback)
# ---------------------------------------------------------------------------


def scale_capacities(node: pp.PlanNode, factor: int) -> pp.PlanNode:
    """Rebuild a plan with all static capacities multiplied (retry path
    after CapacityOverflow); clamped at CAP_MAX."""
    import dataclasses

    kids = {}
    for fname in ("child", "left", "right"):
        if hasattr(node, fname):
            kids[fname] = scale_capacities(getattr(node, fname), factor)
    if hasattr(node, "inputs"):
        kids["inputs"] = [scale_capacities(c, factor) for c in node.inputs]
    updates = dict(kids)
    if hasattr(node, "out_capacity") and node.out_capacity is not None:
        updates["out_capacity"] = min(node.out_capacity * factor, CAP_MAX)
    if getattr(node, "capacity", None) is not None:
        updates["capacity"] = min(node.capacity * factor, CAP_MAX)
    if not updates:
        return node
    return dataclasses.replace(node, **updates)


def overflow_jump_factor(drops: list, slack: float = 1.5) -> int:
    """Capacity-scale factor that clears every overflowing lane in ONE
    re-plan: each diagnostic lane reports (name, static_capacity,
    rows_dropped), so the needed budget is capacity + dropped — jump
    straight there (with slack) instead of riding the blind 4x ladder.
    Returns a power-of-two factor >= 4 (lanes without a recorded
    capacity fall back to the ladder step)."""
    need = 4
    for _name, cap, dropped in drops or []:
        if not cap:
            continue
        want = (cap + dropped) * slack / cap
        f = 4
        while f < want and f < (CAP_MAX // max(cap, 1)):
            f *= 4
        need = max(need, f)
    return need


def after_overflow(plan: pp.PlanNode, drops: list,
                   jump: bool = True) -> tuple[pp.PlanNode, int]:
    """What a CapacityOverflow asks of the next attempt: -> (the plan to
    scale, the factor its budgets grow by).  A build side that repeated
    its declared key (lane ``join_build_dup`` of ``ops.join``) takes the
    ``build_unique`` marks off, as does an overflow that names no lane
    (a PX program names its own since PR 42, its exchanges' apart: the
    session raises an exchange's budget alone).  Budgets grow when
    a budget overflowed: by ``overflow_jump_factor`` with ``jump``, else
    by the ladder's 4."""
    dup = [d for d in drops if d[0] == "join_build_dup"]
    if dup or not drops:
        plan = without_unique_builds(plan)
    if drops and len(dup) == len(drops):
        return plan, 1
    return plan, overflow_jump_factor(drops) if jump else 4


def apply_feedback(plan: pp.PlanNode, corrections: dict,
                   slack: float = 1.5) -> tuple[pp.PlanNode, int]:
    """Correct static budgets AND estimates from observed cardinalities
    at bind time.

    ``corrections`` maps MONITORED-postorder position -> (op_name,
    observed_rows) from the gv$plan_feedback store (keyed by the plan's
    logical hash, so capacity scaling does not orphan the entries; the
    position space is exec/plan.py::monitored_postorder — pass-through
    operators emit no ledger row).  A node whose out_capacity is below
    the observed bucket starts at the bucket instead of re-riding the
    CapacityOverflow retry ladder, and its ``est_rows`` is re-seeded to
    the observation so every downstream consumer (spill candidates, px
    budget snapping, the roofline's q-error ledger) prices against
    measured reality instead of the compounding misestimate.  A Compact
    (a pass-through with no ledger row) takes the observation of its
    child.  The op-name check guards against postorder drift (e.g. the
    fused top-N path).  -> (plan, number of capacities raised)."""
    import dataclasses

    from oceanbase_tpu.exec.plan import monitored_op

    counter = [0]
    n_fixed = [0]
    observed: dict = {}  # id(node) -> rows the ledger saw it put out

    def walk(node, parent=None):
        kids = {}
        changed = False
        for fname in ("child", "left", "right"):
            if hasattr(node, fname):
                old = getattr(node, fname)
                nv = walk(old, node)
                kids[fname] = nv
                changed = changed or nv is not old
        if hasattr(node, "inputs"):
            nv_list = [walk(c, node) for c in node.inputs]
            kids["inputs"] = nv_list
            changed = changed or any(
                a is not b for a, b in zip(nv_list, node.inputs))
        hit = None
        if monitored_op(node, parent):
            hit = corrections.get(counter[0])
            counter[0] += 1
        updates = dict(kids) if changed else {}
        rows = budget = None
        if hit is not None and hit[0] == type(node).__name__:
            rows, budget = hit[1], "out_capacity"
            observed[id(node)] = rows
        elif isinstance(node, pp.Compact):
            # a compacted join input (compact_join_input) keeps no ledger
            # row of its own: its budget follows the filter under it
            rows, budget = observed.get(id(node.child)), "capacity"
        if rows is not None and getattr(node, budget, None) is not None:
            want = _bucket(rows, slack)
            if want > getattr(node, budget):
                updates[budget] = want
                updates["est_rows"] = int(rows)
                n_fixed[0] += 1
        if not updates:
            return node
        return dataclasses.replace(node, **updates)

    out = walk(plan)
    return out, n_fixed[0]
