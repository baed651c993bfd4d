"""Compile the main path's programs for a DESCRIBED TPU v5e (no chip).

The TPU compiler is installed in the sandbox and compiles for a topology
that is described, not attached (on-chip-measurement guide, section 2.3):
what it refuses here costs no chip time.  Nothing runs, so these cases
say nothing of results or times; ``chip_smoke.py`` is the chip run.

This is the only file that describes the chip.  The topology is described
inside a fixture, never at import: one process at a time may load the TPU
library, and every xdist worker imports every test file.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _fits(compiled):
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < V5E_HBM_BYTES, ma


def _tpch_session(new_session, names=None, analyze=False):
    """An SF0.01 load of ``names`` (all eight tables by default)."""
    from oceanbase_tpu.bench.tpch import TPCH_PRIMARY_KEYS, gen_tpch

    tables, types = gen_tpch(sf=0.01)
    sess = new_session()
    for name in names or tables:
        arrays = tables[name]
        sess.catalog.load_numpy(
            name, arrays,
            types={k: v for k, v in types.items() if k in arrays},
            primary_key=TPCH_PRIMARY_KEYS[name])
        if analyze:
            sess.execute(f"analyze table {name}")
    return sess


@pytest.fixture(scope="module")
def tpch_session(new_module_session):
    return _tpch_session(new_module_session)


def _compile_programs_of(session, sql, one_chip, monkeypatch):
    """Execute ``sql`` on the CPU, then compile every plan program the
    execution called, at the shapes it was called with, for the chip."""
    from oceanbase_tpu.exec import plan as qplan

    programs = []
    call = qplan._PlanExecutable.call

    def spy(self, tables):
        programs.append((self._run, tables))
        return call(self, tables)

    monkeypatch.setattr(qplan._PlanExecutable, "call", spy)
    assert session.execute(sql).rowcount > 0
    assert programs
    for run, tables in programs:
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tables)
        _fits(run.lower(shapes).compile())


@pytest.mark.parametrize("qnum", [1, 6, 3, 14])
def test_plan_program_compiles_for_v5e(qnum, tpch_session, one_chip,
                                       no_persistent_cache, monkeypatch):
    """The plan programs Session.execute builds for a smoke query, at the
    shapes of an SF0.01 load, accepted by the chip's compiler."""
    from oceanbase_tpu.bench.tpch_queries import QUERIES

    _compile_programs_of(tpch_session, QUERIES[qnum], one_chip, monkeypatch)


def test_compacted_join_input_compiles_for_v5e(one_chip,
                                               no_persistent_cache,
                                               monkeypatch, new_session):
    """Q14 as the benchmark runs it, with ANALYZE'd statistics: the
    filtered ``lineitem`` is compacted to its estimate's bucket under the
    join (the case above loads without statistics and goes on compiling
    the uncompacted plan)."""
    from oceanbase_tpu.bench.tpch_queries import QUERIES
    from oceanbase_tpu.exec import plan as qplan
    from oceanbase_tpu.sql.parser import parse_sql

    sess = _tpch_session(new_session, ("lineitem", "part"), analyze=True)
    plan, _outs, _est = sess._plan_select(parse_sql(QUERIES[14]), None)
    compacts = [n for n in qplan._postorder(plan)
                if isinstance(n, qplan.Compact)]
    assert len(compacts) == 1 and compacts[0].strict
    _compile_programs_of(sess, QUERIES[14], one_chip, monkeypatch)


@pytest.mark.parametrize("path", ["merge", "search"])
def test_probe_ranges_compile_for_v5e(path, one_chip, no_persistent_cache):
    """Both ways ``ops._probe_ranges`` ranks probe keys, accepted by the
    chip's compiler (an SF0.01 plan is all below the shape rule, so the
    plan programs above never hold the merge).  The sorts of the merge
    cost the compiler the same at any size (ROADMAP S0): a small one."""
    from oceanbase_tpu.exec import ops

    shapes = [jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one_chip)
              for n in (4096, 65536)]
    _fits(jax.jit(lambda b, p: ops._probe_ranges(b, p, _path=path))
          .lower(*shapes).compile())


@pytest.mark.parametrize("path", ["merge", "search"])
def test_unique_match_compiles_for_v5e(path, one_chip, no_persistent_cache):
    """Both ways a join on its probe's lanes finds each probe key's one
    build row (``ops._join_on_probe_lanes``; an SF0.01 plan is below the
    shape rule, the SF10 case further down holds the merge at real size):
    no scatter in either, and no gather in the merge."""
    from oceanbase_tpu.exec import ops

    shapes = [jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one_chip)
              for n in (4096, 65536)]
    compiled = jax.jit(getattr(ops, "_unique_match_by_" + path)).lower(
        *shapes).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert " scatter(" not in text
    assert (" gather(" in text) == (path == "search")


def test_px_groupby_exchange_compiles_for_four_chips(topo,
                                                     no_persistent_cache):
    """One PX program on the 2x2 mesh: partial agg -> all_to_all by key
    hash -> final agg (Q1's group-by exchange), as
    ``__graft_entry__.dryrun_multichip`` drives it.  Fed shapes with a
    NamedSharding: a described device cannot take a device_put."""
    from oceanbase_tpu.exec.ops import AggSpec
    from oceanbase_tpu.expr import ir
    from oceanbase_tpu.px.dist_ops import dist_groupby_shard
    from oceanbase_tpu.px.exchange import datahub_psum
    from oceanbase_tpu.vector import from_numpy

    ndev = len(topo.devices)
    assert ndev == 4
    mesh = Mesh(np.array(topo.devices), ("px",))
    # a small shard: the v5e compiler's time for this program grows with
    # the rows (3 s at 512, 28 s at 64K, 158 s at 1M; sandbox, PR 22), and
    # what the case guards is the mesh and the collective, not the size
    rows = 2048 * ndev
    li = from_numpy({"flag": np.zeros(8, np.int64),
                     "qty": np.zeros(8, np.int64),
                     "price": np.zeros(8, np.int64)})
    sharded = NamedSharding(mesh, P("px"))
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((rows,), x.dtype, sharding=sharded),
        li)

    def step(shard):
        filtered = shard.with_mask(
            (shard.columns["qty"].data < 40) & shard.mask_or_true())
        grouped, ovf = dist_groupby_shard(
            filtered, {"flag": ir.col("flag")},
            [AggSpec("s", "sum", ir.col("price")),
             AggSpec("c", "count_star"),
             AggSpec("a", "avg", ir.col("qty"))],
            ndev=ndev, local_cap=32, out_cap=32)
        return grouped, datahub_psum(ovf)

    compiled = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P("px"),), out_specs=(P("px"), P()),
        check_vma=False)).lower(shapes).compile()
    assert "all-to-all" in compiled.as_text()
    _fits(compiled)


def test_px_plan_over_declared_partitions_compiles_for_four_chips(
        topo, no_persistent_cache, monkeypatch, tmp_path):
    """TPC-H Q3 at ``px_dop = 4`` over tables hash-partitioned by DDL: the
    shard program the planner derives from the declared layout (customer
    broadcast, lineitem partition-wise, the group-by local, the range
    sort's exchange), built again for the described 2x2 mesh and fed the
    partitions' shapes.  It runs on four virtual CPU devices first: that
    is where the program's arguments come from."""
    from oceanbase_tpu.bench.tpch import TPCH_PRIMARY_KEYS, gen_tpch
    from oceanbase_tpu.bench.tpch_queries import QUERIES
    from oceanbase_tpu.exec import plan as qplan
    from oceanbase_tpu.server import Database

    tables, types = gen_tpch(sf=0.01)
    db = Database(str(tmp_path))
    s = db.session()
    for name, key in (("lineitem", "l_orderkey"), ("orders", "o_orderkey"),
                      ("customer", "c_custkey")):
        cols = ", ".join(
            f"{c} " + (str(types[c]) if c in types else
                       "varchar(200)" if a.dtype == object else "bigint")
            for c, a in tables[name].items())
        s.execute(f"create table {name} ({cols}, primary key "
                  f"({', '.join(TPCH_PRIMARY_KEYS[name])})) partition by "
                  f"key ({key}) partitions 4")
        s.catalog.load_numpy(
            name, tables[name],
            types={k: v for k, v in types.items() if k in tables[name]},
            primary_key=TPCH_PRIMARY_KEYS[name])
    built = []
    call = qplan._PlanExecutable.call

    def spy(self, sharded):
        if self.program.shard is not None:
            built.append((self, sharded))
        return call(self, sharded)

    monkeypatch.setattr(qplan._PlanExecutable, "call", spy)
    s.execute("set px_dop = 4")
    assert s.execute(QUERIES[3]).rowcount > 0 and s._last_px
    db.close()
    exe, sharded = built[-1]
    program = exe.program
    _mesh, axis, names, whole = program.shard
    assert whole == ()
    assert dict(program.args[-1].declared) == {
        "customer": ("c_custkey",), "lineitem": ("l_orderkey",),
        "orders": ("o_orderkey",)}
    (_compiled, _flops, _bytes, _peak, notes), = exe._execs.values()
    assert notes["join", "partition_wise"] == 1
    assert notes["join", "broadcast"] == 1
    assert not any(k == ("lanes", "groupby") for k in notes)
    mesh = Mesh(np.array(topo.devices), (axis,))
    run = qplan._PlanExecutable(qplan.Program(
        program.body, program.args, "described", "described",
        shard=(mesh, axis, names, whole)))._run
    on_mesh = NamedSharding(mesh, P(axis))
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_mesh),
        sharded)
    compiled = run.lower(shapes).compile()
    assert "all-gather" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("fn", ["sum", "min"])
def test_lowcard_reduce_compiles_for_v5e(fn, one_chip, no_persistent_cache):
    """``ops._lowcard_reduce`` over SF1's ``lineitem`` bucket into 7
    segments holds no scatter and writes nothing of (segments, lanes):
    what it keeps beside its arguments is under one int64 column (the two
    halves the TPU splits the column into), where the broadcast would be
    six."""
    from oceanbase_tpu.exec import ops

    lanes, nseg = 8_388_608, 7
    compiled = jax.jit(
        lambda d, gid: ops._lowcard_reduce(fn, d, gid, nseg)).lower(
            jax.ShapeDtypeStruct((lanes,), jnp.int64, sharding=one_chip),
            jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip),
        ).compile()
    _fits(compiled)
    assert " scatter(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes <= lanes * 8


def test_sort_path_groupby_compiles_for_v5e_without_a_scatter(
        one_chip, no_persistent_cache):
    """A Q3-shaped sort-path group-by (an int64 key, a DECIMAL sum and
    ``count(*)``) for the described chip: its reductions over the sorted
    lanes are prefix sums read at the groups' end lanes, so the program
    holds no scatter, and every scan is in two levels (rows of 1,024
    lanes, then the rows' totals): none runs over the flat lanes, which
    costs the TPU compiler minutes at real lane counts (PR 34, PR 39).  A
    small relation: the compiler's time for the sorts is what a case at
    Q3's 524,288 lanes would add, and it says nothing more."""
    from oceanbase_tpu.datatypes import SqlType
    from oceanbase_tpu.exec import ops
    from oceanbase_tpu.exec.ops import AggSpec
    from oceanbase_tpu.expr import ir
    from oceanbase_tpu.vector import Relation, from_numpy

    lanes = 8192
    rel = from_numpy({"k": np.zeros(8, np.int64),
                      "rev": np.zeros(8, np.int64)},
                     types={"rev": SqlType.decimal(15, 2)})
    rel = Relation(rel.columns, jnp.ones(8, jnp.bool_))
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((lanes,), x.dtype, sharding=one_chip),
        rel)
    lowered = jax.jit(lambda r: ops.hash_groupby(
        r, {"k": ir.col("k")},
        [AggSpec("revenue", "sum", ir.col("rev")),
         AggSpec("n", "count_star")])).lower(shapes)
    text = lowered.as_text()
    assert "stablehlo.scatter" not in text
    scans = re.findall(r"reduce_window.*?\) : \(tensor<([0-9x]+)x(i\d+)>",
                       text, re.S)
    assert (f"{lanes // 1024}x1024", "i64") in scans, scans
    assert not any(shape == str(lanes) for shape, _dtype in scans), scans
    compiled = lowered.compile()
    _fits(compiled)
    assert " scatter(" not in compiled.as_text()


@pytest.mark.parametrize("rides", [True, False])
def test_sort_path_groupby_reads_no_lane_through_its_permutation(
        rides, one_chip, no_persistent_cache, monkeypatch):
    """A sort-path group-by (a nullable int64 key, a DECIMAL sum and an
    average of the same nullable argument, ``count(*)``; 1,024 groups'
    lanes out of 8,192) for the described chip: the live flag and the key
    in sorted order are outputs of the group-by's own sort (dead flag, key
    validity, key, row number), so no gather is indexed by the sort's row
    numbers over all the lanes for them, before or after the compiler.
    The argument rides the same sort where the shape rule says so (lifted
    here: behind the row number, the argument and its validity, once), and
    every gather left reads at the groups' 1,024 end lanes; at the rule's
    own word for so few lanes the argument's two arrays are the only ones
    read through the row numbers."""
    from oceanbase_tpu.datatypes import SqlType
    from oceanbase_tpu.exec import ops
    from oceanbase_tpu.exec.ops import AggSpec
    from oceanbase_tpu.expr import ir
    from oceanbase_tpu.vector import Relation, from_numpy

    if rides:
        monkeypatch.setattr(ops, "_RIDE_MIN_READS", 0)
    lanes, cap = 8192, 1024
    rel = from_numpy({"k": np.zeros(8, np.int64),
                      "rev": np.zeros(8, np.int64)},
                     types={"rev": SqlType.decimal(15, 2)},
                     valids={"k": np.ones(8, bool), "rev": np.ones(8, bool)})
    rel = Relation(rel.columns, jnp.ones(8, jnp.bool_))
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((lanes,), x.dtype, sharding=one_chip),
        rel)
    lowered = jax.jit(lambda r: ops.hash_groupby(
        r, {"k": ir.col("k")},
        [AggSpec("revenue", "sum", ir.col("rev")),
         AggSpec("mean", "avg", ir.col("rev")),
         AggSpec("n", "count_star")], out_capacity=cap)).lower(shapes)
    text = lowered.as_text()
    sorts = re.findall(r'"stablehlo.sort"\(([^)]*)\)', text)
    assert sorted(len(s.split(",")) for s in sorts) == [
        1, 6 if rides else 4], sorts
    gathers = re.findall(
        r"stablehlo.gather.*?: \(tensor<(\d+)xi(\d+)>, tensor<(\d+)x1xi32>\)",
        text)
    through = sorted(bits for src, bits, idx in gathers
                     if idx == str(lanes))
    assert through == ([] if rides else ["1", "64"]), gathers
    assert any(idx == str(cap) for _src, _bits, idx in gathers)
    compiled = lowered.compile()
    _fits(compiled)
    hlo = compiled.as_text()
    assert " scatter(" not in hlo
    # (the compiled module spells a gather's result, one element an index:
    # the argument's two halves and its validity, or nothing)
    reads = re.findall(r"= \w+\[(\d+)\]\S* gather\(", hlo)
    assert reads.count(str(lanes)) == (0 if rides else 3), reads
    assert set(reads) <= {str(cap), str(lanes)}


@pytest.mark.parametrize("spread", [False, True])
def test_result_pack_compiles_for_v5e(spread, topo, one_chip,
                                      no_persistent_cache):
    """The result boundary's densify + pack (``vector/column.py``) at the
    shape of Q3's SF1 result, ten rows alive on 524,288 lanes (on one
    chip, and on 4 x 262,144 lanes spread over the mesh as the PX
    coordinator's relation lies): a prefix sum, a binary search and
    gathers, with no sort and no scatter over the capacity's lanes."""
    from oceanbase_tpu.vector import Relation, from_numpy
    from oceanbase_tpu.vector import column as vcol

    where = one_chip
    lanes = 524288
    if spread:
        where = NamedSharding(Mesh(np.array(topo.devices), ("px",)),
                              P("px"))
        lanes = 4 * 262144
    rel = from_numpy({"k": np.zeros(8, np.int64),
                      "rev": np.zeros(8, np.int64),
                      "day": np.zeros(8, np.int32),
                      "avg": np.zeros(8, np.float64)},
                     valids={"rev": np.ones(8, bool)})
    rel = Relation(rel.columns, jnp.ones(8, jnp.bool_))
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((lanes,), x.dtype, sharding=where),
        rel)
    compiled = jax.jit(lambda r: vcol._pack_body(64, {"r": r})).lower(
        shapes).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert " sort(" not in text and " scatter(" not in text
    assert set(compiled.output_shardings) == {"int64", "float64"}


#: SF10's buckets (``benchmark/configs/tpch_sf10.json``,
#: ``tpch_sf10_orders.json``): lineitem's 60M rows in 67,108,864 lanes,
#: part's 2,000,000 in 2,097,152, orders' 15,000,000 in 16,777,216,
#: customer's 1,500,000 in 2,097,152
SF10_LANES = {"lineitem": 67_108_864, "part": 2_097_152,
              "orders": 16_777_216, "customer": 2_097_152}
#: the compiler's own limit for one v5e chip's programs
V5E_PROGRAM_BYTES = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def sf10_session(new_module_session):
    """An SF0.01 load whose statistics say SF10: rows and key
    cardinalities x 1,000 (histograms and frequency lists describe
    distributions and stay), so the binder sizes every capacity as it
    does over the real tables, and nothing of that size exists here."""
    sess = _tpch_session(new_module_session, ("lineitem", "part"),
                         analyze=True)
    for name in ("lineitem", "part"):
        td = sess.catalog.table_def(name)
        rows = td.row_count
        for c, ndv in td.ndv.items():
            if ndv * 10 > rows:         # a key, not a code
                td.ndv[c] = ndv * 1000
        td.row_count = rows * 1000
        assert SF10_LANES[name] // 2 < td.row_count <= SF10_LANES[name]
    return sess


@pytest.mark.parametrize("qnum", [1, 6, 14])   # Q14: 53 s of compile here
def test_sf10_plan_compiles_for_v5e_and_fits(qnum, sf10_session, one_chip,
                                             no_persistent_cache):
    """The plan program of a statement of ``tpch_sf10.q1q6q14`` lowered at
    SF10's lanes for the described chip: arguments (the columns the plan
    reaches) plus temporaries plus outputs under the chip's 15.75 GiB by
    the compiler's own memory analysis."""
    from oceanbase_tpu.bench.tpch_queries import QUERIES
    from oceanbase_tpu.exec import plan as qplan
    from oceanbase_tpu.sql.parser import parse_sql

    sess = sf10_session
    plan, _outs, _est = sess._plan_select(parse_sql(QUERIES[qnum]), None)
    key = plan.fingerprint()
    bundle = qplan.executable_for(
        qplan.Program(qplan._lower, (plan,), key, key), True)
    mentioned, renames = bundle.scan_columns
    tables = {}
    for name in qplan.referenced_tables(plan):
        rel = qplan.narrowed(sess.catalog.table_data(name), mentioned,
                             renames.get(name))
        lanes = SF10_LANES[name]
        tables[name] = jax.tree.map(
            lambda x, lanes=lanes: jax.ShapeDtypeStruct(
                (lanes,) + x.shape[1:], x.dtype, sharding=one_chip),
            rel.pad_to(rel.capacity + 1))   # with the mask a load gives
    compiled = bundle._run.lower(tables).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    print(f"Q{qnum} at SF10 lanes: arguments {ma.argument_size_in_bytes}, "
          f"outputs {ma.output_size_in_bytes}, temporaries "
          f"{ma.temp_size_in_bytes} bytes")
    assert total < V5E_PROGRAM_BYTES, ma
    if qnum == 14:
        compacts = [n for n in qplan._postorder(plan)
                    if isinstance(n, qplan.Compact)]
        assert len(compacts) == 1 and compacts[0].strict
        assert compacts[0].capacity <= SF10_LANES["lineitem"] // 8
        # the join emits on the compacted probe's lanes: no scatter-add,
        # and beside the compaction's own gathers out of the scan's lanes
        # only p_type's codes (by the matched row) and the dictionary
        # predicate's are gathered; no compacted lineitem column is
        (join,) = [n for n in qplan._postorder(plan)
                   if isinstance(n, qplan.HashJoin)]
        assert join.build_unique and join.left is compacts[0]
        text = compiled.as_text()
        assert " scatter(" not in text
        gathers = re.findall(r"= (\w+)\[(\d+)\]\S* gather\((%\S+),", text)
        bucket = str(compacts[0].capacity)
        assert all(lanes == bucket for _t, lanes, _src in gathers)
        assert len(gathers) <= 7 + 2, gathers


@pytest.mark.parametrize("qnum", [1, 6, 14])
def test_sf10_wa5_chunk_program_compiles_for_v5e_and_fits(
        qnum, sf10_session, one_chip, no_persistent_cache):
    """The chunk program of a statement of ``tpch_sf10_wa5.streamed``
    (``exec/granule.py::GranulePlan``: the plan under its aggregate with
    the partial aggregate on top) lowered for the described chip over ONE
    granule of 2,097,152 lanes of ``lineitem`` (the columns the plan
    reaches, with the row mask every granule carries) and, for Q14, the
    resident ``part`` at SF10's lanes; and the merge program over 32
    partial states.  A granule's program and its four buffers in flight
    lie under 5 % of the chip's memory by the compiler's own analysis."""
    from oceanbase_tpu.bench.tpch_queries import QUERIES
    from oceanbase_tpu.exec import granule
    from oceanbase_tpu.exec import plan as qplan
    from oceanbase_tpu.sql.parser import parse_sql

    sess = sf10_session
    plan, _outs, _est = sess._plan_select(parse_sql(QUERIES[qnum]), None)
    lanes = granule.DEFAULT_CHUNK_ROWS
    gp = granule.GranulePlan(plan, "lineitem", lanes)
    assert gp.aggregates and (gp.group is not None) == (qnum == 1)

    def compiled_chunk(gp):
        bundle = gp.chunk_executable()
        mentioned, renames = qplan.scan_columns(gp.chunk)
        tables = {}
        for name in qplan.referenced_tables(gp.chunk):
            rel = qplan.narrowed(sess.catalog.table_data(name), mentioned,
                                 renames.get(name))
            n = lanes if name == "lineitem" else SF10_LANES[name]
            tables[name] = jax.tree.map(
                lambda x, n=n: jax.ShapeDtypeStruct(
                    (n,) + x.shape[1:], x.dtype, sharding=one_chip),
                rel.pad_to(rel.capacity + 1))
        return bundle, tables, bundle._run.lower(tables).compile()

    bundle, tables, compiled = compiled_chunk(gp)
    ma = compiled.memory_analysis()
    granule_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(tables["lineitem"]))
    print(f"Q{qnum} chunk program over {lanes} lanes: granule "
          f"{granule_bytes}, arguments {ma.argument_size_in_bytes}, "
          f"outputs {ma.output_size_in_bytes}, temporaries "
          f"{ma.temp_size_in_bytes} bytes")
    if qnum == 14:
        # the chunk program is budgeted for ONE granule: the month's
        # filter compacts into the granule's share of the plan's bucket
        # (2,097,152 lanes of 60.2M rows of 2,097,152: 131,072), and as in
        # the resident plan above every gather runs on that bucket.  The
        # program over the plan's own capacities (what a scan with no
        # estimate keeps) gathered ten times over 2,097,152 lanes
        (was,) = [n for n in qplan._postorder(plan)
                  if isinstance(n, qplan.Compact)]
        (now,) = [n for n in qplan._postorder(gp.chunk)
                  if isinstance(n, qplan.Compact)]
        (join,) = [n for n in qplan._postorder(gp.chunk)
                   if isinstance(n, qplan.HashJoin)]
        assert (was.capacity, now.capacity) == (lanes, 131_072)
        assert now.strict and join.build_unique and join.left is now
        # 131,072 probe lanes against part's 2,097,152 keys still rank by
        # merging (two sorts of 2,228,224 lanes); one rung lower would search
        assert bundle._noted["probe", "merge"] == 1
        assert (gp.budget_lanes, gp.plan_budget_lanes) == \
            (2 * 131_072, 2 * lanes)

        def gathers(text):
            return re.findall(r"= (\w+)\[(\d+)\]\S* gather\((%\S+),", text)

        (scan,) = [n for n in qplan._postorder(plan)
                   if isinstance(n, qplan.TableScan)
                   and n.table == "lineitem"]
        scan.est_rows, est = None, scan.est_rows
        try:
            plain = granule.GranulePlan(plan, "lineitem", lanes)
        finally:
            scan.est_rows = est
        assert plain.budget_lanes == 0 and [
            n.capacity for n in qplan._postorder(plain.chunk)
            if isinstance(n, qplan.Compact)] == [lanes]
        before = compiled_chunk(plain)[2]
        got, had = gathers(compiled.as_text()), gathers(before.as_text())
        message = (
            f"temporaries {before.memory_analysis().temp_size_in_bytes} -> "
            f"{ma.temp_size_in_bytes} bytes, gathers {had} -> {got}")
        print("Q14 chunk program, the plan's capacities -> a granule's: "
              + message)
        assert got and all(n == "131072" for _t, n, _src in got), message
        assert any(n == str(lanes) for _t, n, _src in had), message
        assert " scatter(" not in compiled.as_text()
        assert ma.temp_size_in_bytes < \
            before.memory_analysis().temp_size_in_bytes, message
    work_area = V5E_HBM_BYTES * 5 // 100
    assert granule_bytes * granule.BUFFERS_IN_FLIGHT < work_area
    assert (granule_bytes * (granule.BUFFERS_IN_FLIGHT - 1)
            + ma.output_size_in_bytes + ma.temp_size_in_bytes) < work_area
    # the merge program over the partial states of 29 granules (32 inputs)
    out = jax.eval_shape(bundle._run, tables)[0]
    n_in = granule.merge_inputs(29)
    merge = gp.merge_plan(n_in)
    key = merge.fingerprint()
    mb = qplan.executable_for(
        qplan.Program(qplan._lower, (merge,), key, key), False)
    parts = {granule._PARTIAL.format(i): jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        out) for i in range(n_in)}
    _fits(mb._run.lower(parts).compile())


@pytest.fixture(scope="module")
def sf10_orders_session(new_module_session):
    """``tpch_sf10_orders``' three tables at SF 0.01 with statistics that
    say SF10 (rows x 1,000; a key's distinct values as ANALYZE finds them
    at SF10, ``SF10_NDV``; histograms and samples describe distributions
    and stay), and the scans' lanes SF10's buckets."""
    sess = _tpch_session(new_module_session,
                         ("lineitem", "orders", "customer"), analyze=True)
    ndv = dict(SF10_NDV, c_custkey=1_500_000, c_name=1_500_000,
               o_comment=150_000, o_totalprice=14_000_000)
    for name in ("lineitem", "orders", "customer"):
        td = sess.catalog.table_def(name)
        rows = td.row_count
        for c, n in td.ndv.items():
            td.ndv[c] = ndv.get(c, n * 1000 if n * 10 > rows else n)
        td.row_count = rows * 1000
    lanes = sess.catalog.scan_lanes
    sess.catalog.scan_lanes = lambda t: SF10_LANES.get(t) or lanes(t)
    return sess


def _orders_statement(q: str) -> str:
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "statements",
        f"tpch_{q}_sf10.json")
    with open(path) as f:
        st = json.load(f)
    sql = st["sql"]
    for k, p in st["parameters"].items():
        sql = sql.replace("{%s}" % k, str(p["validation"]))
    return sql


@pytest.mark.parametrize("q", [
    "q4",                                           # 120 s of compile here
    "q13",                                          # 100 s
    pytest.param("q18", marks=pytest.mark.slow)])   # 480 s
def test_sf10_orders_plan_compiles_for_v5e_and_fits(
        q, sf10_orders_session, one_chip, no_persistent_cache, monkeypatch):
    """The plan program of a statement of ``tpch_sf10_orders.q4q13q18``,
    planned over statistics that say SF10 and lowered at SF10's lanes for
    the described chip: arguments + temporaries + outputs under the
    chip's 15.75 GiB, and under what is left of it beside 7.4 GB of
    resident tables.  Q18 is marked slow: the TPU compiler takes minutes
    for its fourteen sorts in the sandbox (PERF.md section 6, PR 44)."""
    import time

    from oceanbase_tpu.exec import plan as qplan
    from oceanbase_tpu.expr import compile as xcompile
    from oceanbase_tpu.sql.parser import parse_sql

    sess = sf10_orders_session
    # o_comment's dictionary is 500 values here and 150,000 at SF10, where
    # its NOT LIKE table is an input of the program: make it one here too
    monkeypatch.setattr(xcompile, "LUT_INPUT_MIN", 0)
    plan, _outs, _est = sess._plan_select(parse_sql(_orders_statement(q)),
                                          None)
    key = plan.fingerprint()
    bundle = qplan.executable_for(
        qplan.Program(qplan._lower, (plan,), key, key), True)
    mentioned, renames = bundle.scan_columns
    shapes, loaded = {}, {}
    for name in qplan.referenced_tables(plan):
        rel = loaded[name] = qplan.narrowed(
            sess.catalog.table_data(name), mentioned, renames.get(name))
        lanes = SF10_LANES[name]
        shapes[name] = jax.tree.map(
            lambda x, lanes=lanes: jax.ShapeDtypeStruct(
                (lanes,) + x.shape[1:], x.dtype, sharding=one_chip),
            rel.pad_to(rel.capacity + 1))   # with the mask a load gives
    if bundle.like_patterns:
        shapes[xcompile.LUTS_TABLE] = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((262_144,), x.dtype,
                                           sharding=one_chip),
            xcompile.dictionary_luts(bundle.like_patterns, loaded))
    t0 = time.monotonic()
    compiled = bundle._run.lower(shapes).compile()
    seconds = time.monotonic() - t0
    ma = compiled.memory_analysis()
    notes = dict(bundle._noted)
    budgets = list(bundle.diag_names)
    print(f"{q} at SF10 lanes: lower+compile {seconds:.0f} s, arguments "
          f"{ma.argument_size_in_bytes}, outputs {ma.output_size_in_bytes}"
          f", temporaries {ma.temp_size_in_bytes} bytes; notes {notes}; "
          f"budgets {budgets}")
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < V5E_PROGRAM_BYTES, ma
    # beside lineitem, orders and customer resident (7.4 GB, of which the
    # arguments are a part)
    assert ma.temp_size_in_bytes + ma.output_size_in_bytes < 6 * 10**9, ma
    compacts = [n for n in qplan._postorder(plan)
                if isinstance(n, qplan.Compact)]
    assert all(c.strict for c in compacts)
    text = compiled.as_text()
    if q == "q4":
        # the quarter's orders compacted, then ONE merged match of
        # lineitem's keys and theirs: no sort of the build side alone
        assert [c.capacity for c in compacts] == [1 << 20]
        assert notes["join_kind", "semi"] == 1 and notes["probe", "merge"] == 1
        assert notes["groupby", "masked"] == 1
        # (the compaction's, the match's two over 68,157,440 lanes, and the
        # ORDER BY's over five rows)
        assert len(re.findall(r" sort\(", text)) == 4
        assert len(re.findall(r"\[68157440\]\S*\) sort\(", text)) == 2
    elif q == "q13":
        # orders counted by customer BELOW the join (PR 46), over orders'
        # lanes; the left join against those counts on customer's lanes,
        # with no expansion and so no budget of its own; the counts
        # combined by customer and then grouped, over customer's lanes.
        # Nothing runs at the 33,554,432 lanes the join expanded into
        assert notes["join_kind", "left"] == 1
        assert notes["join_emit", "probe_lanes"] == 1
        assert ("join_emit", "expanded") not in notes
        assert notes["groupby_placement", "below_join"] == 1
        assert ("groupby_placement", "above_join") not in notes
        assert not [b for b in budgets if b[0] == "join_overflow"]
        assert [cap for name, cap in budgets if name == "groupby_overflow"] \
            == [1 << 20, 1 << 21, 64]
        assert notes["groupby_sort_lanes", ""] == (1 << 24) + 2 * (1 << 21)
        assert "33554432" not in text
    else:
        # the subquery's group-by holds every order; what its HAVING and
        # the semi-join leave is compacted to 2M lanes; customer joins on
        # the probe's lanes; lineitem's join expands into 8M
        assert ("groupby_overflow", 1 << 24) in budgets
        assert [c.capacity for c in compacts] == [1 << 21, 1 << 21]
        assert notes["join_kind", "semi"] == 1
        assert notes["join_kind", "inner"] == 2
        assert notes["join_emit", "probe_lanes"] == 1
        assert ("join_overflow", 1 << 23) in budgets
        assert notes["groupby_sort_lanes", ""] == (1 << 26) + (1 << 23)


#: one partition's lanes at SF10 under ``tpch_sf10_part4``'s DDL
SF10_PART4_LANES = {"lineitem": 16_777_216, "orders": 4_194_304,
                    "partsupp": 2_097_152, "part": 524_288,
                    "supplier": 32_768}
#: what ANALYZE finds at SF10 where SF 0.01 x 1,000 would say otherwise
SF10_NDV = {"l_orderkey": 15_000_000, "l_partkey": 2_000_000,
            "l_suppkey": 100_000, "ps_partkey": 2_000_000,
            "ps_suppkey": 100_000, "p_partkey": 2_000_000,
            "p_name": 2_000_000, "s_suppkey": 100_000, "s_nationkey": 25,
            "o_orderkey": 15_000_000, "o_custkey": 1_000_000,
            "o_orderdate": 2406}


class _Captured(Exception):
    """Raised in place of a shard program's execution."""


@pytest.fixture(scope="module")
def sf10_part4(tmp_path_factory):
    """The six tables of ``tpch_sf10_part4`` under its DDL at SF 0.01,
    with statistics that say SF10 (as ``sf10_session``): the binder and
    the PX planner size every budget as over the real tables."""
    import json
    import os

    from oceanbase_tpu.bench.tpch import TPCH_PRIMARY_KEYS, gen_tpch
    from oceanbase_tpu.server import Database

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", "tpch_sf10_part4.json")) as f:
        cfg = json.load(f)
    tables, types = gen_tpch(sf=0.01)
    db = Database(str(tmp_path_factory.mktemp("sf10part4") / "db"))
    s = db.session()
    for sql in cfg["system_settings"]:
        s.execute(sql)
    names = [sql.split()[2] for sql in cfg["system_settings"]
             if sql.startswith("create table ")]
    for name in names:
        s.catalog.load_numpy(
            name, tables[name],
            types={k: v for k, v in types.items() if k in tables[name]},
            primary_key=TPCH_PRIMARY_KEYS[name])
        s.execute(f"analyze table {name}")
    for name in SF10_PART4_LANES:
        td = s.catalog.table_def(name)
        rows = td.row_count
        for c, ndv in td.ndv.items():
            td.ndv[c] = SF10_NDV.get(c, ndv * 1000 if ndv * 10 > rows
                                     else ndv)
        td.row_count = rows * 1000
    lanes = s.catalog.scan_lanes
    s.catalog.scan_lanes = lambda t: 4 * SF10_PART4_LANES[t] \
        if t in SF10_PART4_LANES else lanes(t)
    s.execute("set px_dop = 4")
    statements = {}
    for q in ("q9", "q14"):
        with open(os.path.join(bench, "statements",
                               f"tpch_{q}_sf10.json")) as f:
            statements[q] = json.load(f)["sql"].replace(
                "{COLOR}", "green").replace("{DATE}", "1995-09-01")
    yield s, statements
    db.close()


@pytest.mark.parametrize("q", [
    "q14",                                          # 40 s of compile here
    pytest.param("q9", marks=pytest.mark.slow)])    # 260 s of compile here
def test_sf10_part4_shard_program_compiles_for_four_chips_and_fits(
        q, sf10_part4, topo, no_persistent_cache, monkeypatch):
    """The shard program of a statement of ``tpch_sf10_part4.q9q14``,
    planned over statistics that say SF10 and lowered at one SF10
    partition's lanes a chip for the described 2x2 mesh: which joins move
    rows, what the exchanges are budgeted at, and arguments + temporaries
    + outputs on a chip by the compiler's own analysis.  Q9 is marked
    slow: the TPU compiler takes over four minutes for it in the sandbox
    (PERF.md section 6, PR 42, has its numbers)."""
    import time

    from oceanbase_tpu.exec import plan as qplan

    s, statements = sf10_part4
    built = []

    def capture(self, sharded):
        if self.program.shard is None:
            return call(self, sharded)
        built.append((self, sharded))
        raise _Captured

    call = qplan._PlanExecutable.call
    from oceanbase_tpu.expr import compile as xcompile

    monkeypatch.setattr(qplan._PlanExecutable, "call", capture)
    # p_name's dictionary is 2,000 values here and 2M at SF10, where its
    # LIKE table is an input of the program: make it one here too
    monkeypatch.setattr(xcompile, "LUT_INPUT_MIN", 0)
    with pytest.raises(_Captured):
        s.execute(statements[q])
    exe, sharded = built[-1]
    luts = xcompile.dictionary_luts(exe.like_patterns, sharded)
    program = exe.program
    _mesh, axis, names, whole = program.shard
    assert whole == (("nation",) if q == "q9" else ())
    mesh = Mesh(np.array(topo.devices), (axis,))
    described = qplan._PlanExecutable(qplan.Program(
        program.body, program.args, "described", "described",
        shard=(mesh, axis, names, whole)))
    shapes = {}
    for t, rel in sharded.items():
        spec = NamedSharding(mesh, P() if t in whole else P(axis))
        lanes = None if t in whole else 4 * SF10_PART4_LANES[t]
        shapes[t] = jax.tree.map(
            lambda x, lanes=lanes, spec=spec: jax.ShapeDtypeStruct(
                ((lanes,) if lanes else x.shape[:1]) + x.shape[1:],
                x.dtype, sharding=spec), rel)
    shapes[xcompile.LUTS_TABLE] = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            (2_097_152 if x.shape[0] >= 2048 else x.shape[0],), x.dtype,
            sharding=NamedSharding(mesh, P())), luts)
    t0 = time.monotonic()
    compiled = described._run.lower(shapes).compile()
    seconds = time.monotonic() - t0
    notes = dict(described._noted)
    budgets = dict(described.diag_names)
    ma = compiled.memory_analysis()
    print(f"{q} over SF10 partitions: lower+compile {seconds:.0f} s, "
          f"arguments {ma.argument_size_in_bytes}, outputs "
          f"{ma.output_size_in_bytes}, temporaries "
          f"{ma.temp_size_in_bytes} bytes a chip; notes {notes}; "
          f"budgets {budgets}")
    _fits(compiled)
    text = compiled.as_text()
    assert " all-to-all(" in text
    if q == "q14":
        # part's partition is over the broadcast threshold at SF10: the
        # month's lineitems move to it, compacted first, and the marked
        # join emits on the lanes they arrive on
        assert notes["join", "pkey"] == 1 and ("join", "broadcast") \
            not in notes
        assert notes["join_emit", "probe_lanes"] == 1
        assert notes["lanes", "pkey"] <= 1 << 20
        assert ma.temp_size_in_bytes < 1 << 30
    else:
        # filtered part, supplier x nation broadcast; the joined lineitems
        # move to partsupp's partitions and on to orders': 2 of 5
        assert notes["join", "pkey"] == 2
        assert notes["join", "broadcast"] == 3
        assert notes["lanes", "pkey"] <= 8 << 20
        assert ma.temp_size_in_bytes < 4 << 30
    assert any(n.startswith("px_exchange.pkey.") for n in budgets)
