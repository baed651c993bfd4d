"""Roofline calibration & device-time profiling plane (the PR's
coverage satellite): calibration determinism, predicted_s monotonicity,
knob on/off behavior, poisoned-lane parity for every probe kernel, and
gv$cost_units / gv$device_profile row shapes + the persistence
(checksum) contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oceanbase_tpu.server import calibrate
from oceanbase_tpu.storage.integrity import CorruptionError


@pytest.fixture()
def db(tmp_path):
    from oceanbase_tpu.server import Database

    d = Database(str(tmp_path / "db"))
    yield d
    d.close()


# ---------------------------------------------------------------------------
# calibration probe
# ---------------------------------------------------------------------------


def test_probe_produces_constants():
    u = calibrate.run_probe("boot")
    assert u.backend == jax.default_backend()
    assert u.device_count >= 1
    assert u.peak_flops_s > 0.0
    assert u.peak_bytes_s > 0.0
    assert u.launch_overhead_s > 0.0
    assert u.calibrated_ts > 0.0
    ok = [m for m in u.measurements if "error" not in m]
    kernels = {m["kernel"] for m in ok}
    assert kernels == {"stream_copy", "masked_reduce",
                       "segment_groupby", "searchsorted",
                       "small_matmul"}
    for m in ok:
        assert m["device_s"] > 0.0
        assert m["flops"] >= 0.0 and m["bytes"] >= 0.0


def test_probe_determinism_two_runs_agree():
    """Two probe runs on the same backend must agree on the machine
    constants within a noise tolerance (min-of-repeats on a shared CI
    host: a generous factor, but a REAL bound — a broken measurement is
    off by orders of magnitude, not by 4x)."""
    a = calibrate.run_probe("boot")
    b = calibrate.run_probe("boot")
    for attr in ("peak_flops_s", "peak_bytes_s"):
        x, y = getattr(a, attr), getattr(b, attr)
        ratio = max(x, y) / max(min(x, y), 1e-30)
        assert ratio < 4.0, f"{attr}: {x} vs {y} (ratio {ratio:.1f})"


def test_predicted_s_monotone_in_rows():
    """The roofline prediction must grow (weakly) with input size —
    the property the CBO's cost comparisons rest on."""
    u = calibrate.run_probe("boot")
    preds = []
    for n in (1_000, 10_000, 100_000, 1_000_000, 10_000_000):
        flops = 2.0 * n
        nbytes = 8.0 * n
        preds.append(calibrate.predict_seconds(u, flops, nbytes))
    assert all(b >= a for a, b in zip(preds, preds[1:])), preds
    # and monotone in launch count
    p1 = calibrate.predict_seconds(u, 1e6, 1e6, calls=1)
    p4 = calibrate.predict_seconds(u, 1e6, 1e6, calls=4)
    assert p4 >= p1


def test_time_q_error():
    assert calibrate.time_q_error(0.0, 1.0) == 0.0
    assert calibrate.time_q_error(1.0, 0.0) == 0.0
    assert calibrate.time_q_error(2.0, 1.0) == pytest.approx(2.0)
    assert calibrate.time_q_error(1.0, 2.0) == pytest.approx(2.0)
    assert calibrate.time_q_error(3.0, 3.0) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# poisoned-lane parity for every probe kernel (Static-shape policy)
# ---------------------------------------------------------------------------


def _poison_floats(x, mask):
    return jnp.where(mask, x, jnp.nan)


def _poison_ints(x, mask):
    from oceanbase_tpu.analysis.poison import INT_POISON

    return jnp.where(mask, x, jnp.asarray(INT_POISON, x.dtype))


def _bit_identical(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape
    assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("case_ix", range(5))
def test_probe_kernels_poison_parity(case_ix):
    """Every calibration kernel must treat masked-dead lanes as if they
    did not exist: NaN/sentinel garbage in the dead lanes may not move
    a single output bit."""
    cases = calibrate.probe_cases("boot")
    name, _rows, build, _f, _b = cases[case_ix]
    fn, args = build()
    mask = args[-1]
    clean = jax.jit(fn)(*args)
    poisoned_args = []
    for a in args[:-1]:
        if jnp.issubdtype(a.dtype, jnp.floating):
            if a.ndim == 2:  # matmul lhs: poison dead rows
                poisoned_args.append(
                    jnp.where(mask[:, None], a, jnp.nan))
            else:
                poisoned_args.append(_poison_floats(a, mask))
        elif name == "searchsorted" and a is args[0]:
            # the sorted KEY column is not masked input — leave it
            poisoned_args.append(a)
        else:
            poisoned_args.append(_poison_ints(a, mask))
    out = jax.jit(fn)(*poisoned_args, mask)
    _bit_identical(clean, out)


# ---------------------------------------------------------------------------
# persistence: checksummed on disk (PR 9 contract)
# ---------------------------------------------------------------------------


def test_units_roundtrip_and_corruption(tmp_path):
    root = str(tmp_path)
    u = calibrate.run_probe("boot")
    calibrate.save_units(root, u)
    loaded = calibrate.load_units(root)
    assert loaded is not None
    assert loaded.peak_flops_s == pytest.approx(u.peak_flops_s)
    assert loaded.backend == u.backend
    # flip bytes: load must raise CorruptionError, never serve garbage
    path = calibrate._units_path(root)
    body = open(path).read().replace(
        '"peak_flops_s"', '"peak_flops_sX"', 1)
    with open(path, "w") as fh:
        fh.write(body)
    with pytest.raises(CorruptionError):
        calibrate.load_units(root)
    # the boot path quarantines + re-probes instead of failing
    units = calibrate.ensure_units(root, force=True)
    assert units.peak_flops_s > 0
    assert calibrate.load_units(root).backend == units.backend


def test_missing_units_file_is_none(tmp_path):
    assert calibrate.load_units(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# the live plane: knobs, gv$ row shapes, PROFILE
# ---------------------------------------------------------------------------


def _load(sess, n=300):
    sess.execute("create table pt (id int primary key, v int)")
    sess.execute("insert into pt values "
                 + ",".join(f"({i},{i % 5})" for i in range(n)))


def test_device_split_recorded(db):
    from oceanbase_tpu.exec.plan import reset_plan_cache_stats

    # gv$plan_cache is the process's: a PX shard program an earlier file
    # of this worker executed (executions without device_executions)
    # would tie with, or outrank, the plan this test runs
    reset_plan_cache_stats()
    s = db.session()
    _load(s)
    for _ in range(2):
        s.execute("select v, count(*) from pt group by v")
    rows = s.execute(
        "select executions, device_executions, achieved_gflops,"
        " achieved_gbps, device_s_total from gv$plan_cache"
        " order by executions desc limit 1").rows()
    execs, dev_execs, gflops, gbps, dev_s = rows[0]
    assert execs >= 2 and dev_execs >= 2
    assert dev_s > 0.0
    assert gflops > 0.0, "achieved_gflops must be nonzero on CPU"
    assert gbps > 0.0
    # gv$sql_audit carries the split
    au = s.execute(
        "select host_s, device_s from gv$sql_audit"
        " where sql like 'select v%' order by start_ts desc limit 1"
    ).rows()
    assert au[0][0] > 0.0 and au[0][1] > 0.0


def test_enable_profiling_off_stops_split(db):
    s = db.session()
    _load(s)
    s.execute("alter system set enable_profiling = false")
    try:
        s.execute("select count(*) from pt")
        au = s.execute(
            "select host_s, device_s from gv$sql_audit"
            " where sql like 'select count%' order by start_ts desc"
            " limit 1").rows()
        assert au[0][1] == 0.0  # no device half without the knob
        assert au[0][0] > 0.0   # host half still measured
    finally:
        s.execute("alter system set enable_profiling = true")


def test_monitor_carries_time_qerror(db):
    s = db.session()
    _load(s)
    assert db.cost_units is not None  # boot calibration ran
    s.execute("select v, count(*) from pt group by v")
    pm = s.execute(
        "select device_s, pred_s, time_q_error from"
        " gv$sql_plan_monitor order by ts desc limit 1").rows()
    dev, pred, tq = pm[0]
    assert dev > 0.0 and pred > 0.0 and tq >= 1.0
    # aggregated per-operator-type calibration table
    tc = s.execute(
        "select operator, executions, correction, time_q_p50"
        " from gv$time_calibration").rows()
    assert len(tc) >= 1
    for _op, n, corr, p50 in tc:
        assert n >= 1 and corr > 0.0 and p50 >= 1.0


def test_explain_analyze_roofline_line(db):
    s = db.session()
    _load(s)
    r = s.execute("explain analyze select v, count(*) from pt group by v")
    text = r.plan_text
    assert "roofline: [pred=" in text
    assert "dev=" in text and "tq=" in text


def test_cost_units_rows(db):
    s = db.session()
    rows = s.execute(
        "select kind, name, value, unit from gv$cost_units").rows()
    kinds = {r[0] for r in rows}
    assert kinds == {"constant", "probe"}
    consts = {r[1]: r[2] for r in rows if r[0] == "constant"}
    assert set(consts) == {"peak_flops_s", "peak_bytes_s",
                           "eff_bytes_s", "launch_overhead_s",
                           "rpc_s_per_byte"}
    assert consts["peak_flops_s"] > 0
    assert 0 < consts["eff_bytes_s"] <= consts["peak_bytes_s"]
    probes = {r[1] for r in rows if r[0] == "probe"}
    assert "stream_copy" in probes and "small_matmul" in probes


def test_alter_system_calibrate(db):
    s = db.session()
    before = db.cost_units.calibrated_ts
    r = s.execute("alter system calibrate")
    got = dict(r.rows())
    assert got["backend"] == jax.default_backend()
    assert float(got["peak_gflops"]) > 0
    assert db.cost_units.calibrated_ts >= before
    assert db.cost_units.preset == "full"
    # calibrate with the knob off is a typed error
    s.execute("alter system set enable_calibration = false")
    try:
        with pytest.raises(ValueError):
            s.execute("alter system calibrate")
    finally:
        s.execute("alter system set enable_calibration = true")


def test_profile_statement_and_device_profile_rows(db):
    s = db.session()
    _load(s)
    s.execute("select sum(v) from pt")  # warm (compile outside trace)
    r = s.execute("profile select sum(v) from pt")
    assert r.rows() == [(600,)]
    # joined by trace_id to the audit row of the PROFILE statement
    tid = s.execute(
        "select trace_id from gv$sql_audit where sql like 'profile%'"
        " order by start_ts desc limit 1").rows()[0][0]
    assert tid
    dp = s.execute(
        f"select kernel, kind, occurrences, total_s from"
        f" gv$device_profile where trace_id = '{tid}'").rows()
    assert len(dp) >= 1, "PROFILE must yield >=1 gv$device_profile row"
    for _k, kind, occ, total in dp:
        assert kind in ("kernel", "host")
        assert occ >= 1 and total >= 0.0
    # kernels as the capture names them, and the profiled statement's
    # own phases (the program's ob: spans opened inside the capture) on
    # the same timeline
    assert any(kind == "kernel" for _k, kind, _o, _t in dp)
    hosts = {k for k, kind, _o, _t in dp if kind == "host"}
    assert {"ob:plan.dispatch", "ob:plan.device_wait",
            "ob:materialize"} <= hosts
    # SHOW PROFILE renders the same capture
    sp = s.execute("show profile").rows()
    assert len(sp) >= 1


def test_show_profile_without_capture(db):
    s = db.session()
    rows = s.execute("show profile").rows()
    assert len(rows) == 1
    assert "no PROFILE captured" in rows[0][1]


def test_profile_knob_off_runs_plain(db):
    s = db.session()
    _load(s)
    s.execute("alter system set enable_profiling = false")
    try:
        r = s.execute("profile select count(*) from pt")
        assert r.rows() == [(300,)]
        assert s.execute(
            "select count(*) from gv$device_profile").rows() == [(0,)]
    finally:
        s.execute("alter system set enable_profiling = true")


def test_profile_propagates_statement_errors(db):
    s = db.session()
    with pytest.raises(Exception):
        s.execute("profile select * from no_such_table_xyz")


def test_gv_backend_row(db):
    s = db.session()
    rows = s.execute(
        "select platform, device_count, cpu_fallback,"
        " calibration_age_s from gv$backend").rows()
    assert len(rows) == 1
    platform, count, _fb, age = rows[0]
    assert platform == jax.default_backend()
    assert count >= 1
    assert age >= 0.0  # boot calibration ran in this process


def test_calibration_disabled_boot(tmp_path):
    """enable_calibration=false at boot: no units adopted, predictions
    degrade to zeros, everything still runs."""
    from oceanbase_tpu.server import Database

    root = str(tmp_path / "nocal")
    import json
    import os

    os.makedirs(root)
    with open(os.path.join(root, "config.json"), "w") as fh:
        json.dump({"enable_calibration": False}, fh)
    d = Database(root)
    try:
        assert d.cost_units is None
        s = d.session()
        _load(s, n=50)
        assert s.execute("select count(*) from pt").rows() == [(50,)]
        assert not os.path.exists(os.path.join(root, "cost_units.json"))
    finally:
        d.close()


def test_calibration_disabled_predicts_nothing(tmp_path):
    """A database booted with enable_calibration=false must emit ZERO
    predictions even when ANOTHER database already calibrated the
    process cache — per-Database units, not the global cache."""
    import json
    import os

    from oceanbase_tpu.server import Database

    calibrate.ensure_units(None)  # process cache deliberately warm
    root = str(tmp_path / "nocal2")
    os.makedirs(root)
    with open(os.path.join(root, "config.json"), "w") as fh:
        json.dump({"enable_calibration": False}, fh)
    d = Database(root)
    try:
        s = d.session()
        _load(s, n=100)
        s.execute("select v, count(*) from pt group by v")
        pm = s.execute(
            "select pred_s, time_q_error from gv$sql_plan_monitor"
            " order by ts desc limit 1").rows()
        assert pm[0] == (0.0, 0.0)
        assert s.execute("select count(*) from gv$time_calibration"
                         ).rows() == [(0,)]
    finally:
        d.close()


def test_profile_with_tracing_off_still_joinable(db):
    s = db.session()
    _load(s, n=100)
    s.execute("select sum(v) from pt")  # warm
    s.execute("alter system set enable_query_trace = false")
    try:
        r = s.execute("profile select sum(v) from pt")
        assert r.rowcount == 1
        sp = s.execute("show profile").rows()
        # a successful capture, not the 'no PROFILE captured' note
        assert sp and sp[0][2] != "note"
        tids = set(s.execute(
            "select trace_id from gv$device_profile").rows())
        assert len(tids) >= 1 and ("",) not in tids
    finally:
        s.execute("alter system set enable_query_trace = true")


def test_units_persisted_at_boot(db):
    import os

    assert os.path.exists(os.path.join(db.root, "cost_units.json"))
    loaded = calibrate.load_units(db.root)
    assert loaded is not None and loaded.peak_flops_s > 0


def test_exec_times_accumulator():
    from oceanbase_tpu.exec import plan as qplan

    qplan.reset_exec_times()
    qplan.add_exec_times(host_s=0.5, device_s=0.25, flops=10.0,
                         bytes=20.0, calls=2)
    t = qplan.exec_times()
    assert (t.host_s, t.device_s, t.flops, t.bytes, t.calls) == \
        (0.5, 0.25, 10.0, 20.0, 2)
    qplan.reset_exec_times()
    t = qplan.exec_times()
    assert t.calls == 0 and t.device_s == 0.0


def test_trace_parse_dir_empty(tmp_path):
    from oceanbase_tpu.server import profiler

    assert profiler.parse_trace_dir(str(tmp_path)) == []
