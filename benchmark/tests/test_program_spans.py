"""The reader of the program's own spans (``harness/program_spans.py``)
against a capture from the chip with ``ob:`` spans in it (three Q6 at
SF0.05, pruned to the XLA-op events and the ``bench:`` / ``ob:`` spans),
against a brute-force timeline that shares no code with it, and against a
capture of a program that writes no ``ob:`` span (the parent of PR 24)."""

import os

import numpy as np
import pytest

from benchmark.harness import program_spans, spec, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
WITH_OB = os.path.join(HERE, "fixtures", "tpu_v5e_ob_spans_sf005.pbtxt")
WITHOUT_OB = os.path.join(HERE, "fixtures", "tpu_v5e_scan_sf005.pbtxt")


def _text(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


@pytest.fixture(scope="module")
def profile():
    return xplane.load_text(_text(WITH_OB))


@pytest.fixture(scope="module")
def reduced(profile):
    return program_spans.reduce_profile(profile)


def _host_events(profile, prefix):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def test_nest_self_times_and_leaves():
    evs = [("root", 0, 100), ("a", 10, 40), ("a.x", 15, 25), ("b", 50, 90),
           ("after", 120, 130)]
    got = {n: (own, leaf) for n, _a, _b, own, leaf in program_spans.nest(evs)}
    assert got == {"root": (30.0, False), "a": (20.0, False),
                   "a.x": (10.0, True), "b": (40.0, True),
                   "after": (10.0, True)}


_SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 60000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = u32[8] fusion()" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 90000000 }
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 10000000 }
    events { metadata_id: 5 offset_ps: 50000000 duration_ps: 40000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:execute:q" } }
  event_metadata { key: 2 value { id: 2 name: "ob:statement" } }
  event_metadata { key: 3 value { id: 3 name: "ob:parse" } }
  event_metadata { key: 4 value { id: 4 name: "ob:gc" } }
  event_metadata { key: 5 value { id: 5 name: "ob:materialize" } } }
"""


def test_a_collector_pause_leaves_the_span_around_it_a_leaf():
    """bench:execute 0-100 us; statement 5-95; parse 10-40 with a pause
    20-30 inside; materialize 50-90; the device busy 60-70."""
    red = program_spans.reduce_profile(xplane.load_text(_SYNTHETIC))
    (st,) = red["statements"]
    assert st["self_ns"] == {"statement": 20000.0, "parse": 20000.0,
                             "gc": 10000.0, "materialize": 40000.0}
    # leaves: parse 10-20 and 30-40, gc 20-30, materialize 50-90
    assert st["unowned_ns"] == 30000.0
    assert st["holes_ns"] == {"start -> parse": 10000.0,
                              "parse -> materialize": 10000.0,
                              "materialize -> end": 10000.0}
    assert red["idle_total_ns"] == 90000.0
    assert red["idle_ns"] == {"gc": 10000.0, "parse": 20000.0,
                              "materialize": 30000.0,
                              program_spans.UNOWNED: 30000.0}


def test_statements_and_spans_found(reduced):
    sts = reduced["statements"]
    assert len(sts) == 3
    for st in sts:
        assert {"statement", "parse", "plan.dispatch", "plan.device_wait",
                "materialize", "statement.close"} <= set(st["self_ns"])
        assert st["unowned_ns"] >= 0
    assert reduced["device_ops"]


def test_unowned_is_what_no_leaf_covers_brute_force(profile, reduced):
    obs = _host_events(profile, "ob:")
    execs = sorted(_host_events(profile, "bench:execute:"),
                   key=lambda e: e[1])
    for (_n, s0, s1), st in zip(execs, reduced["statements"]):
        inside = [(n, a, b) for n, a, b in obs if a >= s0 and b <= s1]
        covered = np.zeros(s1 - s0, dtype=bool)
        for n, a, b in inside:
            leaf = not any(a <= a2 and b2 <= b and (a2, b2, n2) != (a, b, n)
                           for n2, a2, b2 in inside)
            if leaf:
                covered[a - s0:b - s0] = True
        assert st["unowned_ns"] == pytest.approx(
            (~covered).sum(), abs=len(inside) + 2)
        assert sum(st["holes_ns"].values()) == pytest.approx(
            st["unowned_ns"], abs=1e-6)
        # every nanosecond of the statement is some span's own or unowned
        # by ANY span: self times never add up to more than the statement
        assert sum(st["self_ns"].values()) <= (s1 - s0) + 1e-6


def test_idle_by_leaf_span_adds_up_brute_force(profile, reduced):
    ops = xplane._device_ops(profile)
    spans = xplane._spans(profile, "bench:")
    lo, hi = int(spans[0][2]), int(max(s[3] for s in spans))
    busy = np.zeros(hi - lo, dtype=bool)
    for evs, _async in ops.values():
        for _n, a, b in evs:
            busy[max(int(a) - lo, 0):max(int(b) - lo, 0)] = True
    idle = ~busy
    assert reduced["idle_total_ns"] == pytest.approx(idle.sum(), abs=200)
    assert sum(reduced["idle_ns"].values()) == pytest.approx(
        reduced["idle_total_ns"], rel=1e-9)
    # idle under a span never exceeds the span; at SF0.05 the program is
    # over before the host starts to wait (the wait is the runtime's
    # latency), and the fetch to the host finds the device idle
    for name, idle_under in reduced["idle_ns"].items():
        if name != program_spans.UNOWNED:
            assert idle_under <= sum(
                st["self_ns"].get(name, 0.0)
                for st in reduced["statements"]) + 1e-6, name
    assert reduced["idle_ns"]["materialize"] > 0.9 * sum(
        st["self_ns"]["materialize"] for st in reduced["statements"])


def _as_capture(tmp_path, monkeypatch, pbtxt, cell="tpch_sf1.scan",
                template="tpch_q6"):
    """Lay a fixture down where the runner leaves a template's capture."""
    from jax.profiler import ProfileData

    monkeypatch.setattr(spec, "SCRATCH_DIR", str(tmp_path))
    monkeypatch.setattr(program_spans, "_by_cell", {})
    d = tmp_path / "trace" / cell / template / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_text(pbtxt)))
    return {"cell": {"name": cell},
            "captures": [{"template": template, "executions": 3,
                          "reduced": {}}],
            "counters_before": {}, "counters_after": {}}


SPAN_METRICS = ("host_unowned_ms", "idle_unattributed_pct", "parse_ms",
                "result_fetch_ms", "stmt_close_ms", "tables_ms",
                "dispatch_ms")


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metrics_read_the_capture(tmp_path, monkeypatch, name):
    record = _as_capture(tmp_path, monkeypatch, WITH_OB)
    value = spec.load_module("layer_metrics", name).compute(record)
    assert value is not None and value > 0
    if name.endswith("_ms"):
        assert value < 10.0  # a Q6 at SF0.05 takes 6 ms in all
    else:
        assert value < 100.0


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metrics_leave_a_program_without_spans_out(
        tmp_path, monkeypatch, name):
    """The parent of PR 24 writes no ob: span: nothing to read, no error."""
    record = _as_capture(tmp_path, monkeypatch, WITHOUT_OB)
    assert spec.load_module("layer_metrics", name).compute(record) is None


def test_span_metrics_without_a_capture(tmp_path, monkeypatch):
    monkeypatch.setattr(spec, "SCRATCH_DIR", str(tmp_path))
    monkeypatch.setattr(program_spans, "_by_cell", {})
    record = {"cell": {"name": "tpch_sf1.scan"},
              "captures": [{"template": "tpch_q6", "reduced": None}]}
    for name in SPAN_METRICS:
        assert spec.load_module("layer_metrics", name).compute(record) is None


def test_counter_metrics():
    def compute(name, before, after):
        return spec.load_module("layer_metrics", name).compute(
            {"counters_before": before, "counters_after": after})

    # a program without the counters (the parent): left out
    for name in ("device_copy_build_s", "warmup_trace_lower_s",
                 "gc_pause_s"):
        assert compute(name, {"plan.compiles": 3.0},
                       {"plan.compiles": 3.0}) is None
    assert compute("device_copy_build_s", {},
                   {"storage.device_copy_ns": 44.0e9}) == pytest.approx(44.0)
    assert compute("warmup_trace_lower_s",
                   {"jax.compile_ns{stage=trace}": 2.0e9,
                    "jax.compile_ns{stage=lower}": 0.5e9,
                    "jax.compile_ns{stage=backend}": 9e9}, {}) \
        == pytest.approx(2.5)
    assert compute("gc_pause_s", {"runtime.gc_pause_ns": 1.0e9},
                   {"runtime.gc_pause_ns": 1.4e9}) == pytest.approx(0.4)
    # collections before the window only: a quiet window reads 0, not None
    assert compute("gc_pause_s", {"runtime.gc_pause_ns": 1.0e9},
                   {"runtime.gc_pause_ns": 1.0e9}) == 0.0


def test_tables_print_both(tmp_path, monkeypatch):
    _as_capture(tmp_path, monkeypatch, WITH_OB)
    text = program_spans.tables("tpch_sf1.scan")
    assert "| materialize |" in text and "| plan.device_wait |" in text
    assert program_spans.UNOWNED in text
    assert "device idle in the capture" in text
    assert "3 traced statement(s)" in text
