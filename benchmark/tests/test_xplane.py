"""The reduction from a profiler capture to numbers, against small recorded
captures kept as text-format XSpace fixtures (pruned from real captures by
dropping everything but the XLA-op events and the benchmark's spans), and
against a brute-force timeline that shares no code with it."""

import glob
import os

import numpy as np
import pytest

from benchmark.harness import xplane

FIXTURES = sorted(glob.glob(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures", "*.pbtxt")))
PREFIX = "bench:"


def _load(path):
    with open(path, encoding="utf-8") as f:
        return xplane.load_text(f.read())


def _brute(profile):
    """Busy timeline per device at 1 ns, spans, from the raw events."""
    ops = xplane._device_ops(profile)
    spans = xplane._spans(profile, PREFIX)
    lo, hi = int(spans[0][2]), int(max(s[3] for s in spans))
    lines = {}
    for dev, (evs, _async) in ops.items():
        t = np.zeros(hi - lo, dtype=bool)
        for _n, a, b in evs:
            t[max(int(a) - lo, 0):max(int(b) - lo, 0)] = True
        lines[dev] = t
    return lo, hi, lines, spans


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_busy_union_and_idle_share(path):
    profile = _load(path)
    red = xplane.reduce_capture(profile, PREFIX)
    lo, hi, lines, _spans = _brute(profile)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9, abs=2e-9)
    for d in red["devices"]:
        assert d["busy_s"] == pytest.approx(
            lines[d["name"]].sum() * 1e-9, rel=1e-4, abs=5e-8)
    mean_busy = np.mean([t.sum() for t in lines.values()]) * 1e-9
    assert red["busy_s"] == pytest.approx(mean_busy, rel=1e-4, abs=5e-8)
    idle = 1.0 - red["busy_s"] / red["window_s"]
    assert 0.0 < idle < 1.0


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_per_op_self_times_add_up_to_the_busy_time(path):
    """Self time takes nested ops out of their parents, so the sums over
    ops equal the union (ops of one device do not overlap otherwise)."""
    red = xplane.reduce_capture(_load(path), PREFIX, top=10 ** 6)
    assert sum(sec for _n, sec, _c in red["ops"]) == pytest.approx(
        red["ops_total_s"])
    assert red["ops_total_s"] == pytest.approx(red["busy_s"], rel=1e-3)
    assert all(cnt >= 1 and sec >= 0 for _n, sec, cnt in red["ops"])
    assert red["ops"] == sorted(red["ops"], key=lambda o: -o[1])


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_gaps_are_labelled_by_the_span_that_covers_them(path):
    profile = _load(path)
    red = xplane.reduce_capture(profile, PREFIX)
    lo, hi, lines, spans = _brute(profile)
    idle = ~np.any(list(lines.values()), axis=0)
    want = {}
    covered = np.zeros_like(idle)
    for kind, label, a, b in spans:
        sl = slice(int(a) - lo, int(b) - lo)
        want[f"{kind}:{label}"] = want.get(f"{kind}:{label}", 0) \
            + idle[sl].sum() * 1e-9
        covered[sl] = True
    want[xplane.BETWEEN] = (idle & ~covered).sum() * 1e-9
    got = dict(red["gaps"])
    assert set(got) == {k for k, v in want.items() if v > 0}
    for k, v in got.items():
        assert v == pytest.approx(want[k], rel=1e-3, abs=5e-8), k
    assert sum(got.values()) == pytest.approx(
        red["window_s"] - red["any_busy_s"], rel=1e-6)
    assert got[xplane.BETWEEN] > 0  # the fixtures pause between statements


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_busy_time_inside_each_span(path):
    profile = _load(path)
    red = xplane.reduce_capture(profile, PREFIX)
    lo, _hi, lines, spans = _brute(profile)
    assert len(red["spans"]) == len(spans)
    for row, (kind, label, a, b) in zip(red["spans"], spans):
        assert (row["kind"], row["label"]) == (kind, label)
        inside = [t[int(a) - lo:int(b) - lo].sum() * 1e-9
                  for t in lines.values()]
        assert row["busy_max_s"] == pytest.approx(max(inside), rel=1e-3,
                                                  abs=5e-8)


def test_interval_arithmetic():
    u = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert u == [(0, 3), (5, 8)]
    assert xplane.total(u) == 6
    assert xplane.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert xplane.complement(u, -1, 9) == [(-1, 0), (3, 5), (8, 9)]


def test_self_time_takes_nested_ops_out_of_their_parent():
    evs = [("while", 0, 100), ("sort", 10, 40), ("fusion", 40, 45),
           ("sort", 50, 90), ("inner", 60, 70), ("copy", 120, 130)]
    got = xplane.self_times(evs)
    assert got == {"while": [25.0, 1], "sort": [60.0, 2], "fusion": [5.0, 1],
                   "inner": [10.0, 1], "copy": [10.0, 1]}
    assert sum(v[0] for v in got.values()) == xplane.total(
        xplane.union((a, b) for _n, a, b in evs))


def test_collectives_are_recognised_by_their_xla_names():
    for name in ("all-to-all.5", "all-gather.2", "all-reduce",
                 "collective-permute.1", "reduce-scatter.3",
                 "all-gather-start.1", "all-reduce-done"):
        assert xplane.COLLECTIVE.match(name), name
    for name in ("fusion.12", "sort.3", "all_gather.12", "copy"):
        assert not xplane.COLLECTIVE.match(name), name


def test_a_capture_with_no_device_op_reduces_to_nothing():
    text = ('planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" '
            'events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 } } '
            'event_metadata { key: 1 value { id: 1 name: "bench:execute:x" '
            '} } }')
    assert xplane.reduce_capture(xplane.load_text(text), PREFIX) is None


@pytest.mark.parametrize("name,want", [
    ('%fusion.165 = u32[8388608]{0:T(1024)} fusion(u32[262144]{0:T(1024)} '
     '%get-tuple-element.1368, s32[8388608]{0:T(1024)S(1)} %fusion.163), '
     'kind=kCustom, calls=%fused_computation.8.clone.clone',
     "fusion.165 fusion/kCustom u32[8388608]<-(u32[262144],s32[8388608])"),
    ('%custom-call.5 = u32[8388608]{0:T(1024)} custom-call(s64[8388608]'
     '{0:T(1024)} %tables__lineitem___0__3__0_.1), '
     'custom_call_target="X64SplitLow"',
     "custom-call.5 custom-call/X64SplitLow u32[8388608]<-(s64[8388608])"),
    ('%fusion.22 = (u32[7]{0:T(128)S(1)}, u32[7]{0:T(128)S(1)}) fusion('
     'u32[7]{0:T(128)S(1)} %a, s32[8388608]{0:T(1024)S(1)} %b), kind=kLoop, '
     'calls=%fused_computation.6',
     "fusion.22 fusion/kLoop (u32[7],u32[7])<-(u32[7],s32[8388608])"),
    ("sort.0", "sort.0"),  # the CPU's traces name an op by its name alone
])
def test_op_label_keeps_name_opcode_kind_and_shapes(name, want):
    assert xplane.op_label(name) == want


def test_the_tpu_fixture_names_ops_by_label_and_finds_the_device_plane():
    path = next(p for p in FIXTURES if "tpu" in os.path.basename(p))
    red = xplane.reduce_capture(_load(path), PREFIX)
    assert [d["name"] for d in red["devices"]] == ["/device:TPU:0"]
    assert any("custom-call/X64Split" in n for n, _s, _c in red["ops"])
    assert all(len(n) <= 120 for n, _s, _c in red["ops"])
