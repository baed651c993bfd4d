"""The eight readers of the write path (PR 40) on reductions and counters
made by hand: a template whose transactions committed, a traced span that
holds no ``ob:tx.commit`` (a read; a write on a program without the
spans), a program without the series; and each metric's entry in
``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark.harness import program_spans, spec

CELL = "tpch_sf1_htap.fresh"
SPAN_METRICS = {
    "tx_parse_ms": ("session", "program_span", "ms", "lower"),
    "tx_dml_ms": ("transactions", "program_span", "ms", "lower"),
    "tx_commit_ms": ("transactions", "program_span", "ms", "lower"),
    "wal_sync_ms": ("replicated log", "program_span", "ms", "lower"),
    "tx_unowned_ms": ("session", "program_span", "ms", "lower"),
}
COUNTER_METRICS = {
    "fsyncs_per_commit": ("replicated log", "program_counter", "count",
                          "higher"),
    "log_acks_per_commit": ("replicated log", "program_counter", "count",
                            "higher"),
    "log_bytes_per_row": ("replicated log", "program_counter", "B/row",
                          "lower"),
}


def _reader(name):
    return spec.load_module("layer_metrics", name)


def _tx(ms: dict, unowned_ms: float = 0.0) -> dict:
    return {"self_ns": {k: v * 1e6 for k, v in ms.items()},
            "unowned_ns": unowned_ms * 1e6, "holes_ns": {},
            "start_ns": 0, "end_ns": 1}


def _reds(**by_template) -> dict:
    return {t: {"statements": sts} for t, sts in by_template.items()}


INSERTS = [_tx({"parse": p, "dml.bind": 8.0, "dml.write": w, "statement": 1.0,
                "tx.commit": 0.5, "tx.log_encode": 4.0, "tx.apply": 1.5,
                "palf.append": 0.2, "palf.persist": 3.0, "palf.apply": 0.8},
               unowned_ms=u)
           for p, w, u in ((100.0, 20.0, 2.0), (110.0, 22.0, 4.0),
                           (90.0, 30.0, 3.0))]
DELETES = [_tx({"parse": 1.0, "dml.match": 0.5, "dml.candidates": 40.0,
                "dml.predicate": 3.0, "dml.rows": 1.5, "dml.bind": 1.0,
                "dml.write": 4.0, "materialize": 2.0, "tx.commit": 0.25,
                "tx.log_encode": 1.0, "tx.apply": 0.75, "palf.append": 0.1,
                "palf.persist": 2.4, "palf.apply": 0.5}, unowned_ms=1.0)]
READ = [_tx({"parse": 0.3, "plan.dispatch": 0.6, "materialize": 0.3})]
#: a write on a program that has no write span: ``ob:parse`` is there
BLIND_WRITE = [_tx({"parse": 100.0, "statement": 50.0}, unowned_ms=50.0)]

WANT = {
    "tx_parse_ms": (100.0 * 1.0) ** 0.5,
    "tx_dml_ms": (30.0 * 50.0) ** 0.5,
    "tx_commit_ms": (6.0 * 2.0) ** 0.5,
    "wal_sync_ms": (4.0 * 3.0) ** 0.5,
    "tx_unowned_ms": (3.0 * 1.0) ** 0.5,
}


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_readers(monkeypatch, name):
    compute = _reader(name).compute
    # the geometric mean, over the templates that write, of the median per
    # transaction; the read between them holds no commit and counts nowhere
    monkeypatch.setattr(program_spans, "load", lambda record: _reds(
        a=INSERTS, b=READ, c=DELETES))
    assert compute({}) == pytest.approx(WANT[name])
    # one template that writes: its median
    monkeypatch.setattr(program_spans, "load",
                        lambda record: _reds(a=INSERTS, b=READ))
    assert compute({}) == pytest.approx(WANT[name] ** 2 / {
        "tx_parse_ms": 1.0, "tx_dml_ms": 50.0, "tx_commit_ms": 2.0,
        "wal_sync_ms": 3.0, "tx_unowned_ms": 1.0}[name])
    # a traced span with and without ``ob:tx.commit`` in ONE template: only
    # the transaction that committed is read
    monkeypatch.setattr(program_spans, "load", lambda record: _reds(
        a=INSERTS[:1] + BLIND_WRITE))
    assert compute({}) == pytest.approx({
        "tx_parse_ms": 100.0, "tx_dml_ms": 28.0, "tx_commit_ms": 6.0,
        "wal_sync_ms": 4.0, "tx_unowned_ms": 2.0}[name])
    # the parent's program: writes without a write span; a cell of reads;
    # captures without the program's spans at all
    for reds in (_reds(a=BLIND_WRITE, b=READ), _reds(b=READ), None):
        monkeypatch.setattr(program_spans, "load", lambda record: reds)
        assert compute({}) is None


WINDOW = {
    "counters_before": {
        "palf.fsyncs": 50.0, "palf.appends": 10.0, "palf.acks": 30.0,
        "palf.append_bytes": 1000.0, "tx.commits{path=two_phase}": 4.0,
        "tx.commits{path=empty}": 1.0, "tx.rows_written{op=insert}": 100.0},
    "counters_after": {
        "palf.fsyncs": 50.0 + 3 * 30 + 5, "palf.appends": 10.0 + 31,
        "palf.acks": 30.0 + 93, "palf.append_bytes": 1000.0 + 3 * 60000,
        "tx.commits{path=two_phase}": 4.0 + 28,
        "tx.commits{path=one_phase}": 2.0,
        "tx.commits{path=empty}": 7.0,
        "tx.rows_written{op=insert}": 100.0 + 700,
        "tx.rows_written{op=delete}": 300.0,
        "tx.rows_written{op=index}": 5000.0},
}
#: the series the parent's program has: no commit, ack or row is counted
PARENT = {
    "counters_before": {"palf.fsyncs": 50.0, "palf.appends": 10.0,
                        "palf.entries_appended": 900.0},
    "counters_after": {"palf.fsyncs": 140.0, "palf.appends": 40.0,
                       "palf.entries_appended": 9000.0},
}


@pytest.mark.parametrize("name, want", [
    # 30 commits (the empty ones logged nothing), one election's five
    # flushes; 31 appends (the election's no-op) acknowledged by three
    ("fsyncs_per_commit", 95.0 / 30), ("log_acks_per_commit", 3.0),
    # index entries are not user rows
    ("log_bytes_per_row", 180000.0 / 1000)])
def test_counter_readers(name, want):
    compute = _reader(name).compute
    assert compute(WINDOW) == pytest.approx(want)
    assert compute(PARENT) is None
    assert compute({"counters_before": {}, "counters_after": {}}) is None
    # a window that committed, appended and wrote nothing
    still = {"counters_before": WINDOW["counters_after"],
             "counters_after": WINDOW["counters_after"]}
    assert compute(still) is None


def test_a_flush_shared_by_three_replicas_reads_as_one():
    """What the counter pair is for: a later change that flushes once for
    three replicas, or acknowledges at a quorum, moves the number."""
    shared = json.loads(json.dumps(WINDOW))
    shared["counters_after"]["palf.fsyncs"] = 50.0 + 30
    shared["counters_after"]["palf.acks"] = 30.0 + 62
    assert _reader("fsyncs_per_commit").compute(shared) == pytest.approx(1.0)
    assert _reader("log_acks_per_commit").compute(shared) == \
        pytest.approx(2.0)


@pytest.mark.parametrize("name", sorted({**SPAN_METRICS, **COUNTER_METRICS}))
def test_the_entry_in_benchmark_json(name):
    with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    layer, source, unit, better = {**SPAN_METRICS, **COUNTER_METRICS}[name]
    # a later PR may append cells to the list, and metrics behind it
    listed = entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "stmt_geomean_ms"}
    assert CELL in listed
    assert set(listed) <= {w["name"] for w in bench["workloads"]}
    assert callable(_reader(name).compute)
