"""The four-chip partitioned deployment (``tpch_sf1_part4``): its three
groups of entries in BENCHMARK.json, its configuration's DDL, and one
rehearsal of its cell end to end on four virtual CPU devices."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import spec

CELL = "tpch_sf1_part4.mix"
B = spec.read_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
OWN = ("px_collective_ms", "px_shard_ms", "px_program_ms",
       "px_unshard_merge_ms", "px_partition_build_s",
       "px_partition_builds_in_window", "pwj_join_share")


def test_the_cell_and_its_entries():
    cell = spec.Cell(CELL)
    assert cell.chips == 4 and cell.entry["traffic"] == "mix"
    assert cell.config["required_path"] == "px"
    assert cell.config["session_settings"] == ["set px_dop = 4"]
    assert list(cell.statements) == ["tpch_q1", "tpch_q3", "tpch_q6"]
    entry = next(c for c in B["configs"] if c["name"] == "tpch_sf1_part4")
    assert entry["source"] == cell.config["source"]
    assert entry["reduced"] == sorted(cell.config["reduced"], reverse=True)
    assert len(entry["source"]) <= 200
    mine = {m["name"]: m for m in B["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == set(OWN)
    assert {m["layer"] for m in mine.values()} == {"PX"}
    assert mine["px_partition_build_s"]["moves"] == "setup_s"
    # the metrics whose lists name the one-chip cells do not read this one
    listed = {m["name"] for m in cell.metrics("per_layer")}
    assert listed >= set(OWN) and "merge_probe_share" not in listed
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "stmt_geomean_ms", "setup_s"}


def test_the_layout_is_ddl_for_the_tables_the_cell_reads():
    cell = spec.Cell(CELL)
    ddl = [s for s in cell.config["system_settings"]
           if s.startswith("create")]
    assert ddl[0] == "create tablegroup tpch_tg_lineitem_order_group"
    created = [s.split()[2] for s in ddl[1:]]
    assert sorted(created) == sorted(cell.tables())
    dataset = spec.load_module("datasets", "tpch")
    tables, _types = dataset.generate(0.001, 1)
    for sql in ddl[1:]:
        name = sql.split()[2]
        assert "partitions 4" in sql and "partition by key" in sql
        for col in tables[name]:            # every loaded column, typed
            assert f"{col} " in sql
        pk = ", ".join(dataset.PRIMARY_KEYS[name])
        assert f"primary key ({pk})" in sql
    assert sum("tablegroup = tpch_tg_lineitem_order_group" in s
               for s in ddl) == 2


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("part4")
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    shutil.copy(os.path.join(spec.REPO_DIR, "BENCHMARK.json"), root)
    return root


def test_the_cell_rehearses_end_to_end(copy):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_DIR,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2900000007", "--seconds", "2", "--trace", "1", "--rehearse",
         "0.05"], cwd=copy, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 3, p.stderr[-3000:]   # rehearsed, no accelerator
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["failed"] == 0
    assert last["attempted"] >= 6 and last["device"]["count"] == 4
    assert last["compared"]["off_path"]["value"] == 0
    metrics = last["metrics"]
    assert set(OWN) <= set(metrics), sorted(set(OWN) - set(metrics))
    assert metrics["pwj_join_share"]["value"] == 50.0
    assert metrics["px_partition_builds_in_window"]["value"] == 0
    assert metrics["px_partition_build_s"]["value"] > 0
    assert 0 < metrics["px_shard_ms"]["value"] < 1
    assert metrics["compiles_in_window"]["value"] == 0
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith('{"phase": "warmup.')]
    assert [x["plan_fingerprint"]["paths"] for x in lines] == [["px"]] * 3
