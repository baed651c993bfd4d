"""Where the benchmark's files are, and how a cell is put together from them.

``BENCHMARK.json`` names cells, configurations and metrics; everything that
belongs to one of them is a file of its own under ``benchmark/``, found by
that name.  Nothing here knows a cell, a statement or a metric by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
SCRATCH_DIR = os.path.join(BENCH_DIR, ".scratch")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """A file of the benchmark is missing or does not say what it must."""


def read_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no such file: {os.path.relpath(path, REPO_DIR)}")
    except json.JSONDecodeError as e:
        raise SpecError(f"{os.path.relpath(path, REPO_DIR)}: {e}")


def load_module(kind_dir: str, name: str, bench_dir: str = BENCH_DIR):
    """The module ``<bench_dir>/<kind_dir>/<name>.py``, loaded by path (the
    directories are not packages, so that a file added is a file found)."""
    if not NAME_RE.match(name):
        raise SpecError(f"not a name: {name!r}")
    path = os.path.join(bench_dir, kind_dir, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no such file: {kind_dir}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind_dir}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


class Cell:
    """One entry of ``workloads`` with the files it names, read and checked."""

    def __init__(self, name: str, bench_dir: str = BENCH_DIR):
        self.bench_dir = bench_dir
        self.benchmark = read_json(
            os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in cells:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                            f"(there are: {', '.join(sorted(cells))})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.benchmark["configs"]}
        if self.entry["config"] not in configs:
            raise SpecError(f"workload {name!r} names no configuration of "
                            "BENCHMARK.json")
        self.config = read_json(os.path.join(
            os.path.dirname(bench_dir), configs[self.entry["config"]]["file"]))
        self.traffic = read_json(os.path.join(
            bench_dir, "traffic", self.entry["traffic"] + ".json"))
        self.statements = {}
        for t in self.traffic["templates"]:
            s = t["statement"]
            if s not in self.statements:
                self.statements[s] = read_json(os.path.join(
                    bench_dir, "statements", s + ".json"))
        self._check()

    def _check(self):
        if self.traffic.get("loop") != "closed" or \
                int(self.traffic.get("clients", 1)) != 1:
            raise SpecError("the generator drives one closed loop of one "
                            "client; other loops need a benchmark PR")
        if self.traffic.get("order", "round_robin") != "round_robin":
            raise SpecError("order must be round_robin")
        if int(self.config["chips"]) != self.chips:
            raise SpecError("the cell and its configuration disagree on chips")
        rules = {t["statement"]: t.get("params", "validation")
                 for t in self.traffic["templates"]}
        for s, st in self.statements.items():
            write = "writes" in st
            for key in (("writes", "transaction", "rows", "batch") if write
                        else ("sql", "parameters", "reads", "ordered",
                              "reference")):
                if key not in st:
                    raise SpecError(f"statements/{s}.json lacks {key!r}")
            if write != (rules[s] == "sequence"):
                raise SpecError(f"{s}: a write statement takes the rule "
                                '"sequence", and no other statement does')
        if not set(self.read_back()) <= set(self.tables()):
            raise SpecError("read_back names a table that no statement of "
                            "the cell names")

    # -- what the cell needs --------------------------------------------
    def tables(self) -> list[str]:
        """The tables the cell's statements name, in first-use order."""
        out = []
        for st in self.statements.values():
            for t in list(st.get("reads", ())) + list(st.get("writes", ())):
                if t not in out:
                    out.append(t)
        return out

    def writes(self) -> bool:
        """Whether some statement of the cell writes: the reference then
        replays the run's log instead of answering beside the load."""
        return any("writes" in st for st in self.statements.values())

    def read_back(self) -> list[str]:
        """The tables a mix that writes is read back from at the end (the
        configuration's ``guarantees.read_back``)."""
        if not self.writes():
            return []
        return list(self.config.get("guarantees", {}).get("read_back", []))

    def reads(self) -> dict[str, list[str]]:
        """table -> columns some statement of the cell reads."""
        out: dict[str, list[str]] = {}
        for st in self.statements.values():
            for t, cols in st.get("reads", {}).items():
                have = out.setdefault(t, [])
                have += [c for c in cols if c not in have]
        return out

    def metrics(self, group: str) -> list[dict]:
        """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
        return [m for m in self.benchmark[group]
                if metric_applies(m, self.name)]
