"""One run of one cell: set-up, warm-up, the measured window, the traced
captures, the read-back, the comparison with the reference, and the last
line; all of it under the run's own time limit (``budget.py``).

The phases print one JSON line each as they end; the LAST line of the
standard output is the result object the driver reads.  Everything here
is driven by the cell's files (``spec.Cell``): no cell, statement or
metric is known by name.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import sys
import time
import traceback

from . import budget as budgetmod
from . import spec as specmod
from . import stats
from . import traffic as trafficmod
from . import writes as writesmod
from .compare import RTOL, rows_match
from .reference import ReferenceChild, read_back_columns

#: a template that still compiles after this many warm-up runs fails the run
MAX_WARMUP_RUNS = 4
#: a capture larger than this is not reduced (its metrics are left out)
MAX_XPLANE_BYTES = 400 << 20
#: exit code of a rehearsal that passed off the accelerator
EXIT_REHEARSED = 3


def _digest(parts) -> str:
    return hashlib.sha1("|".join(parts).encode()).hexdigest()[:16]


def emit(rec: dict):
    print(json.dumps(rec, default=str), flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the machine's chips.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=float, default=None, metavar="SCALE",
                    help="rehearsal: the data scale is overridden, any "
                    "platform and device count is taken, and the last line "
                    "says correct: false whatever happened")
    return ap.parse_args(argv)


class Run:
    def __init__(self, args, t_start: float):
        self.args = args
        self.t_start = t_start          # time.monotonic() at process start
        self.cell = specmod.Cell(args.workload)
        self.rehearsal = args.rehearse is not None
        self.scale = args.rehearse if self.rehearsal \
            else self.cell.config["dataset"]["scale"]
        self.round = trafficmod.schedule(
            self.cell.traffic, self.cell.statements, args.seed)
        self.templates = list(dict.fromkeys(it.template for it in self.round))
        #: template -> its distinct (template, parameter set)s, in order
        self.items = {t: list(dict.fromkeys(
            it for it in self.round if it.template == t))
            for t in self.templates}
        self.replays = self.cell.writes()  # the reference replays the log
        self.next_set = dict.fromkeys(self.templates, 0)
        self.read_back: dict[str, dict] = {}
        self.phases: dict[str, float] = {}
        self.notes: list[str] = []
        self.log: list[dict] = []       # every workload statement, in order
        self.answers: list = []         # parallel to self.log
        self.system = None
        self.adapter = None
        self.reference = None
        self.watch = None
        self.budget = budgetmod.Budget(
            t_start, os.path.join(
                specmod.SCRATCH_DIR, "started", self.cell.name
                + (".rehearsal" if self.rehearsal else "")),
            on_expire=self.stop_reference, detail=self.so_far)

    # -- plumbing -----------------------------------------------------------
    def enter(self, name: str) -> float:
        """The phase in flight, for the line of a run that ends itself."""
        self.budget.phase = name
        return time.monotonic()

    def stop_reference(self):
        if self.reference is not None:
            self.reference.stop()

    def so_far(self) -> dict:
        """What a run that ends itself adds to its line: the phases that
        ended, and JAX's own compile seconds of the whole process (the
        ``warmup.<template>`` lines hold them per template)."""
        if self.watch is None:
            return {"phases": self.phases}
        return {"phases": self.phases, "compile_s": self.watch.seconds(),
                "compile_cache": self.watch.cache_events()}

    def phase(self, name: str, t0: float, **more):
        self.phases[name] = time.monotonic() - t0
        emit({"phase": name, "seconds": self.phases[name], **more})

    def note(self, text: str):
        self.notes.append(text)
        emit({"note": text})

    @staticmethod
    def _span(on: bool, kind: str, label: str):
        if not on:
            return contextlib.nullcontext()
        from .tracing import span  # JAX's profiler: the traced run only

        return span(kind, label)

    def _write(self, item, phase: str, spans: bool) -> dict:
        """Send the template's next set, transaction by transaction; the
        latency is the client's, from each ``begin`` to its ``commit``'s
        return (rendering the rows as SQL text is not the system's time).
        A transaction that raises is rolled back and ends the set."""
        k = self.next_set[item.template]
        self.next_set[item.template] = k + 1
        rec = {"phase": phase, "template": item.template,
               "key": writesmod.set_key(item.template, k), "sql": None,
               "k": k, "acks": [], "tx_latency_s": [], "error": None}
        for sqls in writesmod.transactions(
                self.cell.statements[item.template], self.dataset,
                self.types, self.scale, self.args.seed, k):
            t0 = time.perf_counter()
            try:
                with self._span(spans, "execute", item.template):
                    for sql in ["begin"] + sqls + ["commit"]:
                        self.system.execute(sql)
            except Exception as e:  # noqa: BLE001 — counted as a failed set
                rec["error"] = f"{type(e).__name__}: {e}"[:400]
                try:
                    self.system.execute("rollback")
                except Exception:  # noqa: BLE001 — nothing was open
                    pass
            rec["tx_latency_s"].append(time.perf_counter() - t0)
            rec["acks"].append(rec["error"] is None)
            if rec["error"]:
                break
        rec["latency_s"] = sum(rec["tx_latency_s"])
        self.log.append(rec)
        self.answers.append(None)
        return rec

    def _statement(self, item, phase: str, spans: bool = False) -> dict:
        """Execute one workload statement and fetch its rows; the latency is
        the client's (``perf_counter`` around both)."""
        if item.sql is None:
            return self._write(item, phase, spans)
        rec = {"phase": phase, "template": item.template, "key": item.key,
               "sql": item.sql, "error": None}
        ans = None
        t0 = time.perf_counter()
        try:
            with self._span(spans, "execute", item.template):
                res = self.system.execute(item.sql)
            with self._span(spans, "fetch", item.template):
                ans = self.system.fetch(res)
        except Exception as e:  # noqa: BLE001 — counted as a failed statement
            rec["error"] = f"{type(e).__name__}: {e}"[:400]
        rec["latency_s"] = time.perf_counter() - t0
        self.log.append(rec)
        self.answers.append(ans)
        return rec

    # -- set-up -------------------------------------------------------------
    def start_reference(self):
        self.enter("reference")
        items, written = [], {}
        for its in self.items.values():
            for it in its:
                st = self.cell.statements[it.template]
                if it.sql is None:
                    written[it.template] = st
                    continue
                ref = st["reference"]
                items.append({"key": it.key, "sql": it.sql,
                              "params": it.params,
                              "sqlite": bool(ref.get("sqlite")),
                              "exact": ref.get("exact")})
        job = {"bench_dir": self.cell.bench_dir,
               "dataset": self.cell.config["dataset"]["generator"],
               "scale": self.scale, "seed": self.args.seed,
               "reads": self.cell.reads(), "items": items}
        if self.replays:
            job["writes"] = written
            job["read_back"] = self.cell.read_back()
        self.reference = ReferenceChild(job)
        self.reference.start()

    def check_device(self) -> dict:
        t0 = self.enter("device")
        from . import adapter  # the first touch of JAX, after the child

        from .compile_watch import CompileWatch

        self.adapter = adapter
        self.watch = CompileWatch()
        dev = adapter.device_info()
        self.phase("device", t0, **dev,
                   compile_cache_dir=adapter.compile_cache_dir())
        if not self.rehearsal:
            if dev["platform"] != "tpu":
                raise SystemExit(f"the platform is {dev['platform']!r}, not "
                                 "tpu: the benchmark measures on the chip "
                                 "only (--rehearse SCALE rehearses)")
            if dev["count"] != self.cell.chips:
                raise SystemExit(f"the cell asks for {self.cell.chips} "
                                 f"chip(s), JAX finds {dev['count']}")
        return dev

    def boot_and_load(self):
        cfg = self.cell.config
        t0 = self.enter("boot")
        self.system = self.adapter.System(os.path.join(
            specmod.SCRATCH_DIR, "db", self.cell.name))
        self.system.apply(cfg.get("system_settings", []))
        self.phase("boot", t0, cost_constants=self.system.cost_constants())

        t_load = self.enter("generate")
        self.dataset = dataset = specmod.load_module(
            "datasets", cfg["dataset"]["generator"])
        tables, self.types = dataset.generate(self.scale, self.args.seed)
        self.phase("generate", t_load)
        self.enter("load")
        self.read_back_columns = {
            t: read_back_columns(dataset, tables[t], self.types, t)
            for t in self.cell.read_back()}
        rows, table_load_s, analyze_s = {}, {}, {}
        for name in self.cell.tables():
            t0 = time.monotonic()
            self.system.load_table(name, tables[name], self.types,
                                   dataset.PRIMARY_KEYS[name])
            table_load_s[name] = time.monotonic() - t0
            rows[name] = len(next(iter(tables[name].values())))
        for name in self.cell.tables():
            t0 = time.monotonic()
            self.system.analyze(name)
            analyze_s[name] = time.monotonic() - t0
        del tables
        self.phase("load", t_load, scale=self.scale, rows=rows,
                   table_load_s=table_load_s, analyze_s=analyze_s)
        t0 = time.monotonic()
        self.layouts = {t: self.system.relation_layout(t)
                        for t in self.cell.tables()}
        self.system.apply(cfg.get("session_settings", []))
        self.phase("resident", t0, capacity={
            t: lay["capacity"] for t, lay in self.layouts.items()})

    def warm_up(self):
        """Every (template, parameter set) of the round until a run of it
        compiles nothing; the plans each template added to the plan cache
        are its fingerprint."""
        t_all = time.monotonic()
        self.fingerprints = {}
        seen = set(self.system.plan_cache())
        last_ts = max((r["ts"] for r in self.system.monitored_plans()),
                      default=0.0)
        for template in self.templates:
            self.enter("warmup." + template)
            runs = 0
            mark = (self.watch.seconds(), self.watch.cache_events())
            for item in self.items[template]:
                # a write takes a new set at each execution: two at most,
                # and one that still compiles is said, not refused (its
                # compiles then show in the window's own count)
                tries = MAX_WARMUP_RUNS if item.sql is not None else 2
                for _attempt in range(tries):
                    before = self.watch.count()
                    rec = self._statement(item, "warmup")
                    runs += 1
                    if rec["error"]:
                        raise RuntimeError(
                            f"warm-up of {rec['key']}: {rec['error']}")
                    rec["compile_events"] = self.watch.count() - before
                    if rec["compile_events"] == 0:
                        break
                else:
                    if item.sql is not None:
                        raise RuntimeError(f"{item.key} still compiles "
                                           f"after {tries} runs")
                    self.note(f"{template} still compiles after {tries} "
                              f"sets ({rec['compile_events']} events)")
            since = [{k: v - was[k] for k, v in now.items()}
                     for was, now in zip(mark, (self.watch.seconds(),
                                                self.watch.cache_events()))]
            new = sorted(h for h in self.system.plan_cache() if h not in seen)
            seen |= set(new)
            monitored = [r for r in self.system.monitored_plans()
                         if r["ts"] > last_ts]
            last_ts = max([last_ts] + [r["ts"] for r in monitored])
            self.fingerprints[template] = {
                # gv$plan_cache.plan_hash of the plans the template added
                # (serial plans; literals are part of a plan)
                "plan_hashes": new[0] if len(new) == 1 else _digest(new),
                "plans": len(new),
                # gv$sql_plan_monitor: capacity-insensitive, PX plans too
                "logical": _digest(sorted({r["logical_hash"]
                                           for r in monitored})),
                "paths": sorted({r["path"] for r in monitored})}
            emit({"phase": "warmup." + template, "runs": runs,
                  "plan_fingerprint": self.fingerprints[template],
                  # JAX's own seconds: backend compiles (a persistent-cache
                  # hit's retrieval is inside them), tracing, lowering
                  "compile_s": since[0], "compile_cache": since[1]})
        self.phase("warmup", t_all, compile_cache=self.watch.cache_events())

    # -- the window ------------------------------------------------------------
    def window(self):
        want_path = self.cell.config.get("required_path")
        self.traces_before = self.system.plan_cache()
        self.counters_before = self.system.counters()
        events0 = self.watch.count()
        n, k = len(self.round), 0
        seconds = self.args.seconds
        self.enter("window")
        self.budget.need(seconds)   # a window that cannot fit is not spent
        self.setup_seconds = time.monotonic() - self.t_start
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            rec = self._statement(self.round[k % n], "window")
            rec["start_s"] = time.perf_counter() - t0 - rec["latency_s"]
            if want_path:
                rec["path"] = self.system.last_path()
            k += 1
        self.window_s = time.perf_counter() - t0
        self.compile_events_in_window = self.watch.count() - events0
        self.traces_after = self.system.plan_cache()
        self.counters_after = self.system.counters()
        emit({"phase": "window", "seconds": self.window_s, "statements": k,
              "setup_seconds": self.setup_seconds})

    # -- the traced captures ------------------------------------------------------
    def traced_captures(self) -> list[dict]:
        from . import tracing, xplane

        captures = []
        n_exec = int(self.cell.traffic.get("trace_executions", 1))
        for template in self.templates:
            items = self.items[template]
            directory = os.path.join(specmod.SCRATCH_DIR, "trace",
                                     self.cell.name, template)
            t0 = self.enter("trace." + template)
            with tracing.capture(directory):
                for k in range(n_exec):
                    self._statement(items[k % len(items)], "trace", spans=True)
            cap = {"template": template, "executions": n_exec,
                   "reduced": None}
            files = tracing.xplane_files(directory)
            size = sum(os.path.getsize(f) for f in files)
            if len(files) != 1:
                self.note(f"capture of {template}: {len(files)} xplane files")
            elif size > MAX_XPLANE_BYTES:
                self.note(f"capture of {template} is {size} bytes: too "
                          "large to reduce, its metrics are left out")
            else:
                try:
                    cap["reduced"] = xplane.reduce_capture(
                        xplane.load(files[0]), tracing.SPAN_PREFIX)
                except Exception as e:  # noqa: BLE001 — metrics left out
                    self.note(f"capture of {template} not reduced: "
                              f"{type(e).__name__}: {e}")
            captures.append(cap)
            red = cap["reduced"] or {}
            self.phase("trace." + template, t0, xplane_bytes=size,
                       window_s=red.get("window_s"), busy_s=red.get("busy_s"),
                       top_ops=(red.get("ops") or [])[:12],
                       gaps=(red.get("gaps") or [])[:6])
        return captures

    # -- after the window -----------------------------------------------------------
    def read_back_tables(self):
        """A configuration with ``guarantees.read_back`` (and a mix that
        writes): after the last statement, each table's ``count(*)``, the
        sum of its key and the sum of one decimal, through the served
        path; ``compare`` holds them to the replayed reference."""
        if not self.read_back_columns:
            return
        t0 = self.enter("read_back")
        for table, (key, dec) in self.read_back_columns.items():
            rec = {"table": table, "got": None, "error": None}
            try:
                ans = self.system.fetch(self.system.execute(
                    f"select count(*) as n, sum({key}) as k, sum({dec}) as d "
                    f"from {table}"))
                rec["got"] = [int(ans.arrays[c][0]) for c in ("n", "k", "d")]
            except Exception as e:  # noqa: BLE001 — counted as a failure
                rec["error"] = f"{type(e).__name__}: {e}"[:400]
            self.read_back[table] = rec
        self.phase("read_back", t0, tables={
            t: r["got"] or r["error"] for t, r in self.read_back.items()})

    def attach_audit(self):
        """Each logged statement gets its ``gv$sql_audit`` row, aligned from
        the newest backwards (the ring may have dropped the oldest)."""
        t0 = self.enter("audit")
        log = [rec for rec in self.log if rec["sql"] is not None]
        mine = {rec["sql"][:200] for rec in log}
        rows = [r for r in self.system.audit_rows() if r["sql"] in mine]
        k = min(len(rows), len(log))
        pairs = list(zip(log[len(log) - k:], rows[len(rows) - k:]))
        if any(rec["sql"][:200] != row["sql"] for rec, row in pairs):
            self.note("gv$sql_audit does not line up with the statements "
                      "sent: the audit-based metrics are left out")
            pairs = []
        for rec, row in pairs:
            rec["audit"] = {c: v for c, v in row.items() if c != "sql"}
        self.phase("audit", t0, rows=len(rows), attached=len(pairs))

    def replay_log(self) -> list[dict]:
        """What the reference replays: every write of the run, and the
        reads that are compared, in the order they were sent."""
        return [
            {"at": at, "template": rec["template"], "k": rec["k"],
             "acks": rec["acks"]} if rec["sql"] is None
            else {"at": at, "key": rec["key"]}
            for at, rec in enumerate(self.log)
            if rec["sql"] is None or rec["phase"] != "warmup"]

    def compare(self) -> tuple[int, int]:
        """-> (attempted, failed) over the window's and the captures'
        statements and the read-back; each record gets ``correct``."""
        t0 = self.enter("compare")
        deadline_s = self.t_start + self.cell.config.get(
            "reference_deadline_s", 900.0)
        ref = self.reference.replay(self.replay_log(), deadline_s) \
            if self.replays else self.reference.join(deadline_s)
        want_path = self.cell.config.get("required_path")
        exact_mods = {}
        attempted = failed = 0
        examples = []
        self.checks = {"raised": 0, "sqlite_differ": 0, "sqlite_rel_gap": 0.0,
                       "exact_differ": 0, "off_path": 0,
                       "read_back_differ": 0}

        def fail(check: str, what: str, why: str):
            nonlocal failed
            failed += 1
            self.checks[check] += 1
            if len(examples) < 5:
                examples.append(f"{what}: {why}"[:300])

        for at, (rec, ans) in enumerate(zip(self.log, self.answers)):
            if rec["phase"] == "warmup":
                continue
            attempted += 1
            st = self.cell.statements[rec["template"]]
            where = at if self.replays else rec["key"]
            check, why = "raised", rec["error"]
            if rec["sql"] is not None:
                if why is None and st["reference"].get("sqlite"):
                    ok, why, gap = rows_match(
                        ans.rows, ref["sqlite"][where],
                        ordered=bool(st["ordered"]))
                    self.checks["sqlite_rel_gap"] = max(
                        self.checks["sqlite_rel_gap"], gap)
                    check, why = "sqlite_differ", \
                        None if ok else "vs sqlite: " + why
                name = st["reference"].get("exact")
                if why is None and name:
                    if name not in exact_mods:
                        exact_mods[name] = specmod.load_module(
                            "references", name)
                    got = exact_mods[name].extract(ans.names, ans.arrays)
                    if got != ref["exact"][where]:
                        check = "exact_differ"
                        why = f"vs exact: {got!r} != {ref['exact'][where]!r}"
                if why is None and want_path and rec["phase"] == "window" \
                        and rec.get("path") != want_path:
                    check = "off_path"
                    why = f"path {rec.get('path')!r}, not {want_path!r}"
            rec["correct"] = why is None
            if why is not None:
                fail(check, rec["key"], why)
        for table, rec in self.read_back.items():
            attempted += 1
            want = ref["read_back"][table]
            rec["want"] = want
            if rec["error"]:
                fail("raised", "read-back of " + table, rec["error"])
            elif rec["got"] != want:
                fail("read_back_differ", "read-back of " + table,
                     f"count, key sum, decimal sum {rec['got']} != {want}")
        self.phase("compare", t0, attempted=attempted, failed=failed,
                   examples=examples, reference_seconds=ref.get("seconds"))
        return attempted, failed

    def compared(self) -> dict:
        """Each number compared beside its limit, for the result's line
        and the standard error's last lines."""
        return {name: {"value": value,
                       "limit": RTOL if name == "sqlite_rel_gap" else 0}
                for name, value in self.checks.items()}

    # -- the record the metric readers get ---------------------------------------------
    def record(self, device: dict, captures) -> dict:
        return {
            "cell": self.cell.entry, "config": self.cell.config,
            "traffic": self.cell.traffic, "statements": self.cell.statements,
            "seed": self.args.seed, "seconds_asked": self.args.seconds,
            "rehearsal": self.rehearsal, "scale": self.scale,
            "device": device, "setup_seconds": self.setup_seconds,
            "window_s": self.window_s, "phases": self.phases,
            "templates": self.templates,
            "warmup": [r for r in self.log if r["phase"] == "warmup"],
            "window": [r for r in self.log if r["phase"] == "window"],
            "traced": [r for r in self.log if r["phase"] == "trace"],
            "plan_traces_before": self.traces_before,
            "plan_traces_after": self.traces_after,
            "counters_before": self.counters_before,
            "counters_after": self.counters_after,
            "compile_events_in_window": self.compile_events_in_window,
            "fingerprints": self.fingerprints,
            "layouts": self.layouts, "read_back": self.read_back,
            "captures": captures, "notes": self.notes,
        }


def compute_metrics(cell, group: str, directory: str, record: dict) -> dict:
    """{name: {"value", "unit"}} of the cell's metrics of one group; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in cell.metrics(group):
        try:
            value = specmod.load_module(directory, m["name"]).compute(record)
        except Exception as e:  # noqa: BLE001 — one reader does not end the run
            emit({"note": f"metric {m['name']}: {type(e).__name__}: {e}"})
            value = None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(captures) -> dict | None:
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for cap in captures:
        red = cap["reduced"]
        if not red:
            continue
        for name, sec, _count in red["ops"]:
            key = f"{cap['template']}:{name}"
            ops[key] = ops.get(key, 0.0) + sec
        for name, sec in red["gaps"]:
            gaps[name] = gaps.get(name, 0.0) + sec
    if not ops:
        return None

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])][:10]

    return {"device_ops": top(ops), "idle_gaps": top(gaps)}


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    run = Run(args, t_start)
    emit({"phase": "cell", "workload": run.cell.name, "seed": args.seed,
          "seconds": args.seconds, "trace": args.trace, "scale": run.scale,
          "rehearsal": run.rehearsal, "templates": run.templates,
          "statements_in_round": len(run.round)})
    try:
        run.start_reference()
        device = run.check_device()
        run.boot_and_load()
        run.warm_up()
        run.window()
        captures = run.traced_captures() if args.trace else []
        run.read_back_tables()
        run.attach_audit()
        attempted, failed = run.compare()
        device["memory_peak_bytes"] = run.adapter.memory_peak_bytes()
        record = run.record(device, captures)
        if args.trace:
            metrics = compute_metrics(run.cell, "per_layer", "layer_metrics",
                                      record)
            reduced = [c["reduced"] for c in captures if c["reduced"]]
            if reduced:
                device["busy_s"] = sum(r["busy_s"] for r in reduced)
                device["window_s"] = sum(r["window_s"] for r in reduced)
        else:
            metrics = compute_metrics(run.cell, "end_to_end", "end_to_end",
                                      record)
        summary = {
            "phase": "summary", "workload": run.cell.name, "seed": args.seed,
            "setup_phases": run.phases, "fingerprints": run.fingerprints,
            "per_template": {
                t: {"n": len(v), "median_s": stats.median_low(v),
                    "min_s": min(v), "max_s": max(v)}
                for t, v in stats.by_template(
                    [s for s in record["window"]
                     if s["error"] is None]).items()},
            "compile_events_in_window": run.compile_events_in_window,
            "notes": run.notes}
        emit(summary)
        _save(run, record, metrics)
    except SystemExit as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    except Exception:  # noqa: BLE001 — no result line: the run did not happen
        traceback.print_exc()
        return 1
    finally:
        run.stop_reference()
        if run.system is not None:
            try:
                run.system.close()
            except Exception:  # noqa: BLE001 — closing must not hide the result
                traceback.print_exc()
    result = {"correct": failed == 0 and attempted > 0 and not run.rehearsal,
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if run.rehearsal:
        result["rehearsal"] = True
    if args.trace:
        bd = breakdown(captures)
        if bd:
            result["breakdown"] = bd
    result["compared"] = run.compared()     # the last key, by contract
    run.budget.close()      # from here on the run has its result
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    if run.rehearsal and device["platform"] != "tpu":
        return EXIT_REHEARSED if failed == 0 and attempted > 0 else 1
    return 0


def _save(run: Run, record: dict, metrics: dict):
    """The whole record, for whoever wants more than the last line."""
    directory = os.path.join(specmod.SCRATCH_DIR, "runs")
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(
        directory, f"{run.cell.name}.seed{run.args.seed}"
        f".trace{run.args.trace}.json")
    slim = dict(record)
    for part in ("warmup", "window", "traced"):
        slim[part] = [{k: v for k, v in r.items() if k != "sql"}
                      for r in record[part]]
    for part in ("plan_traces_before", "plan_traces_after"):
        slim[part] = {h: v["xla_trace_count"] for h, v in record[part].items()}
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"record": slim, "metrics": metrics}, f, default=str)
