"""The plain reference, computed in a child process that never needs the chip.

The child regenerates the data from the seed, loads SQLite with the
columns the cell's statements read, and answers the run's statements:
through SQLite where the statement file says so, and through the
statement's exact reference module where it names one.  It is started
before the parent touches JAX.

- A mix that only reads: every distinct statement is answered once, on
  the seed's data, beside the load (``join``).
- A mix that writes: the child keeps its tables and waits.  After the
  last statement the parent sends the ordered log of what it sent
  (``replay``); the child applies the acknowledged transactions in that
  order, from the dataset module's own row sets (never from the SQL
  text), and answers every read AT ITS POSITION.  Reads with the same key
  and no write between share one answer.  Every replay starts from the
  seed's data again.
"""

from __future__ import annotations

import multiprocessing
import os
import queue
import time
import traceback

import numpy as np

from . import spec as specmod
from . import writes


def read_back_columns(dataset, columns, types: dict, table: str):
    """What a ``read_back`` of ``table`` sums beside ``count(*)``: the
    first column of its primary key and its first decimal column."""
    return (dataset.PRIMARY_KEYS[table][0],
            next(c for c in columns if types.get(c, ("",))[0] == "decimal"))


class _Arrays:
    """The written tables as arrays.  Writes queue up and are applied when
    a read needs the table, consecutive inserts as one concatenation and
    consecutive deletes on one column as one membership test, so a replay
    costs a few passes over a table per group of writes, not per row."""

    def __init__(self, base: dict, keep: dict[str, list[str]]):
        self.base = base
        self.keep = keep        # written table -> the columns anyone reads
        self.reset()

    def reset(self):
        self.current = {t: {c: self.base[t][c] for c in cols}
                        for t, cols in self.keep.items()}
        self.pending = {t: [] for t in self.keep}

    def insert(self, table: str, rows: dict):
        self.pending[table].append(
            ("insert", None, {c: rows[c] for c in self.keep[table]}))

    def delete(self, table: str, column: str, values):
        self.pending[table].append(("delete", column, np.asarray(values)))

    def table(self, name: str) -> dict:
        ops, self.pending[name] = self.pending[name], []
        cur = self.current[name]
        i = 0
        while i < len(ops):
            j = i
            while j < len(ops) and ops[j][:2] == ops[i][:2]:
                j += 1
            kind, column, _ = ops[i]
            if kind == "insert":
                cur = {c: np.concatenate([cur[c]] + [op[2][c]
                                                     for op in ops[i:j]])
                       for c in cur}
            else:
                stay = ~np.isin(cur[column], np.concatenate(
                    [op[2] for op in ops[i:j]]))
                cur = {c: v[stay] for c, v in cur.items()}
            i = j
        self.current[name] = cur
        return cur

    def tables(self) -> dict:
        return {**self.base, **{t: self.table(t) for t in self.keep}}


class _Reference:
    def __init__(self, job: dict):
        from . import sqlite_oracle

        self.job = job
        self.oracle = sqlite_oracle
        self.items = {it["key"]: it for it in job["items"]}
        t0 = time.monotonic()
        self.dataset = specmod.load_module("datasets", job["dataset"],
                                           job["bench_dir"])
        self.tables, self.types = self.dataset.generate(job["scale"],
                                                        job["seed"])
        self.seconds = {"generate": time.monotonic() - t0}
        self.exact_mods: dict = {}
        self.conn = None
        written = job.get("writes", {})
        deletes: dict[str, list[str]] = {}      # table -> its where columns
        for st in written.values():
            for op in st["transaction"]:
                if "delete" in op:
                    deletes.setdefault(op["delete"], []).append(op["where"])
        self.sql_columns = {
            t: list(dict.fromkeys(list(cols) + deletes.get(t, [])))
            for t, cols in job["reads"].items()}
        if any(it["sqlite"] for it in job["items"]):
            t0 = time.monotonic()
            self.conn = self.oracle.load_sqlite(
                {t: {c: self.tables[t][c] for c in cols}
                 for t, cols in self.sql_columns.items()}, self.types)
            self.seconds["sqlite_load"] = time.monotonic() - t0
        self.read_back = {
            t: read_back_columns(self.dataset, self.tables[t], self.types, t)
            for t in job.get("read_back", [])}
        keep = {}
        for st in written.values():
            for t in st["writes"]:
                keep[t] = list(dict.fromkeys(
                    job["reads"].get(t, []) + deletes.get(t, [])
                    + list(self.read_back.get(t, ()))))
        self.arrays = _Arrays(self.tables, keep)

    # -- one statement ------------------------------------------------------
    def _answer(self, it: dict, tables: dict, out: dict, at):
        t0 = time.monotonic()
        if it["sqlite"]:
            out["sqlite"][at] = self.oracle.run_oracle(self.conn, it["sql"])
        t1 = time.monotonic()
        if it["exact"]:
            name = it["exact"]
            if name not in self.exact_mods:
                self.exact_mods[name] = specmod.load_module(
                    "references", name, self.job["bench_dir"])
            out["exact"][at] = self.exact_mods[name].answer(
                tables, it["params"])
        self.seconds["sqlite_queries"] += t1 - t0
        self.seconds["exact"] += time.monotonic() - t1

    def answers(self) -> dict:
        """Every distinct statement once, by its key."""
        out = {"sqlite": {}, "exact": {}}
        self.seconds.update(sqlite_queries=0.0, exact=0.0)
        for it in self.job["items"]:
            self._answer(it, self.tables, out, it["key"])
        if self.conn is not None:
            self.conn.close()
        out["seconds"] = self.seconds
        return out

    # -- a mix that writes -----------------------------------------------------
    def _apply(self, statement: dict, binding: dict):
        for op in statement["transaction"]:
            rows = binding[op["rows"]]
            if "insert" in op:
                table = op["insert"]
                self.arrays.insert(table, rows)
                if self.conn is not None and table in self.sql_columns:
                    self.oracle.insert_rows(
                        self.conn, table,
                        {c: rows[c] for c in self.sql_columns[table]},
                        self.types)
            else:
                table = op["delete"]
                self.arrays.delete(table, op["where"], rows[op["column"]])
                if self.conn is not None and table in self.sql_columns:
                    self.oracle.delete_rows(self.conn, table, op["where"],
                                            rows[op["column"]])

    def replay(self, log: list[dict]) -> dict:
        """``log``: the statements in the order they were sent.  A write is
        ``{"at", "template", "k", "acks"}`` (one flag per transaction of the
        set that was sent: acknowledged, or raised and rolled back), a read
        ``{"at", "key"}``.  -> the answers by ``at``, and each ``read_back``
        table's ``[count, sum of the key, sum of the decimal]`` at the end."""
        t0 = time.monotonic()
        out = {"sqlite": {}, "exact": {}}
        self.seconds.update(sqlite_queries=0.0, exact=0.0)
        self.arrays.reset()
        epoch, shared = 0, {}       # (key, epoch) -> the position answered
        try:
            for entry in log:
                if "acks" in entry:
                    st = self.job["writes"][entry["template"]]
                    sets = writes.bindings(st, self.dataset,
                                           self.job["scale"],
                                           self.job["seed"], entry["k"])
                    for binding, ack in zip(sets, entry["acks"]):
                        if ack:
                            self._apply(st, binding)
                            epoch += 1
                    continue
                first = shared.setdefault((entry["key"], epoch), entry["at"])
                if first == entry["at"]:
                    self._answer(self.items[entry["key"]],
                                 self.arrays.tables(), out, first)
                else:
                    for kind in ("sqlite", "exact"):
                        if first in out[kind]:
                            out[kind][entry["at"]] = out[kind][first]
            tables = self.arrays.tables()
            out["read_back"] = {
                t: [len(tables[t][key]), int(tables[t][key].sum()),
                    int(tables[t][dec].sum())]
                for t, (key, dec) in self.read_back.items()}
        finally:
            if self.conn is not None:
                self.conn.rollback()
        out["seconds"] = dict(self.seconds, replay=time.monotonic() - t0,
                              answered=len(shared))
        return out


def _worker(job: dict, inbox, out):
    os.environ["JAX_PLATFORMS"] = "cpu"  # nothing here imports JAX; if a
    # reference module ever does, it stays off the chip
    try:
        ref = _Reference(job)
        if not job.get("writes"):
            out.put(ref.answers())
            return
        while (log := inbox.get()) is not None:
            out.put(ref.replay(log))
    except BaseException as e:  # noqa: BLE001 — reported by the parent
        out.put({"error": f"{type(e).__name__}: {e}",
                 "traceback": traceback.format_exc()})


class ReferenceChild:
    """Start with ``start()``, read with ``join()`` (a mix that only
    reads) or ``replay(log)`` (one that writes), always ``stop()``."""

    def __init__(self, job: dict):
        self.job = job
        self._proc = self._queue = self._inbox = None
        self.answers = None

    def start(self):
        ctx = multiprocessing.get_context("spawn")
        self._queue = ctx.Queue()
        self._inbox = ctx.Queue()
        self._proc = ctx.Process(
            target=_worker, args=(self.job, self._inbox, self._queue),
            daemon=True)
        self._proc.start()

    def join(self, deadline_s: float) -> dict:
        """The child's answers; raises when it failed, died or is late
        (``deadline_s`` is on ``time.monotonic()``'s clock)."""
        while self.answers is None:
            try:
                self.answers = self._queue.get(timeout=2.0)
            except queue.Empty:
                if not self._proc.is_alive():
                    self.answers = {"error": "the reference child died"}
                elif time.monotonic() > deadline_s:
                    self.answers = {"error": "the reference child is late"}
        if "error" in self.answers:
            raise RuntimeError("reference: " + self.answers["error"] + "\n"
                               + self.answers.get("traceback", ""))
        return self.answers

    def replay(self, log: list[dict], deadline_s: float) -> dict:
        self.answers = None
        self._inbox.put(log)
        return self.join(deadline_s)

    def stop(self):
        if self._proc is None:
            return
        if self._proc.is_alive():
            self._proc.terminate()
        self._proc.join(10)
        if self._proc.is_alive():
            self._proc.kill()
            self._proc.join(10)
