"""The plain reference, computed in a child process that never needs the chip.

The child regenerates the data from the seed, loads SQLite with the
columns the cell's statements read, and answers every distinct statement
of the run: through SQLite where the statement file says so, and through
the statement's exact reference module where it names one.  It is started
before the parent touches JAX and runs beside the load.
"""

from __future__ import annotations

import multiprocessing
import os
import queue
import time
import traceback

from . import spec as specmod


def _answers(job: dict) -> dict:
    from . import sqlite_oracle

    t0 = time.monotonic()
    dataset = specmod.load_module("datasets", job["dataset"], job["bench_dir"])
    tables, types = dataset.generate(job["scale"], job["seed"])
    seconds = {"generate": time.monotonic() - t0}

    items = job["items"]  # [{key, sql, params, sqlite, exact, ...}]
    out = {"sqlite": {}, "exact": {}}
    if any(it["sqlite"] for it in items):
        t0 = time.monotonic()
        used = {t: {c: tables[t][c] for c in cols}
                for t, cols in job["reads"].items()}
        conn = sqlite_oracle.load_sqlite(used, types)
        seconds["sqlite_load"] = time.monotonic() - t0
        t0 = time.monotonic()
        for it in items:
            if it["sqlite"]:
                out["sqlite"][it["key"]] = sqlite_oracle.run_oracle(
                    conn, it["sql"])
        conn.close()
        seconds["sqlite_queries"] = time.monotonic() - t0
    t0 = time.monotonic()
    for it in items:
        if it["exact"]:
            mod = specmod.load_module("references", it["exact"],
                                      job["bench_dir"])
            out["exact"][it["key"]] = mod.answer(tables, it["params"])
    seconds["exact"] = time.monotonic() - t0
    out["seconds"] = seconds
    return out


def _worker(job: dict, out):
    os.environ["JAX_PLATFORMS"] = "cpu"  # nothing here imports JAX; if a
    # reference module ever does, it stays off the chip
    try:
        out.put(_answers(job))
    except BaseException as e:  # noqa: BLE001 — reported by the parent
        out.put({"error": f"{type(e).__name__}: {e}",
                 "traceback": traceback.format_exc()})


class ReferenceChild:
    """Start with ``start()``, read with ``join()``, always ``stop()``."""

    def __init__(self, job: dict):
        self.job = job
        self._proc = self._queue = None
        self.answers = None

    def start(self):
        ctx = multiprocessing.get_context("spawn")
        self._queue = ctx.Queue()
        self._proc = ctx.Process(target=_worker, args=(self.job, self._queue),
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

    def stop(self):
        if self._proc is None:
            return
        if self._proc.is_alive():
            self._proc.terminate()
        self._proc.join(10)
        if self._proc.is_alive():
            self._proc.kill()
            self._proc.join(10)
