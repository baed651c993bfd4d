"""SF1 TPC-H 22-query parity evidence runner .

Generates TPC-H at SF (env PARITY_SF, default 1.0), loads both the
engine and the (now indexed) SQLite oracle, runs all 22 queries through
each, diffs results, and writes SF1_PARITY.json with per-query engine
and oracle wall times plus row counts — an artifact a skeptic can check.

Usage: [PARITY_SF=1.0] python scripts/sf_parity.py
"""

from __future__ import annotations

import json
import os
import sys
import time

# a CPU parity record: the chip is reached only through chip_smoke.py
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from oceanbase_tpu.bench.oracle import (  # noqa: E402
    load_sqlite, rows_match, run_oracle)
from oceanbase_tpu.bench.tpch import (  # noqa: E402
    TPCH_PRIMARY_KEYS, gen_tpch)
from oceanbase_tpu.bench.tpch_queries import QUERIES  # noqa: E402
from oceanbase_tpu.exec.plan import exec_times  # noqa: E402
from oceanbase_tpu.server import metrics as qmetrics  # noqa: E402
from oceanbase_tpu.server import Database  # noqa: E402

SF = float(os.environ.get("PARITY_SF", "1.0"))
OUT = os.path.join(os.path.dirname(__file__), "..",
                   os.environ.get("PARITY_OUT", "SF1_PARITY.json"))


def main():
    t0 = time.monotonic()
    print(f"generating TPC-H SF={SF} ...", flush=True)
    tables, types = gen_tpch(sf=SF)
    gen_s = time.monotonic() - t0
    print(f"  done in {gen_s:.1f}s "
          f"(lineitem={len(tables['lineitem']['l_orderkey'])} rows)",
          flush=True)

    sess = Database().session()
    t0 = time.monotonic()
    for name, arrays in tables.items():
        sess.catalog.load_numpy(
            name, arrays,
            types={k: v for k, v in types.items() if k in arrays},
            primary_key=TPCH_PRIMARY_KEYS[name])
    # gather stats (exact NDV + histograms) before the run — mirrors the
    # reference's DBMS_STATS gather ahead of benchmarking
    for name in tables:
        sess.execute(f"analyze table {name}")
    # mirror the oracle's indexing (bench/oracle.py): a secondary index
    # on every *key column, so the CBO's index-probe access path
    # competes on equal footing with indexed SQLite
    for name, arrays in tables.items():
        for c in arrays:
            if c.endswith("key"):
                sess.execute(
                    f"create index idx_{name}_{c} on {name} ({c})")
    load_engine_s = time.monotonic() - t0
    t0 = time.monotonic()
    conn = load_sqlite(tables, types)
    load_oracle_s = time.monotonic() - t0
    print(f"loads: engine+analyze {load_engine_s:.1f}s, "
          f"oracle {load_oracle_s:.1f}s", flush=True)

    results = {}
    n_ok = 0
    for qnum in sorted(QUERIES):
        sql = QUERIES[qnum]
        t0 = time.monotonic()
        want = run_oracle(conn, sql)
        oracle_s = time.monotonic() - t0
        t0 = time.monotonic()
        try:
            got = sess.execute(sql).rows()
            engine_s = time.monotonic() - t0
            ordered = "order by" in sql.lower() and qnum not in (2, 18, 21)
            ok, why = rows_match(got, want, ordered=ordered)
        except Exception as e:  # noqa: BLE001 — record, keep going
            engine_s = time.monotonic() - t0
            ok, why = False, f"{type(e).__name__}: {e}"
            got = []
        n_ok += bool(ok)
        # per-query device attribution: the XLA cost_analysis totals of
        # the programs this statement ran (its ExecTimes accumulator)
        times = exec_times()
        flops, nbytes = times.flops, times.bytes
        results[f"q{qnum}"] = {
            "ok": bool(ok), "rows": len(got), "oracle_rows": len(want),
            "engine_s": round(engine_s, 3), "oracle_s": round(oracle_s, 3),
            "flops": int(flops), "bytes_accessed": int(nbytes),
            **({} if ok else {"why": why[:300]})}
        print(f"Q{qnum:02d}: {'OK ' if ok else 'FAIL'} "
              f"rows={len(got)} engine={engine_s:.2f}s "
              f"oracle={oracle_s:.2f}s gflops={flops / 1e9:.2f}"
              + ("" if ok else f"  [{why[:120]}]"), flush=True)

    # resolved-backend provenance
    from oceanbase_tpu.server.backend_info import (  # noqa: E402
        resolve_backend)

    artifact = {
        "sf": SF, "queries_ok": n_ok, "queries_total": len(QUERIES),
        "gen_s": round(gen_s, 1), "load_engine_s": round(load_engine_s, 1),
        "load_oracle_s": round(load_oracle_s, 1),
        "host": {"nproc": os.cpu_count()},
        "backend": resolve_backend(),
        "results": results,
        # bench artifacts and the metrics plane share one schema
        "sysstat": qmetrics.sysstat_dict(),
    }
    with open(OUT, "w") as fh:
        json.dump(artifact, fh, indent=1)
    print(f"wrote {OUT}: {n_ok}/{len(QUERIES)} OK", flush=True)
    return 0 if n_ok == len(QUERIES) else 1


if __name__ == "__main__":
    sys.exit(main())
