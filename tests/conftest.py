"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's tiering (SURVEY §4): unit tests construct operators
with synthetic inputs (≙ unittest/sql/engine fake table scan), multi-device
tests use the forced host platform mesh (≙ mittest in-process cluster).
"""

import os

# must be set before jax initializes any backend: unit tests run on the
# CPU (the chip is reached only through chip_smoke.py)
os.environ["JAX_PLATFORMS"] = "cpu"
# no persistent compile cache for the CPU suite (nor for the node processes
# its tests start, which inherit this): it gains nothing from one, and a
# checkout filled with CPU entries can grow too large to copy
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy multi-process cluster scenarios excluded from the "
        "tier-1 (-m 'not slow') gate")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


def _sessions():
    """The served path for a test that wants "a session": each call of
    the yielded ``new_session()`` boots an in-memory ``Database`` and
    hands out its ``session()``; all are closed at teardown."""
    from oceanbase_tpu.server.database import Database

    opened = []

    def new_session():
        db = Database()
        opened.append((db.session(), db))
        return opened[-1][0]

    yield new_session
    for s, db in opened:
        s.close()
        db.close()


@pytest.fixture()
def new_session():
    yield from _sessions()


@pytest.fixture(scope="module")
def new_module_session():
    yield from _sessions()


def rewrite_outer_join_for_old_sqlite(sql: str, left: str, right: str,
                                      left_cols, right_cols) -> str:
    """RIGHT/FULL OUTER JOIN oracle queries for pre-3.39 sqlite: right
    join becomes the swapped left join; full outer becomes a left join
    UNION ALL the unmatched build rows (detected via a rowid probe).
    WHERE/GROUP BY/ORDER BY tails stay outside the rewritten join, which
    preserves their post-join semantics.  No-op on sqlite >= 3.39."""
    import re
    import sqlite3

    if sqlite3.sqlite_version_info >= (3, 39):
        return sql
    m = re.search(
        rf"from {left} (full outer|right outer|right) join {right} on "
        rf"(.+?)(?= where| order by| group by|$)", sql)
    if m is None:
        return sql
    kind, cond = m.group(1), m.group(2).strip()
    if kind in ("right", "right outer"):
        repl = f"from {right} left join {left} on {cond}"
    else:
        exposed = ", ".join(
            [f"{left}.{c} as {c}" for c in left_cols]
            + [f"{right}.{c} as {c}" for c in right_cols])
        plain = ", ".join(
            [f"{left}.{c}" for c in left_cols]
            + [f"{right}.{c}" for c in right_cols])
        repl = (f"from (select {exposed} from {left} left join {right} "
                f"on {cond} union all select {plain} from {right} left "
                f"join {left} on {cond} where {left}.rowid is null)")
    return sql.replace(m.group(0), repl)


@pytest.fixture()
def poison():
    """Poison-lane verifier (oceanbase_tpu.analysis.poison): fills
    masked-dead pad lanes with NaN/sentinel garbage so a query result
    that changes proves an operator read a dead lane."""
    from oceanbase_tpu.analysis import poison as _p

    return _p
