"""A Session has one host, and every host is built in one place.

``server/database.py::Host`` declares each plane a session,
``VirtualTables``, ``WorkloadRepository`` or ``server/trace.py`` reads;
``Database`` and a node's ``NodeDatabase`` are both one, so a reader
tests a declared attribute and never probes for it.
"""

import json
import logging
import os

import pytest

from oceanbase_tpu.server.database import Database, Host
from oceanbase_tpu.sql import Session

# what a host declares: an object where the host runs the plane, None
# where it does not
PLANES = [
    "audit", "plan_monitor", "plan_feedback", "plan_history", "plan_choice",
    "time_calibration", "device_profiles", "cost_units", "time_model",
    "trace_registry", "ash", "wait_events", "workarea_history", "admission",
    "virtual_tables", "workload", "jobs", "faults", "dtl", "dtl_metrics",
    "health", "scrub", "node", "procedures",
]
# the planes only one kind of host runs
DATABASE_ONLY = {"plan_choice", "time_calibration", "device_profiles",
                 "cost_units", "jobs"}
NODE_ONLY = {"faults", "dtl", "dtl_metrics", "health", "scrub", "node"}


@pytest.mark.parametrize("args", [(), (None,)], ids=["none", "no_db"])
def test_a_session_without_a_host_is_a_type_error(args):
    with pytest.raises(TypeError):
        Session(*args)


@pytest.fixture(scope="module")
def hosts(tmp_path_factory):
    """An in-memory Database and one node booted in-process."""
    from oceanbase_tpu.net.node import NodeServer

    db = Database()
    node = NodeServer(1, "127.0.0.1", 0, {},
                      root=str(tmp_path_factory.mktemp("n1")),
                      bootstrap=True)
    node.start()
    yield db, node.db
    node.stop()
    db.close()


@pytest.mark.parametrize("name", PLANES)
def test_both_hosts_declare_the_plane(hosts, name):
    db, node_db = hosts
    for host in hosts:
        assert isinstance(host, Host)
        assert name in vars(host), (type(host).__name__, name)
    if name == "procedures":
        return  # loaded by the first session that asks, on either host
    assert (getattr(db, name) is None) == (name in NODE_ONLY)
    assert (getattr(node_db, name) is None) == (name in DATABASE_ONLY)


def test_the_hosts_differ_only_in_what_they_own(hosts):
    db, node_db = hosts
    assert set(PLANES) <= set(vars(node_db))
    # a Database adds its user store; a node's host adds nothing
    assert set(vars(db)) - set(vars(node_db)) == {"users", "_users_path"}
    assert set(vars(node_db)) <= set(vars(db))


def test_a_nodes_session_runs_on_the_declared_surface(hosts):
    """The statement path asks a node's host the same questions it asks a
    Database: a SELECT, its audit row and a virtual table answer."""
    _db, node_db = hosts
    s = Session(node_db.tenants["sys"], node_db)
    try:
        assert s.execute("select 1 + 1").rows() == [(2,)]
        assert s.execute(
            "select count(*) from gv$plan_choice").rows() == [(0,)]
        n = s.execute("select count(*) from gv$sql_audit").rows()[0][0]
        assert n >= 2
    finally:
        s.close()


def test_a_persisted_config_naming_a_removed_option_still_boots(
        tmp_path, caplog):
    root = str(tmp_path / "db")
    os.makedirs(root)
    with open(os.path.join(root, "config.json"), "w") as fh:
        json.dump({"segment_chunk_rows": 4096, "palf_lease_ms": 400,
                   "minor_compact_trigger": 8}, fh)
    with caplog.at_level(logging.WARNING, logger="oceanbase_tpu.server"):
        db = Database(root)
    try:
        assert db.config["minor_compact_trigger"] == 8
        with pytest.raises(KeyError):
            db.config["segment_chunk_rows"]
        dropped = [r.getMessage() for r in caplog.records
                   if "names no parameter" in r.getMessage()]
        assert len(dropped) == 2
        assert any("segment_chunk_rows" in m for m in dropped)
        assert any("palf_lease_ms" in m for m in dropped)
        # the next write of the file lets the names go
        db.config.set("minor_compact_trigger", 6)
        with open(os.path.join(root, "config.json")) as fh:
            assert set(json.load(fh)) == {"minor_compact_trigger"}
    finally:
        db.close()
