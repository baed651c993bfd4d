"""Multi-node cluster tests: 3 OS processes, networked PALF, statement
routing, DAS remote scan, leader kill + re-election.

≙ mittest/simple_server (ob_simple_server.h:21) booting real observer
processes and driving them over the wire; failover scenarios ≙ the
palf_cluster mittest.  These tests spawn `python -m
oceanbase_tpu.net.node` subprocesses — real sockets, real fsync, real
process kill.
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from oceanbase_tpu.net.rpc import RpcClient, RpcError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


class Cluster:
    def __init__(self, tmp_path, n=3, lease_ms=1500):
        self.n = n
        self.ports = _free_ports(n)
        self.procs: dict[int, subprocess.Popen] = {}
        self.tmp = tmp_path
        self.lease_ms = lease_ms
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        self.env = env
        for i in range(1, n + 1):
            self.start_node(i, bootstrap=(i == 1))
        self.clients = {i: RpcClient("127.0.0.1", self.ports[i - 1],
                                     timeout_s=30.0)
                        for i in range(1, n + 1)}
        self.wait_ready()

    def start_node(self, i, bootstrap=False):
        peers = ",".join(f"{j}=127.0.0.1:{self.ports[j - 1]}"
                         for j in range(1, self.n + 1) if j != i)
        cmd = [sys.executable, "-m", "oceanbase_tpu.net.node",
               "--node-id", str(i), "--port", str(self.ports[i - 1]),
               "--peers", peers, "--root",
               str(self.tmp / f"node{i}"),
               "--lease-ms", str(self.lease_ms)]
        if bootstrap:
            cmd.append("--bootstrap")
        self.procs[i] = subprocess.Popen(
            cmd, env=self.env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    def wait_ready(self, timeout=60):
        deadline = time.time() + timeout
        for i, cli in self.clients.items():
            while time.time() < deadline:
                if self.procs[i].poll() is not None:
                    out = self.procs[i].stdout.read()
                    raise RuntimeError(f"node {i} died:\n{out[-3000:]}")
                if cli.ping():
                    break
                time.sleep(0.2)
            else:
                raise TimeoutError(f"node {i} not ready")

    def kill(self, i, sig=signal.SIGKILL):
        self.procs[i].send_signal(sig)
        self.procs[i].wait(timeout=10)

    def execute(self, i, sql, **kw):
        return self.clients[i].call("sql.execute", sql=sql, **kw)

    def rows(self, res):
        names = res["names"]
        n = res["rowcount"] if not names else len(
            next(iter(res["arrays"].values())))
        out = []
        for r in range(n):
            row = []
            for nm in names:
                v = res.get("valids", {}).get(nm)
                if v is not None and not v[r]:
                    row.append(None)
                else:
                    x = res["arrays"][nm][r]
                    row.append(x.item() if hasattr(x, "item") else x)
            out.append(tuple(row))
        return out

    def close(self):
        for p in self.procs.values():
            if p.poll() is None:
                p.kill()
        for p in self.procs.values():
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass


@pytest.fixture()
def cluster(tmp_path):
    c = Cluster(tmp_path, n=3)
    yield c
    c.close()


def test_replication_and_routing(cluster):
    c = cluster
    # DDL + DML against the bootstrap leader (node 1)
    c.execute(1, "create table t (k int primary key, v int)")
    c.execute(1, "insert into t values (1, 10), (2, 20), (3, 30)")
    # write via a FOLLOWER: statement routes to the leader
    res = c.execute(2, "insert into t values (4, 40)")
    assert res["node"] == 1
    # strong read via a follower routes to the leader
    res = c.execute(3, "select k, v from t order by k")
    assert res["node"] == 1
    assert c.rows(res) == [(1, 10), (2, 20), (3, 30), (4, 40)]
    # replication: followers converge (weak local read)
    deadline = time.time() + 20
    while time.time() < deadline:
        res = c.execute(2, "select count(*) from t",
                        consistency="weak")
        if res["node"] == 2 and c.rows(res)[0][0] == 4:
            break
        time.sleep(0.3)
    else:
        raise AssertionError("follower never converged")


def test_das_remote_scan_endpoint(cluster):
    c = cluster
    c.execute(1, "create table r (k int primary key, s varchar(16))")
    c.execute(1, "insert into r values (1, 'aa'), (2, 'bb')")
    # scan the leader's snapshot directly (the DAS wire surface)
    got = c.clients[1].call("das.scan", table="r")
    assert got["total"] == 2
    assert sorted(got["arrays"]["k"].tolist()) == [1, 2]
    assert sorted(got["arrays"]["s"].tolist()) == ["aa", "bb"]
    # location: every node agrees on the home (the leader)
    st = c.clients[2].call("node.state")
    assert st["leader_hint"] == 1


def test_leader_kill_reelection_no_committed_loss(cluster):
    c = cluster
    c.execute(1, "create table t (k int primary key, v int)")
    c.execute(1, "insert into t values " + ", ".join(
        f"({i}, {i * 7})" for i in range(50)))
    # committed on a majority; kill the leader process outright
    c.kill(1)
    # a write via a surviving node forces re-election (2/3 quorum)
    deadline = time.time() + 40
    last = None
    while time.time() < deadline:
        try:
            res = c.execute(2, "insert into t values (1000, 1)")
            break
        except (RpcError, OSError, ConnectionError) as e:
            last = e
            time.sleep(0.5)
    else:
        raise AssertionError(f"no re-election: {last}")
    assert res["node"] in (2, 3)
    # committed data survived the failover
    res = c.execute(2, "select count(*), sum(v) from t where k < 1000")
    assert c.rows(res)[0] == (50, sum(i * 7 for i in range(50)))
    # and the new cluster keeps serving both nodes
    res = c.execute(3, "select count(*) from t")
    assert c.rows(res)[0][0] == 51


def test_killed_node_rejoins_and_catches_up(cluster):
    """A crashed node restarts from its WAL and catches up on writes it
    missed (≙ rebootstrap + fetch-log catch-up)."""
    c = cluster
    c.execute(1, "create table t (k int primary key, v int)")
    c.execute(1, "insert into t values (1, 1), (2, 2)")
    # take node 3 down; cluster keeps committing on 1+2
    c.kill(3)
    c.execute(1, "insert into t values (3, 3), (4, 4)")
    # restart node 3 from its data dir
    c.start_node(3)
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            if c.clients[3].ping():
                res = c.execute(3, "select count(*) from t",
                                consistency="weak")
                if res["node"] == 3 and c.rows(res)[0][0] == 4:
                    break
        except Exception:
            pass
        time.sleep(0.5)
    else:
        raise AssertionError("rejoined node never caught up")
    # and it serves strong reads through the leader as before
    res = c.execute(3, "select sum(v) from t")
    assert c.rows(res)[0][0] == 10
