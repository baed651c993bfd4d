"""Distributed SQL execution: PX planner over the 8-device CPU mesh.

≙ PX integration tests — the same SQL must produce identical results
serial and distributed (SURVEY §2.3 parity).
"""

import jax
import numpy as np
import pytest

from oceanbase_tpu.bench.tpch import TPCH_PRIMARY_KEYS, gen_tpch
from oceanbase_tpu.bench.tpch_queries import QUERIES

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 devices")


@pytest.fixture(scope="module")
def sess(new_module_session):
    tables, types = gen_tpch(sf=0.01)
    s = new_module_session()
    for name, arrays in tables.items():
        s.catalog.load_numpy(
            name, arrays,
            types={k: v for k, v in types.items() if k in arrays},
            primary_key=TPCH_PRIMARY_KEYS[name])
    return s


def _compare_serial_px(sess, sql, qname):
    sess.variables["px_dop"] = 0
    serial = sess.execute(sql).rows()
    sess.variables["px_dop"] = 8
    dist = sess.execute(sql).rows()
    sess.variables["px_dop"] = 0
    key = lambda r: tuple(
        (x is None, round(x, 6) if isinstance(x, float) else x) for x in r)
    a, b = sorted(dist, key=key), sorted(serial, key=key)
    assert len(a) == len(b), qname
    for ra, rb in zip(a, b):
        for xa, xb in zip(ra, rb):
            if isinstance(xa, float) or isinstance(xb, float):
                # float reduction order differs across shards
                assert xa == pytest.approx(xb, rel=1e-9), qname
            else:
                assert xa == xb, qname


def test_px_q6_scalar_agg(sess):
    _compare_serial_px(sess, QUERIES[6], "q6")


def test_px_q1_groupby(sess):
    _compare_serial_px(sess, QUERIES[1], "q1")


def test_px_q14_join(sess):
    _compare_serial_px(sess, QUERIES[14], "q14")


def test_px_q3_multi_join_groupby(sess):
    _compare_serial_px(sess, QUERIES[3], "q3")


def test_px_q5_six_way_join(sess):
    _compare_serial_px(sess, QUERIES[5], "q5")


def test_px_q12_semi(sess):
    _compare_serial_px(sess, QUERIES[12], "q12")


def test_px_fallback_on_unsupported(sess):
    # Q16 has count(distinct ...): distribution unsupported -> silent
    # serial fallback with identical results
    _compare_serial_px(sess, QUERIES[16], "q16")
