"""Vector-search and FTS SQL surface (VERDICT r3 missing #8).

≙ src/share/vector_index (ANN access path: ORDER BY distance LIMIT k)
and src/storage/fts (MATCH ... AGAINST) — TPU-first: exact search is one
MXU matmul + top_k; IVF-Flat above 100k rows; FTS scores evaluate in the
string-dictionary domain (host LUT + device gather).
"""

import numpy as np
import pytest


def _vec_env(new_session, n=2000, d=16, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    s = new_session()
    s.catalog.load_numpy(
        "emb", {"id": np.arange(n), "v": vecs, "tag": np.arange(n) % 5},
        primary_key=["id"])
    return s, vecs


def test_vector_type_and_distance_functions(new_session):
    s, vecs = _vec_env(new_session)
    q = vecs[7]
    qtxt = "[" + ", ".join(f"{x:.6f}" for x in q) + "]"
    r = s.execute(f"select id, l2_distance(v, '{qtxt}') as d from emb "
                  "order by d limit 3")
    rows = r.rows()
    assert rows[0][0] == 7 and rows[0][1] < 1e-3
    # verify against numpy
    dist = np.linalg.norm(vecs - q, axis=1)
    exp = np.argsort(dist, kind="stable")[:3].tolist()
    assert [r0[0] for r0 in rows] == exp


def test_vector_index_topk_exact_parity(new_session):
    s, vecs = _vec_env(new_session)
    s.execute("create vector index iv on emb (v) with (metric = 'l2')")
    q = vecs[123] + 0.01
    qtxt = "[" + ", ".join(f"{x:.6f}" for x in q) + "]"
    sql = (f"select id from emb order by l2_distance(v, '{qtxt}') "
           "limit 5")
    got = [r[0] for r in s.execute(sql).rows()]
    dist = np.linalg.norm(vecs - q, axis=1)
    exp = np.argsort(dist, kind="stable")[:5].tolist()
    assert got == exp
    # the ANN access path actually engaged (runtime cache populated)
    assert any(k[0] == "emb" for k in s.catalog._ann_cache)


def test_vector_cosine_index(new_session):
    s, vecs = _vec_env(new_session)
    s.execute("create vector index ic on emb (v) "
              "with (metric = 'cosine')")
    q = vecs[55]
    qtxt = "[" + ", ".join(f"{x:.6f}" for x in q) + "]"
    got = [r[0] for r in s.execute(
        f"select id from emb order by cosine_distance(v, '{qtxt}') "
        "limit 1").rows()]
    assert got == [55]


def test_vector_insert_through_engine(tmp_path):
    from oceanbase_tpu.server import Database

    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table items (id int primary key, e vector(3))")
    s.execute("insert into items values (1, '[1, 0, 0]'), "
              "(2, '[0, 1, 0]'), (3, '[0.9, 0.1, 0]')")
    r = s.execute("select id from items "
                  "order by l2_distance(e, '[1, 0, 0]') limit 2")
    assert [x[0] for x in r.rows()] == [1, 3]
    db.close()


def test_fulltext_match_against(new_session):
    s = new_session()
    docs = np.array([
        "the quick brown fox", "jumped over the lazy dog",
        "quick quick slow", "a dog and a fox", "nothing relevant here",
    ], dtype=object)
    s.catalog.load_numpy("docs", {"id": np.arange(5), "body": docs},
                         primary_key=["id"])
    s.execute("create fulltext index ft on docs (body)")
    r = s.execute("select id from docs "
                  "where match(body) against('fox') order by id")
    assert [x[0] for x in r.rows()] == [0, 3]
    # multi-term scoring ranks docs containing more terms higher
    r = s.execute("select id, match(body) against('quick fox') as s "
                  "from docs where match(body) against('quick fox') "
                  "order by s desc, id")
    rows = r.rows()
    assert rows[0][0] == 0 and rows[0][1] == 2.0
    assert {x[0] for x in rows} == {0, 2, 3}
    # boolean-mode syntax parses
    r = s.execute("select count(*) from docs where "
                  "match(body) against('dog' in boolean mode)")
    assert r.rows()[0][0] == 2


def test_vector_index_persists_across_restart(tmp_path):
    from oceanbase_tpu.server import Database

    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table items (id int primary key, e vector(3))")
    s.execute("insert into items values (1, '[1, 0, 0]'), "
              "(2, '[0, 1, 0]')")
    s.execute("create vector index iv on items (e) with (metric = 'l2')")
    db.checkpoint()
    db.close()
    db2 = Database(str(tmp_path / "db"))
    s2 = db2.session()
    td = s2.catalog.table_def("items")
    assert "iv" in td.aux_indexes
    # a second identical CREATE errors (it survived the restart)
    import pytest as _pt

    with _pt.raises(ValueError):
        s2.execute("create vector index iv on items (e)")
    s2.execute("drop index iv on items")
    assert "iv" not in s2.catalog.table_def("items").aux_indexes
    db2.close()
    # the drop also persisted
    db3 = Database(str(tmp_path / "db"))
    assert "iv" not in db3.session().catalog.table_def(
        "items").aux_indexes
    db3.close()


def test_empty_vector_table_create(new_session):
    s = new_session()
    import numpy as np

    # a VECTOR column on a table created without data must not crash
    s.catalog.load_numpy(
        "ev", {"id": np.zeros(1, np.int64),
               "v": np.zeros(1, np.float32)},
        types={"v": __import__("oceanbase_tpu.datatypes",
                               fromlist=["SqlType"]).SqlType.vector(3)})
    assert s.catalog.table_def("ev").column("v").dtype.precision == 3


def test_vector_index_approximate_opt_in(new_session):
    """IVF recall only engages when the index opts in WITH
    (approximate = true); a plain vector index keeps exact answers."""
    import numpy as np

    s, vecs = _vec_env(new_session, n=5000, d=8, seed=4)
    s.execute("create vector index ia on emb (v) "
              "with (metric = 'l2', approximate = true)")
    q = vecs[42]
    qtxt = "[" + ", ".join(f"{x:.6f}" for x in q) + "]"
    got = [r[0] for r in s.execute(
        f"select id from emb order by l2_distance(v, '{qtxt}') "
        "limit 5").rows()]
    # the true nearest (the query vector itself) must be found even by
    # IVF (it lands in the probed centroid's bucket)
    assert got[0] == 42
    from oceanbase_tpu.share.vector_index import IvfFlatIndex

    hit = next(v for k, v in s.catalog._ann_cache.items()
               if k[0] == "emb")
    assert isinstance(hit[1], IvfFlatIndex)
