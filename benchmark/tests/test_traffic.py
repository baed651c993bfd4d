"""The traffic generator: the parameter pool is a pure function of the seed
and stays inside the spec's ranges; the round is what the mix file says."""

import glob
import os

import pytest

from benchmark.harness import spec, traffic

STATEMENTS = sorted(
    os.path.basename(p)[:-5]
    for p in glob.glob(os.path.join(spec.BENCH_DIR, "statements", "*.json")))
MIXES = sorted(
    os.path.basename(p)[:-5]
    for p in glob.glob(os.path.join(spec.BENCH_DIR, "traffic", "*.json")))


def _statement(name):
    return spec.read_json(
        os.path.join(spec.BENCH_DIR, "statements", name + ".json"))


def _space(st):
    n = 1
    for p in st["parameters"].values():
        n *= traffic._count(p)
    return n


@pytest.mark.parametrize("name", STATEMENTS)
def test_pool_is_a_pure_function_of_the_seed(name):
    st = _statement(name)
    n = min(16, _space(st))
    a = traffic.draw_pool(name, st, n, seed=7)
    assert a == traffic.draw_pool(name, st, n, seed=7)
    assert len({tuple(sorted(p.items())) for p in a}) == n  # distinct
    if _space(st) > 4 * n:
        assert a != traffic.draw_pool(name, st, n, seed=8)


@pytest.mark.parametrize("name", STATEMENTS)
@pytest.mark.parametrize("seed", [0, 1, 19920101, 2**40 + 3])
def test_pool_stays_in_the_statements_ranges(name, seed):
    st = _statement(name)
    for params in traffic.draw_pool(name, st, min(16, _space(st)), seed):
        assert set(params) == set(st["parameters"])
        for k, v in params.items():
            assert traffic.in_range(st["parameters"][k], v), (k, v)


@pytest.mark.parametrize("name", STATEMENTS)
def test_validation_values_are_in_range_and_render(name):
    st = _statement(name)
    params = traffic.validation_params(st)
    for k, v in params.items():
        assert traffic.in_range(st["parameters"][k], v), (k, v)
    sql = traffic.render(st, params)
    assert "{" not in sql and "}" not in sql
    assert sql.lower().startswith("select ")


def test_q6_ranges_are_the_specs():
    st = _statement("tpch_q6")
    pool = traffic.draw_pool("tpch_q6", st, 80, seed=3)  # the whole space
    assert {p["DATE"] for p in pool} == {
        f"{y}-01-01" for y in range(1993, 1998)}
    assert {p["DISCOUNT"] for p in pool} == {
        f"0.0{d}" for d in range(2, 10)}
    assert {p["QUANTITY"] for p in pool} == {"24", "25"}
    sql = traffic.render(st, {"DATE": "1994-01-01", "DISCOUNT": "0.06",
                              "QUANTITY": "24"})
    assert "between 0.05 and 0.07" in sql and "l_quantity < 24" in sql


def test_a_pool_larger_than_the_space_is_refused():
    with pytest.raises(ValueError):
        traffic.draw_pool("tpch_q6", _statement("tpch_q6"), 81, seed=1)


@pytest.mark.parametrize("mix", MIXES)
def test_round_follows_the_mix_file(mix):
    tr = spec.read_json(os.path.join(spec.BENCH_DIR, "traffic", mix + ".json"))
    sts = {t["statement"]: _statement(t["statement"])
           for t in tr["templates"]}
    rnd = traffic.schedule(tr, sts, seed=5)
    names = [t["statement"] for t in tr["templates"]]
    assert [it.template for it in rnd[:len(names)]] == names  # round-robin
    assert len({it.key for it in rnd}) == len(rnd)
    assert [it.sql for it in rnd] == [
        it.sql for it in traffic.schedule(tr, sts, seed=5)]
