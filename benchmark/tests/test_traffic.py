"""The traffic generator: the parameter pool is a pure function of the seed
and stays inside the spec's ranges; the round is what the mix file says."""

import glob
import os

import pytest

from benchmark.harness import spec, traffic

def _statement(name):
    return spec.read_json(
        os.path.join(spec.BENCH_DIR, "statements", name + ".json"))


#: the read statements (a write statement has rows, not parameters)
STATEMENTS = sorted(
    name for name in (
        os.path.basename(p)[:-5] for p in glob.glob(
            os.path.join(spec.BENCH_DIR, "statements", "*.json")))
    if "writes" not in _statement(name))
MIXES = sorted(
    os.path.basename(p)[:-5]
    for p in glob.glob(os.path.join(spec.BENCH_DIR, "traffic", "*.json")))


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


def _mix(mix):
    tr = spec.read_json(os.path.join(spec.BENCH_DIR, "traffic", mix + ".json"))
    return tr, {t["statement"]: _statement(t["statement"])
                for t in tr["templates"]}


@pytest.mark.parametrize("mix", MIXES)
def test_round_follows_the_mix_file(mix):
    tr, sts = _mix(mix)
    rnd = traffic.schedule(tr, sts, seed=5)
    turn = [t["statement"] for t in tr["templates"]
            for _ in range(t.get("every", 1))]
    assert [it.template for it in rnd[:len(turn)]] == turn  # round-robin
    assert len(rnd) % len(turn) == 0
    for t in tr["templates"]:  # every parameter set equally often
        mine = [it.key for it in rnd if it.template == t["statement"]]
        assert len({mine.count(k) for k in set(mine)}) == 1
    assert [it.sql for it in rnd] == [
        it.sql for it in traffic.schedule(tr, sts, seed=5)]


@pytest.mark.parametrize("mix", ["heavy", "scan", "mix"])
def test_a_mix_without_every_is_one_statement_a_turn(mix):
    """The rounds of the mixes that PR 23 measured: every (template,
    parameter set) once."""
    tr, sts = _mix(mix)
    rnd = traffic.schedule(tr, sts, seed=5)
    assert len({it.key for it in rnd}) == len(rnd)
    assert len(rnd) == sum(
        t["params"]["pool"] if isinstance(t["params"], dict) else 1
        for t in tr["templates"])


def test_every_gives_the_stated_ratio():
    tr, sts = _mix("refresh")
    rnd = traffic.schedule(tr, sts, seed=9)
    every = {t["statement"]: t.get("every", 1) for t in tr["templates"]}
    assert sorted(every.values()) == [1, 1, 22]
    counts = {name: sum(it.template == name for it in rnd) for name in every}
    turns = counts[min(every, key=every.get)]
    assert counts == {name: turns * n for name, n in every.items()}
    # a pair of writes, then the reads of one stream, turn after turn
    turn = len(rnd) // turns
    for i in range(0, len(rnd), turn):
        assert [it.sql is None for it in rnd[i:i + turn]] == \
            [True, True] + [False] * 22
    # the reads walk through their pool: all 16 sets equally often
    reads = [it.key for it in rnd if it.sql is not None]
    assert {reads.count(k) for k in set(reads)} == {len(reads) // 16}


def test_a_sequence_slot_has_no_text_of_its_own():
    tr, sts = _mix("refresh")
    slots = {it for it in traffic.schedule(tr, sts, seed=1)
             if it.sql is None}
    assert {it.template for it in slots} == {
        t["statement"] for t in tr["templates"] if t["params"] == "sequence"}
    assert len(slots) == 2      # one slot a template: the runner counts


def test_every_under_one_is_refused():
    tr, sts = _mix("scan")
    tr["templates"][0]["every"] = 0
    with pytest.raises(ValueError):
        traffic.schedule(tr, sts, seed=1)
