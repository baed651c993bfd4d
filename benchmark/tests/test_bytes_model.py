"""Bytes of one pass: computed from the layout of a relation loaded through
the adapter (device dtypes, validity masks, row mask, bucket capacity), not
from numbers typed in."""

import os

import pytest

from benchmark.harness import bytes_model, peaks, spec


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    """lineitem at SF0.01 loaded on the CPU, and its layout as read back."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchmark.harness import adapter

    dataset = spec.load_module("datasets", "tpch")
    tables, types = dataset.generate(0.01, seed=3)
    system = adapter.System(str(tmp_path_factory.mktemp("db") / "root"))
    try:
        system.load_table("lineitem", tables["lineitem"], types,
                          dataset.PRIMARY_KEYS["lineitem"])
        rel = system.session.catalog.table_data("lineitem")
        yield rel, system.relation_layout("lineitem"), \
            len(tables["lineitem"]["l_orderkey"])
    finally:
        system.close()


def _q6_reads():
    return spec.read_json(os.path.join(
        spec.BENCH_DIR, "statements", "tpch_q6.json"))["reads"]


def test_q6_one_pass_is_four_columns_and_the_mask(loaded):
    rel, layout, n_rows = loaded
    reads = _q6_reads()
    assert sorted(reads["lineitem"]) == sorted(
        ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"])
    # the layout is the loaded relation's, lane for lane
    assert layout["capacity"] == rel.capacity >= n_rows
    per_lane = rel.mask.dtype.itemsize
    for c in reads["lineitem"]:
        col = rel.columns[c]
        assert layout["columns"][c]["itemsize"] == col.data.dtype.itemsize
        per_lane += col.data.dtype.itemsize
        if col.valid is not None:
            per_lane += col.valid.dtype.itemsize
    assert bytes_model.one_pass_bytes(reads, {"lineitem": layout}) \
        == per_lane * rel.capacity


def test_q6_at_sf1_capacity(loaded):
    """The same dtypes at SF1's bucket capacity: three 8-byte decimals, a
    4-byte date and the 1-byte mask over 8,388,608 lanes."""
    _rel, layout, _n = loaded
    sf1 = dict(layout, capacity=8_388_608)
    got = bytes_model.one_pass_bytes(_q6_reads(), {"lineitem": sf1})
    assert got == (8 + 8 + 8 + 4 + 1) * 8_388_608
    peak = peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert bytes_model.least_seconds(
        _q6_reads(), {"lineitem": sf1}, peak) == pytest.approx(got / 819e9)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        peaks.peaks_for("TPU v9 imaginary")
