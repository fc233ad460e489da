"""What PR 33 added to the benchmark, at small sizes on the CPU: the three
tables' generator (its invariants, and LINEITEM's arrays left as they were),
the Q3 reference against a pandas merge for every parameter set and against
its float32 control, the several-table roofline reader, and one rehearsal of
each new cell."""
import hashlib

import numpy as np
import pandas as pd
import pytest

from perfbench import compare, traffic
from perfbench.datagen import tpch_lineitem, tpch_q3_tables
from perfbench.readers import roofline, roofline_tables
from perfbench.references import tpch_q3_topk
from perfbench.tests.test_run import drive, engine_config_restored  # noqa: F401

ROWS = 60_000
SEED = 2_147_483_659
SEGMENTS = [s.lower() for s in tpch_q3_tables.SEGMENTS]


@pytest.fixture(scope="module")
def data():
    arrays = tpch_q3_tables.generate(ROWS, SEED, scale_factor=10)
    tables = {name: table.to_pandas() for name, table in
              tpch_q3_tables.arrow_tables(arrays).items()}
    return arrays, tables


def test_lineitem_arrays_are_tpch_lineitems_own(data):
    """Same seed, same rows: every array `tpch_lineitem.generate` returns,
    equal; and (a fixed seed) what they were before this module existed."""
    arrays, _ = data
    alone = tpch_lineitem.generate(ROWS, SEED, scale_factor=10)
    assert set(alone) <= set(arrays)
    for name, values in alone.items():
        assert np.array_equal(arrays[name], values), name
    small = tpch_lineitem.generate(1_000, 7, scale_factor=10)
    digest = hashlib.sha256(b"".join(
        np.ascontiguousarray(small[n]).tobytes() for n in sorted(small)))
    assert digest.hexdigest() == LINEITEM_1000_ROWS_SEED_7


#: sha256 over the sorted arrays of `tpch_lineitem.generate(1_000, 7, 10)`,
#: read at the parent commit (a6b56dc)
LINEITEM_1000_ROWS_SEED_7 = "404a97bcf5cf2818836016d42ad260c27ba0c2b0dcc260594f66821767cdb7d1"


def test_orders_and_customer_follow_the_clause(data):
    arrays, t = data
    orders, customer, lineitem = t["orders"], t["customer"], t["lineitem"]
    assert list(orders.columns) == list(tpch_q3_tables.ORDERS) \
        and len(orders.columns) == 9
    assert list(customer.columns) == list(tpch_q3_tables.CUSTOMER) \
        and len(customer.columns) == 8
    # every line has its order, every order its lines, keys sparse and unique
    assert orders.o_orderkey.is_unique
    assert set(orders.o_orderkey) == set(lineitem.l_orderkey)
    assert set(orders.o_orderkey % 32) <= set(range(1, 9))
    by_order = lineitem.merge(orders, left_on="l_orderkey",
                              right_on="o_orderkey")
    assert len(by_order) == len(lineitem)
    days = (by_order.l_shipdate - by_order.o_orderdate).dt.days
    assert days.between(1, 121).all() and days.max() > 100
    # o_custkey: no multiple of 3, inside CUSTOMER's keys
    assert (orders.o_custkey % 3 != 0).all()
    assert orders.o_custkey.between(1, len(customer)).all()
    assert len(customer) == tpch_q3_tables.customers_for(ROWS, 10)
    assert (customer.c_custkey == np.arange(1, len(customer) + 1)).all()
    # derived columns
    lines = lineitem.groupby("l_orderkey")
    status = lines.l_linestatus.agg(
        lambda s: s.iloc[0] if s.nunique() == 1 else "P")
    assert (orders.set_index("o_orderkey").o_orderstatus
            == status.reindex(orders.o_orderkey).to_numpy()).all()
    total = (lineitem.l_extendedprice * (1 + lineitem.l_tax)
             * (1 - lineitem.l_discount)).groupby(lineitem.l_orderkey).sum()
    assert np.allclose(orders.o_totalprice,
                       total.reindex(orders.o_orderkey).to_numpy(),
                       rtol=1e-12)
    # domains and formats
    assert set(orders.o_orderpriority) == set(tpch_q3_tables.PRIORITIES)
    assert (orders.o_shippriority == 0).all()
    assert orders.o_clerk.str.fullmatch(r"Clerk#0000\d{5}").all()
    assert orders.o_comment.str.len().between(19, 78).all()
    assert set(customer.c_mktsegment) == set(tpch_q3_tables.SEGMENTS)
    assert (customer.c_name == "Customer#" + customer.c_custkey.astype(str)
            .str.zfill(9)).all()
    assert customer.c_phone.str.fullmatch(
        r"[1-3]\d-[1-9]\d\d-[1-9]\d\d-[1-9]\d{3}").all()
    assert (customer.c_phone.str[:2].astype(int)
            == customer.c_nationkey + 10).all()
    assert customer.c_acctbal.between(-999.99, 9999.99).all()
    assert customer.c_address.str.len().between(10, 40).all()
    assert customer.c_comment.str.len().between(29, 116).all()


def q3_pandas(t, segment, date):
    date = pd.Timestamp(date)
    c, o, l = t["customer"], t["orders"], t["lineitem"]
    m = c[c.c_mktsegment == segment].merge(
        o[o.o_orderdate < date], left_on="c_custkey", right_on="o_custkey")
    m = m.merge(l[l.l_shipdate > date], left_on="o_orderkey",
                right_on="l_orderkey")
    m = m.assign(revenue=m.l_extendedprice * (1 - m.l_discount))
    return (m.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])
            .revenue.sum().reset_index()
            .sort_values(["revenue", "o_orderdate", "l_orderkey"],
                         ascending=[False, True, True]).head(10)
            [tpch_q3_topk.COLUMNS])


def test_q3_topk_matches_a_pandas_merge_for_every_parameter_set(data):
    arrays, tables = data
    reference = tpch_q3_topk.Reference(arrays)
    sets = 0
    for index, segment in enumerate(SEGMENTS):
        query = traffic.load("queries", f"tpch_q3_{segment}")
        assert segment.upper() in query["sql"]
        for params in traffic.all_params(query):
            assert params["SEGMENT"] == index
            date = traffic.render(query, params).split("DATE '")[1][:10]
            frame = q3_pandas(tables, segment.upper(), date)
            want = {"columns": list(frame.columns), "rows": [
                list(r) for r in frame.itertuples(index=False)]}
            gap = compare.answer_gap(query, reference.answer(params), want)
            assert gap is not None and gap < 1e-12, (params, gap)
            sets += 1
    assert sets == 155


def test_q3_controls(data):
    """float64 as the control's precision is the reference; float32 is not
    within the query file's limit (and may order the rows otherwise)."""
    arrays, _ = data
    reference = tpch_q3_topk.Reference(arrays)
    query = traffic.load("queries", "tpch_q3_household")
    worst = 0.0
    for params in traffic.all_params(query):
        want = reference.answer(params)
        same = compare.answer_gap(query, tpch_q3_topk.control_answer(
            arrays, params, "float64"), want)
        assert same is not None and same < 1e-12
        gap = compare.answer_gap(query, tpch_q3_topk.control_answer(
            arrays, params, "float32"), want)
        worst = max(worst, float("inf") if gap is None else gap)
    assert worst > 1e3 * query["limits"]["rel_err"]


def test_roofline_tables_sums_the_scans_of_every_table():
    tables = {
        "lineitem": {"rows": 1000, "itemsize": {
            "l_orderkey": 4, "l_extendedprice": 8, "l_discount": 2,
            "l_shipdate": 2, "l_tax": 2}},
        "orders": {"rows": 250, "itemsize": {
            "o_orderkey": 4, "o_custkey": 4, "o_orderdate": 2,
            "o_shippriority": 8, "o_comment": 4}},
        "customer": {"rows": 60, "itemsize": {"c_custkey": 4,
                                              "c_mktsegment": 4}}}
    q3 = traffic.load("queries", "tpch_q3_building")
    assert roofline_tables.scan_bytes(q3, tables) \
        == 16 * 1000 + 18 * 250 + 8 * 60
    # LINEITEM's share is what the one-table reader reads in the same cell
    assert roofline.scan_bytes(q3, tables["lineitem"]) == 16 * 1000
    # a query of one table names no scans; a table that was not loaded
    assert roofline_tables.scan_bytes(traffic.load("queries", "tpch_q6"),
                                      tables) is None
    assert roofline_tables.scan_bytes(q3, {"lineitem": tables["lineitem"]}) \
        is None
    assert roofline_tables.read({}, {"profile": None}) is None


@pytest.mark.parametrize("cell", ["sf10_q3_library", "sf10_q6_library"])
def test_new_cells_rehearse_correct(capsys, monkeypatch, cell):
    result, phases = drive(capsys, monkeypatch, cell, rows=40_000)
    assert result["correct"] is True, result
    assert result["attempted"] > 10 and result["failed"] == 0
    window = [p for p in phases if p.get("phase") == "window"][0]
    assert window["persistent_cache"]["before"]["misses"] \
        == window["persistent_cache"]["after"]["misses"]
    if cell == "sf10_q3_library":
        assert window["rungs"] == ["rung:compiled_join_aggregate"]
        warm = [p for p in phases
                if str(p.get("phase", "")).startswith("warm:")]
        assert len(warm) == 10
        assert sum(bool(p["compile_spans"]) for p in warm) == 1


def test_an_engine_without_whole_build_sides_fails_before_a_row_is_drawn(
        monkeypatch):
    """The parent of PR 33 hung in set-up on this cell (the eager join's
    compile at 24M rows); the generator refuses such an engine at once, so
    the run ends with an error instead of being killed."""
    from dask_sql_tpu.serving import metrics

    tpch_q3_tables.require_whole_build_sides()  # this tree: nothing raised
    monkeypatch.setattr(metrics, "DOCUMENTED_METRICS",
                        metrics.DOCUMENTED_METRICS - {"join.build.whole"})
    with pytest.raises(RuntimeError, match="cannot run tpch_sf10_q3_tables"):
        tpch_q3_tables.generate(1_000, 1, scale_factor=10)
