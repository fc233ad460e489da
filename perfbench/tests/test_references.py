"""The binned references against the plain pandas forms of ``chip_smoke.py``
(copied here with the substitution parameters put in), for EVERY parameter
value, at a small size; and each lower-precision control against the same."""
import numpy as np
import pandas as pd
import pytest

from perfbench import compare, traffic
from perfbench.datagen import tpch_lineitem
from perfbench.references import tpch_q1_binned, tpch_q6_binned

ROWS = 150_000


@pytest.fixture(scope="module")
def data():
    arrays = tpch_lineitem.generate(ROWS, 2_147_483_659)
    return arrays, tpch_lineitem.frames(arrays)["lineitem"]


def q1_pandas(df, delta):
    cutoff = np.datetime64("1998-12-01") - np.timedelta64(delta, "D")
    sel = df[df.l_shipdate <= cutoff]
    disc_price = sel.l_extendedprice * (1.0 - sel.l_discount)
    work = sel.assign(disc_price=disc_price,
                      charge=disc_price * (1.0 + sel.l_tax))
    return work.groupby(["l_returnflag", "l_linestatus"], sort=True).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"),
    ).reset_index()


def q6_pandas(df, year, discount, quantity):
    m = ((df.l_shipdate >= np.datetime64(f"{year}-01-01"))
         & (df.l_shipdate < np.datetime64(f"{year + 1}-01-01"))
         & (df.l_discount >= round((discount - 1) / 100.0, 2))
         & (df.l_discount <= round((discount + 1) / 100.0, 2))
         & (df.l_quantity < quantity)).to_numpy()
    return pd.DataFrame({"revenue": [float(np.sum(
        df.l_extendedprice.to_numpy()[m] * df.l_discount.to_numpy()[m]))]})


def as_answer(frame):
    return {"columns": list(frame.columns),
            "rows": [list(r) for r in frame.itertuples(index=False)]}


def test_last_ship_date_is_the_specs(data):
    assert data[1].l_shipdate.max() <= np.datetime64("1998-12-01")
    assert data[1].l_shipdate.max() > np.datetime64("1998-10-02")


def test_q1_binned_matches_pandas_for_every_delta(data):
    arrays, df = data
    query = traffic.load("queries", "tpch_q1")
    reference = tpch_q1_binned.Reference(arrays)
    answers = set()
    for params in traffic.all_params(query):
        got = reference.answer(params)
        gap = compare.answer_gap(query, got,
                                 as_answer(q1_pandas(df, params["DELTA"])))
        assert gap is not None and gap < 1e-12, (params, gap)
        answers.add(str(got["rows"]))
    assert len(answers) > 30  # DELTA changes the answer: the filter bites


def test_q6_binned_matches_pandas_for_every_combination(data):
    arrays, df = data
    query = traffic.load("queries", "tpch_q6")
    reference = tpch_q6_binned.Reference(arrays)
    combos = list(traffic.all_params(query))
    assert len(combos) == 80
    for params in combos:
        gap = compare.answer_gap(
            query, reference.answer(params),
            as_answer(q6_pandas(df, params["YEAR"], params["DISCOUNT"],
                                params["QUANTITY"])))
        assert gap is not None and gap < 1e-12, (params, gap)


@pytest.mark.parametrize("module, name", [(tpch_q1_binned, "tpch_q1"),
                                          (tpch_q6_binned, "tpch_q6")])
def test_float64_control_is_the_reference(data, module, name):
    """The control's own arithmetic is right: in float64 it IS the answer."""
    arrays, _ = data
    query = traffic.load("queries", name)
    reference = module.Reference(arrays)
    for params in list(traffic.all_params(query))[::7]:
        gap = compare.answer_gap(
            query, module.control_answer(arrays, params, "float64"),
            reference.answer(params))
        assert gap is not None and gap < 1e-12, (params, gap)


def test_rendered_sql():
    q1 = traffic.load("queries", "tpch_q1")
    q6 = traffic.load("queries", "tpch_q6")
    assert "l_shipdate <= DATE '1998-09-02' " in traffic.render(
        q1, {"DELTA": 90})
    sql = traffic.render(q6, {"YEAR": 1994, "DISCOUNT": 6, "QUANTITY": 24})
    assert "BETWEEN 0.05 AND 0.07" in sql and "< 24" in sql \
        and ">= DATE '1994-01-01' AND l_shipdate < DATE '1995-01-01'" in sql
    assert "DATE '1998-12-01' - INTERVAL '90' DAY" in traffic.render(
        traffic.load("queries", "tpch_q1_interval"), {"DELTA": 90})


def test_lineitem_is_the_whole_table(data):
    """All sixteen columns (clause 1.4.1) in both forms a configuration can
    hand over, with the domains of clause 4.2.3."""
    arrays, df = data
    table = tpch_lineitem.arrow_tables(arrays)["lineitem"]
    assert list(df.columns) == table.column_names == list(
        tpch_lineitem.COLUMNS) and len(df.columns) == 16
    assert table.to_pandas().equals(df.astype(table.to_pandas().dtypes))
    assert df.l_linenumber.between(1, 7).all()
    assert (df.groupby("l_orderkey").l_linenumber.max()
            == df.groupby("l_orderkey").size())[:-1].all()
    assert set(df.l_orderkey % 32) <= set(range(1, 9))
    assert df.l_comment.str.len().between(10, 43).all()
    assert df.l_comment.nunique() > 0.95 * len(df)
    assert set(df.l_shipmode) == set(tpch_lineitem.MODES)
    assert set(df.l_shipinstruct) == set(tpch_lineitem.INSTRUCTIONS)
    assert ((df.l_receiptdate - df.l_shipdate).dt.days.between(1, 30)).all()
    assert df.l_suppkey.between(1, 10_000).all()


def test_every_seed_sends_the_same_work_in_another_order():
    workload = traffic.load("workloads", "sf1_mix_wire")
    queries = traffic.queries_of(workload)
    size = int(workload["block"]) * sum(m["weight"] for m in workload["mix"])
    orders = set()
    for seed in (1, 2 ** 31 + 11, 77):
        stream = traffic.stream(workload, queries, seed, 0)
        names = [next(stream).query for _ in range(size * 5)]
        for i in range(0, len(names), size):
            assert sorted(names[i:i + size]) == sorted(
                m["query"] for m in workload["mix"]
                for _ in range(m["weight"] * workload["block"]))
        orders.add(tuple(names))
    assert len(orders) == 3
