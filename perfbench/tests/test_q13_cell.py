"""The Q13 cell of the benchmark, at small sizes on the CPU: the Q13
generator's invariants, the reference against a pandas ``str.contains``
oracle for all sixteen (WORD1, WORD2) pairs, the generator's engine check,
one rehearsal of the cell, and the timed path broken two ways (an INNER join
for the LEFT OUTER one, the ON clause's mask dropped) coming out not
correct."""
import dataclasses

import numpy as np
import pytest

from perfbench import traffic
from perfbench.datagen import tpch_q3_tables, tpch_q13_tables
from perfbench.readers import roofline_tables
from perfbench.references import tpch_q13_counts
from perfbench.tests.test_run import drive, engine_config_restored  # noqa: F401

ROWS = 30_000
SEED = 2_147_483_713
FILES = [m["query"] for m in traffic.load("workloads",
                                          "sf10_q13_library")["mix"]]


@pytest.fixture(scope="module")
def data():
    arrays = tpch_q13_tables.generate(ROWS, SEED, scale_factor=10)
    tables = {name: table.to_pandas() for name, table in
              tpch_q13_tables.arrow_tables(arrays).items()}
    return arrays, tables


def test_orders_and_customer_follow_the_clause(data):
    _, t = data
    orders, customer = t["orders"], t["customer"]
    assert list(orders.columns) == list(tpch_q3_tables.ORDERS)
    assert list(customer.columns) == list(tpch_q3_tables.CUSTOMER)
    assert len(orders) == ROWS
    assert len(customer) == tpch_q13_tables.customers_for(ROWS, 10) == 3_000
    assert orders.o_orderkey.is_unique
    assert set(orders.o_orderkey % 32) == set(range(1, 9))
    assert (orders.o_custkey % 3 != 0).all()
    assert orders.o_custkey.between(1, len(customer)).all()
    assert orders.o_comment.str.len().between(19, 78).all()
    assert set(orders.o_orderstatus) == set(tpch_q3_tables.ORDER_STATUSES)
    assert orders.o_totalprice.between(1_000, 500_000).all()


def pandas_answer(t, word1, word2):
    orders, customer = t["orders"], t["customer"]
    kept = orders[~orders.o_comment.str.contains(f"{word1}.*{word2}",
                                                 regex=True)]
    counts = kept.o_custkey.value_counts().reindex(
        customer.c_custkey, fill_value=0)
    dist = counts.value_counts().rename_axis("c_count").reset_index(
        name="custdist").sort_values(["custdist", "c_count"],
                                     ascending=[False, False])
    return {"columns": ["c_count", "custdist"],
            "rows": [[int(a), int(b)] for a, b in
                     zip(dist.c_count, dist.custdist)]}


@pytest.mark.parametrize("name", FILES)
def test_reference_matches_a_pandas_str_contains(data, name):
    arrays, t = data
    query = traffic.load("queries", name)
    params = {k: v["low"] for k, v in query["parameters"].items()}
    word1 = tpch_q13_counts.WORD1[params["WORD1"]]
    word2 = tpch_q13_counts.WORD2[params["WORD2"]]
    assert f"NOT LIKE '%{word1}%{word2}%'" in query["sql"]
    reference = tpch_q13_counts.Reference(arrays)
    matched = reference.matched(word1, word2)
    assert np.array_equal(matched, t["orders"].o_comment.str.contains(
        f"{word1}.*{word2}", regex=True).to_numpy())
    assert reference.answer(params) == pandas_answer(t, word1, word2)
    # the control cannot fail: every number is a whole one under 2**24
    assert tpch_q13_counts.control_answer(arrays, params, "float32") \
        == reference.answer(params)


def test_every_pattern_matches_some_comments(data):
    """The pool's uniform words hold each pattern for some 0.05% of the
    comments: enough to move counts, so the exact compare sees a mask that
    was not applied."""
    arrays, _ = data
    reference = tpch_q13_counts.Reference(arrays)
    shares = [reference.matched(a, b).mean()
              for a in tpch_q13_counts.WORD1 for b in tpch_q13_counts.WORD2]
    assert 0.0001 < min(shares) and max(shares) < 0.002, shares


def test_an_occurrence_past_its_row_is_none():
    """A WORD1 that ends one comment and a WORD2 that starts the next
    ("x special", "requests y") match neither."""
    arrays = {"o_comment_length": np.array([9, 10]),
              "o_comment_text": np.frombuffer(
                  b"x specialrequests y", dtype=np.uint8),
              "o_custkey": np.array([1, 2]), "c_segment": np.zeros(2)}
    reference = tpch_q13_counts.Reference(arrays)
    assert not reference.matched("special", "requests").any()
    assert reference.answer({"WORD1": 0, "WORD2": 1})["rows"] == [[1, 2]]


def test_scan_bytes_of_the_two_tables():
    tables = {"orders": {"rows": 10, "itemsize": {
        "o_orderkey": 4, "o_custkey": 4, "o_comment": 4}},
        "customer": {"rows": 2, "itemsize": {"c_custkey": 4}}}
    query = traffic.load("queries", FILES[0])
    assert roofline_tables.scan_bytes(query, tables) == 120 + 8


def test_the_cell_rehearses_correct(capsys, monkeypatch):
    """Every phase of `sf10_q13_library` at 30,000 orders: one compile in
    set-up, the window on the join rung, every answer compared exactly."""
    result, phases = drive(capsys, monkeypatch, "sf10_q13_library",
                           rows=ROWS)
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["attempted"] > 16
    warm = [p for p in phases if p["phase"].startswith("warm:")]
    assert len(warm) == 32
    assert [p["compile_spans"] for p in warm if p["compile_spans"]] == \
        [["compile:compiled_join_aggregate"]]
    window = [p for p in phases if p["phase"] == "window"][0]
    assert window["rungs"] == ["rung:compiled_join_aggregate"]
    assert result["compared"]["answers_wrong"]["value"] == 0


def test_an_engine_without_outer_joins_fails_before_a_row_is_drawn(
        monkeypatch):
    """The parent commit's engine documents no `join.outer.preserved`: the
    generator raises at once instead of leaving the run in an eager
    compile."""
    from dask_sql_tpu.serving import metrics

    tpch_q13_tables.require_outer_joins()  # this tree: nothing raised
    monkeypatch.setattr(metrics, "DOCUMENTED_METRICS",
                        metrics.DOCUMENTED_METRICS - {"join.outer.preserved"})
    drawn = []
    monkeypatch.setattr(tpch_q13_tables, "_customers",
                        lambda *a: drawn.append(a))
    with pytest.raises(RuntimeError, match="join.outer.preserved"):
        tpch_q13_tables.generate(1_000, 1, 10)
    assert not drawn


def inner_join(monkeypatch):
    render = traffic.render
    monkeypatch.setattr(traffic, "render", lambda query, params: render(
        query, params).replace("LEFT OUTER JOIN", "JOIN"))


def mask_dropped(monkeypatch):
    from dask_sql_tpu.physical import compiled_join

    walk = compiled_join._walk_outer
    monkeypatch.setattr(compiled_join, "_walk_outer", lambda node, ext: walk(
        dataclasses.replace(node, filter=None), ext))


@pytest.mark.parametrize("break_it", [inner_join, mask_dropped])
def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch, break_it):
    from dask_sql_tpu.physical import compiled_join

    compiled_join.PROGRAMS.clear()
    break_it(monkeypatch)
    try:
        result, _ = drive(capsys, monkeypatch, "sf10_q13_library", rows=ROWS)
    finally:
        compiled_join.PROGRAMS.clear()
    assert result["correct"] is False
    wrong = result["compared"]["answers_wrong"]["value"]
    assert wrong == result["attempted"] > 0, result["compared"]
