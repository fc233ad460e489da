"""What PR 35 added to the benchmark, at small sizes on the CPU: the Q18
reference against a plain pandas merge (the LIMIT cutting, none passing, the
clause's own QUANTITY values), its float32 control through the harness's own
comparison, the generator's engine check, and one rehearsal of the cell."""
import numpy as np
import pandas as pd
import pytest

from perfbench import compare, run, traffic
from perfbench.datagen import tpch_q3_tables, tpch_q18_tables
from perfbench.readers import roofline_tables
from perfbench.references import tpch_q18_topk
from perfbench.tests.test_run import drive, engine_config_restored  # noqa: F401

ROWS = 60_000
SEED = 2_147_483_693
QUERY = traffic.load("queries", "tpch_q18")


@pytest.fixture(scope="module")
def data():
    arrays = tpch_q18_tables.generate(ROWS, SEED, scale_factor=10)
    tables = {name: table.to_pandas() for name, table in
              tpch_q18_tables.arrow_tables(arrays).items()}
    return arrays, tables


def test_the_tables_are_the_q3_cells(data):
    """Same seed, same rows: every array `tpch_q3_tables.generate` returns."""
    arrays, _ = data
    q3 = tpch_q3_tables.generate(ROWS, SEED, scale_factor=10)
    assert set(q3) == set(arrays)
    for name, values in q3.items():
        assert np.array_equal(arrays[name], values), name


def q18_pandas(tables, quantity):
    """Q18 as a pandas merge, ordered as the clause orders it and then by the
    order key."""
    lineitem, orders, customer = (tables[n] for n in
                                  ("lineitem", "orders", "customer"))
    total = lineitem.groupby("l_orderkey").l_quantity.sum()
    large = total[total > quantity].index
    joined = lineitem[lineitem.l_orderkey.isin(large)].merge(
        orders, left_on="l_orderkey", right_on="o_orderkey").merge(
        customer, left_on="o_custkey", right_on="c_custkey")
    out = joined.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                          "o_totalprice"], as_index=False).l_quantity.sum()
    out = out.rename(columns={"l_quantity": "SUM"}).sort_values(
        ["o_totalprice", "o_orderdate", "o_orderkey"],
        ascending=[False, True, True], kind="stable")
    return out.head(100).reset_index(drop=True)


@pytest.mark.parametrize("quantity", [312, 315, 250, 200, 100, 351])
def test_q18_topk_matches_a_pandas_merge(data, quantity):
    arrays, tables = data
    frame = q18_pandas(tables, quantity)
    want = {"columns": list(frame.columns), "rows": [
        list(r) for r in frame.itertuples(index=False)]}
    got = tpch_q18_topk.Reference(arrays).answer({"QUANTITY": quantity})
    assert compare.answer_gap(QUERY, got, want) == 0.0, quantity
    assert len(got["rows"]) == {100: 100, 200: 100, 351: 0}.get(
        quantity, len(got["rows"]))
    assert traffic.render(QUERY, {"QUANTITY": quantity}).count(
        f"> {quantity})") == 1


def test_float32_control_is_not_correct_by_the_price(data):
    """The reference in float32 in the engine's place, through the harness's
    own comparison, on requests whose answers hold rows (QUANTITY low enough
    for this size): not within the limits, by `rel_err.tpch_q18`
    (`o_totalprice`, which float32 cannot hold; the sums of small whole
    numbers stay exact); in float64 the control is the reference.  A window whose answers are all empty would
    pass the control: at the cell's size every answer holds 30-50 rows."""
    arrays, _ = data
    reference = tpch_q18_topk.Reference(arrays)
    records = [{"query": "tpch_q18", "params": {"QUANTITY": quantity},
                "answer": reference.answer({"QUANTITY": quantity}),
                "spans": ["rung:compiled_join_aggregate"]}
               for quantity in (200, 230, 250)]
    assert all(r["answer"]["rows"] for r in records)
    queries, references = {"tpch_q18": QUERY}, {"tpch_q18": reference}
    sound = compare.compare_window([dict(r) for r in records], queries,
                                   references, {})
    assert sound["within"] is True
    verdict = run.control_verdict("float32", records, queries, references,
                                  arrays, {})
    assert verdict["within"] is False and verdict["parameter_sets"] == 3
    over = [k for k, c in verdict["compared"].items()
            if c["value"] > c["limit"]]
    assert "rel_err.tpch_q18" in over
    assert verdict["compared"]["rel_err.tpch_q18"]["value"] > 1e5 * \
        QUERY["limits"]["rel_err"]
    for record in records:
        same = tpch_q18_topk.control_answer(arrays, record["params"],
                                            "float64")
        assert compare.answer_gap(QUERY, same, record["answer"]) == 0.0
        quantities = [row[-1] for row in tpch_q18_topk.control_answer(
            arrays, record["params"], "float32")["rows"]]
        assert quantities == [row[-1] for row in record["answer"]["rows"]]


def test_scan_bytes_of_the_three_tables():
    tables = {"lineitem": {"rows": 10, "itemsize": {"l_orderkey": 4,
                                                    "l_quantity": 2}},
              "orders": {"rows": 4, "itemsize": {
                  "o_orderkey": 4, "o_custkey": 4, "o_totalprice": 8,
                  "o_orderdate": 2}},
              "customer": {"rows": 2, "itemsize": {"c_custkey": 4,
                                                   "c_name": 4}}}
    assert roofline_tables.scan_bytes(QUERY, tables) == 60 + 72 + 16


def test_the_cell_rehearses_correct(capsys, monkeypatch):
    """Every phase of `sf10_q18_library` at 40,000 lineitems: one compile in
    set-up, the window on the join rung, every (empty, at this size) answer
    compared."""
    result, phases = drive(capsys, monkeypatch, "sf10_q18_library",
                           rows=40_000)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 5
    warm = {p["phase"]: p for p in phases if p["phase"].startswith("warm:")}
    assert warm["warm:tpch_q18:cold"]["compile_spans"] == \
        ["compile:compiled_join_aggregate"]
    assert warm["warm:tpch_q18:warm"]["compile_spans"] == []
    assert warm["warm:tpch_q18:warm"]["family_hit"] is True
    window = [p for p in phases if p["phase"] == "window"][0]
    assert window["rungs"] == ["rung:compiled_join_aggregate"]
    assert set(result["compared"]) == {
        "rel_err.tpch_q18", "answers_wrong", "requests_failed",
        "ladder_step_downs", "not_on_compiled_rung"}


def test_an_engine_without_semi_join_builds_fails_before_a_row_is_drawn(
        monkeypatch):
    """The parent commit's engine documents no `join.build.semi`: the
    generator raises at once instead of leaving the run in an eager compile."""
    from dask_sql_tpu.serving import metrics

    tpch_q18_tables.require_semi_join_builds()  # this tree: nothing raised
    monkeypatch.setattr(metrics, "DOCUMENTED_METRICS",
                        metrics.DOCUMENTED_METRICS - {"join.build.semi"})
    drawn = []
    monkeypatch.setattr(tpch_q3_tables, "generate",
                        lambda *a, **k: drawn.append(a))
    with pytest.raises(RuntimeError, match="join.build.semi"):
        tpch_q18_tables.generate(1_000, 1, 10)
    assert not drawn
