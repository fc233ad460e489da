"""TPC-H Q18 through `compiled_join_aggregate` (physical/compiled_join.py):
the IN-subquery's aggregate build side reduced INSIDE the program (a
semi-join whose build side is unique on its key because it is an aggregate
grouped by it), its HAVING literal a runtime parameter, group keys on
CUSTOMER read through ORDERS' pointer, the top-100 inside the rung.

Tables come from the benchmark's own generator (`perfbench/datagen/
tpch_q18_tables.py`: the Q3 cell's, sparse order keys of clause 4.2.3) at
50,000 lineitems, the text from `perfbench.traffic`, the answers are held to
the plain reference (`perfbench/references/tpch_q18_topk.py`) through
`perfbench.compare.answer_gap`.  At that size no order's quantities pass
312..315, so the cases with rows use lower, test-only QUANTITY values.

Cost (ROADMAP D11): the module's tables 1.5 s once, one compile of 2.5 s for
all the Q18 cases on them; the shifted-keys, compacting, no-LIMIT, decline
and tests/tpch.py cases 1-3 s each (a compile of their own).
"""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.ops.grouping import RADIX_DOMAIN_LIMIT
from dask_sql_tpu.ops.join import bucket_rows
from dask_sql_tpu.physical import compiled_join as cj
from perfbench import compare, traffic
from perfbench.datagen import tpch_q18_tables
from perfbench.references import tpch_q18_topk
from perfbench.surfaces.library import frame_answer

ROWS = 50_000
QUERY = traffic.load("queries", "tpch_q18")
COUNTERS = ("join.build.semi", "join.build.whole", "join.build.eager",
            "aggregate.domain.wide", "aggregate.sum.codespace",
            "resilience.degraded")


@pytest.fixture(scope="module", autouse=True)
def result_cache_off():
    """The cell's `engine_config`, for this module alone (see
    test_q3_tables.py)."""
    with config_module.set({"serving.cache.enabled": False}):
        yield


def load(arrays):
    frames = tpch_q18_tables.arrow_tables(arrays)
    c = Context()
    for name in ("customer", "orders", "lineitem"):
        c.create_table(name, frames[name])
    return c


@pytest.fixture(scope="module")
def q18(result_cache_off):
    cj.PROGRAMS.clear()
    cj.LUTS.clear()
    arrays = tpch_q18_tables.generate(ROWS, seed=35, scale_factor=10)
    return load(arrays), arrays


def span_names(c):
    return [s.name for s in c.last_trace.spans]


def spans(c):
    return {s.name: s for s in c.last_trace.spans}


def counters(c):
    return {k: c.metrics.counter(k) for k in COUNTERS}


def held_to_reference(c, arrays, quantity):
    """Q18 at `quantity` from the rung, equal to the reference's answer in
    every cell (keys and the price as printed, the sum exactly)."""
    params = {"QUANTITY": quantity}
    frame = c.sql(traffic.render(QUERY, params)).compute()
    names = span_names(c)
    assert "rung:compiled_join_aggregate" in names, names
    want = tpch_q18_topk.Reference(arrays).answer(params)
    assert compare.answer_gap(QUERY, frame_answer(frame), want) == 0.0, \
        (quantity, frame)
    return frame, names


def test_all_four_quantities_share_one_executable(q18):
    """QUANTITY 312..315 (clause 2.4.18.3) and the test-only values below
    them: ONE `compile:` span, a family hit on every later request, no
    ladder step, no eager build side."""
    c, arrays = q18
    before = counters(c)
    compiles = hits = 0
    for quantity in (312, 313, 314, 315, 250, 220):
        frame, names = held_to_reference(c, arrays, quantity)
        compiles += sum(n.startswith("compile:") for n in names)
        hits += "family_hit" in names
        # both SUM(l_quantity), the semi-join's and the outer one, stay in
        # code space (the dictionary is the whole numbers 1..50)
        launch = [s for s in c.last_trace.spans if s.name == "launch"][-1]
        assert launch.attrs["sum_codespace"] == 2
    assert compiles == 1 and hits == 5
    assert len(frame) > 0  # 220: orders pass at this size
    moved = {k: c.metrics.counter(k) - v for k, v in before.items()}
    assert moved == {"join.build.semi": 1, "join.build.whole": 2,
                     "join.build.eager": 0, "aggregate.domain.wide": 0,
                     "aggregate.sum.codespace": 2, "resilience.degraded": 0}
    (program,) = cj.PROGRAMS.values()
    # CUSTOMER and the semi-join are both probed at ORDERS' rows; CUSTOMER's
    # group keys are read through ORDERS' pointer
    assert program.folded == {1: 0, 2: 0} and list(program.semis) == [2]
    assert program.gid_join == 0 and program.dependents == [1]
    assert program.topk["k"] == 100


@pytest.mark.parametrize("quantity,rows", [(100, 100), (150, 100), (200, 100),
                                           (250, None), (351, 0), (400, 0)])
def test_limit_cuts_or_having_ends_the_answer(q18, quantity, rows):
    """Low QUANTITY: more than 100 orders pass, the LIMIT cuts and ties are
    broken as the reference breaks them; past any order's sum (at most 7
    lines of 50): an empty answer with the right columns."""
    c, arrays = q18
    frame, names = held_to_reference(c, arrays, quantity)
    if rows is not None:
        assert len(frame) == rows
    assert list(frame.columns) == tpch_q18_topk.COLUMNS
    semi, tail = spans(c)["join:semi"].attrs, spans(c)["join:tail"].attrs
    orders = len(arrays["o_orderkey"])
    assert semi["groups"] == orders and semi["rows"] == ROWS
    assert semi["domain"] == int(arrays["o_orderkey"][-1]
                                 - arrays["o_orderkey"][0]) + 1
    assert semi["passed"] == tail["groups"] >= len(frame) == tail["rows"]
    assert semi["reused"] is True  # the key's range is kept per table version
    launch = [s for s in c.last_trace.spans if s.name == "launch"][-1]
    assert launch.attrs["semi"] == 1 and launch.attrs["joins"] == 3
    assert launch.attrs["domain"] == bucket_rows(orders)
    assert not [n for n in names if n.startswith("compile:")]


def test_key_range_past_the_radix_gate_is_admitted_by_its_bytes():
    """The upper half of the order keys moved up by 2^23: 12,000-odd orders
    on a range past `RADIX_DOMAIN_LIMIT`.  The semi-join's build side stays
    in the program (its `[domain]` state is some 100 MB), counted as a wide
    domain; a device budget that state does not fit declines it."""
    arrays = tpch_q18_tables.generate(ROWS, seed=36, scale_factor=10)
    shift = np.int64(1 << 23)
    middle = arrays["o_orderkey"][len(arrays["o_orderkey"]) // 2]
    for name in ("orderkey", "o_orderkey"):
        keys = arrays[name]
        arrays[name] = np.where(keys > middle, keys + shift, keys)
    cj.PROGRAMS.clear()
    c = load(arrays)
    before = counters(c)
    held_to_reference(c, arrays, 230)
    frame, _ = held_to_reference(c, arrays, 150)
    assert len(frame) == 100
    assert spans(c)["join:semi"].attrs["domain"] > RADIX_DOMAIN_LIMIT
    moved = {k: c.metrics.counter(k) - v for k, v in before.items()}
    assert moved["aggregate.domain.wide"] == 1
    assert moved["join.build.semi"] == 1 and moved["join.build.eager"] == 0
    # under a budget of 16 MB the rule declines that state and with it the
    # rung, as before PR 35: the interpreted converters answer, and right
    cj.PROGRAMS.clear()
    with config_module.set(
            {"analysis.estimate.device_budget_bytes": 16 << 20}):
        params = {"QUANTITY": 230}
        got = c.sql(traffic.render(QUERY, params)).compute()
    assert "rung:compiled_join_aggregate" not in span_names(c)
    assert c.metrics.counter("join.build.semi") - before["join.build.semi"] \
        == 1
    want = tpch_q18_topk.Reference(arrays).answer(params)
    assert compare.answer_gap(QUERY, frame_answer(got), want) == 0.0


def eager(c, sql):
    """The interpreted converters' answer."""
    want = c.sql(sql, config_options={"sql.compile.join_pipeline": False}
                 ).compute()
    assert "rung:compiled_join_aggregate" not in span_names(c)
    return want


def same_rows(got, want, by):
    got, want = (f.sort_values(by).reset_index(drop=True)
                 for f in (got, want))
    pd.testing.assert_frame_equal(got, want, check_dtype=False, rtol=1e-12)


Q18_NO_LIMIT = QUERY["sql"].split(" ORDER BY")[0]


def with_quantity(arrays, case):
    """The module's tables with ONE row of `l_quantity` changed: NULL, or
    the only value of its dictionary that is no whole number."""
    import pyarrow as pa

    frames = tpch_q18_tables.arrow_tables(arrays)
    lineitem = frames["lineitem"]
    quantity = lineitem.column("l_quantity").to_numpy().copy()
    at = ROWS // 3
    mask = np.zeros(ROWS, dtype=bool)
    if case == "quantity_null":
        mask[at] = True
    else:
        quantity[at] += 0.5
    frames["lineitem"] = lineitem.set_column(
        lineitem.schema.get_field_index("l_quantity"), "l_quantity",
        pa.array(quantity, mask=mask))
    c = Context()
    for name in ("customer", "orders", "lineitem"):
        c.create_table(name, frames[name])
    column = c.schema[c.schema_name].tables["lineitem"].table.columns[
        "l_quantity"]
    assert column.encoding.value == "DICT"
    assert (column.validity is not None) == (case == "quantity_null")
    return c


@pytest.mark.parametrize("case", ["no_limit", "compacting", "overflowing",
                                  "subquery_filter", "count_in_having",
                                  "quantity_null", "quantity_not_whole"])
def test_semi_join_program_equals_the_interpreted_converters(q18, case,
                                                             monkeypatch):
    """The same rules outside Q18's own text: without ORDER BY / LIMIT
    (every passing group leaves, CUSTOMER's keys taken on the host through
    the pointer the pack carries); with the compaction of the passing rows
    engaged (the floor lowered) and overflowing; with a WHERE inside the
    subquery; with two HAVING conjuncts, one on a COUNT; with one NULL in
    `l_quantity` (both sums stay in code space, the column's validity in
    their counts); with one quantity that is no whole number (both sums
    decline to the float64 scatter, `aggregate.sum.codespace` stands)."""
    c, arrays = q18
    cj.PROGRAMS.clear()
    sql = Q18_NO_LIMIT.format(QUANTITY=230)
    if case.startswith("quantity_"):
        c = with_quantity(arrays, case)
    if case in ("compacting", "overflowing"):
        monkeypatch.setattr(cj, "_COMPACT_MIN_ROWS", 1 << 12)
        # 230: a few hundred probe rows pass; 40: most of them do
        sql = QUERY["sql"].format(QUANTITY=230 if case == "compacting"
                                  else 40)
    elif case == "subquery_filter":
        sql = sql.replace("FROM lineitem GROUP BY",
                          "FROM lineitem WHERE l_linenumber < 7 GROUP BY")
    elif case == "count_in_having":
        sql = sql.replace("> 230)", "> 150 AND COUNT(*) = 7)")
    got = c.sql(sql).compute()
    names = span_names(c)
    assert "rung:compiled_join_aggregate" in names
    (program,) = cj.PROGRAMS.values()
    assert list(program.semis) == [2] and program.dependents == [1]
    engaged = 0 if case == "quantity_not_whole" else 2
    assert program.sum_codespace == engaged
    assert c.metrics.counter("aggregate.sum.codespace") >= engaged
    if case == "quantity_not_whole":
        assert c.metrics.counter("aggregate.sum.codespace") == 0
    if case in ("compacting", "overflowing"):
        tail = spans(c)["join:tail"].attrs
        assert program.compact_cap and tail["cap"] == program.compact_cap
        assert (tail["passed"] > tail["cap"]) == (case == "overflowing")
    else:
        assert program.topk is None and len(got) > 20
    want = eager(c, sql)
    same_rows(got, want, ["o_totalprice", "o_orderkey"])


DECLINED = {
    # the build side of the semi-join is no aggregate grouped by the key: a
    # plain filtered scan holds an order key once per passing line
    "semi_join_build_not_unique": (
        "SELECT o_orderkey, o_totalprice, SUM(l_quantity) AS q "
        "FROM orders, lineitem WHERE o_orderkey = l_orderkey "
        "AND o_orderkey IN (SELECT l_orderkey FROM lineitem "
        "WHERE l_quantity > 49) GROUP BY o_orderkey, o_totalprice", None),
    # grouped by two keys: unique on the pair, not on the join key
    "semi_join_build_grouped_by_two_keys": (
        "SELECT o_orderkey, SUM(l_quantity) AS q FROM orders, lineitem "
        "WHERE o_orderkey = l_orderkey AND o_orderkey IN ("
        "SELECT l_orderkey FROM lineitem GROUP BY l_orderkey, l_linenumber "
        "HAVING SUM(l_quantity) > 49) GROUP BY o_orderkey", None),
    # c_name is reached from LINEITEM's own column, not from ORDERS' row, so
    # ORDERS' pointer does not determine it: the radix plan is asked, and
    # declines a string key of 3,125 values beside an order key
    "group_key_on_a_build_side_not_reached_from_the_pointers": (
        "SELECT o_orderkey, c_name, SUM(l_quantity) AS q "
        "FROM lineitem, orders, customer WHERE l_orderkey = o_orderkey "
        "AND l_suppkey = c_custkey GROUP BY o_orderkey, c_name", 0),
}


@pytest.mark.parametrize("case", list(DECLINED))
def test_shapes_the_rules_decline_keep_todays_path(q18, case):
    """What the new rules do not take is answered as before, and right: no
    program with a semi-join build side or a dependent key is built."""
    c, arrays = q18
    sql, dependents = DECLINED[case]
    cj.PROGRAMS.clear()
    before = counters(c)
    got = c.sql(sql).compute()
    assert c.metrics.counter("join.build.semi") == before["join.build.semi"]
    for program in cj.PROGRAMS.values():
        assert not program.semis and not program.dependents
    assert len(got) > 0
    with config_module.set({"sql.compile": False}):
        want = c.sql(sql).compute()
    assert not [n for n in span_names(c) if n.startswith("rung:compiled")]
    same_rows(got, want, list(got.columns[:2]))


def test_tests_tpch_q18_on_sparse_keys_names_its_rung():
    """`tests/tpch.py` value-tests Q18 at 2,000 rows with QUANTITY 250 on
    the interpreted path's terms; here the same text and tables, and the
    rung that answered is named: the join rung, with the subquery inside."""
    from tests import tpch

    tables = tpch.generate()
    assert set(tables["orders"].o_orderkey % 32) <= set(range(1, 9))
    cj.PROGRAMS.clear()
    c = Context()
    for name in ("customer", "orders", "lineitem"):
        c.create_table(name, tables[name])
    # QUANTITY 250 passes nothing at 2,000 rows; 120 passes a handful
    for sql in (tpch.QUERIES[18], tpch.QUERIES[18].replace("> 250", "> 120")):
        got = c.sql(sql).compute()
        rungs = [n for n in span_names(c) if n.startswith("rung:")]
        assert rungs == ["rung:compiled_join_aggregate"], rungs
        assert "join:semi" in span_names(c)
        want = eager(c, sql)
        pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                      want.reset_index(drop=True),
                                      check_dtype=False, rtol=1e-12)
    assert len(got) > 0
