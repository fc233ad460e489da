"""Whole build sides read padded to their bucket (`ops/join.py::bucket_rows`)
by the join rung's program: the answers stay the reference's, and a new
ORDERS version in the same bucket compiles nothing.

The pads are chosen to collide: a padded column repeats its last row, so a
pad row carries a real order key, customer and date, and only the
program's masks keep it out; ORDERS without its last orders leaves those
orders' lines with keys past the LUT's range, in the slots its bucket adds.
Tables: the benchmark's generator (`perfbench/datagen/tpch_q3_tables.py`,
the Q3 and Q18 cells' three tables) at 50,000 lineitems, ORDERS grown by
orders no line references (new keys, the other columns copied) or cut.
Cost: the tables 1.5 s once, a compile of 1-2 s per program, the
interpreted converters' answers 3-5 s each.
"""
import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.ops import join as join_ops
from dask_sql_tpu.ops.join import bucket_rows
from dask_sql_tpu.physical import compiled_join as cj
from dask_sql_tpu.serving import compile_cache
from perfbench import compare, traffic
from perfbench.datagen import tpch_q3_tables
from perfbench.references import tpch_q3_topk, tpch_q18_topk
from perfbench.surfaces.library import frame_answer

ROWS = 50_000
Q3 = traffic.load("queries", "tpch_q3_household")
Q18 = traffic.load("queries", "tpch_q18")
#: Q18 without ORDER BY / LIMIT, its HAVING accepting every group
Q18_ALL = Q18["sql"].split(" ORDER BY")[0].replace("> {QUANTITY}", ">= 0")


@pytest.fixture(scope="module")
def tables():
    """(arrays, arrow tables) at 50,000 lineitems, the result cache off as
    in the cells' configuration."""
    with config_module.set({"serving.cache.enabled": False}):
        arrays = tpch_q3_tables.generate(ROWS, seed=33, scale_factor=10)
        yield arrays, tpch_q3_tables.arrow_tables(arrays)


def orders_version(orders: pa.Table, case: str) -> pa.Table:
    """ORDERS grown by 37 orders that no line references (keys past the
    highest, the other columns copied from the first orders), or without
    its last 5 orders."""
    if case == "cut":
        return orders.slice(0, orders.num_rows - 5)
    extra = orders.slice(0, 37)
    at = orders.schema.get_field_index("o_orderkey")
    keys = orders.column(at).to_numpy()
    extra = extra.set_column(at, "o_orderkey", pa.array(
        keys.max() + 1 + np.arange(37), type=orders.schema.field(at).type))
    return pa.concat_tables([orders, extra])


def load(frames, case: str) -> Context:
    c = Context()
    for name in ("customer", "orders", "lineitem"):
        frame = frames[name]
        c.create_table(name, frame if name != "orders" or case == "base"
                       else orders_version(frame, case))
    return c


def span(c, name):
    return {s.name: s for s in c.last_trace.spans}[name]


def eager(c, sql):
    """The interpreted converters' answer."""
    want = c.sql(sql, config_options={"sql.compile.join_pipeline": False}
                 ).compute()
    assert "rung:compiled_join_aggregate" not in \
        [s.name for s in c.last_trace.spans]
    return want


@pytest.mark.parametrize("fold", ["whole", "in_parts"])
@pytest.mark.parametrize("case", ["grown", "cut"])
@pytest.mark.parametrize("query", ["q3", "q18"])
def test_answers_with_colliding_pads(tables, query, case, fold, monkeypatch):
    """Q3 and Q18 on an ORDERS version that sits inside its bucket: the
    program reads ORDERS and CUSTOMER padded (`join.build.padded` 2 for
    each program), the pad rows repeat real keys, and the answer is the
    reference's (ORDERS grown: the grown orders have no line, so the
    answer is the untouched tables') or the interpreted converters' (ORDERS
    cut: the cut orders' lines find no row, through slots past the range).
    `in_parts`: the LUT folds run as the loops over parts (`by_parts`)
    that the cells' table sizes engage, here at 50,000 lineitems."""
    arrays, frames = tables
    cj.PROGRAMS.clear()
    cj.LUTS.clear()
    loops = []
    if fold == "in_parts":
        monkeypatch.setattr(join_ops, "_PART_FLOOR", 1 << 4)
        real_map = jax.lax.map
        monkeypatch.setattr(jax.lax, "map", lambda f, xs: loops.append(
            xs.shape) or real_map(f, xs))
    c = load(frames, case)
    before = c.metrics.counter("join.build.padded")
    query_file = Q3 if query == "q3" else Q18
    params = {"DAY": 12, "SEGMENT": 3} if query == "q3" \
        else {"QUANTITY": 220}
    sql = traffic.render(query_file, params)
    got = c.sql(sql).compute()
    # the LUTs a filter or a folded join narrows, each in parts: Q3's two
    # (ORDERS by date and CUSTOMER, CUSTOMER by segment), Q18's ORDERS
    assert len(loops) == (0 if fold == "whole" else 2 if query == "q3"
                          else 1), loops
    assert "rung:compiled_join_aggregate" in \
        [s.name for s in c.last_trace.spans]
    assert c.metrics.counter("join.build.padded") - before == 2
    assert span(c, "join:build").attrs["padded"] == 2

    table = c.schema[c.schema_name].tables["orders"].table
    orders = table.num_rows
    (program,) = cj.PROGRAMS.values()
    assert program.build_rows[:2] == [bucket_rows(orders),
                                      bucket_rows(len(arrays["c_segment"]))]
    assert program.build_rows[0] > orders
    # ORDERS' key as the program reads it: the pad rows repeat the last key
    uid = c.schema[c.schema_name].tables["orders"].uid
    padded = cj.padded_column(uid, table.select(["o_orderkey"]), 0)
    keys = np.asarray(padded.data)
    assert len(keys) == bucket_rows(orders)
    assert (keys[orders:] == keys[orders - 1]).all()
    if case == "cut":
        # the cut orders' lines carry keys past the LUT's range, inside
        # the slots of its bucket
        rmin, lut = program.luts[0]
        top = int(arrays["o_orderkey"][-6])
        cut_keys = arrays["o_orderkey"][-5:]
        assert (cut_keys > top).all()
        assert (cut_keys - rmin < lut.shape[0]).all()
        assert (np.asarray(lut)[cut_keys - rmin] == -1).all()
        want = eager(c, sql)
        pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                      want.reset_index(drop=True),
                                      check_dtype=False, rtol=1e-12)
        assert len(got) > 0
    else:
        reference = (tpch_q3_topk if query == "q3" else tpch_q18_topk
                     ).Reference(arrays)
        gap = compare.answer_gap(query_file, frame_answer(got),
                                 reference.answer(params))
        assert gap is not None and gap <= query_file["limits"]["rel_err"], \
            (gap, got)
        assert len(got) > 0


def test_having_that_accepts_zero_keeps_no_pad_group(tables):
    """`HAVING SUM(l_quantity) >= 0` keeps every group that has a row and
    no other: the semi-join's state covers the order keys' range at its
    bucket, and a value past the range (or between two orders) has no row,
    so it is never present.  Every order with lines passes, no more."""
    arrays, frames = tables
    cj.PROGRAMS.clear()
    cj.LUTS.clear()
    c = load(frames, "grown")
    got = c.sql(Q18_ALL).compute()
    semi = span(c, "join:semi").attrs
    lines = len(np.unique(arrays["orderkey"]))
    domain = int(arrays["orderkey"].max() - arrays["orderkey"].min()) + 1
    (program,) = cj.PROGRAMS.values()
    assert program.semis[2]["domain"] == bucket_rows(domain) > domain
    assert semi["domain"] == domain
    assert semi["groups"] == semi["passed"] == lines == len(got)
    want = eager(c, Q18_ALL)
    by = ["o_orderkey"]
    got, want = (f.sort_values(by).reset_index(drop=True)
                 for f in (got, want))
    assert got.o_orderkey.tolist() == want.o_orderkey.tolist()
    np.testing.assert_array_equal(got.iloc[:, -1].to_numpy(),
                                  want.iloc[:, -1].to_numpy())


def test_new_orders_version_in_its_bucket_compiles_nothing(tables, tmp_path,
                                                           monkeypatch):
    """Q18 on ORDERS, then on ORDERS grown by 37 rows (the same buckets),
    in a persistent compile cache with every in-memory cache cleared
    between: the new version's first request loads every executable it
    needs from the persistent cache (`xla:compile` spans, `cache` `hit`)
    and compiles none (no `miss`)."""
    _, frames = tables
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    compile_cache.disable()
    assert compile_cache.enable(str(tmp_path / "cc"))
    try:
        cj.PROGRAMS.clear()
        cj.LUTS.clear()
        jax.clear_caches()  # every executable compiles into the cache
        c = load(frames, "base")
        sql = traffic.render(Q18, {"QUANTITY": 220})
        first = c.sql(sql).compute()
        cold = [s.attrs["cache"] for s in c.last_trace.spans
                if s.name == "xla:compile"]
        assert "miss" in cold
        orders = c.schema[c.schema_name].tables["orders"].table.num_rows
        cj.PROGRAMS.clear()
        cj.LUTS.clear()
        c.create_table("orders", orders_version(frames["orders"], "grown"))
        assert bucket_rows(orders + 37) == bucket_rows(orders)
        jax.clear_caches()
        again = c.sql(sql).compute()
        names = [s.name for s in c.last_trace.spans]
        assert "rung:compiled_join_aggregate" in names
        assert "compile:compiled_join_aggregate" in names  # a new program
        warm = [s.attrs["cache"] for s in c.last_trace.spans
                if s.name == "xla:compile"]
        assert warm and set(warm) == {"hit"}, warm
        assert again.reset_index(drop=True).equals(
            first.reset_index(drop=True))
    finally:
        compile_cache.disable()
