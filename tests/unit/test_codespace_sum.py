"""A SUM / AVG whose argument is a DICT column of whole numbers is summed as
int32, in code space (ISSUE 36; `physical/compiled.py::codespace_sum`,
`WholeSum`, `SegmentReducer.sum_whole`).

Every case runs the same SQL twice on one table through `compiled_aggregate`:
as built, and with the mechanism's test forced false (today's decode and
float64 / int64 scatter), and holds the two answers equal TO THE BIT.  What
engaged is read three ways: the counter `aggregate.sum.codespace`, the
`launch` span's `sum_codespace`, and the census of scatters in the program's
lowered text (operand dtype per scatter).  Off the chip `auto` resolves to
`scatter` at every domain, so the cases need no configuration.

Cost: one 4,096-row table once, two sub-second compiles a case.
"""
import re

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.columnar.encodings import Encoding
from dask_sql_tpu.physical import compiled

ROWS = 4096
#: ROWS x 2^19 is 2^31: the first dictionary value whose worst case (every
#: row in one group) no longer fits an int32
TOO_BIG = float((1 << 31) // ROWS)


def _table() -> pa.Table:
    rng = np.random.RandomState(36)
    quantity = rng.randint(1, 51, ROWS).astype(np.float64)
    nulls = rng.rand(ROWS) < 0.15
    one_off = quantity.copy()
    one_off[17] = 2.5
    return pa.table({
        "k": pa.array(rng.randint(0, 300, ROWS).astype(np.int64)),
        "flag": pa.array(rng.choice(["A", "N", "R"], ROWS)),
        "quantity": pa.array(quantity),  # 1..50: affine, step 1
        "quantity_nulls": pa.array(quantity, mask=nulls),
        "negative": pa.array(  # -60..12 by threes: affine, step 3
            rng.randint(-20, 5, ROWS).astype(np.float64) * 3),
        "powers": pa.array(2.0 ** rng.randint(0, 8, ROWS)),  # not affine
        "code": pa.array(  # an integer dictionary; its SUM is a BIGINT
            (rng.randint(0, 40, ROWS) ** 2 - 300).astype(np.int64)),
        "discount": pa.array(rng.randint(0, 11, ROWS) / 100.0),
        "one_off": pa.array(one_off),  # ONE value of 4,096 is not whole
        "fits": pa.array(rng.choice([1.0, TOO_BIG - 1], ROWS)),
        "too_big": pa.array(rng.choice([1.0, TOO_BIG], ROWS)),
    })


@pytest.fixture(scope="module")
def ctx():
    with config_module.set({"serving.cache.enabled": False}):
        c = Context()
        c.create_table("t", _table())
        t = c.schema[c.schema_name].tables["t"].table
        for name in t.column_names:
            if name not in ("k", "flag"):
                assert t.columns[name].encoding is Encoding.DICT, name
        assert t.columns["quantity"].validity is None
        assert t.columns["quantity_nulls"].validity is not None
        yield c


SCATTER = re.compile(r'"stablehlo\.scatter"\(.*?\}\) : \(tensor<\d+x(\w+)>, '
                     r"tensor<\d+x1xi32>", re.S)


def run(c, sql, monkeypatch, forced_false=False, options=None):
    """``(frame, scatter operand dtypes sorted, counter moved, launch span's
    attrs, lowered text)`` of `sql` on a freshly built program."""
    seen = []
    real = compiled.CompiledAggregate.run

    def spy(self, table=None, params=()):
        seen.append((self, table, params))
        return real(self, table, params)

    with monkeypatch.context() as mp:
        mp.setattr(compiled.CompiledAggregate, "run", spy)
        if forced_false:
            mp.setattr(compiled, "codespace_sum", lambda *a, **kw: None)
        compiled.PROGRAMS.clear()
        before = c.metrics.counter("aggregate.sum.codespace")
        frame = c.sql(sql, config_options=options).compute()
        moved = c.metrics.counter("aggregate.sum.codespace") - before
        assert "rung:compiled_aggregate" in [
            s.name for s in c.last_trace.spans], sql
        launch = [s for s in c.last_trace.spans if s.name == "launch"][-1]
        program, table, params = seen[-1]
        text = program._fn.lower(
            tuple(table.columns[n].data for n in table.column_names),
            tuple(table.columns[n].validity for n in table.column_names),
            table.row_valid, tuple(params)).as_text()
    return frame, sorted(SCATTER.findall(text)), moved, launch.attrs, text


def same_bits(got: pd.DataFrame, want: pd.DataFrame):
    assert list(got.columns) == list(want.columns) and len(got) == len(want)
    for name in got.columns:
        a, b = got[name].to_numpy(), want[name].to_numpy()
        assert a.dtype == b.dtype, (name, a.dtype, b.dtype)
        if a.dtype.kind == "f":
            a, b = a.view(np.int64), b.view(np.int64)
        assert np.array_equal(a, b), name


#: case -> (aggregates, how many of them engage, the 64-bit sum scatters
#: that become int32, today's scatters beside the count of the rows).  A SUM
#: and an AVG of one argument share one sum and one count
ENGAGED = {
    "sum_and_avg": ("SUM(quantity) AS s, AVG(quantity) AS a", 2, 1, 2),
    # the column's validity keeps the argument's own count; the sum is int32
    "nulls_in_the_column": (
        "SUM(quantity_nulls) AS s, AVG(quantity_nulls) AS a", 2, 1, 2),
    "filter_clause": (
        "SUM(quantity) FILTER (WHERE negative < 0) AS s, "
        "AVG(quantity_nulls) FILTER (WHERE flag = 'N') AS a", 2, 2, 4),
    "negative_whole_numbers": (
        "SUM(negative) AS s, AVG(negative) AS a", 2, 1, 2),
    "table_gather": ("SUM(powers) AS s, AVG(powers) AS a", 2, 1, 2),
    # today `sum_int` scatters int64 once for the SUM and once for the AVG
    "integer_dictionary": ("SUM(code) AS s, AVG(code) AS a", 2, 2, 2),
    "under_the_bound": ("SUM(fits) AS s", 1, 1, 2),
    "beside_a_declined_sum": (
        "SUM(quantity) AS s, SUM(discount) AS d, MIN(quantity) AS m, "
        "COUNT(quantity) AS n", 1, 1, 5),
}


@pytest.mark.parametrize("case", list(ENGAGED))
def test_whole_number_dictionary_is_summed_as_int32(ctx, monkeypatch, case):
    aggs, engaged, narrowed, scatters = ENGAGED[case]
    sql = f"SELECT k, {aggs}, COUNT(*) AS c FROM t GROUP BY k"
    got, dtypes, moved, launch, text = run(ctx, sql, monkeypatch)
    want, was, none, launch_was, _ = run(ctx, sql, monkeypatch,
                                         forced_false=True)
    same_bits(got.sort_values("k").reset_index(drop=True),
              want.sort_values("k").reset_index(drop=True))
    assert len(got) == 300
    assert moved == launch["sum_codespace"] == engaged
    assert none == 0 and "sum_codespace" not in launch_was
    assert len(was) == scatters + 1, was

    def wide(dtypes):
        return sum(d in ("f64", "i64") for d in dtypes)

    # those sums are int32 now, and never more scatters than before
    assert wide(was) - wide(dtypes) == narrowed, (dtypes, was)
    assert len(dtypes) <= len(was), (dtypes, was)
    if case == "sum_and_avg":  # the sum, and ONE count: the rows'
        assert dtypes == ["i32", "i32"] and was == ["f64", "i32", "i32"]
    if case == "nulls_in_the_column":  # two counts stay
        assert dtypes == ["i32"] * 3 and was == ["f64", "i32", "i32"]
    if case == "table_gather":
        assert "dense<[1, 2, 4, 8, 16, 32, 64, 128]> : tensor<8xi32>" in text
    if case == "integer_dictionary":
        assert got["s"].dtype == np.int64 and dtypes == ["i32", "i32"]


def test_global_sum_and_empty_groups(ctx, monkeypatch):
    """No GROUP BY (one group holds every row: the bound's worst case), and
    a FILTER that leaves groups without a row (SUM and AVG are NULL there)."""
    for sql in (
            "SELECT SUM(quantity) AS s, AVG(powers) AS a, SUM(fits) AS f "
            "FROM t",
            "SELECT k, SUM(quantity) FILTER (WHERE k > 250) AS s, "
            "AVG(negative) FILTER (WHERE k > 250) AS a FROM t GROUP BY k"):
        got, dtypes, moved, _, _ = run(ctx, sql, monkeypatch)
        want, _, _, _, _ = run(ctx, sql, monkeypatch, forced_false=True)
        by = [c for c in got.columns if c == "k"]
        if by:
            got, want = (f.sort_values(by).reset_index(drop=True)
                         for f in (got, want))
            assert got["s"].isna().sum() > 200
        same_bits(got, want)
        assert moved >= 2 and "f64" not in dtypes
    t = _table().to_pandas()
    got, *_ = run(ctx, "SELECT SUM(fits) AS f FROM t", monkeypatch)
    assert got["f"][0] == t["fits"].sum() and got["f"][0] > 1 << 30


DECLINED = {
    # l_discount's: hundredths
    "dictionary_of_fractions": "SUM(discount) AS s, AVG(discount) AS a",
    "one_value_is_not_whole": "SUM(one_off) AS s, AVG(one_off) AS a",
    # ROWS x max|value| reaches 2^31: every row in one group would wrap
    "rows_times_max_reaches_2_31": "SUM(too_big) AS s, AVG(too_big) AS a",
    # an expression is no raw column reference
    "argument_is_an_expression": "SUM(quantity * 2) AS s, AVG(-quantity) AS a",
    # not a SUM or an AVG
    "other_aggregates": "MIN(quantity) AS s, VAR_POP(quantity) AS a",
}


@pytest.mark.parametrize("case", list(DECLINED))
def test_declined_keeps_todays_program(ctx, monkeypatch, case):
    sql = f"SELECT k, {DECLINED[case]} FROM t GROUP BY k"
    got, dtypes, moved, launch, text = run(ctx, sql, monkeypatch)
    want, was, _, _, text_was = run(ctx, sql, monkeypatch, forced_false=True)
    assert moved == 0 and "sum_codespace" not in launch
    assert text == text_was and dtypes == was
    if case != "other_aggregates":  # the sum scatters stay float64
        assert dtypes.count("f64") >= 1, dtypes
    same_bits(got, want)


def test_matmul_mode_keeps_the_lowered_text(ctx, monkeypatch):
    """Q1's shape in `matmul` mode (what `auto` resolves to on the chip at
    domains up to 2048): the column rides the one blocked matmul with every
    other sum, so the program's text is the one built with the mechanism's
    test forced false, to the letter, and nothing is counted."""
    sql = ("SELECT flag, SUM(quantity) AS s, AVG(quantity) AS a, "
           "SUM(discount * quantity) AS d, COUNT(*) AS n FROM t "
           "WHERE negative <= 9 GROUP BY flag")
    matmul = {"sql.compile.segsum": "matmul"}
    got, dtypes, moved, launch, text = run(ctx, sql, monkeypatch,
                                           options=matmul)
    _, _, _, _, text_was = run(ctx, sql, monkeypatch, forced_false=True,
                               options=matmul)
    assert text == text_was and dtypes == []  # no scatter: the matmul
    assert moved == 0 and "sum_codespace" not in launch
    # the same text in scatter mode engages, and agrees to the matmul's bound
    exact, dtypes, moved, _, _ = run(ctx, sql, monkeypatch)
    assert moved == 2 and dtypes.count("i32") == 3
    for f in (got, exact):
        f.sort_values("flag", inplace=True)
    assert np.array_equal(got["s"].to_numpy(), exact["s"].to_numpy())
    assert np.array_equal(got["n"].to_numpy(), exact["n"].to_numpy())


@pytest.mark.parametrize("values,plan", [
    (np.arange(1.0, 51.0), (None, 1, 1, "float64")),
    (np.array([7.0]), (None, 0, 7, "float64")),
    (np.array([-60.0, -57.0, -54.0]), (None, 3, -60, "float64")),
    (np.array([1.0, 2.0, 4.0]), ([1, 2, 4], 1, 1, "float64")),
    (np.array([3, 5, 9], dtype=np.int64), ([3, 5, 9], 2, 3, "int64")),
    (np.array([1.0, 2.5]), None),
    (np.array([1.0, np.inf]), None),
    (np.array([1.0, np.nan]), None),
    (np.array([1.0, 2.0], dtype=np.float32), None),  # today's sum is float32
    (np.array([1.0, TOO_BIG]), None),
    (np.array([-TOO_BIG, 1.0]), None),
])
def test_codespace_sum_reads_the_dictionary(values, plan):
    """`codespace_sum` on the host: the plan it returns for a dictionary over
    ROWS rows in `scatter` mode, and None in any other mode."""
    from dask_sql_tpu.columnar.column import Column
    from dask_sql_tpu.columnar.dtypes import SqlType
    from dask_sql_tpu.columnar.table import Table
    from dask_sql_tpu.planner.expressions import AggExpr, ColumnRef

    sql_type = SqlType.BIGINT if values.dtype.kind == "i" else \
        SqlType.FLOAT if values.dtype == np.float32 else SqlType.DOUBLE
    col = Column(np.zeros(ROWS, dtype=np.int8), sql_type, None, None,
                 encoding=Encoding.DICT, enc_values=values)
    ev = compiled._TraceEval(Table({"x": col}, ROWS))
    arg = ColumnRef(0, "x", sql_type, False)
    for func in ("sum", "avg"):
        a = AggExpr(func, (arg,), sql_type)
        got = compiled.codespace_sum(ev, a, "scatter", ROWS)
        if plan is None:
            assert got is None
            continue
        table, step, base, dtype = plan
        assert (got.step, got.base, got.dtype) == (step, base,
                                                   np.dtype(dtype))
        if table is None:
            assert got.table is None
        else:
            assert got.table.dtype == np.int32 and list(got.table) == table
        assert np.array_equal(
            np.asarray(got.values(np.arange(len(values), dtype=np.int8))),
            values.astype(np.int32))
        for mode in ("matmul", "pallas"):
            assert compiled.codespace_sum(ev, a, mode, ROWS) is None
        # one more row than the bound admits
        top = int(np.abs(values).max())
        if top:
            assert compiled.codespace_sum(
                ev, a, "scatter", -(-(1 << 31) // top)) is None
    assert compiled.codespace_sum(
        ev, AggExpr("min", (arg,), sql_type), "scatter", ROWS) is None


def test_mesh_reducer_bounds_the_whole_tables_rows():
    """On a mesh every shard's int32 sums meet in ONE `psum`, so the bound is
    over the rows of all shards: `SpmdSegmentReducer.total_rows` is its
    shard's rows times the mesh's width, and the int32 scatter it wraps
    returns the whole table's sums."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from dask_sql_tpu.parallel.mesh import AXIS, make_mesh
    from dask_sql_tpu.spmd.aggregate import SpmdSegmentReducer

    mesh = make_mesh()
    width = len(mesh.devices)
    seen = []

    def body(gid, values):
        reducer = SpmdSegmentReducer(gid, 4, gid.shape[0])
        seen.append((reducer.n_rows, reducer.total_rows))
        h = reducer.sum_whole(values, jnp.ones(gid.shape, dtype=bool),
                              np.dtype(np.float64))
        return reducer.get(h)

    rows = 8 * width
    gid = jnp.arange(rows, dtype=jnp.int32) % 4
    values = jnp.arange(rows, dtype=jnp.int32) - 5
    got = shard_map(body, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
                    out_specs=P(), check_vma=False)(gid, values)
    assert seen == [(8, rows)]
    assert got.dtype == jnp.float64
    assert np.array_equal(np.asarray(got), np.bincount(
        np.asarray(gid), weights=np.asarray(values), minlength=4))
