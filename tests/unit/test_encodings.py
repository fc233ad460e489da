"""Compressed-domain column encodings (columnar/encodings.py, ISSUE 10).

Covers: encode/decode round-trips per SqlType incl. NULL validity,
auto-selection heuristics, code-space predicate equivalence vs decoded
execution (property-style over random literals), plan-family
zero-recompile over an encoded table, estimator interval shrinkage,
casts over encoded columns, EXPLAIN LINT encoding advisories, and the
eager-path decode fallback.
"""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu.columnar import Column, Encoding, Table
from dask_sql_tpu.columnar import encodings

pytestmark = pytest.mark.compressed

N = 4096  # >= columnar.encoding.min_rows so auto-selection engages


def _lineitem(n=N, seed=0):
    rng = np.random.RandomState(seed)
    start = np.datetime64("1992-01-01")
    return pd.DataFrame({
        "l_returnflag": rng.choice(["A", "N", "R"], n),
        "l_orderkey": (rng.randint(0, 1_500_000, n) * 4).astype(np.int64),
        "l_linenumber": rng.randint(1, 8, n).astype(np.int64),
        "l_quantity": rng.randint(1, 51, n).astype(np.float64),
        "l_extendedprice": rng.rand(n) * 100000.0,
        "l_discount": rng.randint(0, 11, n) / 100.0,
        "l_shipdate": start + rng.randint(0, 2526, n).astype("timedelta64[D]"),
    })


def _context(df, **config):
    """Context with `df` registered as lineitem.  Encoding-related options
    apply as a SCOPED overlay around registration only (encoding is a
    load-time property) — the process-global config stays untouched so
    tests cannot contaminate each other."""
    from dask_sql_tpu import Context
    from dask_sql_tpu import config as config_module

    c = Context()
    with config_module.set(dict(config)):
        c.create_table("lineitem", df)
    return c


# ---------------------------------------------------------------- round trips
@pytest.mark.parametrize("dtype,vals", [
    ("int8", [1, 2, 3, 1]),
    ("int16", [100, 200, 100, 300]),
    ("int32", [10**6, 2 * 10**6, 10**6, 0]),
    ("int64", [10**12, 2 * 10**12, 10**12, 0]),
    ("float64", [0.05, 0.07, 0.05, 0.0]),
    ("float32", [1.5, 2.5, 1.5, 0.5]),
])
def test_roundtrip_per_dtype_with_nulls(dtype, vals):
    n = N
    base = np.tile(np.asarray(vals, dtype=dtype), n // len(vals))
    ser = pd.Series(base).astype("object")
    ser[::7] = None  # NULLs ride the validity mask through encode/decode
    df = pd.DataFrame({"x": pd.Series(ser).astype("float64")})
    enc = Table.from_pandas(df, encode=True)
    plain = Table.from_pandas(df, encode=False)
    a, b = enc.columns["x"].to_numpy(), plain.columns["x"].to_numpy()
    assert np.allclose(a, b, equal_nan=True)


def test_roundtrip_datetime_with_nat():
    n = N
    dates = np.datetime64("1995-01-01") + np.tile(
        np.arange(30), n // 30 + 1)[:n].astype("timedelta64[D]")
    ser = pd.Series(dates)
    ser[::11] = pd.NaT
    df = pd.DataFrame({"d": ser})
    enc = Table.from_pandas(df, encode=True)
    assert enc.columns["d"].encoding in (Encoding.DICT, Encoding.FOR,
                                         Encoding.RLE)
    pd.testing.assert_series_equal(
        pd.Series(enc.columns["d"].to_numpy()),
        pd.Series(Table.from_pandas(df, encode=False).columns["d"].to_numpy()))


def test_rle_roundtrip_with_nulls():
    n = N
    vals = np.repeat(np.arange(8, dtype=np.int64), n // 8).astype("float64")
    mask = np.ones(n, dtype=bool)
    mask[: n // 8] = False  # a whole NULL run
    col = encodings.maybe_encode(vals, mask, Column.from_numpy(
        vals).sql_type, force=True)
    # force RLE specifically: disable the competing encodings
    from dask_sql_tpu import config as config_module

    with config_module.set({"columnar.encoding.dict": False,
                            "columnar.encoding.for": False}):
        col = encodings.maybe_encode(vals, mask,
                                     Column.from_numpy(vals).sql_type,
                                     force=True)
    assert col is not None and col.encoding is Encoding.RLE
    assert len(col) == n
    out = col.to_numpy()
    assert np.isnan(out[: n // 8]).all()
    assert np.array_equal(out[n // 8:], vals[n // 8:])
    # positional access decodes first and stays correct
    taken = col.take(np.asarray([0, n // 8, n - 1]))
    assert taken.encoding is Encoding.PLAIN
    assert np.isnan(taken.to_numpy()[0]) and taken.to_numpy()[2] == vals[-1]


# ------------------------------------------------------------- auto-selection
def test_selection_heuristics():
    t = Table.from_pandas(_lineitem(), encode=True)
    enc = {n: c.encoding for n, c in t.columns.items()}
    assert enc["l_discount"] is Encoding.DICT      # 11 uniques
    assert enc["l_quantity"] is Encoding.DICT      # 50 uniques
    assert enc["l_orderkey"] is Encoding.FOR       # wide range, stride 4
    assert enc["l_extendedprice"] is Encoding.PLAIN  # continuous floats
    assert enc["l_returnflag"] is Encoding.PLAIN   # strings keep their own
    # DICT codes are int16 and the dictionary is sorted
    disc = t.columns["l_discount"]
    assert np.dtype(disc.data.dtype) == np.int16
    assert np.all(np.diff(disc.enc_values) > 0)


def test_selection_respects_min_rows_and_off_switch():
    small = _lineitem(n=64)
    t = Table.from_pandas(small, encode=True)
    assert not t.has_encoded_columns()  # below columnar.encoding.min_rows
    c = _context(_lineitem(), **{"columnar.encoding": "off"})
    assert not c.schema["root"].tables["lineitem"].table.has_encoded_columns()


def test_selection_rle_for_sorted_runs():
    n = N
    df = pd.DataFrame({"x": np.repeat(np.arange(16, dtype=np.int64), n // 16)})
    from dask_sql_tpu import config as config_module

    with config_module.set({"columnar.encoding.dict": False,
                            "columnar.encoding.for": False}):
        t = Table.from_pandas(df, encode=True)
    assert t.columns["x"].encoding is Encoding.RLE
    assert np.array_equal(t.columns["x"].to_numpy(), df["x"].to_numpy())


# ------------------------------------------- code-space predicate equivalence
def test_codespace_predicates_match_decoded_property():
    """Property-style: random comparison/IN literals (members, non-members,
    out-of-range) over DICT/FOR columns must match the encodings-off
    context exactly, through the full SQL path."""
    df = _lineitem()
    c_enc = _context(df)
    c_off = _context(df, **{"columnar.encoding": "off"})
    t = c_enc.schema["root"].tables["lineitem"].table
    assert t.columns["l_discount"].encoding is Encoding.DICT

    rng = np.random.RandomState(7)
    literals = [0.05, 0.07, 0.051, -1.0, 2.0]  # members + absent + OOR
    literals += [round(float(rng.uniform(-0.05, 0.15)), 3) for _ in range(4)]
    ops = ["<", "<=", ">", ">=", "=", "<>"]
    for lit in literals:
        for op in (ops if lit in (0.05, 0.051) else
                   [ops[rng.randint(len(ops))]]):
            sql = (f"SELECT COUNT(*) AS n, SUM(l_quantity) AS s "
                   f"FROM lineitem WHERE l_discount {op} {lit}")
            got = c_enc.sql(sql, return_futures=False)
            ref = c_off.sql(sql, return_futures=False)
            assert int(got["n"][0]) == int(ref["n"][0]), (op, lit)
            assert np.array_equal(got["s"].to_numpy(np.float64),
                                  ref["s"].to_numpy(np.float64),
                                  equal_nan=True), (op, lit)
    # IN lists incl. absent members; and a FOR-column range predicate
    for sql in (
        "SELECT COUNT(*) AS n FROM lineitem WHERE l_discount IN (0.02, 0.05, 0.99)",
        "SELECT COUNT(*) AS n FROM lineitem WHERE l_discount NOT IN (0.02, 0.05)",
        "SELECT COUNT(*) AS n FROM lineitem WHERE l_quantity IN (1, 2, 3.5)",
        "SELECT COUNT(*) AS n FROM lineitem WHERE l_orderkey < 3000000",
        "SELECT COUNT(*) AS n FROM lineitem "
        "WHERE l_shipdate >= DATE '1994-01-01' AND l_shipdate < DATE '1995-01-01'",
    ):
        got = c_enc.sql(sql, return_futures=False)
        ref = c_off.sql(sql, return_futures=False)
        assert int(got["n"][0]) == int(ref["n"][0]), sql
    assert c_enc.metrics.counter("columnar.encoding.codespace_pred") >= 1
    assert c_enc.metrics.counter("columnar.encoding.decode") == 0


# ---- code space for a row-invariant comparand (ISSUE 32): the other side of
# the comparison has no column in it and neither planner folds it, so it
# reaches the kernel as params and literals under casts and calls
_CMP_OPS = ["<", "<=", ">", ">=", "=", "<>"]


def _codes_frame():
    """`_lineitem` with its ship dates drawn from 500 of the 2,526 days (few
    enough for DICT at this size, as SF10's 2,526 are at 24M rows) and an
    int64 column of 40 distinct values (an integer dictionary)."""
    df = _lineitem()
    rng = np.random.RandomState(11)
    days = np.sort(rng.choice(2526, 500, replace=False))
    return df.assign(
        l_shipdate=np.datetime64("1992-01-01")
        + days[rng.randint(0, 500, len(df))].astype("timedelta64[D]"),
        l_code=(rng.randint(0, 40, len(df)) * 1001 + 7).astype(np.int64))


@pytest.fixture(scope="module")
def decoded():
    """(frame, encodings-off context): what every case is held to."""
    df = _codes_frame()
    return df, _context(df, **{"columnar.encoding": "off"})


def _ship_day(df, where):
    """A day `d` (ISO text) such that ``d + 1 day`` lies `where` relative
    to l_shipdate's dictionary: on an entry, between two, or outside."""
    days = np.sort(df["l_shipdate"].unique()).astype("datetime64[D]")
    one = np.timedelta64(1, "D")
    if where == "on":
        target = days[len(days) // 2]
    elif where == "between":
        inner = np.arange(days[0], days[-1], one)
        target = inner[~np.isin(inner, days)][7]
    else:
        target = days[0] - 30 * one if where == "below" else days[-1] + 30 * one
    return str(target - one)


def _check_against_decoded(decoded, predicate, codespace, valuespace):
    """One fresh encoded context answers ``WHERE predicate`` on the
    compiled rung exactly as the encodings-off context does, and its
    counters say where the predicate ran."""
    df, c_off = decoded
    c_enc = _context(df)
    t = c_enc.schema["root"].tables["lineitem"].table
    for name in ("l_shipdate", "l_discount", "l_code"):
        assert t.columns[name].encoding is Encoding.DICT, name
    sql = ("SELECT COUNT(*) AS n, SUM(l_quantity) AS s FROM lineitem "
           f"WHERE {predicate}")
    got = c_enc.sql(sql, return_futures=False)
    ref = c_off.sql(sql, return_futures=False)
    assert int(got["n"][0]) == int(ref["n"][0]), predicate
    assert np.array_equal(got["s"].to_numpy(np.float64),
                          ref["s"].to_numpy(np.float64),
                          equal_nan=True), predicate
    counter = c_enc.metrics.counter
    assert counter("resilience.rung.compiled_aggregate") == 1, predicate
    assert counter("columnar.encoding.codespace_pred") == codespace
    assert counter("columnar.encoding.valuespace_pred") == valuespace
    assert counter("columnar.encoding.decode") == 0
    return int(got["n"][0])


@pytest.mark.parametrize("flipped", [False, True], ids=["col_op_x", "x_op_col"])
@pytest.mark.parametrize("op", _CMP_OPS)
@pytest.mark.parametrize("column,comparand", [
    ("l_shipdate", "DATE '1995-06-17' - INTERVAL '90' DAY"),
    ("l_discount", "0.12 / 2"),  # `div` is not in the planners' _FOLDABLE
], ids=["date_minus_days", "number_div"])
def test_codespace_row_invariant_comparand(decoded, column, comparand, op,
                                           flipped):
    """All six operators, both operand orders: a date column against
    ``DATE - INTERVAL 'n' DAY`` and a numeric one against a quotient."""
    predicate = (f"{comparand} {op} {column}" if flipped
                 else f"{column} {op} {comparand}")
    n = _check_against_decoded(decoded, predicate, codespace=1, valuespace=0)
    assert 0 < n < len(decoded[0])  # the case selects, it does not pass all


@pytest.mark.parametrize("predicate", [
    "l_shipdate < DATE '1994-01-01' + INTERVAL '1' YEAR",  # TPC-H Q6's text
    "l_shipdate >= DATE '1994-01-31' + INTERVAL '1' MONTH",  # clamps to 02-28
    "l_shipdate <= DATE '1996-02-29' + INTERVAL '1' YEAR",  # clamps to 02-28
    "l_shipdate < DATE '1994-03-15' - INTERVAL '2' MONTH",
    "l_shipdate > DATE '1994-01-01' + 45",  # integer days
    "DATE '1994-01-01' + 45 >= l_shipdate",
    "l_shipdate <= CAST(DATE '1994-01-01' + INTERVAL '10' DAY AS TIMESTAMP)",
    "l_shipdate > TIMESTAMP '1995-06-01 12:00:00' - INTERVAL '1' HOUR",
    "l_discount > 0.11 / 2",  # between two dictionary entries
    "l_discount <> 0.11 / 2",
    "l_discount >= 0.5 / 2",  # above every entry
    "l_discount = 0.5 / 2",
    "l_code <= CAST(28042.0 / 2 AS BIGINT)",  # integer dictionary: an entry
    "l_code = CAST(28042.0 / 2 AS BIGINT)",
    "l_code > CAST(28050.0 / 2 AS BIGINT)",  # and between two
])
def test_codespace_comparand_shapes(decoded, predicate):
    """Year-month intervals (month-end clamps among them), integer days,
    explicit CASTs, a TIMESTAMP less hours, and numeric comparands on,
    between and outside the entries of a float and an integer dictionary."""
    _check_against_decoded(decoded, predicate, codespace=1, valuespace=0)


@pytest.mark.parametrize("op", ["=", "<>", "<=", ">"])
@pytest.mark.parametrize("where", ["on", "between", "below", "above"])
def test_codespace_date_on_between_outside_entries(decoded, where, op):
    day = _ship_day(decoded[0], where)
    _check_against_decoded(decoded, f"l_shipdate {op} DATE '{day}' + 1",
                           codespace=1, valuespace=0)


@pytest.mark.parametrize("predicate", [
    "l_shipdate <= CAST(NULL AS DATE) + INTERVAL '1' DAY",  # a NULL literal
    "l_discount <= 0.12 / 2 + l_quantity * 0",  # a column in the comparand
    "l_code < 30037 / 2",  # integer division answers a zero divisor by NULL
], ids=["null_literal", "column", "integer_div"])
def test_comparand_not_row_invariant_falls_back_to_value_space(decoded,
                                                               predicate):
    _check_against_decoded(decoded, predicate, codespace=0, valuespace=1)


def test_family_zero_recompile_on_interval_comparand(decoded):
    """The second DELTA of ``DATE - INTERVAL 'n' DAY`` pays ZERO foreground
    compiles: both parameters are evaluated and searched in-kernel."""
    df, c_off = decoded
    c = _context(df)

    def q(delta):
        return ("SELECT COUNT(*) AS n FROM lineitem WHERE l_shipdate <= "
                f"DATE '1998-12-01' - INTERVAL '{delta}' DAY")

    def compiles(tr):
        return [s.name for s in tr.spans if s.name.startswith("compile:")]

    first = c.sql(q(90), return_futures=False)
    assert len(compiles(c.last_trace)) >= 1
    second = c.sql(q(400), return_futures=False)
    assert compiles(c.last_trace) == []
    for got, delta in ((first, 90), (second, 400)):
        assert int(got["n"][0]) == int(
            c_off.sql(q(delta), return_futures=False)["n"][0])
    assert int(second["n"][0]) < int(first["n"][0])
    assert c.metrics.counter("columnar.encoding.codespace_pred") == 1
    assert c.metrics.counter("columnar.encoding.valuespace_pred") == 0


@pytest.fixture(scope="module")
def bench_lineitem():
    """perfbench's LINEITEM (all sixteen columns, pyarrow) at 40,000 rows:
    enough for l_shipdate's 2,526 days to be DICT-encoded as at SF10."""
    from perfbench.datagen import tpch_lineitem

    arrays = tpch_lineitem.generate(40_000, seed=31, scale_factor=10)
    return tpch_lineitem.arrow_tables(arrays)["lineitem"]


@pytest.mark.parametrize("query", ["tpch_q1", "tpch_q1_interval", "tpch_q6"])
def test_benchmark_texts_leave_no_predicate_in_value_space(bench_lineitem,
                                                           query):
    """The benchmark's three query texts, as its cells send them: every
    predicate on a DICT column runs on the codes (`valuespace_pred` 0)."""
    import random

    from perfbench import traffic

    c = _context(bench_lineitem)
    t = c.schema["root"].tables["lineitem"].table
    assert t.columns["l_shipdate"].encoding is Encoding.DICT
    text = traffic.load("queries", query)
    c.sql(traffic.render(text, traffic.draw_params(text, random.Random(3))),
          return_futures=False)
    assert c.metrics.counter("resilience.rung.compiled_aggregate") == 1
    assert c.metrics.counter("columnar.encoding.codespace_pred") >= 1
    assert c.metrics.counter("columnar.encoding.valuespace_pred") == 0
    assert c.metrics.counter("columnar.encoding.decode") == 0


def test_groupby_on_encoded_keys_matches_decoded():
    df = _lineitem()
    c_enc = _context(df)
    c_off = _context(df, **{"columnar.encoding": "off"})
    for sql in (
        "SELECT l_discount, COUNT(*) AS n FROM lineitem "
        "GROUP BY l_discount ORDER BY l_discount",
        "SELECT l_linenumber, SUM(l_extendedprice) AS s FROM lineitem "
        "GROUP BY l_linenumber ORDER BY l_linenumber",
    ):
        got = c_enc.sql(sql, return_futures=False)
        ref = c_off.sql(sql, return_futures=False)
        for col in got.columns:
            assert np.array_equal(got[col].to_numpy(), ref[col].to_numpy()), \
                (sql, col)


def test_eager_path_decodes_once_and_matches():
    df = _lineitem()
    c_enc = _context(df)
    sql = ("SELECT l_linenumber, COUNT(*) AS n FROM lineitem "
           "WHERE l_discount > 0.03 GROUP BY l_linenumber ORDER BY l_linenumber")
    with c_enc.config.set({"sql.compile": False}):
        got = c_enc.sql(sql, return_futures=False)
    assert c_enc.metrics.counter("columnar.encoding.decode") >= 1
    sel = df[df.l_discount > 0.03]
    exp = sel.groupby("l_linenumber").size()
    assert np.array_equal(got["n"].to_numpy(np.int64), exp.to_numpy())


# ------------------------------------------------------- families interaction
def test_family_zero_recompile_on_encoded_table():
    """The second literal variant over an encoded table pays ZERO foreground
    compiles: code-space param translation happens in-kernel (searchsorted
    over the dictionary constant), so one executable serves the family."""
    df = _lineitem()
    c = _context(df)

    def q(lit):
        return ("SELECT l_linenumber, SUM(l_quantity) AS s, COUNT(*) AS n "
                f"FROM lineitem WHERE l_discount > {lit} GROUP BY l_linenumber")

    def compiles(tr):
        return [s.name for s in tr.spans if s.name.startswith("compile:")]

    first = c.sql(q(0.02), return_futures=False)
    assert len(compiles(c.last_trace)) >= 1
    second = c.sql(q(0.06), return_futures=False)
    assert compiles(c.last_trace) == []
    # and the params really steer the result
    exp2 = df[df.l_discount > 0.06].groupby("l_linenumber").l_quantity.sum()
    got2 = second.set_index(second.columns[0])["s"]
    assert np.allclose(sorted(got2.to_numpy(np.float64)),
                       sorted(exp2.to_numpy()))
    assert len(first) == len(second)


# ------------------------------------------------------------------ estimator
def test_estimator_interval_shrinkage():
    from dask_sql_tpu.analysis import estimator
    from dask_sql_tpu.planner.parser import parse_sql

    df = _lineitem()
    c_enc = _context(df)
    c_off = _context(df, **{"columnar.encoding": "off"})
    sql = ("SELECT SUM(l_extendedprice) AS s FROM lineitem "
           "WHERE l_discount > 0.05")
    e_enc = estimator.estimate_plan(
        c_enc._get_ral(parse_sql(sql)[0], sql_text=sql), context=c_enc)
    e_off = estimator.estimate_plan(
        c_off._get_ral(parse_sql(sql)[0], sql_text=sql), context=c_off)
    assert e_enc.peak_bytes.hi < e_off.peak_bytes.hi
    assert e_enc.peak_bytes.lo < e_off.peak_bytes.lo
    # the tightened lower bound stays sound: it never exceeds actual bytes
    from dask_sql_tpu.serving.cache import table_nbytes

    resident = table_nbytes(c_enc.schema["root"].tables["lineitem"].table)
    assert e_enc.peak_bytes.lo <= resident + 10_000


def test_admission_gate_admits_more_when_encoded():
    """The same budget rejects the PLAIN table's scan but admits the
    encoded one — compression as admission headroom, not just footprint."""
    df = _lineitem()
    c_enc = _context(df)
    c_off = _context(df, **{"columnar.encoding": "off"})
    from dask_sql_tpu.analysis import estimator
    from dask_sql_tpu.planner.parser import parse_sql

    sql = "SELECT SUM(l_quantity) AS s FROM lineitem"
    lo_enc = estimator.estimate_plan(
        c_enc._get_ral(parse_sql(sql)[0], sql_text=sql),
        context=c_enc).peak_bytes.lo
    lo_off = estimator.estimate_plan(
        c_off._get_ral(parse_sql(sql)[0], sql_text=sql),
        context=c_off).peak_bytes.lo
    budget = (lo_enc + lo_off) // 2  # between the two provable floors
    from dask_sql_tpu.exceptions import QueryError

    with c_enc.config.set({"serving.admission.max_estimated_bytes": budget}):
        c_enc.sql(sql, return_futures=False)  # admits
    with c_off.config.set({"serving.admission.max_estimated_bytes": budget}):
        with pytest.raises(QueryError):
            c_off.sql(sql, return_futures=False)  # sheds


# ---------------------------------------------------------------------- casts
def test_casts_on_encoded_columns():
    from dask_sql_tpu.columnar.dtypes import SqlType

    df = _lineitem()
    t = Table.from_pandas(df, encode=True)
    # DICT int -> DOUBLE: strictly-increasing value cast keeps the codes
    ln = t.columns["l_linenumber"]
    assert ln.encoding is Encoding.DICT
    as_double = ln.cast(SqlType.DOUBLE)
    assert as_double.encoding is Encoding.DICT
    assert np.array_equal(as_double.to_numpy(),
                          df["l_linenumber"].to_numpy().astype(np.float64))
    # DICT datetime -> DATE (collapsing-safe here: already midnight)
    ship = t.columns["l_shipdate"]
    as_date = ship.cast(SqlType.DATE)
    assert np.array_equal(
        pd.to_datetime(as_date.to_numpy()).values.astype("datetime64[D]"),
        df["l_shipdate"].to_numpy().astype("datetime64[D]"))
    # FOR -> DOUBLE decodes then casts
    ok = t.columns["l_orderkey"]
    assert ok.encoding is Encoding.FOR
    as_d = ok.cast(SqlType.DOUBLE)
    assert np.array_equal(as_d.to_numpy(),
                          df["l_orderkey"].to_numpy().astype(np.float64))
    # collapsing cast (DOUBLE dict -> INTEGER truncation merges values)
    # must fall back to decode, not keep a broken code space
    disc = t.columns["l_quantity"]
    as_int = disc.cast(SqlType.INTEGER)
    assert np.array_equal(as_int.to_numpy(),
                          df["l_quantity"].to_numpy().astype(np.int32))
    # full-SQL cast path over encoded columns
    c = _context(df)
    got = c.sql("SELECT CAST(l_discount AS VARCHAR) AS s FROM lineitem "
                "WHERE l_discount = 0.05 LIMIT 3", return_futures=False)
    assert all(v == "0.05" for v in got["s"])


# -------------------------------------------------------------- lint / pandas
def test_explain_lint_encoding_rows():
    c = _context(_lineitem())
    rows = list(c.sql("EXPLAIN LINT SELECT SUM(l_quantity) FROM lineitem",
                      return_futures=False)["LINT"])
    enc_rows = [r for r in rows if r.startswith("info[encoding]")]
    assert enc_rows, rows
    assert "DICT" in enc_rows[0] and "ratio=" in enc_rows[0]


def test_to_pandas_packed_transfer_with_encoded(monkeypatch):
    monkeypatch.setenv("DSQL_PACK_TO_PANDAS", "1")
    df = _lineitem()
    t = Table.from_pandas(df, encode=True)
    out = t.to_pandas()
    for col in ("l_quantity", "l_discount", "l_orderkey"):
        assert np.allclose(out[col].to_numpy(np.float64),
                           df[col].to_numpy(np.float64)), col
    assert np.array_equal(pd.to_datetime(out["l_shipdate"]).values,
                          df["l_shipdate"].to_numpy())


def test_checkpoint_roundtrip_reencodes(tmp_path):
    from dask_sql_tpu import Context

    df = _lineitem()
    c1 = _context(df)
    snap = str(tmp_path / "snap")
    c1.save_state(snap)
    c2 = Context()
    c2.load_state(snap)
    t2 = c2.schema["root"].tables["lineitem"].table
    assert t2.has_encoded_columns()
    got = c2.sql("SELECT SUM(l_quantity) AS s FROM lineitem",
                 return_futures=False)
    assert float(got["s"][0]) == float(df["l_quantity"].sum())


# ------------------------------------------- dictionary byte count, kept once
_STRINGS = ["a", "héllo", "", "日本語", "ab  ", None, "x" * 40, "Ünï", "a"] * 5


def _walked(d):
    """The rule, walked: characters (not bytes) plus the pointers."""
    return sum(len(str(v)) for v in d) + d.nbytes


def _walks():
    from dask_sql_tpu.utils import DICTIONARY_STATS

    return (DICTIONARY_STATS["columnar.dictionary.walks"],
            DICTIONARY_STATS["columnar.dictionary.walk_entries"])


def _pandas_loaded():
    return Table.from_pandas(pd.DataFrame({"s": _STRINGS})).columns["s"]


def _arrow_loaded(dictionary_typed):
    import pyarrow as pa

    arr = pa.array(_STRINGS)
    if dictionary_typed:
        arr = arr.dictionary_encode()
    return Table.from_arrow(pa.table({"s": arr})).columns["s"]


def _string_function_output():
    from dask_sql_tpu.ops import strings

    return strings.map_unary(_pandas_loaded(), lambda s: s.upper() * 2)


def _append_rows_union():
    from dask_sql_tpu import Context

    c = Context()
    c.create_table("t", pd.DataFrame({"s": _STRINGS}))
    c.append_rows("t", pd.DataFrame({"s": ["neu", "ñandú", ""]}))
    return c.schema["root"].tables["t"].table.columns["s"]


@pytest.mark.parametrize("build,first_walks", [
    (_pandas_loaded, 0),
    (lambda: _arrow_loaded(False), 0),
    (lambda: _arrow_loaded(True), 0),
    (lambda: _pandas_loaded().compact_dictionary(), 1),
    (_string_function_output, 1),
    (_append_rows_union, 1),
], ids=["pandas", "arrow_string", "arrow_dictionary", "compact_dictionary",
        "string_function", "append_rows"])
def test_dictionary_bytes_are_counted_once_per_array(build, first_walks):
    """A load path hands its dictionary over counted; any other dictionary
    is walked on the first ask and never again, whichever column carries
    it; the number is the walked one; a dropped array's entry goes."""
    import gc
    from dataclasses import replace

    col = build()
    d = col.dictionary
    assert any(ord(ch) > 127 for v in d for ch in str(v)) and "" in list(d)
    want = _walked(d)
    others = int(col.data.nbytes) + (0 if col.validity is None
                                     else int(col.validity.nbytes))
    dense = len(col) * 4 + (0 if col.validity is None else len(col))
    before = _walks()
    assert encodings.encoded_nbytes(col) == others + want
    after_first = _walks()
    assert after_first[0] - before[0] == first_walks
    assert after_first[1] - before[1] == first_walks * len(d)
    assert encodings.decoded_nbytes(col) == dense + want
    assert encodings.dictionary_nbytes(d) == want
    assert col.device_nbytes() == others + want
    siblings = [col.take(np.array([0, 2, 1])), col.slice(1, 4),
                col.filter(np.arange(len(col)) % 2 == 0),
                replace(col, validity=None)]
    for sib in siblings:
        assert sib.dictionary is d
        assert encodings.encoded_nbytes(sib) \
            == int(sib.data.nbytes) + (0 if sib.validity is None else
                                       int(sib.validity.nbytes)) + want
    assert _walks() == after_first

    key = id(d)
    assert key in encodings._DICTIONARY_CHARS
    del col, d, sib, siblings
    gc.collect()
    assert key not in encodings._DICTIONARY_CHARS


def test_dictionary_count_never_serves_a_recycled_id():
    """An entry whose array has died is not read for a new array that got
    the same id: the memo checks the weak reference, not the key alone."""
    d = np.array(["abc", "de"], dtype=object)
    other = np.array(["z"], dtype=object)
    encodings.prime_dictionary_nbytes(d, 5)
    encodings._DICTIONARY_CHARS[id(other)] = encodings._DICTIONARY_CHARS[id(d)]
    before = _walks()
    assert encodings.dictionary_nbytes(other) == 1 + other.nbytes
    assert _walks()[0] - before[0] == 1
    assert encodings.dictionary_nbytes(d) == 5 + d.nbytes


def test_dictionary_counted_once_under_concurrent_asks():
    """More threads than cores ask for the same new dictionaries together,
    on a short switch interval: every answer is the walked one, each array
    is walked exactly once and the counters lose no update."""
    import sys
    import threading

    dicts = [np.array([f"wört {i}-{j}" * (j % 3) for j in range(400)],
                      dtype=object) for i in range(24)]
    want = [_walked(d) for d in dicts]
    before = _walks()
    wrong, start = [], threading.Barrier(16)

    def ask(offset):
        start.wait(30)
        for k in range(len(dicts)):
            i = (k + offset) % len(dicts)
            if encodings.dictionary_nbytes(dicts[i]) != want[i]:
                wrong.append(i)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=ask, args=(n,)) for n in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not wrong
    after = _walks()
    assert after[0] - before[0] == len(dicts)
    assert after[1] - before[1] == sum(len(d) for d in dicts)
