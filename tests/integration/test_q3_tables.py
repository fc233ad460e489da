"""TPC-H Q3 with its substitution parameters through `compiled_join_aggregate`
(physical/compiled_join.py): build sides kept WHOLE, their filters evaluated
in the program, one executable for every parameter set, the top-10 inside the
rung.

Tables come from the benchmark's own generator (`perfbench/datagen/
tpch_q3_tables.py`, the sparse order keys of clause 4.2.3) at 50,000
lineitems, the text from `perfbench.traffic`, the answers are held to the
plain reference (`perfbench/references/tpch_q3_topk.py`) through
`perfbench.compare.answer_gap`.

Cost (ROADMAP D11): the module's tables 1.5 s once, the 155 parameter sets
4 s (one compile), the other cases under 1 s each.
"""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.ops.join import dense_unique_lut
from dask_sql_tpu.physical import compiled_join as cj
from perfbench import compare, traffic
from perfbench.datagen import tpch_q3_tables
from perfbench.references import tpch_q3_topk
from perfbench.surfaces.library import frame_answer

ROWS = 50_000
SEGMENTS = [s.lower() for s in tpch_q3_tables.SEGMENTS]


@pytest.fixture(scope="module", autouse=True)
def result_cache_off():
    """The cell's `engine_config`, for this module alone: `Context.config` is
    the process's one config, and a `config.update` here would switch the
    result cache off for every test file the worker runs afterwards."""
    with config_module.set({"serving.cache.enabled": False}):
        yield


@pytest.fixture(scope="module")
def q3(result_cache_off):
    """(context, arrays, frames): CUSTOMER, ORDERS and LINEITEM loaded as the
    benchmark loads them, the result cache off as in its configuration."""
    cj.PROGRAMS.clear()
    cj.LUTS.clear()
    arrays = tpch_q3_tables.generate(ROWS, seed=33, scale_factor=10)
    frames = tpch_q3_tables.arrow_tables(arrays)
    c = Context()
    for name in ("customer", "orders", "lineitem"):
        c.create_table(name, frames[name])
    return c, arrays, frames


def span_names(c):
    return [s.name for s in c.last_trace.spans]


def test_all_155_parameter_sets_share_one_executable(q3):
    """Every SEGMENT x DATE of clause 2.4.3.3 answers from the rung, agrees
    with the reference within the query files' limit (keys and row order
    exactly), and the fifteen-times-ten requests hold ONE `compile:` span:
    neither literal is in the program's identity or its shapes."""
    from dask_sql_tpu.serving import compile_cache

    c, arrays, _ = q3
    reference = tpch_q3_topk.Reference(arrays)
    before = {k: c.metrics.counter(k) for k in
              ("join.lut.built", "join.lut.reused", "join.build.whole",
               "join.build.eager", "resilience.degraded",
               "columnar.encoding.valuespace_pred")}
    compiles, misses, requests = 0, None, 0
    for segment in SEGMENTS:
        query = traffic.load("queries", f"tpch_q3_{segment}")
        for params in traffic.all_params(query):
            frame = c.sql(traffic.render(query, params)).compute()
            names = span_names(c)
            assert "rung:compiled_join_aggregate" in names, (params, names)
            compiles += sum(n.startswith("compile:") for n in names)
            gap = compare.answer_gap(query, frame_answer(frame),
                                     reference.answer(params))
            assert gap is not None and gap <= query["limits"]["rel_err"], \
                (params, gap, frame)
            assert len(frame) == 10
            requests += 1
            if requests == 10:  # every eager shape has been met by now
                misses = compile_cache.stats()["misses"]
    assert requests == 155 and compiles == 1
    assert compile_cache.stats()["misses"] == misses
    moved = {k: c.metrics.counter(k) - v for k, v in before.items()}
    assert moved == {"join.lut.built": 2, "join.lut.reused": 2 * 155 - 2,
                     "join.build.whole": 2, "join.build.eager": 0,
                     "resilience.degraded": 0,
                     "columnar.encoding.valuespace_pred": 0}
    spans = {s.name: s for s in c.last_trace.spans}
    assert spans["join:build"].attrs == {
        "tables": 2, "built": 0,
        "lut_bytes": spans["join:build"].attrs["lut_bytes"]}
    assert spans["join:build"].attrs["lut_bytes"] > 4 * ROWS
    assert spans["join:tail"].attrs["rows"] == 10
    assert spans["join:tail"].attrs["groups"] >= 10
    # ORDERS -> CUSTOMER is probed from ORDERS' rows, not from LINEITEM's
    (program,) = cj.PROGRAMS.values()
    assert program.folded == {1: 0}
    launch = [s for s in c.last_trace.spans if s.name == "launch"][-1]
    assert launch.attrs["joins"] == 2 and launch.attrs["segsum"] == "scatter"
    assert launch.attrs["domain"] == len(arrays["o_orderkey"])


def test_segment_absent_from_the_dictionary_and_a_date_before_every_order(q3):
    """The literal's dictionary code is a runtime parameter: a segment no
    customer has is a code no row holds, and shares the executable."""
    c, _, _ = q3
    query = traffic.load("queries", "tpch_q3_building")
    sql = traffic.render(query, {"DAY": 3, "SEGMENT": 1})
    assert len(c.sql(sql).compute()) == 10
    for text in (sql.replace("BUILDING", "SHIPBUILDING"),
                 sql.replace("1995-03-28", "1991-01-01")):
        frame = c.sql(text).compute()
        names = span_names(c)
        assert len(frame) == 0 and list(frame.columns) == \
            ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]
        assert "rung:compiled_join_aggregate" in names
        assert "family_hit" in names
        assert not [n for n in names if n.startswith("compile:")]


# --------------------------------------------------------------- the LUT rule
def sparse_keys(n):
    index = np.arange(n, dtype=np.int64)
    return (index // 8) * 32 + index % 8 + 1  # clause 4.2.3


@pytest.mark.parametrize("case", ["sparse_primary_key", "duplicates",
                                  "over_budget", "per_query_rule",
                                  "null_keys"])
def test_dense_unique_lut_admits_by_bytes(case):
    """A LUT kept per table version is admitted by what it costs to hold (4
    bytes a key of the range), whatever a filter would select of the rows;
    the per-query rule (no `max_bytes`) stays the density rule."""
    import jax.numpy as jnp

    keys = sparse_keys(4_000)                    # range 15,976: 63,904 bytes
    if case == "sparse_primary_key":
        rmin, lut = dense_unique_lut(jnp.asarray(keys), max_bytes=1 << 20)
        assert rmin == 1 and lut.shape[0] == int(keys.max())
        assert np.array_equal(np.asarray(lut)[keys - 1], np.arange(4_000))
        assert int((np.asarray(lut) >= 0).sum()) == 4_000
    elif case == "duplicates":
        twice = np.concatenate([keys, keys[:1]])
        assert dense_unique_lut(jnp.asarray(twice), max_bytes=1 << 20) is None
    elif case == "over_budget":
        assert dense_unique_lut(jnp.asarray(keys), max_bytes=60_000) is None
    elif case == "per_query_rule":
        # 40 rows of a filtered build: 8 x 40 < the 65,536 floor admits, a
        # range of 1,000,000 does not (what made the rung decline Q3)
        assert dense_unique_lut(jnp.asarray(keys[:40])) is not None
        wide = np.array([1, 1_000_000], dtype=np.int64)
        assert dense_unique_lut(jnp.asarray(wide)) is None
        assert dense_unique_lut(jnp.asarray(wide), max_bytes=1 << 23) \
            is not None
    else:
        valid = np.ones(4_000, dtype=bool)
        valid[::7] = False
        rmin, lut = dense_unique_lut(jnp.asarray(keys), jnp.asarray(valid),
                                     max_bytes=1 << 20)
        assert int((np.asarray(lut) >= 0).sum()) == int(valid.sum())


# ------------------------------- whole build + mask == eager filter-then-join
def star_tables():
    """A fact table with NULL join keys (pyarrow: integers keep their type
    beside a validity mask) and a dimension on sparse keys."""
    import pyarrow as pa

    rng = np.random.default_rng(5)
    dim = pd.DataFrame({
        "d_key": sparse_keys(600),
        "d_tag": rng.choice(["red", "green", "blue"], 600),
        "d_day": rng.integers(0, 40, 600),
    })
    keys = rng.choice(np.concatenate([dim.d_key.to_numpy(), [7, 11, 13]]),
                      5_000)
    null = rng.random(5_000) < 0.05  # NULL join keys match nothing
    fact = pa.table({
        "f_key": pa.array(keys, type=pa.int64(), mask=null),
        "f_val": pa.array(rng.random(5_000)),
        "f_n": pa.array(rng.integers(0, 100, 5_000))})
    return {"dim": dim, "fact": fact}


CASES = {
    "q3": ("q3", traffic.render(traffic.load("queries", "tpch_q3_machinery"),
                                {"DAY": 12, "SEGMENT": 4}), (2, 0)),
    "chained_build_read_at_the_probe": (
        "q3", "SELECT c_mktsegment, COUNT(*) AS n, SUM(l_quantity) AS q "
        "FROM customer, orders, lineitem WHERE c_custkey = o_custkey "
        "AND l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15' "
        "AND c_acctbal > 0 GROUP BY c_mktsegment ORDER BY c_mktsegment",
        (2, 0)),
    "filter_selects_nothing": (
        "star", "SELECT d_key, SUM(f_val) AS s, COUNT(*) AS n FROM fact, dim "
        "WHERE f_key = d_key AND d_tag = 'purple' GROUP BY d_key", (1, 0)),
    "global_aggregate_filter_selects_nothing": (
        "star", "SELECT SUM(f_val) AS s, COUNT(*) AS n FROM fact, dim "
        "WHERE f_key = d_key AND d_day > 100", (1, 0)),
    "null_join_keys": (
        "star", "SELECT d_tag, SUM(f_val) AS s, COUNT(*) AS n, MIN(f_n) AS lo "
        "FROM fact, dim WHERE f_key = d_key AND d_day < 25 AND d_tag <> 'red' "
        "GROUP BY d_tag ORDER BY d_tag", (1, 0)),
    "pointer_gid_topk_ascending": (
        "star", "SELECT d_key, d_day, SUM(f_n) AS s FROM fact, dim "
        "WHERE f_key = d_key AND d_tag = 'green' GROUP BY d_key, d_day "
        "ORDER BY d_day, s DESC LIMIT 7", (1, 0)),
    "build_side_stays_eager": (
        "star", "SELECT t.d_key, SUM(f_val) AS s FROM fact, "
        "(SELECT d_key, MAX(d_day) AS top FROM dim GROUP BY d_key) AS t "
        "WHERE f_key = t.d_key AND t.top > 20 GROUP BY t.d_key", (0, 1)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_whole_build_with_mask_equals_eager_filter_then_join(q3, case):
    """The rung's answer (build sides whole where they are a filtered scan,
    their filters a mask read through the pointer) against the interpreted
    converters' (`sql.compile.join_pipeline` off: filter, then join)."""
    tables, sql, (whole, eager) = CASES[case]
    if tables == "q3":
        c = q3[0]
    else:
        c = Context()
        for name, frame in star_tables().items():
            c.create_table(name, frame)
    cj.PROGRAMS.clear()
    before = {k: c.metrics.counter(k)
              for k in ("join.build.whole", "join.build.eager")}
    got = c.sql(sql).compute()
    assert "rung:compiled_join_aggregate" in span_names(c)
    assert (c.metrics.counter("join.build.whole") - before["join.build.whole"],
            c.metrics.counter("join.build.eager") - before["join.build.eager"]
            ) == (whole, eager)
    (program,) = cj.PROGRAMS.values()
    assert program.folded == ({1: 0} if case == "q3" else {})
    want = c.sql(sql, config_options={"sql.compile.join_pipeline": False}
                 ).compute()
    assert "rung:compiled_join_aggregate" not in span_names(c)
    if "ORDER BY" not in sql:
        by = list(got.columns[:1])
        got, want = (f.sort_values(by).reset_index(drop=True)
                     for f in (got, want))
    pd.testing.assert_frame_equal(got, want, check_dtype=False, rtol=1e-12)


# ------------------------------------------------- string literal as its code
def test_string_literal_on_a_build_side_is_a_runtime_code():
    """`col = 'v'` / `col <> 'v'` on a dictionary-coded column of a whole
    build side parameterise (families/parameterize.py): every value of the
    literal, one absent from the dictionary among them, runs the first
    value's executable and answers as pandas does."""
    tables = star_tables()
    c = Context()
    for name, frame in tables.items():
        c.create_table(name, frame)
    joined = tables["fact"].to_pandas().dropna().astype(
        {"f_key": "int64"}).merge(tables["dim"], left_on="f_key",
                                  right_on="d_key")
    cj.PROGRAMS.clear()
    compiles = 0
    for op, pick in (("=", lambda s, v: s == v), ("<>", lambda s, v: s != v)):
        for value in ("red", "green", "blue", "purple"):
            got = c.sql("SELECT SUM(f_val) AS s, COUNT(*) AS n FROM fact, dim "
                        f"WHERE f_key = d_key AND d_tag {op} '{value}'"
                        ).compute()
            names = span_names(c)
            assert "rung:compiled_join_aggregate" in names
            compiles += sum(n.startswith("compile:") for n in names)
            want = joined[pick(joined.d_tag, value)]
            assert int(got.n[0]) == len(want)
            if len(want):
                assert got.s[0] == pytest.approx(want.f_val.sum(), rel=1e-12)
            else:
                assert pd.isna(got.s[0])
    assert compiles == 2  # one executable per operator, none per value
