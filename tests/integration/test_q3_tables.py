"""TPC-H Q3 with its substitution parameters through `compiled_join_aggregate`
(physical/compiled_join.py): build sides kept WHOLE, their filters evaluated
in the program, one executable for every parameter set, the top-10 inside the
rung.

Tables come from the benchmark's own generator (`perfbench/datagen/
tpch_q3_tables.py`, the sparse order keys of clause 4.2.3) at 50,000
lineitems, the text from `perfbench.traffic`, the answers are held to the
plain reference (`perfbench/references/tpch_q3_topk.py`) through
`perfbench.compare.answer_gap`.

The compaction of the passing rows (PR 34) engages from 2^20 probe rows;
its tests lower that floor (`compacting`) and hold BOTH branches of the one
executable to the interpreted converters' answer.

Cost (ROADMAP D11): the module's tables 1.5 s once, the 155 parameter sets
4 s (one compile), the other cases under 1 s each.
"""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.ops.join import bucket_rows, dense_unique_lut
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
               "join.build.eager", "join.build.padded", "resilience.degraded",
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
                     "join.build.padded": 2, "resilience.degraded": 0,
                     "columnar.encoding.valuespace_pred": 0}
    spans = {s.name: s for s in c.last_trace.spans}
    assert spans["join:build"].attrs == {
        "tables": 2, "built": 0, "padded": 2,
        "lut_bytes": spans["join:build"].attrs["lut_bytes"]}
    assert spans["join:build"].attrs["lut_bytes"] > 4 * ROWS
    assert spans["join:tail"].attrs["rows"] == 10
    assert spans["join:tail"].attrs["groups"] >= 10
    # ORDERS -> CUSTOMER is probed from ORDERS' rows, not from LINEITEM's
    (program,) = cj.PROGRAMS.values()
    assert program.folded == {1: 0}
    launch = [s for s in c.last_trace.spans if s.name == "launch"][-1]
    assert launch.attrs["joins"] == 2 and launch.attrs["segsum"] == "scatter"
    # ORDERS' and CUSTOMER's rows as the program reads them: their buckets
    orders = len(arrays["o_orderkey"])
    assert launch.attrs["domain"] == bucket_rows(orders) > orders
    assert launch.attrs["build_rows"] == [bucket_rows(orders),
                                          bucket_rows(len(arrays["c_segment"]))]


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
        # the range's 15,976 slots in a table of its bucket, the rest empty
        assert rmin == 1 and lut.shape[0] == bucket_rows(int(keys.max())) \
            == 16_000
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


# ------------------------------------------------ compaction of passing rows
COMPACT_COUNTERS = ("join.compact.programs", "join.compact.engaged",
                    "join.compact.overflow")
Q3_SHAPED = (
    "SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)) AS revenue, "
    "o_orderdate, o_shippriority FROM customer, orders, lineitem "
    "WHERE c_custkey = o_custkey AND l_orderkey = o_orderkey{where} "
    "GROUP BY l_orderkey, o_orderdate, o_shippriority "
    "ORDER BY revenue DESC, o_orderdate LIMIT 10")


def counters(c):
    return {k: c.metrics.counter(k) for k in COMPACT_COUNTERS}


def moved(c, before):
    return tuple(c.metrics.counter(k) - before[k] for k in COMPACT_COUNTERS)


def eager(c, sql):
    """The interpreted converters' answer: filter, then join."""
    want = c.sql(sql, config_options={"sql.compile.join_pipeline": False}
                 ).compute()
    assert "rung:compiled_join_aggregate" not in span_names(c)
    return want


def tail_attrs(c):
    return {s.name: s for s in c.last_trace.spans}["join:tail"].attrs


def first_rows(arrays, count):
    """A conjunct that exactly the first `count` lineitems in (order, line)
    order pass: every lineitem finds its order and its customer."""
    key = line = 0  # no row: keys and line numbers start at 1
    if count:
        last = np.lexsort((arrays["linenumber"],
                           arrays["orderkey"]))[count - 1]
        key = int(arrays["orderkey"][last])
        line = int(arrays["linenumber"][last])
    return (f" AND (l_orderkey < {key} OR (l_orderkey = {key} "
            f"AND l_linenumber <= {line}))")


def same_top_rows(got, want):
    """The rows and their order exactly, `revenue` to 1e-15 relative."""
    assert len(got) == len(want)
    for name in ("l_orderkey", "o_orderdate", "o_shippriority"):
        assert got[name].tolist() == want[name].tolist()
    np.testing.assert_allclose(got.revenue.to_numpy(),
                               want.revenue.to_numpy(), rtol=1e-15, atol=0)


class TestCompaction:
    """The compaction of the passing rows (PR 34), with the row floor
    lowered under this file's tables: the programs of this class are built
    WITH it (one per family for the whole class: the eager answers are
    what costs, 3-5 s each), and none outlives the class."""

    @pytest.fixture(scope="class", autouse=True)
    def compacting(self):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(cj, "_COMPACT_MIN_ROWS", 1_000)
            cj.PROGRAMS.clear()
            yield
            cj.PROGRAMS.clear()

    @pytest.mark.parametrize("day", [1, 16, 31])
    @pytest.mark.parametrize("segment", SEGMENTS)
    def test_compact_branch_equals_eager_filter_then_join(self, q3, segment,
                                                          day):
        """Q3 on the compacting program against the interpreted converters:
        the compacted rows keep their order, so each group's float64 sum
        adds the same terms."""
        c, _, _ = q3
        query = traffic.load("queries", f"tpch_q3_{segment}")
        (params,) = [x for x in traffic.all_params(query)
                     if x["DAY"] == day - 1]
        sql = traffic.render(query, params)
        before = counters(c)
        got = c.sql(sql).compute()
        assert "rung:compiled_join_aggregate" in span_names(c)
        assert moved(c, before)[1:] == (1, 0)
        attrs = tail_attrs(c)
        assert attrs["cap"] == cj.compact_capacity(ROWS) == 32_768
        assert 0 < attrs["passed"] < ROWS // 50 and attrs["rows"] == 10
        launch = [s for s in c.last_trace.spans if s.name == "launch"][-1]
        assert launch.attrs["compact"] == attrs["cap"]
        same_top_rows(got, eager(c, sql))

    @pytest.mark.parametrize("case", ["none_pass", "exactly_cap",
                                      "one_over_cap", "no_filter_at_all"])
    def test_overflow_takes_the_whole_probe_in_the_same_executable(self, q3,
                                                                   case):
        """`passed` against `cap`: up to `cap` rows the compact branch
        answers; one row more and the SAME executable reduces the probe
        whole under the mask (`join.compact.overflow`), with the eager
        path's answer and no second `compile:` span."""
        c, arrays, _ = q3
        cap = cj.compact_capacity(ROWS)
        passed = {"none_pass": 0, "exactly_cap": cap, "one_over_cap": cap + 1,
                  "no_filter_at_all": ROWS}[case]
        if case == "no_filter_at_all":
            sql = warm = Q3_SHAPED.format(where="")
        else:
            sql = Q3_SHAPED.format(where=first_rows(arrays, passed))
            warm = Q3_SHAPED.format(where=first_rows(arrays, 100))
        cj.PROGRAMS.clear()
        before = counters(c)
        c.sql(warm).compute()  # builds and compiles the family's ONE program
        assert sum(n.startswith("compile:") for n in span_names(c)) == 1
        assert moved(c, before) == (1, 1, int(warm == sql and passed > cap))
        before = counters(c)
        got = c.sql(sql).compute()
        names = span_names(c)
        assert "rung:compiled_join_aggregate" in names
        assert "family_hit" in names
        assert not [n for n in names if n.startswith("compile:")]
        assert moved(c, before) == (0, 1, int(passed > cap))
        attrs = tail_attrs(c)
        assert (attrs["passed"], attrs["cap"]) == (passed, cap)
        assert len(got) == (10 if passed else 0)
        same_top_rows(got, eager(c, sql))

    @pytest.mark.parametrize("gid, sql", [
        ("pointer", "SELECT d_key, SUM(f_opt) AS s, COUNT(f_opt) AS n, "
         "COUNT(*) AS m, MIN(f_n) AS lo, AVG(f_val) AS a FROM fact, dim "
         "WHERE f_key = d_key AND d_day < 25 GROUP BY d_key"),
        ("radix", "SELECT d_tag, SUM(f_opt) AS s, COUNT(f_opt) AS n, "
         "COUNT(*) AS m, MAX(f_n) AS hi FROM fact, dim "
         "WHERE f_key = d_key AND d_tag <> 'red' GROUP BY d_tag"),
        ("global", "SELECT SUM(f_opt) AS s, COUNT(f_opt) AS n, COUNT(*) AS m "
         "FROM fact, dim WHERE f_key = d_key AND f_n < 40"),
    ], ids=["pointer", "radix", "global"])
    def test_null_keys_and_null_arguments_survive_the_gather(self, gid, sql):
        """NULL probe keys match nothing and a NULL aggregate argument
        counts for nothing, gathered into the compact buffer (here wider
        than the 5,000-row probe: `cap` above the rows) as read in place."""
        import pyarrow as pa

        tables = star_tables()
        rng = np.random.default_rng(6)
        tables["fact"] = tables["fact"].append_column("f_opt", pa.array(
            rng.random(5_000), mask=rng.random(5_000) < 0.1))
        c = Context()
        for name, frame in tables.items():
            c.create_table(name, frame)
        before = counters(c)
        got = c.sql(sql).compute()
        assert "rung:compiled_join_aggregate" in span_names(c)
        assert moved(c, before) == (1, 1, 0)
        attrs = tail_attrs(c)
        assert attrs["cap"] == 32_768 and 0 < attrs["passed"] < 5_000
        want = eager(c, sql)
        if gid != "global":
            by = list(got.columns[:1])
            got, want = (f.sort_values(by).reset_index(drop=True)
                         for f in (got, want))
        assert int(got.n.sum()) < int(got.m.sum())  # NULL arguments exist
        pd.testing.assert_frame_equal(got, want, check_dtype=False,
                                      rtol=1e-15)

    @pytest.mark.parametrize("pull", ["whole_pack", "present_groups"])
    def test_without_topk_passed_rides_beside_the_plain_pack(
            self, q3, monkeypatch, pull):
        """No ORDER BY / LIMIT: the program's pack is the one-table rungs'
        own and `passed` its second, scalar output, in the same ONE pull:
        of the whole pack, or above `HOST_PULL_DOMAIN` of the present
        groups."""
        from dask_sql_tpu.physical import compiled

        c, _, _ = q3
        if pull == "present_groups":
            monkeypatch.setattr(compiled, "HOST_PULL_DOMAIN", 1_000)
        query = traffic.load("queries", "tpch_q3_household")
        sql = traffic.render(query, {"DAY": 7, "SEGMENT": 3})
        sql = sql[:sql.upper().index("ORDER BY")]
        cj.PROGRAMS.clear()
        before = counters(c)
        got = c.sql(sql).compute()
        assert "rung:compiled_join_aggregate" in span_names(c)
        fetches = [s for s in c.last_trace.spans if s.name == "fetch"
                   and s.attrs.get("rung") == "compiled_join_aggregate"]
        assert moved(c, before) == (1, 1, 0)
        (program,) = cj.PROGRAMS.values()
        assert program.topk is None and program.compact_cap == 32_768
        attrs = tail_attrs(c)
        assert attrs["groups"] == attrs["rows"] == len(got) > 10
        assert attrs["groups"] < attrs["passed"] < ROWS // 50
        assert len(fetches) == 1  # the pack and `passed` in ONE pull
        by = ["l_orderkey"]
        got, want = (f.sort_values(by).reset_index(drop=True)
                     for f in (got, eager(c, sql)))
        pd.testing.assert_frame_equal(got, want, check_dtype=False,
                                      rtol=1e-15)
