"""TPC-DS q1-q99 runner: every runnable query is VALUE-CHECKED against a
sqlite oracle (not just executed).

Parity: the reference's coverage yardstick (reference
tests/unit/test_queries.py:5-44 — 99 TPC-DS-style queries with a 38-query
XFAIL list; 61 expected passes on CPU) plus its oracle strategy (reference
tests/integration/test_postgres.py:13-53 value-checks against live engines).
Here 99 standard TPC-DS queries run against generated in-memory tables and
compare full result multisets with tests/ds_oracle (sqlite + dialect
translation); the xfail list below is the honest record of what the engine
cannot do yet, grouped by root cause.
"""
import pandas as pd
import pytest

from tests.ds_oracle import (
    assert_same_result,
    cross_check,
    duckdb_available,
    duckdb_query,
    make_duckdb,
    make_sqlite,
    strip_top_limit,
    translate,
)
from tests.tpcds import generate
from tests.tpcds_queries import QUERIES

# Root causes (round 3 state; re-rooted after the r3 fixes: GROUPING(),
# HAVING/ORDER BY select-alias resolution, empty-frame robustness, and the
# r2 engine work that had already cured the CTE-reuse class).  The three
# remaining shapes — EXISTS under OR (q10/q35) and a correlated scalar
# COUNT whose correlation predicate sits under OR (q41) — are xfailed by
# the REFERENCE too (reference tests/unit/test_queries.py:5-39).
#: round 5: q10/q35 decorrelate via MARK joins (EXISTS under OR becomes a
#: boolean matched column) and q41's hidden correlation factors out of its
#: disjunction — all three of the REFERENCE'S OWN xfails now pass here
XFAIL_QUERIES = {
}
# round 4: the former SLOW skips (q23/q24/q64) are gone — the optimizer now
# descends into subquery-embedded plans and the join reorderer flattens
# through CrossJoin and cast-wrapped join keys, so they run in seconds
SLOW_QUERIES = {}

#: queries with no faithful sqlite translation — value-checked by a
#: hand-built pandas oracle instead (see _pandas_q67)
NO_ORACLE = {
    67: "sqlite parser stack overflow on the 9-level ROLLUP expansion",
}


def _pandas_q67(tables):
    """Pandas oracle for q67: 8-key ROLLUP sum + per-category rank <= 100.

    The LIMIT-stripped comparand drops the top-level LIMIT only; rank ties
    make the <=100 cut itself well-defined (RANK admits all peers)."""
    ss, dd = tables["store_sales"], tables["date_dim"]
    st, it = tables["store"], tables["item"]
    m = (ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
         .merge(it, left_on="ss_item_sk", right_on="i_item_sk")
         .merge(st, left_on="ss_store_sk", right_on="s_store_sk"))
    m = m[(m.d_month_seq >= 1200) & (m.d_month_seq <= 1211)]
    m = m.assign(v=(m.ss_sales_price * m.ss_quantity).fillna(0.0))
    keys = ["i_category", "i_class", "i_brand", "i_product_name",
            "d_year", "d_qoy", "d_moy", "s_store_id"]
    frames = []
    for lvl in range(len(keys), -1, -1):
        kept = keys[:lvl]
        if kept:
            g = m.groupby(kept, dropna=False).v.sum().reset_index(name="sumsales")
        else:
            g = pd.DataFrame({"sumsales": [m.v.sum()]})
        for c in keys[lvl:]:
            g[c] = None
        frames.append(g[keys + ["sumsales"]])
    dw1 = pd.concat(frames, ignore_index=True)
    # RANK() OVER (PARTITION BY i_category ORDER BY sumsales DESC):
    # NaN partition keys group together (SQL GROUP-style null handling)
    part = dw1.i_category.fillna("\x00__null__")
    dw1["rk"] = (dw1.groupby(part).sumsales
                 .rank(method="min", ascending=False).astype(int))
    return dw1[dw1.rk <= 100].reset_index(drop=True)
#: division by zero: engine yields +-inf (pandas parity, like the
#: reference's dask/pandas execution); sqlite yields NULL
INF_IS_NULL = {90}


@pytest.fixture(scope="module")
def tpcds_tables():
    return generate(scale_rows=1000)


@pytest.fixture(scope="module")
def tpcds_context(tpcds_tables):
    from dask_sql_tpu import Context

    c = Context()
    for name, df in tpcds_tables.items():
        c.create_table(name, df)
    return c


@pytest.fixture(scope="module")
def sqlite_oracle(tpcds_tables):
    conn = make_sqlite(tpcds_tables)
    yield conn
    conn.close()


@pytest.fixture(scope="module")
def duckdb_oracle(tpcds_tables):
    """Second independent oracle; None when duckdb isn't installed (this
    image).  Fills the reference's postgres-in-docker role and covers the
    shapes sqlite can't parse (q67's 9-level ROLLUP)."""
    if not duckdb_available():
        yield None
        return
    conn = make_duckdb(tpcds_tables)
    yield conn
    conn.close()


#: the runner is cut into files of every N_SHARDS-th query: xdist's
#: ``--dist loadfile`` pins a file to one worker, and all 99 queries in this
#: one file took 1370 s of a 1399 s six-worker run — the whole suite waited
#: for it.  `test_queries_ds_b.py` / `_c.py` run shards 1 and 2.
N_SHARDS = 3


def _params(shard: int):
    for qnum in sorted(QUERIES):
        if qnum % N_SHARDS != shard:
            continue
        marks = []
        if qnum in SLOW_QUERIES:
            marks.append(pytest.mark.skip(reason=f"q{qnum}: {SLOW_QUERIES[qnum]}"))
        elif qnum in XFAIL_QUERIES:
            # declarative xfail: the query still RUNS, so a query that starts
            # passing surfaces as XPASS instead of silently going stale
            marks.append(pytest.mark.xfail(
                reason=f"q{qnum}: {XFAIL_QUERIES[qnum]}", strict=False))
        yield pytest.param(qnum, marks=marks)


def check_query(tpcds_context, tpcds_tables, sqlite_oracle, duckdb_oracle,
                qnum):
    # 1. the original query (LIMIT/top-k path) must execute
    result = tpcds_context.sql(QUERIES[qnum]).compute()
    assert result is not None
    assert len(result.columns) > 0
    if qnum == 67 and duckdb_oracle is None:
        # sqlite can't parse the shape: compare against the pandas oracle
        sql = strip_top_limit(QUERIES[qnum])
        result = tpcds_context.sql(sql).compute()
        expected = _pandas_q67(tpcds_tables)[list(result.columns)]
        assert_same_result(result, expected, qnum)
        return
    if qnum in NO_ORACLE and duckdb_oracle is None:
        return  # no engine that can parse this shape is available
    # 2. value check on the LIMIT-stripped variant: when ORDER BY keys tie
    # at the cut, engines legitimately keep different rows, so the
    # well-defined comparand is the full multiset
    sql = strip_top_limit(QUERIES[qnum])
    if sql != QUERIES[qnum].rstrip():
        result = tpcds_context.sql(sql).compute()
    oracles = []
    if qnum not in NO_ORACLE:
        tsql = translate(sql)
        assert tsql is not None, f"q{qnum}: translator declined"
        oracles.append(
            ("sqlite", lambda s: pd.read_sql_query(tsql, sqlite_oracle)))
    if duckdb_oracle is not None:
        oracles.append(
            ("duckdb", lambda s: duckdb_query(duckdb_oracle, s)))
    cross_check(result, oracles, sql, qnum, inf_is_null=qnum in INF_IS_NULL)


@pytest.mark.parametrize("qnum", _params(0))
def test_query(tpcds_context, tpcds_tables, sqlite_oracle, duckdb_oracle,
               qnum):
    check_query(tpcds_context, tpcds_tables, sqlite_oracle, duckdb_oracle,
                qnum)
