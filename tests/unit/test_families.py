"""Parameterized plan families + inter-query batched execution (families/).

Covers the family contract end to end: literal extraction (scalars,
optimizer-folded constants, date/interval literals, IN-list pow2 buckets,
LIMIT windows), the compile-once-run-many acceptance criterion (a second
same-family query produces ZERO foreground ``compile:<rung>`` spans), the
family keying of the result cache / breaker / estimator / profiles, the
serving batcher's stacked launch, and the ``families.enabled`` off-switch.
"""
import threading

import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu import families
from dask_sql_tpu.families.batcher import FamilyBatcher
from dask_sql_tpu.planner.expressions import (
    InListExpr,
    InParamExpr,
    Literal,
    ParamRef,
    ScalarFunc,
)
from dask_sql_tpu.columnar.dtypes import SqlType

pytestmark = pytest.mark.families


@pytest.fixture(autouse=True)
def _restore_global_config():
    """`Context.config` is process-global; _ctx() below disables the
    result cache for determinism — restore every key we touch so later
    test modules see the defaults."""
    keys = ("serving.cache.enabled", "families.enabled")
    before = {k: config_module.config.get(k) for k in keys}
    yield
    config_module.config.update(before)


def _ctx(n=512, name="ft"):
    c = Context()
    c.config.update({"serving.cache.enabled": False})
    rng = np.random.RandomState(7)
    df = pd.DataFrame({
        "a": np.arange(n, dtype=np.int64),
        "b": rng.rand(n),
        "k": rng.choice(["x", "y", "z"], n),
        "d": pd.to_datetime("1995-01-01")
        + pd.to_timedelta(rng.randint(0, 900, n), unit="D"),
    })
    c.create_table(name, df)
    return c, df


def _compiles(trace):
    return [s.name for s in trace.spans if s.name.startswith("compile:")]


# ------------------------------------------------------------ parameterize
def test_scalar_literal_parameterizes():
    pz = families.Parameterizer()
    e = ScalarFunc("gt", (Literal(5, SqlType.BIGINT),
                          Literal(2.5, SqlType.DOUBLE)), SqlType.BOOLEAN)
    out = pz.rewrite(e)
    assert isinstance(out.args[0], ParamRef)
    assert isinstance(out.args[1], ParamRef)
    assert [v.item() for v in pz.values] == [5, 2.5]
    # the stripped form is value-free: a different literal stringifies SAME
    pz2 = families.Parameterizer()
    e2 = ScalarFunc("gt", (Literal(99, SqlType.BIGINT),
                           Literal(0.125, SqlType.DOUBLE)), SqlType.BOOLEAN)
    assert str(pz2.rewrite(e2)) == str(out)


def test_string_null_and_pattern_literals_stay_baked():
    pz = families.Parameterizer()
    s = pz.rewrite(Literal("abc", SqlType.VARCHAR))
    n = pz.rewrite(Literal(None, SqlType.BIGINT))
    like = pz.rewrite(ScalarFunc(
        "like", (Literal(1, SqlType.BIGINT), Literal("a%", SqlType.VARCHAR)),
        SqlType.BOOLEAN))
    trunc = pz.rewrite(ScalarFunc(
        "datetime_floor", (Literal(7, SqlType.TIMESTAMP),
                           Literal("DAY", SqlType.VARCHAR)), SqlType.TIMESTAMP))
    assert isinstance(s, Literal) and isinstance(n, Literal)
    # LIKE arg 0 may parameterize; the pattern must not
    assert isinstance(like.args[1], Literal)
    assert isinstance(trunc.args[1], Literal)
    # the truncation VALUE argument also stays baked (static-tail op)
    assert isinstance(trunc.args[0], ParamRef) or isinstance(
        trunc.args[0], Literal)


def test_in_list_pow2_bucketing():
    pz = families.Parameterizer()
    arg = Literal(0, SqlType.BIGINT)  # stands in for a column-typed expr
    from dask_sql_tpu.planner.expressions import ColumnRef

    col = ColumnRef(0, "a", SqlType.BIGINT)
    for items, bucket in ((2, 2), (3, 4), (4, 4), (5, 8), (8, 8), (9, 16)):
        pz = families.Parameterizer()
        e = InListExpr(col, tuple(Literal(i, SqlType.BIGINT)
                                  for i in range(items)), False)
        out = pz.rewrite(e)
        assert isinstance(out, InParamExpr), (items, out)
        assert out.length == bucket
        assert len(pz.values[0]) == bucket
        # padding repeats the max: membership set unchanged
        assert set(pz.values[0].tolist()) == set(range(items))
    del arg


def test_in_list_with_null_member_stays_baked():
    """3VL regression (review finding): `x NOT IN (v, NULL)` is never TRUE
    while `x NOT IN (v)` can be — normalizing the NULL away would give both
    one family identity and ONE result-cache key.  A NULL member must keep
    the whole list baked so the NULL stays in the family repr."""
    from dask_sql_tpu.planner.expressions import ColumnRef

    col = ColumnRef(0, "a", SqlType.BIGINT)
    with_null = InListExpr(col, (Literal(2, SqlType.BIGINT),
                                 Literal(None, SqlType.BIGINT)), True)
    without = InListExpr(col, (Literal(2, SqlType.BIGINT),), True)
    pz1, pz2 = families.Parameterizer(), families.Parameterizer()
    out1, out2 = pz1.rewrite(with_null), pz2.rewrite(without)
    assert isinstance(out1, InListExpr) and not pz1.values
    assert isinstance(out2, InParamExpr)
    assert repr(out1) != repr(out2)


def test_in_list_with_string_or_computed_items_stays_baked():
    from dask_sql_tpu.planner.expressions import ColumnRef

    pz = families.Parameterizer()
    scol = ColumnRef(0, "k", SqlType.VARCHAR)
    e = InListExpr(scol, (Literal("x", SqlType.VARCHAR),), False)
    assert isinstance(pz.rewrite(e), InListExpr)
    icol = ColumnRef(0, "a", SqlType.BIGINT)
    computed = InListExpr(
        icol, (ScalarFunc("add", (Literal(1, SqlType.BIGINT),
                                  Literal(2, SqlType.BIGINT)), SqlType.BIGINT),),
        False)
    out = pz.rewrite(computed)
    assert isinstance(out, InListExpr)
    # and the kept items were NOT parameterized inside (trace evaluator
    # requires Literal items)
    assert not pz.values


# ------------------------------------- compile-once-run-many (acceptance)
def test_second_literal_variant_compiles_nothing_aggregate():
    c, df = _ctx()
    c.sql("SELECT k, SUM(b) AS s FROM ft WHERE a > 10 GROUP BY k",
          return_futures=False)
    t1 = c.last_trace
    c.sql("SELECT k, SUM(b) AS s FROM ft WHERE a > 250 GROUP BY k",
          return_futures=False)
    t2 = c.last_trace
    assert t1.fingerprint == t2.fingerprint
    assert len(_compiles(t1)) >= 1
    assert _compiles(t2) == []
    assert c.metrics.counter("families.hit") >= 1
    assert c.metrics.counter("families.estimate.hit") >= 1


def test_second_literal_variant_compiles_nothing_select():
    c, df = _ctx()
    # literals chosen so both queries land in the same pow2 survivor
    # bucket (the gather kernel's shape); the mask kernel is shared by
    # construction
    r1 = c.sql("SELECT a, b * 2 AS bb FROM ft WHERE b > 0.52 "
               "ORDER BY bb DESC LIMIT 10", return_futures=False)
    t1 = c.last_trace
    r2 = c.sql("SELECT a, b * 3 AS bb FROM ft WHERE b > 0.55 "
               "ORDER BY bb DESC LIMIT 10", return_futures=False)
    t2 = c.last_trace
    assert t1.fingerprint == t2.fingerprint
    assert _compiles(t2) == []
    exp = (df[df.b > 0.55].assign(bb=df.b * 3)
           .sort_values("bb", ascending=False).head(10))
    np.testing.assert_allclose(r2["bb"].to_numpy(), exp["bb"].to_numpy())
    assert len(r1) == 10


def test_optimizer_folded_constants_join_family():
    c, df = _ctx()
    r1 = c.sql("SELECT SUM(b) AS s FROM ft WHERE a > 1 + 1",
               return_futures=False)
    t1 = c.last_trace
    r2 = c.sql("SELECT SUM(b) AS s FROM ft WHERE a > 100",
               return_futures=False)
    t2 = c.last_trace
    assert t1.fingerprint == t2.fingerprint
    assert _compiles(t2) == []
    np.testing.assert_allclose(r1["s"][0], df[df.a > 2].b.sum())
    np.testing.assert_allclose(r2["s"][0], df[df.a > 100].b.sum())


def test_date_and_interval_literals_join_family():
    c, df = _ctx()
    # plain DATE literal comparisons: one family across date values
    r1 = c.sql("SELECT COUNT(*) AS n FROM ft WHERE d <= DATE '1996-01-01'",
               return_futures=False)
    t1 = c.last_trace
    r2 = c.sql("SELECT COUNT(*) AS n FROM ft WHERE d <= DATE '1996-09-02'",
               return_futures=False)
    t2 = c.last_trace
    assert r1["n"][0] == (df.d <= pd.Timestamp("1996-01-01")).sum()
    assert r2["n"][0] == (df.d <= pd.Timestamp("1996-09-02")).sum()
    assert t1.fingerprint == t2.fingerprint
    assert _compiles(t2) == []
    # date - interval arithmetic: both the date and the interval scalar
    # parameterize, so two (date, interval) pairs share one family
    r3 = c.sql("SELECT COUNT(*) AS n FROM ft "
               "WHERE d <= DATE '1997-01-01' - INTERVAL '90' DAY",
               return_futures=False)
    t3 = c.last_trace
    r4 = c.sql("SELECT COUNT(*) AS n FROM ft "
               "WHERE d <= DATE '1998-01-01' - INTERVAL '30' DAY",
               return_futures=False)
    t4 = c.last_trace
    for r, (date, days) in ((r3, ("1997-01-01", 90)),
                            (r4, ("1998-01-01", 30))):
        cutoff = pd.Timestamp(date) - pd.Timedelta(days=days)
        assert r["n"][0] == (df.d <= cutoff).sum()
    assert t3.fingerprint == t4.fingerprint
    assert _compiles(t4) == []


def test_in_list_buckets_split_families_and_stay_correct():
    c, df = _ctx()
    r3 = c.sql("SELECT SUM(b) AS s FROM ft WHERE a IN (1, 2, 3)",
               return_futures=False)
    t3 = c.last_trace
    r4 = c.sql("SELECT SUM(b) AS s FROM ft WHERE a IN (7, 8, 9, 10)",
               return_futures=False)
    t4 = c.last_trace
    r5 = c.sql("SELECT SUM(b) AS s FROM ft WHERE a IN (1, 2, 3, 4, 5)",
               return_futures=False)
    t5 = c.last_trace
    # 3 and 4 values share the 4-bucket => one family, no recompile
    assert t3.fingerprint == t4.fingerprint
    assert _compiles(t4) == []
    # 5 values cross into the 8-bucket => a new family, fresh compile
    assert t5.fingerprint != t3.fingerprint
    assert len(_compiles(t5)) >= 1
    np.testing.assert_allclose(r3["s"][0], df[df.a.isin([1, 2, 3])].b.sum())
    np.testing.assert_allclose(
        r4["s"][0], df[df.a.isin([7, 8, 9, 10])].b.sum())
    np.testing.assert_allclose(
        r5["s"][0], df[df.a.isin([1, 2, 3, 4, 5])].b.sum())


def test_limit_windows_are_family_boundaries():
    c, df = _ctx()
    c.sql("SELECT a FROM ft WHERE b > 0.9 LIMIT 5", return_futures=False)
    ta = c.last_trace
    c.sql("SELECT a FROM ft WHERE b > 0.8 LIMIT 5", return_futures=False)
    tb = c.last_trace
    c.sql("SELECT a FROM ft WHERE b > 0.9 LIMIT 6", return_futures=False)
    tc = c.last_trace
    # same LIMIT, different filter literal: one family
    assert ta.fingerprint == tb.fingerprint
    # different LIMIT window: its own family (static host slicing)
    assert tc.fingerprint != ta.fingerprint


# ------------------------------------------------- family-keyed consumers
def test_result_cache_distinguishes_param_values():
    c, df = _ctx()
    c.config.update({"serving.cache.enabled": True})
    try:
        r1 = c.sql("SELECT SUM(b) AS s FROM ft WHERE a > 100",
                   return_futures=False)
        r1b = c.sql("SELECT SUM(b) AS s FROM ft WHERE a > 100",
                    return_futures=False)
        r2 = c.sql("SELECT SUM(b) AS s FROM ft WHERE a > 300",
                   return_futures=False)
        # identical literals: second is a result-cache hit
        assert c.metrics.counter("query.cache.hit") >= 1
        # different literal, same family: MUST NOT serve the cached result
        np.testing.assert_allclose(r1["s"][0], df[df.a > 100].b.sum())
        np.testing.assert_allclose(r1b["s"][0], df[df.a > 100].b.sum())
        np.testing.assert_allclose(r2["s"][0], df[df.a > 300].b.sum())
    finally:
        c.config.update({"serving.cache.enabled": False})


def test_profiles_roll_up_by_family_and_show_family_column():
    c, df = _ctx()
    c.sql("SELECT SUM(b) AS s FROM ft WHERE a > 11", return_futures=False)
    c.sql("SELECT SUM(b) AS s FROM ft WHERE a > 22", return_futures=False)
    fp = c.last_trace.fingerprint
    prof = c.profiles.get(fp)
    assert prof is not None and prof["hits"] >= 2  # both variants rolled up
    assert prof["family"] == fp
    rows = c.sql("SHOW PROFILES", return_futures=False)
    assert list(rows.columns) == ["Fingerprint", "Family", "Metric", "Value"]
    assert fp in set(rows["Family"])


def test_warm_candidates_dedupe_by_family():
    from dask_sql_tpu.observability import ProfileStore

    store = ProfileStore()
    store.record_exec("fp1", sql="SELECT 1", family="famA")
    store.record_exec("fp2", sql="SELECT 2", family="famA")
    store.record_exec("fp3", sql="SELECT 3", family="famB")
    got = store.warm_candidates(10)
    fams = [store.get(fp)["family"] for fp, _ in got]
    assert sorted(fams) == ["famA", "famB"]  # one representative per family


def test_breaker_keys_by_family():
    """A rung verdict earned under one literal applies to the whole
    family: the breaker key is the family fingerprint."""
    c, df = _ctx()
    c.sql("SELECT SUM(b) AS s FROM ft WHERE a > 5", return_futures=False)
    fam = c.last_trace.fingerprint
    info = families.family_of(
        c.sql("SELECT SUM(b) AS s FROM ft WHERE a > 6").plan, c.config)
    assert info is not None and info.fingerprint == fam


# --------------------------------------------------------------- batcher
def test_batcher_coalesces_concurrent_same_family_queries():
    c, df = _ctx(n=4096)
    from dask_sql_tpu.serving.runtime import ServingRuntime

    rt = ServingRuntime(workers=8, metrics=c.metrics,
                        batch_queries=4, batch_window_ms=2000.0)
    c.serving = rt
    try:
        lits = [50, 150, 250, 350]
        sqls = {l: f"SELECT k, SUM(b) AS s FROM ft WHERE a > {l} GROUP BY k"
                for l in lits}
        for l in lits:
            c.sql(sqls[l])  # pre-plan so clients rendezvous at the executor

        def client(lit):
            def work(_t):
                return c.sql(sqls[lit]).execute()
            return work

        futs = [rt.submit(client(l))[1] for l in lits]
        for lit, fut in zip(lits, futs):
            got = fut.result(300).to_pandas()
            exp = df[df.a > lit].groupby("k").b.sum()
            gotmap = dict(zip([str(x) for x in got[got.columns[0]]],
                              got["s"]))
            for k in exp.index:
                np.testing.assert_allclose(gotmap[k], exp[k], rtol=1e-9)
        assert c.metrics.counter("serving.batch.launches") >= 1
        assert c.metrics.counter("serving.batch.queries") >= 2
    finally:
        rt.shutdown(wait=True)
        c.serving = None


def test_batcher_propagates_leader_failure_to_followers():
    batcher = FamilyBatcher(max_queries=4, window_ms=200.0)
    boom = RuntimeError("stacked launch died")
    outcomes = {}

    def member(i):
        def solo():
            return f"solo-{i}"

        def batched(members):
            raise boom

        try:
            outcomes[i] = batcher.run("key", (i,), solo, batched)
        except RuntimeError as e:
            outcomes[i] = e

    threads = [threading.Thread(target=member, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert all(outcomes[i] is boom for i in range(4))


def test_batcher_solo_when_alone():
    calls = []
    batcher = FamilyBatcher(max_queries=4, window_ms=1.0,
                            busy=lambda: False)
    out = batcher.run("k", (1,), solo=lambda: calls.append("solo") or 42,
                      batched=lambda m: calls.append("batched") or [0] * 4)
    assert out == 42 and calls == ["solo"]


def test_batcher_disabled_at_max_queries_one():
    batcher = FamilyBatcher(max_queries=1, window_ms=1000.0)
    assert batcher.run("k", (1,), solo=lambda: "s",
                       batched=lambda m: ["b"]) == "s"


# ------------------------------------------------------------- off-switch
def test_families_disabled_restores_literal_identity():
    c, df = _ctx()
    c.config.update({"families.enabled": False})
    try:
        c.sql("SELECT SUM(b) AS s FROM ft WHERE a > 10", return_futures=False)
        t1 = c.last_trace
        c.sql("SELECT SUM(b) AS s FROM ft WHERE a > 20", return_futures=False)
        t2 = c.last_trace
        # literal-baked identities again: different fingerprints, and the
        # second variant pays its own compile
        assert t1.fingerprint != t2.fingerprint
        assert len(_compiles(t2)) >= 1
        assert c.metrics.counter("families.parameterized") == 0
    finally:
        c.config.update({"families.enabled": True})


def test_family_fingerprint_is_deterministic():
    c, _ = _ctx(name="ft_det_a")
    c2, _ = _ctx(name="ft_det_a")
    c.sql("SELECT SUM(b) AS s FROM ft_det_a WHERE a > 10",
          return_futures=False)
    c2.sql("SELECT SUM(b) AS s FROM ft_det_a WHERE a > 999",
           return_futures=False)
    # separate Contexts/processes-worth of state, same statement shape:
    # same family fingerprint (the pre-warm/checkpoint contract)
    assert c.last_trace.fingerprint == c2.last_trace.fingerprint


# ------------------------- the benchmark's Q1 texts: identity is a contract
#: `physical/compiled.py`'s program-cache family for perfbench's literal-date
#: Q1 over chip_smoke's lineitem, as every PR up to 31 built it.  The
#: benchmark's other cells find their executables in the persistent XLA cache
#: through this identity (and an unchanged traced body): a change here
#: recompiles them.
_Q1_LITERAL_FAMILY = (
    "root", "lineitem", (), ("le(#6:l_shipdate, ?0:TIMESTAMP)",),
    ("#0:l_returnflag", "#1:l_linestatus"),
    ("sum(#2:l_quantity)", "sum(#3:l_extendedprice)",
     "sum(mul(#3:l_extendedprice, sub(?1:DOUBLE, #4:l_discount)))",
     "sum(mul(mul(#3:l_extendedprice, sub(?2:DOUBLE, #4:l_discount)), "
     "add(?3:DOUBLE, #5:l_tax)))",
     "avg(#2:l_quantity)", "avg(#3:l_extendedprice)", "avg(#4:l_discount)",
     "count_star()"),
    "auto")
_Q1_INTERVAL_FILTER = ("le(#6:l_shipdate, CAST(datetime_sub_interval("
                       "?0:DATE, ?1:INTERVAL_DAY_TIME) AS TIMESTAMP))")


@pytest.fixture
def q1_families(monkeypatch):
    """A context over chip_smoke's lineitem, and every ``(family, bucket)``
    `try_compiled_aggregate` asks its program cache for."""
    import chip_smoke
    from dask_sql_tpu.physical import compiled

    asked = []
    get_or_build = compiled.PROGRAMS.get_or_build

    def spy(ctx, family, bucket, construct, **kwargs):
        asked.append((family, bucket))
        return get_or_build(ctx, family, bucket, construct, **kwargs)

    monkeypatch.setattr(compiled.PROGRAMS, "get_or_build", spy)
    c = Context()
    c.config.update({"serving.cache.enabled": False})
    c.create_table("lineitem", chip_smoke.gen_lineitem(4096, seed=0))
    return c, asked


def test_q1_literal_text_keeps_its_family_and_program_key(q1_families):
    from perfbench import traffic

    c, asked = q1_families
    c.sql(traffic.render(traffic.load("queries", "tpch_q1"), {"DELTA": 90}),
          return_futures=False)
    assert [family for family, _ in asked] == [_Q1_LITERAL_FAMILY]


def test_q1_interval_text_is_one_family_one_program_one_compile(q1_families):
    """Q1 as the specification prints it, over two DELTAs: ONE family (the
    date and the interval are two runtime scalars), one program-cache
    entry, one ``compile:`` span."""
    from dask_sql_tpu.physical import compiled
    from perfbench import traffic

    c, asked = q1_families
    query = traffic.load("queries", "tpch_q1_interval")
    compiles, prints = [], set()
    for delta in (90, 61):
        c.sql(traffic.render(query, {"DELTA": delta}), return_futures=False)
        compiles += _compiles(c.last_trace)
        prints.add(c.last_trace.fingerprint)
    assert compiles == ["compile:compiled_aggregate"]
    assert len(prints) == 1
    assert len(asked) == 2 and asked[0] == asked[1]
    family, bucket = asked[0]
    assert family[3] == (_Q1_INTERVAL_FILTER,)
    assert family[:3] + family[4:5] == \
        _Q1_LITERAL_FAMILY[:3] + _Q1_LITERAL_FAMILY[4:5]
    assert [key for key, _ in compiled.PROGRAMS.items()
            if key[1] == bucket] == [(family, bucket)]
    assert c.metrics.counter("families.hit") == 1
