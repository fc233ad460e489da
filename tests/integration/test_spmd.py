"""SPMD query execution (spmd/, ISSUE 11): device-sharded storage, sharded
compiled rungs, and mesh-aware serving — end to end on the virtual 8-device
mesh.

The acceptance bar: a sharded TPC-H q1-shaped query executes on the
``spmd_aggregate`` rung (trace span attr), returns results byte-identical
to the unsharded single-chip context, the second literal variant of the
family pays ZERO foreground compile spans, and an induced SPMD-rung
failure degrades cleanly to the single-chip compiled rung with the breaker
charged per (family, rung).
"""
import numpy as np
import pandas as pd
import pytest

import jax

from dask_sql_tpu import config as config_module

pytestmark = [
    pytest.mark.spmd,
    pytest.mark.skipif(len(jax.devices()) < 2,
                       reason="needs the virtual multi-device mesh"),
]


@pytest.fixture(autouse=True)
def _restore_global_config():
    keys = ("serving.cache.enabled", "resilience.inject",
            "parallel.auto_shard", "parallel.auto_shard.min_rows",
            "columnar.encoding.min_rows")
    before = {k: config_module.config.get(k) for k in keys}
    yield
    config_module.config.update(before)


def _df(n=100_003):
    """Deterministic frame whose float sums are EXACT in f64 (quarters of
    bounded ints), so per-shard partial sums psum to the same bits the
    single-chip scatter produces — the byte-identical bar is meaningful,
    not rounding luck.  `k` is low-cardinality so DICT encoding kicks in
    (the sharded table stays encoded: exchanges move codes)."""
    rng = np.random.RandomState(11)
    return pd.DataFrame({
        "g": rng.choice(["a", "b", "c", "d", "e"], n),
        "k": rng.randint(0, 40, n).astype(np.int64),
        "x": rng.randint(0, 4000, n) * 0.25,
        "q": rng.randint(1, 51, n).astype(np.int64),
    })


def _pair(df, **config):
    from dask_sql_tpu import Context

    cfg = {"serving.cache.enabled": False,
           # small enough that the test frame's columns encode
           "columnar.encoding.min_rows": 1024}
    cfg.update(config)
    sharded = Context()
    sharded.config.update(cfg)
    sharded.create_table("t", df, distributed=True)
    single = Context()
    single.config.update(cfg)
    single.create_table("t", df)
    return sharded, single


Q1_SHAPE = ("SELECT g, SUM(q) AS sum_qty, SUM(x) AS sum_price, "
            "AVG(x) AS avg_price, MIN(k) AS min_k, MAX(k) AS max_k, "
            "COUNT(*) AS cnt FROM t WHERE k < {lit} GROUP BY g")


def _compiles(ctx):
    tr = ctx.last_trace
    return [s.name for s in tr.spans if s.name.startswith("compile:")]


def _rung_spans(ctx):
    tr = ctx.last_trace
    return [(s.name, dict(s.attrs)) for s in tr.spans
            if s.name.startswith("rung:")]


def test_spmd_aggregate_end_to_end_byte_identical():
    df = _df()
    sharded, single = _pair(df)
    # the stored sharded table kept its encodings (codes move, not values)
    st = sharded.schema["root"].tables["t"].table
    assert st.has_encoded_columns(), "sharding must preserve DICT/FOR"

    got = sharded.sql(Q1_SHAPE.format(lit=33)).compute()
    # executed on the spmd_aggregate rung, visible as a trace span attr
    spans = _rung_spans(sharded)
    assert ("rung:spmd_aggregate",
            {"rung": "spmd_aggregate", "spmd": True}) in spans, spans
    assert sharded.metrics.counter("resilience.rung.spmd_aggregate") == 1
    assert sharded.metrics.counter("parallel.spmd.launches") == 1

    exp = single.sql(Q1_SHAPE.format(lit=33)).compute()
    assert single.metrics.counter("resilience.rung.spmd_aggregate") == 0
    g = got.sort_values("g").reset_index(drop=True)
    e = exp.sort_values("g").reset_index(drop=True)
    assert list(g.columns) == list(e.columns)
    for col in g.columns:
        a, b = g[col].to_numpy(), e[col].to_numpy()
        assert a.dtype == b.dtype, col
        assert (a == b).all(), f"column {col} differs: {a} vs {b}"


def test_second_literal_variant_zero_foreground_compiles():
    df = _df(40_003)
    sharded, _ = _pair(df)
    sharded.sql(Q1_SHAPE.format(lit=30)).compute()
    assert len(_compiles(sharded)) >= 1  # first variant pays the compile
    sharded.sql(Q1_SHAPE.format(lit=22)).compute()
    assert _compiles(sharded) == [], (
        "second literal variant must reuse the family's SPMD executable")
    assert sharded.metrics.counter("families.hit") >= 1


def test_spmd_select_filter_projection_matches():
    df = _df(40_003)
    sharded, single = _pair(df)
    q = "SELECT g, x * 2 AS x2 FROM t WHERE k < 7 LIMIT 11"
    got = sharded.sql(q).compute()
    exp = single.sql(q).compute()
    pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                  exp.reset_index(drop=True))
    assert sharded.metrics.counter("resilience.rung.spmd_select") == 1


def test_induced_spmd_failure_degrades_to_single_chip():
    df = _df(40_003)
    sharded, _ = _pair(df)
    sharded.config.update({"resilience.inject": "spmd:always"})
    got = sharded.sql(Q1_SHAPE.format(lit=25)).compute()
    # served, on the single-chip compiled rung, with the SPMD rung charged
    assert len(got) == 5
    m = sharded.metrics
    assert m.counter("resilience.degraded.spmd_aggregate") == 1
    assert m.counter("resilience.rung.compiled_aggregate") == 1
    assert m.counter("resilience.rung.spmd_aggregate") == 0
    # the breaker key is (family, rung): three strikes skip ONLY the spmd
    # rung — the single-chip rung keeps serving the family
    sharded.sql(Q1_SHAPE.format(lit=24)).compute()
    sharded.sql(Q1_SHAPE.format(lit=23)).compute()
    sharded.sql(Q1_SHAPE.format(lit=21)).compute()
    assert m.counter("resilience.breaker.skip.spmd_aggregate") >= 1
    assert m.counter("resilience.rung.compiled_aggregate") == 4


def test_auto_shard_policy_shards_registration():
    from dask_sql_tpu import Context
    from dask_sql_tpu.parallel.dist_plan import table_is_sharded

    df = _df(40_003)
    c = Context()
    c.config.update({"serving.cache.enabled": False,
                     "parallel.auto_shard": "on",
                     "parallel.auto_shard.min_rows": 1024})
    c.create_table("t", df)
    assert table_is_sharded(c.schema["root"].tables["t"].table)
    assert c.metrics.counter("parallel.auto_shard.tables") == 1
    # below the row floor: stays single-device
    c.create_table("tiny", df.head(100))
    assert not table_is_sharded(c.schema["root"].tables["tiny"].table)
    # an EXPLICIT distributed=False is a per-table opt-out the policy
    # must respect (None, the default, leaves the policy in charge)
    c.create_table("optout", df, distributed=False)
    assert not table_is_sharded(c.schema["root"].tables["optout"].table)


def test_create_table_with_distributed_passthrough(tmp_path):
    from dask_sql_tpu import Context
    from dask_sql_tpu.parallel.dist_plan import table_is_sharded

    df = _df(8_003)
    path = tmp_path / "t.csv"
    df.to_csv(path, index=False)
    c = Context()
    # the WITH (distributed=...) kwarg passes through CREATE TABLE to
    # create_table and shards the registration
    c.sql(f"CREATE TABLE dist_t WITH (location = '{path}', format = 'csv', "
          "distributed = true)")
    assert table_is_sharded(c.schema["root"].tables["dist_t"].table)
    # SQL literals may arrive as strings; 'false' must NOT shard
    c.create_table("dist2", df, distributed="true")
    assert table_is_sharded(c.schema["root"].tables["dist2"].table)
    c.create_table("dist3", df, distributed="false")
    assert not table_is_sharded(c.schema["root"].tables["dist3"].table)


def test_estimator_budgets_per_device():
    from dask_sql_tpu.analysis.estimator import estimate_plan

    df = _df(40_003)
    sharded, single = _pair(df)
    q = "SELECT g, SUM(x) AS s FROM t GROUP BY g"
    est_sharded = estimate_plan(sharded.sql(q).plan, context=sharded)
    est_single = estimate_plan(single.sql(q).plan, context=single)
    ndev = len(jax.devices())
    assert est_sharded.devices == min(ndev, 8)
    assert est_single.devices == 1
    # the provable per-chip floor divides by the mesh width
    assert est_sharded.peak_bytes.lo < est_single.peak_bytes.lo
    rows = est_sharded.format_rows()
    assert any(r.startswith("mesh: devices=") for r in rows), rows


def test_explain_lint_spmd_advisory():
    df = _df(40_003)
    sharded, single = _pair(df)
    rows = list(sharded.sql("EXPLAIN LINT SELECT g, SUM(x) FROM t GROUP BY g",
                            return_futures=False)["LINT"])
    spmd_rows = [r for r in rows if "[spmd]" in r]
    assert len(spmd_rows) == 1, rows
    assert "devices=" in spmd_rows[0]
    assert "per_device_bytes=" in spmd_rows[0]
    assert "eligible" in spmd_rows[0]
    # unsharded scans lint unchanged
    rows = list(single.sql("EXPLAIN LINT SELECT g, SUM(x) FROM t GROUP BY g",
                           return_futures=False)["LINT"])
    assert not [r for r in rows if "[spmd]" in r]


def test_family_batched_stacked_spmd_launch():
    """The family batcher's stacked launch vmaps over the leading
    parameter axis of the SAME SPMD program: member results equal their
    solo runs."""
    from dask_sql_tpu.spmd import aggregate as sa
    from dask_sql_tpu.spmd import select as ss

    df = _df(40_003)
    sharded, _ = _pair(df)
    sharded.sql(Q1_SHAPE.format(lit=20)).compute()  # build + cache
    # project every stored column so the cached pipeline's scan arity
    # matches the stored table we re-run it against below
    sharded.sql("SELECT g, k, x, q FROM t WHERE k < 4").compute()
    table = sharded.schema["root"].tables["t"].table

    aobj = sa.PROGRAMS.values()[-1]  # most recent (module LRU persists)
    params_list = [(np.int64(20),), (np.int64(10),), (np.int64(5),)]
    outs = aobj.run_batched(table, params_list)
    for p, out in zip(params_list, outs):
        exp = aobj.run(table, p).to_pandas()
        got = out.to_pandas()
        for col in got.columns:
            assert (got[col].to_numpy() == exp[col].to_numpy()).all(), col

    sobj = ss.PROGRAMS.values()[-1]
    params_list = [(np.int64(4),), (np.int64(2),)]
    outs = sobj.run_batched(table, params_list)
    for p, out in zip(params_list, outs):
        exp = sobj.run(table, p).to_pandas()
        got = out.to_pandas()
        for col in got.columns:
            assert (got[col].to_numpy() == exp[col].to_numpy()).all(), col


def test_shard_table_threads_existing_row_valid():
    """Regression (ISSUE 11 satellite): a table that ALREADY carries a
    row_valid mask keeps it through shard_table — the pre-fix code
    silently replaced a pre-masked table's mask whenever padding occurred
    (and dropped it when none did)."""
    import jax.numpy as jnp

    from dask_sql_tpu.columnar.column import Column
    from dask_sql_tpu.columnar.dtypes import SqlType
    from dask_sql_tpu.columnar.table import Table
    from dask_sql_tpu.parallel.distribute import shard_table
    from dask_sql_tpu.parallel.mesh import make_mesh

    ndev = min(8, len(jax.devices()))
    mesh = make_mesh(ndev)
    phys = 16 * ndev  # divisible: the pre-fix code would DROP the mask
    n_logical = phys - 5
    data = jnp.arange(phys, dtype=jnp.int64)
    mask = jnp.arange(phys) < n_logical
    t = Table({"a": Column(data, SqlType.BIGINT)}, n_logical, row_valid=mask)
    sharded = shard_table(t, mesh)
    assert sharded.num_rows == n_logical
    assert sharded.row_valid is not None
    np.testing.assert_array_equal(np.asarray(sharded.row_valid),
                                  np.asarray(mask))
    # and with fresh padding on top: the pre-masked rows stay invalid
    phys2 = 16 * ndev + 3  # non-divisible physical length
    n2 = phys2 - 7
    data2 = jnp.arange(phys2, dtype=jnp.int64)
    mask2 = jnp.arange(phys2) < n2
    t2 = Table({"a": Column(data2, SqlType.BIGINT)}, n2, row_valid=mask2)
    sharded2 = shard_table(t2, mesh)
    target = ((phys2 + ndev - 1) // ndev) * ndev
    rv = np.asarray(sharded2.row_valid)
    assert rv.shape[0] == target
    np.testing.assert_array_equal(rv[:phys2], np.asarray(mask2))
    assert not rv[phys2:].any()
    # the sharded mask is what aggregation sees: invalid rows never count
    total = int(np.asarray(
        jnp.sum(jnp.where(sharded2.row_valid,
                          sharded2.columns["a"].data, 0))))
    assert total == int(np.arange(n2).sum())
