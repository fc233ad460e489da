"""Compiled in-plan inference (inference/, physical/compiled_predict.py).

Covers the tentpole contract end to end: tree/linear/kmeans lowering
equivalence vs sklearn ``predict`` (property-style over random fitted
trees, across dtypes and depths), the fused ``compiled_predict`` rung
(one executable, predictions matching the host path), zero-recompile
acceptance for literal variants AND retrained models, the ``predict``
fault site's ladder step-down with breaker charge, the estimator's
``model:`` row + admission interplay, PREDICT over encoded (DICT) inputs,
the SHOW MODELS / DESCRIBE MODEL lowering verdicts, the structured model
error taxonomy, and the HBM ledger's ``model_bytes`` component.
"""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu import observability
from dask_sql_tpu.inference import try_lower
from dask_sql_tpu.resilience import faults
from dask_sql_tpu.resilience.errors import ModelError, QueryError

pytestmark = pytest.mark.inference


@pytest.fixture(autouse=True)
def _restore_global_config():
    keys = ("serving.cache.enabled", "resilience.inject",
            "serving.admission.max_estimated_bytes", "sql.compile.predict",
            "serving.bg_compile.enabled")
    before = {k: config_module.config.get(k) for k in keys}
    faults.reset()
    yield
    config_module.config.update(before)
    faults.reset()


def _ctx(n=3000, seed=0):
    c = Context()
    c.config.update({"serving.cache.enabled": False})
    rng = np.random.RandomState(seed)
    df = pd.DataFrame({
        "x": rng.rand(n),
        "y": rng.rand(n),
        "code": rng.choice([10, 20, 30, 40], n).astype(np.int64),
    })
    df["target"] = (df.x + df.y > 1).astype(np.int64)
    c.create_table("t", df)
    return c, df


def _traced(c, sql):
    tr = observability.QueryTrace(qid="q", sql=sql, metrics=c.metrics,
                                  profiles=c.profiles)
    with observability.activate(tr):
        res = c.sql(sql, return_futures=False)
    return res, tr


def _compiles(tr):
    return [s.name for s in tr.spans if s.name.startswith("compile:")]


# ------------------------------------------------------------- lowering
@pytest.mark.parametrize("maker,classify", [
    (lambda d, s: __import__("sklearn.tree", fromlist=["x"])
     .DecisionTreeRegressor(max_depth=d, random_state=s), False),
    (lambda d, s: __import__("sklearn.tree", fromlist=["x"])
     .DecisionTreeClassifier(max_depth=d, random_state=s), True),
    (lambda d, s: __import__("sklearn.ensemble", fromlist=["x"])
     .RandomForestRegressor(n_estimators=5, max_depth=d, random_state=s),
     False),
    (lambda d, s: __import__("sklearn.ensemble", fromlist=["x"])
     .RandomForestClassifier(n_estimators=5, max_depth=d, random_state=s),
     True),
    (lambda d, s: __import__("sklearn.ensemble", fromlist=["x"])
     .GradientBoostingRegressor(n_estimators=8, max_depth=d,
                                random_state=s), False),
    (lambda d, s: __import__("sklearn.ensemble", fromlist=["x"])
     .GradientBoostingClassifier(n_estimators=6, max_depth=d,
                                 random_state=s), True),
])
@pytest.mark.parametrize("depth", [2, 5])
def test_tree_lowering_equivalence(maker, classify, depth):
    """Property-style: random fitted trees lower to tensor programs whose
    predictions match sklearn ``predict`` across dtypes and depths."""
    import jax
    import jax.numpy as jnp

    for seed, dtype in ((1, np.float64), (2, np.float32), (3, np.int64)):
        rng = np.random.RandomState(seed)
        X = (rng.rand(200, 4) * 100).astype(dtype)
        if classify:
            y = (X[:, 0].astype(np.float64)
                 + X[:, 1].astype(np.float64) > 100).astype(np.int64)
        else:
            y = X.astype(np.float64) @ rng.rand(4) + rng.randn(200)
        model = maker(depth, seed).fit(X, y)
        program, reason = try_lower(model)
        assert program is not None, reason
        Xt = (rng.rand(73, 4) * 100).astype(dtype)
        params = tuple(jnp.asarray(p) for p in program.params)
        out = np.asarray(jax.jit(program.apply)(
            params, jnp.asarray(Xt, dtype=jnp.float64)))
        ref = model.predict(Xt)
        if classify:
            assert (out == ref).all()
        else:
            np.testing.assert_allclose(out, ref, rtol=1e-9, atol=1e-9)


def test_linear_logistic_kmeans_lowering_equivalence():
    import jax.numpy as jnp
    from sklearn.cluster import KMeans
    from sklearn.linear_model import LinearRegression, LogisticRegression

    from dask_sql_tpu.ml import jax_models

    rng = np.random.RandomState(0)
    X = rng.rand(150, 3)
    yreg = X @ rng.rand(3)
    yclf = (X[:, 0] > 0.5).astype(np.int64)
    Xt = rng.rand(40, 3)
    for model, y, classify in (
            (LinearRegression(), yreg, False),
            (LogisticRegression(max_iter=300), yclf, True),
            (KMeans(n_clusters=3, n_init=2, random_state=0), None, True),
            (jax_models.LinearRegression(), yreg, False),
            (jax_models.LogisticRegression(), yclf, True),
            (jax_models.KMeans(n_clusters=3), None, True)):
        model.fit(X) if y is None else model.fit(X, y)
        program, reason = try_lower(model)
        assert program is not None, reason
        params = tuple(jnp.asarray(p) for p in program.params)
        out = np.asarray(program.apply(params,
                                       jnp.asarray(Xt, dtype=jnp.float64)))
        ref = np.asarray(model.predict(Xt))
        if classify:
            assert (out == ref).all(), type(model).__name__
        else:
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_scaler_lowers_as_matrix_and_declines_fused_shape():
    from sklearn.preprocessing import StandardScaler

    X = np.random.RandomState(0).rand(64, 3)
    program, _ = try_lower(StandardScaler().fit(X))
    assert program is not None and program.output == "matrix"
    import jax.numpy as jnp

    out = np.asarray(program.apply(
        tuple(jnp.asarray(p) for p in program.params), jnp.asarray(X)))
    np.testing.assert_allclose(out, StandardScaler().fit(X).transform(X),
                               rtol=1e-12)


def test_declines_keep_host_path():
    from sklearn.tree import DecisionTreeClassifier

    from dask_sql_tpu.ml.wrappers import ParallelPostFit

    X = np.random.RandomState(0).rand(64, 2)
    y = np.array(["a", "b"] * 32)  # string labels: no DOUBLE target
    program, reason = try_lower(DecisionTreeClassifier(max_depth=2)
                                .fit(X, y))
    assert program is None and "class" in reason
    wrapped = ParallelPostFit(DecisionTreeClassifier(max_depth=2)
                              .fit(X, (y == "a").astype(int)))
    program, reason = try_lower(wrapped)
    assert program is None and "host" in reason


def test_gbdt_custom_init_and_multioutput_decline():
    """A custom GBDT ``init`` estimator makes the raw-score baseline
    row-dependent, and multi-output trees would silently drop every
    output but the first — both must DECLINE to the host path instead of
    lowering into silently-wrong fused programs."""
    from sklearn.ensemble import (
        GradientBoostingRegressor,
        RandomForestRegressor,
    )
    from sklearn.linear_model import LinearRegression

    rng = np.random.RandomState(0)
    X = rng.rand(200, 3)
    y = X @ rng.rand(3)
    gb = GradientBoostingRegressor(n_estimators=5, max_depth=2,
                                   init=LinearRegression(),
                                   random_state=0).fit(X, y)
    program, reason = try_lower(gb)
    assert program is None
    Y2 = np.stack([y, -y], axis=1)
    rf = RandomForestRegressor(n_estimators=3, max_depth=3,
                               random_state=0).fit(X, Y2)
    program, reason = try_lower(rf)
    assert program is None and "multi-output" in reason


def test_shape_key_stable_across_retrain():
    """The recompile identity bakes the model's SHAPE, never its weights:
    a bounded-depth retrain on different data keys identically."""
    from sklearn.ensemble import GradientBoostingRegressor

    rng = np.random.RandomState(0)
    X = rng.rand(300, 5)
    y = X @ rng.rand(5)
    a = GradientBoostingRegressor(n_estimators=10, max_depth=3,
                                  random_state=1).fit(X, y)
    b = GradientBoostingRegressor(n_estimators=10, max_depth=3,
                                  random_state=9).fit(X[::-1], y[::-1])
    pa, _ = try_lower(a)
    pb, _ = try_lower(b)
    assert pa.shape_key == pb.shape_key
    assert any((np.asarray(x) != np.asarray(y_)).any()
               for x, y_ in zip(pa.params, pb.params))


# ------------------------------------------------------------ fused rung
def _create_forest(c, **kw):
    opts = dict(n_estimators=6, max_depth=4, random_state=0)
    opts.update(kw)
    with_opts = ", ".join(f"{k} = {v}" for k, v in opts.items())
    c.sql(f"""CREATE OR REPLACE MODEL m WITH (
              model_class = 'sklearn.ensemble.RandomForestClassifier',
              target_column = 'target', {with_opts})
              AS (SELECT x, y, target FROM t)""")


def test_fused_predict_one_executable_matches_sklearn():
    c, df = _create_ctx_and_forest()
    res, tr = _traced(
        c, "SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
           "WHERE x < 0.5)")
    # answered on the fused rung: the rung span is present and the host
    # tier never ran (no mid-plan pandas round trip)
    assert any(s.name == "rung:compiled_predict" for s in tr.spans)
    counters = c.metrics.snapshot()["counters"]
    assert counters.get("inference.predict.compiled") == 1
    assert counters.get("inference.predict.host") is None
    assert counters.get("resilience.rung.compiled_predict") == 1
    model, cols = c.get_model(c.schema_name, "m")
    sub = df[df.x < 0.5]
    assert len(res) == len(sub)
    assert (res["target"].to_numpy()
            == model.predict(sub[cols].to_numpy())).all()


def _create_ctx_and_forest():
    c, df = _ctx()
    _create_forest(c)
    return c, df


def test_zero_recompile_for_variant_and_retrain():
    """Acceptance: a second literal variant AND a retrained model both
    serve with zero foreground compile spans."""
    c, df = _ctx()
    _create_forest(c, random_state=3)
    _res, tr1 = _traced(
        c, "SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
           "WHERE x < 0.74)")
    assert _compiles(tr1), "first member should pay the family compiles"
    res2, tr2 = _traced(
        c, "SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
           "WHERE x < 0.75)")
    assert _compiles(tr2) == []
    # retrain with the same hyper-shape: weights swap, executable reused
    _create_forest(c, random_state=11)
    res3, tr3 = _traced(
        c, "SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
           "WHERE x < 0.75)")
    assert _compiles(tr3) == []
    counters = c.metrics.snapshot()["counters"]
    assert counters.get("inference.model.swap") == 1
    # and the swapped executable serves the NEW model's predictions
    model, cols = c.get_model(c.schema_name, "m")
    sub = df[df.x < 0.75]
    assert (res3["target"].to_numpy()
            == model.predict(sub[cols].to_numpy())).all()
    assert any(e["event"] == "model.swap"
               for e in observability.flight.RECORDER.events())


def test_predict_fault_steps_down_with_breaker_charge():
    """The ``predict`` fault site proves compiled_predict -> host predict
    degradation, charged per (family, rung): three consecutive failures
    trip the breaker and the fourth submission skips the rung."""
    c, df = _ctx()
    _create_forest(c)
    sql = ("SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
           "WHERE x < 0.5)")
    model, cols = c.get_model(c.schema_name, "m")
    expected = model.predict(df[df.x < 0.5][cols].to_numpy())
    c.config.update({"resilience.inject": "predict:3"})
    for _ in range(3):
        res = c.sql(sql, return_futures=False)
        assert (res["target"].to_numpy() == expected).all()
    counters = c.metrics.snapshot()["counters"]
    assert counters.get("resilience.degraded.compiled_predict") == 3
    assert counters.get("inference.predict.host") == 3
    assert counters.get("resilience.breaker.trip", 0) >= 1
    # breaker open: the rung is skipped without re-paying the failure
    res = c.sql(sql, return_futures=False)
    assert (res["target"].to_numpy() == expected).all()
    counters = c.metrics.snapshot()["counters"]
    assert counters.get("resilience.breaker.skip.compiled_predict", 0) >= 1


def test_estimator_model_row_and_admission_interplay():
    """PREDICT plans estimate like any other operator: finite bounds, a
    ``model:`` row in EXPLAIN ESTIMATE, and the admission gate can shed
    an over-budget inference plan BEFORE any compile."""
    c, _df = _ctx()
    _create_forest(c)
    rows = c.sql(
        "EXPLAIN ESTIMATE SELECT * FROM PREDICT(MODEL m, "
        "SELECT x, y FROM t WHERE x < 0.5)",
        return_futures=False)
    text = "\n".join(rows[rows.columns[0]].astype(str))
    assert "model: name=m tier=compiled" in text
    assert "param_bytes=" in text
    assert "rows_hi=3000" in text  # finite bounds, not a CustomNode hole
    c.config.update({"serving.admission.max_estimated_bytes": 1024})
    with pytest.raises(QueryError) as ei:
        c.sql("SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
              "WHERE x < 0.5)", return_futures=False)
    assert "bytes" in str(ei.value).lower()
    counters = c.metrics.snapshot()["counters"]
    assert counters.get("serving.shed_estimated_bytes") == 1
    assert counters.get("inference.predict.compiled") is None  # pre-compile


def test_predict_over_encoded_table_feeds_fused_trace():
    """DICT-encoded input columns feed the fused kernel as codes (decode
    in-kernel, survivors only) — no full-table decode before inference."""
    c, df = _ctx()
    tab = c.get_table_data(c.schema_name, "t")
    from dask_sql_tpu.columnar.encodings import Encoding

    assert tab.columns["code"].encoding is Encoding.DICT
    c.sql("""CREATE MODEL dm WITH (
             model_class = 'sklearn.tree.DecisionTreeClassifier',
             target_column = 'target', max_depth = 4, random_state = 0)
             AS (SELECT x, y, code, target FROM t)""")
    before = c.metrics.counter("columnar.encoding.decode")
    res = c.sql("SELECT * FROM PREDICT(MODEL dm, SELECT x, y, code FROM t "
                "WHERE code = 20)", return_futures=False)
    counters = c.metrics.snapshot()["counters"]
    assert counters.get("inference.predict.compiled") == 1
    assert counters.get("columnar.encoding.decode", 0) == before
    model, cols = c.get_model(c.schema_name, "dm")
    sub = df[df.code == 20]
    assert len(res) == len(sub)
    assert (res["target"].to_numpy()
            == model.predict(sub[cols].to_numpy())).all()


# ----------------------------------------------------- operator surfaces
def test_show_models_and_describe_surface_lowering_verdict():
    c, _df = _ctx()
    _create_forest(c)
    c.sql("""CREATE MODEL hostm WITH (
             model_class = 'sklearn.tree.DecisionTreeClassifier',
             wrap_predict = True, target_column = 'target', max_depth = 2)
             AS (SELECT x, y, target FROM t)""")
    models = c.sql("SHOW MODELS", return_futures=False)
    by_name = {r.Model: r for r in models.itertuples()}
    assert by_name["m"].Tier == "compiled"
    assert int(by_name["m"].ParamBytes) > 0
    assert "trees=6" in by_name["m"].Shape
    assert by_name["hostm"].Tier == "host"
    desc = c.sql("DESCRIBE MODEL m", return_futures=False)
    rows = dict(zip(desc["Params"], desc["Value"]))
    assert rows["lowering.tier"] == "compiled"
    assert int(rows["lowering.param_bytes"]) > 0
    assert "depth=4" in rows["lowering.shape"]


def test_model_error_taxonomy():
    c, _df = _ctx()
    # the historically dead experiment_class option now surfaces
    with pytest.raises(ModelError) as ei:
        c.sql("""CREATE MODEL bad WITH (model_class = 'LinearRegression',
                 experiment_class = 'sklearn.model_selection.GridSearchCV',
                 target_column = 'target')
                 AS (SELECT x, y, target FROM t)""", return_futures=False)
    assert ei.value.code == "MODEL_ERROR"
    assert ei.value.error_type == "USER_ERROR"
    with pytest.raises(ModelError) as ei:
        c.sql("""CREATE MODEL bad WITH (model_class = 'NoSuchModel',
                 target_column = 'target')
                 AS (SELECT x, y, target FROM t)""", return_futures=False)
    assert ei.value.code == "MODEL_ERROR"
    with pytest.raises(QueryError) as ei:
        c.sql("SELECT * FROM PREDICT(MODEL ghost, SELECT x, y FROM t)",
              return_futures=False)
    assert ei.value.code == "MODEL_NOT_FOUND"


def test_ledger_tracks_model_bytes():
    c, _df = _ctx()
    assert c.ledger.snapshot()["modelBytes"] == 0
    _create_forest(c)
    c.sql("SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
          "WHERE x < 0.5)", return_futures=False)
    snap = c.ledger.snapshot()
    assert snap["modelBytes"] > 0
    c.ledger.publish(c.metrics)
    gauges = c.metrics.snapshot()["gauges"]
    assert gauges["serving.ledger.model_bytes"] == snap["modelBytes"]
    c.sql("DROP MODEL m", return_futures=False)
    assert c.ledger.snapshot()["modelBytes"] == 0


def test_show_models_verdict_does_not_commit_hbm():
    """Advisory surfaces (SHOW MODELS / DESCRIBE MODEL / the estimator)
    lower WITHOUT committing params to device: a catalog statement must
    not consume HBM for models that never PREDICT.  First fused use
    commits."""
    c, _df = _ctx()
    _create_forest(c)
    c.sql("SHOW MODELS", return_futures=False)
    c.sql("DESCRIBE MODEL m", return_futures=False)
    assert c.ledger.snapshot()["modelBytes"] == 0
    c.sql("SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
          "WHERE x < 0.5)", return_futures=False)
    assert c.ledger.snapshot()["modelBytes"] > 0


def test_fused_predict_batched_members_share_one_stacked_launch():
    """CompiledPredict.run_batched stacks only the family literal prefix
    (model weights ride unmapped — no per-slot weight copies), and every
    member's predictions match the host model over its own literal's
    survivors."""
    import jax.numpy as jnp

    from dask_sql_tpu import inference
    from dask_sql_tpu.physical import compiled_predict as cp

    c, df = _ctx()
    _create_forest(c)
    c.sql("SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
          "WHERE x < 0.3)", return_futures=False)  # builds + caches
    compiled = next(program for (family, _), program
                    in reversed(cp.PROGRAMS.items()) if family.model == "m")
    model, cols = c.get_model(c.schema_name, "m")
    program, _ = inference.program_for(c, c.schema_name, "m", model,
                                       commit=True)
    table = c.get_table_data(c.schema_name, "t").select(["x", "y"])
    members = [(np.float64(0.25),) + tuple(program.params),
               (np.float64(0.6),) + tuple(program.params)]
    outs = compiled.run_batched(table, members)
    for lit, out in zip((0.25, 0.6), outs):
        sub = df[df.x < lit]
        assert out.num_rows == len(sub)
        got = np.asarray(jnp.ravel(out.columns["target"].data))[
            :out.num_rows]
        assert (got == model.predict(sub[cols].to_numpy())).all()
    # the stacked mask launch must not have duplicated the weight tail:
    # the batched vmap maps ONLY the family prefix
    axes = compiled._mask_batched  # built above
    assert axes is not None


def test_nullable_feature_declines_fused_and_surfaces_on_host():
    """A NULL in a feature column must not silently feed sentinel data
    into the fused kernel: the rung declines at construction and the host
    tier serves it with sklearn's own missing-value routing (or surfaces
    a structured error on models that reject NaN) — never silently-wrong
    fused predictions."""
    c, df = _ctx()
    _create_forest(c)
    df2 = df.copy()
    df2.loc[df2.index[:5], "x"] = np.nan
    c.create_table("tn", df2)
    res = c.sql("SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM tn "
                "WHERE y < 0.9)", return_futures=False)
    counters = c.metrics.snapshot()["counters"]
    assert counters.get("inference.predict.compiled") is None
    assert counters.get("inference.predict.host") == 1
    model, cols = c.get_model(c.schema_name, "m")
    sub = df2[df2.y < 0.9]
    assert (res["target"].to_numpy()
            == model.predict(sub[cols].to_numpy())).all()


def test_bucket_growth_defers_predict_recompile_to_background():
    """Table growth/replacement of a SEEN predict family defers the
    recompile to the background thread (the triggering query serves on
    the host tier) instead of paying a foreground XLA compile — the same
    defer_rebuild policy as the sibling compiled rungs."""
    c, df = _ctx()
    _create_forest(c)
    sql = ("SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
           "WHERE x < 0.5)")
    c.sql(sql, return_futures=False)  # compiles + remembers the bucket
    c.config.update({"serving.bg_compile.enabled": True})
    rng = np.random.RandomState(1)
    big = pd.DataFrame({
        "x": rng.rand(9000), "y": rng.rand(9000),
        "code": rng.choice([10, 20, 30, 40], 9000).astype(np.int64),
    })
    big["target"] = (big.x + big.y > 1).astype(np.int64)
    c.create_table("t", big)  # replacement: new uid, larger pow2 bucket
    res = c.sql(sql, return_futures=False)
    counters = c.metrics.snapshot()["counters"]
    assert counters.get("serving.bg_compile.deferred", 0) >= 1
    assert counters.get("inference.predict.host", 0) >= 1
    model, cols = c.get_model(c.schema_name, "m")
    sub = big[big.x < 0.5]
    assert (res["target"].to_numpy()
            == model.predict(sub[cols].to_numpy())).all()


def test_drop_model_evicts_fused_pipelines():
    """DROP MODEL must not leave cached executables pinning committed
    weights the ledger no longer reports."""
    from dask_sql_tpu.physical import compiled_predict as cp

    c, _df = _ctx()
    _create_forest(c)
    c.sql("SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
          "WHERE x < 0.5)", return_futures=False)
    schema = c.schema_name

    def cached():
        return [family for (family, _), _ in cp.PROGRAMS.items()
                if family.schema == schema and family.model == "m"]

    assert cached()
    c.sql("DROP MODEL m", return_futures=False)
    assert not cached()
    assert c.ledger.snapshot()["modelBytes"] == 0


def test_estimator_param_bytes_ride_upper_bound_only():
    """Model params are device-resident only IF the fused rung serves the
    plan (per-plan eligibility can deny it), so they must ride the
    conservative UPPER bound, never the provable admission floor — and
    vanish from the estimate entirely when the rung is off."""
    c, _df = _ctx()
    _create_forest(c)
    sql = ("EXPLAIN ESTIMATE SELECT * FROM PREDICT(MODEL m, "
           "SELECT x, y FROM t WHERE x < 0.5)")
    on = c.sql(sql, return_futures=False)
    on_text = "\n".join(on[on.columns[0]].astype(str))
    assert "tier=compiled" in on_text
    c.config.update({"sql.compile.predict": False})
    off = c.sql(sql, return_futures=False)
    off_text = "\n".join(off[off.columns[0]].astype(str))
    assert "tier=host" in off_text

    def bound(text, tag):
        row = next(r for r in text.splitlines()
                   if r.startswith("estimate:"))
        return int(row.split(tag)[1].split()[0])

    assert bound(off_text, "bytes_lo=") == bound(on_text, "bytes_lo=")
    assert bound(off_text, "bytes_hi=") < bound(on_text, "bytes_hi=")


def test_model_boundary_keeps_resource_taxonomy():
    """MemoryError / XLA RESOURCE_EXHAUSTED inside fit/predict keep their
    degradable resource taxonomy class instead of becoming a USER_ERROR
    ModelError — the host tier is itself a degradation target."""
    from dask_sql_tpu.physical.rel.custom.ml import _model_boundary
    from dask_sql_tpu.resilience.errors import ResourceExhaustedError

    def oom():
        raise MemoryError("predict allocation")

    with pytest.raises(ResourceExhaustedError):
        _model_boundary("PREDICT(MODEL m)", oom)

    def bad():
        raise TypeError("bad feature matrix")

    with pytest.raises(ModelError) as ei:
        _model_boundary("PREDICT(MODEL m)", bad)
    assert ei.value.code == "MODEL_ERROR"


def test_predict_fault_site_is_registered():
    from dask_sql_tpu.resilience.faults import SITE_ERRORS, FaultInjector

    assert "predict" in SITE_ERRORS
    FaultInjector("predict:once")  # parses


def test_compile_predict_off_switch_keeps_host_path():
    c, df = _ctx()
    _create_forest(c)
    c.config.update({"sql.compile.predict": False})
    res = c.sql("SELECT * FROM PREDICT(MODEL m, SELECT x, y FROM t "
                "WHERE x < 0.5)", return_futures=False)
    counters = c.metrics.snapshot()["counters"]
    assert counters.get("inference.predict.compiled") is None
    assert counters.get("inference.predict.host") == 1
    assert len(res) == (df.x < 0.5).sum()
