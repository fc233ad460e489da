"""Every XLA compile and persistent-cache load as spans and histograms
(observability/xla.py, ISSUE 37): one JAX monitoring listener per process
gives each compile an ``xla:lower`` and an ``xla:compile`` span on the trace
active in the compiling thread, tagged with ``fun``, the persistent cache's
answer and, inside `timed_jit_call`, the ``rung``; the ``xla.*_ms``
histograms observe with tracing on or off."""
import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.observability import (
    QueryTrace,
    activate,
    compile_sink,
    detail,
    flight,
    load_span,
    load_trace,
    timed_jit_call,
    xla,
)
from dask_sql_tpu.serving import compile_cache
from dask_sql_tpu.serving.metrics import MetricsRegistry

pytestmark = pytest.mark.observability


@pytest.fixture
def no_persistent_cache():
    """No cache directory for the test's extent, whatever an earlier test
    or the environment left on the process."""
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def _trace():
    metrics = MetricsRegistry()
    xla.declare(metrics)
    return QueryTrace("SELECT 1", metrics=metrics), metrics


def _xla(trace, fun=None):
    return [s for s in trace.spans if s.name.startswith("xla:")
            and (fun is None or s.attrs["fun"] == fun)]


def _count(metrics, name):
    return metrics.snapshot()["histograms"][name]["count"]


def test_cold_jit_gives_one_lower_and_one_compile(no_persistent_cache):
    def cold_fn_37(x):
        return x * 3.0 + 1.0

    fn = jax.jit(cold_fn_37)
    x = jnp.arange(8, dtype=jnp.float32)
    tr, metrics = _trace()
    with activate(tr), tr.span("execute"):
        fn(x)
    spans = _xla(tr, "cold_fn_37")
    assert [s.name for s in spans] == ["xla:lower", "xla:compile"]
    lower, comp = spans
    assert comp.attrs["cache"] == "off" and "cache" not in lower.attrs
    assert all(s.parent == "execute" and "rung" not in s.attrs
               for s in spans)
    assert lower.t1 <= comp.t0 and all(s.t1 >= s.t0 for s in spans)
    assert _count(metrics, "xla.lower_ms") >= 1
    assert _count(metrics, "xla.compile_ms") >= 1
    assert _count(metrics, "xla.cache_load_ms") == 0

    # a warm call compiles nothing and records nothing
    n = len(tr.spans)
    with activate(tr), tr.span("execute"):
        fn(x)
    assert [s.name for s in tr.spans[n:]] == ["execute"]


def test_eager_op_is_parented_by_the_open_detail_span(no_persistent_cache):
    x = jnp.asarray(np.random.default_rng(37).random(3709, dtype=np.float32))
    tr, _ = _trace()
    with activate(tr), tr.span("execute"), detail("x"):
        jnp.sort(x)
    sorts = _xla(tr, "sort")
    assert [s.name for s in sorts] == ["xla:lower", "xla:compile"]
    assert all(s.parent == "x" for s in sorts)


def test_rung_compile_nests_launch_compile_xla(no_persistent_cache):
    def rung_fn_37(x):
        return x - 2.0

    x = jnp.ones(7, dtype=jnp.float32)
    tr, metrics = _trace()
    with activate(tr), tr.span("execute"):
        timed_jit_call("rung37", jax.jit(rung_fn_37), x)
    by_name = {s.name: s for s in tr.spans}
    launch, comp = by_name["launch"], by_name["compile:rung37"]
    assert comp.parent == "launch" and launch.parent == "execute"
    assert (launch.t0, launch.t1) == (comp.t0, comp.t1)
    assert comp.attrs["persistent_hit"] is None
    spans = _xla(tr, "rung_fn_37")
    assert [s.name for s in spans] == ["xla:lower", "xla:compile"]
    for s in spans:
        assert s.parent == "compile:rung37" and s.attrs["rung"] == "rung37"
        assert comp.t0 <= s.t0 <= s.t1 <= comp.t1
    assert _count(metrics, "resilience.compile_ms.rung37") == 1


def test_persistent_cache_hit_is_a_cache_load(tmp_path, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    compile_cache.disable()
    assert compile_cache.enable(str(tmp_path / "cc"))
    try:
        def cached_fn_37(x):
            return x * 7.0 + 2.0

        x = jnp.ones(11, dtype=jnp.float32)
        tr, metrics = _trace()
        with activate(tr), tr.span("execute"):
            jax.jit(cached_fn_37)(x)
        assert _xla(tr, "cached_fn_37")[-1].attrs["cache"] == "miss"
        assert metrics.counter("resilience.compile_cache.miss") >= 1

        jax.clear_caches()
        before = compile_cache.stats()
        compiles = _count(metrics, "xla.compile_ms")
        n = len(tr.spans)
        with activate(tr), tr.span("execute"):
            jax.jit(cached_fn_37)(x)
        comp = [s for s in tr.spans[n:] if s.name == "xla:compile"]
        assert [s.attrs["cache"] for s in comp] == ["hit"]
        assert _count(metrics, "xla.cache_load_ms") == 1
        assert _count(metrics, "xla.compile_ms") == compiles
        assert metrics.counter("resilience.compile_cache.hit") == 1
        after = compile_cache.stats()
        assert after["hits"] == before["hits"] + 1
        assert after["compiles"] == before["compiles"] + 1
        assert after["cache_load_s"] > before["cache_load_s"]
    finally:
        compile_cache.disable()


def test_histograms_observe_with_tracing_off(no_persistent_cache):
    def untraced_fn_37(x):
        return x + 37.0

    x = jnp.ones(5, dtype=jnp.float32)
    metrics = MetricsRegistry()
    before = xla.totals()
    with compile_sink(metrics):
        jax.jit(untraced_fn_37)(x)
    hists = metrics.snapshot()["histograms"]
    assert hists["xla.lower_ms"]["count"] == 1
    assert hists["xla.compile_ms"]["count"] == 1
    after = xla.totals()
    assert after["compiles"] == before["compiles"] + 1
    assert after["compile_s"] > before["compile_s"]


def test_tracing_off_query_still_observes(no_persistent_cache):
    c = Context()
    c.create_table("t", pd.DataFrame({"a": np.arange(53, dtype=np.int64)}))
    with config_module.set({"observability.trace.enabled": False,
                            "serving.cache.enabled": False}):
        c.sql("SELECT SUM(a) AS s FROM t WHERE a > 3",
              return_futures=False)
    assert c.last_trace is None
    assert _count(c.metrics, "xla.compile_ms") >= 1


def test_fresh_context_declares_the_histograms():
    hists = Context().metrics.snapshot()["histograms"]
    for name in xla.HISTOGRAMS:
        assert hists[name]["count"] == 0 and hists[name]["sum"] == 0.0


def test_every_compile_stamps_the_flight_recorder(no_persistent_cache):
    def flight_fn_37(x):
        return x * x

    x = jnp.ones(3, dtype=jnp.float32)
    flight.RECORDER.clear()
    jax.jit(flight_fn_37)(x)
    start = flight.RECORDER.events(name="compile.start")
    end = flight.RECORDER.events(name="compile.end")
    assert [e["fun"] for e in start] == [e["fun"] for e in end] \
        == ["flight_fn_37"]
    assert end[0]["cache"] == "off" and "rung" not in end[0]
    assert start[0]["ts"] <= end[0]["ts"] and end[0]["ms"] >= 0


def test_load_compiles_land_on_the_load_trace(no_persistent_cache):
    """A compile inside `create_table` goes on the load's own trace, under
    the load phase open at the time, and into the loading context's
    registry."""
    c = Context()
    x = jnp.asarray(np.random.default_rng(7).random(4111, dtype=np.float32))
    with load_trace(c, "root", "t37"), load_span("encode", column="a"):
        jnp.sort(x)
    sorts = _xla(c.traces.get("load:root.t37"), "sort")
    assert [s.name for s in sorts] == ["xla:lower", "xla:compile"]
    assert all(s.parent == "load:encode" for s in sorts)
    assert _count(c.metrics, "xla.compile_ms") == 1
