"""Zero-cold-start serving (ISSUE 7): persistent executable cache,
profile-driven pre-warm + /v1/health readiness, background recompile,
compile watchdog, and checkpointed breaker verdicts.

The restart story under test: process A serves traffic, snapshots; process
B (a fresh Context in the same pytest process) loads the snapshot, warms
the hot fingerprints in the background, and the first real query runs with
ZERO foreground compile spans — either the warm-up compiled it already or
the persistent XLA cache deserialized the executable.  Fault injection
proves a hung compile degrades through the ladder instead of wedging a
worker, and that interrupted warm-ups / torn cache entries never corrupt
state.
"""
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.resilience import faults
from dask_sql_tpu.serving import compile_cache

pytestmark = pytest.mark.coldstart

AGG_QUERY = "SELECT g, SUM(x) AS s FROM t GROUP BY g ORDER BY g"


@pytest.fixture(autouse=True)
def _fresh_injector():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture
def config_keys():
    """Update GLOBAL config keys for the test, restoring originals after.
    Global (not scoped) on purpose: warm-up and background-compile threads
    read base config, not this thread's overlay stack."""
    cfg = config_module.config
    saved = {}

    def apply(options):
        for k, v in options.items():
            saved.setdefault(k, cfg.get(k))
        cfg.update(options)

    yield apply
    cfg.update(saved)


@pytest.fixture
def persistent_cache(tmp_path, config_keys, monkeypatch):
    """A live persistent compile cache for this test, torn down after (the
    jax cache dir is process-global state).  The path goes through config,
    so nothing from outside may place the directory."""
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    path = str(tmp_path / "compile-cache")
    config_keys({"serving.compile_cache.path": path})
    yield path
    compile_cache.disable()


def _frame(n=200):
    return pd.DataFrame({"g": ["a", "b"] * (n // 2),
                         "x": np.arange(n, dtype=np.float64)})


def _ctx(n=200):
    c = Context()
    c.create_table("t", _frame(n))
    return c


def _compile_spans(trace):
    return [s for s in trace.spans if s.name.startswith("compile:")]


# ---------------------------------------------------------------------------
# persistent executable cache
# ---------------------------------------------------------------------------
def test_persistent_cache_survives_restart(persistent_cache, config_keys):
    """A fresh Context (fresh jit functions, the in-process analogue of a
    restart) compiling the same plan family hits the on-disk executable
    cache: the compile span carries persistent_hit and the hit metric."""
    config_keys({"serving.cache.enabled": False})
    c1 = _ctx()
    out1 = c1.sql(AGG_QUERY, return_futures=False)
    assert os.listdir(persistent_cache), "no executables persisted"
    assert c1.metrics.counter("resilience.compile_cache.miss") >= 1

    c2 = _ctx()  # new uid, new CompiledAggregate, new jit: a cold process
    out2 = c2.sql(AGG_QUERY, return_futures=False)
    assert out2["s"].tolist() == out1["s"].tolist()
    assert c2.metrics.counter("resilience.compile_cache.hit") >= 1
    spans = _compile_spans(c2.last_trace)
    assert spans and any(s.attrs.get("persistent_hit") for s in spans)


@pytest.mark.parametrize("placed", ["env", "unset"])
def test_cache_directory_is_placed_from_outside(placed, tmp_path,
                                                 config_keys, monkeypatch):
    """One rule for the one seam: where JAX_COMPILATION_CACHE_DIR is set the
    program adopts that directory and sets none in code; where it is unset
    the bench helper resolves to the fixed in-checkout path."""
    import jax

    import bench

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    compile_cache.disable()
    try:
        if placed == "env":
            outside = str(tmp_path / "placed-from-outside")
            monkeypatch.setenv(compile_cache.ENV_DIR, outside)
            config_keys({"serving.compile_cache.path":
                         str(tmp_path / "ignored-config-path")})
            before = jax.config.jax_compilation_cache_dir
            assert compile_cache.maybe_enable(config_module.config)
            assert jax.config.jax_compilation_cache_dir == before
            assert compile_cache.enabled_path() == outside
            assert not os.path.exists(str(tmp_path / "ignored-config-path"))
            assert bench.compile_cache_dir("coldstart") == outside
            # ... and teardown leaves the environment's directory alone
            compile_cache.disable()
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
            assert not compile_cache.maybe_enable(config_module.config)
            assert bench.compile_cache_dir() == os.path.join(
                repo, ".jax_cache")
            assert bench.compile_cache_dir("coldstart") == os.path.join(
                repo, ".jax_cache", "coldstart")
    finally:
        compile_cache.disable()


def test_torn_cache_entry_degrades_to_recompile(persistent_cache,
                                                config_keys):
    """A half-written (crash mid-write) cache entry is a MISS, never an
    error: the next boot recompiles and serves correctly."""
    config_keys({"serving.cache.enabled": False})
    c1 = _ctx()
    expected = c1.sql(AGG_QUERY, return_futures=False)
    entries = [f for f in os.listdir(persistent_cache)
               if f.endswith("-cache")]
    assert entries
    for f in entries:  # tear every persisted executable
        with open(os.path.join(persistent_cache, f), "wb") as fh:
            fh.write(b"torn-write\x00garbage")

    c2 = _ctx()
    out = c2.sql(AGG_QUERY, return_futures=False)
    assert out["s"].tolist() == expected["s"].tolist()
    # the torn entries were not served as hits on the recorded compile
    spans = _compile_spans(c2.last_trace)
    assert spans and not any(s.attrs.get("persistent_hit") for s in spans)


# ---------------------------------------------------------------------------
# profile-driven pre-warm
# ---------------------------------------------------------------------------
def test_restart_warmup_first_query_has_no_foreground_compile(
        tmp_path, config_keys):
    """The restart acceptance path: snapshot -> fresh Context ->
    load_state kicks the warm-up -> after it finishes, the hottest
    fingerprint's first query shows zero compile spans in its trace."""
    config_keys({"serving.cache.enabled": False})
    c1 = _ctx()
    expected = c1.sql(AGG_QUERY, return_futures=False)
    assert _compile_spans(c1.last_trace), "cold run must compile"
    loc = str(tmp_path / "snaps")
    c1.save_state(loc)

    c2 = Context()
    c2.load_state(loc)
    warm = c2.warmup
    assert warm is not None, "load_state with profiles must start warm-up"
    warm.join(120)
    assert warm.ready
    assert warm.warmed >= 1 and warm.failed == 0
    assert c2.metrics.counter("serving.warmup.warmed") >= 1

    out = c2.sql(AGG_QUERY, return_futures=False)
    assert out["s"].tolist() == expected["s"].tolist()
    assert _compile_spans(c2.last_trace) == [], (
        "pre-warmed fingerprint paid a foreground compile")


def test_warmup_counts_unreplayable_profiles(config_keys):
    """A profile whose table vanished fails its replay; warm-up counts it
    and still reaches ready (readiness must never wedge on bad profiles)."""
    c1 = _ctx()
    c1.sql(AGG_QUERY, return_futures=False)
    c2 = Context()  # no table 't' here
    c2.profiles.load(c1.profiles.snapshot())
    warm = c2.maybe_start_warmup()
    assert warm is not None
    warm.join(60)
    assert warm.ready
    assert warm.failed == 1 and warm.warmed == 0
    assert c2.metrics.counter("serving.warmup.failed") == 1


def test_profiles_record_full_sql_beyond_trace_display_cap(config_keys):
    """Regression: profiles must store the FULL statement, not the trace's
    display-truncated copy (500 chars) — a long query replayed from its
    truncated prefix fails mid-identifier at warm-up."""
    config_keys({"serving.cache.enabled": False})
    c = _ctx()
    pad = " + 0.0" * 120  # pushes the statement well past 500 chars
    long_query = f"SELECT g, SUM(x{pad}) AS s FROM t GROUP BY g ORDER BY g"
    assert len(long_query) > 500
    c.sql(long_query, return_futures=False)
    cands = c.profiles.warm_candidates(5)
    assert cands and cands[0][1] == long_query


def test_warmup_skips_truncated_sql():
    from dask_sql_tpu.observability.profiles import _SQL_KEEP, ProfileStore

    store = ProfileStore()
    store.record_exec("fp_long", sql="SELECT 1 FROM t WHERE " +
                      "x > 0 AND " * (_SQL_KEEP // 8) + "1=1")
    store.record_exec("fp_ok", sql="SELECT COUNT(*) FROM t")
    cands = store.warm_candidates(10)
    assert [fp for fp, _ in cands] == ["fp_ok"]
    # the flag round-trips through snapshot/load
    store2 = ProfileStore()
    store2.load(store.snapshot())
    assert [fp for fp, _ in store2.warm_candidates(10)] == ["fp_ok"]

    # a LEGACY (version-1, 200-char-cap) snapshot has no flag: an entry at
    # the old cap may be a silent prefix and must be treated as truncated
    legacy = {"version": 1, "profiles": {
        "fp_maybe_cut": {"sql": "SELECT x FROM t WHERE " + "y" * 178,
                         "hits": 9},
        "fp_short": {"sql": "SELECT COUNT(*) FROM t", "hits": 1},
    }}
    assert len(legacy["profiles"]["fp_maybe_cut"]["sql"]) == 200
    store3 = ProfileStore()
    store3.load(legacy)
    assert [fp for fp, _ in store3.warm_candidates(10)] == ["fp_short"]


def test_warmup_never_replays_ddl_scripts():
    """A profiled SCRIPT carrying DDL must not re-execute at boot — only
    single read-only statements are warmable."""
    from dask_sql_tpu.serving.warmup import WarmupManager

    ok = WarmupManager._replayable
    assert ok("SELECT g, SUM(x) FROM t GROUP BY g")
    assert ok("  WITH q AS (SELECT 1 AS a) SELECT * FROM q")
    assert not ok("CREATE TABLE boom AS SELECT 1 AS a")
    assert not ok("DROP TABLE t")
    assert not ok("CREATE TABLE s AS SELECT 1 AS a; SELECT * FROM s")
    assert not ok("SELECT 1 AS a; DROP TABLE t")
    assert not ok("not even sql (")


def test_interrupted_warmup_never_corrupts_and_rewarmus(tmp_path,
                                                        config_keys):
    """A warm-up killed mid-pass (the in-process analogue of a crash
    during pre-warm) leaves a Context that serves correctly, and the next
    boot re-warms from the same snapshot."""
    config_keys({"serving.cache.enabled": False,
                 "serving.warmup.throttle_s": 30.0})
    c1 = _ctx()
    expected = c1.sql(AGG_QUERY, return_futures=False)
    loc = str(tmp_path / "snaps")
    c1.save_state(loc)

    c2 = Context()
    c2.load_state(loc)
    warm = c2.warmup
    assert warm is not None
    warm.cancel()  # kill mid-pass (first entry or first throttle window)
    warm.join(60)
    assert warm.ready  # cancelled pass still reports ready, never wedges
    out = c2.sql(AGG_QUERY, return_futures=False)
    assert out["s"].tolist() == expected["s"].tolist()

    # next boot: same snapshot, full warm
    config_keys({"serving.warmup.throttle_s": 0.0})
    c3 = Context()
    c3.load_state(loc)
    c3.warmup.join(120)
    assert c3.warmup.ready and c3.warmup.warmed >= 1
    out3 = c3.sql(AGG_QUERY, return_futures=False)
    assert out3["s"].tolist() == expected["s"].tolist()


@pytest.mark.faults
def test_warmup_with_injected_compile_fault_stays_consistent(tmp_path,
                                                             config_keys):
    """faults site compile:once during pre-warm: the warm statement itself
    degrades through the ladder, warm-up completes, and the next query
    returns correct results — no corrupted state."""
    config_keys({"serving.cache.enabled": False})
    c1 = _ctx()
    expected = c1.sql(AGG_QUERY, return_futures=False)
    loc = str(tmp_path / "snaps")
    c1.save_state(loc)

    faults.reset()
    config_keys({"resilience.inject": "compile:once"})
    c2 = Context()
    c2.load_state(loc)
    c2.warmup.join(120)
    assert c2.warmup.ready
    config_keys({"resilience.inject": None})
    out = c2.sql(AGG_QUERY, return_futures=False)
    assert out["s"].tolist() == expected["s"].tolist()
    # the injected fault stepped the warm statement down a rung
    assert c2.metrics.counter("resilience.degraded") >= 1


# ---------------------------------------------------------------------------
# /v1/health readiness
# ---------------------------------------------------------------------------
def _health(port):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/health") as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_health_endpoint_warming_to_ready(tmp_path, config_keys):
    from dask_sql_tpu.server.app import run_server

    config_keys({"serving.warmup.throttle_s": 0.6})
    c1 = _ctx()
    c1.sql(AGG_QUERY, return_futures=False)
    loc = str(tmp_path / "snaps")
    c1.save_state(loc)

    c2 = Context()
    c2.profiles.load(c1.profiles.snapshot())
    c2.load_state(loc)  # starts the (throttled) warm-up
    srv = run_server(context=c2, host="127.0.0.1", port=0, blocking=False)
    try:
        code, body = _health(srv.port)
        assert code == 503 and body["status"] == "warming", body
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            code, body = _health(srv.port)
            if code == 200:
                break
            time.sleep(0.05)
        assert code == 200 and body["status"] == "ready", body
        assert body["warmed"] >= 1
        # ISSUE 18: the one health probe also carries the routing facts
        # the fleet router (and a cost-aware LB) needs
        assert body["band"] in ("green", "yellow", "red", "critical"), body
        assert "headroomBytes" in body, body
    finally:
        srv.shutdown()


def test_health_ready_with_nothing_to_warm():
    from dask_sql_tpu.server.app import run_server

    c = Context()
    srv = run_server(context=c, host="127.0.0.1", port=0, blocking=False)
    try:
        code, body = _health(srv.port)
        assert code == 200 and body["status"] == "ready"
        assert body["band"] in ("green", "yellow", "red", "critical"), body
        assert "headroomBytes" in body, body
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# compile watchdog
# ---------------------------------------------------------------------------
@pytest.mark.faults
def test_watchdog_degrades_hung_compile(config_keys):
    """Acceptance: a fault-injected hung compile degrades via the ladder
    within the deadline instead of blocking the worker — the query still
    answers correctly, resilience.degraded counts the step, and the
    breaker is charged for the fingerprint's rung."""
    config_keys({"serving.cache.enabled": False,
                 "resilience.breaker.threshold": 1})
    c = _ctx()
    expected_frame = _frame()
    expected = (expected_frame.groupby("g")["x"].sum()
                .sort_index().tolist())
    t0 = time.monotonic()
    with config_module.set({"resilience.inject": "compile_hang:once",
                            "resilience.inject.hang_s": 8.0,
                            "resilience.compile_timeout_ms": 100}):
        out = c.sql(AGG_QUERY, return_futures=False)
    elapsed = time.monotonic() - t0
    assert out["s"].tolist() == expected
    assert elapsed < 8.0, "worker waited for the hung compile"
    counters = c.metrics.snapshot()["counters"]
    assert counters.get("resilience.watchdog.timeout", 0) >= 1
    assert counters.get("resilience.watchdog.abandoned", 0) >= 1
    assert counters.get("resilience.degraded.compiled_aggregate", 0) >= 1
    # breaker charged: threshold 1 means the hang tripped the circuit
    assert counters.get("resilience.breaker.trip", 0) >= 1
    fp = c.last_trace.fingerprint
    assert c.breaker.is_open((fp, "compiled_aggregate"))


def test_watchdog_off_by_default(config_keys):
    """No deadline configured: the call never pays the helper-thread
    dispatch and a slow compile is NOT killed."""
    from dask_sql_tpu.resilience import watchdog

    assert watchdog.timeout_ms(config_module.config) is None
    with config_module.set({"resilience.compile_timeout_ms": "250"}):
        assert watchdog.timeout_ms(config_module.config) == 250.0
    with config_module.set({"resilience.compile_timeout_ms": "bogus"}):
        assert watchdog.timeout_ms(config_module.config) is None


def test_compile_timeout_error_taxonomy():
    from dask_sql_tpu.resilience.errors import (
        CompileError,
        CompileTimeoutError,
        classify,
    )

    err = CompileTimeoutError("compile for x exceeded deadline")
    assert isinstance(err, CompileError)
    assert err.degradable and not err.retryable
    assert classify(err) is err and err.code == "COMPILE_TIMEOUT"


def test_watched_call_propagates_result_and_errors():
    from dask_sql_tpu.resilience.errors import CompileTimeoutError
    from dask_sql_tpu.resilience.watchdog import watched_call

    assert watched_call("x", lambda: 41 + 1, deadline_ms=5000) == 42
    with pytest.raises(ValueError):
        watched_call("x", lambda: (_ for _ in ()).throw(ValueError("boom")),
                     deadline_ms=5000)
    with pytest.raises(CompileTimeoutError):
        watched_call("x", lambda: time.sleep(2.0), deadline_ms=50)


# ---------------------------------------------------------------------------
# background recompile
# ---------------------------------------------------------------------------
def test_bucket_growth_recompiles_in_background(config_keys):
    """A seen plan family whose table grew past its pow2 bucket is served
    interpreted while the new pipeline compiles off-path, then swaps in
    atomically: the next query runs the compiled rung again."""
    config_keys({"serving.cache.enabled": False,
                 "serving.bg_compile.enabled": True})
    c = _ctx(200)
    r1 = c.sql(AGG_QUERY, return_futures=False)
    assert c.metrics.counter("resilience.rung.compiled_aggregate") == 1

    c.create_table("t", _frame(1000))  # growth: new uid, new bucket
    r2 = c.sql(AGG_QUERY, return_futures=False)
    assert c.metrics.counter("serving.bg_compile.deferred") >= 1
    # served on a lower rung, NOT a failure: no degradation recorded
    assert c.metrics.counter("resilience.degraded") == 0
    assert c.metrics.counter("resilience.rung.compiled_aggregate") == 1
    assert r2["s"].sum() > r1["s"].sum()

    assert c.background_compiler().wait_idle(60)
    assert c.metrics.counter("serving.bg_compile.completed") == 1
    r3 = c.sql(AGG_QUERY, return_futures=False)
    assert c.metrics.counter("resilience.rung.compiled_aggregate") == 2
    assert r3["s"].tolist() == r2["s"].tolist()


def test_plain_cache_eviction_is_not_misread_as_growth(config_keys):
    """LRU eviction of an UNCHANGED plan must recompile in the foreground,
    not defer to background: family memory carries the table bucket as
    growth evidence, and identical identity means no deferral."""
    from dask_sql_tpu.physical import compiled as compiled_mod

    config_keys({"serving.cache.enabled": False,
                 "serving.bg_compile.enabled": True})
    c = _ctx(200)
    c.sql(AGG_QUERY, return_futures=False)
    assert c.metrics.counter("resilience.rung.compiled_aggregate") == 1
    with c._plan_lock:  # simulate LRU churn evicting the entry
        compiled_mod.PROGRAMS.clear()
    c.sql(AGG_QUERY, return_futures=False)
    assert c.metrics.counter("serving.bg_compile.deferred") == 0
    assert c.metrics.counter("resilience.rung.compiled_aggregate") == 2


def test_bg_compiler_bounded_queue_and_dedup():
    from dask_sql_tpu.serving.background import BackgroundCompiler
    from dask_sql_tpu.serving.metrics import MetricsRegistry

    metrics = MetricsRegistry()
    bg = BackgroundCompiler(metrics=metrics, max_pending=1)
    import threading

    gate = threading.Event()
    assert bg.submit("a", gate.wait)
    assert not bg.submit("a", gate.wait)  # dup while pending
    # the worker may have popped "a" already (pending but not queued), so
    # fill the queue then overflow it deterministically
    assert bg.submit("b", lambda: None) in (True, False)
    while bg.submit("c", lambda: None):
        pass  # keep filling until the bound rejects
    assert metrics.counter("serving.bg_compile.dropped") >= 1
    gate.set()
    assert bg.wait_idle(30)
    bg.cancel()
    bg.join(10)


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------
def test_runtime_shutdown_joins_background_workers(config_keys):
    """Regression (ISSUE 7 satellite): shutdown(wait=True) must cancel and
    join warm-up / background-compile threads, not only the query queues."""
    from dask_sql_tpu.serving.runtime import ServingRuntime

    config_keys({"serving.cache.enabled": False,
                 "serving.warmup.throttle_s": 30.0})
    c = _ctx()
    c.sql(AGG_QUERY, return_futures=False)
    runtime = ServingRuntime(workers=1)
    c.serving = runtime
    warm = c.maybe_start_warmup()  # registers itself with the runtime
    assert warm is not None and not warm.ready  # throttled mid-pass
    bg = None
    config_keys({"serving.bg_compile.enabled": True})
    bg = c.background_compiler()
    assert bg is not None

    t0 = time.monotonic()
    runtime.shutdown(wait=True, timeout=10.0)
    assert time.monotonic() - t0 < 10.0, "drain did not beat the throttle"
    warm.join(0.1)
    assert warm._thread is not None and not warm._thread.is_alive()
    assert warm.ready


def test_runtime_shutdown_survives_worker_cancel_error():
    from dask_sql_tpu.serving.runtime import ServingRuntime

    class Broken:
        def cancel(self):
            raise RuntimeError("teardown bug")

        def join(self, timeout=None):
            pass

    class Tracked:
        cancelled = joined = False

        def cancel(self):
            self.cancelled = True

        def join(self, timeout=None):
            self.joined = True

    runtime = ServingRuntime(workers=1)
    tracked = Tracked()
    runtime.register_background(Broken())
    runtime.register_background(tracked)
    runtime.shutdown(wait=True, timeout=5.0)
    assert tracked.cancelled and tracked.joined

    # registering AFTER shutdown cancels immediately: the drain snapshot
    # has already run and would never see this worker
    late = Tracked()
    runtime.register_background(late)
    assert late.cancelled


# ---------------------------------------------------------------------------
# checkpointed breaker verdicts
# ---------------------------------------------------------------------------
def test_breaker_verdicts_survive_restart(tmp_path, config_keys):
    """An open circuit rides the snapshot: the restarted process skips the
    proven-bad rung instead of re-proving it (bounded by the TTL)."""
    config_keys({"serving.cache.enabled": False,
                 "serving.warmup.enabled": False})
    c1 = _ctx()
    c1.sql(AGG_QUERY, return_futures=False)  # something to snapshot
    key = ("fp-bad", "compiled_aggregate")
    for _ in range(3):  # default threshold
        c1.breaker.record_failure(key)
    assert c1.breaker.is_open(key)
    loc = str(tmp_path / "snaps")
    c1.save_state(loc)

    c2 = Context()
    c2.load_state(loc)
    assert c2.breaker.is_open(key)
    assert c2.metrics.counter("resilience.breaker.restored") == 1
    # closed-circuit keys (sub-threshold) do not persist
    assert c2.breaker.snapshot()["keys"] == 1


def test_breaker_restore_respects_ttl():
    from dask_sql_tpu.resilience.retry import CircuitBreaker

    b1 = CircuitBreaker(threshold=1)
    b1.record_failure(("fp", "rung"))
    snap = b1.snapshot_state()
    assert len(snap["open"]) == 1

    fresh = CircuitBreaker(threshold=1)
    assert fresh.load_state(snap, ttl_s=300.0) == 1
    assert fresh.is_open(("fp", "rung"))

    stale = dict(snap, saved_at=time.time() - 1000.0)
    expired = CircuitBreaker(threshold=1)
    assert expired.load_state(stale, ttl_s=300.0) == 0
    assert not expired.is_open(("fp", "rung"))

    # malformed entries are skipped, never fatal
    junk = {"saved_at": time.time(), "open": [{"bogus": 1}, None]}
    assert CircuitBreaker().load_state(junk, ttl_s=300.0) == 0
