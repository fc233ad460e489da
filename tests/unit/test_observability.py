"""Observability subsystem: query-lifecycle tracing, per-fingerprint
profiles (SHOW PROFILES + checkpoint persistence), Prometheus exposition,
the slow-query log, and trace isolation across concurrent server workers.
"""
import json
import threading
import time
import urllib.request

import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.observability import (
    ProfileStore,
    QueryTrace,
    TraceStore,
    activate,
    current_trace,
    render_prometheus,
)
from dask_sql_tpu.serving.metrics import MetricsRegistry
from dask_sql_tpu.tracing import NodeTrace

pytestmark = pytest.mark.observability


def _ctx(rows=32, name="t"):
    c = Context()
    c.create_table(name, pd.DataFrame({
        "a": np.arange(rows, dtype=np.int64),
        "b": np.arange(rows, dtype=np.float64) * 1.5,
    }))
    return c


# ------------------------------------------------------------ span model
def test_lifecycle_stages_present_and_monotonic():
    c = _ctx()
    c.sql("SELECT a, b FROM t WHERE a > 3", return_futures=False)
    tr = c.last_trace
    assert tr is not None
    stages = tr.stage_spans()
    names = [s.name for s in stages]
    for required in ("parse", "bind", "verify", "estimate", "cache_lookup",
                     "execute", "d2h"):
        assert required in names, names
    # stages are sequential: each closes before the next opens
    for left, right in zip(stages, stages[1:]):
        assert left.t1 <= right.t0 + 1e-9, (left.name, right.name)


def test_plan_cache_hit_skips_parse_span():
    c = _ctx()
    sql = "SELECT SUM(a) AS s FROM t"
    c.sql(sql, return_futures=False)
    c.sql(sql, return_futures=False)
    tr = c.last_trace
    assert not tr.has_span("parse")
    assert any(s.name == "plan_cache_hit" for s in tr.spans)


def test_trace_disabled_by_config():
    c = _ctx()
    config_module.config.update({"observability.trace.enabled": False})
    try:
        c.last_trace = None
        c.sql("SELECT a FROM t", return_futures=False)
        assert c.last_trace is None
    finally:
        config_module.config.update({"observability.trace.enabled": True})


def test_compile_span_and_metric_recorded():
    c = Context()
    # unique column names => a plan shape no earlier test compiled, so the
    # jit cache MUST grow on first execution
    c.create_table("fresh_ct", pd.DataFrame({
        "zq_one": np.arange(40, dtype=np.int64),
        "zq_two": np.arange(40, dtype=np.float64),
    }))
    c.sql("SELECT zq_one FROM fresh_ct WHERE zq_one > 7",
          return_futures=False)
    tr = c.last_trace
    compiles = [s for s in tr.spans if s.name == "compile:compiled_select"]
    assert compiles, [s.name for s in tr.spans]
    # launch > compile:<rung> > xla:*, all under execute
    assert all(s.parent == "launch" for s in compiles)
    launches = [s for s in tr.spans if s.name == "launch"]
    assert launches and all(s.parent == "execute" for s in launches)
    assert any(s.parent == "compile:compiled_select"
               for s in tr.spans if s.name == "xla:compile")
    snap = c.metrics.snapshot()
    assert "resilience.compile_ms.compiled_select" in snap["histograms"]
    # the profile store saw the compile under this plan's fingerprint
    prof = c.profiles.get(tr.fingerprint)
    assert prof is not None and "compiled_select" in prof["compile"]


def test_result_cache_hit_event_and_profile_hit():
    c = _ctx()
    sql = "SELECT MAX(b) AS m FROM t"
    c.sql(sql, return_futures=False)
    c.sql(sql, return_futures=False)
    tr = c.last_trace
    assert any(s.name == "result_cache_hit" for s in tr.spans)
    prof = c.profiles.get(tr.fingerprint)
    assert prof["hits"] == 2 and prof["cache_hits"] == 1


def test_chrome_trace_export_shape():
    tr = QueryTrace(sql="SELECT 1", metrics=None, profiles=None)
    with tr.span("parse"):
        pass
    tr.event("plan_cache_hit")
    payload = tr.to_chrome_trace()
    assert payload["displayTimeUnit"] == "ms"
    phases = {e["ph"] for e in payload["traceEvents"]}
    assert {"M", "X", "i"} <= phases
    x = [e for e in payload["traceEvents"] if e["ph"] == "X"][0]
    assert x["name"] == "parse" and x["dur"] >= 0
    assert payload["otherData"]["sql"] == "SELECT 1"


def test_activation_is_scoped_per_thread():
    seen = {}

    def worker(i):
        tr = QueryTrace(sql=f"q{i}")
        with activate(tr):
            time.sleep(0.01)
            seen[i] = current_trace().sql

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert seen == {i: f"q{i}" for i in range(8)}
    assert current_trace() is None


# ---------------------------------------------------------- NodeTrace fix
def test_node_trace_format_unknown_rows_and_events():
    root = NodeTrace("Projection", "Projection: x", 2.0, -1, [
        NodeTrace("Resilience", "degraded: compiled_select [OOM]", 0.0, -1),
        NodeTrace("TableScan", "TableScan: t", 1.0, 10),
    ])
    text = root.format()
    assert "? rows" in text
    assert "-1 rows" not in text
    assert "!! degraded: compiled_select [OOM]" in text
    assert "0.00 ms" not in text  # the event marker renders label-only
    assert "[1.00 ms, 10 rows]" in text


# --------------------------------------------------------- EXPLAIN ANALYZE
def test_explain_analyze_lifecycle_header():
    c = _ctx()
    rows = list(c.sql("EXPLAIN ANALYZE SELECT a FROM t WHERE a > 5",
                      return_futures=False)["PLAN"])
    header = [r for r in rows if r.startswith("-- query lifecycle")]
    assert header, rows
    assert any(r.strip().startswith("parse") for r in rows)
    assert any(r.strip().startswith("bind") for r in rows)
    assert any("TableScan" in r for r in rows)


def test_explain_format_json_without_analyze_rejected():
    """FORMAT JSON only pairs with ANALYZE — both parsers reject the
    combination instead of silently returning text a JSON client would
    choke on."""
    from dask_sql_tpu.planner.parser import ParsingException

    c = _ctx()
    for native in ("auto", "off"):
        config_module.config.update({"sql.native.binder": native})
        try:
            with pytest.raises(ParsingException):
                c.sql("EXPLAIN FORMAT JSON SELECT a FROM t",
                      return_futures=False)
        finally:
            config_module.config.update({"sql.native.binder": "auto"})


def test_repeated_compute_does_not_duplicate_d2h_stage():
    c = _ctx()
    frame = c.sql("SELECT a FROM t WHERE a > 4")
    frame.compute()
    frame.compute()
    tr = c.last_trace
    assert sum(1 for s in tr.spans if s.name == "d2h") == 1
    assert tr.finished


def test_d2h_metric_records_with_tracing_disabled():
    c = _ctx(name="d2h_t")
    config_module.config.update({"observability.trace.enabled": False})
    try:
        c.sql("SELECT a FROM d2h_t", return_futures=False)
        assert "query.d2h_ms" in c.metrics.snapshot()["histograms"]
    finally:
        config_module.config.update({"observability.trace.enabled": True})


def test_explain_analyze_format_json_both_parsers():
    c = _ctx()
    for native in ("auto", "off"):
        config_module.config.update({"sql.native.binder": native})
        try:
            out = c.sql(
                "EXPLAIN ANALYZE FORMAT JSON SELECT a FROM t WHERE a > 5",
                return_futures=False)
            payload = json.loads(out["PLAN"][0])
            assert payload["displayTimeUnit"] == "ms"
            names = [e["name"] for e in payload["traceEvents"]
                     if e.get("ph") == "X"]
            assert "parse" in names and "TableScan" in names
        finally:
            config_module.config.update({"sql.native.binder": "auto"})


# ------------------------------------------------------------ SHOW PROFILES
def test_show_profiles_statement_both_parsers():
    c = _ctx()
    c.sql("SELECT SUM(a) AS s FROM t", return_futures=False)
    for native in ("auto", "off"):
        config_module.config.update({"sql.native.binder": native})
        try:
            df = c.sql("SHOW PROFILES", return_futures=False)
            assert list(df.columns) == ["Fingerprint", "Family", "Metric",
                                        "Value"]
            metrics = set(df["Metric"])
            assert {"sql", "hits", "exec_ms.p50"} <= metrics
        finally:
            config_module.config.update({"sql.native.binder": "auto"})


def test_show_profiles_like_filters_fingerprint_and_metric():
    c = _ctx()
    c.sql("SELECT COUNT(*) AS n FROM t", return_futures=False)
    fp = c.last_trace.fingerprint
    by_fp = c.sql(f"SHOW PROFILES LIKE '{fp[:8]}%'", return_futures=False)
    assert set(by_fp["Fingerprint"]) == {fp}
    by_metric = c.sql("SHOW PROFILES LIKE 'hits'", return_futures=False)
    assert set(by_metric["Metric"]) == {"hits", "cache_hits"}


def test_profile_store_rolling_window():
    store = ProfileStore(window=4, keep=2)
    for i in range(10):
        store.record_exec("fp1", sql="q", exec_ms=float(i))
    assert store.get("fp1")["exec_ms"] == [6.0, 7.0, 8.0, 9.0]
    store.record_exec("fp2", exec_ms=1.0)
    store.record_exec("fp3", exec_ms=1.0)  # keep=2 evicts LRU fp1
    assert store.get("fp1") is None and len(store) == 2


def test_profile_store_snapshot_load_round_trip():
    store = ProfileStore(window=8)
    store.record_exec("abc123", sql="SELECT 1", exec_ms=5.5,
                      result_bytes=128)
    store.record_compile("abc123", "compiled_select", 42.0)
    restored = ProfileStore(window=8)
    assert restored.load(json.loads(json.dumps(store.snapshot()))) == 1
    assert restored.get("abc123") == store.get("abc123")
    assert restored.top_fingerprints(1) == ["abc123"]


def test_checkpoint_persists_profiles(tmp_path):
    c = _ctx(name="ckpt_src")
    c.sql("SELECT SUM(a) AS s FROM ckpt_src", return_futures=False)
    fp = c.last_trace.fingerprint
    manifest = c.save_state(str(tmp_path))
    assert manifest["profiles"] == "profiles.json"

    c2 = Context()
    c2.load_state(str(tmp_path))
    prof = c2.profiles.get(fp)
    assert prof is not None and prof["hits"] >= 1
    df = c2.sql("SHOW PROFILES", return_futures=False)
    assert fp in set(df["Fingerprint"])


# -------------------------------------------------------------- prometheus
def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    reg.inc("query.executed", 3)
    reg.gauge("serving.depth", 2.5)
    for v in (1.0, 2.0, 4.0):
        reg.observe("serving.latency_ms", v)
    text = render_prometheus(reg.snapshot())
    assert text == (
        "# TYPE dsql_query_executed_total counter\n"
        "dsql_query_executed_total 3\n"
        "# TYPE dsql_query_cache_hit_rate gauge\n"
        "dsql_query_cache_hit_rate 0\n"
        "# TYPE dsql_serving_depth gauge\n"
        "dsql_serving_depth 2.5\n"
        "# TYPE dsql_serving_latency_ms summary\n"
        'dsql_serving_latency_ms{quantile="0.5"} 2\n'
        'dsql_serving_latency_ms{quantile="0.95"} 4\n'
        'dsql_serving_latency_ms{quantile="0.99"} 4\n'
        "dsql_serving_latency_ms_sum 7\n"
        "dsql_serving_latency_ms_count 3\n"
        "# TYPE dsql_serving_latency_ms_max gauge\n"
        "dsql_serving_latency_ms_max 4\n"
    )


def test_prometheus_extra_gauges_and_sanitization():
    reg = MetricsRegistry()
    reg.inc("executor.node.TableScan.rows", 7)
    text = render_prometheus(reg.snapshot(),
                             extra_gauges={"serving.queue_depth": 1})
    assert "dsql_executor_node_TableScan_rows_total 7" in text
    assert "dsql_serving_queue_depth 1" in text


# ------------------------------------------------------------ slow queries
def test_slow_query_log_threshold(tmp_path):
    log = tmp_path / "slow.jsonl"
    c = _ctx(name="slow_t")
    config_module.config.update({
        "observability.slow_query_ms": 0,  # log every query
        "observability.slow_query_path": str(log),
    })
    try:
        c.sql("SELECT a FROM slow_t WHERE a > 1", return_futures=False)
        lines = log.read_text().strip().splitlines()
        assert len(lines) == 1
        record = json.loads(lines[0])
        assert record["sql"].startswith("SELECT a FROM slow_t")
        span_names = {s["name"] for s in record["spans"]}
        assert {"parse", "execute", "d2h"} <= span_names
        assert c.metrics.counter("observability.slow_query") == 1

        # far-above-threshold: nothing new is written
        config_module.config.update({"observability.slow_query_ms": 1e12})
        c.sql("SELECT a FROM slow_t WHERE a > 2", return_futures=False)
        assert len(log.read_text().strip().splitlines()) == 1
    finally:
        config_module.config.update({"observability.slow_query_ms": None,
                                     "observability.slow_query_path": None})


def test_failed_query_trace_finished_and_slow_logged(tmp_path):
    """A failing query's lifecycle must still finish and reach the
    slow-query log — timeouts and failures ARE the outliers worth
    debugging."""
    from dask_sql_tpu.resilience import faults
    from dask_sql_tpu.resilience.errors import QueryError

    log = tmp_path / "slow_fail.jsonl"
    c = _ctx(name="fail_t")
    faults.reset()
    config_module.config.update({
        "observability.slow_query_ms": 0,
        "observability.slow_query_path": str(log),
        "resilience.inject": "execute:always",
        "serving.cache.enabled": False,
    })
    try:
        with pytest.raises(QueryError):
            c.sql("SELECT a FROM fail_t", return_futures=False)
        tr = c.last_trace
        assert tr.finished
        execute = [s for s in tr.spans if s.name == "execute"]
        assert execute and execute[0].attrs.get("error")
        records = [json.loads(ln) for ln in
                   log.read_text().strip().splitlines()]
        assert any(r["sql"].startswith("SELECT a FROM fail_t")
                   for r in records)
    finally:
        faults.reset()
        config_module.config.update({
            "observability.slow_query_ms": None,
            "observability.slow_query_path": None,
            "resilience.inject": None,
            "serving.cache.enabled": True,
        })


def test_slow_query_config_options_gate_that_querys_failure(tmp_path):
    """Per-query config_options must still be in scope when a FAILING
    query runs its slow-query check (the finish hook fires inside the
    per-query config overlay, not after it pops)."""
    from dask_sql_tpu.resilience import faults
    from dask_sql_tpu.resilience.errors import QueryError

    log = tmp_path / "slow_opt.jsonl"
    c = _ctx(name="opt_t")
    faults.reset()
    try:
        with pytest.raises(QueryError):
            c.sql("SELECT a FROM opt_t", return_futures=False,
                  config_options={
                      "observability.slow_query_ms": 0,
                      "observability.slow_query_path": str(log),
                      "resilience.inject": "execute:always",
                      "resilience.ladder.enabled": False,
                      "serving.cache.enabled": False,
                  })
        assert log.exists() and log.read_text().strip()
    finally:
        faults.reset()


def test_compile_metrics_survive_tracing_disabled():
    """resilience.compile_ms.* and the profile store must record through
    the compile sink even when lifecycle tracing is off."""
    c = Context()
    c.create_table("notrace_ct", pd.DataFrame({
        "nt_col": np.arange(48, dtype=np.int64)}))
    config_module.config.update({"observability.trace.enabled": False})
    try:
        c.sql("SELECT nt_col FROM notrace_ct WHERE nt_col > 11",
              return_futures=False)
        assert c.last_trace is None
        snap = c.metrics.snapshot()
        assert "resilience.compile_ms.compiled_select" in snap["histograms"]
        rows = c.profiles.rows()
        assert any(m == "compile.compiled_select.count"
                   for _, _, m, _ in rows)
        assert any(m == "hits" for _, _, m, _ in rows)
    finally:
        config_module.config.update({"observability.trace.enabled": True})


def test_add_span_once_is_atomic():
    tr = QueryTrace(qid="q")
    results = []

    def add():
        results.append(tr.add_span_once("serialize", 0.0, 1.0))

    threads = [threading.Thread(target=add) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results.count(True) == 1
    assert sum(1 for s in tr.spans if s.name == "serialize") == 1


def test_trace_store_lru_bound():
    store = TraceStore(keep=2)
    for i in range(4):
        store.put(f"q{i}", QueryTrace(qid=f"q{i}"))
    assert len(store) == 2
    assert store.get("q0") is None and store.get("q3") is not None


# ---------------------------------------------------------------- the wire
def _post(port, sql, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/statement", data=sql.encode(),
        method="POST")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


def _follow(port, payload, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        with urllib.request.urlopen(payload["nextUri"]) as resp:
            status = json.loads(resp.read())
        if status.get("error") or "data" in status or "columns" in status:
            return status
        time.sleep(0.02)
    raise AssertionError("query did not finish")


def _get_json(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as resp:
        return json.loads(resp.read())


@pytest.fixture
def obs_server():
    from dask_sql_tpu.server.app import run_server

    c = Context()
    c.create_table("wire_t", pd.DataFrame({
        "wq_a": np.arange(128, dtype=np.int64),
        "wq_b": np.arange(128, dtype=np.float64) * 0.5,
    }))
    srv = run_server(context=c, host="127.0.0.1", port=0, blocking=False)
    yield c, srv
    srv.shutdown()


def test_wire_trace_acceptance(obs_server):
    """The acceptance criterion: a query served through the Presto wire
    yields a /v1/trace/{qid} Chrome trace containing queue-wait, parse,
    bind, verify, estimate, compile, execute and d2h spans with monotonic
    non-overlapping stage timestamps."""
    c, srv = obs_server
    payload = _post(srv.port, "SELECT wq_a, wq_b FROM wire_t WHERE wq_a > 9")
    status = _follow(srv.port, payload)
    assert "data" in status
    qid = payload["id"]
    trace = _get_json(srv.port, f"/v1/trace/{qid}")
    events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in events}
    for required in ("queue_wait", "parse", "bind", "verify", "estimate",
                     "execute", "d2h", "serialize"):
        assert required in names, names
    assert any(n.startswith("compile:") for n in names), names
    stages = sorted((e for e in events if e.get("cat") == "stage"),
                    key=lambda e: e["ts"])
    for left, right in zip(stages, stages[1:]):
        assert left["ts"] + left["dur"] <= right["ts"] + 1.0, (
            left["name"], right["name"])
    # compile spans nest inside the execute stage
    execute = next(e for e in stages if e["name"] == "execute")
    for e in events:
        if e["name"].startswith("compile:"):
            assert e["ts"] >= execute["ts"] - 1.0
            assert e["ts"] + e["dur"] <= execute["ts"] + execute["dur"] + 1.0
    # unknown qid -> 404
    try:
        urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/v1/trace/ghost")
        raise AssertionError("expected 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_wire_prometheus_endpoint(obs_server):
    c, srv = obs_server
    payload = _post(srv.port, "SELECT COUNT(*) AS n FROM wire_t")
    _follow(srv.port, payload)
    req = urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}/v1/metrics?format=prometheus")
    assert req.headers["Content-Type"].startswith(
        "text/plain; version=0.0.4")
    text = req.read().decode()
    assert "dsql_query_executed_total" in text
    assert 'dsql_query_execute_ms{quantile="0.5"}' in text
    assert "dsql_serving_queue_depth" in text
    # the JSON default is untouched
    assert "registry" in _get_json(srv.port, "/v1/metrics")


def test_concurrent_explain_analyze_trace_isolation(obs_server):
    """8 Presto worker threads running EXPLAIN ANALYZE simultaneously must
    not interleave span trees: each trace carries exactly one parse/bind/
    execute stage and references only its own table."""
    c, srv = obs_server
    for i in range(8):
        c.create_table(f"iso_{i}", pd.DataFrame({
            f"col_{i}": np.arange(64 + i, dtype=np.int64)}))
    payloads = {}
    errors = []

    def submit(i):
        try:
            payloads[i] = _post(
                srv.port,
                f"EXPLAIN ANALYZE SELECT col_{i} FROM iso_{i} "
                f"WHERE col_{i} > {i}")
        except Exception as e:  # surfaced via the errors list
            errors.append(e)

    threads = [threading.Thread(target=submit, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    for i in range(8):
        status = _follow(srv.port, payloads[i])
        rows = [r[0] for r in status["data"]]
        # the report's node tree references only this query's table
        assert any(f"iso_{i}" in r for r in rows), rows
        assert not any(f"iso_{(i + 1) % 8}" in r for r in rows)
        trace = _get_json(srv.port, f"/v1/trace/{payloads[i]['id']}")
        assert trace["otherData"]["sql"].endswith(
            f"col_{i} > {i}")
        stage_names = [e["name"] for e in trace["traceEvents"]
                       if e.get("cat") == "stage"]
        for stage in ("parse", "bind", "execute"):
            assert stage_names.count(stage) == 1, (i, stage_names)
        # this query's node-tree details landed on this trace only
        details = [e["args"].get("label", "") for e in trace["traceEvents"]
                   if e.get("cat") == "detail"]
        scans = [d for d in details if d.startswith("TableScan")]
        assert scans and all(f"iso_{i}" in d for d in scans), details
