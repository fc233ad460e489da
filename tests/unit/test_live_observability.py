"""Live serving observability plane (ISSUE 14): the in-flight query table
(SHOW QUERIES / /v1/queries / CANCEL QUERY), the HBM ledger, cross-query
causality links (flow events), the always-on flight recorder (DSQL501
vocabulary + /v1/debug/events + failure auto-flush), streamed progress
gauges, queue-wait attribution, and store bounds under concurrent
eviction-racing-readers load.
"""
import json
import threading
import time
import urllib.error
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
    flight,
    merge_chrome_traces,
    render_prometheus,
)
from dask_sql_tpu.serving.metrics import MetricsRegistry

pytestmark = pytest.mark.observability


@pytest.fixture(autouse=True)
def _fresh_state():
    """The flight recorder and config are process-global; every test
    starts clean and restores what it touched."""
    saved = config_module.config.effective_items()
    flight.RECORDER.clear()
    yield
    config_module.config.update(dict(saved))
    flight.RECORDER.clear()


def _ctx(rows=64, name="lt"):
    c = Context()
    c.create_table(name, pd.DataFrame({
        "a": np.arange(rows, dtype=np.int64),
        "b": np.arange(rows, dtype=np.float64) * 1.5,
    }))
    return c


# ------------------------------------------------------- in-flight table
def test_show_queries_lists_finished_query_with_rung_and_family():
    c = _ctx()
    c.sql("SELECT SUM(a) AS s FROM lt", return_futures=False)
    qid = c.last_trace.qid
    df = c.sql("SHOW QUERIES", return_futures=False)
    assert list(df.columns) == ["Qid", "Field", "Value"]
    rows = {(r.Field): r.Value for r in df.itertuples() if r.Qid == qid}
    assert rows["state"] == "done"
    assert rows["class"] == "interactive"
    assert "rung" in rows and rows["rung"]
    assert rows["sql"].startswith("SELECT SUM(a)")
    # the HBM-ledger summary block rides along under the pseudo-qid
    ledger_fields = {r.Field for r in df.itertuples() if r.Qid == "(ledger)"}
    assert {"reservedBytes", "resultCacheBytes", "tableBytes",
            "headroomBytes", "driftBytes"} <= ledger_fields


def test_show_queries_python_and_native_paths_agree():
    c = _ctx()
    c.sql("SELECT a FROM lt WHERE a > 3", return_futures=False)
    native = c.sql("SHOW QUERIES", return_futures=False)
    python = c.sql("SHOW QUERIES", return_futures=False,
                   config_options={"sql.native.binder": "off"})
    assert list(native.columns) == list(python.columns)
    # same qids visible through both parser/binder paths
    assert set(native["Qid"]) == set(python["Qid"])


def test_show_queries_like_filters_on_qid_and_field():
    c = _ctx()
    c.sql("SELECT a FROM lt", return_futures=False)
    qid = c.last_trace.qid
    only_ledger = c.sql("SHOW QUERIES LIKE 'ledger'", return_futures=False)
    assert set(only_ledger["Qid"]) == {"(ledger)"}
    mine = c.sql(f"SHOW QUERIES LIKE '{qid[:12]}'", return_futures=False)
    assert set(mine["Qid"]) == {qid}


def test_cancel_query_unknown_qid_reports_false():
    c = _ctx()
    df = c.sql("CANCEL QUERY 'no-such-query'", return_futures=False)
    assert list(df.columns) == ["Qid", "Cancelled"]
    assert list(df["Cancelled"]) == ["false"]
    # the request itself is still on the postmortem timeline
    assert any(e["event"] == "query.cancel"
               and e.get("qid") == "no-such-query"
               for e in flight.RECORDER.events())


def _slow_ctx(rows=4000, sleep_s=0.002):
    c = _ctx(rows=rows, name="slow_t")

    def crawl(a):
        time.sleep(sleep_s)
        return a

    c.register_function(crawl, "crawl", [("a", np.int64)], np.int64,
                        row_udf=True)
    return c


def test_cancel_query_statement_stops_running_query():
    """CANCEL QUERY (SQL path) cancels a Context-API query mid-run via its
    live-registry ticket: the executor's per-row checkpoint raises."""
    c = _slow_ctx()
    errors = []

    def run():
        try:
            c.sql("SELECT crawl(a) AS x FROM slow_t", return_futures=False)
        except BaseException as exc:  # noqa: BLE001 - asserted below
            errors.append(exc)

    t = threading.Thread(target=run)
    t.start()
    try:
        entry = None
        deadline = time.time() + 10.0
        while time.time() < deadline:
            live = c.live_queries.live_entries()
            if live and live[0].state == "running":
                entry = live[0]
                break
            time.sleep(0.005)
        assert entry is not None, "query never appeared in the live table"
        df = c.sql(f"CANCEL QUERY '{entry.qid}'", return_futures=False)
        assert list(df["Cancelled"]) == ["true"]
    finally:
        t.join(20.0)
    assert not t.is_alive()
    assert errors, "query was not cancelled"
    from dask_sql_tpu.serving.admission import QueryCancelledError

    assert isinstance(errors[0], QueryCancelledError)
    assert c.live_queries.get(entry.qid).state == "cancelled"
    events = flight.RECORDER.events(name="query.cancel")
    assert any(e.get("qid") == entry.qid for e in events)


def test_live_entry_records_stage_rung_and_measured_bytes():
    c = _ctx()
    c.sql("SELECT SUM(b) AS s FROM lt", return_futures=False)
    entry = c.live_queries.entries()[-1]
    assert entry.state == "done"
    assert entry.stage == "account"  # the last stage stamped
    assert entry.rung  # the ladder stamped the answering rung
    assert entry.measured_bytes is not None and entry.measured_bytes > 0


# --------------------------------------------------- streamed progress
def _stream_ctx(n=40_000):
    c = Context()
    c.config.update({"serving.cache.enabled": False})
    rng = np.random.RandomState(7)
    c.create_table("t", pd.DataFrame({
        "k": rng.randint(0, 5, n).astype(np.int64),
        "v": rng.randint(0, 1000, n).astype(np.int64),
    }))
    from dask_sql_tpu.serving.cache import table_nbytes

    budget = table_nbytes(c.schema["root"].tables["t"].table) // 3
    return c, budget, n


def test_streamed_query_updates_progress_gauges_and_live_entry():
    c, budget, n = _stream_ctx()
    c.sql("SELECT k, SUM(v) AS s FROM t GROUP BY k", return_futures=False,
          config_options={"serving.admission.max_estimated_bytes": budget})
    parts = c.metrics.counter("serving.stream.partitions")
    assert parts > 1
    gauges = c.metrics.snapshot()["gauges"]
    assert gauges["serving.stream.partitions_done"] == parts
    assert gauges["serving.stream.rows_done"] == n
    entry = c.live_queries.entries()[-1]
    assert entry.stream_partitions_done == parts
    assert entry.stream_partitions_total == parts
    assert entry.stream_rows_done == n
    # SHOW QUERIES renders the progress fields
    df = c.sql("SHOW QUERIES", return_futures=False)
    rows = {r.Field: r.Value for r in df.itertuples() if r.Qid == entry.qid}
    assert rows["stream.partitions"] == f"{parts}/{parts}"
    assert rows["stream.rows"] == f"{n}/{n}"


def test_streamed_partitions_are_detail_spans_under_execute():
    c, budget, _ = _stream_ctx()
    c.sql("SELECT k, SUM(v) AS s FROM t GROUP BY k", return_futures=False,
          config_options={"serving.admission.max_estimated_bytes": budget})
    tr = c.last_trace
    parts = [s for s in tr.spans if s.name == "stream_partition"]
    assert len(parts) > 1
    assert all(s.kind == "detail" and s.parent == "execute" for s in parts)


# ------------------------------------------------------------ HBM ledger
def test_ledger_reconciles_and_sums_consistently():
    c, budget, _ = _stream_ctx()
    config_module.config.update(
        {"serving.admission.max_estimated_bytes": budget * 100})
    snap = c.ledger.snapshot()
    assert snap["budgetBytes"] == budget * 100
    assert snap["reservedBytes"] == 0  # idle: nothing dispatched
    assert snap["tableBytes"] > 0
    assert snap["headroomBytes"] == (snap["budgetBytes"]
                                     - snap["reservedBytes"]
                                     - snap["resultCacheBytes"]
                                     - snap["tableBytes"])
    c.sql("SELECT k, SUM(v) AS s FROM t GROUP BY k", return_futures=False)
    snap2 = c.ledger.snapshot()
    assert snap2["resultCacheBytes"] == c._result_cache.stats.bytes


def test_ledger_gauges_match_scheduler_inflight_gauge():
    """Acceptance: the ledger's reserved gauge reads the SAME counter the
    scheduler's ``serving.scheduler.inflight_bytes`` gauge publishes."""
    from dask_sql_tpu.serving.runtime import ServingRuntime
    from dask_sql_tpu.serving.scheduler import QueryCost

    c = _ctx()
    runtime = ServingRuntime(workers=2, metrics=c.metrics,
                             scheduler_budget_bytes=1 << 20)
    c.serving = runtime
    try:
        release = threading.Event()

        def hold(ticket):
            release.wait(10.0)
            return None

        runtime.submit(hold, cost=QueryCost(bytes_lo=12345))
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if c.ledger.reserved_bytes() == 12345:
                break
            time.sleep(0.005)
        snap = c.ledger.publish(c.metrics)
        gauges = c.metrics.snapshot()["gauges"]
        assert snap["reservedBytes"] == 12345
        assert gauges["serving.ledger.reserved_bytes"] == 12345
        assert gauges["serving.scheduler.inflight_bytes"] == 12345
        release.set()
    finally:
        release.set()
        runtime.shutdown(wait=True)
    assert c.ledger.reserved_bytes() == 0  # back to idle after release


def test_prometheus_golden_ledger_gauges(tmp_path):
    """Golden exposition of the ledger gauge block (satellite: golden-file
    update for the new gauges)."""
    c = _ctx(rows=32, name="ldg")
    config_module.config.update(
        {"serving.admission.max_estimated_bytes": 1 << 20})
    from dask_sql_tpu.serving.cache import table_nbytes

    t_bytes = sum(table_nbytes(dc.table)
                  for dc in c.schema["root"].tables.values())
    reg = MetricsRegistry()
    c.ledger.publish(reg)
    text = render_prometheus(reg.snapshot())
    assert text == (
        "# TYPE dsql_query_cache_hit_rate gauge\n"
        "dsql_query_cache_hit_rate 0\n"
        "# TYPE dsql_serving_ledger_budget_bytes gauge\n"
        f"dsql_serving_ledger_budget_bytes {1 << 20}\n"
        "# TYPE dsql_serving_ledger_cache_bytes gauge\n"
        "dsql_serving_ledger_cache_bytes 0\n"
        "# TYPE dsql_serving_ledger_headroom_bytes gauge\n"
        f"dsql_serving_ledger_headroom_bytes {(1 << 20) - t_bytes}\n"
        "# TYPE dsql_serving_ledger_inflight_measured_bytes gauge\n"
        "dsql_serving_ledger_inflight_measured_bytes 0\n"
        "# TYPE dsql_serving_ledger_materialized_bytes gauge\n"
        "dsql_serving_ledger_materialized_bytes 0\n"
        "# TYPE dsql_serving_ledger_model_bytes gauge\n"
        "dsql_serving_ledger_model_bytes 0\n"
        "# TYPE dsql_serving_ledger_reserve_drift_bytes gauge\n"
        "dsql_serving_ledger_reserve_drift_bytes 0\n"
        "# TYPE dsql_serving_ledger_reserved_bytes gauge\n"
        "dsql_serving_ledger_reserved_bytes 0\n"
        "# TYPE dsql_serving_ledger_table_bytes gauge\n"
        f"dsql_serving_ledger_table_bytes {t_bytes}\n"
    )


# ------------------------------------------------- cross-query causality
def test_batch_member_and_leader_traces_carry_flow_links():
    from dask_sql_tpu.families.batcher import FamilyBatcher

    batcher = FamilyBatcher(max_queries=4, window_ms=500.0,
                            busy=lambda: True)
    traces = [QueryTrace(sql="q0"), QueryTrace(sql="q1")]
    barrier = threading.Barrier(2)
    outs = [None, None]

    def worker(i):
        def solo():
            return [(i,)]

        def batched(members):
            return [[m] for m in members]

        with activate(traces[i]):
            barrier.wait(5.0)
            outs[i] = batcher.run("key", (i,), solo, batched)

    threads = [threading.Thread(target=worker, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10.0)
    assert outs[0] is not None and outs[1] is not None
    all_spans = {tr: [s.name for s in tr.spans] for tr in traces}
    leader = next(tr for tr in traces
                  if "batch_launch" in all_spans[tr])
    member = next(tr for tr in traces if tr is not leader)
    assert "batch_join" in all_spans[member]
    join = next(s for s in member.spans if s.name == "batch_join")
    launch = next(s for s in leader.spans if s.name == "batch_launch")
    # the member's flow OUT terminates at the leader's launch flow IN
    assert join.attrs["flow_out"] == launch.attrs["flow_in"]
    # traces are cross-linked so /v1/trace merges both endpoints
    assert leader.qid in member.links
    assert member.qid in leader.links
    merged = merge_chrome_traces([member, leader])
    flows = [e for e in merged["traceEvents"]
             if e.get("cat") == "dsql.flow"]
    starts = {e["id"] for e in flows if e["ph"] == "s"}
    finishes = {e["id"] for e in flows if e["ph"] == "f"}
    assert starts & finishes  # arrow has both endpoints
    # member and leader render as distinct processes in the merged export
    assert {e["pid"] for e in merged["traceEvents"]} == {1, 2}
    # flight recorder saw both roles
    names = {e["event"] for e in flight.RECORDER.events()}
    assert {"batch.lead", "batch.member"} <= names


def test_flow_events_in_single_trace_chrome_export():
    tr = QueryTrace(sql="x")
    tr.event("batch_join", flow_out="g:1")
    out = tr.to_chrome_trace()
    flows = [e for e in out["traceEvents"] if e.get("cat") == "dsql.flow"]
    assert len(flows) == 1 and flows[0]["ph"] == "s"


# -------------------------------------------------------- flight recorder
def test_flight_ring_is_bounded_and_filterable():
    rec = flight.FlightRecorder(capacity=32)
    for i in range(100):
        rec.record("query.admit", qid=f"q{i}")
    assert len(rec) == 32
    assert rec.recorded == 100
    newest = rec.events(limit=5)
    assert [e["qid"] for e in newest] == [f"q{i}" for i in range(95, 100)]
    assert rec.events(qid="q99")[0]["qid"] == "q99"
    assert rec.events(name="query.shed") == []


def test_flight_vocabulary_oracle():
    assert flight.is_registered_event("query.admit")
    assert flight.is_registered_event("breaker.trip")
    assert not flight.is_registered_event("query.admitt")
    assert not flight.is_registered_event("made.up")


def test_flight_auto_flush_on_query_failure(tmp_path):
    dump = tmp_path / "flight.jsonl"
    c = _ctx()
    config_module.config.update({
        "observability.flight.dump_path": str(dump),
        "resilience.ladder.enabled": False,
    })
    from dask_sql_tpu.resilience import faults

    faults.reset()
    with pytest.raises(Exception):
        c.sql("SELECT a FROM lt", return_futures=False,
              config_options={"resilience.inject": "execute:once"})
    faults.reset()
    lines = dump.read_text().strip().splitlines()
    assert len(lines) == 1
    record = json.loads(lines[0])
    assert record["error"]
    assert record["qid"]
    assert any(e["event"] == "query.admit" or e["event"] == "query.fail"
               for e in record["events"]) or record["events"] == []
    # the live table shows the failure too
    entry = c.live_queries.get(record["qid"])
    assert entry is not None and entry.state == "failed"
    assert c.metrics.counter("observability.flight.dumps") == 1


def test_dsql501_flags_unregistered_flight_event():
    from dask_sql_tpu.analysis.selflint import lint_source

    bad = "def f(flight, qid):\n    flight.record('bogus.event', qid=qid)\n"
    findings = lint_source(bad, "x.py")
    assert any(f.rule == "DSQL501" for f in findings)
    good = "def f(flight, qid):\n    flight.record('query.admit', qid=qid)\n"
    assert not [f for f in lint_source(good, "x.py")
                if f.rule == "DSQL501"]
    suppressed = ("def f(flight, qid):\n"
                  "    flight.record('bogus.event')"
                  "  # dsql: allow-flight-event\n")
    assert not [f for f in lint_source(suppressed, "x.py")
                if f.rule == "DSQL501"]


def test_dsql401_now_covers_gauges():
    from dask_sql_tpu.analysis.selflint import lint_source

    bad = "def f(metrics):\n    metrics.gauge('bogus.gauge', 1.0)\n"
    assert any(f.rule == "DSQL401" for f in lint_source(bad, "x.py"))
    good = ("def f(metrics):\n"
            "    metrics.gauge('serving.ledger.reserved_bytes', 1.0)\n")
    assert not [f for f in lint_source(good, "x.py")
                if f.rule == "DSQL401"]


def test_breaker_restore_detected_on_half_open_success():
    from dask_sql_tpu.resilience.retry import CircuitBreaker

    b = CircuitBreaker(threshold=1, cooldown_s=0.0)
    key = ("fp", "compiled_aggregate")
    assert b.record_failure(key)  # trips
    assert b.is_open(key)
    assert b.record_success(key) is True  # restore of an OPEN circuit
    b.record_failure(("fp2", "r"))  # sub-threshold? threshold=1 -> open
    assert b.record_success(("fp3", "r")) is False  # never failed


# ------------------------------------------------- queue-wait attribution
def test_scheduler_stamps_queue_wait_cause():
    from dask_sql_tpu.serving.admission import QueryTicket
    from dask_sql_tpu.serving.scheduler import PackingScheduler, QueryCost

    sched = PackingScheduler(budget_bytes=100)
    t1, t2 = QueryTicket("big"), QueryTicket("small")
    sched.push_locked(t1, lambda: None, None, QueryCost(bytes_lo=80))
    sched.push_locked(t2, lambda: None, None, QueryCost(bytes_lo=50))
    got = sched.pop_locked(batch_ok=True)
    assert got[0] is t1
    assert sched.pop_locked(batch_ok=True) is None  # byte-blocked
    sched.release_locked(t1)
    got2 = sched.pop_locked(batch_ok=True)
    assert got2[0] is t2
    assert t2.queue_reason == "byte_blocked"


# ------------------------------------ store bounds under concurrent load
def test_trace_store_bounds_with_eviction_racing_readers():
    store = TraceStore(keep=8)
    stop = threading.Event()
    failures = []

    def writer(tid):
        try:
            for i in range(300):
                tr = QueryTrace(sql=f"q{tid}-{i}")
                store.put(tr.qid, tr)
        except BaseException as exc:  # noqa: BLE001 - surfaced below
            failures.append(exc)

    def reader():
        try:
            while not stop.is_set():
                store.get("nope")
                assert len(store) <= 8
        except BaseException as exc:  # noqa: BLE001 - surfaced below
            failures.append(exc)

    writers = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
    readers = [threading.Thread(target=reader) for _ in range(4)]
    for t in writers + readers:
        t.start()
    for t in writers:
        t.join(30.0)
    stop.set()
    for t in readers:
        t.join(10.0)
    assert not failures
    assert len(store) <= 8


def test_profile_store_bounds_with_eviction_racing_readers():
    store = ProfileStore(window=4, keep=6)
    stop = threading.Event()
    failures = []

    def writer(tid):
        try:
            for i in range(200):
                fp = f"fp-{tid}-{i % 10}"
                store.record_exec(fp, sql=f"SELECT {i}", exec_ms=float(i),
                                  result_bytes=i)
                store.record_compile(fp, "compiled_aggregate", float(i))
        except BaseException as exc:  # noqa: BLE001 - surfaced below
            failures.append(exc)

    def reader():
        try:
            while not stop.is_set():
                store.rows()
                store.snapshot()
                assert len(store) <= 6
        except BaseException as exc:  # noqa: BLE001 - surfaced below
            failures.append(exc)

    writers = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
    readers = [threading.Thread(target=reader) for _ in range(4)]
    for t in writers + readers:
        t.start()
    for t in writers:
        t.join(30.0)
    stop.set()
    for t in readers:
        t.join(10.0)
    assert not failures
    assert len(store) <= 6


# ----------------------------------------------------------- wire surface
@pytest.fixture()
def live_server():
    from dask_sql_tpu.server.app import PrestoServer

    c = _ctx(rows=256, name="wt")
    srv = PrestoServer(context=c, host="127.0.0.1", port=0)
    srv.start_background()
    yield c, srv
    srv.shutdown()


def _wire(base, path, method="GET", body=b""):
    req = urllib.request.Request(base + path, method=method,
                                 data=body if method == "POST" else None)
    return json.load(urllib.request.urlopen(req))


def test_wire_queries_endpoint_and_cancel(live_server):
    c, srv = live_server
    base = f"http://127.0.0.1:{srv.port}"
    out = _wire(base, "/v1/statement", "POST",
                b"SELECT SUM(a) AS s FROM wt")
    qid = out["id"]
    deadline = time.time() + 10.0
    while time.time() < deadline:
        st = _wire(base, f"/v1/statement/{qid}")
        if "data" in st or "error" in st:
            break
        time.sleep(0.01)
    snap = _wire(base, "/v1/queries")
    entry = next(e for e in snap["queries"] if e["qid"] == qid)
    assert entry["state"] == "done"
    assert entry["rung"]
    assert "ledger" in snap and "reservedBytes" in snap["ledger"]
    one = _wire(base, f"/v1/queries/{qid}")
    assert one["qid"] == qid
    # cancel of a terminal query is a 404, not a crash
    with pytest.raises(urllib.error.HTTPError):
        _wire(base, f"/v1/queries/{qid}/cancel", "POST")
    # the debug-events dump is live and filterable
    ev = _wire(base, "/v1/debug/events?name=query.admit")
    assert any(e.get("qid") == qid for e in ev["events"])


def test_wire_metrics_includes_ledger_gauges(live_server):
    c, srv = live_server
    base = f"http://127.0.0.1:{srv.port}"
    body = urllib.request.urlopen(
        base + "/v1/metrics?format=prometheus").read().decode()
    assert "dsql_serving_ledger_table_bytes" in body
    assert "dsql_serving_ledger_reserved_bytes 0" in body
    snap = _wire(base, "/v1/metrics")
    assert "ledger" in snap
