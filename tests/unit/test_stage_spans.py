"""Stage spans that tile a request and a load (docs/observability.md §1 and
"Load traces"): `plan_lookup` / `reuse` / `admit` / `account` around the
old stages, `launch` / `fetch` details inside `execute`, `result_wait` on
the wire, and the `load:*` spans of `Context.create_table` with the
`load.*` metrics they sum into.
"""
import json
import os
import re
import time
import urllib.request

import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.observability.spans import DETAIL, LOAD_PHASES, STAGE

pytestmark = pytest.mark.observability

NEW_SPANS = ("plan_lookup", "reuse", "admit", "account", "scan_bytes",
             "observe", "launch", "fetch", "handoff", "result_wait")
DOC = os.path.join(os.path.dirname(__file__), "..", "..", "docs",
                   "observability.md")


def _frame(rows=2048):
    return pd.DataFrame({
        "g": (np.arange(rows) % 5).astype(np.int64),
        "v": np.arange(rows, dtype=np.float64) * 0.25,
        "s": np.array([f"k{i % 37}" for i in range(rows)], dtype=object),
        "d": pd.to_datetime(np.arange(rows) % 90, unit="D"),
    })


def _ctx(name="st", rows=2048):
    c = Context()
    c.create_table(name, _frame(rows))
    return c


@pytest.fixture
def served():
    from dask_sql_tpu.server.app import run_server

    c = _ctx("wire_st")
    srv = run_server(context=c, host="127.0.0.1", port=0, blocking=False)
    yield c, srv
    srv.shutdown()


def _serve(port, sql, delay=0.0):
    """POST one statement, wait ``delay``, then poll it out; its qid."""
    req = urllib.request.Request(f"http://127.0.0.1:{port}/v1/statement",
                                 data=sql.encode(), method="POST")
    with urllib.request.urlopen(req) as resp:
        status = json.loads(resp.read())
    qid = status["id"]
    time.sleep(delay)
    deadline = time.time() + 60
    while "nextUri" in status and time.time() < deadline:
        with urllib.request.urlopen(status["nextUri"]) as resp:
            status = json.loads(resp.read())
        if "nextUri" in status:
            time.sleep(0.005)
    assert "data" in status, status
    return qid


def _run(surface, c, srv, sql, delay=0.0):
    if surface == "library":
        c.sql(sql).compute()
        return c.last_trace
    return c.traces.get(_serve(srv.port, sql, delay))


def _covered(trace):
    stages = trace.stage_spans()
    for left, right in zip(stages, stages[1:]):
        assert left.t1 <= right.t0 + 1e-9, (left.name, right.name)
    return sum(s.t1 - s.t0 for s in stages) * 1e3 / trace.total_ms()


def _details(trace, name):
    return [s for s in trace.spans if s.name == name and s.kind == DETAIL]


# ---------------------------------------------------------------- request
@pytest.mark.parametrize("surface", ["library", "wire"])
def test_stages_tile_the_request(surface, served, monkeypatch):
    """(a) sequential stages whose union covers the trace's extent, with
    the bookkeeping after the rung under `account` and its `scan_bytes`."""
    from dask_sql_tpu.serving import cache

    real = cache.table_nbytes

    def slow_nbytes(table):
        time.sleep(0.05)
        return real(table)

    c, srv = served
    sql = "SELECT g, SUM(v) AS sv FROM wire_st WHERE v > {} GROUP BY g"
    _run(surface, c, srv, sql.format(1))  # compile outside the timed one
    monkeypatch.setattr(cache, "table_nbytes", slow_nbytes)
    trace = _run(surface, c, srv, sql.format(2))
    assert _covered(trace) >= 0.9
    names = [s.name for s in trace.stage_spans()]
    order = ["plan_lookup", "parse", "bind", "cache_lookup", "reuse",
             "admit", "execute", "account", "d2h"]
    if surface == "wire":
        order = ["queue_wait"] + order + ["handoff", "result_wait",
                                          "serialize"]
    assert [n for n in names if n in order] == order, names
    account = next(s for s in trace.stage_spans() if s.name == "account")
    assert account.dur_ms >= 50.0
    scan = _details(trace, "scan_bytes")
    assert len(scan) == 1 and scan[0].parent == "account"
    assert scan[0].dur_ms >= 50.0
    assert scan[0].attrs["tables"] == 1 and scan[0].attrs["bytes"] > 0
    assert account.t0 <= scan[0].t0 and scan[0].t1 <= account.t1
    assert [s.parent for s in _details(trace, "observe")] == ["account"]


def _walked_table_nbytes(table):
    """The accounting rule spelled out with every dictionary walked, as
    before the character count was kept per dictionary array."""
    total = 0 if table.row_valid is None else int(table.row_valid.nbytes)
    for col in table.columns.values():
        for buf in (col.data, col.validity, col.enc_lengths, col.enc_values):
            if buf is not None:
                total += int(buf.nbytes)
        if col.dictionary is not None:
            total += sum(len(str(v)) for v in col.dictionary) \
                + col.dictionary.nbytes
    return total


def test_scan_bytes_reads_the_kept_dictionary_count():
    """Two queries over a table with a 50,000-entry string dictionary that
    neither reads: `scan_bytes` reports the same, walked-rule bytes in
    both, the ticket's measured bytes are what a walk would give, and no
    dictionary is walked between the first query and the second."""
    from dask_sql_tpu.serving.cache import table_nbytes
    from dask_sql_tpu.utils import DICTIONARY_STATS

    rows = 50_000
    c = Context()
    c.create_table("wide", pd.DataFrame({
        "g": (np.arange(rows) % 5).astype(np.int64),
        "v": np.arange(rows, dtype=np.float64) * 0.25,
        "note": np.array([f"n\u00f6te {i:06d}" + "x" * (i % 9)
                          for i in range(rows)], dtype=object),
    }))
    table = c.schema["root"].tables["wide"].table
    assert len(table.columns["note"].dictionary) == rows
    scanned = _walked_table_nbytes(table)
    assert table_nbytes(table) == scanned

    seen = []
    for cut in (1, 2):
        frame = c.sql(f"SELECT g, SUM(v) AS sv FROM wide WHERE v > {cut} "
                      "GROUP BY g")
        frame.compute()
        scan, = _details(c.last_trace, "scan_bytes")
        entry = c.live_queries.entries()[-1]
        seen.append((scan.attrs["bytes"], scan.attrs["tables"],
                     entry.measured_bytes
                     - _walked_table_nbytes(frame._result),
                     dict(DICTIONARY_STATS)))
    assert seen[0][:3] == seen[1][:3] == (scanned, 1, scanned)
    assert seen[0][3] == seen[1][3]


@pytest.mark.parametrize("temperature", ["cold", "warm"])
def test_launch_and_fetch_inside_execute(temperature):
    """(b) every jitted call is a `launch`, every blocking pull a `fetch`,
    both naming the rung; a cold call keeps its `compile:<rung>` too."""
    c = Context()
    # column names no other test compiles for: the first run IS cold
    c.create_table("lf", pd.DataFrame({
        f"lf_g_{temperature}": (np.arange(4096) % 3).astype(np.int64),
        f"lf_v_{temperature}": np.arange(4096, dtype=np.float64),
    }))
    sql = (f"SELECT lf_g_{temperature}, SUM(lf_v_{temperature}) AS s "
           f"FROM lf WHERE lf_v_{temperature} > {{}} "
           f"GROUP BY lf_g_{temperature}")
    c.sql(sql.format(1)).compute()
    if temperature == "warm":
        c.sql(sql.format(2)).compute()
    trace = c.last_trace
    execute = next(s for s in trace.stage_spans() if s.name == "execute")
    launches = _details(trace, "launch")
    fetches = [s for s in _details(trace, "fetch") if s.parent == "execute"]
    assert len(launches) == 1 and len(fetches) >= 1
    compiles = [s for s in trace.spans if s.name.startswith("compile:")]
    assert len(compiles) == (1 if temperature == "cold" else 0)
    for span in launches + fetches + compiles:
        assert span.attrs["rung"] == "compiled_aggregate"
        assert execute.t0 <= span.t0 and span.t1 <= execute.t1
    assert launches[0].t1 <= fetches[0].t0
    if compiles:
        assert compiles[0].name == "compile:compiled_aggregate"
    # the result's own pull sits under the d2h stage
    assert {s.parent for s in _details(trace, "fetch")} <= {"execute", "d2h"}
    assert trace.has_span("rung:compiled_aggregate")


def test_repeated_compute_adds_no_fetch():
    c = _ctx()
    frame = c.sql("SELECT g, v FROM st WHERE v > 3")
    frame.compute()
    spans = len(c.last_trace.spans)
    frame.compute()
    assert len(c.last_trace.spans) == spans


def test_served_query_polled_late_waits_under_result_wait(served):
    """(d) a finished result's wait for its client is `result_wait`."""
    c, srv = served
    sql = "SELECT g, COUNT(*) AS n FROM wire_st WHERE v > {} GROUP BY g"
    _serve(srv.port, sql.format(1))
    trace = c.traces.get(_serve(srv.port, sql.format(2), delay=0.4))
    wait = next(s for s in trace.stage_spans() if s.name == "result_wait")
    serialize = next(s for s in trace.stage_spans() if s.name == "serialize")
    d2h = next(s for s in trace.stage_spans() if s.name == "d2h")
    assert wait.dur_ms >= 300.0
    handoff = next(s for s in trace.stage_spans() if s.name == "handoff")
    assert d2h.t1 == handoff.t0 and handoff.t1 == wait.t0
    assert wait.t1 == serialize.t0
    assert _covered(trace) >= 0.99  # the wire trace tiles end to end


def test_ddl_statement_runs_under_execute_with_load_details():
    c = _ctx()
    c.sql("CREATE TABLE st2 AS (SELECT g, v, s FROM st WHERE v > 10)")
    trace = c.last_trace
    assert "execute" in [s.name for s in trace.stage_spans()]
    loads = [s for s in trace.spans if s.name.startswith("load:")]
    assert loads and all(s.kind == DETAIL and s.parent == "execute"
                         for s in loads)
    assert c.traces.get("load:root.st2") is None
    assert _covered(trace) >= 0.9


# ------------------------------------------------------------------- load
def _arrow(frame):
    pa = pytest.importorskip("pyarrow")
    return pa.Table.from_pandas(frame)


@pytest.fixture
def mesh4():
    """The default mesh cut to four of the virtual devices, then restored."""
    from dask_sql_tpu.parallel import mesh as mesh_module

    was = mesh_module._default_mesh
    mesh_module.set_default_mesh(mesh_module.make_mesh(4))
    yield
    mesh_module.set_default_mesh(was)


@pytest.mark.parametrize("source", ["pandas", "pyarrow", "sharded"])
def test_create_table_leaves_a_tiled_load_trace(source, mesh4):
    """(c) the `load:*` stages tile the call and sum to `load.*_ms`; the
    fifth, `load:shard`, is all of `shard_table` on a `distributed=True`
    load and observes 0 on any other."""
    frame = _frame(20000)
    data = _arrow(frame) if source == "pyarrow" else frame
    sharded = source == "sharded"
    c = Context()
    t0 = time.perf_counter()
    c.create_table("ld", data, distributed=sharded)
    call_ms = (time.perf_counter() - t0) * 1e3
    trace = c.traces.get("load:root.ld")
    assert trace is not None and trace.finished
    assert trace.sql == "create_table root.ld"
    stages = trace.stage_spans()
    # beside the phases, the compiles the load ran (observability/xla.py:
    # the mesh's placement programs), each under the phase open at the time
    compiles = [s for s in trace.spans if s.name.startswith("xla:")]
    assert all(s.kind == DETAIL and s.parent.startswith("load:")
               for s in compiles)
    assert {s.name for s in trace.spans} - {"xla:lower", "xla:compile"} \
        == {f"load:{p}" for p in LOAD_PHASES if sharded or p != "shard"}
    assert all(s.kind == STAGE for s in trace.spans
               if not s.name.startswith("xla:"))
    hists = c.metrics.snapshot()["histograms"]
    total = 0.0
    for phase in LOAD_PHASES:
        ms = sum(s.dur_ms for s in stages if s.name == f"load:{phase}")
        assert hists[f"load.{phase}_ms"]["count"] == 1
        assert hists[f"load.{phase}_ms"]["sum"] == pytest.approx(ms, abs=0.01)
        total += ms
    shard = [s for s in stages if s.name == "load:shard"]
    if sharded:
        table = c.schema["root"].tables["ld"].table
        placed = [col.data for col in table.columns.values()] \
            + [col.validity for col in table.columns.values()
               if col.validity is not None]
        assert all(len(b.sharding.device_set) == 4 for b in placed)
        assert all(s.attrs["devices"] == 4 for s in shard)
        assert shard[-1].attrs["bytes"] == sum(b.nbytes for b in placed)
        assert hists["load.shard_ms"]["sum"] > 0
    else:
        assert hists["load.shard_ms"]["sum"] == 0
    assert _covered(trace) >= 0.99  # exclusive segments: no gap, no overlap
    assert 0.9 * call_ms <= total <= call_ms
    assert c.metrics.counter("load.rows") == 20000
    h2d = [s for s in stages if s.name == "load:h2d"]
    assert c.metrics.counter("load.h2d_bytes") == sum(
        s.attrs["bytes"] for s in h2d) > 0
    # per-column grain: every column converts, the strings encode
    assert {s.attrs.get("column") for s in stages
            if s.name == "load:convert"} >= {"g", "v", "s", "d"}
    encodes = {s.attrs["column"]: s.attrs for s in stages
               if s.name == "load:encode" and "encoding" in s.attrs}
    assert encodes["s"]["encoding"] == "STRING"
    assert encodes["s"]["distinct"] == 37
    assert c.sql("SELECT COUNT(*) AS n FROM ld").compute()["n"][0] == 20000


def test_query_time_columns_record_no_load_span():
    from dask_sql_tpu.columnar.column import Column

    c = _ctx()
    before = c.metrics.snapshot()["histograms"]["load.convert_ms"]["count"]
    with pytest.MonkeyPatch.context() as patch:
        built = []
        real = Column.from_numpy
        patch.setattr(Column, "from_numpy", staticmethod(
            lambda *a, **kw: built.append(1) or real(*a, **kw)))
        # the host fallback of a custom aggregation builds its result
        # column with `Column.from_numpy` while the query runs
        c.register_aggregation(lambda x: x.sum(), "mysum",
                               [("x", np.float64)], np.float64)
        c.sql("SELECT g, mysum(v) AS m FROM st GROUP BY g").compute()
        assert built
    assert not [s.name for s in c.last_trace.spans
                if s.name.startswith("load:")]
    assert c.metrics.snapshot()["histograms"]["load.convert_ms"][
        "count"] == before
    # nor does an append: the registration scope is create_table's alone
    c.append_rows("st", _frame(16))
    assert c.metrics.snapshot()["histograms"]["load.convert_ms"][
        "count"] == before


@pytest.mark.parametrize("surface", ["library", "wire", "load"])
def test_tracing_off_records_no_span_and_keeps_load_metrics(surface, served):
    """(e) `observability.trace.enabled: false` silences every new span;
    the `load.*` histograms record all the same."""
    c, srv = served
    config_module.config.update({"observability.trace.enabled": False})
    try:
        c.last_trace = None
        traces = len(c.traces)
        if surface == "load":
            c.create_table("off_t", _frame(4096))
            assert c.traces.get("load:root.off_t") is None
            hists = c.metrics.snapshot()["histograms"]
            assert all(hists[f"load.{p}_ms"]["count"] == 2
                       for p in LOAD_PHASES)
            assert hists["load.convert_ms"]["sum"] > 0
            assert c.metrics.counter("load.rows") == 2048 + 4096
        elif surface == "library":
            c.sql("SELECT g, SUM(v) AS s FROM wire_st GROUP BY g").compute()
        else:
            qid = _serve(srv.port, "SELECT g, MAX(v) AS m FROM wire_st "
                                   "GROUP BY g")
            assert c.traces.get(qid) is None
        assert c.last_trace is None and len(c.traces) == traces
    finally:
        config_module.config.update({"observability.trace.enabled": True})


# -------------------------------------------------------------------- doc
def _doc_table(heading):
    """First-column names of the table under ``heading`` in the doc."""
    with open(DOC) as f:
        text = f.read()
    section = text[text.index(heading):]
    names = []
    for line in section.splitlines()[1:]:
        if line.lstrip().startswith("|"):
            names += re.findall(r"^\s*\|\s*`([^`]+)`", line)
        elif names and not line.strip():
            break
    return names


@pytest.mark.parametrize("surface", ["library", "wire"])
def test_every_recorded_stage_is_in_the_docs_table(surface, served):
    c, srv = served
    trace = _run(surface, c, srv,
                 "SELECT g, MIN(v) AS m FROM wire_st WHERE v > 5 GROUP BY g")
    documented = _doc_table("| stage | recorded by | meaning |")
    recorded = [s.name for s in trace.stage_spans()]
    assert set(recorded) <= set(documented), (recorded, documented)
    # and in the doc's order
    assert recorded == [n for n in documented if n in recorded]


def test_load_spans_and_metrics_are_in_the_docs():
    with open(DOC) as f:
        text = f.read()
    section = text[text.index("## Load traces"):]
    for phase in LOAD_PHASES:
        assert f"`load:{phase}`" in section
        assert f"`load.{phase}_ms`" in section
    assert "`load.rows`" in section and "`load.h2d_bytes`" in section
    for name in NEW_SPANS:
        assert f"`{name}`" in text, name


def test_dictionary_walk_counters_are_in_the_docs():
    from dask_sql_tpu.utils import DICTIONARY_STATS

    assert _doc_table("| counter | counts |") == list(DICTIONARY_STATS)
