"""Streaming partitioned execution (streaming/, ISSUE 13).

The acceptance surface: a query whose provable ``peak_bytes.lo`` exceeds
``serving.admission.max_estimated_bytes`` completes — byte-identical to an
unconstrained context — via N>1 streamed partition launches of ONE morsel
executable (zero foreground compiles after the first partition, and zero
for the second streamed run of a family); an injected mid-stream OOM at
the ``partition`` site repartitions and RESUMES from the last completed
partition; exhausted recovery steps down streamed->interpreted charging
the breaker per (family, rung); the shed is the last resort (only when
even one chunk provably cannot fit); the packing scheduler reserves only
the per-chunk footprint and reconciles reservations against measured
bytes on release.
"""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.resilience import faults
from dask_sql_tpu.serving.admission import EstimatedBytesExceededError
from dask_sql_tpu.serving.cache import table_nbytes

pytestmark = pytest.mark.streaming

N_ROWS = 40_000


@pytest.fixture(autouse=True)
def _fresh_state():
    """Fault budgets, morsel-executable caches and the global config are
    process-wide; every test starts clean and leaves nothing behind."""
    from dask_sql_tpu.streaming import aggregate as stream_agg
    from dask_sql_tpu.streaming import select as stream_sel

    saved = config_module.config.effective_items()
    faults.reset()
    stream_agg.reset_cache()
    stream_sel.reset_cache()
    yield
    config_module.config.update(dict(saved))
    faults.reset()
    stream_agg.reset_cache()
    stream_sel.reset_cache()


def _ctx(n=N_ROWS):
    c = Context()
    c.config.update({"serving.cache.enabled": False})
    rng = np.random.RandomState(7)
    df = pd.DataFrame({
        "k": rng.randint(0, 5, n).astype(np.int64),
        "v": rng.randint(0, 1000, n).astype(np.int64),
        "f": rng.rand(n),
    })
    c.create_table("t", df)
    return c, df


def _budget(c, frac=3):
    """A budget between the one-shot provable floor (the whole resident
    scan) and the per-chunk floor: forces streaming, never shedding."""
    return table_nbytes(c.schema["root"].tables["t"].table) // frac


AGG_Q = ("SELECT k, SUM(v) AS s, COUNT(*) AS n, AVG(v) AS a, "
         "MIN(v) AS mn, MAX(f) AS mx FROM t GROUP BY k ORDER BY k")
SEL_Q = "SELECT k, v * 2 AS v2 FROM t WHERE f > 0.9"


def _stream_counters(c):
    snap = c.metrics.snapshot()["counters"]
    return {k: v for k, v in snap.items()
            if k.startswith(("serving.stream.", "resilience.partition."))}


# -------------------------------------------------- acceptance: streamed run
def test_oversize_aggregate_streams_byte_identical():
    c, _ = _ctx()
    expected = c.sql(AGG_Q, return_futures=False)
    res = c.sql(AGG_Q, return_futures=False, config_options={
        "serving.admission.max_estimated_bytes": _budget(c)})
    # byte-identical to the unconstrained context (int sums/counts/min/max
    # are exact; avg divides exact int states)
    pd.testing.assert_frame_equal(res, expected)
    snap = _stream_counters(c)
    assert snap["serving.stream.admitted"] == 1
    assert snap["serving.stream.partitions"] > 1
    assert snap["serving.stream.rows"] == N_ROWS
    assert c.metrics.counter("resilience.rung.streamed_aggregate") == 1
    # the shed never fired: streaming replaced it
    assert c.metrics.counter("serving.shed_estimated_bytes") == 0


def test_oversize_select_streams_in_global_row_order():
    c, _ = _ctx()
    expected = c.sql(SEL_Q, return_futures=False)
    res = c.sql(SEL_Q, return_futures=False, config_options={
        "serving.admission.max_estimated_bytes": _budget(c)})
    # survivor concatenation preserves global row order — frame-equal
    # without any sort normalization
    pd.testing.assert_frame_equal(res, expected)
    assert c.metrics.counter("serving.stream.partitions") > 1
    assert c.metrics.counter("resilience.rung.streamed_select") == 1


def test_streamed_string_group_keys_match():
    c = Context()
    c.config.update({"serving.cache.enabled": False})
    rng = np.random.RandomState(3)
    df = pd.DataFrame({
        "g": rng.choice(["aa", "bb", "cc", "dd"], N_ROWS),
        "v": rng.randint(0, 100, N_ROWS).astype(np.int64),
    })
    c.create_table("t", df)
    q = "SELECT g, SUM(v) AS s, COUNT(*) AS n FROM t GROUP BY g ORDER BY g"
    expected = c.sql(q, return_futures=False)
    res = c.sql(q, return_futures=False, config_options={
        "serving.admission.max_estimated_bytes": _budget(c)})
    pd.testing.assert_frame_equal(res, expected)
    assert c.metrics.counter("serving.stream.partitions") > 1


# ------------------------------------------- admission x streaming interplay
def test_budget_between_floors_streams_under_it_runs_single_launch():
    c, _ = _ctx()
    # generous budget: no gate trigger, no streaming — the single-launch
    # compiled rung answers
    c.sql(AGG_Q, return_futures=False, config_options={
        "serving.admission.max_estimated_bytes": 1 << 40})
    assert c.metrics.counter("serving.stream.admitted") == 0
    assert c.metrics.counter("serving.stream.partitions") == 0


def test_sheds_only_when_even_one_chunk_cannot_fit():
    c, _ = _ctx()
    # a budget below the floor of even a min_chunk_rows-sized chunk: the
    # last resort fires with the structured taxonomy error
    with pytest.raises(EstimatedBytesExceededError):
        c.sql(AGG_Q, return_futures=False, config_options={
            "serving.admission.max_estimated_bytes": 1 << 10})
    assert c.metrics.counter("serving.shed_estimated_bytes") == 1
    assert c.metrics.counter("serving.stream.admitted") == 0


def test_stream_disabled_restores_plain_shed():
    c, _ = _ctx()
    with pytest.raises(EstimatedBytesExceededError):
        c.sql(AGG_Q, return_futures=False, config_options={
            "serving.admission.max_estimated_bytes": _budget(c),
            "serving.stream.enabled": False})


def test_construction_ineligible_routed_plan_resheds():
    # a shape the static routing walk cannot rule out: a PLAIN int group
    # key whose device span overflows what ONE key's state may cost
    # (`one_key_domain_limit`: 2^26 values at two slots; until PR 35 the
    # 1<<22 radix gate, which 1<<23 overflowed).  The rung
    # discovers it at construction — and must RE-SHED with the gate's 429
    # rather than decline down the ladder into a full over-budget
    # single-launch execution (the regression this guards against)
    c = Context()
    c.config.update({"serving.cache.enabled": False,
                     "columnar.encoding": "off"})
    rng = np.random.RandomState(5)
    df = pd.DataFrame({
        "k": rng.choice([0, 1 << 27], N_ROWS).astype(np.int64),
        "v": rng.randint(0, 100, N_ROWS).astype(np.int64),
    })
    c.create_table("t", df)
    with pytest.raises(EstimatedBytesExceededError):
        c.sql("SELECT k, SUM(v) AS s FROM t GROUP BY k",
              return_futures=False, config_options={
                  "serving.admission.max_estimated_bytes": _budget(c)})
    assert c.metrics.counter("serving.shed_estimated_bytes") == 1
    assert c.metrics.counter("serving.stream.partitions") == 0


def test_compile_disabled_sheds_instead_of_routing_past_the_gate():
    # the rungs require sql.compile; routing would bypass the shed and run
    # the full over-budget working set on a lower rung — the decision must
    # mirror the rung preconditions so the 429 contract survives
    c, _ = _ctx()
    with pytest.raises(EstimatedBytesExceededError):
        c.sql(AGG_Q, return_futures=False, config_options={
            "serving.admission.max_estimated_bytes": _budget(c),
            "sql.compile": False})
    with pytest.raises(EstimatedBytesExceededError):
        c.sql(SEL_Q, return_futures=False, config_options={
            "serving.admission.max_estimated_bytes": _budget(c),
            "sql.compile.select": False})
    assert c.metrics.counter("serving.stream.admitted") == 0
    assert c.metrics.counter("serving.shed_estimated_bytes") == 2


def test_streamed_select_repartition_compiles_under_watchdog(monkeypatch):
    # after a mid-stream repartition the NEW chunk shape's mask kernel must
    # run with may_compile=True (per-shape warm tracking), so the compile
    # watchdog covers exactly the OOM-recovery path (regression: the
    # parent's single-boolean warm flag ran every post-first-chunk compile
    # with may_compile=False, outside the watchdog)
    c, _ = _ctx()
    expected = c.sql(SEL_Q, return_futures=False)
    res = c.sql(SEL_Q, return_futures=False, config_options={
        "serving.admission.max_estimated_bytes": _budget(c),
        "resilience.inject": "partition:at2",
        "serving.stream.min_chunk_rows": 512})
    pd.testing.assert_frame_equal(res, expected)
    assert c.metrics.counter("serving.stream.repartitions") == 1
    # white-box: drive the cached streamed executable over fresh chunk
    # shapes and record the hint each mask launch carries
    from dask_sql_tpu.streaming.select import PROGRAMS
    import dask_sql_tpu.observability as obs

    obj = PROGRAMS.values()[0]
    real = obs.timed_jit_call
    hints = []

    def spy(rung, fn, *args, may_compile=None, **kwargs):
        hints.append(may_compile)
        return real(rung, fn, *args, may_compile=may_compile, **kwargs)

    monkeypatch.setattr(obs, "timed_jit_call", spy)
    from dask_sql_tpu.streaming.partition import slice_chunk

    table = c.schema["root"].tables["t"].table
    # SEL_Q's parameterized literals in rewrite order: the scan filter's
    # 0.9, then the projection's *2 multiplier
    params = (np.float64(0.9), np.int64(2))
    first = []
    for rows in (640, 320, 640):
        hints.clear()
        obj.run(slice_chunk(table, 0, rows), params)
        first.append(hints[0])  # the mask launch's hint
    # new shape -> watched; another new shape (the repartition case) ->
    # watched again; a repeated shape -> known-warm
    assert first == [True, True, False]


def test_stream_verdict_is_per_execution_not_plan_state():
    c, _ = _ctx()
    budget = _budget(c)
    c.sql(AGG_Q, return_futures=False, config_options={
        "serving.admission.max_estimated_bytes": budget})
    assert c.metrics.counter("serving.stream.partitions") > 1
    parts = c.metrics.counter("serving.stream.partitions")
    # same SQL under no budget: the verdict lived on the previous
    # execution's executor, not the cached plan, so this run serves
    # single-launch — and no plan node carries routing marks at all
    c.sql(AGG_Q, return_futures=False)
    assert c.metrics.counter("serving.stream.partitions") == parts
    from dask_sql_tpu.planner.parser import parse_sql

    plan = c._get_ral(parse_sql(AGG_Q)[0], sql_text=AGG_Q)
    from dask_sql_tpu.planner import plan as p

    assert all(getattr(n, "_dsql_stream", None) is None
               for n in p.walk_plan(plan))


def test_second_streamed_family_run_zero_foreground_compiles():
    c, _ = _ctx()
    budget = _budget(c)
    opts = {"serving.admission.max_estimated_bytes": budget}
    q1 = "SELECT k, SUM(v) AS s FROM t WHERE v > 10 GROUP BY k ORDER BY k"
    q2 = "SELECT k, SUM(v) AS s FROM t WHERE v > 500 GROUP BY k ORDER BY k"
    c.sql(q1, return_futures=False, config_options=opts)
    t1 = c.last_trace
    c.sql(q2, return_futures=False, config_options=opts)
    t2 = c.last_trace
    assert t2 is not t1
    compiles1 = [s.name for s in t1.spans if s.name.startswith("compile:")]
    compiles2 = [s.name for s in t2.spans if s.name.startswith("compile:")]
    # first run pays the morsel compile ONCE (not once per partition) ...
    assert compiles1.count("compile:streamed_aggregate") == 1
    assert c.metrics.counter("serving.stream.partitions") > 2
    # ... the second literal variant of the family pays ZERO
    assert compiles2 == []
    # and both runs match the unconstrained answers
    pd.testing.assert_frame_equal(
        c.sql(q2, return_futures=False, config_options=opts),
        c.sql(q2, return_futures=False))


# -------------------------------------------------- mid-stream OOM recovery
def test_midstream_oom_repartitions_and_resumes():
    c, _ = _ctx()
    expected = c.sql(AGG_Q, return_futures=False)
    res = c.sql(AGG_Q, return_futures=False, config_options={
        "serving.admission.max_estimated_bytes": _budget(c),
        "resilience.inject": "partition:at2",
        "serving.stream.min_chunk_rows": 512})
    pd.testing.assert_frame_equal(res, expected)
    snap = _stream_counters(c)
    assert snap["resilience.partition.oom"] == 1
    assert snap["serving.stream.repartitions"] == 1
    # resume, not restart: every logical row was processed EXACTLY once
    # (the completed first partition was never re-executed — a restart
    # would double-count it, corrupting the sums above too)
    assert snap["serving.stream.rows"] == N_ROWS
    assert c.metrics.counter("resilience.degraded") == 0


def test_recovery_exhaustion_steps_down_and_charges_breaker():
    c, _ = _ctx()
    expected = c.sql(AGG_Q, return_futures=False)
    opts = {"serving.admission.max_estimated_bytes": _budget(c),
            "resilience.inject": "partition:always",
            "serving.stream.min_chunk_rows": 4096}
    # streamed -> repartition (until the chunk floor) -> interpreted:
    # the query STILL answers correctly on the lower rung
    res = c.sql(AGG_Q, return_futures=False, config_options=opts)
    pd.testing.assert_frame_equal(res, expected)
    snap = _stream_counters(c)
    assert snap["resilience.partition.exhausted"] >= 1
    assert c.metrics.counter("resilience.degraded.streamed_aggregate") == 1
    # breaker charged per (family, rung): repeated failures trip it and
    # the NEXT submission skips the streamed rung outright
    c.sql(AGG_Q, return_futures=False, config_options=opts)
    c.sql(AGG_Q, return_futures=False, config_options=opts)
    assert c.metrics.counter("resilience.breaker.trip") >= 1
    c.sql(AGG_Q, return_futures=False, config_options=opts)
    assert c.metrics.counter("resilience.breaker.skip.streamed_aggregate") \
        >= 1


def test_at_k_fault_mode_fires_exactly_kth_arm():
    inj = faults.FaultInjector("partition:at3")
    assert not inj.arm("partition")
    assert not inj.arm("partition")
    assert inj.arm("partition")
    assert not inj.arm("partition")
    assert inj.fired("partition") == 1


def test_deadline_checkpoint_between_partitions():
    from dask_sql_tpu.serving.admission import (
        DeadlineExceededError,
        QueryTicket,
    )
    from dask_sql_tpu.serving import runtime as rt

    c, _ = _ctx()
    ticket = QueryTicket("q-stream", deadline=-1.0)  # already expired
    rt._tls.ticket = ticket
    try:
        with pytest.raises(DeadlineExceededError):
            c.sql(AGG_Q, return_futures=False, config_options={
                "serving.admission.max_estimated_bytes": _budget(c)})
    finally:
        rt._tls.ticket = None


# ----------------------------------------------------- scheduler integration
def test_scheduler_reserves_per_chunk_floor_for_streamed_cost():
    from dask_sql_tpu.serving import MetricsRegistry, PackingScheduler
    from dask_sql_tpu.serving.admission import QueryTicket
    from dask_sql_tpu.serving.scheduler import QueryCost

    m = MetricsRegistry()
    s = PackingScheduler(budget_bytes=1000, metrics=m)
    big = QueryTicket("big", "batch")
    s.push_locked(big, lambda: None, None,
                  QueryCost(bytes_lo=10_000, chunk_bytes_lo=600))
    assert s.pop_locked(batch_ok=True) is not None
    # the reservation is the CHUNK floor, not the whole-table floor ...
    assert s.reserved_bytes == 600
    # ... so an interactive query whose floor fits the remainder packs in
    small = QueryTicket("small")
    s.push_locked(small, lambda: None, None, QueryCost(bytes_lo=300))
    assert s.pop_locked(batch_ok=True) is not None
    assert m.counter("serving.scheduler.packed") == 1


def test_release_reconciles_measured_bytes_as_drift():
    from dask_sql_tpu.serving import MetricsRegistry, PackingScheduler
    from dask_sql_tpu.serving.admission import QueryTicket
    from dask_sql_tpu.serving.scheduler import QueryCost

    m = MetricsRegistry()
    s = PackingScheduler(budget_bytes=1000, metrics=m)
    t = QueryTicket("q")
    s.push_locked(t, lambda: None, None, QueryCost(bytes_lo=400))
    assert s.pop_locked(batch_ok=True) is not None
    s.push_locked(QueryTicket("q2"), lambda: None, None,
                  QueryCost(bytes_lo=100))
    assert s.pop_locked(batch_ok=True) is not None
    s.release_locked(t, measured_bytes=640)
    snap = m.snapshot()["histograms"]
    assert snap["serving.scheduler.reserve_drift"]["count"] == 1
    assert snap["serving.scheduler.reserve_drift"]["max"] == 240.0
    assert s.reserved_bytes == 100


def test_ticket_measured_bytes_recorded_through_runtime():
    from dask_sql_tpu.serving import ServingRuntime

    c, _ = _ctx(n=8192)
    rt = ServingRuntime(workers=1, metrics=c.metrics,
                        scheduler_budget_bytes=1 << 30)
    try:
        from dask_sql_tpu.serving.scheduler import QueryCost

        _, fut, ticket = rt.submit(
            lambda t: c.sql("SELECT k, SUM(v) AS s FROM t GROUP BY k",
                            return_futures=False),
            cost=QueryCost(bytes_lo=1024))
        fut.result(60)
        # the executing thread measured its footprint onto the ticket and
        # release reconciled it into the drift histogram
        assert ticket.measured_bytes is not None \
            and ticket.measured_bytes > 0
        hist = c.metrics.snapshot()["histograms"]
        assert hist["serving.scheduler.reserve_drift"]["count"] == 1
    finally:
        rt.shutdown(wait=True)


def test_cost_hint_carries_per_chunk_floor_for_streamed_family():
    c, _ = _ctx()
    budget = _budget(c)
    opts = {"serving.admission.max_estimated_bytes": budget}
    # first execution populates the plan cache and attaches the routing
    # verdict; the submit-time peek must find BOTH (regression: the peek
    # used to compute its key outside the config overlay scope, so any
    # option-carrying submit missed the cache it populated)
    c.sql(AGG_Q, return_futures=False, config_options=opts)
    cost = c.cost_hint(AGG_Q, opts)
    assert cost is not None
    assert cost.chunk_bytes_lo is not None
    assert 0 < cost.chunk_bytes_lo < cost.bytes_lo
    assert cost.chunk_bytes_lo <= budget
    assert cost.reserve_bytes() == cost.chunk_bytes_lo
    # an unconstrained run of the same text reserves the full floor
    c.sql(AGG_Q, return_futures=False)
    plain = c.cost_hint(AGG_Q)
    assert plain is not None and plain.chunk_bytes_lo is None
    assert plain.reserve_bytes() == plain.bytes_lo


# ------------------------------------------------------------- decision unit
def test_stream_decision_sizing_and_eligibility():
    from dask_sql_tpu.planner.parser import parse_sql
    from dask_sql_tpu.streaming import stream_decision

    c, _ = _ctx()
    plan = c._get_ral(parse_sql(AGG_Q)[0], sql_text=AGG_Q)
    est = plan._dsql_estimate
    budget = _budget(c)
    routed = stream_decision(plan, est, c, c.config, budget)
    assert routed is not None
    node, d = routed
    from dask_sql_tpu.planner import plan as p

    # the verdict names the node the sizing was computed for
    assert isinstance(node, p.Aggregate)
    assert d.kind == "aggregate"
    assert d.partitions > 1
    assert d.chunk_bytes_lo <= budget
    assert d.chunk_rows * d.partitions >= d.total_rows
    # per-chunk floor below the whole-scan floor: that is the point
    assert d.chunk_bytes_lo < est.peak_bytes.lo
    # too many partitions -> decline (the shed stays the last resort)
    with c.config.set({"serving.stream.max_partitions": 1}):
        assert stream_decision(plan, est, c, c.config, budget) is None
    # joins (two scans) are not streamable
    c.create_table("u", pd.DataFrame({"k": np.arange(5, dtype=np.int64)}))
    jq = "SELECT t.k, SUM(t.v) AS s FROM t, u WHERE t.k = u.k GROUP BY t.k"
    jplan = c._get_ral(parse_sql(jq)[0], sql_text=jq)
    jest = jplan._dsql_estimate
    assert stream_decision(jplan, jest, c, c.config, budget) is None


def test_chunk_slicing_overlap_masking():
    from dask_sql_tpu.streaming.partition import (
        partition_layout,
        slice_chunk,
    )

    c, df = _ctx(n=1000)
    table = c.schema["root"].tables["t"].table
    layout = partition_layout(1000, 384)
    assert layout == [(0, 384), (384, 768), (768, 1000)]
    covered = np.zeros(1000, dtype=int)
    for lo, _hi in layout:
        chunk = slice_chunk(table, lo, 384)
        assert chunk.padded_rows == 384  # one shape for every chunk
        valid = np.asarray(chunk.row_valid)
        # the masked window covers exactly [lo, hi) of the logical rows
        start = min(lo, 1000 - 384)
        covered[start:start + 384] += valid.astype(int)
    assert (covered == 1).all()  # every row exactly once, no overlap
