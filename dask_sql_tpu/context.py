"""The Context: the main user-facing object of the framework.

Role parity: reference `Context` (context.py:51 there) — create_table
(context.py:168), sql (context.py:482), explain (context.py:535),
register_function (context.py:324), register_aggregation (context.py:415),
register_model (context.py:626), schema DDL (context.py:580-613), run_server
(context.py:704), ipython magic (context.py:651), plus the per-query catalog
sync of _prepare_schemas (context.py:749-817) and plan driving of _get_ral
(context.py:819) / _compute_table_from_rel (context.py:874).

TPU-native differences: tables live in device HBM as columnar Tables
(`backend='tpu'`, with a CPU/pandas ingest path preserved); the planner is
in-process (planner/) instead of a PyO3 Rust module; execution lowers to
jax/XLA kernels through the physical plugin registries.
"""
from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np

from . import config as config_module
from . import observability
from .runtime import locks as runtime_locks
from .columnar.dtypes import SqlType, np_to_sql
from .columnar.table import Table
from .datacontainer import (
    ColumnContainer,
    DataContainer,
    FunctionDescription,
    SchemaContainer,
    Statistics,
)
from .input_utils import InputUtil
from .planner.binder import Binder, BindError
from .planner.catalog import Catalog, CatalogSchema, CatalogTable
from .planner.expressions import Field
from .planner.parser import ParsingException, parse_sql
from .planner import plan as plan_nodes

logger = logging.getLogger(__name__)


class TpuFrame:
    """Lazy query result: holds the optimized plan; executes on `.compute()`.

    Parity: the lazy dask DataFrame the reference returns from Context.sql
    (return_futures=True default, context.py:508).
    """

    def __init__(self, context: "Context", plan, field_names: List[str],
                 config_options: Optional[Dict[str, Any]] = None):
        self._context = context
        self._plan = plan
        self._field_names = field_names
        self._result: Optional[Table] = None
        #: per-query overrides re-applied at execution time (lazy compute
        #: happens after Context.sql's config scope has exited)
        self._config_options = dict(config_options or {})
        #: the lifecycle QueryTrace active when this frame was planned
        #: (observability/spans.py) — lazy execute/compute re-activate it so
        #: plan-time and run-time spans land on ONE trace
        self._trace: Optional[observability.QueryTrace] = None
        #: the FULL statement text (the trace's copy is display-truncated);
        #: recorded into the per-fingerprint profile so the pre-warm pass
        #: can replay it verbatim after a restart
        self._sql: Optional[str] = None
        #: cached plan fingerprint (resilience/ladder.py plan_fingerprint)
        self._fingerprint: Optional[str] = None

    @property
    def plan(self):
        return self._plan

    @property
    def columns(self) -> List[str]:
        return list(self._field_names)

    def execute(self) -> Table:
        """Run the plan to a device Table (cached).

        Serving integration: before executing, the context's result cache is
        consulted under a key of (plan fingerprint, parameter vector,
        per-referenced-table versions, config) — a repeated identical query
        returns the materialized Table without touching the executor; any
        DDL/DML on a referenced table changes the key (uid / delta-epoch
        versioning), so stale results can never be served.  On an exact
        miss the semantic reuse tiers (materialize/) get a shot: an
        incrementally-maintained aggregate state or a provably-subsuming
        cached sibling serves without executing, and a plan whose
        scan->filter stem is pinned executes against the materialized stem
        instead of the base table."""
        if self._result is None:
            from .physical.executor import Executor
            from .resilience.ladder import plan_fingerprint, wrap_boundary

            ctx = self._context
            tr = self._trace
            fp = self._fingerprint
            # family identity (families/): when the plan parameterized, its
            # literal-stripped family fingerprint keys the breaker, the
            # profiles and the warm-up — `user_id = 17` and `user_id = 404`
            # are one serving entity
            family = getattr(self._plan, "_dsql_family", None)
            family_fp = family.fingerprint if family is not None else None
            if fp is None:
                fp = self._fingerprint = family_fp or plan_fingerprint(
                    self._plan)
            sql_text = self._sql or (tr.sql if tr is not None else None)

            def _finish_on_error(exc_type, exc, tb):
                # a failing query's lifecycle ends HERE — the slowest, most
                # log-worthy queries (deadline expiries, OOM sheds, executor
                # failures) must reach the slow-query check too
                if exc is None or tr is None:
                    return False
                from .serving.runtime import current_ticket

                if getattr(exc, "retryable", False) \
                        and current_ticket() is not None:
                    # a serving worker may retry this attempt: leave the
                    # trace open — the registry's terminal done-callback
                    # finishes it on the FINAL outcome, not attempt 1
                    return False
                tr.finish(ctx.config, ctx.metrics)
                return False

            with contextlib.ExitStack() as stack:
                # entered before the finish hook is pushed, so the hook
                # (LIFO) still sees this query's config overrides — a
                # per-query slow_query_ms must gate its own failures
                stack.enter_context(ctx.config.set(self._config_options))
                stack.push(_finish_on_error)
                if tr is not None:
                    if observability.current_trace() is not tr:
                        # lazy compute outside Context.sql's scope (or on a
                        # different thread): re-install the plan-time trace
                        stack.enter_context(observability.activate(tr))
                    tr.fingerprint = fp
                # compile histograms + per-fingerprint profiles record
                # through the sink even with tracing disabled
                stack.enter_context(observability.compile_sink(
                    ctx.metrics, ctx.profiles, fp, sql_text,
                    family=family_fp))
                # in-flight query table (observability/live.py): the server
                # registered an entry at submit (found via the serving
                # ticket); a direct Context-API execution registers its own
                # here — WITH a cancellable ticket installed for the
                # executor's checkpoints, so CANCEL QUERY reaches it too
                from .serving.runtime import current_ticket, ticket_scope

                live_ticket = current_ticket()
                entry = None
                if live_ticket is not None:
                    entry = ctx.live_queries.get(live_ticket.qid)
                owned_entry = entry is None
                if owned_entry:
                    live_qid = tr.qid if tr is not None else None
                    if live_ticket is None:
                        from .serving.admission import QueryTicket
                        import uuid as _uuid

                        live_qid = live_qid or _uuid.uuid4().hex[:16]
                        live_ticket = QueryTicket(live_qid)
                        stack.enter_context(ticket_scope(live_ticket))
                    # dsql: allow-unpaired-effect — _finish_live ExitStack
                    entry = ctx.live_queries.begin(
                        live_qid or live_ticket.qid, sql=sql_text,
                        ticket=live_ticket, trace=tr,
                        priority_class=live_ticket.priority_class)
                ctx.live_queries.start(entry.qid)
                entry.family = family_fp
                entry.fingerprint = fp
                stack.enter_context(observability.live.activate(entry))

                def _finish_live(exc_type, exc, tb):
                    if exc is None:
                        if owned_entry:
                            ctx.live_queries.finish(entry.qid, "done")
                        return False
                    if not owned_entry:
                        # the server registry owns the terminal outcome
                        # (this attempt may be retried by the worker)
                        return False
                    from .serving.admission import QueryCancelledError

                    code = getattr(exc, "code", None) or exc_type.__name__
                    state = "cancelled" if isinstance(
                        exc, QueryCancelledError) else "failed"
                    ctx.live_queries.finish(entry.qid, state, code)
                    if state == "failed":
                        # cancels are user-initiated, not failures: they
                        # already recorded query.cancel at the request
                        # site and must not dump a failure postmortem
                        observability.flight.flush_on_failure(
                            entry.qid, code, ctx.config, ctx.metrics)
                    return False

                # pushed AFTER the trace hook so it runs first on unwind
                # (the live table should be terminal before the slow-query
                # check reads the trace)
                stack.push(_finish_live)
                with observability.stage("cache_lookup"):
                    key = ctx._result_cache_key(self._plan,
                                                self._config_options)
                    hit = ctx._result_cache.get(key) if key is not None \
                        else None
                if hit is not None:
                    if tr is not None:
                        tr.event("result_cache_hit")
                    ctx.profiles.record_exec(fp, sql=sql_text,
                                             cache_hit=True,
                                             family=family_fp)
                    self._result = hit
                    return self._result
                # semantic reuse tiers (materialize/): an incremental
                # aggregate state or a PROVABLY-subsuming cached sibling
                # answers the query without compiling or scanning anything
                with observability.stage("reuse"):
                    reuse = ctx.materialize.try_reuse(self._plan, family,
                                                      key)
                if reuse is not None:
                    served, tier = reuse
                    if tr is not None:
                        tr.event(f"semantic_reuse:{tier}")
                    ctx.profiles.record_exec(fp, sql=sql_text,
                                             cache_hit=True,
                                             family=family_fp)
                    if key is not None:
                        # promote to tier 0: an exact repeat of THIS query
                        # now hits the result cache directly
                        ctx._result_cache.put(
                            key, served,
                            deps=ctx._plan_table_deps(self._plan))
                    self._result = served
                    return self._result
                with observability.stage("admit"):
                    estimate = ctx._plan_estimate(self._plan)
                    routed = None
                    if estimate is not None:
                        # pre-compile OOM gate: a provable over-budget query is
                        # shed HERE — before the executor compiles anything —
                        # with a structured, non-retryable taxonomy error.
                        # Oversize-but-partitionable plans are routed to the
                        # streaming rungs instead (streaming/): shedding is the
                        # last resort, not the first.
                        from .serving.admission import check_estimated_bytes

                        routed = check_estimated_bytes(
                            estimate, ctx.config, ctx.metrics,
                            plan=self._plan, context=ctx)
                        # result-cache admission: a result whose PROVABLE bytes
                        # already exceed the per-entry cap is never cacheable;
                        # skip the insert instead of materializing-then-evicting
                        if key is not None and estimate.result_bytes.lo > \
                                ctx._result_cache.max_entry_bytes:
                            ctx.metrics.inc("query.cache.estimate_skip")
                            key = None
                    trace = bool(ctx.config.get("serving.metrics.node_traces",
                                                False))
                    executor = Executor(ctx, trace=trace)
                    if routed is not None:
                        # per-EXECUTION streaming verdict: keyed by the
                        # streamable node's identity on THIS executor, so a
                        # concurrent execution of the same cached plan under a
                        # different budget cannot null it mid-flight
                        node, decision = routed
                        executor.stream_decisions[id(node)] = decision
                    exec_plan = self._plan
                    if routed is None:
                        # sub-plan materialization (materialize/manager.py):
                        # when this plan's scan->filter stem is pinned, execute
                        # a rewritten copy that scans the materialized stem —
                        # the base table is never touched and nothing compiles.
                        # Streamed executions keep the original plan: their
                        # routing decision is keyed on ITS node identity.
                        rewritten = ctx.materialize.try_stem_rewrite(self._plan)
                        if rewritten is not None:
                            exec_plan, stem_overrides = rewritten
                            executor.table_overrides.update(stem_overrides)
                            if tr is not None:
                                tr.event("materialized_stem_scan")
                t0 = time.perf_counter()
                # executor boundary: every failure leaves here as a taxonomy
                # QueryError (code/retryable/degradable), never a raw
                # device traceback (resilience/errors.py)
                with observability.stage("execute"):
                    self._result = wrap_boundary(
                        lambda: executor.execute_root(exec_plan))
                exec_ms = (time.perf_counter() - t0) * 1000.0
                with observability.stage("account"):
                    ctx.metrics.observe("query.execute_ms", exec_ms)
                    ctx.metrics.inc("query.executed")
                    if trace:
                        executor.tracer.publish(ctx.metrics)
                        if tr is not None:
                            tr.attach_node_tree(executor.tracer.root)
                    from .serving.cache import table_nbytes

                    result_bytes = table_nbytes(self._result)
                    ctx.profiles.record_exec(
                        fp, sql=sql_text, exec_ms=exec_ms,
                        result_bytes=result_bytes,
                        family=family_fp,
                        rows=self._result.num_rows)
                    from .serving.runtime import current_ticket

                    ticket = current_ticket()
                    if ticket is not None:
                        # measured footprint for the packing scheduler's
                        # reservation reconciliation (release surfaces the
                        # drift as serving.scheduler.reserve_drift): result
                        # bytes + the MEASURED resident bytes of the scanned
                        # tables — table_nbytes accounting on both sides, so
                        # reserve-vs-measured comparisons cannot drift
                        with observability.detail(
                                "scan_bytes", parent="account") as scan_attrs:
                            scan_bytes = ctx._measured_scan_bytes(
                                self._plan,
                                routed[1] if routed is not None else None)
                            scan_attrs["bytes"] = scan_bytes
                        ticket.measured_bytes = result_bytes + scan_bytes
                        # the ledger's measured-vs-reserved reconciliation
                        # reads the same number off the live entry
                        entry.measured_bytes = ticket.measured_bytes
                    est = getattr(self._plan, "_dsql_estimate", None)
                    if est is not None:
                        # the "estimated" side of SHOW PROFILES' observed-vs-
                        # estimated pairing, recorded HERE because the entry
                        # now exists (record_estimate never creates entries)
                        ctx.profiles.record_estimate(fp, est.rows.hi,
                                                     family=family_fp)
                    deps = ctx._plan_table_deps(self._plan)
                    if ticket is not None:
                        scan_attrs["tables"] = len(deps)
                    if key is not None:
                        # deps-tagged: append_rows/DDL invalidate exactly the
                        # entries reading the mutated tables (epoch-scoped)
                        ctx._result_cache.put(key, self._result, deps=deps)
                    # semantic reuse observation (materialize/): stem hit
                    # counting (pin at threshold), subsumption candidate
                    # registration, incremental capture registration
                    with observability.detail("observe", parent="account"):
                        ctx.materialize.observe(self._plan, family, key,
                                                deps, self._result)
        return self._result

    def compute(self):
        """Materialize to a pandas DataFrame with the SQL output names."""
        table = self.execute()
        tr = self._trace
        with contextlib.ExitStack() as stack:
            if tr is not None:
                # add-once: a repeated compute() must not mutate a finished
                # (possibly already slow-logged) trace with duplicate stages
                first = stack.enter_context(tr.span_once(
                    "d2h", rows=table.num_rows, cols=len(self._field_names)))
                # the pull's `fetch` detail span needs the trace active (a
                # lazy compute runs outside Context.sql's scope), and a
                # repeated compute() needs it inactive
                stack.enter_context(
                    observability.activate(tr if first else None))
            t0 = time.perf_counter()
            df = table.to_pandas()
            t1 = time.perf_counter()
        # every call transfers again, so every call observes — but the
        # metric must not go dark when tracing is off
        self._context.metrics.observe("query.d2h_ms", (t1 - t0) * 1e3)
        if tr is not None:
            # the lifecycle ends here for the Context API (the server path
            # appends its serialize span post-finish): run the slow-query
            # check exactly once
            tr.finish(self._context.config, self._context.metrics)
        df.columns = self._disambiguated_names()
        return df

    def _disambiguated_names(self) -> List[str]:
        # parity: reference renames duplicate output fields with FQN hints
        # (context.py:890-906); we suffix duplicates positionally
        seen: Dict[str, int] = {}
        out = []
        for n in self._field_names:
            if n in seen:
                seen[n] += 1
                out.append(f"{n}{seen[n]}")
            else:
                seen[n] = 0
                out.append(n)
        return out

    def persist(self) -> "TpuFrame":
        self.execute()
        return self

    def head(self, n: int = 5):
        return self.compute().head(n)

    def __len__(self) -> int:
        return self.execute().num_rows

    def explain_str(self) -> str:
        return self._plan.explain()


class Context:
    DEFAULT_SCHEMA_NAME = "root"

    def __init__(self, logging_level=logging.INFO):
        # join the multi-host runtime if DSQL_COORDINATOR is set (parity:
        # the reference front-ends connecting a Client to the scheduler
        # address, reference server/app.py:249-252); no-op single-host
        from .parallel.bootstrap import initialize_from_env

        initialize_from_env()
        self.schema_name = self.DEFAULT_SCHEMA_NAME
        self.schema: Dict[str, SchemaContainer] = {
            self.DEFAULT_SCHEMA_NAME: SchemaContainer(self.DEFAULT_SCHEMA_NAME)
        }
        self._views: Dict[str, Dict[str, Any]] = {self.DEFAULT_SCHEMA_NAME: {}}
        self.config = config_module.config
        self.server = None
        #: bound+optimized plans for repeated SQL text (keyed on the catalog
        #: signature, so any table/view/function/config change re-plans)
        self._plan_cache: "OrderedDict[Tuple, List[Any]]" = OrderedDict()
        #: guards _plan_cache and _catalog_buf_cache: one Context serves
        #: every worker thread of the Presto server, and an unguarded
        #: OrderedDict move_to_end/popitem pair racing across threads
        #: corrupts the LRU order or KeyErrors (self-lint rule DSQL201).
        #: rank 55: nests inside replica write locks; planning/compiles
        #: happen OUTSIDE it (singleflight in physical/programs.py)
        self._plan_lock = runtime_locks.named_lock("context.plan_cache")
        #: bumped on every view/function (re)definition or drop
        self._catalog_serial = 0
        from .serving.cache import ResultCache
        from .serving.metrics import MetricsRegistry

        #: serving metrics registry: query/cache/executor counters and
        #: latency histograms (SHOW METRICS, server /v1/metrics)
        self.metrics = MetricsRegistry()
        # every compile's histograms (observability/xla.py), empty until the
        # first one: a process whose executables all came from the cache
        # reads 0, not nothing
        observability.xla.declare(self.metrics)
        # arm the process-wide lock sanitizer when this context's config
        # asks for it (arming is one-way: a later default-config Context
        # must not disarm a suite that opted in), and point its
        # violation counters at this registry
        if self.config.get("analysis.lock_sanitizer", False):
            runtime_locks.set_enabled(True)
        runtime_locks.attach_metrics(self.metrics)
        #: materialized-result cache (serving/cache.py); keyed via
        #: _result_cache_key so DDL/DML versions entries out
        self._result_cache = ResultCache(
            max_bytes=int(self.config.get("serving.cache.max_bytes",
                                          256 << 20)),
            max_entry_bytes=int(self.config.get(
                "serving.cache.max_entry_bytes", 64 << 20)),
            ttl_s=self.config.get("serving.cache.ttl_s", 300.0),
            metrics=self.metrics)
        #: the ServingRuntime when a server front-end attached one (so
        #: SHOW METRICS can surface admission/queue state)
        self.serving = None
        #: per-fingerprint rolling profiles (compile/exec/bytes) behind
        #: SHOW PROFILES; persisted by checkpoint.save_state
        self.profiles = observability.ProfileStore(
            window=int(self.config.get("observability.profiles.window", 64)),
            keep=int(self.config.get("observability.profiles.keep", 512)))
        #: finished lifecycle traces, qid -> QueryTrace (/v1/trace/{qid})
        self.traces = observability.TraceStore(
            int(self.config.get("observability.trace.keep", 256)))
        #: the in-flight query table (observability/live.py) behind
        #: SHOW QUERIES / GET /v1/queries and the target of CANCEL QUERY
        self.live_queries = observability.QueryRegistry(
            keep_finished=int(self.config.get("observability.live.keep",
                                              64)))
        #: live HBM accounting (observability/ledger.py): scheduler
        #: reservations + measured in-flight footprints + result-cache +
        #: at-rest table bytes reconciled against the device budget
        self.ledger = observability.DeviceLedger(self)
        from .resilience.pressure import PressureController

        #: coordinated HBM pressure response (resilience/pressure.py):
        #: bands the ledger's headroom against the device budget, suspends
        #: speculative work at YELLOW, reclaims cross-tier at RED, forces
        #: streamed admission / sheds at CRITICAL
        self.pressure = PressureController(self)
        #: per-(schema, table) delta epoch: bumped by append_rows (and any
        #: create/drop of the name) WITHOUT replacing the container — the
        #: result-cache key and the semantic reuse tiers (materialize/)
        #: version on it, so an append invalidates exactly its dependents
        self._table_epochs: Dict[Tuple[str, str], int] = {}
        from .materialize import MaterializationManager

        #: semantic result reuse (materialize/): pinned sub-plan stems,
        #: subsumption answering over cached results, incremental
        #: maintenance of aggregate states across append_rows
        self.materialize = MaterializationManager(self)
        # the process flight recorder is always on; the capacity key only
        # resizes its ring
        observability.flight.RECORDER.resize(
            int(self.config.get("observability.flight.capacity", 4096)))
        #: the most recently started lifecycle trace (bench --profile and
        #: notebook introspection; per-query lookups go through `traces`)
        self.last_trace: Optional[observability.QueryTrace] = None
        from .resilience.retry import CircuitBreaker

        #: per-(plan fingerprint, ladder rung) circuit breaker: a query
        #: shape that repeatedly kills a compiled rung skips straight to
        #: its known-good rung (resilience/ladder.py consults this)
        self.breaker = CircuitBreaker.from_config(self.config)
        #: the active warm-up pass (serving/warmup.py) after load_state /
        #: server boot; /v1/health reports its warming->ready transition
        self.warmup = None
        #: lazily-created background recompiler (serving/background.py);
        #: guarded by _plan_lock — use background_compiler() to read
        self._bg_compiler = None
        #: plan family ((rung, family) of a ProgramCache key) -> table bucket
        #: (uid, rows, padded_rows) last compiled by THIS context: a
        #: program-cache miss whose family maps to a DIFFERENT bucket means
        #: the table grew/was replaced — the background-recompile trigger
        #: (physical/programs.py).  Guarded by _plan_lock.
        self._compiled_families: dict = {}
        #: (family fingerprint, catalog/config key) -> PlanEstimate: the
        #: estimator's intervals are literal-value-agnostic, so one
        #: estimate serves every member of a family (families/,
        #: docs/analysis.md).  Guarded by _plan_lock; cleared on DDL.
        self._family_estimates: dict = {}
        from .serving import compile_cache

        # persistent executable cache: when serving.compile_cache.path is
        # set, XLA executables survive the process (restart = deserialize,
        # not recompile; docs/serving.md "Cold starts")
        compile_cache.maybe_enable(self.config, self.metrics)
        logging.basicConfig(level=logging_level)

    _PLAN_CACHE_CAP = 128

    def _plan_cache_key(self, sql: str, config_options) -> Optional[Tuple]:
        """Cache key for a SQL text against the current catalog state, or
        None when the statement must be re-planned every time (plan-time
        data reads: DPP runs the dim side during optimization, so its
        inputs are pinned by the table uids in the signature)."""
        try:
            parts: List[Any] = [sql, self.schema_name]
            parts.extend(self._catalog_signature())
            # id()-free: view/function redefinitions bump _catalog_serial
            # (id reuse after a drop would silently replay a stale plan)
            parts.append(self._catalog_serial)
            parts.append(self.config.effective_items())
            if config_options:
                parts.append(tuple(sorted(config_options.items())))
            key = tuple(parts)
            hash(key)  # unhashable config values -> skip caching
            return key
        except TypeError:
            return None

    def _catalog_signature(self) -> List[Any]:
        """Versioned identity of the catalog: table uids, statistics row
        counts, view and function names per schema.  Shared by the plan
        cache and the result cache — any DDL/DML that replaces a table
        (fresh uid), redefines a view/function (`_catalog_serial` bump) or
        refreshes statistics changes the signature."""
        parts: List[Any] = []
        for schema_name in sorted(self.schema):
            container = self.schema[schema_name]
            parts.append(schema_name)
            parts.append(tuple(sorted(
                (name, dc.uid) for name, dc in container.tables.items())))
            stats = container.statistics
            parts.append(tuple(sorted(
                (name, s.row_count) for name, s in stats.items()
                if s is not None)))
            parts.append(tuple(sorted(self._views.get(schema_name, {}))))
            parts.append(tuple(sorted(container.function_lists)))
        return parts

    def table_epoch(self, schema_name: str, table_name: str) -> int:
        """The (schema, table) delta epoch — 0 until the first append or
        create/drop of the name.  Rides the result-cache key's per-table
        parts and the materialize/ validity checks."""
        return self._table_epochs.get((schema_name, table_name), 0)

    def _bump_table_epoch(self, schema_name: str, table_name: str) -> int:
        tkey = (schema_name, table_name)
        epoch = self._table_epochs.get(tkey, 0) + 1
        self._table_epochs[tkey] = epoch
        return epoch

    def _on_catalog_change(self, tables=None) -> None:
        """Called by every DDL-shaped mutation.  The result-cache keys
        embed per-referenced-table versions, so stale entries could never
        be *hit* — but unreachable entries would stay pinned in HBM until
        byte-pressure from new inserts; eager invalidation frees those
        buffers now.  With ``tables`` (a set of (schema, table) names) the
        invalidation is TARGETED: only cached results and materializations
        depending on those tables drop — results over other tables
        survive.  Without it (view/function/schema/model DDL, whose blast
        radius is not table-attributable) everything drops, as before."""
        if tables:
            n = self._result_cache.invalidate_tables(tables)
            n += self.materialize.invalidate_tables(tables)
            if n:
                self.metrics.inc("query.cache.invalidated", n)
        else:
            self._result_cache.invalidate_all()
            self.materialize.invalidate_all()
        with self._plan_lock:
            self._family_estimates.clear()

    def _result_cache_key(self, plan, config_options) -> Optional[Tuple]:
        """Result-cache key: (normalized plan fingerprint, parameter
        vector, per-referenced-table versions (uid, rows, delta epoch),
        config options) — or None when this result must not be cached
        (caching disabled, side-effecting/model statements, unhashable
        config).  Versioning only the REFERENCED tables (not the whole
        catalog signature) is what lets an append to one table leave every
        other table's cached results valid."""
        if not self.config.get("serving.cache.enabled", True):
            return None
        if isinstance(plan, plan_nodes.CustomNode):
            # DDL / ML statements: side effects or model-object state that
            # the catalog signature does not fully version
            return None
        if isinstance(plan, plan_nodes.Explain) and plan.analyze:
            # EXPLAIN ANALYZE must re-execute and re-profile every time —
            # serving a cached trace would report a run that never happened
            return None
        from .datacontainer import LazyParquetContainer

        table_parts: List[Tuple] = []
        stack = [plan]
        while stack:
            node = stack.pop()
            if isinstance(node, plan_nodes.Sample) and node.seed is None:
                # unseeded TABLESAMPLE draws fresh randomness per execution;
                # caching it would freeze the first draw for the TTL window
                return None
            if isinstance(node, plan_nodes.TableScan):
                dc = self.schema.get(node.schema_name, SchemaContainer(
                    node.schema_name)).tables.get(node.table_name)
                if isinstance(dc, LazyParquetContainer):
                    # file-backed scan: the files can change on disk without
                    # any catalog version bump, so the result is uncacheable
                    return None
                if dc is None:
                    view = self._views.get(node.schema_name, {}).get(
                        node.table_name)
                    if view is not None:
                        # the scan resolves through a view at execution
                        # time: the UNDERLYING tables must version this key
                        # (an append to one invalidates results over the
                        # view), so the view plan joins the walk
                        stack.append(view)
                # per-referenced-table version: identity (uid), size and
                # delta epoch — an append bumps the epoch, a replace the
                # uid, so exactly the dependent keys go stale while results
                # over OTHER tables keep their keys (and their entries)
                table_parts.append(
                    (node.schema_name, node.table_name,
                     None if dc is None else dc.uid,
                     None if dc is None else int(dc.table.num_rows),
                     self.table_epoch(node.schema_name, node.table_name)))
            # volatile calls (RAND / CURRENT_TIMESTAMP) and UDFs (arbitrary
            # host code) must re-evaluate per query; nested subquery plans
            # join the walk so nothing hides inside an expression
            nested, uncacheable = _scan_node_exprs(node)
            if uncacheable:
                return None
            stack.extend(nested)
            stack.extend(node.inputs())
        try:
            # repr() (not explain()) as the plan fingerprint: dataclass reprs
            # include every semantic field recursively, so two plans that
            # differ only in a detail the pretty-printer omits (e.g. sort
            # null ordering) can never collide.  With plan families enabled
            # the key splits into (literal-stripped family repr, parameter
            # values) — bijective with repr(plan), since substituting the
            # values back into the placeholder slots reconstructs it — so
            # family metrics and cache accounting see one family, while two
            # queries with different literals still get distinct entries.
            # INVARIANT: the parameter vector sits at index 2 in BOTH
            # shapes — subsumption answering (materialize/manager.py)
            # admits a candidate by comparing every part EXCEPT index 2.
            family = getattr(plan, "_dsql_family", None)
            if family is not None:
                parts: List[Any] = ["result", family.family_repr,
                                    family.key_values, self.schema_name]
            else:
                parts = ["result", repr(plan), (), self.schema_name]
            parts.extend(sorted(set(table_parts)))
            parts.append(self.config.effective_items())
            if config_options:
                parts.append(tuple(sorted(config_options.items())))
            key = tuple(parts)
            hash(key)
            return key
        except Exception:  # dsql: allow-broad-except — unhashable config /
            # unprintable plan just means this result is uncacheable
            return None

    def _plan_table_deps(self, plan) -> frozenset:
        """Every (schema, table) name a plan reads — nested subquery plans
        and view expansions included.  Tags result-cache entries and
        semantic-reuse state for targeted (epoch-scoped) invalidation."""
        deps = set()
        stack = [plan]
        while stack:
            node = stack.pop()
            if isinstance(node, plan_nodes.TableScan):
                deps.add((node.schema_name, node.table_name))
                if node.table_name not in self.schema.get(
                        node.schema_name,
                        SchemaContainer(node.schema_name)).tables:
                    view = self._views.get(node.schema_name, {}).get(
                        node.table_name)
                    if view is not None:
                        stack.append(view)
            nested, _ = _scan_node_exprs(node)
            stack.extend(nested)
            stack.extend(node.inputs())
        return frozenset(deps)

    # ------------------------------------------------------------ tables
    def create_table(
        self,
        table_name: str,
        input_table: Any,
        format: Optional[str] = None,
        persist: bool = False,
        schema_name: Optional[str] = None,
        statistics: Optional[Statistics] = None,
        backend: Optional[str] = None,
        gpu: bool = False,
        distributed: Optional[bool] = None,
        **kwargs,
    ) -> None:
        """Register a table (parity: context.py:168).  `backend='tpu'`
        (default) lands columns in device HBM; the reference's `gpu=` flag is
        accepted and treated as a backend hint.  `distributed=True` shards the
        column buffers row-wise over the default device mesh so kernels run
        SPMD with XLA-placed collectives; an EXPLICIT `distributed=False`
        also opts this table out of the `parallel.auto_shard` policy (None,
        the default, leaves the policy in charge)."""
        schema_name = schema_name or self.schema_name
        if schema_name not in self.schema:
            raise KeyError(f"Schema {schema_name} not found")
        with contextlib.ExitStack() as load:
            load.enter_context(
                observability.load_trace(self, schema_name, table_name))
            with observability.load_span("convert"):
                dc = InputUtil.to_dc(input_table, table_name, format=format,
                                     persist=persist, **kwargs)
            # everything below, to the end of the registration
            load.enter_context(observability.load_span("register"))
            # normalize: the CREATE TABLE ... WITH (distributed=...) passthrough
            # delivers SQL literals, and a string 'false' must not shard
            from .spmd.storage import maybe_auto_shard, truthy_option

            if truthy_option(distributed):
                from .datacontainer import LazyParquetContainer
                from .parallel.distribute import shard_table

                if isinstance(dc, LazyParquetContainer):
                    from .datacontainer import DataContainer

                    dc = DataContainer(shard_table(dc.table))
                else:
                    dc.table = shard_table(dc.table)
            elif distributed is None:
                # parallel.auto_shard policy (spmd/storage.py): eligible
                # registrations row-shard over the default mesh without
                # per-table opt-in, so the SPMD rungs serve plain create_table.
                # An EXPLICIT distributed=False (or WITH (distributed='false'))
                # is a per-table opt-out the policy must respect.
                dc = maybe_auto_shard(dc, self.config, self.metrics)
            self.schema[schema_name].tables[table_name] = dc
            from .datacontainer import LazyParquetContainer

            if statistics is None:
                if isinstance(dc, LazyParquetContainer):
                    # footer row counts, no data scan (parity: context.py:281-289)
                    if dc.statistics and dc.statistics.get("num-rows"):
                        statistics = Statistics(float(dc.statistics["num-rows"]))
                elif dc.table.num_rows:
                    statistics = Statistics(float(dc.table.num_rows))
            if statistics is not None:
                self.schema[schema_name].statistics[table_name] = statistics
            filepath = getattr(dc, "filepath", None)
            if filepath:
                self.schema[schema_name].filepaths[table_name] = filepath
            # LazyParquetContainer.table is a LOADING property — peeking it here
            # would defeat lazy registration; lazy scans are PLAIN anyway
            table = None if isinstance(dc, LazyParquetContainer) \
                else getattr(dc, "table", None)
            if table is not None:
                self.metrics.inc("load.rows", int(table.num_rows))
            if table is not None and table.has_encoded_columns():
                # compressed-encoding accounting (columnar/encodings.py):
                # encoded vs would-be-dense resident bytes of this registration
                from .columnar.encodings import Encoding, scan_bytes

                n_enc = sum(1 for c in table.columns.values()
                            if c.encoding is not Encoding.PLAIN)
                enc_b, dec_b = scan_bytes(table)
                self.metrics.inc("columnar.encoding.encoded_columns", n_enc)
                self.metrics.observe("columnar.encoding.encoded_bytes", enc_b)
                self.metrics.observe("columnar.encoding.decoded_bytes", dec_b)
            self._bump_table_epoch(schema_name, table_name)
            if self._views.setdefault(schema_name, {}).pop(table_name, None) is not None:
                # replacing a VIEW with a table: results over OTHER views may
                # reference this name through their plans — full invalidation
                self._catalog_serial += 1
                self._on_catalog_change()
            else:
                self._on_catalog_change(tables={(schema_name, table_name)})

    def drop_table(self, table_name: str, schema_name: Optional[str] = None) -> None:
        schema_name = schema_name or self.schema_name
        self.schema[schema_name].tables.pop(table_name, None)
        self.schema[schema_name].statistics.pop(table_name, None)
        self._bump_table_epoch(schema_name, table_name)
        if self._views.get(schema_name, {}).pop(table_name, None) is not None:
            self._catalog_serial += 1
            self._on_catalog_change()
        else:
            self._on_catalog_change(tables={(schema_name, table_name)})

    def alter_table(self, old_name: str, new_name: str,
                    schema_name: Optional[str] = None) -> None:
        schema_name = schema_name or self.schema_name
        tables = self.schema[schema_name].tables
        if old_name in tables:
            tables[new_name] = tables.pop(old_name)
        stats = self.schema[schema_name].statistics
        if old_name in stats:
            stats[new_name] = stats.pop(old_name)
        self._bump_table_epoch(schema_name, old_name)
        self._bump_table_epoch(schema_name, new_name)
        self._on_catalog_change(tables={(schema_name, old_name),
                                        (schema_name, new_name)})

    def append_rows(self, table_name: str, rows: Any,
                    schema_name: Optional[str] = None) -> int:
        """Append rows to a registered table IN PLACE — the engine behind
        ``INSERT INTO``.  Unlike create_table (replace), the container and
        its uid survive: only the per-table *delta epoch* bumps, so the
        result cache drops exactly the entries depending on this table
        (epoch-scoped keys) while results over other tables stay servable,
        and the semantic reuse tiers (materialize/) fold ONLY the appended
        chunk — pinned stems re-execute over the delta slice, stored
        streamed-combine states absorb it as one more time-axis partition —
        without rescanning history.

        ``rows`` is anything `create_table` accepts (DataFrame, dict of
        arrays, list of tuples...) with a column subset compatible with the
        existing table.  Lazy parquet registrations and row-sharded tables
        cannot concat in place and degrade to a replace (fresh uid,
        wholesale invalidation for this table).  Returns the number of
        appended rows."""
        schema_name = schema_name or self.schema_name
        container = self.schema.get(schema_name)
        dc = container.tables.get(table_name) if container else None
        if dc is None:
            raise KeyError(f"Table {schema_name}.{table_name} not found")
        delta_dc = InputUtil.to_dc(rows, table_name)
        delta = delta_dc.table
        appended = int(delta.num_rows)
        self.metrics.inc("serving.reuse.append_rows", appended)
        from .datacontainer import DataContainer, LazyParquetContainer

        tkey = (schema_name, table_name)
        if isinstance(dc, LazyParquetContainer) \
                or dc.table.row_valid is not None:
            # no in-place concat story for file-backed or padded/sharded
            # storage: degrade to a replace — fresh uid, so every reuse
            # tier fails closed on its identity checks
            base = dc.table
            merged = Table.concat(
                [base.slice(0, base.num_rows), delta])
            container.tables[table_name] = DataContainer(merged)
            container.statistics[table_name] = Statistics(
                float(merged.num_rows))
            self._bump_table_epoch(schema_name, table_name)
            self._on_catalog_change(tables={tkey})
            return appended
        old_rows = int(dc.table.num_rows)
        # same container, same uid: concat decodes + promotes as needed,
        # and raises on an incompatible column set before any state changes
        dc.table = Table.concat([dc.table, delta])
        container.statistics[table_name] = Statistics(
            float(dc.table.num_rows))
        epoch = self._bump_table_epoch(schema_name, table_name)
        # targeted: exactly the cached results reading this table drop
        # (their keys embed the old epoch and can never be hit again);
        # reuse state REFRESHES instead of dropping — that is the point
        n = self._result_cache.invalidate_tables({tkey})
        if n:
            self.metrics.inc("query.cache.invalidated", n)
        with self._plan_lock:
            self._family_estimates.clear()
        self.materialize.on_append(schema_name, table_name, dc, old_rows,
                                   epoch)
        return appended

    # ------------------------------------------------------------ schemas
    def create_schema(self, schema_name: str) -> None:
        self.schema[schema_name] = SchemaContainer(schema_name)
        self._views.setdefault(schema_name, {})
        self._on_catalog_change()

    def drop_schema(self, schema_name: str) -> None:
        if schema_name == self.schema_name:
            self.schema_name = self.DEFAULT_SCHEMA_NAME
        self.schema.pop(schema_name, None)
        if self._views.pop(schema_name, None):
            self._catalog_serial += 1
        self._on_catalog_change()

    def alter_schema(self, old_name: str, new_name: str) -> None:
        if old_name in self.schema:
            container = self.schema.pop(old_name)
            container.name = new_name
            self.schema[new_name] = container
            self._views[new_name] = self._views.pop(old_name, {})
            if self.schema_name == old_name:
                self.schema_name = new_name
            self._on_catalog_change()

    # ------------------------------------------------------------ functions
    def register_function(
        self,
        f: Callable,
        name: str,
        parameters: List[Tuple[str, Any]],
        return_type: Any,
        replace: bool = False,
        schema_name: Optional[str] = None,
        row_udf: bool = False,
    ) -> None:
        """Scalar UDF registration (parity: context.py:324).  Non-row UDFs
        receive jax arrays and should be jax-traceable for fusion."""
        self._register_callable(f, name, parameters, return_type, False,
                                replace, schema_name, row_udf)

    def register_aggregation(
        self,
        f: Callable,
        name: str,
        parameters: List[Tuple[str, Any]],
        return_type: Any,
        replace: bool = False,
        schema_name: Optional[str] = None,
    ) -> None:
        """Custom aggregation (parity: context.py:415): `f` is applied to a
        pandas GroupBy on the host fallback path."""
        self._register_callable(f, name, parameters, return_type, True,
                                replace, schema_name, False)

    def _register_callable(self, f, name, parameters, return_type, aggregation,
                           replace, schema_name, row_udf):
        schema_name = schema_name or self.schema_name
        schema = self.schema[schema_name]
        params = [(pname, _to_sql_type(ptype)) for pname, ptype in (parameters or [])]
        fd = FunctionDescription(name, f, params, _to_sql_type(return_type),
                                 aggregation, row_udf)
        lower = name.lower()
        existing = schema.function_lists.get(lower)
        if existing and not replace:
            # overload check (parity: context.py overload logic)
            for other in existing:
                if [t for _, t in other.parameters] == [t for _, t in params]:
                    raise ValueError(
                        f"Function {name} with signature already registered; "
                        f"use replace=True")
            existing.append(fd)
        else:
            schema.function_lists[lower] = [fd]
        schema.functions[lower] = fd
        self._catalog_serial += 1
        self._on_catalog_change()

    # ------------------------------------------------------------ checkpoint
    def save_state(self, location: str) -> dict:
        """Snapshot every schema (tables->parquet, models->pickle) so a new
        process can `load_state` after a crash — the TPU-native recovery
        story (SURVEY §5; the reference leans on dask worker recomputation,
        which multi-controller JAX does not have)."""
        from . import checkpoint

        return checkpoint.save_state(self, location)

    def load_state(self, location: str) -> dict:
        """Re-hydrate a `save_state` snapshot into this Context, then kick
        the profile-driven warm-up so the restored process compiles its hot
        query families before (or while) traffic arrives."""
        from . import checkpoint

        manifest = checkpoint.load_state(self, location)
        self.maybe_start_warmup()
        return manifest

    def maybe_start_warmup(self):
        """Start a background warm-up over the hottest profiled
        fingerprints (serving/warmup.py), when configured and there is
        anything to warm.  Idempotent while a pass is running; a finished
        pass is replaced (a second load_state re-warms).  Returns the
        `WarmupManager` or None."""
        if not self.config.get("serving.warmup.enabled", True):
            return None
        top_n = int(self.config.get("serving.warmup.top_n", 8) or 0)
        if top_n <= 0 or not len(self.profiles):
            return None
        if self.warmup is not None and not self.warmup.ready:
            return self.warmup  # a pass is already in flight
        from .serving.warmup import WarmupManager

        manager = WarmupManager(
            self, top_n=top_n,
            throttle_s=float(self.config.get(
                "serving.warmup.throttle_s", 0.0) or 0.0))
        self.warmup = manager
        self._register_background(manager)
        return manager.start()

    def background_compiler(self):
        """The bounded background recompiler (serving/background.py), or
        None when ``serving.bg_compile.enabled`` is off.  Created lazily so
        non-serving Contexts never start the thread."""
        if not self.config.get("serving.bg_compile.enabled", False):
            return None
        with self._plan_lock:
            bg = self._bg_compiler
            if bg is None:
                from .serving.background import BackgroundCompiler

                bg = self._bg_compiler = BackgroundCompiler.from_config(
                    self.config, metrics=self.metrics,
                    suspended=self.pressure.suspend_speculative)
            else:
                return bg
        self._register_background(bg)
        return bg

    def _register_background(self, worker) -> None:
        """Hand a cancellable/joinable background worker to the serving
        runtime (if one is attached) so shutdown(wait=True) drains it."""
        runtime = self.serving
        if runtime is not None:
            runtime.register_background(worker)

    # ------------------------------------------------------------ models
    def register_model(self, model_name: str, model: Any,
                       training_columns: List[str],
                       schema_name: Optional[str] = None) -> None:
        """Parity: context.py:626."""
        schema_name = schema_name or self.schema_name
        self.schema[schema_name].models[model_name] = (model, list(training_columns))
        self.metrics.inc("inference.model.registered")
        # the lowered-program cache is NOT invalidated here: it detects the
        # replaced object lazily (id mismatch -> re-lower), and the stale
        # entry is what lets inference/registry.py recognize a same-shape
        # retrain as a zero-recompile model.swap
        self._catalog_serial += 1
        self._on_catalog_change()

    # ------------------------------------------------------------ queries
    def sql(
        self,
        sql: Union[str, Any],
        return_futures: bool = True,
        dataframes: Optional[Dict[str, Any]] = None,
        config_options: Optional[Dict[str, Any]] = None,
    ):
        """Parse, plan, optimize and (lazily) execute a SQL string
        (parity: context.py:482)."""
        if dataframes is not None:
            for df_name, df in dataframes.items():
                self.create_table(df_name, df)
        with contextlib.ExitStack() as scope:
            scope.enter_context(self.config.set(config_options or {}))
            if not isinstance(sql, str):
                raise ValueError("sql must be a string (plans are internal here)")
            # lifecycle trace (observability/): reuse the active trace when
            # an outer scope (the Presto server's worker) already opened one
            # for this query, else open (and own) a fresh trace here
            tr = None
            owned = False
            if self._trace_enabled():
                tr = observability.current_trace()
                if tr is None:
                    tr = observability.QueryTrace(
                        sql=sql, metrics=self.metrics, profiles=self.profiles)
                    self.traces.put(tr.qid, tr)
                    owned = True
                    scope.enter_context(observability.activate(tr))
                self.last_trace = tr

            def _finish_owned_on_error(exc_type, exc, tb):
                # parse/bind/verify failures end the lifecycle of a trace
                # this call opened: close it so the slow-query check runs
                # and /v1/trace never serves a dangling open trace.  (A
                # trace the SERVER opened is not owned here — its registry
                # finishes it at the terminal outcome, after any retries.)
                if exc is not None and owned and tr is not None:
                    tr.finish(self.config, self.metrics)
                return False

            scope.push(_finish_owned_on_error)
            with observability.stage("plan_lookup"):
                key = self._plan_cache_key(sql, config_options)
                plans = None
                if key is not None:
                    with self._plan_lock:
                        plans = self._plan_cache.get(key)
                        if plans is not None:
                            self._plan_cache.move_to_end(key)
            result = None
            if plans is not None:
                self.metrics.inc("query.plan_cache.hit")
                if tr is not None:
                    tr.event("plan_cache_hit")
                for plan in plans:
                    result = self._run_plan(plan, config_options)
            else:
                self.metrics.inc("query.plan_cache.miss")
                with observability.stage("parse"):
                    statements = parse_sql(sql)
                plans = []
                # plan each statement right before running it: a later
                # statement may read what an earlier one created
                for stmt in statements:
                    plan = self._get_ral(
                        stmt, sql_text=sql if len(statements) == 1 else None)
                    plans.append(plan)
                    result = self._run_plan(plan, config_options)
                # only single-statement texts are cacheable — a script's later
                # plans were bound against mid-script catalog state
                if key is not None and len(plans) == 1:
                    with self._plan_lock:
                        self._plan_cache[key] = plans
                        while len(self._plan_cache) > self._PLAN_CACHE_CAP:
                            self._plan_cache.popitem(last=False)
            if result is None:
                # statement(s) with no result frame (DDL): the lifecycle
                # ends here for a trace this call opened
                if owned and tr is not None:
                    tr.finish(self.config, self.metrics)
                return None
            result._trace = tr
            result._sql = sql
            if return_futures:
                return result
            return result.compute()

    def _run_plan(self, plan, config_options=None) -> Optional[TpuFrame]:
        if isinstance(plan, plan_nodes.CustomNode) and not isinstance(
                plan, (plan_nodes.PredictModelNode,)):
            # DDL / side-effecting statements run eagerly (parity: reference
            # converts them immediately, create_memory_table.py etc.)
            from .physical.executor import Executor

            with observability.stage("execute"):
                table = Executor(self).execute(plan)
            if not table.columns:
                return None
            frame = TpuFrame(self, plan, list(table.column_names), config_options)
            frame._result = table
            return frame
        return TpuFrame(self, plan, [f.name for f in plan.schema], config_options)

    def explain(self, sql: str, dataframes: Optional[Dict[str, Any]] = None,
                config_options: Optional[Dict[str, Any]] = None) -> str:
        """Return the optimized logical plan as a string (parity context.py:535)."""
        if dataframes is not None:
            for df_name, df in dataframes.items():
                self.create_table(df_name, df)
        with self.config.set(config_options or {}):
            statements = parse_sql(sql)
            plan = self._get_ral(
                statements[0], sql_text=sql if len(statements) == 1 else None)
        if isinstance(plan, plan_nodes.Explain):
            plan = plan.input
        return plan.explain()

    def visualize(self, sql: str, filename: str = "mydask.png") -> None:
        """Render the optimized plan tree to an image (parity: context.py:573
        there renders the dask task graph to png).  Falls back to a text dump
        next to the requested filename when no renderer is available."""
        statements = parse_sql(sql)
        plan = self._get_ral(
            statements[0], sql_text=sql if len(statements) == 1 else None)
        if isinstance(plan, plan_nodes.Explain):
            plan = plan.input
        try:
            self._render_plan_png(plan, filename)
        except Exception:  # dsql: allow-broad-except — no matplotlib /
            # headless issues: text fallback below renders instead
            logger.warning("plan image rendering unavailable; writing text",
                           exc_info=True)
            path = filename if filename.endswith(".txt") else filename + ".txt"
            with open(path, "w") as f:
                f.write(plan.explain())

    @staticmethod
    def _render_plan_png(plan, filename: str) -> None:
        """Layout the plan tree top-down and draw labeled boxes + edges."""
        import matplotlib

        matplotlib.use("Agg")
        import matplotlib.pyplot as plt

        # depth-first layout: x = leaf order, y = -depth
        positions: Dict[int, Tuple[float, float]] = {}
        labels: Dict[int, str] = {}
        edges: List[Tuple[int, int]] = []
        next_x = [0.0]

        def walk(node, depth):
            kids = node.inputs()
            xs = []
            for kid in kids:
                walk(kid, depth + 1)
                edges.append((id(node), id(kid)))
                xs.append(positions[id(kid)][0])
            x = sum(xs) / len(xs) if xs else next_x[0]
            if not xs:
                next_x[0] += 1.0
            positions[id(node)] = (x, -float(depth))
            label = node._label()
            labels[id(node)] = label if len(label) <= 42 else label[:39] + "..."

        walk(plan, 0)
        depth = -min(y for _, y in positions.values()) + 1
        width = max(x for x, _ in positions.values()) + 1
        fig, ax = plt.subplots(
            figsize=(max(6, 3.2 * width), max(3, 1.1 * depth)))
        for a, b in edges:
            (x1, y1), (x2, y2) = positions[a], positions[b]
            ax.plot([x1, x2], [y1, y2], "-", color="#888888", zorder=1)
        for nid, (x, y) in positions.items():
            ax.text(x, y, labels[nid], ha="center", va="center", fontsize=8,
                    zorder=2, bbox=dict(boxstyle="round,pad=0.35",
                                        facecolor="#eef3fb",
                                        edgecolor="#4a6fa5"))
        ax.set_axis_off()
        fig.tight_layout()
        fig.savefig(filename, dpi=120)
        plt.close(fig)

    # ------------------------------------------------------------ internals
    def _get_ral(self, stmt, sql_text: Optional[str] = None):
        """AST -> bound plan -> optimized plan (parity: context.py:819
        _get_ral driving parse/bind/optimize in the Rust planner).

        When the statement's source text is available, the whole parse+bind
        stage runs natively (native/binder.cpp, the analogue of the
        reference's compiled SqlToRel, src/sql.rs:586-674); the Python
        binder remains the fallback."""
        catalog = self._prepare_catalog()
        case_sensitive = bool(self.config.get("sql.identifier.case_sensitive", True))
        catalog.case_sensitive = case_sensitive
        plan = None
        core_optimized = False
        native_mode = str(self.config.get("sql.native.binder", "auto")).lower()
        want_opt = bool(self.config.get("sql.optimize", True))
        with observability.stage("bind") as bind_attrs:
            if sql_text is not None and native_mode in ("auto", "on", "true"):
                from .planner.native_bridge import native_bind, native_plan

                cat_buf = self._encoded_catalog(catalog)
                strict = native_mode != "auto"
                if want_opt:
                    # one native call runs parse+bind+the structural rule loop
                    # AND the stats-driven join reorder (the reference's
                    # compiled DataFusion pipeline analogue)
                    plan = native_plan(
                        sql_text, catalog, cat_buf=cat_buf,
                        predicate_pushdown=bool(
                            self.config.get("sql.predicate_pushdown", True)),
                        strict=strict,
                        fact_dimension_ratio=float(self.config.get(
                            "sql.optimizer.fact_dimension_ratio", 0.7)),
                        max_fact_tables=int(self.config.get(
                            "sql.optimizer.max_fact_tables", 2)),
                        preserve_user_order=bool(self.config.get(
                            "sql.optimizer.preserve_user_order", True)),
                        filter_selectivity=float(self.config.get(
                            "sql.optimizer.filter_selectivity", 1.0)))
                    core_optimized = plan is not None
                if plan is None:
                    plan = native_bind(sql_text, catalog, cat_buf=cat_buf,
                                       strict=strict)
            bind_attrs["native"] = plan is not None
            if plan is None:
                binder = Binder(catalog, case_sensitive=case_sensitive)
                plan = binder.bind_statement(stmt)
        if want_opt:
            from .planner.optimizer.driver import optimize_core, optimize_post
            from .resilience.errors import QueryError

            try:
                with observability.stage("optimize",
                                         core_native=core_optimized):
                    if not core_optimized:
                        plan = optimize_core(plan, self.config, catalog)
                    plan = optimize_post(plan, self.config, catalog,
                                         context=self,
                                         skip_reorder=core_optimized)
            except QueryError:
                # taxonomy errors (deadline expiry at a checkpoint, resource
                # exhaustion in a plan-time data read) carry policy upstream
                # layers act on — they must cross this boundary, not vanish
                # into a silent unoptimized-plan fallback
                raise
            except Exception:
                # parity: optimizer failure falls back to the unoptimized plan
                # (context.py:857-864), metric-counted so a lived-with
                # planner bug shows up in SHOW METRICS instead of only logs
                self.metrics.inc("planner.optimize.fallback")
                logger.warning("Optimization failed; using unoptimized plan",
                               exc_info=True)
        verify_mode = str(self.config.get("analysis.verify", "on")).lower()
        # plain EXPLAIN / EXPLAIN LINT never execute their input (the LINT
        # plugin runs its own verification walk), so only executing plans —
        # including EXPLAIN ANALYZE — pay the bind-time check
        wants_verify = not (isinstance(plan, plan_nodes.Explain)
                            and not plan.analyze)
        if wants_verify and not isinstance(plan, plan_nodes.CustomNode):
            # plan-family parameterization (families/, docs/serving.md):
            # literals lift into a runtime parameter vector, and the
            # literal-stripped fingerprint becomes the query's serving
            # identity — result-cache key, breaker/ladder key, estimator
            # memo, per-family profile/warm-up entry — while the compiled
            # pipelines share one executable across the whole family
            from . import families

            if families.enabled(self.config):
                with observability.stage("parameterize") as fam_attrs:
                    info = families.family_of(plan, self.config,
                                              metrics=self.metrics)
                    if info is not None:
                        fam_attrs["family"] = info.fingerprint
                        fam_attrs["params"] = info.n_params
                        if info.n_params:
                            self.metrics.inc("families.parameterized")
        if wants_verify and verify_mode not in ("off", "false", "0", "none"):
            from . import analysis

            # static plan verification (docs/analysis.md): schema/dtype
            # cross-check raises taxonomy PlanError here — at bind time —
            # and statically-doomed compiled rungs are marked on the plan
            # so the degradation ladder never attempts them
            with observability.stage("verify", mode=verify_mode):
                analysis.verify_and_apply(plan, self,
                                          strict=(verify_mode == "strict"))
        if wants_verify and not isinstance(plan, plan_nodes.CustomNode) \
                and self._estimate_enabled():
            # static cost & memory estimation (docs/analysis.md): the
            # verdict rides the plan (`_dsql_estimate`) for the admission
            # byte gate and result-cache admission, and compiled aggregate
            # rungs whose intermediate-buffer lower bound provably cannot
            # fit the device budget are pre-skipped for the ladder
            with observability.stage("estimate") as est_attrs:
                est = self._run_estimator(plan)
                if est is not None:
                    est_attrs["rows_hi"] = est.rows.hi
                    est_attrs["bytes_lo"] = est.peak_bytes.lo
        return plan

    def _estimate_enabled(self) -> bool:
        mode = str(self.config.get("analysis.estimate", "on")).lower()
        return mode not in ("off", "false", "0", "none")

    def _trace_enabled(self) -> bool:
        mode = str(self.config.get("observability.trace.enabled",
                                   True)).lower()
        return mode not in ("off", "false", "0", "none")

    def _run_estimator(self, plan):
        """Guarded `estimate_and_apply`: estimation is advisory, so an
        estimator bug must never block planning or execution — the query
        simply runs ungated, metric-counted.

        Family reuse (families/): the estimator's intervals never read
        literal *values* (filters drop the lower bound and keep the upper;
        IN buckets and LIMIT windows are part of the family), so a
        family's first estimate is exact for every member — later members
        reuse it instead of re-walking the plan.  When the device-budget
        rung proofs are armed the walk re-runs per plan, because proofs
        mark the concrete plan's nodes."""
        from .analysis import estimator

        try:
            fam = getattr(plan, "_dsql_family", None)
            key = None
            if fam is not None and estimator.device_budget_bytes(
                    self.config) is None:
                try:
                    key = (fam.fingerprint,
                           tuple(tuple(x) if isinstance(x, list) else x
                                 for x in self._catalog_signature()),
                           self._catalog_serial,
                           self.config.effective_items())
                    hash(key)
                except TypeError:
                    key = None
            if key is not None:
                with self._plan_lock:
                    cached = self._family_estimates.get(key)
                if cached is not None:
                    plan._dsql_estimate = cached
                    self.metrics.inc("families.estimate.hit")
                    return self._feedback_estimate(plan, cached, fam)
            est = estimator.estimate_and_apply(plan, self)
            if key is not None and est is not None:
                with self._plan_lock:
                    if len(self._family_estimates) >= 512:
                        self._family_estimates.clear()
                    self._family_estimates[key] = est
            return self._feedback_estimate(plan, est, fam)
        except Exception:  # dsql: allow-broad-except — advisory analysis
            self.metrics.inc("analysis.estimate.internal_error")
            logger.debug("plan estimation failed; query runs ungated",
                         exc_info=True)
            return None

    def cost_hint(self, sql: str, config_options=None):
        """Submit-time `QueryCost` for the packing scheduler
        (serving/scheduler.py): peek the plan cache for this SQL text — a
        hit carries the family's memoized estimate (the provable
        ``peak_bytes`` floor the packer reserves) and the family's observed
        exec profile (the predicted exec_ms behind drain hints and
        deadline ordering).  Never parses or plans: submit must stay cheap,
        so a cold SQL text returns None and the scheduler treats the query
        as zero-cost (FIFO-equivalent) until its first execution populates
        the plan cache and profile."""
        from .serving.scheduler import QueryCost

        try:
            # Context.sql computes the plan-cache key INSIDE its config
            # overlay scope (effective_items sees the per-query options);
            # the peek must mirror that or option-carrying submits never
            # hit the cache they populated
            with self.config.set(dict(config_options or {})):
                key = self._plan_cache_key(sql, config_options)
            if key is None:
                return None
            with self._plan_lock:
                plans = self._plan_cache.get(key)
            if not plans or len(plans) != 1:
                return None
            plan = plans[0]
            est = getattr(plan, "_dsql_estimate", None)
            fam = getattr(plan, "_dsql_family", None)
            fam_fp = fam.fingerprint if fam is not None else None
            fp = fam_fp
            if fp is None:
                from .resilience.ladder import plan_fingerprint

                fp = plan_fingerprint(plan)
            # streamed plans reserve only their per-chunk footprint: re-run
            # the (pure, read-only) routing decision under this submit's
            # effective config — never read from the shared plan object, so
            # the hint is always current with THIS submit's budget
            chunk = None
            if est is not None:
                chunk = self._stream_chunk_hint(plan, est, config_options)
            return QueryCost(
                bytes_lo=int(est.peak_bytes.lo) if est is not None else 0,
                pred_exec_ms=self.profiles.predicted_exec_ms(fp),
                family=fam_fp,
                chunk_bytes_lo=chunk)
        except Exception:  # dsql: allow-broad-except — advisory hint: a
            # lookup bug must degrade to FIFO treatment, never block submit
            logger.debug("cost hint failed for %r", sql, exc_info=True)
            return None

    def _stream_chunk_hint(self, plan, est, config_options):
        """The provable per-chunk floor a streamed execution of `plan`
        would reserve under this submit's effective config, or None (the
        query runs single-launch).  Mirrors the admission gate's routing
        exactly — same budget parse, same `stream_decision` — but purely
        read-only, so the submit path never mutates shared plan state."""
        with self.config.set(dict(config_options or {})):
            budget = config_module.parse_byte_budget(
                self.config.get("serving.admission.max_estimated_bytes"))
            if budget is None or int(est.peak_bytes.lo) <= budget:
                return None
            from .streaming import stream_decision

            routed = stream_decision(plan, est, self, self.config, budget)
        return int(routed[1].chunk_bytes_lo) if routed is not None else None

    def _measured_scan_bytes(self, plan, stream_decision=None) -> int:
        """MEASURED resident bytes of the registered tables `plan` scans
        (`serving/cache.table_nbytes` accounting — encoded widths, masks,
        dictionaries), the scan side of the scheduler's reserve-vs-measured
        reconciliation.  ``stream_decision`` is this execution's routing
        verdict (streaming/) when it streamed: the streamed table charges
        its PER-CHUNK share, because the reservation it reconciles against
        was the per-chunk floor.  Purely advisory — any failure means 0,
        never a failed query."""
        try:
            from .serving.cache import table_nbytes

            total = 0
            seen = set()
            for node in plan_nodes.walk_plan(plan):
                if not isinstance(node, plan_nodes.TableScan):
                    continue
                key = (node.schema_name, node.table_name)
                if key in seen:
                    continue
                seen.add(key)
                container = self.schema.get(node.schema_name)
                dc = container.tables.get(node.table_name) \
                    if container is not None else None
                if dc is None:
                    continue
                from .datacontainer import LazyParquetContainer

                if isinstance(dc, LazyParquetContainer):
                    continue
                nbytes = table_nbytes(dc.table)
                if stream_decision is not None \
                        and stream_decision.partitions > 1 \
                        and (stream_decision.schema_name,
                             stream_decision.table_name) == key:
                    nbytes = -(-nbytes // stream_decision.partitions)
                total += nbytes
            return total
        except Exception:  # dsql: allow-broad-except — advisory accounting
            logger.debug("measured scan bytes failed", exc_info=True)
            return 0

    def _feedback_estimate(self, plan, est, fam):
        """Close the profile-feedback loop on one freshly produced (or
        family-memoized) estimate: record the static rows upper bound into
        the family's profile (the "estimated" side SHOW PROFILES pairs with
        the observed rows), then tighten the estimate's upper bounds from
        the observed history (`estimator.apply_feedback` — bounded, never
        below the provable floors).  The memoized static verdict is never
        mutated, so every later family member re-applies feedback against
        its own, fresher history."""
        if est is None:
            return None
        try:
            from .analysis import estimator

            fam_fp = fam.fingerprint if fam is not None else None
            fp = fam_fp
            if fp is None:
                from .resilience.ladder import plan_fingerprint

                fp = plan_fingerprint(plan)
            self.profiles.record_estimate(fp, est.rows.hi, family=fam_fp)
            out = estimator.apply_feedback(est, self.profiles.get(fp),
                                           self.config, self.metrics)
            plan._dsql_estimate = out
            return out
        except Exception:  # dsql: allow-broad-except — feedback is an
            # advisory sharpening: a bug here must leave the static
            # verdict in force, never fail the query or EXPLAIN
            self.metrics.inc("analysis.estimate.internal_error")
            logger.debug("estimate feedback failed; static verdict kept",
                         exc_info=True)
            return est

    def _plan_estimate(self, plan):
        """The bind-time `PlanEstimate` riding a plan, or a fresh one when
        the gate is configured but the plan was never estimated (cached
        plans carry theirs; `analysis.estimate = off` disables both)."""
        est = getattr(plan, "_dsql_estimate", None)
        if est is not None:
            if not est.feedback:
                # a plan-cached query keeps its bind-time estimate; apply
                # feedback once history exists so repeated cached traffic
                # still benefits (one-time tightening — an already-fed-back
                # estimate is not re-ratcheted against a rolling window)
                est = self._feedback_estimate(
                    plan, est, getattr(plan, "_dsql_family", None))
            return est
        if config_module.parse_byte_budget(
                self.config.get("serving.admission.max_estimated_bytes")) \
                is None:
            return None
        if not self._estimate_enabled():
            return None
        if isinstance(plan, plan_nodes.CustomNode):
            return None
        if isinstance(plan, plan_nodes.Explain) and not plan.analyze:
            # plain EXPLAIN / LINT / ESTIMATE renders text, never executes
            # its input — it must report on an over-budget query, not be
            # shed by the gate
            return None
        return self._run_estimator(plan)

    def _encoded_catalog(self, catalog) -> Optional[bytes]:
        """Catalog bytes for the native binder, cached across queries until
        any table/view/function changes (keyed like the plan cache)."""
        try:
            # statistics row counts are serialized into the buffer for the
            # native join reorderer, so an in-place stats refresh (same uid,
            # same serial) must also invalidate (ADVICE r5)
            key = (self._catalog_serial, catalog.case_sensitive,
                   catalog.current_schema, tuple(
                       (sname, tname, dc.uid,
                        getattr(cont.statistics.get(tname), "row_count", None))
                       for sname, cont in sorted(self.schema.items())
                       for tname, dc in sorted(cont.tables.items())))
        except Exception:  # dsql: allow-broad-except — unhashable/odd stats
            # only disable caching for this call; encoding still runs
            key = None
        with self._plan_lock:
            cached = getattr(self, "_catalog_buf_cache", None)
        if key is not None and cached is not None and cached[0] == key:
            return cached[1]
        from .planner.native_bridge import encode_catalog

        try:
            buf = encode_catalog(catalog)
        except KeyError:
            buf = None
        if key is not None:
            with self._plan_lock:
                self._catalog_buf_cache = (key, buf)
        return buf

    def _prepare_catalog(self) -> Catalog:
        """Sync python-side schema containers into a planner catalog
        (parity: _prepare_schemas, context.py:749)."""
        catalog = Catalog(self.schema_name)
        catalog.current_schema = self.schema_name
        for schema_name, container in self.schema.items():
            catalog.add_schema(schema_name)
            cschema = catalog.schemas[schema_name]
            for table_name, dc in container.tables.items():
                from .datacontainer import LazyParquetContainer

                if isinstance(dc, LazyParquetContainer):
                    fields = list(dc.fields)
                else:
                    fields = [
                        Field(name, col.sql_type, col.validity is not None or
                              col.sql_type in (SqlType.FLOAT, SqlType.DOUBLE))
                        for name, col in dc.table.columns.items()
                    ]
                stats = container.statistics.get(table_name)
                from .planner.catalog import Statistics as PStats

                cschema.tables[table_name] = CatalogTable(
                    table_name, schema_name, fields,
                    PStats(stats.row_count if stats else None),
                    container.filepaths.get(table_name),
                )
            for view_name, view_plan in self._views.get(schema_name, {}).items():
                fields = list(view_plan.schema)
                ct = CatalogTable(view_name, schema_name, fields)
                ct.view_plan = view_plan
                cschema.tables[view_name] = ct
            for fname, fds in container.function_lists.items():
                cschema.functions[fname] = list(fds)
            cschema.models = container.models
        return catalog

    def _register_view(self, name: str, plan, schema_name: str) -> None:
        self._views.setdefault(schema_name, {})[name] = plan
        self._catalog_serial += 1
        self._on_catalog_change()

    def _table_schema_name(self, parts: List[str]) -> Tuple[str, str]:
        if len(parts) >= 2:
            return parts[-2], parts[-1]
        return self.schema_name, parts[0]

    def _table_fields(self, schema_name: str, table_name: str):
        dc = self.schema[schema_name].tables.get(table_name)
        if dc is not None:
            return [Field(n, c.sql_type, True) for n, c in dc.table.columns.items()]
        view = self._views.get(schema_name, {}).get(table_name)
        if view is not None:
            return list(view.schema)
        raise KeyError(f"Table {table_name} not found")

    # -- executor services ---------------------------------------------------
    def get_table_data(self, schema_name: str, table_name: str) -> Table:
        dc = self.schema[schema_name].tables.get(table_name)
        if dc is not None:
            return dc.assign()
        view = self._views.get(schema_name, {}).get(table_name)
        if view is not None:
            from .physical.executor import Executor

            return Executor(self).execute(view)
        raise KeyError(f"Table {schema_name}.{table_name} not found")

    def lookup_function(self, name: str) -> Optional[FunctionDescription]:
        schema = self.schema[self.schema_name]
        return schema.functions.get(name.lower()) or schema.functions.get(name)

    def get_model(self, schema_name: str, model_name: str):
        models = self.schema[schema_name].models
        if model_name not in models:
            raise KeyError(f"A model with the name {model_name} is not present.")
        return models[model_name]

    def cancel_query(self, qid: str) -> bool:
        """Cooperatively cancel an in-flight query by qid — the engine
        behind ``CANCEL QUERY '<qid>'`` and ``POST /v1/queries/{qid}/
        cancel``.  Resolves the live-registry entry's `QueryTicket` and
        flags it; the executor's per-node checkpoints (and the streaming
        loop's between-launch checkpoints) raise at the next poll, and a
        still-queued serving ticket is skipped by the worker that pops it.
        Returns False for an unknown or already-terminal qid."""
        ok = self.live_queries.cancel(qid)
        self.metrics.inc("serving.cancel_requested")
        observability.flight.record("query.cancel", qid=qid, ok=ok)
        return ok

    # ------------------------------------------------------------ front-ends
    def run_server(self, **kwargs):  # pragma: no cover - thin wrapper
        """Presto-protocol HTTP server (parity: context.py:704)."""
        from .server.app import run_server as _run

        return _run(context=self, **kwargs)

    def stop_server(self):  # pragma: no cover
        if self.server is not None:
            self.server.shutdown()
        self.server = None

    def ipython_magic(self, auto_include: bool = False):  # pragma: no cover
        from .integrations.ipython import ipython_integration

        ipython_integration(self, auto_include=auto_include)

    def fqn(self, parts) -> Tuple[str, str]:
        """Fully-qualified (schema, table) from a name (parity context helper)."""
        return self._table_schema_name(list(parts))


#: ops whose value changes between executions of the same plan (parity:
#: optimizer rules' _is_volatile, plus the clock functions)
_VOLATILE_OPS = frozenset(
    {"rand", "rand_integer", "current_timestamp", "current_date"})


def _scan_node_exprs(node) -> Tuple[List[Any], bool]:
    """Walk every expression hanging off one plan node.  Returns
    (nested subquery plans to keep walking, uncacheable) where uncacheable
    means a volatile builtin or any user-defined function was found — such
    results must never be served from the result cache."""
    import dataclasses

    from .planner.expressions import (
        ExistsExpr,
        Expr,
        InSubqueryExpr,
        ScalarFunc,
        ScalarSubqueryExpr,
        SortKey,
        UdfExpr,
    )
    from .planner.expressions import walk as expr_walk

    def exprs_of(v):
        if isinstance(v, Expr):
            yield v
        elif isinstance(v, SortKey):
            yield v.expr
        elif isinstance(v, (list, tuple)):
            for item in v:
                yield from exprs_of(item)

    nested: List[Any] = []
    if not dataclasses.is_dataclass(node):
        return nested, False
    for f in dataclasses.fields(node):
        for e in exprs_of(getattr(node, f.name, None)):
            for x in expr_walk(e):
                if isinstance(x, ScalarFunc) and x.op in _VOLATILE_OPS:
                    return nested, True
                if isinstance(x, UdfExpr):
                    # arbitrary host code: assume nondeterministic
                    return nested, True
                if isinstance(x, (ScalarSubqueryExpr, InSubqueryExpr,
                                  ExistsExpr)) and x.plan is not None:
                    nested.append(x.plan)
    return nested, False


def _to_sql_type(t) -> SqlType:
    if isinstance(t, SqlType):
        return t
    if isinstance(t, str):
        from .columnar.dtypes import parse_sql_type

        return parse_sql_type(t)
    try:
        return np_to_sql(np.dtype(t))
    except (TypeError, ValueError, KeyError):
        pass  # not a numpy dtype spec: try the python scalar mapping
    mapping = {int: SqlType.BIGINT, float: SqlType.DOUBLE, str: SqlType.VARCHAR,
               bool: SqlType.BOOLEAN}
    if t in mapping:
        return mapping[t]
    raise NotImplementedError(f"Cannot map {t!r} to a SQL type")
