"""Physical executor: walks the logical plan and produces device Tables.

Role parity: reference RelConverter.convert (physical/rel/convert.py:39
there) driven by Context._compute_table_from_rel (context.py:874).  The
registry maps node-type strings to plugins exactly like the reference's
Pluggable registries; execution is eager per node (XLA async dispatch under
the hood), with the distributed path swapping sharded kernels in via
`parallel/`.
"""
from __future__ import annotations

from typing import Dict, Optional

from ..columnar.table import Table
from ..planner.plan import LogicalPlan
from ..serving.runtime import current_ticket
from .rel.base import BaseRelPlugin
from .rex.convert import RexConverter


class Executor:
    _plugins: Dict[str, BaseRelPlugin] = {}

    def __init__(self, context, trace: bool = False):
        self.context = context
        self.rex = RexConverter(self)
        self._memo: Dict[int, Table] = {}
        from ..tracing import Tracer

        self.tracer = Tracer()
        if trace:
            self.tracer.start()
        #: (schema, table) -> Table substitutions (streaming batch execution)
        self.table_overrides: Dict[tuple, Table] = {}
        #: id(streamable node) -> StreamDecision for THIS execution
        #: (streaming/): the admission gate's routing verdict travels here
        #: — per-execution state, never on the shared cached plan object,
        #: so concurrent executions under different budgets cannot race
        self.stream_decisions: Dict[int, object] = {}
        #: id(Aggregate node) -> compiled_join.TopK for THIS execution: the
        #: Sort above an Aggregate tells the rungs under it that only its
        #: first `fetch` rows are wanted (SortPlugin.convert)
        self.topk_hints: Dict[int, object] = {}
        #: ids of the nodes such a hint may have cut short: their tables are
        #: one parent's view, so a shared subtree's memo must not hold them
        self.unmemoized: set = set()

    @classmethod
    def add_plugin_class(cls, plugin_class):
        plugin = plugin_class()
        cls._plugins[plugin.class_name] = plugin
        return plugin_class

    def execute_root(self, rel: LogicalPlan) -> Table:
        """Entry for the plan ROOT: the result goes straight to the host, so
        root select chains compile to one kernel + one packed transfer
        (physical/compiled_select.py) before the recursive converter runs.
        Compressed-domain scans (columnar/encodings.py) late-materialize
        here: the compiled paths keep DICT/FOR codes end-to-end and decode
        only survivors at the root / d2h boundary, while the interpreted
        walk below decodes once at its TableScan.

        Resilience (resilience/ladder.py): the compiled fast path is a
        degradation-ladder rung — a compile failure or device OOM inside it
        steps down to the interpreted walk (recorded in the metrics registry
        and gated by the per-plan circuit breaker) instead of failing the
        query; the interpreted walk itself carries one CPU-backend rung
        under it.  The `execute` fault-injection site fires here so the
        ServingRuntime's retry/backoff path is testable end to end."""
        from ..resilience import faults, ladder
        from ..spmd import try_spmd_select
        from .compiled_predict import root_has_predict, try_compiled_predict
        from .compiled_select import try_compiled_select

        ticket = current_ticket()
        if ticket is not None:  # checkpoint before the one-kernel fast path
            ticket.checkpoint()
        faults.maybe_inject("execute", self.config)
        # cheap pre-check (same gate AggregatePlugin uses): the SPMD rung is
        # only worth attempting — plan extraction, table lookups, sharding
        # probes — when the subtree actually scans a mesh-sharded table
        from ..parallel.dist_plan import plan_has_sharded_scan

        sharded = plan_has_sharded_scan(rel, self.context)
        # admission-routed streamed select (streaming/, this execution's
        # stream_decisions entry): a provably-oversize root chain serves as
        # N pipelined chunk launches instead of being shed — its own
        # (family, rung) breaker entity, stepping down to the single-launch
        # rungs below
        streamed_mark = id(rel) in self.stream_decisions
        # fused PREDICT (physical/compiled_predict.py): a root
        # PredictModelNode whose input is a compilable select chain runs
        # model inference in the SAME executable as the scan — its own
        # (family, compiled_predict) breaker entity, stepping down to the
        # host predict path (PredictModelPlugin) below
        predict_root = root_has_predict(rel)
        # each rung is named once: with `resilience.ladder.enabled` false
        # `ladder.attempt` still fires the injection site and calls the
        # rung, and absorbs nothing (a forced compile fault must propagate
        # then: that is what disabling proves)
        if predict_root:
            out = ladder.attempt(
                self, "compiled_predict",
                lambda: try_compiled_predict(rel, self),
                rel=rel, inject_site="predict")
            if out is not None:
                return out
        if streamed_mark:
            from ..streaming import try_streamed_select

            out = ladder.attempt(
                self, "streamed_select",
                lambda: try_streamed_select(rel, self), rel=rel)
            if out is not None:
                return out
        if sharded:
            # the SPMD rung sits above the single-chip one (which
            # declines sharded tables); its failures degrade and
            # breaker-charge per (family, spmd_select) without
            # poisoning the family's single-chip rung
            out = ladder.attempt(
                self, "spmd_select",
                lambda: try_spmd_select(rel, self),
                rel=rel, inject_site="spmd")
            if out is not None:
                return out
        out = ladder.attempt(
            self, "compiled_select",
            lambda: try_compiled_select(rel, self),
            rel=rel, inject_site="compile")
        if out is not None:
            return out
        return ladder.execute_interpreted(self, rel)

    def execute(self, rel: LogicalPlan) -> Table:
        # cooperative cancellation checkpoint: a query past its serving
        # deadline (or cancelled by the client) raises here, between plan
        # nodes, instead of holding a worker until the full plan finishes
        ticket = current_ticket()
        if ticket is not None:
            ticket.checkpoint()
        key = id(rel)
        if key in self._memo:
            return self._memo[key]
        plugin = self._plugins.get(rel.node_type)
        if plugin is None:
            raise NotImplementedError(f"No rel plugin for node type {rel.node_type!r}")
        if self.tracer.enabled:
            with self.tracer.node(rel) as ctx:
                out = plugin.convert(rel, self)
                ctx.rows = out.num_rows
        else:
            out = plugin.convert(rel, self)
        if key not in self.unmemoized:
            self._memo[key] = out
        return out

    # -- services for plugins ----------------------------------------------
    def eval_expr(self, expr, table: Table):
        return self.rex.convert(expr, table)

    def lookup_function(self, name: str):
        fd = self.context.lookup_function(name)
        if fd is None:
            raise KeyError(f"Function {name!r} not registered")
        return fd

    def get_table(self, schema_name: str, table_name: str) -> Table:
        return self.context.get_table_data(schema_name, table_name)

    @property
    def config(self):
        return self.context.config
