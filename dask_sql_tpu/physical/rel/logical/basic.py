"""Relational converters: scan/project/filter/limit/sort/union/values/etc.

Role parity (one class per reference plugin file under
physical/rel/logical/ there): table_scan.py, project.py, filter.py,
limit.py, sort.py, union.py, values.py, empty_relation.py,
subquery_alias.py, sample.py, explain.py, distributeby.py (custom).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ....columnar.column import Column
from ....columnar.dtypes import SqlType
from ....columnar.table import Table
from ....ops.grouping import factorize, group_first_indices, key_arrays
from ....ops.sorting import sort_permutation, topk_permutation
from ....planner import plan as p
from ..base import BaseRelPlugin, unique_names
from ...executor import Executor


@Executor.add_plugin_class
class TableScanPlugin(BaseRelPlugin):
    """Parity: reference table_scan.py:21 (projection + DNF filter pushdown)."""

    class_name = "TableScan"

    def convert(self, rel: p.TableScan, executor) -> Table:
        from ....datacontainer import LazyParquetContainer

        override = executor.table_overrides.get((rel.schema_name, rel.table_name))
        dc = executor.context.schema.get(rel.schema_name)
        dc = dc.tables.get(rel.table_name) if dc is not None else None
        if override is not None:
            # batch-streaming execution: the batch replaces the scan source;
            # projection subset here, filters apply via the common block below
            # (the IO layer only pre-filtered the *convertible* conjuncts)
            table = override
            if rel.projection is not None:
                table = table.select([c for c in rel.projection if c in table.columns])
        elif isinstance(dc, LazyParquetContainer):
            # lazy parquet: read only projected columns; convertible filter
            # conjuncts prune row groups at the IO layer (pyarrow `filters=`,
            # parity: reference table_scan.py:80-119 DNF pushdown)
            from ....physical.utils.filter import filters_to_pyarrow

            names = rel.projection if rel.projection is not None else [
                f.name for f in dc.fields]
            pa_filters, _ = filters_to_pyarrow(rel.filters, list(names))
            table = dc.scan(columns=rel.projection, filters=pa_filters)
        else:
            table = executor.get_table(rel.schema_name, rel.table_name)
            if rel.projection is not None:
                table = table.select(rel.projection)
            # eager operators index rows positionally: exact-length view
            # (padding-aware consumers bypass this plugin entirely)
            table = table.depad()
        if table.has_encoded_columns():
            # eager operators work in value space: compressed columns
            # (columnar/encodings.py) materialize ONCE at the scan — the
            # encoding-aware compiled pipelines never reach this plugin
            executor.context.metrics.inc("columnar.encoding.decode")
            table = table.decode()
        if rel.filters:
            # filters are bound against the *projected* schema
            mask = None
            for f in rel.filters:
                col = executor.eval_expr(f, table)
                m = col.data & col.valid_mask()
                mask = m if mask is None else (mask & m)
            table = table.filter(mask)
        return self.fix_column_to_row_type(table, rel.schema)


@Executor.add_plugin_class
class ProjectionPlugin(BaseRelPlugin):
    """Parity: reference project.py:17 (column-ref shortcut project.py:48-54)."""

    class_name = "Projection"

    def convert(self, rel: p.Projection, executor) -> Table:
        (inp,) = self.assert_inputs(rel, 1, executor)
        from ....planner.expressions import ColumnRef

        names = unique_names([f.name for f in rel.schema])
        cols = {}
        for name, expr in zip(names, rel.exprs):
            if isinstance(expr, ColumnRef) and type(expr) is ColumnRef:
                cols[name] = inp.columns[inp.column_names[expr.index]]
            else:
                cols[name] = executor.eval_expr(expr, inp)
        return Table(cols, inp.num_rows)


@Executor.add_plugin_class
class FilterPlugin(BaseRelPlugin):
    """Parity: reference filter.py:48 (NULL -> False, filter.py:20-45)."""

    class_name = "Filter"

    def convert(self, rel: p.Filter, executor) -> Table:
        (inp,) = self.assert_inputs(rel, 1, executor)
        cond = executor.eval_expr(rel.predicate, inp)
        mask = cond.data & cond.valid_mask()
        return inp.filter(mask)


@Executor.add_plugin_class
class LimitPlugin(BaseRelPlugin):
    """Parity: reference limit.py:18."""

    class_name = "Limit"

    def convert(self, rel: p.Limit, executor) -> Table:
        (inp,) = self.assert_inputs(rel, 1, executor)
        start = rel.skip or 0
        stop = inp.num_rows if rel.fetch is None else start + rel.fetch
        return inp.slice(start, stop)


@Executor.add_plugin_class
class SortPlugin(BaseRelPlugin):
    """Parity: reference sort.py:12 + utils/sort.py (top-k when fetch set)."""

    class_name = "Sort"

    @staticmethod
    def _topk_hint(rel: p.Sort, executor):
        """ORDER BY .. LIMIT straight above an Aggregate (column-picking
        Projections between them allowed), every key a plain column: tell
        the Aggregate's rungs which `fetch` rows are wanted
        (`Executor.topk_hints`) and return the Aggregate's id, else None.
        A rung that takes the hint returns those rows in order and this Sort
        re-sorts a handful of host rows; one that does not returns every
        group, as without the hint.  The nodes in between are kept out of
        the executor's memo: what they return is this Sort's view."""
        from ....planner.expressions import ColumnRef
        from ...compiled_join import TopK

        if rel.fetch is None or not rel.keys:
            return None
        index = []
        for key in rel.keys:
            if type(key.expr) is not ColumnRef:
                return None
            index.append(key.expr.index)
        node, chain = rel.input, []
        while isinstance(node, (p.Projection, p.SubqueryAlias)):
            if isinstance(node, p.Projection):
                picked = [node.exprs[i] for i in index]
                if not all(type(e) is ColumnRef for e in picked):
                    return None
                index = [e.index for e in picked]
            chain.append(id(node))
            node = node.inputs()[0]
        if not isinstance(node, p.Aggregate):
            return None
        executor.unmemoized.update(chain + [id(node)])
        executor.topk_hints[id(node)] = TopK(int(rel.fetch), tuple(
            (i, bool(key.ascending), bool(key.nulls_first_resolved()))
            for i, key in zip(index, rel.keys)))
        return id(node)

    def convert(self, rel: p.Sort, executor) -> Table:
        hinted = self._topk_hint(rel, executor)
        try:
            (inp,) = self.assert_inputs(rel, 1, executor)
        finally:
            executor.topk_hints.pop(hinted, None)
        if inp.num_rows == 0:
            return inp
        cols = [executor.eval_expr(k.expr, inp) for k in rel.keys]
        # mesh-sharded input + full sort: sample-based range-partition sort
        # over the mesh (output stays row-sharded; device order IS the sort
        # order).  LIMIT keeps the top-k path below — the k survivors are
        # tiny regardless of sharding.
        if rel.fetch is None and cols:
            from ....parallel import dist_plan
            from ....resilience import ladder

            mesh = dist_plan.should_distribute(
                executor, "sql.distributed.sort", inp)
            if mesh is not None:
                # ladder rung: a capacity overflow inside the collectives
                # sort degrades to the single-program sort below (recorded
                # as resilience.degraded.dist_sort / resilience.fallback)
                sorted_t = ladder.attempt(
                    executor, "dist_sort",
                    lambda: dist_plan.dist_sort_table(
                        mesh, inp, cols,
                        [k.ascending for k in rel.keys],
                        [k.nulls_first_resolved() for k in rel.keys],
                        metrics=executor.context.metrics),
                    rel=rel)
                if sorted_t is not None:
                    return self.fix_column_to_row_type(sorted_t, rel.schema)
        limit = executor.config.get("sql.sort.topk-nelem-limit", 1_000_000)
        on_host = all(isinstance(c.data, np.ndarray) for c in cols)
        if (rel.fetch is not None and len(cols) >= 1 and not on_host
                and rel.fetch * max(len(inp.columns), 1) <= limit):
            # top-k on the primary key then exact sort of the k survivors —
            # parity: reference topk_sort utils/sort.py:78 eligibility
            idx = topk_permutation(cols[0], rel.keys[0].ascending, rel.fetch * 4,
                                   exact_ties=len(cols) > 1)
            if idx is not None:
                sub = inp.take(idx)
                sub_cols = [executor.eval_expr(k.expr, sub) for k in rel.keys]
                perm = sort_permutation(
                    sub_cols, [k.ascending for k in rel.keys],
                    [k.nulls_first_resolved() for k in rel.keys])
                return sub.take(perm[: rel.fetch])
        perm = sort_permutation(
            cols, [k.ascending for k in rel.keys],
            [k.nulls_first_resolved() for k in rel.keys])
        if rel.fetch is not None:
            perm = perm[: rel.fetch]
        return inp.take(perm)


@Executor.add_plugin_class
class UnionPlugin(BaseRelPlugin):
    """Parity: reference union.py (rename to common schema + concat)."""

    class_name = "Union"

    def convert(self, rel: p.Union, executor) -> Table:
        tables = [executor.execute(c) for c in rel.inputs()]
        names = unique_names([f.name for f in rel.schema])
        renamed = []
        for t in tables:
            t = self.fix_dtype_to_row_type(t, rel.schema)
            renamed.append(Table(dict(zip(names, t.columns.values())), t.num_rows))
        return Table.concat(renamed)


@Executor.add_plugin_class
class DistinctPlugin(BaseRelPlugin):
    """DISTINCT via group-id factorization (first occurrence per key)."""

    class_name = "Distinct"

    def convert(self, rel: p.Distinct, executor) -> Table:
        (inp,) = self.assert_inputs(rel, 1, executor)
        if inp.num_rows == 0:
            return inp
        keys = key_arrays([inp.columns[n] for n in inp.column_names])
        gid, order, num_groups = factorize(keys)
        first = group_first_indices(gid, num_groups)
        return inp.take(jnp.sort(first))


def _intersect_except(rel, executor, plugin, anti: bool) -> Table:
    from ....ops.join import join_key_gids, semi_join_mask

    left = executor.execute(rel.inputs()[0])
    right = executor.execute(rel.inputs()[1])
    left = plugin.fix_dtype_to_row_type(left, rel.schema)
    right = plugin.fix_dtype_to_row_type(right, rel.schema)
    lcols = [left.columns[n] for n in left.column_names]
    rcols = [right.columns[n] for n in right.column_names]
    if left.num_rows == 0:
        return left
    # NULLs compare equal in set operations (IS NOT DISTINCT semantics)
    lgid, rgid = join_key_gids(lcols, rcols, null_equals_null=True)
    if rel.all:
        # multiset semantics: INTERSECT ALL -> min(count_l, count_r) copies,
        # EXCEPT ALL -> max(count_l - count_r, 0) copies of each distinct row.
        # lgid/rgid are already dense joint ids (null_equals_null path), so
        # counting needs no second factorize
        num = int(jnp.maximum(lgid.max(), rgid.max() if right.num_rows else 0)) + 1
        gl, gr = lgid, rgid
        cl = jax.ops.segment_sum(jnp.ones_like(gl, dtype=jnp.int64), gl, num)
        cr = jax.ops.segment_sum(jnp.ones_like(gr, dtype=jnp.int64), gr, num)
        out_counts = jnp.maximum(cl - cr, 0) if anti else jnp.minimum(cl, cr)
        first = group_first_indices(gl, num)
        present = jnp.nonzero((out_counts > 0) & (first < left.num_rows))[0]
        reps = out_counts[present]
        rows = jnp.repeat(first[present], reps, total_repeat_length=int(reps.sum()))
        return left.take(rows)
    mask = semi_join_mask(lgid, rgid, anti=anti)
    out = left.filter(mask)
    keys = key_arrays([out.columns[n] for n in out.column_names])
    if out.num_rows:
        gid, _, num = factorize(keys)
        out = out.take(jnp.sort(group_first_indices(gid, num)))
    return out


@Executor.add_plugin_class
class IntersectPlugin(BaseRelPlugin):
    class_name = "Intersect"

    def convert(self, rel, executor) -> Table:
        return _intersect_except(rel, executor, self, anti=False)


@Executor.add_plugin_class
class ExceptPlugin(BaseRelPlugin):
    class_name = "Except"

    def convert(self, rel, executor) -> Table:
        return _intersect_except(rel, executor, self, anti=True)


@Executor.add_plugin_class
class ValuesPlugin(BaseRelPlugin):
    """Parity: reference values.py (literal rows -> one-partition frame)."""

    class_name = "Values"

    def convert(self, rel: p.Values, executor) -> Table:
        from ..base import unique_names as _un
        from ....physical.rex.convert import _literal_column

        names = _un([f.name for f in rel.schema])
        cols = {}
        nrows = len(rel.rows)
        for j, (name, f) in enumerate(zip(names, rel.schema)):
            vals = []
            one_row = Table({}, 1)
            for row in rel.rows:
                c = executor.eval_expr(row[j], one_row)
                vals.append(c)
            from ....columnar.concat import concat_columns

            col = concat_columns(vals) if vals else Column.from_scalar(None, 0, f.sql_type)
            cols[name] = col.cast(f.sql_type) if col.sql_type != f.sql_type else col
        return Table(cols, nrows)


@Executor.add_plugin_class
class EmptyRelationPlugin(BaseRelPlugin):
    """Parity: reference empty_relation.py (SELECT without FROM)."""

    class_name = "EmptyRelation"

    def convert(self, rel: p.EmptyRelation, executor) -> Table:
        n = 1 if rel.produce_one_row else 0
        names = unique_names([f.name for f in rel.schema])
        cols = {name: Column.from_scalar(None, n, f.sql_type)
                for name, f in zip(names, rel.schema)}
        return Table(cols, n)


@Executor.add_plugin_class
class SubqueryAliasPlugin(BaseRelPlugin):
    """Parity: reference subquery_alias.py (pass-through rename)."""

    class_name = "SubqueryAlias"

    def convert(self, rel: p.SubqueryAlias, executor) -> Table:
        (inp,) = self.assert_inputs(rel, 1, executor)
        return self.fix_column_to_row_type(inp, rel.schema)


@Executor.add_plugin_class
class SamplePlugin(BaseRelPlugin):
    """Parity: reference sample.py (TABLESAMPLE SYSTEM / BERNOULLI)."""

    class_name = "Sample"

    def convert(self, rel: p.Sample, executor) -> Table:
        (inp,) = self.assert_inputs(rel, 1, executor)
        frac = rel.fraction / 100.0
        seed = rel.seed if rel.seed is not None else np.random.randint(0, 2**31 - 1)
        key = jax.random.PRNGKey(seed)
        if rel.method == "SYSTEM":
            # partition-level sampling: with device-sharded tables this keeps
            # or drops whole shards; single shard here -> block sampling
            nblocks = 16
            bounds = jnp.linspace(0, inp.num_rows, nblocks + 1).astype(jnp.int64)
            chosen = jax.random.uniform(key, (nblocks,)) < frac
            row_block = jnp.searchsorted(bounds[1:], jnp.arange(inp.num_rows), side="right")
            mask = chosen[jnp.clip(row_block, 0, nblocks - 1)]
        else:
            mask = jax.random.uniform(key, (inp.num_rows,)) < frac
        return inp.filter(mask)


@Executor.add_plugin_class
class DistributeByPlugin(BaseRelPlugin):
    """Parity: reference distributeby.py:15 — explicit hash re-shard.

    Single-device: a hash-clustered reorder (rows grouped by key hash), which
    is exactly what the multi-chip path needs per shard after its all_to_all.
    """

    class_name = "DistributeBy"

    def convert(self, rel: p.DistributeBy, executor) -> Table:
        (inp,) = self.assert_inputs(rel, 1, executor)
        cols = [executor.eval_expr(k, inp) for k in rel.keys]
        if inp.num_rows == 0:
            return inp
        gid, order, _ = factorize(key_arrays(cols))
        return inp.take(order)


@Executor.add_plugin_class
class ExplainPlugin(BaseRelPlugin):
    """Parity: reference explain.py (plan string result)."""

    class_name = "Explain"

    def convert(self, rel: p.Explain, executor) -> Table:
        if getattr(rel, "lint", False):
            # EXPLAIN LINT: static plan verifier findings (analysis/),
            # errors and doomed-rung warnings first, then shape/recompile
            # advisories — nothing executes
            from ....analysis import verify_plan

            verdict = verify_plan(rel.input, context=executor.context,
                                  collect_info=True)
            executor.context.metrics.inc("analysis.explain_lint")
            rows = verdict.format_rows()
            lines = np.array(rows, dtype=object)
        elif getattr(rel, "estimate", False):
            # EXPLAIN ESTIMATE: static cost & memory abstract interpreter
            # (analysis/estimator.py) — cardinality + byte intervals per
            # node and the whole-plan peak-bytes verdict; nothing executes
            from ....analysis import estimator

            est = estimator.estimate_plan(rel.input, context=executor.context)
            # report (not apply) the budget proofs so EXPLAIN shows which
            # compiled rungs execution would pre-skip
            est.rung_proofs = estimator.collect_rung_proofs(
                est, estimator.device_budget_bytes(executor.context.config))
            # profile feedback under the same family identity execution
            # uses, so EXPLAIN ESTIMATE shows the bounds the scheduler
            # actually packs with once the family has observed history
            from ....families import family_of

            fam = family_of(rel.input, executor.config,
                            metrics=executor.context.metrics)
            est = executor.context._feedback_estimate(rel.input, est, fam)
            executor.context.metrics.inc("analysis.explain_estimate")
            lines = np.array(est.format_rows(), dtype=object)
        elif rel.analyze:
            # EXPLAIN ANALYZE: run the plan with per-node tracing, headed
            # by the query-lifecycle stages (observability/spans.py) the
            # active trace collected so far — queue wait, parse, bind,
            # verify, estimate, per-rung compiles.  The execute stage is
            # still open while this renders (the report IS the query's
            # result), so it prints as "(open)"; the complete trace stays
            # downloadable at /v1/trace/{qid} after the query finishes.
            import json as _json

            from ....observability import QueryTrace, current_trace
            from ...executor import Executor

            traced = Executor(executor.context, trace=True)
            traced.execute(rel.input)
            root = traced.tracer.root
            tr = current_trace()
            if tr is not None and root is not None:
                tr.attach_node_tree(root)
            if getattr(rel, "fmt_json", False):
                if tr is None:
                    # tracing disabled: export the node tree alone so
                    # FORMAT JSON still yields a loadable Chrome trace
                    tr = QueryTrace(sql="EXPLAIN ANALYZE")
                    tr.attach_node_tree(root)
                lines = np.array([_json.dumps(tr.to_chrome_trace())],
                                 dtype=object)
            else:
                out = []
                if tr is not None:
                    out.extend(tr.format_lines())
                    out.append("")
                text = root.format() if root else ""
                out.extend(text.split("\n"))
                lines = np.array(out, dtype=object)
        else:
            lines = np.array(rel.input.explain().split("\n"), dtype=object)
        col = rel.schema[0].name if rel.schema else "PLAN"
        return Table({col: Column.from_numpy(lines)}, len(lines))
