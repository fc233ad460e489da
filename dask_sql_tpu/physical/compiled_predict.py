"""Fused PREDICT: model inference in the SAME XLA executable as the scan.

The host path (physical/rel/custom/ml.py PredictModelPlugin) executes the
PREDICT input, pulls the whole table to pandas, calls ``model.predict`` on
numpy and re-uploads — a full mid-plan device round trip for the one query
shape the engine could not serve at device speed.  This module is the
``compiled_predict`` ladder rung that removes it (arXiv:2306.08367,
arXiv:2009.00524): the PREDICT input's ``scan -> filter* -> project``
body traces through the compiled-select machinery, and the registered
model — lowered to a tensor program by `dask_sql_tpu.inference` — applies
to the gathered survivor features INSIDE the same gather kernel.  One
executable, one packed d2h transfer carrying the input columns AND the
prediction column.

The family discipline extends to models: filter/projection literals
parameterize exactly as in compiled_select, and the model's weights enter
the kernel as TRACED RUNTIME ARGUMENTS appended after the family params —
the cache key (and the executable) bakes the model's *shape*
(``ModelProgram.shape_key``: tree count / padded depth / feature width),
never its values.  Retraining or ``CREATE OR REPLACE MODEL`` with the
same hyper-shape swaps weights with zero recompile, a second literal
variant reuses the executable outright, and the family batcher can stack
co-admitted same-family PREDICTs into one vmapped launch.

Degradation: any failure inside the rung steps down to the host predict
path through the ladder (per-(family, rung) breaker entity; fault site
``predict`` proves the step-down); models that cannot lower simply
decline here and keep today's behavior.
"""
from __future__ import annotations

import logging
from typing import List, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from ..columnar.dtypes import STRING_TYPES
from ..columnar.table import Table
from ..planner import plan as p
from ..planner.expressions import ColumnRef
from .compiled import PARAMS_SLOT, _Unsupported
from .compiled_select import (
    CompiledSelect,
    _extract,
    resolve_pipeline_inputs,
    select_family,
)
from .programs import ProgramCache

logger = logging.getLogger(__name__)


def root_has_predict(root) -> bool:
    """Cheap pre-check for execute_root: the rung is only worth attempting
    when the ROOT is a PredictModelNode (optionally under the binder's
    identity output projection)."""
    if isinstance(root, p.PredictModelNode):
        return True
    return isinstance(root, p.Projection) \
        and isinstance(root.input, p.PredictModelNode)


def _extract_predict(root):
    """Match ``[Projection(pure refs)]? PredictModelNode <select chain>``;
    None otherwise.  The outer projection (the binder's ``SELECT *``
    rendering) applies host-side on the decoded result."""
    outer = None
    node = root
    if isinstance(node, p.Projection):
        if not all(isinstance(e, ColumnRef) and type(e) is ColumnRef
                   for e in node.exprs):
            return None
        outer = node
        node = node.input
    if not isinstance(node, p.PredictModelNode):
        return None
    inner = _extract(node.input)
    if inner is None:
        return None
    return outer, node, inner


class CompiledPredict(CompiledSelect):
    """One fused scan->filter->project->predict pipeline.

    Extends CompiledSelect through its ``_extra_pack_outputs`` seam: the
    gather kernel stacks the training-column expressions into the feature
    matrix and applies the model program's pure ``apply`` under the same
    trace.  Model params ride the tail of the runtime parameter vector
    (after the family's ParamRef slots), so they are traced arguments —
    swapping weights never retraces."""

    _RUNG = "compiled_predict"

    def __init__(self, table: Table, scan, upper_filters, scan_filters,
                 proj, proj_exprs, sort_keys, sort_fetch, limit, inner_limit,
                 family_params, program, feature_slots: List[int],
                 target_field):
        import dataclasses

        if program.output != "vector":
            raise _Unsupported(
                f"{program.kind} program emits a matrix, not a column")
        for i in feature_slots:
            if proj.schema[i].sql_type in STRING_TYPES:
                raise _Unsupported("string-typed model feature")
        # keep structure only: the program's `apply` closure and meta.
        # Holding the committed params here would pin one stale weight
        # copy in the pipeline cache per retrain (launches always pass the
        # CURRENT program's params through the runtime vector).
        self._program = dataclasses.replace(program, params=())
        self._feature_exprs = [proj_exprs[i] for i in feature_slots]
        self._param_base = len(family_params)
        super().__init__(table, scan, upper_filters, scan_filters, proj,
                         proj_exprs, sort_keys, sort_fetch, limit,
                         inner_limit,
                         tuple(family_params) + tuple(program.params))
        # the appended prediction column: decoded from the extra packed
        # rows the _extra_pack_outputs seam emitted during tracing
        self.out_meta.append((target_field.name, target_field.sql_type,
                              None))

    def _extra_pack_outputs(self, ev, slots, bucket):
        feats = []
        for e in self._feature_exprs:
            d, v = ev.eval(e, slots)
            if v is not None:
                # a NULL-able feature must not silently feed the sentinel
                # value under the mask into the model: the host tier
                # surfaces it (NaN -> sklearn raises a structured error),
                # so the fused rung declines at construction and matches
                raise _Unsupported("nullable model feature")
            if d.ndim == 0:
                d = jnp.broadcast_to(d, (bucket,))
            feats.append(d.astype(jnp.float64))
        X = jnp.stack(feats, axis=1)
        model_params = tuple(slots[PARAMS_SLOT][self._param_base:])
        pred = self._program.apply(model_params, X)
        return ((pred.astype(jnp.float64), None),)

    def _batched_param_split(self) -> Optional[int]:
        """Map only the family literal prefix over the batch axis: every
        member of a batch group references the same registered model (the
        cache key bakes model name + shape), so the weight tail rides
        unmapped — stacking the committed device matrices would d2h-copy
        them through ``np.stack`` and duplicate them per batch slot for a
        mask kernel that never reads them.  The leader's weight tail
        serves the whole group (members racing a retrain see the weights
        current at launch, same as solo launches do)."""
        return self._param_base


class PredictFamily(NamedTuple):
    """What shapes a fused PREDICT program: the model by NAME and SHAPE
    (never its weights: a retrain of the same shape rides the same
    executable) and the select chain under it."""
    schema: str
    model: str
    shape_key: Tuple
    feature_slots: Tuple[int, ...]
    select: Tuple


PROGRAMS = ProgramCache("compiled_predict", 16)


def drop_model_pipelines(context, schema_name: str, name: str) -> None:
    """Evict every cached pipeline built for a model (DROP MODEL, via
    inference.invalidate): a dropped model's executables must not outlive
    its ledger entry.  Matching ignores the table's uid, so a same-named
    model in ANOTHER context over-evicts (costs that context one
    recompile, never correctness)."""
    PROGRAMS.evict(context, lambda family, bucket:
                   family.schema == schema_name and family.model == name)


def try_compiled_predict(root, executor) -> Optional[Table]:
    """Attempt the fused one-executable PREDICT path; None steps down to
    the host predict (the eager PredictModelPlugin)."""
    config = executor.config
    if not config.get("sql.compile.predict", True) \
            or not config.get("sql.compile", True):
        return None
    got = _extract_predict(root)
    if got is None:
        return None
    outer, predict, inner = got
    scan, upper_filters, proj, sort_keys, sort_fetch, limit, inner_limit \
        = inner
    ctx = executor.context
    try:
        schema_name, model_name = ctx._table_schema_name(predict.model_name)
        if model_name not in ctx.schema[schema_name].models:
            return None  # host path raises the structured not-found error
        model, training_columns = ctx.get_model(schema_name, model_name)
        from .. import inference

        program, _reason = inference.program_for(ctx, schema_name,
                                                 model_name, model,
                                                 commit=True)
        if program is None or program.output != "vector":
            return None  # decline verdict: today's host path serves
        if program.meta.get("features") not in (None,
                                                len(training_columns)):
            return None  # stale training-column mismatch: host path errors
        proj_names = [f.name for f in proj.schema]
        try:
            feature_slots = [proj_names.index(col)
                             for col in training_columns]
        except ValueError:
            return None  # missing feature column: host path raises
        # shared eligibility + family parameterization (compiled_select):
        # literals in the PREDICT input become runtime parameters, so
        # every literal variant — and every retrain of the same model
        # shape — shares ONE executable
        resolved = resolve_pipeline_inputs(scan, upper_filters, proj,
                                           executor)
        if resolved is None:
            return None
        dc, table, p_upper, p_scan_flts, p_exprs, params = resolved
        family = PredictFamily(
            schema_name, model_name, program.shape_key, tuple(feature_slots),
            select_family(scan, p_upper, p_scan_flts, p_exprs, sort_keys,
                          sort_fetch, limit, inner_limit))
        bucket = (dc.uid, table.num_rows, table.padded_rows)
        target_field = predict.schema[-1]
        # the CURRENT program's params every launch: a swapped model rides
        # the same executable with fresh (same-shaped) weights
        run_params = tuple(params) + tuple(program.params)

        def construct():
            obj = CompiledPredict(table, scan, p_upper, p_scan_flts, proj,
                                  p_exprs, sort_keys, sort_fetch, limit,
                                  inner_limit, params, program,
                                  feature_slots, target_field)
            obj.table = None  # never pin the construction table's HBM
            return obj

        # bucket growth/replacement of a SEEN family recompiles on the
        # background thread (this query serves on the host tier this once)
        compiled, _ = PROGRAMS.get_or_build(
            ctx, family, bucket, construct,
            warm=lambda obj: obj.run(table, run_params), params=params)
        if compiled is None:
            return None
        from ..observability import trace_event
        from ..resilience import faults

        faults.maybe_inject("oom", config)
        # batched only when the FAMILY has literals to stack (the weight
        # tail alone is no reason to rendezvous)
        result = PROGRAMS.run(
            ctx, family, bucket, compiled, run_params,
            solo=lambda: compiled.run(table, run_params),
            batched=(lambda members: compiled.run_batched(table, members))
            if params else None)
        if compiled.has_encoded:
            ctx.metrics.inc("columnar.encoding.late_rows", result.num_rows)
        if outer is not None:
            result = _apply_outer_projection(outer, result)
        ctx.metrics.inc("inference.predict.compiled")
        trace_event("rung:compiled_predict", rung="compiled_predict",
                    model=f"{schema_name}.{model_name}",
                    model_kind=program.kind)
        return result
    except _Unsupported as e:
        logger.debug("compiled predict unsupported: %s", e)
        return None
    except (ValueError, TypeError, NotImplementedError) as e:
        # a mis-shaped trace must never sink the query — the host predict
        # path is always correct
        logger.debug("compiled predict declined: %s", e)
        return None


def _apply_outer_projection(outer: p.Projection, result: Table) -> Table:
    """Host-side application of the binder's pure-ref output projection
    over the decoded fused result (column pick / rename only)."""
    from .rel.base import unique_names

    names = unique_names([f.name for f in outer.schema])
    inner_names = result.column_names
    cols = {}
    for uname, e in zip(names, outer.exprs):
        cols[uname] = result.columns[inner_names[e.index]]
    return Table(cols, result.num_rows)
