"""streamed_aggregate: the morsel-shaped partial-state aggregation rung.

`CompiledAggregate` compiles a whole scan->filter->aggregate subtree into
one kernel whose output is the FINALIZED group table — which is exactly
wrong for partitioned execution: an avg/var finalized per chunk cannot be
combined.  This subclass keeps the parent's entire traced front half (the
shared `_trace_prelude` mask/gid body, the radix plan computed over the
FULL table so group ids are globally consistent across chunks, the same
`SegmentReducer` registrations) but emits the RAW segment reduction states
— hit counts, sums, counts, min/max contributions — as the kernel output.

Partition states then combine across the time axis with the same
elementwise sum/min/max algebra the SPMD rungs apply across the mesh axis
(spmd/aggregate.py psums/pmins/pmaxes the identical states): one combine
machinery, two axes.  The finalize arithmetic (avg = s/n, variance from
(n, s, s2), NULL = zero contributing rows) runs ONCE over the combined
global states and decodes through the parent's `_decode` — so a streamed
result is byte-identical to the single-launch rung whenever the partial
sums are exact (always for ints/counts/min/max; floats up to
addition-order rounding, the same caveat the SPMD rung carries).

One executable serves every chunk: chunks share a shape (partition.py), so
after the first launch every later launch — and every later query of the
family, ParamRefs included — replays the warm executable with zero
foreground compiles.  A repartition (halved chunks after an absorbed OOM)
re-specializes once per new shape.
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.table import Table
from ..observability import trace_event
from ..physical.compiled import (
    CompiledAggregate,
    SegmentReducer,
    _extract_chain,
    _TableMeta,
    _TraceEval,
    _Unsupported,
    agg_argument,
    pack_flat,
)
from ..physical.programs import ProgramCache
from ..planner import plan as p
from .partition import slice_chunk
from .plan import StreamDecision
from .runner import drive_partitions

logger = logging.getLogger(__name__)

#: elementwise combine per state kind — the time-axis twin of the SPMD
#: rung's psum/pmin/pmax collectives
_COMBINE = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


class StreamedAggregate(CompiledAggregate):
    """CompiledAggregate whose kernel emits combinable partial states.

    Constructed against the FULL table (the radix plan's integer-key
    bounds must cover every chunk), executed against fixed-shape chunks:
    `jax.jit` specializes the traced body per input shape, so all chunks
    of one partitioning share one executable."""

    def __init__(self, agg: p.Aggregate, table: Table, scan, filters,
                 group_exprs, agg_exprs):
        # combine ops / finalize plan are filled by _build (called from the
        # parent constructor); config=None pins segsum_mode "scatter": the
        # matmul state adds across partitions too (the sharded rung psums
        # it, PR 30), but this rung has no cell to show what un-pinning it
        # buys, so it stays pinned (ROADMAP D1)
        self._combine_ops: List[str] = []
        self._finalize_plan: List[Tuple[str, List[int]]] = []
        super().__init__(agg, table, scan, filters, group_exprs, agg_exprs,
                         config=None)
        #: chunk shapes this executable already compiled for (the compile
        #: watchdog / zero-compile-span accounting hint)
        self._warm_shapes: set = set()

    def _build(self):
        ev = _TraceEval(_TableMeta(self.table))
        agg_exprs = self.agg_exprs
        domain = self.domain

        # static state layout: index 0 is the per-group hit count (group
        # presence across ALL partitions), then each aggregate's states in
        # order.  Decided before tracing so combine/finalize never depend
        # on trace-time objects.
        ops: List[str] = ["sum"]
        plan: List[Tuple[str, List[int]]] = []
        for a in agg_exprs:
            if a.func in ("count", "count_star"):
                plan.append((a.func, [_push(ops, "sum")]))
            elif a.func in ("sum", "avg"):
                plan.append((a.func, [_push(ops, "sum"),
                                      _push(ops, "sum")]))
            elif a.func in ("min", "max"):
                plan.append((a.func, [_push(ops, a.func),
                                      _push(ops, "sum")]))
            else:  # variance family: (s1, s2, count)
                plan.append((a.func, [_push(ops, "sum"), _push(ops, "sum"),
                                      _push(ops, "sum")]))
        self._combine_ops = ops
        self._finalize_plan = plan

        def fn(datas, valids, row_valid, params=()):
            slots, sel, gid, nr = self._trace_prelude(ev, datas, valids,
                                                      row_valid, params)
            reducer = SegmentReducer(gid, domain, "scatter", nr)
            arg_cache: Dict[Tuple, Tuple] = {}
            handles: List[Tuple[str, object]] = [
                ("cnt", reducer.count(sel))]
            for a in agg_exprs:
                ad, v = agg_argument(ev, slots, a, sel, arg_cache)
                cnt_h = reducer.count(v)
                if a.func in ("count", "count_star"):
                    handles.append(("cnt", cnt_h))
                    continue
                if a.func in ("sum", "avg"):
                    if ad.dtype == jnp.bool_:
                        h = reducer.sum_int(ad.astype(jnp.int32), v)
                    elif jnp.issubdtype(ad.dtype, jnp.integer):
                        h = reducer.sum_int(ad, v)
                    else:
                        h = reducer.sum_float(ad, v)
                    handles.append(("raw", h))
                    handles.append(("cnt", cnt_h))
                    continue
                if a.func in ("min", "max"):
                    if ad.dtype == jnp.bool_:
                        ad = ad.astype(jnp.int32)
                    if jnp.issubdtype(ad.dtype, jnp.floating):
                        fill = jnp.array(
                            jnp.inf if a.func == "min" else -jnp.inf,
                            dtype=ad.dtype)
                    else:
                        info = jnp.iinfo(ad.dtype)
                        fill = jnp.array(
                            info.max if a.func == "min" else info.min,
                            dtype=ad.dtype)
                    contrib = jnp.where(v, ad, fill)
                    h = (reducer.seg_min if a.func == "min"
                         else reducer.seg_max)(contrib)
                    handles.append(("raw", h))
                    handles.append(("cnt", cnt_h))
                    continue
                # variance family
                x = ad.astype(jnp.float64)
                handles.append(("raw", reducer.sum_float(x, v)))
                handles.append(("raw", reducer.sum_float(x * x, v)))
                handles.append(("cnt", cnt_h))
            reducer.finish()
            states = []
            for kind, h in handles:
                arr = reducer.get(h)
                if kind == "cnt":
                    # counts combine across an unbounded number of chunks:
                    # widen to int64 so the running total can never wrap
                    arr = arr.astype(jnp.int64)
                states.append(arr)
            return tuple(states)

        return fn

    # ----------------------------------------------------------- execution
    def run_partition(self, chunk: Table, params: Tuple = ()) -> Tuple:
        """Launch the morsel executable over one fixed-shape chunk; returns
        its raw partial-state tuple (device arrays, transfer-free)."""
        from ..observability import timed_jit_call

        datas = tuple(chunk.columns[n].data for n in chunk.column_names)
        valids = tuple(chunk.columns[n].validity
                       for n in chunk.column_names)
        shape = datas[0].shape[0] if datas else chunk.padded_rows
        states = timed_jit_call(
            "streamed_aggregate", self._fn, datas, valids, chunk.row_valid,
            tuple(params), may_compile=shape not in self._warm_shapes)
        self._warm_shapes.add(shape)
        return states

    def combine(self, acc: Optional[Sequence], states: Sequence) -> List:
        """Fold one partition's states into the running accumulator — the
        checkpointable partial-combine state a mid-stream recovery resumes
        from.  Elementwise on (domain,)-sized arrays: tiny, async, and
        identical in algebra to the SPMD collectives."""
        if acc is None:
            return list(states)
        return [_COMBINE[op](a, s)
                for op, a, s in zip(self._combine_ops, acc, states)]

    def finalize(self, acc: Sequence) -> Table:
        """Global finalize over the combined states: ONE host pull, the
        finalize arithmetic of `segment_agg_outputs` phase B in numpy, then
        the parent's `_decode` (group-key radix decode, output naming,
        zero-row global-aggregate semantics — literally shared code)."""
        from ..utils import d2h_fetch

        with d2h_fetch(nbytes=sum(int(x.nbytes) for x in acc)):
            host = [np.asarray(x) for x in jax.device_get(tuple(acc))]
        hit = host[0]
        flat: List[np.ndarray] = [hit != 0]

        def emit(d: np.ndarray, v: np.ndarray) -> None:
            flat.extend((d, v))

        for func, idxs in self._finalize_plan:
            # idxs are absolute state positions (index 0 is the hit count)
            st = [host[i] for i in idxs]
            if func in ("count", "count_star"):
                cnt = st[0]
                emit(cnt, np.ones_like(cnt, dtype=bool))
            elif func == "sum":
                s, cnt = st
                emit(s, cnt > 0)
            elif func == "avg":
                s, cnt = st
                emit(s.astype(np.float64) / np.maximum(cnt, 1), cnt > 0)
            elif func in ("min", "max"):
                red, cnt = st
                ok = cnt > 0
                emit(np.where(ok, red, np.zeros(1, dtype=red.dtype)), ok)
            else:  # variance family from (s1, s2, count)
                s1, s2, cnt = (st[0].astype(np.float64),
                               st[1].astype(np.float64), st[2])
                ddof = 1 if func.endswith("samp") else 0
                mean = s1 / np.maximum(cnt, 1)
                var = (np.maximum(s2 - cnt * mean * mean, 0.0)
                       / np.maximum(cnt - ddof, 1))
                out = np.sqrt(var) if func.startswith("stddev") else var
                emit(out, cnt > ddof)
        tags: List[Tuple[str, np.dtype]] = []
        matrix = pack_flat(flat, tags, xp=np)
        present = np.nonzero(hit != 0)[0]
        return self._decode(matrix[:, present], present, tags)


def _push(ops: List[str], op: str) -> int:
    ops.append(op)
    return len(ops) - 1


# keyed like the compiled aggregate cache plus nothing chunk-specific: ONE
# object serves every partitioning of a family (jit re-specializes per chunk
# shape), so the second streamed run of a family replays warm executables
PROGRAMS = ProgramCache("streamed_aggregate", 8)


def reset_cache() -> None:
    """Tests: drop cached morsel executables (warm-shape state included)."""
    PROGRAMS.clear()


def try_streamed_aggregate(rel: p.Aggregate, executor) -> Optional[Table]:
    """The streamed_aggregate ladder rung: fires only for plans the
    admission layer routed to streaming (this execution's
    ``executor.stream_decisions`` entry); None declines down the ladder
    like every rung."""
    decision: Optional[StreamDecision] = \
        executor.stream_decisions.get(id(rel))
    if decision is None or decision.kind != "aggregate":
        return None
    config = executor.config
    if not config.get("serving.stream.enabled", True):
        return None
    if not config.get("sql.compile", True):
        return None
    chain = _extract_chain(rel)
    if chain is None:
        return None
    scan, filters, group_exprs, agg_exprs = chain
    ctx = executor.context
    # -- eligibility + morsel-executable build ----------------------------
    # construction-time ineligibility (a shape the static routing walk
    # could not rule out — e.g. an integer radix span only device data
    # reveals, or a trace-unsupported filter expression) RE-SHEDS with the
    # gate's 429: the alternative, declining down the ladder, runs the
    # full provably-over-budget working set single-launch
    try:
        dc = ctx.schema[scan.schema_name].tables.get(scan.table_name)
        if dc is None:
            return None
        table = executor.get_table(scan.schema_name, scan.table_name)
        if scan.projection is not None:
            table = table.select(scan.projection)
        if table.row_valid is not None:
            return None  # padded/sharded storage: not this rung's shape
        from .. import families

        pz = families.pipeline_parameterizer(config)
        filters = [pz.rewrite(f) for f in filters]
        agg_exprs = [pz.rewrite_agg(a) for a in agg_exprs]
        params = pz.params
        family = (
            scan.schema_name, scan.table_name,
            tuple(scan.projection or ()),
            tuple(str(f) for f in filters),
            tuple(str(e) for e in group_exprs),
            tuple(str(a) for a in agg_exprs),
        )
        bucket = (dc.uid, table.num_rows)

        def construct():
            obj = StreamedAggregate(rel, table, scan, filters, group_exprs,
                                    agg_exprs)
            obj.table = None  # never pin the construction table's HBM
            return obj

        # no `warm`: this rung never defers to the background compiler
        compiled, _ = PROGRAMS.get_or_build(ctx, family, bucket, construct,
                                            params=params)
    except (_Unsupported, ValueError, TypeError, NotImplementedError) as e:
        from .plan import shed_ineligible

        shed_ineligible(decision, ctx.metrics, reason=str(e))
        raise  # unreachable: shed_ineligible always raises
    ctx.metrics.inc("serving.stream.queries")
    # -- pipelined partition drive ----------------------------------------
    # failures in here keep the ladder's semantics: transient errors retry,
    # degradable OOM repartitions/resumes, exhaustion degrades the rung
    acc: List[Optional[List]] = [None]

    def launch(lo: int, chunk_rows: int) -> None:
        chunk = slice_chunk(table, lo, chunk_rows)
        states = compiled.run_partition(chunk, params)
        acc[0] = compiled.combine(acc[0], states)

    launches = drive_partitions(executor, decision, launch,
                                "streamed_aggregate")
    trace_event("rung:streamed_aggregate", rung="streamed_aggregate",
                partitions=launches, chunk_rows=decision.chunk_rows)
    return compiled.finalize(acc[0])
