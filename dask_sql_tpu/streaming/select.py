"""streamed_select: chunked root select chains, survivors in row order.

Reuses `CompiledSelect` (physical/compiled_select.py) wholesale: ONE
object is built against the full table (so string dictionaries and
parameter slots are table-global), and each partition launch runs its
mask + per-pow2-bucket gather kernels over a fixed-shape chunk — jit
specializes once per chunk shape, so N launches share the executables and
the second streamed run of the family pays zero foreground compiles (the
same per-bucket re-specialization budget the SPMD select rung accepts).

Survivor tables land host-side per chunk and concatenate in ascending
chunk order; within a chunk the sized-nonzero gather already yields
ascending row indices, so the concatenation IS the global row order the
unconstrained single-launch path produces.  Sort/limit windows are global
row properties a chunk cannot see — plans carrying them are never routed
here (streaming/plan.py declines them at decision time).
"""
from __future__ import annotations

import logging
from typing import List, Optional

from ..columnar.table import Table
from ..observability import trace_event
from ..physical.compiled import _Unsupported
from ..physical.compiled_select import (CompiledSelect, _extract,
                                        select_family)
from ..physical.programs import ProgramCache
from .partition import slice_chunk
from .plan import StreamDecision
from .runner import drive_partitions

logger = logging.getLogger(__name__)


class _StreamableSelect(CompiledSelect):
    """CompiledSelect with PER-SHAPE mask-kernel warm tracking.

    The parent's ``_mask_warm`` is a single boolean — correct for its own
    rung, where one object only ever sees one table shape.  Streamed
    execution feeds the same object different chunk shapes after a
    mid-stream repartition; the recompile for the new shape must run with
    ``may_compile=True`` so the compile watchdog
    (``resilience.compile_timeout_ms``) covers exactly the OOM-recovery
    path (the aggregate rung's ``_warm_shapes`` set, mirrored here).  The
    hint is computed LOCALLY per call, never by mutating a shared flag —
    cached objects serve concurrent worker threads, and a write/read dance
    on shared state would let one thread's warm shape mark another
    thread's cold compile unwatched."""

    _RUNG = "streamed_select"  # compiles attribute to THIS rung's metrics

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._warm_shapes: set = set()

    def run(self, table=None, params=()):
        from ..observability import timed_jit_call
        from ..utils import d2h_fetch

        t = table if table is not None else self.table
        shape = t.padded_rows
        datas = tuple(t.columns[n].data for n in t.column_names)
        valids = tuple(t.columns[n].validity for n in t.column_names)
        mask, count_dev = timed_jit_call(
            self._RUNG, self._mask_fn, datas, valids, t.row_valid,
            tuple(params), may_compile=shape not in self._warm_shapes)
        self._warm_shapes.add(shape)
        with d2h_fetch(nbytes=int(count_dev.nbytes)):
            count = int(count_dev)
        return self._finish(datas, valids, mask, count, tuple(params))


PROGRAMS = ProgramCache("streamed_select", 8)


def reset_cache() -> None:
    """Tests: drop cached streamed select executables."""
    PROGRAMS.clear()


def try_streamed_select(root, executor) -> Optional[Table]:
    """The streamed_select ladder rung (physical/executor.py execute_root):
    fires only for plans the admission layer routed to streaming (this
    execution's ``executor.stream_decisions`` entry); None declines down
    the ladder."""
    decision: Optional[StreamDecision] = \
        executor.stream_decisions.get(id(root))
    if decision is None or decision.kind != "select":
        return None
    config = executor.config
    if not config.get("serving.stream.enabled", True):
        return None
    if not config.get("sql.compile", True) \
            or not config.get("sql.compile.select", True):
        return None
    got = _extract(root)
    if got is None:
        return None
    scan, upper_filters, proj, sort_keys, sort_fetch, limit, inner_limit = got
    if sort_keys is not None or limit is not None or inner_limit is not None:
        return None  # global row windows: not a chunk-local shape
    ctx = executor.context
    # -- eligibility + executable build: construction-time ineligibility
    # re-sheds with the gate's 429 (see streaming/aggregate.py) ----------
    try:
        dc = ctx.schema[scan.schema_name].tables.get(scan.table_name)
        if dc is None:
            return None
        table = executor.get_table(scan.schema_name, scan.table_name)
        if scan.projection is not None:
            table = table.select(scan.projection)
        if not table.column_names or table.row_valid is not None:
            return None
        from .. import families

        pz = families.pipeline_parameterizer(config)
        p_upper = [pz.rewrite(f) for f in upper_filters]
        p_scan_flts = [pz.rewrite(f) for f in scan.filters]
        p_exprs = [pz.rewrite(e) for e in proj.exprs]
        params = pz.params
        # sort and limit windows are None here (declined above)
        family = select_family(scan, p_upper, p_scan_flts, p_exprs, sort_keys,
                               sort_fetch, limit, inner_limit)
        bucket = (dc.uid, table.num_rows)

        def construct():
            obj = _StreamableSelect(table, scan, p_upper, p_scan_flts, proj,
                                    p_exprs, None, None, None, None, params)
            obj.table = None
            return obj

        # no `warm`: this rung never defers to the background compiler
        compiled, _ = PROGRAMS.get_or_build(ctx, family, bucket, construct,
                                            params=params)
    except (_Unsupported, ValueError, TypeError, NotImplementedError) as e:
        from .plan import shed_ineligible

        shed_ineligible(decision, ctx.metrics, reason=str(e))
        raise  # unreachable: shed_ineligible always raises
    ctx.metrics.inc("serving.stream.queries")
    # -- pipelined partition drive (ladder semantics preserved) -----------
    parts: List[Table] = []

    def launch(lo: int, chunk_rows: int) -> None:
        chunk = slice_chunk(table, lo, chunk_rows)
        out = compiled.run(chunk, params)
        if out.num_rows:
            parts.append(out)

    launches = drive_partitions(executor, decision, launch,
                                "streamed_select")
    trace_event("rung:streamed_select", rung="streamed_select",
                partitions=launches, chunk_rows=decision.chunk_rows)
    if not parts:
        return _empty_like(compiled)
    return Table.concat(parts)


def _empty_like(compiled: CompiledSelect) -> Table:
    """Zero-survivor result with the pipeline's output schema."""
    cols, valids = compiled._decode_packed(None, 0)
    return compiled._assemble(cols, valids, 0)
