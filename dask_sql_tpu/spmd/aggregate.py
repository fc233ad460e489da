"""spmd_aggregate: the sharded compiled scan->aggregate rung.

One `shard_map` SPMD executable per plan family: every device computes the
radix-gid partial aggregation states over ITS row block (the same traced
body as the single-chip `CompiledAggregate` — same masks, same radix plan,
same finalize arithmetic), and the per-shard partial states tree-reduce
across the mesh with `psum`/`pmin`/`pmax` collectives before the shared
finalize assembles outputs.  This is the reference engine's
partial->shuffle->final aggregation tree (Dask `split_out`, PAPER.md layer
4) expressed as XLA collectives (TQP arXiv:2203.01877), compiled into ONE
native program per family (Flare arXiv:1703.08219).

Because the cross-device combine happens on the RAW reduction states (sums,
counts, mins, maxes) and the finalize code is literally shared with the
single-chip rung, results are bit-equal to the unsharded path whenever the
partial sums are exact (always for ints/counts/min/max; for floats up to
addition-order rounding).  Each shard runs the segment sum the single-chip
rung would (`choose_segsum_impl`: the blocked one-hot matmul on a TPU for
small group domains, the scatter elsewhere).  ParamRefs stay traced runtime
arguments, so the second literal variant of a family pays zero foreground
compiles, and in scatter mode (`batchable`) the family batcher's stacked
launches vmap over the leading parameter axis of the same SPMD program.
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from ..columnar.table import Table
from ..parallel.mesh import AXIS
from ..physical.compiled import (
    CompiledAggregate,
    SegmentReducer,
    _extract_chain,
    _Unsupported,
    fetch_packed,
)
from ..physical.programs import ProgramCache
from ..planner import plan as p
from .core import (ColumnSpmdWrap, count_launch, launch_attrs, mesh_key,
                   mesh_of_sharded_table, raise_rung_fault, rung_enabled)

logger = logging.getLogger(__name__)


class SpmdSegmentReducer(SegmentReducer):
    """SegmentReducer whose reductions combine across the mesh.

    Every raw state is a plain per-shard sum, min or max, so it combines
    with one collective and `segment_agg_outputs`' finalize phase runs on
    GLOBAL states, byte-for-byte the single-chip code path.  In 'scatter'
    mode each segment sum/count psums as it is registered; in 'matmul' mode
    (what `auto` resolves to on a TPU for small group domains) the float
    sums and counts are deferred into ONE ``[domain, K]`` float64 array of
    sums, which `finish()` psums in one all-reduce.  `auto` never resolves
    to 'pallas'; set by hand it would take the deferred path too, which no
    test and no cell runs on a mesh (ROADMAP D1, S3g).  Integer sums, min
    and max scatter in every mode and psum / pmin / pmax where they are
    registered."""

    def __init__(self, gid, domain: int, n_rows: int, mode: str = "scatter"):
        super().__init__(gid, domain, mode, n_rows)

    @property
    def total_rows(self) -> int:
        return self.n_rows * jax.lax.axis_size(AXIS)

    def finish(self):
        super().finish()
        if self._out is not None:
            self._out = jax.lax.psum(self._out, AXIS)

    def _scatter(self, x):
        return jax.lax.psum(super()._scatter(x), AXIS)

    def seg_min(self, contrib):
        kind, red = super().seg_min(contrib)
        return (kind, jax.lax.pmin(red, AXIS))

    def seg_max(self, contrib):
        kind, red = super().seg_max(contrib)
        return (kind, jax.lax.pmax(red, AXIS))


class SpmdAggregate(CompiledAggregate):
    """CompiledAggregate over a mesh-sharded table: the same traced kernel
    body, mapped per-shard with explicit collective state combines."""

    def __init__(self, mesh, agg: p.Aggregate, table: Table, scan, filters,
                 group_exprs, agg_exprs, config=None):
        self.mesh = mesh
        # segsum_mode is chosen as on one chip (`choose_segsum_impl`: config,
        # platform, group domain); config=None keeps "scatter"
        super().__init__(agg, table, scan, filters, group_exprs, agg_exprs,
                         config=config)
        names = table.column_names
        self._wrap = ColumnSpmdWrap(
            self._fn_raw, mesh,
            valid_present=[table.columns[n].validity is not None
                           for n in names],
            has_row_valid=table.row_valid is not None,
            n_params=0,  # rebuilt lazily once the param arity is known
            out_specs=(jax.sharding.PartitionSpec(None, None)),
            check_vma=False)
        self._wraps: Dict[int, ColumnSpmdWrap] = {0: self._wrap}
        self._batched_jit = None

    def _make_reducer(self, gid, domain: int, n_rows: int) -> SegmentReducer:
        return SpmdSegmentReducer(gid, domain, n_rows, self.segsum_mode)

    def _wrap_for(self, n_params: int) -> ColumnSpmdWrap:
        w = self._wraps.get(n_params)
        if w is None:
            base = self._wraps[0]
            w = ColumnSpmdWrap(
                self._fn_raw, self.mesh, base.valid_present,
                base.has_row_valid, n_params,
                out_specs=(jax.sharding.PartitionSpec(None, None)),
                check_vma=False)
            self._wraps[n_params] = w
        return w

    def run(self, table: Optional[Table] = None, params: Tuple = ()) -> Table:
        from ..observability import timed_jit_call

        table = table if table is not None else self.table
        datas = [table.columns[n].data for n in table.column_names]
        valids = [table.columns[n].validity for n in table.column_names]
        wrap = self._wrap_for(len(params))
        args = wrap.pack_args(datas, valids, table.row_valid, params)
        packed = timed_jit_call(
            "spmd_aggregate", wrap.jitted, *args, may_compile=not self._warm,
            launch_attrs=launch_attrs(self.mesh, table.padded_rows,
                                      self.segsum_mode))
        self._warm = True
        tags = self._pack_tags
        host, present = fetch_packed(packed, self.domain)
        return self._decode(host, present, tags)

    def run_batched(self, table: Table, params_list: List[Tuple]
                    ) -> List[Table]:
        """Family-batched stacked launch: the member literal vectors stack
        along a new leading axis and ONE vmapped SPMD program evaluates
        every member over a single sharded scan."""
        from ..families import stack_params
        from ..observability import timed_jit_call
        from ..utils import d2h_fetch

        n = len(params_list)
        stacked, bucket = stack_params(params_list)
        wrap = self._wrap_for(len(params_list[0]))
        if self._batched_jit is None:
            self._batched_jit = jax.jit(
                jax.vmap(wrap.mapped, in_axes=(None, None, None, 0)))
        datas = [table.columns[n_].data for n_ in table.column_names]
        valids = [table.columns[n_].validity for n_ in table.column_names]
        args = wrap.pack_args(datas, valids, table.row_valid, stacked)
        packed = timed_jit_call(
            "spmd_aggregate", self._batched_jit, *args,
            may_compile=bucket not in self._warm_batch,
            launch_attrs=launch_attrs(self.mesh, table.padded_rows,
                                      self.segsum_mode))
        self._warm_batch.add(bucket)
        tags = self._pack_tags
        with d2h_fetch(nbytes=int(packed.nbytes)):
            # (bucket, R, domain)
            host_all = np.asarray(jax.device_get(packed))
        out = []
        for b in range(n):
            host = host_all[b]
            present = np.nonzero(host[0] != 0.0)[0]
            out.append(self._decode(host[:, present], present, tags))
        return out


PROGRAMS = ProgramCache("spmd_aggregate", 16)


def try_spmd_aggregate(rel: p.Aggregate, executor) -> Optional[Table]:
    """Attempt the sharded SPMD path for an Aggregate subtree; None falls
    down the ladder (single-chip compiled rungs, then the all_to_all
    collectives engine)."""
    if not executor.config.get("sql.compile", True):
        return None
    if not rung_enabled(executor.config, "spmd_aggregate"):
        return None
    chain = _extract_chain(rel)
    if chain is None:
        return None
    scan, filters, group_exprs, agg_exprs = chain
    try:
        ctx = executor.context
        from ..datacontainer import LazyParquetContainer

        dc = ctx.schema[scan.schema_name].tables.get(scan.table_name)
        if dc is None or isinstance(dc, LazyParquetContainer):
            return None
        table = executor.get_table(scan.schema_name, scan.table_name)
        if scan.projection is not None:
            table = table.select(scan.projection)
        mesh = mesh_of_sharded_table(table)
        if mesh is None:
            return None
        from .. import families

        pz = families.pipeline_parameterizer(executor.config)
        filters = [pz.rewrite(f) for f in filters]
        agg_exprs = [pz.rewrite_agg(a) for a in agg_exprs]
        params = pz.params
        family = (
            mesh_key(mesh),
            scan.schema_name, scan.table_name,
            tuple(scan.projection or ()),
            tuple(str(f) for f in filters),
            tuple(str(e) for e in group_exprs),
            tuple(str(a) for a in agg_exprs),
            # the configured segment-sum mode, as on one chip: two modes
            # are two programs and two families
            str(executor.config.get("sql.compile.segsum", "auto")),
        )
        bucket = (dc.uid, table.num_rows, table.padded_rows)

        def construct():
            obj = SpmdAggregate(mesh, rel, table, scan, filters,
                                group_exprs, agg_exprs, executor.config)
            obj.table = None  # never pin the construction table's HBM
            return obj

        compiled, _ = PROGRAMS.get_or_build(
            ctx, family, bucket, construct,
            warm=lambda obj: obj.run(table, params), params=params)
        if compiled is None:
            return None  # deferred to the background compiler
        count_launch(ctx.metrics, mesh, table.num_rows,
                     compiled.segsum_mode)
        from ..resilience import faults

        faults.maybe_inject("oom", executor.config)
        return PROGRAMS.run(
            ctx, family, bucket, compiled, params,
            solo=lambda: compiled.run(table, params),
            batched=lambda members: compiled.run_batched(table, members))
    except _Unsupported as e:
        logger.debug("spmd aggregate unsupported: %s", e)
        return None
    except (ValueError, TypeError, NotImplementedError) as e:
        # a fault in the wrap (not an ineligible shape): a counted step
        # down — the single-chip rungs below still answer
        raise_rung_fault("spmd_aggregate", e)
