"""spmd_join_aggregate: the sharded compiled scan->joins->aggregate rung.

The probe table stays row-sharded across the mesh; every build side is
SMALL (post-filter dimension tables) and broadcasts — its value-indexed LUT
and used columns replicate to every device, so each shard probes its own
row block with plain gathers (the reference engine's broadcast join,
`sql.join.broadcast`, as an SPMD program).  Partial aggregation states then
tree-reduce across the mesh with psum/pmin/pmax exactly as
`spmd_aggregate` does — the traced body is the single-chip
`CompiledJoinAggregate` kernel, so join semantics, radix plans and finalize
arithmetic are shared, not re-implemented.

Build sides larger than ``parallel.spmd.broadcast_rows`` decline this rung:
the all_to_all hash-shuffle engine (`parallel/dist_plan.py`,
`dist_inner_pairs`) remains the path for big-big joins.
"""
from __future__ import annotations

import logging
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..columnar.table import Table
from ..parallel.mesh import AXIS
from ..physical.compiled import (
    SegmentReducer,
    _Unsupported,
    check_agg_static_support,
    fetch_packed,
)
from ..physical.compiled_join import (
    CompiledJoinAggregate,
    _extract,
    _plan_nodes,
)
from ..physical.programs import ProgramCache
from ..planner import plan as p
from .aggregate import SpmdSegmentReducer
from .core import (count_launch, launch_attrs, mesh_key,
                   mesh_of_sharded_table, raise_rung_fault, rung_enabled)

logger = logging.getLogger(__name__)


class SpmdJoinAggregate(CompiledJoinAggregate):
    """CompiledJoinAggregate whose probe side shards over the mesh and
    whose aggregation states combine with collectives."""

    def __init__(self, mesh, rel, ext, group_exprs, agg_exprs, probe_table,
                 build_tables, executor):
        self.mesh = mesh
        super().__init__(rel, ext, group_exprs, agg_exprs, probe_table,
                         build_tables, executor)
        # static arg-shape description for the shard_map wrap (the cache
        # keys every table version, so these flags are stable across runs)
        names = probe_table.column_names
        self._pvalid_present = tuple(
            probe_table.columns[n].validity is not None for n in names)
        self._has_row_valid = probe_table.row_valid is not None
        bkeys = sorted(self.used_build_slots.items(), key=lambda kv: kv[1])
        self._bkeys = [kc for kc, _ in bkeys]
        self._bvalid_present = []
        for (k, col) in self._bkeys:
            bt = build_tables[k]
            c = bt.columns[bt.column_names[col]]
            self._bvalid_present.append(c.validity is not None)
        self._bvalid_present = tuple(self._bvalid_present)
        # the raw traced body is derived NOW, while the construction tables
        # are still bound (build_domains snapshot) — run() then takes its
        # tables as per-call parameters, so the cached pipeline carries no
        # shared table state for concurrent workers to race on
        self._raw_fn = self._build()
        self._mapped: Dict[int, object] = {}

    def _make_reducer(self, gid, domain: int, n_rows: int) -> SegmentReducer:
        # every shard reduces ITS row block whole under the mask: the
        # one-chip rung's compaction of the passing rows
        # (`CompiledJoinAggregate._plan_compaction`) is off for any subclass
        # and any sharded or padded probe.  Under `shard_map` it would want
        # a per-shard capacity and a `psum` of `passed` to pick ONE branch on
        # every chip (the branches' collectives must agree): its own design
        return SpmdSegmentReducer(gid, domain, n_rows)

    def _mapped_for(self, n_params: int):
        fn = self._mapped.get(n_params)
        if fn is not None:
            return fn
        raw = self._raw_fn
        bkeys = self._bkeys
        pvp = self._pvalid_present
        bvp = self._bvalid_present
        has_rv = self._has_row_valid

        def packed_fn(pdatas, pvalids_p, luts, bdatas, bvalids_p, rv_t,
                      params, bounds):
            pvalids = []
            i = 0
            for present in pvp:
                pvalids.append(pvalids_p[i] if present else None)
                i += 1 if present else 0
            build_cols = {}
            j = 0
            for key, bd, present in zip(bkeys, bdatas, bvp):
                bv = bvalids_p[j] if present else None
                j += 1 if present else 0
                build_cols[key] = (bd, bv)
            rv = rv_t[0] if rv_t else None
            return raw(tuple(pdatas), tuple(pvalids), tuple(luts),
                       build_cols, rv, tuple(params), bounds)

        in_specs = (
            (P(AXIS),) * len(pvp),
            (P(AXIS),) * sum(pvp),
            (P(),) * len(self.luts),
            (P(),) * len(bkeys),
            (P(),) * sum(bvp),
            (P(AXIS),) * (1 if has_rv else 0),
            (P(),) * n_params,
            P(),
        )
        mapped = shard_map(packed_fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=P(None, None), check_vma=False)
        fn = jax.jit(mapped)
        self._mapped[n_params] = fn
        return fn

    def run(self, params: Tuple = (), probe_table=None,
            build_tables=None) -> Table:
        """Tables are per-call PARAMETERS (not rebound shared state): the
        cached pipeline serves concurrent worker threads, and the single-
        chip set-run-reset dance would let one thread's reset null the
        tables out from under another's run."""
        from ..observability import timed_jit_call
        from ..parallel import dist_plan as _dp

        pt = probe_table if probe_table is not None else self.probe_table
        bts = build_tables if build_tables is not None else self.build_tables
        # same fused sharded join->aggregate family as the GSPMD path —
        # joined rows never materialize on host or device
        _dp.STATS["sharded_join_agg"] += 1
        pdatas = tuple(pt.columns[n].data for n in pt.column_names)
        pvalids = tuple(pt.columns[n].validity for n in pt.column_names)
        luts = tuple(lut for _, lut in self.luts)
        build_cols = {}
        for (k, col), _slot in self.used_build_slots.items():
            bt = bts[k]
            c = bt.columns[bt.column_names[col]]
            build_cols[(k, col)] = (c.data, c.validity)
        row_valid = pt.row_valid
        params = tuple(params)
        pvalids_p = tuple(v for v, present in zip(pvalids,
                                                  self._pvalid_present)
                          if present)
        bdatas, bvalids_p = [], []
        for key, present in zip(self._bkeys, self._bvalid_present):
            bd, bv = build_cols[key]
            bdatas.append(bd)
            if present:
                # a rebound table version may have dropped its mask; the
                # wrap's arity is static, so synthesize all-valid
                bvalids_p.append(bv if bv is not None
                                 else jnp.ones(bd.shape[0], dtype=bool))
        rv_t = (row_valid,) if self._has_row_valid else ()
        fn = self._mapped_for(len(params))
        packed = timed_jit_call(
            "spmd_join_aggregate", fn, tuple(pdatas), pvalids_p, luts,
            tuple(bdatas), tuple(bvalids_p), rv_t, params, self.bounds,
            may_compile=not self._warm,
            launch_attrs=launch_attrs(self.mesh, pt.padded_rows))
        self._warm = True
        tags = self._pack_tags
        host, present = fetch_packed(packed, self.domain)
        return self._decode_result(host, present, tags, build_tables=bts)


PROGRAMS = ProgramCache("spmd_join_aggregate", 8)


def try_spmd_join_aggregate(rel: p.Aggregate, executor) -> Optional[Table]:
    """Attempt the SPMD broadcast-join pipeline for an Aggregate subtree;
    None falls to the single-chip compiled rungs / shuffle engine."""
    config = executor.config
    if not config.get("sql.compile", True) \
            or not config.get("sql.compile.join_pipeline", True):
        return None
    if not rung_enabled(config, "spmd_join_aggregate"):
        return None
    extraction = _extract(rel)
    if extraction is None:
        return None
    ext, group_exprs, agg_exprs = extraction
    if not all(j["exposes"] and "pair" not in j for j in ext.joins):
        # a semi-join's build side, and a two-column key's slots, are the
        # one-chip program's
        return None
    try:
        from ..datacontainer import LazyParquetContainer

        ctx = executor.context
        dc = ctx.schema[ext.scan.schema_name].tables.get(ext.scan.table_name)
        if dc is None or isinstance(dc, LazyParquetContainer):
            return None
        uids = [dc.uid]
        for j in ext.joins:
            for node in _plan_nodes(j["plan"]):
                if isinstance(node, p.TableScan):
                    bdc = ctx.schema[node.schema_name].tables.get(
                        node.table_name)
                    if bdc is None:
                        return None
                    uids.append(bdc.uid)
        # the broadcast threshold is part of the decline identity: raising
        # parallel.spmd.broadcast_rows must re-open a size-declined family
        limit = int(config.get("parallel.spmd.broadcast_rows", 1 << 20))
        decline_key = (tuple(uids), "spmd", limit, str(rel))
        if PROGRAMS.declined(decline_key):
            return None
        check_agg_static_support(agg_exprs)
        from .. import families

        pz = families.pipeline_parameterizer(config)
        ext.conjuncts = [pz.rewrite(e) for e in ext.conjuncts]
        agg_exprs = [pz.rewrite_agg(a) for a in agg_exprs]
        params = pz.params
        probe_table = executor.get_table(ext.scan.schema_name,
                                         ext.scan.table_name)
        if ext.scan.projection is not None:
            probe_table = probe_table.select(ext.scan.projection)
        if not probe_table.column_names:
            return None
        mesh = mesh_of_sharded_table(probe_table)
        if mesh is None:
            return None
        # build sides run through the normal recursive converter, then
        # broadcast; big builds decline to the hash-shuffle engine
        build_tables = [executor.execute(j["plan"]) for j in ext.joins]
        if any(bt.num_rows > limit for bt in build_tables):
            # memoize the decline (keyed by every base-table uid): a repeat
            # of this query must not re-execute the build subtrees here
            # just to re-measure them — the shuffle engine pays them once
            PROGRAMS.decline(decline_key)
            logger.debug("spmd join declining: build side exceeds "
                         "parallel.spmd.broadcast_rows=%d", limit)
            return None
        family = (
            mesh_key(mesh),
            ext.scan.schema_name, ext.scan.table_name,
            tuple(ext.scan.projection or ()),
            tuple(repr(j["plan"]) for j in ext.joins),
            tuple(str(j["lkey"]) + "=" + str(j["rkey"]) for j in ext.joins),
            tuple(str(e) for e in ext.conjuncts),
            tuple(str(e) for e in group_exprs),
            tuple(str(a) for a in agg_exprs),
            tuple((f.name, f.sql_type) for f in rel.schema),
        )
        bucket = (tuple(uids), probe_table.num_rows, probe_table.padded_rows,
                  tuple(bt.num_rows for bt in build_tables))

        def construct():
            obj = SpmdJoinAggregate(mesh, rel, ext, group_exprs, agg_exprs,
                                    probe_table, build_tables, executor)
            # the (large) construction tables never pin HBM on the cached
            # object: every run() takes its tables as parameters
            obj.probe_table = None
            obj.build_tables = None
            return obj

        # no `warm`: this rung never defers to the background compiler
        compiled, _ = PROGRAMS.get_or_build(ctx, family, bucket, construct,
                                            params=params)
        count_launch(ctx.metrics, mesh, probe_table.num_rows)
        from ..resilience import faults

        faults.maybe_inject("oom", config)
        return compiled.run(params, probe_table, build_tables)
    except _Unsupported as e:
        logger.debug("spmd join pipeline unsupported: %s", e)
        if "decline_key" in locals():
            PROGRAMS.decline(decline_key)
        return None
    except (ValueError, TypeError, NotImplementedError) as e:
        # a fault in the wrap (not an ineligible shape): a counted step
        # down — the single-chip rungs below still answer
        raise_rung_fault("spmd_join_aggregate", e)
