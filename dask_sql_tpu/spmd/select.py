"""spmd_select: the sharded compiled rung for ROOT select chains.

`scan -> filter* -> project [limit]` over a mesh-sharded table compiles to
TWO shard_map SPMD programs sharing the single-chip `CompiledSelect` traced
bodies: the mask kernel evaluates the selection per shard (pad rows masked
by `row_valid`) and returns the sharded mask plus per-shard survivor
counts; the gather kernel compacts each shard's survivors into a static
power-of-two bucket and packs them into one f64 matrix whose device axis is
the mesh — the host pulls it in ONE transfer sized by the largest shard's
survivors, slices each shard's real rows, and concatenates in global row
order (row-block sharding is contiguous, sized-nonzero indices ascend).

Declines: ORDER BY chains only (the range-partition `dist_sort` keeps
results sharded in sort order — pulling everything to one host would
defeat that layout).  Inner LIMIT windows ARE supported: the survivor
ordinal the window slices stays a GLOBAL row ordinal via an
all_gather-prefix override of `_survivor_ordinal`.  ParamRefs stay traced
runtime arguments — one SPMD executable per family, zero foreground
compiles for the second literal variant — and the family batcher's
stacked launches vmap the mask program over the parameter axis.
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from ..columnar.table import Table
from ..parallel.mesh import AXIS
from ..physical.compiled import _Unsupported
from ..physical.compiled_select import (CompiledSelect, _extract,
                                        select_family)
from ..physical.programs import ProgramCache
from .core import (ColumnSpmdWrap, count_launch, launch_attrs, mesh_key,
                   mesh_of_sharded_table, raise_rung_fault, rung_enabled)

logger = logging.getLogger(__name__)


class SpmdSelect(CompiledSelect):
    #: False while the single-chip eval_shape eligibility trace runs in
    #: __init__ (no mesh axis bound there); flipped once construction
    #: finishes so shard_map traces take the cross-shard ordinal
    _use_global_ordinal = False

    def __init__(self, mesh, table, scan, upper_filters, scan_filters,
                 proj, proj_exprs, sort_keys, sort_fetch, limit, inner_limit,
                 params=()):
        if sort_keys is not None:
            raise _Unsupported("spmd select keeps ORDER BY on dist_sort")
        super().__init__(table, scan, upper_filters, scan_filters, proj,
                         proj_exprs, sort_keys, sort_fetch, limit,
                         inner_limit, params)
        self._use_global_ordinal = True
        self.mesh = mesh
        self.ndev = int(mesh.devices.size)
        names = table.column_names
        self._valid_present = tuple(table.columns[n].validity is not None
                                    for n in names)
        self._has_row_valid = table.row_valid is not None

        def mask_shard(datas, valids, row_valid, params):
            mask, cnt = self._mask_fn_raw(datas, valids, row_valid, params)
            return mask, cnt[None]  # per-shard survivor count -> [ndev]

        self._mask_wraps: Dict[int, ColumnSpmdWrap] = {}
        self._mask_shard = mask_shard
        #: per pow2 bucket: jitted shard_map gather (out [R, ndev*bucket])
        self._spmd_gathers: Dict[Tuple[int, int], object] = {}
        self._mask_batched_jit = None

    def _survivor_ordinal(self, mask):
        """Global survivor ordinal under shard_map: local cumsum plus the
        all-gathered prefix of lower-indexed shards' totals, so an inner
        LIMIT window (PushDownLimit parks limits right above the scan)
        keeps its single-chip semantics — the window is a prefix of the
        GLOBAL survivor sequence, not a per-shard one."""
        import jax.numpy as jnp

        local = jnp.cumsum(mask.astype(jnp.int64))
        if not self._use_global_ordinal:
            return local
        total = local[-1] if mask.shape[0] else jnp.int64(0)
        totals = jax.lax.all_gather(total, AXIS)  # [ndev]
        me = jax.lax.axis_index(AXIS)
        offset = jnp.sum(jnp.where(
            jnp.arange(totals.shape[0]) < me, totals, 0))
        return local + offset

    # ------------------------------------------------------------- wrappers
    def _mask_wrap(self, n_params: int) -> ColumnSpmdWrap:
        w = self._mask_wraps.get(n_params)
        if w is None:
            w = ColumnSpmdWrap(
                self._mask_shard, self.mesh, self._valid_present,
                self._has_row_valid, n_params,
                out_specs=(P(AXIS), P(AXIS)), check_vma=False)
            self._mask_wraps[n_params] = w
        return w

    def _gather_mapped(self, bucket: int, n_params: int):
        key = (bucket, n_params)
        fn = self._spmd_gathers.get(key)
        if fn is None:
            raw = self._gather_fn_raw

            def gather_shard(datas, valids, mask, params):
                # the mask rides the row_valid slot of the generic wrap
                # (same row-block spec); the raw single-chip gather body
                # compacts this shard's survivors into the static bucket
                return raw(datas, valids, mask, params, bucket)

            w = ColumnSpmdWrap(gather_shard, self.mesh, self._valid_present,
                               True, n_params,
                               out_specs=P(None, AXIS), check_vma=False)
            fn = (w, w.jitted)
            self._spmd_gathers[key] = fn
        return fn

    # ------------------------------------------------------------ execution
    def run(self, table: Optional[Table] = None, params: Tuple = ()) -> Table:
        from ..observability import timed_jit_call
        from ..utils import d2h_fetch

        t = table if table is not None else self.table
        datas = [t.columns[n].data for n in t.column_names]
        valids = [t.columns[n].validity for n in t.column_names]
        wrap = self._mask_wrap(len(params))
        args = wrap.pack_args(datas, valids, t.row_valid, params)
        mask, counts = timed_jit_call(
            "spmd_select", wrap.jitted, *args,
            may_compile=not self._mask_warm,
            launch_attrs=launch_attrs(self.mesh, t.padded_rows))
        self._mask_warm = True
        with d2h_fetch(nbytes=int(counts.nbytes)):
            counts_h = np.asarray(jax.device_get(counts)).astype(np.int64)
        return self._finish_spmd(datas, valids, mask, counts_h, params)

    def run_batched(self, table: Table, params_list: List[Tuple]
                    ) -> List[Table]:
        """ONE vmapped SPMD mask launch for every co-admitted member over a
        single sharded scan; per-member survivor gathers share the
        per-bucket SPMD gather executables."""
        from ..families import stack_params
        from ..observability import timed_jit_call
        from ..utils import d2h_fetch

        n = len(params_list)
        stacked, bucket = stack_params(params_list)
        wrap = self._mask_wrap(len(params_list[0]))
        if self._mask_batched_jit is None:
            self._mask_batched_jit = jax.jit(
                jax.vmap(wrap.mapped, in_axes=(None, None, None, 0)))
        datas = [table.columns[c].data for c in table.column_names]
        valids = [table.columns[c].validity for c in table.column_names]
        args = wrap.pack_args(datas, valids, table.row_valid, stacked)
        masks, counts = timed_jit_call(
            "spmd_select", self._mask_batched_jit, *args,
            may_compile=bucket not in self._warm_mask_batch,
            launch_attrs=launch_attrs(self.mesh, table.padded_rows))
        self._warm_mask_batch.add(bucket)
        with d2h_fetch(nbytes=int(counts.nbytes)):
            counts_h = np.asarray(jax.device_get(counts)).astype(np.int64)
        return [self._finish_spmd(datas, valids, masks[b], counts_h[b],
                                  params_list[b]) for b in range(n)]

    def _finish_spmd(self, datas, valids, mask, counts_h: np.ndarray,
                     params: Tuple) -> Table:
        from ..observability import timed_jit_call
        from ..utils import d2h_fetch

        total = int(counts_h.sum())
        want = self._limit_trim(total)
        if want < total:
            # sort-free LIMIT: survivors ascend in global row order, so the
            # window is a prefix across shards in mesh order
            before = np.concatenate(([0], np.cumsum(counts_h)[:-1]))
            take = np.clip(want - before, 0, counts_h)
        else:
            take = counts_h
        count = int(take.sum())
        if count == 0:
            cols, valid_arrs = self._decode_packed(None, 0)
            return self._assemble(cols, valid_arrs, 0)
        bucket = 1 << (int(take.max()) - 1).bit_length()
        wrap, gfn = self._gather_mapped(bucket, len(params))
        args = wrap.pack_args(datas, valids, mask, params)
        packed = timed_jit_call(
            "spmd_select", gfn, *args,
            may_compile=bucket not in self._warm_buckets,
            launch_attrs=launch_attrs(self.mesh, datas[0].shape[0]))
        self._warm_buckets.add(bucket)
        with d2h_fetch(nbytes=int(packed.nbytes)):
            # [R, ndev*bucket]
            host_all = np.asarray(jax.device_get(packed))
        parts = [host_all[:, d * bucket: d * bucket + int(take[d])]
                 for d in range(self.ndev) if take[d]]
        host = np.concatenate(parts, axis=1) if parts else None
        cols, valid_arrs = self._decode_packed(host, count)
        return self._assemble(cols, valid_arrs, count)


PROGRAMS = ProgramCache("spmd_select", 16)


def try_spmd_select(root, executor) -> Optional[Table]:
    """Attempt the SPMD root-select path over a mesh-sharded scan; None
    steps down (compiled_select declines sharded tables, so the next
    answering rung is typically the interpreted walk)."""
    if not executor.config.get("sql.compile", True) \
            or not executor.config.get("sql.compile.select", True):
        return None
    if not rung_enabled(executor.config, "spmd_select"):
        return None
    got = _extract(root)
    if got is None:
        return None
    scan, upper_filters, proj, sort_keys, sort_fetch, limit, inner_limit = got
    if sort_keys is not None:
        return None  # ORDER BY keeps the dist_sort sharded layout
    try:
        ctx = executor.context
        from ..datacontainer import LazyParquetContainer

        dc = ctx.schema[scan.schema_name].tables.get(scan.table_name)
        if dc is None or isinstance(dc, LazyParquetContainer):
            return None
        table = executor.get_table(scan.schema_name, scan.table_name)
        if scan.projection is not None:
            table = table.select(scan.projection)
        if not table.column_names:
            return None
        mesh = mesh_of_sharded_table(table)
        if mesh is None:
            return None
        from .. import families

        pz = families.pipeline_parameterizer(executor.config)
        p_upper = [pz.rewrite(f) for f in upper_filters]
        p_scan_flts = [pz.rewrite(f) for f in scan.filters]
        p_exprs = [pz.rewrite(e) for e in proj.exprs]
        params = pz.params
        # the table NAME is the family's (only the uid is table-version
        # identity): same-shaped queries over different tables must not
        # collide in the background-recompile family map
        family = (mesh_key(mesh), scan.schema_name, scan.table_name) \
            + select_family(scan, p_upper, p_scan_flts, p_exprs, sort_keys,
                            sort_fetch, limit, inner_limit)
        bucket = (dc.uid, table.num_rows, table.padded_rows)

        def construct():
            obj = SpmdSelect(mesh, table, scan, p_upper, p_scan_flts, proj,
                             p_exprs, sort_keys, sort_fetch, limit,
                             inner_limit, params)
            obj.table = None
            return obj

        # the warming run compiles the mask + the first gather
        compiled, _ = PROGRAMS.get_or_build(
            ctx, family, bucket, construct,
            warm=lambda obj: obj.run(table, params), params=params)
        if compiled is None:
            return None  # deferred to the background compiler
        count_launch(ctx.metrics, mesh, table.num_rows)
        from ..resilience import faults

        faults.maybe_inject("oom", executor.config)
        return PROGRAMS.run(
            ctx, family, bucket, compiled, params,
            solo=lambda: compiled.run(table, params),
            batched=lambda members: compiled.run_batched(table, members))
    except _Unsupported as e:
        logger.debug("spmd select unsupported: %s", e)
        return None
    except (ValueError, TypeError, NotImplementedError) as e:
        raise_rung_fault("spmd_select", e)
