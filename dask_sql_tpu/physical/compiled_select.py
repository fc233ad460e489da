"""Compiled SELECT pipelines: one kernel + one transfer for root-level
`scan -> filter* -> project [-> sort -> limit]` queries.

The eager converters dispatch one XLA op per expression and per filter, then
materialize column-by-column — every dispatch and every pull is a
host-device round trip.  For the plan ROOT (the result goes straight to the
host anyway), this module compiles the whole chain into ONE jitted program
whose output is a single packed f64 matrix: row 0 is the selection mask,
then each projected column (and its validity) — pulled in ONE device_get,
compacted/ordered/limited with numpy on the host.

Two static-shape kernels: kernel 1 evaluates the filter mask and its count
(one scalar pull); kernel 2 — specialized per power-of-two survivor bucket,
so XLA re-traces at most log2(n) times — compacts the input columns with a
sized nonzero, evaluates the projections over the bucket, and packs
everything into one matrix whose transfer size tracks the SURVIVORS, not
the scan.  Sort/limit run on the compacted host result — the root is
host-bound regardless, and np.lexsort on the survivor set replaces a device
sort plus per-column gathers.

Parity note: the reference executes the same shape as a dask task tree with
one pandas kernel per operator; this is the TPU-native replacement.
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.column import Column
from ..columnar.dtypes import STRING_TYPES, SqlType, sql_to_np
from ..columnar.table import Table
from ..planner import plan as p
from ..planner.expressions import ColumnRef
from .compiled import (
    PARAMS_SLOT,
    _TableMeta,
    _TraceEval,
    _Unsupported,
    check_no_rle,
    count_codespace_predicates,
    record_predicate_spaces,
    pack_flat,
)
from .programs import ProgramCache

logger = logging.getLogger(__name__)


def _extract(root):
    """Match [Limit]? [Sort]? Projection Filter* TableScan; None otherwise."""
    node = root
    limit = None
    if isinstance(node, p.Limit):
        limit = (node.skip, node.fetch)
        node = node.input
    sort_keys = None
    sort_fetch = None
    if isinstance(node, p.Sort):
        sort_keys = list(node.keys)
        sort_fetch = node.fetch  # caps the window INSIDE any outer Limit
        node = node.input
    if not isinstance(node, p.Projection):
        return None
    proj = node
    node = proj.input
    filters = []
    while isinstance(node, p.Filter):
        filters.append(node.predicate)
        node = node.input
    inner_limit = None
    while isinstance(node, p.Limit):
        # PushDownLimit parks (possibly stacked) Limits right above the
        # scan: compose them (EliminateLimit's rule) into one row window
        # baked into the mask
        if inner_limit is None:
            inner_limit = (node.skip, node.fetch)
        else:
            oskip, ofetch = inner_limit  # applied AFTER this inner node
            iskip, ifetch = node.skip, node.fetch
            fetches = [f for f in (
                None if ifetch is None else max(ifetch - oskip, 0),
                ofetch) if f is not None]
            inner_limit = (iskip + oskip, min(fetches) if fetches else None)
        node = node.input
    if not isinstance(node, p.TableScan):
        return None
    # upper Filter-node predicates stay separate from scan.filters: a Limit
    # parked between them windows only the scan-filtered rows
    # (limit-then-filter), so the mask builder needs both lists
    return (node, list(filters), proj, sort_keys, sort_fetch, limit,
            inner_limit)


class CompiledSelect:
    #: the ladder-rung label this pipeline's compiles are recorded under
    #: (``resilience.compile_ms.<rung>`` histograms, ``compile:<rung>``
    #: trace spans) — subclasses that serve a DIFFERENT rung (the streamed
    #: select, streaming/select.py) override it so their compiles never
    #: pollute this rung's compile-cost prior (ladder.cost_skip reads it)
    _RUNG = "compiled_select"

    def __init__(self, table: Table, scan, upper_filters, scan_filters,
                 proj, proj_exprs, sort_keys, sort_fetch, limit, inner_limit,
                 params=()):
        self.scan = scan
        self.proj = proj
        self.sort_keys = sort_keys
        self.sort_fetch = sort_fetch
        self.limit = limit
        self.inner_limit = inner_limit
        self.table: Optional[Table] = table

        # eligibility: every output expr must trace; string outputs only as
        # plain column refs (codes + dictionary pass through); sort keys must
        # be output positions over non-string columns (host lexsort order on
        # dictionary codes is only lexicographic for sorted dictionaries)
        check_no_rle(table)
        from ..columnar.encodings import Encoding

        #: compressed-domain accounting: encoded inputs mean the mask phase
        #: reads codes and the survivor gather late-materializes values
        self.has_encoded = any(
            c.encoding is not Encoding.PLAIN for c in table.columns.values())
        self.codespace_preds, self.valuespace_preds = \
            count_codespace_predicates(
                list(upper_filters) + list(scan_filters) + list(proj_exprs),
                table) if self.has_encoded else (0, 0)
        self.out_meta: List[Tuple[str, SqlType, Optional[object]]] = []
        for e, f in zip(proj_exprs, proj.schema):
            if f.sql_type in STRING_TYPES:
                if not (isinstance(e, ColumnRef) and type(e) is ColumnRef):
                    raise _Unsupported("computed string output")
                dictionary = table.columns[table.column_names[e.index]].dictionary
            else:
                dictionary = None
            self.out_meta.append((f.name, f.sql_type, dictionary))
        if sort_keys is not None:
            for k in sort_keys:
                e = k.expr
                if not (isinstance(e, ColumnRef) and type(e) is ColumnRef):
                    raise _Unsupported("sort key is not an output column")
                if proj.schema[e.index].sql_type in STRING_TYPES:
                    dic = self.out_meta[e.index][2]
                    if dic is None or not _dictionary_sorted(dic):
                        raise _Unsupported("string sort key w/o sorted dict")

        ev = _TraceEval(_TableMeta(table))
        n_cols = len(table.column_names)
        exprs = list(proj_exprs)
        upper_flts = list(upper_filters)
        scan_flts = list(scan_filters)
        self._pack_tags: List[Tuple[str, np.dtype]] = []

        inner_limit = self.inner_limit

        def mask_fn(datas, valids, row_valid, params=()):
            slots = {i: (datas[i], valids[i]) for i in range(n_cols)}
            slots[PARAMS_SLOT] = params
            nr = datas[0].shape[0] if datas else 0

            def fold(mask, f):
                d, v = ev.eval(f, slots)
                m = d if v is None else (d & v)
                return m if mask is None else (mask & m)

            def as_rows(mask):
                if mask is None:
                    return jnp.ones(nr, dtype=bool)
                if mask.ndim == 0:  # constant predicate (e.g. WHERE 1 = 1)
                    return jnp.broadcast_to(mask, (nr,))
                return mask

            mask = row_valid
            for f in scan_flts:
                mask = fold(mask, f)
            if inner_limit is not None:
                # a Limit parked above the scan windows the rows the SCAN's
                # own filters keep — the plan order is limit-then-filter
                # (Projection <- Filter* <- Limit <- TableScan), so upper
                # Filter-node predicates must apply AFTER the window, not
                # shrink it (ADVICE r5).  The survivor ordinal makes the
                # window a static-shape mask refinement.
                mask = as_rows(mask)
                skip_i, fetch_i = inner_limit
                ordinal = self._survivor_ordinal(mask)
                w = ordinal > skip_i
                if fetch_i is not None:
                    w &= ordinal <= skip_i + fetch_i
                mask = mask & w
            for f in upper_flts:
                mask = fold(mask, f)
            mask = as_rows(mask)
            return mask, jnp.sum(mask.astype(jnp.int64))

        def gather_fn(datas, valids, mask, params, bucket):
            # bucket is static per trace: sized nonzero keeps shapes static,
            # and jit re-specializes per distinct bucket (<= log2 n traces)
            (idx,) = jnp.nonzero(mask, size=bucket, fill_value=0)
            slots = {}
            for i in range(n_cols):
                d = datas[i][idx]
                v = valids[i][idx] if valids[i] is not None else None
                slots[i] = (d, v)
            slots[PARAMS_SLOT] = params
            flat = []
            for e in exprs:
                d, v = ev.eval(e, slots)
                if d.ndim == 0:  # scalar literal output: broadcast
                    d = jnp.broadcast_to(d, (bucket,))
                if v is not None and v.ndim == 0:
                    # kernels may emit a scalar validity (e.g. a literal arg
                    # folded into the op's mask): broadcast to the row shape
                    v = jnp.broadcast_to(v, (bucket,))
                flat.append(d)
                flat.append(v if v is not None else jnp.ones(bucket, dtype=bool))
            # extension seam: the fused PREDICT rung (compiled_predict.py)
            # appends its model-program outputs here, INSIDE the same
            # traced gather — one executable, one packed transfer
            for d, v in self._extra_pack_outputs(ev, slots, bucket):
                if d.ndim == 0:
                    d = jnp.broadcast_to(d, (bucket,))
                flat.append(d)
                flat.append(v if v is not None
                            else jnp.ones(bucket, dtype=bool))
            tags: List[Tuple[str, np.dtype]] = []
            out = pack_flat(flat, tags)
            self._pack_tags = tags
            return out

        # trace-check now so ineligible expressions fall back BEFORE the
        # plugin cache ever sees this object
        datas_s = tuple(table.columns[n].data for n in table.column_names)
        valids_s = tuple(table.columns[n].validity for n in table.column_names)
        # eval_shape needs only shapes/dtypes: anything already exposing
        # them (numpy values, committed DEVICE weight arrays from the
        # fused PREDICT rung) passes through without a d2h pull
        params_s = tuple(v if hasattr(v, "shape") and hasattr(v, "dtype")
                         else np.asarray(v) for v in params)
        jax.eval_shape(mask_fn, datas_s, valids_s, table.row_valid, params_s)
        jax.eval_shape(lambda d, v, m, q: gather_fn(d, v, m, q, 8), datas_s,
                       valids_s,
                       jax.ShapeDtypeStruct((table.padded_rows,), jnp.bool_),
                       params_s)
        self._mask_fn_raw = mask_fn
        self._mask_fn = jax.jit(mask_fn)
        self._gather_fn_raw = gather_fn  # for the SPMD rung's shard_map
        self._gather_fn = jax.jit(gather_fn, static_argnames=("bucket",))
        #: lazily-built vmapped mask variant for the family batcher: ONE
        #: stacked launch evaluates every co-admitted member's filter over
        #: a single scan; compiled per pow2 batch bucket
        self._mask_batched = None
        self._warm_mask_batch: set = set()
        #: compile-watchdog hints: the mask kernel compiles once, the
        #: gather kernel once per distinct pow2 survivor bucket
        self._mask_warm = False
        self._warm_buckets: set = set()

    def _extra_pack_outputs(self, ev, slots, bucket):
        """Extra (data, validity_or_None) pairs appended to the packed
        gather output under trace — the seam the fused PREDICT rung
        (CompiledPredict) overrides to run its model program over the
        gathered survivors in the SAME jit.  ``slots`` holds the gathered
        per-column (data, valid) pairs plus the runtime parameter vector
        under PARAMS_SLOT."""
        return ()

    def _survivor_ordinal(self, mask):
        """1-based running survivor count the inner-LIMIT window slices.
        Local cumsum on a single device; the SPMD rung (spmd/select.py)
        overrides with a cross-shard prefix so the window stays a GLOBAL
        row ordinal under shard_map."""
        return jnp.cumsum(mask.astype(jnp.int64))

    def run(self, table: Optional[Table] = None, params: Tuple = ()) -> Table:
        from ..utils import d2h_fetch
        from ..observability import timed_jit_call

        # parameter, not shared state: cached pipelines serve concurrent
        # worker threads (see CompiledAggregate.run)
        t = table if table is not None else self.table
        datas = tuple(t.columns[n].data for n in t.column_names)
        valids = tuple(t.columns[n].validity for n in t.column_names)
        mask, count_dev = timed_jit_call(
            self._RUNG, self._mask_fn, datas, valids, t.row_valid,
            tuple(params), may_compile=not self._mask_warm)
        self._mask_warm = True
        with d2h_fetch(nbytes=int(count_dev.nbytes)):
            count = int(count_dev)  # one scalar round trip
        return self._finish(datas, valids, mask, count, tuple(params))

    def _batched_param_split(self) -> Optional[int]:
        """Count of leading runtime-parameter slots the batched vmap maps
        over the batch axis; None = every slot (the family literal
        vector).  The fused PREDICT rung (CompiledPredict) returns its
        family-prefix length so the shared model weight tail rides
        UNMAPPED instead of being stacked per batch slot."""
        return None

    def run_batched(self, table: Table, params_list: List[Tuple]
                    ) -> List[Table]:
        """Family-batched execution: member literal vectors stack along a
        new leading axis and ONE vmapped launch computes every member's
        selection mask over a single shared scan (batch padded to the pow2
        bucket by repeating the last member).  Survivor gathers then run
        per member — they share the per-bucket gather executables."""
        from ..families import stack_params
        from ..utils import d2h_fetch
        from ..observability import timed_jit_call

        n = len(params_list)
        base = self._batched_param_split()
        if base is None:
            stacked, bucket = stack_params(params_list)
            launch_params, axes = stacked, 0
            member_params = params_list
        else:
            # shared unmapped tail (e.g. model weights): every member
            # references the same arrays, so stacking would copy them per
            # batch slot for a mask kernel that never reads them
            tail = tuple(params_list[0][base:])
            stacked, bucket = stack_params([m[:base] for m in params_list])
            launch_params = tuple(stacked) + tail
            axes = tuple([0] * base) + tuple([None] * len(tail))
            member_params = [tuple(m[:base]) + tail for m in params_list]
        if self._mask_batched is None:
            self._mask_batched = jax.jit(
                jax.vmap(self._mask_fn_raw,
                         in_axes=(None, None, None, axes)))
        datas = tuple(table.columns[c].data for c in table.column_names)
        valids = tuple(table.columns[c].validity
                       for c in table.column_names)
        masks, counts_dev = timed_jit_call(
            self._RUNG, self._mask_batched, datas, valids,
            table.row_valid, launch_params,
            may_compile=bucket not in self._warm_mask_batch)
        self._warm_mask_batch.add(bucket)
        with d2h_fetch(nbytes=int(counts_dev.nbytes)):
            counts = np.asarray(jax.device_get(counts_dev))
        return [self._finish(datas, valids, masks[b], int(counts[b]),
                             member_params[b]) for b in range(n)]

    def _finish(self, datas, valids, mask, count: int,
                params: Tuple) -> Table:
        from ..utils import d2h_fetch
        from ..observability import timed_jit_call

        # without an ORDER BY, a LIMIT caps how many survivors we even pull:
        # sized nonzero returns ascending indices, so the first `want` rows
        # ARE the eager path's first `want` rows
        count = self._limit_trim(count)
        if count == 0:
            host = None
        else:
            bucket = 1 << (count - 1).bit_length()
            # jit re-specializes per bucket: each new bucket is a fresh
            # XLA compile the observability layer records per rung
            packed = timed_jit_call(self._RUNG, self._gather_fn,
                                    datas, valids, mask, params,
                                    bucket=bucket,
                                    may_compile=bucket not in
                                    self._warm_buckets)
            self._warm_buckets.add(bucket)
            with d2h_fetch(nbytes=int(packed.nbytes)):
                host = np.asarray(jax.device_get(packed))
        cols, valid_arrs = self._decode_packed(host, count)
        return self._assemble(cols, valid_arrs, count)

    def _limit_trim(self, count: int) -> int:
        """Sort-free LIMIT: survivor indices ascend, so the first `want`
        rows ARE the eager path's — cap the pull."""
        if self.sort_keys is None and self.limit is not None \
                and self.limit[1] is not None:
            return min(count, self.limit[0] + self.limit[1])
        return count

    def _decode_packed(self, host: Optional[np.ndarray], count: int):
        """Packed host matrix -> per-output (data, validity) numpy arrays.
        `host` is None when there are zero survivors."""
        from .compiled import unpack_row

        cols: List[np.ndarray] = []
        valid_arrs: List[Optional[np.ndarray]] = []
        if count == 0 or host is None:
            for name, sql_type, dictionary in self.out_meta:
                cols.append(np.zeros(0, dtype=sql_to_np(sql_type)))
                valid_arrs.append(None)
            return cols, valid_arrs
        tags = self._pack_tags
        for i, (name, sql_type, dictionary) in enumerate(self.out_meta):
            d = unpack_row(host, 2 * i, tags)[:count]
            v = unpack_row(host, 1 + 2 * i, tags).astype(bool)[:count]
            target = sql_to_np(sql_type)
            if d.dtype != target:
                d = d.astype(target)
            cols.append(d)
            valid_arrs.append(None if bool(v.all()) else v)
        return cols, valid_arrs

    def _assemble(self, cols: List[np.ndarray],
                  valid_arrs: List[Optional[np.ndarray]],
                  count: int) -> Table:
        """Host-side tail shared with the SPMD rung (spmd/select.py):
        ORDER BY + window slicing + output naming over decoded survivor
        columns."""
        # host-side ORDER BY: the same host-numpy sort the engine uses for
        # tiny post-aggregate tables (ops/sorting.sort_permutation — NaN
        # sorts as +inf, NULL placement per nulls_first)
        order = None
        if self.sort_keys:
            from ..ops.sorting import sort_permutation

            key_cols = []
            for k in self.sort_keys:
                idx = k.expr.index
                _, sql_type, dictionary = self.out_meta[idx]
                key_cols.append(Column(cols[idx], sql_type, valid_arrs[idx],
                                       dictionary))
            order = np.asarray(sort_permutation(
                key_cols, [k.ascending for k in self.sort_keys],
                [k.nulls_first_resolved() for k in self.sort_keys]))

        from .rel.base import unique_names

        names = [m[0] for m in self.out_meta]
        uniq = unique_names(names)
        out: Dict[str, Column] = {}
        n_out = count
        if self.sort_fetch is not None:
            n_out = min(n_out, self.sort_fetch)
        lo, hi = 0, n_out
        if self.limit is not None:
            skip, fetch = self.limit
            lo = min(skip, n_out)
            hi = n_out if fetch is None else min(skip + fetch, n_out)
        for i, (uname, (name, sql_type, dictionary)) in enumerate(
                zip(uniq, self.out_meta)):
            d = cols[i]
            v = valid_arrs[i]
            if order is not None:
                d = d[order]
                v = v[order] if v is not None else None
            d = d[lo:hi]
            v = v[lo:hi] if v is not None else None
            out[uname] = Column(d, sql_type, v, dictionary)
        return Table(out, hi - lo)


def _dictionary_sorted(dic) -> bool:
    a = np.asarray(dic, dtype=object)
    return bool(all(str(a[i]) <= str(a[i + 1]) for i in range(len(a) - 1)))


PROGRAMS = ProgramCache("compiled_select", 32)


def resolve_pipeline_inputs(scan, upper_filters, proj, executor):
    """Shared eligibility preamble + family parameterization of a root
    select chain — used by BOTH try_compiled_select and the fused PREDICT
    rung (compiled_predict.py), so a new eligibility rule can never
    silently apply to one and not the other.  Returns ``(dc, table,
    p_upper, p_scan_flts, p_exprs, params)`` or None (decline)."""
    dc = executor.context.schema[scan.schema_name].tables.get(scan.table_name)
    if dc is None:
        return None  # view-backed scans take the eager path
    from ..datacontainer import LazyParquetContainer

    if isinstance(dc, LazyParquetContainer):
        return None  # IO-pushdown path already minimizes transfers
    table = executor.get_table(scan.schema_name, scan.table_name)
    if scan.projection is not None:
        table = table.select(scan.projection)
    if not table.column_names:
        return None
    from ..parallel.dist_plan import table_is_sharded

    if table_is_sharded(table):
        # mesh-sharded scans keep the distributed operators (range-
        # partition sort leaves results sharded in sort order; pulling
        # the whole table to one host defeats the layout)
        return None
    # parameterize (families/): filter and projection literals become
    # runtime parameters so the cache key — and the mask/gather
    # executables — are shared by the whole query family.  LIMIT /
    # sort-fetch windows stay static (they steer host slicing and the
    # survivor pull), so each window is its own family.
    from .. import families

    pz = families.pipeline_parameterizer(executor.config)
    p_upper = [pz.rewrite(f) for f in upper_filters]
    p_scan_flts = [pz.rewrite(f) for f in scan.filters]
    p_exprs = [pz.rewrite(e) for e in proj.exprs]
    return dc, table, p_upper, p_scan_flts, p_exprs, pz.params


def select_family(scan, p_upper, p_scan_flts, p_exprs, sort_keys, sort_fetch,
                  limit, inner_limit) -> Tuple:
    """What shapes a root select chain's program (shared with the fused
    PREDICT rung): the parameterised plan text and the static windows."""
    return (
        tuple(scan.projection or ()),
        tuple(str(f) for f in p_upper),
        tuple(str(f) for f in p_scan_flts),
        tuple(str(e) for e in p_exprs),
        tuple(str(k.expr) + str(k.ascending) + str(k.nulls_first)
              for k in sort_keys) if sort_keys else None,
        sort_fetch,
        limit,
        inner_limit,
    )


def try_compiled_select(root, executor) -> Optional[Table]:
    """Attempt the one-kernel/one-transfer path for a ROOT select chain."""
    mode = executor.config.get("sql.compile.select", True)
    if not mode or not executor.config.get("sql.compile", True):
        return None
    got = _extract(root)
    if got is None:
        return None
    scan, upper_filters, proj, sort_keys, sort_fetch, limit, inner_limit = got
    try:
        resolved = resolve_pipeline_inputs(scan, upper_filters, proj,
                                           executor)
        if resolved is None:
            return None
        dc, table, p_upper, p_scan_flts, p_exprs, params = resolved
        family = select_family(scan, p_upper, p_scan_flts, p_exprs, sort_keys,
                               sort_fetch, limit, inner_limit)
        bucket = (dc.uid, table.num_rows, table.padded_rows)
        ctx = executor.context

        def construct():
            obj = CompiledSelect(table, scan, p_upper, p_scan_flts, proj,
                                 p_exprs, sort_keys, sort_fetch, limit,
                                 inner_limit, params)
            # cached pipelines must not pin the construction table's HBM
            obj.table = None
            return obj

        # the warming run compiles the mask + the first gather
        compiled, built_here = PROGRAMS.get_or_build(
            ctx, family, bucket, construct,
            warm=lambda obj: obj.run(table, params), params=params)
        if compiled is None:
            return None  # deferred to the background compiler
        if built_here:
            record_predicate_spaces(ctx, compiled)
        from ..resilience import faults

        faults.maybe_inject("oom", executor.config)
        result = PROGRAMS.run(
            ctx, family, bucket, compiled, params,
            solo=lambda: compiled.run(table, params),
            batched=lambda members: compiled.run_batched(table, members))
        if compiled.has_encoded:
            # late materialization: only surviving rows decoded (in the
            # per-bucket gather), and only at the root
            ctx.metrics.inc("columnar.encoding.late_rows", result.num_rows)
        return result
    except _Unsupported as e:
        logger.debug("compiled select unsupported: %s", e)
        return None
    except (ValueError, TypeError, NotImplementedError) as e:
        # an expression the trace evaluator mis-shapes must never sink the
        # query — the eager converters are always correct
        logger.debug("compiled select declined: %s", e)
        return None
