"""Compiled join->aggregate pipelines: the whole probe side in ONE jit.

Role parity: the reference executes joins as dask hash-shuffle merges feeding
a tree aggregation (reference physical/rel/logical/join.py:241-246,
aggregate.py:321) — many materialized intermediates.  TPU-first mechanism:
for left-deep chains of INNER equijoins whose build sides have unique
dense-int keys (every PK/FK star join in TPC-H/DS), each probe row matches
at most ONE build row, so the entire pipeline — scan filters, N pointer
joins, projection arithmetic, segment aggregation — is static-shaped and
fuses into a single XLA program over the probe table:

    build sides  : executed eagerly (small after filters), value-indexed
                   LUTs scattered once per table version
    probe side   : filters become masks (nothing compacts), joins become
                   `lut[key - rmin]` gathers carrying a matched mask,
                   build columns materialize as gathers through the pointer
    aggregation  : group keys that live on one build table (or are that
                   join's key) make the build-row pointer itself the segment
                   id — no factorize, no sort; segment reductions land at
                   HBM bandwidth

One device sync for the whole query (the group-presence compaction).
"""
from __future__ import annotations

import logging
from dataclasses import dataclass, replace as _rp
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.column import Column
from ..columnar.dtypes import STRING_TYPES, SqlType, sql_to_np
from ..columnar.table import Table
from ..ops.join import dense_unique_lut
from ..planner import plan as p
from ..planner.expressions import (
    AggExpr,
    ColumnRef,
    Expr,
    shift_columns,
    transform,
    walk,
)
from ..columnar.encodings import Encoding
from .compiled import (
    PARAMS_SLOT,
    _ColMeta,
    _TraceEval,
    _Unsupported,
    check_agg_static_support,
    check_no_rle,
    count_codespace_predicates,
    record_predicate_spaces,
    decode_radix_group_key,
    segment_agg_outputs,
)
from .programs import ProgramCache

logger = logging.getLogger(__name__)

_MAX_JOINS = 6


@dataclass(frozen=True)
class _BuildRef(Expr):
    """Placeholder ref to column `col` of build table `k` during extraction;
    rewritten to an extended-slot ColumnRef before tracing."""

    k: int
    col: int
    sql_type: SqlType
    nullable: bool = True

    def children(self):
        return []


class _Extraction:
    def __init__(self):
        self.scan: Optional[p.TableScan] = None
        self.conjuncts: List[Expr] = []  # over global space (probe + _BuildRef)
        self.joins: List[dict] = []  # {"plan": right subplan, "lkey", "rkey"}


def _rewrite(expr: Expr, slots: List[Expr]) -> Expr:
    """Bind `expr`'s ColumnRefs (input-schema positions) to slot exprs."""

    def fn(x):
        if isinstance(x, ColumnRef) and type(x) is ColumnRef:
            return slots[x.index]
        return x

    return transform(expr, fn)


def _walk_left_spine(node, ext: _Extraction) -> Optional[List[Expr]]:
    """Returns the node's output as a list of slot exprs, or None to decline.

    Probe-side columns/computations stay as exprs over the scan schema;
    build-side columns become _BuildRef markers.  Filters anywhere on the
    spine turn into conjuncts — INNER-join chains are pure AND pipelines,
    so predicate position doesn't matter for the final row mask."""
    if isinstance(node, p.SubqueryAlias):
        return _walk_left_spine(node.inputs()[0], ext)
    if isinstance(node, p.Projection):
        inner = _walk_left_spine(node.input, ext)
        if inner is None:
            return None
        return [_rewrite(e, inner) for e in node.exprs]
    if isinstance(node, p.Filter):
        inner = _walk_left_spine(node.input, ext)
        if inner is None:
            return None
        ext.conjuncts.append(_rewrite(node.predicate, inner))
        return inner
    if isinstance(node, p.Join):
        if node.join_type != "INNER" or node.filter is not None:
            return None
        if len(node.on) != 1 or len(ext.joins) >= _MAX_JOINS:
            return None
        left = _walk_left_spine(node.left, ext)
        if left is None:
            return None
        k = len(ext.joins)
        lkey_raw, rkey_raw = node.on[0]
        lkey = _rewrite(lkey_raw, left)
        rkey = shift_columns(rkey_raw, -len(node.left.schema))
        ext.joins.append({"plan": node.right, "lkey": lkey, "rkey": rkey})
        rslots = [_BuildRef(k, j, f.sql_type, f.nullable)
                  for j, f in enumerate(node.right.schema)]
        return left + rslots
    if isinstance(node, p.TableScan):
        if ext.scan is not None:
            return None  # a second scan can only mean a non-left-deep shape
        ext.scan = node
        ext.conjuncts.extend(node.filters)
        return [ColumnRef(j, f.name, f.sql_type, f.nullable)
                for j, f in enumerate(node.schema)]
    return None


def _extract(agg: p.Aggregate):
    ext = _Extraction()
    slots = _walk_left_spine(agg.input, ext)
    if slots is None or ext.scan is None or not ext.joins:
        return None
    group_exprs = [_rewrite(e, slots) for e in agg.group_exprs]
    agg_exprs = []
    for a in agg.agg_exprs:
        new_args = tuple(_rewrite(x, slots) for x in a.args)
        new_filter = _rewrite(a.filter, slots) if a.filter is not None else None
        agg_exprs.append(_rp(a, args=new_args, filter=new_filter))
    return ext, group_exprs, agg_exprs


def _choose_gid_join(ext, group_exprs) -> Optional[Tuple[int, List[int]]]:
    """Find a join k whose build-row pointer can serve as the segment id.

    Sound only when the group keys functionally DETERMINE the build row:
    the key set must include join k's key itself (probe-side expr, or the
    build key column), and every other key must be a column of build k
    (functionally dependent on the row).  Grouping by a non-key build
    column (e.g. a category shared by many dim rows) must NOT use the
    pointer — it would split one group per build row — that case goes
    through the radix gid instead.  Returns (k, build col per group expr)."""
    if not group_exprs:
        return (-1, [])  # global aggregate
    for k in range(len(ext.joins) - 1, -1, -1):
        rkey = ext.joins[k]["rkey"]
        if not (isinstance(rkey, ColumnRef) and type(rkey) is ColumnRef):
            continue
        cols = []
        has_key = False
        ok = True
        for g in group_exprs:
            if g == ext.joins[k]["lkey"] or (
                    isinstance(g, _BuildRef) and g.k == k
                    and g.col == rkey.index):
                cols.append(rkey.index)
                has_key = True
            elif isinstance(g, _BuildRef) and g.k == k:
                cols.append(g.col)
            else:
                ok = False
                break
        if ok and has_key:
            return (k, cols)
    return None


class _SlotMeta:
    """Duck-typed stand-in for Table inside _TraceEval: column metadata for
    the extended slot space (probe scan columns + gathered build columns)."""

    def __init__(self, cols: List[Column], names: List[str]):
        self.columns = dict(zip(names, cols))
        self.column_names = names


class CompiledJoinAggregate:
    """One compiled scan->joins->aggregate pipeline bound to concrete tables."""

    def __init__(self, rel: p.Aggregate, ext: _Extraction, group_exprs,
                 agg_exprs, probe_table: Table, build_tables: List[Table],
                 executor):
        self.rel = rel
        self.ext = ext
        self.probe_table = probe_table
        self.build_tables = build_tables

        check_agg_static_support(agg_exprs)
        check_no_rle(probe_table)
        #: compressed-domain accounting: probe-side scans read encoded bytes
        self.has_encoded = any(
            getattr(c, "encoding", Encoding.PLAIN) is not Encoding.PLAIN
            for c in probe_table.columns.values())

        choice = _choose_gid_join(ext, group_exprs)
        if choice is not None:
            self.gid_join, self.group_cols = choice
            self.radix_spec = None
        else:
            # radix gid over the (gathered) group-key values — the general
            # merge-correct form; pointer gid above is the high-cardinality
            # escape hatch for group-by-join-key shapes
            self.gid_join, self.group_cols = None, []
            self.radix_spec = self._plan_radix(group_exprs, probe_table,
                                               build_tables)

        # eager per-build prep: key column + LUT (reused across runs of the
        # same table version via the plugin-level cache)
        self.luts: List[Tuple[int, jnp.ndarray]] = []
        rkeys = []
        for j, bt in zip(ext.joins, build_tables):
            kc = executor.eval_expr(j["rkey"], bt)
            if kc.sql_type in STRING_TYPES:
                raise _Unsupported("string join key")
            prep = dense_unique_lut(kc.data, kc.validity)
            if prep is None:
                raise _Unsupported("build keys not unique-dense ints")
            self.luts.append(prep)
            rkeys.append(kc)

        # global slot space: probe scan columns, then every _BuildRef used
        n_probe = len(probe_table.column_names)
        used: Dict[Tuple[int, int], int] = {}
        all_exprs = (ext.conjuncts + [j["lkey"] for j in ext.joins]
                     + [x for a in agg_exprs for x in a.args]
                     + [a.filter for a in agg_exprs if a.filter is not None])
        if self.radix_spec is not None:
            all_exprs = all_exprs + list(group_exprs)
        for e in all_exprs:
            for sub in walk(e):
                if isinstance(sub, _BuildRef):
                    used.setdefault((sub.k, sub.col), n_probe + len(used))
        self.used_build_slots = used

        def finalize(expr):
            def fn(x):
                if isinstance(x, _BuildRef):
                    return ColumnRef(used[(x.k, x.col)], f"__b{x.k}_{x.col}",
                                     x.sql_type, x.nullable)
                return x

            return transform(expr, fn)

        self.conjuncts = [finalize(e) for e in ext.conjuncts]
        self.lkeys = [finalize(j["lkey"]) for j in ext.joins]
        if self.radix_spec is not None:
            self.radix_spec = [dict(s, ref=finalize(s["ref"]),
                                    col=_ColMeta(s["col"]))
                               for s in self.radix_spec]
        self.agg_exprs = [
            _rp(a, args=tuple(finalize(x) for x in a.args),
                filter=finalize(a.filter) if a.filter is not None else None)
            for a in agg_exprs]

        # metadata-only columns for the trace-time evaluator: the jit
        # closure must not pin probe/build device buffers (ADVICE r2)
        meta_cols = [_ColMeta(probe_table.columns[n])
                     for n in probe_table.column_names]
        meta_names = list(probe_table.column_names)
        for (k, col), _slot in sorted(used.items(), key=lambda kv: kv[1]):
            bt = build_tables[k]
            meta_cols.append(_ColMeta(bt.columns[bt.column_names[col]]))
            meta_names.append(f"__b{k}_{col}")
        self._ev = _TraceEval(_SlotMeta(meta_cols, meta_names))
        self.codespace_preds, self.valuespace_preds = \
            count_codespace_predicates(
                list(self.conjuncts)
                + [x for a in self.agg_exprs for x in list(a.args)
                   + ([a.filter] if a.filter is not None else [])],
                self._ev.table) if self.has_encoded else (0, 0)
        # segment-reduction strategy: one mode per pipeline, chosen from the
        # (static) group domain — radix product, or the gid build table's
        # row count for pointer gids
        if self.radix_spec is not None:
            domain_est = 1
            for s in self.radix_spec:
                domain_est *= s["r"]
        elif self.gid_join is not None and self.gid_join >= 0:
            domain_est = build_tables[self.gid_join].num_rows
        else:
            domain_est = 1
        from ..ops.pallas_kernels import choose_segsum_impl

        self.domain = domain_est
        self.segsum_mode = choose_segsum_impl(executor.config, domain_est)
        #: (kind, np.dtype) per packed output row; filled when _fn traces
        self._pack_tags: List[Tuple[str, np.dtype]] = []
        self._fn = jax.jit(self._build())
        #: compile-watchdog hint: True after _fn compiled for these shapes
        self._warm = False

    @staticmethod
    def _plan_radix(group_exprs, probe_table, build_tables):
        """Mixed-radix gid plan over group-key columns (same scheme as
        CompiledAggregate: dict strings / bools / small-int ranges, one
        extra code per key for NULL)."""
        spec = []
        domain = 1
        pending = []  # (slot, device min, device max): ONE pull for all keys
        for g in group_exprs:
            if isinstance(g, _BuildRef):
                bt = build_tables[g.k]
                col = bt.columns[bt.column_names[g.col]]
                row_valid = bt.row_valid
            elif isinstance(g, ColumnRef) and type(g) is ColumnRef:
                col = probe_table.columns[probe_table.column_names[g.index]]
                row_valid = probe_table.row_valid
            else:
                raise _Unsupported("non-column group key")
            if col.sql_type in STRING_TYPES and col.dictionary is not None:
                spec.append({"ref": g, "kind": "str",
                             "r": len(col.dictionary) + 1, "off": 0,
                             "col": col})
            elif getattr(col, "encoding", Encoding.PLAIN) is Encoding.DICT:
                # numeric dictionary codes are the radix domain directly
                spec.append({"ref": g, "kind": "dict", "raw": True,
                             "r": len(col.enc_values) + 1, "off": 0,
                             "col": col})
            elif col.data.dtype == jnp.bool_:
                spec.append({"ref": g, "kind": "bool", "r": 3, "off": 0,
                             "col": col})
            elif jnp.issubdtype(col.data.dtype, jnp.integer) and len(col):
                from .compiled import padded_int_bounds

                # PLAIN values and FOR codes alike: bounds are over the
                # STORED ints (the kernel reads the raw slot for encoded
                # keys; host decode maps codes back through the affine)
                lo, hi = padded_int_bounds(col.data, row_valid)
                pending.append((len(spec), lo, hi))
                spec.append({
                    "ref": g, "kind": "int", "r": None, "off": None,
                    "col": col,
                    "raw": getattr(col, "encoding",
                                   Encoding.PLAIN) is Encoding.FOR})
            else:
                raise _Unsupported("group key not radix-encodable")
        from ..ops.grouping import RADIX_DOMAIN_LIMIT, resolve_int_bounds

        spans = resolve_int_bounds(pending, RADIX_DOMAIN_LIMIT)
        if spans is None:
            raise _Unsupported("integer key range too large")
        for slot, (span, lo) in spans.items():
            spec[slot]["r"] = span + 1
            spec[slot]["off"] = lo
        for entry in spec:
            domain *= entry["r"]
            if domain > RADIX_DOMAIN_LIMIT:
                raise _Unsupported("group domain too large")
        return spec

    def _build(self):
        ev = self._ev
        n_probe = len(self.probe_table.column_names)
        used = self.used_build_slots
        conjuncts = self.conjuncts
        lkeys = self.lkeys
        agg_exprs = self.agg_exprs
        gid_join = -1 if self.gid_join is None else self.gid_join
        radix_spec = self.radix_spec
        n_joins = len(self.ext.joins)
        rmins = [rmin for rmin, _ in self.luts]

        def fn(probe_datas, probe_valids, luts, build_cols, row_valid,
               params=()):
            # build_cols: {(k,col): (data, valid_or_None)} full build tables
            n_rows = probe_datas[0].shape[0] if probe_datas else 0
            slots: Dict[int, Tuple] = {
                i: (probe_datas[i], probe_valids[i]) for i in range(n_probe)}
            slots[PARAMS_SLOT] = params
            # padded sharded probe: the row mask keeps pad rows out of every
            # join match, filter, and reduction (exact-spec sharding)
            mask = jnp.ones(n_rows, dtype=bool) if row_valid is None \
                else row_valid
            ri_safe: List[jnp.ndarray] = []
            for k in range(n_joins):
                kd, kv = ev.eval(lkeys[k], slots)
                lut = luts[k]
                size = lut.shape[0]
                # widen sub-int32 keys before subtracting (narrow dtypes can
                # overflow under `key - rmin`); if rmin itself doesn't fit
                # the key dtype, compute in int64 (no match is representable
                # without it).  LUT positions/row-ids always fit int32.
                rmin = rmins[k]
                if np.dtype(kd.dtype).itemsize < 4:
                    kd = kd.astype(jnp.int32)
                if rmin:
                    info = jnp.iinfo(kd.dtype)
                    if info.min <= rmin <= info.max:
                        # in-dtype subtraction can wrap for probe keys far
                        # outside the build range (e.g. kd < INT_MIN + rmin)
                        # and land back inside [0, size) — bound the KEY
                        # itself first; within [rmin, rmin+size-1] the
                        # subtraction is exact (ADVICE r3)
                        lo_k = jnp.asarray(rmin, dtype=kd.dtype)
                        hi_k = jnp.asarray(min(rmin + size - 1, int(info.max)),
                                           dtype=kd.dtype)
                        inb = (kd >= lo_k) & (kd <= hi_k)
                        idx = jnp.where(inb, kd - lo_k,
                                        jnp.zeros_like(kd))
                    else:
                        idx = kd.astype(jnp.int64) - rmin
                        inb = (idx >= 0) & (idx < size)
                else:
                    idx = kd
                    inb = (idx >= 0) & (idx < size)
                idx32 = jnp.clip(idx, 0, size - 1).astype(jnp.int32)
                ri = jnp.where(inb, lut[idx32].astype(jnp.int32), jnp.int32(-1))
                if kv is not None:
                    ri = jnp.where(kv, ri, -1)
                matched = ri >= 0
                mask = mask & matched
                safe = jnp.clip(ri, 0, None)
                ri_safe.append(safe)
                # materialize this build table's used columns into the slot
                # space so later keys/aggs/filters can reference them
                for (bk, col), slot in used.items():
                    if bk != k:
                        continue
                    bd, bv = build_cols[(bk, col)]
                    d = bd[safe]
                    v = matched if bv is None else (matched & bv[safe])
                    slots[slot] = (d, v)
            for f in conjuncts:
                d, v = ev.eval(f, slots)
                mask = mask & (d if v is None else (d & v))
            if radix_spec is not None:
                gid = jnp.zeros(n_rows, dtype=jnp.int32)
                domain = 1
                for s in radix_spec:
                    if s.get("raw"):
                        # encoded key: the CODES are the radix digits —
                        # never decode inside the kernel
                        d, v = slots[s["ref"].index]
                    else:
                        d, v = ev.eval(s["ref"], slots)
                    r = s["r"]
                    if s["kind"] == "bool":
                        code = d.astype(jnp.int32)
                    else:
                        # widen narrow ints before subtracting (overflow),
                        # subtract in the (possibly int64) source dtype, then
                        # narrow — span always fits int32
                        if np.dtype(d.dtype).itemsize < 4:
                            d = d.astype(jnp.int32)
                        if s["off"]:
                            d = d - jnp.asarray(s["off"], dtype=d.dtype)
                        code = d.astype(jnp.int32)
                    code = jnp.clip(code, 0, r - 2)
                    if v is not None:
                        code = jnp.where(v, code, r - 1)
                    gid = gid * r + code
                    domain *= r
            elif gid_join < 0:
                gid = jnp.zeros(n_rows, dtype=jnp.int32)
                domain = 1
            else:
                gid = ri_safe[gid_join].astype(jnp.int32)
                domain = build_domains[gid_join]
            from .compiled import pack_flat

            reducer = self._make_reducer(gid, domain, n_rows)
            hit_h = reducer.count(mask)
            outs = segment_agg_outputs(ev, slots, agg_exprs, mask, gid, domain,
                                       reducer)
            hit = reducer.get(hit_h) > 0
            flat = [hit]
            for d, v in outs:
                flat.append(d)
                flat.append(v if v is not None else jnp.ones_like(hit))
            tags: List[Tuple[str, np.dtype]] = []
            out = pack_flat(flat, tags)
            self._pack_tags = tags
            return out

        # domains are python ints (build table row counts) — bind them now
        build_domains = [bt.num_rows for bt in self.build_tables]
        return fn

    def _make_reducer(self, gid, domain: int, n_rows: int):
        """Reducer factory seam — overridden by the SPMD join rung
        (spmd/join.py) to combine per-shard partials with collectives."""
        from .compiled import SegmentReducer

        return SegmentReducer(gid, domain, self.segsum_mode, n_rows)

    def _run_args(self, params: Tuple):
        """The concrete kernel arguments for one run (shared with the SPMD
        rung, spmd/join.py): (probe_datas, probe_valids, luts, build_cols,
        row_valid, params)."""
        pt = self.probe_table
        probe_datas = tuple(pt.columns[n].data for n in pt.column_names)
        probe_valids = tuple(pt.columns[n].validity for n in pt.column_names)
        luts = tuple(lut for _, lut in self.luts)
        build_cols = {}
        for (k, col), _slot in self.used_build_slots.items():
            bt = self.build_tables[k]
            c = bt.columns[bt.column_names[col]]
            build_cols[(k, col)] = (c.data, c.validity)
        return (probe_datas, probe_valids, luts, build_cols, pt.row_valid,
                tuple(params))

    def run(self, params: Tuple = ()) -> Table:
        args = self._run_args(params)
        from ..parallel import dist_plan as _dp

        if any(_dp.array_is_sharded(d) for d in args[0]):
            # SPMD over the sharded probe: GSPMD inserts the all-reduce for
            # the segment outputs; joined rows never materialize anywhere
            _dp.STATS["sharded_join_agg"] += 1
        from ..observability import timed_jit_call

        packed = timed_jit_call("compiled_join_aggregate", self._fn, *args,
                                may_compile=not self._warm)
        self._warm = True
        from .compiled import fetch_packed

        tags = self._pack_tags
        host, present = fetch_packed(packed, self.domain)
        return self._decode_result(host, present, tags)

    def _decode_result(self, host, present, tags, build_tables=None) -> Table:
        from .compiled import unpack_row

        # the SPMD rung passes tables per call (no shared rebinding); the
        # single-chip path keeps its bound self state
        if build_tables is None:
            build_tables = self.build_tables
        is_global = self.radix_spec is None and (self.gid_join is None
                                                 or self.gid_join < 0)
        if is_global and present.shape[0] == 0:
            # SQL: global aggregate over zero rows still yields one row
            present = np.zeros(1, dtype=np.int64)
            host = np.zeros((host.shape[0], 1), dtype=np.float64)
            for i, a in enumerate(self.rel.agg_exprs):
                if a.func in ("count", "count_star"):
                    host[2 + 2 * i] = 1.0  # COUNT stays valid (= 0), not NULL

        from .rel.base import unique_names

        names = unique_names([f.name for f in self.rel.schema])
        out: Dict[str, Column] = {}
        if self.radix_spec is not None:
            # decode group values from the mixed-radix id
            strides = []
            s = 1
            for spec in reversed(self.radix_spec):
                strides.append(s)
                s *= spec["r"]
            strides = list(reversed(strides))
            # host numpy decode: the group table is tiny, downstream operators
            # consume it without another device round trip
            for name, spec, stride in zip(names, self.radix_spec, strides):
                r = spec["r"]
                code = (present // stride) % r
                is_null = code == (r - 1)
                validity = ~is_null if bool(is_null.any()) else None
                code = np.minimum(code, r - 2)
                # shared host decode handles str/bool/plain-int AND the
                # encoded (DICT/FOR) key kinds
                out[name] = decode_radix_group_key(spec["col"], code,
                                                   spec["off"], validity)
            n_groups = len(self.radix_spec)
        elif self.gid_join is not None and self.gid_join >= 0:
            bt = build_tables[self.gid_join]
            for name, col_idx in zip(names, self.group_cols):
                c = bt.columns[bt.column_names[col_idx]]
                out[name] = c.take(present)
            n_groups = len(self.group_cols)
        else:
            n_groups = 0
        for i, a in enumerate(self.rel.agg_exprs):
            d = unpack_row(host, 1 + 2 * i, tags)
            v = unpack_row(host, 2 + 2 * i, tags) != 0.0
            target = sql_to_np(a.sql_type)
            d = d.astype(target) if d.dtype != target else d
            validity = None if bool(v.all()) else v
            out[names[n_groups + i]] = Column(d, a.sql_type, validity)
        return Table(out, int(present.shape[0]))


def _plan_nodes(node):
    yield node
    for k in node.inputs():
        yield from _plan_nodes(k)


# entries keep device-resident LUTs + string dictionaries warm across runs
# of the same table versions; capped so stale table versions can't pin HBM
# forever (ADVICE r2); probe/build table refs are dropped after every run
# (re-bound on each call)
PROGRAMS = ProgramCache("compiled_join_aggregate", 16)


def try_compiled_join_aggregate(rel: p.Aggregate, executor) -> Optional[Table]:
    """Attempt the one-jit join pipeline for an Aggregate subtree; None to
    fall back to the generic (eager) converters."""
    if not executor.config.get("sql.compile", True):
        return None
    if not executor.config.get("sql.compile.join_pipeline", True):
        return None
    extraction = _extract(rel)
    if extraction is None:
        return None
    ext, group_exprs, agg_exprs = extraction
    try:
        from ..datacontainer import LazyParquetContainer

        dc = executor.context.schema[ext.scan.schema_name].tables.get(
            ext.scan.table_name)
        if dc is None:
            return None  # view-backed probe scans take the eager path
        if isinstance(dc, LazyParquetContainer):
            # lazy parquet probes keep the eager TableScan path so scan
            # filters (incl. DPP in-arrays) reach pyarrow row-group pruning
            return None
        # every base table version must key the cache: the LUTs and string
        # dictionaries are baked per build-table contents.  Computed BEFORE
        # any execution so declines can short-circuit.
        uids = [dc.uid]
        for j in ext.joins:
            for node in _plan_nodes(j["plan"]):
                if isinstance(node, p.TableScan):
                    bdc = executor.context.schema[node.schema_name].tables.get(
                        node.table_name)
                    if bdc is None:
                        return None
                    uids.append(bdc.uid)
        decline_key = (tuple(uids), str(rel))
        if PROGRAMS.declined(decline_key):
            return None
        # cheap plan-only checks BEFORE any build-side execution (ADVICE r2:
        # an ineligible query used to pay for its build subtrees twice)
        check_agg_static_support(agg_exprs)
        # parameterize (families/): literals in the PROBE-side conjuncts
        # and aggregate arguments become runtime parameters.  Build-side
        # literals stay baked — they shape the eagerly-executed build
        # tables and their LUTs — and key the cache via the build plans'
        # reprs, so a build-side literal change is a different family.
        from .. import families

        pz = families.pipeline_parameterizer(executor.config)
        ext.conjuncts = [pz.rewrite(e) for e in ext.conjuncts]
        agg_exprs = [pz.rewrite_agg(a) for a in agg_exprs]
        params = pz.params
        probe_table = executor.get_table(ext.scan.schema_name,
                                         ext.scan.table_name)
        if ext.scan.projection is not None:
            probe_table = probe_table.select(ext.scan.projection)
        if not probe_table.column_names:
            return None
        # build sides run through the normal recursive converter (they may
        # be filtered scans, nested joins, anything) — compacted eagerly
        build_tables = [executor.execute(j["plan"]) for j in ext.joins]
        family = (
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
        ctx = executor.context
        # the constructor binds the tables this first run reads; the finally
        # below drops them.  No `warm`: this rung never defers
        compiled, built_here = PROGRAMS.get_or_build(
            ctx, family, bucket,
            lambda: CompiledJoinAggregate(rel, ext, group_exprs, agg_exprs,
                                          probe_table, build_tables,
                                          executor),
            params=params)
        if not built_here:
            compiled.probe_table = probe_table
            compiled.build_tables = build_tables
        if built_here:
            record_predicate_spaces(ctx, compiled)
        try:
            from ..resilience import faults

            faults.maybe_inject("oom", executor.config)
            result = compiled.run(params)
            if compiled.has_encoded:
                ctx.metrics.inc("columnar.encoding.late_rows",
                                result.num_rows)
            return result
        finally:
            # the LUTs/dictionaries stay warm; the (large) table refs do not
            compiled.probe_table = None
            compiled.build_tables = None
    except _Unsupported as e:
        logger.debug("compiled join pipeline unsupported: %s", e)
        if "decline_key" in locals():
            PROGRAMS.decline(decline_key)
        return None
