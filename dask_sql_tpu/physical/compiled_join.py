"""Compiled join->aggregate pipelines: the whole probe side in ONE jit.

Role parity: the reference executes joins as dask hash-shuffle merges feeding
a tree aggregation (reference physical/rel/logical/join.py:241-246,
aggregate.py:321) — many materialized intermediates.  TPU-first mechanism:
for left-deep chains of INNER equijoins whose build sides have unique
dense-int keys (every PK/FK star join in TPC-H/DS), each probe row matches
at most ONE build row, so the entire pipeline — scan filters, N pointer
joins, projection arithmetic, segment aggregation — is static-shaped and
fuses into a single XLA program over the probe table:

    build sides  : a filtered base-table scan stays WHOLE: its value-indexed
                   LUT is scattered once per table version over the
                   UNFILTERED key column and kept (`LUTS`), its conjuncts
                   are parameterised like the probe's (a string literal on
                   a dictionary-coded column rides as its code) and
                   evaluated in the program as a mask over the build
                   table's rows, read through the pointer.  So neither the
                   program's shapes nor its identity depend on the
                   literals: one executable per plan family and table
                   version.  Any other build side (a nested join, an
                   aggregate, computed columns, a key the LUT rule
                   declines) is executed eagerly per request as before,
                   its LUT built from the filtered rows
    probe side   : filters become masks, joins become `lut[key - rmin]`
                   gathers carrying a matched mask, build columns
                   materialize as gathers through the pointer
    compaction   : where the reducer's cost is per row (a scatter) and the
                   probe is large and on one chip, the rows that pass every
                   join and filter are compacted into a buffer of fixed
                   capacity (`compact_capacity`: 1/16 of the probe) and only
                   the aggregates' arguments of those rows are gathered,
                   evaluated and reduced; when more rows pass than the
                   buffer holds, a `lax.cond` in the SAME executable
                   reduces the probe whole under the mask, so the answer is
                   exact whatever the parameters select
    aggregation  : group keys that live on one build table (or are that
                   join's key) make the build-row pointer itself the segment
                   id — no factorize, no sort; segment reductions land at
                   HBM bandwidth
    tail         : under ORDER BY .. LIMIT k (`TopK`, handed down by the
                   Sort above) the k first groups are selected inside the
                   program over the `[domain]` state, exactly, and only
                   `[rows, k]` leaves the device: static shapes whatever
                   the parameters select.  Otherwise the present groups are
                   compacted on the device before the pull

One device sync for the whole query (the pull of the packed rows).
"""
from __future__ import annotations

import logging
import threading
from collections import OrderedDict
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
    _TableMeta,
    _TraceEval,
    _Unsupported,
    check_agg_static_support,
    check_no_rle,
    compact_positions,
    count_codespace_predicates,
    record_predicate_spaces,
    decode_radix_group_key,
    segment_agg_outputs,
)
from .programs import ProgramCache

logger = logging.getLogger(__name__)

_MAX_JOINS = 6
#: ORDER BY .. LIMIT k is selected inside the program up to this k (one
#: round of masked reductions over the group domain per row)
_MAX_TOPK = 64
#: widest LUT (bytes) a whole build side may keep resident; a configured
#: device budget (``analysis.estimate.device_budget_bytes``) below it holds
_LUT_MAX_BYTES = 1 << 30
#: probes below this many rows keep the uncompacted program: a scatter over
#: so few rows costs less than the sort that would spare it
_COMPACT_MIN_ROWS = 1 << 20
#: the compact buffer's rows come in the blocks the engine pads tables to
_COMPACT_BLOCK = 32_768


def compact_capacity(n_rows: int) -> int:
    """Rows of the compact buffer of a probe of `n_rows`: a sixteenth of the
    probe in whole blocks, a function of the program's shapes alone (TPC-H
    Q3 passes 0.5% of LINEITEM, 2.5% without its SEGMENT).  More rows than
    that pass: the program's other branch reduces the probe whole."""
    return -(-(n_rows // 16) // _COMPACT_BLOCK) * _COMPACT_BLOCK


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
        #: {"plan": right subplan, "lkey", "rkey"}; `_plan_whole_builds`
        #: adds "whole": the build side's own conjuncts where it is a
        #: filtered base-table scan, else None
        self.joins: List[dict] = []


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


def _plan_whole_builds(ext: _Extraction, group_exprs, agg_exprs):
    """Mark the build sides that are a filtered scan of ONE base table
    (Filter / SubqueryAlias / column-picking Projection over a TableScan):
    ``join["whole"]`` gets their conjuncts over the scan's schema and
    ``join["plan"]`` becomes that scan with the conjuncts as its filters,
    so that "build table k" is the scan's own (projected) table whether the
    join keeps it whole or, the LUT rule declining, executes it eagerly.
    `_BuildRef`s and the build key are re-based from the subplan's output
    onto the scan's columns.  Returns the re-based (group_exprs, agg_exprs);
    other build sides are left as they are."""
    rebase: Dict[int, List[int]] = {}
    for k, j in enumerate(ext.joins):
        j["whole"] = None
        sub = _Extraction()
        slots = _walk_left_spine(j["plan"], sub)
        if slots is None or sub.scan is None or sub.joins \
                or not all(type(x) is ColumnRef for x in slots):
            continue
        rebase[k] = [x.index for x in slots]
        j["whole"] = list(sub.conjuncts)
        j["rkey"] = _rewrite(j["rkey"], slots)
        j["plan"] = _rp(sub.scan, filters=list(sub.conjuncts))

    def fn(x):
        if isinstance(x, _BuildRef) and x.k in rebase:
            return _rp(x, col=rebase[x.k][x.col])
        return x

    ext.conjuncts = [transform(e, fn) for e in ext.conjuncts]
    for j in ext.joins:
        j["lkey"] = transform(j["lkey"], fn)
    group_exprs = [transform(e, fn) for e in group_exprs]
    agg_exprs = [
        _rp(a, args=tuple(transform(x, fn) for x in a.args),
            filter=transform(a.filter, fn) if a.filter is not None else None)
        for a in agg_exprs]
    return group_exprs, agg_exprs


@dataclass(frozen=True)
class TopK:
    """ORDER BY .. LIMIT above an Aggregate, as the Sort plugin hands it
    down (`Executor.topk_hints`): the first `k` rows by `keys`, each
    ``(aggregate output column, ascending, nulls first)``.  A rung that
    takes the hint returns those rows alone, in that order; one that does
    not returns every group and the Sort above does the work."""

    k: int
    keys: Tuple[Tuple[int, bool, bool], ...]


def select_topk(alive, keys, k: int):
    """The `k` first of the rows where `alive`, by `keys` = ``[(data,
    validity or None, ascending, nulls first)]`` and then by position, under
    trace: ``(positions int32[k], found bool[k])``.  Exact in the keys' own
    dtypes: one round per row narrows the candidates key by key with masked
    min / max reductions, so no 64-bit sort and no shape that depends on
    how many rows are alive.  NaN orders as the largest value, as
    `ops/sorting.py` has it."""

    def narrow(cand, d, v, asc, nulls_first):
        if d.dtype == jnp.bool_:
            d = d.astype(jnp.int32)
        if jnp.issubdtype(d.dtype, jnp.floating):
            d = jnp.where(jnp.isnan(d), jnp.inf, d)
            lo, hi = -jnp.inf, jnp.inf
        else:
            lo, hi = jnp.iinfo(d.dtype).min, jnp.iinfo(d.dtype).max
        vals = cand if v is None else cand & v
        best = jnp.min(jnp.where(vals, d, hi)) if asc \
            else jnp.max(jnp.where(vals, d, lo))
        on_value = vals & (d == best)
        if v is None:
            return on_value
        nulls = cand & ~v
        take_nulls = jnp.any(nulls) if nulls_first else ~jnp.any(vals)
        return jnp.where(take_nulls, nulls, on_value)

    def pick(i, state):
        alive, at, found = state
        cand = alive
        for d, v, asc, nulls_first in keys:
            cand = narrow(cand, d, v, asc, nulls_first)
        first = jnp.argmax(cand).astype(jnp.int32)
        return (alive.at[first].set(False), at.at[i].set(first),
                found.at[i].set(cand[first]))

    _, at, found = jax.lax.fori_loop(
        0, k, pick, (alive, jnp.zeros(k, dtype=jnp.int32),
                     jnp.zeros(k, dtype=bool)))
    return at, found


class LutCache:
    """The LUTs of whole build sides, one per (table version, key column,
    byte budget), built on first use and kept: bounded, least recently used
    first out, so a replaced table's LUT cannot pin device memory for good.
    A key the rule declines is remembered as None."""

    def __init__(self, cap: int):
        self.cap = cap
        self._entries: "OrderedDict[Tuple, Optional[Tuple]]" = OrderedDict()
        self._lock = threading.Lock()

    def get_or_build(self, key: Tuple, build):
        """``(entry, built_here)``; concurrent first uses may both build,
        the later insert wins (the tables are equal)."""
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return self._entries[key], False
        entry = build()
        with self._lock:
            self._entries[key] = entry
            while len(self._entries) > self.cap:
                self._entries.popitem(last=False)
        return entry, True

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


LUTS = LutCache(16)


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


def build_lut(executor, rkey: Expr, table: Table,
              max_bytes: Optional[int] = None):
    """``(rmin, lut)`` of `table`'s join key `rkey` (`dense_unique_lut`,
    which says what `max_bytes` means), or None where the key declines."""
    kc = executor.eval_expr(rkey, table).decode()
    if kc.sql_type in STRING_TYPES:
        return None
    return dense_unique_lut(kc.data, kc.validity, max_bytes=max_bytes)


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
                 executor, whole: Optional[List[Optional[dict]]] = None,
                 topk: Optional[TopK] = None):
        """``whole[k]``: None where build table k was executed eagerly (its
        LUT is built here, from the rows it has left), else ``{"conjuncts":
        the build side's own parameterised conjuncts, "lut": (rmin, lut)}``
        with build table k the base table's scan, unfiltered."""
        self.rel = rel
        self.ext = ext
        self.probe_table = probe_table
        self.build_tables = build_tables
        #: the registry of the context whose request this program is serving
        #: (rebound with the tables): counts `join.compact.*` per request
        self.metrics = executor.context.metrics
        whole = whole if whole is not None else [None] * len(build_tables)

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

        # per-build prep: a whole build side brings the LUT of its table
        # version (`LUTS`); an eagerly executed one gets its own here
        self.luts: List[Tuple[int, jnp.ndarray]] = []
        for j, bt, w in zip(ext.joins, build_tables, whole):
            prep = w["lut"] if w is not None else build_lut(
                executor, j["rkey"], bt)
            if prep is None:
                raise _Unsupported("build keys not unique-dense ints")
            self.luts.append(prep)
        #: per join: the conjuncts evaluated over the WHOLE build table in
        #: the program (None: the build side came filtered)
        self.build_conjuncts: List[Optional[List[Expr]]] = [
            None if w is None else list(w["conjuncts"]) for w in whole]
        self._build_evs = [
            None if w is None else _TraceEval(_TableMeta(bt))
            for bt, w in zip(build_tables, whole)]

        # global slot space: probe scan columns, then every _BuildRef used
        n_probe = len(probe_table.column_names)
        used: Dict[Tuple[int, int], int] = {}
        rest = (ext.conjuncts + [x for a in agg_exprs for x in a.args]
                + [a.filter for a in agg_exprs if a.filter is not None])
        if self.radix_spec is not None:
            rest = rest + list(group_exprs)
        #: join k -> the WHOLE build side j whose rows it is probed from
        self.folded = self._plan_folds(ext, whole, rest)
        all_exprs = rest + [j["lkey"] for k, j in enumerate(ext.joins)
                            if k not in self.folded]
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

        def onto_build(expr):
            """A folded join's key over ITS parent build table's columns."""
            return transform(expr, lambda x: ColumnRef(
                x.col, f"__b{x.k}_{x.col}", x.sql_type, x.nullable)
                if isinstance(x, _BuildRef) else x)

        self.conjuncts = [finalize(e) for e in ext.conjuncts]
        self.lkeys = [onto_build(j["lkey"]) if k in self.folded
                      else finalize(j["lkey"])
                      for k, j in enumerate(ext.joins)]
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
        for conj, bev in zip(self.build_conjuncts, self._build_evs):
            if conj:
                code, value = count_codespace_predicates(conj, bev.table)
                self.codespace_preds += code
                self.valuespace_preds += value
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
        self.topk = self._plan_topk(topk, build_tables)
        self.compact_cap = self._plan_compaction(probe_table)
        #: every build column the program is handed: gathered through a
        #: pointer, read by a build side's own conjuncts, or a group key the
        #: top-k tail orders by and returns
        keys = set(used)
        for k, conj in enumerate(self.build_conjuncts):
            for e in conj or ():
                keys.update((k, sub.index) for sub in walk(e)
                            if type(sub) is ColumnRef)
        for k, j in self.folded.items():
            keys.update((j, sub.index) for sub in walk(self.lkeys[k])
                        if type(sub) is ColumnRef)
        if self.topk is not None:
            keys.update((self.gid_join, col) for col in self.topk["cols"])
        self.build_col_keys = sorted(keys)
        #: (kind, np.dtype) per packed output row; filled when _fn traces
        self._pack_tags: List[Tuple[str, np.dtype]] = []
        self._fn = jax.jit(self._build())
        #: compile-watchdog hint: True after _fn compiled for these shapes
        self._warm = False

    def _plan_folds(self, ext, whole, rest) -> Dict[int, int]:
        """Joins to probe from an earlier build side's rows instead of the
        probe table's: join k whose key reads columns of ONE whole build
        side j alone (ORDERS -> CUSTOMER under LINEITEM -> ORDERS), where
        nothing but such joins reads build k's columns.  Its match then
        narrows build j's row mask, at j's row count, and the probe pays
        neither the gather of the key through j's pointer nor the second
        LUT's.  `rest`: every expression evaluated at the probe's rows
        other than the join keys."""
        folded: Dict[int, int] = {}

        def reads(exprs, k):
            return any(isinstance(sub, _BuildRef) and sub.k == k
                       for e in exprs for sub in walk(e))

        for k in range(len(ext.joins) - 1, 0, -1):
            subs = list(walk(ext.joins[k]["lkey"]))
            parents = {sub.k for sub in subs if isinstance(sub, _BuildRef)}
            if len(parents) != 1 or any(type(sub) is ColumnRef
                                        for sub in subs):
                continue
            (j,) = parents
            later = [ext.joins[m]["lkey"] for m in range(k + 1,
                                                         len(ext.joins))
                     if folded.get(m) != k]
            if whole[j] is not None and k != self.gid_join \
                    and not reads(rest + later, k):
                folded[k] = j
        return folded

    def _plan_compaction(self, probe_table) -> int:
        """The compact buffer's rows, or 0 where the program reduces the
        probe whole as ever: a segment sum that is not a scatter (its cost
        is not per row), a probe sharded over a mesh or padded (the sharded
        rung, spmd/join.py, traces this class's body per shard), a probe
        under `_COMPACT_MIN_ROWS`."""
        from ..parallel import dist_plan as _dp

        datas = [c.data for c in probe_table.columns.values()]
        n_rows = int(datas[0].shape[0])
        if (type(self) is not CompiledJoinAggregate
                or self.segsum_mode != "scatter"
                or probe_table.row_valid is not None
                or any(_dp.array_is_sharded(d) for d in datas)
                or not _COMPACT_MIN_ROWS <= n_rows < (1 << 31)):
            return 0
        return compact_capacity(n_rows)

    def _plan_topk(self, topk: Optional[TopK], build_tables):
        """The top-k tail this program runs, or None where the hint is
        absent or names what the tail cannot order: ``{"k", "keys": [(kind,
        index, ascending, nulls first)], "cols"}`` with kind ``"agg"`` (an
        aggregate's output) or ``"group"`` (a group key: a column of the
        pointer-gid build table, ordered on its stored integers, which DICT,
        FOR and PLAIN all keep in value order), and ``cols`` the group-key
        columns whose rows the program gathers for the result."""
        if topk is None or not 0 < topk.k <= _MAX_TOPK:
            return None
        if self.gid_join is None or self.gid_join < 0:
            return None  # a radix domain is small: pulled whole as before
        bt = build_tables[self.gid_join]
        n_groups = len(self.group_cols)

        def sortable(col) -> bool:
            enc = getattr(col, "encoding", Encoding.PLAIN)
            if enc is Encoding.RLE or col.sql_type in STRING_TYPES:
                return False
            return enc is not Encoding.FOR or col.enc_scale > 0

        keys = []
        for index, asc, nulls_first in topk.keys:
            if index >= n_groups:
                keys.append(("agg", index - n_groups, asc, nulls_first))
                continue
            bcol = self.group_cols[index]
            if not sortable(bt.columns[bt.column_names[bcol]]):
                return None
            keys.append(("group", bcol, asc, nulls_first))
        cols = sorted({
            c for c in self.group_cols
            if getattr(bt.columns[bt.column_names[c]], "encoding",
                       Encoding.PLAIN) is not Encoding.RLE})
        return {"k": topk.k, "keys": keys, "cols": cols}

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
        build_conjuncts = self.build_conjuncts
        build_evs = self._build_evs
        folded = self.folded
        topk = self.topk
        compact_cap = self.compact_cap
        #: the slots the aggregates read: all the compact branch gathers
        agg_slots = sorted({
            sub.index for a in agg_exprs
            for e in list(a.args) + ([a.filter] if a.filter is not None
                                     else [])
            for sub in walk(e) if type(sub) is ColumnRef})

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
            def pointer(k, kd, kv, lut):
                """Build-row index per key of join `k` (-1: no row)."""
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
                return ri if kv is None else jnp.where(kv, ri, -1)

            def kept_lut(k):
                """LUT `k` without the rows that build side k's own filters
                reject, or a join probed from its rows (`folded`) leaves
                unmatched: the mask is evaluated over ITS rows and folded
                into the pointers the LUT holds (gathers of the build's and
                the LUT's size, none of the probe's), so a key whose row
                falls out finds no row."""
                lut = luts[k]
                if build_evs[k] is None:
                    return lut  # an eagerly executed build side came filtered
                bslots = {col: build_cols[(bk, col)]
                          for (bk, col) in build_cols if bk == k}
                bslots[PARAMS_SLOT] = params
                keep = None
                for f in build_conjuncts[k]:
                    d, v = build_evs[k].eval(f, bslots)
                    d = d if v is None else (d & v)
                    keep = d if keep is None else (keep & d)
                for m, parent in folded.items():
                    if parent == k:
                        kd, kv = build_evs[k].eval(lkeys[m], bslots)
                        hit = pointer(m, kd, kv, kept_lut(m)) >= 0
                        keep = hit if keep is None else (keep & hit)
                if keep is None:
                    return lut
                return jnp.where(keep[jnp.clip(lut, 0, None)], lut, -1)

            ri_safe: Dict[int, jnp.ndarray] = {}
            for k in range(n_joins):
                if k in folded:
                    continue
                kd, kv = ev.eval(lkeys[k], slots)
                ri = pointer(k, kd, kv, kept_lut(k))
                matched = ri >= 0
                mask = mask & matched
                safe = jnp.clip(ri, 0, None)
                ri_safe[k] = safe
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

            def reduce_rows(slots, mask, gid, rows):
                """Per group: the count of `mask`'s rows, and every
                aggregate's ``(values, validity)``, over `rows` rows."""
                reducer = self._make_reducer(gid, domain, rows)
                hit_h = reducer.count(mask)
                outs = segment_agg_outputs(ev, slots, agg_exprs, mask, gid,
                                           domain, reducer)
                return reducer.get(hit_h), outs

            if not compact_cap:
                hits, outs = reduce_rows(slots, mask, gid, n_rows)
            else:
                passed = jnp.sum(mask, dtype=jnp.int32)

                def compacted():
                    # within a group the rows keep their order, so a float
                    # sum adds the terms the whole probe's would
                    at = compact_positions(mask, compact_cap)
                    some = {i: (slots[i][0][at], None if slots[i][1] is None
                                else slots[i][1][at]) for i in agg_slots}
                    some[PARAMS_SLOT] = params
                    live = jnp.arange(compact_cap, dtype=jnp.int32) < passed
                    return reduce_rows(some, live, gid[at], compact_cap)

                hits, outs = jax.lax.cond(
                    passed <= compact_cap, compacted,
                    lambda: reduce_rows(slots, mask, gid, n_rows))
            hit = hits > 0
            tags: List[Tuple[str, np.dtype]] = []
            if topk is None:
                flat = [hit]
                for d, v in outs:
                    flat.append(d)
                    flat.append(v if v is not None else jnp.ones_like(hit))
            else:
                # the tail: the k first present groups by the sort keys,
                # then only their rows of every output and group-key column
                keys = [(outs[i] if kind == "agg"
                         else build_cols[(gid_join, i)]) + (asc, nulls_first)
                        for kind, i, asc, nulls_first in topk["keys"]]
                at, found = select_topk(hit, keys, topk["k"])
                groups = jnp.sum(hit, dtype=jnp.int32)
                flat = [found, at, jnp.broadcast_to(groups, at.shape)]
                if compact_cap:
                    flat.append(jnp.broadcast_to(passed, at.shape))
                for d, v in outs + [build_cols[(gid_join, c)]
                                    for c in topk["cols"]]:
                    flat.append(d[at])
                    flat.append(v[at] if v is not None
                                else jnp.ones_like(found))
            out = pack_flat(flat, tags)
            self._pack_tags = tags
            # `passed` rides in the top-k pack; the plain pack is the one
            # the other rungs pull (`fetch_packed`), so there it is a
            # second, scalar output
            return (out, passed) if compact_cap and topk is None else out

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
        for (k, col) in self.build_col_keys:
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

        cap = self.compact_cap
        launch_attrs = {"joins": len(self.luts), "domain": self.domain,
                        "segsum": self.segsum_mode}
        if cap:
            launch_attrs["compact"] = cap
        packed = timed_jit_call(
            "compiled_join_aggregate", self._fn, *args,
            may_compile=not self._warm, launch_attrs=launch_attrs)
        self._warm = True
        from ..observability import detail
        from .compiled import fetch_packed

        tags = self._pack_tags
        # the tail as the host sees it: the pull of the packed rows (its
        # `fetch` child also holds the wait for the device) and the decode
        with detail("join:tail") as attrs:
            if self.topk is not None:
                result, groups, passed = self._decode_topk(packed, tags)
            else:
                if cap:  # the program's outputs: (pack, passed)
                    host, present, passed = _fetch_packed_and_passed(
                        *packed, self.domain)
                else:
                    host, present = fetch_packed(packed, self.domain)
                result = self._decode_result(host, present, tags)
                groups = int(present.shape[0])
            attrs.update(groups=groups, rows=result.num_rows)
            if cap:
                attrs.update(passed=passed, cap=cap)
        if cap:
            self.metrics.inc("join.compact.engaged")
            if passed > cap:
                self.metrics.inc("join.compact.overflow")
        return result

    def _decode_topk(self, packed, tags) -> Tuple[Table, int, Optional[int]]:
        """The host's half of the top-k tail: one pull of the ``[rows, k]``
        pack, then the found rows as a host-resident table in the tail's
        order, the count of present groups and, from a compacting program,
        of the probe rows that passed."""
        from ..utils import d2h_fetch
        from .compiled import unpack_row
        from .rel.base import unique_names

        with d2h_fetch(nbytes=int(packed.nbytes)):
            host = np.asarray(jax.device_get(packed))
        n = int(np.count_nonzero(host[0]))  # found rows come first
        at = unpack_row(host, 1, tags)
        groups = int(unpack_row(host, 2, tags)[0])
        head = 3  # found, at, groups; a compacting program adds `passed`
        passed = None
        if self.compact_cap:
            passed = int(unpack_row(host, head, tags)[0])
            head += 1
        pairs = iter(range(head, host.shape[0], 2))

        def pulled(i):
            v = unpack_row(host, i + 1, tags)[:n] != 0
            return unpack_row(host, i, tags)[:n], None if v.all() else v

        names = unique_names([f.name for f in self.rel.schema])
        n_groups = len(self.group_cols)
        out: Dict[str, Column] = {}
        for a, name in zip(self.rel.agg_exprs, names[n_groups:]):
            d, validity = pulled(next(pairs))
            target = sql_to_np(a.sql_type)
            out[name] = Column(d.astype(target) if d.dtype != target else d,
                               a.sql_type, validity)
        bt = self.build_tables[self.gid_join]
        keys: Dict[int, Column] = {}
        for col in self.topk["cols"]:
            d, validity = pulled(next(pairs))
            keys[col] = decode_radix_group_key(
                _ColMeta(bt.columns[bt.column_names[col]]), d, 0, validity)
        for col in set(self.group_cols) - set(keys):
            # an RLE key has no row to gather in the program: k rows of it
            # (a static shape), cut to the found ones on the host
            c = bt.columns[bt.column_names[col]].take(jnp.asarray(at))
            with d2h_fetch():
                d, v = jax.device_get((c.data, c.validity))
            keys[col] = Column(
                np.asarray(d)[:n], c.sql_type,
                None if v is None else np.asarray(v)[:n], c.dictionary)
        group_out = {name: keys[col]
                     for name, col in zip(names, self.group_cols)}
        return Table({**group_out, **out}, n), groups, passed

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


def _fetch_packed_and_passed(packed, passed, domain: int):
    """`compiled.fetch_packed` for a compacting program without a top-k
    tail, whose second output is the scalar `passed`: the same ONE pull,
    the scalar in it: ``(host_matrix[:, present], present, passed)``."""
    from ..utils import d2h_fetch
    from .compiled import HOST_PULL_DOMAIN

    if domain <= HOST_PULL_DOMAIN:
        with d2h_fetch(nbytes=int(packed.nbytes)):
            host, passed = jax.device_get((packed, passed))
        present = np.nonzero(host[0] != 0.0)[0]
        return host[:, present], present, int(passed)
    present_dev = jnp.nonzero(packed[0] != 0.0)[0]
    with d2h_fetch():
        host, present, passed = jax.device_get(
            (packed[:, present_dev], present_dev, passed))
    return np.asarray(host), np.asarray(present), int(passed)


def _whole_lut(executor, join: dict, bdc, table: Table):
    """The kept LUT of a whole build side's table version, built on first
    use: ``((rmin, lut) or None, built here)``."""
    from ..analysis.estimator import device_budget_bytes

    budget = min(device_budget_bytes(executor.config) or _LUT_MAX_BYTES,
                 _LUT_MAX_BYTES)
    return LUTS.get_or_build(
        (bdc.uid, str(join["rkey"]), budget),
        lambda: build_lut(executor, join["rkey"], table, max_bytes=budget))


def _stays_whole(k: int, join: dict, table: Table, ext, group_exprs,
                 agg_exprs) -> bool:
    """Whether every column of whole build candidate `k` that the PROGRAM
    would read is stored one value a row: an RLE column is run-aligned, so
    it may only be a key of the pointer gid, which the host decodes."""
    rle = {i for i, n in enumerate(table.column_names)
           if getattr(table.columns[n], "encoding",
                      Encoding.PLAIN) is Encoding.RLE}
    if not rle:
        return True
    read = {sub.index for e in join["whole"] for sub in walk(e)
            if type(sub) is ColumnRef}
    exprs = (ext.conjuncts + [j["lkey"] for j in ext.joins]
             + [x for a in agg_exprs for x in a.args]
             + [a.filter for a in agg_exprs if a.filter is not None])
    choice = _choose_gid_join(ext, group_exprs)
    if choice is None or choice[0] != k:
        exprs = exprs + list(group_exprs)
    read |= {sub.col for e in exprs for sub in walk(e)
             if isinstance(sub, _BuildRef) and sub.k == k}
    return not (read & rle)


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
        from ..observability import detail

        ctx = executor.context
        dc = ctx.schema[ext.scan.schema_name].tables.get(ext.scan.table_name)
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
                    bdc = ctx.schema[node.schema_name].tables.get(
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
        group_exprs, agg_exprs = _plan_whole_builds(ext, group_exprs,
                                                    agg_exprs)
        # parameterize (families/): literals in the probe-side conjuncts,
        # the aggregate arguments and the conjuncts of WHOLE build sides
        # become runtime parameters.  The literals of an eagerly executed
        # build side stay baked: they shape its table and its LUT, and key
        # the cache through the build plan's repr.
        from .. import families

        pz = families.pipeline_parameterizer(executor.config)
        ext.conjuncts = [pz.rewrite(e) for e in ext.conjuncts]
        agg_exprs = [pz.rewrite_agg(a) for a in agg_exprs]
        probe_table = executor.get_table(ext.scan.schema_name,
                                         ext.scan.table_name)
        if ext.scan.projection is not None:
            probe_table = probe_table.select(ext.scan.projection)
        if not probe_table.column_names:
            return None
        # all the host does per request to have the build sides ready
        build_tables: List[Table] = []
        whole: List[Optional[dict]] = []
        with detail("join:build") as attrs:
            built = lut_bytes = 0
            for k, j in enumerate(ext.joins):
                w = None
                scan = j["plan"]
                if j["whole"] is not None and (
                        scan.schema_name, scan.table_name
                ) not in executor.table_overrides:
                    bdc = ctx.schema[scan.schema_name].tables[scan.table_name]
                    bt = executor.get_table(scan.schema_name, scan.table_name)
                    if scan.projection is not None:
                        bt = bt.select(scan.projection)
                    if bt.column_names and _stays_whole(
                            k, j, bt, ext, group_exprs, agg_exprs):
                        lut, built_here = _whole_lut(executor, j, bdc, bt)
                        if lut is not None:
                            ctx.metrics.inc("join.lut.built" if built_here
                                            else "join.lut.reused")
                            built += int(built_here)
                            lut_bytes += int(lut[1].nbytes)

                            def dictionary_of(i, bt=bt):
                                return bt.columns[
                                    bt.column_names[i]].dictionary

                            w = {"lut": lut, "conjuncts": [
                                pz.rewrite(e, dictionary_of)
                                for e in j["whole"]]}
                if w is None:
                    # any other build side runs through the normal recursive
                    # converter (nested joins, aggregates, anything) and
                    # comes compacted
                    bt = executor.execute(j["plan"])
                build_tables.append(bt)
                whole.append(w)
            attrs.update(tables=len(build_tables), lut_bytes=lut_bytes,
                         built=built)
        params = pz.params
        topk = executor.topk_hints.get(id(rel))
        family = (
            ext.scan.schema_name, ext.scan.table_name,
            tuple(ext.scan.projection or ()),
            tuple(repr(j["plan"]) if w is None else
                  (j["plan"].schema_name, j["plan"].table_name,
                   tuple(j["plan"].projection or ()),
                   tuple(str(e) for e in w["conjuncts"]))
                  for j, w in zip(ext.joins, whole)),
            tuple(str(j["lkey"]) + "=" + str(j["rkey"]) for j in ext.joins),
            tuple(str(e) for e in ext.conjuncts),
            tuple(str(e) for e in group_exprs),
            tuple(str(a) for a in agg_exprs),
            tuple((f.name, f.sql_type) for f in rel.schema),
            topk,
        )
        bucket = (tuple(uids), probe_table.num_rows, probe_table.padded_rows,
                  tuple(bt.num_rows for bt in build_tables))
        # the constructor binds the tables this first run reads; the finally
        # below drops them.  No `warm`: this rung never defers
        compiled, built_here = PROGRAMS.get_or_build(
            ctx, family, bucket,
            lambda: CompiledJoinAggregate(rel, ext, group_exprs, agg_exprs,
                                          probe_table, build_tables,
                                          executor, whole=whole, topk=topk),
            params=params)
        if not built_here:
            compiled.probe_table = probe_table
            compiled.build_tables = build_tables
            compiled.metrics = ctx.metrics
        else:
            record_predicate_spaces(ctx, compiled)
            if compiled.compact_cap:
                ctx.metrics.inc("join.compact.programs")
            kept = sum(w is not None for w in whole)
            if kept:
                ctx.metrics.inc("join.build.whole", kept)
            if kept < len(whole):
                ctx.metrics.inc("join.build.eager", len(whole) - kept)
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
