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
                   version.  The program reads such a side, its LUT and
                   a semi-join's key range at lengths rounded up to
                   `bucket_rows` (rows past the true count masked, the
                   bounds runtime operands), so a table version whose
                   sizes fall in the same buckets lowers to the same
                   program and finds its executables in the persistent
                   compile cache.  A LEFTSEMI join whose build side is an
                   Aggregate grouped by the join key (``x IN (SELECT k ..
                   GROUP BY k HAVING ..)``: unique on the key by
                   construction) is an INNER join that exposes no column,
                   and its build side is REDUCED IN the program: the inner
                   aggregates over all of that table's rows into the values
                   of the key's range (kept per table version, admitted by
                   the bytes of the state: `one_key_domain_limit`), the
                   HAVING filters a mask over them with runtime literals,
                   the mask the join's LUT.  Any other build side (a nested
                   join, another aggregate, computed columns, a key the LUT
                   rule declines) is executed eagerly per request as
                   before, its LUT built from the filtered rows
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
                   HBM bandwidth.  A key on a SECOND build table that is
                   reached from the first's columns alone by its unique key
                   (ORDERS -> CUSTOMER) is determined by the first's row
                   too, and is read through that pointer for the rows that
                   leave (a string among them decoded on the host)
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
from ..columnar.dtypes import INTEGER_TYPES, STRING_TYPES, SqlType, sql_to_np
from ..columnar.table import Table
from ..ops.join import bucket_rows, by_parts, dense_unique_lut, pad_rows
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
    count_codespace_sums,
    record_predicate_spaces,
    decode_radix_group_key,
    segment_agg_outputs,
)
from .programs import ProgramCache

logger = logging.getLogger(__name__)

_MAX_JOINS = 6
#: ORDER BY .. LIMIT k is selected inside the program up to this k (one
#: round of masked reductions over the group domain per row; TPC-H Q18
#: asks for the first 100)
_MAX_TOPK = 128
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
        #: {"plan": right subplan, "lkey", "rkey", "exposes": False for a
        #: semi-join, "grouped": `_grouped_by_key`'s reading of its build
        #: side}; `_plan_whole_builds` adds "whole": the build side's
        #: own conjuncts where it is a filtered base-table scan, else None,
        #: and "semi": `_plan_semi_build`'s reading of it, or None
        self.joins: List[dict] = []


def _rewrite(expr: Expr, slots: List[Expr]) -> Expr:
    """Bind `expr`'s ColumnRefs (input-schema positions) to slot exprs."""

    def fn(x):
        if isinstance(x, ColumnRef) and type(x) is ColumnRef:
            return slots[x.index]
        return x

    return transform(expr, fn)


def _rewrite_agg(a: AggExpr, slots: List[Expr]) -> AggExpr:
    return _rp(a, args=tuple(_rewrite(x, slots) for x in a.args),
               filter=_rewrite(a.filter, slots) if a.filter is not None
               else None)


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
        if inner is None or any(j.get("outer") for j in ext.joins):
            # above an outer join a WHERE would drop preserved groups
            return None
        ext.conjuncts.append(_rewrite(node.predicate, inner))
        return inner
    if isinstance(node, p.Join):
        if node.join_type in ("LEFT", "RIGHT"):
            return _walk_outer(node, ext)
        if node.join_type not in ("INNER", "LEFTSEMI") \
                or node.filter is not None:
            return None
        if len(node.on) not in ((1, 2) if node.join_type == "INNER" else (1,)) \
                or len(ext.joins) >= _MAX_JOINS:
            return None
        lkey_raw, rkey_raw = node.on[0]
        rkey = shift_columns(rkey_raw, -len(node.left.schema))
        grouped = None
        if node.join_type == "LEFTSEMI":
            grouped = _grouped_by_key(node.right, rkey)
            if grouped is None:
                return None
        left = _walk_left_spine(node.left, ext)
        if left is None:
            return None
        k = len(ext.joins)
        lkey = _rewrite(lkey_raw, left)
        ext.joins.append({"plan": node.right, "lkey": lkey, "rkey": rkey,
                          "exposes": grouped is None, "grouped": grouped})
        if len(node.on) == 2:
            # a two-column key (TPC-H's PARTSUPP): `_composite_side` keeps
            # such a build side whole, or the rung declines
            lkey2_raw, rkey2_raw = node.on[1]
            ext.joins[-1]["pair"] = (
                _rewrite(lkey2_raw, left),
                shift_columns(rkey2_raw, -len(node.left.schema)))
        if grouped is not None:
            # the build side is unique on the key, so a left row matches at
            # most one row of it: an INNER join that exposes no column
            return left
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


def _walk_outer(node: p.Join, ext: _Extraction) -> Optional[List[Expr]]:
    """``A LEFT JOIN B ON a.k = b.k [AND <conjuncts over B>]`` (or its
    mirror ``B RIGHT JOIN A``) for `_walk_left_spine`: B, the side whose
    rows the join may drop, is the probe, and A, the preserved side, the
    build side: a join that keeps every row of A (`_outer_shape`).  The
    ON conjuncts read B alone and become probe masks, which drop B's rows
    and never a row of A; a conjunct over A declines."""
    if len(node.on) != 1:
        return None
    left_n = len(node.left.schema)
    lkey_raw, rkey_raw = node.on[0]
    rkey_raw = shift_columns(rkey_raw, -left_n)
    if node.join_type == "LEFT":
        build, probe, bkey, pkey = node.left, node.right, lkey_raw, rkey_raw
    else:
        build, probe, bkey, pkey = node.right, node.left, rkey_raw, lkey_raw
    pslots = _walk_left_spine(probe, ext)
    if pslots is None or ext.joins:
        return None
    bslots = [_BuildRef(0, j, f.sql_type, f.nullable)
              for j, f in enumerate(build.schema)]
    slots = bslots + pslots if node.join_type == "LEFT" else pslots + bslots
    ext.joins.append({"plan": build, "lkey": _rewrite(pkey, pslots),
                      "rkey": bkey, "exposes": True, "grouped": None,
                      "outer": True})
    if node.filter is not None:
        on = _rewrite(node.filter, slots)
        if any(isinstance(sub, _BuildRef) for sub in walk(on)):
            return None
        ext.conjuncts.append(on)
    return slots


def _outer_shape(ext: _Extraction, group_exprs, agg_exprs) -> bool:
    """Whether an extraction with an outer join is one the program answers
    exactly: the chain's only join, grouped by the preserved side's key
    (and its other columns), every aggregate ``COUNT(*)`` or one over a
    bare column of the probe, which a row the join extends with NULLs
    leaves NULL.  Each preserved row is then its group: ``COUNT(*)`` is 1
    where no probe row reached it, ``COUNT(col)`` 0, the others NULL.
    True where no join is outer."""
    if not any(j.get("outer") for j in ext.joins):
        return True
    if len(ext.joins) != 1 or type(ext.joins[0]["rkey"]) is not ColumnRef:
        return False
    key = ext.joins[0]["rkey"].index
    if not group_exprs or not all(isinstance(g, _BuildRef)
                                  for g in group_exprs) \
            or not any(g.col == key for g in group_exprs):
        return False
    return all(a.filter is None and all(type(x) is ColumnRef for x in a.args)
               for a in agg_exprs)


def _reduce_nested(nested: dict, present, outs, params):
    """Under trace: the outer aggregate of `_nested_shape` over the inner's
    ``[domain]`` state, its groups `present` and its aggregates `outs`
    (``(values, validity or None)`` each).  The outer key's runtime range
    ``[lo, hi]`` decides: where it spans under `_NESTED_CAP` values the
    groups fall into a histogram of that many slots (and one for NULL);
    else a branch of the same executable gives each distinct value a slot
    of a hash table (`_hash_slots`), exact whatever the range, and with no
    sort (a sort compiles for a minute on the TPU).  ``(flat, fits)``:
    rows of as many slots as the table has (and one), present then the
    key's values and validity then each aggregate's values and
    validity, the histogram's slots first; and whether the histogram took
    them."""
    from .compiled import SegmentReducer

    outs = [(d.astype(dt), v) for (d, v), dt in zip(outs, nested["dtypes"])]
    kd, kv = outs[nested["key"]]
    n = kd.shape[0]
    table = 1 << (2 * n - 1).bit_length()
    width = max(table, _NESTED_CAP) + 1
    valued = present if kv is None else present & kv
    info = jnp.iinfo(kd.dtype)
    lo = jnp.min(jnp.where(valued, kd, info.max))
    hi = jnp.max(jnp.where(valued, kd, info.min))
    # no valued group: hi < lo; a range past the dtype wraps below 0
    span = hi - lo
    fits = (hi < lo) | ((span >= 0) & (span < _NESTED_CAP))
    aggs, ev = nested["aggs"], nested["ev"]
    slots = dict(enumerate(outs))
    slots[PARAMS_SLOT] = params

    def reduce(gid, key, mode, domain):
        reducer = SegmentReducer(gid, domain, mode, n)
        hit_h = reducer.count(present)
        got = segment_agg_outputs(ev, slots, aggs, present, gid, domain,
                                  reducer)
        values, valid = key
        flat = [reducer.get(hit_h) > 0, values, valid]
        for a, (d, v) in zip(aggs, got):
            flat.append(d.astype(sql_to_np(a.sql_type)))
            flat.append(jnp.ones(domain, dtype=bool) if v is None else v)
        return [jnp.pad(x, (0, width - domain)) for x in flat]

    def histogram():
        gid = jnp.where(valued, jnp.clip(kd - lo, 0, _NESTED_CAP - 1),
                        _NESTED_CAP).astype(jnp.int32)
        at = jnp.arange(_NESTED_CAP + 1)
        return reduce(gid, (lo + at.astype(kd.dtype), at < _NESTED_CAP),
                      nested["mode"], _NESTED_CAP + 1)

    def exact():
        gid, used, keys = _hash_slots(kd, valued, table)
        # the NULL key's group takes the slot past the table's
        gid = jnp.where(valued, gid, table)
        return reduce(gid, (jnp.pad(keys, (0, 1)), jnp.pad(used, (0, 1))),
                      "scatter", table + 1)

    return jax.lax.cond(fits, histogram, exact), fits


def _hash_slots(kd, valued, table: int):
    """Under trace: a slot of a hash table of `table` slots (a power of two
    past the rows) for each distinct value of `kd` among the `valued` rows,
    by open addressing: every round, each unplaced row probes its key's
    next slot (double hashing, an odd step, so a key's probes visit every
    slot), a free slot goes to the highest row that probes it, and every
    row whose probed slot holds its key is placed.  Each round places the
    keys of every free slot probed, so the rounds end.  ``(slot per row,
    table's slots in use, the key in each)``."""
    bits = table.bit_length() - 1
    x = kd.astype(jnp.uint64)
    shift = np.uint64(64 - bits)
    at = ((x * np.uint64(0x9E3779B97F4A7C15)) >> shift).astype(jnp.int32)
    step = (((x * np.uint64(0xC2B2AE3D27D4EB4F)) >> shift)
            | np.uint64(1)).astype(jnp.int32)
    rows = jnp.arange(kd.shape[0], dtype=jnp.int32)

    def probe(state):
        at, gid, used, keys, open_ = state
        free = open_ & ~used[at]
        winner = jnp.full(table, -1, dtype=jnp.int32).at[at].max(
            jnp.where(free, rows, -1))
        claimed = winner >= 0
        used = used | claimed
        keys = jnp.where(claimed, kd[jnp.clip(winner, 0)], keys)
        placed = open_ & used[at] & (keys[at] == kd)
        return ((at + step) & (table - 1), jnp.where(placed, at, gid), used,
                keys, open_ & ~placed)

    state = (at, jnp.zeros_like(rows), jnp.zeros(table, dtype=bool),
             jnp.zeros(table, dtype=kd.dtype), valued)
    _, gid, used, keys, _ = jax.lax.while_loop(
        lambda s: jnp.any(s[4]), probe, state)
    return gid, used, keys


def _grouped_by_key(node, rkey: Expr) -> Optional[dict]:
    """A semi-join's build side that is UNIQUE on its join key by
    construction: an Aggregate grouped by ONE key, under HAVING filters,
    column-picking Projections and aliases, whose key column is what `rkey`
    (over `node`'s schema) reads.  ``{"agg": the Aggregate, "having": its
    filters over the Aggregate's schema}``, or None for any other shape."""
    having: List[Expr] = []

    def down(node):
        if isinstance(node, p.SubqueryAlias):
            return down(node.inputs()[0])
        if isinstance(node, p.Projection):
            inner = down(node.input)
            if inner is None or not all(type(e) is ColumnRef
                                        for e in node.exprs):
                return None
            agg, slots = inner
            return agg, [slots[e.index] for e in node.exprs]
        if isinstance(node, p.Filter):
            inner = down(node.input)
            if inner is None:
                return None
            having.append(_rewrite(node.predicate, inner[1]))
            return inner
        if isinstance(node, p.Aggregate) and len(node.group_exprs) == 1:
            return node, [ColumnRef(j, f.name, f.sql_type, f.nullable)
                          for j, f in enumerate(node.schema)]
        return None

    found = down(node)
    if found is None or type(rkey) is not ColumnRef:
        return None
    agg, slots = found
    if slots[rkey.index].index != 0:
        return None
    return {"agg": agg, "having": having}


def _plan_semi_build(join: dict) -> Optional[dict]:
    """The build side of `_grouped_by_key`'s shape whose Aggregate reads a
    filtered scan of ONE base table and groups by one of its columns, as the
    program reduces it: ``{"scan": the scan with its conjuncts as filters,
    "conjuncts", "key": the group column over the scan's schema, "aggs":
    the aggregates over it, "having": the filters over [key, aggregates..],
    "schema": the Aggregate's}``; None where it reads anything else."""
    side = join["grouped"]
    agg = side["agg"]
    sub = _Extraction()
    slots = _walk_left_spine(agg.input, sub)
    if slots is None or sub.scan is None or sub.joins:
        return None
    key = _rewrite(agg.group_exprs[0], slots)
    if type(key) is not ColumnRef:
        return None
    return {"scan": _rp(sub.scan, filters=list(sub.conjuncts)),
            "conjuncts": list(sub.conjuncts), "key": key,
            "aggs": [_rewrite_agg(a, slots) for a in agg.agg_exprs],
            "having": side["having"], "schema": list(agg.schema)}


def _extract(agg: p.Aggregate):
    ext = _Extraction()
    slots = _walk_left_spine(agg.input, ext)
    if slots is None or ext.scan is None or not ext.joins:
        return None
    group_exprs = [_rewrite(e, slots) for e in agg.group_exprs]
    agg_exprs = [_rewrite_agg(a, slots) for a in agg.agg_exprs]
    if not _outer_shape(ext, group_exprs, agg_exprs):
        return None
    return ext, group_exprs, agg_exprs


#: an outer GROUP BY over an inner aggregate's outputs (`_nested_shape`) is
#: reduced into this many values of its key's runtime range (and NULL)
_NESTED_CAP = 1024
_NESTED_AGGS = frozenset({"count_star", "count", "sum", "min", "max"})


def _nested_shape(rel: p.Aggregate) -> Optional[dict]:
    """A group-by of a group-by (TPC-H Q13's ``GROUP BY c_count`` over
    ``c_orders``): `rel` an Aggregate over (aliases and column-picking
    Projections over) an inner Aggregate with group keys, grouped by ONE
    integer output of the inner's aggregates, its own aggregates
    ``COUNT(*)``, ``COUNT``, ``SUM``, ``MIN`` or ``MAX`` of such outputs.
    ``{"agg": the inner Aggregate, "key": the key's index among the
    inner's aggregates, "aggs": `rel`'s aggregates over them (slot i:
    aggregate i), "fields": the inner aggregates' fields, "rel": rel}``,
    or None for any other shape."""
    def down(node):
        if isinstance(node, p.SubqueryAlias):
            return down(node.inputs()[0])
        if isinstance(node, p.Projection):
            inner = down(node.input)
            if inner is None or not all(type(e) is ColumnRef
                                        for e in node.exprs):
                return None
            return inner[0], [inner[1][e.index] for e in node.exprs]
        if isinstance(node, p.Aggregate) and node.group_exprs:
            return node, list(range(len(node.schema)))
        return None

    found = down(rel.input)
    if found is None or len(rel.group_exprs) != 1:
        return None
    inner, at = found
    n_keys = len(inner.group_exprs)
    fields = list(inner.schema)[n_keys:]

    def output(x) -> Optional[int]:
        """The inner aggregate `x` reads, or None."""
        if type(x) is not ColumnRef or at[x.index] < n_keys:
            return None
        return at[x.index] - n_keys

    key = output(rel.group_exprs[0])
    if key is None or fields[key].sql_type not in INTEGER_TYPES:
        return None
    aggs = []
    for a in rel.agg_exprs:
        reads = [output(x) for x in a.args]
        if a.func not in _NESTED_AGGS or a.distinct or a.filter is not None \
                or None in reads:
            return None
        aggs.append(_rp(a, args=tuple(
            ColumnRef(i, f"__n{i}", fields[i].sql_type, fields[i].nullable)
            for i in reads)))
    return {"agg": inner, "key": key, "aggs": aggs, "fields": fields,
            "rel": rel}


def _plan_whole_builds(ext: _Extraction, group_exprs, agg_exprs):
    """Mark the semi-joins' aggregate build sides (``join["semi"]``:
    `_plan_semi_build`'s reading, else None) and the build sides that are a
    filtered scan of ONE base table
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
        #: a semi-join's aggregate build side the PROGRAM reduces
        j["semi"] = None if j["exposes"] else _plan_semi_build(j)
        if not j["exposes"] and j["semi"] is None:
            raise _Unsupported("semi-join over more than a filtered scan")
        sub = _Extraction()
        slots = _walk_left_spine(j["plan"], sub)
        if slots is None or sub.scan is None or sub.joins \
                or not all(type(x) is ColumnRef for x in slots):
            continue
        rebase[k] = [x.index for x in slots]
        j["whole"] = list(sub.conjuncts)
        j["rkey"] = _rewrite(j["rkey"], slots)
        if "pair" in j:
            j["pair"] = (j["pair"][0], _rewrite(j["pair"][1], slots))
        j["plan"] = _rp(sub.scan, filters=list(sub.conjuncts))

    def fn(x):
        if isinstance(x, _BuildRef) and x.k in rebase:
            return _rp(x, col=rebase[x.k][x.col])
        return x

    ext.conjuncts = [transform(e, fn) for e in ext.conjuncts]
    for j in ext.joins:
        j["lkey"] = transform(j["lkey"], fn)
        if "pair" in j:
            j["pair"] = (transform(j["pair"][0], fn), j["pair"][1])
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
    """What the program reads of whole build sides, per table version: the
    LUT of each (key column, byte budget), each column padded to its
    bucket (`padded_column`), a semi-join key's range; built on first use
    and kept: bounded, least recently used first out, so a replaced table's
    buffers cannot pin device memory for good.  A key the rule declines is
    remembered as None."""

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


LUTS = LutCache(64)


def _reached_from(ext, m: int) -> Optional[int]:
    """The ONE build side whose columns alone join `m`'s probe key reads
    (ORDERS for ORDERS -> CUSTOMER under LINEITEM -> ORDERS), else None: a
    row of that build side then determines build `m`'s row, since `m`'s key
    is unique.  A two-column key is never so reached, nor reaches: its
    side's rows are laid out in its slots (`slotted_column`)."""
    if "pair" in ext.joins[m]:
        return None
    subs = list(walk(ext.joins[m]["lkey"]))
    parents = {sub.k for sub in subs if isinstance(sub, _BuildRef)}
    if len(parents) != 1 or any(type(sub) is ColumnRef for sub in subs):
        return None
    (j,) = parents
    return None if "pair" in ext.joins[j] else j


def _column_of(build_tables, bcol: Tuple[int, int]) -> Column:
    """Column ``(build side, position)`` of the build tables."""
    bt = build_tables[bcol[0]]
    return bt.columns[bt.column_names[bcol[1]]]


def _choose_gid_join(ext, group_exprs, dependents: bool = True
                     ) -> Optional[Tuple[int, List[Tuple[int, int]]]]:
    """Find a join k whose build-row pointer can serve as the segment id.

    Sound only when the group keys functionally DETERMINE the build row:
    the key set must include join k's key itself (probe-side expr, or the
    build key column), and every other key must be functionally dependent
    on the row: a column of build k, or (`dependents`) a column of a build
    side m that is reached from build k's columns alone by m's unique key
    (`_reached_from`).  Grouping by a non-key build column (e.g. a category
    shared by many dim rows) must NOT use the pointer — it would split one
    group per build row — that case goes through the radix gid instead.
    Returns (k, (build side, column) per group expr)."""
    if not group_exprs:
        return (-1, [])  # global aggregate
    for k in range(len(ext.joins) - 1, -1, -1):
        rkey = ext.joins[k]["rkey"]
        if not (isinstance(rkey, ColumnRef) and type(rkey) is ColumnRef) \
                or not ext.joins[k]["exposes"] or "pair" in ext.joins[k]:
            # a semi-join's build side has no row to point at, a
            # two-column key's no row a group key alone determines
            continue
        cols = []
        has_key = False
        ok = True
        for g in group_exprs:
            if g == ext.joins[k]["lkey"] or (
                    isinstance(g, _BuildRef) and g.k == k
                    and g.col == rkey.index):
                cols.append((k, rkey.index))
                has_key = True
            elif isinstance(g, _BuildRef) and (g.k == k or (
                    dependents and _reached_from(ext, g.k) == k)):
                cols.append((g.k, g.col))
            else:
                ok = False
                break
        if ok and has_key:
            return (k, cols)
    return None


def build_lut(executor, rkey: Expr, table: Table,
              max_bytes: Optional[int] = None, uid=None):
    """``(rmin, lut)`` of `table`'s join key `rkey` (`dense_unique_lut`,
    which says what `max_bytes` means), or None where the key declines.
    With `uid`, the table version of a whole build side, a key column is
    read padded to its bucket (`padded_column`), so the build compiles
    once per bucket and not once per row count."""
    if uid is not None and type(rkey) is ColumnRef:
        kc = padded_column(uid, table, rkey.index)
    else:
        kc = executor.eval_expr(rkey, table)
    kc = kc.decode()
    if kc.sql_type in STRING_TYPES:
        return None
    return dense_unique_lut(kc.data, kc.validity, max_bytes=max_bytes,
                            rows=table.num_rows)


def padded_column(uid, table: Table, index: int) -> Column:
    """Column `index` of whole build side `table` (table version `uid`) as
    the program reads it: its buffers padded to `bucket_rows` of the
    table's rows by `pad_rows`, kept per table version in `LUTS`.  The
    rows past `table.num_rows` repeat the last one: every reader masks
    them."""
    name = table.column_names[index]

    def pad():
        col = table.columns[name]  # one value a row: never RLE here
        rows = bucket_rows(table.padded_rows)
        return _rp(col, data=pad_rows(col.data, rows),
                   validity=None if col.validity is None
                   else pad_rows(col.validity, rows))

    return LUTS.get_or_build((uid, "padded", name), pad)[0]


def slotted_column(uid, table: Table, index: int, composite: dict) -> Column:
    """Column `index` of whole build side `table` (table version `uid`)
    joined on a two-column key, as the program reads it: one value per
    slot of `composite` (`ops/join.py::composite_slots`), kept per table
    version and key in `LUTS`.  A slot that holds no row reads row 0: the
    slots' ``second`` masks it."""
    name = table.column_names[index]

    def lay_out():
        from ..utils import d2h_fetch

        col = table.columns[name]  # one value a row: never RLE here
        with d2h_fetch(nbytes=int(col.data.nbytes)):
            data, valid = jax.device_get((col.data, col.validity))
        rows = composite["rows"]
        return _rp(col, data=jax.device_put(np.asarray(data)[rows]),
                   validity=None if valid is None
                   else jax.device_put(np.asarray(valid)[rows]))

    return LUTS.get_or_build((uid, "slotted", composite["key"], name),
                             lay_out)[0]


def _derived_key(g: Expr, source) -> dict:
    """The radix digit of group key `g`, an expression over ONE numeric
    DICT-coded column (``EXTRACT(YEAR FROM o_orderdate)``), as a
    `_plan_radix` entry of kind ``derived``: `g` evaluated on the host over
    the column's dictionary gives each code's value; ``values`` are those
    values' sorted uniques (the domain, read off the dictionary), ``map``
    each code's index among them (``len(values)``, the NULL digit, where
    `g` is NULL).  The program reads the column's codes, so the key costs
    one lookup in a table of the dictionary's length.  `source(ref)` gives
    the column a ref reads.  Any other expression raises `_Unsupported`."""
    def ref_key(x):
        if isinstance(x, _BuildRef):
            return ("build", x.k, x.col)
        if isinstance(x, ColumnRef) and type(x) is ColumnRef:
            return ("probe", x.index)
        return None

    refs = {ref_key(sub): sub for sub in walk(g) if ref_key(sub) is not None}
    if len(refs) != 1:
        raise _Unsupported("non-column group key")
    (key, ref), = refs.items()
    col, _ = source(ref)
    if getattr(col, "encoding", Encoding.PLAIN) is not Encoding.DICT \
            or col.sql_type in STRING_TYPES:
        raise _Unsupported("group key over a column that is not DICT-coded")
    n = len(col.enc_values)
    on_codes = transform(g, lambda x: ColumnRef(0, "__k", x.sql_type,
                                                x.nullable)
                         if ref_key(x) == key else x)
    ev = _TraceEval(_SlotMeta([_ColMeta(col)], ["__k"]))
    d, v = ev.eval(on_codes, {0: (jnp.arange(n, dtype=jnp.int32), None),
                              PARAMS_SLOT: ()})
    d = np.broadcast_to(np.asarray(d), (n,))
    if d.dtype.kind not in "iub":
        raise _Unsupported("group key expression of a non-integer type")
    valid = np.ones(n, dtype=bool) if v is None \
        else np.broadcast_to(np.asarray(v), (n,))
    values, index = np.unique(d[valid], return_inverse=True)
    code_map = np.full(n, len(values), dtype=np.int32)
    code_map[valid] = index
    return {"ref": ref, "kind": "derived", "raw": True, "r": len(values) + 1,
            "off": 0, "col": col, "map": code_map,
            "values": values.astype(sql_to_np(g.sql_type)),
            "sql_type": g.sql_type}


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
                 topk: Optional[TopK] = None, nested: Optional[dict] = None):
        """``whole[k]``: None where build table k was executed eagerly (its
        LUT is built here, from the rows it has left), else ``{"conjuncts":
        the build side's own parameterised conjuncts, "lut": (rmin, lut),
        "uid": the table version}`` with build table k the base table's
        scan, unfiltered: the program reads its columns padded to
        `bucket_rows` (`padded_column`).  A semi-join's aggregate build side
        the program reduces itself has ``"semi": {"domain", "key", "aggs",
        "having", "schema"}`` (parameterised) in place of "uid", and
        ``"lut": (the key range's low end, None)``: its LUT is made in the
        program, over the ``domain`` values (bucketed) of that range.
        `nested`: `_nested_shape`'s outer aggregate over `rel`, which the
        program reduces over `rel`'s groups and returns in its place."""
        self.rel = rel
        self.ext = ext
        self.probe_table = probe_table
        self.build_tables = build_tables
        #: the registry of the context whose request this program is serving
        #: (rebound with the tables): counts `join.compact.*` per request
        self.metrics = executor.context.metrics
        whole = whole if whole is not None else [None] * len(build_tables)
        #: join k -> the slots of its two-column key (`composite_slots`):
        #: only a side kept whole has them
        self.composites: Dict[int, dict] = {
            k: w["composite"] for k, w in enumerate(whole)
            if w is not None and "composite" in w}

        check_agg_static_support(agg_exprs)
        check_no_rle(probe_table)
        #: compressed-domain accounting: probe-side scans read encoded bytes
        self.has_encoded = any(
            getattr(c, "encoding", Encoding.PLAIN) is not Encoding.PLAIN
            for c in probe_table.columns.values())

        # a key on a second build side is read through the pointer of the
        # first: only the one-chip program evaluates join keys at a WHOLE
        # build side's rows
        choice = _choose_gid_join(ext, group_exprs,
                                  type(self) is CompiledJoinAggregate)
        if choice is not None and any(
                bk != choice[0] for bk, _ in choice[1]) \
                and whole[choice[0]] is None:
            choice = _choose_gid_join(ext, group_exprs, False)
        if choice is not None:
            self.gid_join, self.group_cols = choice
            self.radix_spec = None
        else:
            # radix gid over the (gathered) group-key values — the general
            # merge-correct form; pointer gid above is the high-cardinality
            # escape hatch for group-by-join-key shapes
            self.gid_join, self.group_cols = None, []
            self.radix_spec = self._plan_radix(
                group_exprs, probe_table, build_tables, len(agg_exprs) + 1,
                executor.config)

        # per-build prep: a whole build side brings the LUT of its table
        # version (`LUTS`); an eagerly executed one gets its own here
        self.luts: List[Tuple[int, jnp.ndarray]] = []
        for j, bt, w in zip(ext.joins, build_tables, whole):
            prep = w["lut"] if w is not None else build_lut(
                executor, j["rkey"], bt)
            if prep is None:
                raise _Unsupported("build keys not unique-dense ints")
            self.luts.append(prep)
        #: per build side: its table version where the program reads its
        #: columns padded (`padded_column`), else None
        self.side_uids = [None if w is None else w.get("uid") for w in whole]
        #: per build side: the rows of its buffers as the program reads them
        self.build_rows = [
            self.composites[k]["slots"] if k in self.composites
            else bucket_rows(bt.padded_rows) if uid is not None
            else bt.padded_rows
            for k, (bt, uid) in enumerate(zip(build_tables, self.side_uids))]
        #: per join, the program's runtime bounds ``[lo, hi, rows]``: the
        #: lowest and highest key its LUT's (or semi-join state's) slots
        #: hold, and the build side's true row count; operands, so the
        #: lowered program carries none of them.  int32 where they fit (a
        #: 64-bit integer is two words on the TPU, and the program compares
        #: with them at a build side's rows).  With a two-column key in the
        #: program, ``[lo2, hi2]`` follow: the other column's range (0, 0
        #: for the other joins); `lo` and `hi` are then the leading one's
        bounds = np.array(
            [[lo, self.composites[k]["hi"] if k in self.composites else
              min(lo + (lut.shape[0] if lut is not None else
                        w["semi"]["domain"]) - 1, np.iinfo(np.int64).max),
              bt.num_rows]
             + ([] if not self.composites else
                [self.composites[k]["lo2"], self.composites[k]["hi2"]]
                if k in self.composites else [0, 0])
             for k, ((lo, lut), bt, w) in enumerate(
                 zip(self.luts, build_tables, whole))],
            dtype=np.int64)
        narrow = np.iinfo(np.int32)
        self.bounds = bounds.astype(np.int32) if bounds.size and \
            narrow.min <= bounds.min() and bounds.max() <= narrow.max \
            else bounds
        #: per join: the conjuncts evaluated over the WHOLE build table in
        #: the program (None: the build side came filtered)
        self.build_conjuncts: List[Optional[List[Expr]]] = [
            None if w is None else list(w["conjuncts"]) for w in whole]
        self._build_evs = [
            None if w is None else _TraceEval(_TableMeta(bt))
            for bt, w in zip(build_tables, whole)]
        #: join k -> its semi-join build side as the program reduces it
        self.semis: Dict[int, dict] = {
            k: self._plan_semi(w["semi"], executor)
            for k, w in enumerate(whole)
            if w is not None and w.get("semi") is not None}
        #: (groups, passed the HAVING) per semi-join, of the newest run
        self.semi_stats: List[Tuple[int, int]] = []

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
                            if k not in self.folded] \
            + [ext.joins[k]["pair"][0] for k in self.composites]
        for e in all_exprs:
            for sub in walk(e):
                if isinstance(sub, _BuildRef):
                    used.setdefault((sub.k, sub.col), n_probe + len(used))
        self.used_build_slots = used
        #: build sides other than the pointer gid's that hold group keys
        self.dependents = sorted({bk for bk, _ in self.group_cols
                                  if bk != self.gid_join})
        for bcol in self.group_cols:
            if bcol[0] != self.gid_join and getattr(
                    _column_of(build_tables, bcol), "encoding",
                    Encoding.PLAIN) is Encoding.RLE:
                raise _Unsupported("run-length key on a dependent build")

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
        #: a two-column key's other probe key, per such join
        self.lkeys2 = {k: finalize(ext.joins[k]["pair"][0])
                       for k in self.composites}
        #: a dependent's key over the gid build side's own columns
        self.dep_lkeys = {m: onto_build(ext.joins[m]["lkey"])
                          for m in self.dependents}
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
            domain_est = self.build_rows[self.gid_join]
        else:
            domain_est = 1
        from ..ops.pallas_kernels import choose_segsum_impl

        self.domain = domain_est
        from ..ops.grouping import RADIX_DOMAIN_LIMIT

        #: one-key integer ranges past the mixed-radix gate that this
        #: program reduces into (`aggregate.domain.wide`)
        self.wide_domains = sum(
            d > RADIX_DOMAIN_LIMIT for d in
            [semi["domain"] for semi in self.semis.values()]
            + ([domain_est] if self.radix_spec is not None else []))
        self.segsum_mode = choose_segsum_impl(executor.config, domain_est)
        #: the outer join's build side (`_walk_outer`), each row a group
        self.outer = next((k for k, j in enumerate(ext.joins)
                           if j.get("outer")), None)
        #: the outer aggregate the program reduces over this one's groups
        self.nested = None if nested is None \
            else self._plan_nested(nested, executor)
        if self.outer is not None or self.nested is not None:
            from ..parallel import dist_plan as _dp

            if type(self) is not CompiledJoinAggregate \
                    or probe_table.row_valid is not None \
                    or any(_dp.array_is_sharded(c.data)
                           for c in probe_table.columns.values()):
                raise _Unsupported("outer join or nested aggregate off "
                                   "one chip")
        if self.outer is not None and (
                self.gid_join != self.outer or self.dependents
                or whole[self.outer] is None
                or "uid" not in whole[self.outer]):
            raise _Unsupported("outer join not grouped by its preserved rows")
        self.topk = self._plan_topk(topk, build_tables)
        #: joins probed at the compacted rows only (`_plan_deferred`), in
        #: join order; none where the program does not compact
        self.deferred = self._plan_deferred(ext)
        if self.deferred and str(executor.config.get(
                "sql.compile.segsum", "auto")) == "auto":
            # what a compacting program reduces is the buffer's rows: few,
            # so the exact float64 scatter costs little where the blocked
            # matmul's float32 partials lose more than a float32 sum would
            self.segsum_mode = "scatter"
        self.compact_cap = self._plan_compaction(probe_table)
        if not self.compact_cap:
            self.deferred = ()
        #: SUM / AVG aggregates summed in code space (`aggregate.sum.
        #: codespace`): the outer ones over the probe's rows (the compact
        #: branch reduces fewer, so it engages wherever the whole one does),
        #: each semi-join's over its build side's
        self.sum_codespace = count_codespace_sums(
            self.agg_exprs, self._ev.table, self.segsum_mode,
            probe_table.padded_rows) + sum(
                count_codespace_sums(semi["aggs"], self._build_evs[k].table,
                                     semi["mode"],
                                     build_tables[k].padded_rows)
                for k, semi in self.semis.items())
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
        for m, lkey in self.dep_lkeys.items():
            keys.update((self.gid_join, sub.index) for sub in walk(lkey)
                        if type(sub) is ColumnRef)
        for k, semi in self.semis.items():
            exprs = [semi["key"]] + [x for a in semi["aggs"] for x in
                                     list(a.args) + ([a.filter] if a.filter
                                                     is not None else [])]
            keys.update((k, sub.index) for e in exprs for sub in walk(e)
                        if type(sub) is ColumnRef)
        if self.topk is not None:
            keys.update(self.topk["cols"])
        self.build_col_keys = sorted(keys)
        #: (kind, np.dtype) per packed output row; filled when _fn traces
        self._pack_tags: List[Tuple[str, np.dtype]] = []
        self._fn = jax.jit(self._build())
        #: compile-watchdog hint: True after _fn compiled for these shapes
        self._warm = False

    @staticmethod
    def _plan_semi(semi: dict, executor) -> dict:
        """`semi` with what the trace needs beside it: the segment-sum mode
        of its domain, and the evaluator of its HAVING filters over the
        inner Aggregate's output (the key's values, then each aggregate)."""
        from ..ops.pallas_kernels import choose_segsum_impl

        check_agg_static_support(semi["aggs"])
        fields = semi["schema"]
        metas = [_ColMeta(Column(np.empty(0, dtype=sql_to_np(f.sql_type)),
                                 f.sql_type)) for f in fields]
        return dict(semi, mode=choose_segsum_impl(executor.config,
                                                  semi["domain"]),
                    key_dtype=sql_to_np(fields[0].sql_type),
                    having_ev=_TraceEval(_SlotMeta(
                        metas, [f"__h{i}" for i in range(len(fields))])))

    @staticmethod
    def _plan_nested(nested: dict, executor) -> dict:
        """`nested` with the evaluator of its aggregates over the inner
        aggregates' outputs and the segment-sum mode of its histogram."""
        from ..ops.pallas_kernels import choose_segsum_impl

        check_agg_static_support(nested["aggs"])
        fields = nested["fields"]
        metas = [_ColMeta(Column(np.empty(0, dtype=sql_to_np(f.sql_type)),
                                 f.sql_type)) for f in fields]
        return dict(nested, dtypes=[sql_to_np(f.sql_type) for f in fields],
                    mode=choose_segsum_impl(executor.config, _NESTED_CAP + 1),
                    ev=_TraceEval(_SlotMeta(
                        metas, [f"__n{i}" for i in range(len(fields))])))

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
            j = _reached_from(ext, k)
            if j is None:
                continue
            later = [ext.joins[m]["lkey"] for m in range(k + 1,
                                                         len(ext.joins))
                     if folded.get(m) != k]
            if whole[j] is not None and k != self.gid_join \
                    and not reads(rest + later, k):
                folded[k] = j
        return folded

    def _plan_deferred(self, ext) -> Tuple[int, ...]:
        """Joins whose build side no filter narrows (a whole side without
        conjuncts of its own, nothing folded into it; not the pointer gid's,
        nor a side its group keys are read from): in TPC-H, a foreign key
        every row finds.  They cannot make the rows that pass much fewer,
        so the program compacts the rows the OTHER joins and filters pass
        and probes these at the compacted rows alone (at the probe's rows,
        under the mask, where more pass than the buffer holds).  A join
        whose columns the probe's filters, or a join not deferred, read
        stays at the probe's rows."""
        if type(self) is not CompiledJoinAggregate:
            return ()
        parents = set(self.folded.values())
        deferred = {
            k for k, j in enumerate(ext.joins)
            if j["exposes"] and k not in self.folded and k not in parents
            and k != self.gid_join and k not in self.dependents
            and self.build_conjuncts[k] == []}

        def reads(e, k):
            return any(isinstance(sub, _BuildRef) and sub.k == k
                       for sub in walk(e))

        changed = True
        while changed:
            kept = [m for m in range(len(ext.joins)) if m not in deferred
                    and m not in self.folded]
            readers = list(ext.conjuncts) + [
                key for m in kept for key in [ext.joins[m]["lkey"]]
                + ([ext.joins[m]["pair"][0]] if "pair" in ext.joins[m]
                   else [])]
            stay = {k for k in deferred if any(reads(e, k) for e in readers)}
            deferred -= stay
            changed = bool(stay)
        return tuple(sorted(deferred))

    def _plan_compaction(self, probe_table) -> int:
        """The compact buffer's rows, or 0 where the program reduces the
        probe whole as ever: a segment sum that is not a scatter (its cost
        is not per row) and no join to defer to the compacted rows, a probe
        sharded over a mesh or padded (the sharded rung, spmd/join.py,
        traces this class's body per shard), a probe under
        `_COMPACT_MIN_ROWS`, an outer join's probe: its only conjuncts are
        its ON clause's, whose share is the pattern's and Q13's NOT LIKE
        passes nearly every row, so the compacted branch would compile for
        nothing."""
        from ..parallel import dist_plan as _dp

        datas = [c.data for c in probe_table.columns.values()]
        n_rows = int(datas[0].shape[0])
        if (type(self) is not CompiledJoinAggregate
                or (self.segsum_mode != "scatter" and not self.deferred)
                or self.outer is not None
                or probe_table.row_valid is not None
                or any(_dp.array_is_sharded(d) for d in datas)
                or not _COMPACT_MIN_ROWS <= n_rows < (1 << 31)):
            return 0
        return compact_capacity(n_rows)

    def _plan_topk(self, topk: Optional[TopK], build_tables):
        """The top-k tail this program runs, or None where the hint is
        absent or names what the tail cannot order: ``{"k", "keys": [(kind,
        index, ascending, nulls first)], "cols"}`` with kind ``"agg"`` (an
        aggregate's output) or ``"group"`` (a group key, ``(build side,
        column)``: a column of the pointer-gid build table or of a build
        side reached from it, ordered as stored: integers, which DICT, FOR
        and PLAIN all keep in value order, or PLAIN floats), and ``cols``
        the group-key columns whose rows the program gathers for the
        result."""
        if topk is None or not 0 < topk.k <= _MAX_TOPK:
            return None
        if self.gid_join is None or self.gid_join < 0:
            return None  # a radix domain is small: pulled whole as before
        n_groups = len(self.group_cols)

        def column(bcol):
            return _column_of(build_tables, bcol)

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
            if not sortable(column(bcol)):
                return None
            keys.append(("group", bcol, asc, nulls_first))
        cols = sorted({
            bcol for bcol in self.group_cols
            if getattr(column(bcol), "encoding",
                       Encoding.PLAIN) is not Encoding.RLE})
        return {"k": topk.k, "keys": keys, "cols": cols}

    @staticmethod
    def _plan_radix(group_exprs, probe_table, build_tables, slots: int = 1,
                    config=None):
        """Mixed-radix gid plan over group-key columns (same scheme as
        CompiledAggregate: dict strings / bools / small-int ranges, one
        extra code per key for NULL; ONE integer key is admitted by the
        bytes of its state of `slots` slots, `one_key_domain_limit`)."""
        spec = []
        domain = 1
        pending = []  # (slot, device min, device max): ONE pull for all keys

        def source(ref):
            """The column `ref` reads, and its table's row mask."""
            if isinstance(ref, _BuildRef):
                bt = build_tables[ref.k]
                return bt.columns[bt.column_names[ref.col]], bt.row_valid
            return (probe_table.columns[probe_table.column_names[ref.index]],
                    probe_table.row_valid)

        for g in group_exprs:
            if isinstance(g, _BuildRef) or (isinstance(g, ColumnRef)
                                            and type(g) is ColumnRef):
                col, row_valid = source(g)
            else:
                spec.append(_derived_key(g, source))
                continue
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
        from ..ops.grouping import (RADIX_DOMAIN_LIMIT, one_key_domain_limit,
                                    resolve_int_bounds)

        limit = one_key_domain_limit(slots, config) \
            if len(group_exprs) == 1 and pending else RADIX_DOMAIN_LIMIT
        spans = resolve_int_bounds(pending, limit)
        if spans is None:
            raise _Unsupported("integer key range too large")
        for slot, (span, lo) in spans.items():
            spec[slot]["r"] = span + 1
            spec[slot]["off"] = lo
        for entry in spec:
            domain *= entry["r"]
            if domain > limit:
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
        #: the host's lowest keys, read only to pick the arithmetic of a
        #: probe key's dtype (whether it holds the lowest key: the same for
        #: every version of a table); the program's values are `bounds`
        rmins = [rmin for rmin, _ in self.luts]
        padded = [uid is not None for uid in self.side_uids]
        build_domains = list(self.build_rows)
        build_conjuncts = self.build_conjuncts
        build_evs = self._build_evs
        folded = self.folded
        topk = self.topk
        compact_cap = self.compact_cap
        semis = self.semis
        outer = self.outer
        nested = self.nested
        dependents = self.dependents
        dep_lkeys = self.dep_lkeys
        lkeys2 = self.lkeys2
        #: the host's numbers of each two-column key's slots (none of its
        #: buffers: the closure must not pin them)
        composites = {k: {n: c[n] for n in ("run", "lo", "slots", "lo2",
                                            "hi2")}
                      for k, c in self.composites.items()}
        #: the slots the aggregates read: all the compact branch gathers
        agg_slots = sorted({
            sub.index for a in agg_exprs
            for e in list(a.args) + ([a.filter] if a.filter is not None
                                     else [])
            for sub in walk(e) if type(sub) is ColumnRef})
        deferred = self.deferred
        #: ... and, with deferred joins, what their keys and the radix group
        #: keys read, but the slots those joins fill in the branch
        branch_slots = agg_slots if not deferred else sorted({
            sub.index for e in [lkeys[k] for k in deferred]
            + [lkeys2[k] for k in deferred if k in lkeys2]
            + [s["ref"] for s in radix_spec or ()]
            for sub in walk(e) if type(sub) is ColumnRef}.union(agg_slots)
            - {slot for (k, _), slot in used.items() if k in deferred})

        def fn(probe_datas, probe_valids, luts, build_cols, row_valid,
               params=(), bounds=None):
            # build_cols: {(k,col): (data, valid_or_None)} full build tables
            # (whole ones padded); bounds: `self.bounds`
            n_rows = probe_datas[0].shape[0] if probe_datas else 0
            slots: Dict[int, Tuple] = {
                i: (probe_datas[i], probe_valids[i]) for i in range(n_probe)}
            slots[PARAMS_SLOT] = params
            # padded sharded probe: the row mask keeps pad rows out of every
            # join match, filter, and reduction (exact-spec sharding)
            mask = jnp.ones(n_rows, dtype=bool) if row_valid is None \
                else row_valid

            def offset(kd, lo, hi, rmin, size):
                """``(int32 offset of each key from lo, clipped into [0,
                size), whether it lies in [lo, hi])``."""
                # widen sub-int32 keys before subtracting (narrow dtypes can
                # overflow under `key - rmin`); if rmin itself doesn't fit
                # the key dtype, compute in int64 (no match is representable
                # without it).  LUT positions/row-ids always fit int32.
                if np.dtype(kd.dtype).itemsize < 4:
                    kd = kd.astype(jnp.int32)
                info = jnp.iinfo(kd.dtype)
                if info.min <= rmin <= info.max:
                    # in-dtype subtraction can wrap for probe keys far
                    # outside the build range (e.g. kd < INT_MIN + rmin)
                    # and land back inside [0, size) — bound the KEY itself
                    # first; within [rmin, rmin+size-1] the subtraction is
                    # exact (ADVICE r3)
                    lo_k = lo.astype(kd.dtype)
                    if jnp.iinfo(hi.dtype).max > info.max:
                        hi = jnp.minimum(hi, int(info.max))
                    hi_k = hi.astype(kd.dtype)
                    inb = (kd >= lo_k) & (kd <= hi_k)
                    idx = jnp.where(inb, kd - lo_k, jnp.zeros_like(kd))
                else:
                    idx = kd.astype(jnp.int64) - lo
                    inb = (idx >= 0) & (idx < size)
                return jnp.clip(idx, 0, size - 1).astype(jnp.int32), inb

            def pointer(k, kd, kv, lut):
                """Build-row index per key of join `k` (-1: no row)."""
                idx32, inb = offset(kd, bounds[k, 0], bounds[k, 1], rmins[k],
                                    lut.shape[0])
                ri = jnp.where(inb, lut[idx32].astype(jnp.int32), jnp.int32(-1))
                return ri if kv is None else jnp.where(kv, ri, -1)

            def composite_pointer(k, keys, second):
                """Slot index per probe row of two-column join `k` (-1: no
                row): the leading key's ``run`` slots, the one whose
                ``second`` is the other key's offset."""
                (kd, kv), (kd2, kv2) = keys
                comp = composites[k]
                run = comp["run"]
                idx, inb = offset(kd, bounds[k, 0], bounds[k, 1], comp["lo"],
                                  comp["slots"] // run)
                idx2, inb2 = offset(kd2, bounds[k, 3], bounds[k, 4],
                                    comp["lo2"], comp["hi2"] - comp["lo2"] + 1)
                base = idx * run
                ri = jnp.full(base.shape, -1, dtype=jnp.int32)
                for i in range(run):
                    ri = jnp.where(second[base + i] == idx2, base + i, ri)
                ri = jnp.where(inb & inb2, ri, -1)
                for v in (kv, kv2):
                    if v is not None:
                        ri = jnp.where(v, ri, -1)
                return ri

            def build_slots(k):
                bslots = {col: build_cols[(bk, col)]
                          for (bk, col) in build_cols if bk == k}
                bslots[PARAMS_SLOT] = params
                return bslots

            semi_counts: List = []

            def semi_lut(k):
                """The LUT of semi-join `k`, made here: its build side's
                aggregates reduced over ALL of that table's rows into the
                values of the key's kept range, the HAVING filters as a mask
                over those values (their literals runtime parameters): 0
                where a key's group passes, -1 elsewhere."""
                semi, bev, bslots = semis[k], build_evs[k], build_slots(k)
                domain = semi["domain"]
                kd, kv = bev.eval(semi["key"], bslots)
                sel = jnp.ones(kd.shape[0], dtype=bool) if kv is None else kv
                for f in build_conjuncts[k]:
                    d, v = bev.eval(f, bslots)
                    sel = sel & (d if v is None else (d & v))
                if np.dtype(kd.dtype).itemsize < 4:
                    kd = kd.astype(jnp.int32)
                # every row's key lies in the kept range of its table version
                lo = bounds[k, 0].astype(kd.dtype)
                gid = jnp.clip(kd - lo, 0, domain - 1).astype(jnp.int32)
                from .compiled import SegmentReducer

                reducer = SegmentReducer(gid, domain, semi["mode"],
                                         kd.shape[0])
                hit_h = reducer.count(sel)
                outs = segment_agg_outputs(bev, bslots, semi["aggs"], sel,
                                           gid, domain, reducer)
                keep = present = reducer.get(hit_h) > 0
                # a slot past the range's top holds no row: not `present`
                hslots = {0: (jnp.arange(domain, dtype=semi["key_dtype"])
                              + bounds[k, 0].astype(semi["key_dtype"]), None),
                          PARAMS_SLOT: params}
                hslots.update({1 + i: out for i, out in enumerate(outs)})
                for f in semi["having"]:
                    d, v = semi["having_ev"].eval(f, hslots)
                    keep = keep & (d if v is None else (d & v))
                semi_counts.extend([jnp.sum(present, dtype=jnp.int32),
                                    jnp.sum(keep, dtype=jnp.int32)])
                return jnp.where(keep, jnp.int8(0), jnp.int8(-1))

            kept: Dict[int, jnp.ndarray] = {}

            def kept_lut(k):
                if k not in kept:
                    kept[k] = semi_lut(k) if k in semis else filtered_lut(k)
                return kept[k]

            def build_keep(k):
                """The rows of whole build side `k` its own conjuncts keep,
                or None where it has none."""
                bslots = build_slots(k)
                keep = None
                for f in build_conjuncts[k]:
                    d, v = build_evs[k].eval(f, bslots)
                    d = d if v is None else (d & v)
                    keep = d if keep is None else (keep & d)
                return keep

            def filtered_lut(k):
                """LUT `k` without the rows that build side k's own filters
                reject, or a join probed from its rows (`folded`) leaves
                unmatched: the mask is evaluated over ITS rows and folded
                into the pointers the LUT holds (gathers of the build's and
                the LUT's size, none of the probe's), so a key whose row
                falls out finds no row."""
                lut = luts[k]
                if build_evs[k] is None:
                    return lut  # an eagerly executed build side came filtered
                keep = build_keep(k)
                for m, parent in folded.items():
                    if parent == k:
                        hit = rows_of(m, k) >= 0
                        keep = hit if keep is None else (keep & hit)
                if keep is None:
                    return lut
                if k in composites:
                    # one entry a slot: a slot whose row falls out holds
                    # no key
                    return jnp.where(keep, lut, -1)
                return by_parts(
                    lambda part: jnp.where(keep[jnp.clip(part, 0, None)],
                                           part, -1), lut)

            reached: Dict[int, jnp.ndarray] = {}

            def rows_of(m, k):
                """Build side `m`'s row (-1: none) per ROW of build side
                `k`, whose columns alone its key reads; none for the pad
                rows of a padded `k`."""
                if m not in reached:
                    lkey = lkeys[m] if m in folded else dep_lkeys[m]
                    kd, kv = build_evs[k].eval(lkey, build_slots(k))
                    ri = pointer(m, kd, kv, kept_lut(m))
                    if padded[k]:
                        ri = jnp.where(jnp.arange(ri.shape[0], dtype=bounds.dtype)
                                       < bounds[k, 2], ri, -1)
                    reached[m] = ri
                return reached[m]

            def group_column(bcol):
                """A group key's ``(data, validity)`` per row of the pointer
                gid's build side, read through `rows_of` from another's."""
                if bcol[0] == gid_join:
                    return build_cols[bcol]
                ri = rows_of(bcol[0], gid_join)
                d, v = build_cols[bcol]
                safe = jnp.clip(ri, 0, None)
                return d[safe], (ri >= 0) if v is None else (ri >= 0) & v[safe]

            ri_safe: Dict[int, jnp.ndarray] = {}

            def probe(ks, slots, mask):
                """`mask` narrowed by the matches of joins `ks` probed at
                the rows of `slots`, their used build columns put there."""
                for k in ks:
                    kd, kv = ev.eval(lkeys[k], slots)
                    if k in composites:
                        ri = composite_pointer(
                            k, [(kd, kv), ev.eval(lkeys2[k], slots)],
                            kept_lut(k))
                    else:
                        ri = pointer(k, kd, kv, kept_lut(k))
                    matched = ri >= 0
                    mask = mask & matched
                    safe = jnp.clip(ri, 0, None)
                    ri_safe[k] = safe
                    # materialize this build table's used columns into the
                    # slot space so later keys/aggs/filters can reference
                    # them
                    for (bk, col), slot in used.items():
                        if bk != k:
                            continue
                        bd, bv = build_cols[(bk, col)]
                        d = bd[safe]
                        v = matched if bv is None else (matched & bv[safe])
                        slots[slot] = (d, v)
                return mask

            mask = probe([k for k in range(n_joins)
                          if k not in folded and k not in deferred],
                         slots, mask)
            for f in conjuncts:
                d, v = ev.eval(f, slots)
                mask = mask & (d if v is None else (d & v))

            def radix_gid(slots, n):
                """The mixed-radix group id of `slots`' `n` rows."""
                gid = jnp.zeros(n, dtype=jnp.int32)
                for s in radix_spec:
                    if s.get("raw"):
                        # encoded key: the CODES are the radix digits —
                        # never decode inside the kernel
                        d, v = slots[s["ref"].index]
                    else:
                        d, v = ev.eval(s["ref"], slots)
                    r = s["r"]
                    if s["kind"] == "derived":
                        # the codes' digits, NULL included: `_derived_key`
                        code = jnp.asarray(s["map"])[
                            jnp.clip(d, 0, len(s["map"]) - 1)]
                        if v is not None:
                            code = jnp.where(v, code, r - 1)
                        gid = gid * r + code
                        continue
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
                return gid

            if radix_spec is not None:
                domain = int(np.prod([s["r"] for s in radix_spec]))
                # read from a deferred join's columns: made in each branch
                gid = None if deferred else radix_gid(slots, n_rows)
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
                # with deferred joins: the rows the others pass, a superset
                passed = jnp.sum(mask, dtype=jnp.int32)

                def compacted():
                    # within a group the rows keep their order, so a float
                    # sum adds the terms the whole probe's would
                    at = compact_positions(mask, compact_cap)
                    some = {i: (slots[i][0][at], None if slots[i][1] is None
                                else slots[i][1][at]) for i in branch_slots}
                    some[PARAMS_SLOT] = params
                    live = jnp.arange(compact_cap, dtype=jnp.int32) < passed
                    if not deferred:
                        return reduce_rows(some, live, gid[at], compact_cap)
                    live = probe(deferred, some, live)
                    return reduce_rows(
                        some, live, radix_gid(some, compact_cap)
                        if gid is None else gid[at], compact_cap)

                def whole():
                    if not deferred:
                        return reduce_rows(slots, mask, gid, n_rows)
                    full = dict(slots)
                    matched = probe(deferred, full, mask)
                    return reduce_rows(full, matched, radix_gid(full, n_rows)
                                       if gid is None else gid, n_rows)

                hits, outs = jax.lax.cond(passed <= compact_cap, compacted,
                                          whole)
            hit = hits > 0
            if outer is not None:
                # every row of the preserved side its own filters keep is a
                # group, whether a probe row reached it or not: where none
                # did, the join's one row for it holds NULLs, which COUNT(*)
                # counts and every other aggregate (of a probe column) skips
                hit = jnp.arange(domain, dtype=bounds.dtype) < bounds[outer, 2]
                keep = build_keep(outer)
                if keep is not None:
                    hit = hit & keep
                outs = [(jnp.maximum(d, 1), v) if a.func == "count_star"
                        else (d, v) for a, (d, v) in zip(agg_exprs, outs)]
            tags: List[Tuple[str, np.dtype]] = []
            # the counts the host reads beside the rows: the probe rows that
            # passed (a compacting program), then each semi-join's groups
            # and those of them its HAVING kept
            counts = ([passed] if compact_cap else []) + semi_counts
            if nested is not None:
                # the outer aggregate over these groups: only its rows
                # leave, the histogram's from the pack's head (`run`), and
                # whether they are all there last among the counts
                flat, fits = _reduce_nested(nested, hit, outs, params)
                out = pack_flat(flat, tags)
                self._pack_tags = tags
                counts.append((~fits).astype(jnp.int32))
                return out[:, :_NESTED_CAP + 1], out, jnp.stack(counts)
            if topk is None:
                flat = [hit]
                for d, v in outs:
                    flat.append(d)
                    flat.append(v if v is not None else jnp.ones_like(hit))
                # a dependent build side's row per group, for the host's take
                flat.extend(rows_of(m, gid_join) for m in dependents)
            else:
                # the tail: the k first present groups by the sort keys,
                # then only their rows of every output and group-key column
                keys = [(outs[i] if kind == "agg"
                         else group_column(i)) + (asc, nulls_first)
                        for kind, i, asc, nulls_first in topk["keys"]]
                at, found = select_topk(hit, keys, topk["k"])
                groups = jnp.sum(hit, dtype=jnp.int32)
                flat = [found, at, jnp.broadcast_to(groups, at.shape)]
                flat.extend(jnp.broadcast_to(c, at.shape) for c in counts)
                for d, v in outs:
                    flat.append(d[at])
                    flat.append(v[at] if v is not None
                                else jnp.ones_like(found))
                for bcol in topk["cols"]:
                    rows = at if bcol[0] == gid_join else jnp.clip(
                        rows_of(bcol[0], gid_join)[at], 0, None)
                    d, v = build_cols[bcol]
                    flat.append(d[rows])
                    flat.append(v[rows] if v is not None
                                else jnp.ones_like(found))
            out = pack_flat(flat, tags)
            self._pack_tags = tags
            # the counts ride in the top-k pack; the plain pack is the one
            # the other rungs pull (`fetch_packed`), so there they are a
            # second, small output
            return (out, jnp.stack(counts)) if counts and topk is None \
                else out

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
            bt, uid = self.build_tables[k], self.side_uids[k]
            c = bt.columns[bt.column_names[col]] if uid is None \
                else slotted_column(uid, bt, col, self.composites[k]) \
                if k in self.composites else padded_column(uid, bt, col)
            build_cols[(k, col)] = (c.data, c.validity)
        return (probe_datas, probe_valids, luts, build_cols, pt.row_valid,
                tuple(params), self.bounds)

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
                        "segsum": self.segsum_mode,
                        "build_rows": list(self.build_rows)}
        if cap:
            launch_attrs["compact"] = cap
        if self.semis:
            launch_attrs["semi"] = len(self.semis)
        if self.composites:
            launch_attrs["composite"] = len(self.composites)
        if self.outer is not None:
            launch_attrs["outer"] = 1
        if self.nested is not None:
            launch_attrs["nested"] = 1
        if self.deferred:
            launch_attrs["deferred"] = len(self.deferred)
        if self.sum_codespace:
            launch_attrs["sum_codespace"] = self.sum_codespace
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
            if self.nested is not None:
                result, counts, overflow = self._decode_nested(packed, tags)
                groups = result.num_rows
                attrs.update(overflow=overflow)
            elif self.topk is not None:
                result, groups, counts = self._decode_topk(packed, tags)
            else:
                if cap or self.semis:  # the program's outputs: (pack, counts)
                    host, present, counts = _fetch_packed_and_counts(
                        *packed, self.domain)
                else:
                    host, present = fetch_packed(packed, self.domain)
                    counts = []
                result = self._decode_result(host, present, tags)
                groups = int(present.shape[0])
            attrs.update(groups=groups, rows=result.num_rows)
            if cap:
                passed, counts = counts[0], counts[1:]
                attrs.update(passed=passed, cap=cap)
            self.semi_stats = list(zip(counts[::2], counts[1::2]))
        if cap:
            self.metrics.inc("join.compact.engaged")
            if passed > cap:
                self.metrics.inc("join.compact.overflow")
        return result

    def _decode_nested(self, packed, tags) -> Tuple[Table, List[int], bool]:
        """The host's half of a nested aggregate (`_reduce_nested`): one
        pull of the histogram's slots and the counts, a second of every
        slot where the key's range overflowed the histogram; then the
        outer aggregate's present rows as a host-resident table, the
        program's other counts, and whether it overflowed."""
        from ..utils import d2h_fetch
        from .compiled import unpack_row
        from .rel.base import unique_names

        head, whole, counts = packed
        with d2h_fetch(nbytes=int(head.nbytes) + int(counts.nbytes)):
            host, counts = jax.device_get((head, counts))
        counts = [int(c) for c in counts]
        overflow = bool(counts.pop())
        if overflow:
            with d2h_fetch(nbytes=int(whole.nbytes)):
                host = jax.device_get(whole)
        host = np.asarray(host)
        host = host[:, np.nonzero(host[0] != 0.0)[0]]
        outer = self.nested["rel"]
        names = unique_names([f.name for f in outer.schema])
        out: Dict[str, Column] = {}
        for i, (name, field) in enumerate(zip(names, outer.schema)):
            d = unpack_row(host, 1 + 2 * i, tags)
            v = unpack_row(host, 2 + 2 * i, tags) != 0
            target = sql_to_np(field.sql_type)
            out[name] = Column(d.astype(target) if d.dtype != target else d,
                               field.sql_type, None if bool(v.all()) else v)
        return Table(out, host.shape[1]), counts, overflow

    def _decode_topk(self, packed, tags) -> Tuple[Table, int, List[int]]:
        """The host's half of the top-k tail: one pull of the ``[rows, k]``
        pack, then the found rows as a host-resident table in the tail's
        order, the count of present groups and the program's other counts
        (`_build`: the probe rows that passed, from a compacting program,
        then two per semi-join)."""
        from ..utils import d2h_fetch
        from .compiled import unpack_row
        from .rel.base import unique_names

        with d2h_fetch(nbytes=int(packed.nbytes)):
            host = np.asarray(jax.device_get(packed))
        n = int(np.count_nonzero(host[0]))  # found rows come first
        at = unpack_row(host, 1, tags)
        groups = int(unpack_row(host, 2, tags)[0])
        head = 3 + bool(self.compact_cap) + 2 * len(self.semis)
        counts = [int(unpack_row(host, i, tags)[0]) for i in range(3, head)]
        pairs = iter(range(head, host.shape[0], 2))

        def pulled(i):
            v = unpack_row(host, i + 1, tags)[:n] != 0
            return unpack_row(host, i, tags)[:n], None if v.all() else v

        def column(bcol):
            return _column_of(self.build_tables, bcol)

        names = unique_names([f.name for f in self.rel.schema])
        n_groups = len(self.group_cols)
        out: Dict[str, Column] = {}
        for a, name in zip(self.rel.agg_exprs, names[n_groups:]):
            d, validity = pulled(next(pairs))
            target = sql_to_np(a.sql_type)
            out[name] = Column(d.astype(target) if d.dtype != target else d,
                               a.sql_type, validity)
        keys: Dict[Tuple[int, int], Column] = {}
        for bcol in self.topk["cols"]:
            d, validity = pulled(next(pairs))
            keys[bcol] = decode_radix_group_key(_ColMeta(column(bcol)), d, 0,
                                                validity)
        for bcol in set(self.group_cols) - set(keys):
            # an RLE key has no row to gather in the program: the runs of
            # the found rows, looked up on the host (a decode on the device
            # would compile at the table's row count)
            c = column(bcol)
            with d2h_fetch():
                d, lengths, v = jax.device_get(
                    (c.data, c.enc_lengths, c.validity))
            run = np.searchsorted(np.cumsum(lengths, dtype=np.int64), at[:n],
                                  side="right")
            keys[bcol] = Column(
                np.asarray(d)[run], c.sql_type,
                None if v is None else np.asarray(v)[run], c.dictionary)
        group_out = {name: keys[bcol]
                     for name, bcol in zip(names, self.group_cols)}
        return Table({**group_out, **out}, n), groups, counts

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
                if spec["kind"] == "derived":
                    out[name] = Column(spec["values"][code], spec["sql_type"],
                                       validity)
                    continue
                # shared host decode handles str/bool/plain-int AND the
                # encoded (DICT/FOR) key kinds
                out[name] = decode_radix_group_key(spec["col"], code,
                                                   spec["off"], validity)
            n_groups = len(self.radix_spec)
        elif self.gid_join is not None and self.gid_join >= 0:
            # a dependent build side's row per present group rides behind
            # the aggregates' rows of the pack
            n_aggs = len(self.rel.agg_exprs)
            rows = {self.gid_join: present}
            for i, m in enumerate(self.dependents):
                rows[m] = unpack_row(host, 1 + 2 * n_aggs + i, tags)
            for name, bcol in zip(names, self.group_cols):
                out[name] = _column_of(build_tables, bcol).take(rows[bcol[0]])
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


def _fetch_packed_and_counts(packed, counts, domain: int):
    """`compiled.fetch_packed` for a program without a top-k tail whose
    second output is its small vector of counts (`_build`): the same ONE
    pull, the counts in it: ``(host_matrix[:, present], present, counts)``."""
    from ..utils import d2h_fetch
    from .compiled import HOST_PULL_DOMAIN

    if domain <= HOST_PULL_DOMAIN:
        with d2h_fetch(nbytes=int(packed.nbytes)):
            host, counts = jax.device_get((packed, counts))
        present = np.nonzero(host[0] != 0.0)[0]
        return host[:, present], present, [int(c) for c in counts]
    present_dev = jnp.nonzero(packed[0] != 0.0)[0]
    with d2h_fetch():
        host, present, counts = jax.device_get(
            (packed[:, present_dev], present_dev, counts))
    return np.asarray(host), np.asarray(present), [int(c) for c in counts]


def _whole_lut(executor, join: dict, bdc, table: Table):
    """The kept LUT of a whole build side's table version, built on first
    use: ``((rmin, lut) or None, built here)``."""
    from ..analysis.estimator import device_budget_bytes

    budget = min(device_budget_bytes(executor.config) or _LUT_MAX_BYTES,
                 _LUT_MAX_BYTES)
    return LUTS.get_or_build(
        (bdc.uid, str(join["rkey"]), budget),
        lambda: build_lut(executor, join["rkey"], table, max_bytes=budget,
                          uid=bdc.uid))


def _composite_side(executor, join: dict, bdc, table: Table):
    """The kept slots of a whole build side's table version joined on a
    two-column key (`composite_slots`, ``second`` on the device, ``key``
    naming the layout for `slotted_column`), built on first use: ``(entry
    or None, built here)``.  None where the rule declines: a key that is
    no plain integer column on either side, a padded (sharded) table, or
    what `composite_slots` declines."""
    from ..analysis.estimator import device_budget_bytes
    from ..ops.join import composite_slots
    from ..utils import d2h_fetch

    budget = min(device_budget_bytes(executor.config) or _LUT_MAX_BYTES,
                 _LUT_MAX_BYTES)
    rkeys = (join["rkey"], join["pair"][1])
    key = f"{rkeys[0]}&{rkeys[1]}"
    if not all(type(x) is ColumnRef or isinstance(x, _BuildRef)
               for x in (join["lkey"], join["pair"][0])):
        return None, False  # an expression on the probe's side of a key

    def build():
        if table.row_valid is not None \
                or not all(type(k) is ColumnRef for k in rkeys):
            return None
        cols = [executor.eval_expr(k, table).decode() for k in rkeys]
        if any(c.sql_type in STRING_TYPES
               or not jnp.issubdtype(c.data.dtype, jnp.integer)
               for c in cols):
            return None
        with d2h_fetch():
            host = jax.device_get([(c.data, c.validity) for c in cols])
        got = composite_slots(host, budget)
        if got is None:
            return None
        return dict(got, key=key, second=jax.device_put(got["second"]))

    return LUTS.get_or_build((bdc.uid, "composite", key, budget), build)


def _key_range(executor, key: Expr, table: Table) -> Optional[Tuple[int, int]]:
    """``(lowest, highest)`` value of integer column `key` of `table`, or
    None where it is no integer column, holds no value, or the table is
    padded (a sharded one: the sharded rungs' to serve)."""
    from .compiled import padded_int_bounds
    from ..utils import host_ints

    if table.row_valid is not None or not table.num_rows:
        return None
    kc = executor.eval_expr(key, table).decode()
    if not jnp.issubdtype(kc.data.dtype, jnp.integer):
        return None
    lo, hi, valid = host_ints(
        *padded_int_bounds(kc.data, kc.validity),
        jnp.bool_(True) if kc.validity is None else jnp.any(kc.validity))
    return (lo, hi) if valid else None


def _semi_build(executor, ctx, join: dict, pz, attrs: dict):
    """A semi-join's aggregate build side as the program reduces it:
    ``(its scan's table, CompiledJoinAggregate's whole[k])``, or None where
    the rule declines it (and with it the rung, as before PR 35): a
    table the request overrides, a run-length or non-integer key, a range
    of key values whose ``[domain]`` state `one_key_domain_limit` refuses.
    The key's range is kept per table version (`LUTS`); the HAVING filters'
    and the aggregates' literals become runtime parameters of `pz`."""
    from ..ops.grouping import one_key_domain_limit

    semi, scan = join["semi"], join["semi"]["scan"]
    if (scan.schema_name, scan.table_name) in executor.table_overrides:
        return None
    sdc = ctx.schema[scan.schema_name].tables.get(scan.table_name)
    if sdc is None:
        return None
    table = executor.get_table(scan.schema_name, scan.table_name)
    if scan.projection is not None:
        table = table.select(scan.projection)
    if not table.column_names or any(
            getattr(c, "encoding", Encoding.PLAIN) is Encoding.RLE
            for c in table.columns.values()):
        return None
    span, built_here = LUTS.get_or_build(
        (sdc.uid, "range", str(semi["key"])),
        lambda: _key_range(executor, semi["key"], table))
    if span is None:
        return None
    domain = span[1] - span[0] + 1
    attrs.update(domain=domain, rows=table.num_rows, reused=not built_here)
    if domain > one_key_domain_limit(len(semi["aggs"]) + 1, executor.config):
        return None
    # the program's state covers the range's bucket: a value past its top
    # holds no row, so its group is never present and no HAVING keeps it
    domain = bucket_rows(domain)

    def dictionary_of(i):
        return table.columns[table.column_names[i]].dictionary

    return table, {
        "lut": (span[0], None),
        "conjuncts": [pz.rewrite(e, dictionary_of)
                      for e in semi["conjuncts"]],
        "semi": {"domain": domain, "key": semi["key"],
                 "aggs": [pz.rewrite_agg(a) for a in semi["aggs"]],
                 "having": [pz.rewrite(e) for e in semi["having"]],
                 "schema": semi["schema"]}}


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
             + [j["pair"][0] for j in ext.joins if "pair" in j]
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
    # a group-by of a group-by: the program reduces the inner aggregate and
    # the outer one over its groups, and answers for `rel`
    nested = _nested_shape(rel)
    extraction = _extract(rel if nested is None else nested["agg"])
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
        probe_table = executor.get_table(ext.scan.schema_name,
                                         ext.scan.table_name)
        if ext.scan.projection is not None:
            probe_table = probe_table.select(ext.scan.projection)
        if not probe_table.column_names:
            return None

        def probe_dictionary(i):
            return probe_table.columns[probe_table.column_names[i]].dictionary

        # a LIKE on a probe column rides as a mask over its dictionary
        ext.conjuncts = [pz.rewrite(e, probe_dictionary, probe=True)
                         for e in ext.conjuncts]
        agg_exprs = [pz.rewrite_agg(a) for a in agg_exprs]
        # all the host does per request to have the build sides ready
        build_tables: List[Table] = []
        whole: List[Optional[dict]] = []
        semi_attrs: List[dict] = []
        with detail("join:build") as attrs:
            built = lut_bytes = padded = 0
            for k, j in enumerate(ext.joins):
                w = None
                scan = j["plan"]
                if j["semi"] is not None:
                    # all the host does per request for this build side:
                    # its table and its key's kept range; the program
                    # reduces it
                    with detail("join:semi") as sattrs:
                        got = _semi_build(executor, ctx, j, pz, sattrs)
                    if got is None:
                        # today's path: the interpreted converters, and no
                        # eager run of the subquery for a LUT it may fail
                        raise _Unsupported("semi-join build side declined")
                    bt, w = got
                    semi_attrs.append(sattrs)
                elif j["whole"] is not None and (
                        scan.schema_name, scan.table_name
                ) not in executor.table_overrides:
                    bdc = ctx.schema[scan.schema_name].tables[scan.table_name]
                    bt = executor.get_table(scan.schema_name, scan.table_name)
                    if scan.projection is not None:
                        bt = bt.select(scan.projection)
                    if bt.column_names and _stays_whole(
                            k, j, bt, ext, group_exprs, agg_exprs):
                        comp = None
                        if "pair" in j:
                            with detail("join:composite") as cattrs:
                                comp, built_here = _composite_side(
                                    executor, j, bdc, bt)
                                cattrs.update(
                                    rows=bt.num_rows, reused=not built_here,
                                    run=comp and comp["run"])
                            lut = comp and (comp["lo"], comp["second"])
                            if comp and comp["lead"]:
                                # the other pair leads: it is the join's key
                                (j["lkey"], j["rkey"]), j["pair"] = \
                                    j["pair"], (j["lkey"], j["rkey"])
                        else:
                            lut, built_here = _whole_lut(executor, j, bdc, bt)
                        if lut is not None:
                            ctx.metrics.inc("join.lut.built" if built_here
                                            else "join.lut.reused")
                            built += int(built_here)
                            lut_bytes += int(lut[1].nbytes)
                            padded += int(comp is None and bucket_rows(
                                bt.padded_rows) > bt.padded_rows)

                            def dictionary_of(i, bt=bt):
                                return bt.columns[
                                    bt.column_names[i]].dictionary

                            w = {"lut": lut, "uid": bdc.uid, "conjuncts": [
                                pz.rewrite(e, dictionary_of)
                                for e in j["whole"]]}
                            if comp:
                                w["composite"] = comp
                if w is None and "pair" in j:
                    raise _Unsupported("two-column key not kept whole")
                if j.get("outer") and (
                        w is None or "composite" in w
                        or bt.columns[bt.column_names[j["rkey"].index]]
                        .validity is not None):
                    # each preserved row is its own group: a NULL key would
                    # be one group of several rows
                    raise _Unsupported("outer join's preserved side not kept "
                                       "whole on a key without NULLs")
                if w is None:
                    # any other build side runs through the normal recursive
                    # converter (nested joins, aggregates, anything) and
                    # comes compacted
                    bt = executor.execute(j["plan"])
                build_tables.append(bt)
                whole.append(w)
            attrs.update(tables=len(build_tables), lut_bytes=lut_bytes,
                         built=built, padded=padded)
        if pz.masks:
            ctx.metrics.inc("join.like.masks", len(pz.masks))
        params = pz.params
        # the Sort above a nested aggregate orders its few rows itself
        topk = None if nested else executor.topk_hints.get(id(rel))
        family = (
            ext.scan.schema_name, ext.scan.table_name,
            tuple(ext.scan.projection or ()),
            tuple(repr(j["plan"]) if w is None else
                  (j["plan"].schema_name, j["plan"].table_name,
                   tuple(j["plan"].projection or ()),
                   tuple(str(e) for e in w["conjuncts"]))
                  if "semi" not in w else
                  ("semi", j["semi"]["scan"].schema_name,
                   j["semi"]["scan"].table_name,
                   tuple(j["semi"]["scan"].projection or ()),
                   tuple(str(e) for e in w["conjuncts"]),
                   str(w["semi"]["key"]),
                   tuple(str(a) for a in w["semi"]["aggs"]),
                   tuple(str(e) for e in w["semi"]["having"]))
                  for j, w in zip(ext.joins, whole)),
            tuple(str(j["lkey"]) + "=" + str(j["rkey"])
                  + ("&{}={}".format(*j["pair"]) if "pair" in j else "")
                  + (" outer" if j.get("outer") else "")
                  for j in ext.joins),
            tuple(str(e) for e in ext.conjuncts),
            tuple(str(e) for e in group_exprs),
            tuple(str(a) for a in agg_exprs),
            tuple((f.name, f.sql_type) for f in rel.schema),
            topk,
            nested and (tuple(str(a) for a in nested["agg"].agg_exprs),
                        nested["key"], tuple(str(a) for a in nested["aggs"])),
        )
        bucket = (tuple(uids), probe_table.num_rows, probe_table.padded_rows,
                  tuple(bt.num_rows if w is None or "semi" in w
                        else (w["composite"]["slots"], w["composite"]["run"])
                        if "composite" in w else bucket_rows(bt.padded_rows)
                        for bt, w in zip(build_tables, whole)))
        # the constructor binds the tables this first run reads; the finally
        # below drops them.  No `warm`: this rung never defers
        compiled, built_here = PROGRAMS.get_or_build(
            ctx, family, bucket,
            lambda: CompiledJoinAggregate(
                rel if nested is None else nested["agg"], ext, group_exprs,
                agg_exprs, probe_table, build_tables, executor, whole=whole,
                topk=topk, nested=nested),
            params=params)
        if not built_here:
            compiled.probe_table = probe_table
            compiled.build_tables = build_tables
            compiled.metrics = ctx.metrics
        else:
            record_predicate_spaces(ctx, compiled)
            if compiled.compact_cap:
                ctx.metrics.inc("join.compact.programs")
            kept = sum(w is not None and "semi" not in w for w in whole)
            semi = len(compiled.semis)
            if kept:
                ctx.metrics.inc("join.build.whole", kept)
            if padded:
                ctx.metrics.inc("join.build.padded", padded)
            if semi:
                ctx.metrics.inc("join.build.semi", semi)
            if compiled.composites:
                ctx.metrics.inc("join.build.composite",
                                len(compiled.composites))
            if kept + semi < len(whole):
                ctx.metrics.inc("join.build.eager", len(whole) - kept - semi)
            if compiled.wide_domains:
                ctx.metrics.inc("aggregate.domain.wide",
                                compiled.wide_domains)
        try:
            from ..resilience import faults

            faults.maybe_inject("oom", executor.config)
            result = compiled.run(params)
            if compiled.outer is not None:
                ctx.metrics.inc("join.outer.preserved")
            if nested is not None:
                ctx.metrics.inc("aggregate.nested")
            for sattrs, (groups, passed) in zip(semi_attrs,
                                                compiled.semi_stats):
                sattrs.update(groups=groups, passed=passed)
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
