"""Compiled query pipelines: whole-subtree JIT for the hot aggregation shape.

The eager converters dispatch one XLA op at a time; this module instead
compiles a `TableScan -> [Filter/Projection]* -> Aggregate` subtree into ONE
jitted function so XLA fuses the filter mask, the projection arithmetic and
the segment reductions into a single pass over HBM.  The core trick for TPU
(SURVEY.md §7 "dynamic shapes"): selection is *deferred* — the filter never
compacts rows; its boolean mask is ANDed into each aggregate's validity mask,
so every array keeps its static shape end-to-end and only the (tiny) group
table is compacted on the host afterwards.

Parity note: the reference has no analogue — dask fuses blockwise tasks but
each kernel is still an interpreted pandas call; this is the TPU-native
replacement for that entire execution layer.
"""
from __future__ import annotations

import logging
import re
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.column import Column
from ..columnar.dtypes import (
    DATETIME_TYPES,
    FLOAT_TYPES,
    INTEGER_TYPES,
    INTERVAL_TYPES,
    NUMERIC_TYPES,
    STRING_TYPES,
    SqlType,
    sql_to_np,
)
from ..columnar.encodings import FLIP_CMP, Encoding, dict_literal_bounds
from ..columnar.table import Table
from ..ops import datetime as dt_ops
from ..ops import strings as str_ops
from ..ops.membership import dictionary_membership, sorted_membership
from ..planner import plan as p
from ..planner.expressions import (
    AggExpr,
    CaseExpr,
    Cast,
    ColumnRef,
    Expr,
    InArrayExpr,
    InListExpr,
    InParamExpr,
    Literal,
    ParamRef,
    ScalarFunc,
    transform,
    walk,
)
from .programs import ProgramCache

logger = logging.getLogger(__name__)

#: reserved slot-dict key the per-call runtime parameter vector rides in
#: (column slots are ints, so a string key can never collide).  Threading
#: params through the slots dict — instead of mutating evaluator state —
#: keeps concurrent traces of the same pipeline (solo + batched variants
#: on different worker threads) race-free.
PARAMS_SLOT = "__params__"


_SUPPORTED_AGGS = {"sum", "count", "avg", "min", "max", "count_star",
                   "var_samp", "var_pop", "stddev_samp", "stddev_pop"}

_NUMERIC_BINOPS = {
    "add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
    "eq": jnp.equal, "ne": jnp.not_equal, "lt": jnp.less, "le": jnp.less_equal,
    "gt": jnp.greater, "ge": jnp.greater_equal,
}

_MATH_UNARY = {
    "abs": jnp.abs, "neg": jnp.negative, "sqrt": jnp.sqrt, "exp": jnp.exp,
    "ln": jnp.log, "log10": jnp.log10, "log2": jnp.log2, "sin": jnp.sin,
    "cos": jnp.cos, "tan": jnp.tan, "floor": jnp.floor, "ceil": jnp.ceil,
    "sign": jnp.sign,
}


class _Unsupported(Exception):
    pass


def padded_int_bounds(data, row_valid):
    """Device min/max of an integer group-key column, with pad rows masked
    out: on a padded sharded table the zero pad rows would otherwise widen
    the radix span/offset, and real keys far from 0 could falsely trip the
    1<<22 domain gate (ADVICE r5).  Row 0 is always a logical row when any
    exist (padding appends at the tail), so it is a safe fill value."""
    if row_valid is None:
        return jnp.min(data), jnp.max(data)
    safe = jnp.where(row_valid, data, data[0])
    return jnp.min(safe), jnp.max(safe)


def check_no_rle(table) -> None:
    """RLE columns are run-aligned (storage-at-rest); the row-positional
    compiled pipelines decline them so the eager path decodes once at scan.
    Shared eligibility guard — raises _Unsupported."""
    for c in table.columns.values():
        if getattr(c, "encoding", Encoding.PLAIN) is Encoding.RLE:
            raise _Unsupported("rle-encoded column in compiled pipeline")


#: the calls a row-invariant comparand may hold: each answers a 0-d input
#: with a 0-d value that is never NULL and never differs between two rows
#: (so nothing volatile); integer `div` is kept out below, since it answers
#: a zero divisor with NULL
_ROW_INVARIANT_OPS = frozenset({
    "add", "sub", "mul", "div", "neg", "datetime_add", "datetime_sub",
    "datetime_sub_interval", "int_to_interval_days"})


def _is_number(value) -> bool:
    """A Python / numpy int or float, and no bool."""
    return not isinstance(value, bool) and isinstance(
        value, (int, float, np.integer, np.floating))


def row_invariant(expr: Expr) -> bool:
    """True when `expr` is ONE never-NULL number per query: runtime
    parameters and numeric literals under casts and the calls of
    `_ROW_INVARIANT_OPS`, and nothing else — so no column reference of any
    kind, no subquery, aggregate or window expression, no NULL, boolean or
    string literal, no volatile call.  What `_encoded_compare` takes as the
    other side of a code-space comparison and what
    `count_codespace_predicates` counts as one: both ask here."""
    for sub in walk(expr):
        if isinstance(sub, Literal):
            if not _is_number(sub.value):
                return False
        elif isinstance(sub, ScalarFunc):
            if sub.op not in _ROW_INVARIANT_OPS or (
                    sub.op == "div" and all(a.sql_type in INTEGER_TYPES
                                            for a in sub.args)):
                return False
        elif not isinstance(sub, (ParamRef, Cast)):
            return False
        if sub.sql_type in STRING_TYPES:
            return False
    return True


def count_codespace_predicates(exprs, table) -> Tuple[int, int]:
    """Static ``(code space, value space)`` count of the predicates a
    pipeline over `table` evaluates on a raw numeric DICT-column ref: the
    comparisons and IN tests it rewrites onto the codes
    (``columnar.encoding.codespace_pred``) and those it does not, which
    decode the column per row (``columnar.encoding.valuespace_pred``).
    Computed from the plan, through the evaluator's own `_codespace_operands`
    / `_codespace_members`, so the metrics are trace-independent and cannot
    disagree with the kernel."""
    ev = _TraceEval(table)
    code = value = 0
    for e in exprs:
        if e is None:
            continue
        for sub in walk(e):
            try:
                if isinstance(sub, ScalarFunc) and sub.op in FLIP_CMP \
                        and len(sub.args) == 2:
                    if ev._codespace_operands(sub.op, sub.args) is not None:
                        code += 1
                    elif any(ev._dict_source(a) is not None
                             for a in sub.args):
                        value += 1
                elif isinstance(sub, (InListExpr, InArrayExpr)) \
                        and ev._dict_source(sub.arg) is not None:
                    if ev._codespace_members(sub) is not None:
                        code += 1
                    else:
                        value += 1
            except (IndexError, KeyError):
                pass
    return code, value


def record_predicate_spaces(ctx, compiled) -> None:
    """A freshly built pipeline's `count_codespace_predicates` pair into the
    ``columnar.encoding.*`` counters, and the SUM / AVG aggregates it sums in
    code space (`count_codespace_sums`) into ``aggregate.sum.codespace``
    (once per build, not per query)."""
    if compiled.codespace_preds:
        ctx.metrics.inc("columnar.encoding.codespace_pred",
                        compiled.codespace_preds)
    if compiled.valuespace_preds:
        ctx.metrics.inc("columnar.encoding.valuespace_pred",
                        compiled.valuespace_preds)
    if getattr(compiled, "sum_codespace", 0):
        ctx.metrics.inc("aggregate.sum.codespace", compiled.sum_codespace)


def check_agg_static_support(agg_exprs):
    """Plan-only aggregate eligibility for the compiled pipelines (shared by
    CompiledAggregate and compiled_join) — raises _Unsupported."""
    for a in agg_exprs:
        if a.func not in _SUPPORTED_AGGS or a.distinct:
            raise _Unsupported(f"agg {a.func}")
        if a.args and a.args[0].sql_type in STRING_TYPES:
            # string min/max needs dictionary-order handling (eager path)
            raise _Unsupported("string-typed aggregate argument")
        for x in list(a.args) + ([a.filter] if a.filter is not None else []):
            for sub in walk(x):
                if isinstance(sub, AggExpr) and sub is not x:
                    raise _Unsupported("nested agg")


def pack_flat(flat, tags_sink: List, xp=jnp):
    """Pack every (domain,)-sized aggregate output into ONE f64 matrix so the
    host pulls the whole result in a single transfer — per-array decode costs
    ~15 device round trips per query, which can dwarf the kernel itself.
    Row i holds ``flat[i]``; everything of 32 bits or less is exact in f64.
    A 64-bit integer rides as two words: its high word in row i, its low
    (uint32) word in a row past the flat's.  It cannot ride as a bitcast:
    the TPU's f64 is a pair of f32, which flushes the denormals a small
    integer's bits make.  Runs under trace (or on the host with ``xp=np``);
    the (kind, dtype) tag per row of `flat` lands in `tags_sink` for
    `unpack_row`."""
    tags_sink.clear()
    packed, low = [], []
    for x in flat:
        dt = np.dtype(x.dtype)
        if dt.kind in "iu" and dt.itemsize == 8:
            high = np.int32 if dt.kind == "i" else np.uint32
            packed.append((x >> 32).astype(high).astype(np.float64))
            low.append((x & 0xFFFFFFFF).astype(np.uint32).astype(np.float64))
            tags_sink.append(("words", dt))
        else:  # f64 as it is; bool, f32/f16, ints <= 32 bits: exact in f64
            packed.append(x if dt == np.float64 else x.astype(np.float64))
            tags_sink.append(("as", dt))
    return xp.stack(packed + low, axis=0)


# above this domain the device compacts to the present groups before the
# pull; below it the whole packed matrix rides one transfer
HOST_PULL_DOMAIN = 1 << 16


def fetch_packed(packed, domain: int) -> Tuple[np.ndarray, np.ndarray]:
    """One-transfer host fetch of a packed output matrix.

    Returns (host_matrix[:, present], present) as numpy arrays; row 0 of the
    matrix is the group-present indicator."""
    from ..utils import d2h_fetch

    if domain <= HOST_PULL_DOMAIN:
        with d2h_fetch(nbytes=int(packed.nbytes)):
            host = np.asarray(jax.device_get(packed))
        present = np.nonzero(host[0] != 0.0)[0]
        return host[:, present], present
    present_dev = jnp.nonzero(packed[0] != 0.0)[0]
    with d2h_fetch():
        host, present = (np.asarray(a) for a in jax.device_get(
            (packed[:, present_dev], present_dev)))
    return host, present


def unpack_row(host: np.ndarray, i: int, tags) -> np.ndarray:
    """Recover output row i of a fetched pack (`pack_flat`) in its original
    dtype."""
    kind, dt = tags[i]
    row = np.ascontiguousarray(host[i])
    if kind == "words":
        # the low words follow the flat's rows, in the order of their tags
        j = len(tags) + sum(k == "words" for k, _ in tags[:i])
        return ((row.astype(np.int64) << 32)
                | host[j].astype(np.int64)).astype(dt)
    return row.astype(dt) if row.dtype != dt else row


class SegmentReducer:
    """Batched segment reductions for one compiled kernel (works under jit).

    TPU-first design: the naive per-aggregate formulation
    issued ~2 scatter-adds per aggregate — most of them emulated int64 —
    which dominated the Q1 kernel on-chip.  This reducer instead
      * computes gid/counts in 32-bit (int64 scatter is emulated on TPU),
      * dedupes identical count reductions across aggregates,
      * and, in 'matmul' mode, collects ALL float sums and counts into ONE
        blocked one-hot MXU matmul (`ops.pallas_kernels.segsum_scan_blocked`)
        with float64 per-block partial accumulation — float64 inputs ride an
        exact hi/lo float32 split, counts are exact, and the float error is
        bounded by MATMUL_FLOAT_REL_ERR_BOUND.  Each block of rows is read
        as a contiguous slice of every column and stacked row-minor,
        ``[K, block]``, on chip: no stack of the whole columns is built.
    Integer sums always use exact int64 scatter (SQL exactness).

    Usage: register reductions (count / sum_float / sum_int / minmax),
    call finish(), then resolve handles via get().
    """

    def __init__(self, gid, domain: int, mode: str, n_rows: int):
        self.gid = gid.astype(jnp.int32)
        self.domain = domain
        self.mode = mode
        self.n_rows = n_rows
        self._cnt_dtype = jnp.int32 if n_rows < (1 << 31) else jnp.int64
        self._fcols: List = []        # deferred f32 columns (matmul mode)
        self._fdedup: Dict[Tuple[int, int], Tuple[int, Optional[int]]] = {}
        self._cnt_dedup: Dict[int, object] = {}
        self._out = None
        # id()-keyed dedup is only sound while the keyed objects stay alive:
        # transient registrands (e.g. the x and x*x arrays of a variance
        # aggregate) would otherwise be collected right after registration,
        # letting a later allocation reuse the id and falsely hit the cache
        # (ADVICE r3).  Pin every keyed object for the reducer's lifetime.
        self._keepalive: List = []

    @property
    def total_rows(self) -> int:
        """The most rows one group of a finished state can hold (a mesh
        reducer combines every shard's: spmd/aggregate.py)."""
        return self.n_rows

    # -- immediate scatter reductions ---------------------------------------
    def _scatter(self, x):
        return jax.ops.segment_sum(x, self.gid, self.domain)

    # -- registrations -------------------------------------------------------
    def count(self, mask):
        """Segment count of True rows; deduped by mask identity.

        Exact in every mode: 'matmul' keeps integer-valued f32 block
        partials below 2^24 and combines them in f64; other modes (incl.
        'pallas', whose whole-input f32 accumulation saturates at 2^24)
        use integer scatter."""
        h = self._cnt_dedup.get(id(mask))
        if h is None:
            if self.mode == "matmul":
                h = self._push(mask.astype(jnp.float32))
            else:
                h = ("done", self._scatter(mask.astype(self._cnt_dtype)))
            self._cnt_dedup[id(mask)] = h
            self._keepalive.append(mask)
        return h

    def sum_float(self, data, mask):
        """Segment sum of a float column (rows where mask is False ignored)."""
        key = (id(data), id(mask))
        h = self._fdedup.get(key)
        if h is not None:
            return h
        if self.mode == "scatter":
            h = ("done", self._scatter(jnp.where(mask, data, jnp.zeros_like(data))))
        elif data.dtype == jnp.float64:
            from ..ops.pallas_kernels import split_hi_lo

            hi, lo = split_hi_lo(jnp.where(mask, data, 0.0))
            h = self._push2(hi, lo)
        else:
            h = self._push(jnp.where(mask, data, jnp.zeros_like(data)))
        self._fdedup[key] = h
        self._keepalive.append((data, mask))
        return h

    def sum_whole(self, data, mask, dtype):
        """Segment sum of int32 whole numbers that `WholeSum` has bounded
        (``total_rows * max|value| < 2**31``: no group's sum can wrap), as
        ONE int32 scatter, exact in any order, converted at ``[domain]`` to
        `dtype`.  Deduped like `sum_float`."""
        key = (id(data), id(mask))
        h = self._fdedup.get(key)
        if h is None:
            h = ("done", self._scatter(
                jnp.where(mask, data, jnp.zeros_like(data))).astype(dtype))
            self._fdedup[key] = h
            self._keepalive.append((data, mask))
        return h

    def sum_int(self, data, mask):
        """Exact integer segment sum (always int64 scatter)."""
        acc = data.astype(jnp.int64)
        return ("done", self._scatter(jnp.where(mask, acc, jnp.zeros_like(acc))))

    def seg_min(self, contrib):
        """Segment min of pre-filled contributions (absent rows carry the
        identity fill).  Routed through the reducer — not called inline —
        so the SPMD subclass (spmd/aggregate.py) can combine the per-shard
        partials with a pmin collective."""
        return ("done", jax.ops.segment_min(contrib, self.gid, self.domain))

    def seg_max(self, contrib):
        return ("done", jax.ops.segment_max(contrib, self.gid, self.domain))

    def _push(self, col):
        self._fcols.append(col)
        return ("f", len(self._fcols) - 1, None)

    def _push2(self, hi, lo):
        self._fcols.append(hi)
        self._fcols.append(lo)
        return ("f", len(self._fcols) - 2, len(self._fcols) - 1)

    # -- execution -----------------------------------------------------------
    def finish(self):
        if self._fcols:
            from ..ops.pallas_kernels import segsum_pallas, segsum_scan_blocked

            if self.mode == "pallas":
                # columns are already f32 (f64 inputs were hi/lo-split at
                # registration) — feed them to the kernel as-is
                stack = jnp.stack(self._fcols, axis=1)
                self._out = segsum_pallas(self.gid, stack,
                                          self.domain).astype(jnp.float64)
            else:
                self._out = segsum_scan_blocked(self.gid, self._fcols, self.domain)

    def get(self, h):
        if h[0] == "done":
            return h[1]
        _, i, j = h
        v = self._out[:, i]
        if j is not None:
            v = v + self._out[:, j]
        return v


def compact_positions(mask, cap: int):
    """Fixed-capacity compaction under trace: ``int32[cap]``, the positions
    of the first `cap` True rows of `mask` in row order; where fewer are
    True the rest of the buffer repeats the last position, for the caller
    to mask (``arange(cap) < sum(mask)``).  ONE 32-bit sort of the True
    rows' own positions (a False row's key lies past every position) and a
    slice: no gather or scatter per row of `mask`, so what a reducer pays
    per row it can pay per PASSING row (compiled_join.py).  On a v5e at 24M
    rows the sort reads 0.067 s; a stable two-operand sort of the positions
    by ``~mask`` 0.059 s, but its buffer ends in False rows' positions and
    the gathers that follow read 0.155 s for this one's 0.129;
    `jnp.nonzero(mask, size=cap)` scatter-adds over every row: 2.2 s
    (PERF.md, PR 34)."""
    n = mask.shape[0]
    last = max(n - 1, 0)
    keys = jnp.where(mask, jnp.arange(n, dtype=jnp.int32), jnp.int32(n))
    at = jnp.minimum(jax.lax.sort(keys)[:cap], last)
    if cap > n:
        at = jnp.pad(at, (0, cap - n), constant_values=last)
    return at


class WholeSum(NamedTuple):
    """How a SUM / AVG over a raw DICT column stays in code space: the
    dictionary's values as int32, affine in the code (``code * step + base``,
    `table` None) or looked up in `table`, and the dtype today's decoded sum
    has (float64 for a DOUBLE dictionary, int64 for an integer one)."""

    table: Optional[np.ndarray]
    step: int
    base: int
    dtype: np.dtype

    def values(self, codes):
        """The per-row int32 values of the column's `codes`."""
        if self.table is None:
            return codes.astype(jnp.int32) * jnp.int32(self.step) \
                + jnp.int32(self.base)
        return jnp.asarray(self.table)[
            jnp.clip(codes, 0, len(self.table) - 1)]


def codespace_sum(ev, a: AggExpr, mode: str, total_rows: int
                  ) -> Optional[WholeSum]:
    """The `WholeSum` of aggregate `a` where a reducer in `mode` over
    `total_rows` rows may sum it as int32, else None (today's decode and
    float64 / int64 scatter).  It may when `a` is a SUM or AVG of a raw
    reference to a numeric DICT column whose dictionary, read here on the
    host, is float64 or integer (a float32 dictionary's decoded sum is not
    exact, so the two answers would differ) and holds finite whole numbers
    alone with ``total_rows * max|value| < 2**31`` (every row in one group
    cannot wrap an int32), and the mode is
    ``scatter``: in ``matmul`` mode the column rides the one blocked matmul
    with every other sum, and a scatter beside it would only add a pass.
    The dictionary also answers the NaN test (`agg_argument`).  What the
    trace asks (`segment_agg_outputs`) and what `count_codespace_sums`
    counts: both ask here."""
    if a.func not in ("sum", "avg") or mode != "scatter":
        return None
    col = ev._dict_source(a.args[0])
    if col is None:
        return None
    vals = np.asarray(col.enc_values)
    if vals.size == 0 or not (vals.dtype == np.float64
                              or vals.dtype.kind == "i"):
        return None
    if vals.dtype.kind == "f" and not (
            np.isfinite(vals).all() and (vals == np.rint(vals)).all()):
        return None
    if max(total_rows, 1) * max(abs(int(vals.min())),
                                abs(int(vals.max()))) >= 1 << 31:
        return None
    whole = np.rint(vals).astype(np.int64)
    step = int(whole[1] - whole[0]) if len(whole) > 1 else 0
    affine = bool((np.diff(whole) == step).all())
    return WholeSum(None if affine else whole.astype(np.int32), step,
                    int(whole[0]),
                    np.dtype(np.float64 if vals.dtype.kind == "f"
                             else np.int64))


def count_codespace_sums(agg_exprs, table, mode: str, total_rows: int) -> int:
    """Static count of the aggregates a pipeline over `table` sums in code
    space (``aggregate.sum.codespace``): `codespace_sum`'s own answer for the
    reducer's mode and rows, so the counter cannot disagree with the
    kernel."""
    ev = _TraceEval(table)
    return sum(codespace_sum(ev, a, mode, total_rows) is not None
               for a in agg_exprs)


def agg_argument(ev, slots, a: AggExpr, sel, cache: Dict[Tuple, Tuple],
                 whole: Optional[WholeSum] = None):
    """One aggregate's ``(argument_or_None, validity)`` pair under trace:
    the row-selection mask ANDed with the FILTER clause and the argument's
    own validity (floats additionally drop NaNs — pandas dropna parity).
    Given `whole` the argument is the DICT column's int32 values, never
    decoded, and its dictionary has answered the NaN test: where the column
    has no validity and the aggregate no FILTER the mask IS `sel`, which
    `SegmentReducer.count` dedupes by identity.
    Deduped by (arg, filter) repr in ``cache`` so identical masks register
    once.  Shared by the finalized-output kernels (below) and the streamed
    partial-state kernel (streaming/aggregate.py) so their NULL semantics
    can never drift."""
    key = (str(a.args[0]) if a.args else "*",
           str(a.filter) if a.filter is not None else None,
           whole is not None)
    got = cache.get(key)
    if got is not None:
        return got
    valid = sel
    if a.filter is not None:
        fd, fv = ev.eval(a.filter, slots)
        valid = valid & (fd if fv is None else (fd & fv))
    if not a.args:
        got = (None, valid)
    else:
        if whole is not None:
            codes, av = slots[a.args[0].index]
            ad = whole.values(codes)
        else:
            ad, av = ev.eval(a.args[0], slots)
        v = valid if av is None else (valid & av)
        if jnp.issubdtype(ad.dtype, jnp.floating):
            v = v & ~jnp.isnan(ad)
        got = (ad, v)
    cache[key] = got
    return got


def segment_agg_outputs(ev, slots, agg_exprs, sel, gid, domain, reducer):
    """Per-aggregate segment reductions under jit tracing.

    Shared by the scan->aggregate pipeline (CompiledAggregate) and the
    join->aggregate pipeline (compiled_join.py).  Returns one
    (values[domain], validity_or_None[domain]) pair per AggExpr; `sel`
    is the row-selection mask (deferred filters — nothing compacts).

    Two-phase: every aggregate registers its reductions on `reducer`
    (deduping identical (arg, filter) masks), one batched reduction runs,
    then outputs assemble.  Count/sum semantics match the reference's
    pandas NULL handling (reference physical/rel/logical/aggregate.py
    sum `min_count=1`, dropna-style counts)."""
    arg_cache: Dict[Tuple, Tuple] = {}

    # phase A: register reductions
    plans = []
    for a in agg_exprs:
        whole = codespace_sum(ev, a, reducer.mode, reducer.total_rows)
        ad, v = agg_argument(ev, slots, a, sel, arg_cache, whole)
        cnt_h = reducer.count(v)
        if a.func in ("count", "count_star"):
            plans.append(("count", cnt_h))
            continue
        if a.func in ("sum", "avg"):
            if whole is not None:
                h = reducer.sum_whole(ad, v, whole.dtype)
            elif ad.dtype == jnp.bool_:
                h = reducer.sum_int(ad.astype(jnp.int32), v)
            elif jnp.issubdtype(ad.dtype, jnp.integer):
                h = reducer.sum_int(ad, v)
            else:
                h = reducer.sum_float(ad, v)
            plans.append((a.func, h, cnt_h))
            continue
        if a.func in ("min", "max"):
            if ad.dtype == jnp.bool_:
                ad = ad.astype(jnp.int32)  # ADVICE r2: jnp.iinfo rejects bool
            if jnp.issubdtype(ad.dtype, jnp.floating):
                fill = jnp.array(jnp.inf if a.func == "min" else -jnp.inf,
                                 dtype=ad.dtype)
            else:
                info = jnp.iinfo(ad.dtype)
                fill = jnp.array(info.max if a.func == "min" else info.min,
                                 dtype=ad.dtype)
            contrib = jnp.where(v, ad, fill)
            h = (reducer.seg_min if a.func == "min"
                 else reducer.seg_max)(contrib)
            plans.append(("minmax", h, cnt_h))
            continue
        # variance family
        x = ad.astype(jnp.float64)
        h1 = reducer.sum_float(x, v)
        h2 = reducer.sum_float(x * x, v)
        plans.append((a.func, h1, h2, cnt_h))

    reducer.finish()

    # phase B: assemble outputs in order
    outs = []
    for plan in plans:
        kind = plan[0]
        if kind == "count":
            outs.append((reducer.get(plan[1]), None))
        elif kind == "sum":
            s, cnt = reducer.get(plan[1]), reducer.get(plan[2])
            outs.append((s, cnt > 0))
        elif kind == "avg":
            s, cnt = reducer.get(plan[1]), reducer.get(plan[2])
            outs.append((s.astype(jnp.float64) / jnp.maximum(cnt, 1), cnt > 0))
        elif kind == "minmax":
            red, cnt = reducer.get(plan[1]), reducer.get(plan[2])
            outs.append((jnp.where(cnt > 0, red, jnp.zeros_like(red)), cnt > 0))
        else:
            s1 = reducer.get(plan[1]).astype(jnp.float64)
            s2 = reducer.get(plan[2]).astype(jnp.float64)
            cnt = reducer.get(plan[3])
            ddof = 1 if kind.endswith("samp") else 0
            mean = s1 / jnp.maximum(cnt, 1)
            var = (jnp.maximum(s2 - cnt * mean * mean, 0.0)
                   / jnp.maximum(cnt - ddof, 1))
            out = jnp.sqrt(var) if kind.startswith("stddev") else var
            outs.append((out, cnt > ddof))
    return outs


def decode_radix_group_key(col, code: np.ndarray, off,
                           validity) -> Column:
    """Host decode of one radix group-key column (shared by the scan- and
    join-aggregate pipelines): `code` is the extracted radix digit (already
    clamped below the NULL slot), `col` the _ColMeta of the key source.
    Encoded keys map codes back through their dictionary / affine."""
    if col.sql_type in STRING_TYPES:
        return Column(code.astype(np.int32), col.sql_type, validity,
                      col.dictionary)
    enc = getattr(col, "encoding", Encoding.PLAIN)
    if enc is Encoding.DICT:
        vals = col.enc_values[np.minimum(code, len(col.enc_values) - 1)]
        return Column(vals, col.sql_type, validity)
    if col.data.dtype == np.bool_:
        return Column(code == 1, col.sql_type, validity)
    raw = code + off
    if enc is Encoding.FOR:
        vals = (raw.astype(np.int64) * col.enc_scale + col.enc_ref).astype(
            sql_to_np(col.sql_type))
        return Column(vals, col.sql_type, validity)
    return Column(raw.astype(col.data.dtype), col.sql_type, validity)


class _ColMeta:
    """Trace-time stand-in for a Column: metadata + dictionary only.

    The jitted kernel's closure holds its _TraceEval forever; giving it the
    real Columns would pin every input table's device buffers for the cache
    entry's lifetime (ADVICE r2).  Only the dtype (as an empty host array),
    the SQL type, the (host, numpy) string dictionary and the compressed-
    encoding metadata (host-side) are retained."""

    __slots__ = ("sql_type", "dictionary", "data", "_len", "encoding",
                 "enc_values", "enc_ref", "enc_scale")

    def __init__(self, col):
        self.sql_type = col.sql_type
        self.dictionary = col.dictionary
        self.data = np.empty(0, dtype=np.dtype(col.data.dtype))
        self._len = col.data.shape[0]
        self.encoding = getattr(col, "encoding", Encoding.PLAIN)
        self.enc_values = getattr(col, "enc_values", None)
        self.enc_ref = getattr(col, "enc_ref", 0)
        self.enc_scale = getattr(col, "enc_scale", 1)

    def __len__(self):
        return self._len


class _TableMeta:
    """Column-metadata view of a Table for trace-time use."""

    def __init__(self, table):
        self.column_names = list(table.column_names)
        self.columns = {n: _ColMeta(table.columns[n]) for n in self.column_names}
        self.num_rows = table.num_rows


class _TraceEval:
    """Expression evaluator usable under jit tracing.

    Values are (data, valid_or_None) pairs; string columns appear as their
    integer dictionary codes with host-precomputed lookup tables for any
    string-typed operation (computed at *compile* time from the concrete
    dictionaries, entering the program as constants).

    `table` may be a real Table (plan-time use) or a _TableMeta (inside jit
    closures, so device buffers are not pinned)."""

    def __init__(self, table):
        self.table = table
        self.names = table.column_names

    def col(self, index: int) -> Column:
        return self.table.columns[self.names[index]]

    def eval(self, expr: Expr, slots):
        if isinstance(expr, ColumnRef) and type(expr) is ColumnRef:
            return self._decode_slot(expr.index, slots)
        if isinstance(expr, ParamRef):
            # runtime query parameter (families/parameterize.py): a traced
            # scalar argument instead of a baked constant, so one compiled
            # executable serves every literal of the family
            return (slots[PARAMS_SLOT][expr.index], None)
        if isinstance(expr, InParamExpr):
            return self._in_param(expr, slots)
        if isinstance(expr, Literal):
            if expr.value is None:
                return (jnp.zeros((), dtype=jnp.float64), jnp.zeros((), dtype=bool))
            if expr.sql_type in STRING_TYPES:
                raise _Unsupported("free string literal")
            v = expr.value
            dtype = sql_to_np(expr.sql_type)
            return (jnp.asarray(v, dtype=dtype), None)
        if isinstance(expr, Cast):
            d, v = self.eval(expr.arg, slots)
            src, dst = expr.arg.sql_type, expr.sql_type
            if dst in STRING_TYPES or src in STRING_TYPES:
                raise _Unsupported("string cast in compiled pipeline")
            if src in FLOAT_TYPES and dst in INTEGER_TYPES:
                d = jnp.nan_to_num(jnp.trunc(d))
            if src in DATETIME_TYPES and dst == SqlType.DATE:
                # match the eager cast: truncate epoch-ns to midnight
                ns_per_day = jnp.int64(86_400_000_000_000)
                d = (jnp.floor_divide(d, ns_per_day)) * ns_per_day
            if dst == SqlType.BOOLEAN:
                return (d != 0, v)
            return (d.astype(sql_to_np(dst)), v)
        if isinstance(expr, CaseExpr):
            out_d, out_v = (jnp.zeros((), dtype=sql_to_np(expr.sql_type)),
                            jnp.zeros((), dtype=bool))
            if expr.else_ is not None:
                out_d, out_v = self.eval(expr.else_, slots)
            for cond, val in reversed(expr.whens):
                cd, cv = self.eval(cond, slots)
                take = cd if cv is None else (cd & cv)
                vd, vv = self.eval(val, slots)
                out_d = jnp.where(take, vd, out_d)
                if vv is None and out_v is None:
                    out_v = None
                else:
                    vv_ = jnp.ones_like(take) if vv is None else vv
                    ov_ = jnp.ones_like(take) if out_v is None else out_v
                    out_v = jnp.where(take, vv_, ov_)
            return (out_d, out_v)
        if isinstance(expr, InListExpr):
            return self._in_list(expr, slots)
        if isinstance(expr, InArrayExpr):
            return self._in_array(expr, slots)
        if isinstance(expr, ScalarFunc):
            return self._call(expr, slots)
        raise _Unsupported(f"expr {type(expr).__name__}")

    # -- compressed-domain column access ------------------------------------
    def _decode_slot(self, index: int, slots):
        """Slot value as VALUES: DICT gathers through the (tiny, constant)
        value LUT, FOR applies its fused affine — either way the HBM read
        was the narrow code array; the decode lives in registers.  PLAIN
        (and string codes, whose dictionary IS the representation) pass
        through untouched."""
        d, v = slots[index]
        c = self.col(index)
        enc = getattr(c, "encoding", Encoding.PLAIN)
        if enc is Encoding.DICT and c.sql_type not in STRING_TYPES:
            lut = jnp.asarray(c.enc_values)
            d = lut[jnp.clip(d, 0, len(c.enc_values) - 1)]
        elif enc is Encoding.FOR:
            d = d.astype(sql_to_np(c.sql_type))
            if c.enc_scale != 1:
                d = d * c.enc_scale
            if c.enc_ref:
                d = d + jnp.asarray(c.enc_ref, dtype=d.dtype)
        return (d, v)

    def _dict_source(self, expr: Expr):
        """The column meta when `expr` is a raw ref to a numeric
        DICT-encoded column (the code-space predicate target)."""
        if isinstance(expr, ColumnRef) and type(expr) is ColumnRef:
            c = self.col(expr.index)
            if getattr(c, "encoding", Encoding.PLAIN) is Encoding.DICT \
                    and c.sql_type not in STRING_TYPES:
                return c
        return None

    def _codespace_operands(self, op: str, args):
        """``(column ref, comparand, op)`` with the column on the left when
        one side of the comparison is a raw numeric DICT column and the
        other is `row_invariant`; else None."""
        a, b = args
        for colarg, other, o in ((a, b, op), (b, a, FLIP_CMP[op])):
            if self._dict_source(colarg) is not None \
                    and row_invariant(other):
                return colarg, other, o
        return None

    def _encoded_compare(self, op: str, args, slots):
        """``dict_col CMP row-invariant comparand`` rewritten into CODE
        space, in either operand order.

        The dictionary is sorted, so order predicates translate through a
        searchsorted boundary — host-side for a bare literal (a static int
        enters the program); for any other `row_invariant` comparand (a
        runtime param, or params and literals under casts and date / number
        arithmetic, as in ``DATE '1998-12-01' - INTERVAL '90' DAY``) the
        comparand is evaluated ONCE, to a 0-d value, and searched in-kernel
        over the (tiny) value-constant, which keeps ONE executable per plan
        family and no per-row decode.  Returns None when the shape doesn't
        match or the scalar can be NULL (caller evaluates in value space)."""
        found = self._codespace_operands(op, args)
        if found is None:
            return None
        colarg, other, o = found
        codes, valid = slots[colarg.index]
        vals = self.col(colarg.index).enc_values
        if isinstance(other, Literal):
            kind, code = dict_literal_bounds(vals, o, other.value)
            if kind == "lt":
                hit = codes < code
            elif kind == "ge":
                hit = codes >= code
            elif kind == "eq":
                hit = codes == code
            elif kind == "ne":
                hit = codes != code
            elif kind == "all":
                hit = jnp.ones(codes.shape, dtype=bool)
            else:  # "none"
                hit = jnp.zeros(codes.shape, dtype=bool)
            return (hit, valid)
        p, p_valid = self.eval(other, slots)
        if p_valid is not None:
            return None
        vj = jnp.asarray(vals)
        if np.dtype(vj.dtype).kind != np.dtype(p.dtype).kind:
            # cross-kind comparand (float vs int dictionary): compare
            # in f64 — exact for every dictionary this path serves
            vj = vj.astype(jnp.float64)
            p = p.astype(jnp.float64)
        left = jnp.searchsorted(vj, p, side="left")
        if o in ("lt", "ge"):
            bound = left
        else:
            bound = jnp.searchsorted(vj, p, side="right")
        if o in ("lt", "le"):
            hit = codes < bound
        elif o in ("gt", "ge"):
            hit = codes >= bound
        else:  # eq / ne: exact-member test
            present = (left < len(vals)) & \
                (vj[jnp.clip(left, 0, len(vals) - 1)] == p)
            eq = present & (codes == left)
            hit = eq if o == "eq" else ~eq
        return (hit, valid)

    # -- compile-time string handling --------------------------------------
    def _string_source(self, expr: Expr) -> Optional[Column]:
        if isinstance(expr, ColumnRef) and type(expr) is ColumnRef:
            c = self.col(expr.index)
            if c.sql_type in STRING_TYPES:
                return c
        return None

    def _codespace_members(self, expr) -> Optional[list]:
        """The value list (NULLs dropped) of an IN over a raw numeric DICT
        column when every item is a number: what `_dict_membership` tests in
        code space and `count_codespace_predicates` counts there.  Else
        None."""
        if self._dict_source(expr.arg) is None:
            return None
        if isinstance(expr, InArrayExpr):
            values = list(np.asarray(expr.values))
        elif all(isinstance(it, Literal) for it in expr.items):
            values = [it.value for it in expr.items if it.value is not None]
        else:
            return None
        return values if all(_is_number(v) for v in values) else None

    def _dict_membership(self, expr, slots):
        """IN over a numeric DICT column: map the value list through the
        sorted dictionary on the host (absent values drop out) and test
        CODE membership on device."""
        values = self._codespace_members(expr)
        if values is None:
            return None
        enc_values = self.col(expr.arg.index).enc_values
        code_list = []
        for v in values:
            i = int(np.searchsorted(enc_values, v))
            if i < len(enc_values) and enc_values[i] == v:
                code_list.append(i)
        codes, valid = slots[expr.arg.index]
        if code_list:
            hit = sorted_membership(codes, np.asarray(code_list,
                                                      dtype=np.int32))
        else:
            hit = jnp.zeros(codes.shape, dtype=bool)
        return (~hit if expr.negated else hit, valid)

    def _in_list(self, expr: InListExpr, slots):
        src = self._string_source(expr.arg)
        if src is not None:
            if not all(isinstance(it, Literal) for it in expr.items):
                raise _Unsupported("non-literal IN list")
            values = [it.value for it in expr.items if it.value is not None]
            codes, valid = slots[expr.arg.index]
            hit = dictionary_membership(codes, src.dictionary, values)
            if expr.negated:
                hit = ~hit
            return (hit, valid)
        got = self._dict_membership(expr, slots)
        if got is not None:
            return got
        ad, av = self.eval(expr.arg, slots)
        if not all(isinstance(it, Literal) for it in expr.items):
            raise _Unsupported("non-literal IN list")
        vals = [it.value for it in expr.items if it.value is not None]
        if all(isinstance(v, (int, float)) and not isinstance(v, bool)
               for v in vals):
            # exact for int columns vs float literals (no dtype truncation)
            hit = sorted_membership(ad, np.asarray(vals))
        else:
            hit = jnp.zeros_like(ad, dtype=bool)
            for v in vals:
                hit = hit | (ad == jnp.asarray(v))
        if expr.negated:
            hit = ~hit
        return (hit, av)

    def _in_param(self, expr: InParamExpr, slots):
        """Membership against a runtime parameter vector: the value list is
        a traced (sorted, pow2-padded) argument, so IN lists of different
        values — and different lengths within one bucket — share the
        executable.  Same search the host-constant path uses."""
        ad, av = self.eval(expr.arg, slots)
        sv = slots[PARAMS_SLOT][expr.index]
        d = ad.astype(sv.dtype)
        idx = jnp.clip(jnp.searchsorted(sv, d), 0, expr.length - 1)
        hit = jnp.take(sv, idx) == d
        return (~hit if expr.negated else hit, av)

    def _in_array(self, expr: InArrayExpr, slots):
        src = self._string_source(expr.arg)
        if src is not None:
            codes, valid = slots[expr.arg.index]
            hit = dictionary_membership(codes, src.dictionary, expr.values)
            return (~hit if expr.negated else hit, valid)
        got = self._dict_membership(expr, slots)
        if got is not None:
            return got
        ad, av = self.eval(expr.arg, slots)
        hit = sorted_membership(ad, expr.values)
        return (~hit if expr.negated else hit, av)

    def _call(self, expr: ScalarFunc, slots):
        op = expr.op
        args = expr.args

        if op == "code_mask":
            # a string predicate as a runtime bool per dictionary entry
            # (families/parameterize.py::_string_mask), read at the codes
            codes, valid = slots[args[0].index]
            entries = slots[PARAMS_SLOT][args[1].index]
            return (entries[jnp.clip(codes, 0, entries.shape[0] - 1)], valid)

        # string comparisons / LIKE against literals via dictionary LUTs
        if op in ("eq", "ne", "like", "ilike", "similar") and len(args) >= 2:
            src = self._string_source(args[0])
            lit = args[1]
            if src is not None and isinstance(lit, ParamRef) \
                    and op in ("eq", "ne"):
                # the literal's dictionary code as a runtime parameter
                # (families/parameterize.py::_string_code_compare)
                codes, valid = slots[args[0].index]
                hit = codes == slots[PARAMS_SLOT][lit.index].astype(
                    codes.dtype)
                return (~hit if op == "ne" else hit, valid)
            if src is not None and isinstance(lit, Literal) and isinstance(lit.value, str):
                d = src.dictionary if src.dictionary is not None else np.array([""], dtype=object)
                if op in ("eq", "ne"):
                    lut = jnp.asarray(d.astype(str) == lit.value)
                else:
                    esc = None
                    if len(args) > 2 and isinstance(args[2], Literal):
                        esc = args[2].value
                    pat = (str_ops.similar_to_regex(lit.value, esc) if op == "similar"
                           else str_ops.like_to_regex(lit.value, esc))
                    rx = re.compile(pat, re.IGNORECASE if op == "ilike" else 0)
                    lut = jnp.asarray(np.array([rx.match(str(x)) is not None for x in d]))
                codes, valid = slots[args[0].index]
                hit = lut[jnp.clip(codes, 0, len(d) - 1)]
                if op == "ne":
                    hit = ~hit
                return (hit, valid)

        # numeric comparisons against DICT-encoded columns run in CODE space
        if op in ("eq", "ne", "lt", "le", "gt", "ge") and len(args) == 2:
            got = self._encoded_compare(op, args, slots)
            if got is not None:
                return got

        vals = [self.eval(a, slots) for a in args]
        if op in _NUMERIC_BINOPS:
            (ad, av), (bd, bv) = vals
            if _is_string_typed(args[0]) or _is_string_typed(args[1]):
                raise _Unsupported(f"string {op}")
            ad, bd = _promote_pair(ad, bd)
            return (_NUMERIC_BINOPS[op](ad, bd), _and_valid(av, bv))
        if op == "div":
            (ad, av), (bd, bv) = vals
            ad, bd = _promote_pair(ad, bd)
            if jnp.issubdtype(ad.dtype, jnp.integer):
                safe = jnp.where(bd == 0, 1, bd)
                q = jnp.floor_divide(jnp.abs(ad), jnp.abs(safe))
                q = jnp.where((ad < 0) ^ (bd < 0), -q, q)
                return (q, _and_valid(av, bv, bd != 0))
            return (ad / bd, _and_valid(av, bv))
        if op == "mod":
            (ad, av), (bd, bv) = vals
            ad, bd = _promote_pair(ad, bd)
            if jnp.issubdtype(ad.dtype, jnp.integer):
                safe = jnp.where(bd == 0, 1, bd)
                return (jnp.fmod(ad, safe), _and_valid(av, bv, bd != 0))
            return (jnp.fmod(ad, bd), _and_valid(av, bv))
        if op == "and":
            (ad, av), (bd, bv) = vals
            a_t = ad if av is None else (ad & av)
            b_t = bd if bv is None else (bd & bv)
            value = a_t & b_t
            av_ = jnp.ones_like(ad) if av is None else av
            bv_ = jnp.ones_like(bd) if bv is None else bv
            known = (av_ & bv_) | (av_ & ~ad) | (bv_ & ~bd)
            return (value, known)
        if op == "or":
            (ad, av), (bd, bv) = vals
            a_t = ad if av is None else (ad & av)
            b_t = bd if bv is None else (bd & bv)
            value = a_t | b_t
            av_ = jnp.ones_like(ad) if av is None else av
            bv_ = jnp.ones_like(bd) if bv is None else bv
            known = (av_ & bv_) | (av_ & ad) | (bv_ & bd)
            return (value, known)
        if op == "not":
            (ad, av) = vals[0]
            return (~ad, av)
        if op == "is_null":
            (ad, av) = vals[0]
            if av is None:
                base = jnp.zeros_like(ad, dtype=bool)
            else:
                base = ~av
            if jnp.issubdtype(ad.dtype, jnp.floating):
                base = base | jnp.isnan(ad)
            return (base, None)
        if op == "is_not_null":
            d, _ = self._call(ScalarFunc("is_null", expr.args, SqlType.BOOLEAN), slots)
            return (~d, None)
        if op in ("is_true", "is_false", "is_not_true", "is_not_false"):
            (ad, av) = vals[0]
            av_ = jnp.ones_like(ad) if av is None else av
            t = ad & av_
            f = ~ad & av_
            out = {"is_true": t, "is_false": f, "is_not_true": ~t, "is_not_false": ~f}[op]
            return (out, None)
        if op in _MATH_UNARY:
            (ad, av) = vals[0]
            x = ad.astype(jnp.float64) if op not in ("abs", "neg", "sign") else ad
            return (_MATH_UNARY[op](x), av)
        if op.startswith("extract_"):
            (ad, av) = vals[0]
            return (dt_ops.extract(op[8:], ad), av)
        if op == "datetime_add":
            (ad, av), (bd, bv) = vals
            if args[1].sql_type == SqlType.INTERVAL_YEAR_MONTH:
                return (dt_ops.add_months(ad, bd), _and_valid(av, bv))
            return (ad + bd, _and_valid(av, bv))
        if op == "datetime_sub_interval":
            (ad, av), (bd, bv) = vals
            if args[1].sql_type == SqlType.INTERVAL_YEAR_MONTH:
                return (dt_ops.add_months(ad, -bd), _and_valid(av, bv))
            return (ad - bd, _and_valid(av, bv))
        if op == "datetime_sub":
            (ad, av), (bd, bv) = vals
            return (ad - bd, _and_valid(av, bv))
        if op == "int_to_interval_days":
            (ad, av) = vals[0]
            return (ad.astype(jnp.int64) * dt_ops.NS_PER_DAY, av)
        if op in ("datetime_floor", "datetime_ceil"):
            (ad, av) = vals[0]
            unit = args[1].value if isinstance(args[1], Literal) else None
            if unit is None:
                raise _Unsupported("dynamic truncation unit")
            fn = dt_ops.truncate if op == "datetime_floor" else dt_ops.ceil_to
            return (fn(str(unit), ad), av)
        if op == "coalesce":
            # fold from the last fallback toward the first (highest-precedence)
            # argument; an always-valid argument resets the chain to all-valid
            out_d, out_v = vals[-1]
            for d, v in reversed(vals[:-1]):
                if v is None:
                    out_d, out_v = d, None
                    continue
                base_valid = jnp.ones_like(v) if out_v is None else out_v
                out_d = jnp.where(v, d, out_d)
                out_v = v | base_valid
            return (out_d, out_v)
        raise _Unsupported(f"op {op}")


def _is_string_typed(e: Expr) -> bool:
    return e.sql_type in STRING_TYPES


def _promote_pair(a, b):
    dt = jnp.promote_types(a.dtype, b.dtype)
    return a.astype(dt), b.astype(dt)


def _and_valid(*vs):
    out = None
    for v in vs:
        if v is None:
            continue
        out = v if out is None else (out & v)
    return out


# ---------------------------------------------------------------------------
# Pipeline extraction: Aggregate <- [Filter/Projection]* <- TableScan
# ---------------------------------------------------------------------------
def _extract_chain(agg: p.Aggregate):
    """Substitute projections so group/agg/filter exprs are all over the scan
    schema.  Returns (scan, filters, group_exprs, agg_exprs) or None."""
    # walk the chain top-down, remembering each node's position
    chain: List[p.LogicalPlan] = []
    node = agg.input
    while True:
        if isinstance(node, p.Projection):
            if any(isinstance(x, AggExpr) for e in node.exprs for x in walk(e)):
                return None
            chain.append(node)
            node = node.input
        elif isinstance(node, (p.Filter, p.SubqueryAlias)):
            chain.append(node)
            node = node.input
        elif isinstance(node, p.TableScan):
            break
        else:
            return None
    scan = node

    def subst_below(expr: Expr, pos: int) -> Expr:
        """Rewrite an expression bound at chain[pos]'s *input* onto the scan
        schema by folding in every projection below that point."""
        for lower in chain[pos:]:
            if not isinstance(lower, p.Projection):
                continue

            def fn(x, proj=lower):
                if isinstance(x, ColumnRef) and type(x) is ColumnRef:
                    return proj.exprs[x.index]
                return x

            expr = transform(expr, fn)
        return expr

    filters: List[Expr] = []
    for i, n_ in enumerate(chain):
        if isinstance(n_, p.Filter):
            filters.append(subst_below(n_.predicate, i + 1))
    group_exprs = [subst_below(e, 0) for e in agg.group_exprs]
    agg_exprs = []
    for a in agg.agg_exprs:
        new_args = tuple(subst_below(x, 0) for x in a.args)
        new_filter = subst_below(a.filter, 0) if a.filter is not None else None
        from dataclasses import replace as _rp

        agg_exprs.append(_rp(a, args=new_args, filter=new_filter))
    filters = filters + list(scan.filters)
    return scan, filters, group_exprs, agg_exprs


class CompiledAggregate:
    """One compiled scan→aggregate pipeline bound to a concrete input table."""

    def __init__(self, agg: p.Aggregate, table: Table, scan, filters,
                 group_exprs, agg_exprs, config=None):
        self.agg = agg
        self.segsum_mode = "scatter"
        self.table = table
        self.filters = filters
        self.group_exprs = group_exprs
        self.agg_exprs = agg_exprs
        ev = _TraceEval(table)

        # radix group-id plan (compile-time): dict/bool/small-int group keys
        radices = []
        offsets = []
        gcols: List[Column] = []
        pending = []  # (slot, device min, device max): ONE pull for all keys
        check_no_rle(table)
        for e in group_exprs:
            if not (isinstance(e, ColumnRef) and type(e) is ColumnRef):
                raise _Unsupported("non-column group key")
            c = ev.col(e.index)
            if c.sql_type in STRING_TYPES and c.dictionary is not None:
                radices.append(len(c.dictionary) + 1)
                offsets.append(0)
            elif getattr(c, "encoding", Encoding.PLAIN) is Encoding.DICT:
                # dictionary codes ARE the radix domain — no device min/max
                # pull, no decode, and float/datetime keys become groupable
                radices.append(len(c.enc_values) + 1)
                offsets.append(0)
            elif c.data.dtype == jnp.bool_:
                radices.append(3)
                offsets.append(0)
            elif jnp.issubdtype(c.data.dtype, jnp.integer) and len(c):
                # PLAIN ints and FOR codes alike: codes are ints; FOR keys
                # decode through their affine only at host group decode
                lo, hi = padded_int_bounds(c.data, table.row_valid)
                pending.append((len(radices), lo, hi))
                radices.append(None)
                offsets.append(None)
            else:
                raise _Unsupported("non-dictionary group key")
            gcols.append(c)
        from ..ops.grouping import (RADIX_DOMAIN_LIMIT, one_key_domain_limit,
                                    resolve_int_bounds)

        limit = RADIX_DOMAIN_LIMIT
        if len(group_exprs) == 1 and pending:
            # ONE integer key: admitted by the bytes of its [domain] state
            limit = one_key_domain_limit(len(agg_exprs) + 1, config)
        spans = resolve_int_bounds(pending, limit)
        if spans is None:
            raise _Unsupported("integer key range too large")
        for slot, (span, lo) in spans.items():
            radices[slot] = span + 1
            offsets[slot] = lo
        domain = 1
        for r in radices:
            domain *= r
        if domain > limit:
            raise _Unsupported("group domain too large")
        self.domain = max(domain, 1)
        #: a one-key range past the mixed-radix gate (`aggregate.domain.wide`)
        self.wide_domain = self.domain > RADIX_DOMAIN_LIMIT
        self.radices = radices
        self.offsets = offsets
        # metadata only — the decode in run() needs dtype/sql_type/dictionary
        self.gcols = [_ColMeta(c) for c in gcols]
        check_agg_static_support(agg_exprs)

        if config is not None:
            from ..ops.pallas_kernels import choose_segsum_impl

            self.segsum_mode = choose_segsum_impl(config, self.domain)
        #: compressed-domain accounting (columnar.encoding.* metrics)
        self.has_encoded = any(
            getattr(c, "encoding", Encoding.PLAIN) is not Encoding.PLAIN
            for c in table.columns.values())
        self.codespace_preds, self.valuespace_preds = \
            count_codespace_predicates(
                list(filters) + [x for a in agg_exprs
                                 for x in list(a.args)
                                 + ([a.filter] if a.filter is not None
                                    else [])],
                table) if self.has_encoded else (0, 0)
        #: SUM / AVG aggregates the kernel sums in code space
        #: (`aggregate.sum.codespace`); `_build` counts its own
        self.sum_codespace = 0
        #: (kind, np.dtype) per packed output row; rebound atomically each
        #: time a variant traces (solo and batched traces on concurrent
        #: threads produce identical tags — rebinding instead of clearing
        #: in place keeps a concurrent decoder's snapshot intact)
        self._pack_tags: List[Tuple[str, np.dtype]] = []
        #: the raw traced callable, kept for the batcher's vmap variant —
        #: `_build` closes over the construction table's metadata, which is
        #: nulled once the pipeline enters the plugin cache
        self._fn_raw = self._build()
        self._fn = jax.jit(self._fn_raw)
        #: lazily-built vmapped variant for the family batcher (one stacked
        #: launch over the params' leading axis); compiled per pow2 batch
        #: bucket, tracked in _warm_batch for the compile watchdog
        self._fn_batched = None
        self._warm_batch: set = set()
        # warming is left to the caller; tracing happens on first call
        #: True once _fn compiled for this table's shapes — the compile
        #: watchdog only watches calls that may compile
        self._warm = False

    def _build(self) -> Callable:
        # metadata-only eval inside the closure: no device buffers pinned
        ev = _TraceEval(_TableMeta(self.table))
        agg_exprs = self.agg_exprs
        domain = self.domain
        self.sum_codespace = count_codespace_sums(
            agg_exprs, ev.table, self.segsum_mode, self.table.padded_rows)

        def fn(datas, valids, row_valid, params=()):
            slots, sel, gid, nr = self._trace_prelude(ev, datas, valids,
                                                      row_valid, params)
            reducer = self._make_reducer(gid, domain, nr)
            hit_h = reducer.count(sel)
            outs = segment_agg_outputs(ev, slots, agg_exprs, sel, gid, domain,
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

        return fn

    def _trace_prelude(self, ev: "_TraceEval", datas, valids, row_valid,
                       params) -> Tuple[Dict, object, object, int]:
        """The shared traced front half of every aggregate kernel: slot
        table, deferred filter-mask fold, and the radix group id.  Returns
        ``(slots, sel, gid, nr)``.  Split from `_build` so the streamed
        morsel rung (streaming/aggregate.py) can reuse the identical mask
        and gid semantics under a state-emitting tail — the single-chip,
        SPMD and streamed kernels share ONE traced body, so their
        per-chunk/per-shard selections can never drift.  `ev` must be the
        metadata-only evaluator captured at build time (self.table is
        nulled once the pipeline enters the plugin cache)."""
        group_refs = [e.index for e in self.group_exprs]
        n_cols = len(ev.names)
        slots = {i: (datas[i], valids[i]) for i in range(n_cols)}
        slots[PARAMS_SLOT] = params
        nr = (datas[0].shape[0] if datas
              else row_valid.shape[0] if row_valid is not None
              else ev.table.num_rows)
        # selection mask (never compacts — static shapes end to end);
        # a padded sharded table contributes its row mask here, so pad
        # rows never count, never aggregate, never mark a group present
        mask = row_valid
        for f in self.filters:
            d, v = ev.eval(f, slots)
            m = d if v is None else (d & v)
            mask = m if mask is None else (mask & m)
        # 32-bit radix gid: domain is capped at 2^22 so int32 is exact,
        # and int64 index arithmetic is emulated on TPU
        gid = jnp.zeros((), dtype=jnp.int32)
        first = True
        for idx, r, off in zip(group_refs, self.radices, self.offsets):
            codes, valid = slots[idx]
            # widen sub-int32 keys FIRST (int8/int16 spans can overflow
            # their own dtype under `x - off`), then subtract in that
            # dtype (int64 offsets can exceed int32), then narrow: the
            # result is in [0, span] which always fits int32
            if codes.dtype == jnp.bool_ or np.dtype(codes.dtype).itemsize < 4:
                codes = codes.astype(jnp.int32)
            if off:
                codes = codes - jnp.asarray(off, dtype=codes.dtype)
            codes = jnp.clip(codes.astype(jnp.int32), 0, r - 2)
            if valid is not None:
                codes = jnp.where(valid, codes, r - 1)
            gid = codes if first else gid * r + codes
            first = False
        if first:
            gid = jnp.zeros(nr, dtype=jnp.int32)
        sel = mask if mask is not None else jnp.ones(nr, dtype=bool)
        return slots, sel, gid, nr

    def _make_reducer(self, gid, domain: int, n_rows: int) -> SegmentReducer:
        """Reducer factory the traced kernel calls — the seam the SPMD
        rung (spmd/aggregate.py) overrides to psum/pmin/pmax per-shard
        partial states across the mesh before the shared finalize."""
        return SegmentReducer(gid, domain, self.segsum_mode, n_rows)

    def _launch_attrs(self) -> Optional[dict]:
        """What this rung's `launch` spans carry beside `rung`."""
        return {"sum_codespace": self.sum_codespace} \
            if self.sum_codespace else None

    @property
    def batchable(self) -> bool:
        """Eligible for the family batcher's stacked (vmapped) launch: the
        whole packed matrix must ride one host pull per member, and only
        the scatter segment-sum mode is known vmap-clean (the pallas /
        blocked-matmul kernels are not batched here)."""
        return self.domain <= HOST_PULL_DOMAIN \
            and self.segsum_mode == "scatter"

    def run(self, table: Optional[Table] = None, params: Tuple = ()) -> Table:
        from ..observability import timed_jit_call

        # the input table is a PARAMETER, not shared object state: cached
        # pipelines are hit by concurrent server worker threads, and the
        # historical set-run-reset dance on self.table let one thread's
        # reset null the table out from under another's run
        table = table if table is not None else self.table
        datas = [table.columns[n].data for n in table.column_names]
        valids = [table.columns[n].validity for n in table.column_names]
        packed = timed_jit_call("compiled_aggregate", self._fn,
                                tuple(datas), tuple(valids),
                                table.row_valid, tuple(params),
                                may_compile=not self._warm,
                                launch_attrs=self._launch_attrs())
        self._warm = True
        tags = self._pack_tags
        host, present = fetch_packed(packed, self.domain)
        return self._decode(host, present, tags)

    def run_batched(self, table: Table, params_list: List[Tuple]
                    ) -> List[Table]:
        """One stacked launch for several same-family queries: member
        parameter vectors stack along a new leading axis (padded to the
        pow2 batch bucket by repeating the last member — padding work is
        discarded), the vmapped kernel reads the scan ONCE, and each
        member decodes its slice of the packed output."""
        from ..families import stack_params
        from ..observability import timed_jit_call
        from ..utils import d2h_fetch

        n = len(params_list)
        stacked, bucket = stack_params(params_list)
        if self._fn_batched is None:
            self._fn_batched = jax.jit(
                jax.vmap(self._fn_raw, in_axes=(None, None, None, 0)))
        datas = tuple(table.columns[c].data for c in table.column_names)
        valids = tuple(table.columns[c].validity for c in table.column_names)
        packed = timed_jit_call("compiled_aggregate", self._fn_batched,
                                datas, valids, table.row_valid, stacked,
                                may_compile=bucket not in self._warm_batch,
                                launch_attrs=self._launch_attrs())
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

    def _decode(self, host: np.ndarray, present: np.ndarray, tags) -> Table:
        if not self.gcols and present.shape[0] == 0:
            # SQL: a global aggregate over zero input rows still yields one
            # row (COUNT=0, other aggs NULL via their cnt>0 validity)
            present = np.zeros(1, dtype=np.int64)
            host = np.zeros((host.shape[0], 1), dtype=np.float64)
            for i, a in enumerate(self.agg_exprs):
                if a.func in ("count", "count_star"):
                    host[2 + 2 * i] = 1.0  # COUNT stays valid (= 0), not NULL

        def unpack(i: int) -> np.ndarray:
            return unpack_row(host, i, tags)

        from ..physical.rel.base import unique_names

        names = unique_names([f.name for f in self.agg.schema])
        out: Dict[str, Column] = {}
        # decode group keys from the radix id — all host numpy: the result
        # table is tiny and downstream operators (sort/limit/projection) run
        # on it host-side without another device round trip
        strides = []
        s = 1
        for r in reversed(self.radices):
            strides.append(s)
            s *= r
        strides = list(reversed(strides))
        for name, col, r, off, stride in zip(names, self.gcols, self.radices,
                                             self.offsets, strides):
            code = (present // stride) % r
            is_null = code == (r - 1)
            validity = ~is_null if bool(is_null.any()) else None
            code = np.minimum(code, r - 2)
            out[name] = decode_radix_group_key(col, code, off, validity)
        for i, (a, f) in enumerate(zip(self.agg_exprs,
                                       self.agg.schema[len(self.gcols):])):
            d = unpack(1 + 2 * i)
            v = unpack(2 + 2 * i) != 0.0
            target = sql_to_np(a.sql_type)
            d = d.astype(target) if d.dtype != target else d
            validity = None if bool(v.all()) else v
            out[names[len(self.gcols) + i]] = Column(d, a.sql_type, validity)
        return Table(out, int(present.shape[0]))


# compiled scan->aggregate programs; bounded, and table refs are dropped at
# construction so stale table versions don't pin HBM (ADVICE r2)
PROGRAMS = ProgramCache("compiled_aggregate", 32)


def try_compiled_aggregate(rel: p.Aggregate, executor) -> Optional[Table]:
    """Attempt the compiled path for an Aggregate subtree; None to fall back."""
    if not executor.config.get("sql.compile", True):
        return None
    chain = _extract_chain(rel)
    if chain is None:
        return None
    scan, filters, group_exprs, agg_exprs = chain
    try:
        ctx = executor.context
        table = executor.get_table(scan.schema_name, scan.table_name)
        if scan.projection is not None:
            table = table.select(scan.projection)
        dc = ctx.schema[scan.schema_name].tables.get(scan.table_name)
        if dc is None:
            return None  # view-backed scans take the eager path
        # parameterize (families/): literals in filters and aggregate
        # arguments become runtime parameters, so the cache key — and the
        # compiled executable — is shared by the whole query family
        from .. import families

        pz = families.pipeline_parameterizer(executor.config)
        filters = [pz.rewrite(f) for f in filters]
        agg_exprs = [pz.rewrite_agg(a) for a in agg_exprs]
        params = pz.params
        family = (
            scan.schema_name, scan.table_name,
            tuple(scan.projection or ()),
            tuple(str(f) for f in filters),
            tuple(str(e) for e in group_exprs),
            tuple(str(a) for a in agg_exprs),
            # two configured segment-sum modes are two programs
            str(executor.config.get("sql.compile.segsum", "auto")),
        )
        bucket = (dc.uid, table.num_rows, table.padded_rows)

        def construct():
            obj = CompiledAggregate(rel, table, scan, filters, group_exprs,
                                    agg_exprs, executor.config)
            # cached pipelines must not pin the construction table's HBM
            obj.table = None
            return obj

        compiled, built_here = PROGRAMS.get_or_build(
            ctx, family, bucket, construct,
            warm=lambda obj: obj.run(table, params), params=params)
        if compiled is None:
            return None  # deferred to the background compiler
        if built_here:
            record_predicate_spaces(ctx, compiled)
            if compiled.wide_domain:
                ctx.metrics.inc("aggregate.domain.wide")
        from ..resilience import faults

        faults.maybe_inject("oom", executor.config)
        result = PROGRAMS.run(
            ctx, family, bucket, compiled, params,
            solo=lambda: compiled.run(table, params),
            batched=lambda members: compiled.run_batched(table, members))
        if compiled.has_encoded:
            # late materialization: only the group table's rows ever decode
            ctx.metrics.inc("columnar.encoding.late_rows", result.num_rows)
        return result
    except _Unsupported as e:
        logger.debug("compiled pipeline unsupported: %s", e)
        return None
