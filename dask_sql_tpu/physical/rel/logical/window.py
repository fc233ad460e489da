"""Window-function converter.

Role parity: reference window.py:201 (groupby(partition).apply with per-group
sort + pandas expanding/rolling Indexers, window.py:96-198; ops row_number/
sum/count/max/min/avg/first/last window.py:214-225 — we add the rank family
and lag/lead).

TPU-first mechanism (SURVEY.md §7 "windows"): ONE device lexsort by
(partition keys, order keys), segment boundaries from key-change flags, then
every window function is a vectorized segmented prefix-scan / prefix-sum
difference over the sorted layout, scattered back through the inverse
permutation.  No per-group host loops.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ....columnar.column import Column
from ....columnar.dtypes import STRING_TYPES, SqlType, sql_to_np
from ....columnar.table import Table
from ....ops.grouping import key_arrays
from ....ops.sorting import sort_permutation
from ....planner import plan as p
from ....planner.expressions import WindowExpr, WindowFrameBound
from ..base import BaseRelPlugin, unique_names
from ...executor import Executor


@Executor.add_plugin_class
class WindowPlugin(BaseRelPlugin):
    class_name = "Window"

    def convert(self, rel: p.Window, executor) -> Table:
        (inp,) = self.assert_inputs(rel, 1, executor)
        names = unique_names([f.name for f in rel.schema])
        out_cols = dict(zip(names[: len(inp.column_names)],
                            [inp.columns[c] for c in inp.column_names]))
        n = inp.num_rows
        # group window exprs by identical (partition, order) so one sort serves many
        by_spec = {}
        for i, w in enumerate(rel.window_exprs):
            key = (w.spec.partition_by, w.spec.order_by)
            by_spec.setdefault(key, []).append((i, w))
        results: List[Column] = [None] * len(rel.window_exprs)
        for (part, order), items in by_spec.items():
            part_cols = [executor.eval_expr(e, inp) for e in part]
            order_cols = [executor.eval_expr(k.expr, inp) for k in order]
            layout = _SortedLayout(part_cols, order_cols,
                                   [k.ascending for k in order],
                                   [k.nulls_first_resolved() for k in order], n)
            for i, w in items:
                args = [executor.eval_expr(a, inp) for a in w.args]
                results[i] = _compute_window(w, args, layout)
        # densify all-valid masks back to None in ONE device round trip for
        # the whole node (per-expr bool(v.all()) is a blocking device sync
        # each; downstream fast paths want None masks)
        with_masks = [(name, col) for name, col in
                      zip(names[len(inp.column_names):], results)
                      if col.validity is not None]
        if with_masks:
            from ....utils import d2h_fetch

            with d2h_fetch(nbytes=len(with_masks)):
                flags = np.asarray(jax.device_get(jnp.stack(
                    [jnp.all(col.validity) for _, col in with_masks])))
            dense = {name: bool(f) for (name, _), f in zip(with_masks, flags)}
        for name, col in zip(names[len(inp.column_names):], results):
            if col.validity is not None and dense.get(name):
                col = Column(col.data, col.sql_type, None, col.dictionary)
            out_cols[name] = col
        return Table(out_cols, n)


class _SortedLayout:
    """Shared sorted layout for one (partition, order) spec."""

    def __init__(self, part_cols, order_cols, ascendings, nulls_firsts, n: int):
        self.n = n
        if n == 0:
            self.perm = jnp.zeros(0, dtype=jnp.int64)
            self.inv = jnp.zeros(0, dtype=jnp.int64)
            return
        keys_cols = list(part_cols) + list(order_cols)
        asc = [True] * len(part_cols) + list(ascendings)
        nf = [False] * len(part_cols) + list(nulls_firsts)
        if keys_cols:
            self.perm = sort_permutation(keys_cols, asc, nf)
        else:
            self.perm = jnp.arange(n, dtype=jnp.int64)
        self.inv = jnp.zeros(n, dtype=jnp.int64).at[self.perm].set(
            jnp.arange(n, dtype=jnp.int64))
        # segment flags in sorted space
        self.new_seg = _change_flags(part_cols, self.perm, n)
        self.new_peer = self.new_seg | _change_flags(order_cols, self.perm, n) \
            if order_cols else self.new_seg.copy()
        if not order_cols:
            self.new_peer = self.new_seg
        idx = jnp.arange(n, dtype=jnp.int64)
        self.seg_start = _running_latest(jnp.where(self.new_seg, idx, -1))
        self.peer_start = _running_latest(jnp.where(self.new_peer, idx, -1))
        # segment/peer end (exclusive): next start, scanned from the right
        self.seg_end = _next_start(self.new_seg, n)
        self.peer_end = _next_start(self.new_peer, n)
        # single numeric/datetime order key: value source for RANGE offsets
        # (materialized lazily — only RANGE-offset frames pay for it)
        self._order_col = order_cols[0] if len(order_cols) == 1 else None
        self._order_asc = ascendings[0] if ascendings else True
        self._order_sorted = None

    def order_values(self):
        """Ascending-within-segment order-key values, or None when RANGE
        offsets are unsupported (multi-key, strings, bools, NULLs/NaNs —
        the binary-search invariant needs a monotone segment)."""
        if self._order_sorted is not None:
            return self._order_sorted
        col = self._order_col
        if col is None or col.dictionary is not None \
                or col.data.dtype == jnp.bool_ or col.validity is not None:
            return None
        v = col.data[self.perm]
        if jnp.issubdtype(v.dtype, jnp.floating) and bool(jnp.isnan(v).any()):
            # NaN breaks the monotone-segment invariant (and SQL orders NaN
            # above +inf, so folding them together would mis-frame peers).
            # The device round trip this costs is confined to explicit
            # RANGE-offset frames over float keys — the only caller.
            return None
        self._order_sorted = v if self._order_asc else -v
        return self._order_sorted

    def scatter_back(self, sorted_vals, validity=None):
        data = sorted_vals[self.inv]
        v = None if validity is None else validity[self.inv]
        return data, v


def _change_flags(cols, perm, n):
    flags = jnp.zeros(n, dtype=bool).at[0].set(True)
    for k in key_arrays(cols):
        ks = k[perm]
        flags = flags.at[1:].set(flags[1:] | (ks[1:] != ks[:-1]))
    if not cols:
        flags = jnp.zeros(n, dtype=bool).at[0].set(True)
    return flags


def _running_latest(marked):
    """Per position, the latest index where marked >= 0 (cummax)."""
    return jax.lax.cummax(marked)


def _next_start(flags, n):
    idx = jnp.arange(n, dtype=jnp.int64)
    nxt = jnp.where(flags, idx, n)
    rev = jax.lax.cummin(nxt[::-1])[::-1]
    # next start *after* each position
    shifted = jnp.concatenate([rev[1:], jnp.array([n], dtype=rev.dtype)])
    return shifted


def _prefix(vals):
    """P[k] = sum of first k entries (length n+1)."""
    return jnp.concatenate([jnp.zeros(1, dtype=vals.dtype), jnp.cumsum(vals)])


def _segmented_searchsorted(vals, lo_bound, hi_bound, targets, side: str):
    """Per-row binary search of `targets[i]` within vals[lo_bound[i]:hi_bound[i]].

    `vals` is sorted ascending within each segment; a fixed log2(n) round count
    of gathers keeps everything vectorized (no per-segment slices).
    """
    n = vals.shape[0]
    lo = lo_bound.astype(jnp.int64)
    hi = hi_bound.astype(jnp.int64)
    rounds = max(int(np.ceil(np.log2(max(n, 2)))) + 1, 1)

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        mv = vals[jnp.clip(mid, 0, n - 1)]
        if side == "left":
            go_right = mv < targets
        else:
            go_right = mv <= targets
        new_lo = jnp.where((lo < hi) & go_right, mid + 1, lo)
        new_hi = jnp.where((lo < hi) & ~go_right, mid, hi)
        return (new_lo, new_hi)

    lo, hi = jax.lax.fori_loop(0, rounds, body, (lo, hi))
    return lo


def _frame_bounds(w: WindowExpr, lay: _SortedLayout):
    """Per sorted row: [lo, hi) frame range."""
    n = lay.n
    i = jnp.arange(n, dtype=jnp.int64)
    spec = w.spec
    if spec.units == "RANGE" or not spec.explicit_frame and spec.order_by:
        # default ordered frame: start of segment .. end of current peer group
        lo = lay.seg_start
        hi = lay.peer_end
        if spec.explicit_frame:
            s, e = spec.start, spec.end
            if s.kind == "CURRENT_ROW":
                lo = lay.peer_start
            if e.kind == "UNBOUNDED_FOLLOWING":
                hi = lay.seg_end
            if s.kind == "UNBOUNDED_PRECEDING":
                lo = lay.seg_start
            if e.kind == "CURRENT_ROW":
                hi = lay.peer_end
            if s.kind in ("PRECEDING", "FOLLOWING") and s.offset is not None \
                    or e.kind in ("PRECEDING", "FOLLOWING") and e.offset is not None:
                # value-based offsets: per-segment binary search on the order key
                v = lay.order_values()
                if v is None:
                    raise NotImplementedError(
                        "RANGE offset frames need a single non-null numeric/datetime "
                        "ORDER BY key")
                if s.kind == "PRECEDING":
                    lo = _segmented_searchsorted(v, lay.seg_start, lay.seg_end,
                                                 v - s.offset, "left")
                elif s.kind == "FOLLOWING":
                    lo = _segmented_searchsorted(v, lay.seg_start, lay.seg_end,
                                                 v + s.offset, "left")
                if e.kind == "PRECEDING":
                    hi = _segmented_searchsorted(v, lay.seg_start, lay.seg_end,
                                                 v - e.offset, "right")
                elif e.kind == "FOLLOWING":
                    hi = _segmented_searchsorted(v, lay.seg_start, lay.seg_end,
                                                 v + e.offset, "right")
        return lo, hi
    # ROWS frames
    s, e = w.spec.start, w.spec.end
    if s.kind == "UNBOUNDED_PRECEDING":
        lo = lay.seg_start
    elif s.kind == "PRECEDING":
        lo = jnp.maximum(lay.seg_start, i - int(s.offset))
    elif s.kind == "CURRENT_ROW":
        lo = i
    elif s.kind == "FOLLOWING":
        lo = jnp.minimum(lay.seg_end, i + int(s.offset))
    else:
        lo = lay.seg_start
    if e.kind == "UNBOUNDED_FOLLOWING":
        hi = lay.seg_end
    elif e.kind == "FOLLOWING":
        hi = jnp.minimum(lay.seg_end, i + int(e.offset) + 1)
    elif e.kind == "CURRENT_ROW":
        hi = i + 1
    elif e.kind == "PRECEDING":
        hi = jnp.maximum(lay.seg_start, i - int(e.offset) + 1)
    else:
        hi = lay.seg_end
    return lo, hi


def _compute_window(w: WindowExpr, args: List[Column], lay: _SortedLayout) -> Column:
    n = lay.n
    if n == 0:
        return Column(jnp.zeros(0, dtype=sql_to_np(w.sql_type)), w.sql_type)
    i = jnp.arange(n, dtype=jnp.int64)
    func = w.func

    if func == "row_number":
        vals = i - lay.seg_start + 1
        data, _ = lay.scatter_back(vals)
        return Column(data.astype(jnp.int64), SqlType.BIGINT)
    if func == "rank":
        vals = lay.peer_start - lay.seg_start + 1
        data, _ = lay.scatter_back(vals)
        return Column(data.astype(jnp.int64), SqlType.BIGINT)
    if func == "dense_rank":
        np_int = lay.new_peer.astype(jnp.int64)
        c = jnp.cumsum(np_int)
        vals = c - c[lay.seg_start] + 1
        data, _ = lay.scatter_back(vals)
        return Column(data.astype(jnp.int64), SqlType.BIGINT)
    if func == "percent_rank":
        seg_len = lay.seg_end - lay.seg_start
        rank = lay.peer_start - lay.seg_start + 1
        vals = jnp.where(seg_len > 1, (rank - 1) / jnp.maximum(seg_len - 1, 1), 0.0)
        data, _ = lay.scatter_back(vals)
        return Column(data.astype(jnp.float64), SqlType.DOUBLE)
    if func == "cume_dist":
        seg_len = lay.seg_end - lay.seg_start
        vals = (lay.peer_end - lay.seg_start) / jnp.maximum(seg_len, 1)
        data, _ = lay.scatter_back(vals)
        return Column(data.astype(jnp.float64), SqlType.DOUBLE)
    if func == "ntile":
        k = int(np.asarray(args[0].data)[0]) if args else 1
        seg_len = lay.seg_end - lay.seg_start
        rn = i - lay.seg_start
        vals = jnp.minimum((rn * k) // jnp.maximum(seg_len, 1), k - 1) + 1
        data, _ = lay.scatter_back(vals)
        return Column(data.astype(jnp.int64), SqlType.BIGINT)
    if func in ("lag", "lead"):
        x = args[0]
        off = int(np.asarray(args[1].data)[0]) if len(args) > 1 else 1
        default = args[2] if len(args) > 2 else None
        xs = x.data[lay.perm]
        xv = x.valid_mask()[lay.perm]
        if w.ignore_nulls:
            # k-th previous/next VALID value: rank rows among valid ones
            P = jnp.cumsum(xv.astype(jnp.int64))  # valids among rows [0..i]
            valid_pos = jnp.nonzero(xv)[0]
            nvalid = int(valid_pos.shape[0])
            if func == "lag":
                rank = P - xv.astype(jnp.int64) - off  # 0-based among prior valids
            else:
                rank = P + off - 1  # 0-based among valids up to target
            ok = (rank >= 0) & (rank < nvalid)
            j = valid_pos[jnp.clip(rank, 0, max(nvalid - 1, 0))] if nvalid else jnp.zeros(n, dtype=jnp.int64)
            inside = ok & (j >= lay.seg_start) & (j < lay.seg_end)
        else:
            j = i - off if func == "lag" else i + off
            inside = (j >= lay.seg_start) & (j < lay.seg_end)
        j_safe = jnp.clip(j, 0, n - 1)
        vals = xs[j_safe]
        valid = xv[j_safe] & inside
        dictionary = x.dictionary
        if default is not None:
            dv = default.cast(x.sql_type)
            if dictionary is not None:
                # dv's codes index dv's OWN dictionary: translate into x's
                # space, extending it when the default value is new
                dictionary, dv = _remap_into_dictionary(dictionary, dv)
            ds = dv.data[lay.perm]
            vals = jnp.where(inside, vals, ds)
            valid = jnp.where(inside, valid, dv.valid_mask()[lay.perm])
        data, v = lay.scatter_back(vals, valid)
        return Column(data, w.sql_type, v, dictionary)

    # frame-based functions
    lo, hi = _frame_bounds(w, lay)
    if func in ("first_value", "last_value", "nth_value"):
        x = args[0]
        xs = x.data[lay.perm]
        xv = x.valid_mask()[lay.perm]
        if w.ignore_nulls and func in ("first_value", "last_value"):
            idx64 = jnp.arange(n, dtype=jnp.int64)
            if func == "first_value":
                # next valid index at-or-after each position (reverse cummin)
                marked = jnp.where(xv, idx64, n)
                nxt = jax.lax.cummin(marked[::-1])[::-1]
                j = nxt[jnp.clip(lo, 0, n - 1)]
            else:
                marked = jnp.where(xv, idx64, -1)
                prev = jax.lax.cummax(marked)
                j = prev[jnp.clip(hi - 1, 0, n - 1)]
        elif func == "first_value":
            j = lo
        elif func == "last_value":
            j = hi - 1
        else:
            if w.ignore_nulls:
                raise NotImplementedError("NTH_VALUE ... IGNORE NULLS is not supported")
            k = int(np.asarray(args[1].data)[0])
            j = lo + (k - 1)
        inside = (j >= lo) & (j < hi) & (hi > lo)
        j_safe = jnp.clip(j, 0, n - 1)
        vals = xs[j_safe]
        valid = xv[j_safe] & inside
        data, v = lay.scatter_back(vals, valid)
        return Column(data, w.sql_type, v, x.dictionary)

    if func == "count_star":
        vals = (hi - lo).astype(jnp.int64)
        data, _ = lay.scatter_back(vals)
        return Column(data, SqlType.BIGINT)

    x = args[0] if args else None
    xs = x.data[lay.perm] if x is not None else None
    xv = x.valid_mask()[lay.perm] if x is not None else None

    if func == "count":
        P = _prefix(xv.astype(jnp.int64))
        vals = P[hi] - P[lo]
        data, _ = lay.scatter_back(vals)
        return Column(data, SqlType.BIGINT)
    if func in ("sum", "avg"):
        acc = xs.astype(jnp.float64) if func == "avg" or xs.dtype.kind == "f" \
            else xs.astype(jnp.int64)
        acc = jnp.where(xv, acc, jnp.zeros_like(acc))
        P = _prefix(acc)
        s = P[hi] - P[lo]
        Pc = _prefix(xv.astype(jnp.int64))
        cnt = Pc[hi] - Pc[lo]
        if func == "avg":
            vals = s / jnp.maximum(cnt, 1)
        else:
            vals = s
        valid = cnt > 0
        data, v = lay.scatter_back(vals, valid)
        target = sql_to_np(w.sql_type)
        return Column(data.astype(target), w.sql_type, v)
    if func in ("min", "max"):
        big = _extreme_val(xs.dtype, func == "min")
        masked = jnp.where(xv, xs, big)
        # segmented running min/max handles prefix frames; bounded frames use
        # a log-shift sparse table (O(n log w)).  Prefix-ness is decided
        # STATICALLY from the frame spec — a device comparison here would be
        # a blocking host sync per query
        if _is_prefix_frame(w.spec):
            op = jnp.minimum if func == "min" else jnp.maximum
            run = _segmented_scan(masked, lay.new_seg, op)
            peer_adjusted = run[jnp.clip(hi - 1, 0, n - 1)]
            vals = peer_adjusted
        else:
            vals = _range_minmax(masked, lo, hi, func == "min")
        Pc = _prefix(xv.astype(jnp.int64))
        cnt = Pc[hi] - Pc[lo]
        valid = cnt > 0
        data, v = lay.scatter_back(vals, valid)
        return Column(data, w.sql_type, v, x.dictionary)
    if func in ("stddev_samp", "stddev_pop", "var_samp", "var_pop"):
        acc = jnp.where(xv, xs.astype(jnp.float64), 0.0)
        P1 = _prefix(acc)
        P2 = _prefix(acc * acc)
        Pc = _prefix(xv.astype(jnp.int64))
        cnt = Pc[hi] - Pc[lo]
        s1 = P1[hi] - P1[lo]
        s2 = P2[hi] - P2[lo]
        ddof = 1 if func.endswith("samp") else 0
        mean = s1 / jnp.maximum(cnt, 1)
        var = (s2 - cnt * mean * mean) / jnp.maximum(cnt - ddof, 1)
        var = jnp.maximum(var, 0.0)
        vals = jnp.sqrt(var) if func.startswith("stddev") else var
        valid = cnt > ddof
        data, v = lay.scatter_back(vals, valid)
        return Column(data, SqlType.DOUBLE, v)
    raise NotImplementedError(f"window function {func}")


def _remap_into_dictionary(base_dict, col: Column):
    """Translate `col`'s dictionary codes into `base_dict`'s code space,
    appending values base_dict lacks.  Returns (new_dict, remapped_col)."""
    src = np.asarray(col.dictionary if col.dictionary is not None
                     else np.array([], dtype=object), dtype=object)
    base = np.asarray(base_dict, dtype=object)
    pos = {str(v): i for i, v in enumerate(base)}
    extended = list(base)
    mapping = np.zeros(max(len(src), 1), dtype=np.int32)
    for i, v in enumerate(src):
        key = str(v)
        if key not in pos:
            pos[key] = len(extended)
            extended.append(v)
        mapping[i] = pos[key]
    codes = jnp.asarray(mapping)[jnp.clip(col.data, 0, max(len(src) - 1, 0))]
    return (np.asarray(extended, dtype=object),
            Column(codes, col.sql_type, col.validity,
                   np.asarray(extended, dtype=object)))


def _is_prefix_frame(spec) -> bool:
    """Frame always spans [segment start, current row/peer end): the shapes
    _frame_bounds emits lo = seg_start and hi = i+1 or peer_end for."""
    if not spec.explicit_frame:
        return True  # default frames are prefix frames either way
    s, e = spec.start, spec.end
    if s.kind != "UNBOUNDED_PRECEDING":
        return False
    if spec.units == "RANGE" or spec.order_by:
        return e.kind == "CURRENT_ROW" and e.offset is None
    return e.kind == "CURRENT_ROW"


def _extreme_val(dtype, for_min: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if for_min else -jnp.inf, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if for_min else info.min, dtype=dtype)


def _segmented_scan(vals, new_seg, op):
    """Running op within segments via associative scan with reset flags."""

    def combine(a, b):
        af, av = a
        bf, bv = b
        return (af | bf, jnp.where(bf, bv, op(av, bv)))

    flags, out = jax.lax.associative_scan(combine, (new_seg, vals))
    return out


def _range_minmax(masked, lo, hi, is_min: bool):
    """Sparse-table (doubling) range min/max query for arbitrary frames."""
    n = masked.shape[0]
    op = jnp.minimum if is_min else jnp.maximum
    big = _extreme_val(masked.dtype, is_min)
    levels = [masked]
    length = 1
    while length < n:
        prev = levels[-1]
        shifted = jnp.concatenate([prev[length:], jnp.full(min(length, n), big, dtype=prev.dtype)])
        levels.append(op(prev, shifted))
        length *= 2
    width = jnp.maximum(hi - lo, 1)
    k = jnp.floor(jnp.log2(width.astype(jnp.float64))).astype(jnp.int32)
    table = jnp.stack(levels)  # [levels, n]
    idx1 = jnp.clip(lo, 0, n - 1)
    idx2 = jnp.clip(hi - (1 << k.astype(jnp.int64)), 0, n - 1)
    a = table[k, idx1]
    b = table[k, idx2]
    return op(a, b)
