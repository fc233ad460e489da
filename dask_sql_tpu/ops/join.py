"""Equijoin kernels: sort + searchsorted, TPU-first.

Replaces the reference's dask hash-shuffle merge (join.py:241-246 there) for
the single-device path: both sides' keys are jointly factorized to dense ints
(`grouping.factorize` over the concatenation), the right side is sorted once,
and each left row finds its match range via two `searchsorted`s — O((n+m) log m)
in fully-vectorized XLA ops, no host hash tables.  Match expansion uses
data-dependent shapes (eager dispatch), which is fine outside jit; the
distributed path shuffles with collectives first (parallel/shuffle.py) and
then runs this same kernel per shard.

NULL semantics: SQL equijoin keys never match NULL (reference join.py:202-213
filters NULL keys); invalid rows get sentinel gids (-1 left, -2 right).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.column import Column
from ..columnar.dtypes import STRING_TYPES, promote
from .grouping import factorize
from ..utils import d2h_fetch, host_ints


def _merge_string_dicts(lcol: Column, rcol: Column) -> Tuple[jnp.ndarray, jnp.ndarray]:
    ld = lcol.dictionary if lcol.dictionary is not None else np.array([""], dtype=object)
    rd = rcol.dictionary if rcol.dictionary is not None else np.array([""], dtype=object)
    merged = np.unique(np.concatenate([ld.astype(str), rd.astype(str)]))
    lmap = jnp.asarray(np.searchsorted(merged, ld.astype(str)).astype(np.int32))
    rmap = jnp.asarray(np.searchsorted(merged, rd.astype(str)).astype(np.int32))
    lk = lmap[jnp.clip(lcol.data, 0, len(ld) - 1)]
    rk = rmap[jnp.clip(rcol.data, 0, len(rd) - 1)]
    return lk, rk


def join_key_gids(
    left_keys: Sequence[Column], right_keys: Sequence[Column],
    null_equals_null: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Jointly factorize both sides' key columns into comparable dense ints.

    `null_equals_null=True` gives IS NOT DISTINCT FROM matching (set ops);
    the default is SQL equijoin semantics where NULL matches nothing.
    """
    nl = len(left_keys[0]) if left_keys else 0
    nr = len(right_keys[0]) if right_keys else 0
    if len(left_keys) == 1 and not null_equals_null:
        fast = _single_key_fast_path(left_keys[0], right_keys[0])
        if fast is not None:
            return fast
    combined: List[jnp.ndarray] = []
    for lc, rc in zip(left_keys, right_keys):
        if lc.sql_type in STRING_TYPES or rc.sql_type in STRING_TYPES:
            lk, rk = _merge_string_dicts(lc, rc)
        else:
            target = promote(lc.sql_type, rc.sql_type)
            lk = lc.cast(target).data
            rk = rc.cast(target).data
        k = jnp.concatenate([lk, rk])
        if null_equals_null and (lc.validity is not None or rc.validity is not None):
            # NULL == NULL matching: validity becomes part of the key and the
            # payload is zeroed under NULL so all NULLs collide
            v = jnp.concatenate([lc.valid_mask(), rc.valid_mask()])
            combined.append(jnp.where(v, k, jnp.zeros_like(k)))
            combined.append(v.astype(jnp.int32))
        else:
            combined.append(k)
    gid, _, _ = factorize(combined)
    lgid, rgid = gid[:nl], gid[nl:]
    if null_equals_null:
        return lgid.astype(jnp.int64), rgid.astype(jnp.int64)
    # NULL keys never match
    lvalid = jnp.ones(nl, dtype=bool)
    for c in left_keys:
        if c.validity is not None:
            lvalid &= c.valid_mask()
    rvalid = jnp.ones(nr, dtype=bool)
    for c in right_keys:
        if c.validity is not None:
            rvalid &= c.valid_mask()
    lgid = jnp.where(lvalid, lgid, -1)
    rgid = jnp.where(rvalid, rgid, -2)
    return lgid.astype(jnp.int64), rgid.astype(jnp.int64)


def _single_key_fast_path(lc: Column, rc: Column):
    """Single integer/datetime key: the values themselves are the join ids —
    no joint factorization lexsort needed (the dominant cost for big probes).
    NULL sentinels use int64 extremes, which real key values never hit."""
    if lc.sql_type in STRING_TYPES or rc.sql_type in STRING_TYPES:
        lk, rk = _merge_string_dicts(lc, rc)
        lk = lk.astype(jnp.int64)
        rk = rk.astype(jnp.int64)
    else:
        target = promote(lc.sql_type, rc.sql_type)
        lk = lc.cast(target).data
        rk = rc.cast(target).data
        if not jnp.issubdtype(lk.dtype, jnp.integer):
            return None  # float keys keep the exact factorize path
        lk = lk.astype(jnp.int64)
        rk = rk.astype(jnp.int64)
    lo = jnp.iinfo(jnp.int64).min
    if lc.validity is not None or rc.validity is not None:
        # sentinel safety: real keys must not collide with the NULL
        # sentinels — both mins ride one device pull
        mins = host_ints(*([jnp.min(lk)] if lk.shape[0] else []),
                         *([jnp.min(rk)] if rk.shape[0] else []))
        if any(m <= lo + 1 for m in mins):
            return None
        if lc.validity is not None:
            lk = jnp.where(lc.valid_mask(), lk, lo)  # never matches rhs sentinel
        if rc.validity is not None:
            rk = jnp.where(rc.valid_mask(), rk, lo + 1)
    return lk, rk


# LUT join: cap the value range at a small multiple of the build side so the
# scatter table stays HBM-friendly (TPC-H orderkeys are 4x-sparse, hence 8x)
_DENSE_RANGE_SLACK = 8
_DENSE_RANGE_FLOOR = 1 << 16


def bucket_rows(n: int) -> int:
    """`n` rounded up to a multiple of 1/128 of the power of two at or above
    it, so by less than that 1/128 (under 1.6% of `n` just past a power of
    two, 0.49% at TPC-H SF10's table sizes): the length the join rung hands
    XLA for a build side of `n` rows or a key range of `n` values.  Two
    table versions whose sizes fall in one bucket then lower to the same
    program, and the second finds the first's executables in the
    persistent compile cache."""
    n = int(n)
    step = 1 << max(0, (n - 1).bit_length() - 7)
    return -(-n // step) * step


#: the shortest part `by_parts` loops over: shorter parts cost more in
#: the loop's steps than the gather's step saves
_PART_FLOOR = 1 << 13


def by_parts(f, x):
    """`f` mapped over `x` of a bucket's length (`bucket_rows`) in a loop
    of equal parts, the bucket's step long, where that step is
    `_PART_FLOOR` or more; else `f(x)`.  `f` is elementwise along `x`
    and gathers: on a TPU v5e a gather's time per index steps with the
    index count (7.7 / 8.1 / 8.6 / 9.7 ns), lengths on the buckets' grid
    sit on the slow step, and a part of 2^14 .. 2^18 reads 8.2."""
    n = int(x.shape[0])
    part = 1 << max(0, (n - 1).bit_length() - 7)
    if part < _PART_FLOOR or n % part:
        return f(x)
    return jax.lax.map(f, x.reshape(-1, part)).reshape(-1)


def pad_rows(x, rows: int):
    """Device array `x` lengthened to `rows` by repeats of its last row,
    copied through the host: an operation over `x` itself would compile once
    per length of `x`.  The repeats are real values on purpose: whoever
    reads a padded buffer masks the rows past its true count."""
    n = int(x.shape[0])
    if n == rows:
        return x
    with d2h_fetch(nbytes=int(x.nbytes)):
        host = np.asarray(jax.device_get(x))
    if n == 0:
        return jax.device_put(np.zeros(rows, dtype=host.dtype))
    return jax.device_put(np.pad(host, (0, rows - n), mode="edge"))


def _live(k, valid, n):
    """The rows of key column `k` that hold a key: the first `n`, not NULL."""
    live = jnp.arange(k.shape[0], dtype=jnp.int32) < n
    return live if valid is None else live & valid


@jax.jit
def _key_bounds(k, valid, n):
    k = k.astype(jnp.int64)
    live = _live(k, valid, n)
    return (jnp.min(jnp.where(live, k, jnp.iinfo(jnp.int64).max)),
            jnp.max(jnp.where(live, k, jnp.iinfo(jnp.int64).min)))


@functools.partial(jax.jit, static_argnames="slots")
def _scatter_lut(k, valid, n, rmin, slots: int):
    """(most rows on one key, ``int32[slots]`` LUT): each live row's index at
    its key's slot, -1 elsewhere; dead rows go out of bounds and drop."""
    idx = jnp.where(_live(k, valid, n), k.astype(jnp.int64) - rmin, slots)
    most = jnp.max(jnp.zeros(slots, dtype=jnp.int32).at[idx].add(
        1, mode="drop"))
    # row ids always fit int32 (single-shard row counts < 2^31); int64
    # gathers/compares are emulated on TPU
    lut = jnp.full(slots, -1, dtype=jnp.int32).at[idx].set(
        jnp.arange(k.shape[0], dtype=jnp.int32), mode="drop")
    return most, lut


def _dense_match(lgid, rgid):
    """Unique-dense-int build side: per-left-row (matched, right_idx) in
    O(n) scatter/gather, no sort.  None when ineligible.

    The reference leans on pandas' hash join (join.py:241-246 there); on
    XLA the natural analogue of a hash table is a value-indexed LUT — a
    single scatter + gather that the TPU does at HBM bandwidth, vs the
    O(n log n) argsort of the general probe.

    NULL sentinels need no special casing: the factorized-gid encoding uses
    -1 (left) / -2 (right) against non-negative real gids, so a NULL slot in
    the LUT can never be probed by a real key; the raw single-key encoding
    uses int64 extremes, which blow the range gate and fall back to the
    sort path (only when NULLs are actually present — see join_key_gids)."""
    if lgid.shape[0] == 0:
        return None
    prep = dense_unique_lut(rgid)
    if prep is None:
        return None
    rmin, lut = prep
    size = lut.shape[0]
    pidx = lgid - rmin
    inb = (pidx >= 0) & (pidx < size)
    ri_cand = jnp.where(inb, lut[jnp.clip(pidx, 0, size - 1)],
                        -1).astype(jnp.int64)
    return ri_cand >= 0, ri_cand


def dense_unique_lut(key: jnp.ndarray, valid=None,
                     max_bytes: Optional[int] = None,
                     rows: Optional[int] = None):
    """(rmin, lut) for a unique-int key column, or None if ineligible.

    lut[v - rmin] = row index holding key v, -1 where no row does; the
    table has `bucket_rows` of the key range's slots, the last ones -1.
    NULL rows (valid=False) never enter the table, nor rows past `rows`
    (the key's true row count where its buffers are padded; all of them
    by default).  Duplicate and non-integer keys decline.  How wide a key
    range may be depends on who pays:

    * ``max_bytes=None``: a table built for ONE query (the broadcast join,
      an eagerly executed build side) shares `_dense_match`'s density rule,
      a small multiple of the rows it was built from;
    * ``max_bytes=n``: a table built once per table version and kept (the
      compiled join pipeline's whole build sides) is admitted by what it
      costs to hold, 4 bytes a key of the range, whatever a later filter
      selects of its rows: TPC-H's order keys use 8 of every 32.

    One jitted bound pass and one jitted scatter over the buffers as they
    come: the true row count and `rmin` are operands, never constants."""
    nr = int(key.shape[0]) if rows is None else int(rows)
    if nr == 0 or not jnp.issubdtype(key.dtype, jnp.integer):
        return None
    rmin, rmax = host_ints(*_key_bounds(key, valid, nr))
    if rmin > rmax:
        return None  # all NULL
    size = rmax - rmin + 1
    widest = max(_DENSE_RANGE_SLACK * nr, _DENSE_RANGE_FLOOR) \
        if max_bytes is None else int(max_bytes) // 4
    if size > widest:
        return None
    most, lut = _scatter_lut(key, valid, nr, rmin, slots=bucket_rows(size))
    if host_ints(most)[0] > 1:
        return None
    return rmin, lut


#: the most rows one leading key value may hold in `composite_slots`
COMPOSITE_MAX_RUN = 16


def composite_slots(keys, max_bytes: int) -> Optional[dict]:
    """The slots of a build side joined on a TWO-column key whose pair is
    unique though neither column is (TPC-H's PARTSUPP: four suppliers a
    part), or None where the side declines.

    `keys`: the side's two key columns on the host, ``[(int64 data, valid
    or None)]``.  One column leads: each of its values owns ``run`` slots
    in a row, ``run`` the most rows any value holds rounded up to a power
    of two (4 for PARTSUPP by part key; whichever column gives the shorter
    run leads).  A row takes the slot of its leading value and its rank
    among that value's rows, so the probe finds its row among ``run``
    candidates at ``(lead - lo) * run + i`` by comparing the other column,
    with no sort and no shape that depends on the data.  Returns ``{"lead":
    which column leads, "run", "lo", "hi": the leading column's range,
    "lo2", "hi2": the other's, "second": int32[slots], the other column
    less ``lo2`` per slot (-1: no row), "rows": int32[slots], the row in
    each slot (row 0 where none: `second` masks it), "slots"}``, the slots
    `bucket_rows` of the leading range times ``run``.  A NULL key leaves
    its row out.  Declines: no row, a duplicate pair, a run past
    `COMPOSITE_MAX_RUN`, slots past `max_bytes` at 4 bytes each, a second
    range past int32."""
    live = np.ones(len(keys[0][0]), dtype=bool)
    for _, valid in keys:
        if valid is not None:
            live &= np.asarray(valid, dtype=bool)
    rows = np.flatnonzero(live)
    if not len(rows):
        return None
    cols = [np.asarray(data, dtype=np.int64)[rows] for data, _ in keys]
    best = None
    for lead in (0, 1):
        a = cols[lead]
        lo, hi = int(a.min()), int(a.max())
        span = hi - lo + 1
        if bucket_rows(span) * 4 > max_bytes:
            continue
        most = int(np.unique(a, return_counts=True)[1].max())
        run = 1 << (most - 1).bit_length()
        slots = bucket_rows(span) * run
        if run <= COMPOSITE_MAX_RUN and slots * 4 <= max_bytes \
                and (best is None or (run, slots) < best[:2]):
            best = (run, slots, lead, lo, hi)
    if best is None:
        return None
    run, slots, lead, lo, hi = best
    a, b = cols[lead], cols[1 - lead]
    order = np.lexsort((b, a))
    a, b, rows = a[order], b[order], rows[order]
    if np.any((a[1:] == a[:-1]) & (b[1:] == b[:-1])):
        return None
    lo2, hi2 = int(b.min()), int(b.max())
    if hi2 - lo2 >= np.iinfo(np.int32).max:
        return None
    starts = np.flatnonzero(np.concatenate([[True], a[1:] != a[:-1]]))
    rank = np.arange(len(a)) - np.repeat(starts,
                                         np.diff(np.append(starts, len(a))))
    at = (a - lo) * run + rank
    second = np.full(slots, -1, dtype=np.int32)
    second[at] = b - lo2
    row_of = np.zeros(slots, dtype=np.int32)
    row_of[at] = rows
    return {"lead": lead, "run": run, "lo": lo, "hi": hi, "lo2": lo2,
            "hi2": hi2, "second": second, "rows": row_of, "slots": slots}


def inner_join_indices(lgid: jnp.ndarray, rgid: jnp.ndarray,
                       use_jit: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(left_idx, right_idx) pairs of matches, left-major order."""
    dense = _dense_match(lgid, rgid)
    if dense is not None:
        matched, ri_cand = dense
        li = jnp.nonzero(matched)[0].astype(jnp.int64)
        return li, ri_cand[li]
    li, ri, _ = _probe(lgid, rgid, use_jit)
    return li, ri


def left_join_indices(lgid, rgid, use_jit: bool = False):
    """Left outer: unmatched left rows appear once with right_idx == -1."""
    dense = _dense_match(lgid, rgid)
    if dense is not None:
        # unique build keys: every left row appears exactly once
        matched, ri_cand = dense
        li = jnp.arange(lgid.shape[0], dtype=jnp.int64)
        return li, jnp.where(matched, ri_cand, -1)
    phase = _probe_phase_jit if use_jit else _probe_phase
    r_order, start, counts, _, _ = phase(lgid, rgid)
    out_counts = jnp.maximum(counts, 1)
    total = int(out_counts.sum())
    offsets = jnp.cumsum(out_counts) - out_counts  # exclusive prefix
    li = jnp.repeat(jnp.arange(lgid.shape[0], dtype=jnp.int64), out_counts,
                    total_repeat_length=total)
    pos_in_row = jnp.arange(total, dtype=jnp.int64) - offsets[li]
    matched = counts[li] > 0
    ri_raw = r_order[jnp.clip(start[li] + pos_in_row, 0, max(rgid.shape[0] - 1, 0))]
    ri = jnp.where(matched, ri_raw, -1)
    return li, ri


def semi_join_mask(lgid, rgid, anti: bool = False) -> jnp.ndarray:
    dense = _dense_match(lgid, rgid)
    if dense is not None:
        matched, _ = dense
        return ~matched if anti else matched
    r_sorted = jnp.sort(rgid)
    start = jnp.searchsorted(r_sorted, lgid, side="left")
    end = jnp.searchsorted(r_sorted, lgid, side="right")
    matched = (end - start) > 0
    return ~matched if anti else matched


def full_join_indices(lgid, rgid, use_jit: bool = False):
    li, ri = left_join_indices(lgid, rgid, use_jit)
    r_unmatched = ~semi_join_mask(rgid, lgid)
    extra_r = jnp.nonzero(r_unmatched)[0].astype(jnp.int64)
    li = jnp.concatenate([li, jnp.full(extra_r.shape[0], -1, dtype=jnp.int64)])
    ri = jnp.concatenate([ri, extra_r])
    return li, ri


def _probe_phase(lgid, rgid):
    """Shape-stable probe phase: sort, two binary searches, prefix sums.

    Everything up to the data-dependent expansion is static-shaped, so the
    jitted variant compiles once per (n_l, n_r) signature — removing per-op
    dispatch round trips, which dominate when the device sits behind a link
    (TPU).  Selected via `sql.compile.join`.
    """
    r_order = jnp.argsort(rgid)
    r_sorted = rgid[r_order]
    start = jnp.searchsorted(r_sorted, lgid, side="left")
    end = jnp.searchsorted(r_sorted, lgid, side="right")
    counts = end - start
    offsets = jnp.cumsum(counts) - counts
    total = counts.sum()
    return r_order, start, counts, offsets, total


_probe_phase_jit = jax.jit(_probe_phase)


def _probe(lgid, rgid, use_jit: bool = False):
    phase = _probe_phase_jit if use_jit else _probe_phase
    r_order, start, counts, offsets, total_arr = phase(lgid, rgid)
    total = int(total_arr)
    li = jnp.repeat(jnp.arange(lgid.shape[0], dtype=jnp.int64), counts,
                    total_repeat_length=total)
    pos_in_row = jnp.arange(total, dtype=jnp.int64) - offsets[li]
    ri = r_order[start[li] + pos_in_row]
    return li, ri, counts


def take_with_nulls(col: Column, indices: jnp.ndarray,
                    may_pad: Optional[bool] = None) -> Column:
    """Gather rows; index -1 produces NULL (outer-join fill).

    `may_pad` tells the gather statically whether -1 fills can occur
    (False for inner/semi matches, True for outer padding) — without it a
    per-column content check costs a device round trip per column."""
    n = len(col)
    if n == 0:
        # empty source: every index is the -1 fill (outer join against an
        # empty side, TPC-DS q77) — an all-NULL column of the output length
        m = int(indices.shape[0])
        return Column(jnp.zeros(m, dtype=col.data.dtype), col.sql_type,
                      jnp.zeros(m, dtype=bool), col.dictionary)
    neg = indices < 0
    if may_pad is False and __debug__:
        # contract check: may_pad=False promises no -1 fills, and a violation
        # silently materializes clamped garbage rows marked valid.  The
        # device sync is only paid when the validation flag is on.
        from .. import config as config_module

        if config_module.get("sql.debug.validate_take", False):
            assert not bool(neg.any()), (
                "take_with_nulls(may_pad=False) received negative indices; "
                "the calling join type must pass may_pad=True")
    safe = jnp.clip(indices, 0, max(n - 1, 0))
    data = col.data[safe]
    if may_pad is None:
        may_pad = bool(neg.any())
    if not may_pad and col.validity is None:
        return Column(data, col.sql_type, None, col.dictionary)
    valid = col.valid_mask()[safe] & ~neg
    return Column(data, col.sql_type, valid, col.dictionary)
