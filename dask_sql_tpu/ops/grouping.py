"""Group-id factorization and segment aggregation kernels.

TPU-first replacement for the reference's pandas `groupby().agg()` tree
(aggregate.py:575-581 there): keys are factorized to dense integer group ids
with a single device lexsort, and every aggregate lowers to an XLA segment
reduction (`jax.ops.segment_sum`/`_min`/`_max`) — embarrassingly parallel on
the VPU, and the same kernels serve as the partial-aggregation stage of the
distributed partial→final tree (see `parallel/collectives.py`).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.column import Column
from ..columnar.dtypes import STRING_TYPES, SqlType


def key_arrays(cols: Sequence[Column]) -> List[jnp.ndarray]:
    """Device sort/group keys for columns: ints stay, floats stay, strings use
    *sorted-dictionary* codes so code order == lexicographic order."""
    out = []
    for c in cols:
        if c.sql_type in STRING_TYPES:
            c = c.compact_dictionary()
            data = c.data
        elif c.data.dtype == jnp.bool_:
            data = c.data.astype(jnp.int32)
        else:
            data = c.data
        valid = None
        if c.validity is not None:
            valid = c.valid_mask()
        if jnp.issubdtype(data.dtype, jnp.floating):
            # unconditional: a content check would be a device round trip;
            # an all-true mask keys identically
            nan = jnp.isnan(data)
            valid = ~nan if valid is None else (valid & ~nan)
        if valid is not None:
            # NULL forms its own single group (dropna=False semantics,
            # reference aggregate.py:575-577): zero the payload under NULL and
            # key on validity so all NULLs collide
            data = jnp.where(valid, data, jnp.zeros_like(data))
            out.append(data)
            out.append(valid.astype(jnp.int32))
        else:
            out.append(data)
    return out


#: mixed-radix group-id domain gate shared by every radix planner
#: (CompiledAggregate, compiled-join _plan_radix, radix_gid) and the
#: static plan verifier (analysis/verifier.py) — one constant so the
#: bind-time verdict and the compile-time gate can never drift
RADIX_DOMAIN_LIMIT = 1 << 22

#: bytes of ``[domain]`` reduction state (8 a slot: the present indicator and
#: one per aggregate) that ONE integer group key's range may cost.  A product
#: of several keys' radices is mostly empty space and keeps the gate above; a
#: single key's range is what the table's own keys span (TPC-H's 6M orders
#: lie on a range of 24M), and what declines it is the memory of its state,
#: not the count of its values
ONE_KEY_STATE_BYTES = 1 << 30


def one_key_domain_limit(slots: int, config=None) -> int:
    """The widest range of a group-by on ONE integer key (PLAIN values, FOR
    codes) that the compiled rungs reduce into ``[domain]`` state of `slots`
    8-byte slots: `ONE_KEY_STATE_BYTES` of it, or `config`'s device budget
    (``analysis.estimate.device_budget_bytes``) below that; never under the
    mixed-radix gate.  Shared by `CompiledAggregate`, the join rung's radix
    plan and its semi-join build sides, and the estimator's bound."""
    from ..config import parse_byte_budget

    budget = parse_byte_budget(config.get(
        "analysis.estimate.device_budget_bytes")) if config is not None \
        else None
    room = min(budget or ONE_KEY_STATE_BYTES, ONE_KEY_STATE_BYTES)
    return max(RADIX_DOMAIN_LIMIT, room // (8 * max(int(slots), 1)))


def radix_gid(cols: Sequence[Column], max_domain: int = RADIX_DOMAIN_LIMIT):
    """Sort-free group ids for small-domain keys (dictionary codes / bools).

    When every key column is dictionary-encoded (or boolean), group ids are a
    mixed-radix combination of the codes — one fused multiply-add per column,
    no O(n log n) sort.  This is the hot path for TPC-H Q1-style aggregations.
    Returns (gid, domain, decode) or None when ineligible; `decode(gids)`
    maps group ids back to per-column Columns (for key materialization).
    """
    radices = []
    offsets = []
    # phase 1: classify columns, queueing every int key's min/max so ALL
    # bounds ride ONE device pull (phase 2) — one sync per node, not per key
    pending = []  # (slot, device min, device max)
    for c in cols:
        if c.sql_type in STRING_TYPES and c.dictionary is not None:
            radices.append(len(c.dictionary) + 1)  # +1 slot for NULL
            offsets.append(0)
        elif c.data.dtype == jnp.bool_:
            radices.append(3)
            offsets.append(0)
        elif jnp.issubdtype(c.data.dtype, jnp.integer) and len(c):
            pending.append((len(radices), jnp.min(c.data), jnp.max(c.data)))
            radices.append(None)
            offsets.append(None)
        else:
            return None
    spans = resolve_int_bounds(pending, max_domain)
    if spans is None:
        return None
    for slot, (span, lo) in spans.items():
        radices[slot] = span + 1
        offsets[slot] = lo
    domain = 1
    for r in radices:
        domain *= r
    if domain > max_domain:
        return None
    gid = None
    for c, r, off in zip(cols, radices, offsets):
        codes = c.data.astype(jnp.int64) - off
        codes = jnp.clip(codes, 0, r - 2)
        if c.validity is not None:
            codes = jnp.where(c.validity, codes, r - 1)  # NULL -> last slot
        gid = codes if gid is None else gid * r + codes

    def decode(gids: jnp.ndarray) -> List[Column]:
        out = []
        strides = []
        s = 1
        for r in reversed(radices):
            strides.append(s)
            s *= r
        strides = list(reversed(strides))
        # ONE device pull decides every column's NULL-group presence (a
        # per-column bool(any()) is a blocking device sync each)
        null_masks = [(gids // stride) % r == (r - 1)
                      for r, stride in zip(radices, strides)]
        if null_masks:
            from ..utils import host_ints

            flags = host_ints(*[m.any() for m in null_masks])
        for ci, (c, r, off, stride) in enumerate(zip(cols, radices, offsets,
                                                     strides)):
            code = (gids // stride) % r
            is_null = null_masks[ci]
            validity = ~is_null if bool(flags[ci]) else None
            code = jnp.minimum(code, r - 2)
            if c.sql_type in STRING_TYPES:
                out.append(Column(code.astype(jnp.int32), c.sql_type, validity,
                                  c.dictionary))
            elif c.data.dtype == jnp.bool_:
                out.append(Column(code == 1, c.sql_type, validity))
            else:
                out.append(Column((code + off).astype(c.data.dtype), c.sql_type,
                                  validity))
        return out

    return gid.astype(jnp.int32) if domain < 2**31 else gid, domain, decode


def resolve_int_bounds(pending, max_domain):
    """Batch-resolve queued (slot, device_min, device_max) integer-key
    bounds in ONE device pull.  {slot: (span, lo)}, or None when any span
    blows the domain gate.  Shared by the three radix planners so the
    gate/backfill logic cannot drift."""
    if not pending:
        return {}
    from ..utils import host_ints

    flat = host_ints(*[v for _, mn, mx in pending for v in (mn, mx)])
    out = {}
    for j, (slot, _, _) in enumerate(pending):
        lo, hi = flat[2 * j], flat[2 * j + 1]
        span = hi - lo + 1
        if span <= 0 or span > max_domain:
            return None
        out[slot] = (span, lo)
    return out


def factorize(keys: Sequence[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Dense group ids for multi-column keys.

    Returns (group_ids per row, sorted-order permutation, num_groups).
    Group ids number the distinct keys in ascending lexicographic order.
    """
    n = int(keys[0].shape[0])
    if n == 0:
        return jnp.zeros(0, dtype=jnp.int32), jnp.zeros(0, dtype=jnp.int32), 0
    order = jnp.lexsort(tuple(reversed([k for k in keys])))
    changed = jnp.zeros(n, dtype=bool).at[0].set(True)
    for k in keys:
        ks = k[order]
        changed = changed.at[1:].set(changed[1:] | (ks[1:] != ks[:-1]))
    gid_sorted = jnp.cumsum(changed.astype(jnp.int32)) - 1
    gid = jnp.zeros(n, dtype=jnp.int32).at[order].set(gid_sorted)
    num_groups = int(gid_sorted[-1]) + 1
    return gid, order, num_groups


def group_first_indices(gid: jnp.ndarray, num_groups: int) -> jnp.ndarray:
    """Row index of the first occurrence of each group (for key materialization)."""
    n = gid.shape[0]
    big = jnp.full(num_groups, n, dtype=jnp.int64)
    first = big.at[gid].min(jnp.arange(n, dtype=jnp.int64))
    return first


# ---------------------------------------------------------------------------
# Segment aggregation kernels.  All take (values, valid, gid, num_groups) and
# return (agg_values, agg_valid).  `valid` is a bool mask; aggregates skip
# NULLs per SQL semantics (reference sum min_count=1, aggregate.py:486-493).
# ---------------------------------------------------------------------------
def seg_count(valid: jnp.ndarray, gid: jnp.ndarray, num_groups: int) -> jnp.ndarray:
    return jax.ops.segment_sum(valid.astype(jnp.int64), gid, num_groups)


def seg_sum(values, valid, gid, num_groups):
    contrib = jnp.where(valid, values, jnp.zeros_like(values))
    s = jax.ops.segment_sum(contrib, gid, num_groups)
    cnt = seg_count(valid, gid, num_groups)
    return s, cnt > 0


def seg_min(values, valid, gid, num_groups):
    fill = _extreme(values.dtype, maximum=True)
    contrib = jnp.where(valid, values, fill)
    m = jax.ops.segment_min(contrib, gid, num_groups)
    cnt = seg_count(valid, gid, num_groups)
    return jnp.where(cnt > 0, m, jnp.zeros_like(m)), cnt > 0


def seg_max(values, valid, gid, num_groups):
    fill = _extreme(values.dtype, maximum=False)
    contrib = jnp.where(valid, values, fill)
    m = jax.ops.segment_max(contrib, gid, num_groups)
    cnt = seg_count(valid, gid, num_groups)
    return jnp.where(cnt > 0, m, jnp.zeros_like(m)), cnt > 0


def seg_avg(values, valid, gid, num_groups):
    s, _ = seg_sum(values.astype(jnp.float64), valid, gid, num_groups)
    cnt = seg_count(valid, gid, num_groups)
    return s / jnp.maximum(cnt, 1), cnt > 0


def seg_var(values, valid, gid, num_groups, ddof: int):
    """Variance via the (count, sum, sumsq) triple — the same shape as the
    reference's tree-aggregation triple (aggregate.py:117-160)."""
    x = values.astype(jnp.float64)
    s, _ = seg_sum(x, valid, gid, num_groups)
    s2, _ = seg_sum(x * x, valid, gid, num_groups)
    cnt = seg_count(valid, gid, num_groups)
    denom = jnp.maximum(cnt - ddof, 1)
    mean = s / jnp.maximum(cnt, 1)
    var = (s2 - cnt * mean * mean) / denom
    var = jnp.maximum(var, 0.0)
    return var, cnt > ddof


def seg_bool_and(values, valid, gid, num_groups):
    contrib = jnp.where(valid, values.astype(jnp.int32), 1)
    m = jax.ops.segment_min(contrib, gid, num_groups)
    cnt = seg_count(valid, gid, num_groups)
    return m.astype(bool), cnt > 0


def seg_bool_or(values, valid, gid, num_groups):
    contrib = jnp.where(valid, values.astype(jnp.int32), 0)
    m = jax.ops.segment_max(contrib, gid, num_groups)
    cnt = seg_count(valid, gid, num_groups)
    return m.astype(bool), cnt > 0


def seg_bitwise(values, valid, gid, num_groups, op: str):
    """bit_and/bit_or/bit_xor per group via per-bit segment reductions.

    64 segment reductions over the bit planes — rarely-used ops, so clarity
    beats peak efficiency here (reference ReduceAggregation parity).
    """
    x = values.astype(jnp.int64)
    nbits = 64
    bits = (x[:, None] >> jnp.arange(nbits, dtype=jnp.int64)[None, :]) & 1
    if op == "bit_and":
        contrib = jnp.where(valid[:, None], bits, 1)
        red = jax.ops.segment_min(contrib, gid, num_groups)
    elif op == "bit_or":
        contrib = jnp.where(valid[:, None], bits, 0)
        red = jax.ops.segment_max(contrib, gid, num_groups)
    else:  # bit_xor
        contrib = jnp.where(valid[:, None], bits, 0)
        red = jax.ops.segment_sum(contrib, gid, num_groups) & 1
    out = jnp.sum(red << jnp.arange(nbits, dtype=jnp.int64)[None, :], axis=1)
    cnt = seg_count(valid, gid, num_groups)
    return out, cnt > 0


def seg_first(values, valid, gid, num_groups):
    """Value at the smallest row index with a valid value per group."""
    n = values.shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    big = jnp.full(num_groups, n, dtype=jnp.int64)
    first = big.at[gid].min(jnp.where(valid, idx, n))
    cnt = seg_count(valid, gid, num_groups)
    safe = jnp.clip(first, 0, max(n - 1, 0))
    return values[safe], cnt > 0


def seg_last(values, valid, gid, num_groups):
    n = values.shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    small = jnp.full(num_groups, -1, dtype=jnp.int64)
    last = small.at[gid].max(jnp.where(valid, idx, -1))
    cnt = seg_count(valid, gid, num_groups)
    safe = jnp.clip(last, 0, max(n - 1, 0))
    return values[safe], cnt > 0


def seg_percentile(values, valid, gid, num_groups, q: float):
    """Exact per-group quantile: one lexsort by (group, validity, value), then
    a linear-interpolated pick at the group offset (PERCENTILE_CONT rule).
    TPU-shaped: sort + gathers, no per-group loops."""
    n = values.shape[0]
    if n == 0:
        return (jnp.zeros(num_groups, dtype=jnp.float64),
                jnp.zeros(num_groups, dtype=bool))
    x = values.astype(jnp.float64)
    x = jnp.where(valid, x, jnp.inf)  # invalid (and NaN-masked) sort last
    order = jnp.lexsort((x, (~valid).astype(jnp.int32), gid))
    sorted_gid = gid[order]
    sorted_val = x[order]
    idx = jnp.arange(n, dtype=jnp.int64)
    starts = jnp.full(num_groups, n, dtype=jnp.int64).at[sorted_gid].min(idx)
    cnt = seg_count(valid, gid, num_groups)
    k = jnp.maximum(cnt - 1, 0).astype(jnp.float64) * q
    lo = jnp.floor(k).astype(jnp.int64)
    hi = jnp.ceil(k).astype(jnp.int64)
    frac = k - lo
    safe = lambda i: jnp.clip(starts + i, 0, max(n - 1, 0))
    v = sorted_val[safe(lo)] * (1.0 - frac) + sorted_val[safe(hi)] * frac
    return v, cnt > 0


def _extreme(dtype, maximum: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if maximum else -jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.array(maximum, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if maximum else info.min, dtype=dtype)
