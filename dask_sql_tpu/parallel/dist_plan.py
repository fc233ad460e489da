"""Collectives-routed distributed execution of Aggregate and Join.

Round-1 ran sharded-table SQL on GSPMD auto-layout of the eager kernels
(implicit all-gathers).  This module is the round-2 engine path: when a plan
node's input bottoms out in a mesh-sharded table, Aggregate and Join lower to
purpose-built `shard_map` kernels that communicate ONLY through explicit XLA
collectives (`all_to_all`), with static capacity-bounded shapes and a
capacity-ladder retry on overflow.

Role parity (reference):
- Aggregate: dask's partial->shuffle->final tree with split_out
  (`/root/reference/dask_sql/physical/rel/logical/aggregate.py:321`) — here a
  local segment pre-aggregation per shard, an `all_to_all` key-routed exchange
  of the bounded partial-group tables, and an owner-side combine.
- Join: dask's tasks-shuffle merge
  (`/root/reference/dask_sql/physical/rel/logical/join.py:241-246`) — here an
  `all_to_all` hash shuffle of (gid, row-id) pairs for both sides and a local
  sort/searchsorted probe per device, materializing (left, right) global row
  index pairs (full row output, not counts).

Aggregation state layout per value column (chunk/agg/finalize triples like the
reference's AGGREGATION_MAPPING, aggregate.py:117-231 there):
  int64 states  (isum, imin, imax)  — exact for BIGINT/timestamps/dict codes
  float64 states (cnt, fsum, fsumsq) — for avg/var/stddev and float sums
Floats are carried through imin/imax via an order-preserving int64 bit trick
so min/max stay exact for every dtype.
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..columnar.column import Column
from ..columnar.dtypes import SqlType, STRING_TYPES, sql_to_np
from ..resilience.errors import ResourceExhaustedError
from .bootstrap import host_read
from .mesh import AXIS, default_mesh, pad_to_multiple, row_sharding

logger = logging.getLogger(__name__)

I64 = jnp.int64
I64_MIN = np.iinfo(np.int64).min
I64_MAX = np.iinfo(np.int64).max

#: capacity ladders (compile-cache friendly: powers of 4)
GROUP_CAPACITY_LADDER = (1024, 16384, 262144, 1 << 22)
PEER_CAPACITY_LADDER = (2048, 16384, 131072, 1 << 20, 1 << 23)

#: test/observability hooks: counts of kernel executions this process.
#: Fallback/degradation events are NOT counted here anymore — they go to the
#: per-context MetricsRegistry as ``resilience.fallback.*`` so SHOW METRICS
#: and /v1/metrics see them (the old ad-hoc "agg_fallback" key is retained
#: at 0 for callers that snapshot the dict).
STATS = {"agg_kernel": 0, "join_kernel": 0, "agg_fallback": 0,
         "broadcast_join": 0, "broadcast_join_sorted": 0,
         "sharded_join_agg": 0, "sort_kernel": 0}


# ---------------------------------------------------------------------------
# sharding predicates
# ---------------------------------------------------------------------------
def array_is_sharded(arr) -> bool:
    sh = getattr(arr, "sharding", None)
    if sh is None or not isinstance(sh, NamedSharding):
        return False
    try:
        return len(sh.device_set) > 1 and not sh.is_fully_replicated
    except Exception as e:  # dsql: allow-broad-except — deleted buffer /
        # backend teardown mid-query; metric-counted fallback below
        # treated as unsharded (single-program path still computes the right
        # answer) — but say so instead of silently swallowing the probe
        logger.debug("sharding probe failed on %r: %s; treating as "
                     "unsharded", type(arr).__name__, e)
        return False


def table_is_sharded(table) -> bool:
    return any(array_is_sharded(c.data) for c in table.columns.values())


def mesh_for_table(table) -> Optional[Mesh]:
    for c in table.columns.values():
        sh = getattr(c.data, "sharding", None)
        if isinstance(sh, NamedSharding) and len(sh.device_set) > 1:
            return sh.mesh
    return None


def _mode(executor, key: str) -> str:
    return str(executor.config.get(key, "auto")).lower()


def plan_has_sharded_scan(plan, context) -> bool:
    """Cheap pre-check: does this subtree scan a mesh-sharded table?
    (Never touches lazy parquet containers, so no accidental loads.)"""
    from ..datacontainer import LazyParquetContainer
    from ..planner import plan as p

    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, p.TableScan):
            schema = context.schema.get(node.schema_name)
            dc = schema.tables.get(node.table_name) if schema else None
            if dc is not None and not isinstance(dc, LazyParquetContainer):
                if table_is_sharded(dc.table):
                    return True
        stack.extend(node.inputs())
    return False


def should_distribute(executor, key: str, *tables) -> Optional[Mesh]:
    """Return the mesh to use, or None to keep the single-program path."""
    mode = _mode(executor, key)
    if mode in ("off", "false", "0"):
        return None
    for t in tables:
        m = mesh_for_table(t)
        if m is not None and m.devices.size > 1:
            return m
    if mode in ("on", "force", "true", "1"):
        m = default_mesh()
        return m if m.devices.size > 1 else None
    return None


# ---------------------------------------------------------------------------
# host-side encoding: Column -> int64 key/value arrays (stays sharded; the
# transforms are elementwise so GSPMD keeps the row layout)
# ---------------------------------------------------------------------------
def _float_to_ordered_i64(x: jnp.ndarray) -> jnp.ndarray:
    """Monotone float64 -> int64 (IEEE bit trick); NaNs must be pre-masked."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float64), jnp.int64)
    return jnp.where(b >= 0, b, I64_MAX - b)


def _ordered_i64_to_float(o: np.ndarray) -> np.ndarray:
    b = np.where(o >= 0, o, I64_MAX - o).astype(np.int64)
    return b.view(np.float64)


def encode_key_column(col: Column) -> Tuple[List[jnp.ndarray], dict]:
    """Encode a group-key column into int64 key arrays + decode info.

    NULL keys form their own group (dropna=False parity): nullable columns
    contribute an extra null-flag key array.
    """
    info = {"sql_type": col.sql_type, "dictionary": col.dictionary,
            "float": False, "nullable": col.validity is not None}
    data = col.data
    if data.dtype == jnp.bool_:
        enc = data.astype(I64)
    elif jnp.issubdtype(data.dtype, jnp.floating):
        clean = jnp.where(jnp.isnan(data), 0.0, data)
        clean = jnp.where(clean == 0.0, 0.0, clean)  # -0.0 == 0.0 for grouping
        enc = _float_to_ordered_i64(clean)
        info["float"] = True
    else:
        enc = data.astype(I64)
    arrays = []
    if col.validity is not None:
        null = ~col.valid_mask()
        enc = jnp.where(null, 0, enc)
        arrays.append(null.astype(I64))
    arrays.append(enc)
    return arrays, info


def decode_key_outputs(key_arrays: List[np.ndarray], infos: List[dict]) -> List[Column]:
    """Rebuild group-key Columns from the kernel's int64 key outputs."""
    cols = []
    i = 0
    for info in infos:
        if info["nullable"]:
            null = key_arrays[i].astype(bool)
            i += 1
        else:
            null = None
        raw = key_arrays[i]
        i += 1
        st = info["sql_type"]
        if info["float"]:
            data = _ordered_i64_to_float(raw)
        elif st in STRING_TYPES:
            data = raw.astype(np.int32)
        elif st == SqlType.BOOLEAN:
            data = raw.astype(bool)
        else:
            data = raw.astype(sql_to_np(st))
        validity = None if null is None or not null.any() else jnp.asarray(~null)
        cols.append(Column(jnp.asarray(data), st, validity, info["dictionary"]))
    return cols


def encode_value_column(col: Optional[Column]) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Encode an aggregate input column -> (ivals, fvals, info)."""
    if col is None:  # count_star: constant 1
        raise ValueError("encode_value_column requires a column")
    info = {"sql_type": col.sql_type, "dictionary": col.dictionary, "float": False}
    data = col.data
    if data.dtype == jnp.bool_:
        iv = data.astype(I64)
        fv = data.astype(jnp.float64)
    elif jnp.issubdtype(data.dtype, jnp.floating):
        clean = jnp.where(jnp.isnan(data), 0.0, data.astype(jnp.float64))
        iv = _float_to_ordered_i64(clean)
        fv = clean
        info["float"] = True
    else:
        iv = data.astype(I64)
        fv = data.astype(jnp.float64)
    return iv, fv, info


# ---------------------------------------------------------------------------
# jit building blocks (all static shapes; run inside shard_map)
# ---------------------------------------------------------------------------
def _mix(h: jnp.ndarray) -> jnp.ndarray:
    """splitmix64-style finalizer on int64 (wrapping arithmetic)."""
    h = h * jnp.int64(-7046029254386353131)  # 0x9E3779B97F4A7C15 as signed
    h = h ^ (h >> 33)
    h = h * jnp.int64(-4417276706812531889)  # 0xC2B2AE3D27D4EB4F
    h = h ^ (h >> 29)
    return h


def _hash_keys(keys: Sequence[jnp.ndarray]) -> jnp.ndarray:
    h = jnp.zeros_like(keys[0])
    for k in keys:
        h = _mix(h + k)
    return h


def _lex_groups(keys: Sequence[jnp.ndarray], valid: jnp.ndarray, capacity: int):
    """Sort rows by key tuple (invalid rows last) and produce segment ids.

    Returns (order, seg, sorted_valid, uniq_keys, uniq_valid, overflow).
    """
    n = valid.shape[0]
    inv = (~valid).astype(jnp.int32)
    iota = jnp.arange(n, dtype=I64)
    ops = (inv,) + tuple(keys) + (iota,)
    sorted_ops = jax.lax.sort(ops, num_keys=1 + len(keys))
    order = sorted_ops[-1]
    ks = sorted_ops[1:1 + len(keys)]
    vs = valid[order]
    diff = jnp.zeros(n - 1, dtype=bool) if n > 1 else jnp.zeros(0, dtype=bool)
    for k in ks:
        diff = diff | (k[1:] != k[:-1])
    changed = jnp.concatenate([vs[:1], diff & vs[1:]])
    seg_raw = jnp.cumsum(changed.astype(jnp.int32)) - 1
    n_groups = jnp.max(jnp.where(vs, seg_raw + 1, 0), initial=0)
    overflow = n_groups > capacity
    seg = jnp.where(vs, jnp.clip(seg_raw, 0, capacity - 1), capacity - 1)
    uniq_keys = []
    for k in ks:
        uk = jnp.full((capacity,), I64_MIN, dtype=I64).at[seg].max(
            jnp.where(vs, k, I64_MIN))
        uniq_keys.append(uk)
    uniq_valid = jnp.zeros((capacity,), dtype=bool).at[seg].max(vs)
    # a real group parked in the overflow slot would alias invalid rows;
    # overflow is flagged anyway, so the caller retries with more capacity
    return order, seg, vs, uniq_keys, uniq_valid, overflow


def _bucket_rows(dest: jnp.ndarray, valid: jnp.ndarray, iblock: jnp.ndarray,
                 fblock: jnp.ndarray, ndev: int, C: int):
    """Counting-sort rows into [ndev, C] per-peer buckets for all_to_all.

    iblock [n, ni] int64, fblock [n, nf] float64.  Returns bucketed
    (ikeys [ndev, C, ni], fvals [ndev, C, nf], bvalid [ndev, C], overflow).
    """
    n = dest.shape[0]
    d = jnp.where(valid, dest, ndev).astype(jnp.int32)
    iota = jnp.arange(n, dtype=I64)
    ds, order = jax.lax.sort((d, iota), num_keys=1)
    vs = valid[order]
    ib = iblock[order]
    fb = fblock[order]
    idx = jnp.arange(n)
    start_of_dest = jnp.searchsorted(ds, jnp.arange(ndev + 1, dtype=jnp.int32))
    pos = idx - start_of_dest[jnp.clip(ds, 0, ndev)]
    overflow = jnp.any((pos >= C) & vs)
    ok = vs & (pos < C)
    # rows that don't land (invalid or over-capacity) scatter out-of-bounds so
    # mode="drop" discards the write — a clipped index would nondeterministically
    # clobber a real slot
    flat = jnp.where(ok, ds.astype(I64) * C + pos, ndev * C)
    bi = jnp.zeros((ndev * C, ib.shape[1]), dtype=I64).at[flat].set(
        ib, mode="drop")
    bf = jnp.zeros((ndev * C, fb.shape[1]), dtype=jnp.float64).at[flat].set(
        fb, mode="drop")
    bv = jnp.zeros((ndev * C,), dtype=bool).at[flat].set(ok, mode="drop")
    return (bi.reshape(ndev, C, ib.shape[1]), bf.reshape(ndev, C, fb.shape[1]),
            bv.reshape(ndev, C), overflow)


def _exchange(bi, bf, bv):
    """The collective: per-peer buckets <-> devices over ICI/DCN."""
    ndev, C = bv.shape
    ri = jax.lax.all_to_all(bi[None], AXIS, split_axis=1, concat_axis=1)[0]
    rf = jax.lax.all_to_all(bf[None], AXIS, split_axis=1, concat_axis=1)[0]
    rv = jax.lax.all_to_all(bv[None], AXIS, split_axis=1, concat_axis=1)[0]
    return (ri.reshape(ndev * C, bi.shape[-1]),
            rf.reshape(ndev * C, bf.shape[-1]), rv.reshape(ndev * C))


# ---------------------------------------------------------------------------
# distributed groupby-aggregate kernel
# ---------------------------------------------------------------------------
_AGG_KERNELS: Dict[tuple, object] = {}

N_ISTATE = 3  # isum, imin, imax
N_FSTATE = 3  # cnt, fsum, fsumsq


def _local_states(seg, order, vs, ivals, fvals, vvalid, capacity: int):
    """Per value column: segment-reduce the 6 states over sorted rows."""
    istates, fstates = [], []
    for j in range(ivals.shape[0]):
        w = vvalid[j][order] & vs
        iv = ivals[j][order]
        fv = fvals[j][order]
        isum = jnp.zeros((capacity,), I64).at[seg].add(jnp.where(w, iv, 0))
        imin = jnp.full((capacity,), I64_MAX, I64).at[seg].min(
            jnp.where(w, iv, I64_MAX))
        imax = jnp.full((capacity,), I64_MIN, I64).at[seg].max(
            jnp.where(w, iv, I64_MIN))
        cnt = jnp.zeros((capacity,), jnp.float64).at[seg].add(
            w.astype(jnp.float64))
        fsum = jnp.zeros((capacity,), jnp.float64).at[seg].add(
            jnp.where(w, fv, 0.0))
        fsq = jnp.zeros((capacity,), jnp.float64).at[seg].add(
            jnp.where(w, fv * fv, 0.0))
        istates.append(jnp.stack([isum, imin, imax], axis=-1))
        fstates.append(jnp.stack([cnt, fsum, fsq], axis=-1))
    return jnp.stack(istates), jnp.stack(fstates)  # [nv, capacity, 3]


def _combine_states(seg, order, vs, istates, fstates, capacity: int):
    """Merge received partial states by group (the `agg` stage)."""
    nv = istates.shape[0]
    iout, fout = [], []
    for j in range(nv):
        ist = istates[j][order]
        fst = fstates[j][order]
        isum = jnp.zeros((capacity,), I64).at[seg].add(
            jnp.where(vs, ist[:, 0], 0))
        imin = jnp.full((capacity,), I64_MAX, I64).at[seg].min(
            jnp.where(vs, ist[:, 1], I64_MAX))
        imax = jnp.full((capacity,), I64_MIN, I64).at[seg].max(
            jnp.where(vs, ist[:, 2], I64_MIN))
        cnt = jnp.zeros((capacity,), jnp.float64).at[seg].add(
            jnp.where(vs, fst[:, 0], 0.0))
        fsum = jnp.zeros((capacity,), jnp.float64).at[seg].add(
            jnp.where(vs, fst[:, 1], 0.0))
        fsq = jnp.zeros((capacity,), jnp.float64).at[seg].add(
            jnp.where(vs, fst[:, 2], 0.0))
        iout.append(jnp.stack([isum, imin, imax], axis=-1))
        fout.append(jnp.stack([cnt, fsum, fsq], axis=-1))
    return jnp.stack(iout), jnp.stack(fout)


def get_agg_kernel(mesh: Mesh, nk: int, nv: int, capacity: int, cpeer: int):
    key = (tuple(d.id for d in mesh.devices.flat), nk, nv, capacity, cpeer)
    fn = _AGG_KERNELS.get(key)
    if fn is not None:
        return fn
    ndev = mesh.devices.size

    def per_shard(keys, ivals, fvals, vvalid, rowvalid):
        # keys [nk, n]; ivals/fvals [nv, n]; vvalid [nv, n]; rowvalid [n]
        keys = [keys[i] for i in range(nk)]
        # 1. local pre-aggregation (`chunk`)
        order, seg, vs, uk, uv, of1 = _lex_groups(keys, rowvalid, capacity)
        istates, fstates = _local_states(seg, order, vs, ivals, fvals,
                                         vvalid, capacity)
        # 2. route each partial group row to its owner via all_to_all
        dest = jnp.mod(_hash_keys(uk), ndev)
        iblock = jnp.concatenate(
            [jnp.stack(uk, axis=-1)] +
            [istates[j] for j in range(nv)], axis=-1)  # [cap, nk + nv*3]
        fblock = jnp.concatenate(
            [fstates[j] for j in range(nv)], axis=-1) if nv else \
            jnp.zeros((capacity, 0), jnp.float64)
        bi, bf, bv, of2 = _bucket_rows(dest, uv, iblock, fblock, ndev, cpeer)
        ri, rf, rv = _exchange(bi, bf, bv)
        # 3. owner-side combine (`agg`)
        rkeys = [ri[:, i] for i in range(nk)]
        rist = jnp.stack([ri[:, nk + j * N_ISTATE: nk + (j + 1) * N_ISTATE]
                          for j in range(nv)]) if nv else \
            jnp.zeros((0, ri.shape[0], N_ISTATE), I64)
        rfst = jnp.stack([rf[:, j * N_FSTATE:(j + 1) * N_FSTATE]
                          for j in range(nv)]) if nv else \
            jnp.zeros((0, rf.shape[0], N_FSTATE), jnp.float64)
        order2, seg2, vs2, fk, fv_, of3 = _lex_groups(rkeys, rv, capacity)
        iout, fout = _combine_states(seg2, order2, vs2, rist, rfst, capacity)
        overflow = of1 | of2 | of3
        return (jnp.stack(fk)[None], fv_[None], iout[None], fout[None],
                overflow[None])

    mapped = shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(None, AXIS), P(None, AXIS), P(None, AXIS), P(None, AXIS),
                  P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
    )
    fn = jax.jit(mapped)
    _AGG_KERNELS[key] = fn
    return fn


# ---------------------------------------------------------------------------
# distributed join kernel
# ---------------------------------------------------------------------------
_JOIN_KERNELS: Dict[tuple, object] = {}


def get_join_kernel(mesh: Mesh, cpeer: int, out_cap: int):
    key = (tuple(d.id for d in mesh.devices.flat), cpeer, out_cap)
    fn = _JOIN_KERNELS.get(key)
    if fn is not None:
        return fn
    ndev = mesh.devices.size

    def shuffle_side(gid, idx, valid):
        dest = jnp.mod(_mix(gid), ndev)
        iblock = jnp.stack([gid, idx], axis=-1)
        fblock = jnp.zeros((gid.shape[0], 0), jnp.float64)
        bi, bf, bv, of = _bucket_rows(dest, valid, iblock, fblock, ndev, cpeer)
        ri, _, rv = _exchange(bi, bf, bv)
        return ri[:, 0], ri[:, 1], rv, of

    def per_shard(lgid, lidx, lvalid, rgid, ridx, rvalid):
        lk, li_orig, lv, of1 = shuffle_side(lgid, lidx, lvalid)
        rk, ri_orig, rv, of2 = shuffle_side(rgid, ridx, rvalid)
        nrecv = rk.shape[0]
        # local probe: sort right, binary-search left.  Empty right slots get
        # the I64_MIN sentinel (real gids are >= 0 for factorized keys and
        # > I64_MIN+1 for the raw fast path, join_ops._single_key_fast_path)
        rk_s = jnp.where(rv, rk, I64_MIN)
        iota = jnp.arange(nrecv, dtype=I64)
        rs, r_order = jax.lax.sort((rk_s, iota), num_keys=1)
        lk_s = jnp.where(lv, lk, I64_MIN + 1)  # counts also masked by lv
        start = jnp.searchsorted(rs, lk_s, side="left")
        end = jnp.searchsorted(rs, lk_s, side="right")
        counts = jnp.where(lv, end - start, 0)
        ends = jnp.cumsum(counts)
        total = ends[-1] if nrecv else jnp.int64(0)
        # static-shape pair expansion into out_cap slots
        t = jnp.arange(out_cap, dtype=I64)
        i = jnp.searchsorted(ends, t, side="right")
        safe_i = jnp.clip(i, 0, max(nrecv - 1, 0))
        pos = t - (ends[safe_i] - counts[safe_i])
        ovalid = t < total
        out_li = jnp.where(ovalid, li_orig[safe_i], -1)
        rpos = jnp.clip(start[safe_i] + pos, 0, max(nrecv - 1, 0))
        out_ri = jnp.where(ovalid, ri_orig[r_order[rpos]], -1)
        of3 = total > out_cap
        matched = (counts > 0) & lv
        overflow = of1 | of2 | of3
        return (out_li[None], out_ri[None], ovalid[None],
                li_orig[None], matched[None], lv[None],
                total[None], overflow[None])

    mapped = shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(AXIS),) * 6,
        out_specs=(P(AXIS),) * 8,
    )
    fn = jax.jit(mapped)
    _JOIN_KERNELS[key] = fn
    return fn


# ---------------------------------------------------------------------------
# distributed range-partition sort
# ---------------------------------------------------------------------------
_SORT_KERNELS: Dict[tuple, object] = {}


def get_sort_kernel(mesh: Mesh, nk: int, nc: int, cpeer: int, cpeer2: int,
                    rows_out: int):
    """Two-exchange distributed sort (parity: the reference's persist +
    range-shuffle sort_values, reference physical/utils/sort.py:9-87 — here
    sample splitters + all_to_all range partition + local sort + a second
    all_to_all that rebalances to equal-size sorted shards).

    nk encoded i64 sort-key arrays, nc i64 payload arrays, cpeer/cpeer2
    per-peer bucket capacities for the two exchanges, rows_out rows per
    device in the dense output."""
    key = (tuple(d.id for d in mesh.devices.flat), nk, nc, cpeer, cpeer2,
           rows_out)
    fn = _SORT_KERNELS.get(key)
    if fn is not None:
        return fn
    ndev = mesh.devices.size

    def per_shard(keys, payload, rowvalid, splitters):
        # keys [nk, n]; payload [nc, n]; rowvalid [n]; splitters [nk, ndev-1]
        n = rowvalid.shape[0]
        # 1. destination by lexicographic rank among the splitters
        dest = jnp.zeros(n, dtype=jnp.int32)
        for s in range(ndev - 1):
            gt = jnp.zeros(n, dtype=bool)
            eq = jnp.ones(n, dtype=bool)
            for i in range(nk):
                ki = keys[i]
                si = splitters[i, s]
                gt = gt | (eq & (ki > si))
                eq = eq & (ki == si)
            dest = dest + (gt | eq).astype(jnp.int32)  # ties go right
        # 2. exchange rows to their range owner
        iblock = jnp.concatenate(
            [jnp.stack([keys[i] for i in range(nk)], axis=-1),
             jnp.stack([payload[j] for j in range(nc)], axis=-1)], axis=-1)
        fblock = jnp.zeros((n, 0), jnp.float64)
        bi, bf, bv, of1 = _bucket_rows(dest, rowvalid, iblock, fblock, ndev,
                                       cpeer)
        ri, _, rv = _exchange(bi, bf, bv)
        nrecv = rv.shape[0]
        # 3. local sort (invalid rows last)
        inv = (~rv).astype(jnp.int32)
        iota = jnp.arange(nrecv, dtype=I64)
        ops = (inv,) + tuple(ri[:, i] for i in range(nk)) + (iota,)
        order = jax.lax.sort(ops, num_keys=1 + nk)[-1]
        rs = ri[order]
        vs = rv[order]
        # 4. global sorted position: device-prefix offset + local rank
        cnt = jnp.sum(rv.astype(I64))
        counts = jax.lax.all_gather(cnt, AXIS)  # [ndev]
        me = jax.lax.axis_index(AXIS)
        offset = jnp.sum(jnp.where(jnp.arange(ndev) < me, counts, 0))
        pos = offset + jnp.arange(nrecv, dtype=I64)  # valid rows come first
        # 5. rebalance so device d owns rows [d*rows_out, (d+1)*rows_out)
        dest2 = jnp.clip(pos // rows_out, 0, ndev - 1).astype(jnp.int32)
        iblock2 = jnp.concatenate([pos[:, None], rs[:, nk:]], axis=-1)
        bi2, bf2, bv2, of2 = _bucket_rows(
            dest2, vs, iblock2, jnp.zeros((nrecv, 0), jnp.float64),
            ndev, cpeer2)
        ri2, _, rv2 = _exchange(bi2, bf2, bv2)
        # 6. order the received rows by global position, keep rows_out
        n2 = rv2.shape[0]
        ops2 = (jnp.where(rv2, ri2[:, 0], I64_MAX),
                jnp.arange(n2, dtype=I64))
        order2 = jax.lax.sort(ops2, num_keys=1)[-1][:rows_out]
        out = ri2[order2][:, 1:]          # [rows_out, nc]
        return out.T[:, None, :], of1[None], of2[None]

    mapped = shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(None, AXIS), P(None, AXIS), P(AXIS), P(None, None)),
        out_specs=(P(None, AXIS, None), P(AXIS), P(AXIS)),
    )
    fn = jax.jit(mapped)
    _SORT_KERNELS[key] = fn
    return fn


def _ladder_next_or_none(ladder, v):
    """Next rung, or None at the top (caller falls back instead of dying)."""
    try:
        return _ladder_next(ladder, v)
    except ResourceExhaustedError as e:
        logger.debug("capacity ladder topped out at %d: %s", v, e)
        return None


def _encode_sort_key(col: Column, ascending: bool, nulls_first: bool):
    """Column -> list of ascending-order int64 arrays (leading null key when
    nullable).  Dictionary strings must be compact (sorted dict) first.

    MUST stay semantically in lockstep with the single-device
    ops/sorting.py:sort_permutation (NaN sorts as +inf, null-indicator key
    leads, descending = monotone reversal): tests compare the two paths
    row-for-row (tests/integration/test_dist_sort.py)."""
    data = col.data
    if col.sql_type in STRING_TYPES:
        col = col.compact_dictionary()
        data = col.data
    if data.dtype == jnp.bool_:
        enc = data.astype(I64)
    elif jnp.issubdtype(data.dtype, jnp.floating):
        clean = jnp.where(jnp.isnan(data), jnp.inf, data)  # NaN sorts last
        enc = _float_to_ordered_i64(clean)
    else:
        enc = data.astype(I64)
    if not ascending:
        enc = -1 - enc  # monotone reversal, no overflow
    arrays = []
    if col.validity is not None:
        valid = col.valid_mask()
        nullkey = jnp.where(valid, 1, 0) if nulls_first else \
            jnp.where(valid, 0, 1)
        arrays.append(nullkey.astype(I64))
        enc = jnp.where(valid, enc, 0)
    arrays.append(enc)
    return arrays


def _encode_payload(col: Column):
    """Column -> (list of i64 transport arrays, decode(arr_list)->Column)."""
    data = col.data
    sql_type = col.sql_type
    dictionary = col.dictionary
    np_dtype = np.dtype(data.dtype)
    if np_dtype.kind == "f":
        enc = jax.lax.bitcast_convert_type(data.astype(jnp.float64), I64)
    elif np_dtype.kind == "b":
        enc = data.astype(I64)
    else:
        enc = data.astype(I64)
    arrays = [enc]
    nullable = col.validity is not None
    if nullable:
        arrays.append(col.valid_mask().astype(I64))

    def decode(dev_arrays: List[jnp.ndarray], n: int, sharding) -> Column:
        # elementwise device ops, then an explicit row-block re-pin: the
        # sorted table stays sharded on the mesh (device order IS the sort
        # order)
        def place(x):
            if sharding is None:
                return x[:n]
            ndev_ = sharding.mesh.devices.size
            if n % ndev_ == 0:
                # divisible: commit the sliced output to the row sharding
                return jax.jit(lambda a: a[:n], out_shardings=sharding)(x)
            # non-divisible lengths cannot be row-block committed; pin the
            # padded layout and slice (same trade as distribute.shard_table)
            return jax.jit(lambda a: a, out_shardings=sharding)(x)[:n]

        raw = dev_arrays[0]
        if np_dtype.kind == "f":
            vals = jax.lax.bitcast_convert_type(
                raw, jnp.float64).astype(np_dtype)
        elif np_dtype.kind == "b":
            vals = raw.astype(bool)
        else:
            vals = raw.astype(np_dtype)
        vals = place(vals)
        validity = None
        if nullable:
            v = place(dev_arrays[1].astype(bool))
            # scalar reduce on device — never pull the whole mask to host
            if not bool(host_read(jnp.all(v))):
                validity = v
        return Column(vals, sql_type, validity, dictionary)

    return arrays, decode


def dist_sort_table(mesh: Mesh, table, sort_cols: List[Column],
                    ascendings: List[bool], nulls_firsts: List[bool],
                    metrics=None):
    """Sort a mesh-sharded Table globally; output stays row-sharded.

    Sample-based splitters + the two-exchange kernel above.  Returns the
    sorted Table (device order IS the sort order) or None when ineligible
    or when the capacity ladder tops out (recorded in `metrics` as a
    ``resilience.fallback`` so the step-down is observable)."""

    def _fallback(why: str):
        logger.debug("dist sort falling back to single-program path: %s", why)
        if metrics is not None:
            metrics.inc("resilience.fallback")
            metrics.inc("resilience.fallback.dist_sort")
        return None

    n = table.num_rows
    ndev = mesh.devices.size
    if n == 0 or ndev <= 1:
        return None

    key_arrays: List[jnp.ndarray] = []
    for col, asc, nf in zip(sort_cols, ascendings, nulls_firsts):
        key_arrays.extend(_encode_sort_key(col, asc, nf))
    # string sort keys whose dictionaries were re-encoded produce NEW code
    # arrays; the payload still carries the ORIGINAL columns
    payload_arrays: List[jnp.ndarray] = []
    decoders = []
    for name in table.column_names:
        arrs, dec = _encode_payload(table.columns[name])
        payload_arrays.append(arrs)
        decoders.append(dec)
    flat_payload = [a for arrs in payload_arrays for a in arrs]

    nk = len(key_arrays)
    nc = len(flat_payload)

    # placement (pad to ndev multiple)
    def place_stack(arrs):
        padded = [pad_to_multiple(a.astype(I64), ndev)[0] for a in arrs]
        return jax.device_put(jnp.stack(padded),
                              NamedSharding(mesh, P(None, AXIS)))

    keys_mat = place_stack(key_arrays)
    pay_mat = place_stack(flat_payload) if nc else jnp.zeros(
        (0, keys_mat.shape[1]), I64)
    rowvalid = jax.device_put(
        pad_to_multiple(jnp.ones(n, bool), ndev, fill=False)[0],
        row_sharding(mesh))

    # splitters from an evenly-spaced sample (host: tiny)
    ns = min(n, max(ndev * 64, 512))
    sample_idx = np.linspace(0, n - 1, ns).astype(np.int64)
    sample = np.stack([host_read(k[jnp.asarray(sample_idx)])
                       for k in key_arrays])  # [nk, ns]
    order = np.lexsort(sample[::-1])
    qs = sample[:, order][:, np.linspace(0, ns - 1, ndev + 1
                                         ).astype(int)[1:-1]]
    splitters = jnp.asarray(qs.reshape(nk, ndev - 1))

    n_padded = keys_mat.shape[1]
    rows_out = n_padded // ndev
    # a source holds exactly rows_out rows, so no src->peer pair can exceed
    # rows_out in either exchange; a target also receives exactly rows_out
    # rows total in exchange 2.  rows_out + slack is therefore overflow-free
    # by construction (the of1/of2 ladders only matter past the ladder top,
    # where the single-program sort takes over).
    cpeer = _ladder_at_least(PEER_CAPACITY_LADDER, rows_out + 16)
    cpeer2 = cpeer
    for _ in range(10):
        fn = get_sort_kernel(mesh, nk, nc, cpeer, cpeer2, rows_out)
        out, of1, of2 = fn(keys_mat, pay_mat, rowvalid, splitters)
        STATS["sort_kernel"] += 1
        if metrics is not None:
            metrics.inc("parallel.dist.sort_kernel")
        grew = False
        if bool(host_read(of1).any()):
            cpeer = _ladder_next_or_none(PEER_CAPACITY_LADDER, cpeer)
            if cpeer is None:
                return _fallback("exchange-1 capacity ladder exhausted")
            grew = True
        if bool(host_read(of2).any()):
            cpeer2 = _ladder_next_or_none(PEER_CAPACITY_LADDER, cpeer2)
            if cpeer2 is None:
                return _fallback("exchange-2 capacity ladder exhausted")
            grew = True
        if not grew:
            break
    else:
        return _fallback("pathological skew: retries exhausted")

    # out [nc, ndev, rows_out] sharded on the device axis; flatten to global
    # row order and slice the padding off (stays sharded, like shard_table)
    from ..columnar.table import Table as _Table

    cols = {}
    i = 0
    flat = out.reshape(nc, n_padded) if nc else out
    sh = row_sharding(mesh)
    for name, arrs, dec in zip(table.column_names, payload_arrays, decoders):
        k = len(arrs)
        cols[name] = dec([flat[i + j] for j in range(k)], n, sh)
        i += k
    return _Table(cols, n)
def _place_rows(arr: jnp.ndarray, mesh: Mesh, fill=0):
    """Pad to a multiple of ndev and row-shard; returns (placed, valid)."""
    ndev = mesh.devices.size
    padded, valid = pad_to_multiple(arr, ndev, fill=fill)
    sh = row_sharding(mesh)
    return jax.device_put(padded, sh), jax.device_put(valid, sh)


def broadcast_inner_pairs(big_gid, big_valid, small_gid, small_valid):
    """Broadcast-join matching: the small side stays replicated, the big
    side is NEVER shuffled (parity: reference join.py:228-246 small-side
    broadcast merge under `sql.join.broadcast` — which broadcasts ANY small
    table, so this must too).

    Fast path: unique-dense-int small keys get a value-indexed LUT — one
    scatter + gather at HBM bandwidth.  General path (string-keyed,
    non-unique, sparse): sort the replicated small side once, probe with two
    searchsorteds per shard — still no collectives, no big-side shuffle.
    Pair compaction happens on host after one packed read (multi-host safe:
    the probe output is what the caller materializes anyway).  Returns
    (big_idx, small_idx, big_matched); never declines a small build side."""
    from ..ops.join import dense_unique_lut

    sv = None if bool(small_valid.all()) else small_valid
    prep = dense_unique_lut(small_gid, sv)
    if prep is None:
        return _broadcast_sorted_pairs(big_gid, big_valid,
                                       small_gid, small_valid)
    rmin, lut = prep
    size = lut.shape[0]
    idx = big_gid.astype(I64) - rmin
    inb = (idx >= 0) & (idx < size) & big_valid
    safe = jnp.clip(idx, 0, size - 1).astype(jnp.int32)
    cand = jnp.where(inb, lut[safe].astype(jnp.int64), jnp.int64(-1))
    STATS["broadcast_join"] += 1
    cand_h = host_read(cand)
    matched = cand_h >= 0
    bi = np.nonzero(matched)[0].astype(np.int64)
    si = cand_h[bi]
    return jnp.asarray(bi), jnp.asarray(si), matched


@jax.jit
def _sorted_probe(big_gid, big_valid, small_gid, small_valid):
    """Replicated-build probe for arbitrary keys: NULL build rows sort to
    the end (valid-first lexsort, so no sentinel value can collide with a
    real key — int64.max is a legal BIGINT) and the match range is clamped
    to the valid prefix, so NULL rows can never match."""
    sg = small_gid.astype(I64)
    # primary: valid first; secondary: key — the valid prefix is key-sorted
    order = jnp.lexsort((sg, ~small_valid))
    n_valid = jnp.sum(small_valid.astype(jnp.int64))
    iota = jnp.arange(sg.shape[0], dtype=jnp.int64)
    # suffix (invalid rows) holds arbitrary key values after the gather —
    # overwrite with +inf so the array is globally sorted for binary search
    sg_sorted = jnp.where(iota < n_valid, sg[order],
                          jnp.iinfo(jnp.int64).max)
    bg = big_gid.astype(I64)
    start = jnp.minimum(jnp.searchsorted(sg_sorted, bg, side="left"), n_valid)
    end = jnp.minimum(jnp.searchsorted(sg_sorted, bg, side="right"), n_valid)
    counts = jnp.where(big_valid, jnp.maximum(end - start, 0), 0)
    return jnp.stack([start.astype(I64), counts.astype(I64)]), order


def _broadcast_sorted_pairs(big_gid, big_valid, small_gid, small_valid):
    ns = int(small_gid.shape[0])
    nb = int(big_gid.shape[0])
    STATS["broadcast_join"] += 1
    STATS["broadcast_join_sorted"] += 1
    if ns == 0 or nb == 0:
        empty = jnp.zeros(0, dtype=I64)
        return empty, empty, np.zeros(nb, dtype=bool)
    packed, order = _sorted_probe(big_gid, big_valid, small_gid, small_valid)
    packed_h = host_read(packed)  # one transfer for both per-row arrays
    order_h = host_read(order)  # replicated small side: tiny
    start_h, counts_h = packed_h[0], packed_h[1]
    matched = counts_h > 0
    total = int(counts_h.sum())
    bi = np.repeat(np.arange(nb, dtype=np.int64), counts_h)
    offsets = np.cumsum(counts_h) - counts_h
    within = np.arange(total, dtype=np.int64) - np.repeat(offsets, counts_h)
    si = order_h[np.repeat(start_h, counts_h) + within].astype(np.int64)
    return jnp.asarray(bi), jnp.asarray(si), matched


def dist_inner_pairs(mesh: Mesh, lgid: jnp.ndarray, lvalid: jnp.ndarray,
                     rgid: jnp.ndarray, rvalid: jnp.ndarray):
    """Distributed equijoin matching: (li, ri) global row-index pairs.

    Shuffles both sides' (gid, row-id) with all_to_all, probes per device,
    and returns host int64 arrays of matching row indices (left-major within
    each device partition).  Also returns (l_matched bool[n_l]) for
    semi/anti/outer handling.
    """
    nl, nr = int(lgid.shape[0]), int(rgid.shape[0])
    ndev = mesh.devices.size
    lg, lrow = _place_rows(lgid.astype(I64), mesh)
    rg, rrow = _place_rows(rgid.astype(I64), mesh)
    lidx = jax.device_put(jnp.arange(lg.shape[0], dtype=I64), row_sharding(mesh))
    ridx = jax.device_put(jnp.arange(rg.shape[0], dtype=I64), row_sharding(mesh))
    lval, _ = _place_rows(lvalid & jnp.ones(nl, bool), mesh, fill=False)
    rval, _ = _place_rows(rvalid & jnp.ones(nr, bool), mesh, fill=False)
    lval = lval & lrow
    rval = rval & rrow

    per_shard_rows = max(lg.shape[0], rg.shape[0]) // ndev
    # uniform-hash expectation + slack; skew is caught by the overflow retry
    cpeer = _ladder_at_least(PEER_CAPACITY_LADDER,
                             2 * per_shard_rows // ndev + 256)
    out_cap = _ladder_at_least(PEER_CAPACITY_LADDER, 2 * per_shard_rows + 256)
    for _ in range(8):
        fn = get_join_kernel(mesh, cpeer, out_cap)
        (li, ri, ovalid, lorig, matched, lrecv_valid, totals,
         overflow) = fn(lg, lidx, lval, rg, ridx, rval)
        STATS["join_kernel"] += 1
        if not bool(np.asarray(overflow).any()):
            break
        # distinguish shuffle vs output overflow: grow both (cheap ladder)
        cpeer = _ladder_next(PEER_CAPACITY_LADDER, cpeer)
        out_cap = _ladder_next(PEER_CAPACITY_LADDER, out_cap)
    else:
        raise ResourceExhaustedError(
            "distributed join exceeded capacity ladder")

    ov = np.asarray(ovalid).reshape(-1)
    li_h = np.asarray(li).reshape(-1)[ov]
    ri_h = np.asarray(ri).reshape(-1)[ov]
    lmatch = np.zeros(nl, dtype=bool)
    lo = np.asarray(lorig).reshape(-1)
    mt = np.asarray(matched).reshape(-1) & np.asarray(lrecv_valid).reshape(-1)
    valid_rows = lo[mt]
    lmatch[valid_rows[valid_rows < nl]] = True
    return jnp.asarray(li_h), jnp.asarray(ri_h), lmatch


def _ladder_at_least(ladder, n):
    for v in ladder:
        if v >= n:
            return v
    return ladder[-1]


def _ladder_next(ladder, cur):
    for v in ladder:
        if v > cur:
            return v
    # taxonomy-degradable: the resilience ladder (resilience/ladder.py)
    # catches this and steps the query down to the single-program path
    raise ResourceExhaustedError(
        f"capacity ladder exhausted at {cur} (top {ladder[-1]})")


# ---------------------------------------------------------------------------
# SQL integration: Aggregate
# ---------------------------------------------------------------------------
#: aggregates decomposable into the 6-state layout
_DECOMPOSABLE = {
    "count", "count_star", "sum", "min", "max", "avg",
    "var_samp", "var_pop", "stddev_samp", "stddev_pop",
    "every", "bool_or", "single_value", "first_value",
    "regr_count", "regr_syy", "regr_sxx",
}


def try_dist_aggregate(rel, executor, inp) -> Optional[object]:
    """Lower a groupby-aggregate over a sharded input through the
    collectives kernel; None falls back to the single-program path."""
    from ..columnar.table import Table

    mesh = should_distribute(executor, "sql.distributed.aggregate", inp)
    if mesh is None:
        return None
    if not rel.group_exprs or inp.num_rows == 0:
        return None  # global aggregates reduce fine under GSPMD psum
    for agg in rel.agg_exprs:
        if agg.func not in _DECOMPOSABLE or agg.distinct:
            logger.debug("dist aggregate declining %s%s: single-program "
                         "path", agg.func, " DISTINCT" if agg.distinct else "")
            executor.context.metrics.inc("resilience.fallback")
            executor.context.metrics.inc("resilience.fallback.dist_aggregate")
            return None

    group_cols = [executor.eval_expr(e, inp) for e in rel.group_exprs]
    key_arrays: List[jnp.ndarray] = []
    key_infos: List[dict] = []
    for col in group_cols:
        if col.sql_type in STRING_TYPES and col.dictionary is None:
            return None
        arrs, info = encode_key_column(col)
        key_arrays.extend(arrs)
        key_infos.append(info)

    # one value slot per aggregate (keeps filter/arg pairing trivial)
    n = inp.num_rows
    ivals, fvals, vvalids, val_infos = [], [], [], []
    for agg in rel.agg_exprs:
        fmask = None
        if agg.filter is not None:
            fc = executor.eval_expr(agg.filter, inp)
            fmask = fc.data & fc.valid_mask()
        if agg.func == "count_star":
            iv = jnp.ones(n, I64)
            fv = jnp.ones(n, jnp.float64)
            valid = jnp.ones(n, bool)
            info = {"sql_type": SqlType.BIGINT, "dictionary": None,
                    "float": False}
        else:
            args = [executor.eval_expr(a, inp) for a in agg.args]
            col = args[0]
            if col.sql_type in STRING_TYPES:
                if col.dictionary is None:
                    return None
                col = col.compact_dictionary()
            valid = col.valid_mask()
            if jnp.issubdtype(col.data.dtype, jnp.floating):
                valid = valid & ~jnp.isnan(col.data)
            if agg.func in ("regr_count", "regr_syy", "regr_sxx"):
                if len(args) < 2:
                    return None
                y, x = args[0], args[1]
                valid = y.valid_mask() & x.valid_mask()
                col = {"regr_count": y, "regr_syy": y, "regr_sxx": x}[agg.func]
                if col.sql_type in STRING_TYPES:
                    return None
            iv, fv, info = encode_value_column(col)
        if fmask is not None:
            valid = valid & fmask
        ivals.append(iv)
        fvals.append(fv)
        vvalids.append(valid)
        val_infos.append(info)

    nv = len(rel.agg_exprs)
    nk = len(key_arrays)
    if nv == 0:
        # pure GROUP BY (distinct keys): one count_star slot keeps shapes sane
        ivals = [jnp.ones(n, I64)]
        fvals = [jnp.ones(n, jnp.float64)]
        vvalids = [jnp.ones(n, bool)]
        val_infos = [{"sql_type": SqlType.BIGINT, "dictionary": None,
                      "float": False}]
        nv = 1

    # pad + place (row-sharded over the mesh)
    ndev = mesh.devices.size
    sh = row_sharding(mesh)
    col_sh = NamedSharding(mesh, P(None, AXIS))

    def place_stack(arrs, dtype):
        padded = [pad_to_multiple(a.astype(dtype), ndev)[0] for a in arrs]
        return jax.device_put(jnp.stack(padded), col_sh)

    keys_mat = place_stack(key_arrays, I64)
    ivals_mat = place_stack(ivals, I64)
    fvals_mat = place_stack(fvals, jnp.float64)
    vvalid_mat = place_stack(vvalids, jnp.bool_)
    rowvalid = jax.device_put(
        pad_to_multiple(jnp.ones(n, bool), ndev, fill=False)[0], sh)

    cap = _ladder_at_least(GROUP_CAPACITY_LADDER, 0)
    for _ in range(8):
        cpeer = _ladder_at_least(PEER_CAPACITY_LADDER,
                                 min(2 * cap // ndev + 256, cap))
        fn = get_agg_kernel(mesh, nk, nv, cap, cpeer)
        fk, fv_, iout, fout, overflow = fn(keys_mat, ivals_mat, fvals_mat,
                                           vvalid_mat, rowvalid)
        STATS["agg_kernel"] += 1
        executor.context.metrics.inc("parallel.dist.agg_kernel")
        if not bool(host_read(overflow).any()):
            break
        cap = _ladder_next(GROUP_CAPACITY_LADDER, cap)
    else:
        raise ResourceExhaustedError(
            "distributed aggregate exceeded capacity ladder")

    # host finalize: concat per-device owned tables (keys are disjoint);
    # host_read all-gathers first when the mesh spans processes
    fk_h = host_read(fk)             # [ndev, nk, cap]
    fv_h = host_read(fv_).reshape(-1)             # [ndev*cap]
    iout_h = host_read(iout)         # [ndev, nv, cap, 3]
    fout_h = host_read(fout)
    keys_flat = [fk_h[:, i, :].reshape(-1) for i in range(nk)]
    sel = fv_h
    key_cols = decode_key_outputs([k[sel] for k in keys_flat], key_infos)
    ngroups = int(sel.sum())

    from ..physical.rel.base import unique_names
    names = unique_names([f.name for f in rel.schema])
    out: Dict[str, Column] = {}
    for name, col in zip(names, key_cols):
        out[name] = col

    agg_names = names[len(group_cols):]
    for j, (name, agg) in enumerate(zip(agg_names, rel.agg_exprs)):
        ist = iout_h[:, j, :, :].reshape(-1, N_ISTATE)[sel]
        fst = fout_h[:, j, :, :].reshape(-1, N_FSTATE)[sel]
        out[name] = _finalize_agg(agg, val_infos[j], ist, fst)
    return Table(out, ngroups)


def _finalize_agg(agg, info: dict, ist: np.ndarray, fst: np.ndarray) -> Column:
    """states -> final aggregate Column (the `finalize` stage)."""
    isum, imin, imax = ist[:, 0], ist[:, 1], ist[:, 2]
    cnt, fsum, fsq = fst[:, 0], fst[:, 1], fst[:, 2]
    func = agg.func
    nonempty = cnt > 0
    st = agg.sql_type

    def mk(vals, ok=None, dictionary=None, np_dtype=None):
        dtype = np_dtype or sql_to_np(st)
        arr = np.asarray(vals).astype(dtype)
        validity = None if ok is None or ok.all() else jnp.asarray(ok)
        return Column(jnp.asarray(arr), st, validity, dictionary)

    if func in ("count", "count_star", "regr_count"):
        return mk(cnt.astype(np.int64))
    if func == "sum":
        if info["float"]:
            return mk(fsum, nonempty)
        return mk(isum, nonempty)
    if func in ("min", "max"):
        raw = imin if func == "min" else imax
        if info["float"]:
            return mk(_ordered_i64_to_float(raw), nonempty)
        if info["sql_type"] in STRING_TYPES:
            return mk(raw.astype(np.int32), nonempty,
                      dictionary=info["dictionary"], np_dtype=np.int32)
        return mk(raw, nonempty)
    if func == "avg":
        return mk(fsum / np.maximum(cnt, 1), nonempty, np_dtype=np.float64)
    if func in ("var_samp", "var_pop", "stddev_samp", "stddev_pop"):
        mean = fsum / np.maximum(cnt, 1)
        m2 = np.maximum(fsq - cnt * mean * mean, 0.0)
        ddof = 1 if func.endswith("samp") else 0
        denom = np.maximum(cnt - ddof, 1)
        v = m2 / denom
        if func.startswith("stddev"):
            v = np.sqrt(v)
        ok = cnt > ddof
        return mk(v, ok, np_dtype=np.float64)
    if func == "every":
        return mk(np.where(nonempty, imin, 0).astype(bool), nonempty,
                  np_dtype=np.bool_)
    if func == "bool_or":
        return mk(np.where(nonempty, imax, 0).astype(bool), nonempty,
                  np_dtype=np.bool_)
    if func in ("single_value", "first_value"):
        raw = imin
        if info["float"]:
            return mk(_ordered_i64_to_float(raw), nonempty)
        if info["sql_type"] in STRING_TYPES:
            return mk(raw.astype(np.int32), nonempty,
                      dictionary=info["dictionary"], np_dtype=np.int32)
        return mk(raw, nonempty)
    if func in ("regr_syy", "regr_sxx"):
        mean = fsum / np.maximum(cnt, 1)
        m2 = np.maximum(fsq - cnt * mean * mean, 0.0)
        return mk(m2, nonempty, np_dtype=np.float64)
    raise NotImplementedError(f"distributed finalize for {func}")
