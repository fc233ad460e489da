"""Distributed aggregation and shuffle kernels: shard_map + XLA collectives.

Role parity: the reference's distribution strategies (SURVEY.md §2.3) —
partial→final tree aggregation (dd.Aggregation chunk/agg/finalize +
split_out/split_every), tasks-based hash shuffle, broadcast join — rebuilt as
jit-compiled SPMD programs: every kernel below is `shard_map`ped over a 1-D
device mesh, uses static shapes (capacity-padded, validity-masked), and
communicates only through XLA collectives (all_gather / all_to_all / psum)
so the compiler schedules them onto ICI/DCN.

Key design (SURVEY.md §7 hard parts — dynamic shapes): each shard reduces its
rows into a CAPACITY-bounded sorted partial table (keys, states, valid).
Exactness is preserved by construction: if a shard sees more than CAPACITY
distinct keys an overflow flag is raised so the caller re-runs with doubled
capacity (compile-cache friendly: capacities come from a fixed ladder).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import AXIS, default_mesh

#: capacity ladder keeps recompiles bounded (capacity-doubling strategy)
CAPACITY_LADDER = (256, 4096, 65536, 1 << 20)

# ---------------------------------------------------------------------------
# Local (per-shard) building blocks — pure jnp, jit-safe static shapes
# ---------------------------------------------------------------------------


def _local_sorted_groups(keys: jnp.ndarray, valid: jnp.ndarray, capacity: int):
    """Sort rows by key and produce segment ids, bounded by `capacity`.

    Returns (order, seg_of_sorted_row, uniq_keys[capacity], uniq_valid[capacity],
    overflow: bool scalar).  Invalid rows sort last and take no segment.
    """
    n = keys.shape[0]
    big = jnp.iinfo(keys.dtype).max
    sort_keys = jnp.where(valid, keys, big)
    order = jnp.argsort(sort_keys)
    ks = sort_keys[order]
    vs = valid[order]
    changed = jnp.concatenate([vs[:1], (ks[1:] != ks[:-1]) & vs[1:]])
    seg = jnp.cumsum(changed.astype(jnp.int32)) - 1
    seg = jnp.where(vs, seg, capacity - 1)  # park invalid rows in the last slot
    n_groups = jnp.max(jnp.where(vs, seg + 1, 0), initial=0)
    overflow = n_groups > capacity
    seg = jnp.minimum(seg, capacity - 1)
    uniq_keys = jnp.zeros((capacity,), dtype=keys.dtype).at[seg].max(
        jnp.where(vs, ks, jnp.zeros_like(ks)))
    uniq_valid = jnp.zeros((capacity,), dtype=bool).at[seg].max(vs)
    return order, seg, uniq_keys, uniq_valid, overflow


# aggregation state layout: (count, sum, min, max, sumsq) per value column —
# the same chunk/agg/finalize triple family as the reference's
# AGGREGATION_MAPPING (aggregate.py:117-231 there)
N_STATE = 5


def _partial_states(values: jnp.ndarray, valid: jnp.ndarray, seg, order, capacity: int):
    v = values[order].astype(jnp.float64)
    val = valid[order]
    zero = jnp.zeros((capacity,), dtype=jnp.float64)
    cnt = zero.at[seg].add(val.astype(jnp.float64))
    s = zero.at[seg].add(jnp.where(val, v, 0.0))
    mn = jnp.full((capacity,), jnp.inf).at[seg].min(jnp.where(val, v, jnp.inf))
    mx = jnp.full((capacity,), -jnp.inf).at[seg].max(jnp.where(val, v, -jnp.inf))
    s2 = zero.at[seg].add(jnp.where(val, v * v, 0.0))
    return jnp.stack([cnt, s, mn, mx, s2], axis=-1)  # [capacity, N_STATE]


def _combine_states(keys, valid, states, capacity: int):
    """Merge duplicate keys in a concatenated partial table (the `agg` stage)."""
    order, seg, uniq_keys, uniq_valid, overflow = _local_sorted_groups(keys, valid, capacity)
    st = states[order]
    val = valid[order]
    zero = jnp.zeros((capacity,), dtype=jnp.float64)
    cnt = zero.at[seg].add(jnp.where(val, st[:, 0], 0.0))
    s = zero.at[seg].add(jnp.where(val, st[:, 1], 0.0))
    mn = jnp.full((capacity,), jnp.inf).at[seg].min(jnp.where(val, st[:, 2], jnp.inf))
    mx = jnp.full((capacity,), -jnp.inf).at[seg].max(jnp.where(val, st[:, 3], -jnp.inf))
    s2 = zero.at[seg].add(jnp.where(val, st[:, 4], 0.0))
    return uniq_keys, uniq_valid, jnp.stack([cnt, s, mn, mx, s2], axis=-1), overflow


# ---------------------------------------------------------------------------
# Distributed groupby-aggregate (partial -> shuffle-by-key -> final)
# ---------------------------------------------------------------------------
def make_dist_groupby(mesh: Optional[Mesh] = None, capacity: int = 4096):
    """Build the jitted distributed groupby-sum/min/max/count/avg kernel.

    Input arrays are row-sharded over the mesh; output partial tables are
    key-sharded (hash(key) % n_devices == device_id) — the split_out analogue.
    """
    mesh = mesh or default_mesh()
    ndev = mesh.devices.size

    def per_shard(keys, values, valid):
        # 1. local partial aggregation (the `chunk` stage)
        order, seg, uk, uv, overflow = _local_sorted_groups(keys, valid, capacity)
        states = _partial_states(values, valid, seg, order, capacity)
        states = jnp.where(uv[:, None], states, _identity_states(capacity))
        # 2. route each partial group to its owner device and combine there.
        #    all_gather over ICI: every device sees all partial tables, keeps
        #    the keys it owns (hash % ndev) — one collective, static shapes.
        all_keys = jax.lax.all_gather(uk, AXIS).reshape(-1)
        all_valid = jax.lax.all_gather(uv, AXIS).reshape(-1)
        all_states = jax.lax.all_gather(states, AXIS).reshape(-1, N_STATE)
        me = jax.lax.axis_index(AXIS)
        mine = all_valid & ((all_keys % ndev) == me)
        fk, fv, fstates, overflow2 = _combine_states(all_keys, mine, all_states, capacity)
        return fk[None], fv[None], fstates[None], (overflow | overflow2)[None]

    fn = shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
    )
    return jax.jit(fn)


def _identity_states(capacity: int):
    return jnp.stack([
        jnp.zeros((capacity,)), jnp.zeros((capacity,)),
        jnp.full((capacity,), jnp.inf), jnp.full((capacity,), -jnp.inf),
        jnp.zeros((capacity,)),
    ], axis=-1)


def finalize_states(keys, valid, states):
    """Host-side: sharded partial tables -> dense (keys, count, sum, min, max,
    mean, var) arrays."""
    k = np.asarray(keys).reshape(-1)
    v = np.asarray(valid).reshape(-1)
    st = np.asarray(states).reshape(-1, N_STATE)
    k, st = k[v], st[v]
    order = np.argsort(k, kind="stable")
    k, st = k[order], st[order]
    cnt, s, mn, mx, s2 = st.T
    mean = s / np.maximum(cnt, 1)
    var = np.maximum(s2 - cnt * mean * mean, 0) / np.maximum(cnt - 1, 1)
    return k, cnt, s, mn, mx, mean, var


# ---------------------------------------------------------------------------
# Hash shuffle (DISTRIBUTE BY / join partitioning)
# ---------------------------------------------------------------------------
def make_hash_shuffle(mesh: Optional[Mesh] = None, capacity_per_peer: int = 4096,
                      n_payloads: int = 1):
    """Build the jitted all_to_all hash shuffle.

    Each shard routes its rows to `hash(key) % ndev`; per-(src,dst) traffic is
    bounded by `capacity_per_peer` rows (overflow flagged).  Payload columns
    ride along as a [n, n_payloads] float64 block.

    Parity: the reference's tasks-based shuffle (`shuffle_method="tasks"`,
    dask_sql/__init__.py:16 there) — here one `all_to_all` on ICI.
    """
    mesh = mesh or default_mesh()
    ndev = mesh.devices.size
    C = capacity_per_peer

    def per_shard(keys, payload, valid):
        n = keys.shape[0]
        dest = (keys % ndev).astype(jnp.int32)
        dest = jnp.where(valid, dest, ndev)  # invalid rows route nowhere
        # stable counting sort by destination into [ndev, C] buckets
        order = jnp.argsort(dest)
        ks = keys[order]
        ps = payload[order]
        ds = dest[order]
        vs = valid[order]
        # position within destination bucket
        idx = jnp.arange(n)
        start_of_dest = jnp.searchsorted(ds, jnp.arange(ndev + 1))
        pos_in_bucket = idx - start_of_dest[jnp.clip(ds, 0, ndev)]
        overflow = jnp.any((pos_in_bucket >= C) & vs)
        slot_ok = vs & (pos_in_bucket < C)
        # non-landing rows scatter out-of-bounds so mode="drop" discards them;
        # a clipped index would nondeterministically clobber a real slot
        flat = jnp.where(slot_ok, ds * C + pos_in_bucket, ndev * C)
        bk = jnp.zeros((ndev * C,), dtype=keys.dtype).at[flat].set(
            ks, mode="drop")
        bv = jnp.zeros((ndev * C,), dtype=bool).at[flat].set(
            slot_ok, mode="drop")
        bp = jnp.zeros((ndev * C, payload.shape[1]), dtype=payload.dtype).at[flat].set(
            ps, mode="drop")
        # the collective: exchange bucket b with device b
        bk = bk.reshape(ndev, C)
        bv = bv.reshape(ndev, C)
        bp = bp.reshape(ndev, C, payload.shape[1])
        rk = jax.lax.all_to_all(bk[None], AXIS, split_axis=1, concat_axis=1)[0]
        rv = jax.lax.all_to_all(bv[None], AXIS, split_axis=1, concat_axis=1)[0]
        rp = jax.lax.all_to_all(bp[None], AXIS, split_axis=1, concat_axis=1)[0]
        return (rk.reshape(1, -1), rv.reshape(1, -1),
                rp.reshape(1, -1, payload.shape[1]), overflow[None])

    fn = shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Broadcast join: replicate the small (build) side, probe locally — no
# shuffle of the big side at all (parity: reference broadcast joins,
# join.py:228 + `sql.join.broadcast` config)
# ---------------------------------------------------------------------------
def make_broadcast_join_count(mesh: Optional[Mesh] = None):
    """Jitted broadcast equijoin match-count: the probe side stays put
    (row-sharded); the build side is all_gather'ed to every device over ICI.
    Returns per-probe-row match counts, row-sharded like the probe input."""
    mesh = mesh or default_mesh()

    def per_shard(probe_keys, probe_valid, build_keys, build_valid):
        # build side arrives shard-local; replicate it
        all_bk = jax.lax.all_gather(build_keys, AXIS).reshape(-1)
        all_bv = jax.lax.all_gather(build_valid, AXIS).reshape(-1)
        big = jnp.iinfo(all_bk.dtype).max
        b_sorted = jnp.sort(jnp.where(all_bv, all_bk, big))
        start = jnp.searchsorted(b_sorted, probe_keys, side="left")
        end = jnp.searchsorted(b_sorted, probe_keys, side="right")
        counts = jnp.where(probe_valid, end - start, 0)
        return counts

    fn = shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
        out_specs=P(AXIS),
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Distributed hash join: shuffle both sides, local sort/searchsorted probe
# ---------------------------------------------------------------------------
def make_dist_join_count(mesh: Optional[Mesh] = None, capacity_per_peer: int = 4096):
    """Distributed equijoin *match-count* kernel (the shuffle + probe core).

    Returns per-shard match counts — the shape-static part of the join; the
    eager layer materializes pairs per shard afterwards.  Demonstrates the
    full collectives path: 2 shuffles + local probe, all inside one jit.
    """
    mesh = mesh or default_mesh()
    ndev = mesh.devices.size
    shuffle = make_hash_shuffle(mesh, capacity_per_peer)

    def probe(lk, lv, rk, rv):
        big = jnp.iinfo(rk.dtype).max
        r_sorted = jnp.sort(jnp.where(rv, rk, big))
        n_valid_r = jnp.sum(rv.astype(jnp.int64))
        start = jnp.searchsorted(r_sorted, lk, side="left")
        end = jnp.searchsorted(r_sorted, lk, side="right")
        counts = jnp.where(lv, end - start, 0)
        return counts

    def per_shard(lk, lval, rk, rval):
        counts = probe(lk, lval, rk, rval)
        total = jnp.sum(counts)
        return counts[None], total[None]

    def run(lkeys, lvalid, rkeys, rvalid):
        one = jnp.zeros((lkeys.shape[0], 1), dtype=jnp.float64)
        slk, slv, _, of1 = shuffle(lkeys, one, lvalid)
        oner = jnp.zeros((rkeys.shape[0], 1), dtype=jnp.float64)
        srk, srv, _, of2 = shuffle(rkeys, oner, rvalid)
        fn = shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(AXIS), P(AXIS)),
        )
        counts, totals = fn(slk.reshape(-1), slv.reshape(-1),
                            srk.reshape(-1), srv.reshape(-1))
        return counts, totals, of1 | of2

    return jax.jit(run)
