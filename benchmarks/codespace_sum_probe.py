"""On-chip probe behind PERF.md's PR 36 finding (run from the repo root on a
TPU: ``python benchmarks/codespace_sum_probe.py``; ``--rows`` cuts it for a
CPU rehearsal, whose times mean nothing).

`sf10_q18_library`'s semi-join build side at the cell's shapes: 24,000,000
int16 codes of a 50-value dictionary (the whole numbers 1..50) summed per
order key into the 24,006,178 values of the keys' range, four rows a key on
TPC-H's sparse keys.  One JSON line per variant, seconds per call after a
warm-up (`block_until_ready`, the median of the repeats):

(a) `decoded_f64`: the float64 dictionary gather, the NaN test, the float64
    `segment_sum` and the count of the NaN test's mask (the path until PR 36);
(b) `affine_i32`: ``code + 1`` as int32 and ONE int32 `segment_sum`
    (`physical/compiled.py::WholeSum`, the dictionary affine in its code);
(c) `table_i32`: the same through a constant int32 table (a dictionary of
    whole numbers that is not affine);
(d) `count_i32`: the count of the rows alone, which every variant's program
    pays once beside it.
Every variant's sums are checked against numpy's."""
import argparse
import json
import statistics
import sys
import time

sys.path.insert(0, ".")
import numpy as np

import jax
import jax.numpy as jnp

import dask_sql_tpu  # noqa: F401 — turns x64 on

ap = argparse.ArgumentParser()
ap.add_argument("--rows", type=int, default=24_000_000)
ap.add_argument("--domain", type=int, default=24_006_178)
ap.add_argument("--repeats", type=int, default=5)
args = ap.parse_args()

dev = jax.devices()[0]
print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                  "rows": args.rows, "domain": args.domain}), flush=True)

rng = np.random.default_rng(36)
orders = args.domain * 8 // 32
lines = np.sort(rng.integers(0, orders, args.rows))
gid_host = ((lines // 8) * 32 + lines % 8).astype(np.int32)  # 8 of every 32
codes_host = rng.integers(0, 50, args.rows).astype(np.int16)
want = np.bincount(gid_host, weights=codes_host + 1.0,
                   minlength=args.domain)
values = jnp.asarray(np.arange(1.0, 51.0))
table = jnp.asarray(np.arange(1, 51, dtype=np.int32))
sel = jnp.ones(args.rows, dtype=bool)


def seg(x, gid):
    return jax.ops.segment_sum(x, gid, args.domain)


def decoded_f64(codes, gid, sel):
    d = values[jnp.clip(codes, 0, 49)]
    v = sel & ~jnp.isnan(d)
    return seg(jnp.where(v, d, 0.0), gid), seg(v.astype(jnp.int32), gid)


def affine_i32(codes, gid, sel):
    d = codes.astype(jnp.int32) + jnp.int32(1)
    return seg(jnp.where(sel, d, 0), gid).astype(jnp.float64)


def table_i32(codes, gid, sel):
    d = table[jnp.clip(codes, 0, 49)]
    return seg(jnp.where(sel, d, 0), gid).astype(jnp.float64)


def count_i32(codes, gid, sel):
    return seg(sel.astype(jnp.int32), gid)


codes, gid = jnp.asarray(codes_host), jnp.asarray(gid_host)
for fn in (decoded_f64, affine_i32, table_i32, count_i32):
    jitted = jax.jit(fn)
    t0 = time.perf_counter()
    out = jax.block_until_ready(jitted(codes, gid, sel))
    first = time.perf_counter() - t0
    times = []
    for _ in range(args.repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(codes, gid, sel))
        times.append(time.perf_counter() - t0)
    got = np.asarray(out[0] if isinstance(out, tuple) else out)
    exact = bool((got == want).all()) if fn is not count_i32 else \
        bool((got == np.bincount(gid_host, minlength=args.domain)).all())
    print(json.dumps({"variant": fn.__name__, "first_call_s": round(first, 4),
                      "median_s": statistics.median(times),
                      "all_s": [round(t, 4) for t in times],
                      "equal_to_numpy": exact}), flush=True)
