"""On-chip probe behind PERF.md's PR 25 findings (run from the repo root on a
TPU: ``python benchmarks/segsum_precision_probe.py``; it refuses the CPU).

Three questions, one JSON line each: what matmul precision does to the
blocked one-hot segment sum (DEFAULT / HIGH / HIGHEST against exact float64,
6M rows, domain 6, hi/lo split — `blocked` below is `segsum_scan_blocked`
with the precision as an argument); whether the pallas kernel really compiles
AND runs there (domain 6 and 2048); and a smoke timing of the matmul against
float64 scatter at domain 6.  Smoke readings, not benchmark numbers."""
import json
import sys
import time

sys.path.insert(0, ".")
import numpy as np

import jax
import jax.numpy as jnp

import dask_sql_tpu  # noqa: F401 — turns x64 on
from dask_sql_tpu.ops.pallas_kernels import (
    _round_up,
    segsum_pallas,
    segsum_scan_blocked,
    split_hi_lo,
)

dev = jax.devices()[0]
print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}), flush=True)
if dev.platform != "tpu":
    sys.exit("segsum_precision_probe: needs a TPU")

def blocked(gid, cols, domain, precision, block=32768):
    k = len(cols); n = gid.shape[0]
    b = min(block, max(_round_up(n, 8), 8)); npad = max(_round_up(n, b), b); nb = npad // b
    pad = npad - n
    gid_p = jnp.pad(gid.astype(jnp.int32), (0, pad))
    stack = jnp.stack([c.astype(jnp.float32) for c in cols], axis=1)
    if pad: stack = jnp.pad(stack, ((0, pad), (0, 0)))
    def step(carry, xs):
        g, c = xs
        onehot = jax.nn.one_hot(g, domain, dtype=jnp.float32)
        part = jax.lax.dot_general(onehot, c, (((0,), (0,)), ((), ())),
                                   precision=precision, preferred_element_type=jnp.float32)
        return carry + part.astype(jnp.float64), None
    out, _ = jax.lax.scan(step, jnp.zeros((domain, k), jnp.float64),
                          (gid_p.reshape(nb, b), stack.reshape(nb, b, k)))
    return out

rng = np.random.default_rng(0)
n, domain = 6_000_000, 6
gid_h = rng.integers(0, domain, n).astype(np.int32)
x_h = rng.integers(1, 51, n) * (900 + rng.random(n) * 1200) * (1 - rng.integers(0, 11, n) / 100.0)
exact = np.zeros(domain); np.add.at(exact, gid_h, x_h)
cnt = np.bincount(gid_h, minlength=domain).astype(np.float64)
gid, x = jnp.asarray(gid_h), jnp.asarray(x_h)

def rel(a): return float(np.max(np.abs(np.asarray(a) - exact) / exact))

for name, prec in (("DEFAULT", jax.lax.Precision.DEFAULT), ("HIGH", jax.lax.Precision.HIGH),
                   ("HIGHEST", jax.lax.Precision.HIGHEST)):
    def f(g, v, prec=prec):
        hi, lo = split_hi_lo(v)
        out = blocked(g, [jnp.ones_like(hi), hi, lo], domain, prec)
        return out[:, 0], out[:, 1] + out[:, 2]
    jf = jax.jit(f)
    c, s = jax.block_until_ready(jf(gid, x))
    ts = []
    for _ in range(5):
        t = time.perf_counter(); jax.block_until_ready(jf(gid, x)); ts.append(time.perf_counter() - t)
    print(json.dumps({"probe": "blocked_matmul", "precision": name, "rows": n, "domain": domain,
                      "sum_max_rel_err": rel(s), "counts_exact": bool(np.array_equal(np.asarray(c), cnt)),
                      "seconds_median_of_5": sorted(ts)[2]}), flush=True)

# the engine's own function (HIGHEST hard-wired)
def g_engine(g, v):
    hi, lo = split_hi_lo(v)
    out = segsum_scan_blocked(g, [jnp.ones_like(hi), hi, lo], domain)
    return out[:, 1] + out[:, 2]
print(json.dumps({"probe": "segsum_scan_blocked", "sum_max_rel_err": rel(jax.jit(g_engine)(gid, x))}), flush=True)

# scatter f64 for the same job
def g_scatter(g, v):
    return jax.ops.segment_sum(jnp.ones_like(v), g, domain), jax.ops.segment_sum(v, g, domain)
js = jax.jit(g_scatter)
c, s = jax.block_until_ready(js(gid, x))
ts = []
for _ in range(5):
    t = time.perf_counter(); jax.block_until_ready(js(gid, x)); ts.append(time.perf_counter() - t)
print(json.dumps({"probe": "scatter_f64", "rows": n, "domain": domain, "sum_max_rel_err": rel(s),
                  "seconds_median_of_5": sorted(ts)[2]}), flush=True)

# the pallas kernel, compiled by Mosaic and RUN on the chip
for dom in (6, 2048):
    m = 1 << 20
    g_h = rng.integers(0, dom, m).astype(np.int32)
    c_h = rng.random((m, 3)).astype(np.float32)
    ex = np.zeros((dom, 3)); np.add.at(ex, g_h, c_h.astype(np.float64))
    out = jax.block_until_ready(jax.jit(lambda g, c: segsum_pallas(g, c, dom))(jnp.asarray(g_h), jnp.asarray(c_h)))
    err = float(np.max(np.abs(np.asarray(out, dtype=np.float64) - ex) / np.maximum(ex, 1e-30)))
    print(json.dumps({"probe": "segsum_pallas_on_chip", "domain": dom, "rows": m, "max_rel_err": err,
                      "device": str(out.devices())}), flush=True)
print(json.dumps({"probe_done": True}))
