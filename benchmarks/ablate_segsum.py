"""On-chip ablation: where do Q1's kernel seconds go?

Times, on the current backend (TPU or CPU), the primitive variants the
compiled aggregate pipeline can be built from, so dtype/strategy choices are
measured rather than guessed:

  scatter segment_sum   x {f32, f64, int32, int64}
  one-hot matmul segsum x {f32, hi/lo double-float, blocked-f64-partials}
  gid radix computation x {int32, int64}
  full Q1-shaped kernel x {current-x64 shapes, int32/f32 shapes}

Run:  python benchmarks/ablate_segsum.py [n_rows]
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

N = int(sys.argv[1]) if len(sys.argv) > 1 else 6_000_000
DOMAIN = 12
REPS = 5


def timed(name, fn, *args):
    fn_j = jax.jit(fn)
    t0 = time.time()
    out = fn_j(*args)
    jax.block_until_ready(out)
    compile_s = time.time() - t0
    t0 = time.time()
    for _ in range(REPS):
        out = fn_j(*args)
    jax.block_until_ready(out)
    per = (time.time() - t0) / REPS
    print(f"{name:44s} {per*1e3:9.2f} ms   (compile {compile_s:.1f}s)", flush=True)
    return per


def main():
    print("backend:", jax.devices()[0].platform, jax.devices()[0], flush=True)
    rng = np.random.RandomState(0)
    gid_np = rng.randint(0, DOMAIN, N)
    x_np = rng.rand(N)

    gid64 = jnp.asarray(gid_np, dtype=jnp.int64)
    gid32 = jnp.asarray(gid_np, dtype=jnp.int32)
    xf32 = jnp.asarray(x_np, dtype=jnp.float32)
    xf64 = jnp.asarray(x_np, dtype=jnp.float64)
    xi32 = jnp.asarray((x_np * 100).astype(np.int32))
    xi64 = jnp.asarray((x_np * 100).astype(np.int64))
    jax.block_until_ready((gid64, gid32, xf32, xf64, xi32, xi64))

    # -- scatter segment_sum by dtype --------------------------------------
    for name, x, g in [("scatter f32/gid32", xf32, gid32),
                       ("scatter f32/gid64", xf32, gid64),
                       ("scatter f64/gid32", xf64, gid32),
                       ("scatter i32/gid32", xi32, gid32),
                       ("scatter i64/gid32", xi64, gid32),
                       ("scatter i64/gid64", xi64, gid64)]:
        timed(name, lambda a, b: jax.ops.segment_sum(a, b, DOMAIN), x, g)

    # -- one-hot matmul variants -------------------------------------------
    def onehot_f32(g, x):
        oh = jax.nn.one_hot(g, DOMAIN, dtype=jnp.float32)
        return oh.T @ x

    timed("onehot-matmul f32 [n,1]", onehot_f32, gid32, xf32[:, None])

    def onehot_hilo(g, x):
        hi = x.astype(jnp.float32)
        lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
        st = jnp.stack([hi, lo], axis=1)
        oh = jax.nn.one_hot(g, DOMAIN, dtype=jnp.float32)
        out = oh.T @ st
        return out[:, 0].astype(jnp.float64) + out[:, 1].astype(jnp.float64)

    timed("onehot-matmul hi/lo f64-in", onehot_hilo, gid32, xf64)

    def onehot_blocked(g, x, b=65536):
        npad = ((N + b - 1) // b) * b
        gp = jnp.zeros(npad, jnp.int32).at[:N].set(g)
        hp = jnp.zeros(npad, jnp.float32).at[:N].set(x.astype(jnp.float32))
        lp = jnp.zeros(npad, jnp.float32).at[:N].set(
            (x - x.astype(jnp.float32).astype(jnp.float64)).astype(jnp.float32))
        nb = npad // b
        gb = gp.reshape(nb, b)
        sb = jnp.stack([hp, lp], axis=1).reshape(nb, b, 2)
        oh = jax.nn.one_hot(gb, DOMAIN, dtype=jnp.float32)  # [nb, b, d]
        part = jax.lax.dot_general(
            oh, sb, dimension_numbers=(((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)  # [nb, d, 2]
        tot = part.astype(jnp.float64).sum(axis=0)
        return tot[:, 0] + tot[:, 1]

    timed("onehot-matmul blocked hi/lo", onehot_blocked, gid32, xf64)

    # accuracy of the variants vs exact f64 (numpy) -------------------------
    exact = np.zeros(DOMAIN)
    np.add.at(exact, gid_np, x_np)
    for name, fn in [("scatter f32", lambda: np.asarray(
                        jax.ops.segment_sum(xf32, gid32, DOMAIN), dtype=np.float64)),
                     ("scatter f64", lambda: np.asarray(
                        jax.ops.segment_sum(xf64, gid32, DOMAIN))),
                     ("onehot hi/lo", lambda: np.asarray(onehot_hilo(gid32, xf64))),
                     ("onehot blocked hi/lo", lambda: np.asarray(
                        jax.jit(onehot_blocked)(gid32, xf64)))]:
        got = fn()
        rel = np.max(np.abs(got - exact) / np.maximum(np.abs(exact), 1e-30))
        print(f"accuracy {name:32s} max-rel-err {rel:.3e}", flush=True)

    # -- gid radix computation ---------------------------------------------
    codes1 = jnp.asarray(rng.randint(0, 4, N), dtype=jnp.int64)
    codes2 = jnp.asarray(rng.randint(0, 3, N), dtype=jnp.int64)

    def gid_i64(a, b):
        return jnp.clip(a, 0, 3) * 3 + jnp.clip(b, 0, 2)

    def gid_i32(a, b):
        return (jnp.clip(a, 0, 3) * 3 + jnp.clip(b, 0, 2)).astype(jnp.int32)

    timed("gid radix int64", gid_i64, codes1, codes2)
    timed("gid radix int32->", gid_i32,
          codes1.astype(jnp.int32), codes2.astype(jnp.int32))

    # -- Q1-shaped kernels --------------------------------------------------
    ship = jnp.asarray(rng.randint(0, 2526, N) * 86_400_000_000_000, dtype=jnp.int64)
    qty = jnp.asarray(rng.randint(1, 51, N).astype(np.float32))
    price = jnp.asarray((rng.rand(N) * 1e5).astype(np.float32))
    disc = jnp.asarray((rng.rand(N) * 0.1).astype(np.float32))
    tax = jnp.asarray((rng.rand(N) * 0.08).astype(np.float32))
    cutoff = jnp.int64(2430 * 86_400_000_000_000)

    def q1_current(ship, qty, price, disc, tax, g1, g2):
        sel = ship <= cutoff
        gid = jnp.clip(g1.astype(jnp.int64), 0, 3) * 3 + jnp.clip(
            g2.astype(jnp.int64), 0, 2)
        dp = price * (1 - disc)
        ch = dp * (1 + tax)
        outs = [jax.ops.segment_sum(sel.astype(jnp.int32), gid, DOMAIN)]
        for col in (qty, price, dp, ch, disc):
            cnt = jax.ops.segment_sum(sel.astype(jnp.int64), gid, DOMAIN)
            s = jax.ops.segment_sum(jnp.where(sel, col, 0.0), gid, DOMAIN)
            outs.append(s)
            outs.append(cnt)
        return tuple(outs)

    def q1_lean(ship, qty, price, disc, tax, g1, g2):
        sel = ship <= cutoff
        gid = (jnp.clip(g1, 0, 3) * 3 + jnp.clip(g2, 0, 2)).astype(jnp.int32)
        dp = price * (1 - disc)
        ch = dp * (1 + tax)
        cnt = jax.ops.segment_sum(sel.astype(jnp.float32), gid, DOMAIN)
        outs = [cnt]
        for col in (qty, price, dp, ch, disc):
            s = jax.ops.segment_sum(jnp.where(sel, col, 0.0), gid, DOMAIN)
            outs.append(s)
        return tuple(outs)

    def q1_matmul(ship, qty, price, disc, tax, g1, g2):
        sel = ship <= cutoff
        gid = (jnp.clip(g1, 0, 3) * 3 + jnp.clip(g2, 0, 2)).astype(jnp.int32)
        dp = price * (1 - disc)
        ch = dp * (1 + tax)
        cols = jnp.stack([sel.astype(jnp.float32)]
                         + [jnp.where(sel, c, 0.0) for c in (qty, price, dp, ch, disc)],
                         axis=1)
        oh = jax.nn.one_hot(gid, DOMAIN, dtype=jnp.float32)
        return oh.T @ cols

    g1 = jnp.asarray(rng.randint(0, 3, N), dtype=jnp.int32)
    g2 = jnp.asarray(rng.randint(0, 2, N), dtype=jnp.int32)
    args = (ship, qty, price, disc, tax, g1.astype(jnp.int64), g2.astype(jnp.int64))
    args32 = (ship, qty, price, disc, tax, g1, g2)
    timed("Q1 kernel current (i64 cnt x5, i64 gid)", q1_current, *args)
    timed("Q1 kernel lean (f32 scatter, i32 gid)", q1_lean, *args32)
    timed("Q1 kernel matmul (one-hot, 6 cols)", q1_matmul, *args32)

    # -- pallas compile probe ----------------------------------------------
    try:
        sys.path.insert(0, ".")
        from dask_sql_tpu.ops.pallas_kernels import segsum_pallas

        t0 = time.time()
        out = segsum_pallas(gid32[:1 << 20], xf32[:1 << 20, None], DOMAIN)
        jax.block_until_ready(out)
        print(f"pallas segsum COMPILED+RAN in {time.time()-t0:.1f}s", flush=True)
    except Exception as e:  # noqa: BLE001
        print(f"pallas segsum FAILED: {type(e).__name__}: {str(e)[:300]}", flush=True)


if __name__ == "__main__":
    main()
