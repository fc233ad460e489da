"""Pallas / MXU segment-reduction kernel tests (interpret mode on CPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _case(seed, n=1000, domain=37, k=3):
    rng = np.random.RandomState(seed)
    gid = rng.randint(0, domain, n).astype(np.int32)
    contribs = rng.rand(n, k).astype(np.float32)
    expected = np.zeros((domain, k), dtype=np.float64)
    for g, row in zip(gid, contribs):
        expected[g] += row
    return jnp.asarray(gid), jnp.asarray(contribs), expected


def test_segsum_onehot_jnp_matches_scatter():
    from dask_sql_tpu.ops.pallas_kernels import segsum_onehot_jnp

    gid, contribs, expected = _case(0)
    out = segsum_onehot_jnp(gid, contribs, 37)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_segsum_pallas_interpret():
    from dask_sql_tpu.ops.pallas_kernels import segsum_pallas

    gid, contribs, expected = _case(1, n=700, domain=19, k=2)
    out = segsum_pallas(gid, contribs, 19, block_rows=256, interpret=True)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_segsum_pallas_padding_edges():
    from dask_sql_tpu.ops.pallas_kernels import segsum_pallas

    # n not a multiple of the block, domain 1, single column
    gid, contribs, expected = _case(2, n=301, domain=1, k=1)
    out = segsum_pallas(gid, contribs, 1, block_rows=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_compiled_pipeline_matmul_mode(c):
    import pandas as pd

    # integer group key so the radix-compiled pipeline actually engages
    rng = np.random.RandomState(3)
    df = pd.DataFrame({"g": rng.randint(0, 5, 4000).astype(np.int64),
                       "v": rng.rand(4000) * 1e9})
    c.create_table("mmagg", df)
    q = "SELECT g, COUNT(*) AS n, SUM(v) AS s FROM mmagg GROUP BY g"
    got = c.sql(q, config_options={"sql.compile.segsum": "matmul"}).compute()
    ref = c.sql(q, config_options={"sql.compile.segsum": "scatter"}).compute()
    got = got.sort_values("g").reset_index(drop=True)
    ref = ref.sort_values("g").reset_index(drop=True)
    assert list(got["n"]) == list(ref["n"])
    # hi/lo double-float: representation-exact, f32-grade accumulation
    np.testing.assert_allclose(got["s"], ref["s"], rtol=1e-6)
    # and the compiled matmul path really ran (not an eager fallback)
    from dask_sql_tpu.physical import compiled as comp

    assert any("matmul" in family and program.segsum_mode == "matmul"
               for (family, _), program in comp.PROGRAMS.items())


def test_segsum_double_float_accuracy():
    from dask_sql_tpu.ops.pallas_kernels import segsum_double_float

    rng = np.random.RandomState(4)
    gid = jnp.asarray(rng.randint(0, 4, 5000).astype(np.int32))
    vals = jnp.asarray(rng.rand(5000, 1) * 1e12 + 0.12345)
    out = segsum_double_float(gid, vals, 4)
    expected = np.zeros((4, 1))
    for g, v in zip(np.asarray(gid), np.asarray(vals)):
        expected[g] += v
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_bad_segsum_config_rejected():
    from dask_sql_tpu import config
    from dask_sql_tpu.ops.pallas_kernels import choose_segsum_impl

    with config.set({"sql.compile.segsum": "scater"}):
        with pytest.raises(ValueError):
            choose_segsum_impl(config.config, 10)


def test_choose_impl(c):
    import pandas as pd

    from dask_sql_tpu import config
    from dask_sql_tpu.ops.pallas_kernels import choose_segsum_impl

    with config.set({"sql.compile.segsum": "pallas"}):
        # an explicit request is never swapped for another implementation
        assert choose_segsum_impl(config.config, 100) == "pallas"
    with config.set({"sql.compile.segsum": "auto"}):
        # CPU backend in tests -> scatter
        assert choose_segsum_impl(config.config, 100) == "scatter"
    # ... and where the backend cannot compile the kernel (the CPU backend
    # only interprets pallas) the query fails instead of becoming 'matmul'
    c.create_table("plagg", pd.DataFrame({"g": np.arange(64) % 4,
                                          "v": np.arange(64) * 0.5}))
    with pytest.raises(Exception, match="(?i)interpret|pallas"):
        c.sql("SELECT g, SUM(v) AS s FROM plagg GROUP BY g",
              config_options={"sql.compile.segsum": "pallas"}).compute()
    assert c.metrics.snapshot()["counters"].get("resilience.degraded", 0) == 0


def _blocked_case(seed, n, k, domain):
    """ids, k float32 columns (a 0/1 count column, then hi/lo halves of
    float64 values, then one plain float32 column if k is even) and the
    exact float64 sums each column and each hi/lo pair stands for."""
    from dask_sql_tpu.ops.pallas_kernels import split_hi_lo

    rng = np.random.RandomState(seed)
    gid = rng.randint(0, domain, n).astype(np.int32)
    mask = rng.rand(n) < 0.8
    cols, pairs = [mask.astype(np.float32)], []
    while len(cols) + 2 <= k:
        x64 = np.where(mask, rng.rand(n) * 1e9 + 0.123456789, 0.0)
        hi, lo = split_hi_lo(jnp.asarray(x64))
        pairs.append((len(cols), x64))
        cols += [np.asarray(hi), np.asarray(lo)]
    if len(cols) < k:
        cols.append(np.where(mask, rng.rand(n), 0.0).astype(np.float32))
    return gid, cols, pairs


@pytest.mark.parametrize("n, k, domain, block", [
    (200_000, 3, 16, 8192),      # the original case: a ragged last block
    (4 * 4096, 4, 12, 4096),     # rows a multiple of the block
    (4 * 4096 + 1, 16, 12, 4096),  # one row past a multiple
    (3000, 16, 12, 4096),        # rows below one block
    (1000, 1, 1, 4096),          # one column, one group
    (2 * 4096 + 100, 1, 12, 4096),  # one column, a short tail
    (9000, 4, 2048, 4096),       # the widest domain `auto` sends here
    (5000, 16, 2048, 4096),
    (6_000_000, 4, 1, 32768),    # a shard of the four-chip cell: 3456-row tail
])
def test_segsum_scan_blocked_accuracy_and_counts(n, k, domain, block):
    from dask_sql_tpu.ops.pallas_kernels import (
        MATMUL_FLOAT_REL_ERR_BOUND,
        segsum_scan_blocked,
    )

    gid, cols, pairs = _blocked_case(7, n, k, domain)
    out = np.asarray(segsum_scan_blocked(
        jnp.asarray(gid), [jnp.asarray(c) for c in cols], domain,
        block=block))
    assert out.shape == (domain, k) and out.dtype == np.float64
    # counts: EXACT (integer-valued f32 block partials, f64 combine), so a
    # row counted twice or a row left out shows
    cnt_exact = np.zeros(domain)
    np.add.at(cnt_exact, gid, cols[0].astype(np.float64))
    assert np.array_equal(out[:, 0], cnt_exact)
    # float sums: within the stated bound of the exact f64 result
    exact = [(i, i + 1, x64) for i, x64 in pairs]
    if k % 2 == 0:
        exact.append((k - 1, None, cols[-1].astype(np.float64)))
    for i, j, x64 in exact:
        s_exact = np.zeros(domain)
        np.add.at(s_exact, gid, x64)
        got = out[:, i] + (out[:, j] if j is not None else 0.0)
        rel = np.max(np.abs(got - s_exact) / np.maximum(np.abs(s_exact), 1e-30))
        assert rel < MATMUL_FLOAT_REL_ERR_BOUND, (i, rel)


@pytest.mark.parametrize("n", [5 * 32768, 5 * 32768 + 3456])
def test_segsum_scan_blocked_keeps_its_blocks(n):
    """Each block of 32768 rows, and the rows past the last whole block,
    is one float32 partial, summed in float64 in block order: the result
    is that sum to the bit (on the CPU, whose dot is deterministic)."""
    from dask_sql_tpu.ops.pallas_kernels import segsum_scan_blocked

    domain, block = 12, 32768
    gid, cols, _ = _blocked_case(11, n, 16, domain)
    stack = np.stack(cols)  # [k, n]
    part = jax.jit(lambda g, c: jax.lax.dot_general(
        (jnp.arange(domain)[:, None] == g[None, :]).astype(jnp.float32), c,
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))
    want = np.zeros((domain, len(cols)))
    for s in range(0, n, block):
        want += np.asarray(part(gid[s:s + block], stack[:, s:s + block]),
                           dtype=np.float64)
    got = jax.jit(lambda g, cs: segsum_scan_blocked(g, cs, domain))(
        jnp.asarray(gid), [jnp.asarray(c) for c in cols])
    assert np.array_equal(np.asarray(got), want)


def test_segsum_scan_blocked_no_rows():
    from dask_sql_tpu.ops.pallas_kernels import segsum_scan_blocked

    out = segsum_scan_blocked(jnp.zeros((0,), jnp.int32),
                              [jnp.zeros((0,), jnp.float32)] * 2, 3)
    assert np.array_equal(np.asarray(out), np.zeros((3, 2)))
