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


def test_segsum_scan_blocked_accuracy_and_counts():
    from dask_sql_tpu.ops.pallas_kernels import (
        MATMUL_FLOAT_REL_ERR_BOUND,
        segsum_scan_blocked,
        split_hi_lo,
    )

    rng = np.random.RandomState(7)
    n, domain = 200_000, 16
    gid = jnp.asarray(rng.randint(0, domain, n).astype(np.int32))
    x64 = jnp.asarray(rng.rand(n) * 1e9 + 0.123456789)
    mask = jnp.asarray(rng.rand(n) < 0.8)
    hi, lo = split_hi_lo(jnp.where(mask, x64, 0.0))
    cols = [mask.astype(jnp.float32), hi, lo]
    out = segsum_scan_blocked(gid, cols, domain, block=8192)
    # counts: EXACT (integer-valued f32 block partials, f64 combine)
    cnt_exact = np.zeros(domain)
    np.add.at(cnt_exact, np.asarray(gid), np.asarray(mask).astype(np.float64))
    assert np.array_equal(np.asarray(out[:, 0]), cnt_exact)
    # float sums: within the stated bound of the exact f64 result
    s_exact = np.zeros(domain)
    np.add.at(s_exact, np.asarray(gid),
              np.where(np.asarray(mask), np.asarray(x64), 0.0))
    got = np.asarray(out[:, 1] + out[:, 2])
    rel = np.max(np.abs(got - s_exact) / np.maximum(np.abs(s_exact), 1e-30))
    assert rel < MATMUL_FLOAT_REL_ERR_BOUND, rel
