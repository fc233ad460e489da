"""Test harness configuration.

Parity with the reference's strategy (SURVEY.md §4): fixture Context with
small golden frames, assert-vs-pandas equality, a distributed-mode switch.
Runs on the CPU backend with 8 virtual devices so multi-chip sharding tests
(`tests/integration/test_distributed.py`) exercise real collectives without
TPU hardware.
"""
import os
import sys

# Must happen before jax initializes a backend: force CPU + virtual 8-device
# mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import numpy as np
import pandas as pd
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The lock sanitizer (runtime/locks.py, ISSUE 19) is ON for the whole
# suite: every test thread's lock acquisitions feed the process-global
# order graph, and any rank inversion / cycle raises LockOrderError at
# the acquire instead of deadlocking a worker.  Set both the config
# default (so Contexts built from config agree) and the module switch
# (so locks taken before the first Context exists are sanitized too).
from dask_sql_tpu import config as _config_module
from dask_sql_tpu.runtime import locks as _runtime_locks

_config_module.config.update({"analysis.lock_sanitizer": True})
_runtime_locks.set_enabled(True)


@pytest.fixture
def df_simple():
    return pd.DataFrame({"a": [1, 2, 3], "b": [1.1, 2.2, 3.3]})


@pytest.fixture
def df():
    np.random.seed(42)
    return pd.DataFrame(
        {"a": [1.0] * 100 + [2.0] * 200 + [3.0] * 400, "b": 10 * np.random.rand(700)}
    )


@pytest.fixture
def user_table_1():
    return pd.DataFrame({"user_id": [2, 1, 2, 3], "b": [3, 3, 1, 3]})


@pytest.fixture
def user_table_2():
    return pd.DataFrame({"user_id": [1, 1, 2, 4], "c": [1, 2, 3, 4]})


@pytest.fixture
def long_table():
    return pd.DataFrame({"a": [0] * 100 + [1] * 101 + [2] * 103})


@pytest.fixture
def user_table_inf():
    return pd.DataFrame({"c": [3, float("inf"), 1]})


@pytest.fixture
def user_table_nan():
    return pd.DataFrame({"c": [3.0, float("nan"), 1.0]})


@pytest.fixture
def string_table():
    return pd.DataFrame({"a": ["a normal string", "%_%", "^|()-*[]$"]})


@pytest.fixture
def datetime_table():
    return pd.DataFrame(
        {
            "timezone": pd.date_range(start="2014-08-01 09:00", freq="8h", periods=6),
            "no_timezone": pd.date_range(start="2014-08-01 09:00", freq="8h", periods=6),
            "utc_timezone": pd.date_range(start="2014-08-01 09:00", freq="8h", periods=6),
        }
    )


@pytest.fixture
def user_table_lk():
    out = pd.DataFrame(
        [[0, 1, 2, 3], [1, 1, 3, 3], [2, 2, 3, 3], [1, None, 1, 3]],
        columns=["b", "k", "c", "d"],
    )
    return out


@pytest.fixture
def c(
    df_simple,
    df,
    user_table_1,
    user_table_2,
    long_table,
    user_table_inf,
    user_table_nan,
    string_table,
    datetime_table,
    user_table_lk,
):
    from dask_sql_tpu import Context

    tables = {
        "df_simple": df_simple,
        "df": df,
        "user_table_1": user_table_1,
        "user_table_2": user_table_2,
        "long_table": long_table,
        "user_table_inf": user_table_inf,
        "user_table_nan": user_table_nan,
        "string_table": string_table,
        "datetime_table": datetime_table,
        "user_table_lk": user_table_lk,
    }
    # DSQL_DISTRIBUTED_TESTS=1 runs the same suite with every fixture table
    # sharded over the virtual device mesh (parity: the reference's
    # DASK_SQL_DISTRIBUTED_TESTS switch, tests/utils.py:8-12 there)
    import jax as _jax

    distributed = os.environ.get("DSQL_DISTRIBUTED_TESTS", "") == "1"
    if distributed and len(_jax.devices()) < 2:
        pytest.exit(
            "DSQL_DISTRIBUTED_TESTS=1 requires a multi-device mesh; only one "
            "device is visible (virtual-device XLA flags did not take effect)",
            returncode=3)
    ctx = Context()
    for name, frame in tables.items():
        ctx.create_table(name, frame, distributed=distributed)
    return ctx


@pytest.fixture
def temporary_data_file(tmp_path):
    return str(tmp_path / "data.parquet")


@pytest.fixture
def assert_query_gives_same_result(c):
    """Differential oracle vs sqlite (parity: reference eq_sqlite /
    assert_query_gives_same_result fixtures)."""
    import sqlite3

    from tests.utils import assert_eq

    def _assert(query, sort_columns=None, **kwargs):
        import pandas as pd

        conn = sqlite3.connect(":memory:")
        for schema in c.schema.values():
            for name, dc in schema.tables.items():
                try:
                    dc.assign().to_pandas().to_sql(name, conn, index=False)
                except Exception:
                    pass
        expected = pd.read_sql_query(query, conn)
        got = c.sql(query, return_futures=False)
        if sort_columns:
            expected = expected.sort_values(sort_columns).reset_index(drop=True)
            got = got.sort_values(sort_columns).reset_index(drop=True)
        assert_eq(got, expected, check_dtype=False, **kwargs)

    return _assert
