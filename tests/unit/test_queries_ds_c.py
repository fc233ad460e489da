"""TPC-DS runner, shard 2 of 3: every third query of
`tests/unit/test_queries_ds.py`, in a file of its own so that xdist's
``--dist loadfile`` can give it to another worker (see N_SHARDS there)."""
import pytest

from tests.unit.test_queries_ds import (  # noqa: F401 — fixtures by name
    _params,
    check_query,
    duckdb_oracle,
    sqlite_oracle,
    tpcds_context,
    tpcds_tables,
)


@pytest.mark.parametrize("qnum", _params(2))
def test_query(tpcds_context, tpcds_tables, sqlite_oracle, duckdb_oracle,
               qnum):
    check_query(tpcds_context, tpcds_tables, sqlite_oracle, duckdb_oracle,
                qnum)
