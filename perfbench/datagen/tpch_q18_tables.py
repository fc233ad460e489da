"""TPC-H CUSTOMER, ORDERS and LINEITEM for Q18: ``tpch_q3_tables``' arrays
and tables, unchanged (same seed, same rows, same columns), behind one check
of the engine.

Q18's HAVING is a semi-join whose build side is an aggregate over all of
LINEITEM.  An engine whose join rung does not reduce such a build side
inside its program declines the rung and answers from the eager sort-merge
join, whose first XLA compile at 24M rows PR 33 watched for 320 s on the
chip without an end: the run would sit in set-up until it is killed.  So
``generate`` fails first, in seconds, on such an engine.
"""
from __future__ import annotations

from perfbench.datagen import tpch_q3_tables

arrow_tables = tpch_q3_tables.arrow_tables
frames = tpch_q3_tables.frames


def require_semi_join_builds() -> None:
    """Fail now, before a row is drawn, on an engine that cannot serve this
    configuration: one whose join rung has no semi-join build side (every
    tree before PR 35).  Read off the engine's documented counters
    (``docs/observability.md``), not off its internals."""
    from dask_sql_tpu.serving.metrics import DOCUMENTED_METRICS

    if "join.build.semi" not in DOCUMENTED_METRICS:
        raise RuntimeError(
            "this engine cannot run tpch_sf10_q18_tables_1chip: its join "
            "rung reduces no semi-join build side in its program (no "
            "counter join.build.semi), so Q18 at SF10's sparse order keys "
            "would fall to the eager join and not end inside a run")


def generate(rows: int, seed: int, scale_factor: int = 1) -> dict:
    """``tpch_q3_tables.generate``'s arrays, from ``seed`` alone."""
    require_semi_join_builds()
    return tpch_q3_tables.generate(rows, seed, scale_factor)
