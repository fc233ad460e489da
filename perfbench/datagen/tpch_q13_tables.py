"""TPC-H CUSTOMER and ORDERS, every column at its width, after clause 4.2.3:
the two tables Q13 joins, ORDERS whole and drawn without LINEITEM.

CUSTOMER is drawn as ``tpch_q3_tables`` draws it, and both tables are made
by its column recipes (``tpch_q3_tables.CUSTOMER``, ``.ORDERS``) from the
same compact arrays, so their formats are that module's letter for letter.

* ``o_orderkey``: the clause's sparse keys, the first 8 of every 32.
* ``o_custkey``: uniform over the customer keys that are no multiple of 3,
  as ``tpch_q3_tables`` draws it: a third of the customers never order.
* ``o_comment``: 19..78 characters, consecutive cuts of a pseudo-text pool
  of the clause's words (``tpch_lineitem.WORDS``), as ``tpch_q3_tables``
  cuts them.  The words come uniformly, not by dbgen's sentence grammar
  (cl.4.2.2.10), so a Q13 pattern (WORD1 then WORD2) holds for some 0.05%
  of the comments.
* ``o_orderdate``, ``o_totalprice`` and ``o_orderstatus`` are drawn, not
  derived from lines (there are none): the date uniform over the clause's
  range, the price 1,000.00 .. 500,000.00, the status one of F / O / P.
  Q13 reads none of them.
* Customers: SF's 150,000 a scale factor; a rehearsal's cut of ``rows``
  orders keeps one customer per ten orders, SF10's 15,000,000 : 1,500,000.

The draws come from children of ``SeedSequence([seed, 13])``.  Before a row
is drawn, ``require_outer_joins`` checks the engine's documented counters:
an engine whose join rung takes no LEFT OUTER join answers Q13 from the
eager sort-merge join, whose first XLA compile at 24M rows had not ended
after 320 s on the chip, and such an engine fails here, in seconds.

The compact arrays the reference reads: ``o_custkey``,
``o_comment_length`` and ``o_comment_text`` (the comments' characters, row
after row), and ``c_segment`` (one a customer, customer key - 1 the
index).
"""
from __future__ import annotations

import numpy as np

from perfbench.datagen import tpch_lineitem as li
from perfbench.datagen import tpch_q3_tables as q3

ORDERS_PER_CUSTOMER = 10     # SF10: 15,000,000 orders : 1,500,000 customers


def require_outer_joins() -> None:
    """Fail now, before a row is drawn, on an engine that cannot serve this
    configuration: one whose join rung takes no LEFT OUTER join.  Read off
    the engine's documented counters (``docs/observability.md``), not off
    its internals."""
    from dask_sql_tpu.serving.metrics import DOCUMENTED_METRICS

    if "join.outer.preserved" not in DOCUMENTED_METRICS:
        raise RuntimeError(
            "this engine cannot run tpch_sf10_q13_tables_1chip: its join "
            "rung takes no LEFT OUTER join (no counter join.outer.preserved), "
            "so Q13 at SF10 would fall to the eager join and not end inside "
            "a run")


def customers_for(rows: int, scale_factor: int) -> int:
    return min(150_000 * int(scale_factor),
               max(int(rows) // ORDERS_PER_CUSTOMER, 30))


def _customers(rng, pool: np.ndarray, customers: int) -> dict:
    """CUSTOMER's compact arrays, drawn as ``tpch_q3_tables.generate``
    draws them."""
    out = {"c_nation": rng.integers(0, 25, customers, dtype=np.int8)}
    digits = rng.integers(0, 10, (customers, 10), dtype=np.uint8)
    digits[:, (0, 3, 6)] = rng.integers(1, 10, (customers, 3), dtype=np.uint8)
    out["c_phone_digits"] = digits
    out["c_acctbal_cents"] = rng.integers(-99_999, 1_000_000, customers,
                                          dtype=np.int32)
    out["c_segment"] = rng.integers(0, len(q3.SEGMENTS), customers,
                                    dtype=np.int8)
    out["c_address_length"], out["c_address_text"] = q3._cuts(
        rng, pool, 10, 40, customers)
    out["c_comment_length"], out["c_comment_text"] = q3._cuts(
        rng, pool, 29, 116, customers)
    return out


def generate(rows: int, seed: int, scale_factor: int = 1) -> dict:
    """Compact columns of the two tables, ``rows`` orders, from ``seed``
    alone."""
    require_outer_joins()
    orders = int(rows)
    customers = customers_for(orders, scale_factor)
    own = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), 13])
    pool_seq, order_seq, customer_seq = own.spawn(3)
    words = np.array(li.WORDS, dtype=object)
    picks = np.random.default_rng(pool_seq).integers(0, len(words),
                                                     q3.POOL_WORDS)
    pool = np.frombuffer(" ".join(words[picks].tolist()).encode("ascii"),
                         dtype=np.uint8)
    pool = np.concatenate([pool, pool])

    rng = np.random.default_rng(order_seq)
    index = np.arange(orders, dtype=np.int64)
    ordering = customers - customers // 3   # keys that are no multiple of 3
    pick = rng.integers(0, ordering, orders, dtype=np.int64)
    arrays = {
        "o_orderkey": (index // 8) * 32 + index % 8 + 1,
        "o_custkey": 3 * (pick // 2) + pick % 2 + 1,
        "o_orderday": rng.integers(0, li.LAST_ORDER_DAY + 1, orders,
                                   dtype=np.int32),
        "o_status": rng.integers(0, len(q3.ORDER_STATUSES), orders,
                                 dtype=np.int8),
        "o_total_micro": rng.integers(1_000_000_000, 500_000_000_001,
                                      orders, dtype=np.int64),
        "o_priority": rng.integers(0, len(q3.PRIORITIES), orders,
                                   dtype=np.int8),
        "o_clerk": rng.integers(1, 1000 * int(scale_factor) + 1, orders,
                                dtype=np.int32),
    }
    arrays["o_comment_length"], arrays["o_comment_text"] = q3._cuts(
        rng, pool, 19, 78, orders)
    arrays.update(_customers(np.random.default_rng(customer_seq), pool,
                             customers))
    return arrays


def arrow_tables(arrays: dict) -> dict:
    """The two tables as pyarrow tables, as a parquet reader hands them
    over: strings plain (not dictionary-encoded), dates ``timestamp[s]``."""
    import pyarrow as pa

    def table(columns):
        built = {name: make(arrays) for name, make in columns.items()}
        return pa.table({name: pa.array(col) if isinstance(col, np.ndarray)
                         else col for name, col in built.items()})

    return {"customer": table(q3.CUSTOMER), "orders": table(q3.ORDERS)}


def frames(arrays: dict) -> dict:
    """The same tables as pandas frames (strings as pandas makes them)."""
    return {name: table.to_pandas()
            for name, table in arrow_tables(arrays).items()}
