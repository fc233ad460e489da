"""TPC-H CUSTOMER, ORDERS and LINEITEM, every column at its width, after
clause 4.2.3: the three tables Q3 joins.

LINEITEM is ``tpch_lineitem.generate``'s, array for array (same seed, same
rows).  ORDERS holds the orders those lines belong to, one row each, on the
sparse order keys, and CUSTOMER is whole for the scale factor (cut with the
lines in a rehearsal: one customer per sixteen lines, which is SF10's 1.5M at
the configuration's 24M).

* ``o_orderdate`` is the very day the lines' ship dates were drawn from:
  ``tpch_lineitem._chunk`` keeps it private, so ``_order_days`` replays the
  head of each chunk's stream (the draws that come before it) and takes it
  out there; ``perfbench/tests`` hold every line's ship date to 1..121 days
  after its order's date, and LINEITEM's arrays to what they were.
* ``o_orderstatus`` and ``o_totalprice`` are derived from the order's lines
  as the clause says (F / O / P by the lines' status; the sum of
  extendedprice * (1 + tax) * (1 - discount), worked out in whole
  millionths).
* ``o_custkey`` is uniform over the customer keys that are no multiple of 3
  (a third of the customers never order).
* The new columns' own draws come from children of ``SeedSequence([seed,
  3])``, so they move nothing of LINEITEM's.  No string is made one Python
  object at a time: fixed-width texts (names, phones, clerks) are written as
  digits into one character buffer, texts of varying length are consecutive
  cuts from a pseudo-text pool, as ``l_comment`` is.

The compact arrays the reference reads: LINEITEM's, and ``o_orderkey``,
``o_orderday`` (day number from 1992-01-01), ``o_custkey``, ``c_segment``
(code per customer, customer key - 1 the index).
"""
from __future__ import annotations

import numpy as np

from perfbench.datagen import tpch_lineitem as li

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")
PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
ORDER_STATUSES = ("F", "O", "P")
POOL_WORDS = 1_000_000       # the pool of this module's texts: about 7 MB
LINES_PER_CUSTOMER = 16      # 24,000,000 lines : SF10's 1,500,000 customers


def _order_days(seed_seq, rows: int) -> np.ndarray:
    """``o_orderdate`` (day numbers) of one chunk's orders: the head of
    ``tpch_lineitem._chunk``'s stream, draw for draw."""
    rng = np.random.default_rng(seed_seq)
    lines = rng.integers(1, 8, rows // 4 + 64, dtype=np.int8)
    while int(lines.sum(dtype=np.int64)) < rows:
        lines = np.concatenate([lines, rng.integers(1, 8, 64, dtype=np.int8)])
    ends = np.cumsum(lines, dtype=np.int64)
    orders = int(np.searchsorted(ends, rows)) + 1
    return rng.integers(0, li.LAST_ORDER_DAY + 1, orders, dtype=np.int32)


def customers_for(rows: int, scale_factor: int) -> int:
    return min(150_000 * int(scale_factor),
               max(int(rows) // LINES_PER_CUSTOMER, 30))


def _cuts(rng, pool: np.ndarray, low: int, high: int, count: int):
    """``count`` consecutive cuts of ``low..high`` characters from the pool,
    read round from a start of their own: (lengths, one character buffer)."""
    lengths = rng.integers(low, high + 1, count, dtype=np.int64)
    start = int(rng.integers(0, len(pool) // 2))
    once = pool[start:start + len(pool) // 2]
    laps, rest = divmod(int(lengths.sum()), len(once))
    return lengths, np.concatenate([once] * laps + [once[:rest]])


def require_whole_build_sides() -> None:
    """Fail now, before a row is drawn, on an engine that cannot serve this
    configuration: one whose join rung does not keep a filtered build side
    whole.  Such an engine (every tree before PR 33) declines the rung at
    TPC-H's key sparsity and answers Q3 from the eager sort-merge join,
    whose first XLA compile at 24M rows had not ended after 320 s on the
    chip (and no key of ``engine_config`` bounds it: the compile watchdog
    covers the rungs' programs only), so the run would hang in set-up until
    killed instead of ending.  Read off the engine's documented counters
    (``docs/observability.md``), not off its internals."""
    from dask_sql_tpu.serving.metrics import DOCUMENTED_METRICS

    if "join.build.whole" not in DOCUMENTED_METRICS:
        raise RuntimeError(
            "this engine cannot run tpch_sf10_q3_tables_1chip: its join "
            "rung keeps no filtered build side whole (no counter "
            "join.build.whole), so Q3 at SF10's sparse order keys would "
            "fall to the eager join and not end inside a run")


def generate(rows: int, seed: int, scale_factor: int = 1) -> dict:
    """Compact columns of the three tables, from ``seed`` alone."""
    require_whole_build_sides()
    arrays = li.generate(rows, seed, scale_factor)
    root = np.random.SeedSequence(int(seed) & (2 ** 64 - 1))
    _, *seqs = root.spawn(li.CHUNKS + 1)
    sizes = np.diff(np.linspace(0, rows, li.CHUNKS + 1).astype(np.int64))
    orderday = np.concatenate([_order_days(seq, int(size))
                               for seq, size in zip(seqs, sizes)])

    # one ORDERS row per order key the lines use (the keys come sorted)
    key = arrays["orderkey"]
    first = np.flatnonzero(np.concatenate([[True], key[1:] != key[:-1]]))
    orders = len(first)
    assert orders == len(orderday), (orders, len(orderday))
    line_order = np.cumsum(np.concatenate([[0], key[1:] != key[:-1]]),
                           dtype=np.int64)
    open_lines = np.bincount(line_order, weights=arrays["status"],
                             minlength=orders)
    lines = np.bincount(line_order, minlength=orders)
    # millionths: cents * (100 + tax%) * (100 - discount%)
    charge = (arrays["price_cents"].astype(np.int64)
              * (100 + arrays["tax_pct"].astype(np.int64))
              * (100 - arrays["discount_pct"].astype(np.int64)))
    total = np.bincount(line_order, weights=charge, minlength=orders)

    customers = customers_for(rows, scale_factor)
    own = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), 3])
    pool_seq, order_seq, customer_seq = own.spawn(3)
    words = np.array(li.WORDS, dtype=object)
    picks = np.random.default_rng(pool_seq).integers(0, len(words), POOL_WORDS)
    pool = np.frombuffer(" ".join(words[picks].tolist()).encode("ascii"),
                         dtype=np.uint8)
    pool = np.concatenate([pool, pool])

    rng = np.random.default_rng(order_seq)
    ordering = customers - customers // 3   # keys that are no multiple of 3
    pick = rng.integers(0, ordering, orders, dtype=np.int64)
    arrays["o_orderkey"] = key[first]
    arrays["o_custkey"] = 3 * (pick // 2) + pick % 2 + 1
    arrays["o_orderday"] = orderday
    arrays["o_status"] = np.where(open_lines == 0, 0,
                                  np.where(open_lines == lines, 1, 2)
                                  ).astype(np.int8)
    arrays["o_total_micro"] = total.astype(np.int64)
    arrays["o_priority"] = rng.integers(0, len(PRIORITIES), orders,
                                        dtype=np.int8)
    arrays["o_clerk"] = rng.integers(1, 1000 * int(scale_factor) + 1, orders,
                                     dtype=np.int32)
    arrays["o_comment_length"], arrays["o_comment_text"] = _cuts(
        rng, pool, 19, 78, orders)

    rng = np.random.default_rng(customer_seq)
    arrays["c_nation"] = rng.integers(0, 25, customers, dtype=np.int8)
    arrays["c_phone_digits"] = rng.integers(0, 10, (customers, 10),
                                            dtype=np.uint8)
    arrays["c_phone_digits"][:, (0, 3, 6)] = rng.integers(
        1, 10, (customers, 3), dtype=np.uint8)  # 100..999, 1000..9999
    arrays["c_acctbal_cents"] = rng.integers(-99_999, 1_000_000, customers,
                                             dtype=np.int32)
    arrays["c_segment"] = rng.integers(0, len(SEGMENTS), customers,
                                       dtype=np.int8)
    arrays["c_address_length"], arrays["c_address_text"] = _cuts(
        rng, pool, 10, 40, customers)
    arrays["c_comment_length"], arrays["c_comment_text"] = _cuts(
        rng, pool, 29, 116, customers)
    return arrays


def _text(lengths: np.ndarray, text: np.ndarray):
    import pyarrow as pa

    offsets = np.zeros(len(lengths) + 1, dtype=np.int64)
    np.cumsum(lengths, dtype=np.int64, out=offsets[1:])
    return pa.LargeStringArray.from_buffers(
        len(lengths), pa.py_buffer(offsets), pa.py_buffer(text))


def _numbered(prefix: str, numbers: np.ndarray, digits: int):
    """``prefix`` + the number in ``digits`` digits, as one character buffer
    (``Customer#000000001``, ``Clerk#000000951``)."""
    head = np.frombuffer(prefix.encode("ascii"), dtype=np.uint8)
    width = len(head) + digits
    out = np.empty((len(numbers), width), dtype=np.uint8)
    out[:, :len(head)] = head
    rest = numbers.astype(np.int64)
    for place in range(width - 1, len(head) - 1, -1):
        out[:, place] = 48 + rest % 10
        rest = rest // 10
    return _text(np.full(len(numbers), width, dtype=np.int64), out.ravel())


def _phones(nation: np.ndarray, digits: np.ndarray):
    """``CC-LLL-LLL-LLLL``, CC the nation key + 10 (clause 4.2.2.9)."""
    out = np.full((len(nation), 15), ord("-"), dtype=np.uint8)
    country = nation.astype(np.int64) + 10
    out[:, 0] = 48 + country // 10
    out[:, 1] = 48 + country % 10
    out[:, [3, 4, 5, 7, 8, 9, 11, 12, 13, 14]] = 48 + digits
    return _text(np.full(len(nation), 15, dtype=np.int64), out.ravel())


#: table -> column -> how it is made from the compact arrays, in the
#: schema's order (clause 1.4.1)
ORDERS = {
    "o_orderkey": lambda a: a["o_orderkey"],
    "o_custkey": lambda a: a["o_custkey"],
    "o_orderstatus": lambda a: li._by_code(a["o_status"], ORDER_STATUSES),
    "o_totalprice": lambda a: a["o_total_micro"] / 1e6,
    "o_orderdate": lambda a: li._seconds(a["o_orderday"]),
    "o_orderpriority": lambda a: li._by_code(a["o_priority"], PRIORITIES),
    "o_clerk": lambda a: _numbered("Clerk#", a["o_clerk"], 9),
    "o_shippriority": lambda a: np.zeros(len(a["o_orderkey"]),
                                         dtype=np.int64),
    "o_comment": lambda a: _text(a["o_comment_length"], a["o_comment_text"]),
}
CUSTOMER = {
    "c_custkey": lambda a: np.arange(1, len(a["c_segment"]) + 1,
                                     dtype=np.int64),
    "c_name": lambda a: _numbered(
        "Customer#", np.arange(1, len(a["c_segment"]) + 1), 9),
    "c_address": lambda a: _text(a["c_address_length"], a["c_address_text"]),
    "c_nationkey": lambda a: a["c_nation"].astype(np.int64),
    "c_phone": lambda a: _phones(a["c_nation"], a["c_phone_digits"]),
    "c_acctbal": lambda a: a["c_acctbal_cents"] / 100.0,
    "c_mktsegment": lambda a: li._by_code(a["c_segment"], SEGMENTS),
    "c_comment": lambda a: _text(a["c_comment_length"], a["c_comment_text"]),
}


def arrow_tables(arrays: dict) -> dict:
    """The three tables as pyarrow tables, as a parquet reader hands them
    over: strings plain (not dictionary-encoded), dates ``timestamp[s]``."""
    import pyarrow as pa

    def table(columns):
        built = {name: make(arrays) for name, make in columns.items()}
        return pa.table({name: pa.array(col) if isinstance(col, np.ndarray)
                         else col for name, col in built.items()})

    return {"customer": table(CUSTOMER), "orders": table(ORDERS),
            **li.arrow_tables(arrays)}


def frames(arrays: dict) -> dict:
    """The same tables as pandas frames (strings as pandas makes them)."""
    return {name: table.to_pandas()
            for name, table in arrow_tables(arrays).items()}
