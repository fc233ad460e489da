"""TPC-H LINEITEM, all sixteen columns at their widths, after clause 4.2.3.

A vectorised numpy generator, not ``dbgen``: the same value domains and the
same derived columns (clause 4.2.3), orders of 1..7 lines on the sparse order
keys, comments cut from a pseudo-text pool (clause 4.2.2.10).

* Rows are drawn in ``CHUNKS`` independent streams (children of one
  ``SeedSequence(seed)``), filled by a few threads: the data depends on the
  seed alone, never on how many threads filled it.
* The columns Q1 and Q6 read are kept as compact integers (cents, hundredths,
  day numbers, a group code) for the reference; what is handed to
  ``Context.create_table`` is built from them last, as a pandas frame
  (``frames``) or a pyarrow table (``arrow_tables``): a configuration's
  ``input`` says which.
* No string is made one Python object at a time: the low-cardinality columns
  are taken from a few values by code, and ``l_comment`` is one character
  buffer with offsets (consecutive cuts of 10..43 characters from the pool).

DECIMAL columns are float64 (the engine's DECIMAL), identifiers and integers
int64 (what pandas and parquet readers give), dates seconds since the epoch.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNKS = 16              # fixed: part of what a seed means
THREADS = 8
POOL_WORDS = 4_000_000   # the comment pool: about 29 MB of text

DAY0 = np.datetime64("1992-01-01")
CURRENT_DAY = int((np.datetime64("1995-06-17") - DAY0).astype(np.int64))
#: o_orderdate is uniform in [STARTDATE, ENDDATE - 151 days]; the smoke
#: subtracts the 151 days twice (its last ship date is 1998-07-04, so every
#: DELTA of Q1 selects every row); here the last ship date is 1998-12-01
LAST_ORDER_DAY = int((np.datetime64("1998-12-31") - DAY0).astype(np.int64)) - 151
#: group code of Q1 = FLAG code * 2 + STATUS code, in its ORDER BY order
FLAGS = ("A", "N", "R")
STATUSES = ("F", "O")
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
#: clause 4.2.2.13, a part of each word class
WORDS = ("foxes ideas theodolites pinto beans instructions dependencies "
         "excuses platelets asymptotes courts dolphins multipliers sauternes "
         "warthogs frets dinos attainments somas Tiresias' patterns forges "
         "braids hockey players frays warhorses dugouts notornis epitaphs "
         "pearls tithes waters orbits gifts sheaves depths sentiments "
         "decoys realms pains grouches escapades packages requests accounts "
         "deposits sleep wake are cajole haggle nag use boost affix detect "
         "integrate maintain nod was lose sublate solve thrash promise "
         "engage hinder print x-ray breach eat grow impress mold poach "
         "serve run dazzle snooze doze unwind kindle play hang believe doubt "
         "furious sly careful blithe quick fluffy slow quiet ruthless thin "
         "close dogged daring brave stealthy permanent enticing idle busy "
         "regular final ironic even bold silent special pending unusual "
         "express sometimes always never furiously slyly carefully blithely "
         "quickly fluffily slowly quietly ruthlessly thinly closely doggedly "
         "daringly bravely stealthily permanently enticingly idly busily "
         "regularly finally ironically evenly boldly silently about above "
         "according to across after against along alongside of among around "
         "at atop before behind beneath beside besides between beyond by "
         "despite during except for from in place of inside instead of into "
         "near of on outside over past since through throughout to toward "
         "under until up upon without with within the . ; : ? ! --").split()


def _chunk(seed_seq, rows: int, scale_factor: int, pool: np.ndarray):
    rng = np.random.default_rng(seed_seq)
    # orders of 1..7 lines (four on average), cut to the chunk's rows
    lines = rng.integers(1, 8, rows // 4 + 64, dtype=np.int8)
    while int(lines.sum(dtype=np.int64)) < rows:
        lines = np.concatenate([lines, rng.integers(1, 8, 64, dtype=np.int8)])
    ends = np.cumsum(lines, dtype=np.int64)
    orders = int(np.searchsorted(ends, rows)) + 1
    order = np.repeat(np.arange(orders, dtype=np.int32), lines[:orders])[:rows]
    starts = ends[:orders] - lines[:orders]
    # o_orderdate uniform in [STARTDATE, ENDDATE - 151 days] = day 0..2405
    orderdate = rng.integers(0, LAST_ORDER_DAY + 1, orders,
                             dtype=np.int32)[order]
    shipday = orderdate + rng.integers(1, 122, rows, dtype=np.int32)
    receipt = shipday + rng.integers(1, 31, rows, dtype=np.int32)
    coin = rng.integers(0, 2, rows, dtype=np.int8)
    # R or A by the coin where the receipt is in the past, else N
    flag = np.where(receipt <= CURRENT_DAY, np.where(coin == 0, 2, 0),
                    1).astype(np.int8)
    quantity = rng.integers(1, 51, rows, dtype=np.int32)
    partkey = rng.integers(1, 200_000 * scale_factor + 1, rows,
                           dtype=np.int32)
    # p_retailprice (clause 4.2.3), in cents
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    suppliers = 10_000 * scale_factor
    hop = rng.integers(0, 4, rows, dtype=np.int64)
    suppkey = (partkey + hop * (suppliers // 4 + (partkey - 1) // suppliers)
               ) % suppliers + 1
    lengths = rng.integers(10, 44, rows, dtype=np.int64)
    # consecutive cuts from the pool, read round from a start of its own
    start = int(rng.integers(0, len(pool) // 2))
    once = pool[start:start + len(pool) // 2]
    laps, rest = divmod(int(lengths.sum()), len(once))
    text = np.concatenate([once] * laps + [once[:rest]])
    return {
        "order": order,
        "orders": orders,
        "linenumber": (np.arange(rows, dtype=np.int64) - starts[order] + 1
                       ).astype(np.int8),
        "partkey": partkey,
        "suppkey": suppkey.astype(np.int32),
        "flag": flag,
        "status": (shipday > CURRENT_DAY).astype(np.int8),
        "quantity": quantity.astype(np.int8),
        "price_cents": quantity * retail,  # at most 50 * 209,900: int32
        "discount_pct": rng.integers(0, 11, rows, dtype=np.int8),
        "tax_pct": rng.integers(0, 9, rows, dtype=np.int8),
        "shipday": shipday.astype(np.int16),
        "commitday": (orderdate + rng.integers(30, 91, rows, dtype=np.int32)
                      ).astype(np.int16),
        "receiptday": receipt.astype(np.int16),
        "instruct": rng.integers(0, len(INSTRUCTIONS), rows, dtype=np.int8),
        "mode": rng.integers(0, len(MODES), rows, dtype=np.int8),
        "comment_length": lengths.astype(np.int8),
        "comment_text": text,
    }


def generate(rows: int, seed: int, scale_factor: int = 1) -> dict:
    """Compact columns of ``rows`` lineitems, from ``seed`` alone."""
    root = np.random.SeedSequence(int(seed) & (2 ** 64 - 1))
    pool_seq, *seqs = root.spawn(CHUNKS + 1)
    words = np.array(WORDS, dtype=object)
    picks = np.random.default_rng(pool_seq).integers(0, len(words), POOL_WORDS)
    pool = np.frombuffer(" ".join(words[picks].tolist()).encode("ascii"),
                         dtype=np.uint8)
    pool = np.concatenate([pool, pool])  # any start reads one whole round
    sizes = np.diff(np.linspace(0, rows, CHUNKS + 1).astype(np.int64))
    with ThreadPoolExecutor(THREADS) as pool_of_threads:
        parts = list(pool_of_threads.map(
            lambda seq, size: _chunk(seq, int(size), int(scale_factor), pool),
            seqs, sizes))
    first = np.cumsum([0] + [p.pop("orders") for p in parts])
    for part, base in zip(parts, first):
        # clause 4.2.3: only the first 8 of every 32 order keys are used
        index = part.pop("order").astype(np.int64) + int(base)
        part["orderkey"] = (index // 8) * 32 + index % 8 + 1
    return {name: np.concatenate([p[name] for p in parts])
            for name in parts[0]}


def _comments(arrays: dict):
    import pyarrow as pa

    rows = len(arrays["comment_length"])
    offsets = np.zeros(rows + 1, dtype=np.int64)
    np.cumsum(arrays["comment_length"], dtype=np.int64, out=offsets[1:])
    return pa.LargeStringArray.from_buffers(
        rows, pa.py_buffer(offsets), pa.py_buffer(arrays["comment_text"]))


def _by_code(codes: np.ndarray, values):
    import pyarrow as pa

    return pa.DictionaryArray.from_arrays(
        pa.array(codes), pa.array(list(values), type=pa.large_string())
    ).cast(pa.large_string())


def _seconds(days: np.ndarray) -> np.ndarray:
    day0_s = int(DAY0.astype("datetime64[s]").astype(np.int64))
    return (days.astype(np.int64) * 86400 + day0_s).view("datetime64[s]")


#: column -> how it is made from the compact arrays, in the schema's order
#: (clause 1.4.1); strings come as pyarrow large_string arrays
COLUMNS = {
    "l_orderkey": lambda a: a["orderkey"],
    "l_partkey": lambda a: a["partkey"].astype(np.int64),
    "l_suppkey": lambda a: a["suppkey"].astype(np.int64),
    "l_linenumber": lambda a: a["linenumber"].astype(np.int64),
    "l_quantity": lambda a: a["quantity"].astype(np.float64),
    "l_extendedprice": lambda a: a["price_cents"] / 100.0,
    "l_discount": lambda a: a["discount_pct"] / 100.0,
    "l_tax": lambda a: a["tax_pct"] / 100.0,
    "l_returnflag": lambda a: _by_code(a["flag"], FLAGS),
    "l_linestatus": lambda a: _by_code(a["status"], STATUSES),
    "l_shipdate": lambda a: _seconds(a["shipday"]),
    "l_commitdate": lambda a: _seconds(a["commitday"]),
    "l_receiptdate": lambda a: _seconds(a["receiptday"]),
    "l_shipinstruct": lambda a: _by_code(a["instruct"], INSTRUCTIONS),
    "l_shipmode": lambda a: _by_code(a["mode"], MODES),
    "l_comment": _comments,
}


def _built(arrays: dict) -> dict:
    with ThreadPoolExecutor(THREADS) as pool:
        return dict(zip(COLUMNS, pool.map(lambda make: make(arrays),
                                          COLUMNS.values())))


def frames(arrays: dict) -> dict:
    """The tables as pandas frames with the schema's own types (``str``,
    float64 DECIMALs, int64, ``datetime64[s]``)."""
    import pandas as pd

    str_dtype = pd.Series(["a"]).dtype  # what pandas makes of Python strings
    return {"lineitem": pd.DataFrame(
        {name: col if isinstance(col, np.ndarray)
         else pd.array(col, dtype=str_dtype)
         for name, col in _built(arrays).items()}, copy=False)}


def arrow_tables(arrays: dict) -> dict:
    """The same tables as pyarrow tables, as a parquet reader hands them
    over: strings plain (not dictionary-encoded), dates ``timestamp[s]``."""
    import pyarrow as pa

    return {"lineitem": pa.table(
        {name: pa.array(col) if isinstance(col, np.ndarray) else col
         for name, col in _built(arrays).items()})}
