"""TPC-H PART, SUPPLIER, PARTSUPP, NATION, ORDERS and LINEITEM, every column
at its width, after clause 4.2.3: the six tables Q9 joins.

ORDERS and LINEITEM are ``tpch_q3_tables.generate``'s arrays and tables,
unchanged (same seed, same rows; CUSTOMER is left out, Q9 reads it not).

* PART: ``p_name`` is five distinct words of the clause's 92, a space
  between them, drawn per part; ``p_retailprice`` is the clause's formula,
  the one ``tpch_lineitem._chunk`` prices lines with, so a line's
  ``l_extendedprice / l_quantity`` is its part's price.  Whole for the
  scale factor (2,000,000 parts at SF10; `generate` says how a rehearsal
  cut keeps its twelve lines a part).
* PARTSUPP: the clause's four suppliers a part, ``(partkey + i * (S / 4 +
  (partkey - 1) / S)) mod S + 1`` for i in 0..3, the formula
  ``tpch_lineitem._chunk`` draws ``l_suppkey`` from, so every line's
  (part, supplier) pair is a PARTSUPP row.
* SUPPLIER whole (10,000 a scale factor; in a cut the suppliers of the
  cut's parts), NATION the clause's 25.
* The new columns' draws come from children of ``SeedSequence([seed, 9])``:
  they move nothing of ORDERS' or LINEITEM's.  No string is made one Python
  object at a time (names are words copied into one character buffer,
  comments cuts of a pseudo-text pool, as ``tpch_q3_tables`` makes them).

Before a row is drawn, ``require_composite_builds`` checks the engine's
documented counters: Q9 joins PARTSUPP on (suppkey, partkey), and an engine
whose join rung keeps no build side on a two-column key answers from the
eager sort-merge join, whose first XLA compile at 24M rows had not ended
after 320 s on the chip.  Such an engine fails here, in seconds.

The compact arrays the reference reads beside ``tpch_q3_tables``': ``p_key``
(PART's keys, sorted), ``p_words`` (``[parts, 5]`` word indices of
``p_name``), ``ps_supp`` (``[parts, 4]`` supplier keys, the formula's) and
``ps_cost_cents`` (the same rows flat), ``s_key`` (SUPPLIER's keys, sorted)
and ``s_nation`` (nation key per supplier).
"""
from __future__ import annotations

import numpy as np

from perfbench.datagen import tpch_lineitem as li
from perfbench.datagen import tpch_q3_tables as q3

#: clause 4.2.3: the words P_NAME is made of, and COLOR drawn from
#: (cl.2.4.9.3)
COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()
assert len(COLORS) == 92
#: clause 4.2.3: N_NAME and N_REGIONKEY of the 25 nations, key the index
NATIONS = (
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2),
    ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0),
    ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1))
TYPE_SYLLABLES = (("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"),
                  ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"),
                  ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER"))
CONTAINER_SYLLABLES = (("SM", "LG", "MED", "JUMBO", "WRAP"),
                       ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM"))
SUPPLIERS_PER_PART = 4
LINES_PER_PART = 12          # 24,000,000 lines : SF10's 2,000,000 parts
POOL_WORDS = 1_000_000


def require_composite_builds() -> None:
    """Fail now, before a row is drawn, on an engine that cannot serve this
    configuration: one whose join rung keeps no build side joined on a
    two-column key.  Read off the engine's
    documented counters (``docs/observability.md``), not off its
    internals."""
    from dask_sql_tpu.serving.metrics import DOCUMENTED_METRICS

    if "join.build.composite" not in DOCUMENTED_METRICS:
        raise RuntimeError(
            "this engine cannot run tpch_sf10_q9_tables_1chip: its join "
            "rung keeps no build side on a two-column key (no counter "
            "join.build.composite), so Q9's PARTSUPP join would fall to the "
            "eager join and not end inside a run")


def part_suppliers(partkey: np.ndarray, scale_factor: int) -> np.ndarray:
    """``[parts, 4]`` supplier keys of each part, the clause's formula."""
    suppliers = 10_000 * int(scale_factor)
    hop = np.arange(SUPPLIERS_PER_PART, dtype=np.int64)
    key = partkey.astype(np.int64)[:, None]
    return (key + hop * (suppliers // 4 + (key - 1) // suppliers)
            ) % suppliers + 1


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """``p_retailprice`` in cents (clause 4.2.3)."""
    key = partkey.astype(np.int64)
    return 90000 + (key // 10) % 20001 + 100 * (key % 1000)


def _distinct_words(rng, parts: int) -> np.ndarray:
    """``[parts, 5]`` word indices, five distinct words a part."""
    words = rng.integers(0, len(COLORS), (parts, 5), dtype=np.int16)
    while True:
        ordered = np.sort(words, axis=1)
        again = np.flatnonzero((ordered[:, 1:] == ordered[:, :-1]).any(1))
        if not len(again):
            return words
        words[again] = rng.integers(0, len(COLORS), (len(again), 5),
                                    dtype=np.int16)


def _fold_lines(arrays: dict, p_key: np.ndarray, scale_factor: int) -> None:
    """A rehearsal cut's lines, their part keys folded onto the cut's parts
    `p_key` (each line keeps its supplier's rank among its part's four),
    their prices and their orders' totals worked out again."""
    old = arrays["partkey"].astype(np.int64)
    hop = np.argmax(part_suppliers(old, scale_factor)
                    == arrays["suppkey"][:, None], axis=1)
    new = p_key[(old - 1) % len(p_key)]
    arrays["partkey"] = new.astype(np.int32)
    arrays["suppkey"] = part_suppliers(new, scale_factor)[
        np.arange(len(new)), hop].astype(np.int32)
    arrays["price_cents"] = (arrays["quantity"].astype(np.int64)
                             * retail_cents(new)).astype(np.int32)
    key = arrays["orderkey"]
    line_order = np.cumsum(np.concatenate([[0], key[1:] != key[:-1]]))
    charge = (arrays["price_cents"].astype(np.int64)
              * (100 + arrays["tax_pct"].astype(np.int64))
              * (100 - arrays["discount_pct"].astype(np.int64)))
    arrays["o_total_micro"] = np.bincount(
        line_order, weights=charge,
        minlength=len(arrays["o_orderkey"])).astype(np.int64)


def generate(rows: int, seed: int, scale_factor: int = 1) -> dict:
    """Compact columns of the six tables, from ``seed`` alone.  A rehearsal
    cut (fewer than `LINES_PER_PART` lines a part of the scale factor)
    keeps that many lines a part as the whole tables do: a sample of the
    part keys, the lines folded onto them (`_fold_lines`), and the
    suppliers those parts name, on their SF keys; so LINEITEM stays the
    largest table, the one the planner probes."""
    require_composite_builds()
    arrays = q3.generate(rows, seed, scale_factor)
    sf = int(scale_factor)
    own = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), 9])
    pool_seq, part_seq, supp_seq, nation_seq, cut_seq = own.spawn(5)
    if rows >= LINES_PER_PART * 200_000 * sf:
        p_key = np.arange(1, 200_000 * sf + 1, dtype=np.int64)
        s_key = np.arange(1, 10_000 * sf + 1, dtype=np.int64)
    else:
        p_key = np.sort(np.random.default_rng(cut_seq).choice(
            200_000 * sf, max(rows // LINES_PER_PART, 8),
            replace=False)).astype(np.int64) + 1
        _fold_lines(arrays, p_key, sf)
        s_key = np.unique(part_suppliers(p_key, sf))
    parts, suppliers = len(p_key), len(s_key)
    arrays["s_key"] = s_key
    arrays["ps_supp"] = part_suppliers(p_key, sf).astype(np.int32)
    words = np.array(li.WORDS, dtype=object)
    picks = np.random.default_rng(pool_seq).integers(0, len(words),
                                                     POOL_WORDS)
    pool = np.frombuffer(" ".join(words[picks].tolist()).encode("ascii"),
                         dtype=np.uint8)
    pool = np.concatenate([pool, pool])

    rng = np.random.default_rng(part_seq)
    arrays["p_key"] = p_key
    arrays["p_words"] = _distinct_words(rng, parts)
    arrays["p_mfgr"] = rng.integers(1, 6, parts, dtype=np.int8)
    arrays["p_brand"] = rng.integers(1, 6, parts, dtype=np.int8)
    arrays["p_type"] = rng.integers(0, 150, parts, dtype=np.int16)
    arrays["p_size"] = rng.integers(1, 51, parts, dtype=np.int8)
    arrays["p_container"] = rng.integers(0, 40, parts, dtype=np.int8)
    arrays["p_comment_length"], arrays["p_comment_text"] = q3._cuts(
        rng, pool, 5, 22, parts)
    arrays["ps_availqty"] = rng.integers(
        1, 10_000, parts * SUPPLIERS_PER_PART, dtype=np.int32)
    arrays["ps_cost_cents"] = rng.integers(
        100, 100_001, parts * SUPPLIERS_PER_PART, dtype=np.int32)
    arrays["ps_comment_length"], arrays["ps_comment_text"] = q3._cuts(
        rng, pool, 49, 198, parts * SUPPLIERS_PER_PART)

    rng = np.random.default_rng(supp_seq)
    arrays["s_nation"] = rng.integers(0, len(NATIONS), suppliers,
                                      dtype=np.int8)
    arrays["s_phone_digits"] = rng.integers(0, 10, (suppliers, 10),
                                            dtype=np.uint8)
    arrays["s_phone_digits"][:, (0, 3, 6)] = rng.integers(
        1, 10, (suppliers, 3), dtype=np.uint8)
    arrays["s_acctbal_cents"] = rng.integers(-99_999, 1_000_000, suppliers,
                                             dtype=np.int32)
    arrays["s_address_length"], arrays["s_address_text"] = q3._cuts(
        rng, pool, 10, 40, suppliers)
    arrays["s_comment_length"], arrays["s_comment_text"] = q3._cuts(
        rng, pool, 25, 100, suppliers)

    rng = np.random.default_rng(nation_seq)
    arrays["n_comment_length"], arrays["n_comment_text"] = q3._cuts(
        rng, pool, 31, 114, len(NATIONS))
    return arrays


def _joined(values, codes: np.ndarray, sep: str = " "):
    """Per row, the strings ``values[codes[row, :]]`` joined by `sep`, as
    one character buffer."""
    pieces = np.array([v + sep for v in values], dtype=object)
    lengths = np.array([len(p) for p in pieces], dtype=np.int64)
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    chars = np.frombuffer("".join(pieces).encode("ascii"), dtype=np.uint8)
    flat = codes.reshape(-1).astype(np.int64)
    each = lengths[flat]
    at = np.cumsum(each) - each
    buf = chars[np.repeat(starts[flat] - at, each)
                + np.arange(int(each.sum()), dtype=np.int64)]
    row_ends = np.cumsum(each.reshape(codes.shape).sum(1))
    keep = np.ones(len(buf), dtype=bool)
    keep[row_ends - len(sep)] = False  # the separator after the last piece
    return q3._text(each.reshape(codes.shape).sum(1) - len(sep), buf[keep])


def _by_code(codes: np.ndarray, values):
    return li._by_code(codes, values)


#: table -> column -> how it is made from the compact arrays, in the
#: schema's order (clause 1.4.1)
PART = {
    "p_partkey": lambda a: a["p_key"],
    "p_name": lambda a: _joined(COLORS, a["p_words"]),
    "p_mfgr": lambda a: _by_code(a["p_mfgr"] - 1, [f"Manufacturer#{m}"
                                                   for m in range(1, 6)]),
    "p_brand": lambda a: _by_code(
        (a["p_mfgr"].astype(np.int16) - 1) * 5 + a["p_brand"] - 1,
        [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]),
    "p_type": lambda a: _by_code(a["p_type"], [
        f"{x} {y} {z}" for x in TYPE_SYLLABLES[0]
        for y in TYPE_SYLLABLES[1] for z in TYPE_SYLLABLES[2]]),
    "p_size": lambda a: a["p_size"].astype(np.int64),
    "p_container": lambda a: _by_code(a["p_container"], [
        f"{x} {y}" for x in CONTAINER_SYLLABLES[0]
        for y in CONTAINER_SYLLABLES[1]]),
    "p_retailprice": lambda a: retail_cents(a["p_key"]) / 100.0,
    "p_comment": lambda a: q3._text(a["p_comment_length"],
                                    a["p_comment_text"]),
}
SUPPLIER = {
    "s_suppkey": lambda a: a["s_key"],
    "s_name": lambda a: q3._numbered("Supplier#", a["s_key"], 9),
    "s_address": lambda a: q3._text(a["s_address_length"],
                                    a["s_address_text"]),
    "s_nationkey": lambda a: a["s_nation"].astype(np.int64),
    "s_phone": lambda a: q3._phones(a["s_nation"], a["s_phone_digits"]),
    "s_acctbal": lambda a: a["s_acctbal_cents"] / 100.0,
    "s_comment": lambda a: q3._text(a["s_comment_length"],
                                    a["s_comment_text"]),
}
PARTSUPP = {
    "ps_partkey": lambda a: np.repeat(a["p_key"], SUPPLIERS_PER_PART),
    "ps_suppkey": lambda a: a["ps_supp"].reshape(-1).astype(np.int64),
    "ps_availqty": lambda a: a["ps_availqty"].astype(np.int64),
    "ps_supplycost": lambda a: a["ps_cost_cents"] / 100.0,
    "ps_comment": lambda a: q3._text(a["ps_comment_length"],
                                     a["ps_comment_text"]),
}
NATION = {
    "n_nationkey": lambda a: np.arange(len(NATIONS), dtype=np.int64),
    "n_name": lambda a: _by_code(np.arange(len(NATIONS)),
                                 [n for n, _ in NATIONS]),
    "n_regionkey": lambda a: np.array([r for _, r in NATIONS],
                                      dtype=np.int64),
    "n_comment": lambda a: q3._text(a["n_comment_length"],
                                    a["n_comment_text"]),
}


def arrow_tables(arrays: dict) -> dict:
    """The six tables as pyarrow tables, as a parquet reader hands them
    over: strings plain (not dictionary-encoded), dates ``timestamp[s]``."""
    import pyarrow as pa

    def table(columns):
        built = {name: make(arrays) for name, make in columns.items()}
        return pa.table({name: pa.array(col) if isinstance(col, np.ndarray)
                         else col for name, col in built.items()})

    return {"part": table(PART), "supplier": table(SUPPLIER),
            "partsupp": table(PARTSUPP), "nation": table(NATION),
            "orders": table(q3.ORDERS), **li.arrow_tables(arrays)}


def frames(arrays: dict) -> dict:
    """The same tables as pandas frames (strings as pandas makes them)."""
    return {name: table.to_pandas()
            for name, table in arrow_tables(arrays).items()}
