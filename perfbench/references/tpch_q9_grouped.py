"""TPC-H Q9 in plain numpy, for any COLOR: profit by supplier nation and
order year over the lines whose part's name holds the word COLOR.

A line's profit is EXACT in whole units of 10^-4: ``l_extendedprice * (1 -
l_discount)`` is ``price_cents * (100 - discount_pct)`` and ``ps_supplycost
* l_quantity`` is ``supplycost_cents * quantity * 100``, so the int64
difference of the two is the amount and a group's sum is exact in any
order, divided once at the end.  The line's supplier row of PARTSUPP is the
one of its part whose supplier is the line's (`tpch_q9_tables`: the
clause's four a part, so every line finds one); its nation is its
supplier's, its year its order's.  The rows come as the query orders them
(nation ASC, o_year DESC), one per group some line falls in.  A part's name
holds COLOR (``LIKE '%COLOR%'``) where one of its five words holds it: the
pattern has no space and the words are joined by one.
Nothing of the engine is imported; keys are rendered as the library
surface renders them (``surfaces/library.py::frame_answer``): the nation
as str, the year as int, the profit as float.
"""
from __future__ import annotations

import numpy as np

from perfbench.datagen.tpch_lineitem import DAY0
from perfbench.datagen.tpch_q9_tables import COLORS, NATIONS

COLUMNS = ["nation", "o_year", "sum_profit"]
#: nation keys in the order of their names (the query's ORDER BY)
BY_NAME = np.argsort([name for name, _ in NATIONS], kind="stable")
#: id(arrays) -> what every Reference over those arrays shares (a cell's
#: twelve query files differ in COLOR alone)
_SHARED: dict = {}


def _shared(arrays: dict) -> dict:
    got = _SHARED.get(id(arrays))
    if got is None or got["arrays"] is not arrays:
        _SHARED.clear()
        part = np.searchsorted(arrays["p_key"], arrays["partkey"])
        hop = np.argmax(arrays["ps_supp"][part]
                        == arrays["suppkey"][:, None], axis=1)
        cost = arrays["ps_cost_cents"][part * arrays["ps_supp"].shape[1]
                                       + hop]
        supplier = np.searchsorted(arrays["s_key"], arrays["suppkey"])
        order = np.searchsorted(arrays["o_orderkey"], arrays["orderkey"])
        days = DAY0 + arrays["o_orderday"][order].astype("timedelta64[D]")
        year = days.astype("datetime64[Y]").astype(np.int64) + 1970
        got = _SHARED[id(arrays)] = {
            "arrays": arrays, "part": part.astype(np.int32),
            "nation": arrays["s_nation"][supplier].astype(np.int64),
            "year": year, "cost_cents": cost.astype(np.int64),
        }
    return got


def _colored(arrays: dict, shared: dict, params: dict) -> np.ndarray:
    """The lines whose part's name holds COLOR."""
    color = COLORS[int(params["COLOR"])]
    holds = np.array([color in word for word in COLORS])
    has = holds[arrays["p_words"]].any(axis=1)
    return np.flatnonzero(has[shared["part"]])


def _groups(shared: dict, lines: np.ndarray):
    """``nation * 10_000 + year`` of every group the lines fall in, in the
    query's order, and each line's group among them."""
    key = shared["nation"][lines] * 10_000 + shared["year"][lines]
    present, inverse = np.unique(key, return_inverse=True)
    rank = np.empty(len(NATIONS), dtype=np.int64)
    rank[BY_NAME] = np.arange(len(NATIONS))
    order = np.lexsort((-(present % 10_000), rank[present // 10_000]))
    position = np.empty(len(present), dtype=np.int64)
    position[order] = np.arange(len(present))
    return present[order], position[inverse]


def _rows(groups, sums) -> dict:
    return {"columns": COLUMNS, "rows": [
        [NATIONS[int(g // 10_000)][0], int(g % 10_000), float(s)]
        for g, s in zip(groups, sums)]}


class Reference:
    def __init__(self, arrays: dict):
        self.arrays = arrays
        self.shared = _shared(arrays)

    def answer(self, params: dict) -> dict:
        a, sh = self.arrays, self.shared
        lines = _colored(a, sh, params)
        groups, group = _groups(sh, lines)
        amount = (a["price_cents"][lines].astype(np.int64)
                  * (100 - a["discount_pct"][lines].astype(np.int64))
                  - sh["cost_cents"][lines] * a["quantity"][lines] * 100)
        exact = np.zeros(len(groups), dtype=np.int64)
        np.add.at(exact, group, amount)
        return _rows(groups, exact / 1e4)


def control_answer(arrays: dict, params: dict, precision: str) -> dict:
    """The same query with every number held, multiplied and summed in
    `precision` (``float32``: the nearest below the configuration's
    float64), each group's lines added one after another."""
    dtype = np.dtype(precision).type
    sh = _shared(arrays)
    lines = _colored(arrays, sh, params)
    groups, group = _groups(sh, lines)
    price = (arrays["price_cents"][lines] / 100).astype(dtype)
    discount = (arrays["discount_pct"][lines] / 100).astype(dtype)
    cost = (sh["cost_cents"][lines] / 100).astype(dtype)
    quantity = arrays["quantity"][lines].astype(dtype)
    amount = price * (dtype(1) - discount) - cost * quantity
    by = np.argsort(group, kind="stable")
    starts = np.searchsorted(group[by], np.arange(len(groups)))
    sums = np.add.reduceat(amount[by], starts, dtype=dtype) \
        if len(by) else np.zeros(0, dtype=dtype)
    return _rows(groups, sums)
