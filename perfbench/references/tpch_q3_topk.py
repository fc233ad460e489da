"""TPC-H Q3 in plain numpy, for any (SEGMENT, DAY): the ten orders worth
most that were placed before DATE, by customers of SEGMENT, and have lines
shipped after it.

An order's revenue is an EXACT sum: ``price_cents * (100 - discount_pct)``
over its lines with ``shipday > DATE`` is a whole number of hundredths of a
cent (at most 7 lines of under 1.1e9 each, far inside float64's whole
numbers), added
by one ``bincount`` over the lines' order index and divided once.  Kept are
the orders with ``orderday < DATE`` whose customer's segment is SEGMENT and
that have such a line; the first ten by (revenue DESC, o_orderdate, order key).
Nothing of the engine is imported.  Keys are rendered as the library surface
renders them (``surfaces/library.py::frame_answer``): the order key as an
int, ``o_orderdate`` as a pandas Timestamp, ``o_shippriority`` 0.
"""
from __future__ import annotations

import datetime

import numpy as np

from perfbench.datagen.tpch_lineitem import DAY0

COLUMNS = ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]
ROWS = 10
LAST_DATE = datetime.date(1995, 3, 31)  # DATE = LAST_DATE - DAY days

#: id(arrays) -> what every Reference over those arrays shares (a cell's
#: five query files differ in SEGMENT alone)
_SHARED: dict = {}


def cut_day(params: dict) -> int:
    date = LAST_DATE - datetime.timedelta(days=int(params["DAY"]))
    return int((np.datetime64(date.isoformat()) - DAY0).astype(np.int64))


def _shared(arrays: dict) -> dict:
    got = _SHARED.get(id(arrays))
    if got is None or got["arrays"] is not arrays:
        _SHARED.clear()
        got = _SHARED[id(arrays)] = {
            "arrays": arrays,
            "line_order": np.searchsorted(
                arrays["o_orderkey"], arrays["orderkey"]).astype(np.int32),
            "order_segment": arrays["c_segment"][arrays["o_custkey"] - 1],
        }
    return got


def _rows(arrays: dict, order, revenue) -> dict:
    import pandas as pd

    days = arrays["o_orderday"][order].astype("timedelta64[D]")
    return {"columns": COLUMNS, "rows": [
        [int(key), float(r), pd.Timestamp(day), 0]
        for key, r, day in zip(arrays["o_orderkey"][order], revenue,
                               DAY0 + days)]}


def _first_ten(arrays, shared, params, revenue, lines):
    day = cut_day(params)
    keep = np.flatnonzero((lines > 0) & (arrays["o_orderday"] < day)
                          & (shared["order_segment"] == int(params["SEGMENT"])))
    by = np.lexsort((keep, arrays["o_orderday"][keep], -revenue[keep]))
    return keep[by[:ROWS]]


class Reference:
    def __init__(self, arrays: dict):
        self.arrays = arrays
        self.shared = _shared(arrays)

    def answer(self, params: dict) -> dict:
        a, orders = self.arrays, len(self.arrays["o_orderkey"])
        shipped = np.flatnonzero(a["shipday"] > cut_day(params))
        order = self.shared["line_order"][shipped]
        units = (a["price_cents"][shipped].astype(np.int64)
                      * (100 - a["discount_pct"][shipped].astype(np.int64)))
        revenue = np.bincount(order, weights=units, minlength=orders)
        lines = np.bincount(order, minlength=orders)
        first = _first_ten(a, self.shared, params, revenue, lines)
        return _rows(a, first, revenue[first] / 1e4)


def control_answer(arrays: dict, params: dict, precision: str) -> dict:
    """The same query with the product AND the per-order sum in
    ``precision`` (``float32``: the nearest below the configuration's
    float64), the rows ordered by those sums."""
    dtype = np.dtype(precision).type
    shared = _shared(arrays)
    orders = len(arrays["o_orderkey"])
    shipped = arrays["shipday"] > cut_day(params)
    price = (arrays["price_cents"] / 100.0).astype(dtype)
    disc = (arrays["discount_pct"] / 100.0).astype(dtype)
    line = np.where(shipped, price * (dtype(1) - disc), dtype(0))
    # the lines come sorted by order: one running sum per order, in `dtype`
    starts = np.searchsorted(shared["line_order"], np.arange(orders))
    revenue = np.add.reduceat(line, starts, dtype=dtype)
    lines = np.bincount(shared["line_order"][shipped], minlength=orders)
    first = _first_ten(arrays, shared, params, revenue, lines)
    return _rows(arrays, first, revenue[first])
