"""TPC-H Q18 in plain numpy, for any QUANTITY: the hundred largest orders
(by total price) among those whose lines' quantities add up past QUANTITY,
each with its customer.

An order's quantity is an EXACT sum: ``l_quantity`` is a whole number 1..50
and an order has at most 7 lines, so one ``bincount`` over the lines' order
index gives it whatever the order of addition.  Kept are the orders whose
sum is ``> QUANTITY``; the first hundred by (``o_totalprice`` DESC,
``o_orderdate``, order key), a stable order.  ``o_totalprice`` is the value
the table is loaded with (``o_total_micro / 1e6``, the generator's own
division); the chip holds a float64 as a pair of float32, so what comes back
is within 2**-49 of it and the query file compares it by ``limits.rel_err``.
Nothing of the engine is imported.  Keys are
rendered as the library surface renders them (``surfaces/library.py::
frame_answer``): names as str, keys as int, ``o_orderdate`` as a pandas
Timestamp, the price as float.
"""
from __future__ import annotations

import numpy as np

from perfbench.datagen.tpch_lineitem import DAY0

#: ``SUM``: the name the engine gives the unaliased ``SUM(l_quantity)``
COLUMNS = ["c_name", "c_custkey", "o_orderkey", "o_orderdate",
           "o_totalprice", "SUM"]
ROWS = 100


def _line_order(arrays: dict) -> np.ndarray:
    return np.searchsorted(arrays["o_orderkey"], arrays["orderkey"])


def _first_hundred(arrays: dict, price, quantity, params: dict) -> np.ndarray:
    keep = np.flatnonzero(quantity > int(params["QUANTITY"]))
    by = np.lexsort((keep, arrays["o_orderday"][keep], -price[keep]))
    return keep[by[:ROWS]]


def _rows(arrays: dict, order, price, quantity) -> dict:
    import pandas as pd

    days = arrays["o_orderday"][order].astype("timedelta64[D]")
    custkey = arrays["o_custkey"][order]
    return {"columns": COLUMNS, "rows": [
        [f"Customer#{int(c):09d}", int(c), int(key), pd.Timestamp(day),
         float(p), float(q)]
        for c, key, day, p, q in zip(custkey, arrays["o_orderkey"][order],
                                     DAY0 + days, price[order],
                                     quantity[order])]}


class Reference:
    def __init__(self, arrays: dict):
        self.arrays = arrays
        self.price = arrays["o_total_micro"] / 1e6
        self.quantity = np.bincount(
            _line_order(arrays), weights=arrays["quantity"],
            minlength=len(arrays["o_orderkey"]))

    def answer(self, params: dict) -> dict:
        first = _first_hundred(self.arrays, self.price, self.quantity, params)
        return _rows(self.arrays, first, self.price, self.quantity)


def control_answer(arrays: dict, params: dict, precision: str) -> dict:
    """The same query with ``o_totalprice`` held, the quantities summed and
    the rows ordered in ``precision`` (``float32``: the nearest below the
    configuration's float64).  The sums of small whole numbers stay exact;
    the price does not fit float32's 24 bits."""
    dtype = np.dtype(precision).type
    orders = len(arrays["o_orderkey"])
    price = (arrays["o_total_micro"] / 1e6).astype(dtype)
    line_order = _line_order(arrays)
    # the lines come sorted by order: one running sum per order, in `dtype`
    starts = np.searchsorted(line_order, np.arange(orders))
    quantity = np.add.reduceat(arrays["quantity"].astype(dtype), starts,
                               dtype=dtype)
    first = _first_hundred(arrays, price, quantity, params)
    return _rows(arrays, first, price, quantity)
