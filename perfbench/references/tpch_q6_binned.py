"""TPC-H Q6 in plain numpy float64, for EVERY (YEAR, DISCOUNT, QUANTITY).

One ``bincount`` of ``l_extendedprice * l_discount`` over bins of (ship year,
discount in hundredths, quantity); an answer is the sum of the bins its
predicate selects.  Same float64 inputs as the loaded table, nothing of the
engine imported.
"""
from __future__ import annotations

import numpy as np

from perfbench.datagen.tpch_lineitem import DAY0
from perfbench.references import over_blocks

YEARS = list(range(1992, 2000))
YEAR_START = np.array([int((np.datetime64(f"{y}-01-01") - DAY0)
                           .astype(np.int64)) for y in YEARS])
DISCOUNTS = 11   # 0.00 .. 0.10
QUANTITIES = 51  # 1 .. 50


def _block_bins(arrays, lo, hi):
    sl = slice(lo, hi)
    year = np.searchsorted(YEAR_START, arrays["shipday"][sl], side="right") - 1
    bins = ((year * DISCOUNTS + arrays["discount_pct"][sl]) * QUANTITIES
            + arrays["quantity"][sl])
    revenue = (arrays["price_cents"][sl] / 100.0) \
        * (arrays["discount_pct"][sl] / 100.0)
    return np.bincount(bins, weights=revenue,
                       minlength=len(YEARS) * DISCOUNTS * QUANTITIES)


class Reference:
    def __init__(self, arrays: dict):
        rows = len(arrays["shipday"])
        parts = over_blocks(rows, lambda lo, hi: _block_bins(arrays, lo, hi))
        self.bins = sum(parts).reshape(len(YEARS), DISCOUNTS, QUANTITIES)

    def answer(self, params: dict) -> dict:
        year = YEARS.index(int(params["YEAR"]))
        d = int(params["DISCOUNT"])
        picked = self.bins[year, max(d - 1, 0):d + 2, :int(params["QUANTITY"])]
        return {"columns": ["revenue"], "rows": [[float(picked.sum())]]}


def control_answer(arrays: dict, params: dict, precision: str) -> dict:
    """The same query for one parameter set with the product AND the sum in
    ``precision`` (``float32``: the nearest below the configuration's
    float64; numpy's pairwise sum)."""
    dtype = np.dtype(precision).type
    year = YEARS.index(int(params["YEAR"]))
    d = int(params["DISCOUNT"])
    m = ((arrays["shipday"] >= YEAR_START[year])
         & (arrays["shipday"] < YEAR_START[year + 1])
         & (arrays["discount_pct"] >= d - 1) & (arrays["discount_pct"] <= d + 1)
         & (arrays["quantity"] < int(params["QUANTITY"])))
    price = (arrays["price_cents"][m] / 100.0).astype(dtype)
    disc = (arrays["discount_pct"][m] / 100.0).astype(dtype)
    return {"columns": ["revenue"],
            "rows": [[float(np.sum(price * disc, dtype=dtype))]]}
