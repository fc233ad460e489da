"""TPC-H Q1 in plain numpy float64, for EVERY value of DELTA from one pass.

Each row falls into one bin of (group, ship day); six ``bincount`` passes give
the per-bin sums, a running sum over the days gives, for any cut-off date,
what ``WHERE l_shipdate <= cutoff GROUP BY returnflag, linestatus`` selects.
The arithmetic is the query's own on the same float64 inputs the table was
loaded from (``price_cents / 100.0`` and so on); nothing of the engine is
imported.
"""
from __future__ import annotations

import numpy as np

from perfbench.datagen.tpch_lineitem import DAY0, FLAGS, STATUSES
from perfbench.references import over_blocks

GROUPS = len(FLAGS) * len(STATUSES)
BASE_DAY = int((np.datetime64("1998-12-01") - DAY0).astype(np.int64))
SUMS = ("qty", "price", "disc_price", "charge", "disc")
COLUMNS = ["l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
           "sum_disc_price", "sum_charge", "avg_qty", "avg_price",
           "avg_disc", "count_order"]


def _block_bins(arrays, lo, hi, days):
    sl = slice(lo, hi)
    bins = ((arrays["flag"][sl].astype(np.int64) * len(STATUSES)
             + arrays["status"][sl]) * days + arrays["shipday"][sl])
    price = arrays["price_cents"][sl] / 100.0
    disc = arrays["discount_pct"][sl] / 100.0
    disc_price = price * (1.0 - disc)
    weights = {"qty": arrays["quantity"][sl].astype(np.float64),
               "price": price, "disc_price": disc_price,
               "charge": disc_price * (1.0 + arrays["tax_pct"][sl] / 100.0),
               "disc": disc}
    size = GROUPS * days
    out = {name: np.bincount(bins, weights=w, minlength=size)
           for name, w in weights.items()}
    out["count"] = np.bincount(bins, minlength=size)
    return out


class Reference:
    def __init__(self, arrays: dict):
        rows = len(arrays["shipday"])
        self.days = int(arrays["shipday"].max()) + 1
        parts = over_blocks(rows, lambda lo, hi: _block_bins(
            arrays, lo, hi, self.days))
        self.running = {
            name: np.cumsum(sum(p[name] for p in parts)
                            .reshape(GROUPS, self.days), axis=1)
            for name in parts[0]}

    def answer(self, params: dict) -> dict:
        cutoff = min(BASE_DAY - int(params["DELTA"]), self.days - 1)
        rows = []
        for g in range(GROUPS):
            count = int(self.running["count"][g, cutoff]) if cutoff >= 0 else 0
            if count == 0:
                continue
            s = {name: float(self.running[name][g, cutoff]) for name in SUMS}
            rows.append([FLAGS[g // len(STATUSES)], STATUSES[g % len(STATUSES)],
                         s["qty"], s["price"], s["disc_price"], s["charge"],
                         s["qty"] / count, s["price"] / count,
                         s["disc"] / count, count])
        return {"columns": COLUMNS, "rows": rows}


def control_answer(arrays: dict, params: dict, precision: str) -> dict:
    """The same query for one DELTA with every product AND every sum in
    ``precision`` (``float32``: the nearest precision below the float64 the
    configuration states; numpy's pairwise sum, the kindest way to add in
    it).  Stands in the program's place to show the comparison fails it."""
    dtype = np.dtype(precision).type
    cutoff = BASE_DAY - int(params["DELTA"])
    picked = arrays["shipday"] <= cutoff
    group = arrays["flag"].astype(np.int16) * len(STATUSES) + arrays["status"]
    qty = arrays["quantity"].astype(dtype)
    price = (arrays["price_cents"] / 100.0).astype(dtype)
    disc = (arrays["discount_pct"] / 100.0).astype(dtype)
    tax = (arrays["tax_pct"] / 100.0).astype(dtype)
    disc_price = price * (dtype(1) - disc)
    charge = disc_price * (dtype(1) + tax)
    rows = []
    for g in range(GROUPS):
        m = picked & (group == g)
        count = int(np.count_nonzero(m))
        if count == 0:
            continue
        s = [float(np.sum(x[m], dtype=dtype))
             for x in (qty, price, disc_price, charge, disc)]
        rows.append([FLAGS[g // len(STATUSES)], STATUSES[g % len(STATUSES)],
                     s[0], s[1], s[2], s[3], s[0] / count, s[1] / count,
                     s[4] / count, count])
    return {"columns": COLUMNS, "rows": rows}
