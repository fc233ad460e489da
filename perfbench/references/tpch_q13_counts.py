"""TPC-H Q13 in plain numpy, for any WORD1 and WORD2: how many customers
placed how many orders, leaving out the orders whose comment holds WORD1
and, after it, WORD2 (``o_comment NOT LIKE '%WORD1%WORD2%'`` in the LEFT
OUTER join's ON clause).

The comments are one character buffer, row after row
(``tpch_q13_tables``).  Each word's occurrences are found in the whole
buffer at once (``re.finditer``: the eight words of cl.2.4.13.3 have no
proper prefix that is also a suffix, so no two occurrences of one word
overlap and none is missed) and mapped to rows by ``searchsorted`` over the
comments' offsets; an occurrence that runs past its row's end is no
occurrence.  A row matches where some WORD2 starts at or after the end of
its first WORD1.  The orders that do not match are counted per customer
(``bincount`` over every customer key, so a customer without one counts
0), the customers per count, and the rows come as the query orders them
(custdist DESC, c_count DESC).  Nothing of the engine is imported, nor
pyarrow's LIKE.
"""
from __future__ import annotations

import re

import numpy as np

#: cl.2.4.13.3: WORD1 and WORD2, a query file's parameters their indices
WORD1 = ("special", "pending", "unusual", "express")
WORD2 = ("packages", "requests", "accounts", "deposits")
COLUMNS = ["c_count", "custdist"]
#: id(arrays) -> what every Reference over those arrays shares (a cell's
#: sixteen query files differ in their words alone): the comments' offsets
#: and each word's occurrences
_SHARED: dict = {}


def _shared(arrays: dict) -> dict:
    got = _SHARED.get(id(arrays))
    if got is None or got["arrays"] is not arrays:
        _SHARED.clear()
        lengths = arrays["o_comment_length"]
        offsets = np.zeros(len(lengths) + 1, dtype=np.int64)
        np.cumsum(lengths, dtype=np.int64, out=offsets[1:])
        got = _SHARED[id(arrays)] = {"arrays": arrays, "offsets": offsets,
                                     "found": {}}
    return got


class Reference:
    def __init__(self, arrays: dict):
        shared = _shared(arrays)
        self.offsets, self._found = shared["offsets"], shared["found"]
        self.text = np.ascontiguousarray(arrays["o_comment_text"])
        self.custkey = arrays["o_custkey"]
        self.customers = len(arrays["c_segment"])

    def occurrences(self, word: str):
        """``(row, start, end)`` of every occurrence of `word` that lies
        inside one comment, in buffer order."""
        got = self._found.get(word)
        if got is None:
            pattern = re.compile(re.escape(word.encode("ascii")))
            starts = np.fromiter(
                (m.start() for m in pattern.finditer(memoryview(self.text))),
                dtype=np.int64)
            rows = np.searchsorted(self.offsets, starts, side="right") - 1
            ends = starts + len(word)
            inside = ends <= self.offsets[rows + 1]
            got = self._found[word] = (rows[inside], starts[inside],
                                       ends[inside])
        return got

    def matched(self, word1: str, word2: str) -> np.ndarray:
        """One bool an order: whether its comment is LIKE
        ``'%word1%word2%'``."""
        n = len(self.offsets) - 1
        rows1, _, ends1 = self.occurrences(word1)
        rows2, starts2, _ = self.occurrences(word2)
        first_end = np.full(n, np.iinfo(np.int64).max, dtype=np.int64)
        at, first = np.unique(rows1, return_index=True)
        first_end[at] = ends1[first]
        last_start = np.full(n, -1, dtype=np.int64)
        at, last = np.unique(rows2[::-1], return_index=True)
        last_start[at] = starts2[::-1][last]
        return last_start >= first_end

    def counts(self, params: dict) -> np.ndarray:
        """Orders per customer (customer key - 1 the index) that the ON
        clause keeps."""
        kept = ~self.matched(WORD1[int(params["WORD1"])],
                             WORD2[int(params["WORD2"])])
        return np.bincount(self.custkey[kept],
                           minlength=self.customers + 1)[1:]

    def answer(self, params: dict) -> dict:
        return _rows(np.bincount(self.counts(params)))


def _rows(custdist: np.ndarray) -> dict:
    """``custdist[c]`` customers with ``c`` orders, as the query's rows."""
    c_count = np.flatnonzero(custdist)
    dist = custdist[c_count]
    order = np.lexsort((-c_count, -dist))
    return {"columns": COLUMNS, "rows": [[int(c), int(d)] for c, d in
                                         zip(c_count[order], dist[order])]}


def control_answer(arrays: dict, params: dict, precision: str) -> dict:
    """The same query with each customer's count and each count's
    customers held in `precision`.  Both are whole numbers under 2**24, so
    float32 holds them exactly and this control agrees with the reference:
    the query has no float cell for a lower precision to move."""
    dtype = np.dtype(precision).type
    counts = Reference(arrays).counts(params).astype(dtype)
    c_count, custdist = np.unique(counts, return_counts=True)
    full = np.zeros(int(c_count.max()) + 1, dtype=np.int64)
    full[c_count.astype(np.int64)] = custdist.astype(dtype)
    return _rows(full)
