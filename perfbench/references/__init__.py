"""Plain references, one module per query kind, found by the name a query
file gives.  Each has ``Reference(arrays).answer(params)`` (numpy float64) and
``control_answer(arrays, params, precision)``."""
from concurrent.futures import ThreadPoolExecutor

BLOCK = 4_000_000


def over_blocks(rows: int, fn):
    """``fn(lo, hi)`` over the rows in blocks, on a few threads (numpy lets
    go of the interpreter lock); the parts in order."""
    starts = range(0, rows, BLOCK)
    with ThreadPoolExecutor(8) as pool:
        return list(pool.map(lambda lo: fn(lo, min(lo + BLOCK, rows)),
                             starts))
