"""Reader ``roofline``: the least time the chip could take for the traced
slice's queries, over the time its operations really ran, in percent.

The least time of a scan is memory-bound: the bytes the query's referenced
columns occupy on the device, at the widths the loaded table holds them in,
over the chip's peak memory bandwidth (``peaks.json``).  It reads the query's
file and the table, never the kernel, so it stays what it is whatever the
program does to answer.  A request counts by the share of its ``execute``
span that lies inside the traced slice.
"""


def scan_bytes(query: dict, table: dict) -> int:
    """Bytes of ``query``'s referenced columns in ``table``
    ({"rows": n, "itemsize": {column: bytes per row}})."""
    return table["rows"] * sum(table["itemsize"][c]
                               for c in query["referenced_columns"])


def read(metric: dict, run: dict):
    profile = run.get("profile")
    if not profile or profile["busy_s"] <= 0:
        return None
    lo, hi = run["slice"]
    least_s = 0.0
    for rec in run["records"]:
        trace = rec.get("trace")
        if trace is None or rec.get("error"):
            continue
        query = run["queries"][rec["query"]]
        for span in trace.spans:
            if span.name != "execute" or span.t1 is None \
                    or span.t1 <= span.t0:
                continue
            inside = max(0.0, min(span.t1, hi) - max(span.t0, lo))
            least_s += (inside / (span.t1 - span.t0)) * scan_bytes(
                query, run["tables"][query["table"]]) \
                / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / profile["busy_s"] if least_s else None
