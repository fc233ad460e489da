"""Reader ``roofline_tables``: ``roofline`` for a query over several tables.

The least time is memory-bound as there, the bytes being summed over the
query file's ``scans``: a map of table to the columns the query references
in it, at the widths the loaded tables hold them in.  It reads the query's
file and the tables, never the kernel, so it is the same work whatever
implements the joins: look-up tables, hash tables and other state are the
program's choice and are left out.  A query file without ``scans`` (or a
table the run did not load) gives nothing.
"""


def scan_bytes(query: dict, tables: dict):
    """Bytes of every column ``query`` references, over all its tables
    ({table: {"rows": n, "itemsize": {column: bytes per row}}}); None where
    the query names no ``scans`` or a table or column is not there."""
    scans = query.get("scans")
    if not scans:
        return None
    total = 0
    for table, columns in scans.items():
        held = tables.get(table)
        if held is None or any(c not in held["itemsize"] for c in columns):
            return None
        total += held["rows"] * sum(held["itemsize"][c] for c in columns)
    return total


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
        nbytes = scan_bytes(run["queries"][rec["query"]], run["tables"])
        if nbytes is None:
            continue
        for span in trace.spans:
            if span.name != "execute" or span.t1 is None \
                    or span.t1 <= span.t0:
                continue
            inside = max(0.0, min(span.t1, hi) - max(span.t0, lo))
            least_s += (inside / (span.t1 - span.t0)) * nbytes \
                / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / profile["busy_s"] if least_s else None
