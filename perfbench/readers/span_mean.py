"""Reader ``span_mean``: the mean, over the window's answered requests, of
the summed durations (ms) of the metric's ``spans`` in each request's trace.
Requests whose trace is gone are left out; none left, nothing returned."""


def read(metric: dict, run: dict):
    wanted = set(metric["spans"])
    totals = []
    for rec in run["records"]:
        trace = rec.get("trace")
        if trace is None or rec.get("error"):
            continue
        found = [s for s in trace.spans
                 if s.name in wanted and s.t1 is not None]
        if found:
            totals.append(sum((s.t1 - s.t0) for s in found) * 1e3)
    return sum(totals) / len(totals) if totals else None
