"""Reader ``latency_percentile``: the metric's ``percentile`` (nearest rank)
of the client's latency (ms, send to the last row in its hands) over ALL
requests of the window.  A request that failed or never answered counts as
slower than any; where the percentile falls on one, nothing is returned
(the run is not correct then, and says so)."""


def read(metric: dict, run: dict):
    latencies = sorted(
        (rec["done"] - rec["sent"]) * 1e3
        if rec.get("answer") is not None and not rec.get("error")
        else float("inf") for rec in run["records"])
    if not latencies:
        return None
    rank = max(1, -(-len(latencies) * int(metric["percentile"]) // 100))
    value = latencies[rank - 1]
    return None if value == float("inf") else value
