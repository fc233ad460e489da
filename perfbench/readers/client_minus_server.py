"""Reader ``client_minus_server``: the mean over the window's answered
requests of the client's latency minus the extent of the server's own trace
of that request (first span's start to last span's end), in ms.  What is
left is what the wire, the polling and the admission queue in front of the
trace add.  Only requests that carry a query id (wire cells) are read."""


def read(metric: dict, run: dict):
    gaps = []
    for rec in run["records"]:
        trace = rec.get("trace")
        if trace is None or rec.get("error") or "qid" not in rec:
            continue
        closed = [s for s in trace.spans if s.t1 is not None]
        if not closed:
            continue
        extent = max(s.t1 for s in closed) - min(s.t0 for s in closed)
        gaps.append((rec["done"] - rec["sent"] - extent) * 1e3)
    return sum(gaps) / len(gaps) if gaps else None
