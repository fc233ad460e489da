"""Reader ``span_gap``: the mean, over the window's answered requests that
kept their trace, of the time the engine held the request under NO stage
span, in ms: the engine's extent minus the length of the union of the
trace's closed stage spans (``kind == "stage"``) clipped to that extent.
The extent of a library request (no ``qid``) is the client's send to its
answer; of a wire request the first span's start to the last span's end
(what lies outside that is ``wire_ms``'s).  It is the tile check: code on the
request path that no stage covers shows here and nowhere else."""
from perfbench import xplane


def covered(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(b - a for a, b in xplane.union(xplane.clip(intervals, lo, hi)))


def read(metric: dict, run: dict):
    gaps = []
    for rec in run["records"]:
        trace = rec.get("trace")
        if trace is None or rec.get("error"):
            continue
        closed = [s for s in trace.spans if s.t1 is not None]
        if "qid" in rec:
            if not closed:
                continue
            lo = min(s.t0 for s in closed)
            hi = max(s.t1 for s in closed)
        else:
            lo, hi = rec["sent"], rec["done"]
        stages = [(s.t0, s.t1) for s in closed
                  if getattr(s, "kind", "stage") == "stage"]
        gaps.append((hi - lo - covered(stages, lo, hi)) * 1e3)
    return sum(gaps) / len(gaps) if gaps else None
