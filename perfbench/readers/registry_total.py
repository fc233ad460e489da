"""Reader ``registry_total``: the cumulative sum of the engine's histogram
named by the metric's ``histogram``, times ``scale``, from the engine's
``MetricsRegistry`` through its public ``snapshot()``.  The harness hands a
reader no ``Context``; every query trace carries the registry it records
into (``QueryTrace.metrics``), so the registry is taken from the first
request that kept its trace.  No such request, or a program that keeps no
such histogram: nothing returned."""


def read(metric: dict, run: dict):
    for rec in run["records"]:
        registry = getattr(rec.get("trace"), "metrics", None)
        if registry is None:
            continue
        hist = registry.snapshot()["histograms"].get(metric["histogram"])
        if hist is None:
            return None
        return hist["sum"] * metric.get("scale", 1.0)
    return None
