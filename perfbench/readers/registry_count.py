"""Reader ``registry_count``: how many observations the engine's histogram
named by the metric's ``histogram`` holds, from the engine's
``MetricsRegistry`` through its public ``snapshot()``, the registry taken as
``registry_total`` takes it (from the first request that kept its trace).
0 where the histogram is empty; no such request, or a program that keeps no
such histogram: nothing returned."""


def read(metric: dict, run: dict):
    for rec in run["records"]:
        registry = getattr(rec.get("trace"), "metrics", None)
        if registry is None:
            continue
        hist = registry.snapshot()["histograms"].get(metric["histogram"])
        return None if hist is None else hist["count"]
    return None
