"""Reader ``counter``: a count or a gauge by its name in ``run["counters"]``
(``memory_peak_bytes``, ``d2h_transfers``), times ``scale``."""


def read(metric: dict, run: dict):
    value = run["counters"].get(metric["counter"])
    return None if value is None else value * metric.get("scale", 1.0)
