"""Reader ``profile_idle``: 100 * (1 - busy / slice) from the profiler trace
of the run's traced slice (``xplane.reduce``); nothing without a trace."""


def read(metric: dict, run: dict):
    profile = run.get("profile")
    if not profile or profile["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - profile["busy_s"] / profile["window_s"])
