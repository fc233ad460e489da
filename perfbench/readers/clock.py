"""Reader ``clock``: a host-clock reading (seconds) the harness took itself
during set-up, by its name in ``run["clocks"]``."""


def read(metric: dict, run: dict):
    return run["clocks"].get(metric["clock"])
