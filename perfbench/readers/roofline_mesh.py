"""Reader ``roofline_mesh``: ``roofline`` over a mesh of ``metric["chips"]``
chips.  ``roofline`` divides the whole table's bytes by ONE chip's memory
bandwidth while ``xplane.reduce`` averages busy seconds over the device
planes, so on a row-sharded table it reads ``chips`` times the share of the
mesh's bandwidth; this divides that out."""
from perfbench.readers import roofline


def read(metric: dict, run: dict):
    value = roofline.read(metric, run)
    return None if value is None else value / int(metric["chips"])
