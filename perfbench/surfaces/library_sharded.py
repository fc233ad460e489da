"""Surface ``library_sharded``: ``library``, held to the deployment's layout.

The cell's table is registered ``distributed=True``; what it measures exists
only while every buffer lies in row blocks on all of the cell's chips and
every answer comes from a sharded rung.  ``start`` refuses to run on another
layout (no result line, as for too few chips), and after the window a request
that was answered below the sharded rungs counts as failed
(``requests_failed``, limit 0) and not into ``queries_per_s``: a mesh that
quietly answers from one program is not this cell at another speed.
"""
from __future__ import annotations

from perfbench.run import buffers_of
from perfbench.surfaces import library

SHARDED_RUNG = "rung:spmd_"


class Surface(library.Surface):
    def start(self) -> None:
        chips = int(self.workload["chips"])
        schema = self.ctx.schema[self.ctx.schema_name]
        for name, dc in schema.tables.items():
            for buf in buffers_of(dc.table):
                on = len(buf.sharding.device_set)
                if on != chips:
                    raise RuntimeError(
                        f"library_sharded: a buffer of {name!r} lies on {on} "
                        f"device(s), the cell's layout is {chips}")

    def run(self, tick):
        records, start, end = super().run(tick)
        for rec in records:
            trace = rec.get("trace")
            if trace is not None and "error" not in rec and not any(
                    s.name.startswith(SHARDED_RUNG) for s in trace.spans):
                rec.pop("answer", None)
                rec["error"] = "answered below the sharded rungs"
        return records, start, end
