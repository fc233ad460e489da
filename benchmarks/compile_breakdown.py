#!/usr/bin/env python3
"""Where a cell's set-up compiles: one benchmark run, every compile listed.

    python3 benchmarks/compile_breakdown.py --out chiprun_out/q18.json -- \\
        --workload sf10_q18_library --seed 3500003701 --seconds 30 --trace 1

Runs ``perfbench.run.main`` in this process with the arguments after ``--``
(its lines print as they always do), keeping every trace the Context makes,
and then writes ``--out``: for each trace that holds ``xla:*`` spans (each
load's, each cold request's) its XLA seconds, lowering seconds and
persistent-cache loads, what is left of its extent once they are taken out,
and its ten longest ``xla:compile`` spans with ``fun``, ``cache`` and
``parent``; the process totals (``compile_cache.stats()``); and the cost of
the one JAX monitoring listener (observability/xla.py): calls and the
microseconds a call.  The last line printed is a summary of the same.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as bench_run  # noqa: E402  (stamps PROCESS_START)


def _timed_listeners(cost: dict) -> None:
    """Wrap the listener's two callbacks where JAX holds them, counting
    calls and seconds (this tool's own diagnostic, not the program's)."""
    from jax._src import monitoring

    from dask_sql_tpu.observability import xla

    def timed(fn):
        def call(*args, **kwargs):
            t = time.perf_counter()
            fn(*args, **kwargs)
            cost["seconds"] += time.perf_counter() - t
            cost["calls"] += 1
        return call

    for registry, fn in ((monitoring._event_listeners, xla._on_event),
                         (monitoring._event_time_span_listeners,
                          xla._on_time_span)):
        registry[registry.index(fn)] = timed(fn)


def _summary(trace) -> dict:
    spans = [s for s in trace.spans if s.t1 is not None]
    xla = [s for s in spans if s.name.startswith("xla:")]
    compiles = [s for s in xla if s.name == "xla:compile"]

    def ms(pred):
        return sum(s.dur_ms for s in xla if pred(s))

    out = {
        "qid": trace.qid, "sql": trace.sql[:120],
        "extent_ms": trace.total_ms(),
        "xla_compile_ms": ms(lambda s: s.name == "xla:compile"
                             and s.attrs.get("cache") != "hit"),
        "xla_lower_ms": ms(lambda s: s.name == "xla:lower"),
        "cache_load_ms": ms(lambda s: s.attrs.get("cache") == "hit"),
        "compiles": len(compiles),
        "by_cache": {c: sum(1 for s in compiles if s.attrs.get("cache") == c)
                     for c in ("hit", "miss", "off")},
        "stages_ms": {s.name: round(s.dur_ms, 1) for s in spans
                      if s.kind == "stage"},
        "rung_compiles": [(s.name, round(s.dur_ms, 1)) for s in spans
                          if s.name.startswith("compile:")],
        "top": [{"fun": s.attrs.get("fun"), "cache": s.attrs.get("cache"),
                 "parent": s.parent, "rung": s.attrs.get("rung"),
                 "ms": round(s.dur_ms, 1)}
                for s in sorted(compiles, key=lambda s: -s.dur_ms)[:10]],
    }
    out["rest_ms"] = out["extent_ms"] - out["xla_compile_ms"] \
        - out["xla_lower_ms"] - out["cache_load_ms"]
    return out


def main() -> int:
    argv = sys.argv[1:]
    split = argv.index("--") if "--" in argv else len(argv)
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv[:split])

    import dask_sql_tpu
    from dask_sql_tpu.serving import compile_cache

    cost = {"calls": 0, "seconds": 0.0}
    _timed_listeners(cost)
    kept = []  # (trace store, registry) of each Context, not the Context:
    # run.py frees the engine's tables before its references run
    init = dask_sql_tpu.Context.__init__

    def keep_all(self, *a, **k):
        init(self, *a, **k)
        self.traces.keep = 1 << 20
        kept.append((self.traces, self.metrics))

    dask_sql_tpu.Context.__init__ = keep_all
    rc = bench_run.main(argv[split + 1:])
    traces = [t for store, _ in kept for t in list(store._traces.values())]
    listed = [_summary(t) for t in traces
              if any(s.name.startswith("xla:") for s in t.spans)]
    hists = {}
    if kept:
        snap = kept[0][1].snapshot()["histograms"]
        hists = {k: snap.get(k) for k in
                 ("xla.compile_ms", "xla.lower_ms", "xla.cache_load_ms")}
    out = {"argv": argv[split + 1:], "rc": rc,
           "totals": compile_cache.stats(), "histograms": hists,
           "listener": {"calls": cost["calls"],
                        "us_per_call": 1e6 * cost["seconds"]
                        / max(cost["calls"], 1)},
           "traces": listed}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=str)
    print(json.dumps({
        "compile_breakdown": args.out, "totals": out["totals"],
        "listener": out["listener"],
        "traces": [{k: t[k] for k in ("qid", "extent_ms", "xla_compile_ms",
                                      "xla_lower_ms", "cache_load_ms",
                                      "rest_ms", "by_cache")}
                   for t in listed]}, default=str), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
