#!/usr/bin/env python3
"""One run of one benchmark cell: load, warm, measure, check, print.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  It makes the tables from ``--seed``, hands them
to ``Context.create_table``, runs each of the cell's plan families once cold
and once warm, starts the cell's surface (``surfaces/<name>.py``), and then
drives the cell's traffic (``traffic.py``) for ``--seconds``.  After the
window it frees the engine, builds the plain references
(``references/<name>.py``) and compares every answer the window returned
(``compare.py``).

Every line but the last is one JSON object per phase (seconds there are
readings for the curious, not metrics).  The last line is the result:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``), then ``compared``: each number compared
beside its limit.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics; which those are is read
from ``BENCHMARK.json``, how each per-layer one is read from
``metrics/<name>.json`` and ``readers/<kind>.py``.

No TPU, fewer chips than the cell asks for, or a device kind that
``peaks.json`` does not list: exit 2 and no result line.  ``--rehearse-rows N``
is the CPU rehearsal: every phase with every table cut to N rows, any
platform, no metric reported and ``correct`` false.

``main`` takes two more things that are no part of the command line, for
``tools.py`` (whoever sets a limit or records a trace): ``control``, a lower
precision in which each reference also answers in the engine's place, and
``keep_trace``, where to keep the ``.xplane.pb`` of a traced run.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import compare, traffic, xplane  # noqa: E402

SLICE_AT = 0.4      # the traced slice opens this far into the window
SLICE_SECONDS = 4.0  # and lasts this long (a third of a short window)
PLAN_SPANS = ("parse", "bind", "optimize", "parameterize", "verify",
              "estimate", "cache_lookup")


def emit(**obj) -> None:
    print(json.dumps(obj, default=str), flush=True)


def plugin(kind: str, name: str):
    """``perfbench/<kind>/<name>.py``, found by the name a data file gives."""
    return importlib.import_module(f"perfbench.{kind}.{name}")


def buffers_of(table):
    for col in table.columns.values():
        yield col.data
        if col.validity is not None:
            yield col.validity
    if table.row_valid is not None:
        yield table.row_valid


class SliceTracer:
    """Profiles one slice of the window; ``poll`` is called as time passes."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled = enabled
        self.after = SLICE_AT * seconds
        self.length = min(SLICE_SECONDS, seconds / 3.0)
        self.window_start = None
        self.dir = self.mark = self.lo = self.hi = None

    def poll(self, now: float) -> None:
        if not self.enabled:
            return
        import jax

        if self.window_start is None:
            self.window_start = now
        if self.lo is None and now >= self.window_start + self.after:
            self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            options.enable_hlo_proto = False  # most of a trace's bytes
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.mark = time.perf_counter()
            with jax.profiler.TraceAnnotation(xplane.MARK):
                pass
            self.lo = time.perf_counter()
        elif self.lo is not None and self.hi is None \
                and now >= self.lo + self.length:
            self.hi = time.perf_counter()
            jax.profiler.stop_trace()

    def reduce(self, spans, keep_as=None):
        if self.lo is not None and self.hi is None:  # window closed early
            import jax

            self.hi = time.perf_counter()
            jax.profiler.stop_trace()
        if self.hi is None:
            return None
        try:
            path = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                          "*", "*.xplane.pb"))[0]
            if keep_as:
                os.makedirs(os.path.dirname(keep_as), exist_ok=True)
                shutil.copy(path, keep_as)
            trace = xplane.read(path)
            emit(phase="trace", bytes=os.path.getsize(path),
                 lines=trace["lines"], slice_s=self.hi - self.lo)
            return xplane.reduce(trace, self.mark, self.lo, self.hi, spans)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def host_spans(records):
    """(label, t0, t1) on the perf_counter clock for the idle-gap labels:
    the program's own spans (plan stages under one name); ``unspanned``,
    the request in the engine's hands with none of them open (the extent of
    its server-side trace, or all of a library call); and for requests that
    crossed the wire the client's wait around that."""
    out = []
    for rec in records:
        trace = rec.get("trace")
        closed = [s for s in (trace.spans if trace is not None else ())
                  if s.t1 is not None and s.t1 > s.t0]
        for s in closed:
            out.append(("plan" if s.name in PLAN_SPANS else s.name,
                        s.t0, s.t1))
        if "qid" in rec:
            out.append(("wire", rec["sent"], rec["done"]))
            if closed:
                out.append(("unspanned", min(s.t0 for s in closed),
                            max(s.t1 for s in closed)))
        else:
            out.append(("unspanned", rec["sent"], rec["done"]))
    return out


def applies(entry: dict, cell: dict) -> bool:
    """Whether a metric of ``BENCHMARK.json`` is this cell's to report."""
    return "workloads" not in entry or cell["name"] in entry["workloads"]


def chip_fault(device: dict, cell: dict, peaks_table: dict):
    """Why this machine cannot run the cell, or None where it can."""
    if device["platform"] != "tpu":
        return f"no TPU: jax.devices()[0].platform is {device['platform']!r}"
    if device["count"] < int(cell["chips"]):
        return (f"the cell asks for {cell['chips']} chips, jax sees "
                f"{device['count']}")
    if device["kind"] not in peaks_table:
        return (f"device kind {device['kind']!r} is not in "
                f"perfbench/peaks.json")
    return None


def control_verdict(precision, records, queries, references, arrays, ladder):
    """The lower-precision control in the engine's place: every answered
    request of the window gets the control's answer for its parameters, and
    the copies go through the same ``compare_window``.  It has to come out
    as not within the limits."""
    answers, copies = {}, []
    for rec in records:
        copy = {k: v for k, v in rec.items() if k not in ("ok", "gap")}
        if rec.get("answer") is not None:
            key = (rec["query"], json.dumps(rec["params"], sort_keys=True))
            if key not in answers:
                module = plugin("references",
                                queries[rec["query"]]["reference"])
                answers[key] = module.control_answer(arrays, rec["params"],
                                                     precision)
            copy["answer"] = answers[key]
        copies.append(copy)
    verdict = compare.compare_window(copies, queries, references, ladder)
    return {"within": verdict["within"], "compared": verdict["compared"],
            "parameter_sets": len(answers)}


def main(argv=None, control=None, keep_trace=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-rows", type=int, default=None,
                    help="CPU rehearsal: every table cut to this many rows; "
                         "any platform; reports no metric and correct: false")
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_rows is not None

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"perfbench: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    workload = traffic.load("workloads", args.workload)
    config = traffic.load("configs", cell["config"])
    queries = traffic.queries_of(workload)
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks_table = json.load(f)

    # nothing of the engine (or jax) is imported above this line
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    no_chip = chip_fault(device, cell, peaks_table)
    if no_chip and not rehearsal:
        print(f"perfbench: {no_chip}", file=sys.stderr)
        return 2
    peaks = peaks_table.get(device["kind"])
    used = devices[:int(cell["chips"])]

    from dask_sql_tpu import Context
    from dask_sql_tpu.serving import compile_cache
    from dask_sql_tpu.utils import TRANSFER_STATS

    compile_cache.enable(compile_cache.checkout_path())
    emit(phase="device", **device, chips=cell["chips"], rehearsal=rehearsal,
         compile_cache_dir=compile_cache.enabled_path(),
         imports_s=time.perf_counter() - PROCESS_START)

    # ------------------------------------------------------------ generate
    datagen = plugin("datagen", config["datagen"])
    rows = args.rehearse_rows if rehearsal else int(config["rows"])
    t0 = time.perf_counter()
    arrays = datagen.generate(rows, args.seed, int(config["scale_factor"]))
    t1 = time.perf_counter()
    # what a user hands to create_table: pandas frames or pyarrow tables
    frames = getattr(datagen, {"pandas": "frames", "arrow": "arrow_tables"}
                     [config["input"]])(arrays)
    emit(phase="generate", rows=rows, seed=args.seed, input=config["input"],
         draw_s=t1 - t0, frames_s=time.perf_counter() - t1)

    # ---------------------------------------------------------------- load
    ctx = Context()
    ctx.config.update(config["engine_config"])
    tables, load_s, loaded_rows = {}, 0.0, 0
    for name in config["tables"]:
        t0 = time.perf_counter()
        ctx.create_table(name, frames[name],
                         distributed=bool(config.get("distributed")))
        table = ctx.schema[ctx.schema_name].tables[name].table
        for buf in buffers_of(table):
            buf.block_until_ready()
        load_s += time.perf_counter() - t0
        loaded_rows += table.num_rows
        tables[name] = {"rows": table.num_rows,
                        "itemsize": {c: col.data.dtype.itemsize
                                     for c, col in table.columns.items()},
                        "device_bytes": sum(int(b.nbytes)
                                            for b in buffers_of(table))}
    del frames, table
    gc.collect()
    emit(phase="load", seconds=load_s, tables=tables,
         memory_stats=used[0].memory_stats())

    # ---------------------------------------------------------------- warm
    warm_rng = traffic.warm_rng(args.seed)
    first_query_s, parser = 0.0, None
    for name, query in queries.items():
        for temp in ("cold", "warm"):
            sql = traffic.render(query, traffic.draw_params(query, warm_rng))
            t0 = time.perf_counter()
            ctx.sql(sql).compute()
            seconds = time.perf_counter() - t0
            names = [s.name for s in ctx.last_trace.spans]
            if temp == "cold":
                first_query_s += seconds
                if parser is None:
                    native = [s.attrs.get("native") for s in
                              ctx.last_trace.spans if s.name == "bind"]
                    parser = "native" if native == [True] else "python"
            emit(phase=f"warm:{name}:{temp}", seconds=seconds,
                 compile_spans=[n for n in names if n.startswith("compile:")],
                 rungs=[n for n in names if n.startswith("rung:")],
                 family_hit="family_hit" in names)

    surface = plugin("surfaces", workload["surface"]).Surface(
        ctx, workload, queries, args.seed, args.seconds, emit)
    tracer = SliceTracer(bool(args.trace), args.seconds)
    try:
        surface.start()
        gc.collect()
        ladder_before = {k: ctx.metrics.counter(k) for k in
                         ("resilience.degraded", "resilience.rung.cpu")}
        d2h_before = TRANSFER_STATS["d2h"]
        cache_before = compile_cache.stats()
        setup_s = time.perf_counter() - PROCESS_START

        # ---------------------------------------------------------- window
        records, start, end = surface.run(tracer.poll)

        memory_peak = max(int((d.memory_stats() or {})
                              .get("peak_bytes_in_use", 0)) for d in used)
        ladder = {k: ctx.metrics.counter(k) - v
                  for k, v in ladder_before.items()}
        counters = {"memory_peak_bytes": memory_peak,
                    "d2h_transfers": TRANSFER_STATS["d2h"] - d2h_before}
    finally:
        surface.stop()
    for rec in records:
        trace = rec.get("trace")
        rec["spans"] = None if trace is None else [s.name
                                                   for s in trace.spans]
    try:
        profile = tracer.reduce(host_spans(records) if args.trace else [],
                                keep_trace)
    except ValueError as exc:
        if not rehearsal:  # a traced run must find the device's operations
            raise
        profile = None
        emit(phase="trace", rehearsal_no_device_plane=str(exc)[:200])
    emit(phase="window", seconds=end - start, requests=len(records),
         by_query={q: sum(1 for r in records if r["query"] == q)
                   for q in queries},
         polls=sum(r.get("polls", 0) for r in records),
         traces_kept=sum(1 for r in records if r.get("trace") is not None),
         rungs=sorted({n for r in records for n in (r["spans"] or ())
                       if n.startswith("rung:")}),
         parser=parser, ladder=ladder, counters=counters,
         persistent_cache={"before": cache_before,
                           "after": compile_cache.stats()})

    # the engine's state goes before the reference runs
    run = {"records": records, "clocks": {"first_query_s": first_query_s},
           "counters": counters, "profile": profile, "tables": tables,
           "peaks": peaks, "queries": queries,
           "slice": (tracer.lo, tracer.hi)}
    per_layer = {}
    if args.trace:
        for entry in bench["per_layer"]:
            if not applies(entry, cell):
                continue
            metric = traffic.load("metrics", entry["name"])
            value = plugin("readers", metric["reader"]).read(metric, run)
            if value is not None:
                per_layer[entry["name"]] = {"value": value,
                                            "unit": entry["unit"]}
    for rec in records:
        rec.pop("trace", None)
    del ctx, surface, run
    gc.collect()

    # ------------------------------------------------------------- compare
    t0 = time.perf_counter()
    references = {name: plugin("references", q["reference"]).Reference(arrays)
                  for name, q in queries.items()}
    reference_s = time.perf_counter() - t0
    verdict = compare.compare_window(records, queries, references, ladder)
    emit(phase="compare", reference_s=reference_s,
         compare_s=time.perf_counter() - t0 - reference_s,
         answers_compared=verdict["answers_compared"])
    if control:
        emit(phase="control", control=control,
             **control_verdict(control, records, queries, references, arrays,
                               ladder))

    # ------------------------------------------------------------- metrics
    # clients send nothing new after ``end``; the window closes when the
    # last request sent before it is answered, and the rate is all the
    # answered-and-right requests over all of that time (no request is cut
    # off and none counted in part, so a few long requests do not read in
    # steps of one)
    ok = [r for r in records if r.get("ok")]
    closed = max([end] + [r["done"] for r in records])
    latencies_ms = [(r["done"] - r["sent"]) * 1e3 if r.get("ok")
                    else float("inf") for r in records]
    end_to_end = {
        "queries_per_s": len(ok) / (closed - start),
        "load_mrows_per_s": loaded_rows / 1e6 / load_s,
        "setup_s": setup_s,
    }
    # a stall shows as one long request or as a client that paused between
    # a reply and its next send: both are printed, neither is a metric
    in_order = sorted(records, key=lambda r: (r["client"], r["index"]))
    pauses = [b["sent"] - a["done"] for a, b in zip(in_order, in_order[1:])
              if a["client"] == b["client"]]
    emit(phase="end_to_end", **end_to_end, window_s=closed - start,
         query_p95_ms=plugin("readers", "latency_percentile").read(
             {"percentile": 95}, {"records": records}),
         query_p50_ms=statistics.median(latencies_ms) if records else None,
         query_max_ms=max(latencies_ms, default=None),
         client_pause_max_ms=max(pauses, default=0.0) * 1e3,
         slowest=[[round(r["sent"] - start, 3), r["client"], round(l, 1)]
                  for l, r in sorted(zip(latencies_ms, records),
                                     key=lambda lr: -lr[0])[:6]],
         per_query_p50_ms={q: statistics.median(
             [l for l, r in zip(latencies_ms, records) if r["query"] == q]
             or [float("nan")]) for q in queries})
    metrics = {}
    if args.trace:
        metrics = per_layer
    else:
        for entry in bench["end_to_end"]:
            if not applies(entry, cell):
                continue
            value = end_to_end[entry["name"]]
            if value is not None and value != float("inf"):
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
    device["memory_peak_bytes"] = memory_peak
    result = {"correct": bool(verdict["within"] and records and not no_chip),
              "attempted": len(records),
              "failed": len(records) - len(ok),
              "metrics": {} if rehearsal else metrics,
              "device": device}
    if args.trace and profile is not None:
        device["busy_s"] = profile["busy_s"]
        device["window_s"] = profile["window_s"]
        result["breakdown"] = {"device_ops": profile["device_ops"],
                               "idle_gaps": profile["idle_gaps"]}
    if rehearsal:
        emit(phase="rehearsal", within_limits=verdict["within"],
             cpu_readings={**end_to_end, **{k: v["value"]
                                            for k, v in per_layer.items()}})
    result["compared"] = verdict["compared"]
    for name, c in verdict["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
