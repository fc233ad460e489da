#!/usr/bin/env python3
"""Chip smoke: the SQL main path on one TPU chip, through the entry points a
user calls, checked against a plain float64 reference.

What it drives
    TPC-H ``lineitem`` -> ``Context.create_table`` -> Q1 and Q6 through
    ``Context.sql(...).compute()`` (cold, then warm) -> the same two queries
    over the Presto wire (``POST /v1/statement`` + ``nextUri`` polling)
    against ``run_server`` started in THIS process.  One process, one chip.

Source of the shapes
    TPC-H v3, clause 1.4 (LINEITEM) and clauses 2.4.1 / 2.4.6 (Q1, Q6) — the
    same query texts ``tests/tpch.py`` holds.  Value domains follow the
    clause-4.2.3 population rules: quantity 1..50, discount 0.00..0.10, tax
    0.00..0.08, extendedprice = quantity * retailprice(partkey), shipdate =
    orderdate + 1..121 days, returnflag / linestatus derived from the
    receipt / ship date against 1995-06-17.  DECIMAL columns are float64 (the
    engine's DECIMAL, ``columnar/dtypes.py``).

Cuts (scale, not shape)
    Seven of LINEITEM's sixteen columns — the seven Q1 and Q6 read; one of
    the eight tables.  Default size: ``--sf 10`` = 60,000,000 rows, 24 B/row
    encoded on the device (two int32 dictionary codes, four int16 codes, one
    float64).  Joins, ORDER BY over large inputs and top-k are NOT part of
    this smoke (64-bit sorts compile very slowly for this chip; see
    CHANGES.md, PR 25 "Open questions").

What fails it
    No TPU (unless ``--allow-cpu``, a rehearsal that never reports ok), any
    phase raising, any answer outside tolerance, ANY step down the
    degradation ladder (``resilience.degraded`` / ``resilience.rung.cpu``
    must stay 0 and every query's trace must name a compiled rung), a
    ``compile:`` span on a warm call, a result buffer off the TPU, or wire
    rows that differ from library rows.

``--chips 4`` runs only the sharded rung: generate, load row-sharded over
four devices, Q1 through the library, the reference, the checks.

Every line but the last is one JSON object per phase.  Seconds printed here
are SMOKE READINGS, NOT BENCHMARK NUMBERS: single runs on a shared host.
Read on a TPU v5e at the default size (PR 25, one chip, SF10): generate 33 s,
load 70 s, references 17 s, cold Q1 10 s, whole command 148 s — inside the
1200 s the driver allows, so the default is not cut.  Unrounded: PERF.md.

The last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request

import numpy as np

ROWS_PER_SF = 6_000_000  # TPC-H clause 4.2.5: ~6M lineitem rows per SF

# tests/tpch.py QUERIES[1] and QUERIES[6], verbatim
Q1 = """
    SELECT l_returnflag, l_linestatus,
           SUM(l_quantity) AS sum_qty,
           SUM(l_extendedprice) AS sum_base_price,
           SUM(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
           SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
           AVG(l_quantity) AS avg_qty,
           AVG(l_extendedprice) AS avg_price,
           AVG(l_discount) AS avg_disc,
           COUNT(*) AS count_order
    FROM lineitem
    WHERE l_shipdate <= DATE '1998-09-02'
    GROUP BY l_returnflag, l_linestatus
    ORDER BY l_returnflag, l_linestatus
"""
Q6 = """
    SELECT SUM(l_extendedprice * l_discount) AS revenue
    FROM lineitem
    WHERE l_shipdate >= DATE '1994-01-01' AND l_shipdate < DATE '1995-01-01'
      AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24
"""
QUERIES = {"q1": Q1, "q6": Q6}

#: float sums: where the engine sums in float64 (scatter) the reference is met
#: to RTOL_F64; where it takes the blocked one-hot matmul, to the bound that
#: path is tested to (ops/pallas_kernels.py MATMUL_FLOAT_REL_ERR_BOUND)
RTOL_F64 = 1e-9
RTOL_MATMUL = 5e-6

COMPILED_RUNG_PREFIXES = ("compiled_", "spmd_")


def emit(**obj) -> None:
    print(json.dumps(obj, default=str), flush=True)


# --------------------------------------------------------------------- data
def gen_lineitem(rows: int, seed: int):
    """The seven LINEITEM columns Q1 and Q6 read, vectorised numpy."""
    import pandas as pd

    rng = np.random.default_rng(seed)
    day0 = np.datetime64("1992-01-01")
    current = int((np.datetime64("1995-06-17") - day0).astype(np.int64))
    # o_orderdate uniform in [STARTDATE, ENDDATE - 151 days]
    orderdate = rng.integers(0, 2406 - 151 + 1, rows, dtype=np.int32)
    shipdate = orderdate + rng.integers(1, 122, rows, dtype=np.int32)
    receiptdate = shipdate + rng.integers(1, 31, rows, dtype=np.int32)
    returnflag = np.where(
        receiptdate <= current,
        np.where(rng.integers(0, 2, rows, dtype=np.int8) == 0, "R", "A"), "N")
    linestatus = np.where(shipdate > current, "O", "F")
    del orderdate, receiptdate
    quantity = rng.integers(1, 51, rows, dtype=np.int64)
    partkey = rng.integers(1, 200_000 * 10 + 1, rows, dtype=np.int64)
    # p_retailprice (clause 4.2.3), in cents
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    del partkey
    return pd.DataFrame({
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_quantity": quantity.astype(np.float64),
        "l_extendedprice": (quantity * retail_cents) / 100.0,
        "l_discount": rng.integers(0, 11, rows, dtype=np.int64) / 100.0,
        "l_tax": rng.integers(0, 9, rows, dtype=np.int64) / 100.0,
        "l_shipdate": day0 + shipdate.astype("timedelta64[D]"),
    })


# ---------------------------------------------------------------- reference
def q1_reference(df):
    """Q1 in plain pandas/numpy float64; rows sorted by the group keys."""
    sel = df[df.l_shipdate <= np.datetime64("1998-09-02")]
    disc_price = sel.l_extendedprice * (1.0 - sel.l_discount)
    work = sel.assign(disc_price=disc_price,
                      charge=disc_price * (1.0 + sel.l_tax))
    return work.groupby(["l_returnflag", "l_linestatus"], sort=True).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"),
    ).reset_index()


def q6_reference(df):
    import pandas as pd

    m = ((df.l_shipdate >= np.datetime64("1994-01-01"))
         & (df.l_shipdate < np.datetime64("1995-01-01"))
         & (df.l_discount >= 0.05) & (df.l_discount <= 0.07)
         & (df.l_quantity < 24))
    price = df.l_extendedprice.to_numpy()[m.to_numpy()]
    disc = df.l_discount.to_numpy()[m.to_numpy()]
    return pd.DataFrame({"revenue": [float(np.sum(price * disc))]})


REFERENCES = {"q1": q1_reference, "q6": q6_reference}
KEY_COLUMNS = {"q1": ["l_returnflag", "l_linestatus"], "q6": []}
EXACT_COLUMNS = {"q1": ["count_order"], "q6": []}


# ------------------------------------------------------------------- checks
class Checks:
    """Every check lands here; one failure fails the run (never a warning)."""

    def __init__(self):
        self.failed = []

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failed.append(what)
            emit(check_failed=what)
        return bool(ok)


def compare_answer(checks: Checks, label: str, name: str, got, ref,
                   rtol: float) -> float:
    """`got` (engine, pandas) against `ref` (float64 reference); returns the
    worst relative error over the float columns."""
    keys, exact = KEY_COLUMNS[name], EXACT_COLUMNS[name]
    worst_seen = 0.0
    if not checks.check(list(got.columns) == list(ref.columns)
                        and len(got) == len(ref),
                        f"{label}: shape {got.shape} columns "
                        f"{list(got.columns)} != reference {ref.shape}"):
        return float("nan")
    for col in ref.columns:
        g, r = got[col].to_numpy(), ref[col].to_numpy()
        if col in keys:
            checks.check([str(x) for x in g] == [str(x) for x in r],
                         f"{label}: group keys {col} differ")
        elif col in exact:
            checks.check(np.array_equal(g.astype(np.int64),
                                        r.astype(np.int64)),
                         f"{label}: {col} not exact: {g} != {r}")
        else:
            g, r = g.astype(np.float64), r.astype(np.float64)
            fine = bool(np.all(np.isfinite(g))) and np.allclose(
                g, r, rtol=rtol, atol=0.0)
            worst = float(np.max(np.abs(g - r)
                                 / np.maximum(np.abs(r), 1e-300)))
            checks.check(fine, f"{label}: {col} off by {worst} relative "
                               f"(rtol {rtol})")
            worst_seen = max(worst_seen, worst)
    return worst_seen


def trace_names(trace):
    return [s.name for s in trace.spans]


def rung_of(trace):
    """Names of the rungs that answered, from the ``rung:<name>`` spans."""
    return [n[len("rung:"):] for n in trace_names(trace)
            if n.startswith("rung:")]


def ladder_reading(ctx) -> dict:
    return {"degraded": ctx.metrics.counter("resilience.degraded"),
            "rung_cpu": ctx.metrics.counter("resilience.rung.cpu")}


def check_ladder(checks: Checks, label: str, ctx, trace, *, cold: bool,
                 want_rung=None) -> dict:
    """Read the ladder after a query: no step-down, a compiled rung named,
    a compile span exactly where one belongs."""
    reading = ladder_reading(ctx)
    rungs = rung_of(trace)
    compiles = [n for n in trace_names(trace) if n.startswith("compile:")]
    steps = [n for n in trace_names(trace) if n.startswith("degraded")]
    checks.check(reading["degraded"] == 0 and reading["rung_cpu"] == 0,
                 f"{label}: the ladder stepped down: {reading} {steps}")
    checks.check(bool(rungs) and all(r.startswith(COMPILED_RUNG_PREFIXES)
                                     for r in rungs),
                 f"{label}: no compiled rung answered (rung spans: {rungs})")
    if want_rung is not None:
        spmd = [s for s in trace.spans if s.name == f"rung:{want_rung}"]
        checks.check(bool(spmd) and spmd[0].attrs.get("spmd") is True,
                     f"{label}: rung:{want_rung} with spmd=True not in trace "
                     f"({rungs})")
    if cold:
        checks.check(bool(compiles),
                     f"{label}: cold call shows no compile:<rung> span")
    else:
        checks.check(not compiles,
                     f"{label}: warm call compiled again: {compiles}")
    return {"rung": rungs, "compile_spans": compiles, **reading}


def buffers_of(table):
    for col in table.columns.values():
        yield col.data
        if col.validity is not None:
            yield col.validity
    if table.row_valid is not None:
        yield table.row_valid


def check_result_devices(checks: Checks, label: str, table, platform: str):
    """Every device buffer of a result sits on the smoke's platform (a CPU
    rung would leave them on the host platform); host-decoded numpy columns
    are counted, not checked."""
    import jax

    on_device = host = 0
    for buf in buffers_of(table):
        if isinstance(buf, jax.Array):
            on_device += 1
            plats = {d.platform for d in buf.devices()}
            checks.check(plats == {platform},
                         f"{label}: result buffer on {plats}, not {platform}")
        else:
            host += 1
    return {"result_device_buffers": on_device, "result_host_buffers": host}


# --------------------------------------------------------------------- wire
def wire_query(port: int, sql: str, timeout_s: float):
    """POST /v1/statement, poll nextUri to the end: (query id, rows)."""
    import pandas as pd

    req = urllib.request.Request(f"http://127.0.0.1:{port}/v1/statement",
                                 data=sql.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        payload = json.loads(resp.read())
    deadline = time.monotonic() + timeout_s
    while "nextUri" in payload:
        if time.monotonic() > deadline:
            raise TimeoutError(f"wire query still running after {timeout_s}s")
        time.sleep(0.05)
        with urllib.request.urlopen(payload["nextUri"],
                                    timeout=timeout_s) as resp:
            payload = json.loads(resp.read())
    if "error" in payload:
        raise RuntimeError(f"wire query failed: {payload['error']}")
    names = [c["name"] for c in payload["columns"]]
    return payload["id"], pd.DataFrame(payload.get("data", []), columns=names)


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=int, default=10,
                    help="TPC-H scale factor (6,000,000 lineitem rows each)")
    ap.add_argument("--rows", type=int, default=None,
                    help="lineitem rows; overrides --sf")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the row-sharded spmd_aggregate rung")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal: run every phase and check off the TPU; "
                         "the last line then says ok: false")
    args = ap.parse_args(argv)
    rows = args.rows if args.rows is not None else args.sf * ROWS_PER_SF

    # nothing of the engine (or jax) is imported above this line
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.allow_cpu:
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{device['platform']!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"jax sees {len(devices)}", file=sys.stderr)
        return 2

    from dask_sql_tpu import Context
    from dask_sql_tpu.physical import compiled as single_chip
    from dask_sql_tpu.serving import compile_cache
    from dask_sql_tpu.spmd import aggregate as spmd_aggregate
    from dask_sql_tpu.utils import TRANSFER_STATS

    # the cache directory the environment places, else <repo>/.jax_cache
    compile_cache.enable(compile_cache.checkout_path())
    emit(phase="device", **device, chips_asked=args.chips,
         compile_cache_dir=compile_cache.enabled_path(),
         note="seconds below are smoke readings, not benchmark numbers")

    checks = Checks()
    t0 = time.perf_counter()
    df = gen_lineitem(rows, args.seed)
    emit(phase="generate", rows=rows, seed=args.seed,
         seconds=time.perf_counter() - t0)

    ctx = Context()
    # every call executes: a warm call answered by the result cache would
    # never reach the device
    ctx.config.update({"serving.cache.enabled": False})
    t0 = time.perf_counter()
    ctx.create_table("lineitem", df, distributed=(args.chips > 1))
    table = ctx.schema[ctx.schema_name].tables["lineitem"].table
    for buf in buffers_of(table):
        buf.block_until_ready()
    load_s = time.perf_counter() - t0
    placed = sorted({d.id for buf in buffers_of(table) for d in buf.devices()})
    plats = {d.platform for buf in buffers_of(table) for d in buf.devices()}
    checks.check(plats == {device["platform"]},
                 f"load: table buffers on {plats}")
    if args.chips > 1:
        per_buffer = [len(buf.devices()) for buf in buffers_of(table)]
        checks.check(len(placed) >= args.chips
                     and all(n == len(placed) for n in per_buffer),
                     f"load: shards on devices {placed}, per buffer "
                     f"{per_buffer}; wanted {args.chips} distinct")
    emit(phase="load", seconds=load_s, rows=table.num_rows,
         device_bytes=sum(int(buf.nbytes) for buf in buffers_of(table)),
         columns={n: str(c.data.dtype) for n, c in table.columns.items()},
         devices=placed, memory_stats=devices[0].memory_stats())

    references = {}
    names = ["q1"] if args.chips > 1 else ["q1", "q6"]
    want_rung = "spmd_aggregate" if args.chips > 1 else None
    pipelines = (spmd_aggregate if args.chips > 1 else single_chip).PROGRAMS
    library_rows = {}
    tolerances = {}
    parser = None
    for name in names:
        t0 = time.perf_counter()
        references[name] = REFERENCES[name](df)
        emit(phase=f"reference:{name}", seconds=time.perf_counter() - t0)
        for temp in ("cold", "warm"):
            label = f"library:{name}:{temp}"
            before = set(pipelines.values())
            TRANSFER_STATS["d2h"] = 0
            t0 = time.perf_counter()
            frame = ctx.sql(QUERIES[name])
            got = frame.compute()
            seconds = time.perf_counter() - t0
            new = [p for p in pipelines.values() if p not in before]
            if temp == "cold":
                checks.check(bool(new), f"{label}: built no compiled pipeline")
                if parser is None:  # what bound the first query
                    native = [s.attrs.get("native") for s in
                              ctx.last_trace.spans if s.name == "bind"]
                    parser = "native" if native == [True] else "python"
                modes = sorted({p.segsum_mode for p in new})
                tolerances[name] = (RTOL_F64 if set(modes) <= {"scatter"}
                                    else RTOL_MATMUL)
            reading = check_ladder(checks, label, ctx, ctx.last_trace,
                                   cold=(temp == "cold"), want_rung=want_rung)
            reading.update(check_result_devices(
                checks, label, frame.execute(), device["platform"]))
            worst = compare_answer(checks, label, name, got,
                                   references[name], tolerances[name])
            library_rows[name] = got
            emit(phase=label, seconds=seconds, segsum=modes,
                 rtol=tolerances[name], max_rel_err=worst,
                 d2h=TRANSFER_STATS["d2h"], **reading)
    del df

    if args.chips == 1:
        from dask_sql_tpu.server.app import run_server

        server = run_server(context=ctx, host="127.0.0.1", port=0,
                            blocking=False)
        try:
            if ctx.warmup is not None:
                # server boot replays the hot profiled queries (the two
                # above) in the background; wait as a /v1/health client would
                t0 = time.perf_counter()
                ctx.warmup.join(600.0)
                checks.check(ctx.warmup.ready, "wire: boot warm-up not ready")
                emit(phase="wire:boot_warmup",
                     seconds=time.perf_counter() - t0, **ctx.warmup.status())
            for name in names:
                label = f"wire:{name}"
                TRANSFER_STATS["d2h"] = 0
                t0 = time.perf_counter()
                qid, got = wire_query(server.port, QUERIES[name],
                                      timeout_s=600.0)
                seconds = time.perf_counter() - t0
                reading = check_ladder(checks, label, ctx,
                                       ctx.traces.get(qid), cold=False)
                worst = compare_answer(checks, label, name, got,
                                       references[name], tolerances[name])
                lib = library_rows[name]
                checks.check(
                    got.shape == lib.shape and all(
                        list(got[c]) == [x.item() if hasattr(x, "item") else x
                                         for x in lib[c]]
                        for c in lib.columns),
                    f"{label}: wire rows differ from library rows")
                emit(phase=label, seconds=seconds, max_rel_err=worst,
                     d2h=TRANSFER_STATS["d2h"], **reading)
        finally:
            server.shutdown()

    emit(phase="summary", parser=parser,
         persistent_cache=compile_cache.stats(), ladder=ladder_reading(ctx),
         checks_failed=checks.failed)

    passed = not checks.failed
    if not on_tpu:
        emit(rehearsal_checks_passed=passed)
    print(json.dumps({"ok": bool(passed and on_tpu), "device": device}),
          flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
