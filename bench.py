"""Benchmark: TPC-H Q1 (SF~1 lineitem, synthetic) through the full SQL path.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
value = rows/sec/chip through c.sql() end-to-end (plan + device execution),
vs_baseline = speedup over pandas executing the same query (the reference's
single-partition execution engine).
"""
from __future__ import annotations

import json
import time

import numpy as np


N_ROWS = 6_000_000  # ~SF1 lineitem row count
QUERY = """
SELECT
    l_returnflag,
    l_linestatus,
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


def gen_lineitem(n: int, seed: int = 0):
    import pandas as pd

    rng = np.random.RandomState(seed)
    start = np.datetime64("1992-01-01")
    return pd.DataFrame(
        {
            "l_returnflag": rng.choice(["A", "N", "R"], n),
            "l_linestatus": rng.choice(["F", "O"], n),
            "l_quantity": rng.randint(1, 51, n).astype(np.float32),
            "l_extendedprice": (rng.rand(n).astype(np.float32) * 100000.0),
            "l_discount": (rng.rand(n).astype(np.float32) * 0.1),
            "l_tax": (rng.rand(n).astype(np.float32) * 0.08),
            "l_shipdate": start + rng.randint(0, 2526, n).astype("timedelta64[D]"),
        }
    )


def run_pandas(df):
    cutoff = np.datetime64("1998-09-02")
    sel = df[df.l_shipdate <= cutoff]
    disc_price = sel.l_extendedprice * (1 - sel.l_discount)
    charge = disc_price * (1 + sel.l_tax)
    work = sel.assign(disc_price=disc_price, charge=charge)
    out = work.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "count"),
    ).reset_index().sort_values(["l_returnflag", "l_linestatus"])
    return out


def compile_cache_dir(sub: str = "") -> str:
    """Where a bench phase keeps its persistent compile cache: the directory
    ``JAX_COMPILATION_CACHE_DIR`` places, else ``<repo>/.jax_cache[/sub]`` —
    fixed, because the path is part of the cache key."""
    import os

    from dask_sql_tpu.serving import compile_cache

    base = compile_cache.checkout_path()
    return compile_cache.env_path() or (
        os.path.join(base, sub) if sub else base)


def bench_q3_line(backend: str):
    """TPC-H Q3 (3-way join + topN) on the same chip.  Emitted as its own
    JSON line before the headline metric."""
    import sys

    sys.path.insert(0, "tests")
    from tpch import QUERIES, generate

    from dask_sql_tpu import Context

    n = 1_000_000
    tables = generate(scale_rows=n)
    c = Context()
    # result cache off: measure execution, not serving-cache lookups
    c.config.update({"serving.cache.enabled": False})
    for name, frame in tables.items():
        c.create_table(name, frame)
    q3 = QUERIES[3]
    c.sql(q3).compute()  # warm-up
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        c.sql(q3).compute()
        times.append(time.perf_counter() - t0)
    print(json.dumps({
        "metric": "tpch_q3_sf1_rows_per_sec_per_chip",
        "value": round(n / min(times), 1),
        "unit": "rows/s",
        "backend": backend,
    }), flush=True)


def run_inject_smoke():
    """`bench.py --inject`: deterministic fault-injection smoke.

    Proves on real hardware (or CPU) that a forced compile failure and a
    forced device-OOM each complete the benchmark query via a lower ladder
    rung with the SAME result as the clean run, and prints one JSON line
    with the degradation counters.  Small and seed-pinned so CI can run it
    on every change without slowing the normal bench path.
    """
    import jax

    from dask_sql_tpu import Context
    from dask_sql_tpu import config as config_module
    from dask_sql_tpu.resilience import faults

    df = gen_lineitem(100_000, seed=0)
    c = Context()
    c.config.update({"serving.cache.enabled": False})
    c.create_table("lineitem", df)
    clean = c.sql(QUERY, return_futures=False)

    degradations = {}
    ok = True
    for spec in ("compile:always", "oom:once"):
        faults.reset()
        ctx = Context()
        ctx.config.update({"serving.cache.enabled": False})
        ctx.create_table("lineitem", df)
        with config_module.set({"resilience.inject": spec,
                                "resilience.inject.seed": 0}):
            hurt = ctx.sql(QUERY, return_futures=False)
        degraded = ctx.metrics.counter("resilience.degraded")
        degradations[spec] = degraded
        same = (len(hurt) == len(clean) and np.allclose(
            hurt["sum_qty"].to_numpy(np.float64),
            clean["sum_qty"].to_numpy(np.float64), rtol=1e-9))
        ok = ok and same and degraded >= 1
    faults.reset()
    print(json.dumps({
        "metric": "fault_injection_smoke",
        "backend": jax.default_backend(),
        "ok": bool(ok),
        "degradations": degradations,
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_estimate_smoke():
    """`bench.py --estimate`: estimate-vs-actual bytes for the bench queries.

    Prints one JSON line per bench query with the estimator's
    (rows_lo, rows_hi, bytes_lo, bytes_hi) next to the measured resident
    bytes and result rows, and fails when a bound is violated (upper bound
    below measured, or measured rows outside the cardinality interval).
    Host + small-device work only — safe to run on every change.
    """
    from dask_sql_tpu import Context
    from dask_sql_tpu.analysis import estimator
    from dask_sql_tpu.planner.parser import parse_sql
    from dask_sql_tpu.serving.cache import table_nbytes

    # tests/ is a package and the script dir rides sys.path, so this works
    # from any cwd (the cwd-relative "tests" path hack would not)
    from tests.tpch import QUERIES, generate

    ok = True
    # q1 shape on synthetic lineitem; q3 shape on the tpch toolkit tables
    cases = []
    c1 = Context()
    c1.config.update({"serving.cache.enabled": False})
    c1.create_table("lineitem", gen_lineitem(100_000, seed=0))
    cases.append(("q1", c1, QUERY))
    c3 = Context()
    c3.config.update({"serving.cache.enabled": False})
    for name, frame in generate(scale_rows=100_000).items():
        c3.create_table(name, frame)
    cases.append(("q3", c3, QUERIES[3]))

    from dask_sql_tpu.planner import plan as plan_nodes

    def scanned_tables(node, seen):
        if isinstance(node, plan_nodes.TableScan):
            seen.add(node.table_name)
        for child in node.inputs():
            scanned_tables(child, seen)
        return seen

    for label, c, sql in cases:
        plan = c._get_ral(parse_sql(sql)[0], sql_text=sql)
        est = estimator.estimate_plan(plan, context=c)
        frame = c.sql(sql)
        result_table = frame.execute()
        result = frame.compute()
        # a true peak lower bound the hi bound must dominate: the tables
        # the PLAN references (plan-scoped — unreferenced catalog tables
        # are not its claim) plus the materialized result, both resident
        # simultaneously at query end.  Intermediate/scratch peaks are not
        # observable from the host here, so this check is partial.
        measured = sum(table_nbytes(c.schema["root"].tables[t].table)
                       for t in scanned_tables(plan, set()))
        measured += table_nbytes(result_table)
        rows_ok = (est.rows.lo <= len(result)
                   and (est.rows.hi is None or len(result) <= est.rows.hi))
        bytes_ok = est.peak_bytes.hi is None or est.peak_bytes.hi >= measured
        # the lower bound is what admission SHEDS on: it claims exactly
        # "resident scanned tables + materialized root", both of which
        # `measured` observes, so lo <= measured is a hard invariant
        lo_ok = est.peak_bytes.lo <= measured
        ok = ok and rows_ok and bytes_ok and lo_ok
        print(json.dumps({
            "metric": f"estimate_vs_actual_{label}",
            "rows_lo": est.rows.lo, "rows_hi": est.rows.hi,
            "bytes_lo": est.peak_bytes.lo, "bytes_hi": est.peak_bytes.hi,
            "measured_resident_bytes": measured,
            "actual_rows": len(result),
            "rows_ok": bool(rows_ok), "bytes_ok": bool(bytes_ok),
            "bytes_lo_ok": bool(lo_ok),
        }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_profile_smoke():
    """`bench.py --profile`: query-lifecycle trace smoke.

    Runs the benchmark query once through the Context API with lifecycle
    tracing (observability/), asserts the trace is COMPLETE — every
    expected stage present, stage timestamps monotonic and non-overlapping,
    at least one per-rung compile span recorded — and writes the
    Chrome-trace JSON artifact so a CI run leaves a loadable profile
    behind.  Small input, safe to run on every change.
    """
    import json as _json
    import os

    from dask_sql_tpu import Context

    c = Context()
    c.config.update({"serving.cache.enabled": False})
    c.create_table("lineitem", gen_lineitem(100_000, seed=0))
    c.sql(QUERY, return_futures=False)
    tr = c.last_trace
    stages = tr.stage_spans()
    names = [s.name for s in stages]
    required = ["parse", "bind", "verify", "estimate", "execute", "d2h"]
    missing = [r for r in required if r not in names]
    # stages must be sequential: each one ends before the next begins
    monotonic = all(stages[i].t1 <= stages[i + 1].t0 + 1e-9
                    for i in range(len(stages) - 1))
    compiles = [s for s in tr.spans if s.name.startswith("compile:")]
    artifact = os.environ.get("DSQL_PROFILE_ARTIFACT",
                              "/tmp/dsql_q1_trace.json")
    with open(artifact, "w") as f:
        _json.dump(tr.to_chrome_trace(), f)
    ok = not missing and monotonic and len(compiles) >= 1
    print(_json.dumps({
        "metric": "lifecycle_profile_smoke",
        "ok": bool(ok),
        "stages": names,
        "missing_stages": missing,
        "monotonic": bool(monotonic),
        "compile_spans": len(compiles),
        "fingerprint": tr.fingerprint,
        "artifact": artifact,
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_coldstart_smoke():
    """`bench.py --coldstart`: zero-cold-start restart smoke.

    Serves the benchmark query cold (foreground compiles, persistent
    executable cache filling), snapshots, then restarts the Context
    in-process: load_state restores tables + profiles and kicks the
    profile-driven warm-up.  Asserts the restart contract — the warm-up
    reaches ready, the pre-warmed fingerprint's first query shows ZERO
    foreground ``compile:<rung>`` spans in its lifecycle trace, and the
    persistent cache recorded at least one cross-"process" hit — and
    reports cold-vs-warm first-query latency.  Exit 1 on violation.
    """
    import json as _json
    import os
    import shutil
    import tempfile

    import jax

    from dask_sql_tpu import Context
    from dask_sql_tpu import config as config_module
    from dask_sql_tpu.serving import compile_cache

    # the phase wants an EMPTY cache: it empties its own fixed
    # sub-directory (a directory the environment places is left as found)
    cache_dir = compile_cache_dir("coldstart")
    if compile_cache.env_path() is None:
        shutil.rmtree(cache_dir, ignore_errors=True)
    work = tempfile.mkdtemp(prefix="dsql_coldstart_")  # snapshot only
    config_module.config.update({
        "serving.cache.enabled": False,
        "serving.compile_cache.path": cache_dir,
    })
    df = gen_lineitem(100_000, seed=0)

    c1 = Context()
    c1.create_table("lineitem", df)
    t0 = time.perf_counter()
    cold = c1.sql(QUERY, return_futures=False)
    cold_ms = (time.perf_counter() - t0) * 1000.0
    c1.sql(QUERY).execute()  # second hit: the fingerprint is clearly hot
    snap = os.path.join(work, "snapshot")
    c1.save_state(snap)

    c2 = Context()  # the "restarted process"
    c2.load_state(snap)
    warm = c2.warmup
    warmed = ready = 0
    if warm is not None:
        warm.join(300)
        ready = int(warm.ready)
        warmed = warm.warmed
    t0 = time.perf_counter()
    out = c2.sql(QUERY, return_futures=False)
    warm_ms = (time.perf_counter() - t0) * 1000.0
    tr = c2.last_trace
    fg_compiles = [s.name for s in tr.spans if s.name.startswith("compile:")]
    same = len(out) == len(cold) and np.allclose(
        out["sum_qty"].to_numpy(np.float64),
        cold["sum_qty"].to_numpy(np.float64), rtol=1e-9)

    ok = bool(ready and warmed >= 1 and not fg_compiles and same)
    print(_json.dumps({
        "metric": "coldstart_smoke",
        "backend": jax.default_backend(),
        "ok": ok,
        "cold_first_query_ms": round(cold_ms, 2),
        "warm_first_query_ms": round(warm_ms, 2),
        "cold_over_warm": round(cold_ms / warm_ms, 2) if warm_ms else None,
        "warmed_fingerprints": warmed,
        "foreground_compile_spans": fg_compiles,
        "persistent_cache": compile_cache.stats(),
        "results_match": bool(same),
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_families_smoke():
    """`bench.py --families`: parameterized plan families + batching smoke.

    Two checks, exit 1 on violation:

    1. *Compile-once-run-many*: two sequential queries differing only in a
       literal must share one family fingerprint, and the SECOND query's
       lifecycle trace must contain ZERO foreground ``compile:<rung>``
       spans (one executable serves the family).
    2. *Inter-query batching*: N concurrent clients issuing same-family
       queries with distinct literals through a ServingRuntime must be
       served with exactly ONE client paying a foreground compile (the
       batch leader) and at least one stacked launch serving >1 query
       (``serving.batch.launches`` / ``serving.batch.queries``), with
       every client's result matching pandas.
    """
    import json as _json

    import jax

    from dask_sql_tpu import Context
    from dask_sql_tpu.serving.runtime import ServingRuntime

    def q(disc):
        return ("SELECT l_returnflag, SUM(l_extendedprice) AS s, "
                "COUNT(*) AS n FROM lineitem "
                f"WHERE l_discount > {disc} GROUP BY l_returnflag")

    def compile_spans(tr):
        return [s.name for s in tr.spans if s.name.startswith("compile:")]

    df = gen_lineitem(100_000, seed=0)

    # -- phase 1: sequential family proof ---------------------------------
    c1 = Context()
    c1.config.update({"serving.cache.enabled": False})
    c1.create_table("lineitem", df)
    c1.sql(q(0.02), return_futures=False)
    tr_first = c1.last_trace
    c1.sql(q(0.05), return_futures=False)
    tr_second = c1.last_trace
    seq_same_family = tr_first.fingerprint == tr_second.fingerprint
    seq_second_compiles = compile_spans(tr_second)
    seq_ok = (seq_same_family and len(compile_spans(tr_first)) >= 1
              and not seq_second_compiles)

    # -- phase 2: concurrent clients, cold context, batched launch --------
    c2 = Context()
    c2.config.update({"serving.cache.enabled": False})
    c2.create_table("lineitem", df)
    discs = [0.01, 0.03, 0.05, 0.07]
    # batch bound == client count so the group closes the moment everyone
    # arrives; the window is an upper bound for stragglers (host-side
    # parse/bind of the members serializes under the GIL)
    runtime = ServingRuntime(workers=8, metrics=c2.metrics,
                             batch_queries=len(discs),
                             batch_window_ms=2000.0)
    c2.serving = runtime
    for d in discs:
        # pre-plan (no execution): the clients then hit the plan cache and
        # reach the executor together, so the phase measures EXECUTION
        # batching rather than GIL-serialized parse jitter
        c2.sql(q(d))
    frames = {}

    def client(disc):
        def work(_ticket):
            frame = c2.sql(q(disc))
            frame.execute()
            frames[disc] = frame
            return frame
        return work

    futures = [runtime.submit(client(d))[1] for d in discs]
    for fut in futures:
        fut.result(300)
    runtime.shutdown(wait=True)
    results_ok = True
    for disc in discs:
        got = frames[disc].execute().to_pandas().set_index(
            frames[disc].columns[0])
        exp = df[df.l_discount > disc].groupby("l_returnflag").agg(
            s=("l_extendedprice", "sum"), n=("l_extendedprice", "count"))
        # rtol: f32 sums of ~25k values differ by summation order alone
        results_ok = results_ok and len(got) == len(exp) and all(
            np.allclose(got.loc[k, "s"], exp.loc[k, "s"], rtol=1e-4)
            and got.loc[k, "n"] == exp.loc[k, "n"] for k in exp.index)
    compiling_clients = sum(
        1 for f in frames.values()
        if f._trace is not None and compile_spans(f._trace))
    launches = c2.metrics.counter("serving.batch.launches")
    batched_queries = c2.metrics.counter("serving.batch.queries")
    conc_ok = (compiling_clients == 1 and launches >= 1
               and batched_queries >= 2 and results_ok)

    ok = seq_ok and conc_ok
    print(_json.dumps({
        "metric": "plan_families_smoke",
        "backend": jax.default_backend(),
        "ok": bool(ok),
        "sequential_same_family": bool(seq_same_family),
        "sequential_second_query_compiles": seq_second_compiles,
        "concurrent_clients": len(discs),
        "clients_with_foreground_compile": compiling_clients,
        "batched_launches": launches,
        "queries_served_batched": batched_queries,
        "results_match": bool(results_ok),
        "family": tr_first.fingerprint,
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def gen_lineitem_compressed(n: int, seed: int = 0):
    """Lineitem with the REAL TPC-H value domains the float32 bench
    generator flattens away: 11 distinct discounts, 9 taxes, 50 quantities,
    day-granular dates (DICT targets) and a stride-4 orderkey (FOR target);
    l_extendedprice stays continuous float64 (PLAIN control)."""
    import pandas as pd

    rng = np.random.RandomState(seed)
    start = np.datetime64("1992-01-01")
    return pd.DataFrame({
        "l_returnflag": rng.choice(["A", "N", "R"], n),
        "l_linestatus": rng.choice(["F", "O"], n),
        "l_orderkey": (rng.randint(0, 1_500_000, n) * 4).astype(np.int64),
        "l_linenumber": rng.randint(1, 8, n).astype(np.int64),
        "l_quantity": rng.randint(1, 51, n).astype(np.float64),
        "l_extendedprice": rng.rand(n) * 100000.0,
        "l_discount": rng.randint(0, 11, n) / 100.0,
        "l_tax": rng.randint(0, 9, n) / 100.0,
        "l_shipdate": start + rng.randint(0, 2526, n).astype("timedelta64[D]"),
    })


def run_compressed_smoke():
    """`bench.py --compressed`: compressed-domain execution smoke.

    Contracts, exit 1 on violation:

    1. *Byte reduction*: the registered lineitem stores DICT/FOR-encoded
       columns and its resident scan bytes are < 0.6x the decoded widths.
    2. *Compressed-domain execution*: TPC-H q1/q6-shape scans run on the
       COMPILED rungs with ZERO full-column decodes
       (``columnar.encoding.decode`` == 0) and at least one code-space
       predicate rewrite — predicates evaluate on codes, values
       materialize late.
    3. *Correctness*: every result is byte-identical to the same query on
       an encodings-off context, and matches pandas.
    4. *Estimator*: ``EXPLAIN ESTIMATE`` (estimate_plan) on the encoded
       context reports a strictly smaller ``peak_bytes.hi`` than with
       encodings off — encoded widths shrink the admission intervals.
    """
    import json as _json

    import jax

    from dask_sql_tpu import Context
    from dask_sql_tpu.analysis import estimator
    from dask_sql_tpu.columnar.encodings import Encoding, scan_bytes
    from dask_sql_tpu.planner.parser import parse_sql

    n = 200_000
    df = gen_lineitem_compressed(n, seed=0)

    c_enc = Context()
    c_enc.config.update({"serving.cache.enabled": False})
    c_enc.create_table("lineitem", df)
    c_off = Context()
    c_off.config.update({"serving.cache.enabled": False,
                         "columnar.encoding": "off"})
    c_off.create_table("lineitem", df)

    t = c_enc.schema["root"].tables["lineitem"].table
    encodings = {name: col.encoding.value for name, col in t.columns.items()}
    enc_b, dec_b = scan_bytes(t)
    ratio = enc_b / dec_b
    dict_for = any(v == "DICT" for v in encodings.values()) and \
        any(v == "FOR" for v in encodings.values())
    bytes_ok = dict_for and ratio < 0.6

    q6 = ("SELECT SUM(l_extendedprice * l_discount) AS revenue, COUNT(*) AS n "
          "FROM lineitem WHERE l_shipdate >= DATE '1994-01-01' "
          "AND l_shipdate < DATE '1995-01-01' "
          "AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24")
    qg = ("SELECT l_linenumber, COUNT(*) AS n, SUM(l_quantity) AS s "
          "FROM lineitem GROUP BY l_linenumber ORDER BY l_linenumber")
    queries = {"q1": QUERY, "q6": q6, "qgroup": qg}

    results_identical = True
    for label, sql in queries.items():
        got = c_enc.sql(sql, return_futures=False)
        ref = c_off.sql(sql, return_futures=False)
        same = len(got) == len(ref) and all(
            np.array_equal(got[col].to_numpy(), ref[col].to_numpy())
            for col in got.columns)
        results_identical = results_identical and same

    # pandas cross-checks
    pd_ok = True
    exp1 = run_pandas(df)
    got1 = c_enc.sql(QUERY, return_futures=False)
    pd_ok &= len(got1) == len(exp1) and np.allclose(
        got1["sum_qty"].to_numpy(np.float64),
        exp1["sum_qty"].to_numpy(np.float64), rtol=1e-9)
    sel = df[(df.l_shipdate >= np.datetime64("1994-01-01"))
             & (df.l_shipdate < np.datetime64("1995-01-01"))
             & (df.l_discount >= 0.05) & (df.l_discount <= 0.07)
             & (df.l_quantity < 24)]
    got6 = c_enc.sql(q6, return_futures=False)
    pd_ok &= np.allclose(float(got6["revenue"][0]),
                         float((sel.l_extendedprice * sel.l_discount).sum()),
                         rtol=1e-9) and int(got6["n"][0]) == len(sel)

    decodes = c_enc.metrics.counter("columnar.encoding.decode")
    codespace = c_enc.metrics.counter("columnar.encoding.codespace_pred")
    compiled_runs = (c_enc.metrics.counter("resilience.rung.compiled_aggregate")
                     + c_enc.metrics.counter("resilience.rung.compiled_select")
                     + c_enc.metrics.counter(
                         "resilience.rung.compiled_join_aggregate"))
    compressed_ok = decodes == 0 and codespace >= 1 and compiled_runs >= 1

    est_enc = estimator.estimate_plan(
        c_enc._get_ral(parse_sql(q6)[0], sql_text=q6), context=c_enc)
    est_off = estimator.estimate_plan(
        c_off._get_ral(parse_sql(q6)[0], sql_text=q6), context=c_off)
    est_ok = (est_enc.peak_bytes.hi is not None
              and est_off.peak_bytes.hi is not None
              and est_enc.peak_bytes.hi < est_off.peak_bytes.hi)

    ok = bytes_ok and results_identical and pd_ok and compressed_ok and est_ok
    print(_json.dumps({
        "metric": "compressed_domain_smoke",
        "backend": jax.default_backend(),
        "ok": bool(ok),
        "encodings": encodings,
        "encoded_bytes": enc_b,
        "decoded_bytes": dec_b,
        "encoded_over_decoded": round(ratio, 3),
        "bytes_ok": bool(bytes_ok),
        "full_column_decodes": decodes,
        "codespace_predicates": codespace,
        "compiled_rung_runs": compiled_runs,
        "results_identical_to_decoded": bool(results_identical),
        "results_match_pandas": bool(pd_ok),
        "estimate_hi_encoded": est_enc.peak_bytes.hi,
        "estimate_hi_plain": est_off.peak_bytes.hi,
        "estimate_ok": bool(est_ok),
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_spmd_smoke():
    """`bench.py --spmd`: SPMD sharded-execution smoke (ISSUE 11).

    Shards lineitem over the local mesh, runs the Q1 shape on the sharded
    and the single-chip context, and asserts: the spmd_aggregate rung
    fired (trace span attr), results match pandas, and — on >= 2 REAL
    devices — sharded rows/s is at least the single-chip run.  On the CPU
    backend the mesh is virtual (every "device" shares the same cores), so
    the perf bar is reported but not enforced.  Exit 1 on violation."""
    import os

    # the virtual mesh must exist BEFORE jax initializes
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    import jax

    from dask_sql_tpu import Context

    ndev = len(jax.devices())
    if ndev < 2:
        print(json.dumps({"metric": "spmd_smoke", "ok": True,
                          "skipped": "single-device environment"}),
              flush=True)
        return

    n = min(N_ROWS, 2_000_000)
    df = gen_lineitem(n, seed=0)
    expected = run_pandas(df)

    def timed(ctx):
        ctx.sql(QUERY).compute()  # warm (compile)
        t0 = time.perf_counter()
        res = ctx.sql(QUERY).compute()
        return res, n / (time.perf_counter() - t0)

    single = Context()
    single.config.update({"serving.cache.enabled": False})
    single.create_table("lineitem", df)
    _, single_rate = timed(single)

    sharded = Context()
    sharded.config.update({"serving.cache.enabled": False})
    sharded.create_table("lineitem", df, distributed=True)
    res, spmd_rate = timed(sharded)

    tr = sharded.last_trace
    rung_spans = [s for s in tr.spans if s.name == "rung:spmd_aggregate"
                  and s.attrs.get("spmd")]
    rung_fired = bool(rung_spans) and \
        sharded.metrics.counter("resilience.rung.spmd_aggregate") >= 1

    res = res.sort_values(["l_returnflag", "l_linestatus"]).reset_index(
        drop=True)
    exp = expected.reset_index(drop=True)
    try:
        np.testing.assert_allclose(
            res["sum_qty"].to_numpy(np.float64),
            exp["sum_qty"].to_numpy(np.float64), rtol=1e-6)
        np.testing.assert_allclose(
            res["count_order"].to_numpy(np.float64),
            exp["count_order"].to_numpy(np.float64))
        pd_ok = list(res["l_returnflag"]) == list(exp["l_returnflag"])
    except AssertionError:
        pd_ok = False

    perf_enforced = jax.default_backend() != "cpu"
    perf_ok = (not perf_enforced) or spmd_rate >= single_rate
    ok = rung_fired and pd_ok and perf_ok
    print(json.dumps({
        "metric": "spmd_smoke",
        "backend": jax.default_backend(),
        "ok": bool(ok),
        "devices": ndev,
        "spmd_rung_fired": bool(rung_fired),
        "results_match_pandas": bool(pd_ok),
        "spmd_rows_per_sec": round(spmd_rate, 1),
        "single_chip_rows_per_sec": round(single_rate, 1),
        "speedup": round(spmd_rate / single_rate, 3) if single_rate else None,
        "perf_enforced": bool(perf_enforced),
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_predict_smoke():
    """`bench.py --predict`: compiled in-plan inference smoke.

    Trains a gradient-boosted model on TPC-H-shaped data, then asserts
    (exit 1 on violation):

    1. *Fused rung*: the PREDICT query answers on ``compiled_predict``
       (the ``rung:compiled_predict`` span is present — model inference
       ran in the scan's executable, no mid-plan host round trip);
    2. *Correctness*: the fused predictions match ``model.predict`` over
       the pandas-filtered rows within float tolerance;
    3. *Zero recompile*: a second literal variant AND a retrained
       same-shape model both serve with ZERO foreground compile spans.
    """
    import json as _json

    import jax

    from dask_sql_tpu import Context

    df = gen_lineitem(100_000, seed=0)
    c = Context()
    c.config.update({"serving.cache.enabled": False})
    c.create_table("lineitem", df)

    def train(seed):
        c.sql("""CREATE OR REPLACE MODEL revenue WITH (
                 model_class = 'sklearn.ensemble.GradientBoostingRegressor',
                 target_column = 'l_extendedprice',
                 n_estimators = 10, max_depth = 3, random_state = {})
                 AS (SELECT l_quantity, l_discount, l_tax, l_extendedprice
                     FROM lineitem)""".format(seed), return_futures=False)

    def q(disc):
        return ("SELECT * FROM PREDICT(MODEL revenue, "
                "SELECT l_quantity, l_discount, l_tax FROM lineitem "
                f"WHERE l_discount > {disc})")

    def compile_spans(tr):
        return [s.name for s in tr.spans if s.name.startswith("compile:")]

    train(0)
    res1 = c.sql(q(0.02), return_futures=False)
    tr1 = c.last_trace
    fused = any(s.name == "rung:compiled_predict" for s in tr1.spans)
    model, cols = c.get_model(c.schema_name, "revenue")
    sub = df[df.l_discount > 0.02]
    expected = model.predict(sub[cols].to_numpy())
    correct = len(res1) == len(sub) and np.allclose(
        res1["target"].to_numpy(dtype=np.float64), expected, rtol=1e-6)
    # second literal variant: zero foreground compiles
    c.sql(q(0.021), return_futures=False)  # warm this survivor bucket
    res2 = c.sql(q(0.0215), return_futures=False)
    tr2 = c.last_trace
    variant_compiles = compile_spans(tr2)
    # retrain with the same hyper-shape: weights swap, zero compiles
    train(7)
    res3 = c.sql(q(0.0215), return_futures=False)
    tr3 = c.last_trace
    retrain_compiles = compile_spans(tr3)
    model2, _ = c.get_model(c.schema_name, "revenue")
    sub3 = df[df.l_discount > 0.0215]
    retrain_correct = np.allclose(
        res3["target"].to_numpy(dtype=np.float64),
        model2.predict(sub3[cols].to_numpy()), rtol=1e-6)
    swaps = c.metrics.counter("inference.model.swap")

    ok = (fused and correct and not variant_compiles
          and not retrain_compiles and retrain_correct and swaps >= 1)
    print(_json.dumps({
        "metric": "compiled_predict_smoke",
        "backend": jax.default_backend(),
        "fused_rung": bool(fused),
        "predictions_match": bool(correct),
        "variant_foreground_compiles": variant_compiles,
        "retrain_foreground_compiles": retrain_compiles,
        "retrain_predictions_match": bool(retrain_correct),
        "model_swaps": swaps,
        "rows": len(res1),
        "ok": bool(ok),
    }, indent=2), flush=True)
    if not ok:
        raise SystemExit(1)


def run_lint_smoke():
    """`bench.py --lint`: static + runtime concurrency-analysis smoke.

    Three gates, one JSON line, exit 1 on any failure:

    1. engine self-lint (all rules DSQL101-703, including the repo-wide
       lock-order pass and the CFG-based effect-lifecycle rules) must be
       clean — a per-rule findings table is printed either way;
    2. `EXPLAIN LINT` of the benchmark query must verify with zero errors;
    3. a 2-replica fleet booted with the runtime lock sanitizer ON serves
       concurrent reads plus a fanned-out INSERT INTO with ZERO
       ``lock.order_violation`` flight events — the dynamic counterpart
       of gate 1's DSQL601.

    Pure host work — safe to run on every change without touching devices.
    """
    from dask_sql_tpu.analysis import self_lint
    from dask_sql_tpu.analysis.selflint import RULES

    findings = self_lint()
    for f in findings:
        print(f.format(), flush=True)
    by_rule = {rule: 0 for rule in sorted(RULES)}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    width = max(len(r) for r in by_rule)
    print(f"  {'rule':<{width}}  findings  description", flush=True)
    for rule, count in sorted(by_rule.items()):
        desc = RULES.get(rule, "syntax error")
        print(f"  {rule:<{width}}  {count:>8}  {desc}", flush=True)

    from dask_sql_tpu import Context

    c = Context()
    c.create_table("lineitem", gen_lineitem(10_000, seed=0))
    rows = list(c.sql("EXPLAIN LINT " + QUERY, return_futures=False)["LINT"])
    errors = sum(1 for r in rows if r.startswith("error["))

    # gate 3: the sanitizer watching the full declared rank order
    # (router.apply 10 -> ... -> observability.flight 95) under a real
    # concurrent fleet workload
    from concurrent.futures import ThreadPoolExecutor

    from dask_sql_tpu import config as _config_module
    from dask_sql_tpu.fleet import build_fleet
    from dask_sql_tpu.observability import flight
    from dask_sql_tpu.runtime import locks as runtime_locks

    _config_module.config.update({"analysis.lock_sanitizer": True})
    lock_baseline = runtime_locks.violation_count()
    flight_baseline = len(flight.RECORDER.events(name="lock.order_violation"))
    df = gen_lineitem(5_000, seed=1)

    def factory():
        fc = Context()  # arms the sanitizer (analysis.lock_sanitizer)
        fc.create_table("lineitem", df)
        return fc

    router, members, _replicator = build_fleet(factory, replicas=2,
                                               standby=False)
    try:
        with ThreadPoolExecutor(max_workers=4,
                                thread_name_prefix="lint-fleet") as pool:
            futs = [pool.submit(router.execute, QUERY, f"lint-r{i}")
                    for i in range(6)]
            futs.append(pool.submit(
                router.execute,
                "INSERT INTO lineitem SELECT * FROM lineitem LIMIT 5",
                "lint-w0"))
            fleet_results = [f.result(300.0) for f in futs]
    finally:
        router.shutdown()
    lock_violations = runtime_locks.violation_count() - lock_baseline
    flight_violations = len(flight.RECORDER.events(
        name="lock.order_violation")) - flight_baseline
    fleet_ok = (all(r is not None for r in fleet_results)
                and lock_violations == 0 and flight_violations == 0)
    for v in runtime_locks.violations()[-max(lock_violations, 0):] \
            if lock_violations else []:
        print(f"  LOCK VIOLATION: {v['kind']}: holding {v['holding']} "
              f"acquiring {v['acquiring']} on {v['thread']}", flush=True)

    ok = not findings and errors == 0 and fleet_ok
    print(json.dumps({
        "metric": "static_analysis_smoke",
        "ok": bool(ok),
        "self_lint_findings": len(findings),
        "findings_by_rule": {r: n for r, n in sorted(by_rule.items()) if n},
        "explain_lint_errors": errors,
        "explain_lint_rows": len(rows),
        "fleet_queries": len(fleet_results),
        "lock_order_violations": int(lock_violations),
        "lock_sanitizer_edges": len(runtime_locks.snapshot()["edges"]),
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_schedule_smoke():
    """`bench.py --schedule`: packing-scheduler smoke, exit 1 on violation.

    Mixed interactive+batch workload against a device budget that fits one
    batch working set plus three interactive ones (floors from the REAL
    estimator via `Context.cost_hint`):

    1. *FIFO baseline* — `serving.scheduler.enabled=false` with ONE worker:
       absent byte-aware packing, serial execution is the only provably
       safe concurrency under a device budget, so this is the conservative
       operator config the scheduler replaces.  Interactive queries queue
       behind the batch scan (head-of-line blocking).
    2. *Packing scheduler* — 4 workers, same budget: the batch scan and
       interactive queries run CONCURRENTLY (`serving.scheduler.packed`
       >= 1) because their floors fit, and interactive p95 latency must be
       strictly below the FIFO baseline measured in this same process.
    3. *Tenant quotas* — a greedy tenant flooding the queue must not starve
       a victim tenant (victim completes within the leading completions)
       while every greedy query still succeeds.
    """
    import json as _json

    import jax

    from dask_sql_tpu import Context
    from dask_sql_tpu.serving import QueryCost, ServingRuntime
    from dask_sql_tpu.serving.metrics import nearest_rank

    df = gen_lineitem(400_000, seed=0)
    c = Context()
    # result cache off: every interactive repeat must EXECUTE (the smoke
    # measures scheduling, not cache lookups)
    c.config.update({"serving.cache.enabled": False})
    c.create_table("lineitem", df)
    # the interactive working set is a small dimension table — the classic
    # mixed workload: dashboards hitting point lookups while one report
    # scans the fact table
    c.create_table("dim", gen_lineitem(20_000, seed=1))
    # the batch scan: a multi-branch report (UNION ALL of q1-shaped
    # aggregates) — many kernel launches, so packed interactive queries
    # interleave BETWEEN launches.  (A single fused kernel is
    # non-preemptible on any backend: packing overlaps queue wait and
    # host work, it cannot preempt a running launch.)
    batch_q = " UNION ALL ".join(
        f"SELECT l_returnflag, SUM(l_extendedprice * {1.0 + i / 10}) AS s, "
        f"AVG(l_quantity) AS q FROM lineitem "
        f"WHERE l_discount > 0.0{i} GROUP BY l_returnflag"
        for i in range(1, 9))
    inter_q = ("SELECT l_returnflag, l_extendedprice FROM dim "
               "WHERE l_extendedprice > 99000.0 LIMIT 20")
    # pre-warm: compile both families and populate plan cache + profiles
    # (cost_hint reads both; the smoke measures warm serving, not compiles)
    c.sql(batch_q, return_futures=False)
    c.sql(inter_q, return_futures=False)
    batch_cost = c.cost_hint(batch_q)
    inter_cost = c.cost_hint(inter_q)
    costs_ok = (batch_cost is not None and inter_cost is not None
                and batch_cost.bytes_lo > 0 and inter_cost.bytes_lo > 0)
    # the acceptance budget: one batch + three interactive provable floors
    budget = (batch_cost.bytes_lo + 3 * inter_cost.bytes_lo
              + (1 << 20)) if costs_ok else None

    def run_phase(runtime, n_inter=6):
        """One batch scan, then n interactive arrivals DURING it (the
        head-of-line shape: the report is already on the device when the
        dashboards land); returns interactive submit->completion seconds."""
        import threading as _threading

        done_at = {}
        batch_running = _threading.Event()

        def work(q, started=None):
            def fn(_t):
                if started is not None:
                    started.set()
                c.sql(q, return_futures=False)
                return q
            return fn

        futs = []
        _, bf, _ = runtime.submit(work(batch_q, batch_running),
                                  priority_class="batch", cost=batch_cost)
        batch_running.wait(60)
        t0s = []
        for i in range(n_inter):
            t0 = time.perf_counter()
            qid, f, _ = runtime.submit(work(inter_q), cost=inter_cost)
            f.add_done_callback(
                lambda _f, qid=qid: done_at.__setitem__(
                    qid, time.perf_counter()))
            t0s.append((qid, t0))
            futs.append(f)
        bf.result(300)
        for f in futs:
            f.result(300)
        return [done_at[qid] - t0 for qid, t0 in t0s]

    # -- phase 1: FIFO baseline (the byte-safe serial config) -------------
    rt_fifo = ServingRuntime(workers=1, metrics=c.metrics,
                             scheduler_enabled=False)
    fifo_lat = run_phase(rt_fifo)
    rt_fifo.shutdown(wait=True)
    fifo_p95 = nearest_rank(sorted(fifo_lat), 0.95)

    # -- phase 2: packing scheduler, same budget, same process ------------
    # workers exceed what the budget admits: concurrency is bounded by the
    # PACKER (batch + 3 interactive floors fit -> two packing waves for
    # the 6 interactive arrivals), not by the pool size
    rt_sched = ServingRuntime(workers=8, metrics=c.metrics,
                              scheduler_budget_bytes=budget)
    sched_lat = run_phase(rt_sched)
    rt_sched.shutdown(wait=True)
    sched_p95 = nearest_rank(sorted(sched_lat), 0.95)
    packed = c.metrics.counter("serving.scheduler.packed")

    # -- phase 3: tenant quotas under contention --------------------------
    import threading as _threading

    rt_q = ServingRuntime(workers=2, metrics=c.metrics,
                          tenant_rate=0.001, tenant_burst=1)
    completions = []
    # hold both workers until the whole mixed backlog is queued, so the
    # scheduler (not submission timing) decides the order
    hold = _threading.Event()
    held = _threading.Semaphore(0)
    holders = [rt_q.submit(
        lambda t: (held.release(), hold.wait(30)))[1] for _ in range(2)]
    held.acquire()
    held.acquire()
    greedy_futs = [rt_q.submit(
        lambda t, i=i: completions.append(f"greedy{i}") or i,
        cost=QueryCost(tenant="greedy", pred_exec_ms=1.0))[1]
        for i in range(6)]
    victim_fut = rt_q.submit(
        lambda t: completions.append("victim") or "v",
        cost=QueryCost(tenant="victim", pred_exec_ms=1.0))[1]
    hold.set()
    greedy_ok = all(f.result(60) == i
                    for i, f in enumerate(greedy_futs))
    victim_ok = victim_fut.result(60) == "v" \
        and "victim" in completions[:3]
    for f in holders:
        f.result(60)
    rt_q.shutdown(wait=True)

    ok = (costs_ok and packed >= 1 and sched_p95 < fifo_p95
          and greedy_ok and victim_ok)
    print(_json.dumps({
        "metric": "packing_scheduler_smoke",
        "backend": jax.default_backend(),
        "ok": bool(ok),
        "budget_bytes": budget,
        "batch_floor_bytes": None if batch_cost is None
        else batch_cost.bytes_lo,
        "interactive_floor_bytes": None if inter_cost is None
        else inter_cost.bytes_lo,
        "fifo_interactive_p95_ms": round(fifo_p95 * 1000, 2),
        "sched_interactive_p95_ms": round(sched_p95 * 1000, 2),
        "packed_dispatches": packed,
        "quota_throttled": c.metrics.counter(
            "serving.scheduler.quota_throttled"),
        "greedy_all_succeeded": bool(greedy_ok),
        "victim_not_starved": bool(victim_ok),
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_stream_smoke():
    """`bench.py --stream`: streamed partitioned execution smoke, exit 1
    on violation (ISSUE 13 acceptance).

    1. *Streamed completion* — a working set whose provable resident floor
       is >2x the configured admission budget completes via N>1 pipelined
       partition launches of one morsel executable (instead of the 429 the
       gate used to return), with results matching pandas.
    2. *Mid-stream OOM recovery* — an injected ``partition:atK`` fault
       mid-sequence repartitions (halved chunks) and RESUMES from the last
       completed partition: the per-run processed-row counter equals the
       table rows exactly (a restart would re-count completed partitions),
       and results still match pandas.
    """
    import json as _json

    import jax
    import pandas as pd

    from dask_sql_tpu import Context
    from dask_sql_tpu.resilience import faults
    from dask_sql_tpu.serving.cache import table_nbytes

    n = 600_000
    df = gen_lineitem(n, seed=0)
    c = Context()
    c.config.update({"serving.cache.enabled": False})
    c.create_table("lineitem", df)
    resident = table_nbytes(c.schema["root"].tables["lineitem"].table)
    q = ("SELECT l_returnflag, SUM(l_quantity) AS sum_qty, "
         "COUNT(*) AS count_order, AVG(l_quantity) AS avg_qty "
         "FROM lineitem GROUP BY l_returnflag ORDER BY l_returnflag")
    # warm the plan cache, then size the budget from the query's PROVABLE
    # working-set floor (the estimator's peak_bytes.lo — what the gate
    # actually sheds on): the floor is > 2x the budget, so the single
    # launch is provably infeasible and only streaming can serve it
    c.sql(q, return_futures=False)
    cost = c.cost_hint(q)
    floor = int(cost.bytes_lo) if cost is not None else 0
    budget = floor // 2 - (1 << 10)
    expected = (df.groupby("l_returnflag").agg(
        sum_qty=("l_quantity", "sum"), count_order=("l_quantity", "size"),
        avg_qty=("l_quantity", "mean")).reset_index().sort_values(
            "l_returnflag").reset_index(drop=True))

    def matches(res) -> bool:
        got = res.sort_values("l_returnflag").reset_index(drop=True)
        try:
            assert list(got["l_returnflag"]) == list(
                expected["l_returnflag"])
            np.testing.assert_allclose(got["sum_qty"], expected["sum_qty"],
                                       rtol=1e-5)
            np.testing.assert_array_equal(got["count_order"],
                                          expected["count_order"])
            np.testing.assert_allclose(got["avg_qty"], expected["avg_qty"],
                                       rtol=1e-5)
            return True
        except AssertionError:
            return False

    opts = {"serving.admission.max_estimated_bytes": budget}
    # phase 1: streamed completion, N>1 launches, pandas-identical
    res1 = c.sql(q, return_futures=False, config_options=opts)
    parts1 = c.metrics.counter("serving.stream.partitions")
    rows1 = c.metrics.counter("serving.stream.rows")
    ok_stream = (budget > 0 and floor > 2 * budget
                 and c.metrics.counter("serving.stream.admitted") >= 1
                 and parts1 > 1 and rows1 == n
                 and c.metrics.counter("serving.shed_estimated_bytes") == 0
                 and matches(res1))

    # phase 2: induced mid-stream OOM -> repartition + resume (no restart)
    faults.reset()
    res2 = c.sql(q, return_futures=False, config_options={
        **opts, "resilience.inject": "partition:at2",
        "serving.stream.min_chunk_rows": 1024})
    rows2 = c.metrics.counter("serving.stream.rows") - rows1
    reparts = c.metrics.counter("serving.stream.repartitions")
    ooms = c.metrics.counter("resilience.partition.oom")
    # rows2 == n proves completed partitions were NOT re-executed: a
    # restart would re-process partition 0 and overshoot
    ok_recover = (ooms >= 1 and reparts >= 1 and rows2 == n
                  and c.metrics.counter("resilience.degraded") == 0
                  and matches(res2))

    ok = ok_stream and ok_recover
    print(_json.dumps({
        "metric": "streaming_partitioned_smoke",
        "backend": jax.default_backend(),
        "ok": bool(ok),
        "resident_bytes": resident,
        "working_set_floor_bytes": floor,
        "budget_bytes": budget,
        "partitions_first_run": parts1,
        "rows_processed_first_run": rows1,
        "streamed_completion_ok": bool(ok_stream),
        "midstream_oom_injected": ooms,
        "repartitions": reparts,
        "rows_processed_recovery_run": rows2,
        "resumed_without_restart": bool(rows2 == n),
        "recovery_ok": bool(ok_recover),
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_live_smoke():
    """`bench.py --live`: live observability plane smoke, exit 1 on
    violation (ISSUE 14 acceptance).

    Starts a Presto server over a context whose admission budget forces a
    multi-partition streamed execution, submits the query over the wire,
    and while it is IN FLIGHT:

    1. polls ``GET /v1/queries`` asserting the entry is visible with
       ADVANCING partition progress and a NONZERO reserved-byte floor;
    2. cancels it with the ``CANCEL QUERY '<qid>'`` SQL statement
       (exercising the native parser path) and asserts the query
       terminates cooperatively between launches;
    3. asserts the flight recorder (``/v1/debug/events``) holds the
       cancel event and the HBM ledger returns to idle (zero reserved
       bytes) after the cancellation.
    """
    import json as _json
    import urllib.error
    import urllib.request

    import jax

    from dask_sql_tpu import Context
    from dask_sql_tpu.observability import flight
    from dask_sql_tpu.server.app import run_server

    n = 600_000
    df = gen_lineitem(n, seed=0)
    c = Context()
    c.config.update({"serving.cache.enabled": False})
    c.create_table("lineitem", df)
    q = ("SELECT l_returnflag, SUM(l_quantity) AS sum_qty, "
         "COUNT(*) AS count_order FROM lineitem GROUP BY l_returnflag")
    # size the budget below the provable floor so the gate routes the
    # query to a streamed rung; pin small chunks so the stream is long
    # enough to observe mid-flight over HTTP
    c.sql(q, return_futures=False)
    cost = c.cost_hint(q)
    floor = int(cost.bytes_lo) if cost is not None else 0
    budget = max(1 << 16, floor // 3)
    c.config.update({
        "serving.admission.max_estimated_bytes": budget,
        "serving.stream.chunk_rows": 4096,
        "serving.stream.max_partitions": 512,
    })
    # re-plan under the final config so the submit-time cost hint (keyed
    # on effective config) carries the streamed per-chunk floor
    c.sql(q, return_futures=False)
    srv = run_server(context=c, host="127.0.0.1", port=0, blocking=False)
    base = f"http://127.0.0.1:{srv.port}"

    def _get(path):
        return _json.load(urllib.request.urlopen(base + path))

    def _post(path, body=b""):
        req = urllib.request.Request(base + path, data=body,
                                     headers={"X-Dsql-Class": "batch",
                                              "X-Dsql-Tenant": "bench"})
        return _json.load(urllib.request.urlopen(req))

    flight.RECORDER.clear()
    qid = _post("/v1/statement", q.encode())["id"]
    # poll the live table until the entry streams, sampling progress
    samples, reserved_seen = [], 0
    deadline = time.perf_counter() + 30.0
    while time.perf_counter() < deadline:
        snap = _get("/v1/queries")
        entry = next((e for e in snap["queries"] if e["qid"] == qid), None)
        if entry is not None and entry["state"] in ("failed", "cancelled",
                                                    "done"):
            break
        if entry is not None and entry.get("stream"):
            samples.append(entry["stream"]["partitionsDone"])
            reserved_seen = max(reserved_seen,
                                int(entry.get("reservedBytes") or 0),
                                int(snap["ledger"]["reservedBytes"] or 0))
            if len(samples) >= 2 and samples[-1] > samples[0] \
                    and samples[-1] >= 2:
                break
        time.sleep(0.002)
    advancing = len(samples) >= 2 and samples[-1] > samples[0]
    # cancel through the SQL statement (native parser path) mid-flight
    cancel_df = None
    try:
        cancel_df = _post("/v1/statement",
                          f"CANCEL QUERY '{qid}'".encode())
    except urllib.error.HTTPError:
        pass
    # wait for the cooperative cancellation to land between launches
    final = None
    deadline = time.perf_counter() + 30.0
    while time.perf_counter() < deadline:
        entry = _get(f"/v1/queries/{qid}")
        if entry["state"] in ("failed", "cancelled", "done"):
            final = entry
            break
        time.sleep(0.01)
    cancelled = final is not None and final["state"] == "cancelled"
    events = _get("/v1/debug/events?name=query.cancel")["events"]
    cancel_recorded = any(e.get("qid") == qid for e in events)
    ledger = _get("/v1/queries")["ledger"]
    ledger_idle = int(ledger["reservedBytes"]) == 0 \
        and int(ledger["inflightMeasuredBytes"]) == 0
    srv.shutdown()
    ok = (advancing and reserved_seen > 0 and cancelled
          and cancel_recorded and ledger_idle)
    print(_json.dumps({
        "metric": "live_observability_smoke",
        "backend": jax.default_backend(),
        "ok": bool(ok),
        "budget_bytes": budget,
        "working_set_floor_bytes": floor,
        "progress_samples": samples[:16],
        "partitions_advancing": bool(advancing),
        "reserved_bytes_seen": reserved_seen,
        "cancel_submitted": cancel_df is not None,
        "cancelled_cooperatively": bool(cancelled),
        "final_state": None if final is None else final["state"],
        "flight_cancel_recorded": bool(cancel_recorded),
        "ledger_idle_after": bool(ledger_idle),
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_reuse_smoke():
    """`bench.py --reuse`: semantic result reuse smoke, exit 1 on
    violation (ISSUE 16 acceptance).

    Replays a 20-query dashboard twice against one context:

    1. *Cold wave*: 20 distinct queries — sibling projections sharing
       scan->filter stems, filtered point-lookups, grouped aggregates —
       populate the exact-match cache, pin hot stems, register
       subsumption candidates and incremental aggregate states.
    2. *Warm wave*: the replay (exact repeats + TIGHTER int literals +
       a NEVER-SEEN sibling projection) must be served entirely by the
       reuse tiers: >=1 materialized-stem hit, >=1 subsumption answer,
       ZERO foreground compiles (no rung's ``compile.start`` event) and
       ZERO base-table scan launches (every surviving TableScan reads a
       pinned stem, never the catalog).
    3. *Append*: ``INSERT INTO ... SELECT`` folds the delta through the
       pinned stems (refresh, not rescan) and the stored combine states;
       the re-queried aggregate matches pandas over base+delta and is
       served as an incremental hit.
    """
    import json as _json

    import jax

    from dask_sql_tpu import Context
    from dask_sql_tpu.observability import flight
    from dask_sql_tpu.physical.rel.logical import basic

    n = 200_000
    df = gen_lineitem(n, seed=0)
    rng = np.random.RandomState(1)
    # non-null int columns: the provable-interval domain for subsumption
    df["l_orderkey"] = (rng.randint(0, 1_500_000, n) * 4).astype(np.int64)
    df["l_linenumber"] = rng.randint(1, 8, n).astype(np.int64)

    ctx = Context()
    ctx.config.update({"serving.materialize.min_bytes": 1})
    ctx.create_table("lineitem", df)

    stem_where = "l_quantity < 30 AND l_discount < 0.05"
    wave1 = [
        # stem A siblings: pinned at the 2nd observation
        f"SELECT l_extendedprice FROM lineitem WHERE {stem_where}",
        f"SELECT l_quantity FROM lineitem WHERE {stem_where}",
        f"SELECT l_tax FROM lineitem WHERE {stem_where}",
        # subsumption families (int comparators, loose literals)
        "SELECT l_orderkey, l_quantity FROM lineitem WHERE l_orderkey < 5000000",
        "SELECT l_orderkey, l_linenumber FROM lineitem WHERE l_linenumber <= 6",
        # incremental aggregate states + cacheable aggregates
        "SELECT l_linenumber, SUM(l_quantity) AS s, COUNT(*) AS c "
        "FROM lineitem GROUP BY l_linenumber",
        "SELECT SUM(l_extendedprice) AS s FROM lineitem",
        "SELECT l_returnflag, COUNT(*) AS c FROM lineitem GROUP BY l_returnflag",
        "SELECT MAX(l_orderkey) AS m FROM lineitem",
        "SELECT AVG(l_discount) AS a FROM lineitem",
        # stem B siblings
        "SELECT l_returnflag FROM lineitem WHERE l_tax < 0.04",
        "SELECT l_discount FROM lineitem WHERE l_tax < 0.04",
        # assorted dashboard panels (exact repeats in wave 2)
        "SELECT l_linestatus, SUM(l_tax) AS s FROM lineitem GROUP BY l_linestatus",
        "SELECT COUNT(*) AS c FROM lineitem WHERE l_returnflag = 'A'",
        "SELECT COUNT(*) AS c FROM lineitem WHERE l_returnflag = 'R'",
        "SELECT SUM(l_quantity) AS s FROM lineitem WHERE l_linestatus = 'F'",
        "SELECT SUM(l_quantity) AS s FROM lineitem WHERE l_linestatus = 'O'",
        "SELECT l_orderkey FROM lineitem WHERE l_orderkey >= 5900000",
        "SELECT MIN(l_shipdate) AS d FROM lineitem",
        "SELECT MAX(l_shipdate) AS d FROM lineitem",
    ]
    assert len(wave1) == 20
    for q in wave1:
        ctx.sql(q).compute()

    # warm wave: exact repeats + tighter literals + a new stem sibling
    wave2 = list(wave1[5:])  # 15 exact repeats
    wave2 += [
        f"SELECT l_linestatus FROM lineitem WHERE {stem_where}",  # new sibling
        "SELECT l_orderkey, l_quantity FROM lineitem WHERE l_orderkey < 2000000",
        "SELECT l_orderkey, l_linenumber FROM lineitem WHERE l_linenumber <= 3",
        "SELECT l_orderkey FROM lineitem WHERE l_orderkey >= 5950000",
        f"SELECT l_quantity FROM lineitem WHERE {stem_where}",  # repeat
    ]
    assert len(wave2) == 20

    base_scans = {"n": 0}
    orig_convert = basic.TableScanPlugin.convert

    def counting_convert(self, rel, executor):
        if executor.table_overrides.get(
                (rel.schema_name, rel.table_name)) is None:
            base_scans["n"] += 1
        return orig_convert(self, rel, executor)

    m = ctx.metrics
    cache0 = ctx._result_cache.stats.hits
    sub0 = m.counter("serving.reuse.subsumption.hits")
    stem0 = m.counter("serving.materialize.hits")
    incr0 = m.counter("serving.reuse.incremental.hits")
    flight.RECORDER.clear()
    basic.TableScanPlugin.convert = counting_convert
    try:
        results2 = [ctx.sql(q).compute() for q in wave2]
    finally:
        basic.TableScanPlugin.convert = orig_convert
    # a rung's compile (an eager op on a new shape compiles without one)
    compiles2 = sum(1 for e in flight.RECORDER.events(name="compile.start")
                    if e.get("rung"))
    cache_d = ctx._result_cache.stats.hits - cache0
    sub_d = m.counter("serving.reuse.subsumption.hits") - sub0
    stem_d = m.counter("serving.materialize.hits") - stem0
    incr_d = m.counter("serving.reuse.incremental.hits") - incr0
    served = cache_d + sub_d + stem_d + incr_d
    ok_warm = (sub_d >= 1 and stem_d >= 1 and served >= len(wave2)
               and compiles2 == 0 and base_scans["n"] == 0)

    # spot-check the reuse-served answers against pandas
    sub_df = results2[16]
    ok_sub = len(sub_df) == int((df["l_orderkey"] < 2_000_000).sum())
    sel = (df["l_quantity"] < 30) & (df["l_discount"] < 0.05)
    ok_stem = len(results2[15]) == int(sel.sum())

    # append phase: INSERT INTO folds the delta, never rescans history
    refreshed0 = m.counter("serving.materialize.refreshed")
    folds0 = m.counter("serving.reuse.incremental.folds")
    ins = ctx.sql(
        "INSERT INTO lineitem SELECT * FROM lineitem "
        "WHERE l_orderkey < 40000").compute()
    delta = df[df["l_orderkey"] < 40000]
    ok_insert = int(ins["Inserted"][0]) == len(delta)
    agg = ctx.sql(wave1[5]).compute()
    incr_hit = m.counter("serving.reuse.incremental.hits") - incr0 - incr_d
    full = df if not len(delta) else \
        __import__("pandas").concat([df, delta], ignore_index=True)
    exp = (full.groupby("l_linenumber", as_index=False)
           .agg(s=("l_quantity", "sum"), c=("l_quantity", "count")))
    got = agg.sort_values("l_linenumber").reset_index(drop=True)
    exp = exp.sort_values("l_linenumber").reset_index(drop=True)
    ok_incr = (incr_hit >= 1
               and got["c"].tolist() == exp["c"].tolist()
               and np.allclose(got["s"].to_numpy(),
                               exp["s"].to_numpy(), rtol=1e-4))
    ok_append = (ok_insert and ok_incr
                 and m.counter("serving.materialize.refreshed") > refreshed0
                 and m.counter("serving.reuse.incremental.folds") > folds0)

    # ledger reconciliation: pinned bytes visible, idle after eviction
    pinned = ctx.materialize.pinned_bytes()
    ok_ledger = (pinned > 0
                 and ctx.ledger.snapshot()["materializedBytes"] == pinned)
    ctx.materialize.invalidate_all()
    ok_ledger = ok_ledger and ctx.ledger.snapshot()["materializedBytes"] == 0

    ok = ok_warm and ok_sub and ok_stem and ok_append and ok_ledger
    print(_json.dumps({
        "metric": "semantic_reuse_smoke",
        "backend": jax.default_backend(),
        "ok": bool(ok),
        "rows": n,
        "warm_wave": {
            "queries": len(wave2),
            "served_by_reuse": int(served),
            "cache_hits": int(cache_d),
            "subsumption_hits": int(sub_d),
            "stem_hits": int(stem_d),
            "incremental_hits": int(incr_d),
            "foreground_compiles": int(compiles2),
            "base_table_scans": int(base_scans["n"]),
            "ok": bool(ok_warm and ok_sub and ok_stem),
        },
        "append": {
            "rows_appended": int(ins["Inserted"][0]),
            "stem_refreshes": int(
                m.counter("serving.materialize.refreshed") - refreshed0),
            "incremental_folds": int(
                m.counter("serving.reuse.incremental.folds") - folds0),
            "aggregate_matches_pandas": bool(ok_incr),
            "ok": bool(ok_append),
        },
        "ledger": {"pinned_bytes_seen": int(pinned), "ok": bool(ok_ledger)},
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_chaos_smoke():
    """`bench.py --chaos`: seeded chaos campaigns, exit 1 on any
    invariant violation (ISSUE 17 acceptance).

    Runs >= 5 seeds, each a deterministic fault storm of >= 40
    concurrent mixed queries (interactive aggregates, batch scans,
    streamed partitioned queries, PREDICT inference, exact repeats,
    mid-flight cancels) with rotating probability-armed subsets of
    every inject site.  Individual query outcomes are free under
    chaos; what must hold after every drain are the GLOBAL invariants
    (resilience/chaos.py): terminal live-table entries, idle
    reservations and ledger, restorable breakers, no zombie threads,
    causally consistent flight timelines.
    """
    import json as _json

    import jax

    from dask_sql_tpu.resilience.chaos import run_campaign

    seeds = [1, 2, 3, 4, 5]
    per_seed = []
    total_violations = 0
    for seed in seeds:
        t0 = time.perf_counter()
        report = run_campaign(seed=seed, queries=40, rounds=4, workers=4)
        elapsed = time.perf_counter() - t0
        print(report.summary(), flush=True)
        for v in report.violations:
            print(f"  VIOLATION: {v}", flush=True)
        total_violations += len(report.violations)
        per_seed.append({
            "seed": seed,
            "submitted": report.submitted,
            "completed": report.completed,
            "failed": report.failed,
            "cancelled": report.cancelled,
            "shed": report.shed,
            "rounds": report.rounds,
            "sites_armed": len(report.armed),
            "violations": len(report.violations),
            "seconds": round(elapsed, 2),
            "ok": report.ok,
        })
    ok = total_violations == 0
    print(_json.dumps({
        "metric": "chaos_campaign_smoke",
        "backend": jax.default_backend(),
        "ok": bool(ok),
        "seeds": len(seeds),
        "queries_per_seed": 40,
        "invariant_violations": int(total_violations),
        "campaigns": per_seed,
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def run_fleet_smoke():
    """`bench.py --fleet`: fault-tolerant replica fleet smoke (ISSUE 18).

    Part 1 — failover + warm-standby promotion: a router over 3
    in-process replicas plus a warm standby serves a concurrent workload;
    one replica is killed (kill -9 semantics) mid-workload.  Asserts:

    - every routed query completes despite the kill (failover re-dispatch
      to survivors, dedupe through the result-cache idempotency key);
    - the standby is promoted into the serving set;
    - after the surviving original replicas drain, the PROMOTED standby
      serves its first routed query of the hot family with ZERO
      foreground ``compile:<rung>`` spans (the replication transport —
      checkpoint snapshot + profile store + shared compile cache — paid
      every compile off the serving path);
    - the promoted replica's result matches the pre-kill result.

    Part 2 — replica-kill chaos: `run_fleet_campaign` over 5 seeds
    (3 replicas, mixed concurrent workload, one kill per round): zero
    lost queries, INSERT INTO applied exactly once per survivor under
    failover (epoch fencing), ledgers idle after drain.

    Exit 1 on any violation.
    """
    import json as _json
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from dask_sql_tpu import Context
    from dask_sql_tpu.fleet import READY, build_fleet
    from dask_sql_tpu.resilience.chaos import run_fleet_campaign

    df = gen_lineitem(50_000, seed=0)

    def factory():
        c = Context()
        c.create_table("lineitem", df)
        return c

    router, members, replicator = build_fleet(factory, replicas=3,
                                              standby=True)
    baseline = router.execute(QUERY, qid="fleet-cold")
    router.execute(QUERY, qid="fleet-hot")  # the family is clearly hot
    replicator.sync()  # standby: snapshot + profiles + warm-up, off-path

    with ThreadPoolExecutor(max_workers=4,
                            thread_name_prefix="fleet-smoke") as pool:
        futs = [pool.submit(router.execute, QUERY, f"fleet-w{i}")
                for i in range(8)]
        time.sleep(0.05)
        router.kill(members[1].name)  # kill -9 one replica mid-workload
        results = [f.result(300.0) for f in futs]
    all_complete = all(r is not None for r in results)

    promoted = router.find("standby")
    was_promoted = bool(promoted is not None and promoted.state == READY
                        and promoted in router.replicas)
    # drain the surviving originals so the next routed query can only
    # land on the promoted standby — ITS first serve of this family
    router.drain(members[0].name)
    router.drain(members[2].name)
    out = router.execute(QUERY, qid="fleet-promoted")
    tr = promoted.context.last_trace if promoted is not None else None
    fg_compiles = [] if tr is None else \
        [s.name for s in tr.spans if s.name.startswith("compile:")]
    match = out is not None and len(out) == len(baseline) and np.allclose(
        out["sum_qty"].to_numpy(np.float64),
        baseline["sum_qty"].to_numpy(np.float64), rtol=1e-9)
    router.shutdown()
    part1_ok = bool(all_complete and was_promoted and not fg_compiles
                    and match)

    seeds = [1, 2, 3, 4, 5]
    per_seed = []
    total_violations = 0
    for seed in seeds:
        t0 = time.perf_counter()
        report = run_fleet_campaign(seed=seed, queries=21, rounds=3,
                                    replicas=3, clients=4)
        elapsed = time.perf_counter() - t0
        print(report.summary(), flush=True)
        for v in report.violations:
            print(f"  VIOLATION: {v}", flush=True)
        total_violations += len(report.violations)
        per_seed.append({
            "seed": seed,
            "submitted": report.submitted,
            "completed": report.completed,
            "retried": report.retried,
            "failed": report.failed,
            "shed": report.shed,
            "kills": report.kills,
            "promoted": report.promoted,
            "inserts": report.inserts,
            "violations": len(report.violations),
            "seconds": round(elapsed, 2),
            "ok": report.ok,
        })

    ok = bool(part1_ok and total_violations == 0)
    print(_json.dumps({
        "metric": "fleet_smoke",
        "backend": jax.default_backend(),
        "ok": ok,
        "workload_completed": int(sum(1 for r in results if r is not None)),
        "workload_submitted": len(results),
        "standby_promoted": was_promoted,
        "promoted_foreground_compile_spans": fg_compiles,
        "results_match": bool(match),
        "chaos_seeds": len(seeds),
        "chaos_violations": int(total_violations),
        "campaigns": per_seed,
    }), flush=True)
    if not ok:
        raise SystemExit(1)


def main():
    import sys

    if "--coldstart" not in sys.argv:  # that phase empties a cache of its own
        from dask_sql_tpu.serving import compile_cache

        compile_cache.enable(compile_cache_dir())

    if "--fleet" in sys.argv:
        run_fleet_smoke()
        return
    if "--chaos" in sys.argv:
        run_chaos_smoke()
        return
    if "--live" in sys.argv:
        run_live_smoke()
        return
    if "--reuse" in sys.argv:
        run_reuse_smoke()
        return
    if "--lint" in sys.argv:
        run_lint_smoke()
        return
    if "--stream" in sys.argv:
        run_stream_smoke()
        return
    if "--inject" in sys.argv:
        run_inject_smoke()
        return
    if "--estimate" in sys.argv:
        run_estimate_smoke()
        return
    if "--profile" in sys.argv:
        run_profile_smoke()
        return
    if "--coldstart" in sys.argv:
        run_coldstart_smoke()
        return
    if "--families" in sys.argv:
        run_families_smoke()
        return
    if "--compressed" in sys.argv:
        run_compressed_smoke()
        return
    if "--spmd" in sys.argv:
        run_spmd_smoke()
        return
    if "--schedule" in sys.argv:
        run_schedule_smoke()
        return
    if "--predict" in sys.argv:
        run_predict_smoke()
        return

    import jax

    from dask_sql_tpu import Context
    from dask_sql_tpu.utils import TRANSFER_STATS

    df = gen_lineitem(N_ROWS)

    c = Context()
    # result cache off: measure execution, not serving-cache lookups
    c.config.update({"serving.cache.enabled": False})
    c.create_table("lineitem", df)

    # warm-up (compile caches, device transfer)
    frame = c.sql(QUERY)
    _ = frame.compute()

    # phase breakdown on THIS backend (the driver runs this on the chip):
    # cached-plan time, execute+decode, and device->host round trips
    t0 = time.perf_counter()
    plan_frame = c.sql(QUERY)
    t_plan = time.perf_counter() - t0
    TRANSFER_STATS["d2h"] = 0
    t0 = time.perf_counter()
    plan_frame.compute()
    t_exec = time.perf_counter() - t0
    print(json.dumps({
        "metric": "q1_phase_breakdown",
        "backend": jax.default_backend(),
        "plan_ms": round(t_plan * 1000, 2),
        "execute_ms": round(t_exec * 1000, 2),
        "d2h_round_trips": TRANSFER_STATS["d2h"],
    }), flush=True)

    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        res = c.sql(QUERY).compute()
        times.append(time.perf_counter() - t0)
    best = min(times)
    throughput = N_ROWS / best

    # root SELECT pipeline (filter+project+topk): two kernels, two round
    # trips, transfer sized by survivors (physical/compiled_select.py)
    sel_sql = ("SELECT l_returnflag, l_extendedprice * (1 - l_discount) AS rev "
               "FROM lineitem WHERE l_discount > 0.09 "
               "ORDER BY rev DESC LIMIT 100")
    c.sql(sel_sql).compute()
    TRANSFER_STATS["d2h"] = 0
    t0 = time.perf_counter()
    c.sql(sel_sql).compute()
    t_sel = time.perf_counter() - t0
    print(json.dumps({
        "metric": "select_topk_rows_per_sec",
        "value": round(N_ROWS / t_sel, 1),
        "unit": "rows/s",
        "backend": jax.default_backend(),
        "d2h_round_trips": TRANSFER_STATS["d2h"],
    }), flush=True)

    try:
        bench_q3_line(jax.default_backend())
    except Exception as e:  # Q3 must never sink the headline metric
        print(json.dumps({"metric": "tpch_q3_sf1_rows_per_sec_per_chip",
                          "error": f"{type(e).__name__}: {e}"}), flush=True)

    # pandas baseline (the reference's per-partition engine)
    t0 = time.perf_counter()
    expected = run_pandas(df)
    pandas_time = time.perf_counter() - t0
    t0 = time.perf_counter()
    expected = run_pandas(df)
    pandas_time = min(pandas_time, time.perf_counter() - t0)

    # correctness spot check
    assert len(res) == len(expected), (len(res), len(expected))
    np.testing.assert_allclose(
        res["sum_qty"].to_numpy(dtype=np.float64),
        expected["sum_qty"].to_numpy(dtype=np.float64), rtol=1e-2)

    print(json.dumps({
        "metric": "tpch_q1_sf1_rows_per_sec_per_chip",
        "value": round(throughput, 1),
        "unit": "rows/s",
        "vs_baseline": round((N_ROWS / pandas_time) and throughput / (N_ROWS / pandas_time), 3),
    }))


if __name__ == "__main__":
    main()
