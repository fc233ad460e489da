"""Phase profiler for the TPC-H Q1 bench: where does end-to-end time go?

Phases: parse+plan / execute-dispatch / device-sync / to_pandas, plus the raw
compiled-kernel time (direct call on resident device buffers) as the floor.
Emits each phase as ITS OWN JSON line the moment it is measured, so a crash
in a later phase can't swallow earlier data, then one
combined line at the end.  Run on the real chip:  python benchmarks/profile_q1.py
"""
from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, ".")

from bench import N_ROWS, QUERY, gen_lineitem  # noqa: E402

phases = {}


def emit(name, value):
    phases[name] = value
    print(json.dumps({name: value}), flush=True)


def main():
    import jax

    from dask_sql_tpu import Context
    from dask_sql_tpu.planner.parser import parse_sql

    n = int(sys.argv[1]) if len(sys.argv) > 1 else N_ROWS
    df = gen_lineitem(n)

    c = Context()
    # result cache off: measure execution, not serving-cache lookups
    c.config.update({"serving.cache.enabled": False})
    t0 = time.perf_counter()
    c.create_table("lineitem", df)
    emit("create_table_s", round(time.perf_counter() - t0, 3))
    emit("rows", n)
    emit("backend", jax.default_backend())

    # warm-up: compile + caches
    c.sql(QUERY).compute()

    # 1. parse + plan
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        stmt = parse_sql(QUERY)[0]
        plan = c._get_ral(stmt)
    emit("plan_ms", round((time.perf_counter() - t0) / reps * 1000, 2))

    # 2. full execute to device table (dispatch incl. any host work)
    from dask_sql_tpu.physical.executor import Executor

    times = {"exec": [], "sync": [], "pandas": []}
    for _ in range(3):
        ex = Executor(c)
        t0 = time.perf_counter()
        table = ex.execute(plan)
        t1 = time.perf_counter()
        for col in table.columns.values():
            jax.block_until_ready(col.data)
        t2 = time.perf_counter()
        table.to_pandas()
        t3 = time.perf_counter()
        times["exec"].append(t1 - t0)
        times["sync"].append(t2 - t1)
        times["pandas"].append(t3 - t2)
    for k, v in times.items():
        emit(f"{k}_ms", round(min(v) * 1000, 2))

    # 3. compiled-kernel floor: direct call on the cached CompiledAggregate.
    # The plugin cache drops `compiled.table` after every run (so stale table
    # versions don't pin HBM) — rebind the live table before driving _fn.
    from dask_sql_tpu.physical import compiled as C

    if C._cache:
        key, ca = next(iter(C._cache.items()))
        schema_name, table_name, projection = key[1], key[2], key[3]
        table = ex.get_table(schema_name, table_name)
        if projection:
            table = table.select(list(projection))
        ca.table = table
        try:
            datas = tuple(table.columns[nm].data for nm in table.column_names)
            valids = tuple(table.columns[nm].validity
                           for nm in table.column_names)
            flat = ca._fn(datas, valids)
            jax.block_until_ready(flat)
            t0 = time.perf_counter()
            for _ in range(5):
                flat = ca._fn(datas, valids)
                jax.block_until_ready(flat)
            emit("kernel_ms", round((time.perf_counter() - t0) / 5 * 1000, 2))
            t0 = time.perf_counter()
            for _ in range(3):
                ca.run()
            emit("kernel_plus_decode_ms",
                 round((time.perf_counter() - t0) / 3 * 1000, 2))
        finally:
            ca.table = None
    else:
        emit("kernel_ms", None)  # compiled path was not taken — investigate

    # 4. end-to-end (the bench number)
    t0 = time.perf_counter()
    c.sql(QUERY).compute()
    e2e = round((time.perf_counter() - t0) * 1000, 2)
    emit("end_to_end_ms", e2e)
    emit("rows_per_sec", round(n / (e2e / 1000), 0))

    print(json.dumps(phases), flush=True)


if __name__ == "__main__":
    main()
