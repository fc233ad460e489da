"""Ask the TPU v5e's compiler about the main path, with no chip attached.

Section 2 of the on-chip-measurement guide: libtpu compiles for a DESCRIBED
``v5e:2x2`` topology from shapes only, so what the chip's compiler would
refuse (a Mosaic kernel it cannot lower, a program that does not fit 16 GB)
is refused here, at no chip time.  Nothing runs: a compile that passes is
not a chip run.

The topology is described inside a module-scoped fixture — never at import
— because only one process may load the TPU's library: every xdist worker
imports this file, only the worker that runs it loads libtpu.  All such
tests live in this one file for the same reason.

Shapes are `chip_smoke.py`'s default: TPC-H lineitem at SF10, 60,000,000
rows, in the dtypes `Context.create_table` encodes the smoke's frame to.
No sort-based program is compiled here (64-bit sorts take minutes).
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

ROWS = 60_000_000
CELL_ROWS = 24_000_000  # perfbench's tpch_sf10_lineitem_4chip: 6M a shard
HBM_BYTES = 16 * 10**9  # one v5e chip
SMALL_ROWS = 200_000

#: what create_table encodes chip_smoke's lineitem to (24 B/row)
ENCODED_DTYPES = {
    "l_returnflag": "int32", "l_linestatus": "int32", "l_quantity": "int16",
    "l_extendedprice": "float64", "l_discount": "int16", "l_tax": "int16",
    "l_shipdate": "int16",
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but can never be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def captured():
    """Run the smoke's Q1 and Q6 at a small size on the CPU — single-chip
    and row-sharded — and keep what the compiled rungs were built from:
    ``{"q1": (pipeline, table, params), ..., "spmd_q1": (ctor args, params)}``;
    ``"q1_interval"`` is Q1 as the specification prints it (perfbench's
    ``tpch_q1_interval`` text, DELTA 90) over the same table.
    The segment sum is steered to what ``auto`` picks on a TPU for these
    domains (the blocked matmul) through the existing config key."""
    import chip_smoke
    from dask_sql_tpu import Context
    from dask_sql_tpu import config as config_module
    from dask_sql_tpu.physical.compiled import CompiledAggregate
    from dask_sql_tpu.spmd.aggregate import SpmdAggregate

    df = chip_smoke.gen_lineitem(SMALL_ROWS, seed=0)
    runs, ctors = [], []
    run, init = CompiledAggregate.run, SpmdAggregate.__init__
    spmd_run = SpmdAggregate.run  # its own: the sharded Q1's parameters

    def spy_run(self, table=None, params=()):
        runs.append((self, table, params))
        return (spmd_run if isinstance(self, SpmdAggregate) else run)(
            self, table, params)

    def spy_init(self, *args, **kwargs):
        ctors.append(args)
        init(self, *args, **kwargs)

    out = {}
    # the result cache off for these contexts only: the config is the
    # process's, and this worker runs other files after this one
    with pytest.MonkeyPatch.context() as mp, \
            config_module.set({"serving.cache.enabled": False}):
        mp.setattr(CompiledAggregate, "run", spy_run)
        mp.setattr(SpmdAggregate, "run", spy_run)
        mp.setattr(SpmdAggregate, "__init__", spy_init)
        c = Context()
        c.create_table("lineitem", df)
        table = c.schema[c.schema_name].tables["lineitem"].table
        assert {n: str(col.data.dtype) for n, col in table.columns.items()} \
            == ENCODED_DTYPES
        for name in ("q1", "q6"):
            c.sql(chip_smoke.QUERIES[name],
                  config_options={"sql.compile.segsum": "matmul"}).compute()
            out[name] = runs[-1]
            assert out[name][0].segsum_mode == "matmul"
        from perfbench import traffic

        c.sql(traffic.render(traffic.load("queries", "tpch_q1_interval"),
                             {"DELTA": 90}),
              config_options={"sql.compile.segsum": "matmul"}).compute()
        out["q1_interval"] = runs[-1]
        assert out["q1_interval"][0] is not out["q1"][0]
        sharded = Context()
        sharded.create_table("lineitem", df, distributed=True)
        sharded.sql(chip_smoke.QUERIES["q1"]).compute()
        assert sharded.metrics.counter("resilience.rung.spmd_aggregate") == 1
        out["spmd_q1"] = (ctors[-1], runs[-1][2])
        # the benchmark's four-chip cell (sf10_q1_sharded_4chip): all
        # sixteen columns of perfbench's LINEITEM from pyarrow, its own Q1
        from perfbench.datagen import tpch_lineitem

        arrays = tpch_lineitem.generate(SMALL_ROWS, seed=29, scale_factor=10)
        query = traffic.load("queries", "tpch_q1")
        cell = Context()
        cell.create_table("lineitem",
                          tpch_lineitem.arrow_tables(arrays)["lineitem"],
                          distributed=True)
        assert len(cell.schema[cell.schema_name]
                   .tables["lineitem"].table.columns) == 16
        cell.sql(traffic.render(query, {"DELTA": 90})).compute()
        assert cell.metrics.counter("resilience.rung.spmd_aggregate") == 1
        out["spmd_q1_cell"] = (ctors[-1], runs[-1][2])
    return out


def _shapes(tree, sharding):
    """Concrete (tiny) runtime parameters -> shapes placed by `sharding`."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(lambda t: jax.tree.map(jnp.asarray, t), tree))


def _column_shapes(table, rows, sharding):
    cols = [table.columns[n] for n in table.column_names]
    datas = tuple(jax.ShapeDtypeStruct((rows,), c.data.dtype,
                                       sharding=sharding) for c in cols)
    valids = tuple(None if c.validity is None else jax.ShapeDtypeStruct(
        (rows,), jnp.bool_, sharding=sharding) for c in cols)
    return datas, valids


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes)


def test_blocked_segsum_compiles_at_sf10(one_chip):
    from dask_sql_tpu.ops.pallas_kernels import segsum_scan_blocked

    gid = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    cols = [jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=one_chip)] * 8
    compiled = jax.jit(
        lambda g, cs: segsum_scan_blocked(g, cs, 6)).lower(gid, cols).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def _instructions(text):
    """``(result type, dims, opcode)`` of every instruction of an HLO text
    whose result is one array."""
    return [(t, tuple(int(d) for d in dims.split(",") if d), op)
            for t, dims, op in re.findall(
                r"= ([a-z0-9]+)\[([0-9,]*)\]\{[^}]*\} ([a-z-]+)\(", text)]


def test_blocked_segsum_reads_contiguous_blocks(one_chip):
    """Q1's segment sum at the benchmark's 24M rows, 16 columns and 12
    groups: each step of the loop slices its 32768 rows out of each column
    and stacks the slices to ``[16, 32768]`` on chip.  Nothing the size of
    a column or more is built in front of the loop: no stack of the whole
    columns, no relayout of one, no pad, no ``[blocks, 32768, 16]`` view."""
    from dask_sql_tpu.ops.pallas_kernels import segsum_scan_blocked

    gid = jax.ShapeDtypeStruct((CELL_ROWS,), jnp.int32, sharding=one_chip)
    cols = [jax.ShapeDtypeStruct((CELL_ROWS,), jnp.float32,
                                 sharding=one_chip)] * 16
    compiled = jax.jit(lambda g, cs: segsum_scan_blocked(g, cs, 12)).lower(
        gid, cols).compile()
    found = _instructions(compiled.as_text())
    assert not [f for f in found if f[1][1:] == (32768, 16)]
    assert not [f for f in found if np.prod(f[1]) >= CELL_ROWS
                and f[2] not in ("parameter", "get-tuple-element")]
    assert ("f32", (16, 32768), "concatenate") in found
    assert found.count(("f32", (32768,), "dynamic-slice")) == 16
    assert compiled.memory_analysis().temp_size_in_bytes < CELL_ROWS * 4
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("domain", [6, 2048])
def test_pallas_segsum_compiles(one_chip, domain):
    """The kernel that had only ever run in interpret mode: Mosaic takes it
    with x64 on (int32 index maps) and for a wide domain (128-lane blocks)."""
    from dask_sql_tpu.ops.pallas_kernels import segsum_pallas

    n = 1 << 20
    gid = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    contribs = jax.ShapeDtypeStruct((n, 8), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda g, c: segsum_pallas(g, c, domain)).lower(
        gid, contribs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _row_gathers(text, rows):
    """``(operand types, result type)`` of every gather in a lowered
    (StableHLO) text that has `rows` as a dimension on either side."""
    found = re.findall(r'"?stablehlo\.gather"?\(.*?:\s*\((.*?)\)\s*->\s*(\S+)',
                       text)
    return [(ins, out) for ins, out in found
            if re.search(rf"<(\d+x)*{rows}x", ins + " " + out)]


def test_q1_interval_decodes_no_ship_date_per_row(captured):
    """Structural, off-chip: Q1 as the specification prints it
    (``l_shipdate <= DATE '1998-12-01' - INTERVAL '90' DAY``) compares the
    DICT-encoded ship date in CODE space: lowered for the CPU at
    `SMALL_ROWS`, the program gathers nothing per row through the date's
    int64 dictionary (on a TPU: two ``u32[rows]`` gathers, 4/5 of the
    device's time before ISSUE 32).  The per-row gathers it does hold are the
    literal text's, type for type: the float64 dictionaries of l_quantity,
    l_discount and l_tax, which the sums read as values."""
    lut = np.arange(2526, dtype=np.int64)
    control = jax.jit(lambda codes: jnp.asarray(lut)[codes]).lower(
        jax.ShapeDtypeStruct((SMALL_ROWS,), jnp.int16)).as_text()
    assert [out for _, out in _row_gathers(control, SMALL_ROWS)] \
        == [f"tensor<{SMALL_ROWS}xi64>"]

    def lowered(name):
        pipeline, table, params = captured[name]
        datas, valids = _column_shapes(table, SMALL_ROWS, None)
        return jax.jit(pipeline._fn_raw).lower(
            datas, valids, None, _shapes(tuple(params), None)).as_text()

    gathers = _row_gathers(lowered("q1_interval"), SMALL_ROWS)
    assert gathers and not [g for g in gathers if "i64>" in g[0] + g[1]]
    assert sorted(gathers) == sorted(_row_gathers(lowered("q1"), SMALL_ROWS))


@pytest.mark.parametrize("name", ["q1", "q6", "q1_interval"])
def test_compiled_aggregate_fits_one_chip_at_sf10(one_chip, captured, name):
    """The jitted pipeline `physical/compiled.py` builds for the query, at
    60M rows of the encoded dtypes, with the table resident beside it.
    ``q1_interval`` is Q1 in the specification's ``DATE - INTERVAL`` text
    (two runtime scalars searched in the date dictionary in-kernel); the
    case costs the suite 3.2 s at 60M rows, as ``q1`` does (ROADMAP D11)."""
    pipeline, table, params = captured[name]
    datas, valids = _column_shapes(table, ROWS, one_chip)
    compiled = jax.jit(pipeline._fn_raw).lower(
        datas, valids, None, _shapes(tuple(params), one_chip)).compile()
    resident = sum(ROWS * np.dtype(d).itemsize
                   for d in ENCODED_DTYPES.values())
    used = _device_bytes(compiled)
    assert used < HBM_BYTES, (used, compiled.memory_analysis())
    # arguments are the projected columns of the resident table: the rest of
    # the table still has to fit beside the program
    args = compiled.memory_analysis().argument_size_in_bytes
    assert used - args + resident < HBM_BYTES


@pytest.mark.parametrize("name, rows, segsum", [
    ("spmd_q1", ROWS, "scatter"),
    ("spmd_q1_cell", CELL_ROWS, "matmul"),
    ("spmd_q1_cell", CELL_ROWS, "scatter"),
])  # spmd_q1 in matmul mode (15M rows a shard) compiles too, in 65-107 s
def test_spmd_aggregate_compiles_for_four_chips(topo, captured, name, rows,
                                                segsum):
    """The sharded rung as one program over the 2x2 mesh: row-sharded
    columns in, an all-reduce combining the per-shard partial states.  At
    the smoke's 15M rows a shard, and at the 6M rows a shard of the
    benchmark's four-chip cell (Q1 from `perfbench/queries/tpch_q1.json`
    over the columns it projects from the sixteen).  ``matmul`` is what the
    chip resolves ``auto`` to for Q1's domain (`choose_segsum_impl` sees the
    CPU here, so the test says it through the existing key): the blocked
    one-hot matmul a shard and ONE all-reduce of its float64 state;
    ``scatter`` is the program under ``sql.compile.segsum: scatter``."""
    from dask_sql_tpu.parallel.mesh import AXIS
    from dask_sql_tpu.spmd.aggregate import SpmdAggregate

    (_, rel, table, scan, filters, group_exprs, agg_exprs, _), params = \
        captured[name]
    mesh = Mesh(np.array(topo.devices), (AXIS,))
    assert mesh.devices.size == 4
    row_blocks, replicated = NamedSharding(mesh, P(AXIS)), \
        NamedSharding(mesh, P())
    pipeline = SpmdAggregate(mesh, rel, table, scan, filters, group_exprs,
                             agg_exprs, {"sql.compile.segsum": segsum})
    assert pipeline.segsum_mode == segsum
    wrap = pipeline._wrap_for(len(params))
    datas, valids = _column_shapes(table, rows, row_blocks)
    args = wrap.pack_args(datas, valids, None,
                          _shapes(tuple(params), replicated))
    compiled = wrap.jitted.lower(*args).compile()
    text = compiled.as_text()
    # the combine: the float64 psum is an all-reduce, or (emulated as
    # float32 pairs) an all-gather and a local reduce
    assert "shard_map/psum" in text
    assert " all-reduce(" in text or " all-gather(" in text
    assert (" scatter(" in text) == (segsum == "scatter")
    assert _device_bytes(compiled) < HBM_BYTES  # per device


#: the benchmark's join cell (sf10_q3_library): rows of LINEITEM, ORDERS and
#: CUSTOMER, the key ranges their LUTs span (8 of every 32 order keys are
#: used), and what create_table encodes the program's columns to at that
#: size (the run's `load` line prints them)
Q3_ROWS = {"lineitem": 24_000_000, "orders": 6_000_000, "customer": 1_500_000}
Q3_LUT_KEYS = (24_000_000, 1_500_000)
Q3_DTYPES = {"l_orderkey": "int32", "l_extendedprice": "float64",
             "l_discount": "int16", "l_shipdate": "int16",
             "o_orderkey": "int32", "o_custkey": "int32",
             "o_orderdate": "int16", "c_custkey": "int32",
             "c_mktsegment": "int32"}


def test_join_aggregate_q3_fits_one_chip_at_cell_size(one_chip):
    """`sf10_q3_library`'s ONE program (two pointer joins through kept LUTs,
    the build sides' own filters as masks over their rows, the passing rows
    compacted into 1,507,328 and a float64 scatter segment sum of those
    into 6M groups, or past that capacity of the whole probe: a `lax.cond`;
    the top-10 tail) compiled for a v5e at the cell's shapes: 24M / 6M /
    1.5M rows.  Captured from Q3 as
    `perfbench.traffic` renders it over the cell's generator at 200,000
    lineitems, then traced anew with the cell's domains.  What is read here
    (ROADMAP S3f's first step): compile seconds and temporaries; a 64-bit
    sort or gather that compiles for minutes shows here, not on the chip.
    Costs the suite 3 s of set-up and the compile (printed with -s)."""
    import time

    from dask_sql_tpu import Context
    from dask_sql_tpu import config as config_module
    from dask_sql_tpu.ops.join import bucket_rows
    from dask_sql_tpu.physical import compiled_join
    from dask_sql_tpu.physical.compiled_join import CompiledJoinAggregate
    from perfbench import traffic
    from perfbench.datagen import tpch_q3_tables

    arrays = tpch_q3_tables.generate(SMALL_ROWS, seed=33, scale_factor=10)
    frames = tpch_q3_tables.arrow_tables(arrays)
    seen = []
    run = CompiledJoinAggregate.run

    def spy_run(self, params=()):
        seen.append((self, self.probe_table, self._run_args(params)))
        return run(self, params)

    with pytest.MonkeyPatch.context() as mp, \
            config_module.set({"serving.cache.enabled": False}):
        mp.setattr(CompiledJoinAggregate, "run", spy_run)
        # the cell's probe is above the compaction's row floor: the outer
        # join plans none even so
        mp.setattr(compiled_join, "_COMPACT_MIN_ROWS", SMALL_ROWS)
        c = Context()
        for name in ("customer", "orders", "lineitem"):
            c.create_table(name, frames[name])
        c.sql(traffic.render(traffic.load("queries", "tpch_q3_building"),
                             {"DAY": 16, "SEGMENT": 1})).compute()
    (pipeline, probe_table, args), = seen
    assert pipeline.topk is not None and pipeline.segsum_mode == "scatter"
    assert all(conj is not None for conj in pipeline.build_conjuncts)
    probe_datas, probe_valids, luts, build_cols, row_valid, params, bounds \
        = args
    assert row_valid is None and not any(v is not None for v in probe_valids)

    def shaped(rows, name, data):
        return jax.ShapeDtypeStruct((rows,), Q3_DTYPES.get(name, data.dtype),
                                    sharding=one_chip)

    tables = [c.schema[c.schema_name].tables[n].table
              for n in ("orders", "customer")]
    # the build sides as the program reads them: at their buckets
    rows = [bucket_rows(Q3_ROWS["orders"]), bucket_rows(Q3_ROWS["customer"])]
    scans = [j["plan"] for j in pipeline.ext.joins]
    big_probe = tuple(shaped(Q3_ROWS["lineitem"], n, d) for n, d in
                      zip(probe_table.column_names, probe_datas))
    big_luts = tuple(jax.ShapeDtypeStruct((bucket_rows(n),), jnp.int32,
                                          sharding=one_chip)
                     for n in Q3_LUT_KEYS)
    big_build = {}
    for (k, col), (data, valid) in build_cols.items():
        assert valid is None
        name = (scans[k].projection or tables[k].column_names)[col]
        big_build[(k, col)] = (shaped(rows[k], name, data), None)
    # the trace binds the build sides' bucketed rows (the group domain)
    pipeline.probe_table = probe_table
    pipeline.build_rows = rows
    pipeline.domain = rows[0]
    assert pipeline.compact_cap == compiled_join.compact_capacity(SMALL_ROWS)
    cap = pipeline.compact_cap = compiled_join.compact_capacity(
        Q3_ROWS["lineitem"])
    assert cap == 1_507_328
    try:
        lowered = jax.jit(pipeline._build()).lower(
            big_probe, probe_valids, big_luts, big_build, None,
            _shapes(tuple(params), one_chip), _shapes(bounds, one_chip))
    finally:
        pipeline.probe_table = pipeline.build_tables = None
    t0 = time.perf_counter()
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    m = compiled.memory_analysis()
    print(f"q3 at cell size: compile {seconds:.1f} s, arguments "
          f"{m.argument_size_in_bytes}, temporaries {m.temp_size_in_bytes}, "
          f"output {m.output_size_in_bytes}")
    assert seconds < 240, seconds
    # ONE executable, two branches: the compaction's `lax.cond`.  The branch
    # taken while the passing rows fit scatters `cap` update rows, the other
    # the whole probe's (three scatters each: the float64 sum, two counts)
    text = compiled.as_text()
    (branches,) = re.findall(r" conditional\(.*?branch_computations=\{(.*?)\}",
                             text)
    assert len(branches.split(",")) == 2, branches
    updates = re.findall(r'"stablehlo\.scatter"\(.*?\}\) : \(tensor<\d+xf?\w+>, '
                         r"tensor<(\d+)x1xi32>", lowered.as_text(), re.S)
    assert sorted(map(int, updates)) == [cap] * 3 \
        + [Q3_ROWS["lineitem"]] * 3, updates
    # the sorts in it are the TPU's own lowering of scatter-add (32-bit keys
    # over the rows it is handed) and the compaction's ONE over the probe's
    # rows, in the branch that compacts; the tail brings none
    sorts = re.findall(r"= \(?\w+\[(\d+)\][^=]*? sort\(.*?op_name=\"([^\"]*)\"",
                       text)
    by_branch = {}
    for n, name in sorts:
        by_branch.setdefault(name.split("/")[-2], []).append(
            (name.split("/")[-1], int(n)))
    assert sorted(by_branch["branch_0_fun"]) == \
        [("scatter-add", Q3_ROWS["lineitem"])] * 2, sorts
    assert sorted(by_branch["branch_1_fun"]) == \
        [("scatter-add", cap)] * 2 + [("sort", Q3_ROWS["lineitem"])], sorts
    assert set(by_branch) == {"branch_0_fun", "branch_1_fun"}, sorts
    # LINEITEM resident at 54 B/row, ORDERS and CUSTOMER beside it
    resident = 54 * Q3_ROWS["lineitem"] + 40 * Q3_ROWS["orders"] \
        + 40 * Q3_ROWS["customer"]
    assert _device_bytes(compiled) + resident < HBM_BYTES


#: the benchmark's Q18 cell (sf10_q18_library): the Q3 cell's tables; what
#: create_table encodes Q18's columns to at that size, and the range its
#: 6.0M order keys span (8 of every 32 are used)
Q18_DTYPES = {"l_orderkey": "int32", "l_quantity": "int16",
              "o_orderkey": "int32", "o_custkey": "int32",
              "o_totalprice": "float64", "o_orderdate": "int16",
              "c_custkey": "int32", "c_name": "int32"}
Q18_KEY_RANGE = 24_007_040


def test_join_aggregate_q18_fits_one_chip_at_cell_size(one_chip):
    """`sf10_q18_library`'s ONE program compiled for a v5e at the cell's
    shapes (24M / 6M / 1.5M rows): the semi-join's build side reduced in the
    program (a float64 scatter and a count of ALL 24M LINEITEM rows into the
    24M values of the order keys' range, the HAVING as a mask with a runtime
    threshold), ORDERS' LUT narrowed at ORDERS' rows by that mask and by the
    CUSTOMER join, one pointer join of the probe, the passing rows compacted
    (a `lax.cond`), and a top-100 by two group attributes whose customer
    columns are read through ORDERS' pointer.  Captured from Q18 as
    `perfbench.traffic` renders it over the cell's generator at 200,000
    lineitems, then traced anew with the cell's domains.  Read here: compile
    seconds, temporaries, and that no 64-bit sort is in the compiled text.
    Costs the suite 3 s of set-up and the compile (printed with -s)."""
    import time

    from dask_sql_tpu import Context
    from dask_sql_tpu import config as config_module
    from dask_sql_tpu.ops.join import bucket_rows
    from dask_sql_tpu.physical import compiled_join
    from dask_sql_tpu.physical.compiled_join import CompiledJoinAggregate
    from perfbench import traffic
    from perfbench.datagen import tpch_q18_tables

    arrays = tpch_q18_tables.generate(SMALL_ROWS, seed=35, scale_factor=10)
    frames = tpch_q18_tables.arrow_tables(arrays)
    seen = []
    run = CompiledJoinAggregate.run

    def spy_run(self, params=()):
        seen.append((self, self.probe_table, self._run_args(params)))
        return run(self, params)

    with pytest.MonkeyPatch.context() as mp, \
            config_module.set({"serving.cache.enabled": False}):
        mp.setattr(CompiledJoinAggregate, "run", spy_run)
        mp.setattr(compiled_join, "_COMPACT_MIN_ROWS", SMALL_ROWS)
        compiled_join.PROGRAMS.clear()
        c = Context()
        for name in ("customer", "orders", "lineitem"):
            c.create_table(name, frames[name])
        c.sql(traffic.render(traffic.load("queries", "tpch_q18"),
                             {"QUANTITY": 313})).compute()
    (pipeline, probe_table, args), = seen
    assert pipeline.topk is not None and pipeline.topk["k"] == 100
    assert list(pipeline.semis) == [2] and pipeline.folded == {1: 0, 2: 0}
    assert pipeline.dependents == [1] and pipeline.gid_join == 0
    probe_datas, probe_valids, luts, build_cols, row_valid, params, bounds \
        = args
    assert row_valid is None and not any(v is not None for v in probe_valids)
    assert luts[2] is None and len(params) == 1

    def shaped(rows, name, data):
        return jax.ShapeDtypeStruct((rows,), Q18_DTYPES.get(name, data.dtype),
                                    sharding=one_chip)

    names = ("orders", "customer", "lineitem")
    tables = [c.schema[c.schema_name].tables[n].table for n in names]
    # ORDERS and CUSTOMER at their buckets; the semi-join's LINEITEM is
    # read at its own rows, as the probe is
    rows = [bucket_rows(Q3_ROWS["orders"]), bucket_rows(Q3_ROWS["customer"]),
            Q3_ROWS["lineitem"]]
    key_range = bucket_rows(Q18_KEY_RANGE)
    scans = [j["plan"] for j in pipeline.ext.joins[:2]] \
        + [pipeline.ext.joins[2]["semi"]["scan"]]
    big_probe = tuple(shaped(Q3_ROWS["lineitem"], n, d) for n, d in
                      zip(probe_table.column_names, probe_datas))
    big_luts = tuple(jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
                     for n in (key_range, rows[1])) + (None,)
    big_build = {}
    for (k, col), (data, valid) in build_cols.items():
        assert valid is None
        name = (scans[k].projection or tables[k].column_names)[col]
        big_build[(k, col)] = (shaped(rows[k], name, data), None)
    pipeline.probe_table = probe_table
    pipeline.build_rows = rows
    pipeline.domain = rows[0]
    pipeline.semis[2]["domain"] = key_range
    cap = pipeline.compact_cap = compiled_join.compact_capacity(
        Q3_ROWS["lineitem"])
    try:
        lowered = jax.jit(pipeline._build()).lower(
            big_probe, probe_valids, big_luts, big_build, None,
            _shapes(tuple(params), one_chip), _shapes(bounds, one_chip))
    finally:
        pipeline.probe_table = pipeline.build_tables = None
    t0 = time.perf_counter()
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    m = compiled.memory_analysis()
    print(f"q18 at cell size: compile {seconds:.1f} s, arguments "
          f"{m.argument_size_in_bytes}, temporaries {m.temp_size_in_bytes}, "
          f"output {m.output_size_in_bytes}")
    assert seconds < 240, seconds
    assert _device_bytes(compiled) < HBM_BYTES
    # the scatters, every one int32 (PR 36: `l_quantity`'s dictionary is the
    # whole numbers 1..50, so both SUMs stay in code space and, with no NULL
    # and no NaN test, their counts ARE the rows' count): the semi-join's
    # sum and ONE count over all of LINEITEM into the key range, then the
    # outer sum and its count, over the compact buffer in one branch and
    # over the whole probe in the other.  No scatter has a 64-bit operand
    updates = re.findall(r'"stablehlo\.scatter"\(.*?\}\) : \(tensor<(\d+)x(\w+)>, '
                         r"tensor<(\d+)x1xi32>", lowered.as_text(), re.S)
    assert sorted((int(d), dtype, int(n)) for d, dtype, n in updates) == sorted(
        [(key_range, "i32", Q3_ROWS["lineitem"])] * 2
        + [(rows[0], "i32", cap)] * 2
        + [(rows[0], "i32", Q3_ROWS["lineitem"])] * 2), updates
    assert pipeline.sum_codespace == 2
    # every sort is 32-bit: the TPU's lowering of scatter-add and the
    # compaction's one; the top-100 tail brings none
    text = compiled.as_text()
    sorts = re.findall(r"= \(?(\w+)\[(\d+)\][^=]*? sort\(", text)
    assert sorts and all(dtype in ("s32", "u32", "f32", "pred")
                         for dtype, _ in sorts), sorts


#: the benchmark's Q9 cell (sf10_q9_library): LINEITEM and ORDERS as the Q3
#: cell's, PART, SUPPLIER, NATION whole, PARTSUPP's slots (four a part key),
#: and the dtypes create_table encodes Q9's columns to at that size
Q9_ROWS = {"lineitem": 24_000_000, "supplier": 100_000, "part": 2_000_000,
           "orders": 6_000_000, "nation": 25}
Q9_DTYPES = {"l_orderkey": "int32", "l_partkey": "int32",
             "l_suppkey": "int32", "o_orderkey": "int32",
             "p_partkey": "int32", "s_suppkey": "int32"}


def test_join_aggregate_q9_fits_one_chip_at_cell_size(one_chip):
    """`sf10_q9_library`'s ONE program compiled for a v5e at the cell's
    shapes, as the chip builds it (a float64 scatter segment sum into the
    208 (nation, year) groups): PART's LIKE as a runtime mask of its 2M
    names' bucket folded into its LUT at the probe's 24M rows, the passing
    rows compacted into 1,507,328, and SUPPLIER, NATION, PARTSUPP (its
    two-column key's slots, four a part) and ORDERS probed at those rows
    alone, or at every row in the `lax.cond`'s other branch.  Captured from
    Q9 as `perfbench.traffic` renders it over the cell's generator at
    200,000 lineitems, then traced anew with the cell's domains.  Costs the
    suite 4 s of set-up and the compile (printed with -s)."""
    import time

    from dask_sql_tpu import Context
    from dask_sql_tpu import config as config_module
    from dask_sql_tpu.ops.join import bucket_rows
    from dask_sql_tpu.physical import compiled_join
    from dask_sql_tpu.physical.compiled_join import CompiledJoinAggregate
    from perfbench import traffic
    from perfbench.datagen import tpch_q9_tables

    arrays = tpch_q9_tables.generate(SMALL_ROWS, seed=39, scale_factor=10)
    frames = tpch_q9_tables.arrow_tables(arrays)
    seen = []
    run = CompiledJoinAggregate.run

    def spy_run(self, params=()):
        seen.append((self, self.probe_table, self._run_args(params)))
        return run(self, params)

    with pytest.MonkeyPatch.context() as mp, \
            config_module.set({"serving.cache.enabled": False}):
        mp.setattr(CompiledJoinAggregate, "run", spy_run)
        mp.setattr(compiled_join, "_COMPACT_MIN_ROWS", SMALL_ROWS)
        compiled_join.PROGRAMS.clear()
        c = Context()
        for name in ("nation", "supplier", "part", "partsupp", "orders",
                     "lineitem"):
            c.create_table(name, frames[name])
        query = traffic.load("queries", "tpch_q9_green")
        c.sql(traffic.render(query, {"COLOR": 33})).compute()
    (pipeline, probe_table, args), = seen
    names = [j["plan"].table_name for j in pipeline.ext.joins]
    assert sorted(names) == ["nation", "orders", "part", "partsupp",
                             "supplier"], names
    ps = names.index("partsupp")
    assert list(pipeline.composites) == [ps]
    assert pipeline.composites[ps]["run"] == 4
    assert [names[k] for k in pipeline.deferred] == [
        n for n in names if n != "part"]
    # the compacted rows are few: the exact scatter, whatever the domain
    assert pipeline.segsum_mode == "scatter"
    probe_datas, probe_valids, luts, build_cols, row_valid, params, bounds \
        = args
    assert row_valid is None and not any(v is not None for v in probe_valids)

    def shaped(rows, name, data):
        return jax.ShapeDtypeStruct((rows,), Q9_DTYPES.get(name, data.dtype),
                                    sharding=one_chip)

    slots = bucket_rows(Q9_ROWS["part"]) * 4
    rows = [slots if n == "partsupp" else bucket_rows(Q9_ROWS[n])
            for n in names]
    lut_rows = {"orders": bucket_rows(24_000_616), "partsupp": slots,
                **{n: bucket_rows(Q9_ROWS[n])
                   for n in ("supplier", "part", "nation")}}
    big_probe = tuple(shaped(Q9_ROWS["lineitem"], n, d) for n, d in
                      zip(probe_table.column_names, probe_datas))
    big_luts = tuple(jax.ShapeDtypeStruct((lut_rows[n],), jnp.int32,
                                          sharding=one_chip) for n in names)
    tables = [c.schema[c.schema_name].tables[n].table for n in names]
    big_build = {}
    for (k, col), (data, valid) in build_cols.items():
        assert valid is None
        name = (pipeline.ext.joins[k]["plan"].projection
                or tables[k].column_names)[col]
        big_build[(k, col)] = (shaped(rows[k], name, data), None)
    # the LIKE's mask: one bool per name, at the names' bucket
    (mask_at,) = [i for i, p in enumerate(params) if np.ndim(p) == 1]
    big_params = list(_shapes(tuple(params), one_chip))
    big_params[mask_at] = jax.ShapeDtypeStruct(
        (bucket_rows(Q9_ROWS["part"]),), jnp.bool_, sharding=one_chip)
    pipeline.probe_table = probe_table
    pipeline.build_rows = rows
    pipeline.composites[ps] = dict(pipeline.composites[ps], slots=slots)
    cap = pipeline.compact_cap = compiled_join.compact_capacity(
        Q9_ROWS["lineitem"])
    assert cap == 1_507_328 and pipeline.domain == 26 * 8
    try:
        lowered = jax.jit(pipeline._build()).lower(
            big_probe, probe_valids, big_luts, big_build, None,
            tuple(big_params), _shapes(bounds, one_chip))
    finally:
        pipeline.probe_table = pipeline.build_tables = None
    t0 = time.perf_counter()
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    m = compiled.memory_analysis()
    print(f"q9 at cell size: compile {seconds:.1f} s, arguments "
          f"{m.argument_size_in_bytes}, temporaries {m.temp_size_in_bytes}, "
          f"output {m.output_size_in_bytes}")
    assert seconds < 240, seconds
    # the six tables resident (50 columns, some 1.75 GB) beside the program
    assert _device_bytes(compiled) + 1_750_000_000 < HBM_BYTES
    # the scatters: the float64 sum and its count over the buffer's rows in
    # one branch, over the probe's in the other
    updates = re.findall(r'"stablehlo\.scatter"\(.*?\}\) : \(tensor<(\d+)x\w+>, '
                         r"tensor<(\d+)x1xi32>", lowered.as_text(), re.S)
    assert {int(n) for _, n in updates} == {cap, Q9_ROWS["lineitem"]}, updates
    assert {int(d) for d, _ in updates} == {26 * 8}, updates
    text = compiled.as_text()
    sorts = re.findall(r"= \(?(\w+)\[(\d+)\][^=]*? sort\(", text)
    assert sorts and all(dtype in ("s32", "u32", "f32", "pred")
                         for dtype, _ in sorts), sorts


#: the benchmark's Q13 cell (sf10_q13_library): ORDERS and CUSTOMER whole
#: at SF10, what create_table encodes Q13's columns to at that size, and
#: the comments' dictionary (one entry per order, near enough)
Q13_ROWS = {"orders": 15_000_000, "customer": 1_500_000}
Q13_DTYPES = {"o_orderkey": "int32", "o_custkey": "int32",
              "o_comment": "int32", "c_custkey": "int32"}


def test_join_aggregate_q13_fits_one_chip_at_cell_size(one_chip):
    """`sf10_q13_library`'s ONE program compiled for a v5e at the cell's
    shapes: ORDERS' 15M rows the probe, the NOT LIKE a runtime mask of the
    comments' 15M-entry bucket read at the rows' codes, CUSTOMER's 1.5M
    rows through its kept LUT, each of them a group (the LEFT OUTER join),
    a scatter of the counts into them over the whole probe (an outer join
    plans no compaction: the NOT LIKE passes nearly every order), and the
    outer GROUP BY c_count over those groups: a `lax.cond` between the
    histogram of 1,024 values and the exact branch's hash table, which
    sorts nothing (a sort compiles for a minute).  Captured from Q13 as
    `perfbench.traffic` renders it over the cell's generator at 200,000
    orders, then traced anew with the cell's domains.  Costs the suite 3 s
    of set-up and the compile (printed with -s)."""
    import time

    from dask_sql_tpu import Context
    from dask_sql_tpu import config as config_module
    from dask_sql_tpu.ops.join import bucket_rows
    from dask_sql_tpu.physical import compiled_join
    from dask_sql_tpu.physical.compiled_join import CompiledJoinAggregate
    from perfbench import traffic
    from perfbench.datagen import tpch_q13_tables

    arrays = tpch_q13_tables.generate(SMALL_ROWS, seed=41, scale_factor=10)
    frames = tpch_q13_tables.arrow_tables(arrays)
    seen = []
    run = CompiledJoinAggregate.run

    def spy_run(self, params=()):
        seen.append((self, self.probe_table, self._run_args(params)))
        return run(self, params)

    with pytest.MonkeyPatch.context() as mp, \
            config_module.set({"serving.cache.enabled": False}):
        mp.setattr(CompiledJoinAggregate, "run", spy_run)
        # the cell's probe is above the compaction's row floor: the outer
        # join plans none even so
        mp.setattr(compiled_join, "_COMPACT_MIN_ROWS", SMALL_ROWS)
        compiled_join.PROGRAMS.clear()
        c = Context()
        for name in ("customer", "orders"):
            c.create_table(name, frames[name])
        c.sql(traffic.render(traffic.load("queries",
                                          "tpch_q13_special_requests"),
                             {"WORD1": 0, "WORD2": 1})).compute()
    (pipeline, probe_table, args), = seen
    assert pipeline.outer == 0 and pipeline.nested is not None
    assert pipeline.compact_cap == 0
    probe_datas, probe_valids, luts, build_cols, row_valid, params, bounds \
        = args
    assert row_valid is None and not any(v is not None for v in probe_valids)

    def shaped(rows, name, data):
        return jax.ShapeDtypeStruct((rows,), Q13_DTYPES.get(name, data.dtype),
                                    sharding=one_chip)

    rows = bucket_rows(Q13_ROWS["customer"])
    big_probe = tuple(shaped(Q13_ROWS["orders"], n, d) for n, d in
                      zip(probe_table.column_names, probe_datas))
    big_luts = (jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),)
    (scan,) = [j["plan"] for j in pipeline.ext.joins]
    big_build = {(k, col): (shaped(rows, scan.projection[col], data), None)
                 for (k, col), (data, valid) in build_cols.items()}
    (mask_at,) = [i for i, p in enumerate(params) if np.ndim(p) == 1]
    big_params = list(_shapes(tuple(params), one_chip))
    big_params[mask_at] = jax.ShapeDtypeStruct(
        (bucket_rows(Q13_ROWS["orders"]),), jnp.bool_, sharding=one_chip)
    pipeline.probe_table = probe_table
    pipeline.build_rows = [rows]
    pipeline.domain = rows
    try:
        lowered = jax.jit(pipeline._build()).lower(
            big_probe, probe_valids, big_luts, big_build, None,
            tuple(big_params), _shapes(bounds, one_chip))
    finally:
        pipeline.probe_table = pipeline.build_tables = None
    t0 = time.perf_counter()
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    m = compiled.memory_analysis()
    print(f"q13 at cell size: compile {seconds:.1f} s, arguments "
          f"{m.argument_size_in_bytes}, temporaries {m.temp_size_in_bytes}, "
          f"output {m.output_size_in_bytes}")
    assert seconds < 240, seconds
    text = compiled.as_text()
    sorts = re.findall(r"= \(?(\w+)\[(\d+)\][^=]*? sort\(", text)
    assert all(dtype in ("s32", "u32", "f32", "pred")
               for dtype, _ in sorts), sorts
    # ORDERS' 17 B a row and CUSTOMER's 8 resident beside the program
    resident = 40 * Q13_ROWS["orders"] + 40 * Q13_ROWS["customer"]
    assert _device_bytes(compiled) + resident < HBM_BYTES
