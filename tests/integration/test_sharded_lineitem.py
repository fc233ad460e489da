"""The deployment `sf10_q1_sharded_4chip` measures, at a small size on the
virtual mesh: all sixteen columns of the benchmark's own LINEITEM, from
pyarrow, registered ``distributed=True`` over four devices, answered by the
sharded rungs and compared with the benchmark's plain reference
(`perfbench/references/`: numpy float64, nothing of the engine) through the
benchmark's own comparison.  And the other side of holding a cell to its
layout: a sharded rung that fails by exception is a COUNTED step down
(`resilience.degraded.spmd_*`), the lower rung still answering right.

Since PR 30 the rung chooses its segment sum as the one-chip rung does
(`sql.compile.segsum`; on a TPU ``auto`` is the blocked one-hot matmul, whose
``[domain, K]`` float64 state crosses the mesh in one `psum`), so every
answer here is asked for in both modes.  The CPU's float32 dot accumulates
worse than the MXU (3.8e-7 at these sizes against the chip's 3e-8), so the
matmul mode is held to `MATMUL_FLOAT_REL_ERR_BOUND` here and to the query's
own limit on the chip.
"""
import random

import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.ops.pallas_kernels import MATMUL_FLOAT_REL_ERR_BOUND
from dask_sql_tpu.parallel import mesh as mesh_module
from perfbench import compare, traffic
from perfbench.datagen import tpch_lineitem
from perfbench.references import tpch_q1_binned, tpch_q6_binned
from perfbench.run import buffers_of
from perfbench.surfaces.library import frame_answer

pytestmark = pytest.mark.spmd

REFERENCES = {"tpch_q1": tpch_q1_binned, "tpch_q6": tpch_q6_binned}
#: 50,000 divides by four; 49,999 pads one row and carries `row_valid`
ROWS = (50_000, 49_999)
MODES = ("scatter", "matmul")
#: four shards of exactly one 32,768-row block of the blocked matmul: each
#: shard's dot is one of the one-device scan's four, to the bit
ALIGNED_ROWS = 4 * 32_768
#: between two block layouts of the CPU's float32 dot (each reads up to
#: 4.2e-7 against the reference at these sizes, the pair up to 4.6e-7)
CPU_PAIR_REL = 1e-6


@pytest.fixture(scope="module", autouse=True)
def mesh4():
    """The cell's mesh, four of the virtual devices, and its
    `engine_config` (the result cache off): both the process's, so both
    restored after, for the files this worker runs next."""
    was = mesh_module._default_mesh
    mesh_module.set_default_mesh(mesh_module.make_mesh(4))
    with config_module.set({"serving.cache.enabled": False}):
        yield
    mesh_module.set_default_mesh(was)


@pytest.fixture(scope="module")
def lineitem():
    """rows -> (generated arrays, pyarrow table), made once per module."""
    made = {}

    def get(rows):
        if rows not in made:
            arrays = tpch_lineitem.generate(rows, seed=2900 + rows,
                                            scale_factor=10)
            made[rows] = (arrays,
                          tpch_lineitem.arrow_tables(arrays)["lineitem"])
        return made[rows]
    return get


def _context(arrow_table, distributed=True, name="lineitem"):
    c = Context()
    c.create_table(name, arrow_table, distributed=distributed)
    return c


def _compute(c, sql, mode):
    """``sql`` under ``sql.compile.segsum: mode``, for this query alone (the
    config is the process's)."""
    return c.sql(sql, config_options={"sql.compile.segsum": mode}) \
        .compute().reset_index(drop=True)


def _launch(c):
    return [s for s in c.last_trace.spans if s.name == "launch"][-1]


def _other(mode):
    return MODES[1 - MODES.index(mode)]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("name", ["tpch_q1", "tpch_q6"])
def test_sharded_lineitem_answers_as_the_plain_reference(lineitem, name, rows,
                                                         mode):
    arrays, arrow_table = lineitem(rows)
    query = traffic.load("queries", name)
    reference = REFERENCES[name].Reference(arrays)
    limit = query["limits"]["rel_err"] if mode == "scatter" \
        else MATMUL_FLOAT_REL_ERR_BOUND
    c = _context(arrow_table)
    table = c.schema["root"].tables["lineitem"].table
    assert len(table.columns) == 16
    assert (table.row_valid is not None) == (rows % 4 != 0)
    assert all(len(b.sharding.device_set) == 4 for b in buffers_of(table))
    rng = random.Random(f"{name}:{rows}")
    for _ in range(3):
        params = traffic.draw_params(query, rng)
        frame = _compute(c, traffic.render(query, params), mode)
        # every float cell within the query file's limit, keys and
        # count_order exact: the comparison that decides `correct`
        gap = compare.answer_gap(query, frame_answer(frame),
                                 reference.answer(params))
        assert gap is not None and gap <= limit, (params, gap)
        spans = [s.name for s in c.last_trace.spans]
        assert "rung:spmd_aggregate" in spans, spans
        launch = _launch(c)
        assert launch.attrs["rung"] == "spmd_aggregate"
        assert launch.attrs["devices"] == 4
        assert launch.attrs["rows_per_device"] == (rows + 3) // 4
        assert launch.attrs["segsum"] == mode
    assert c.metrics.counter("resilience.degraded") == 0
    assert c.metrics.counter("resilience.rung.spmd_aggregate") == 3
    assert c.metrics.counter(f"parallel.spmd.segsum.{mode}") == 3
    assert c.metrics.counter(f"parallel.spmd.segsum.{_other(mode)}") == 0
    assert c.metrics.snapshot()["gauges"]["parallel.spmd.devices"] == 4


def _answers(c, name, rows, mode, n=2):
    """The cell's own traffic: ``n`` draws of the query's parameters."""
    query = traffic.load("queries", name)
    rng = random.Random(f"pair:{name}:{rows}")
    return [_compute(c, traffic.render(query, traffic.draw_params(query, rng)),
                     mode) for _ in range(n)]


@pytest.mark.parametrize("mode, rows, rtol", [
    ("scatter", ROWS[0], 1e-12), ("scatter", ROWS[1], 1e-12),
    ("matmul", ROWS[0], CPU_PAIR_REL), ("matmul", ROWS[1], CPU_PAIR_REL),
    ("matmul", ALIGNED_ROWS, 1e-14)])
@pytest.mark.parametrize("name", ["tpch_q1", "tpch_q6"])
def test_sharding_adds_nothing_to_a_mode(lineitem, name, mode, rows, rtol):
    """The sharded answer against the ONE-device answer in the same mode.
    ``scatter`` is the program the rung had before it chose: float64 partial
    sums, the unsharded answer to addition order.  ``matmul``: the float64
    `psum` of four shards' block partials against one device's float64 carry
    over the same rows; where the shards ARE the one-device blocks the
    answers agree to float64 addition order, elsewhere the blocks differ and
    so do the CPU's float32 dots.  Keys and COUNT(*) exact."""
    _, arrow_table = lineitem(rows)
    sharded = _context(arrow_table)
    single = _context(arrow_table, distributed=False)
    for got, want in zip(_answers(sharded, name, rows, mode),
                         _answers(single, name, rows, mode)):
        pd.testing.assert_frame_equal(got, want, check_exact=False,
                                      rtol=rtol, atol=0.0)
        exact = [n for n in got.columns
                 if not pd.api.types.is_float_dtype(got[n])]
        pd.testing.assert_frame_equal(got[exact], want[exact])
    assert sharded.metrics.counter(f"parallel.spmd.segsum.{mode}") == 2
    assert single.metrics.counter("resilience.rung.spmd_aggregate") == 0


def test_auto_stays_scatter_off_the_chip(lineitem):
    """`choose_segsum_impl` is asked, and off a TPU it says scatter: no
    tier-1 program changed with the un-pinning."""
    _, arrow_table = lineitem(ROWS[0])
    c = _context(arrow_table)
    assert str(c.config.get("sql.compile.segsum", "auto")) == "auto"
    c.sql(_q1()).compute()
    assert _launch(c).attrs["segsum"] == "scatter"
    assert c.metrics.counter("parallel.spmd.segsum.scatter") == 1
    assert c.metrics.counter("parallel.spmd.segsum.matmul") == 0


@pytest.mark.parametrize("mode", MODES)
def test_two_literals_of_a_family_share_one_program(lineitem, mode):
    """The configured mode is part of the FAMILY, the table's rows are the
    BUCKET: a second DELTA hits the first's compiled object; the other
    mode is another program."""
    from dask_sql_tpu.spmd import aggregate

    _, arrow_table = lineitem(ROWS[1])
    query = traffic.load("queries", "tpch_q1")
    c = _context(arrow_table)
    before = set(aggregate.PROGRAMS.values())
    for delta in (60, 120):
        _compute(c, traffic.render(query, {"DELTA": delta}), mode)
    built = [(key, program) for key, program in aggregate.PROGRAMS.items()
             if program not in before]
    assert len(built) == 1, built
    (family, bucket), program = built[0]
    assert program.segsum_mode == mode
    assert c.metrics.counter("families.hit") == 1
    assert mode in family
    dc = c.schema["root"].tables["lineitem"]
    assert bucket == (dc.uid, dc.table.num_rows, dc.table.padded_rows)
    assert not [s for s in c.last_trace.spans
                if s.name.startswith("compile:")]
    _compute(c, traffic.render(query, {"DELTA": 90}), _other(mode))
    assert _launch(c).attrs["segsum"] == _other(mode)
    assert len([program for program in aggregate.PROGRAMS.values()
                if program not in before]) == 2


@pytest.mark.parametrize("mode", MODES)
def test_done_handles_and_the_psummed_state_meet_in_one_finalize(mode):
    """A float SUM and AVG and COUNT(col) (the deferred ``[domain, K]``
    state in matmul mode), an integer SUM, MIN and MAX (scatter handles in
    every mode), NULLs in every argument, 1,003 rows (padded: `row_valid`)."""
    rng = np.random.default_rng(30)
    n = 1_003
    frame = pd.DataFrame({
        "k": rng.choice(["a", "b", "c"], n),
        "x": np.where(rng.random(n) < 0.2, np.nan, rng.normal(100.0, 30.0, n)),
        "i": pd.array(np.where(rng.random(n) < 0.2, None,
                               rng.integers(-50, 50, n)), dtype="Int64"),
    })
    sql = ("SELECT k, SUM(x) AS sx, SUM(i) AS si, MIN(x) AS mn, MAX(i) AS mx, "
           "AVG(x) AS ax, COUNT(x) AS cx, COUNT(i) AS ci, COUNT(*) AS c "
           "FROM t GROUP BY k ORDER BY k")
    c = _context(frame, name="t")
    got = _compute(c, sql, mode)
    assert "rung:spmd_aggregate" in [s.name for s in c.last_trace.spans]
    assert _launch(c).attrs["segsum"] == mode
    g = frame.groupby("k")
    want = {
        "k": sorted(frame["k"].unique()),
        "sx": g["x"].sum().values, "si": g["i"].sum().values.astype("int64"),
        "mn": g["x"].min().values, "mx": g["i"].max().values.astype("int64"),
        "ax": g["x"].mean().values, "cx": g["x"].count().values,
        "ci": g["i"].count().values, "c": g.size().values,
    }
    for name in ("k", "si", "mx", "cx", "ci", "c"):
        assert list(got[name]) == list(want[name]), name
    for name in ("sx", "mn", "ax"):
        np.testing.assert_allclose(
            got[name].astype("float64"), want[name], atol=0.0,
            rtol=1e-12 if mode == "scatter" or name == "mn"
            else MATMUL_FLOAT_REL_ERR_BOUND, err_msg=name)


SELECT = ("SELECT l_orderkey, l_extendedprice * 2 AS twice FROM lineitem "
          "WHERE l_quantity < 3 LIMIT 11")


def _q1():
    query = traffic.load("queries", "tpch_q1")
    return traffic.render(query, {"DELTA": 90})


@pytest.mark.parametrize("rung, target, sql", [
    ("spmd_aggregate", "dask_sql_tpu.spmd.aggregate.SpmdAggregate.run", None),
    ("spmd_select", "dask_sql_tpu.spmd.select.SpmdSelect.run", SELECT),
], ids=["aggregate", "select"])
@pytest.mark.parametrize("error", [ValueError, TypeError])
def test_a_rung_failing_by_exception_is_a_counted_step_down(
        lineitem, monkeypatch, rung, target, sql, error):
    _, arrow_table = lineitem(ROWS[0])
    sql = sql or _q1()
    expected = _context(arrow_table, distributed=False).sql(sql).compute()
    c = _context(arrow_table)

    def broken(self, *args, **kwargs):
        raise error("the wrap mis-handles this shape")

    monkeypatch.setattr(target, broken)
    got = c.sql(sql).compute()
    pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                  expected.reset_index(drop=True),
                                  check_exact=False, rtol=1e-12)
    m = c.metrics
    assert m.counter(f"resilience.degraded.{rung}") == 1
    assert m.counter("resilience.degraded") == 1
    assert m.counter(f"resilience.rung.{rung}") == 0
    events = [s for s in c.last_trace.spans
              if s.name == f"degraded:{rung}"]
    assert len(events) == 1 and events[0].attrs["code"] == "COMPILE_ERROR"
    # a single-chip compiled rung, or the interpreted walk (no rung span)
    assert not [s.name for s in c.last_trace.spans
                if s.name.startswith("rung:spmd_")]


def test_an_ineligible_shape_declines_uncounted(lineitem, monkeypatch):
    from dask_sql_tpu.physical.compiled import _Unsupported
    from dask_sql_tpu.spmd.aggregate import SpmdAggregate

    _, arrow_table = lineitem(ROWS[0])
    c = _context(arrow_table)

    def ineligible(self, *args, **kwargs):
        raise _Unsupported("not a shape this rung serves")

    monkeypatch.setattr(SpmdAggregate, "__init__", ineligible)
    got = c.sql(_q1()).compute()
    assert len(got) == 4 and np.isfinite(got["sum_charge"]).all()
    assert c.metrics.counter("resilience.degraded") == 0
    assert c.metrics.counter("resilience.rung.spmd_aggregate") == 0
    assert not [s for s in c.last_trace.spans
                if s.name.startswith("degraded:")]
