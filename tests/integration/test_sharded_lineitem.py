"""The deployment `sf10_q1_sharded_4chip` measures, at a small size on the
virtual mesh: all sixteen columns of the benchmark's own LINEITEM, from
pyarrow, registered ``distributed=True`` over four devices, answered by the
sharded rungs and compared with the benchmark's plain reference
(`perfbench/references/`: numpy float64, nothing of the engine) through the
benchmark's own comparison.  And the other side of holding a cell to its
layout: a sharded rung that fails by exception is a COUNTED step down
(`resilience.degraded.spmd_*`), the lower rung still answering right.
"""
import random

import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
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


@pytest.fixture(scope="module", autouse=True)
def mesh4():
    """The cell's mesh: four of the virtual devices, restored after."""
    was = mesh_module._default_mesh
    mesh_module.set_default_mesh(mesh_module.make_mesh(4))
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


def _context(arrow_table, distributed=True):
    c = Context()
    c.config.update({"serving.cache.enabled": False})
    c.create_table("lineitem", arrow_table, distributed=distributed)
    return c


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("name", ["tpch_q1", "tpch_q6"])
def test_sharded_lineitem_answers_as_the_plain_reference(lineitem, name, rows):
    arrays, arrow_table = lineitem(rows)
    query = traffic.load("queries", name)
    reference = REFERENCES[name].Reference(arrays)
    c = _context(arrow_table)
    table = c.schema["root"].tables["lineitem"].table
    assert len(table.columns) == 16
    assert (table.row_valid is not None) == (rows % 4 != 0)
    assert all(len(b.sharding.device_set) == 4 for b in buffers_of(table))
    rng = random.Random(f"{name}:{rows}")
    for _ in range(3):
        params = traffic.draw_params(query, rng)
        frame = c.sql(traffic.render(query, params)).compute()
        # every float cell within the query file's limit, keys and
        # count_order exact: the comparison that decides `correct`
        gap = compare.answer_gap(query, frame_answer(frame),
                                 reference.answer(params))
        assert gap is not None and gap <= query["limits"]["rel_err"], \
            (params, gap)
        spans = [s.name for s in c.last_trace.spans]
        assert "rung:spmd_aggregate" in spans, spans
        launch = [s for s in c.last_trace.spans if s.name == "launch"][-1]
        assert launch.attrs["rung"] == "spmd_aggregate"
        assert launch.attrs["devices"] == 4
        assert launch.attrs["rows_per_device"] == (rows + 3) // 4
    assert c.metrics.counter("resilience.degraded") == 0
    assert c.metrics.counter("resilience.rung.spmd_aggregate") == 3
    assert c.metrics.snapshot()["gauges"]["parallel.spmd.devices"] == 4


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
