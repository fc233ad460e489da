"""TPC-H Q9 through `compiled_join_aggregate` (physical/compiled_join.py):
PARTSUPP joined on its two-column key through kept slots
(`ops/join.py::composite_slots`), ``p_name LIKE '%COLOR%'`` bound as a
runtime mask over PART's dictionary (`families/parameterize.py::
_string_mask`), ``EXTRACT(YEAR FROM o_orderdate)`` a group key over the
order dates' dictionary codes (`compiled_join._derived_key`).

Tables come from the benchmark's own generator (`perfbench/datagen/
tpch_q9_tables.py`) at 50,000 lineitems on SF10's key domains, the texts
from the cell's twelve query files, the answers are held to the plain
reference (`perfbench/references/tpch_q9_grouped.py`) through
`perfbench.compare.answer_gap` under the files' own limits.  The two-column
key's edge cases run on small hand-made tables against the interpreted
converters.

Cost (ROADMAP D11): the module's tables 3 s once and one compile of some
3 s for every Q9 case on them; the baked-LIKE cases and the small tables a
compile each, under a second.
"""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.ops.join import COMPOSITE_MAX_RUN, composite_slots
from dask_sql_tpu.physical import compiled_join as cj
from perfbench import compare, traffic
from perfbench.datagen import tpch_q9_tables
from perfbench.references import tpch_q9_grouped
from perfbench.surfaces.library import frame_answer

ROWS = 50_000
FILES = [m["query"] for m in traffic.load("workloads",
                                          "sf10_q9_library")["mix"]]
RUNG = "rung:compiled_join_aggregate"


@pytest.fixture(scope="module", autouse=True)
def result_cache_off():
    """The cell's `engine_config`, for this module alone (see
    test_q3_tables.py)."""
    with config_module.set({"serving.cache.enabled": False}):
        yield


@pytest.fixture(scope="module")
def q9(result_cache_off):
    cj.PROGRAMS.clear()
    cj.LUTS.clear()
    arrays = tpch_q9_tables.generate(ROWS, seed=39, scale_factor=10)
    frames = tpch_q9_tables.arrow_tables(arrays)
    c = Context()
    for name in ("nation", "supplier", "part", "partsupp", "orders",
                 "lineitem"):
        c.create_table(name, frames[name])
    return c, arrays, {name: frames[name].to_pandas() for name in frames}


def span_names(c):
    return [s.name for s in c.last_trace.spans]


def compiled_here(names):
    return [n for n in names if n.startswith(("compile:", "xla:compile"))]


def test_partsupp_holds_every_line_pair(q9):
    """The generator's PARTSUPP has a row for every (l_partkey, l_suppkey)
    of LINEITEM, four suppliers a part, and its pairs are unique."""
    _, _, t = q9
    ps, li = t["partsupp"], t["lineitem"]
    pairs = set(zip(ps.ps_partkey, ps.ps_suppkey))
    assert len(pairs) == len(ps) == 4 * len(t["part"])
    assert set(zip(li.l_partkey, li.l_suppkey)) <= pairs
    assert set(li.l_partkey) <= set(t["part"].p_partkey)
    assert set(ps.ps_suppkey) <= set(t["supplier"].s_suppkey)


def test_twelve_colors_equal_the_reference_on_one_executable(q9):
    """Every file of the cell's mix: on the rung, equal to the reference
    under the file's limits, and after the first no compile of any kind."""
    c, arrays, _ = q9
    reference = tpch_q9_grouped.Reference(arrays)
    before = c.metrics.counter("join.build.composite")
    for i, name in enumerate(FILES):
        query = traffic.load("queries", name)
        params = {"COLOR": query["parameters"]["COLOR"]["low"]}
        frame = c.sql(traffic.render(query, params)).compute()
        names = span_names(c)
        assert RUNG in names and "join:like" in names \
            and "join:composite" in names, (name, names)
        if i:
            assert not compiled_here(names), (name, names)
        answer = frame_answer(frame)
        assert len(answer["rows"]) == 175  # 25 nations x 7 years
        gap = compare.answer_gap(query, answer, reference.answer(params))
        assert gap is not None and gap <= query["limits"]["rel_err"], \
            (name, gap)
    assert c.metrics.counter("join.build.composite") - before <= 1
    launch = [s for s in c.last_trace.spans if s.name == "launch"]
    assert launch[0].attrs["composite"] == 1
    assert "outer" not in launch[0].attrs and "nested" not in launch[0].attrs


def test_the_float32_control_is_outside_the_limit(q9):
    """The reference in float32 in the engine's place fails the files'
    limit where the engine passes it."""
    _, arrays, _ = q9
    reference = tpch_q9_grouped.Reference(arrays)
    query = traffic.load("queries", "tpch_q9_green")
    params = {"COLOR": query["parameters"]["COLOR"]["low"]}
    control = tpch_q9_grouped.control_answer(arrays, params, "float32")
    gap = compare.answer_gap(query, control, reference.answer(params))
    assert gap is not None and gap > query["limits"]["rel_err"]


@pytest.fixture
def programs_cleared():
    """Programs built under a test's own config or row floor go with it:
    a family's key holds neither."""
    cj.PROGRAMS.clear()
    yield
    cj.PROGRAMS.clear()


@pytest.mark.parametrize("segsum", ["scatter", "matmul"])
def test_unfiltered_joins_probe_the_compacted_rows(q9, segsum, monkeypatch,
                                                   programs_cleared):
    """Above the compaction's row floor (lowered here) Q9 compacts the rows
    PART's LIKE passes and probes SUPPLIER, NATION, PARTSUPP and ORDERS at
    those rows alone, whatever the segment sum; equal to the reference
    (the files' limit for the float64 scatter the program takes by itself,
    1e-6 for the blocked matmul's float32 partials).  With a buffer too
    small for them (`compact_capacity` lowered too) the same executable's
    other branch probes them at every row."""
    c, arrays, _ = q9
    reference = tpch_q9_grouped.Reference(arrays)
    monkeypatch.setattr(cj, "_COMPACT_MIN_ROWS", 1 << 10)
    for cap, overflow in ((None, 0), (lambda rows: 1_024, 1)):
        if cap is not None:
            monkeypatch.setattr(cj, "compact_capacity", cap)
        cj.PROGRAMS.clear()
        before = c.metrics.counter("join.compact.overflow")
        config = {} if segsum == "scatter" else {"sql.compile.segsum": segsum}
        with config_module.set(config):
            for name in ("tpch_q9_green", "tpch_q9_smoke"):
                query = traffic.load("queries", name)
                params = {"COLOR": query["parameters"]["COLOR"]["low"]}
                frame = c.sql(traffic.render(query, params)).compute()
                launch, = [s.attrs for s in c.last_trace.spans
                           if s.name == "launch"]
                assert launch["deferred"] == 4 and launch["segsum"] == segsum
                gap = compare.answer_gap(query, frame_answer(frame),
                                         reference.answer(params))
                limit = query["limits"]["rel_err"] if segsum == "scatter" \
                    else 1e-6
                assert gap is not None and gap <= limit, gap
        assert c.metrics.counter("join.compact.overflow") - before \
            == 2 * overflow


Q9 = traffic.load("queries", "tpch_q9_green")["sql"]


@pytest.mark.parametrize("predicate, shape", [
    ("p_name LIKE '%green%'", "p_name LIKE '%blue%'"),
    ("p_name LIKE 'forest%'", "p_name LIKE '%blue%'"),
    ("p_name LIKE '%green'", "p_name LIKE '%blue%'"),
    ("p_name LIKE '%gr_en%'", "p_name LIKE '%blue%'"),
    ("p_name LIKE 'b___ %'", "p_name LIKE '%blue%'"),
    ("p_name LIKE '%gr!een%' ESCAPE '!'", "p_name LIKE '%bl!ue%' ESCAPE '!'"),
    ("p_name NOT LIKE '%green%'", "p_name NOT LIKE '%blue%'"),
    ("p_name LIKE '%no such word%'", "p_name LIKE '%blue%'"),
    ("p_name = 'no such name'", "p_name = 'blue'"),
])
def test_like_mask_equals_the_baked_route(q9, predicate, shape,
                                         monkeypatch):
    """A pattern through the runtime mask answers as the same pattern baked
    into its own program (the parameter pass off), on the executable
    another pattern of its shape (`shape`) built; one that matches nothing
    gives an empty answer from it too.  ``=`` takes the mask past
    `_CODE_LOOKUP_ENTRIES` entries (4,166 names here: the cap is lowered)."""
    from dask_sql_tpu.families import parameterize

    monkeypatch.setattr(parameterize, "_CODE_LOOKUP_ENTRIES", 1_000)
    c, _, _ = q9
    c.sql(Q9.replace("p_name LIKE '%green%'", shape)).compute()
    sql = Q9.replace("p_name LIKE '%green%'", predicate)
    masked = c.sql(sql).compute()
    names = span_names(c)
    assert RUNG in names and "join:like" in names, names
    assert not compiled_here(names), names
    with config_module.set({"families.enabled": False}):
        baked = c.sql(sql).compute()
        assert "join:like" not in span_names(c)
    if "no such" in predicate:
        assert len(masked) == 0
    pd.testing.assert_frame_equal(masked.reset_index(drop=True),
                                  baked.reset_index(drop=True),
                                  check_exact=False, rtol=1e-12)


# --------------------------------------------------- the two-column key
def small(dims: pd.DataFrame, seed: int = 0, rows: int = 400,
          nulls: bool = False) -> Context:
    """A fact table `f` whose (a, b) pairs are drawn from `dims`' (x, y)
    and beyond them, over build side `d`; both as pyarrow tables, so a
    NULL key stays a BIGINT with a validity."""
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    xs = np.concatenate([dims.x.dropna().to_numpy(), [0, 9_999]])
    ys = np.concatenate([dims.y.dropna().to_numpy(), [-1, 7_777]])
    a, b = (rng.choice(v, rows).astype(np.int64) for v in (xs, ys))
    missing = [rng.random(rows) < (0.1 if nulls else 0.0) for _ in "ab"]
    c = Context()
    c.create_table("f", pa.table({
        "a": pa.array(a, mask=missing[0]), "b": pa.array(b, mask=missing[1]),
        "g": rng.integers(0, 5, rows), "v": rng.integers(1, 100, rows)
        .astype(np.float64)}))
    c.create_table("d", pa.Table.from_pandas(dims, preserve_index=False))
    return c


SMALL_SQL = ("SELECT g, SUM(v * w) AS s, COUNT(*) AS n FROM f JOIN d "
             "ON f.a = d.x AND f.b = d.y GROUP BY g ORDER BY g")


def answered(c, sql=SMALL_SQL):
    frame = c.sql(sql).compute()
    names = span_names(c)
    with config_module.set({"sql.compile.join_pipeline": False}):
        eager = c.sql(sql).compute()
    pd.testing.assert_frame_equal(frame.reset_index(drop=True),
                                  eager.reset_index(drop=True),
                                  check_dtype=False)
    return frame, names


def runs(lengths, seed=0):
    """A build side whose leading key x has runs of `lengths` rows, each
    row a distinct y, its rows shuffled."""
    rng = np.random.default_rng(seed)
    x = np.repeat(np.arange(1, len(lengths) + 1) * 3, lengths)
    y = np.concatenate([rng.choice(500, n, replace=False) for n in lengths])
    dims = pd.DataFrame({"x": x, "y": y, "w": rng.integers(1, 9, len(x))})
    return dims.sample(frac=1, random_state=seed).reset_index(drop=True)


@pytest.mark.parametrize("lengths, nulls", [
    ([1, 2, 3, 4], False),                  # runs of 1..R, R = 4
    ([4, 1, 0, 3, 2], False),               # a leading key with no run
    ([1] * 7 + [16], False),                # the longest run admitted
    ([3, 2, 1], True),                      # NULL keys on the probe
])
def test_composite_join_equals_the_interpreted_converters(lengths, nulls):
    dims = runs(lengths)
    c = small(dims, nulls=nulls)
    _, names = answered(c)
    assert RUNG in names and "join:composite" in names, names
    assert c.metrics.counter("join.build.composite") == 1


def test_composite_join_leaves_out_null_build_keys():
    dims = runs([2, 3, 1]).astype({"y": "Int64"})
    dims.loc[[0, 3], "y"] = pd.NA
    c = small(dims)
    _, names = answered(c)
    assert RUNG in names, names


def test_composite_join_with_a_filter_on_its_side():
    """The side's own filter folds into its slots."""
    c = small(runs([2, 4, 1, 3]))
    sql = SMALL_SQL.replace("GROUP BY", "WHERE d.w > 3 GROUP BY")
    _, names = answered(c, sql)
    assert RUNG in names, names


@pytest.mark.parametrize("dims", [
    # a duplicate pair
    pd.DataFrame({"x": [1, 1, 2], "y": [5, 5, 6], "w": [1, 2, 3]}),
    # both columns' runs past the bound: a 17 x 17 grid
    pd.DataFrame({"x": np.repeat(np.arange(17), 17),
                  "y": np.tile(np.arange(17), 17), "w": 1}),
], ids=["duplicate pair", "run past the bound"])
def test_composite_join_declines(dims):
    c = small(dims)
    _, names = answered(c)
    assert RUNG not in names, names
    assert c.metrics.counter("join.build.composite") == 0


def test_three_column_key_declines():
    dims = runs([2, 2, 3])
    dims["z"] = dims.y % 3
    c = small(dims)
    c.create_table("f", c.sql("SELECT a, b, g, v, b % 3 AS c FROM f")
                   .compute())
    sql = SMALL_SQL.replace("AND f.b = d.y", "AND f.b = d.y AND f.c = d.z")
    _, names = answered(c, sql)
    assert RUNG not in names, names


def test_composite_slots_pick_the_shorter_run():
    """x has runs of 8 and y of 2: y leads, R = 2."""
    x = np.repeat(np.arange(2), 8)
    y = np.tile(np.arange(8), 2)
    got = composite_slots([(x, None), (y, None)], 1 << 20)
    assert got["lead"] == 1 and got["run"] == 2
    assert sorted(got["second"][got["second"] >= 0]) == sorted(x)
    assert COMPOSITE_MAX_RUN == 16
