"""TPC-H Q13 through `compiled_join_aggregate` (physical/compiled_join.py):
``customer LEFT OUTER JOIN orders`` with ORDERS the probe and CUSTOMER a
build side kept whole, every row of which is a group (`_walk_outer`),
``o_comment NOT LIKE '%WORD1%WORD2%'`` of the ON clause bound as a runtime
mask over the probe column's dictionary (`families/parameterize.py::
_string_mask`, ``probe=True``), and the outer ``GROUP BY c_count`` reduced
in the same program over the inner's groups (`_nested_shape`,
`_reduce_nested`).

Tables come from the benchmark's own generator (`perfbench/datagen/
tpch_q13_tables.py`) at 20,000 orders on SF10's key domains, the texts from
the cell's sixteen query files; the answers are held to the plain reference
(`perfbench/references/tpch_q13_counts.py`) and to a pandas oracle, the
one of ``tests/unit/test_queries.py::test_q13_values``.  The edge cases run
on small hand-made tables against the interpreted converters (the join
rung off).

Cost (ROADMAP D11): the module's tables under a second, one compile of
some 2 s for the sixteen files, a compile a case for the small tables.
"""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.physical import compiled_join as cj
from perfbench import compare, traffic
from perfbench.datagen import tpch_q13_tables
from perfbench.references import tpch_q13_counts
from perfbench.surfaces.library import frame_answer

ROWS = 20_000
FILES = [m["query"] for m in traffic.load("workloads",
                                          "sf10_q13_library")["mix"]]
RUNG = "rung:compiled_join_aggregate"
Q13 = traffic.load("queries", "tpch_q13_special_requests")["sql"]
ON = "o_comment NOT LIKE '%special%requests%'"
LADDER = ("resilience.degraded", "resilience.rung.cpu")


@pytest.fixture(scope="module", autouse=True)
def result_cache_off():
    """The cell's `engine_config`, for this module alone (see
    test_q3_tables.py)."""
    with config_module.set({"serving.cache.enabled": False}):
        yield


@pytest.fixture(scope="module")
def q13(result_cache_off):
    cj.PROGRAMS.clear()
    cj.LUTS.clear()
    arrays = tpch_q13_tables.generate(ROWS, seed=41, scale_factor=10)
    frames = tpch_q13_tables.arrow_tables(arrays)
    c = Context()
    for name in ("customer", "orders"):
        c.create_table(name, frames[name])
    return c, arrays, {name: frames[name].to_pandas() for name in frames}


def span_names(c):
    return [s.name for s in c.last_trace.spans]


def compiled_here(names):
    return [n for n in names if n.startswith(("compile:", "xla:compile"))]


def oracle(customer, orders, word1, word2):
    """``tests/unit/test_queries.py::test_q13_values``'s pandas answer."""
    ok = orders[~orders.o_comment.str.contains(f"{word1}.*{word2}",
                                               regex=True)]
    m = customer.merge(ok, left_on="c_custkey", right_on="o_custkey",
                       how="left")
    counts = m.groupby("c_custkey").o_orderkey.count()
    expected = (counts.value_counts().rename_axis("c_count")
                .reset_index(name="custdist")
                .sort_values(["custdist", "c_count"], ascending=[False, False])
                .reset_index(drop=True))
    return {"columns": ["c_count", "custdist"],
            "rows": [[int(a), int(b)] for a, b in
                     zip(expected.c_count, expected.custdist)]}


def test_sixteen_pairs_equal_the_reference_on_one_executable(q13):
    """Every file of the cell's mix: on the rung with the probe-side mask
    and no step down the ladder, equal to the reference and to the pandas
    oracle, and after the first no compile of any kind."""
    c, arrays, t = q13
    reference = tpch_q13_counts.Reference(arrays)
    before = {k: c.metrics.counter(k) for k in
              ("join.outer.preserved", "aggregate.nested") + LADDER}
    for i, name in enumerate(FILES):
        query = traffic.load("queries", name)
        params = {k: v["low"] for k, v in query["parameters"].items()}
        frame = c.sql(traffic.render(query, params)).compute()
        names = span_names(c)
        assert RUNG in names and "join:probe_like" in names, (name, names)
        if i:
            assert not compiled_here(names), (name, names)
        answer = frame_answer(frame)
        assert compare.answer_gap(query, answer,
                                  reference.answer(params)) == 0.0, name
        words = (tpch_q13_counts.WORD1[params["WORD1"]],
                 tpch_q13_counts.WORD2[params["WORD2"]])
        assert answer == oracle(t["customer"], t["orders"], *words), name
    moved = {k: c.metrics.counter(k) - v for k, v in before.items()}
    assert moved == {"join.outer.preserved": 16, "aggregate.nested": 16,
                     **{k: 0 for k in LADDER}}, moved
    launch, = [s.attrs for s in c.last_trace.spans if s.name == "launch"]
    assert launch["outer"] == 1 and launch["nested"] == 1


@pytest.mark.parametrize("segsum", ["scatter", "matmul"])
def test_either_segment_sum_gives_the_reference(q13, segsum):
    """The chip reduces the 1,025-slot histogram with the blocked matmul
    (`choose_segsum_impl`: a small domain on a TPU), the CPU with the
    scatter: both, forced here for the whole program, equal the reference
    to the count."""
    c, arrays, _ = q13
    reference = tpch_q13_counts.Reference(arrays)
    cj.PROGRAMS.clear()
    try:
        with config_module.set({"sql.compile.segsum": segsum}):
            for name in FILES[:3]:
                query = traffic.load("queries", name)
                params = {k: v["low"] for k, v in query["parameters"].items()}
                frame = c.sql(traffic.render(query, params)).compute()
                launch, = [s.attrs for s in c.last_trace.spans
                           if s.name == "launch"]
                assert launch["segsum"] == segsum
                assert compare.answer_gap(query, frame_answer(frame),
                                          reference.answer(params)) == 0.0
    finally:
        cj.PROGRAMS.clear()


@pytest.mark.parametrize("on", [ON, "o_comment LIKE '%special%'"])
def test_the_compaction_keeps_every_preserved_group(q13, on, monkeypatch):
    """Above the compaction's row floor (lowered here, as the cell's 15M
    orders are above it) an outer join's program still reduces the orders
    whole: its ON clause's share is the pattern's, and Q13's NOT LIKE
    passes nearly all, so a compacted branch would only add its compile.
    Whatever the pattern passes, every customer is a group."""
    c, _, _ = q13
    monkeypatch.setattr(cj, "_COMPACT_MIN_ROWS", 1 << 10)
    cj.PROGRAMS.clear()
    try:
        sql = Q13.replace(ON, on)
        frame = c.sql(sql).compute()
        tail, = [s.attrs for s in c.last_trace.spans if s.name == "join:tail"]
        assert "cap" not in tail and "passed" not in tail, tail
        assert frame.custdist.sum() == ROWS // 10
        with config_module.set({"sql.compile.join_pipeline": False}):
            eager = c.sql(sql).compute()
        pd.testing.assert_frame_equal(frame, eager, check_dtype=False)
    finally:
        cj.PROGRAMS.clear()


def test_customers_without_orders_are_the_zero_group(q13):
    """A third of the customers never order (cl.4.2.3): they, and those
    whose every order the ON clause drops, are the ``c_count = 0`` group;
    the patterns hold for a few comments, which moves some counts."""
    c, arrays, t = q13
    customers = len(t["customer"])
    frame = c.sql(Q13).compute()
    zero = frame[frame.c_count == 0].custdist
    assert len(zero) == 1 and zero.iloc[0] >= customers // 3
    assert frame.custdist.sum() == customers
    matched = tpch_q13_counts.Reference(arrays).matched("special", "requests")
    assert 0 < matched.sum() < len(matched) // 100
    assert frame.c_count.dot(frame.custdist) == len(matched) - matched.sum()


def small(orders, customers=6):
    c = Context()
    c.create_table("customer", pd.DataFrame(
        {"c_custkey": np.arange(1, customers + 1, dtype=np.int64),
         "c_nationkey": np.arange(customers, dtype=np.int64) % 3}))
    c.create_table("orders", pd.DataFrame(orders))
    return c


ORDERS = {"o_orderkey": np.arange(1, 9, dtype=np.int64),
          "o_custkey": np.array([1, 1, 2, 4, 4, 4, 5, 5], dtype=np.int64),
          "o_comment": ["x special y requests", "plain", "special requests",
                        "b", "special, requests", "c", "d", "e"],
          "o_totalprice": np.arange(8, dtype=np.float64) + 0.5}


def answered(c, sql, rung=True):
    """`sql` on the rung (or, with `rung` False, declined by it: no
    request counted as preserved), equal to the interpreted converters."""
    before = c.metrics.counter("join.outer.preserved")
    frame = c.sql(sql).compute()
    names = span_names(c)
    assert (c.metrics.counter("join.outer.preserved") > before) == rung, names
    with config_module.set({"sql.compile.join_pipeline": False}):
        eager = c.sql(sql).compute()
    pd.testing.assert_frame_equal(frame.reset_index(drop=True),
                                  eager.reset_index(drop=True),
                                  check_dtype=False)
    return frame


def test_a_customer_whose_orders_all_match_stays_in_the_zero_group():
    """Customer 2's one order matches: it is a group of 0 orders, beside 3
    and 6, which have none."""
    c = small(ORDERS)
    frame = answered(c, Q13)
    assert dict(zip(frame.c_count, frame.custdist)) == {0: 3, 1: 1, 2: 2}


def test_the_predicate_in_where_drops_the_groups_it_empties():
    """The same NOT LIKE in WHERE filters the joined rows: the customers it
    leaves with no row (and those without orders, whose NULL comment it
    rejects) are no group at all, where the ON clause keeps them at 0."""
    c = small(ORDERS)
    where = Q13.replace(f" AND {ON}", "").replace(
        " GROUP BY c_custkey", f" WHERE {ON} GROUP BY c_custkey")
    frame = c.sql(where).compute()
    with config_module.set({"sql.compile.join_pipeline": False}):
        eager = c.sql(where).compute()
    pd.testing.assert_frame_equal(frame, eager, check_dtype=False)
    assert dict(zip(frame.c_count, frame.custdist)) == {1: 1, 2: 2}
    on = answered(c, Q13)
    assert dict(zip(on.c_count, on.custdist)) == {0: 3, 1: 1, 2: 2}


@pytest.mark.parametrize("pattern, expected", [
    ("%no such word%", {0: 2, 1: 1, 2: 2, 3: 1}),
    ("%", {0: 6}),
    ("%special%", {0: 3, 1: 1, 2: 2}),
])
def test_patterns_that_match_nothing_or_everything(pattern, expected):
    c = small(ORDERS)
    frame = answered(c, Q13.replace("%special%requests%", pattern))
    assert dict(zip(frame.c_count, frame.custdist)) == expected


def test_a_null_comment_fails_the_on_clause():
    """``NULL NOT LIKE p`` is NULL: the order joins no customer, who keeps
    a group (with the orders the clause passes)."""
    import pyarrow as pa

    orders = dict(ORDERS, o_comment=pa.array(
        [None, "plain", None, "b", None, "c", "d", "e"]))
    c = Context()
    c.create_table("customer", pd.DataFrame(
        {"c_custkey": np.arange(1, 7, dtype=np.int64)}))
    c.create_table("orders", pa.table(orders))
    frame = answered(c, Q13)
    assert dict(zip(frame.c_count, frame.custdist)) == {0: 3, 1: 1, 2: 2}


def test_count_star_and_sum_see_the_null_extended_row():
    """Over the LEFT join a customer no order reaches has ONE row, all of
    ORDERS' columns NULL: ``COUNT(*)`` 1, ``COUNT(o_orderkey)`` 0, SUM, MIN,
    MAX and AVG NULL."""
    c = small(ORDERS)
    frame = answered(c, (
        "SELECT c_custkey, COUNT(*) AS n, COUNT(o_orderkey) AS k, "
        "SUM(o_totalprice) AS s, MIN(o_totalprice) AS lo, "
        "MAX(o_totalprice) AS hi, AVG(o_totalprice) AS mean FROM customer "
        f"LEFT JOIN orders ON c_custkey = o_custkey AND {ON} "
        "GROUP BY c_custkey ORDER BY c_custkey"))
    empty = frame[frame.c_custkey.isin([2, 3, 6])]
    assert (empty.n == 1).all() and (empty.k == 0).all()
    assert empty[["s", "lo", "hi", "mean"]].isna().all().all()
    assert list(frame.n) == [1, 1, 1, 2, 2, 1] and frame.s[0] == 1.5


def test_the_right_join_mirror():
    c = small(ORDERS)
    mirror = Q13.replace("customer LEFT OUTER JOIN orders",
                         "orders RIGHT OUTER JOIN customer")
    frame = answered(c, mirror)
    assert dict(zip(frame.c_count, frame.custdist)) == {0: 3, 1: 1, 2: 2}


def test_order_by_limit_over_the_preserved_groups():
    """The top-k tail inside the program orders every preserved group, the
    ones no order reached (a count of 0) first."""
    c = small(ORDERS)
    frame = answered(c, (
        "SELECT c_custkey, COUNT(o_orderkey) AS k FROM customer LEFT JOIN "
        f"orders ON c_custkey = o_custkey AND {ON} GROUP BY c_custkey "
        "ORDER BY k, c_custkey LIMIT 4"))
    assert list(frame.c_custkey) == [2, 3, 6, 1]


def test_a_filter_on_the_preserved_side_leaves_its_rows_out():
    """A WHERE on CUSTOMER's own column (pushed into its scan) drops those
    customers before the join: the rest are groups, reached or not."""
    c = small(ORDERS)
    frame = answered(c, Q13.replace(
        " GROUP BY c_custkey", " WHERE c_nationkey <> 1 GROUP BY c_custkey"))
    assert frame.custdist.sum() == 4


@pytest.mark.parametrize("sql", [
    # an ON conjunct over the preserved side
    Q13.replace(ON, f"{ON} AND c_custkey > 2"),
    # a residual over both sides
    Q13.replace(ON, "o_orderkey > c_custkey"),
    # FULL OUTER
    Q13.replace("LEFT OUTER", "FULL OUTER"),
    # a group key on the side the join may drop
    ("SELECT o_custkey, COUNT(*) AS n FROM customer LEFT JOIN orders "
     "ON c_custkey = o_custkey GROUP BY o_custkey ORDER BY o_custkey"),
    # an aggregate of the preserved side's column
    ("SELECT c_custkey, SUM(c_nationkey) AS s FROM customer LEFT JOIN orders "
     "ON c_custkey = o_custkey GROUP BY c_custkey ORDER BY c_custkey"),
    # an aggregate of an expression a NULL-extended row need not leave NULL
    ("SELECT c_custkey, SUM(COALESCE(o_totalprice, 1)) AS s FROM customer "
     "LEFT JOIN orders ON c_custkey = o_custkey GROUP BY c_custkey "
     "ORDER BY c_custkey"),
    # two outer joins in a chain
    ("SELECT c_custkey, COUNT(o_orderkey) AS k, COUNT(n.c) AS m FROM "
     "customer LEFT JOIN orders ON c_custkey = o_custkey LEFT JOIN "
     "(SELECT c_custkey AS c FROM customer) AS n ON n.c = o_custkey "
     "GROUP BY c_custkey ORDER BY c_custkey"),
])
def test_shapes_the_rules_decline_keep_todays_path(sql):
    c = small(ORDERS)
    answered(c, sql, rung=False)


def test_a_preserved_side_not_unique_on_its_key_declines():
    c = small(ORDERS)
    c.create_table("customer", pd.DataFrame(
        {"c_custkey": np.array([1, 1, 2, 3], dtype=np.int64)}))
    answered(c, Q13, rung=False)


def test_a_key_range_past_the_histogram_takes_the_exact_branch(
        q13, monkeypatch):
    """With the histogram cut to 4 values (the counts span more) the same
    executable's other branch sorts the key: the answer is the same, and
    ``join:tail`` says it overflowed."""
    c, arrays, _ = q13
    monkeypatch.setattr(cj, "_NESTED_CAP", 4)
    cj.PROGRAMS.clear()
    try:
        reference = tpch_q13_counts.Reference(arrays)
        for name in FILES[:2]:
            query = traffic.load("queries", name)
            params = {k: v["low"] for k, v in query["parameters"].items()}
            frame = c.sql(traffic.render(query, params)).compute()
            tail, = [s.attrs for s in c.last_trace.spans
                     if s.name == "join:tail"]
            assert tail["overflow"] is True
            assert compare.answer_gap(query, frame_answer(frame),
                                      reference.answer(params)) == 0.0
    finally:
        cj.PROGRAMS.clear()


@pytest.mark.parametrize("outer, key", [
    ("n, COUNT(*) AS c, SUM(s) AS t, MIN(s) AS lo, MAX(k) AS hi", "n"),
    ("s, COUNT(n) AS c, SUM(n) AS t", "s"),
    ("k, COUNT(*) AS c", "k"),
])
def test_group_by_of_a_group_by_over_an_inner_join(outer, key):
    """The outer aggregate over an INNER join's groups (only the groups a
    probe row reached are present), keys and arguments among the inner's
    outputs; ``SUM(o_x)`` is NULL for customer 4, whose o_x are all NULL,
    and that NULL is a group of its own."""
    import pyarrow as pa

    c = Context()
    c.create_table("customer", pd.DataFrame(
        {"c_custkey": np.arange(1, 7, dtype=np.int64)}))
    c.create_table("orders", pa.table(dict(ORDERS, o_x=pa.array(
        [1, None, 2, None, None, None, 3, None], pa.int64()))))
    sql = (f"SELECT {outer} FROM (SELECT o_custkey, COUNT(*) AS n, "
           "COUNT(o_x) AS k, SUM(o_x) AS s FROM orders JOIN customer "
           "ON c_custkey = o_custkey GROUP BY o_custkey) AS g "
           f"GROUP BY {key} ORDER BY {key}")
    before = c.metrics.counter("aggregate.nested")
    frame = c.sql(sql).compute()
    assert c.metrics.counter("aggregate.nested") == before + 1
    with config_module.set({"sql.compile.join_pipeline": False}):
        eager = c.sql(sql).compute()
    pd.testing.assert_frame_equal(frame, eager, check_dtype=False)


def test_q3_and_q18_keep_their_inner_paths():
    """Two of the cells that share the rung (Q9's is held so in
    test_q9_tables.py): their programs take no outer join and no nested
    aggregate."""
    from perfbench.datagen import tpch_q3_tables

    arrays = tpch_q3_tables.generate(20_000, seed=41, scale_factor=10)
    frames = tpch_q3_tables.arrow_tables(arrays)
    c = Context()
    for name in ("customer", "orders", "lineitem"):
        c.create_table(name, frames[name])
    for name in ("tpch_q3_building", "tpch_q18"):
        query = traffic.load("queries", name)
        params = {k: v["low"] for k, v in query["parameters"].items()}
        c.sql(traffic.render(query, params)).compute()
        launch, = [s.attrs for s in c.last_trace.spans if s.name == "launch"]
        assert "outer" not in launch and "nested" not in launch, name
    assert c.metrics.counter("join.outer.preserved") == 0
    assert c.metrics.counter("aggregate.nested") == 0


@pytest.mark.parametrize("keys", [
    np.arange(1_000, dtype=np.int64) * (1 << 40),      # low bits all equal
    np.repeat(np.arange(-50, 50, dtype=np.int64), 7),  # runs, negatives
    np.random.default_rng(3).integers(-2**62, 2**62, 3_000),
    np.random.default_rng(4).integers(0, 9, 500).astype(np.int32),
])
def test_hash_slots_give_each_distinct_key_one_slot(keys):
    """The exact branch's table: rows of one key share a slot, rows of two
    keys never do, each used slot holds its key; unvalued rows take none."""
    import jax.numpy as jnp

    valued = np.random.default_rng(5).random(len(keys)) < 0.9
    table = 1 << (2 * len(keys) - 1).bit_length()
    gid, used, held = (np.asarray(a) for a in cj._hash_slots(
        jnp.asarray(keys), jnp.asarray(valued), table))
    at, key = gid[valued], keys[valued]
    assert len(set(zip(at, key))) == len(set(key)) == len(set(at))
    assert used[at].all() and (held[at] == key).all()
    assert used.sum() == len(set(key))


def test_64_bit_words_survive_the_pack():
    """`pack_flat` carries a 64-bit integer as two 32-bit words, whole
    numbers that a pair of float32 (the TPU's float64) holds: bitcast into a
    float64, a small integer's bits are a denormal, which the chip flushes
    to 0.  `unpack_row` puts the words back; the host's pack (``xp=np``,
    the streamed aggregate's) is the device's."""
    import jax.numpy as jnp

    from dask_sql_tpu.physical.compiled import pack_flat, unpack_row

    values = np.array([-2**63, -2**40 - 7, -1, 0, 5, 2**32 + 1, 2**63 - 1])
    unsigned = np.array([0, 1, 5, 2**32 - 1, 2**32, 2**63, 2**64 - 1],
                        dtype=np.uint64)
    flat = [values != 0, values, np.linspace(-1, 1, 7), unsigned,
            values.astype(np.int32)]
    tags = []
    host = np.asarray(pack_flat([jnp.asarray(x) for x in flat], tags))
    assert [k for k, _ in tags] == ["as", "words", "as", "words", "as"]
    assert host.shape == (7, 7)
    words = host[[1, 3, 5, 6]]
    assert (words == np.trunc(words)).all()
    assert np.abs(words).max() < 2.0**32
    for i, x in enumerate(flat):
        back = unpack_row(host, i, tags)
        assert back.dtype == x.dtype and np.array_equal(back, x), i
    on_host = []
    assert np.array_equal(pack_flat(flat, on_host, xp=np), host)
    assert on_host == tags


def test_the_q13_pack_carries_its_bigint_columns_as_words(q13, monkeypatch):
    """The Q13 program's pack: the key (an inner COUNT) and custdist are
    BIGINT, so both ride as words, and no row of the pack is a bitcast."""
    import jax.numpy as jnp

    from dask_sql_tpu.physical import compiled

    c, _, _ = q13
    seen = []
    pack_flat = compiled.pack_flat

    def spy(flat, tags, xp=jnp):
        out = pack_flat(flat, tags, xp)
        seen.append(list(tags))
        return out

    monkeypatch.setattr(compiled, "pack_flat", spy)
    cj.PROGRAMS.clear()
    try:
        c.sql(Q13).compute()
    finally:
        cj.PROGRAMS.clear()
    assert seen and all(tags == seen[0] for tags in seen)
    assert [(k, np.dtype(dt).name) for k, dt in seen[0]] == [
        ("as", "bool"), ("words", "int64"), ("as", "bool"),
        ("words", "int64"), ("as", "bool")]
