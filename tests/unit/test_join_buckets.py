"""The join rung's bucketed shapes (`ops/join.py::bucket_rows`): a build
side's rows, its LUT's slots and a semi-join's key range reach XLA rounded
up to a bucket, so two table versions whose sizes fall in one bucket lower
to the SAME program text (the persistent compile cache keys on it), and a
version past the bucket's edge to another.

The program tests register TPC-H's CUSTOMER, ORDERS and LINEITEM from the
benchmark's generator (`perfbench/datagen/tpch_q3_tables.py`) at 50,000
lineitems and grow ORDERS by orders that no line references: new keys past
the highest, every other column copied from existing orders, so each
column keeps its encoding and only ORDERS' row count and key range move.
Cost: the tables 1.5 s once, two lowerings of 0.3 s a program and table.
"""
import jax
import numpy as np
import pyarrow as pa
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu import config as config_module
from dask_sql_tpu.ops import join as join_ops
from dask_sql_tpu.ops.join import bucket_rows, pad_rows
from dask_sql_tpu.physical import compiled_join as cj
from perfbench import traffic
from perfbench.datagen import tpch_q3_tables

ROWS = 50_000


@pytest.mark.parametrize("n, bucket", [
    (0, 0), (1, 1), (2, 2), (127, 127), (128, 128), (129, 130),
    (255, 256), (256, 256), (257, 260), (1 << 20, 1 << 20),
    ((1 << 20) + 1, (1 << 20) + (1 << 14)),
    # the cells' sizes: ORDERS' rows over seeds, the order keys' ranges,
    # CUSTOMER's rows (one bucket each)
    (5_999_674, 6_029_312), (6_002_069, 6_029_312),
    (24_000_616, 24_117_248), (24_008_261, 24_117_248),
    (1_500_000, 1_507_328)])
def test_bucket_rows_at_its_edges(n, bucket):
    """Up to a multiple of 1/128 of the power of two at or above `n`: a
    power of two and anything at or under 128 stay, one over a power of two
    goes to the next step, and no bucket is 1/64 of its `n` past it."""
    assert bucket_rows(n) == bucket
    assert bucket_rows(bucket) == bucket
    assert bucket - n <= n / 64
    if n > 128:
        step = 1 << ((n - 1).bit_length() - 7)
        assert bucket % step == 0 and bucket - n < step


def lut_build_text(keys: np.ndarray) -> str:
    """The lowered text of the kept-LUT build over `keys`, as
    `dense_unique_lut` runs it on a whole build side's padded key column:
    the bound pass, then the scatter into the range's bucket."""
    n = len(keys)
    k = pad_rows(jax.device_put(keys), bucket_rows(n))
    rmin, rmax = int(keys.min()), int(keys.max())
    slots = bucket_rows(rmax - rmin + 1)
    return (join_ops._key_bounds.lower(k, None, n).as_text()
            + join_ops._scatter_lut.lower(k, None, n, rmin,
                                          slots=slots).as_text())


def sparse_keys(n, first=1):
    index = np.arange(n, dtype=np.int64)
    return (index // 8) * 32 + index % 8 + first  # clause 4.2.3


@pytest.mark.parametrize("where", ["within_a_bucket", "across_a_bucket_edge"])
def test_lut_build_text_by_bucket(where):
    """12,370 keys over a range of 49,474 (bucket 12,416 rows, 49,664
    slots) against 37 more keys, and another lowest key: the same text;
    60 more cross the rows' edge: another text."""
    keys = sparse_keys(12_370)
    text = lut_build_text(keys)
    if where == "within_a_bucket":
        assert text == lut_build_text(sparse_keys(12_370 + 37))
        assert text == lut_build_text(sparse_keys(12_370, first=1_000))
    else:
        assert text != lut_build_text(sparse_keys(12_370 + 60))


def test_lut_of_padded_keys_ignores_the_pad_rows():
    """A key column padded by repeats of its last row: the pad rows hold
    a real key, yet neither the duplicate test nor the LUT sees them; the
    slots past the range hold no row."""
    keys = sparse_keys(1_000)
    padded = pad_rows(jax.device_put(keys), bucket_rows(1_000) + 8)
    assert np.asarray(padded)[-1] == keys[-1]  # the pad collides
    rmin, lut = join_ops.dense_unique_lut(padded, max_bytes=1 << 20,
                                          rows=1_000)
    lut = np.asarray(lut)
    assert rmin == 1 and len(lut) == bucket_rows(int(keys.max()))
    assert np.array_equal(lut[keys - 1], np.arange(1_000))
    assert (lut >= 0).sum() == 1_000 and (lut[int(keys.max()):] == -1).all()


@pytest.mark.parametrize("n, parts", [
    (bucket_rows(1_050_000), 65),   # 65 parts of 2^14: a loop
    (1_050_000, 1),                 # not a bucket's length: whole
    (bucket_rows(50_000), 1)])      # a step under 2^13: whole
def test_by_parts_folds_a_bucket_in_equal_parts(n, parts):
    """The LUT fold over a table of a bucket's length runs as a loop over
    parts of the bucket's step, with the answer of the whole fold."""
    rng = np.random.default_rng(0)
    keep = jax.numpy.asarray(rng.random(1_000) < 0.5)
    lut = jax.numpy.asarray(rng.integers(-1, 1_000, n).astype(np.int32))

    def fold(part):
        return jax.numpy.where(keep[jax.numpy.clip(part, 0, None)], part, -1)

    run = jax.jit(lambda x: join_ops.by_parts(fold, x))
    assert np.array_equal(np.asarray(run(lut)), np.asarray(fold(lut)))
    assert ("stablehlo.while" in run.lower(lut).as_text()) == (parts > 1)


# ------------------------------------------------------ the whole program
@pytest.fixture(scope="module")
def frames():
    with config_module.set({"serving.cache.enabled": False}):
        yield tpch_q3_tables.arrow_tables(
            tpch_q3_tables.generate(ROWS, seed=33, scale_factor=10))


def grown(orders: pa.Table, more: int) -> pa.Table:
    """ORDERS with `more` orders that no line references: keys past the
    highest, every other column copied from the first orders."""
    extra = orders.slice(0, more)
    keys = orders.column("o_orderkey").to_numpy()
    at = orders.schema.get_field_index("o_orderkey")
    extra = extra.set_column(at, "o_orderkey", pa.array(
        keys.max() + 1 + np.arange(more), type=orders.schema.field(at).type))
    return pa.concat_tables([orders, extra])


def program_text(frames, more: int, query: str) -> str:
    """The lowered text of the join rung's program for `query` with ORDERS
    grown by `more` orders."""
    texts = []
    run = cj.CompiledJoinAggregate.run

    def spy(self, params=()):
        texts.append(self._fn.lower(*self._run_args(params)).as_text())
        return run(self, params)

    cj.PROGRAMS.clear()
    cj.LUTS.clear()
    c = Context()
    for name in ("customer", "orders", "lineitem"):
        c.create_table(name, grown(frames[name], more)
                       if name == "orders" else frames[name])
    q = traffic.load("queries", query)
    params = {"QUANTITY": 250} if query == "tpch_q18" \
        else {"DAY": 12, "SEGMENT": 4}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cj.CompiledJoinAggregate, "run", spy)
        c.sql(traffic.render(q, params)).compute()
    (text,) = texts
    return text


@pytest.mark.parametrize("fold", ["whole", "in_parts"])
@pytest.mark.parametrize("query", ["tpch_q3_machinery", "tpch_q18"])
def test_program_text_by_bucket(frames, query, fold, monkeypatch):
    """Q3's and Q18's ONE program: ORDERS at 12,370 rows and at 12,407
    (bucket 12,416; the key range 49,474 and 49,511 in bucket 49,664) lower
    to one text, at 12,430 rows (bucket 12,544) to another; `in_parts`
    with the LUT folds as loops over parts (`by_parts`), as at the cells'
    sizes."""
    if fold == "in_parts":
        monkeypatch.setattr(join_ops, "_PART_FLOOR", 1 << 4)
    n = frames["orders"].num_rows
    assert bucket_rows(n) == bucket_rows(n + 37) < bucket_rows(n + 60)
    base = program_text(frames, 0, query)
    assert base == program_text(frames, 37, query)
    assert base != program_text(frames, 60, query)
