"""Direct kernel tests (parity: reference tests/unit/test_call.py — exercising
the op layer without SQL)."""
import numpy as np
import pandas as pd
import pytest

import jax.numpy as jnp


def _col(arr, mask=None):
    from dask_sql_tpu.columnar import Column

    return Column.from_numpy(np.asarray(arr), mask)


class TestGrouping:
    def test_factorize_matches_pandas(self):
        from dask_sql_tpu.ops.grouping import factorize, key_arrays

        keys = np.array([3, 1, 3, 2, 1, 3])
        gid, order, num = factorize(key_arrays([_col(keys)]))
        assert num == 3
        # same partition structure as pandas
        expected = pd.Series(keys).groupby(keys).ngroup()
        codes = np.asarray(gid)
        mapping = {}
        for c, e in zip(codes, pd.factorize(np.sort(np.unique(keys)))[0][np.searchsorted(np.sort(np.unique(keys)), keys)]):
            mapping.setdefault(c, e)
        assert len(set(codes)) == 3

    def test_segment_sum_null_skip(self):
        from dask_sql_tpu.ops.grouping import seg_count, seg_sum

        vals = jnp.asarray([1.0, 2.0, 3.0, 4.0])
        valid = jnp.asarray([True, False, True, True])
        gid = jnp.asarray([0, 0, 1, 1])
        s, ok = seg_sum(vals, valid, gid, 2)
        assert list(np.asarray(s)) == [1.0, 7.0]
        assert list(np.asarray(seg_count(valid, gid, 2))) == [1, 2]

    def test_seg_var_matches_numpy(self):
        from dask_sql_tpu.ops.grouping import seg_var

        rng = np.random.RandomState(0)
        vals = rng.rand(100)
        gid = jnp.asarray(np.repeat([0, 1], 50))
        v, ok = seg_var(jnp.asarray(vals), jnp.ones(100, dtype=bool), gid, 2, 1)
        np.testing.assert_allclose(np.asarray(v), [vals[:50].var(ddof=1), vals[50:].var(ddof=1)], rtol=1e-9)

    def test_radix_gid_int_keys(self):
        from dask_sql_tpu.ops.grouping import radix_gid

        col = _col(np.array([10, 12, 10, 11], dtype=np.int64))
        out = radix_gid([col])
        assert out is not None
        gid, domain, decode = out
        assert domain == 4  # span 3 + null slot
        decoded = decode(jnp.asarray([0, 1, 2]))[0]
        assert list(np.asarray(decoded.data)) == [10, 11, 12]


class TestJoinKernels:
    def test_inner_indices(self):
        from dask_sql_tpu.ops.join import inner_join_indices, join_key_gids

        l = _col(np.array([1, 2, 3, 2], dtype=np.int64))
        r = _col(np.array([2, 2, 4], dtype=np.int64))
        lg, rg = join_key_gids([l], [r])
        li, ri = inner_join_indices(lg, rg)
        pairs = sorted(zip(np.asarray(li).tolist(), np.asarray(ri).tolist()))
        assert pairs == [(1, 0), (1, 1), (3, 0), (3, 1)]

    def test_left_indices_pad(self):
        from dask_sql_tpu.ops.join import join_key_gids, left_join_indices

        l = _col(np.array([1, 5], dtype=np.int64))
        r = _col(np.array([1], dtype=np.int64))
        lg, rg = join_key_gids([l], [r])
        li, ri = left_join_indices(lg, rg)
        assert np.asarray(li).tolist() == [0, 1]
        assert np.asarray(ri).tolist() == [0, -1]

    def test_null_keys_never_match(self):
        from dask_sql_tpu.ops.join import inner_join_indices, join_key_gids

        l = _col(np.array([1.0, np.nan]))
        r = _col(np.array([1.0, np.nan]))
        lg, rg = join_key_gids([l], [r])
        li, ri = inner_join_indices(lg, rg)
        assert np.asarray(li).tolist() == [0]

    def test_string_keys_merge_dicts(self):
        from dask_sql_tpu.ops.join import inner_join_indices, join_key_gids

        l = _col(np.array(["a", "b", "c"], dtype=object))
        r = _col(np.array(["c", "a"], dtype=object))
        lg, rg = join_key_gids([l], [r])
        li, ri = inner_join_indices(lg, rg)
        got = sorted(zip(np.asarray(li).tolist(), np.asarray(ri).tolist()))
        assert got == [(0, 1), (2, 0)]


class TestDatetimeKernels:
    def test_extract_fields(self):
        from dask_sql_tpu.ops import datetime as dt

        ts = pd.date_range("1999-12-28", periods=10, freq="37h")
        ns = jnp.asarray(np.asarray(ts, dtype="datetime64[ns]").view(np.int64))
        for unit, expect in [
            ("year", ts.year), ("month", ts.month), ("day", ts.day),
            ("hour", ts.hour), ("minute", ts.minute), ("second", ts.second),
            ("quarter", ts.quarter), ("doy", ts.dayofyear),
        ]:
            got = np.asarray(dt.extract(unit, ns))
            assert list(got) == list(expect), unit

    def test_iso_week(self):
        from dask_sql_tpu.ops import datetime as dt

        ts = pd.to_datetime(["2020-01-01", "2021-01-01", "2015-12-31", "2016-01-04"])
        got = np.asarray(dt.extract("week", jnp.asarray(np.asarray(ts, dtype="datetime64[ns]").view(np.int64))))
        expected = ts.isocalendar().week.to_numpy()
        assert list(got) == list(expected)

    def test_truncate_and_ceil(self):
        from dask_sql_tpu.ops import datetime as dt

        ts = pd.to_datetime(["2020-03-15 13:45:10", "2020-01-01 00:00:00"])
        ns = jnp.asarray(np.asarray(ts, dtype="datetime64[ns]").view(np.int64))
        got_m = pd.to_datetime(np.asarray(dt.truncate("MONTH", ns)))
        assert list(got_m) == list(ts.to_period("M").start_time)
        got_c = pd.to_datetime(np.asarray(dt.ceil_to("DAY", ns)))
        assert list(got_c) == list(ts.ceil("D"))

    def test_add_months_clamps(self):
        from dask_sql_tpu.ops import datetime as dt

        ts = pd.to_datetime(["2020-01-31"])
        out = pd.to_datetime(np.asarray(dt.add_months(jnp.asarray(np.asarray(ts, dtype="datetime64[ns]").view(np.int64)), 1)))
        assert out[0] == pd.Timestamp("2020-02-29")

    def test_timestampdiff(self):
        from dask_sql_tpu.ops import datetime as dt

        a = jnp.asarray(np.asarray(pd.to_datetime(["2020-01-31"]), dtype="datetime64[ns]").view(np.int64))
        b = jnp.asarray(np.asarray(pd.to_datetime(["2020-03-01"]), dtype="datetime64[ns]").view(np.int64))
        assert int(np.asarray(dt.timestampdiff("MONTH", a, b))[0]) == 1


class TestStringsKernels:
    def test_like_regex(self):
        from dask_sql_tpu.ops.strings import like_to_regex

        assert like_to_regex("a%b_c") == "^a.*b.c$"
        assert like_to_regex("50%%", escape=None) == "^50.*.*$"
        assert like_to_regex(r"50\%", escape="\\") == "^50%$"

    def test_map_unary_dictionary_only(self):
        from dask_sql_tpu.ops.strings import map_unary

        col = _col(np.array(["aa", "bb", "aa"], dtype=object))
        out = map_unary(col, str.upper)
        assert list(out.to_numpy()) == ["AA", "BB", "AA"]
        assert len(out.dictionary) == 2  # transformed uniques only

    def test_binary_string_op_pairs(self):
        from dask_sql_tpu.ops.strings import binary_string_op

        a = _col(np.array(["x", "y", "x"], dtype=object))
        b = _col(np.array(["1", "1", "2"], dtype=object))
        out = binary_string_op(a, b, lambda p, q: p + q)
        assert list(out.to_numpy()) == ["x1", "y1", "x2"]


class TestSortKernels:
    def test_sort_permutation_mixed(self):
        from dask_sql_tpu.ops.sorting import sort_permutation

        a = _col(np.array([1, 1, 2, 2]))
        b = _col(np.array([9.0, 1.0, 8.0, 2.0]))
        perm = sort_permutation([a, b], [True, False], [False, False])
        assert np.asarray(perm).tolist() == [0, 1, 2, 3]
        perm = sort_permutation([a, b], [True, True], [False, False])
        assert np.asarray(perm).tolist() == [1, 0, 3, 2]

    def test_topk(self):
        from dask_sql_tpu.ops.sorting import topk_permutation

        col = _col(np.array([5.0, 1.0, 9.0, 3.0]))
        idx = topk_permutation(col, ascending=True, k=2)
        assert sorted(np.asarray(idx).tolist()) == [1, 3]


class TestCompaction:
    """`physical/compiled.py::compact_positions`, the join rung's
    fixed-capacity compaction, alone: the first `cap` True positions in row
    order, under jit, whatever the mask holds."""

    ROWS, BLOCK = 100_000, 32_768

    @pytest.mark.parametrize("case, cap", [
        ("all_false", 4096), ("all_true", 4096), ("cap_larger_than_rows",
                                                  131_072),
        ("true_only_in_last_block", 4096), ("sparse", 4096),
        ("exactly_cap", 4096), ("one_over_cap", 4096)])
    def test_compact_positions(self, case, cap):
        import jax

        from dask_sql_tpu.physical.compiled import compact_positions

        rng = np.random.default_rng(11)
        mask = np.zeros(self.ROWS, dtype=bool)
        if case in ("all_true", "cap_larger_than_rows"):
            mask[:] = True
        elif case == "true_only_in_last_block":
            mask[3 * self.BLOCK + 17::5] = True
        elif case == "sparse":
            mask = rng.random(self.ROWS) < 0.02
        elif case in ("exactly_cap", "one_over_cap"):
            picks = cap + (case == "one_over_cap")
            mask[rng.choice(self.ROWS, picks, replace=False)] = True
        got = np.asarray(jax.jit(compact_positions, static_argnums=1)(
            jnp.asarray(mask), cap))
        want = np.nonzero(mask)[0]
        assert got.shape == (cap,) and got.dtype == np.int32
        kept = min(len(want), cap)
        # order preserved: ascending positions, the first `cap` of them
        assert np.array_equal(got[:kept], want[:kept])
        # the rest is a position a gather may read (the caller masks it)
        assert ((got[kept:] >= 0) & (got[kept:] < self.ROWS)).all()
