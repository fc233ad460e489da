"""Arrow <-> device Table conversion (zero-ish-copy ingest path).

Role parity: the reference's IO boundary is dask's `read_parquet` into pandas
partitions; ours is pyarrow -> numpy -> jax device buffers, with Arrow
dictionary arrays mapping directly onto our dictionary-encoded string columns.
"""
from __future__ import annotations

import numpy as np

from ..observability.spans import load_span
from .column import Column, to_device
from .dtypes import STRING_TYPES, SqlType
from .encodings import prime_dictionary_nbytes
from .table import Table


def arrow_to_table(at) -> Table:
    import pyarrow as pa
    import pyarrow.compute as pc

    cols = {}
    for name, col in zip(at.column_names, at.columns):
        # the registration's per-column span: the encode and h2d spans
        # opened below are cut out of it
        with load_span("convert", column=name):
            arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) \
                else col
            cols[name] = _arrow_array_to_column(arr)
    return Table(cols, at.num_rows)


def _arrow_array_to_column(arr) -> Column:
    import pyarrow as pa
    import pyarrow.compute as pc

    mask = None
    if arr.null_count:
        mask = np.asarray(pc.is_valid(arr))
    t = arr.type
    if pa.types.is_dictionary(t):
        codes = np.asarray(arr.indices.fill_null(0)).astype(np.int32)
        uniques = np.asarray(arr.dictionary.to_pylist(), dtype=object)
        if len(uniques) == 0:
            uniques = np.array([""], dtype=object)
        elif pa.types.is_string(t.value_type) \
                or pa.types.is_large_string(t.value_type):
            # characters, not bytes: `dictionary_nbytes`'s rule; a null
            # entry decodes to None, which that rule reads as "None"
            chars = pc.sum(pc.utf8_length(arr.dictionary)).as_py() or 0
            prime_dictionary_nbytes(
                uniques, chars + 4 * arr.dictionary.null_count)
        return Column(to_device(codes), SqlType.VARCHAR, _mask(mask), uniques)
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        with load_span("encode", encoding="STRING") as attrs:
            enc = pc.dictionary_encode(arr)
            attrs["distinct"] = len(enc.dictionary)
        return _arrow_array_to_column(enc)
    if pa.types.is_timestamp(t):
        ns = np.asarray(arr.cast(pa.timestamp("ns")).fill_null(0)).astype("datetime64[ns]").view(np.int64)
        return _build(ns, mask, SqlType.TIMESTAMP)
    if pa.types.is_date(t):
        ns = np.asarray(arr.cast(pa.timestamp("ns")).fill_null(0)).astype("datetime64[ns]").view(np.int64)
        return _build(ns, mask, SqlType.DATE)
    if pa.types.is_decimal(t):
        vals = np.asarray(arr.cast(pa.float64()).fill_null(0.0))
        return _build(vals, mask, SqlType.DECIMAL)
    if pa.types.is_boolean(t):
        vals = np.asarray(arr.fill_null(False))
        return Column(to_device(vals), SqlType.BOOLEAN, _mask(mask))
    vals = np.asarray(arr.fill_null(0)) if arr.null_count else np.asarray(arr)
    return Column.from_numpy(vals, mask)


def _mask(mask):
    if mask is None or mask.all():
        return None
    return to_device(mask)


def _build(vals, mask, sql_type) -> Column:
    """Device column from an already-device-repr host array; the load scope
    may pick a compressed encoding (columnar/encodings.py)."""
    from .encodings import maybe_encode

    col = maybe_encode(vals, mask, sql_type)
    if col is not None:
        return col
    return Column(to_device(vals), sql_type, _mask(mask))


def table_to_arrow(table: Table):
    import pyarrow as pa

    arrays, names = [], []
    for name, col in table.columns.items():
        names.append(name)
        if col.sql_type in STRING_TYPES:
            codes = np.asarray(col.data)
            d = col.dictionary if col.dictionary is not None else np.array([""], dtype=object)
            codes = np.clip(codes, 0, len(d) - 1).astype(np.int32)
            valid = None if col.validity is None else np.asarray(col.validity)
            ind = pa.array(codes, mask=None if valid is None else ~valid)
            arrays.append(pa.DictionaryArray.from_arrays(ind, pa.array(d.astype(str))))
        else:
            np_vals = col.to_numpy()
            arrays.append(pa.array(np_vals))
    return pa.table(arrays, names=names)
