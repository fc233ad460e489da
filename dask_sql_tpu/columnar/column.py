"""Device-resident column: the unit of data in the TPU backend.

Role parity: a single pandas Series inside a dask partition (reference
`dask_sql/datacontainer.py` works over `dd.Series`).  TPU-first re-design:

- the value buffer is a flat jax array in HBM (numeric / encoded),
- NULLs are an explicit boolean validity mask (pandas nullable dtypes don't exist on
  device — SURVEY.md §7 "NULL semantics"),
- strings are dictionary-encoded: an int32 code array on device plus a host-side
  numpy object array of unique values.  All string *equality/hashing/grouping* then
  runs on the MXU/VPU as integer ops; only regex-ish ops (LIKE) touch the host
  dictionary (which is tiny compared to the data).
- datetimes are int64 nanoseconds since epoch.
- numeric/datetime columns may additionally carry a compressed ``encoding``
  (DICT / FOR / RLE, columnar/encodings.py): the device buffer then holds
  codes (or run values) and ``enc_*`` metadata describes the mapping.
  Encoding-aware consumers (the compiled pipelines, the estimator, host
  decode) operate on the codes; everyone else calls ``decode()`` first.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

import jax.numpy as jnp
import numpy as np

from .dtypes import (
    DATETIME_TYPES,
    INTERVAL_TYPES,
    STRING_TYPES,
    SqlType,
    np_to_sql,
    sql_to_np,
)
from .encodings import Encoding, prime_dictionary_nbytes
from ..observability.spans import load_span

_NS_PER_DAY = 86_400_000_000_000


@dataclass(frozen=True)
class Column:
    data: jnp.ndarray  # 1-D device buffer (values, or codes when encoded)
    sql_type: SqlType
    validity: Optional[jnp.ndarray] = None  # bool, True = valid; None = all-valid
    dictionary: Optional[np.ndarray] = None  # host uniques for STRING_TYPES
    #: physical encoding of `data` (columnar/encodings.py); PLAIN = dense
    encoding: Encoding = Encoding.PLAIN
    #: DICT: host-side SORTED unique values in the device representation
    enc_values: Optional[np.ndarray] = None
    #: FOR: value = code * enc_scale + enc_ref
    enc_ref: int = 0
    enc_scale: int = 1
    #: RLE: int32 run lengths (device) + the logical row count; `data` holds
    #: the run values and `validity` is per-RUN for RLE columns
    enc_lengths: Optional[jnp.ndarray] = None
    enc_rows: Optional[int] = None

    # -- construction -------------------------------------------------------
    @staticmethod
    def from_numpy(arr: np.ndarray, mask: Optional[np.ndarray] = None,
                   encode: Optional[bool] = None) -> "Column":
        """Build a Column from a host numpy array (+ optional validity mask).

        ``encode`` controls load-time compression (columnar/encodings.py):
        None consults the registration load-scope + ``columnar.encoding``
        config (so only table ingest auto-encodes), True forces the
        heuristics to run, False never encodes.  When an encoding is
        selected the dense buffer is never uploaded at all."""
        from . import encodings

        def finish(vals, msk, sql_type):
            if encode is not False:
                col = encodings.maybe_encode(vals, msk, sql_type,
                                             force=bool(encode))
                if col is not None:
                    return col
            return Column(to_device(vals), sql_type, _dev_mask(msk))

        kind = arr.dtype.kind
        if kind == "M":  # datetime64 -> ns int64
            ns = arr.astype("datetime64[ns]").view("int64")
            nat = ns == np.iinfo(np.int64).min
            mask = _merge_mask(mask, ~nat)
            return finish(ns, mask, SqlType.TIMESTAMP)
        if kind == "m":  # timedelta64 -> ns int64
            ns = arr.astype("timedelta64[ns]").view("int64")
            nat = ns == np.iinfo(np.int64).min
            mask = _merge_mask(mask, ~nat)
            return finish(ns, mask, SqlType.INTERVAL_DAY_TIME)
        if kind in ("O", "U", "S"):
            return Column._encode_strings(arr, mask)
        if kind == "f":
            nan = np.isnan(arr)
            if nan.any():
                mask = _merge_mask(mask, ~nan)
        sql_type = np_to_sql(arr.dtype)
        return finish(arr, mask, sql_type)

    @staticmethod
    def _encode_strings(arr: np.ndarray, mask: Optional[np.ndarray]) -> "Column":
        obj = np.asarray(arr, dtype=object)
        # dtype=bool: an empty comprehension otherwise yields float64, which
        # breaks ~mask and boolean indexing (empty frames, TPC-DS q84)
        isnull = np.array([v is None or (isinstance(v, float) and np.isnan(v))
                           for v in obj], dtype=bool)
        mask = _merge_mask(mask, ~isnull)
        filled = obj.copy()
        filled[isnull] = ""
        with load_span("encode", encoding="STRING") as attrs:
            uniques, codes = np.unique(filled.astype(str), return_inverse=True)
            codes = codes.astype(np.int32)
            # the lengths are one vectorised pass while the uniques are
            # still a <U array: the byte accounting never walks them
            chars = int(np.char.str_len(uniques).sum())
            uniques = uniques.astype(object)
            prime_dictionary_nbytes(uniques, chars)
            attrs["distinct"] = len(uniques)
        return Column(
            to_device(codes),
            SqlType.VARCHAR,
            _dev_mask(mask),
            uniques,
        )

    @staticmethod
    def from_scalar(value, length: int, sql_type: Optional[SqlType] = None) -> "Column":
        """Broadcast a python scalar to a column of the given length."""
        from .dtypes import python_to_sql_type

        if value is None:
            st = sql_type or SqlType.DOUBLE
            data = jnp.zeros(length, dtype=sql_to_np(st))
            return Column(data, st, jnp.zeros(length, dtype=bool),
                          np.array([""], dtype=object) if st in STRING_TYPES else None)
        if isinstance(value, str):
            return Column(
                jnp.zeros(length, dtype=jnp.int32), SqlType.VARCHAR, None,
                np.array([value], dtype=object),
            )
        if isinstance(value, np.datetime64):
            ns = value.astype("datetime64[ns]").astype(np.int64)
            return Column(jnp.full(length, ns, dtype=jnp.int64), SqlType.TIMESTAMP)
        if isinstance(value, np.timedelta64):
            ns = value.astype("timedelta64[ns]").astype(np.int64)
            return Column(jnp.full(length, ns, dtype=jnp.int64), SqlType.INTERVAL_DAY_TIME)
        st = sql_type or python_to_sql_type(value)
        return Column(jnp.full(length, value, dtype=sql_to_np(st)), st)

    # -- basic properties ---------------------------------------------------
    def __len__(self) -> int:
        if self.encoding is Encoding.RLE:
            return int(self.enc_rows)
        return int(self.data.shape[0])

    @property
    def has_nulls(self) -> bool:
        return self.validity is not None and not bool(jnp.all(self.validity))

    def valid_mask(self) -> jnp.ndarray:
        """Always-materialized ROW-length validity mask."""
        if self.validity is None:
            return jnp.ones(len(self), dtype=bool)
        if self.encoding is Encoding.RLE:  # per-run mask: expand to rows
            return jnp.repeat(self.validity, self.enc_lengths,
                              total_repeat_length=self.enc_rows)
        return self.validity

    # -- encoding -----------------------------------------------------------
    def decode(self) -> "Column":
        """Materialize a compressed column as PLAIN (identity if already)."""
        from . import encodings

        return encodings.decode_column(self)

    def device_nbytes(self) -> int:
        """Resident bytes of this column as stored (encoded widths)."""
        from . import encodings

        return encodings.encoded_nbytes(self)

    # -- transformations ----------------------------------------------------
    def with_data(self, data: jnp.ndarray, sql_type: Optional[SqlType] = None) -> "Column":
        # replaced data is computed VALUES: any code-space encoding no
        # longer describes it
        return replace(self, data=data, sql_type=sql_type or self.sql_type,
                       encoding=Encoding.PLAIN, enc_values=None, enc_ref=0,
                       enc_scale=1, enc_lengths=None, enc_rows=None)

    def take(self, indices: jnp.ndarray) -> "Column":
        """Row gather (join/materialize/sort primitive).  DICT/FOR codes
        gather like values (the encoding survives); RLE is run-aligned, so
        positional access decodes first."""
        if self.encoding is Encoding.RLE:
            return self.decode().take(indices)
        validity = None if self.validity is None else self.validity[indices]
        return replace(self, data=self.data[indices], validity=validity)

    def filter(self, mask) -> "Column":
        """Keep rows where mask is True (eager, data-dependent shape)."""
        if self.encoding is Encoding.RLE:
            return self.decode().filter(mask)
        mask = jnp.asarray(mask)
        validity = None if self.validity is None else self.validity[mask]
        return replace(self, data=self.data[mask], validity=validity)

    def slice(self, start: int, stop: int) -> "Column":
        if self.encoding is Encoding.RLE:
            return self.decode().slice(start, stop)
        validity = None if self.validity is None else self.validity[start:stop]
        return replace(self, data=self.data[start:stop], validity=validity)

    def compact_dictionary(self) -> "Column":
        """Re-encode so the dictionary contains only referenced values, sorted.

        Sorted dictionaries make string ORDER BY / comparisons pure integer ops.
        """
        if self.dictionary is None:
            return self
        codes = np.asarray(self.data)
        used = np.unique(codes)
        used = used[(used >= 0) & (used < len(self.dictionary))]
        sub = self.dictionary[used].astype(str)
        order = np.argsort(sub, kind="stable")
        new_dict = sub[order].astype(object)
        remap = np.zeros(max(len(self.dictionary), 1), dtype=np.int32)
        remap[used[order]] = np.arange(len(used), dtype=np.int32)
        new_codes = remap[np.clip(codes, 0, len(remap) - 1)]
        # host-resident columns (tiny post-aggregate tables) stay host-resident
        data = new_codes if isinstance(self.data, np.ndarray) else jnp.asarray(new_codes)
        return Column(data, self.sql_type, self.validity, new_dict)

    def cast(self, target: SqlType) -> "Column":
        from . import casts

        return casts.cast_column(self, target)

    # -- host materialization ----------------------------------------------
    def to_numpy(self) -> np.ndarray:
        """Materialize to a host numpy array with NULLs as None/NaN/NaT."""
        data = np.asarray(self.data)
        mask = None if self.validity is None else ~np.asarray(self.validity)
        return self.decode_host(data, mask)

    def decode_host(self, data: np.ndarray,
                    mask: Optional[np.ndarray]) -> np.ndarray:
        """Host decode of already-transferred buffers (mask = ~validity).

        Split from to_numpy so Table.to_pandas can pull every column in ONE
        packed device transfer and decode here.  Encoded columns transfer
        their NARROW codes and late-materialize on the host — the d2h wire
        moves encoded bytes."""
        if self.encoding is not Encoding.PLAIN:
            from .encodings import decode_host_buffers

            data, mask = decode_host_buffers(self, data, mask)
        if self.sql_type in STRING_TYPES:
            codes = np.clip(data, 0, max(len(self.dictionary) - 1, 0))
            out = self.dictionary[codes].astype(object) if len(self.dictionary) else np.full(len(data), "", dtype=object)
            if mask is not None:
                out[mask] = None
            return out
        if self.sql_type in DATETIME_TYPES:
            out = data.view("datetime64[ns]") if data.dtype == np.int64 else data.astype("datetime64[ns]")
            out = out.copy()
            if self.sql_type == SqlType.DATE:
                pass  # stored as ns at midnight; keep datetime64 for pandas parity
            if mask is not None:
                out[mask] = np.datetime64("NaT")
            return out
        if self.sql_type == SqlType.INTERVAL_DAY_TIME:
            out = data.view("timedelta64[ns]").copy()
            if mask is not None:
                out[mask] = np.timedelta64("NaT")
            return out
        if mask is not None and mask.any():
            if data.dtype.kind == "f":
                out = data.copy()
                out[mask] = np.nan
                return out
            if data.dtype.kind == "b":
                out = data.astype(object)
                out[mask] = None
                return out
            # int with NULLs -> float64 + NaN (pandas behaviour)
            out = data.astype(np.float64)
            out[mask] = np.nan
            return out
        return data

    def to_pandas(self, name: str = "col"):
        import pandas as pd

        return pd.Series(self.to_numpy(), name=name)


def _merge_mask(a: Optional[np.ndarray], b: Optional[np.ndarray]) -> Optional[np.ndarray]:
    if a is None:
        return b
    if b is None:
        return a
    return a & b


def _dev_mask(mask: Optional[np.ndarray]) -> Optional[jnp.ndarray]:
    if mask is None:
        return None
    mask = np.asarray(mask, dtype=bool)
    if mask.all():
        return None
    return to_device(mask)


def to_device(host) -> jnp.ndarray:
    """`jnp.asarray` of a host array; inside a table registration the call
    is the load's ``h2d`` span (observability/spans.py `load_span`)."""
    with load_span("h2d", bytes=int(getattr(host, "nbytes", 0))):
        return jnp.asarray(host)
