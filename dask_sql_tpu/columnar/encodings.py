"""First-class column encodings: compressed-domain storage for the TPU backend.

ROADMAP item 2 ("GPU Acceleration of SQL Analytics on Compressed Data",
arXiv:2506.10092, applied to the tensor-runtime operator style of TQP,
arXiv:2203.01877).  Strings have always been dictionary-encoded here
(`Column.dictionary`); this module extends the idea to every other column
family so scans move encoded bytes and decode happens late:

- ``DICT``   — low-cardinality numerics/datetimes: an int16/int32 code array
  in HBM plus a host-side SORTED array of unique values (``enc_values``).
  Sortedness is the operational trick: comparisons and IN-lists translate
  MONOTONICALLY into code space (``x < lit  <=>  code < searchsorted(values,
  lit)``), so the compiled predicates never materialize the values, and
  group-by radix domains come straight from ``len(enc_values)`` with no
  device min/max pull.
- ``FOR``    — frame-of-reference + implicit bit-pack for narrow-range ints
  (and epoch-ns datetimes, whose day-granularity gcd divides out):
  ``value = code * enc_scale + enc_ref`` with codes stored in the narrowest
  int dtype that fits.  Decode is one fused multiply-add inside the kernel;
  HBM traffic is the code width.
- ``RLE``    — run-length for sorted/clustered columns: ``data`` holds the
  run values, ``enc_lengths`` the int32 run lengths, ``enc_rows`` the
  logical row count; ``validity`` is per-RUN.  A storage-at-rest encoding:
  row-positional consumers (take/filter/slice, the compiled pipelines)
  decode first.
- ``PLAIN``  — the dense device buffer, unchanged.

Selection happens once at LOAD time (``input_utils`` registration, arrow
ingest, checkpoint restore) from the host array, so the decoded buffer is
never uploaded at all.  Late materialization: the compiled select path
decodes only the surviving rows inside the per-bucket gather kernel, and
host transfer (``Table.to_pandas`` / packed d2h) pulls the narrow codes and
decodes on the host.
"""
from __future__ import annotations

import contextlib
import contextvars
import enum
import threading
import weakref
from typing import Dict, Optional, Tuple

import numpy as np

from ..utils import DICTIONARY_STATS
from .dtypes import SqlType, sql_to_np, STRING_TYPES


class Encoding(enum.Enum):
    """Physical encoding of a Column's device buffer."""

    PLAIN = "PLAIN"
    DICT = "DICT"
    RLE = "RLE"
    FOR = "FOR"

    def __str__(self) -> str:  # pragma: no cover - repr sugar
        return self.value


#: active while InputUtil registers a table (the one boundary where
#: auto-selection applies); Column.from_numpy consults it so intermediate
#: host->device conversions (UDF results, ML frames) stay PLAIN
_load_scope: contextvars.ContextVar = contextvars.ContextVar(
    "dsql_encoding_load_scope", default=False)


@contextlib.contextmanager
def load_scope():
    token = _load_scope.set(True)
    try:
        yield
    finally:
        _load_scope.reset(token)


def in_load_scope() -> bool:
    return bool(_load_scope.get())


def auto_enabled() -> bool:
    """True when load-time auto-selection is configured on."""
    from .. import config

    return str(config.get("columnar.encoding", "auto")).lower() == "auto"


def should_auto_encode() -> bool:
    return in_load_scope() and auto_enabled()


# ---------------------------------------------------------------------------
# selection heuristics (host-side, over the device-representation array)
# ---------------------------------------------------------------------------
#: dtypes eligible per encoding; bool/strings never encode (bool is already
#: 1 byte; strings carry their own dictionary mechanism)
_INT16_MAX_CODES = 1 << 15


def _code_dtype(n_codes: int) -> Optional[np.dtype]:
    """Narrowest signed int dtype holding codes ``[0, n_codes)`` with one
    spare slot (radix NULL code headroom)."""
    if n_codes < _INT16_MAX_CODES:
        return np.dtype(np.int16)
    if n_codes < (1 << 31) - 1:
        return np.dtype(np.int32)
    return None


def maybe_encode(values: np.ndarray, valid: Optional[np.ndarray],
                 sql_type: SqlType, force: bool = False):
    """Pick and build an encoded Column from a HOST array in its device
    representation (ints/floats; datetimes already epoch-ns int64), or
    return None (caller constructs PLAIN).  ``valid`` is a host bool mask
    (True = valid) or None.  ``force=True`` bypasses the load-scope/config
    gate (tests), not the heuristics.  Inside a table registration the
    probes and the build are the load's ``encode`` span."""
    from ..observability.spans import load_span

    with load_span("encode") as attrs:
        col = _select_and_build(values, valid, sql_type, force)
        attrs["encoding"] = "PLAIN" if col is None else col.encoding.value
        return col


def _select_and_build(values, valid, sql_type, force):
    from .. import config
    from .column import Column, _dev_mask, to_device

    if not force and not should_auto_encode():
        return None
    if sql_type in STRING_TYPES or sql_type in (SqlType.BOOLEAN, SqlType.NULL,
                                                SqlType.ANY):
        return None
    values = np.asarray(values)
    if values.ndim != 1 or values.dtype.kind not in "if":
        return None
    n = values.shape[0]
    if n < int(config.get("columnar.encoding.min_rows", 1024)):
        return None
    valid_vals = values if valid is None else values[np.asarray(valid, bool)]
    if valid_vals.shape[0] == 0:
        return None
    if values.dtype.kind == "f" and np.isnan(valid_vals).any():
        return None  # NaN-bearing valid values: leave dense
    plain_width = values.dtype.itemsize
    plain_bytes = n * plain_width

    candidates = []  # (bytes, preference_rank, builder)

    # DICT: sorted uniques of the VALID values (invalid rows code to 0)
    if config.get("columnar.encoding.dict", True):
        uniques = np.unique(valid_vals)
        cd = _code_dtype(len(uniques))
        if cd is not None and len(uniques) <= int(
                config.get("columnar.encoding.dict_max_card", 1 << 15)) \
                and len(uniques) <= max(n // 4, 1):
            u = uniques

            def build_dict(u=u, cd=cd):
                filled = values if valid is None else \
                    np.where(np.asarray(valid, bool), values, u[0])
                codes = np.searchsorted(u, filled).astype(cd)
                return Column(to_device(codes), sql_type, _dev_mask(valid),
                              None, encoding=Encoding.DICT,
                              enc_values=u.astype(sql_to_np(sql_type)))

            candidates.append((n * cd.itemsize, 0, build_dict))

    # FOR: affine frame-of-reference for integer representations
    if config.get("columnar.encoding.for", True) and values.dtype.kind == "i":
        lo = int(valid_vals.min())
        hi = int(valid_vals.max())
        offs = valid_vals.astype(np.int64) - lo
        scale = int(np.gcd.reduce(offs)) if offs.shape[0] else 1
        scale = max(scale, 1)
        span_codes = (hi - lo) // scale
        cd = _code_dtype(span_codes + 1)
        if cd is not None and cd.itemsize < plain_width:

            def build_for(lo=lo, scale=scale, cd=cd):
                filled = values if valid is None else \
                    np.where(np.asarray(valid, bool), values, lo)
                codes = ((filled.astype(np.int64) - lo) // scale).astype(cd)
                return Column(to_device(codes), sql_type, _dev_mask(valid),
                              None, encoding=Encoding.FOR, enc_ref=lo,
                              enc_scale=scale)

            candidates.append((n * cd.itemsize, 1, build_for))

    # RLE: only when extreme (runs must pay for the lengths array AND the
    # decode-before-positional-use policy)
    if config.get("columnar.encoding.rle", True):
        v = np.asarray(valid, bool) if valid is not None else None
        change = values[1:] != values[:-1]
        if v is not None:
            change = change | (v[1:] != v[:-1])
        n_runs = 1 + int(change.sum())
        rle_bytes = n_runs * (plain_width + 4)
        if rle_bytes * 8 <= plain_bytes:

            def build_rle(change=change, n_runs=n_runs, v=v):
                starts = np.concatenate(
                    [[0], np.flatnonzero(change) + 1]).astype(np.int64)
                lengths = np.diff(np.concatenate(
                    [starts, [n]])).astype(np.int32)
                run_vals = values[starts]
                run_valid = None if v is None else v[starts]
                if run_valid is not None and bool(run_valid.all()):
                    run_valid = None
                return Column(
                    to_device(run_vals), sql_type,
                    None if run_valid is None else to_device(run_valid),
                    None, encoding=Encoding.RLE,
                    enc_lengths=to_device(lengths), enc_rows=n)

            candidates.append((rle_bytes, -1, build_rle))

    # require a real saving (>= 25%) so borderline columns stay PLAIN
    candidates = [c for c in candidates if c[0] * 4 <= plain_bytes * 3]
    if not candidates:
        return None
    candidates.sort(key=lambda c: (c[0], c[1]))
    return candidates[0][2]()


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def decode_host_buffers(col, data: np.ndarray, aligned=None):
    """THE host-side decode rule, single-sourced for ``Column.decode_host``
    (d2h late materialization) and ``decode_column`` (host-resident
    columns): DICT maps codes through the value array, FOR applies the
    affine, RLE expands runs — expanding ``aligned`` (a per-run validity
    mask or its inverse) alongside.  PLAIN passes through.  Returns
    ``(values, aligned)``."""
    if col.encoding is Encoding.DICT:
        data = col.enc_values[np.clip(data, 0, len(col.enc_values) - 1)]
    elif col.encoding is Encoding.FOR:
        data = data.astype(sql_to_np(col.sql_type))
        if col.enc_scale != 1:
            data = data * col.enc_scale
        if col.enc_ref:
            data = data + col.enc_ref
    elif col.encoding is Encoding.RLE:
        lengths = np.asarray(col.enc_lengths)
        data = np.repeat(np.asarray(data), lengths)
        if aligned is not None:
            aligned = np.repeat(np.asarray(aligned), lengths)
    return data, aligned


def decode_column(col):
    """Materialize an encoded Column as PLAIN (device ops for device
    buffers, numpy via `decode_host_buffers` for host-resident ones).
    Identity for PLAIN columns."""
    import jax.numpy as jnp
    from dataclasses import replace

    if col.encoding is Encoding.PLAIN:
        return col
    plain = dict(encoding=Encoding.PLAIN, enc_values=None, enc_ref=0,
                 enc_scale=1, enc_lengths=None, enc_rows=None)
    if isinstance(col.data, np.ndarray):
        data, validity = decode_host_buffers(col, col.data, col.validity)
        return replace(col, data=data, validity=validity, **plain)
    target = sql_to_np(col.sql_type)
    if col.encoding is Encoding.DICT:
        lut = jnp.asarray(col.enc_values)
        data = lut[jnp.clip(col.data, 0, len(col.enc_values) - 1)]
        return replace(col, data=data, **plain)
    if col.encoding is Encoding.FOR:
        data = col.data.astype(target)
        if col.enc_scale != 1:
            data = data * col.enc_scale
        if col.enc_ref:
            data = data + jnp.asarray(col.enc_ref, dtype=target)
        return replace(col, data=data, **plain)
    # RLE: expand runs back to rows (static total length keeps this jit-safe)
    n = col.enc_rows
    data = jnp.repeat(col.data, col.enc_lengths, total_repeat_length=n)
    validity = None if col.validity is None else \
        jnp.repeat(col.validity, col.enc_lengths, total_repeat_length=n)
    return replace(col, data=data, validity=validity, **plain)


# ---------------------------------------------------------------------------
# byte accounting (metrics / estimator / bench)
# ---------------------------------------------------------------------------
#: character counts of string dictionaries, kept per dictionary ARRAY:
#: ``id(array) -> (weak reference, characters)``.  Keyed by the array and not
#: by the Column because `take` / `filter` / `slice` / `replace(...)` hand the
#: same array to new columns, and every sharer reads the one kept number.
#: Sound because a dictionary array is never written in place: every path
#: that changes a dictionary builds a new array.  Held weakly: a dropped
#: table's entry goes with its array, before the id can be handed out again.
_DICTIONARY_CHARS: Dict[int, Tuple[weakref.ref, int]] = {}
_WALK_LOCK = threading.Lock()


def _kept_chars(dictionary) -> Optional[int]:
    kept = _DICTIONARY_CHARS.get(id(dictionary))
    if kept is not None and kept[0]() is dictionary:
        return kept[1]
    return None


def prime_dictionary_nbytes(dictionary, chars: int) -> None:
    """Keep ``chars``, the character count `dictionary_nbytes` would walk
    out, for a dictionary array whose builder had the lengths cheaply (the
    load paths: numpy's ``<U`` uniques, arrow's ``utf8_length``), so that
    it is never walked."""
    key = id(dictionary)
    try:
        # the callback runs while the array is being freed, so before its
        # id can belong to another
        ref = weakref.ref(dictionary,
                          lambda _ref: _DICTIONARY_CHARS.pop(key, None))
    except TypeError:  # a duck-typed stand-in: counted on every ask
        return
    _DICTIONARY_CHARS[key] = (ref, int(chars))


def dictionary_nbytes(dictionary) -> int:
    """Host bytes of a string dictionary (an object array of uniques, whose
    ``nbytes`` only counts pointers): its characters plus its pointers.  The
    characters are counted once per dictionary array and kept with it; the
    interpreted walk that counts them shows in
    ``DICTIONARY_STATS["columnar.dictionary.walks"]``."""
    chars = _kept_chars(dictionary)
    if chars is None:
        # one walker at a time: threads that ask for the same new array
        # together walk it once, and the counters lose no update
        with _WALK_LOCK:
            chars = _kept_chars(dictionary)
            if chars is None:
                chars = sum(len(str(v)) for v in dictionary)
                DICTIONARY_STATS["columnar.dictionary.walks"] += 1
                DICTIONARY_STATS["columnar.dictionary.walk_entries"] += \
                    len(dictionary)
                prime_dictionary_nbytes(dictionary, chars)
    return chars + dictionary.nbytes


def encoded_nbytes(col) -> int:
    """Resident bytes of a column AS STORED: data buffer + validity mask +
    RLE lengths; host-side dictionaries (strings and DICT values) included
    since they are part of the working set the estimator answers for.
    THE single byte-accounting rule — serving/cache.table_nbytes and the
    estimator's scan bounds both delegate here so they can never drift.
    getattr-defensive so duck-typed column stand-ins keep working."""
    total = int(getattr(getattr(col, "data", None), "nbytes", 0) or 0)
    validity = getattr(col, "validity", None)
    if validity is not None:
        total += int(getattr(validity, "nbytes", 0) or 0)
    enc_lengths = getattr(col, "enc_lengths", None)
    if enc_lengths is not None:
        total += int(getattr(enc_lengths, "nbytes", 0) or 0)
    enc_values = getattr(col, "enc_values", None)
    if enc_values is not None:
        total += int(enc_values.nbytes)
    dictionary = getattr(col, "dictionary", None)
    if dictionary is not None:
        total += dictionary_nbytes(dictionary)
    return total


def decoded_nbytes(col) -> int:
    """Bytes the same column would occupy fully decoded (dense device
    representation + its validity mask).  String columns are int32 codes in
    BOTH worlds — their dictionary is the native representation."""
    n = len(col)
    total = n * sql_to_np(col.sql_type).itemsize
    if col.validity is not None:
        total += n  # bool mask, expanded for RLE
    if col.dictionary is not None:
        total += dictionary_nbytes(col.dictionary)
    return total


def scan_bytes(table, names=None) -> Tuple[int, int]:
    """(encoded, decoded) resident bytes of the named columns of a table."""
    names = list(names) if names is not None else list(table.column_names)
    enc = sum(encoded_nbytes(table.columns[n]) for n in names)
    dec = sum(decoded_nbytes(table.columns[n]) for n in names)
    return enc, dec


def resolve_encoded_scan(context, node):
    """``(table, projected names)`` for a TableScan over a REGISTERED table
    whose projected columns include at least one encoded column; None when
    there is no context, the table is unknown, the container is lazy
    (``LazyParquetContainer.table`` is a LOADING property — peeking it
    would defeat lazy registration, and lazy scans read PLAIN buffers per
    query anyway), a projected name is missing, or everything is PLAIN.
    Shared by the estimator's encoded-width scan bounds and the verifier's
    EXPLAIN LINT encoding advisory so the two can never disagree about
    which scans are encoded."""
    if context is None:
        return None
    try:
        dc = context.schema[node.schema_name].tables.get(node.table_name)
    except (KeyError, AttributeError):
        return None
    from ..datacontainer import LazyParquetContainer

    if dc is None or isinstance(dc, LazyParquetContainer):
        return None
    table = getattr(dc, "table", None)
    if table is None:
        return None
    names = [str(c) for c in (node.projection if node.projection is not None
                              else table.column_names)]
    cols = [table.columns.get(n) for n in names]
    if any(c is None for c in cols):
        return None
    if not any(c.encoding is not Encoding.PLAIN for c in cols):
        return None
    return table, names


# ---------------------------------------------------------------------------
# code-space predicate translation (DICT columns, sorted enc_values)
# ---------------------------------------------------------------------------
#: operator mirror for `lit OP col` -> `col OP' lit`
FLIP_CMP = {"eq": "eq", "ne": "ne", "lt": "gt", "le": "ge",
            "gt": "lt", "ge": "le"}


def dict_literal_bounds(values: np.ndarray, op: str, literal):
    """Host translation of ``col OP literal`` into code space for a SORTED
    dictionary.  Returns (kind, code) where kind/code describe a pure
    integer predicate over the codes:

    - ("lt", L)      codes <  L
    - ("ge", L)      codes >= L
    - ("eq", i)      codes == i      (exact dictionary member)
    - ("none", _)    no code matches (eq of an absent literal)
    - ("all", _)     every code matches
    """
    lit = literal
    left = int(np.searchsorted(values, lit, side="left"))
    right = int(np.searchsorted(values, lit, side="right"))
    if op == "lt":
        return ("lt", left)
    if op == "le":
        return ("lt", right)
    if op == "gt":
        return ("ge", right)
    if op == "ge":
        return ("ge", left)
    present = left < len(values) and left < right
    if op == "eq":
        return ("eq", left) if present else ("none", 0)
    if op == "ne":
        # ne of an absent literal is TRUE for every (valid) row
        return ("ne", left) if present else ("all", 0)
    raise ValueError(f"untranslatable op {op!r}")
