"""Shared utilities (parity: reference dask_sql/utils.py — Pluggable registry
base utils.py:61, convert_sql_kwargs utils.py:144, LoggableDataFrame
utils.py:121-141, new_temporary_column)."""
from __future__ import annotations

import uuid
from typing import Any, Dict, Optional


class Pluggable:
    """Registry base: subclasses share a class-level plugin dict."""

    __plugins: Dict[type, Dict[str, Any]] = {}

    @classmethod
    def add_plugin(cls, name: str, plugin: Any, replace: bool = True) -> None:
        registry = Pluggable.__plugins.setdefault(cls, {})
        if name in registry and not replace:
            return
        registry[name] = plugin

    @classmethod
    def get_plugin(cls, name: str) -> Any:
        return Pluggable.__plugins.setdefault(cls, {})[name]

    @classmethod
    def get_plugins(cls):
        return list(Pluggable.__plugins.setdefault(cls, {}).values())


def convert_sql_kwargs(sql_kwargs) -> Dict[str, Any]:
    """Normalize parsed WITH(...) kwargs (nested maps/lists/scalars) into
    plain python values (parity: utils.py:144)."""
    if isinstance(sql_kwargs, dict):
        return {k: convert_sql_kwargs(v) for k, v in sql_kwargs.items()}
    if isinstance(sql_kwargs, (list, tuple)):
        return [convert_sql_kwargs(v) for v in sql_kwargs]
    return sql_kwargs


def new_temporary_column(table) -> str:
    """Unique backend column name (parity: utils.py new_temporary_column)."""
    while True:
        name = f"__tmp_{uuid.uuid4().hex[:12]}"
        if name not in getattr(table, "columns", {}):
            return name


class LoggableDataFrame:
    """Lazy repr wrapper so logging never materializes a frame
    (parity: utils.py:121-141)."""

    def __init__(self, df):
        self.df = df

    def __str__(self):
        df = self.df
        if hasattr(df, "column_names"):
            return f"Table[{getattr(df, 'num_rows', '?')} rows, cols={df.column_names}]"
        if hasattr(df, "columns"):
            return f"DataFrame[cols={list(df.columns)}]"
        return f"{type(df).__name__}"

    __repr__ = __str__


# ---------------------------------------------------------------------------
# device-transfer accounting (perf instrumentation)
# ---------------------------------------------------------------------------
#: device->host transfers made through the engine's own seams (the packed
#: result pull, host_read, table materialization).  Each
#: transfer is a blocking round trip, so the per-query delta is the number the Q1
#: perf work drives toward 1.  Reset with `TRANSFER_STATS.clear()`.
TRANSFER_STATS: Dict[str, int] = {"d2h": 0}

#: full interpreted walks of a string dictionary made to count its characters
#: (columnar/encodings.py::dictionary_nbytes), and the entries they visited.
#: A dictionary array is walked at most once in its life and the load paths
#: prime theirs, so over a window of queries both stand still.
DICTIONARY_STATS: Dict[str, int] = {"columnar.dictionary.walks": 0,
                                    "columnar.dictionary.walk_entries": 0}


def d2h_fetch(n: int = 1, nbytes: Optional[int] = None):
    """Scope of ``n`` blocking device->host pulls (a `jax.device_get` /
    `np.asarray` of device buffers): counts them in `TRANSFER_STATS` and
    records one ``fetch`` detail span on the active query trace
    (observability/spans.py), ``nbytes`` where the size is known."""
    from .observability.spans import fetch

    TRANSFER_STATS["d2h"] = TRANSFER_STATS.get("d2h", 0) + n
    return fetch(nbytes)


def host_ints(*vals):
    """Pull several device scalars in ONE device_get (each separate int()
    call blocks on its own round trip)."""
    import jax

    with d2h_fetch():
        return tuple(int(v) for v in jax.device_get(vals))
