"""Device-resident columnar table: the unit a plan node produces/consumes.

Role parity: one dask DataFrame in the reference (SURVEY.md §1 layer 3).  Here a
table is an ordered mapping of backend column names to `Column`s, all of equal
length, resident in device HBM.  Distribution is handled above this layer
(`dask_sql_tpu.parallel`): a distributed table is this same structure with jax
arrays sharded over a `Mesh` via NamedSharding.
"""
from __future__ import annotations

import logging
from typing import Dict, Iterable, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from .column import Column
from .dtypes import SqlType

logger = logging.getLogger(__name__)


class Table:
    __slots__ = ("columns", "_num_rows", "row_valid")

    def __init__(self, columns: Dict[str, Column], num_rows: Optional[int] = None,
                 row_valid=None):
        """`row_valid` marks a PADDED table: column buffers are a multiple of
        the shard count (so NamedSharding row specs stay exact end-to-end on
        non-divisible tables), `row_valid` is a same-length device mask of the
        real rows, and `num_rows` stays the logical count.  Padded tables
        exist only at rest (sharded base tables); padding-aware consumers
        (the compiled pipelines) fold `row_valid` into their masks, everyone
        else goes through `depad()`."""
        self.columns: Dict[str, Column] = dict(columns)
        self.row_valid = row_valid
        if num_rows is None:
            num_rows = len(next(iter(self.columns.values()))) if self.columns else 0
        self._num_rows = num_rows
        if row_valid is not None:
            padded = int(row_valid.shape[0])
            assert padded >= num_rows, f"padded {padded} < logical {num_rows}"
            for name, col in self.columns.items():
                assert len(col) == padded, \
                    f"column {name}: {len(col)} != padded {padded}"
        else:
            for name, col in self.columns.items():
                assert len(col) == num_rows, f"column {name}: {len(col)} != {num_rows}"

    @property
    def is_padded(self) -> bool:
        return self.row_valid is not None

    @property
    def padded_rows(self) -> int:
        return int(self.row_valid.shape[0]) if self.row_valid is not None \
            else self._num_rows

    def depad(self) -> "Table":
        """Exact-length view for consumers that index rows positionally.
        The slice keeps a sharded (but no longer block-exact) layout —
        today's pre-padding behavior, paid only on the eager paths."""
        if self.row_valid is None:
            return self
        n = self._num_rows
        return Table({name: c.slice(0, n) for name, c in self.columns.items()}, n)

    # -- construction -------------------------------------------------------
    @staticmethod
    def from_pandas(df, encode=None) -> "Table":
        """``encode``: load-time compressed encodings (columnar/encodings.py)
        — None consults the registration load-scope + config, True forces
        the selection heuristics, False stays dense."""
        from ..observability.spans import load_span

        cols = {}
        for name in df.columns:
            # the registration's per-column span: the encode and h2d spans
            # opened inside `Column.from_numpy` are cut out of it
            with load_span("convert", column=str(name)):
                ser = df[name]
                mask = None
                values = ser.to_numpy()
                if ser.isna().any():
                    mask = ~ser.isna().to_numpy()
                    if values.dtype.kind in ("i", "u", "b"):
                        pass  # no NaN possible; mask already captured
                if str(ser.dtype) in ("string", "str") or ser.dtype == object:
                    values = ser.astype(object).to_numpy()
                elif values.dtype.kind not in ("O", "U", "S", "M", "m", "f", "i", "u", "b"):
                    values = ser.astype(object).to_numpy()
                cols[str(name)] = Column.from_numpy(values, mask,
                                                    encode=encode)
        return Table(cols, len(df))

    @staticmethod
    def from_arrow(arrow_table) -> "Table":
        from . import interop

        return interop.arrow_to_table(arrow_table)

    # -- basic properties ---------------------------------------------------
    @property
    def num_rows(self) -> int:
        return self._num_rows

    @property
    def column_names(self) -> List[str]:
        return list(self.columns.keys())

    def __len__(self) -> int:
        return self._num_rows

    def __contains__(self, name: str) -> bool:
        return name in self.columns

    def __getitem__(self, name: str) -> Column:
        return self.columns[name]

    # -- transformations (all return new Tables; columns are immutable) -----
    def select(self, names: Sequence[str]) -> "Table":
        return Table({n: self.columns[n] for n in names}, self._num_rows,
                     self.row_valid)

    def assign(self, **new_cols: Column) -> "Table":
        cols = dict(self.columns)
        cols.update(new_cols)
        return Table(cols, self._num_rows, self.row_valid)

    def rename(self, mapping: Dict[str, str]) -> "Table":
        return Table({mapping.get(n, n): c for n, c in self.columns.items()},
                     self._num_rows, self.row_valid)

    def decode(self) -> "Table":
        """Materialize every encoded column as PLAIN (eager-operator view).
        Identity when nothing is encoded — the common case stays free."""
        from .encodings import Encoding

        if all(c.encoding is Encoding.PLAIN for c in self.columns.values()):
            return self
        return Table({n: c.decode() for n, c in self.columns.items()},
                     self._num_rows, self.row_valid)

    def has_encoded_columns(self) -> bool:
        from .encodings import Encoding

        return any(c.encoding is not Encoding.PLAIN
                   for c in self.columns.values())

    def filter(self, mask) -> "Table":
        # one nonzero for the whole table, then integer gathers per column —
        # per-column boolean indexing pays the bool->index expansion N times
        mask = jnp.asarray(mask)
        if self.row_valid is not None and \
                int(mask.shape[0]) == self.padded_rows:
            # padded-frame mask: pad rows must never pass, and the gather
            # frame must match the mask frame
            indices = jnp.nonzero(mask & self.row_valid)[0]
            return Table({n: c.take(indices) for n, c in self.columns.items()},
                         int(indices.shape[0]))
        src = self.depad()
        indices = jnp.nonzero(mask)[0]
        return Table({n: c.take(indices) for n, c in src.columns.items()},
                     int(indices.shape[0]))

    def take(self, indices) -> "Table":
        # indices are LOGICAL row positions (< num_rows); a padded table
        # gathers from its exact-length view
        src = self.depad()
        indices = jnp.asarray(indices)
        return Table({n: c.take(indices) for n, c in src.columns.items()},
                     int(indices.shape[0]))

    def slice(self, start: int, stop: int) -> "Table":
        src = self.depad()
        stop = min(stop, self._num_rows)
        start = min(start, stop)
        return Table({n: c.slice(start, stop) for n, c in src.columns.items()}, stop - start)

    def head(self, n: int) -> "Table":
        return self.slice(0, n)

    @staticmethod
    def concat(tables: Sequence["Table"]) -> "Table":
        """Vertical concatenation (UNION ALL primitive)."""
        from .concat import concat_tables

        return concat_tables(tables)

    # -- host materialization ----------------------------------------------
    def to_pandas(self):
        import pandas as pd

        data = self._host_columns()
        if not data:
            return pd.DataFrame(index=range(self._num_rows))
        return pd.DataFrame(data)

    def _host_columns(self):
        """{name: numpy} with NULL decoding.

        On accelerator backends every device buffer rides ONE packed
        transfer (per-column pulls each cost a dispatch round trip);
        host-resident columns and the CPU backend use the plain per-column
        path."""
        if self.row_valid is not None:
            return self.depad()._host_columns()
        import os

        import jax

        cols = self.columns
        force = os.environ.get("DSQL_PACK_TO_PANDAS") == "1"  # for tests
        if not cols or self._num_rows == 0 or (
                jax.default_backend() == "cpu" and not force):
            return {n: c.to_numpy() for n, c in cols.items()}
        from .pack import packed_host_arrays

        bufs = []
        for c in cols.values():
            bufs.append(c.data)
            if c.validity is not None:
                bufs.append(c.validity)
        from ..resilience.errors import QueryError

        try:
            host = packed_host_arrays(bufs)
        except QueryError:
            # taxonomy failures (a dropped transfer — fault site ``d2h``)
            # must keep their retry semantics: the serving worker's backoff
            # absorbs them; a silent per-column fallback would hide the
            # drop AND re-pay the transfer N times
            raise
        except Exception as exc:  # dsql: allow-broad-except — backend pack quirk -> per-column
            # never silent: the step to per-column pulls is logged, and
            # the pulls below land in TRANSFER_STATS
            logger.warning("packed device->host pull failed (%s: %s); "
                           "pulling %d buffers one by one",
                           type(exc).__name__, exc, len(bufs))
            host = None
        if host is None:
            from ..utils import d2h_fetch

            pulled = [b for b in bufs if not isinstance(b, np.ndarray)]
            with d2h_fetch(len(pulled), sum(int(b.nbytes) for b in pulled)):
                return {n: c.to_numpy() for n, c in cols.items()}
        # decode errors propagate: a silent fallback here would double-pay
        # the transfer on every call while hiding the defect
        out = {}
        i = 0
        for n, c in cols.items():
            data = host[i]
            i += 1
            mask = None
            if c.validity is not None:
                mask = ~host[i]
                i += 1
            out[n] = c.decode_host(data, mask)
        return out

    def to_arrow(self):
        from . import interop

        return interop.table_to_arrow(self.depad())

    def __repr__(self) -> str:
        cols = ", ".join(f"{n}:{c.sql_type.value}" for n, c in self.columns.items())
        return f"Table[{self._num_rows} rows]({cols})"
