"""Shared plumbing for the SPMD compiled rungs.

The single-chip compiled pipelines (physical/compiled*.py) trace a function
of ``(datas, valids, row_valid, params)`` where ``valids`` entries and
``row_valid`` may be ``None``.  `shard_map` wants a concrete pytree of
arrays with one PartitionSpec per leaf, so this module packs the optional
arguments into flag-described tuples: column data and the row mask shard
row-block over the mesh axis, runtime parameters replicate.

The wrap is built ONCE per pipeline (the flags are static properties of the
bound table), and the returned jitted callable is what `timed_jit_call`
watches for fresh XLA compiles — the spmd rungs get the same compile-span /
compile-histogram accounting as the single-chip rungs.
"""
from __future__ import annotations

import logging
from typing import Callable, Optional, Sequence, Tuple

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import AXIS

logger = logging.getLogger(__name__)


def _mode(config, key: str, default="auto") -> str:
    return str(config.get(key, default)).lower()


def spmd_enabled(config) -> bool:
    """Master switch for the sharded compiled rungs (``parallel.spmd``)."""
    return _mode(config, "parallel.spmd") not in ("off", "false", "0", "none")


def rung_enabled(config, rung: str) -> bool:
    """Per-rung toggle under the master switch, e.g.
    ``parallel.spmd.select`` for ``spmd_select``."""
    if not spmd_enabled(config):
        return False
    short = rung[len("spmd_"):] if rung.startswith("spmd_") else rung
    v = config.get(f"parallel.spmd.{short}", True)
    return str(v).lower() not in ("off", "false", "0", "none")


def raise_rung_fault(rung: str, exc: BaseException):
    """Re-raise an exception out of building or running a sharded rung's
    wrap as the taxonomy's degradable `CompileError`, so `ladder.attempt`
    counts the step down (``resilience.degraded.<rung>``, the breaker, the
    ``degraded:<rung>`` trace event) and the lower rung still answers.  A
    bare ValueError/TypeError would classify as the NON-degradable
    ExecutionError and sink the query; returning None instead (the old
    behaviour) left a mesh quietly answering from one device."""
    from ..resilience.errors import CompileError

    msg = (f"{rung} declined ({type(exc).__name__}: {exc}); a lower rung "
           "serves instead")
    logger.warning(msg)
    raise CompileError(msg) from exc


def count_launch(metrics, mesh, rows: int,
                 segsum: Optional[str] = None) -> None:
    """One sharded-rung execution in the registry: ``parallel.spmd.launches``
    / ``.rows`` accumulate, ``parallel.spmd.devices`` is the mesh width of
    the newest one (a gauge).  A rung that reduces segments names its
    segment-sum mode: ``parallel.spmd.segsum.<mode>`` counts beside it."""
    metrics.inc("parallel.spmd.launches")
    if segsum is not None:
        metrics.inc(f"parallel.spmd.segsum.{segsum}")
    metrics.inc("parallel.spmd.rows", rows)
    metrics.gauge("parallel.spmd.devices", int(mesh.devices.size))


def launch_attrs(mesh, padded_rows: int,
                 segsum: Optional[str] = None) -> dict:
    """Attrs of a sharded rung's ``launch`` span (`timed_jit_call`);
    ``segsum`` is the segment-sum mode the rung traced, where it has one."""
    ndev = int(mesh.devices.size)
    attrs = {"devices": ndev, "rows_per_device": int(padded_rows) // ndev}
    if segsum is not None:
        attrs["segsum"] = segsum
    return attrs


def mesh_of_sharded_table(table):
    """The mesh a table's buffers are row-sharded over, or None when the
    table is not mesh-sharded (or the mesh has a single device)."""
    from ..parallel.dist_plan import mesh_for_table

    mesh = mesh_for_table(table)
    if mesh is None or mesh.devices.size < 2:
        return None
    return mesh


def mesh_key(mesh) -> Tuple[int, ...]:
    """Stable cache-key component for a mesh (device ids in mesh order)."""
    return tuple(int(d.id) for d in mesh.devices.flat)


def resolve_sharded_scan(context, node):
    """(table, mesh) when a TableScan reads a registered, device-resident
    (non-lazy), mesh-sharded table; None otherwise.  THE sharding-detection
    rule, shared by the estimator's per-device budgeting and the EXPLAIN
    LINT advisory so they can never disagree with the rungs.  Never touches
    lazy parquet containers (no accidental loads)."""
    if context is None:
        return None
    schema = getattr(context, "schema", {}).get(node.schema_name)
    dc = schema.tables.get(node.table_name) if schema else None
    if dc is None:
        return None
    from ..datacontainer import LazyParquetContainer

    if isinstance(dc, LazyParquetContainer):
        return None
    table = getattr(dc, "table", None)
    if table is None:
        return None
    mesh = mesh_of_sharded_table(table)
    if mesh is None:
        return None
    return table, mesh


class ColumnSpmdWrap:
    """shard_map wrapper around a traced pipeline callable.

    ``fn_raw(datas, valids, row_valid, params)`` is the raw (unjitted)
    pipeline function; ``valid_present[i]`` says whether column i carries a
    validity mask and ``has_row_valid`` whether the table is padded — the
    ``None`` slots are re-inserted inside the mapped function so the traced
    body is IDENTICAL to the single-chip trace, just over per-shard rows.

    ``out_specs`` follows shard_map semantics: ``P(None, ...)`` outputs are
    device-invariant (everything derived from psum/pmin/pmax partials),
    ``P(AXIS, ...)``/``P(..., AXIS)`` outputs stay sharded.
    """

    def __init__(self, fn_raw: Callable, mesh,
                 valid_present: Sequence[bool], has_row_valid: bool,
                 n_params: int, out_specs, check_vma: bool = True):
        self.mesh = mesh
        self.valid_present = tuple(bool(v) for v in valid_present)
        self.has_row_valid = bool(has_row_valid)
        n_cols = len(self.valid_present)
        n_valid = sum(self.valid_present)

        def packed_fn(datas, valids_p, row_valid_t, params):
            valids = []
            i = 0
            for present in self.valid_present:
                if present:
                    valids.append(valids_p[i])
                    i += 1
                else:
                    valids.append(None)
            rv = row_valid_t[0] if row_valid_t else None
            return fn_raw(tuple(datas), tuple(valids), rv, tuple(params))

        in_specs = (
            (P(AXIS),) * n_cols,
            (P(AXIS),) * n_valid,
            (P(AXIS),) * (1 if self.has_row_valid else 0),
            (P(),) * n_params,
        )
        self.mapped = shard_map(packed_fn, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs,
                                check_vma=check_vma)
        self.jitted = jax.jit(self.mapped)

    def pack_args(self, datas, valids, row_valid, params) -> Tuple:
        """(datas, valids, row_valid, params) -> the 4 packed positional
        arguments of the mapped/jitted callable."""
        valids_p = tuple(v for v, present in zip(valids, self.valid_present)
                         if present)
        rv = (row_valid,) if self.has_row_valid else ()
        return (tuple(datas), valids_p, rv, tuple(params))
