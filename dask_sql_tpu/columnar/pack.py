"""Packed host transfer: N device buffers -> ONE device_get.

On an accelerator every dispatch/transfer costs a host-device round
trip; materializing a 10-column result as per-column `np.asarray` pays ~10+
of them.  This module bitcasts every 64-bit-encodable buffer into one
[n_buffers, n_rows] int64 matrix inside a single jitted kernel, pulls it
with one transfer, and recovers the original dtypes on host.

Lossless transport: f64 via bitcast, f32/f16 via exact widening to f64 then
bitcast (narrowing back is exact), ints/bools via sign-extending int64.

Trade-off: narrow buffers (bool masks, int32 dictionary codes) widen to 8B
for transport, so this path trades bytes for round trips — the right trade
on a latency-dominated link, the wrong one on a bandwidth-starved link
with wide string-heavy results (the CPU backend skips it entirely).
Relationship to physical/compiled.py pack_flat/unpack_row: that pair packs
DOMAIN-sized aggregate outputs into f64 during kernel tracing; this packs
ROW-sized raw columns post-execution — both must stay independently
lossless for their dtype sets.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

_jit_cache: dict = {}


def _build(sig):
    def fn(*bufs):
        cols = []
        for x, (kind, _) in zip(bufs, sig):
            if kind == "f64":
                cols.append(jax.lax.bitcast_convert_type(x, jnp.int64))
            elif kind == "f":
                cols.append(jax.lax.bitcast_convert_type(
                    x.astype(jnp.float64), jnp.int64))
            else:
                cols.append(x.astype(jnp.int64))
        return jnp.stack(cols)

    return jax.jit(fn)


def packed_host_arrays(bufs: List) -> Optional[List[np.ndarray]]:
    """All buffers as host numpy via one packed transfer; None if any
    buffer is host-resident or not 64-bit encodable (caller falls back)."""
    if len(bufs) < 2:
        return None
    sig = []
    n = None
    for x in bufs:
        if isinstance(x, np.ndarray) or not hasattr(x, "dtype"):
            return None
        dt = np.dtype(x.dtype)
        if x.ndim != 1:
            return None
        if n is None:
            n = x.shape[0]
        elif x.shape[0] != n:
            return None
        if dt == np.float64:
            sig.append(("f64", dt))
        elif dt.kind == "f":
            sig.append(("f", dt))
        elif dt.kind in "iub":
            sig.append(("i", dt))
        else:
            return None
    # keyed by signature only: jax.jit re-specializes per input shape
    # internally, so distinct row counts share one function object
    key = tuple(sig)
    fn = _jit_cache.get(key)
    if fn is None:
        fn = _build(sig)
        _jit_cache[key] = fn
    from ..config import config as _config
    from ..resilience import faults
    from ..utils import d2h_fetch

    # fault site ``d2h`` (resilience/faults.py): the packed transfer is
    # the one device->host transfer of a result that can fail — injected
    # here as a retryable TransientExecutionError so the serving worker's
    # backoff retry (never the rung breaker) absorbs it
    faults.maybe_inject("d2h", _config)
    with d2h_fetch():
        packed = np.asarray(jax.device_get(fn(*bufs)))
    out = []
    for i, (kind, dt) in enumerate(sig):
        row = np.ascontiguousarray(packed[i])
        if kind == "f64":
            out.append(row.view(np.float64))
        elif kind == "f":
            out.append(row.view(np.float64).astype(dt))
        elif dt.kind == "b":
            out.append(row.astype(bool))
        else:
            out.append(row.astype(dt))
    return out
