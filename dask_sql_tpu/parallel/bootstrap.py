"""Multi-host process bootstrap: the coordination layer that replaces the
reference's external dask scheduler.

Role parity: the reference connects every front-end to a scheduler address
(`Client(scheduler_address)` in reference server/app.py:249-252 and
cmd.py:207-214) and lets dask.distributed coordinate workers.  The TPU-native
equivalent is JAX's multi-controller runtime: every host runs the SAME
program, `jax.distributed.initialize` wires them into one runtime, and
`jax.devices()` then spans all hosts — meshes built over it place collectives
on ICI within a slice and DCN across slices with no further engine changes
(SURVEY.md §2.4).

Environment contract (mirrors the reference's scheduler-address argument):

    DSQL_COORDINATOR   host:port of process 0 (e.g. "10.0.0.1:8476")
    DSQL_NUM_PROCESSES total process count
    DSQL_PROCESS_ID    this process's rank (0-based)

`initialize_from_env()` is idempotent and a no-op when the variables are
absent (single-host operation needs no coordinator, exactly like running the
reference without a scheduler address).
"""
from __future__ import annotations

import logging
import os
from typing import Optional

logger = logging.getLogger(__name__)

_initialized = False


def initialize_from_env(timeout_s: Optional[int] = None) -> bool:
    """Join the multi-host runtime described by DSQL_* env vars.

    Returns True when running multi-host (after initialize), False for
    single-host.  Safe to call repeatedly; only the first call acts."""
    global _initialized
    if _initialized:
        return True
    coordinator = os.environ.get("DSQL_COORDINATOR")
    if not coordinator:
        return False
    num_processes = int(os.environ.get("DSQL_NUM_PROCESSES", "1"))
    process_id = int(os.environ.get("DSQL_PROCESS_ID", "0"))
    import jax

    kwargs = {}
    if timeout_s is not None:
        kwargs["initialization_timeout"] = timeout_s
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )
    _initialized = True
    logger.info("joined multi-host runtime: process %d/%d via %s",
                process_id, num_processes, coordinator)
    return True


def is_multihost() -> bool:
    import jax

    return jax.process_count() > 1


def process_index() -> int:
    import jax

    return jax.process_index()


def make_global_array(host_arr, sharding):
    """Place a host array under a (possibly multi-host) NamedSharding.

    Single-host this is jax.device_put; multi-host every process holds the
    SAME full host array (SPMD ingest — each host generated or read identical
    input) and contributes only its addressable shards."""
    import jax
    import numpy as np

    if not is_multihost():
        return jax.device_put(host_arr, sharding)
    host_arr = np.asarray(host_arr)
    return jax.make_array_from_callback(
        host_arr.shape, sharding, lambda idx: host_arr[idx])


def host_read(arr):
    """numpy value of a (possibly multi-host sharded) device array.

    Single-host (or fully-addressable) arrays read directly; global arrays
    spanning other processes are first replicated with an XLA all-gather —
    every process then reads its local replica (SPMD: all processes call
    this at the same point)."""
    import jax
    import numpy as np

    from ..utils import d2h_fetch

    with d2h_fetch(nbytes=getattr(arr, "nbytes", None)):
        if not hasattr(arr, "sharding") or getattr(
                arr, "is_fully_addressable", True):
            return np.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec

        sharding = arr.sharding
        rep = jax.jit(
            lambda x: x,
            out_shardings=NamedSharding(sharding.mesh, PartitionSpec()))(arr)
        return np.asarray(rep)


def all_processes_allgather(local_np):
    """Host-level allgather of small numpy arrays (result assembly on every
    host, e.g. pulling a replicated aggregate to the driver process)."""
    import jax

    if not is_multihost():
        return local_np
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(local_np)
