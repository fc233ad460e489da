"""Two-process multi-host execution: the coordination layer works end-to-end.

Each subprocess gets 4 virtual CPU devices; `jax.distributed.initialize`
(driven by the DSQL_* env contract in parallel/bootstrap.py) joins them into
one 8-device runtime.  Both processes run the same SQL program over a
distributed table; process 0 checks values against pandas.  Parity target:
the reference's scheduler-connected execution
(reference server/app.py:249-252 Client(scheduler_address))."""
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WORKER = r"""
import os, sys
sys.path.insert(0, os.environ["DSQL_REPO"])
import numpy as np
import pandas as pd

from dask_sql_tpu import Context
from dask_sql_tpu.parallel import bootstrap

c = Context()  # joins the runtime via DSQL_* env
import jax
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, len(jax.devices())

rng = np.random.RandomState(7)
n = 10_000
df = pd.DataFrame({
    "k": rng.choice(["a", "b", "c", "d"], n),
    "v": rng.rand(n),
    "w": rng.randint(0, 100, n),
})
c.create_table("t", df, distributed=True)
got = c.sql(
    "SELECT k, SUM(v) AS sv, COUNT(*) AS n, AVG(w) AS aw FROM t "
    "GROUP BY k ORDER BY k",
    return_futures=False,
)
exp = (df.groupby("k").agg(sv=("v", "sum"), n=("v", "size"), aw=("w", "mean"))
       .reset_index().sort_values("k").reset_index(drop=True))
assert list(got["k"]) == list(exp["k"]), (list(got["k"]), list(exp["k"]))
np.testing.assert_allclose(got["sv"], exp["sv"], rtol=1e-9)
np.testing.assert_allclose(got["n"], exp["n"])
np.testing.assert_allclose(got["aw"], exp["aw"], rtol=1e-9)
print(f"proc {jax.process_index()} OK", flush=True)
"""


#: the exact XLA error a jaxlib built without CPU collectives (gloo/mpi)
#: raises on ANY cross-process op — an environment capability gap, not an
#: engine bug (fails identically on the unmodified tree in such containers)
_CPU_COLLECTIVES_UNSUPPORTED = (
    "Multiprocess computations aren't implemented on the CPU backend")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_aggregate(tmp_path):
    port = _free_port()
    procs = []
    logs = []
    for pid in (0, 1):
        env = dict(os.environ)
        env.pop("PYTHONPATH", None)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "DSQL_COORDINATOR": f"127.0.0.1:{port}",
            "DSQL_NUM_PROCESSES": "2",
            "DSQL_PROCESS_ID": str(pid),
            "DSQL_REPO": REPO,
        })
        log = open(tmp_path / f"proc{pid}.log", "w+")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", WORKER], env=env,
            stdout=log, stderr=subprocess.STDOUT))
    codes = [p.wait(timeout=560) for p in procs]
    outputs = []
    for log in logs:
        log.seek(0)
        outputs.append(log.read())
        log.close()
    if any(code != 0 for code in codes) and any(
            _CPU_COLLECTIVES_UNSUPPORTED in out for out in outputs):
        # this container's jaxlib CPU client has no cross-process
        # collectives implementation (no gloo/mpi backend compiled in):
        # every cross-host op fails with this exact XLA error regardless
        # of engine code.  Skip with the evidence; any OTHER failure mode
        # still fails the test so real regressions stay visible.
        pytest.skip(
            "jaxlib CPU backend lacks cross-process collectives in this "
            f"container ({_CPU_COLLECTIVES_UNSUPPORTED!r}); the two-process "
            "runtime cannot execute any collective here")
    for pid, (code, out) in enumerate(zip(codes, outputs)):
        assert code == 0, f"process {pid} failed:\n{out}"
        assert f"proc {pid} OK" in out
