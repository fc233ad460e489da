"""The four-chip cell ``sf10_q1_sharded_4chip`` rehearsed on the CPU's virtual
devices, each run in a child process of its own (the device count is fixed
when JAX starts): sound, it comes out ``correct``; with the sharded rung made
to fail underneath, every request counts as failed and the ladder's record
fails the run; on another layout than the cell's the surface refuses to
start.  And ``roofline_mesh`` against ``roofline``."""
import json
import os
import subprocess
import sys

import pytest

from perfbench.readers import roofline, roofline_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "sf10_q1_sharded_4chip"
SEED = 2_147_483_929

#: what the child runs: ``run.main`` with the look for a chip skipped and
#: the settings of argv[1] put into the configuration's ``engine_config``,
#: as ``test_run.py::with_engine_config`` does in its own process
CHILD = """
import json, sys
from perfbench import run, traffic
settings = json.loads(sys.argv[1])
load = traffic.load
def patched(kind, name):
    loaded = load(kind, name)
    if kind == "configs":
        loaded["engine_config"].update(settings)
    return loaded
traffic.load = patched
run.chip_fault = lambda *a: None
sys.exit(run.main(sys.argv[2:]))
"""


def rehearse(devices, trace=0, **settings):
    """(exit code, result line or None, phase lines, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    # a stacked program met first inside the window would compile there
    # (the CPU's scatter batches, the chip's path is rehearsed unstacked)
    settings.setdefault("serving.batch.max_queries", 1)
    done = subprocess.run(
        [sys.executable, "-c", CHILD, json.dumps(settings),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "1.5",
         "--trace", str(trace), "--rehearse-rows", "30000"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(l) for l in done.stdout.splitlines()
             if l.startswith("{")]
    result = lines[-1] if lines and "correct" in lines[-1] else None
    return done.returncode, result, lines, done.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_sound_rehearsal_is_correct(trace):
    code, result, phases, err = rehearse(4, trace)
    assert code == 0, err[-2000:]
    assert result["correct"] is True, result
    assert result["attempted"] > 5 and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert all(c["value"] <= c["limit"]
               for c in result["compared"].values())
    window = [p for p in phases if p.get("phase") == "window"][0]
    assert window["rungs"] == ["rung:spmd_aggregate"]
    if trace:
        readings = [p for p in phases
                    if p.get("phase") == "rehearsal"][0]["cpu_readings"]
        assert readings["load_shard_s"] > 0
        phases_s = sum(readings[f"load_{p}_s"] for p in
                       ("convert", "encode", "h2d", "shard", "register"))
        load = [p for p in phases if p.get("phase") == "load"][0]
        assert 0.9 * load["seconds"] <= phases_s <= load["seconds"]


def test_a_rung_that_fails_underneath_is_not_correct():
    """The answers stay right (a single-chip rung gives them); the surface
    counts each as failed and the ladder's own record fails the run."""
    code, result, _, err = rehearse(
        4, **{"resilience.inject": "spmd:always"})
    assert code == 0, err[-2000:]
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["requests_failed"]["value"] == result["attempted"] > 0
    assert result["failed"] == result["attempted"]
    assert compared["ladder_step_downs"]["value"] > 0


def test_another_layout_than_the_cells_refuses_to_start():
    code, result, phases, err = rehearse(1)
    assert code != 0 and result is None
    assert "library_sharded" in err and "the cell's layout is 4" in err
    assert not [p for p in phases if p.get("phase") == "window"]


def test_roofline_mesh_reads_a_quarter_of_roofline():
    class Span:
        name, t0, t1 = "execute", 1.0, 2.0

    class Trace:
        spans = [Span()]

    run = {"profile": {"busy_s": 0.5}, "slice": (0.0, 4.0),
           "records": [{"query": "q", "trace": Trace()}],
           "queries": {"q": {"table": "t", "referenced_columns": ["a"]}},
           "tables": {"t": {"rows": 10**9, "itemsize": {"a": 8}}},
           "peaks": {"hbm_bytes_per_s": 8e11}}
    whole = roofline.read({}, run)
    assert whole == pytest.approx(100.0 * (8e9 / 8e11) / 0.5)
    assert roofline_mesh.read({"chips": 4}, run) == pytest.approx(whole / 4)
    assert roofline_mesh.read({"chips": 4}, {"profile": None}) is None
