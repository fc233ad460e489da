"""`chip_smoke.py` rehearsed in-process on the CPU (``--allow-cpu``): every
phase and check runs, a step down the ladder fails it, and a CPU run is
never reported as a pass."""
import json

import pytest

import chip_smoke
from dask_sql_tpu import config as config_module
from dask_sql_tpu.resilience import faults
from dask_sql_tpu.serving import compile_cache


@pytest.fixture
def smoke(tmp_path, monkeypatch, capsys):
    """Run the smoke with its compile cache under tmp_path (the jax cache
    directory is process-global state: undone after); returns
    ``(exit code, [parsed stdout lines], last raw line)``."""
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    monkeypatch.setattr(compile_cache, "checkout_path",
                        lambda: str(tmp_path / "jax_cache"))
    compile_cache.disable()
    faults.reset()

    def run(*argv):
        rc = chip_smoke.main(list(argv))
        lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
        return rc, [json.loads(ln) for ln in lines], (lines or [""])[-1]

    yield run
    compile_cache.disable()
    faults.reset()


def _phases(objs):
    return [o["phase"] for o in objs if "phase" in o]


def test_rehearsal_runs_every_phase_and_never_reports_ok(smoke, tmp_path):
    rc, objs, last = smoke("--allow-cpu", "--rows", "50000")
    assert rc == 0, objs
    assert _phases(objs) == [
        "device", "generate", "load",
        "reference:q1", "library:q1:cold", "library:q1:warm",
        "reference:q6", "library:q6:cold", "library:q6:warm",
        "wire:boot_warmup", "wire:q1", "wire:q6", "summary"]
    by = {o["phase"]: o for o in objs if "phase" in o}
    assert by["device"]["compile_cache_dir"] == str(tmp_path / "jax_cache")
    assert by["load"]["rows"] == 50000
    assert by["load"]["device_bytes"] == 50000 * 24
    for label in ("library:q1:cold", "library:q6:cold"):
        assert by[label]["rung"] == ["compiled_aggregate"]
        assert by[label]["compile_spans"] == ["compile:compiled_aggregate"]
        assert by[label]["segsum"] == ["scatter"]  # auto on the CPU backend
    for label in ("library:q1:warm", "library:q6:warm", "wire:q1", "wire:q6"):
        assert by[label]["rung"] == ["compiled_aggregate"]
        assert by[label]["compile_spans"] == []
        assert by[label]["degraded"] == 0 and by[label]["rung_cpu"] == 0
    assert by["summary"]["parser"] in ("native", "python")
    assert by["summary"]["checks_failed"] == []
    # a CPU run is never a pass: checks passed, yet ok is false, and the
    # last line has exactly the form the driver reads
    assert objs[-2] == {"rehearsal_checks_passed": True}
    import jax

    assert last == json.dumps({"ok": False, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}})


def test_forced_step_down_fails_the_smoke(smoke):
    """`bench.py --inject`'s fault site: a compile failure the ladder
    absorbs (right rows, exit 0 everywhere else) must fail the smoke."""
    with config_module.set({"resilience.inject": "compile:always",
                            "resilience.inject.seed": 0}):
        rc, objs, last = smoke("--allow-cpu", "--rows", "50000")
    assert rc == 1
    failed = [o["check_failed"] for o in objs if "check_failed" in o]
    assert any("the ladder stepped down" in f for f in failed), failed
    assert any("no compiled rung answered" in f for f in failed), failed
    assert {"rehearsal_checks_passed": False} in objs
    assert json.loads(last)["ok"] is False


def test_sharded_rung_rehearsal(smoke):
    """``--chips 4`` on the virtual CPU devices: only the sharded phases,
    served by spmd_aggregate with the shards spread over the mesh."""
    rc, objs, last = smoke("--allow-cpu", "--rows", "50001", "--chips", "4")
    assert rc == 0, objs
    assert _phases(objs) == ["device", "generate", "load", "reference:q1",
                             "library:q1:cold", "library:q1:warm", "summary"]
    by = {o["phase"]: o for o in objs if "phase" in o}
    assert len(by["load"]["devices"]) >= 4
    assert by["library:q1:cold"]["rung"] == ["spmd_aggregate"]
    assert by["library:q1:warm"]["compile_spans"] == []
    assert json.loads(last)["ok"] is False


def test_refuses_the_cpu_without_the_flag(smoke):
    rc, objs, last = smoke("--rows", "1000")
    assert rc != 0
    assert objs == [] and last == ""  # no result line, nothing loaded
