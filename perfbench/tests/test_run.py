"""One whole run of each cell's harness on the CPU at a cut row count, with
the look for a chip skipped: sound, it comes out ``correct``; with the timed
path broken underneath (an answer altered where the engine produces it, a
count off by one, a forced step down the ladder) or the float32 control in
the engine's place, it comes out not correct.  And the bytes of a scan."""
import json

import pytest

from perfbench import compare, run, tools, traffic
from perfbench.readers import roofline

SEED = 2_147_483_777
LOAD = traffic.load


@pytest.fixture(autouse=True)
def engine_config_restored():
    """``run.main`` writes the cell's ``engine_config`` into the engine's
    process-wide configuration; each test leaves it as it found it."""
    from dask_sql_tpu import config

    before = dict(config.config._values)
    yield
    with config.config._lock:
        config.config._values.clear()
        config.config._values.update(before)


def with_engine_config(monkeypatch, **settings):
    load = traffic.load

    def patched(kind, name):
        loaded = load(kind, name)
        if kind == "configs":
            loaded["engine_config"].update(settings)
        return loaded

    monkeypatch.setattr(traffic, "load", patched)


def drive(capsys, monkeypatch, cell, *extra, rows=30_000, main=run.main):
    monkeypatch.setattr(run, "chip_fault", lambda *a: None)
    if traffic.load is LOAD:
        # on the TPU the blocked-matmul segment sum is never stacked; on the
        # CPU (scatter) it is, and a stacked program met first inside the
        # window compiles there and fails its request, as it should: keep
        # the CPU run like the chip's
        with_engine_config(monkeypatch, **{"serving.batch.max_queries": 1})
    code = main(["--workload", cell, "--seed", str(SEED), "--seconds",
                 "1.5", "--trace", "0", "--rehearse-rows", str(rows), *extra])
    assert code == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    return lines[-1], lines[:-1]


CELLS = ["sf10_q1_library", "sf1_mix_wire"]


@pytest.mark.parametrize("cell", CELLS + ["sf10_q1_interval_library"])
def test_sound_run_is_correct(capsys, monkeypatch, cell):
    result, phases = drive(capsys, monkeypatch, cell)
    assert result["correct"] is True, result
    assert result["attempted"] > 10 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert result["metrics"] == {}  # a rehearsal reports no metric


def alter_answers(monkeypatch, change):
    from dask_sql_tpu.context import TpuFrame

    compute = TpuFrame.compute

    def altered(self, *args, **kwargs):
        frame = compute(self, *args, **kwargs)
        return change(frame.copy())

    monkeypatch.setattr(TpuFrame, "compute", altered)


def scale_last_float(frame, by=1.0 + 1e-5):
    name = [c for c in frame.columns if frame[c].dtype.kind == "f"][-1]
    frame[name] = frame[name] * by
    return frame


@pytest.mark.parametrize("cell", CELLS)
def test_altered_sum_is_not_correct(capsys, monkeypatch, cell):
    alter_answers(monkeypatch, scale_last_float)
    result, _ = drive(capsys, monkeypatch, cell)
    assert result["correct"] is False
    over = [k for k, c in result["compared"].items()
            if c["value"] > c["limit"]]
    assert over and all(k.startswith("rel_err.") for k in over), over


def test_count_off_by_one_is_not_correct(capsys, monkeypatch):
    def one_more(frame):
        frame["count_order"] = frame["count_order"] + 1
        return frame

    alter_answers(monkeypatch, one_more)
    result, _ = drive(capsys, monkeypatch, "sf10_q1_library")
    assert result["correct"] is False
    assert result["compared"]["answers_wrong"]["value"] == result["attempted"]


def test_step_down_the_ladder_is_not_correct(capsys, monkeypatch):
    """The answers stay right (the CPU rung gives them); the ladder's own
    record fails the run."""
    with_engine_config(monkeypatch, **{"resilience.inject": "compile:always"})
    result, _ = drive(capsys, monkeypatch, "sf10_q1_library")
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["ladder_step_downs"]["value"] > 0
    assert compared["not_on_compiled_rung"]["value"] > 0


@pytest.mark.parametrize("name", ["tpch_q1", "tpch_q6"])
def test_float32_control_is_not_correct_at_sf1_size(name):
    """The reference in float32 (products and sums) in the engine's place on
    a window that sent every parameter set, at SF1's 6M rows, through the
    harness's own comparison: not within the limits, by the window's widest
    gap; the reference's own answers in the same place are."""
    from perfbench.datagen import tpch_lineitem

    query = traffic.load("queries", name)
    module = run.plugin("references", query["reference"])
    arrays = tpch_lineitem.generate(6_000_000, SEED)
    reference = module.Reference(arrays)
    records = [{"query": name, "params": params,
                "answer": reference.answer(params),
                "spans": ["rung:compiled_aggregate"]}
               for params in traffic.all_params(query)]
    queries, references = {name: query}, {name: reference}
    sound = compare.compare_window([dict(r) for r in records], queries,
                                   references, {})
    assert sound["within"] is True
    verdict = run.control_verdict("float32", records, queries, references,
                                  arrays, {})
    assert verdict["parameter_sets"] == len(records)
    assert verdict["within"] is False
    over = [k for k, c in verdict["compared"].items()
            if c["value"] > c["limit"]]
    assert over == [f"rel_err.{name}"]


def test_tools_put_the_control_through_the_comparison(capsys, monkeypatch):
    result, phases = drive(
        capsys, monkeypatch, "sf1_mix_wire", main=lambda argv: tools.main(
            ["--control", "float32", "--", *argv]))
    control = [p for p in phases if p.get("phase") == "control"][0]
    assert result["correct"] is True
    assert control["parameter_sets"] > 10
    assert set(control["compared"]) == set(result["compared"])
    assert isinstance(control["within"], bool)


def test_latency_percentile_counts_a_failed_request_as_slower_than_any():
    from perfbench.readers import latency_percentile

    records = [{"sent": 0.0, "done": (i + 1) / 1e3, "answer": {}}
               for i in range(40)]
    metric = {"percentile": 95}
    assert latency_percentile.read(metric, {"records": records}) \
        == pytest.approx(38.0)
    records[3]["error"] = "refused"
    assert latency_percentile.read(metric, {"records": records}) \
        == pytest.approx(39.0)
    for rec in records[:3]:
        rec["answer"] = None
    assert latency_percentile.read(metric, {"records": records}) is None
    assert latency_percentile.read(metric, {"records": []}) is None


def test_scan_bytes_per_row():
    table = {"rows": 1000, "itemsize": {
        "l_returnflag": 4, "l_linestatus": 4, "l_quantity": 2,
        "l_extendedprice": 8, "l_discount": 2, "l_tax": 2, "l_shipdate": 2}}
    assert roofline.scan_bytes(traffic.load("queries", "tpch_q1"),
                               table) == 24 * 1000
    assert roofline.scan_bytes(traffic.load("queries", "tpch_q6"),
                               table) == 14 * 1000


def test_loaded_table_has_those_widths(capsys, monkeypatch):
    _, phases = drive(capsys, monkeypatch, "sf10_q1_library")
    load = [p for p in phases if p.get("phase") == "load"][0]
    table = load["tables"]["lineitem"]
    assert roofline.scan_bytes(traffic.load("queries", "tpch_q1"),
                               table) == 24 * table["rows"]
    assert roofline.scan_bytes(traffic.load("queries", "tpch_q6"),
                               table) == 14 * table["rows"]
