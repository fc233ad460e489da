"""The reduction from a profiler trace to busy time, top operations and
labelled idle gaps: on made-up intervals, and on a small trace recorded on
the chip (``data/recorded.xplane.pb.gz``: ``--workload sf10_q1_library
--rehearse-rows 20000 --seconds 0.9 --trace 1 --keep-trace ...`` on a TPU v5e,
PR 26: 200,000 rows, a 0.31 s slice, 6,069 device operations)."""
import gzip
import os

import pytest

from perfbench import xplane

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "recorded.xplane.pb.gz")


def test_union_clip_and_gaps():
    busy = xplane.union([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert busy == [(1, 4), (5, 8)]
    assert xplane.clip(busy, 2, 6) == [(2, 4), (5, 6)]
    assert xplane.gaps(busy, 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert xplane.gaps([], 0, 10) == [(0, 10)]


def test_gap_labels_prefer_the_span_nearest_the_device():
    spans = [("wire", 0.0, 10.0), ("plan", 1.0, 2.0), ("execute", 2.0, 6.0),
             ("d2h", 6.0, 7.0)]
    assert xplane.label_gap((1.2, 1.4), spans) == "plan"
    assert xplane.label_gap((3.0, 4.0), spans) == "execute"
    assert xplane.label_gap((6.2, 6.4), spans) == "d2h"
    assert xplane.label_gap((8.0, 9.0), spans) == "wire"
    assert xplane.label_gap((8.0, 9.0),
                            spans + [("unspanned", 1.0, 9.5)]) == "unspanned"
    assert xplane.label_gap((11.0, 12.0), spans) == "client"


def test_reduce_on_made_up_trace():
    trace = {"mark_ns": 1e9, "lines": {},
             "devices": {"/device:TPU:0": [("fusion.1", 2.0e9, 2.5e9),
                                           ("fusion.2", 2.4e9, 3.0e9),
                                           ("copy", 4.0e9, 4.1e9)]}}
    # the mark was written at perf_counter 101.0: trace second 1.0 is 101.0
    out = xplane.reduce(trace, 101.0, 101.5, 105.0,
                        [("execute", 102.0, 104.05)])
    assert out["window_s"] == pytest.approx(3.5)
    assert out["busy_s"] == pytest.approx(1.1)
    assert out["device_ops"][0] == ["fusion.2", pytest.approx(0.6)]
    assert xplane.short_name(
        "%fusion.1 = u32[6000000]{0:T(1024)} fusion(u32[2526]{0:T(1024)} "
        "%constant.18), kind=kCustom") == "%fusion.1 u32[6000000] fusion"
    gaps = dict(out["idle_gaps"])
    assert gaps["execute"] == pytest.approx(1.0)   # 103.0 .. 104.0
    assert gaps["client"] == pytest.approx(0.5 + 0.9)
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(3.5)


def test_reduce_refuses_a_trace_without_mark_or_device():
    with pytest.raises(ValueError):
        xplane.reduce({"mark_ns": None, "devices": {"d": []}, "lines": {}},
                      0.0, 0.0, 1.0, [])
    with pytest.raises(ValueError):
        xplane.reduce({"mark_ns": 1.0, "devices": {}, "lines": {}},
                      0.0, 0.0, 1.0, [])


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the test")
def test_recorded_chip_trace(tmp_path):
    path = tmp_path / "recorded.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    trace = xplane.read(str(path))
    assert trace["mark_ns"] is not None
    assert list(trace["devices"]) == ["/device:TPU:0"]
    events = trace["devices"]["/device:TPU:0"]
    first = min(s for _, s, _ in events) / 1e9
    last = max(e for _, _, e in events) / 1e9
    # put the mark at perf second 0: the slice is then in trace seconds
    shift = -trace["mark_ns"] / 1e9
    out = xplane.reduce(trace, 0.0, first + shift, last + shift, [])
    assert 0.0 < out["busy_s"] <= out["window_s"]
    assert out["window_s"] == pytest.approx(last - first)
    assert len(out["device_ops"]) <= 10 and out["device_ops"][0][1] > 0
    assert all(len(name) <= 80 for name, _ in out["device_ops"])
    # the run that recorded it read 0.016084911 s busy in its 0.308874232 s
    # slice; the whole span of the device's events holds no less
    assert out["busy_s"] >= 0.016
    idle = sum(s for _, s in out["idle_gaps"])
    assert idle + out["busy_s"] == pytest.approx(out["window_s"])
    # half the slice holds no more busy time than the whole
    half = xplane.reduce(trace, 0.0, first + shift,
                         (first + last) / 2 + shift, [])
    assert half["busy_s"] <= out["busy_s"]
