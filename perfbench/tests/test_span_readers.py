"""Readers ``span_gap`` and ``registry_total`` on made-up records: spans are
plain objects with the fields the program's ``Span`` has, so the readers are
tested without the engine, as they have to work on a program that lacks the
spans and the histograms (the parent of the PR that added them)."""
from types import SimpleNamespace

import pytest

from perfbench.readers import registry_total, span_gap, span_mean


def span(name, t0, t1, kind="stage"):
    return SimpleNamespace(name=name, t0=t0, t1=t1, kind=kind)


def trace(spans, metrics=None):
    return SimpleNamespace(spans=spans, metrics=metrics)


def library(sent, done, spans, **extra):
    return {"sent": sent, "done": done, "trace": trace(spans), **extra}


def wire(sent, done, spans, **extra):
    return {"sent": sent, "done": done, "qid": "q", "trace": trace(spans),
            **extra}


def test_covered_merges_overlaps_and_clips():
    assert span_gap.covered([(1, 3), (2, 4), (6, 7)], 0, 10) == 4
    assert span_gap.covered([(-5, 2), (8, 20)], 0, 10) == 4
    assert span_gap.covered([(2, 4), (2, 4), (3, 3.5)], 0, 10) == 2
    assert span_gap.covered([], 0, 10) == 0


def test_library_extent_is_send_to_answer():
    rec = library(10.0, 11.0, [span("parse", 10.1, 10.3),
                               span("execute", 10.3, 10.9)])
    assert span_gap.read({}, {"records": [rec]}) == pytest.approx(200.0)


def test_wire_extent_is_first_span_to_last_span():
    # the client's 0.5 s around the server's trace is wire_ms's, not a gap
    rec = wire(10.0, 12.0, [span("queue_wait", 10.5, 10.6),
                            span("execute", 10.7, 11.4),
                            span("serialize", 11.4, 11.5)])
    assert span_gap.read({}, {"records": [rec]}) == pytest.approx(100.0)


def test_overlapping_stages_count_once_and_details_do_not_count():
    rec = library(0.0, 1.0, [span("execute", 0.0, 0.6),
                             span("account", 0.5, 0.8),
                             span("fetch", 0.85, 0.95, kind="detail"),
                             span("rung:x", 0.9, 0.9, kind="event")])
    assert span_gap.read({}, {"records": [rec]}) == pytest.approx(200.0)


def test_open_span_and_stage_outside_the_extent():
    rec = library(1.0, 2.0, [span("plan_lookup", 0.5, 1.2),  # starts early
                             span("execute", 1.2, None),     # still open
                             span("d2h", 1.9, 2.5)])         # ends late
    assert span_gap.read({}, {"records": [rec]}) == pytest.approx(700.0)


def test_records_without_trace_or_with_error_are_left_out():
    good = library(0.0, 1.0, [span("execute", 0.0, 0.9)])
    lost = {"sent": 0.0, "done": 5.0, "trace": None}
    failed = library(0.0, 9.0, [], error="boom")
    empty_wire = wire(0.0, 1.0, [])
    run = {"records": [lost, failed, empty_wire, good]}
    assert span_gap.read({}, run) == pytest.approx(100.0)
    assert span_gap.read({}, {"records": [lost, failed, empty_wire]}) is None
    assert span_gap.read({}, {"records": []}) is None


def test_mean_over_requests():
    recs = [library(0.0, 1.0, [span("execute", 0.0, 0.9)]),
            wire(0.0, 3.0, [span("execute", 1.0, 1.5),
                            span("serialize", 1.8, 2.0)])]
    assert span_gap.read({}, {"records": recs}) == pytest.approx(200.0)


def test_a_span_without_kind_reads_as_a_stage():
    bare = SimpleNamespace(name="execute", t0=0.0, t1=1.0)
    assert span_gap.read({}, {"records": [library(0.0, 1.0, [bare])]}) == 0.0


def test_stage_means_and_the_gap_add_up_to_the_extent():
    rec = library(0.0, 1.0, [span("plan_lookup", 0.0, 0.1),
                             span("execute", 0.1, 0.5),
                             span("launch", 0.1, 0.2, kind="detail"),
                             span("account", 0.6, 1.0)])
    run = {"records": [rec]}
    parts = sum(span_mean.read({"spans": [n]}, run)
                for n in ("plan_lookup", "execute", "account"))
    assert parts + span_gap.read({}, run) == pytest.approx(1000.0)
    assert span_mean.read({"spans": ["result_wait"]}, run) is None


# ---------------------------------------------------------- registry_total
class Registry:
    def __init__(self, sums):
        self.sums = sums

    def snapshot(self):
        return {"counters": {}, "gauges": {},
                "histograms": {k: {"count": 1, "sum": v}
                               for k, v in self.sums.items()}}


METRIC = {"reader": "registry_total", "histogram": "load.encode_ms",
          "scale": 0.001}


def test_registry_total_reads_the_first_trace_that_has_a_registry():
    registry = Registry({"load.encode_ms": 25500.0, "load.h2d_ms": 800.0})
    recs = [{"trace": None}, {"trace": trace([], None)},
            {"trace": trace([], registry)},
            {"trace": trace([], Registry({"load.encode_ms": 1.0}))}]
    assert registry_total.read(METRIC, {"records": recs}) == \
        pytest.approx(25.5)
    assert registry_total.read({"histogram": "load.h2d_ms"},
                               {"records": recs}) == pytest.approx(800.0)


def test_registry_total_returns_nothing_where_there_is_nothing_to_read():
    # the parent commit: a registry without the histogram
    recs = [{"trace": trace([], Registry({"query.execute_ms": 3.0}))}]
    assert registry_total.read(METRIC, {"records": recs}) is None
    assert registry_total.read(METRIC, {"records": [{"trace": None}]}) is None
    assert registry_total.read(METRIC, {"records": []}) is None
    assert registry_total.read(METRIC, {"records": [{}]}) is None
