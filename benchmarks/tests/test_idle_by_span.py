"""Device idle time by host span: the arithmetic on hand-made spans, the
attribution of a small trace recorded on the chip
(``fixtures/host_spans_v5e.xplane.pb``: ``host_spans.py --record`` on one
TPU v5e), and the two readers on a trace without the program's spans."""

import importlib.util
import os

import pytest

from benchmarks.harness import host_spans, spec

FIXTURE = os.path.join(spec.BENCH_DIR, "fixtures", "host_spans_v5e.xplane.pb")
NO_SPANS = os.path.join(spec.BENCH_DIR, "fixtures", "toy_v5e.xplane.pb")


def test_innermost_span_owns_each_piece():
    spans = [(0, 100, "visit"), (10, 30, "plan"), (30, 60, "pack"),
             (40, 50, "compile"), (120, 130, "gap")]
    assert host_spans.innermost_timeline(spans) == [
        (0, 10, "visit"), (10, 30, "plan"), (30, 40, "pack"),
        (40, 50, "compile"), (50, 60, "pack"), (60, 100, "visit"),
        (120, 130, "gap")]
    assert host_spans.merged([(5, 7), (0, 2), (1, 3), (7, 9)]) == [
        (0, 3), (5, 9)]


def test_a_known_gap_goes_to_a_known_span_and_the_rest_is_unattributed():
    timeline = host_spans.innermost_timeline(
        [(0, 100, "parallax.visit"), (20, 60, "parallax.engine.commit"),
         (200, 300, "parallax.visit")])
    gaps = [(25, 55), (90, 210), (400, 410)]
    assert host_spans.split_by_spans(gaps, timeline) == {
        "parallax.engine.commit": 30,
        "parallax.visit": 10 + 10,
        host_spans.UNATTRIBUTED: 100 + 10,
    }
    assert host_spans.split_by_spans([], timeline) == {}


def test_fixture_idle_lies_under_commit_and_between_visits():
    """The recorded toy: four visits whose ``engine.commit`` sleeps 4 ms
    while the device idles, and 2 ms under no span between two visits."""
    att = host_spans.attribute(FIXTURE)
    assert att["chips"] == 1
    by = att["by_span"]
    assert set(by) >= {"engine.commit", "engine.readback_wait",
                       host_spans.UNATTRIBUTED}
    # Three commits and three pauses lie between the device's first and
    # last operation (the fourth of each follows its last operation).
    assert 3 * 0.004 <= by["engine.commit"] < 3 * 0.0055
    assert 3 * 0.002 <= by[host_spans.UNATTRIBUTED] < 3 * 0.0035
    assert sum(by.values()) == pytest.approx(att["idle_s"], rel=1e-9)
    assert 0 < att["idle_s"] < att["span_s"]
    assert 2.5 < att["visits"] < 4.5
    assert att["idle_ms_per_visit"] == pytest.approx(
        att["idle_s"] * 1e3 / att["visits"])
    share = 100 * (1 - by[host_spans.UNATTRIBUTED] / att["idle_s"])
    assert att["attributed_share"] == pytest.approx(share)
    assert 40 < att["attributed_share"] < 90
    gaps = host_spans.idle_gaps(FIXTURE)
    assert [g[0] for g in gaps] == list(by) and gaps[0][1] == max(by.values())


@pytest.mark.parametrize("name", ["idle_ms_per_visit", "idle_attributed_share"])
def test_readers_leave_the_metric_out_without_the_programs_spans(name):
    path = spec.layer_metric_paths(name)[1]
    s = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    assert mod.reduce({"trace": None}) is None
    assert mod.reduce({}) is None
    # A trace of a program older than the spans: device operations, no
    # ``parallax.visit``.
    assert host_spans.attribute(NO_SPANS) is None
    assert mod.reduce({"trace": {"file": NO_SPANS}}) is None
    assert mod.reduce({"trace": {"file": FIXTURE}}) > 0
