"""The trace reduction on a small trace recorded on the chip
(``fixtures/toy_v5e.xplane.pb``: four runs of a toy jitted program on one
TPU v5e, ``trace_reduce.py --record``)."""

import os
import re

import pytest

from benchmarks.harness import metrics, spec, trace_reduce

FIXTURE = os.path.join(spec.BENCH_DIR, "fixtures", "toy_v5e.xplane.pb")


def test_union_and_gaps_on_hand_made_intervals():
    iv = [(0.0, 1.0), (0.5, 1.5), (3.0, 4.0), (3.2, 3.4), (6.0, 6.5)]
    assert trace_reduce.union_seconds(iv) == pytest.approx(1.5 + 1.0 + 0.5)
    gaps = trace_reduce.idle_gaps(iv)
    assert gaps[0] == (4.0, 2.0) and gaps[1] == (1.5, 1.5)
    assert trace_reduce.union_seconds([]) == 0.0


def test_family_names():
    assert trace_reduce.family("fusion.123") == "fusion"
    assert trace_reduce.family("convolution_tanh_fusion.2") == "convolution_tanh_fusion"
    assert trace_reduce.family("copy-start") == "copy-start"
    assert trace_reduce._short("%fusion.7 = bf16[8]{0} fusion(...)") == "fusion.7"


def test_fixture_reduces_to_busy_union_and_per_pattern_sums():
    red = trace_reduce.reduce_trace(FIXTURE)
    assert red["chips"] == 1
    # Four executions of one program, four operations each.
    assert list(red["module_counts"].values()) == [4]
    assert all(n == 4 for n in red["op_counts"].values())
    assert sum(red["op_counts"].values()) == 16
    # The toy's operations never overlap, so busy is their sum, and it is
    # a small share of the traced span (the host launches one at a time).
    assert red["busy_s"] == pytest.approx(sum(red["op_seconds"].values()), rel=1e-6)
    assert 0 < red["busy_s"] < red["span_s"]
    fusion = sum(s for k, s in red["op_seconds"].items() if re.search("fusion", k))
    copies = sum(s for k, s in red["op_seconds"].items() if re.search("^copy", k))
    assert fusion + copies == pytest.approx(red["busy_s"], rel=1e-6)
    assert fusion > 50 * copies
    b = trace_reduce.breakdown(red)
    assert b["device_ops"][0][0] in ("convolution_tanh_fusion", "fusion")
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][1] >= b["idle_gaps"][-1][1] > 0


def test_trace_readers_on_the_fixture():
    red = trace_reduce.reduce_trace(FIXTURE)
    peaks = spec.peaks_for("TPU v5 lite")
    seconds = sum(s for k, s in red["op_seconds"].items() if "tanh" in k)
    ctx = {"trace": red, "peaks": peaks,
           "span_work": {"toy": {"flops": 197e12 * seconds / 4, "bytes": 0},
                         "things": 8}}
    share = metrics.read_layer_metric({"name": "x", "source": {
        "kind": "trace", "pattern": "tanh", "reduce": "roofline_share",
        "work": "toy"}}, ctx)
    assert share == pytest.approx(25.0)
    per = metrics.read_layer_metric({"name": "y", "source": {
        "kind": "trace_module", "pattern": "jit_toy", "reduce": "ms_per_unit",
        "unit_of_work": "things"}}, ctx)
    assert per == pytest.approx(sum(red["module_seconds"].values()) * 1e3 / 8)
    # A reader that finds nothing to read returns nothing.
    assert metrics.read_layer_metric({"name": "z", "source": {
        "kind": "trace", "pattern": "no_such_kernel", "reduce": "roofline_share",
        "work": "toy"}}, ctx) is None


def test_device_events_are_cut_to_the_window_they_are_given():
    """``fixtures/host_spans_v5e.xplane.pb`` carries the program's two
    ``parallax.clock_sync`` marks. A window that ends in the middle of
    a device event keeps that event's part inside it and nothing after:
    busy time cannot pass the window (the refusal of PR 26)."""
    from jax.profiler import ProfileData

    fixture = os.path.join(spec.BENCH_DIR, "fixtures", "host_spans_v5e.xplane.pb")
    data = ProfileData.from_file(fixture)
    offset = trace_reduce.program_clock_offset_ns(data)
    assert offset is not None
    events = sorted(
        (int(ev.start_ns), int(ev.duration_ns), trace_reduce._short(ev.name))
        for plane in data.planes if trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines if line.name == trace_reduce.OPS_LINE
        for ev in line.events)
    whole = trace_reduce.reduce_trace(fixture)
    assert whole["clipped"] is False
    # From before the first event to the middle of the longest one.
    s, d, name = max(events, key=lambda e: e[1])
    t0 = events[0][0] - 1000 - offset
    t1 = s + d // 2 - offset
    red = trace_reduce.reduce_trace(fixture, window_ns=(t0, t1))
    assert red["clipped"] is True
    window_s = (t1 - t0) * 1e-9
    kept = [e for e in events if e[0] < s]
    assert red["busy_s"] == pytest.approx(
        sum(e[1] for e in kept) * 1e-9 + (d // 2) * 1e-9, rel=1e-9)
    assert red["busy_s"] < window_s < whole["busy_s"] + whole["span_s"]
    assert red["busy_s"] < whole["busy_s"]
    # The cut event counts as the part of an execution that lies inside.
    n_before = sum(1 for e in kept if e[2] == name)
    assert red["op_counts"][name] == pytest.approx(n_before + (d // 2) / d)
    # A window that holds every event changes nothing.
    wide = trace_reduce.reduce_trace(
        fixture, window_ns=(t0, events[-1][0] + events[-1][1] + 1000 - offset))
    assert wide["busy_s"] == pytest.approx(whole["busy_s"], rel=1e-12)
    assert wide["op_counts"] == whole["op_counts"]
    # A window no wider than one event: busy equals the window, never more.
    inside = trace_reduce.reduce_trace(
        fixture, window_ns=(s + d // 4 - offset, s + d // 2 - offset))
    assert inside["busy_s"] == pytest.approx((d // 2 - d // 4) * 1e-9)
    # A trace without the program's marks cannot be cut: reduced whole.
    toy = trace_reduce.reduce_trace(FIXTURE, window_ns=(0, 1))
    assert toy["clipped"] is False and toy["busy_s"] > 0


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    assert trace_reduce.reduce_trace(str(tmp_path)) is None
