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


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    assert trace_reduce.reduce_trace(str(tmp_path)) is None
