"""The five readers of what disturbed a window (PR 44: ``slow_visit_ms``,
``loop_offcpu_ms``, ``host_pause_ms``, ``jit_trace_ms_in_window``,
``window_ahead_avoidable_miss_share``) read a number — 0, not nothing —
on every cell, under ``--rehearse`` (which prints counters only: all
five read counters the program keeps, and are listed so). They are
looked up by name, wherever later entries leave them in the list."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import metrics, spec, work

RUN = os.path.join(spec.ROOT, "benchmarks", "run.py")
READERS = ("slow_visit_ms", "loop_offcpu_ms", "host_pause_ms",
           "jit_trace_ms_in_window", "window_ahead_avoidable_miss_share")


def cells():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def test_the_five_entries_are_there_and_list_no_cells():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in READERS:
        m = per_layer[name]
        assert m["moves"] == "out_tok_s" and m["better"] == "lower"
        assert m["source"] == "program_counter"
        assert "workloads" not in m
        reader = spec.load_layer_metric(m["name"])
        assert reader["source"]["span"] == "window"
        assert reader["layer"] == m["layer"] and reader["unit"] == m["unit"]


def test_a_program_without_the_series_reports_nothing_and_does_not_raise():
    """The parent of PR 44 under this PR's benchmark files."""
    old = {"parallax_visit_pack_ms_sum": 1.0, "parallax_visit_pack_ms_count": 1}
    for name in READERS:
        reader = spec.load_layer_metric(name)
        assert metrics.read_layer_metric(
            reader, {"scrape_w0": old, "scrape_w1": old}) is None
        assert metrics.read_layer_metric(reader, {}) is None
    # ... and one that has them at 0 reads 0.
    new = {"parallax_slow_visit_excess_ms_total": 0.0,
           "parallax_loop_offcpu_ms_total": 5.0,
           "parallax_host_pause_ms_total": 0.0,
           "parallax_jit_trace_ms_total": 7.5,
           "parallax_visit_window_ahead_avoidable_miss_sum": 3.0,
           "parallax_visit_window_ahead_avoidable_miss_count": 10}
    later = {k: v * 2 for k, v in new.items()}
    got = {name: metrics.read_layer_metric(
        spec.load_layer_metric(name), {"scrape_w0": new, "scrape_w1": later})
        for name in READERS}
    assert got == {"slow_visit_ms": 0.0, "loop_offcpu_ms": 5.0,
                   "host_pause_ms": 0.0, "jit_trace_ms_in_window": 7.5,
                   "window_ahead_avoidable_miss_share": 30.0}


@pytest.mark.parametrize("cell", cells())
def test_the_five_readers_read_a_number_on_every_cell(cell):
    # One device, whatever mesh the collecting process was given.
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", "3000000019",
         "--seconds", "3", "--trace", "2", "--rehearse"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=600,
        env=dict(env, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in READERS:
        assert isinstance(got.get(name), (int, float)), (name, got)
        assert got[name] >= 0
    assert got["window_ahead_avoidable_miss_share"] <= (
        100.0 - got["window_ahead_share"]) + 1e-9
    # A rehearsal's rows end all through, which no reason calls
    # avoidable; only the snapshots of a model that carries recurrent
    # state (and a pool too small) are.
    bench = spec.load()
    config = bench["configs"][bench["cells"][cell]["config"]]
    hf = dict(config["hf"], **config["bench"]["rehearse"])
    if not work.load_stage(hf, config["work"]["path"])["state_bytes"]:
        assert got["window_ahead_avoidable_miss_share"] == 0.0
