"""The two readers of what set-up pays in Python for the programs it
loads (PR 45: ``setup_trace_ms``, ``setup_block_traces``): both move
``setup_s``, list no cells, and read the process's whole count as the
scrape at the window's end has it. They read counters the program
keeps, so a rehearsal prints them; a program without a series (the
parent of PR 45 has no ``parallax_block_traces_total``) reports nothing
there and raises nothing. Looked up by name, wherever later entries
leave them in the list."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import metrics, spec, work

RUN = os.path.join(spec.ROOT, "benchmarks", "run.py")
READERS = {"setup_trace_ms": ("parallax_jit_trace_ms_total", "ms"),
           "setup_block_traces": ("parallax_block_traces_total", "count")}


def test_the_two_entries_are_there_and_list_no_cells():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, (series, unit) in READERS.items():
        m = per_layer[name]
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["source"] == "program_counter" and m["unit"] == unit
        # The layer of ``decode_step_device_ms``, letter for letter.
        assert m["layer"] == per_layer["decode_step_device_ms"]["layer"]
        assert "workloads" not in m
        reader = spec.load_layer_metric(name)
        assert reader["source"] == {
            "kind": "metrics_series", "series": series, "reduce": "last",
            "span": "window"}
        assert reader["layer"] == m["layer"] and reader["unit"] == m["unit"]
        assert reader["moves"] == "setup_s"


def test_a_program_without_the_series_reports_nothing_and_does_not_raise():
    """The parent of PR 45 under this PR's benchmark files: it has the
    listener's series and no block counter."""
    parent_w0 = {"parallax_jit_trace_ms_total": 61250.5}
    parent_w1 = {"parallax_jit_trace_ms_total": 61250.5}
    ctx = {"scrape_w0": parent_w0, "scrape_w1": parent_w1}
    trace_ms, blocks = (spec.load_layer_metric(n) for n in READERS)
    assert metrics.read_layer_metric(trace_ms, ctx) == 61250.5
    assert metrics.read_layer_metric(blocks, ctx) is None
    for reader in (trace_ms, blocks):
        assert metrics.read_layer_metric(reader, {}) is None
        assert metrics.read_layer_metric(
            reader, {"scrape_w0": {}, "scrape_w1": {}}) is None
    # The whole count at the window's end, not the window's growth.
    change = {"scrape_w0": {"parallax_jit_trace_ms_total": 9000.0,
                            "parallax_block_traces_total": 146.0},
              "scrape_w1": {"parallax_jit_trace_ms_total": 9000.0,
                            "parallax_block_traces_total": 146.0}}
    assert metrics.read_layer_metric(trace_ms, change) == 9000.0
    assert metrics.read_layer_metric(blocks, change) == 146.0


@pytest.mark.parametrize("cell", ["qwen2.5-7b-d24.decode-probe8",
                                  "jamba2-3b.decode-probe8-10k"])
def test_a_rehearsal_counts_a_kind_of_block_a_program_not_a_layer(cell):
    # One device, whatever mesh the collecting process was given.
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", "3000000021",
         "--seconds", "3", "--trace", "2", "--rehearse"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=600,
        env=dict(env, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["setup_trace_ms"] > 0
    summary = [json.loads(x) for x in proc.stderr.splitlines()
               if x.startswith('{"phase": "summary"')][-1]
    built = summary["compile"] + summary["cache_hits"]
    # A hybrid's program has two kinds of block, a dense one's one;
    # not every program built holds the stage (samplers, gathers, copies).
    bench = spec.load()
    config = bench["configs"][bench["cells"][cell]["config"]]
    hf = dict(config["hf"], **config["bench"]["rehearse"])
    kinds = 2 if work.load_stage(hf, config["work"]["path"])["state_bytes"] else 1
    assert got["setup_block_traces"] % kinds == 0
    assert kinds <= got["setup_block_traces"] <= kinds * built
