"""A ``--rehearse`` run of each cell ends in one well-formed last line
saying ``"platform": "cpu"``; a later cell arrives as files and one entry."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import spec

RUN = os.path.join(spec.ROOT, "benchmarks", "run.py")
E2E_TIMES = {"out_tok_s", "ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}


def cells():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def rehearse(cell, trace, extra=()):
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", "3000000019",
         "--seconds", "3", "--trace", str(trace), "--rehearse", *extra],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"]          # no device metric
    assert not E2E_TIMES & set(line["metrics"])    # no time, no rate
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    return line


@pytest.mark.parametrize("cell", cells())
def test_rehearse_each_cell(cell):
    line = rehearse(cell, trace=1)
    assert line["metrics"]["prefix_hit_share"]["value"] == 0.0
    assert line["metrics"]["kv_preemptions"]["value"] == 0.0
    assert line["metrics"]["batch_tokens_per_visit"]["value"] > 0
    assert 0 < line["metrics"]["kv_live_share"]["value"] <= line["metrics"]["kv_pool_used_share"]["value"] <= 100


def test_a_later_cell_is_files_and_one_entry(tmp_path):
    """``qwen2.5-7b-d24.sessions-prefix``: sessions of turns over a shared
    system prompt. Two new files (a traffic mix, a copy of BENCHMARK.json
    with one more entry) and no edit to a file that is there."""
    traffic = os.path.join(spec.BENCH_DIR, "traffic", "sessions-prefix.json")
    assert not os.path.exists(traffic)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "qwen2.5-7b-d24.sessions-prefix"
    bench["workloads"].append({
        "name": cell, "config": "qwen2.5-7b-d24", "traffic": "sessions-prefix",
        "chips": 1, "why": "sessions of 3-6 turns over a shared 1,024-token system prompt"})
    bench_path = tmp_path / "BENCHMARK.json"
    bench_path.write_text(json.dumps(bench))
    try:
        with open(traffic, "w") as f:
            json.dump({
                "loop": "open",
                "arrival": {"process": "poisson", "rate_rps": 2.0},
                "warm_seconds": 8.0,
                "prompt_tokens": {"dist": "uniform", "min": 128, "max": 512},
                "output_tokens": {"dist": "uniform", "min": 64, "max": 256},
                "sampling": {"temperature": 0.7, "top_k": 20, "seed": "per_request"},
                "sharing": {"prefix_tokens": 1024, "share": 1.0, "pool": 1},
                "sessions": {"turns": {"dist": "uniform", "min": 3, "max": 6},
                             "think_s": {"dist": "fixed", "value": 1}},
                "who": "multi-turn chat over one system prompt",
                "why": "prefix cache under a small pool",
            }, f)
        line = rehearse(cell, trace=1,
                        extra=("--benchmark-json", str(bench_path)))
    finally:
        os.remove(traffic)
    # Every request shares the system prompt and later turns their history.
    assert line["metrics"]["prefix_hit_share"]["value"] > 20.0
