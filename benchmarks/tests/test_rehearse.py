"""A ``--rehearse`` run of each cell ends in one well-formed last line
saying ``"platform": "cpu"``; a later cell arrives as files and one entry,
a later architecture as a configuration that names its own reference."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import server, spec

RUN = os.path.join(spec.ROOT, "benchmarks", "run.py")
E2E_TIMES = {"out_tok_s", "ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}


def cells():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def run(cell, trace, extra=()):
    return subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", "3000000019",
         "--seconds", "3", "--trace", str(trace), "--rehearse", *extra],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=600,
    )


def rehearse(cell, trace, extra=()):
    proc = run(cell, trace, extra)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"]          # no device metric
    assert not E2E_TIMES & set(line["metrics"])    # no time, no rate
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert_compared(line, proc.stderr)
    assert server.holds(line["compared"])
    return line


def assert_compared(line, stderr):
    """Every number ``correct`` compared, beside its limit: the last key
    of the last line, and the last lines of standard error."""
    numbers = line["compared"]
    assert list(line)[-1] == "compared"
    assert set(numbers) == {
        "positions", "logprob_gap_max", "ties", "tie_top2_gap_max",
        "divergences_untied", "repeat_identical", "requests_attempted",
        "requests_failed_or_short"}
    assert numbers["logprob_gap_max"]["limit"] == server.LOGPROB_TOL
    assert numbers["tie_top2_gap_max"]["limit"] == 2 * server.LOGPROB_TOL
    last = stderr.strip().splitlines()[-len(numbers):]
    assert [x.split(":")[0] for x in last] == [
        f"compared {name}" for name in numbers]


@pytest.mark.parametrize("cell", cells())
def test_rehearse_each_cell(cell):
    line = rehearse(cell, trace=1)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # A rehearsal prints the counters of the cell, and of no other cell.
    assert set(got) == {
        name for name, m in spec.load()["per_layer"].items()
        if cell in m["cells"] and m["source"] == "program_counter"}
    assert got["prefix_hit_share"] == 0.0
    assert got["kv_preemptions"] == 0.0
    assert got["batch_tokens_per_visit"] > 0
    assert 0 < got["kv_pool_used_share"] <= 100
    if "kv_live_share" in got:
        assert 0 < got["kv_live_share"] <= got["kv_pool_used_share"]


def test_a_split_ramp_starts_the_run_again_on_a_new_child():
    """One client of the first attempt sends after the window has opened
    (what the program's admission race does to a row that loses it): the
    run says so, stops that child, does everything once more and ends in
    the same well-formed line, ``ramp_attempts`` 2; the second attempt
    sends what the first would have sent."""
    cell = cells()[0]
    proc = run(cell, 0, ("--split-first-ramp", "2.0"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["ramp_attempts"] == 2
    log = [json.loads(x) for x in proc.stderr.splitlines()
           if x.startswith("{")]
    (split,) = [x for x in log if x["phase"] == "ramp_split"]
    assert split["attempt"] == 1 and split["in_flight"] < split["clients"]
    assert [x["phase"] for x in log].count("reference") == 2
    assert rehearse(cell, 0)["ramp_attempts"] == 1


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


# A reference module as a ``model_config`` PR brings it: here the dense
# block under another name, whole or with its last layer left out.
REFERENCE = '''"""Written by tests/test_rehearse.py."""
from benchmarks.harness import reference


def greedy_continuations(params, cfg, prompts, n_new):
    params = dict(params, layers=params["layers"][:len(params["layers"]) - DROP])
    return reference.greedy_continuations(params, cfg, prompts, n_new)
'''


@pytest.fixture()
def own_reference(tmp_path):
    """``qwen2.5-3b-ownref``: the 3B's configuration with a reference
    module and row shapes of its own. Three new files (configuration,
    module, a copy of BENCHMARK.json with two more entries) and no edit
    to a file that is there. Yields ``write(drop)`` -> (cell, extra)."""
    name, stem = "qwen2.5-3b-ownref", "ownref_for_test"
    cfg_path, mod_path = spec.config_path(name), spec.reference_path(stem)
    assert not os.path.exists(cfg_path) and not os.path.exists(mod_path)
    with open(spec.config_path("qwen2.5-3b")) as f:
        cfg = json.load(f)
    cfg["bench"]["reference"] = {"module": stem, "rows": [
        {"prompts": 2, "prompt_tokens": 24, "new_tokens": 8},
        {"prompts": 1, "prompt_tokens": 4096, "new_tokens": 4}]}
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    base = next(c for c in bench["configs"] if c["name"] == "qwen2.5-3b")
    bench["configs"].append(dict(
        base, name=name, file=os.path.relpath(cfg_path, spec.ROOT)))
    cell = name + ".decode-probe8"
    bench["workloads"].append({
        "name": cell, "config": name, "traffic": "decode-probe8-5k",
        "chips": 1, "why": "a configuration that names its own reference"})
    bench_path = tmp_path / "BENCHMARK.json"
    bench_path.write_text(json.dumps(bench))

    def write(drop):
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        with open(mod_path, "w") as f:
            f.write(REFERENCE.replace("DROP", str(drop)))
        return cell, ("--benchmark-json", str(bench_path))

    try:
        yield write
    finally:
        for path in (cfg_path, mod_path):
            if os.path.exists(path):
                os.remove(path)


def test_a_later_architecture_is_files_and_entries(own_reference):
    cell, extra = own_reference(drop=0)
    line = rehearse(cell, trace=2, extra=extra)
    assert line["metrics"]["batch_tokens_per_visit"]["value"] > 0
    with open(os.path.join(spec.ROOT, ".bench_work", cell,
                           "reference.json")) as f:
        ref = json.load(f)
    assert ref["module"] == "ownref_for_test"
    # Both row shapes ran (the long one cut to the rehearsal's sizes).
    assert [(len(r["prompt"]), len(r["tokens"])) for r in ref["rows"]] == [
        (24, 8), (24, 8), (48, 4)]


def test_a_reference_that_leaves_out_a_layer_fails_the_run(own_reference):
    """The comparison's own control at the rehearsal's size: with one
    layer of the mathematics left out on one side the run opens no
    window and ends in its line, ``correct`` false, with the number that
    failed beside its limit."""
    cell, extra = own_reference(drop=1)
    proc = run(cell, trace=0, extra=extra)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert (line["attempted"], line["failed"], line["metrics"]) == (0, 0, {})
    assert line["device"]["platform"] == "cpu"
    assert_compared(line, proc.stderr)
    numbers = line["compared"]
    assert not server.holds(numbers)
    # What failed says so: a logprob further from the reference's than
    # the tolerance, or a token the reference was sure of left.
    assert (numbers["logprob_gap_max"]["value"] > server.LOGPROB_TOL
            or numbers["tie_top2_gap_max"]["value"] > 2 * server.LOGPROB_TOL
            or numbers["divergences_untied"]["value"] == 1)
    # The repeat and the window were not reached.
    assert numbers["repeat_identical"]["value"] is None
    assert numbers["requests_attempted"]["value"] is None
    summary = [json.loads(x) for x in proc.stderr.splitlines()
               if x.startswith("{") and '"summary"' in x]
    assert "position" in summary[-1]["not_correct"]
