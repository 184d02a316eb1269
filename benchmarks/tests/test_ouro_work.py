"""Ouro-2.6B's configuration, traffic, work file and readers against sums
done by hand (pure Python: tier-1 collects these through
``tests/test_benchmark_harness.py``).

The configuration, the cell and the three per-layer metrics are
``BENCHMARK.json``'s last entries, each at the end of its list
(PR 46)."""

import json
import os

import pytest

from benchmarks.harness import metrics, spec, trace_reduce, work

CONFIG = "ouro-2.6b"
CELL = "ouro-2.6b.decode-probe2-2k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def with_the_cell():
    """The benchmark the driver runs."""
    return spec.load()

# By hand, at the published widths (ISSUE 46).
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
HEAD = 2048 + 49152 * 2048
PASSES, LAYERS = 4, 48
WHOLE = 2_667_974_657


@pytest.fixture(scope="module")
def ouro_stage():
    c = spec.load_config(CONFIG)
    return c, work.load_stage(c["hf"], c["work"]["path"])


def test_the_configuration_is_the_published_one_with_nothing_reduced(
        with_the_cell):
    c = spec.load_config(CONFIG)
    assert c["bench"]["reduced"] == {}
    assert c["hf"]["architectures"] == ["OuroForCausalLM"]
    assert c["reference"]["import"] == "benchmarks.references.ouro"
    assert [(r["prompts"], r["prompt_tokens"], r["new_tokens"])
            for r in c["reference"]["rows"]] == [(4, 48, 16), (1, 1100, 8)]
    assert c["bench"]["serve_flags"] == ["--max-model-len", "4096",
                                         "--host-cache-bytes", "0"]
    assert c["bench"]["rehearse"]["total_ut_steps"] >= 2
    for key in ("norms", "pass_norm", "cache_index", "exit_gate",
                "attention", "head_dim", "residual_stream", "weights",
                "tokenizer", "sampling"):
        assert c["bench"]["assumed"][key]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of published configurations here")
    with open(CATALOG) as f:
        published = next(json.loads(line) for line in f
                         if json.loads(line)["name"] == "Ouro-2.6B")
    assert {k: c["hf"].get(k, "absent") for k in published["config"]} == (
        published["config"])
    assert c["bench"]["source"] == published["source_url"]
    b = with_the_cell
    assert list(b["configs"])[-1] == CONFIG
    assert list(b["cells"])[-1] == CELL
    assert b["cells"][CELL]["chips"] == 1


def test_the_cell_is_two_rows_that_fit_the_pool_without_a_preemption(
        with_the_cell):
    t = spec.load_traffic("decode-probe2-2k")
    probe = spec.load_traffic("decode-probe8")
    own = {"clients", "ramp_blocker_tokens", "output_tokens", "assumed",
           "who", "why", "name"}
    assert {k: v for k, v in t.items() if k not in own} == {
        k: v for k, v in probe.items() if k not in own}
    assert (t["clients"], t["ramp_blocker_tokens"], t["ramp_whole"]) == (
        2, 256, True)
    out = t["output_tokens"]
    assert (out["min"], out["max"]) == (2304, 2432)
    c = spec.load_config(CONFIG)
    longest = spec.serve_sizes(c["bench"]["serve_flags"])["max_model_len"]
    row = t["prompt_tokens"]["max"] + out["max"]
    assert row == 2688 <= longest == 4096
    # Two rows and the blocker, in pages of 64: 88 of the pool's ~93.
    pages = lambda n: -(-n // 64)
    assert 2 * pages(row) + pages(t["ramp_blocker_tokens"]) == 88
    b = with_the_cell
    for name in ("loop_attn_launches_per_step", "loop_attn_ms_per_step",
                 "kv_token_kib"):
        assert b["per_layer"][name]["cells"] == [CELL]
    assert list(b["per_layer"])[-3:] == [
        "loop_attn_launches_per_step", "loop_attn_ms_per_step",
        "kv_token_kib"]
    for name in ("decode_step_roofline", "attn_decode_roofline"):
        assert CELL in b["per_layer"][name]["cells"]


def test_the_work_file_lists_192_applications_by_hand(ouro_stage):
    c, stage = ouro_stage
    ouro = spec.import_file("bench_work_", c["work"]["path"])
    assert LAYER == 51_388_416
    layers = stage["layers"]
    assert len(layers) == PASSES * LAYERS == 192 == stage["paged_layers"]
    closing = [i for i, l in enumerate(layers) if l["always"] != LAYER]
    # The norm that closes passes 0-2 rides on their last application;
    # the last pass's is the head's.
    assert closing == [47, 95, 143]
    assert all(layers[i]["always"] == LAYER + 2048 for i in closing)
    for l in layers:
        assert (l["entry_bytes"], l["entry_flops"], l["row_bytes"]) == (
            8192, 4 * 2048, 8192)
        assert l["state_bytes"] == l["experts_held"] == 0
    assert ouro.head_elements(c["hf"]) == HEAD
    always = 192 * LAYER + 3 * 2048
    assert stage["always"] == always + HEAD
    # 19.73 GB of layers a step, 19.93 with the head.
    assert 2 * 192 * LAYER == 19_733_151_744
    assert round(2 * stage["always"] / 1e9, 2) == 19.93
    assert stage["entry_bytes"] == ouro.kv_bytes_per_token(c["hf"]) == (
        1_572_864)
    assert stage["kernel"] == "^gqa_fused_decode_pallas"
    # The parameters held: each layer once, both vocabulary matrices,
    # the final norm and the exit gate (which no step reads).
    assert LAYERS * LAYER + 2 * 49152 * 2048 + 2048 + 2049 == WHOLE
    # One pass is the dense block but for the two branch norms.
    once = work.load_stage(dict(c["hf"], total_ut_steps=1),
                           c["work"]["path"])
    dense = work.stage(c["hf"])
    assert [l["always"] - 2 * 2048 for l in once["layers"]] == [
        l["always"] for l in dense["layers"]]


def test_a_decode_step_at_2_rows_needs_what_the_issue_reckoned(ouro_stage):
    """100 steps of 2 rows at 1,000 of context: 19.93 GB of weights a
    step (24.3 ms at 819 GB/s) and 1.92 us of cache a live token."""
    _, stage = ouro_stage
    sw = {"decode_tokens": 200, "decode_context_sum": 200 * 1000}
    attn = work.span_decode_attention(stage, sw, None, None)
    assert attn["bytes"] == 200 * 1000 * 1_572_864 + 200 * (
        192 * 8192 + 1_572_864)
    assert attn["flops"] == 200 * 1000 * 192 * 8192
    step = work.decode_step_work(
        stage, 100, 200, attn, work.span_experts(stage, sw, 100, None, None))
    assert step["bytes"] == 100 * 2 * stage["always"] + attn["bytes"]
    peaks = spec.peaks_for("TPU v5 lite")
    assert work.bound_by(step, peaks) == "memory"
    per_step = work.least_seconds(step, peaks) / 100
    assert 24.3e-3 + 2 * 1000 * 1.92e-6 < per_step < 28.3e-3
    assert 1_572_864 / 819e9 == pytest.approx(1.92e-6, rel=2e-3)


def test_the_readers_read_what_the_program_exports_and_nothing_else(
        ouro_stage, with_the_cell):
    _, stage = ouro_stage
    b = with_the_cell
    read = lambda name, ctx: metrics.read_layer_metric(
        b["per_layer"][name]["reader"], ctx)
    # A trace recorded on the chip (``test_trace_reduce.py``'s): four
    # executions of a toy program, each with one ``tanh`` fusion; the
    # first and the last are the ones a tracer may have cut, so two are
    # counted, and 8 steps an execution make 1/8 of a launch a step.
    fixture = os.path.join(spec.BENCH_DIR, "fixtures", "toy_v5e.xplane.pb")
    red = trace_reduce.reduce_trace(fixture)
    toy = dict(stage, program="^jit_toy", kernel="tanh")
    ctx = {"trace": red, "work": toy}
    runs = spec.import_file("layer_metric_", b["per_layer"][
        "loop_attn_launches_per_step"]["reader"]["py"]).whole_executions(ctx)
    assert [n for n, _ in runs] == [1, 1]
    assert sum(s for _, s in runs) == pytest.approx(
        red["op_seconds"]["convolution_tanh_fusion.2"] / 2, rel=0.2)
    assert read("loop_attn_launches_per_step", ctx) == 1 / 8
    assert read("loop_attn_ms_per_step", ctx) == pytest.approx(
        red["op_seconds"]["convolution_tanh_fusion.2"] / 4 * 1e3 / 8,
        rel=0.2)
    # Both operations of an execution, and both halves of the quotient.
    both = dict(toy, kernel="fusion")
    assert read("loop_attn_launches_per_step",
                {"trace": red, "work": both}) == 2 / 8
    for name in ("loop_attn_launches_per_step", "loop_attn_ms_per_step"):
        # No decode kernel in the trace (the plain-XLA step), no decode
        # window, no trace or none on disk: nothing, never a guess.
        assert read(name, {"trace": red, "work": stage}) is None
        assert read(name, {"trace": red, "work": dict(
            toy, program="^jit_fn")}) is None
        assert read(name, {"trace": None, "work": toy}) is None
        assert read(name, {"trace": {"op_counts": {}}, "work": toy}) is None

    w1 = {"parallax_kv_bytes_per_token": 1_572_864.0}
    assert read("kv_token_kib", {"scrape_w0": {}, "scrape_w1": w1}) == 1536.0
    # The parent of PR 46 exports no such gauge.
    assert read("kv_token_kib", {"scrape_w0": {}, "scrape_w1": {}}) is None
    assert read("kv_token_kib", {"scrape_w0": None, "scrape_w1": w1}) is None
