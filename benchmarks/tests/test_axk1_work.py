"""A.X-K1's configuration, traffic, work file and readers against sums
done by hand (pure Python: tier-1 collects these through
``tests/test_benchmark_harness.py``).

The configuration, the cell and the five per-layer metrics were
``BENCHMARK.json``'s last entries when PR 51 added them; they are held
here by name and order, not by being last."""

import json
import math
import os

import pytest

from benchmarks.harness import metrics, spec, work

CONFIG = "a.x-k1-ep16-d7"
CELL = "a.x-k1-ep16-d7.decode-probe128-3k"
TRAFFIC = "decode-probe128-3k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["moe_gmm_us_per_layer_step", "moe_dispatch_us_per_layer_step",
       "mla_append_us_per_layer_step", "moe_held_hit_share",
       "moe_pairs_held_per_step"]

# By hand, at the published widths (ISSUE 51), in elements.
ATTENTION = (7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384
             + 8192 * 7168)
NORMS = 2 * 7168 + 1536 + 512
EXPERT = 3 * 7168 * 2048
ROUTER = 192 * 7168
DENSE_MLP = 3 * 7168 * 18432
HEAD = 7168 + 20480 * 7168


@pytest.fixture(scope="module")
def bench():
    return spec.load()


@pytest.fixture(scope="module")
def stage():
    c = spec.load_config(CONFIG)
    return c, work.load_stage(c["hf"], c["work"]["path"])


def test_the_configuration_is_the_published_one_cut_to_a_chips_share(bench):
    c = spec.load_config(CONFIG)
    assert c["bench"]["reduced"] == {
        "num_hidden_layers": {"published": 61, "run": 7},
        "experts_held": {"published": 192, "run": 12},
        "vocab_size": {"published": 163840, "run": 20480}}
    hf = c["hf"]
    assert hf["architectures"] == ["AXK1ForCausalLM"]
    assert (hf["n_routed_experts"], hf["num_experts_per_tok"],
            hf["experts_held"], hf["expert_offset"]) == (192, 8, 12, 0)
    assert hf["topk_method"] == "none"
    assert c["reference"]["import"] == "benchmarks.references.axk1"
    assert [(r["prompts"], r["prompt_tokens"], r["new_tokens"])
            for r in c["reference"]["rows"]] == [(12, 48, 16), (1, 2200, 8)]
    assert c["bench"]["serve_flags"] == ["--max-batch-size", "128",
                                         "--max-model-len", "4096"]
    toy = c["bench"]["rehearse"]
    assert (toy["n_routed_experts"], toy["experts_held"],
            toy["expert_offset"], toy["num_experts_per_tok"]) == (16, 4, 4, 2)
    assert toy["num_hidden_layers"] == 3      # a dense layer, two routed
    for key in ("architectures", "topk_method", "router_scores", "rope",
                "norms", "expert_share", "init", "weights", "tokenizer"):
        assert c["bench"]["assumed"][key]
    assert "noaux_tc" in c["bench"]["assumed"]["topk_method"]
    # Floors of the guide's section 4.
    assert hf["num_hidden_layers"] - hf["first_k_dense_replace"] >= 4
    assert hf["experts_held"] >= 8
    assert hf["vocab_size"] * 8 >= 163840
    entry = next(e for e in bench["raw"]["configs"] if e["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(c["bench"]["reduced"])
    assert bench["cells"][CELL]["chips"] == 1
    names = [e["name"] for e in bench["raw"]["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 5] == NEW
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of published configurations here")
    with open(CATALOG) as f:
        published = next(json.loads(line) for line in f
                         if json.loads(line)["name"] == "A.X-K1")
    assert c["bench"]["source"] == published["source_url"]
    changed = set(c["bench"]["reduced"]) - {"experts_held"}
    assert {k: hf.get(k, "absent") for k in published["config"]
            if k not in changed} == {
        k: v for k, v in published["config"].items() if k not in changed}


def test_the_cell_is_128_rows_that_fit_a_page_table_of_64(bench):
    t = spec.load_traffic(TRAFFIC)
    probe = spec.load_traffic("decode-probe8")
    own = {"clients", "warm_seconds", "prompt_tokens", "warmup", "assumed",
           "who", "why", "name"}
    assert {k: v for k, v in t.items() if k not in own} == {
        k: v for k, v in probe.items() if k not in own}
    assert (t["clients"], t["ramp_blocker_tokens"], t["ramp_whole"]) == (
        128, 1024, True)
    # Every sequence bucket is walked: the ramp prefills 5 prompts a step
    # beside the rows already decoding (PERF.md, PR 51).
    assert "seq_buckets" not in t["warmup"]
    assert (t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]) == (256, 512)
    out = t["output_tokens"]
    assert (out["min"], out["max"]) == (3072, 3328)
    c = spec.load_config(CONFIG)
    sizes = spec.serve_sizes(c["bench"]["serve_flags"])
    row = t["prompt_tokens"]["max"] + out["max"]
    assert row == 3840 < sizes["max_model_len"] == 4096
    assert sizes["max_batch_size"] == t["clients"]
    # 60 pages a row: 7,680 for the rows, 16 for the blocker.
    assert -(-row // 64) * 128 == 7680
    # At 128 rows a held expert is hit with probability 0.996 a step.
    assert 1 - (184 / 192) ** 128 == pytest.approx(0.9957, abs=1e-4)
    for name in NEW:
        assert bench["per_layer"][name]["cells"] == [CELL]
        assert bench["per_layer"][name]["moves"] == "out_tok_s"
    for name in ("decode_step_roofline", "attn_decode_roofline"):
        assert CELL in bench["per_layer"][name]["cells"]


def test_the_work_file_sums_a_step_by_hand(stage):
    c, stg = stage
    axk1 = spec.import_file("bench_work_", c["work"]["path"])
    assert ATTENTION == 101_122_048 == axk1.attention_elements(c["hf"]) - (
        1536 + 512)
    assert EXPERT == 44_040_192 == axk1.expert_elements(c["hf"])
    layers = stg["layers"]
    assert len(layers) == 7 == stg["paged_layers"]
    assert layers[0]["always"] == ATTENTION + NORMS + DENSE_MLP
    assert layers[0]["experts_held"] == 0
    for l in layers[1:]:
        assert l["always"] == ATTENTION + NORMS + ROUTER + EXPERT
        assert (l["expert"], l["experts_held"], l["experts_per_token"]) == (
            EXPERT, 12, 8)
    for l in layers:
        # One latent row, read once for all 64 heads.
        assert (l["entry_bytes"], l["entry_flops"], l["row_bytes"]) == (
            1152, 2 * 64 * (576 + 512), 64 * (576 + 512) * 2)
        assert l["state_bytes"] == 0
    assert axk1.head_elements(c["hf"]) == HEAD
    assert (stg["expert_layers"], stg["experts_held"]) == (6, 72)
    assert stg["entry_bytes"] == 8064           # a cached token, 7 layers
    assert stg["kernel"] == "^mla_decode_attention_pallas"
    # What every step reads, and with every held expert hit: 9.39 GB.
    always = 2 * stg["always"]
    assert round(always / 1e9, 2) == 3.05
    assert round((always + 72 * 2 * EXPERT) / 1e9, 2) == 9.39
    # The parts of the issue's arithmetic: a dense layer 0.995 GB, a
    # routed one 1.350, the head 0.294, the stage held 9.68.
    assert round(2 * (ATTENTION + DENSE_MLP) / 1e9, 3) == 0.995
    assert round(2 * (ATTENTION + ROUTER + 13 * EXPERT) / 1e9, 3) == 1.350
    assert round(2 * 20480 * 7168 / 1e9, 3) == 0.294
    held = (7 * (ATTENTION + NORMS) + DENSE_MLP + 6 * (ROUTER + 13 * EXPERT)
            + 2 * 20480 * 7168 + 7168)
    assert round(2 * held / 1e9, 2) == 9.68
    # The toy keeps the keys the work file reads.
    toy = work.load_stage(dict(c["hf"], **c["bench"]["rehearse"]),
                          c["work"]["path"])
    assert (toy["expert_layers"], toy["experts_held"],
            toy["experts_per_token"]) == (2, 8, 2)


def test_a_decode_step_at_128_rows_needs_what_the_issue_reckoned(stage):
    """100 steps of 128 rows at 2,000 of context, every held expert hit:
    9.39 GB of weights a step and 8,064 B of latent cache a live token:
    least step 12-16 ms, memory-bound."""
    c, stg = stage
    tokens = 100 * 128
    sw = {"decode_tokens": tokens, "decode_context_sum": tokens * 2000}
    attn = work.span_decode_attention(stg, sw, None, None)
    assert attn["bytes"] == tokens * 2000 * 8064 + tokens * (
        7 * 64 * 1088 * 2 + 8064)
    t0 = {}
    t1 = {work.EXPERTS_READ_SERIES: 100 * 72.0,
          work.PAIRS_HELD_SERIES: 100 * 384.0}
    experts = work.span_experts(stg, sw, 100, t0, t1)
    assert experts == {"experts_read": 7200.0, "pairs_held": 38400.0}
    step = work.decode_step_work(stg, 100, tokens, attn, experts)
    peaks = spec.peaks_for("TPU v5 lite")
    assert work.bound_by(step, peaks) == "memory"
    per_step = work.least_seconds(step, peaks) / 100
    assert 12e-3 < per_step < 16e-3
    # A count outside the bracket reads nothing: more experts than held.
    t1[work.EXPERTS_READ_SERIES] = 100 * 90.0
    assert work.span_experts(stg, sw, 100, t0, t1) is None
    # One expert layer's grouped matmuls at 64 pairs over 12 experts.
    axk1 = spec.import_file("bench_work_", c["work"]["path"])
    w = axk1.expert_layer_work(c["hf"], 64, 12)
    assert w["flops"] == 2 * EXPERT * 64
    assert w["bytes"] == 12 * EXPERT * 2 + 64 * (3 * 7168 + 2 * 2048) * 2
    assert work.least_seconds(w, peaks) == pytest.approx(1.294e-3, rel=1e-3)


def test_the_readers_read_what_the_program_exports_and_nothing_else(
        stage, bench):
    _, stg = stage
    read = lambda name, ctx: metrics.read_layer_metric(
        bench["per_layer"][name]["reader"], ctx)
    visits = "parallax_step_batch_tokens_count"
    w0 = {visits: 10.0, "parallax_moe_experts_read": 1000.0,
          "parallax_moe_pairs_held": 5000.0}
    w1 = {visits: 110.0, "parallax_moe_experts_read": 1000.0 + 800 * 71.7,
          "parallax_moe_pairs_held": 5000.0 + 800 * 384.0}
    ctx = {"scrape_w0": w0, "scrape_w1": w1, "work": stg}
    assert read("moe_held_hit_share", ctx) == pytest.approx(
        100 * 71.7 / 72)
    assert read("moe_pairs_held_per_step", ctx) == pytest.approx(384.0)
    # The parent of PR 51 exports neither series; a dense stage holds no
    # expert; no scrape, no number.
    bare = {k: {visits: v[visits]} for k, v in (("scrape_w0", w0),
                                                ("scrape_w1", w1))}
    dense = work.stage(spec.load_config("qwen2.5-3b")["hf"])
    for name in ("moe_held_hit_share", "moe_pairs_held_per_step"):
        assert read(name, dict(bare, work=stg)) is None
        assert read(name, {"scrape_w0": None, "scrape_w1": w1,
                           "work": stg}) is None
    assert read("moe_held_hit_share", dict(ctx, work=dense)) is None

    # The three device readers, over operations as a trace names them
    # (an event's name is the instruction's text): two whole executions
    # of the decode window, 8 steps each.
    gmm = spec.import_file("layer_metric_", bench["per_layer"][
        "moe_gmm_us_per_layer_step"]["reader"]["py"])
    assert gmm.result_of(
        "%fusion.13 = bf16[1024,7168]{1,0:T(8,128)(2,1)S(1)} fusion(...)"
    ) == ("bf16", (1024, 7168))
    assert gmm.result_of("%x = (f32[8]{0}, s32[]) tuple()") == ("f32", (8,))
    assert gmm.result_of("no shape here") is None
    ops = [
        ("mla_decode_attention_pallas.2",
         "%mla_decode_attention_pallas.2 = bf16[128,64,512]{2,1,0} "
         "custom-call(...)", 1e-3),
        ("gmm.1", "%gmm.1 = f32[1024,2048]{1,0} custom-call(...)", 6e-3),
        ("fusion.13", "%fusion.13 = bf16[1024,7168]{1,0} fusion(...)", 1e-3),
        ("fusion.145", "%fusion.145 = f32[128,192]{0,1} fusion(...)", 5e-4),
        ("fusion.26", "%fusion.26 = f32[128,7168]{1,0} fusion(...)", 9e-3),
        ("fusion.7", "%fusion.7 = bf16[9600,64,640]{2,1,0} fusion(...)",
         4e-4),
        ("fusion.8", "%fusion.8 = bf16[614400,640]{1,0} fusion(...)", 3e-4),
        ("fusion.9", "%fusion.9 = bf16[128,640]{1,0} fusion(...)", 5e-3),
    ]
    model = spec.load_config(CONFIG)["hf"]
    ctx = {"_decode_ops": (2, ops), "work": stg, "model": model}
    per = 1e6 / (2 * 8)
    assert read("moe_gmm_us_per_layer_step", ctx) == pytest.approx(
        6e-3 * per / 6)
    assert read("moe_dispatch_us_per_layer_step", ctx) == pytest.approx(
        1.5e-3 * per / 6)
    assert read("mla_append_us_per_layer_step", ctx) == pytest.approx(
        7e-4 * per / 7)
    for name in NEW[:3]:
        # No trace, no decode window in it, a dense stage: nothing.
        assert read(name, {"trace": None, "work": stg,
                           "model": model}) is None
        assert read(name, {"_decode_ops": None, "work": stg,
                           "model": model}) is None
    assert read("moe_gmm_us_per_layer_step", {
        "_decode_ops": (2, ops), "work": dense, "model": model}) is None
    assert read("mla_append_us_per_layer_step", {
        "_decode_ops": (2, ops), "work": dense,
        "model": spec.load_config("qwen2.5-3b")["hf"]}) is None
    assert math.isclose(gmm.STEPS_PER_EXECUTION, 8)
