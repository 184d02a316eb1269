"""The work formulas against numbers worked by hand from the configs, and
the two roofline readers on hand-built ``ctx`` dicts."""

import json

import pytest

from benchmarks.harness import metrics, spec, work

PEAKS = spec.peaks_for("TPU v5 lite")


def cfg(name):
    return spec.load_config(name)["hf"]


def test_kv_bytes_per_token():
    # K and V: 4 kv heads x 128 x 2 bytes each, 24 layers.
    assert work.kv_bytes_per_token(cfg("qwen2.5-7b-d24")) == 2 * 4 * 128 * 2 * 24 == 49152
    # 2 kv heads, 36 layers.
    assert work.kv_bytes_per_token(cfg("qwen2.5-3b")) == 2 * 2 * 128 * 2 * 36 == 36864


def test_weight_bytes():
    # 7B at 24 layers: q 3584, kv 512 each, inter 18944, untied head.
    layer = (3584 * 4608 + 4608 + 3584 * 3584 + 3 * 3584 * 18944 + 2 * 3584)
    assert layer == 233_057_792
    total = 24 * layer + 2 * 152064 * 3584 + 3584
    assert work.weight_bytes(cfg("qwen2.5-7b-d24")) == 2 * total == 13_366_770_688
    # 3B: q 2048, kv 256 each, inter 11008, 36 layers, tied head.
    layer = (2048 * 2560 + 2560 + 2048 * 2048 + 3 * 2048 * 11008 + 2 * 2048)
    assert layer == 77_076_992
    total = 36 * layer + 151936 * 2048 + 2048
    assert work.weight_bytes(cfg("qwen2.5-3b")) == 2 * total == 6_171_877_376


def test_attn_decode_work_is_memory_bound():
    c = cfg("qwen2.5-7b-d24")
    w = work.attn_decode_work(c, context=1000)
    # 4 * 28 heads * 128 * 1000 positions * 24 layers.
    assert w["flops"] == 4 * 28 * 128 * 1000 * 24 == 344_064_000
    # 1001 tokens of KV (the context read, the new token written) plus
    # the query and output rows (2 * 28 * 128 * 2 bytes) in 24 layers.
    assert w["bytes"] == 1001 * 49152 + 2 * 28 * 128 * 2 * 24 == 49_545_216
    assert work.bound_by(w, PEAKS) == "memory"
    assert work.least_seconds(w, PEAKS) == pytest.approx(49_545_216 / 819e9)


def test_attn_prefill_work():
    c = cfg("qwen2.5-3b")
    w = work.attn_prefill_work(c, 0, 1024)
    # sum_{p<1024} (p + 1) = 1024 * 1025 / 2 = 524800 query-key pairs.
    assert w["flops"] == 4 * 16 * 128 * 524800 * 36 == 154_769_817_600
    assert w["bytes"] == 2048 * 36864 + 2 * 1024 * 16 * 128 * 2 * 36
    assert work.bound_by(w, PEAKS) == "compute"
    # A chunk [1024, 2048) of a longer prompt attends to what precedes it.
    w2 = work.attn_prefill_work(c, 1024, 2048)
    pairs = 2048 * 2049 // 2 - 524800
    assert w2["flops"] == 4 * 16 * 128 * pairs * 36
    whole = work.attn_prefill_work(c, 0, 2048)
    assert whole["flops"] == w["flops"] + w2["flops"]


def test_span_work_counts_tokens_in_the_span():
    from benchmarks.harness.loadgen import Req, Result

    c = cfg("qwen2.5-3b")
    r = Result(req=Req(due=0, prompt=[1] * 100, max_tokens=5, seed=0))
    r.chunks = [(1.0, 1), (2.0, 2), (3.0, 2)]
    r.usage = {"prompt_tokens_details": {"cached_tokens": 0}}
    sw = work.span_work([r], 1.5, 2.5, c)
    # Tokens 1 and 2 (0-based) arrive at t=2.0: contexts 101 and 102.
    assert sw["decode_tokens"] == 2 and sw["prompts"] == 0
    assert sw["attn_decode"]["flops"] == 4 * 16 * 128 * (101 + 102) * 36
    sw = work.span_work([r], 0.5, 1.5, c)
    assert sw["prompts"] == 1 and sw["prompt_tokens"] == 100
    assert sw["attn_prefill"] == work.attn_prefill_work(c, 0, 100)


def test_span_work_sums_that_hold_for_any_architecture():
    from benchmarks.harness.loadgen import Req, Result

    c = cfg("qwen2.5-3b")
    a = Result(req=Req(due=0, prompt=[1] * 100, max_tokens=5, seed=0))
    a.chunks = [(1.0, 1), (2.0, 2), (3.0, 2)]
    a.usage = {"prompt_tokens_details": {"cached_tokens": 0}}
    # A prompt of 300 of which 64 came from the prefix cache; its first
    # token and two more arrive at t=2.2.
    b = Result(req=Req(due=0, prompt=[1] * 300, max_tokens=9, seed=0))
    b.chunks = [(2.2, 3), (4.0, 6)]
    b.usage = {"prompt_tokens_details": {"cached_tokens": 64}}
    sw = work.span_work([a, b], 1.5, 2.5, c)
    # a's tokens 1, 2 at contexts 101, 102; b's tokens 1, 2 at 301, 302.
    assert sw["decode_tokens"] == 4
    assert sw["decode_context_sum"] == 101 + 102 + 301 + 302
    # b's prompt finished in the span: positions 64..299 attend to p + 1.
    assert sw["prompts"] == 1
    assert sw["prefill_new_tokens"] == sw["prompt_tokens"] == 236
    assert sw["prefill_pair_sum"] == sum(p + 1 for p in range(64, 300))
    assert work.causal_pairs(0, 1024) == 524800
    # The keys it had keep their values.
    assert sw["attn_decode"]["flops"] == 4 * 16 * 128 * 806 * 36
    assert sw["attn_prefill"] == work.attn_prefill_work(c, 64, 300)
    assert sw["prompt_ktok"] == 0.236
    empty = work.span_work([a, b], 10.0, 11.0, c)
    assert (empty["decode_context_sum"], empty["prefill_new_tokens"],
            empty["prefill_pair_sum"]) == (0, 0, 0)


def test_decode_step_weights_leave_out_the_embedding_table():
    # 7B at 24 layers, untied: the layers, the final norm, one head matrix.
    c = cfg("qwen2.5-7b-d24")
    assert work.decode_step_weight_bytes(c) == 2 * (
        24 * 233_057_792 + 3584 + 152064 * 3584) == 12_276_775_936
    assert work.weight_bytes(c) - work.decode_step_weight_bytes(c) \
        == 2 * 152064 * 3584          # exactly one embedding table
    # 3B: the head is the tied embedding, read as the head; nothing left out.
    c = cfg("qwen2.5-3b")
    assert work.decode_step_weight_bytes(c) == work.weight_bytes(c) \
        == 6_171_877_376
    # EvaByte: 16 layers without biases, the final norm, head 0's 320 rows
    # of the 2,560 the head matrix holds.
    c = cfg("evabyte-6.5b-d16")
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096
    assert layer == 202_383_360
    assert work.decode_step_weight_bytes(c) == 2 * (
        16 * layer + 4096 + 320 * 4096) == 6_478_897_152
    assert work.weight_bytes(c) - work.decode_step_weight_bytes(c) \
        == 2 * 320 * 4096


SERIES = "parallax_eva_entries_attended"
OLD_DENSE_READER = {"name": "old", "source": {
    "kind": "trace", "pattern": "^gqa_fused_decode_pallas",
    "reduce": "roofline_share", "work": "attn_decode"}}


reader = spec.load_layer_metric


def hand_ctx(model, sw, t0=None, t1=None):
    """What ``metrics.read_layer_metric`` hands a reader, built by hand:
    0.4 s of the decode kernel in two layers' events beside another
    operation, 12.5 executions (one cut by the span's end) of the K=8
    window program in 2.0 s."""
    return {"trace": {"op_seconds": {"gqa_fused_decode_pallas.3": 0.25,
                                     "gqa_fused_decode_pallas.7": 0.15,
                                     "fusion.12": 9.0},
                      "module_seconds": {"jit_fn(1234)": 2.0,
                                         "jit__stage_fn(99)": 0.5},
                      "module_counts": {"jit_fn(1234)": 12.5,
                                        "jit__stage_fn(99)": 3}},
            "span_work": sw, "scrape_t0": t0, "scrape_t1": t1,
            "model": model, "peaks": PEAKS}


def dense_span_work(c):
    from benchmarks.harness.loadgen import Req, Result

    rows = []
    for plen in (100, 300):
        r = Result(req=Req(due=0, prompt=[1] * plen, max_tokens=801, seed=0))
        r.chunks = [(0.5, 1)] + [(1.0 + 0.01 * i, 8) for i in range(100)]
        r.usage = {"prompt_tokens_details": {"cached_tokens": 0}}
        rows.append(r)
    return work.span_work(rows, 0.9, 3.0, c)


@pytest.mark.parametrize("name", ["qwen2.5-7b-d24", "qwen2.5-3b"])
@pytest.mark.parametrize("scrapes", [
    (None, None),                                  # no scrape at all
    ({}, {"parallax_step_batch_tokens_sum": 9.0}),  # no such series
    ({SERIES: 5.0}, {SERIES: 5.0 + 7.0}),          # outside the bracket
], ids=["no-scrape", "no-series", "outside-bracket"])
def test_attn_decode_roofline_on_a_dense_model_is_the_old_reader(name, scrapes):
    c = cfg(name)
    sw = dense_span_work(c)
    assert sw["decode_tokens"] == 1600
    assert sw["decode_context_sum"] == 800 * (100 + 300) + 2 * sum(range(1, 801))
    ctx = hand_ctx(c, sw, *scrapes)
    got = metrics.read_layer_metric(reader("attn_decode_roofline"), ctx)
    # Term for term the sum of attn_decode_work over the span's tokens.
    assert got == pytest.approx(
        metrics.read_layer_metric(OLD_DENSE_READER, ctx), rel=1e-12)
    assert got == pytest.approx(
        100 * sw["attn_decode"]["bytes"] / 819e9 / 0.4, rel=1e-12)


# 640 decode tokens at contexts of ~10k: the program's count must lie
# between 6,400,000 / 16 and 6,400,000 entries.
EVA_SPAN_WORK = {"decode_tokens": 640, "decode_context_sum": 6_400_000}


def test_attn_decode_roofline_on_evabyte_reads_the_programs_entries():
    c = cfg("evabyte-6.5b-d16")
    ctx = hand_ctx(c, EVA_SPAN_WORK, {SERIES: 250_000.0},
                   {SERIES: 1_250_000.0})
    # 1,000,000 entries of 2 * 32 * 128 * 2 B = 16,384 B, and 640
    # row-steps of query + output (2 * 32 * 128 * 2 B) and one new entry,
    # in 16 layers, at 819 GB/s, over 0.4 s of the kernel.
    nbytes = (1_000_000 * 16_384 + 640 * (16_384 + 16_384)) * 16
    assert nbytes == 262_479_544_320
    got = metrics.read_layer_metric(reader("attn_decode_roofline"), ctx)
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.4, rel=1e-12)
    assert got == pytest.approx(80.1219, abs=1e-3)


@pytest.mark.parametrize("t0, t1", [
    (None, None),                                   # no scrape
    ({}, {}),                                       # no series
    ({SERIES: 0.0}, {SERIES: 6_400_001.0}),         # more than every position
    ({SERIES: 0.0}, {SERIES: 399_999.0}),           # fewer than all summaries
], ids=["no-scrape", "no-series", "over", "under"])
@pytest.mark.parametrize("metric", ["attn_decode_roofline", "decode_step_roofline"])
def test_an_eva_model_outside_the_bracket_reads_nothing(metric, t0, t1):
    ctx = hand_ctx(cfg("evabyte-6.5b-d16"), EVA_SPAN_WORK, t0, t1)
    assert metrics.read_layer_metric(reader(metric), ctx) is None


def test_a_reader_without_its_kernel_or_tokens_reads_nothing():
    c = cfg("qwen2.5-3b")
    ctx = hand_ctx(c, dense_span_work(c))
    ctx["trace"] = dict(ctx["trace"], op_seconds={"fusion.12": 9.0},
                        module_seconds={"jit__stage_fn(99)": 0.5},
                        module_counts={"jit__stage_fn(99)": 3})
    idle = hand_ctx(c, dict(dense_span_work(c), decode_tokens=0,
                            decode_context_sum=0))
    for metric in ("attn_decode_roofline", "decode_step_roofline"):
        assert metrics.read_layer_metric(reader(metric), ctx) is None
        assert metrics.read_layer_metric(reader(metric), idle) is None
        assert metrics.read_layer_metric(
            reader(metric), dict(ctx, trace=None)) is None


def test_decode_step_roofline_is_weights_a_step_plus_the_attention():
    c = cfg("qwen2.5-7b-d24")
    sw = dense_span_work(c)
    got = metrics.read_layer_metric(reader("decode_step_roofline"),
                                    hand_ctx(c, sw))
    # 12.5 executions of 8 steps read the step's weights 100 times; the
    # attention's bytes are the kernel reader's; 2.0 s of the program.
    nbytes = 100 * 12_276_775_936 + sw["attn_decode"]["bytes"]
    assert got == pytest.approx(100 * nbytes / 819e9 / 2.0, rel=1e-12)
    # Operations (2 an element and row, 1,600 row-steps) are far from
    # binding at 16 rows a step.
    flops = 2 * 6_138_387_968 * 1600 + sw["attn_decode"]["flops"]
    assert flops / 197e12 < nbytes / 819e9 / 10
    # EvaByte: head 0 and 16 layers a step, the program's entries.
    c = cfg("evabyte-6.5b-d16")
    got = metrics.read_layer_metric(
        reader("decode_step_roofline"),
        hand_ctx(c, EVA_SPAN_WORK, {SERIES: 0.0}, {SERIES: 1_000_000.0}))
    assert got == pytest.approx(
        100 * (100 * 6_478_897_152 + 262_479_544_320) / 819e9 / 2.0, rel=1e-12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.peaks_for("TPU v9 imaginary")
    assert json.dumps(PEAKS)
