"""The work formulas against numbers worked by hand from the configs, and
the two roofline readers on hand-built ``ctx`` dicts."""

import json

import pytest

from benchmarks.harness import metrics, spec, work
from benchmarks.tests import toy_hybrid

PEAKS = spec.peaks_for("TPU v5 lite")


def cfg(name):
    return spec.load_config(name)["hf"]


def stg(name):
    """The configuration's stage through the path ``run.py`` takes: its
    own work file where ``bench.work`` names one, else the dense block."""
    c = spec.load_config(name)
    return work.load_stage(c["hf"], c["work"]["path"])


def test_kv_bytes_per_token():
    # K and V: 4 kv heads x 128 x 2 bytes each, 24 layers.
    assert stg("qwen2.5-7b-d24")["entry_bytes"] == 2 * 4 * 128 * 2 * 24 == 49152
    # 2 kv heads, 36 layers.
    assert stg("qwen2.5-3b")["entry_bytes"] == 2 * 2 * 128 * 2 * 36 == 36864


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
    w = work.attn_decode_work(stg("qwen2.5-7b-d24"), context=1000)
    # 4 * 28 heads * 128 * 1000 positions * 24 layers.
    assert w["flops"] == 4 * 28 * 128 * 1000 * 24 == 344_064_000
    # 1001 tokens of KV (the context read, the new token written) plus
    # the query and output rows (2 * 28 * 128 * 2 bytes) in 24 layers.
    assert w["bytes"] == 1001 * 49152 + 2 * 28 * 128 * 2 * 24 == 49_545_216
    assert work.bound_by(w, PEAKS) == "memory"
    assert work.least_seconds(w, PEAKS) == pytest.approx(49_545_216 / 819e9)


def test_attn_prefill_work():
    c = stg("qwen2.5-3b")
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

    c = stg("qwen2.5-3b")
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

    c = stg("qwen2.5-3b")
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


def step_weight_bytes(name):
    """What every decode step reads, in bf16."""
    return 2 * stg(name)["always"]


def test_decode_step_weights_leave_out_the_embedding_table():
    # 7B at 24 layers, untied: the layers, the final norm, one head matrix.
    c = cfg("qwen2.5-7b-d24")
    assert step_weight_bytes("qwen2.5-7b-d24") == 2 * (
        24 * 233_057_792 + 3584 + 152064 * 3584) == 12_276_775_936
    assert work.weight_bytes(c) - step_weight_bytes("qwen2.5-7b-d24") \
        == 2 * 152064 * 3584          # exactly one embedding table
    # 3B: the head is the tied embedding, read as the head; nothing left out.
    assert step_weight_bytes("qwen2.5-3b") == work.weight_bytes(
        cfg("qwen2.5-3b")) == 6_171_877_376
    # EvaByte: 16 layers without biases, the final norm, head 0's 320 rows
    # of the 2,560 the head matrix holds.
    c = cfg("evabyte-6.5b-d16")
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096
    assert layer == 202_383_360
    assert step_weight_bytes("evabyte-6.5b-d16") == 2 * (
        16 * layer + 4096 + 320 * 4096) == 6_478_897_152
    assert work.weight_bytes(c) - step_weight_bytes("evabyte-6.5b-d16") \
        == 2 * 320 * 4096


SERIES = "parallax_eva_entries_attended"
OLD_DENSE_READER = {"name": "old", "source": {
    "kind": "trace", "pattern": "^gqa_fused_decode_pallas",
    "reduce": "roofline_share", "work": "attn_decode"}}


reader = spec.load_layer_metric


hand_ctx = toy_hybrid.hand_ctx


def dense_span_work(c):
    from benchmarks.harness.loadgen import Req, Result

    rows = []
    for plen in (100, 300):
        r = Result(req=Req(due=0, prompt=[1] * plen, max_tokens=801, seed=0))
        r.chunks = [(0.5, 1)] + [(1.0 + 0.01 * i, 8) for i in range(100)]
        r.usage = {"prompt_tokens_details": {"cached_tokens": 0}}
        rows.append(r)
    return work.span_work(rows, 0.9, 3.0, c)


# --------------------------------------------------------------------------
# The parent's arithmetic, kept as the expectation: one kind of layer,
# every entry multiplied by ``num_hidden_layers`` (work.py before the
# layer list, term for term).
# --------------------------------------------------------------------------


def old_decode_read_work(c, entries, steps):
    hq, hkv, d = c["num_attention_heads"], c["num_key_value_heads"], 128
    layers = c["num_hidden_layers"]
    entry = 2 * hkv * d * 2
    per_step = 2 * hq * d * 2 + entry
    return {"flops": 4 * hq * d * entries * layers,
            "bytes": (entries * entry + steps * per_step) * layers}


def old_decode_step_work(c, steps, tokens, attn):
    h, inter = c["hidden_size"], c["intermediate_size"]
    q, kv = c["num_attention_heads"] * 128, c["num_key_value_heads"] * 128
    bias = q + 2 * kv if c.get("attention_bias") else 0
    layer = h * (q + 2 * kv) + bias + q * h + 3 * h * inter + 2 * h
    elements = c["num_hidden_layers"] * layer + h + c["vocab_size"] * h
    return {"flops": attn["flops"] + 2 * elements * tokens,
            "bytes": attn["bytes"] + steps * elements * 2}


@pytest.mark.parametrize("name, step_bytes, entries, scrapes", [
    ("qwen2.5-7b-d24", 12_276_775_936, 6_400_000, (None, None)),
    ("qwen2.5-3b", 6_171_877_376, 6_400_000, (None, None)),
    ("evabyte-6.5b-d16", 6_478_897_152, 1_000_000,
     ({SERIES: 250_000.0}, {SERIES: 1_250_000.0})),
])
def test_the_accepted_configurations_count_what_they_counted(
        name, step_bytes, entries, scrapes):
    """Through the configuration's own work file (or none) the layer
    list gives the parent's operations and bytes integer for integer:
    12.277 / 6.172 / 6.479 GB a step of weights, the attention of every
    layer, and so both shares on one ``ctx``."""
    c, stage = cfg(name), stg(name)
    assert stage["always"] * 2 == step_bytes
    assert stage["paged_layers"] == c["num_hidden_layers"]
    assert (stage["expert"], stage["experts_held"], stage["state_bytes"]) \
        == (0, 0, 0)
    sw = {"decode_tokens": 640, "decode_context_sum": 6_400_000}
    attn = work.span_decode_attention(stage, sw, *scrapes)
    assert attn == old_decode_read_work(c, entries, 640)
    none = work.span_experts(stage, sw, 100.0, *scrapes)
    assert none == {"experts_read": 0, "pairs_held": 0}
    step = work.decode_step_work(stage, 100.0, 640, attn, none)
    assert step == old_decode_step_work(c, 100.0, 640, attn)
    ctx = hand_ctx(stage, sw, *scrapes)
    assert metrics.read_layer_metric(reader("attn_decode_roofline"), ctx) \
        == 100.0 * work.least_seconds(attn, PEAKS) / 0.4
    assert metrics.read_layer_metric(reader("decode_step_roofline"), ctx) \
        == 100.0 * work.least_seconds(step, PEAKS) / 2.0


@pytest.mark.parametrize("name", ["qwen2.5-7b-d24", "qwen2.5-3b"])
@pytest.mark.parametrize("scrapes", [
    (None, None),                                  # no scrape at all
    ({}, {"parallax_step_batch_tokens_sum": 9.0}),  # no such series
    ({SERIES: 5.0}, {SERIES: 5.0 + 7.0}),          # outside the bracket
], ids=["no-scrape", "no-series", "outside-bracket"])
def test_attn_decode_roofline_on_a_dense_model_is_the_old_reader(name, scrapes):
    c = stg(name)
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
    c = stg("evabyte-6.5b-d16")
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
    ctx = hand_ctx(stg("evabyte-6.5b-d16"), EVA_SPAN_WORK, t0, t1)
    assert metrics.read_layer_metric(reader(metric), ctx) is None


@pytest.mark.parametrize("metric", ["attn_decode_roofline", "decode_step_roofline"])
def test_a_key_called_chunk_size_says_nothing_of_what_a_row_attends(metric):
    """EVA's rule sits in EvaByte's work file, not on the key's name: a
    configuration that merely has a ``chunk_size`` (a scan's, say) and
    no work file is the dense block, every cached position an entry,
    with or without EVA's series in the scrape."""
    c = dict(cfg("qwen2.5-3b"), chunk_size=128)
    plain = stg("qwen2.5-3b")
    sw = dense_span_work(plain)
    want = metrics.read_layer_metric(reader(metric), hand_ctx(plain, sw))
    assert want is not None
    for scrapes in ((None, None), ({SERIES: 0.0}, {SERIES: 7.0})):
        got = metrics.read_layer_metric(
            reader(metric), hand_ctx(work.stage(c), sw, *scrapes))
        assert got == want


def test_a_reader_without_its_kernel_or_tokens_reads_nothing():
    c = stg("qwen2.5-3b")
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
    c = stg("qwen2.5-7b-d24")
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
    c = stg("evabyte-6.5b-d16")
    got = metrics.read_layer_metric(
        reader("decode_step_roofline"),
        hand_ctx(c, EVA_SPAN_WORK, {SERIES: 0.0}, {SERIES: 1_000_000.0}))
    assert got == pytest.approx(
        100 * (100 * 6_478_897_152 + 262_479_544_320) / 819e9 / 2.0, rel=1e-12)


# --------------------------------------------------------------------------
# A hybrid from files: layers of three kinds, experts held and hit.
# --------------------------------------------------------------------------

READ = work.EXPERTS_READ_SERIES
HYBRID_SPAN_WORK, counts = toy_hybrid.SPAN_WORK, toy_hybrid.counts


@pytest.fixture()
def hybrid(tmp_path, monkeypatch):
    bench_dir = tmp_path / "benchmarks"
    bench_dir.mkdir()
    name = toy_hybrid.write(str(bench_dir))
    monkeypatch.setattr(spec, "BENCH_DIR", str(bench_dir))
    return stg(name)


def test_a_hybrid_is_counted_by_layer_kind(hybrid):
    """2 of 16 layers hold pages, 7 hold state, 7 hold 4 of 8 experts;
    the elements are the hand sums of ``toy_hybrid``."""
    assert hybrid["cfg"]["chunk_size"] == 128       # and it reads all the same
    assert len(hybrid["layers"]) == 16
    assert hybrid["always"] == toy_hybrid.ALWAYS == 7 * 11_084 + 7 * 6_728 \
        + 2 * 20_544 + 64_064
    assert (hybrid["paged_layers"], hybrid["expert_layers"]) == (2, 7)
    assert (hybrid["expert"], hybrid["experts_per_token"]) == (4_096, 2)
    assert hybrid["experts_held"] == 7 * 4
    assert hybrid["state_bytes"] == 7 * toy_hybrid.MAMBA_STATE == 7 * 5_248
    # Attention for the 2 layers that hold pages, not 16: an entry is
    # K and V of 2 heads of 16 in bf16, QK^T + PV for 8 query heads.
    assert hybrid["entry_bytes"] == 2 * (2 * 2 * 16 * 2) == 256
    assert hybrid["entry_flops"] == 2 * (4 * 8 * 16) == 1_024
    assert hybrid["row_bytes"] == 2 * (2 * 8 * 16 * 2) == 1_024
    attn = work.span_decode_attention(hybrid, HYBRID_SPAN_WORK, None, None)
    assert attn == {"flops": 2_400_000 * 1_024,
                    "bytes": 2_400_000 * 256 + 800 * (1_024 + 256)}
    assert work.attn_decode_work(hybrid, 1000) == {
        "flops": 1000 * 1_024, "bytes": 1001 * 256 + 1_024}
    assert work.attn_prefill_work(hybrid, 0, 10) == {
        "flops": 55 * 1_024, "bytes": 20 * 256 + 10 * 1_024}
    # 800 tokens x 2 experts x 7 layers = 11,200 pairs at most of which
    # half land here; 1,900 of the 2,800 expert-steps held were hit.
    experts = work.span_experts(hybrid, HYBRID_SPAN_WORK, 100.0,
                                *counts(1_900, 5_600))
    assert experts == {"experts_read": 1_900, "pairs_held": 5_600}
    step = work.decode_step_work(hybrid, 100.0, 800, attn, experts)
    assert step == {
        "flops": attn["flops"] + 2 * toy_hybrid.ALWAYS * 800
                 + 2 * 4_096 * 5_600,
        "bytes": attn["bytes"] + 100 * toy_hybrid.ALWAYS * 2
                 + 1_900 * 4_096 * 2 + 800 * 7 * 5_248}


def test_both_shares_read_a_hybrid_from_a_hand_built_ctx(hybrid):
    ctx = hand_ctx(hybrid, HYBRID_SPAN_WORK, *counts(1_900, 5_600))
    attn_bytes = 2_400_000 * 256 + 800 * 1_280
    step_bytes = (attn_bytes + 100 * toy_hybrid.ALWAYS * 2
                  + 1_900 * 4_096 * 2 + 800 * 7 * 5_248)
    assert metrics.read_layer_metric(reader("attn_decode_roofline"), ctx) \
        == pytest.approx(100 * attn_bytes / 819e9 / 0.4, rel=1e-12)
    assert metrics.read_layer_metric(reader("decode_step_roofline"), ctx) \
        == pytest.approx(100 * step_bytes / 819e9 / 2.0, rel=1e-12)


@pytest.mark.parametrize("scrapes, why", [
    ((None, None), "no scrape"),
    (({}, {}), "no series"),
    (({READ: 0.0}, {READ: 1_900.0}), "one series of the two"),
    (counts(5_601, 5_600), "more experts read than pairs landed"),
    (counts(3_200, 5_600), "more than steps x experts held (2,800)"),
    (counts(600, 5_600), "fewer than pairs / rows a step (700)"),
    (counts(1_900, 12_400), "more pairs than tokens x k x layers (11,200)"),
    (counts(0, 0), "nothing landed here"),
])
def test_held_experts_without_a_count_inside_the_bracket_read_nothing(
        hybrid, scrapes, why):
    assert work.span_experts(hybrid, HYBRID_SPAN_WORK, 100.0, *scrapes) \
        is None, why
    ctx = hand_ctx(hybrid, HYBRID_SPAN_WORK, *scrapes)
    assert metrics.read_layer_metric(
        reader("decode_step_roofline"), ctx) is None, why
    # The attention kernel's share needs no count of experts.
    assert metrics.read_layer_metric(
        reader("attn_decode_roofline"), ctx) is not None


def test_with_every_held_expert_read_a_step_reads_the_whole_layer(hybrid):
    """8 rows x 2 experts can hit all 4 held: 2,800 expert-steps, and the
    step's bytes are every layer's whole size. A count inside the margin
    over that (the scrapes reach over the span) is cut to it."""
    whole = (toy_hybrid.ALWAYS + 28 * 4_096) * 2
    attn = {"flops": 0, "bytes": 0}
    for read in (2_800, 2_900):
        experts = work.span_experts(hybrid, HYBRID_SPAN_WORK, 100.0,
                                    *counts(read, 5_600))
        assert experts == {"experts_read": 2_800, "pairs_held": 5_600}
        step = work.decode_step_work(hybrid, 100.0, 800, attn, experts)
        assert step["bytes"] == 100 * whole + 800 * 7 * 5_248
    # One row a step reads exactly the experts its pairs land on.
    one = {"decode_tokens": 100, "decode_context_sum": 300_000}
    assert work.span_experts(hybrid, one, 100.0, *counts(700, 700)) \
        == {"experts_read": 700, "pairs_held": 700}


def test_a_work_file_is_held_to_its_contract():
    class Typo:
        @staticmethod
        def layers(c):
            return [{"always": 1, "entry_byte": 2}]

    class NoAlways(Typo):
        @staticmethod
        def layers(c):
            return [{"expert": 1}]

    class TwoSizes(Typo):
        @staticmethod
        def layers(c):
            return [{"always": 1, "expert": 8, "experts_held": 2,
                     "experts_per_token": 1},
                    {"always": 1, "expert": 16, "experts_held": 2,
                     "experts_per_token": 1}]

    c = cfg("qwen2.5-3b")
    for module, message in ((Typo, "keys"), (NoAlways, "keys"),
                            (TwoSizes, "one expert size")):
        with pytest.raises(ValueError, match=message):
            work.stage(c, module)

    class OtherKernel(Typo):
        DECODE_KERNEL = "^mla_decode"
        DECODE_PROGRAM = "^jit_window"

        @staticmethod
        def layers(c):
            return [dict(work.dense_layer(c), entry_bytes=1_152)] * 3

        @staticmethod
        def head_elements(c):
            return 7

    s = work.stage(c, OtherKernel)
    assert (s["kernel"], s["program"]) == ("^mla_decode", "^jit_window")
    assert s["entry_bytes"] == 3 * 1_152
    assert s["always"] == 3 * 77_076_992 + 7
    # Another kernel's name is found by its own pattern.
    ctx = hand_ctx(s, dense_span_work(s))
    assert metrics.read_layer_metric(reader("attn_decode_roofline"), ctx) is None
    ctx["trace"]["op_seconds"]["mla_decode.4"] = 0.2
    ctx["trace"]["module_seconds"]["jit_window(5)"] = 1.0
    ctx["trace"]["module_counts"]["jit_window(5)"] = 10
    for metric in ("attn_decode_roofline", "decode_step_roofline"):
        assert metrics.read_layer_metric(reader(metric), ctx) > 0


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.peaks_for("TPU v9 imaginary")
    assert json.dumps(PEAKS)
