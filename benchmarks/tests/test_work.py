"""The work formulas against numbers worked by hand from the two configs."""

import json

import pytest

from benchmarks.harness import spec, work

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


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.peaks_for("TPU v9 imaginary")
    assert json.dumps(PEAKS)
