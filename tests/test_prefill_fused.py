"""Fused Pallas ragged chunked prefill (ops/prefill_fused_pallas.py) —
interpret-mode parity against the XLA reference (ragged lengths, cached
prefixes, page/chunk boundaries, sinks, sliding windows, soft caps,
attend-only mode), engine-level bit-identity of prefill-fused on/off
streams (greedy + seeded, sync + overlap, K=1 and K>1), prefix-aware
chunk skipping (mid-prefill radix re-consult), mid-prefill checkpoint
park/restore, and the one-knob sequence-parallel prefill path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.config import normalize_config
from parallax_tpu.models.base import StageModel
from parallax_tpu.ops.attention import _ragged_paged_attention_xla
from parallax_tpu.ops.kv_cache_ops import reshape_and_cache
from parallax_tpu.ops.prefill_fused_pallas import gqa_fused_prefill_pallas
from parallax_tpu.parallel import make_mesh
from parallax_tpu.runtime.checkpoint import (
    CheckpointError,
    build_resumed_request,
    checkpoint_from_request,
    checkpoint_from_wire,
    checkpoint_to_wire,
)
from parallax_tpu.runtime.engine import EngineConfig, StageEngine, drive_step
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, RequestStatus, SamplingParams

# ---------------------------------------------------------------------------
# Kernel parity: fused append+attend vs the separate-scatter XLA oracle.
# ---------------------------------------------------------------------------

PAGE = 8
HQ, HKV, D = 4, 2, 32
PAGES_PER_SEQ = 12


def _prefill_case(q_lens, cached, sinks_on, seed=0):
    """Ragged chunk geometry: per-row ``cached`` tokens already in the
    cache, ``q_lens`` new tokens arriving this chunk."""
    rng = np.random.default_rng(seed)
    s = len(q_lens)
    kv_lens = np.array([c + q for c, q in zip(cached, q_lens)], np.int32)
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    t = int(cu[-1])
    tp = max(64, 1 << (t - 1).bit_length())   # token-bucket padding
    q = rng.standard_normal((tp, HQ, D)).astype(np.float32)
    k = rng.standard_normal((tp, HKV, D)).astype(np.float32)
    v = rng.standard_normal((tp, HKV, D)).astype(np.float32)
    cache = rng.standard_normal(
        (s * PAGES_PER_SEQ + 1, PAGE, 2 * HKV, D)
    ).astype(np.float32)
    pages = (
        np.arange(s * PAGES_PER_SEQ, dtype=np.int32)
        .reshape(s, PAGES_PER_SEQ) + 1
    )
    slots = np.full((tp,), -1, np.int32)   # padding rows: no append
    for i in range(s):
        for j in range(q_lens[i]):
            pos = cached[i] + j
            slots[cu[i] + j] = pages[i, pos // PAGE] * PAGE + pos % PAGE
    sinks = (
        rng.standard_normal((HQ,)).astype(np.float32) if sinks_on else None
    )
    return (
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(cache),
        jnp.asarray(kv_lens), jnp.asarray(pages), jnp.asarray(cu),
        jnp.asarray([s], jnp.int32), jnp.asarray(slots),
        None if sinks is None else jnp.asarray(sinks), t,
    )


@pytest.mark.parametrize("q_lens,cached,sinks_on,window,cap", [
    ([17, 8, 33], [0, 0, 0], False, None, None),     # basic ragged
    ([17, 8, 33], [0, 16, 5], False, None, None),    # cached prefixes
    ([16, 8, 8], [8, 0, 24], False, None, None),     # page-aligned bounds
    ([17, 8, 33], [0, 16, 5], True, None, None),     # sinks
    ([17, 8, 33], [3, 16, 5], False, 11, None),      # sliding window
    ([17, 8, 33], [3, 16, 5], True, None, 30.0),     # sinks + soft cap
    ([17, 8, 33], [3, 16, 5], True, 11, 30.0),       # all three
    ([64], [0], False, None, None),                  # exact single block
    ([1, 1, 1], [40, 7, 0], False, None, None),      # decode-shaped chunk
], ids=["ragged", "cached", "page-aligned", "sinks", "window",
        "sinks-softcap", "sinks-window-softcap", "one-block", "decode-shaped"])
def test_fused_prefill_parity_and_append(q_lens, cached, sinks_on,
                                         window, cap):
    (q, k, v, cache, kv_lens, pages, cu, nseq, slots, sinks,
     t) = _prefill_case(q_lens, cached, sinks_on)
    out_f, cache_f = gqa_fused_prefill_pallas(
        q, k, v, cache, kv_lens, pages, cu, nseq, slots, sinks,
        sm_scale=D ** -0.5, sliding_window=window, soft_cap=cap,
        use_sinks=sinks_on, q_block=32, interpret=True,
    )
    # Reference: separate scatter dispatch, then the XLA oracle.
    cache_x = reshape_and_cache(cache, k, v, slots)
    out_x = _ragged_paged_attention_xla(
        q, cache_x, kv_lens, pages, cu, nseq,
        sm_scale=D ** -0.5, sliding_window=window, soft_cap=cap,
        sinks=sinks,
    )
    # In-kernel append == the kv_cache_ops scatter, bit for bit
    # (including skipped padding rows).
    assert np.array_equal(np.asarray(cache_f), np.asarray(cache_x))
    np.testing.assert_allclose(
        np.asarray(out_f)[:t], np.asarray(out_x)[:t], atol=2e-5, rtol=2e-5
    )
    # Padding rows produce exact zeros.
    assert np.all(np.asarray(out_f)[t:] == 0.0)


def test_fused_prefill_attend_only_mode():
    """``k_new=None``: the kernel attends over an already-populated
    cache without appending (the sink-prefill path whose scatter
    already ran) and returns the cache untouched."""
    (q, k, v, cache, kv_lens, pages, cu, nseq, slots, sinks,
     t) = _prefill_case([17, 8, 33], [0, 16, 5], True)
    cache_x = reshape_and_cache(cache, k, v, slots)
    out_f, cache_out = gqa_fused_prefill_pallas(
        q, None, None, cache_x, kv_lens, pages, cu, nseq,
        jnp.full_like(slots, -1), sinks,
        sm_scale=D ** -0.5, use_sinks=True, q_block=32, interpret=True,
    )
    out_x = _ragged_paged_attention_xla(
        q, cache_x, kv_lens, pages, cu, nseq,
        sm_scale=D ** -0.5, sliding_window=None, soft_cap=None,
        sinks=sinks,
    )
    assert np.array_equal(np.asarray(cache_out), np.asarray(cache_x))
    np.testing.assert_allclose(
        np.asarray(out_f)[:t], np.asarray(out_x)[:t], atol=2e-5, rtol=2e-5
    )


# ---------------------------------------------------------------------------
# Engine-level: prefill-fused on vs off streams bit-identical through
# CHUNKED prefill (token budget below the prompt length).
# ---------------------------------------------------------------------------

GQA_CFG = normalize_config(dict(
    architectures=["Qwen2ForCausalLM"], hidden_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, vocab_size=199, max_position_embeddings=512,
    tie_word_embeddings=False,
))

# Lengths straddle page and chunk boundaries: 64 = two exact 32-token
# chunks, 71 leaves a ragged 7-token tail chunk.
PROMPTS = [
    [int(x) for x in np.random.default_rng(7).integers(1, 198, size=n)]
    for n in (64, 71, 19)
]


@pytest.fixture(scope="module")
def gqa_model():
    model = StageModel(GQA_CFG, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)
    return model, params


def _run_engine(model, params, *, prefill_fused, lookahead=1, overlap=True,
                temp=0.0, seed=None, max_new=7, **cfg_over):
    cfg = dict(
        page_size=8, num_pages=128, max_model_len=256, kv_dtype="float32",
        max_num_tokens_per_batch=32,    # forces chunked prefill
        decode_lookahead=lookahead, prefill_fused=prefill_fused,
        overlap_steps=overlap,
    )
    cfg.update(cfg_over)
    eng = StageEngine(model, params, EngineConfig(**cfg))
    pipe = InProcessPipeline([eng])
    reqs = []
    for i, pr in enumerate(PROMPTS):
        req = Request(
            f"r{i}", prompt_ids=list(pr),
            sampling_params=SamplingParams(
                temperature=temp, max_new_tokens=max_new, seed=seed,
                top_k=5 if temp else 0,
            ),
        )
        reqs.append(req)
        pipe.submit(req)
    pipe.run_until_complete()
    return [r.output_ids for r in reqs], eng


@pytest.mark.parametrize("lookahead", [1, 8])
@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("temp,seed", [(0.0, None), (0.8, 77)])
def test_engine_prefill_streams_bit_identical(gqa_model, lookahead,
                                              overlap, temp, seed):
    model, params = gqa_model
    off, _ = _run_engine(model, params, prefill_fused=False,
                         lookahead=lookahead, overlap=overlap,
                         temp=temp, seed=seed)
    on, eng = _run_engine(model, params, prefill_fused=True,
                          lookahead=lookahead, overlap=overlap,
                          temp=temp, seed=seed)
    assert on == off
    summary = eng.kernel_dispatch_summary()
    assert summary["prefill_impl"] == "pallas-fused"
    assert summary["prefill_fused"] is True
    assert any(k == "pallas-fused/prefill" for k in
               summary["dispatch_total"])


def test_prefill_dispatch_counter_labels(gqa_model):
    """Prefill dispatches land in the registry counter under
    path="prefill" with the resolved impl label."""
    from parallax_tpu.obs.registry import get_registry

    model, params = gqa_model
    _, eng = _run_engine(model, params, prefill_fused=True)
    assert any(
        path == "prefill" and impl == "pallas-fused"
        for impl, path in eng._kernel_counts
    )
    text = get_registry().render()
    assert "parallax_attn_kernel_dispatch_total" in text
    assert 'path="prefill"' in text


# ---------------------------------------------------------------------------
# Prefix-aware chunk skipping: the mid-prefill radix re-consult.
# ---------------------------------------------------------------------------

# Donor A: a 64-token (8 exact pages) prompt that prefills in ONE step
# (budget = 64) and finishes immediately (max_new=1), releasing -> radix
# insert. B shares A's whole prompt as a prefix and is admitted in the
# same step but gets zero token budget (A consumed it all) — B's first
# chunk planning happens AFTER A released, so the re-consult covers the
# full 64-token prefix that the admission-time match (empty tree) missed.
A_PROMPT = [int(x) for x in np.random.default_rng(11).integers(1, 198, 64)]
B_PROMPT = A_PROMPT + [int(x) for x in
                       np.random.default_rng(12).integers(1, 198, 100)]


def _run_chunk_skip_pair(model, params, *, chunk_skip, temp=0.0,
                         seed=None, cache_digests=False):
    eng = StageEngine(model, params, EngineConfig(
        page_size=8, num_pages=128, max_model_len=256, kv_dtype="float32",
        max_num_tokens_per_batch=64, overlap_steps=False,
        enable_prefix_cache=True, prefill_chunk_skip=chunk_skip,
        cache_digests=cache_digests,
    ))
    pipe = InProcessPipeline([eng])
    a = Request("a", prompt_ids=list(A_PROMPT),
                sampling_params=SamplingParams(
                    temperature=temp, max_new_tokens=1, seed=seed,
                    top_k=5 if temp else 0, ignore_eos=True))
    b = Request("b", prompt_ids=list(B_PROMPT),
                sampling_params=SamplingParams(
                    temperature=temp, max_new_tokens=5, seed=seed,
                    top_k=5 if temp else 0, ignore_eos=True))
    pipe.submit(a)
    pipe.submit(b)
    pipe.run_until_complete()
    return a.output_ids, b.output_ids, eng


@pytest.mark.parametrize("temp,seed", [(0.0, None), (0.8, 31)],
                         ids=["greedy", "seeded"])
def test_chunk_skip_recomputes_zero_covered_chunks(gqa_model, temp, seed):
    model, params = gqa_model
    a_on, b_on, eng_on = _run_chunk_skip_pair(
        model, params, chunk_skip=True, temp=temp, seed=seed)
    # The whole warm 64-token prefix was skipped mid-prefill — zero
    # covered chunks recomputed.
    assert eng_on.cache.stats.tokens_chunk_skipped == 64
    # Bit-identical streams with the knob off (full recompute).
    a_off, b_off, eng_off = _run_chunk_skip_pair(
        model, params, chunk_skip=False, temp=temp, seed=seed)
    assert eng_off.cache.stats.tokens_chunk_skipped == 0
    assert (a_on, b_on) == (a_off, b_off)


def test_chunk_skip_radix_digests_identical(gqa_model, monkeypatch):
    """Skip on/off end with the SAME radix content: the published
    prefix digests match block for block."""
    model, params = gqa_model
    *_, eng_on = _run_chunk_skip_pair(
        model, params, chunk_skip=True, cache_digests=True)
    *_, eng_off = _run_chunk_skip_pair(
        model, params, chunk_skip=False, cache_digests=True)
    d_on = sorted(eng_on.cache.prefix_cache.prefix_digests())
    d_off = sorted(eng_off.cache.prefix_cache.prefix_digests())
    assert d_on and d_on == d_off
    # And the skip actually fired on the "on" side.
    assert eng_on.cache.stats.tokens_chunk_skipped == 64


def test_chunk_skip_surfaces_in_cache_stats_summary(gqa_model, monkeypatch):
    model, params = gqa_model
    *_, eng = _run_chunk_skip_pair(model, params, chunk_skip=True)
    summary = eng.cache_stats()
    assert summary is not None
    assert summary["tokens_chunk_skipped"] == 64


# ---------------------------------------------------------------------------
# Mid-prefill checkpoints: park partway through chunked prefill, restore
# on a fresh engine, resume AT the mark — bit-identical continuation.
# ---------------------------------------------------------------------------

def _mk_ckpt_engine(gqa_model, **over):
    model, params = gqa_model
    cfg = dict(
        page_size=8, num_pages=128, max_model_len=256, kv_dtype="float32",
        max_num_tokens_per_batch=32, host_cache_bytes=1 << 24,
        enable_prefix_cache=True, overlap_steps=False,
    )
    cfg.update(over)
    return StageEngine(model, params, EngineConfig(**cfg))


def _drive(eng, n_guard=5000):
    pending, guard = None, 0
    while (eng.has_work() or pending is not None) and guard < n_guard:
        guard += 1
        _outs, pending = drive_step(eng, pending)
    assert guard < n_guard


def _drive_steps(eng, n):
    """Drive exactly n resolved steps, leaving no step in flight."""
    pending = None
    for _ in range(n):
        _outs, pending = drive_step(eng, pending)
    if pending is not None:
        eng.resolve(pending)


LONG_PROMPT = [int(x) for x in np.random.default_rng(5).integers(1, 198, 100)]


@pytest.mark.parametrize("sp_kw", [
    dict(temperature=0.0),
    dict(temperature=0.8, top_k=8, seed=1234),
], ids=["greedy", "seeded"])
def test_mid_prefill_checkpoint_roundtrip_bit_identical(gqa_model, sp_kw):
    sp = SamplingParams(max_new_tokens=8, ignore_eos=True, **sp_kw)

    # Uninterrupted baseline.
    eng0 = _mk_ckpt_engine(gqa_model)
    base = Request("base", prompt_ids=list(LONG_PROMPT),
                   sampling_params=dataclasses.replace(sp))
    eng0.submit(base)
    _drive(eng0)
    assert len(base.output_ids) == 8

    # Source: two 32-token chunks of the 100-token prompt, then park.
    eng_a = _mk_ckpt_engine(gqa_model)
    mig = Request("mig", prompt_ids=list(LONG_PROMPT),
                  sampling_params=dataclasses.replace(sp))
    eng_a.submit(mig)
    _drive_steps(eng_a, 2)
    assert mig.status is RequestStatus.PREFILLING
    assert 0 < mig.num_computed_tokens < len(LONG_PROMPT)
    mark = mig.num_computed_tokens

    # The park path: drop the pre-allocated-but-uncomputed prompt pages
    # so the host image covers exactly the computed span, then harvest.
    freed = eng_a.cache.trim_uncomputed_pages(mig)
    assert freed > 0
    assert eng_a.cache.preempt_to_host(mig)
    image = eng_a.harvest_kv_image(mig)
    assert image is not None and image.computed_tokens == mark
    assert eng_a.extract("mig") is mig
    ckpt = checkpoint_from_request(mig, kv=image)
    assert ckpt.prefill_computed_tokens == mark
    eng_a.cache.release(mig)
    wire = checkpoint_from_wire(checkpoint_to_wire(ckpt))
    assert wire.prefill_computed_tokens == mark

    # Target: adopt the image, resume chunked prefill AT the mark.
    eng_b = _mk_ckpt_engine(gqa_model)
    res = build_resumed_request(wire)
    assert eng_b.adopt_checkpoint_kv(res, wire.kv)
    assert res.status is RequestStatus.PREEMPTED
    assert res.num_computed_tokens == mark
    assert eng_b.submit(res)
    _drive(eng_b)
    assert res.status.is_finished
    # Swap-in resumed mid-prefill: no re-prefill from token zero.
    assert eng_b.cache.stats.resumes == 1
    assert res.full_output_ids == base.output_ids


def test_mid_prefill_park_with_finished_checkpoint_is_zero(gqa_model):
    """A request parked after prefill completes carries
    prefill_computed_tokens == 0 (the field means 'mid-prefill mark',
    not 'computed tokens')."""
    eng = _mk_ckpt_engine(gqa_model)
    req = Request("d", prompt_ids=list(LONG_PROMPT),
                  sampling_params=SamplingParams(
                      temperature=0.0, max_new_tokens=8, ignore_eos=True))
    eng.submit(req)
    _drive_steps(eng, 5)
    assert req.is_prefill_done
    ck = checkpoint_from_request(req)
    assert ck.prefill_computed_tokens == 0


def test_mid_prefill_wire_validation_rejects_bad_marks(gqa_model):
    eng = _mk_ckpt_engine(gqa_model)
    mig = Request("w", prompt_ids=list(LONG_PROMPT),
                  sampling_params=SamplingParams(
                      temperature=0.0, max_new_tokens=8, ignore_eos=True))
    eng.submit(mig)
    _drive_steps(eng, 2)
    assert mig.status is RequestStatus.PREFILLING
    eng.cache.trim_uncomputed_pages(mig)
    assert eng.cache.preempt_to_host(mig)
    image = eng.harvest_kv_image(mig)
    eng.extract("w")
    ckpt = checkpoint_from_request(mig, kv=image)
    eng.cache.release(mig)

    # Clean frame parses.
    checkpoint_from_wire(checkpoint_to_wire(ckpt))
    # Mark beyond the total token span: rejected.
    d = checkpoint_to_wire(ckpt)
    d["prefill_computed_tokens"] = len(ckpt.prompt_ids) + len(
        ckpt.output_ids
    )
    with pytest.raises(CheckpointError):
        checkpoint_from_wire(d)
    # Mark disagreeing with the KV image's computed span: rejected.
    d = checkpoint_to_wire(ckpt)
    d["prefill_computed_tokens"] = ckpt.prefill_computed_tokens - 8
    with pytest.raises(CheckpointError):
        checkpoint_from_wire(d)


# ---------------------------------------------------------------------------
# One-knob sequence-parallel prefill.
# ---------------------------------------------------------------------------

SP_PROMPT = [int(x) for x in np.random.default_rng(3).integers(1, 198, 300)]


def _gen_one(engine, prompt):
    pipe = InProcessPipeline([engine])
    req = Request("r", prompt_ids=list(prompt),
                  sampling_params=SamplingParams(
                      temperature=0.0, max_new_tokens=5, ignore_eos=True))
    pipe.submit(req)
    pipe.run_until_complete()
    return req.output_ids, req


def test_prefill_seq_parallel_matches_single_chip(gqa_model):
    """prefill_seq_parallel on a 2-device CPU sp mesh: the long prompt
    ring-prefills in one step and the stream matches a plain
    single-chip engine with identical weights."""
    model, params = gqa_model
    base = dict(page_size=8, num_pages=128, max_model_len=512,
                max_num_tokens_per_batch=512, kv_dtype="float32",
                enable_prefix_cache=False)
    plain_out, _ = _gen_one(
        StageEngine(model, params, EngineConfig(**base)), SP_PROMPT)

    model_b = StageModel(GQA_CFG, 0, 2, use_pallas=False)
    sp_eng = StageEngine(
        model_b, params,
        EngineConfig(**base, prefill_seq_parallel=True, sp_threshold=256),
        sp_mesh=make_mesh(sp_size=2, tp_size=1),
    )
    sp_out, sp_req = _gen_one(sp_eng, SP_PROMPT)
    assert sp_req.num_computed_tokens >= len(SP_PROMPT)   # one-step prefill
    assert sp_out == plain_out
    # The SP dispatch is counted under path="prefill".
    assert any(k.endswith("/prefill") for k in
               sp_eng.kernel_dispatch_summary()["dispatch_total"])


def test_prefill_seq_parallel_defaults_threshold(gqa_model):
    """The one-knob form: an sp axis exists and no explicit threshold
    was given — the engine defaults sp_threshold so long prompts shard
    without further flags."""
    model, params = gqa_model
    eng = StageEngine(
        model, params,
        EngineConfig(page_size=8, num_pages=64, max_model_len=256,
                     kv_dtype="float32", prefill_seq_parallel=True),
        sp_mesh=make_mesh(sp_size=2, tp_size=1),
    )
    assert eng.cfg.sp_threshold == 2048
    assert eng._sp_enabled


def test_prefill_seq_parallel_single_chip_gate(gqa_model):
    """No sp axis to shard over: the knob degrades to the registered
    gate (warning, ordinary chunked prefill) instead of erroring."""
    import logging

    # The package logger does not propagate to root (utils/logging.py),
    # so capture with a direct handler instead of caplog.
    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    lg = logging.getLogger("parallax_tpu.runtime.engine")
    lg.addHandler(handler)
    try:
        model, params = gqa_model
        eng = StageEngine(
            model, params,
            EngineConfig(page_size=8, num_pages=64, max_model_len=256,
                         kv_dtype="float32", prefill_seq_parallel=True),
        )
    finally:
        lg.removeHandler(handler)
    assert not eng._sp_enabled
    assert any("sequence-parallel prefill disabled: single-chip stage"
               in m for m in records)
