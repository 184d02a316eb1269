"""DSA (DeepSeek-V3.2 / GLM-MoE-DSA) tests: lightning indexer + top-k
sparse attention over the MLA latent cache.

Capability parity: reference ``tests/test_deepseek_v32.py`` +
``tests/parallax_extensions_tests/test_dsa_paged_attention.py`` /
``test_dsa_indexer.py`` — exact-match against dense references.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.config import derive_indexer_types, normalize_config
from parallax_tpu.models.registry import create_stage_model
from parallax_tpu.ops.dsa import (
    dsa_indexer_scores_xla,
    dsa_topk_indices,
    mla_ragged_sparse_attention_xla,
    new_index_pages,
    store_index_cache,
)
from parallax_tpu.ops.mla import mla_ragged_attention_xla, new_mla_pages, store_mla_cache
from parallax_tpu.runtime.engine import EngineConfig, StageEngine
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, SamplingParams

TINY_V32 = dict(
    architectures=["DeepseekV32ForCausalLM"],
    hidden_size=64,
    num_hidden_layers=3,
    num_attention_heads=4,
    num_key_value_heads=4,
    kv_lora_rank=32,
    q_lora_rank=48,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    index_n_heads=4,
    index_head_dim=32,
    index_topk=64,
    intermediate_size=128,
    moe_intermediate_size=32,
    n_routed_experts=8,
    num_experts_per_tok=2,
    n_shared_experts=1,
    n_group=2,
    topk_group=1,
    scoring_func="sigmoid",
    first_k_dense_replace=1,
    vocab_size=199,
    max_position_embeddings=512,
    rms_norm_eps=1e-6,
    rope_theta=10000.0,
    rope_interleave=True,
    tie_word_embeddings=False,
)

CONFIG = normalize_config(TINY_V32)


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

def test_config_detects_dsa():
    assert CONFIG.dsa is not None
    assert CONFIG.dsa.index_n_heads == 4
    assert CONFIG.dsa.index_topk == 64
    assert CONFIG.dsa.indexer_types == ("full",) * 3
    assert CONFIG.dsa.indexer_rope_traditional  # DeepSeek default
    # index cache adds to the per-token KV budget
    # The latent row in whole lane tiles (128), the index key beside it.
    assert CONFIG.kv_bytes_per_token_per_layer() == 2 * (128 + 32)


def test_glm_dsa_defaults():
    cfg = normalize_config(dict(
        model_type="glm_moe_dsa",
        hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4,
        index_head_dim=32, index_topk=64, index_topk_freq=4,
        first_k_dense_replace=1, intermediate_size=128, vocab_size=100,
        n_routed_experts=4, num_experts_per_tok=2,
    ))
    assert cfg.architecture == "GlmMoeDsaForCausalLM"
    assert not cfg.dsa.indexer_rope_traditional   # GLM uses half-rotation
    assert cfg.dsa.indexer_norm_eps == 1e-6
    assert cfg.moe.scoring_func == "sigmoid"
    # freq=4, first_k=1, offset defaults to 3: full at 0 and 1+(3,7,...)
    assert cfg.dsa.indexer_types == (
        "full", "shared", "shared", "shared", "full",
        "shared", "shared", "shared",
    )


def test_derive_indexer_types_matches_reference_rule():
    # Mirrors reference deepseek_v32.py:27-58 semantics.
    assert derive_indexer_types(4) == ("full",) * 4
    assert derive_indexer_types(6, 2, None, 0, None) == (
        "shared", "full", "shared", "full", "shared", "full"
    )
    assert derive_indexer_types(3, 4, ["full", "shared", "full"]) == (
        "full", "shared", "full"
    )


# ---------------------------------------------------------------------------
# ops vs numpy references
# ---------------------------------------------------------------------------

def _fill_index_cache(keys, page_size, num_pages, page_ids, dim):
    """Store keys[i] at logical position i through the real scatter op."""
    cache = new_index_pages(num_pages, page_size, dim, jnp.float32)
    t = keys.shape[0]
    slots = np.array(
        [page_ids[i // page_size] * page_size + i % page_size
         for i in range(t)], np.int32,
    )
    return store_index_cache(cache, jnp.asarray(keys), jnp.asarray(slots))


def test_indexer_scores_match_numpy():
    rng = np.random.default_rng(0)
    page_size, num_pages = 4, 8
    ctx = 10                      # cached context length
    hi, d = 3, 16
    page_ids = [1, 2, 3]          # pages holding the context
    keys = rng.standard_normal((ctx, d)).astype(np.float32)
    cache = _fill_index_cache(keys, page_size, num_pages, page_ids, d)

    # One decode token: q_pos = ctx - 1.
    q = rng.standard_normal((1, hi, d)).astype(np.float32)
    w = rng.standard_normal((1, hi)).astype(np.float32)
    scores = np.asarray(dsa_indexer_scores_xla(
        jnp.asarray(q), jnp.asarray(w), cache,
        jnp.asarray([ctx], jnp.int32),
        jnp.asarray([page_ids], jnp.int32),
        jnp.asarray([0, 1], jnp.int32),
    ))
    ref = (w[0][:, None] * np.maximum(q[0] @ keys.T, 0.0)).sum(0)
    np.testing.assert_allclose(scores[0, :ctx], ref, rtol=1e-5, atol=1e-5)
    assert np.all(np.isneginf(scores[0, ctx:]))


def test_indexer_pallas_decode_matches_xla():
    """The Pallas decode indexer kernel (interpret mode off-TPU) must
    reproduce the XLA oracle bit-for-near-bit: multi-sequence decode
    batch with ragged context lengths and a padding row."""
    from parallax_tpu.ops.dsa_pallas import dsa_indexer_scores_decode_pallas

    rng = np.random.default_rng(4)
    page_size, num_pages = 8, 32
    hi, d = 4, 16
    ctxs = [19, 7, 0]             # third row = padding sequence
    pages_per_seq = 4
    page_tables = [[1, 2, 3, 0], [4, 5, 0, 0], [0, 0, 0, 0]]
    cache = new_index_pages(num_pages, page_size, d, jnp.float32)
    for ctx, table in zip(ctxs, page_tables):
        if ctx == 0:
            continue
        keys = rng.standard_normal((ctx, d)).astype(np.float32)
        slots = np.array(
            [table[i // page_size] * page_size + i % page_size
             for i in range(ctx)], np.int32,
        )
        cache = store_index_cache(cache, jnp.asarray(keys),
                                  jnp.asarray(slots))

    s = len(ctxs)
    q = rng.standard_normal((s, hi, d)).astype(np.float32)
    w = rng.standard_normal((s, hi)).astype(np.float32)
    kv_lens = jnp.asarray(ctxs, jnp.int32)
    page_indices = jnp.asarray(page_tables, jnp.int32)
    cu = jnp.asarray(np.arange(s + 1), jnp.int32)

    want = np.asarray(dsa_indexer_scores_xla(
        jnp.asarray(q), jnp.asarray(w), cache, kv_lens, page_indices, cu,
    ))
    got = np.asarray(dsa_indexer_scores_decode_pallas(
        jnp.asarray(q), jnp.asarray(w), cache, kv_lens, page_indices,
        interpret=True,
    ))
    assert got.shape == (s, pages_per_seq * page_size)
    valid = np.asarray(kv_lens)[:, None] > np.arange(got.shape[1])[None, :]
    np.testing.assert_allclose(got[valid], want[valid], rtol=1e-5,
                               atol=1e-5)
    assert np.all(np.isneginf(got[~valid]))


def test_indexer_scores_causal_in_prefill():
    rng = np.random.default_rng(1)
    page_size, num_pages = 4, 8
    ctx, hi, d = 6, 2, 8
    page_ids = [1, 2]
    keys = rng.standard_normal((ctx, d)).astype(np.float32)
    cache = _fill_index_cache(keys, page_size, num_pages, page_ids, d)
    # 6 prefill query tokens of one sequence.
    q = rng.standard_normal((ctx, hi, d)).astype(np.float32)
    w = np.ones((ctx, hi), np.float32)
    scores = np.asarray(dsa_indexer_scores_xla(
        jnp.asarray(q), jnp.asarray(w), cache,
        jnp.asarray([ctx], jnp.int32),
        jnp.asarray([page_ids], jnp.int32),
        jnp.asarray([0, ctx], jnp.int32),
    ))
    for t in range(ctx):
        assert np.all(np.isfinite(scores[t, : t + 1]))
        assert np.all(np.isneginf(scores[t, t + 1:]))


def test_topk_marks_dense_rows():
    scores = np.full((2, 16), -np.inf, np.float32)
    scores[0, :4] = [1.0, 3.0, 2.0, 0.5]    # 4 valid < topk=8 -> dense
    scores[1, :12] = np.arange(12)          # 12 valid > 8 -> sparse
    topk = np.asarray(dsa_topk_indices(jnp.asarray(scores), index_topk=8))
    assert np.all(topk[0] == -1)
    assert set(topk[1].tolist()) == set(range(4, 12))


def test_sparse_attention_dense_rows_match_dense_mla():
    rng = np.random.default_rng(2)
    page_size, num_pages = 4, 8
    ctx, hq, r, dr = 10, 3, 16, 8
    page_ids = [1, 2, 3]
    latent = rng.standard_normal((ctx, r)).astype(np.float32)
    rope = rng.standard_normal((ctx, dr)).astype(np.float32)
    cache = new_mla_pages(num_pages, page_size, r, dr, jnp.float32)
    slots = np.array([page_ids[i // page_size] * page_size + i % page_size
                      for i in range(ctx)], np.int32)
    cache = store_mla_cache(cache, jnp.asarray(latent), jnp.asarray(rope),
                            jnp.asarray(slots))

    q_latent = rng.standard_normal((1, hq, r)).astype(np.float32)
    q_pe = rng.standard_normal((1, hq, dr)).astype(np.float32)
    args = (
        jnp.asarray(q_latent), jnp.asarray(q_pe), cache,
        jnp.asarray([ctx], jnp.int32), jnp.asarray([page_ids], jnp.int32),
        jnp.asarray([0, 1], jnp.int32),
    )
    dense = mla_ragged_attention_xla(
        *args, jnp.asarray([1], jnp.int32), sm_scale=0.25, kv_lora_rank=r
    )
    # All -1 topk (dense row) with K >= ctx must match exactly.
    topk = jnp.full((1, 12), -1, jnp.int32)
    sparse = mla_ragged_sparse_attention_xla(
        *args, topk, sm_scale=0.25, kv_lora_rank=r
    )
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


def test_sparse_attention_matches_numpy_restriction():
    rng = np.random.default_rng(3)
    page_size, num_pages = 4, 16
    ctx, hq, r, dr, k = 20, 2, 8, 4, 6
    page_ids = [1, 2, 3, 4, 5]
    latent = rng.standard_normal((ctx, r)).astype(np.float32)
    rope = rng.standard_normal((ctx, dr)).astype(np.float32)
    cache = new_mla_pages(num_pages, page_size, r, dr, jnp.float32)
    slots = np.array([page_ids[i // page_size] * page_size + i % page_size
                      for i in range(ctx)], np.int32)
    cache = store_mla_cache(cache, jnp.asarray(latent), jnp.asarray(rope),
                            jnp.asarray(slots))
    q_latent = rng.standard_normal((1, hq, r)).astype(np.float32)
    q_pe = rng.standard_normal((1, hq, dr)).astype(np.float32)
    picks = np.array([2, 5, 7, 11, 13, 19], np.int32)

    out = np.asarray(mla_ragged_sparse_attention_xla(
        jnp.asarray(q_latent), jnp.asarray(q_pe), cache,
        jnp.asarray([ctx], jnp.int32), jnp.asarray([page_ids], jnp.int32),
        jnp.asarray([0, 1], jnp.int32), jnp.asarray(picks[None, :]),
        sm_scale=0.5, kv_lora_rank=r,
    ))
    # numpy reference restricted to the picked positions
    lat_k, rope_k = latent[picks], rope[picks]
    scores = (q_latent[0] @ lat_k.T + q_pe[0] @ rope_k.T) * 0.5  # [hq, k]
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = p @ lat_k
    np.testing.assert_allclose(out[0], ref, rtol=1e-4, atol=1e-4)


def test_sparse_attention_chunked_matches_single_pass():
    """K above the chunk threshold switches to the online-softmax scan;
    the result must match the single-pass gather bit-for-near-bit."""
    from parallax_tpu.ops import dsa as dsa_mod

    rng = np.random.default_rng(7)
    page_size, num_pages = 8, 128
    ctx, hq, r, dr = 700, 2, 16, 8
    k = dsa_mod.SPARSE_CHUNK_THRESHOLD + 90   # force the chunked path
    pages_needed = -(-ctx // page_size)
    page_ids = list(range(1, 1 + pages_needed))
    latent = rng.standard_normal((ctx, r)).astype(np.float32)
    rope = rng.standard_normal((ctx, dr)).astype(np.float32)
    cache = new_mla_pages(num_pages, page_size, r, dr, jnp.float32)
    slots = np.array([page_ids[i // page_size] * page_size + i % page_size
                      for i in range(ctx)], np.int32)
    cache = store_mla_cache(cache, jnp.asarray(latent), jnp.asarray(rope),
                            jnp.asarray(slots))
    t = 3
    q_latent = rng.standard_normal((t, hq, r)).astype(np.float32)
    q_pe = rng.standard_normal((t, hq, dr)).astype(np.float32)
    # Random sparse picks inside the context + some -1 padding tails.
    picks = np.stack([
        np.sort(rng.choice(ctx, size=k, replace=False)) for _ in range(t)
    ]).astype(np.int32)
    picks[0, -17:] = -1
    args = (
        jnp.asarray(q_latent), jnp.asarray(q_pe), cache,
        jnp.asarray([ctx], jnp.int32), jnp.asarray([page_ids], jnp.int32),
        jnp.asarray([0, t], jnp.int32),
    )
    chunked = np.asarray(mla_ragged_sparse_attention_xla(
        *args, jnp.asarray(picks), sm_scale=0.3, kv_lora_rank=r,
    ))
    # Single-pass oracle: same function with the threshold raised past K
    # (fresh trace: clear the jit cache so the patched constant applies).
    import unittest.mock as mock

    with mock.patch.object(dsa_mod, "SPARSE_CHUNK_THRESHOLD", 10_000):
        jax.clear_caches()
        single = np.asarray(mla_ragged_sparse_attention_xla(
            *args, jnp.asarray(picks), sm_scale=0.3, kv_lora_rank=r,
        ))
    jax.clear_caches()
    np.testing.assert_allclose(chunked, single, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# model level
# ---------------------------------------------------------------------------

def _generate(config, bounds, prompts, max_new=6, params_src=None,
              page_size=8):
    engines = []
    for s, e in bounds:
        model = create_stage_model(config, s, e, use_pallas=False)
        params = (params_src(model) if params_src
                  else model.init_params(jax.random.key(0),
                                         dtype=jnp.float32))
        engines.append(StageEngine(
            model, params,
            EngineConfig(page_size=page_size, num_pages=128,
                         max_model_len=256, kv_dtype="float32"),
        ))
    pipe = InProcessPipeline(engines)
    for i, prompt in enumerate(prompts):
        pipe.submit(Request(
            request_id=f"r{i}", prompt_ids=list(prompt),
            sampling_params=SamplingParams(temperature=0.0,
                                           max_new_tokens=max_new),
        ))
    done = pipe.run_until_complete()
    return {r.request_id: r.output_ids for r in done}


def test_v32_dense_budget_matches_v3_exactly():
    """With index_topk >= context every row is dense (-1): the DSA model
    must reproduce the plain MLA model token-for-token — the dense
    exact-match bar of reference test_dsa_paged_attention.py."""
    prompt = [3, 14, 15, 92, 65, 35, 89, 101]
    v32_out = _generate(CONFIG, [(0, 3)], [prompt])

    # Same weights, dense model: V3 ignores the indexer params + dsa config.
    v3_cfg = dataclasses.replace(
        CONFIG, architecture="DeepseekV3ForCausalLM", dsa=None
    )

    def v3_params(model):
        v32_model = create_stage_model(
            CONFIG, model.start_layer, model.end_layer, use_pallas=False
        )
        return v32_model.init_params(jax.random.key(0), dtype=jnp.float32)

    v3_out = _generate(v3_cfg, [(0, 3)], [prompt], params_src=v3_params)
    assert v32_out["r0"] == v3_out["r0"], (v32_out, v3_out)


def test_v32_pipeline_matches_single_stage():
    # Per-stage random init is not layout-deterministic for the base params,
    # so slice one full-model param set per stage (as the loader would).
    full_model = create_stage_model(CONFIG, 0, 3, use_pallas=False)
    full = full_model.init_params(jax.random.key(0), dtype=jnp.float32)

    def sliced(model):
        p = {"layers": full["layers"][model.start_layer:model.end_layer]}
        if model.is_first:
            p["embed_tokens"] = full["embed_tokens"]
        if model.is_last:
            p["norm"] = full["norm"]
            if "lm_head" in full:
                p["lm_head"] = full["lm_head"]
            p.setdefault("embed_tokens", full["embed_tokens"])
        return p

    prompt = [7, 21, 108, 55, 44, 12]
    single = _generate(CONFIG, [(0, 3)], [prompt], params_src=sliced)
    multi = _generate(CONFIG, [(0, 1), (1, 3)], [prompt], params_src=sliced)
    assert single["r0"] == multi["r0"]


def test_v32_sparse_path_generates():
    """index_topk smaller than the context: the sparse gather path is
    actually exercised (rows are NOT dense) and generation completes."""
    cfg = normalize_config({**TINY_V32, "index_topk": 8})
    prompt = list(np.random.default_rng(0).integers(1, 198, size=40))
    out = _generate(cfg, [(0, 3)], [[int(x) for x in prompt]], max_new=4)
    assert len(out["r0"]) == 4


def test_v32_shared_indexer_layers():
    """GLM-style freq: shared layers reuse the previous full layer's topk."""
    cfg = normalize_config({
        **TINY_V32, "index_topk_freq": 3, "index_skip_topk_offset": 0,
        "first_k_dense_replace": 0,
    })
    assert cfg.dsa.indexer_types == ("full", "shared", "shared")
    prompt = [5, 6, 7, 8, 9]
    out = _generate(cfg, [(0, 3)], [prompt])
    assert len(out["r0"]) == 6


def test_v32_shard_must_start_on_full_layer():
    cfg = normalize_config({
        **TINY_V32, "index_topk_freq": 3, "index_skip_topk_offset": 0,
        "first_k_dense_replace": 0,
    })
    with pytest.raises(ValueError, match="full indexer layer"):
        create_stage_model(cfg, 1, 3, use_pallas=False)


def test_v32_chunked_prefill_matches_unchunked():
    prompt = [int(x) for x in
              np.random.default_rng(5).integers(1, 198, size=30)]
    full = _generate(CONFIG, [(0, 3)], [prompt])
    engines_out = None
    # chunked: 8-token prefill chunks
    model = create_stage_model(CONFIG, 0, 3, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)
    eng = StageEngine(model, params, EngineConfig(
        page_size=8, num_pages=128, max_model_len=256, kv_dtype="float32",
        prefill_chunk_size=8,
    ))
    pipe = InProcessPipeline([eng])
    req = Request("rc", prompt_ids=list(prompt),
                  sampling_params=SamplingParams(temperature=0.0,
                                                 max_new_tokens=6))
    pipe.submit(req)
    pipe.run_until_complete()
    assert req.output_ids == full["r0"]


def test_indexer_scores_chunked_scan_matches_single_pass(monkeypatch):
    """Force multiple scoring chunks; the recombined [T, kv_cap] scores
    must equal the single-pass result exactly."""
    import parallax_tpu.ops.dsa as dsa_mod
    import parallax_tpu.ops.ragged as ragged_mod

    rng = np.random.default_rng(12)
    page_size, num_pages = 4, 32
    ctx, hi, d = 60, 3, 16
    page_ids = list(range(1, 17))
    keys = rng.standard_normal((ctx, d)).astype(np.float32)
    cache = _fill_index_cache(keys, page_size, num_pages, page_ids, d)
    q = rng.standard_normal((5, hi, d)).astype(np.float32)
    w = rng.standard_normal((5, hi)).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(w), cache,
            jnp.asarray([ctx], jnp.int32)[:1].repeat(1),
            jnp.asarray([page_ids], jnp.int32),
            jnp.asarray([0, 5], jnp.int32))
    single = np.asarray(dsa_indexer_scores_xla(*args))
    monkeypatch.setattr(ragged_mod, "KV_CHUNK_ROWS", 8)  # 8 chunks
    chunked = np.asarray(dsa_indexer_scores_xla.__wrapped__(*args))
    np.testing.assert_allclose(chunked, single, rtol=1e-6, atol=1e-6)
