"""Ring attention (sequence parallelism) exact-match tests on the virtual
CPU mesh: sp-sharded flash accumulation must equal dense causal attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.parallel import make_mesh
from parallax_tpu.parallel.sp import dense_causal_reference, ring_attention


def make_inputs(t, hq, hkv, d, seed=0, pad=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((t, hq, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((t, hkv, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((t, hkv, d)).astype(np.float32))
    pos = np.arange(t, dtype=np.int32)
    if pad:
        pos[-pad:] = -1
    return q, k, v, jnp.asarray(pos)


@pytest.mark.parametrize("sp", [2, 4, 8])
@pytest.mark.parametrize("gqa", [(4, 4), (8, 2)])
def test_ring_matches_dense(sp, gqa):
    if len(jax.devices()) < sp:
        pytest.skip("not enough devices")
    hq, hkv = gqa
    t, d = 64, 16
    mesh = make_mesh(sp_size=sp, tp_size=1)
    # shard over "sp": mesh axes are (sp, tp); use sp axis directly.
    q, k, v, pos = make_inputs(t, hq, hkv, d)
    scale = d**-0.5
    got = ring_attention(mesh, q, k, v, pos, sm_scale=scale)
    want = dense_causal_reference(q, k, v, pos, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ring_with_padding_rows():
    if len(jax.devices()) < 4:
        pytest.skip("not enough devices")
    mesh = make_mesh(sp_size=4, tp_size=1)
    t, hq, hkv, d = 32, 4, 2, 16
    q, k, v, pos = make_inputs(t, hq, hkv, d, seed=1, pad=5)
    scale = d**-0.5
    got = np.asarray(ring_attention(mesh, q, k, v, pos, sm_scale=scale))
    want = np.asarray(dense_causal_reference(q, k, v, pos, scale))
    valid = np.asarray(pos) >= 0
    np.testing.assert_allclose(got[valid], want[valid], rtol=2e-4, atol=2e-4)


def test_ring_prefix_continuation():
    """Chunk continuation: positions offset by a cached prefix length."""
    if len(jax.devices()) < 2:
        pytest.skip("not enough devices")
    mesh = make_mesh(sp_size=2, tp_size=1)
    t, hq, hkv, d = 16, 4, 2, 8
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((t, hq, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((t, hkv, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((t, hkv, d)).astype(np.float32))
    pos = jnp.asarray(np.arange(100, 100 + t, dtype=np.int32))
    scale = d**-0.5
    got = np.asarray(ring_attention(mesh, q, k, v, pos, sm_scale=scale))
    want = np.asarray(dense_causal_reference(q, k, v, pos, scale))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_rejects_indivisible_sequence():
    mesh = make_mesh(sp_size=2, tp_size=1)
    q, k, v, pos = make_inputs(15, 4, 2, 8)
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(mesh, q, k, v, pos, sm_scale=1.0)


# ---------------------------------------------------------------------------
# engine integration: SP long-prefill path (VERDICT r1 item 7)
# ---------------------------------------------------------------------------

def test_engine_sp_prefill_matches_dense_engine():
    """A prompt above sp_threshold prefills in one ring-attention step; the
    generated tokens must match a plain engine with identical weights."""
    import numpy as np

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.base import StageModel
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams

    cfg = normalize_config(dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, vocab_size=199, max_position_embeddings=2048,
        tie_word_embeddings=False,
    ))
    model_a = StageModel(cfg, 0, 2, use_pallas=False)
    params = model_a.init_params(jax.random.key(0), dtype=jnp.float32)
    prompt = [int(x) for x in
              np.random.default_rng(0).integers(1, 198, size=300)]

    def gen(engine):
        pipe = InProcessPipeline([engine])
        req = Request("r", prompt_ids=list(prompt),
                      sampling_params=SamplingParams(temperature=0.0,
                                                     max_new_tokens=5))
        pipe.submit(req)
        pipe.run_until_complete()
        return req.output_ids, req

    base = dict(page_size=8, num_pages=128, max_model_len=512,
                max_num_tokens_per_batch=512, kv_dtype="float32",
                enable_prefix_cache=False)
    dense_eng = StageEngine(model_a, params, EngineConfig(**base))
    dense_out, _ = gen(dense_eng)

    model_b = StageModel(cfg, 0, 2, use_pallas=False)
    sp_mesh = make_mesh(sp_size=8, tp_size=1)
    sp_eng = StageEngine(
        model_b, params, EngineConfig(**base, sp_threshold=256),
        sp_mesh=sp_mesh,
    )
    sp_out, sp_req = gen(sp_eng)
    # The whole prompt prefilled in ONE step (not chunked): computed jumped
    # from 0 to full in a single on_batch_computed.
    assert sp_req.num_computed_tokens >= len(prompt)
    assert sp_out == dense_out, (sp_out, dense_out)


def test_engine_sp_tp_composed_matches_dense_engine():
    """SP x TP composition: a 2x4 ("sp", "tp") mesh engine — ring body
    inside the TP shard_map — must match the unsharded engine
    token-for-token, and decode afterwards must read the same KV cache
    the SP prefill wrote."""
    import numpy as np

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.base import StageModel
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams

    cfg = normalize_config(dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
        intermediate_size=128, vocab_size=199, max_position_embeddings=2048,
        tie_word_embeddings=False,
    ))
    model_a = StageModel(cfg, 0, 2, use_pallas=False)
    params = model_a.init_params(jax.random.key(0), dtype=jnp.float32)
    prompt = [int(x) for x in
              np.random.default_rng(1).integers(1, 198, size=300)]

    def gen(engine):
        pipe = InProcessPipeline([engine])
        req = Request("r", prompt_ids=list(prompt),
                      sampling_params=SamplingParams(temperature=0.0,
                                                     max_new_tokens=5))
        pipe.submit(req)
        pipe.run_until_complete()
        return req.output_ids, req

    base = dict(page_size=8, num_pages=128, max_model_len=512,
                max_num_tokens_per_batch=512, kv_dtype="float32",
                enable_prefix_cache=False)
    dense_eng = StageEngine(model_a, params, EngineConfig(**base))
    dense_out, _ = gen(dense_eng)

    model_b = StageModel(cfg, 0, 2, use_pallas=False, tp_size=4)
    mesh = make_mesh(tp_size=4, sp_size=2)
    sp_eng = StageEngine(
        model_b, params, EngineConfig(**base, sp_threshold=256),
        mesh=mesh,
    )
    assert sp_eng._sp_enabled
    sp_out, sp_req = gen(sp_eng)
    # The whole prompt prefilled in ONE ring step, then decode (5 tokens)
    # ran on the normal TP path against the SP-written cache.
    assert sp_req.num_computed_tokens >= len(prompt)
    assert sp_out == dense_out, (sp_out, dense_out)


def test_engine_sp_below_threshold_uses_normal_path():
    import numpy as np

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.base import StageModel
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams

    cfg = normalize_config(dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, vocab_size=199, max_position_embeddings=2048,
        tie_word_embeddings=False,
    ))
    model = StageModel(cfg, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)
    eng = StageEngine(
        model, params,
        EngineConfig(page_size=8, num_pages=64, max_model_len=256,
                     kv_dtype="float32", sp_threshold=256),
        sp_mesh=make_mesh(sp_size=8, tp_size=1),
    )
    pipe = InProcessPipeline([eng])
    req = Request("r", prompt_ids=[1, 2, 3, 4, 5],
                  sampling_params=SamplingParams(temperature=0.0,
                                                 max_new_tokens=4))
    pipe.submit(req)
    pipe.run_until_complete()
    assert len(req.output_ids) == 4


def test_engine_sp_two_stage_pipeline():
    """SP through a 2-stage pipeline: the head ships ONE big hidden packet
    and the next stage runs its own ring prefill."""
    import numpy as np

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.base import StageModel
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams

    cfg = normalize_config(dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, vocab_size=199, max_position_embeddings=2048,
        tie_word_embeddings=False,
    ))
    full_model = StageModel(cfg, 0, 2, use_pallas=False)
    full = full_model.init_params(jax.random.key(0), dtype=jnp.float32)

    def sliced(model):
        p = {"layers": full["layers"][model.start_layer:model.end_layer]}
        if model.is_first:
            p["embed_tokens"] = full["embed_tokens"]
        if model.is_last:
            p["norm"] = full["norm"]
            p["lm_head"] = full["lm_head"]
            p.setdefault("embed_tokens", full["embed_tokens"])
        return p

    prompt = [int(x) for x in
              np.random.default_rng(1).integers(1, 198, size=300)]
    base = dict(page_size=8, num_pages=128, max_model_len=512,
                max_num_tokens_per_batch=512, kv_dtype="float32",
                enable_prefix_cache=False)

    def gen(sp):
        engines = []
        for s, e in [(0, 1), (1, 2)]:
            m = StageModel(cfg, s, e, use_pallas=False)
            kw = {}
            ecfg = dict(base)
            if sp:
                ecfg["sp_threshold"] = 256
                kw["sp_mesh"] = make_mesh(sp_size=8, tp_size=1)
            engines.append(StageEngine(m, sliced(m), EngineConfig(**ecfg),
                                       **kw))
        pipe = InProcessPipeline(engines)
        req = Request("r", prompt_ids=list(prompt),
                      sampling_params=SamplingParams(temperature=0.0,
                                                     max_new_tokens=5))
        pipe.submit(req)
        pipe.run_until_complete()
        return req.output_ids

    assert gen(sp=True) == gen(sp=False)


def test_sp_refused_for_unsupported_models():
    """Windowed/sinks/MLA/hybrid models must not silently take the SP path
    (ring attention has no window/sinks/latent semantics)."""
    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.registry import create_stage_model
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine

    sp_mesh = make_mesh(sp_size=8, tp_size=1)
    ecfg = EngineConfig(page_size=8, num_pages=64, max_model_len=256,
                        kv_dtype="float32", sp_threshold=64)

    sliding = normalize_config(dict(
        architectures=["MistralForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, vocab_size=199, sliding_window=32,
        max_position_embeddings=512, tie_word_embeddings=False,
    ))
    m = create_stage_model(sliding, 0, 2, use_pallas=False)
    eng = StageEngine(m, m.init_params(jax.random.key(0),
                                       dtype=jnp.float32),
                      ecfg, sp_mesh=sp_mesh)
    assert not eng._sp_enabled

    mla = normalize_config(dict(
        architectures=["DeepseekV3ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=4, num_experts_per_tok=2,
        first_k_dense_replace=2, vocab_size=199, rope_interleave=True,
        max_position_embeddings=512, tie_word_embeddings=False,
    ))
    m2 = create_stage_model(mla, 0, 2, use_pallas=False)
    eng2 = StageEngine(m2, m2.init_params(jax.random.key(0),
                                          dtype=jnp.float32),
                       ecfg, sp_mesh=sp_mesh)
    assert not eng2._sp_enabled


@pytest.mark.parametrize("sp", [2, 4])
def test_context_blocks_local_matches_dense(sp):
    """The SP x TP per-rank body (local query block vs full K/V in sp
    chunks, no collectives) must equal dense causal attention on the
    corresponding query rows."""
    from parallax_tpu.parallel.sp import context_blocks_attention_local

    t, hq, hkv, d = 64, 8, 4, 16
    q, k, v, pos = make_inputs(t, hq, hkv, d, seed=3, pad=5)
    kv_pos = jnp.where(pos < 0, jnp.int32(2**30), pos)
    dense = dense_causal_reference(q, k, v, pos, sm_scale=d**-0.5)
    tshard = t // sp
    for rank in range(sp):
        sl = slice(rank * tshard, (rank + 1) * tshard)
        out = context_blocks_attention_local(
            q[sl], k, v, pos[sl], kv_pos, sm_scale=d**-0.5, sp=sp,
        )
        valid = np.asarray(pos[sl]) >= 0
        np.testing.assert_allclose(
            np.asarray(out)[valid], np.asarray(dense[sl])[valid],
            rtol=2e-5, atol=2e-5,
        )
