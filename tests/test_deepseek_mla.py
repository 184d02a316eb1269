"""DeepSeek-V3 MLA tests: latent-cache attention vs HF transformers.

Capability parity: reference tests for deepseek_v3 (MLA compressed cache)
— tests/test_deepseek_v32.py / parallax_extensions MLA kernel tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.config import normalize_config
from parallax_tpu.models.registry import create_stage_model
from parallax_tpu.runtime.engine import EngineConfig, StageEngine
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, SamplingParams

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

TINY_DSV3 = dict(
    architectures=["DeepseekV3ForCausalLM"],
    hidden_size=64,
    num_hidden_layers=3,
    num_attention_heads=4,
    num_key_value_heads=4,
    kv_lora_rank=32,
    q_lora_rank=48,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    intermediate_size=128,
    moe_intermediate_size=32,
    n_routed_experts=8,
    num_experts_per_tok=2,
    n_shared_experts=1,
    n_group=2,
    topk_group=1,
    routed_scaling_factor=1.0,
    norm_topk_prob=True,
    scoring_func="sigmoid",
    first_k_dense_replace=1,
    moe_layer_freq=1,
    vocab_size=199,
    max_position_embeddings=512,
    rms_norm_eps=1e-6,
    rope_theta=10000.0,
    rope_interleave=True,
    tie_word_embeddings=False,
    attention_bias=False,
)

CONFIG = normalize_config(TINY_DSV3)


def test_config_detects_mla_and_moe():
    assert CONFIG.is_mla
    assert CONFIG.mla.kv_lora_rank == 32
    assert CONFIG.moe.num_experts == 8
    assert not CONFIG.is_moe_layer(0)     # first_k_dense_replace=1
    assert CONFIG.is_moe_layer(1)
    # One row of 32 + 8 values, held in whole 128-value lane tiles.
    assert CONFIG.kv_bytes_per_token_per_layer() == 2 * 128


@pytest.fixture(scope="module")
def hf_dsv3():
    torch.manual_seed(0)
    cfg = transformers.DeepseekV3Config(**{
        k: v for k, v in TINY_DSV3.items() if k != "architectures"
    })
    model = transformers.DeepseekV3ForCausalLM(cfg)
    model.eval()
    return model


def build_engines(hf_model, bounds):
    from parallax_tpu.models.loader import params_from_torch_state_dict

    engines = []
    for s, e in bounds:
        model = create_stage_model(CONFIG, s, e, use_pallas=False)
        params = params_from_torch_state_dict(
            model, hf_model.state_dict(), dtype=jnp.float32
        )
        engines.append(StageEngine(
            model, params,
            EngineConfig(page_size=8, num_pages=128, max_model_len=256,
                         kv_dtype="float32"),
        ))
    return engines


def generate(engines, prompt, n=6):
    pipe = InProcessPipeline(engines)
    req = Request("r", prompt_ids=list(prompt),
                  sampling_params=SamplingParams(temperature=0.0,
                                                 max_new_tokens=n))
    pipe.submit(req)
    pipe.run_until_complete()
    return req.output_ids


def test_mla_generation_matches_hf(hf_dsv3):
    from tests.test_engine_e2e import assert_greedy_matches

    prompt = [3, 14, 15, 92, 65, 35]
    out = generate(build_engines(hf_dsv3, [(0, 3)]), prompt)
    assert_greedy_matches(hf_dsv3, prompt, out, 6)


def test_mla_pipeline_matches_single(hf_dsv3):
    prompt = [9, 8, 7, 6, 5]
    single = generate(build_engines(hf_dsv3, [(0, 3)]), prompt)
    staged = generate(build_engines(hf_dsv3, [(0, 1), (1, 3)]), prompt)
    assert single == staged


def test_mla_chunked_prefill(hf_dsv3):
    from tests.test_engine_e2e import assert_greedy_matches

    prompt = [int(x) for x in
              np.random.default_rng(5).integers(0, 198, size=30)]
    engines = build_engines(hf_dsv3, [(0, 3)])
    for e in engines:
        e.scheduler.prefill_chunk_size = 8
    out = generate(engines, prompt, n=4)
    assert_greedy_matches(hf_dsv3, prompt, out, 4)
