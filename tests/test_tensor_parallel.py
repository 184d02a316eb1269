"""Tensor-parallel stage correctness on the virtual CPU mesh.

TP must be output-invariant: a tp=2 / tp=4 sharded engine produces the same
generations as the unsharded engine (reference counterpart: TP shard tests
via mx.distributed; here shard_map over an 8-device CPU mesh).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.config import normalize_config
from parallax_tpu.models.base import StageModel
from parallax_tpu.parallel import make_mesh
from parallax_tpu.runtime.engine import EngineConfig, StageEngine
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, SamplingParams

TINY = dict(
    architectures=["Qwen2ForCausalLM"],
    hidden_size=64,
    num_hidden_layers=2,
    num_attention_heads=8,
    num_key_value_heads=4,
    intermediate_size=128,
    vocab_size=128,
    max_position_embeddings=256,
)


def run_engine(tp_size, prompts, n_new=6):
    config = normalize_config(TINY)
    mesh = make_mesh(tp_size=tp_size) if tp_size > 1 else None
    model = StageModel(config, 0, 2, use_pallas=False, tp_size=tp_size)
    # init_params builds global (unsharded) shapes from config alone.
    params = model.init_params(jax.random.key(7), dtype=jnp.float32)
    eng = StageEngine(
        model,
        params,
        EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                     kv_dtype="float32", max_num_tokens_per_batch=128),
        mesh=mesh,
    )
    pipe = InProcessPipeline([eng])
    for i, p in enumerate(prompts):
        pipe.submit(Request(
            request_id=f"r{i}", prompt_ids=list(p),
            sampling_params=SamplingParams(temperature=0.0, max_new_tokens=n_new),
        ))
    pipe.run_until_complete()
    return {r.request_id: r.output_ids for r in pipe.finished}


@pytest.mark.parametrize("tp_size", [2, 4])
def test_tp_matches_single_device(tp_size):
    if len(jax.devices()) < tp_size:
        pytest.skip("not enough virtual devices")
    prompts = [[1, 2, 3, 4, 5], [100, 90, 80, 70]]
    expected = run_engine(1, prompts)
    got = run_engine(tp_size, prompts)
    assert got == expected


def run_engine_fused(tp_size, specs, n_new=10, lookahead=1, pipeline=1):
    """specs: (prompt, temperature, seed). Returns (outputs, engine)."""
    config = normalize_config(TINY)
    mesh = make_mesh(tp_size=tp_size) if tp_size > 1 else None
    model = StageModel(config, 0, 2, use_pallas=False, tp_size=tp_size)
    params = model.init_params(jax.random.key(7), dtype=jnp.float32)
    eng = StageEngine(
        model, params,
        EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                     kv_dtype="float32", max_num_tokens_per_batch=128,
                     decode_lookahead=lookahead, decode_pipeline=pipeline),
        mesh=mesh,
    )
    pipe = InProcessPipeline([eng])
    for i, (p, temp, seed) in enumerate(specs):
        pipe.submit(Request(
            request_id=f"r{i}", prompt_ids=list(p),
            sampling_params=SamplingParams(
                temperature=temp, max_new_tokens=n_new, seed=seed,
                ignore_eos=True),
        ))
    pipe.run_until_complete()
    return {r.request_id: r.output_ids for r in pipe.finished}, eng


def test_tp_fused_multistep_matches_single_step():
    """VERDICT r2 #3: the k-token decode window must cover TP-sharded
    stages — the whole scan runs inside one shard_map dispatch."""
    if len(jax.devices()) < 2:
        pytest.skip("not enough virtual devices")
    specs = [([1, 2, 3, 4, 5], 0.0, None), ([100, 90, 80], 0.0, None)]
    base, _ = run_engine_fused(2, specs, lookahead=1)
    fused, eng = run_engine_fused(2, specs, lookahead=4, pipeline=2)
    assert (4, False, False, ()) in eng._jit_multistep   # fused path ran under TP
    assert fused == base


def test_tp_fused_sampled_seeded_matches_single_step():
    if len(jax.devices()) < 2:
        pytest.skip("not enough virtual devices")
    specs = [([5, 6, 7], 0.9, 17), ([8, 9, 10, 11], 0.0, None)]
    base, _ = run_engine_fused(2, specs, lookahead=1)
    fused, eng = run_engine_fused(2, specs, lookahead=3)
    assert (3, True, False, ()) in eng._jit_multistep
    assert fused == base


def test_tp_speculative_matches_plain_greedy():
    """Prompt-lookup speculation is TP-eligible now the mesh bar is
    lifted; verification logits come from the same shard_mapped stage fn
    so acceptance must reproduce plain greedy exactly."""
    if len(jax.devices()) < 2:
        pytest.skip("not enough virtual devices")
    config = normalize_config(TINY)
    rep = [7, 8, 9, 10] * 5    # repetitive: n-gram proposals fire

    def run(spec_tokens):
        mesh = make_mesh(tp_size=2)
        model = StageModel(config, 0, 2, use_pallas=False, tp_size=2)
        params = model.init_params(jax.random.key(7), dtype=jnp.float32)
        eng = StageEngine(
            model, params,
            EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                         kv_dtype="float32", max_num_tokens_per_batch=128,
                         speculative_tokens=spec_tokens),
            mesh=mesh,
        )
        pipe = InProcessPipeline([eng])
        pipe.submit(Request(
            "r", prompt_ids=list(rep),
            sampling_params=SamplingParams(temperature=0.0,
                                           max_new_tokens=12,
                                           ignore_eos=True),
        ))
        pipe.run_until_complete()
        return pipe.finished[0].output_ids

    assert run(4) == run(0)


def test_tp_requires_divisible_heads():
    config = normalize_config(dict(TINY, num_key_value_heads=3))
    with pytest.raises(ValueError, match="not divisible"):
        StageModel(config, 0, 2, tp_size=2)


def test_tp_row_parallel_bias_added_once():
    """o_proj/down_proj biases must be added after the psum, not per-shard."""
    if len(jax.devices()) < 2:
        pytest.skip("not enough virtual devices")
    config = normalize_config(TINY)
    prompts = [[1, 2, 3, 4]]

    def run(tp_size):
        model = StageModel(config, 0, 2, use_pallas=False, tp_size=tp_size)
        params = model.init_params(jax.random.key(3), dtype=jnp.float32)
        for lp in params["layers"]:
            h = config.hidden_size
            lp["self_attn"]["o_proj"]["bias"] = (
                jnp.arange(h, dtype=jnp.float32) * 0.01
            )
            lp["mlp"]["down_proj"]["bias"] = (
                jnp.arange(h, dtype=jnp.float32) * -0.02
            )
        mesh = make_mesh(tp_size=tp_size) if tp_size > 1 else None
        eng = StageEngine(
            model, params,
            EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                         kv_dtype="float32"),
            mesh=mesh,
        )
        pipe = InProcessPipeline([eng])
        pipe.submit(Request(
            "r", prompt_ids=list(prompts[0]),
            sampling_params=SamplingParams(temperature=0.0, max_new_tokens=5),
        ))
        pipe.run_until_complete()
        return pipe.finished[0].output_ids

    assert run(2) == run(1)


def test_tied_embeddings_split_pipeline():
    """A tied-embedding model split across stages must still serve: the last
    stage needs the embedding matrix as its lm_head."""
    config = normalize_config(dict(TINY, tie_word_embeddings=True))
    engines = []
    for s, e in [(0, 1), (1, 2)]:
        m = StageModel(config, s, e, use_pallas=False)
        engines.append(StageEngine(
            m, m.init_params(jax.random.key(5), dtype=jnp.float32),
            EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                         kv_dtype="float32"),
        ))
    pipe = InProcessPipeline(engines)
    req = Request(
        "r", prompt_ids=[5, 6, 7],
        sampling_params=SamplingParams(temperature=0.0, max_new_tokens=4),
    )
    pipe.submit(req)
    pipe.run_until_complete()
    assert len(req.output_ids) == 4


def test_dsa_model_engine_with_tp_mesh():
    """DeepSeek-V3.2 under tp=2: tuple (latent, index) cache specs must
    build and the engine must generate (index caches replicated, MLA heads
    sharded)."""
    import numpy as np

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.registry import create_stage_model
    from parallax_tpu.parallel import make_mesh
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams

    cfg = normalize_config(dict(
        architectures=["DeepseekV32ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4,
        index_head_dim=32, index_topk=16,
        # GLM-style: layer 1 shares layer 0's top-k (exercises the
        # (latent, None) tuple spec).
        index_topk_freq=2, index_skip_topk_offset=0,
        intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=4, num_experts_per_tok=2, first_k_dense_replace=2,
        vocab_size=199, rope_interleave=True,
        max_position_embeddings=512, tie_word_embeddings=False,
    ))
    mesh = make_mesh(tp_size=2)
    model = create_stage_model(cfg, 0, 2, use_pallas=False, tp_size=2)
    eng = StageEngine(
        model, model.init_params(jax.random.key(0), dtype=jnp.float32),
        EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                     kv_dtype="float32"),
        mesh=mesh,
    )
    pipe = InProcessPipeline([eng])
    req = Request("tp-dsa", prompt_ids=[int(x) for x in
                                        np.arange(1, 25)],
                  sampling_params=SamplingParams(temperature=0.0,
                                                 max_new_tokens=4))
    pipe.submit(req)
    pipe.run_until_complete()
    assert len(req.output_ids) == 4

    # TP output must match the unsharded engine exactly.
    m1 = create_stage_model(cfg, 0, 2, use_pallas=False)
    e1 = StageEngine(
        m1, m1.init_params(jax.random.key(0), dtype=jnp.float32),
        EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                     kv_dtype="float32"),
    )
    p1 = InProcessPipeline([e1])
    r1 = Request("base", prompt_ids=[int(x) for x in np.arange(1, 25)],
                 sampling_params=SamplingParams(temperature=0.0,
                                                max_new_tokens=4))
    p1.submit(r1)
    p1.run_until_complete()
    assert req.output_ids == r1.output_ids
