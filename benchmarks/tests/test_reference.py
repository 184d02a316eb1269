"""The plain reference against ``models/base.py`` at the toy preset (CPU,
float32 weights, XLA attention): prefill then decode through the paged
cache must give the reference's full-forward logits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference, spec


@pytest.mark.parametrize("tied_head", [False, True])
def test_reference_matches_the_stage_model(tied_head):
    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.registry import create_stage_model
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams

    c = spec.load_config("qwen2.5-7b-d24")
    hf = dict(c["hf"], **c["bench"]["rehearse"], tie_word_embeddings=tied_head)
    cfg = normalize_config(hf)
    model = create_stage_model(cfg, 0, cfg.num_hidden_layers, tp_size=1)
    params = model.init_params(jax.random.key(3), dtype=jnp.float32)
    for i, layer in enumerate(params["layers"]):       # biases that matter
        for name in ("q_proj", "k_proj", "v_proj"):
            b = layer["self_attn"][name]["bias"]
            layer["self_attn"][name]["bias"] = 0.1 * jax.random.normal(
                jax.random.key(100 + i), b.shape, b.dtype)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, hf["vocab_size"], (2, 20)).tolist()
    rows = reference.greedy_continuations(params, hf, prompts, 6)

    engine = StageEngine(model, params, EngineConfig(
        page_size=8, num_pages=64, max_model_len=128, kv_dtype="float32"))
    pipe = InProcessPipeline([engine])
    reqs = [Request(f"r{i}", prompt_ids=list(p), sampling_params=SamplingParams(
        temperature=0.0, max_new_tokens=6, ignore_eos=True, logprobs=True))
        for i, p in enumerate(prompts)]
    for r in reqs:
        pipe.submit(r)
    pipe.run_until_complete()
    for r, row in zip(reqs, rows):
        assert list(r.output_ids) == row["tokens"]
        np.testing.assert_allclose(r.output_logprobs, row["logprobs"], atol=2e-4)
