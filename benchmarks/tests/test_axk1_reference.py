"""A.X-K1's plain reference (``references/axk1.py``) against the
program's model at the configuration's rehearsal widths (CPU, float32
weights, XLA attention, the experts' masked loop): a prompt prefilled in
chunks into the paged latent cache, then decode step by step and in
K-step windows, must give the logprobs of the reference's full forward
with the same share of the experts; the margins the reference states are
distances; and a reference with a part of the block left out — the
routed sum first — is another model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import server, server_child, spec
from benchmarks.references import axk1

CONFIG = "a.x-k1-ep16-d7"
# The engine's logprobs against the reference's, both float32: the
# absorbed form (W_uk into the query, W_uv after) and the paged softmax
# reorder float32 sums, nothing more.
TOLERANCE = 2e-4
# Under this margin two float32 computations may rank a held expert
# otherwise (a rounding of the router's logits, ~1e-6 of their spread).
FLOAT32_TIE = 1e-3


def toy():
    c = spec.load_config(CONFIG)
    return dict(c["hf"], **c["bench"]["rehearse"])


@pytest.fixture(scope="module")
def seeded():
    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.registry import create_stage_model

    hf = toy()
    cfg = normalize_config(hf)
    model = create_stage_model(cfg, 0, cfg.num_hidden_layers, tp_size=1)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          server_child.make_params(model, 3000000019))
    return hf, model, params


def through_the_engine(model, params, prompts, new_tokens, chunk, k):
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams

    engine = StageEngine(model, params, EngineConfig(
        page_size=16, num_pages=64, max_model_len=128, kv_dtype="float32",
        prefill_chunk_size=chunk, decode_lookahead=k))
    pipe = InProcessPipeline([engine])
    reqs = [Request(f"r{i}", prompt_ids=list(p), sampling_params=SamplingParams(
        temperature=0.0, max_new_tokens=new_tokens, ignore_eos=True,
        logprobs=True)) for i, p in enumerate(prompts)]
    for r in reqs:
        pipe.submit(r)
    pipe.run_until_complete()
    return reqs


def test_the_toy_keeps_the_shape_of_the_cut(seeded):
    hf, model, params = seeded
    moe = model.config.moe
    assert (moe.num_experts, moe.num_held, moe.expert_offset) == (16, 4, 4)
    assert moe.topk_method == "none" and moe.n_group > 1
    kinds = ["experts" in lp["mlp"] for lp in params["layers"]]
    assert kinds == [False, True, True]
    routed = params["layers"][1]["mlp"]
    assert routed["gate"]["weight"].shape[0] == 16
    assert routed["experts"]["gate_proj"].shape[0] == 4
    assert "e_score_correction_bias" not in routed["gate"]
    assert params["embed_tokens"]["weight"].shape[0] == hf["vocab_size"]
    assert list(axk1.held_experts(params, hf)) == [4, 5, 6, 7]


@pytest.mark.parametrize("prompt_tokens, new_tokens, chunk, k", [
    (20, 6, 64, 1), (48, 16, 24, 8), (70, 30, 40, 8)])
def test_reference_matches_the_stage_model(seeded, prompt_tokens, new_tokens,
                                           chunk, k):
    hf, model, params = seeded
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, hf["vocab_size"], (2, prompt_tokens)).tolist()
    rows = axk1.greedy_continuations(params, hf, prompts, new_tokens)
    server_child.check_choice_margins(rows)
    reqs = through_the_engine(model, params, prompts, new_tokens, chunk, k)
    compared = 0
    for r, row in zip(reqs, rows):
        for j, margin in enumerate(row["choice_margin"]):
            if margin < FLOAT32_TIE or r.output_ids[j] != row["tokens"][j]:
                # A tie inside rounding: the streams may part here.
                assert margin < FLOAT32_TIE or row["top2_gap"][j] < 1e-3
                break
            assert abs(r.output_logprobs[j] - row["logprobs"][j]) < TOLERANCE
            compared += 1
    # (Two rows; a row is left at its first tie inside float32's rounding.)
    assert compared >= new_tokens
    margins = [m for row in rows for m in row["choice_margin"]]
    assert min(margins) >= 0
    if new_tokens >= 16:
        # With 4 of 16 experts held some positions stand near a boundary.
        assert any(m < server.CHOICE_TIE for m in margins)
        assert any(m >= server.CHOICE_TIE for m in margins)


@pytest.mark.parametrize("part", sorted(axk1.PARTS))
def test_a_reference_with_a_part_left_out_is_another_model(seeded, part):
    """The routed sum (the control), the shared expert, the rotation and
    the latent's norm: left out of the reference, the program's logprobs
    are no longer its."""
    hf, model, params = seeded
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, hf["vocab_size"], (2, 48)).tolist()
    right = axk1.greedy_continuations(params, hf, prompts, 8)
    wrong = axk1.greedy_continuations(params, hf, prompts, 8,
                                      leave_out=frozenset({part}))
    gap = max(abs(a - b) for r, w in zip(right, wrong)
              for a, b in zip(r["logprobs"], w["logprobs"]))
    assert gap > 50 * TOLERANCE, (part, gap)
    with pytest.raises(ValueError, match="leave_out"):
        axk1.logits_at(params, hf, np.zeros((1, 4), np.int32),
                       np.zeros((1,), np.int32), leave_out={"conv_bias"})


def test_the_reference_knows_one_selection(seeded):
    hf, _, params = seeded
    with pytest.raises(NotImplementedError, match="topk_method"):
        axk1.logits_at(params, dict(hf, topk_method="noaux_tc"),
                       np.zeros((1, 4), np.int32), np.zeros((1,), np.int32))
