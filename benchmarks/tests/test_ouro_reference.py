"""Ouro's plain reference (``references/ouro.py``) against the program's
model at the configuration's rehearsal widths (CPU, float32 weights, XLA
attention): a prompt prefilled in chunks into ``passes x layers`` cache
layers, then decode step by step and in K-step windows, must give the
logprobs of the reference's full forward (which holds no cache at all);
a reference with a norm or a pass left out must not; and the harness's
weight draw reaches the exit gate's bias and leaves the norms as
``init_params`` drew them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import server_child, spec
from benchmarks.references import ouro

CONFIG = "ouro-2.6b"
# The engine's logprobs against the reference's, both float32.
TOLERANCE = 2e-4


def toy(**over):
    c = spec.load_config(CONFIG)
    return {**c["hf"], **c["bench"]["rehearse"], **over}


def seeded_model(hf):
    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.registry import create_stage_model

    cfg = normalize_config(hf)
    model = create_stage_model(cfg, 0, cfg.num_hidden_layers, tp_size=1)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          server_child.make_params(model, 3000000019))
    return model, params


@pytest.fixture(scope="module", params=[2, 4], ids=["2-passes", "4-passes"])
def seeded(request):
    hf = toy(total_ut_steps=request.param)
    return (hf, *seeded_model(hf))


def through_the_engine(model, params, prompts, new_tokens, chunk, k):
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams

    engine = StageEngine(model, params, EngineConfig(
        page_size=16, num_pages=64, max_model_len=128, kv_dtype="float32",
        prefill_chunk_size=chunk, decode_lookahead=k))
    # One array a layer, every pass's pages in it.
    assert [a.shape[0] for a in engine.kv] == [
        model.config.loop_passes * 64] * model.config.num_hidden_layers
    pipe = InProcessPipeline([engine])
    reqs = [Request(f"r{i}", prompt_ids=list(p), sampling_params=SamplingParams(
        temperature=0.0, max_new_tokens=new_tokens, ignore_eos=True,
        logprobs=True)) for i, p in enumerate(prompts)]
    for r in reqs:
        pipe.submit(r)
    pipe.run_until_complete()
    return reqs


@pytest.mark.parametrize("prompt_tokens, new_tokens, chunk, k", [
    (20, 6, 64, 1), (48, 16, 24, 8), (70, 30, 40, 8)])
def test_reference_matches_the_stage_model(seeded, prompt_tokens, new_tokens,
                                           chunk, k):
    hf, model, params = seeded
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, hf["vocab_size"], (2, prompt_tokens)).tolist()
    rows = ouro.greedy_continuations(params, hf, prompts, new_tokens)
    reqs = through_the_engine(model, params, prompts, new_tokens, chunk, k)
    for r, row in zip(reqs, rows):
        assert list(r.output_ids) == row["tokens"]
        np.testing.assert_allclose(r.output_logprobs, row["logprobs"],
                                   atol=TOLERANCE)


@pytest.mark.parametrize("part", sorted(ouro.PARTS))
def test_a_reference_with_a_part_left_out_is_another_model(part):
    """The norm closing a pass, either branch norm, the last pass: left
    out of the reference, the program's logprobs are no longer its.
    (The harness draws every norm as a constant: the normalisation
    alone, not a weight, is what the wrong reference lacks.)"""
    hf = toy()
    model, params = seeded_model(hf)
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, hf["vocab_size"], (2, 48)).tolist()
    right = ouro.greedy_continuations(params, hf, prompts, 8)
    wrong = ouro.greedy_continuations(params, hf, prompts, 8,
                                      leave_out=frozenset({part}))
    gap = max(abs(a - b) for r, w in zip(right, wrong)
              for a, b in zip(r["logprobs"], w["logprobs"]))
    assert gap > 50 * TOLERANCE, (part, gap)
    with pytest.raises(ValueError, match="leave_out"):
        ouro.logits_at(params, hf, np.zeros((1, 4), np.int32),
                       np.zeros((1,), np.int32), leave_out={"rope"})


def test_make_params_draws_the_gate_and_leaves_the_norms_as_drawn():
    model, _ = seeded_model(toy())
    a, again, other = (server_child.make_params(model, seed)
                       for seed in (3000000019, 3000000019, 7))
    gate = a["early_exit_gate"]
    assert gate["weight"].shape == (1, 128) and gate["bias"].shape == (1,)
    assert float(jnp.abs(gate["bias"].astype(jnp.float32)).max()) > 0
    for layer in a["layers"]:
        assert sorted(k for k in layer if "norm" in k) == [
            "input_layernorm", "input_layernorm_2",
            "post_attention_layernorm", "post_attention_layernorm_2"]
        # The norms before a branch at one; the norms on a branch at
        # (2 x layers)^-0.5, the depth scaling of the residual branches.
        branch = float(jnp.asarray(6 ** -0.5, jnp.bfloat16))
        for k in layer:
            if "norm" in k:
                want = branch if k.endswith("_2") else 1.0
                assert np.all(np.asarray(layer[k]["weight"],
                                         np.float32) == want)
        assert not any("bias" in p for p in layer["self_attn"]["q_proj"])
    same = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, again)
    assert all(jax.tree.leaves(same))
    differs = jax.tree.map(lambda x, y: not bool(jnp.array_equal(x, y)),
                           a, other)
    assert any(jax.tree.leaves(differs))


def test_the_cell_rehearses_from_its_files():
    """``ouro-2.6b.decode-probe2-2k`` at ``--rehearse``, from the cell's
    files and ``BENCHMARK.json``'s entries."""
    from benchmarks.tests.test_rehearse import rehearse

    line = rehearse("ouro-2.6b.decode-probe2-2k", trace=1)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # Two passes of three layers at the toy widths: 2 x 3 x 2 x 4 x 32 x 2 B.
    assert got["kv_token_kib"] == 3.0
    assert got["kv_preemptions"] == 0.0
