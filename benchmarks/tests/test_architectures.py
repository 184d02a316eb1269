"""The weight draw and the reference hook know no layer by name: every
family of ``models/registry.py`` gets seeded weights at toy widths, the
dense tree is bit for bit what it was before the draw became general,
and a configuration's own reference module is the one the child runs."""

import hashlib
import json
import os

import jax
import numpy as np
import pytest

from benchmarks.harness import server_child, spec

COMMON = dict(hidden_size=64, num_attention_heads=4, vocab_size=199,
              max_position_embeddings=512, rms_norm_eps=1e-6,
              rope_theta=10000.0, tie_word_embeddings=False)
GQA = dict(COMMON, num_key_value_heads=2, head_dim=16)
MLA = dict(COMMON, num_hidden_layers=3, num_key_value_heads=4,
           kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
           moe_intermediate_size=32, n_routed_experts=8,
           num_experts_per_tok=2, n_shared_experts=1, n_group=2,
           topk_group=1, routed_scaling_factor=1.0, norm_topk_prob=True,
           scoring_func="sigmoid", first_k_dense_replace=1,
           rope_interleave=True)
# One toy configuration per StageModel class of the registry (the tests/
# of each family use the same widths).
FAMILIES = {
    "Qwen2ForCausalLM": dict(GQA, num_hidden_layers=2, intermediate_size=96,
                             attention_bias=True),
    "Qwen3ForCausalLM": dict(GQA, num_hidden_layers=2, intermediate_size=96),
    "LlamaForCausalLM": dict(GQA, num_hidden_layers=2, intermediate_size=96),
    "MistralForCausalLM": dict(GQA, num_hidden_layers=2,
                               intermediate_size=96),
    "Qwen3MoeForCausalLM": dict(
        GQA, num_hidden_layers=2, intermediate_size=128,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[]),
    "Qwen3NextForCausalLM": dict(
        GQA, num_hidden_layers=4, intermediate_size=96,
        moe_intermediate_size=32, num_experts=4, num_experts_per_tok=2,
        shared_expert_intermediate_size=32, decoder_sparse_step=1,
        mlp_only_layers=[], norm_topk_prob=True,
        layer_types=["linear_attention", "full_attention"] * 2,
        linear_conv_kernel_dim=4, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, partial_rotary_factor=0.25),
    "DeepseekV3ForCausalLM": dict(MLA, moe_layer_freq=1),
    "DeepseekV32ForCausalLM": dict(MLA, index_n_heads=4, index_head_dim=32,
                                   index_topk=64),
    "Glm4ForCausalLM": dict(GQA, num_hidden_layers=2, intermediate_size=96,
                            partial_rotary_factor=0.5, attention_bias=True),
    "Glm4MoeForCausalLM": dict(
        GQA, num_hidden_layers=3, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
        n_shared_experts=1, n_group=2, topk_group=1, scoring_func="sigmoid",
        norm_topk_prob=True, routed_scaling_factor=1.0,
        first_k_dense_replace=1, partial_rotary_factor=0.5,
        use_qk_norm=True),
    "GptOssForCausalLM": dict(
        GQA, num_hidden_layers=2, intermediate_size=32, num_local_experts=4,
        num_experts_per_tok=2, sliding_window=8,
        layer_types=["sliding_attention", "full_attention"],
        attention_bias=True),
    "MiniMaxM2ForCausalLM": dict(
        GQA, num_hidden_layers=2, intermediate_size=64, num_local_experts=4,
        num_experts_per_tok=2, scoring_func="sigmoid",
        routed_scaling_factor=1.0, partial_rotary_factor=0.5,
        use_qk_norm=True, rotary_dim=8),
    "MiniMaxM3SparseForCausalLM": dict(
        GQA, model_type="minimax_m3", num_hidden_layers=3,
        intermediate_size=64, dense_intermediate_size=128,
        shared_intermediate_size=64, rope_theta=5000000,
        partial_rotary_factor=0.5, use_qk_norm=True, use_gemma_norm=True,
        num_local_experts=4, num_experts_per_tok=2, n_shared_experts=1,
        scoring_func="sigmoid", use_routing_bias=True,
        routed_scaling_factor=2.0,
        mlp_layer_types=["dense", "sparse", "sparse"],
        layer_types=["full_attention", "minimax_m3_sparse",
                     "minimax_m3_sparse"],
        index_n_heads=2, index_head_dim=16, index_block_size=4,
        index_topk_blocks=2, index_local_blocks=1, swiglu_alpha=1.702,
        swiglu_limit=7.0, swiglu_beta=1.0),
    "Step3p5ForCausalLM": dict(
        COMMON, num_hidden_layers=4, num_attention_groups=2, head_dim=16,
        intermediate_size=64, moe_num_experts=4, moe_top_k=2,
        sliding_window=16,
        layer_types=["full_attention", "sliding_attention"] * 2),
}


def stage_model(hf: dict):
    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.registry import create_stage_model

    cfg = normalize_config(hf)
    return create_stage_model(cfg, 0, cfg.num_hidden_layers, tp_size=1)


def leaves(params) -> dict:
    return {"/".join(server_child.path_names(path)): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}


def tree_hash(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.view(np.uint8).tobytes())
    return h.hexdigest()


def test_the_toy_configurations_cover_the_registry():
    import parallax_tpu.models  # noqa: F401  (fills the registry)
    from parallax_tpu.models.registry import MODEL_REGISTRY

    classes = {MODEL_REGISTRY[a] for a in FAMILIES}
    assert classes == set(MODEL_REGISTRY.values())


@pytest.mark.parametrize("arch", sorted(FAMILIES))
def test_make_params_draws_every_bias_of_every_family(arch):
    model = stage_model(dict(FAMILIES[arch], architectures=[arch]))
    a, again, other = (leaves(server_child.make_params(model, seed))
                       for seed in (3000000019, 3000000019, 7))
    biases = [k for k in a if server_child.is_bias(tuple(k.split("/")))]
    if arch not in ("Qwen3ForCausalLM", "LlamaForCausalLM",
                    "MistralForCausalLM", "Qwen3MoeForCausalLM",
                    "Step3p5ForCausalLM", "MiniMaxM2ForCausalLM"):
        assert biases, "this family has a bias-like leaf"
    for k in biases:
        x = a[k].astype(np.float32)
        # Drawn, not left at init_params' constant.
        assert np.all(np.isfinite(x)) and x.std() > 0, k
        assert not np.array_equal(x, other[k].astype(np.float32)), k
    assert a.keys() == again.keys() == other.keys()
    for k in a:
        assert np.array_equal(a[k], again[k]), k     # one seed repeats
    assert any(not np.array_equal(a[k], other[k]) for k in a)
    # Norm weights stay at init_params' constant (one; zero where the
    # family stores a gemma norm's offset).
    for k, v in a.items():
        if k.endswith("layernorm/weight") or k == "norm/weight":
            assert np.unique(v.astype(np.float32)).tolist() in ([1.0], [0.0]), k


# sha256 of the toy Qwen2 tree as ``make_params`` of the parent commit
# (8165e35, before the draw became general) gave it on the CPU:
# qwen2.5-7b-d24's ``rehearse`` widths, (tied head, seed).
PARENT_TREES = {
    (False, 7): "01cfbd6cdf7ccba4c5e255528e9e940161e044931948a0fbedd47695a151b922",
    (False, 3000000019): "4845f0c68bf229690f1d6369fb42aa551bc43e88ee2c7cf44449384faff644de",
    (True, 7): "cc997701623976d066339f8876f1733fe56b714a153c4d85871cc9b37b4af8ed",
    (True, 3000000019): "0ef2c2420f82a729bc8fcf5b07b38f7834aa4eab6561fd1adf374a49ad291a72",
}


@pytest.mark.parametrize("tied, seed", sorted(PARENT_TREES))
def test_the_dense_tree_is_bit_identical_to_the_parents(tied, seed):
    c = spec.load_config("qwen2.5-7b-d24")
    model = stage_model(dict(c["hf"], **c["bench"]["rehearse"],
                             tie_word_embeddings=tied))
    params = server_child.make_params(model, seed)
    assert tree_hash(params) == PARENT_TREES[(tied, seed)]


DELEGATE = '''"""Written by a test: the dense block under another name."""
from benchmarks.harness.reference import greedy_continuations  # noqa: F401
CALLS = []
_dense = greedy_continuations


def greedy_continuations(params, cfg, prompts, n_new):
    CALLS.append((len(prompts), len(prompts[0]), n_new))
    return _dense(params, cfg, prompts, n_new)
'''


@pytest.fixture()
def delegate_module():
    path = spec.reference_path("delegate_for_test")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        f.write(DELEGATE)
    try:
        yield "delegate_for_test"
    finally:
        os.remove(path)


def test_run_reference_calls_the_module_the_configuration_names(
        tmp_path, delegate_module):
    import importlib

    c = spec.load_config("qwen2.5-7b-d24")
    hf = dict(c["hf"], **c["bench"]["rehearse"])
    rows = [{"prompts": 2, "prompt_tokens": 12, "new_tokens": 3},
            {"prompts": 1, "prompt_tokens": 40, "new_tokens": 2}]
    ref = spec.reference_of(dict(c["bench"], reference={
        "module": delegate_module, "rows": rows}))
    assert ref["module"] == delegate_module
    params = server_child.make_params(stage_model(hf), 11)
    out = str(tmp_path / "reference.json")
    server_child.run_reference(params, hf, 11, out, ref)
    module = importlib.import_module(ref["import"])
    assert module.CALLS == [(2, 12, 3), (1, 40, 2)]
    with open(out) as f:
        got = json.load(f)
    assert got["module"] == delegate_module
    assert [(len(r["prompt"]), len(r["tokens"])) for r in got["rows"]] == [
        (12, 3), (12, 3), (40, 2)]
    # The first shape's prompts are what the one fixed shape drew before.
    rng = np.random.default_rng([11, 0x5EF])
    assert got["rows"][0]["prompt"] == rng.integers(
        0, hf["vocab_size"], (2, 12)).tolist()[0]
    # Without the key: the dense block and its one row shape.
    default = spec.reference_of(c["bench"])
    assert default["module"] == "harness/reference"
    assert default["rows"] == [
        {"prompts": 4, "prompt_tokens": 48, "new_tokens": 16}]
