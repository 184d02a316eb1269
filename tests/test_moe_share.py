"""The expert layer told its share (``MoEConfig.experts_held`` /
``expert_offset``, ``models/moe.py``): the shares of a layer add up to
the uncut layer, ``topk_method`` "none" is the plain top-k, the two
counts against a hand count, the loader keeps a stage's share of a
whole layer's checkpoint, and the grouped-matmul path's movement between
tokens and pairs around a stood-in ``gmm`` (PR 53). Fast, no torch:
tier-1 runs these
(``tests/test_moe.py`` is one of conftest's slow modules)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.config import MoEConfig, normalize_config
from parallax_tpu.models.moe import moe_ffn, route_topk
from parallax_tpu.models.registry import create_stage_model

SHARED = dict(
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=128, vocab_size=199, max_position_embeddings=512,
    rms_norm_eps=1e-6, rope_theta=10000.0, tie_word_embeddings=False,
    norm_topk_prob=True,
    architectures=["AXK1ForCausalLM"], hidden_size=32,
    moe_intermediate_size=16, n_routed_experts=192, num_experts_per_tok=8,
    n_shared_experts=1, n_group=8, topk_group=4, scoring_func="sigmoid",
    routed_scaling_factor=2.5, topk_method="none", kv_lora_rank=16,
    q_lora_rank=24, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    first_k_dense_replace=1,
)


def _layer_params(rng, e, h, i):
    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    return {
        "gate": {"weight": draw(e, h)},
        "experts": {"gate_proj": draw(e, i, h) * h ** -0.5,
                    "up_proj": draw(e, i, h) * h ** -0.5,
                    "down_proj": draw(e, h, i) * i ** -0.5},
        "shared_expert": {
            "gate_proj": {"weight": draw(i, h) * h ** -0.5},
            "up_proj": {"weight": draw(i, h) * h ** -0.5},
            "down_proj": {"weight": draw(h, i) * i ** -0.5}},
    }


def _share_of(p, start, held):
    return dict(p, experts={k: v[start:start + held]
                            for k, v in p["experts"].items()})


def test_sixteen_shares_of_twelve_add_up_to_the_uncut_layer():
    """192 experts over 16 chips: the parts of the result that the 16
    shares give, with the shared expert (which every chip computes
    alike) counted once, are the uncut layer's; no share computes a pair
    it does not hold, and none is dropped."""
    rng = np.random.default_rng(5)
    h, i = 32, 16
    whole = normalize_config(SHARED).moe
    assert (whole.num_experts, whole.num_held, whole.expert_offset) == (
        192, 192, 0)
    p = _layer_params(rng, 192, h, i)
    x = jnp.asarray(rng.standard_normal((24, h)).astype(np.float32))
    rows = jnp.ones((24,), bool)
    uncut, counted = moe_ffn(x, p, whole, use_megablox=False,
                             count_rows=rows)
    assert np.asarray(counted).tolist()[1] == 24 * 8
    no_routed = dict(p, experts={k: v[:0] for k, v in p["experts"].items()})
    shared_once = moe_ffn(
        x, no_routed, normalize_config(dict(SHARED, experts_held=0)).moe,
        use_megablox=False)
    # (A stack of no experts: the layer's shared expert alone.)
    parts = jnp.zeros_like(uncut)
    pairs = 0
    for chip in range(16):
        moe = normalize_config(dict(
            SHARED, experts_held=12, expert_offset=12 * chip)).moe
        assert (moe.num_experts, moe.num_held) == (192, 12)
        out, counts = moe_ffn(x, _share_of(p, 12 * chip, 12), moe,
                              use_megablox=False, count_rows=rows)
        parts = parts + (out - shared_once)
        pairs += int(counts[1])
        assert int(counts[0]) <= 12
    assert pairs == 24 * 8                   # every pair on one chip
    np.testing.assert_allclose(np.asarray(parts + shared_once),
                               np.asarray(uncut), rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="no share"):
        normalize_config(dict(SHARED, experts_held=12, expert_offset=185))


def test_topk_method_none_is_the_plain_top_k_whatever_the_groups():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))
    # (Small logits: a saturated sigmoid would tie the best scores.)
    w = jnp.asarray(0.1 * rng.standard_normal((192, 32)).astype(np.float32))
    plain = normalize_config(SHARED).moe
    assert plain.topk_method == "none" and plain.n_group == 8
    assert not plain.uses_correction_bias
    grouped = normalize_config(dict(SHARED, topk_method="noaux_tc")).moe
    assert grouped.uses_correction_bias
    weights, ids = route_topk(x, w, plain)
    scores = np.asarray(jax.nn.sigmoid(x @ w.T))
    best = np.sort(np.argsort(-scores, axis=-1)[:, :8], axis=-1)
    assert np.array_equal(np.sort(np.asarray(ids), axis=-1), best)
    picked = np.take_along_axis(scores, np.asarray(ids), axis=-1)
    np.testing.assert_allclose(
        np.asarray(weights), 2.5 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)
    # The group limit keeps 4 of 8 groups of 24: some token's 8 best
    # experts lie in five groups or more, and there it selects otherwise.
    _, limited = route_topk(x, w, grouped)
    assert np.all(np.unique(np.asarray(limited) // 24, axis=-1).shape[-1]
                  <= 8)
    groups = [len(set(row // 24)) for row in np.asarray(limited)]
    assert max(groups) <= 4 < max(len(set(row // 24)) for row in best)
    assert not np.array_equal(np.sort(np.asarray(limited), axis=-1), best)


def test_the_two_counts_against_a_hand_count():
    """``held_counts``: distinct held experts hit and pairs landed on
    them, over the rows that count (a frozen or padding row does not)."""
    from parallax_tpu.models.moe import held_counts

    rng = np.random.default_rng(7)
    h, i = 32, 16
    moe = normalize_config(dict(SHARED, experts_held=12,
                                expert_offset=24)).moe
    p = _share_of(_layer_params(rng, 192, h, i), 24, 12)
    x = jnp.asarray(rng.standard_normal((40, h)).astype(np.float32))
    rows = np.ones((40,), bool)
    rows[[3, 17, 39]] = False
    out, counts = moe_ffn(x, p, moe, use_megablox=False,
                          count_rows=jnp.asarray(rows))
    _, ids = route_topk(x, p["gate"]["weight"], moe)
    ids = np.asarray(ids)[rows]
    held = (ids >= 24) & (ids < 36)
    assert np.asarray(counts).tolist() == [
        len(set(ids[held].tolist())), int(held.sum())]
    assert 0 < int(counts[1]) < rows.sum() * 8
    # Plain arithmetic of the helper: ids already local, 3 = not held.
    local = jnp.asarray([[0, 3], [1, 1], [2, 3], [0, 0]])
    every = jnp.ones((4,), bool)
    assert np.asarray(held_counts(local, 3, every)).tolist() == [3, 6]
    assert np.asarray(held_counts(
        local, 3, jnp.asarray([True, False, False, True]))).tolist() == [1, 3]
    assert out.shape == x.shape


def test_the_loader_keeps_the_share_of_a_whole_layers_checkpoint():
    """``finalize_params`` on per-expert checkpoint names: a stage told
    its share stacks experts ``offset .. offset + held`` and the first
    ``vocab_size`` rows of embedding and head; an uncut stage all."""
    hf = dict(SHARED, n_routed_experts=8, num_experts_per_tok=2, n_group=1,
              topk_group=1, num_hidden_layers=2, intermediate_size=32)

    def checkpoint():
        def w(i, shape):
            return {"weight": jnp.full(shape, float(i))}

        layer = {"mlp": {
            "gate": {"weight": jnp.zeros((8, 32))},
            "experts": {str(i): {"gate_proj": w(i, (16, 32)),
                                 "up_proj": w(i, (16, 32)),
                                 "down_proj": w(i, (32, 16))}
                        for i in range(8)},
            "shared_experts": {"gate_proj": w(9, (16, 32))}}}
        return {"layers": [{"mlp": {}}, layer],
                "embed_tokens": {"weight": jnp.arange(199.0)[:, None]},
                "lm_head": {"weight": jnp.arange(199.0)[:, None]}}

    cut = normalize_config(dict(hf, experts_held=2, expert_offset=4,
                                vocab_size=50))
    tree = create_stage_model(cut, 0, 2).finalize_params(checkpoint())
    experts = tree["layers"][1]["mlp"]["experts"]
    assert experts["gate_proj"].shape == (2, 16, 32)
    assert np.asarray(experts["down_proj"][:, 0, 0]).tolist() == [4.0, 5.0]
    assert "shared_expert" in tree["layers"][1]["mlp"]
    assert tree["embed_tokens"]["weight"].shape[0] == 50
    assert tree["lm_head"]["weight"].shape[0] == 50
    whole = create_stage_model(normalize_config(hf), 0, 2).finalize_params(
        checkpoint())
    assert whole["layers"][1]["mlp"]["experts"]["up_proj"].shape[0] == 8
    assert whole["embed_tokens"]["weight"].shape[0] == 199


V32 = dict(
    SHARED, architectures=["DeepseekV32ForCausalLM"], topk_method="greedy",
    n_group=1, topk_group=1, n_routed_experts=8, num_experts_per_tok=2,
    index_n_heads=4, index_head_dim=32, index_topk=64, rope_interleave=True,
)


@pytest.mark.parametrize("raw", [
    dict(SHARED, experts_held=12, expert_offset=24),
    dict(V32, experts_held=4, expert_offset=2),
], ids=["latent", "latent-sparse"])
def test_a_decode_window_carries_the_counts_out_with_its_tokens(raw):
    """Through the engine: the K-step window stacks ``"held"`` of the
    blocks' carry a step and ``_resolve_multistep`` adds it to the two
    series — in a family whose blocks hand a top-k on in the same carry
    too (DeepSeek-V3.2), with no flag on either."""
    from parallax_tpu.obs import get_registry, names as mnames
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams

    def total(name):
        text = get_registry().render()
        return sum(float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
                   if line.startswith(name + "{") or line.startswith(name + " "))

    cfg = normalize_config(raw)
    model = create_stage_model(cfg, 0, cfg.num_hidden_layers,
                               use_pallas=False)
    eng = StageEngine(
        model, model.init_params(jax.random.key(1), dtype=jnp.float32),
        EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                     kv_dtype="float32"))
    before = {n: total(n) for n in (mnames.MOE_EXPERTS_READ,
                                    mnames.MOE_PAIRS_HELD)}
    pipe = InProcessPipeline([eng])
    rows, new = 3, 17
    for i in range(rows):
        pipe.submit(Request(f"c{i}", prompt_ids=[5 + i, 9, 11, 2],
                            sampling_params=SamplingParams(
                                temperature=0.0, max_new_tokens=new)))
    pipe.run_until_complete()
    assert any(key[0] == 8 for key in eng._jit_multistep), eng._jit_multistep
    read = total(mnames.MOE_EXPERTS_READ) - before[mnames.MOE_EXPERTS_READ]
    pairs = total(mnames.MOE_PAIRS_HELD) - before[mnames.MOE_PAIRS_HELD]
    held, k, layers = cfg.moe.num_held, cfg.moe.num_experts_per_tok, 1
    steps = new - 1                   # the first token is the prefill's
    # No step counts more than its live rows' pairs or the held experts,
    # every counted expert was hit by a pair, and something was held.
    assert 0 < read <= pairs <= steps * rows * k * layers
    assert read <= steps * layers * min(held, rows * k)


def test_a_configuration_states_the_scales_of_its_own_seeded_draw():
    """``seeded_init``: the head's logits at a stated standard deviation
    and a gain on the routed ``down_proj``; every other leaf, and the
    whole draw of a configuration without the group, is the family's."""
    def draw(raw):
        cfg = normalize_config(raw)
        model = create_stage_model(cfg, 0, cfg.num_hidden_layers,
                                   use_pallas=False)
        return model.init_params(jax.random.key(3), dtype=jnp.float32)

    raw = dict(SHARED, experts_held=12)
    plain = draw(raw)
    own = draw(dict(raw, seeded_init={"lm_head_logit_std": 0.5,
                                      "routed_down_proj_gain": 0.5}))
    h = raw["hidden_size"]
    assert np.std(plain["lm_head"]["weight"]) == pytest.approx(0.02, rel=0.05)
    assert np.std(own["lm_head"]["weight"]) == pytest.approx(
        0.5 * h ** -0.5, rel=0.05)
    np.testing.assert_array_equal(
        np.asarray(own["layers"][1]["mlp"]["experts"]["down_proj"]),
        0.5 * np.asarray(plain["layers"][1]["mlp"]["experts"]["down_proj"]))
    own["lm_head"] = plain["lm_head"]
    own["layers"][1]["mlp"]["experts"]["down_proj"] = (
        plain["layers"][1]["mlp"]["experts"]["down_proj"])
    jax.tree.map(np.testing.assert_array_equal, own, plain)


def plain_gmm(lhs, rhs, group_sizes, transpose_rhs=False, tiling=None):
    """``megablox.gmm``'s contract as one einsum: row ``r`` times the
    matrix of the group it lies in; NaN in every row past the last
    group, which the kernel never writes."""
    assert transpose_rhs and lhs.shape[0] % tiling[0] == 0
    ends = jnp.cumsum(group_sizes)
    group = jnp.searchsorted(ends, jnp.arange(lhs.shape[0]), side="right")
    out = jnp.einsum("mk,mnk->mn", lhs,
                     rhs[jnp.minimum(group, rhs.shape[0] - 1)],
                     preferred_element_type=jnp.float32)
    return jnp.where((group < rhs.shape[0])[:, None], out, jnp.nan)


# (routed experts, held, offset, what the selection is steered to)
SHARES = {
    "every-expert-held": (16, 16, 0, None),
    "12-of-192-from-0": (192, 12, 0, None),
    "a-share-no-row-selects": (192, 12, 24, "away"),
    "every-pair-on-held-experts": (192, 12, 0, "onto"),
}


@pytest.mark.parametrize("share", list(SHARES))
@pytest.mark.parametrize("k", [2, 8])
@pytest.mark.parametrize("t", [1, 8, 128, 300])
def test_megablox_moves_tokens_to_pairs_and_back_as_the_fallback_does(
        monkeypatch, t, k, share):
    """``_moe_megablox``'s sort, gather, groups and combine around a
    stood-in ``gmm``: equal to the masked loop in float32, finite though
    every unwritten row is NaN, at a row count that is no multiple of 8
    and at a K that is not 8; the counts of ``count_rows`` by hand."""
    from jax.experimental.pallas.ops.tpu import megablox

    monkeypatch.setattr(megablox, "gmm", plain_gmm)
    experts, held, offset, steer = SHARES[share]
    h, i = 32, 16
    moe = MoEConfig(num_experts=experts, num_experts_per_tok=k,
                    moe_intermediate_size=i, scoring_func="sigmoid",
                    topk_method="noaux_tc", routed_scaling_factor=2.5,
                    experts_held=held, expert_offset=offset)
    rng = np.random.default_rng(1000 * t + 10 * k + len(share))

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    mine = (np.arange(experts) >= offset) & (np.arange(experts) < offset + held)
    bias = {None: np.zeros(experts), "away": np.where(mine, -1e4, 0.0),
            "onto": np.where(mine, 1e4, 0.0)}[steer]
    p = {
        "gate": {"weight": draw(experts, h),
                 "e_score_correction_bias": jnp.asarray(bias, jnp.float32)},
        "experts": {"gate_proj": draw(held, i, h) * h ** -0.5,
                    "up_proj": draw(held, i, h) * h ** -0.5,
                    "down_proj": draw(held, h, i) * i ** -0.5},
    }
    x = draw(t, h)
    rows = jnp.asarray(rng.random(t) < 0.7)
    want, want_counts = moe_ffn(x, p, moe, use_megablox=False,
                                count_rows=rows)
    got, got_counts = moe_ffn(x, p, moe, use_megablox=True, count_rows=rows)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    _, ids = route_topk(x, p["gate"]["weight"], moe,
                        bias=p["gate"]["e_score_correction_bias"])
    landed = mine[np.asarray(ids)]                         # [T, K]
    if steer is not None:
        assert landed.sum() == {"away": 0, "onto": t * k}[steer]
    elif experts > held and t >= 128:     # a share: some pairs, not all
        assert 0 < landed.sum() < t * k
    counted = np.asarray(ids)[np.asarray(rows)[:, None] & landed]
    by_hand = [len(set(counted.tolist())), counted.size]
    assert np.asarray(got_counts).tolist() == by_hand
    assert np.asarray(want_counts).tolist() == by_hand
