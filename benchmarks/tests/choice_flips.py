"""Where a bf16 program and its float32 reference choose otherwise: the
measurement behind ``server.CHOICE_TIE`` (PERF.md section 2). Not a test
and not a configuration: a measurement of rounding at a router.

    JAX_PLATFORMS=cpu python3 benchmarks/tests/choice_flips.py --size cpu
    chiprun -- python3 benchmarks/tests/choice_flips.py --size chip

The registry's DeepSeek class on the chip (``models/deepseek_v3.py``,
latent attention; the Glm4Moe class on the CPU, see ``FAMILIES``), XLA
path, no ``serve``: one leading dense layer, then ``--layers`` layers of 192
sigmoid-routed experts, 8 a token, one shared expert.
The same seeded weights run twice over the same prompts, in bf16 as a
program holds them and upcast to float32 under ``"highest"`` as a
reference does; ``moe.route_topk`` is wrapped so that every routed
layer hands out its router logits and the experts it chose. For every
token and layer the two selections are compared, and a candidate chosen
by one side only is a *flip*, at the margin the float32 side gave it
(``references.choice_margin``'s unit: distance to the selection
boundary over the standard deviation of the layer's logits).

``--held 12`` is a chip's share: the other experts' ``down_proj`` is
nought on both sides, so only a flip of a held expert changes what the
stage computes, as in a program told which experts it holds.

Printed, and written to ``chiprun_out/choice_flips_<size>_<family>_held<n>.json``
(``--out``):
the flipping margins (all of them, and those of a token's first
flipping layer, which rounding alone explains: after a flip the streams
differ by a whole expert), the error of a router logit, and by
``CHOICE_TIE`` the share of positions it makes unsure and the sure
positions that flipped all the same (the unsound event: none may).
``--control`` is the same with the program one precision down (float8
weights): its flips must stand well over the constant.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXPERTS, TOP_K, SHARE = 192, 8, 12

COMMON = dict(
    first_k_dense_replace=1, moe_layer_freq=1, n_routed_experts=EXPERTS,
    num_experts_per_tok=TOP_K, n_shared_experts=1, n_group=1, topk_group=1,
    topk_method="greedy", scoring_func="sigmoid", norm_topk_prob=True,
    routed_scaling_factor=2.5, moe_intermediate_size=64, vocab_size=2048,
    max_position_embeddings=4096, rms_norm_eps=1e-6, rope_theta=10000.0,
    tie_word_embeddings=False, attention_bias=False)
# Which class of the registry runs, and the attention it needs sized.
# XLA's CPU backend has no batched bf16 x bf16 -> float32 product, which
# the DeepSeek class's absorbed latent attention asks for: off the chip
# the same expert layer (``moe.moe_ffn``: router, routed and shared
# experts, a leading dense layer) runs under the Glm4Moe class, whose
# attention is grouped-query. Both carry the stream in bf16. The
# Qwen3Moe class runs ``StageModel``'s own block, which carries it in
# float32 where the configuration says so (as the Jamba and Ouro cells
# do) and rounds it where a matmul reads it.
FAMILIES = {
    "deepseek": ("latent", dict(architectures=["DeepseekV3ForCausalLM"],
                                rope_interleave=True)),
    "glm4moe": ("grouped", dict(architectures=["Glm4MoeForCausalLM"],
                                partial_rotary_factor=0.5, use_qk_norm=True)),
    "qwen3moe": ("grouped", dict(architectures=["Qwen3MoeForCausalLM"],
                                 n_shared_experts=0)),
    "qwen3moe-fp32-stream": ("grouped", dict(
        architectures=["Qwen3MoeForCausalLM"], n_shared_experts=0,
        fp32_skip_add=True)),
}
SIZES = {
    # The router at the width of the drawn configuration (7168 x 192,
    # its latent attention's ranks and head sizes as published); the
    # experts' own width cut to 64 so that six routed layers fit twice.
    "chip": dict(
        family="deepseek", hidden_size=7168, intermediate_size=2048,
        prompts=8, tokens=512,
        latent=dict(num_attention_heads=64, num_key_value_heads=64,
                    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                    qk_rope_head_dim=64, v_head_dim=128),
        grouped=dict(num_attention_heads=64, num_key_value_heads=8,
                     head_dim=128)),
    "cpu": dict(
        family="glm4moe", hidden_size=1024, intermediate_size=1024,
        prompts=8, tokens=640,
        grouped=dict(num_attention_heads=8, num_key_value_heads=2,
                     head_dim=128)),
    "toy": dict(
        family="glm4moe", hidden_size=128, intermediate_size=128,
        moe_intermediate_size=16, vocab_size=199, prompts=2, tokens=48,
        grouped=dict(num_attention_heads=4, num_key_value_heads=2,
                     head_dim=32)),
}


class Recorded:
    """A stage whose routed layers hand out their router logits and the
    experts they chose: ``moe.route_topk`` wrapped while the stage's
    step is traced (the block is traced once a kind and replayed a
    layer: the callback is an equation of it, so the records come in
    the order the layers run). One jitted step, so one compile a dtype."""

    def __init__(self, model):
        import jax

        self.model = model
        self.sink: list = []
        self.step = jax.jit(lambda p, kv, inputs: model(p, kv, inputs)[0])

    def route_topk(self, route):
        import jax
        import jax.numpy as jnp

        def recording(x, router_weight, cfg, bias=None):
            weights, ids = route(x, router_weight, cfg, bias=bias)
            # The router's own product (bf16 factors are exact in
            # float32; the CPU backend has no bf16 x bf16 -> float32
            # dot of its own).
            logits = (x.astype(jnp.float32)
                      @ router_weight.astype(jnp.float32).T)
            jax.debug.callback(lambda a, b: self.sink.append((a, b)),
                               logits, ids, ordered=True)
            return weights, ids

        return recording

    def forward_all(self, params, prompts, kv_dtype, page_size=64):
        """Every prompt prefilled alone from an empty cache: per prompt
        and routed layer, (logits [T, E], ids [T, K]) as numpy."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from parallax_tpu.models import moe
        from parallax_tpu.models.base import BatchInputs

        t = len(prompts[0])
        pages = -(-t // page_size)
        out = []
        route = moe.route_topk
        moe.route_topk = self.route_topk(route)
        try:
            for prompt in prompts:
                inputs = BatchInputs(
                    token_ids=jnp.asarray(prompt, jnp.int32),
                    hidden_states=None,
                    positions=jnp.arange(t, dtype=jnp.int32),
                    kv_lens=jnp.asarray([t], jnp.int32),
                    page_indices=jnp.arange(pages, dtype=jnp.int32)[None],
                    cu_q_lens=jnp.asarray([0, t], jnp.int32),
                    num_seqs=jnp.asarray([1], jnp.int32),
                    slot_mapping=jnp.arange(t, dtype=jnp.int32),
                    logits_indices=jnp.asarray([t - 1], jnp.int32))
                kv = self.model.new_kv_caches(pages, page_size, kv_dtype)
                del self.sink[:]
                jax.block_until_ready(self.step(params, kv, inputs))
                jax.effects_barrier()
                out.append([(np.asarray(a, np.float64), np.asarray(b))
                            for a, b in self.sink])
        finally:
            moe.route_topk = route
        return out


def upcast_in_place(params):
    """The same weights in float32, a leaf at a time with the bf16 leaf
    freed, so that the two trees never stand side by side."""
    import jax
    import jax.numpy as jnp

    def up(leaf):
        if leaf.dtype != jnp.bfloat16:
            return leaf
        wide = leaf.astype(jnp.float32)
        jax.block_until_ready(wide)
        leaf.delete()
        return wide

    return jax.tree.map(up, params)


def quantiles(values, qs=(0.5, 0.9, 0.99, 0.999)) -> dict:
    import numpy as np

    values = np.asarray(values, np.float64)
    if not values.size:
        return {"n": 0}
    return {"n": int(values.size), "max": float(values.max()),
            **{f"q{q}": float(np.quantile(values, q)) for q in qs}}


def analyse(program, reference, held: int,
            ties=(0.01, 0.02, 0.03, 0.05, 0.1)) -> dict:
    """``program`` / ``reference``: ``Recorded.forward_all``'s lists; the
    first ``held`` experts are the stage's (the others' output is nought on
    both sides). Margins are the reference's; a flip is a held candidate
    that one side chose alone."""
    import numpy as np

    from benchmarks.references import choice_distances

    every, first, logit_err = [], [], []
    least, flipped = [], []     # per position, over its layers
    # What another share size would call unsure, from the margins alone.
    shares = [np.arange(j * SHARE, (j + 1) * SHARE)
              for j in range(EXPERTS // SHARE)]
    least_of_a_share, least_of_all = [], []
    for prog_layers, ref_layers in zip(program, reference):
        t = ref_layers[0][0].shape[0]
        m_pos, f_pos = np.full(t, np.inf), np.zeros(t, bool)
        m_every = np.full(t, np.inf)
        m_share = np.full((len(shares), t), np.inf)
        for (p_logits, p_ids), (r_logits, r_ids) in zip(prog_layers,
                                                        ref_layers):
            std = r_logits.std(axis=-1, keepdims=True)
            margin = choice_distances(r_logits, TOP_K)
            chosen = np.zeros((t, EXPERTS), bool)
            np.put_along_axis(chosen, r_ids, True, axis=-1)
            other = np.zeros((t, EXPERTS), bool)
            np.put_along_axis(other, p_ids, True, axis=-1)
            flips = (chosen != other)[:, :held]
            every.extend(margin[:, :held][flips])
            # A token's first flipping layer: until then the two streams
            # differ by rounding alone.
            first.extend(margin[:, :held][flips & ~f_pos[:, None]])
            logit_err.extend(
                (np.abs(p_logits - r_logits) / std)[~f_pos].ravel()[::97])
            m_pos = np.minimum(m_pos, margin[:, :held].min(axis=-1))
            m_every = np.minimum(m_every, margin.min(axis=-1))
            f_pos |= flips.any(axis=-1)
            for j, share in enumerate(shares):
                m_share[j] = np.minimum(m_share[j],
                                        margin[:, share].min(axis=-1))
        least.append(m_pos)
        flipped.append(f_pos)
        least_of_a_share.append(m_share)
        least_of_all.append(m_every)
    least, flipped = np.concatenate(least), np.concatenate(flipped)
    least_of_a_share = np.concatenate(least_of_a_share, axis=-1)
    least_of_all = np.concatenate(least_of_all)
    layers = len(reference[0])
    return {
        "token_layers": int(least.size * layers),
        "positions": int(least.size), "routed_layers": layers,
        "experts_held": held,
        "flip_margin": quantiles(every),
        "flip_margin_first_layer": quantiles(first),
        "router_logit_error_over_std": quantiles(logit_err),
        "positions_flipped_share": float(flipped.mean()),
        # ``sure_and_flipped``: the unsound event, a position the
        # constant calls sure at which the sides chose otherwise.
        "by_choice_tie": {str(tie): {
            "unsure_share": float((least < tie).mean()),
            "sure_and_flipped": int((flipped & (least >= tie)).sum()),
            f"unsure_share_were_{SHARE}_held": float(
                (least_of_a_share < tie).mean()),
            "unsure_share_were_all_held": float((least_of_all < tie).mean()),
        } for tie in ties},
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", choices=sorted(SIZES), required=True)
    ap.add_argument("--family", choices=sorted(FAMILIES),
                    help="the registry's class (default: the size's)")
    ap.add_argument("--layers", type=int, default=6, help="routed layers")
    ap.add_argument("--held", type=int, nargs="+", default=[EXPERTS],
                    help="experts of each layer whose output counts: the "
                    "others' down_proj is nought, as on a chip that holds "
                    "a share (the router ranks all 192 all the same); "
                    "several: one measurement each, one compile for all")
    ap.add_argument("--control", action="store_true",
                    help="the program one precision down: its weights "
                    "rounded to float8 (e4m3) on their way to bf16; the "
                    "reference keeps the weights as drawn")
    ap.add_argument("--seed", type=int, default=50)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"),
                    help="directory of the result's file")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.registry import create_stage_model

    size = dict(SIZES[args.size])
    prompts, tokens = size.pop("prompts"), size.pop("tokens")
    family = args.family or size["family"]
    attention, conventions = FAMILIES[family]
    hf = dict(COMMON, **{k: v for k, v in size.items()
                         if k not in ("family", "latent", "grouped")},
              **size[attention], **conventions,
              num_hidden_layers=1 + args.layers)
    device = jax.devices()[0]
    if args.size == "chip" and device.platform != "tpu":
        print(f"choice_flips: --size chip wants a TPU, JAX found {device}",
              file=sys.stderr)
        return 3
    cfg = normalize_config(hf)
    model = create_stage_model(cfg, 0, cfg.num_hidden_layers, tp_size=1,
                               use_pallas=False)
    ids = np.random.default_rng(args.seed).integers(
        0, hf["vocab_size"], (prompts, tokens)).tolist()
    os.makedirs(args.out, exist_ok=True)
    stage = Recorded(model)
    for held in args.held:
        def init(key):
            params = model.finalize_params(
                model.init_params(key, dtype=jnp.bfloat16))
            for layer in params["layers"]:
                if "experts" in layer["mlp"]:
                    down = layer["mlp"]["experts"]["down_proj"]
                    layer["mlp"]["experts"]["down_proj"] = down.at[
                        held:].set(0)
            return params

        t0 = time.monotonic()
        params = jax.jit(init)(jax.random.key(args.seed))
        lower = params if not args.control else jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
            if x.dtype == jnp.bfloat16 else x, params)
        program = stage.forward_all(lower, ids, jnp.bfloat16)
        del lower
        t1 = time.monotonic()
        params = upcast_in_place(params)
        with jax.default_matmul_precision("highest"):
            reference = stage.forward_all(params, ids, jnp.float32)
        t2 = time.monotonic()
        del params
        result = {
            "size": args.size, "family": family, "seed": args.seed,
            "program": "float8 weights" if args.control else "bfloat16",
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "hidden_size": hf["hidden_size"], "experts": EXPERTS,
            "top_k": TOP_K, "prompts": prompts, "tokens_a_prompt": tokens,
            "seconds": {"program": round(t1 - t0, 1),
                        "reference": round(t2 - t1, 1)},
            **analyse(program, reference, held),
        }
        name = (f"choice_flips_{args.size}_{family}_held{held}"
                f"{'_control' if args.control else ''}.json")
        with open(os.path.join(args.out, name), "w") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result, indent=1), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
