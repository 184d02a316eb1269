"""Qwen3-MoE / Qwen2-MoE family stage model.

Capability parity: reference ``src/parallax/models/qwen3_moe.py`` (MoE via
SwitchGLU). TPU re-design: stacked expert weights + grouped matmul
(``models/moe.py``), expert parallelism over the tp axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from parallax_tpu.models import layers as L
from parallax_tpu.models.base import StageModel
from parallax_tpu.models.moe import moe_ffn
from parallax_tpu.models.registry import register_model


@register_model("Qwen3MoeForCausalLM", "Qwen2MoeForCausalLM")
class MoEStageModel(StageModel):
    """Dense attention + (per-layer) MoE FFN."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        moe = self.config.moe
        if moe is None:
            raise ValueError(f"{self.config.architecture} requires MoE config")
        if self.tp_size > 1 and moe.num_experts % self.tp_size:
            raise ValueError(
                f"num_experts={moe.num_experts} not divisible by "
                f"tp={self.tp_size}"
            )

    def _mlp(self, lp: dict, h: jax.Array) -> jax.Array:
        if "experts" in lp["mlp"]:
            return moe_ffn(
                h, lp["mlp"], self.config.moe,
                axis_name=self.axis_name,
                use_megablox=self.use_pallas,
            )
        return L.swiglu_mlp(h, lp["mlp"], axis_name=self.axis_name)

    # -- params -----------------------------------------------------------

    def init_params(self, rng: jax.Array, dtype=jnp.bfloat16) -> dict:
        params = super().init_params(rng, dtype)
        cfg = self.config
        moe = cfg.moe
        for li in range(self.num_local_layers):
            gi = self.start_layer + li
            if not cfg.is_moe_layer(gi):
                continue
            key = jax.random.fold_in(rng, 7000 + gi)
            k = jax.random.split(key, 4)
            e, h, i = moe.num_experts, cfg.hidden_size, moe.moe_intermediate_size
            params["layers"][li]["mlp"] = {
                "gate": {"weight": (
                    jax.random.normal(k[0], (e, h), jnp.float32) * h**-0.5
                ).astype(dtype)},
                "experts": {
                    "gate_proj": (
                        jax.random.normal(k[1], (e, i, h), jnp.float32) * h**-0.5
                    ).astype(dtype),
                    "up_proj": (
                        jax.random.normal(k[2], (e, i, h), jnp.float32) * h**-0.5
                    ).astype(dtype),
                    "down_proj": (
                        jax.random.normal(k[3], (e, h, i), jnp.float32) * i**-0.5
                    ).astype(dtype),
                },
            }
        return params

    def finalize_params(self, tree: dict) -> dict:
        """Stack per-expert HF weights: ``experts.{i}.gate_proj.weight`` ->
        ``experts.gate_proj [E, I, H]`` (loader hook). Quantized experts
        (``qweight``/``scales``/``biases`` from ops/quant.py) stack into a
        quantized dict with a leading expert axis. A stage told its
        share (``MoEConfig.experts_held`` from ``expert_offset``) stacks
        those experts of a whole layer's checkpoint and keeps the first
        ``vocab_size`` rows of the embedding and the head."""
        moe = self.config.moe
        for layer in tree.get("layers", []):
            mlp = layer.get("mlp")
            if not isinstance(mlp, dict):
                continue
            experts = mlp.get("experts")
            if not isinstance(experts, dict) or "gate_proj" in experts:
                continue
            ids = range(len(experts))
            if len(experts) == moe.num_experts > moe.num_held:
                ids = range(moe.expert_offset,
                            moe.expert_offset + moe.num_held)
            stacked = {}
            for proj in ("gate_proj", "up_proj", "down_proj"):
                first = experts[str(ids[0])][proj]
                if "qweight" in first:
                    stacked[proj] = {
                        k: jnp.stack(
                            [experts[str(i)][proj][k] for i in ids]
                        )
                        for k in first
                    }
                else:
                    stacked[proj] = jnp.stack(
                        [experts[str(i)][proj]["weight"] for i in ids]
                    )
            mlp["experts"] = stacked
        if moe.experts_held:
            rows = self.config.vocab_size
            for name in ("embed_tokens", "lm_head"):
                w = tree.get(name, {}).get("weight")
                if w is not None and w.shape[0] > rows:
                    tree[name]["weight"] = w[:rows]
        return tree
