"""DeepSeek-V2/V3 (and Kimi-K2) stage model: MLA + sigmoid-routed MoE.

Capability parity: reference ``src/parallax/models/deepseek_v3.py`` (MLA
compressed latent cache + mla_paged_attention). The TPU design runs decode
AND prefill in the absorbed form over the latent cache (``ops/mla.py``):
per-token HBM is kv_lora_rank + rope_dim, independent of head count.

Weight names follow HF ``DeepseekV3ForCausalLM``:
q_a_proj/q_a_layernorm/q_b_proj (or q_proj when q_lora_rank is null),
kv_a_proj_with_mqa/kv_a_layernorm/kv_b_proj, o_proj; MoE:
mlp.gate.{weight,e_score_correction_bias}, mlp.experts.{i}.*,
mlp.shared_experts.* ; first_k_dense_replace leading dense layers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from parallax_tpu.models import layers as L
from parallax_tpu.models.base import BatchInputs, StageModel
from parallax_tpu.models.moe import moe_ffn
from parallax_tpu.models.qwen3_moe import MoEStageModel
from parallax_tpu.models.registry import register_model
from parallax_tpu.obs.trace import note_block_trace
from parallax_tpu.ops.mla import (
    mla_append_and_attend,
    mla_rope_permute,
    new_mla_pages,
)
from parallax_tpu.ops.rope import apply_rope


@register_model(
    "DeepseekV2ForCausalLM", "DeepseekV3ForCausalLM", "KimiK2ForCausalLM",
    # A.X-K1 (``model_type`` axk1) is this block: MLA with a low-rank
    # query, ``first_k_dense_replace``, sigmoid-scored routed experts
    # beside a shared one; its ``topk_method`` "none" is plain top-k
    # (``moe.route_topk``).
    "AXK1ForCausalLM",
)
class DeepseekStageModel(MoEStageModel):
    """MLA attention + (mostly) MoE FFN."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)  # MoE + EP divisibility checks
        cfg = self.config
        if cfg.mla is None:
            raise ValueError("DeepSeek family requires MLA config")
        # Rope covers only the rope head dims, not the full (nope+rope) head.
        from parallax_tpu.ops.rope import (
            rope_frequencies,
            rope_table,
            yarn_mscale,
        )

        inv = rope_frequencies(
            cfg.mla.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling
        )
        self.cos_table, self.sin_table = rope_table(
            inv, cfg.max_position_embeddings
        )
        # YaRN magnitude correction folds into the softmax scale
        # (HF DeepseekV3Attention: scaling *= mscale^2 when mscale_all_dim).
        self.sm_scale = (
            cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
        ) ** -0.5
        rs = cfg.rope_scaling or {}
        if rs.get("rope_type", rs.get("type")) == "yarn":
            mscale_all = float(rs.get("mscale_all_dim", 0) or 0)
            if mscale_all:
                m_ = yarn_mscale(float(rs.get("factor", 1.0)), mscale_all)
                self.sm_scale = self.sm_scale * m_ * m_
        # MLA shards heads over tp like GQA would; latent cache is shared
        # (replicated) across chips because it is head-independent.

    # -- cache -------------------------------------------------------------

    def new_kv_caches(self, num_pages, page_size, dtype=jnp.bfloat16):
        m = self.config.mla
        return [
            new_mla_pages(num_pages, page_size, m.kv_lora_rank,
                          m.qk_rope_head_dim, dtype)
            for _ in range(self.num_local_layers)
        ]

    # -- layers ------------------------------------------------------------

    def _block(self, key, lp, x, kv, inputs: BatchInputs, carry):
        """``carry`` is a dict of what the family's blocks hand on. On a
        decode step ``"held"`` is the stage's running
        ``moe.held_counts`` (zeros from the first block on, so a dense
        and an expert layer are the stage's two kinds of block); any
        other step counts nothing."""
        note_block_trace()
        cfg = self.config
        carry = dict(carry or {})
        if inputs.decode_only and cfg.moe is not None:
            carry.setdefault("held", jnp.zeros((2,), jnp.int32))
        # With a float32 residual stream (``fp32_residual``) the norms
        # read it whole and hand the branches their input in the weights'
        # dtype; the adds promote back to float32.
        act = lp["input_layernorm"]["weight"].dtype
        h = L.rms_norm(x, lp["input_layernorm"]["weight"], cfg.rms_norm_eps)
        attn_out, kv = self._mla_attention(
            lp["self_attn"], h.astype(act), kv, inputs
        )
        x = x + attn_out
        h = L.rms_norm(x, lp["post_attention_layernorm"]["weight"],
                       cfg.rms_norm_eps).astype(act)
        if "held" in carry and "experts" in lp["mlp"]:
            # Frozen and padding rows of a decode window write no cache
            # row (slot -1): they are no rows of the step.
            out, counts = moe_ffn(
                h, lp["mlp"], cfg.moe, axis_name=self.axis_name,
                use_megablox=self.use_pallas,
                count_rows=inputs.slot_mapping >= 0,
            )
            carry["held"] = carry["held"] + counts
            return x + out, kv, carry
        return x + self._mlp(lp, h), kv, carry

    def _mla_qkv(self, p, x, inputs: BatchInputs):
        """Shared MLA projection pipeline: returns the absorbed query parts,
        the new latent/rope rows to cache, the up-projection, and the
        low-rank query activation (``qr`` — the DSA indexer reads it)."""
        cfg = self.config
        m = cfg.mla
        t = x.shape[0]
        dn, dr, dv, r = (
            m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
            m.kv_lora_rank,
        )

        # Query path (optionally low-rank).
        if "q_a_proj" in p:
            qr = L.linear(x, p["q_a_proj"])
            qr = L.rms_norm(qr, p["q_a_layernorm"]["weight"], cfg.rms_norm_eps)
            q = L.linear(qr, p["q_b_proj"])
        else:
            qr = None
            q = L.linear(x, p["q_proj"])
        hq = q.shape[-1] // (dn + dr)
        q = q.reshape(t, hq, dn + dr)
        q_nope, q_pe = q[..., :dn], q[..., dn:]

        # KV path: compressed latent + shared rope key.
        kv_a = L.linear(x, p["kv_a_proj_with_mqa"])
        latent, k_pe = kv_a[..., :r], kv_a[..., r:]
        latent = L.rms_norm(latent, p["kv_a_layernorm"]["weight"],
                            cfg.rms_norm_eps)

        # DeepSeek checkpoints use interleaved rope weights: permute the
        # rope dims, then standard rotate-half (HF rope_interleave flag).
        if cfg.extra.get("rope_interleave", True):
            q_pe = mla_rope_permute(q_pe)
            k_pe = mla_rope_permute(k_pe)
        q_pe = apply_rope(q_pe, inputs.positions, self.cos_table, self.sin_table)
        k_pe = apply_rope(k_pe, inputs.positions, self.cos_table, self.sin_table)

        # Absorb W_UK into the query: kv_b_proj [Hq*(dn+dv), R].
        w_kv_b = L.get_weight(p["kv_b_proj"]).reshape(hq, dn + dv, r)
        w_uk = w_kv_b[:, :dn, :]           # [Hq, dn, R]
        w_uv = w_kv_b[:, dn:, :]           # [Hq, dv, R]
        # Heads leading on both sides: XLA's CPU backend has no bf16 dot
        # for "thd,hdr->thr" as written (a rehearsal runs there); to a
        # TPU the two are one dot.
        q_latent = jnp.einsum(
            "htd,hdr->htr", jnp.swapaxes(q_nope, 0, 1), w_uk,
            preferred_element_type=jnp.float32,
        ).swapaxes(0, 1).astype(x.dtype)
        return q_latent, q_pe, latent, k_pe, w_uv, qr, hq

    def _mla_out(self, p, out_latent, w_uv, hq):
        """Up-project latent attention output and apply o_proj."""
        t = out_latent.shape[0]
        dv = w_uv.shape[1]
        out = jnp.einsum(
            "thr,hdr->thd", out_latent, w_uv,
            preferred_element_type=jnp.float32,
        ).astype(out_latent.dtype)
        return L.row_parallel_linear(
            out.reshape(t, hq * dv), p["o_proj"], self.axis_name
        )

    def _mla_attention(self, p, x, cache, inputs: BatchInputs):
        q_latent, q_pe, latent, k_pe, w_uv, _qr, hq = self._mla_qkv(
            p, x, inputs
        )
        out_latent, cache = mla_append_and_attend(
            q_latent,
            q_pe,
            latent,
            k_pe,
            cache,
            inputs.kv_lens,
            inputs.page_indices,
            inputs.cu_q_lens,
            inputs.num_seqs,
            inputs.slot_mapping,
            sm_scale=self.sm_scale,
            kv_lora_rank=self.config.mla.kv_lora_rank,
            decode_only=inputs.decode_only,
            use_pallas=self.use_pallas,
            decode_fused=inputs.decode_fused,
        )
        return self._mla_out(p, out_latent, w_uv, hq), cache

    def finalize_params(self, tree: dict) -> dict:
        tree = super().finalize_params(tree)
        # HF names shared experts "shared_experts"; moe_ffn expects
        # "shared_expert".
        for layer in tree.get("layers", []):
            mlp = layer.get("mlp")
            if isinstance(mlp, dict) and "shared_experts" in mlp:
                mlp["shared_expert"] = mlp.pop("shared_experts")
        return tree

    # -- init --------------------------------------------------------------

    def init_params(self, rng, dtype=jnp.bfloat16) -> dict:
        cfg = self.config
        m = cfg.mla
        params = StageModel.init_params(self, rng, dtype)

        def dense(key, out_dim, in_dim):
            return {"weight": (
                jax.random.normal(key, (out_dim, in_dim), jnp.float32)
                * (in_dim**-0.5)
            ).astype(dtype)}

        # Two scales a configuration may state for its own seeded draw
        # (top-level ``seeded_init``; docs/models.md): the standard
        # deviation of the head's logits whatever the width, and a gain
        # on the routed experts' ``down_proj`` beside the fan-in scale.
        # Without the key the draw is the family's as it always was.
        draw = cfg.extra.get("seeded_init") or {}
        if "lm_head" in params and "lm_head_logit_std" in draw:
            params["lm_head"]["weight"] = (
                jax.random.normal(
                    jax.random.fold_in(rng, 8000),
                    (cfg.vocab_size, cfg.hidden_size), jnp.float32,
                ) * (cfg.hidden_size
                     / float(draw["lm_head_logit_std"]) ** 2) ** -0.5
            ).astype(dtype)
        down_gain = float(draw.get("routed_down_proj_gain", 1.0))
        hq = cfg.num_attention_heads
        dn, dr, dv, r = (
            m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
            m.kv_lora_rank,
        )
        for li in range(self.num_local_layers):
            gi = self.start_layer + li
            key = jax.random.fold_in(rng, 9000 + gi)
            k = jax.random.split(key, 6)
            attn = {
                "kv_a_proj_with_mqa": dense(k[0], r + dr, cfg.hidden_size),
                "kv_a_layernorm": {"weight": jnp.ones((r,), dtype)},
                "kv_b_proj": dense(k[1], hq * (dn + dv), r),
                "o_proj": dense(k[2], cfg.hidden_size, hq * dv),
            }
            if m.q_lora_rank:
                attn["q_a_proj"] = dense(k[3], m.q_lora_rank, cfg.hidden_size)
                attn["q_a_layernorm"] = {
                    "weight": jnp.ones((m.q_lora_rank,), dtype)
                }
                attn["q_b_proj"] = dense(k[4], hq * (dn + dr), m.q_lora_rank)
            else:
                attn["q_proj"] = dense(k[3], hq * (dn + dr), cfg.hidden_size)
            params["layers"][li]["self_attn"] = attn

            if cfg.moe is not None and cfg.is_moe_layer(gi):
                # The router over every expert, the stacks of those held.
                e, held, h_, i = (cfg.moe.num_experts, cfg.moe.num_held,
                                  cfg.hidden_size,
                                  cfg.moe.moe_intermediate_size)
                km = jax.random.split(jax.random.fold_in(rng, 7000 + gi), 8)
                mlp_params = {
                    "gate": {
                        "weight": (
                            jax.random.normal(km[0], (e, h_), jnp.float32)
                            * h_**-0.5
                        ).astype(dtype),
                    },
                    "experts": {
                        "gate_proj": (
                            jax.random.normal(
                                km[1], (held, i, h_), jnp.float32
                            ) * h_**-0.5
                        ).astype(dtype),
                        "up_proj": (
                            jax.random.normal(
                                km[2], (held, i, h_), jnp.float32
                            ) * h_**-0.5
                        ).astype(dtype),
                        "down_proj": (
                            jax.random.normal(
                                km[3], (held, h_, i), jnp.float32
                            ) * (i / down_gain ** 2) ** -0.5
                        ).astype(dtype),
                    },
                }
                if cfg.moe.uses_correction_bias:
                    # Only ``noaux_tc`` selects by biased scores; a leaf
                    # here would be drawn by a seeded harness and steer
                    # the selection of a method that has none.
                    mlp_params["gate"]["e_score_correction_bias"] = (
                        jnp.zeros((e,), jnp.float32)
                    )
                if cfg.moe.num_shared_experts > 0:
                    si = (cfg.moe.shared_expert_intermediate_size
                          or i) * cfg.moe.num_shared_experts
                    mlp_params["shared_expert"] = {
                        "gate_proj": {"weight": (
                            jax.random.normal(km[4], (si, h_), jnp.float32)
                            * h_**-0.5).astype(dtype)},
                        "up_proj": {"weight": (
                            jax.random.normal(km[5], (si, h_), jnp.float32)
                            * h_**-0.5).astype(dtype)},
                        "down_proj": {"weight": (
                            jax.random.normal(km[6], (h_, si), jnp.float32)
                            * si**-0.5).astype(dtype)},
                    }
                params["layers"][li]["mlp"] = mlp_params
        return params
