"""MiniMax-M3 stage model: block-sparse attention (MSA) + swiglu-oai MoE.

Capability parity: reference ``src/parallax/models/minimax_m3.py:23-1019``
(MiniMaxAttention w/ sparse index projections + _build_sparse_mask,
MiniMaxSparseMoeBlock w/ sigmoid+bias routing and routed_scaling 2.0,
gemma-style norms, partial rotary 0.5, dense layers on a per-layer MLP
type list) and the MSA kernels (``ops.py:594-804``).

Weight names follow the HF checkpoint: ``self_attn.{q,k,v,o}_proj``,
``self_attn.{q,k}_norm``, sparse layers add
``self_attn.index_{q,k}_proj`` + ``self_attn.index_{q,k}_norm``; MoE
layers use ``block_sparse_moe.{gate,experts.N.*,shared_experts.*,
e_score_correction_bias}``; dense layers use ``mlp.*``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from parallax_tpu.models import layers as L
from parallax_tpu.models.base import BatchInputs
from parallax_tpu.models.moe import moe_ffn
from parallax_tpu.models.qwen3_moe import MoEStageModel
from parallax_tpu.models.registry import register_model
from parallax_tpu.ops.kv_cache_ops import new_kv_pages, reshape_and_cache
from parallax_tpu.ops.attention import append_and_attend
from parallax_tpu.ops.msa import (
    msa_store_and_positions,
    new_index_pages,
    paged_sparse_gqa_attention_xla,
)


def swiglu_oai(alpha: float, limit: float, beta: float):
    """MiniMax/gpt-oss clamped GLU (reference _swiglu_oai,
    minimax_m3.py:177-181): ``clip(g, max=limit) * sigmoid(alpha*g) *
    (clip(u, +-limit) + beta)``."""

    def act(g, u):
        g = jnp.minimum(g, limit)
        u = jnp.clip(u, -limit, limit)
        return g * jax.nn.sigmoid(alpha * g) * (u + beta)

    return act


@register_model("MiniMaxM3SparseForCausalLM", "MiniMaxM3ForCausalLM")
class MiniMaxM3StageModel(MoEStageModel):
    """GQA + per-layer MSA sparse attention + MoE/dense FFN mix."""

    norm_offset = 1.0  # gemma convention: x_hat * (1 + w)

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        cfg = self.config
        if cfg.msa is None:
            raise ValueError("MiniMax-M3 requires sparse-attention config")
        if not cfg.extra.get("use_gemma_norm", True):
            self.norm_offset = 0.0  # instance override, class default stays
        self._act = swiglu_oai(
            float(cfg.extra.get("swiglu_alpha", 1.702)),
            float(cfg.extra.get("swiglu_limit", 7.0)),
            float(cfg.extra.get("swiglu_beta", 1.0)),
        )

    # -- cache -------------------------------------------------------------

    def _layer_sparse(self, gi: int) -> bool:
        mask = self.config.msa.sparse_layer_mask
        return bool(mask[gi]) if gi < len(mask) else False

    def new_kv_caches(self, num_pages, page_size, dtype=jnp.bfloat16):
        cfg = self.config
        caches = []
        for li in range(self.num_local_layers):
            kv = new_kv_pages(
                num_pages, page_size, cfg.num_key_value_heads,
                cfg.head_dim, dtype,
            )
            if self._layer_sparse(self.start_layer + li):
                caches.append((kv, new_index_pages(
                    num_pages, page_size, cfg.msa.index_head_dim, dtype
                )))
            else:
                caches.append(kv)
        return caches

    # -- forward -----------------------------------------------------------

    def _attention(self, lp, h, kv, inputs: BatchInputs, window):
        cfg = self.config
        p = lp["self_attn"]
        t = h.shape[0]
        d = cfg.head_dim
        # A sparse layer is one whose cache holds index pages beside
        # its K/V pages (new_kv_caches).
        sparse = isinstance(kv, tuple)

        q = L.linear(h, p["q_proj"]).reshape(t, -1, d)
        k = L.linear(h, p["k_proj"]).reshape(t, -1, d)
        v = L.linear(h, p["v_proj"]).reshape(t, -1, d)
        hq = q.shape[1]
        if cfg.use_qk_norm and "q_norm" in p:
            q = L.rms_norm(q, p["q_norm"]["weight"], cfg.rms_norm_eps,
                           offset=self.norm_offset)
            k = L.rms_norm(k, p["k_norm"]["weight"], cfg.rms_norm_eps,
                           offset=self.norm_offset)
        q = self.rope_fn(q, inputs.positions, self.cos_table, self.sin_table)
        k = self.rope_fn(k, inputs.positions, self.cos_table, self.sin_table)

        if sparse:
            kv_pages, index_pages = kv
        else:
            kv_pages, index_pages = kv, None

        if sparse:
            kv_pages = reshape_and_cache(kv_pages, k, v,
                                         inputs.slot_mapping)
            msa = cfg.msa
            idx_q = L.linear(h, p["index_q_proj"]).reshape(
                t, msa.index_n_heads, msa.index_head_dim
            )
            idx_k = L.linear(h, p["index_k_proj"])       # [T, D_idx]
            idx_q = L.rms_norm(idx_q, p["index_q_norm"]["weight"],
                               cfg.rms_norm_eps, offset=self.norm_offset)
            idx_k = L.rms_norm(idx_k, p["index_k_norm"]["weight"],
                               cfg.rms_norm_eps, offset=self.norm_offset)
            idx_q = self.rope_fn(idx_q, inputs.positions, self.cos_table,
                                 self.sin_table)
            idx_k = self.rope_fn(idx_k, inputs.positions, self.cos_table,
                                 self.sin_table)
            # Index-key cache write + block scoring through the fused
            # facade: one Pallas program on the fused decode path,
            # scatter + split scorer otherwise.
            positions, index_pages = msa_store_and_positions(
                idx_q, idx_k, index_pages,
                inputs.kv_lens, inputs.page_indices, inputs.cu_q_lens,
                inputs.slot_mapping,
                block_size=msa.block_size,
                topk_blocks=msa.topk_blocks,
                init_blocks=msa.init_blocks,
                local_blocks=msa.local_blocks,
                sm_scale=d ** -0.5,
                decode_only=inputs.decode_only,
                use_pallas=self.use_pallas,
                decode_fused=inputs.decode_fused,
            )
            out = paged_sparse_gqa_attention_xla(
                q, kv_pages,
                inputs.kv_lens, inputs.page_indices, inputs.cu_q_lens,
                positions, sm_scale=d ** -0.5,
            )
            new_kv = (kv_pages, index_pages)
        else:
            out, kv_pages = append_and_attend(
                q, k, v, kv_pages,
                inputs.kv_lens, inputs.page_indices, inputs.cu_q_lens,
                inputs.num_seqs, inputs.slot_mapping, sm_scale=d ** -0.5,
                sliding_window=None, use_pallas=self.use_pallas,
                decode_only=inputs.decode_only,
                decode_fused=inputs.decode_fused,
                prefill_fused=inputs.prefill_fused,
            )
            new_kv = kv_pages
        out = L.row_parallel_linear(
            out.reshape(t, hq * d), p["o_proj"], self.axis_name
        )
        return out, new_kv

    def _mlp(self, lp: dict, h: jax.Array) -> jax.Array:
        if "experts" in lp["mlp"]:
            return moe_ffn(
                h, lp["mlp"], self.config.moe,
                axis_name=self.axis_name,
                use_megablox=self.use_pallas,
                act_fn=self._act,
            )
        return L.glu_mlp(h, lp["mlp"], self._act, axis_name=self.axis_name)

    def finalize_params(self, tree: dict) -> dict:
        """HF checkpoint: MoE lives under ``block_sparse_moe`` with
        ``shared_experts``; map onto the generic ``mlp`` structure (the
        expert stacking of MoEStageModel.finalize_params runs after the
        rename)."""
        for layer in tree.get("layers", []):
            moe = layer.pop("block_sparse_moe", None)
            if moe is None:
                continue
            if "shared_experts" in moe:
                moe["shared_expert"] = moe.pop("shared_experts")
            if "e_score_correction_bias" in moe and isinstance(
                moe.get("gate"), dict
            ):
                moe["gate"]["e_score_correction_bias"] = moe.pop(
                    "e_score_correction_bias"
                )
            layer["mlp"] = moe
        return super().finalize_params(tree)

    # -- init --------------------------------------------------------------

    def init_params(self, rng, dtype=jnp.bfloat16) -> dict:
        # Base init gives attention + dense mlp + (MoE via MoEStageModel).
        params = super().init_params(rng, dtype)
        cfg = self.config
        msa = cfg.msa

        def dense(key, out_dim, in_dim):
            return {"weight": (
                jax.random.normal(key, (out_dim, in_dim), jnp.float32)
                * (in_dim**-0.5)
            ).astype(dtype)}

        for li in range(self.num_local_layers):
            gi = self.start_layer + li
            layer = params["layers"][li]
            attn = layer["self_attn"]
            if cfg.use_qk_norm:
                init_w = (jnp.zeros if self.norm_offset else jnp.ones)
                attn["q_norm"] = {"weight": init_w((cfg.head_dim,), dtype)}
                attn["k_norm"] = {"weight": init_w((cfg.head_dim,), dtype)}
            if self._layer_sparse(gi):
                k = jax.random.split(jax.random.fold_in(rng, 13000 + gi), 2)
                attn["index_q_proj"] = dense(
                    k[0], msa.index_n_heads * msa.index_head_dim,
                    cfg.hidden_size,
                )
                attn["index_k_proj"] = dense(
                    k[1], msa.index_head_dim, cfg.hidden_size
                )
                init_w = (jnp.zeros if self.norm_offset else jnp.ones)
                attn["index_q_norm"] = {
                    "weight": init_w((msa.index_head_dim,), dtype)
                }
                attn["index_k_norm"] = {
                    "weight": init_w((msa.index_head_dim,), dtype)
                }
            # Norm weights: gemma convention zero-init.
            if self.norm_offset:
                h = cfg.hidden_size
                layer["input_layernorm"]["weight"] = jnp.zeros((h,), dtype)
                layer["post_attention_layernorm"]["weight"] = jnp.zeros(
                    (h,), dtype
                )
            # MoE layers get shared expert + correction bias.
            if cfg.is_moe_layer(gi) and "experts" in layer["mlp"]:
                moe = cfg.moe
                if moe.num_shared_experts and "shared_expert" not in layer["mlp"]:
                    ks = jax.random.split(
                        jax.random.fold_in(rng, 15000 + gi), 3
                    )
                    si = (moe.shared_expert_intermediate_size
                          or moe.moe_intermediate_size)
                    h = cfg.hidden_size
                    layer["mlp"]["shared_expert"] = {
                        "gate_proj": dense(ks[0], si, h),
                        "up_proj": dense(ks[1], si, h),
                        "down_proj": dense(ks[2], h, si),
                    }
                if cfg.extra.get("use_routing_bias", True):
                    layer["mlp"]["gate"].setdefault(
                        "e_score_correction_bias",
                        jnp.zeros((moe.num_experts,), jnp.float32),
                    )
        if self.is_last and self.norm_offset:
            params["norm"]["weight"] = jnp.zeros((cfg.hidden_size,), dtype)
        return params
