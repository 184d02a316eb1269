"""DeepSeek-V3.2 / GLM-MoE-DSA stage model: sparse attention (DSA) over the
MLA latent cache.

Capability parity: reference ``src/parallax/models/deepseek_v32.py:27-571``
(ParallaxDeepSeekV32Indexer / Attention / Block: lightning indexer, paged
index-key cache, top-k sparse decode, full/shared indexer layers, GLM
defaults) and ``src/parallax_extensions/ops.py:182-367``.

Layer protocol: a "full" layer runs the indexer and publishes its top-k;
"shared" layers reuse the previous full layer's top-k (GLM's
``index_topk_freq``). Shard boundaries must start at layer 0 or a full
layer because top-k is never transferred between stages (reference
``validate_shard_start``).

Weight names follow HF ``DeepseekV32ForCausalLM``: everything from
DeepSeek-V3 plus ``self_attn.indexer.{wq_b,wk,k_norm,weights_proj}``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from parallax_tpu.models import layers as L
from parallax_tpu.models.base import BatchInputs
from parallax_tpu.models.deepseek_v3 import DeepseekStageModel
from parallax_tpu.models.registry import register_model
from parallax_tpu.ops.dsa import (
    dsa_store_and_score,
    dsa_topk_indices,
    mla_ragged_sparse_attention_xla,
    new_index_pages,
)
from parallax_tpu.ops.mla import new_mla_pages, store_mla_cache
from parallax_tpu.ops.rope import apply_rope, apply_rope_interleaved


@register_model(
    "DeepseekV32ForCausalLM", "GlmMoeDsaForCausalLM", "Glm4MoeDsaForCausalLM"
)
class DeepseekV32StageModel(DeepseekStageModel):
    """MLA + lightning-indexer sparse attention + (mostly) MoE FFN."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        cfg = self.config
        if cfg.dsa is None:
            raise ValueError(
                "DeepSeek-V3.2/GLM-DSA requires index_n_heads/index_head_dim"
            )
        # Shard boundary rule (reference validate_shard_start): top-k never
        # crosses stages, so a stage may not begin on a "shared" layer.
        if self.start_layer > 0 and (
            cfg.dsa.indexer_types[self.start_layer] != "full"
        ):
            raise ValueError(
                "DSA shards must start at layer 0 or a full indexer layer; "
                f"layer {self.start_layer} is "
                f"{cfg.dsa.indexer_types[self.start_layer]!r}"
            )
        self._idx_softmax_scale = cfg.dsa.index_head_dim ** -0.5

    # -- cache -------------------------------------------------------------

    def new_kv_caches(self, num_pages, page_size, dtype=jnp.bfloat16):
        m = self.config.mla
        d = self.config.dsa
        caches = []
        for li in range(self.num_local_layers):
            mla = new_mla_pages(num_pages, page_size, m.kv_lora_rank,
                                m.qk_rope_head_dim, dtype)
            # Only "full" indexer layers write/read index keys; shared
            # layers reuse the previous full layer's top-k, so an index
            # cache there would be dead HBM.
            if d.indexer_types[self.start_layer + li] == "full":
                caches.append((mla, new_index_pages(
                    num_pages, page_size, d.index_head_dim, dtype
                )))
            else:
                caches.append((mla, None))
        return caches

    # -- forward -----------------------------------------------------------

    def _block(self, key, lp, x, kv, inputs: BatchInputs, carry):
        """``carry["topk"]`` is the newest full layer's top-k (absent
        before the stage's first): it rides into ``_mla_attention`` as a
        third member of the layer's cache and comes back the same way."""
        x, (mla_pages, index_pages, topk), carry = super()._block(
            key, lp, x, (*kv, (carry or {}).get("topk")), inputs, carry
        )
        return x, (mla_pages, index_pages), {**carry, "topk": topk}

    def _indexer_topk(self, p, x, qr, index_cache, inputs: BatchInputs):
        """Lightning indexer: score the cached context, return top-k
        positions + the updated index-key cache.

        Reference: ParallaxDeepSeekV32Indexer.__call__
        (deepseek_v32.py:100-238) — q from wq_b(qr), single shared key from
        wk(x) + LayerNorm, rope on the leading rope dims, score
        ``sum_h w_h * relu(q_h . k)``.
        """
        cfg = self.config
        d = cfg.dsa
        dr = cfg.mla.qk_rope_head_dim
        t = x.shape[0]

        q = L.linear(qr if qr is not None else x, p["wq_b"])
        q = q.reshape(t, d.index_n_heads, d.index_head_dim)
        q_pe, q_nope = q[..., :dr], q[..., dr:]
        k = L.linear(x, p["wk"])                       # [T, D_idx]
        k = L.layer_norm(k, p["k_norm"], d.indexer_norm_eps)
        k_pe, k_nope = k[..., :dr], k[..., dr:]

        rope_fn = (
            apply_rope_interleaved if d.indexer_rope_traditional
            else apply_rope
        )
        q_pe = rope_fn(q_pe, inputs.positions, self.cos_table, self.sin_table)
        k_pe = rope_fn(k_pe, inputs.positions, self.cos_table, self.sin_table)
        q = jnp.concatenate([q_pe, q_nope], axis=-1)
        k = jnp.concatenate([k_pe, k_nope], axis=-1)

        weights = L.linear(x, p["weights_proj"]).astype(jnp.float32) * (
            d.index_n_heads ** -0.5 * self._idx_softmax_scale
        )
        # Index-key cache write + full-context scoring through the fused
        # facade: one Pallas program on the fused decode path, scatter +
        # split scorer otherwise.
        scores, index_cache = dsa_store_and_score(
            q, weights, k, index_cache,
            inputs.kv_lens, inputs.page_indices, inputs.cu_q_lens,
            inputs.slot_mapping,
            decode_only=inputs.decode_only,
            use_pallas=self.use_pallas,
            decode_fused=inputs.decode_fused,
        )
        return dsa_topk_indices(scores, index_topk=d.index_topk), index_cache

    def _mla_attention(self, p, x, cache, inputs: BatchInputs):
        # A "full" layer is one that holds index pages (new_kv_caches).
        mla_pages, index_pages, prev_topk = cache
        q_latent, q_pe, latent, k_pe, w_uv, qr, hq = self._mla_qkv(
            p, x, inputs
        )
        mla_pages = store_mla_cache(mla_pages, latent, k_pe,
                                    inputs.slot_mapping)

        if index_pages is not None:
            topk, index_pages = self._indexer_topk(
                p["indexer"], x, qr, index_pages, inputs
            )
        else:
            if prev_topk is None:
                raise ValueError(
                    "DSA shared layer requires a previous full layer's "
                    "top-k in the same shard"
                )
            topk = prev_topk

        out_latent = mla_ragged_sparse_attention_xla(
            q_latent,
            q_pe,
            mla_pages,
            inputs.kv_lens,
            inputs.page_indices,
            inputs.cu_q_lens,
            topk,
            sm_scale=self.sm_scale,
            kv_lora_rank=self.config.mla.kv_lora_rank,
        )
        out = self._mla_out(p, out_latent, w_uv, hq)
        return out, (mla_pages, index_pages, topk)

    # -- init --------------------------------------------------------------

    def init_params(self, rng, dtype=jnp.bfloat16) -> dict:
        params = super().init_params(rng, dtype)
        cfg = self.config
        d = cfg.dsa

        def dense(key, out_dim, in_dim):
            return {"weight": (
                jax.random.normal(key, (out_dim, in_dim), jnp.float32)
                * (in_dim**-0.5)
            ).astype(dtype)}

        q_in = cfg.mla.q_lora_rank or cfg.hidden_size
        for li in range(self.num_local_layers):
            gi = self.start_layer + li
            if d.indexer_types[gi] != "full":
                continue
            k = jax.random.split(jax.random.fold_in(rng, 11000 + gi), 3)
            params["layers"][li]["self_attn"]["indexer"] = {
                "wq_b": dense(k[0], d.index_n_heads * d.index_head_dim, q_in),
                "wk": dense(k[1], d.index_head_dim, cfg.hidden_size),
                "k_norm": {
                    "weight": jnp.ones((d.index_head_dim,), dtype),
                    "bias": jnp.zeros((d.index_head_dim,), dtype),
                },
                "weights_proj": dense(k[2], d.index_n_heads, cfg.hidden_size),
            }
        return params
